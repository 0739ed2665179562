"""What PR 53 added to the benchmark: the cell ``tgv384.zsplit``
rehearsed through run.py on four virtual CPU devices at a small box
(the shards' planes tiled in y, as at 96 x 384 x 384), its guard, the
mesh reference against ``d3q27_cumulant_tgv.run``, the control at a size
a test can hold, and the reader ``kernel_shard_roofline`` on the kept
recording of ``test_phases.py`` with a sharded engine's account written
into its events."""

import json
import os

import numpy as np
import pytest

from benchmark import bytes_model, casegen, check, trace
from benchmark.layer_metrics import kernel_shard_roofline
from benchmark.tests import tiny
from benchmark.tests.test_tgv import ACCOUNT, CELL, output_of

SHAPES = {"tgv384": [32, 32, 64]}
INTERVALS = {"zsplit": {250: 3, 500: 6}}
# VMEM the 3D planner may count on in the rehearsal: no kernel then
# holds a whole 32 x 64 plane of a shard of 8 slabs, so the shard's
# windows are tiled, (1, 8, 1) (at one step a call, as tgv256's
# rehearsal: the fused windows of 96 x 384 x 384 want more rows than the
# CPU can afford; tests/test_sharded_slab.py runs K = 2 and 3)
SMALL_VMEM = 2_000_000
ENGINE = "pallas_sharded[{'z': 4, 'y': 1, 'x': 1},fuse=1,by=8]"


@pytest.fixture
def tiny_run(monkeypatch):
    """run.py with the no-TPU refusal lifted and the cell cut to a tiny
    size; Pallas in interpret mode."""
    import jax

    from benchmark import run
    from tclb_tpu.ops import pallas_d3q
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    plan = pallas_d3q.tile_plan
    monkeypatch.setattr(
        pallas_d3q, "tile_plan",
        lambda model, shape, itemsize=4, fuse=None, budget=None:
        plan(model, shape, itemsize, fuse, SMALL_VMEM))
    for name, shape in SHAPES.items():
        monkeypatch.setitem(tiny.SHAPES, name, shape)
    for name, table in INTERVALS.items():
        monkeypatch.setitem(tiny.INTERVALS, name, table)
    monkeypatch.setattr(run, "load_cell", tiny.shrink(run.load_cell))
    monkeypatch.setattr(run, "template_path", tiny.template_path)
    monkeypatch.setattr(run, "find_chips", lambda chips: jax.devices())
    return run


def test_rehearsal(tiny_run, capsys):
    rc = tiny_run.main(["--workload", "tgv384.zsplit", "--seed",
                        "4294967311", "--seconds", "0.3", "--trace", "0"])
    result, lines = output_of(capsys)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"mlups", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert f"engine: {ENGINE}; fields" in "\n".join(lines)
    assert any("check: engine_fallback events = 0.0" in ln for ln in lines)
    with open(os.path.join(
            tiny_run.OUT,
            "tgv384.zsplit.seed4294967311.trace0.segments.json")) as f:
        rec = json.load(f)
    assert rec["summary"]["steps"] % 6 == 0
    assert {k for _, _, k in rec["segments"]} == {"Log", "Failcheck+Log"}


def test_traced_rehearsal_reports_the_new_metric(tiny_run, capsys,
                                                 monkeypatch):
    """The traced run: the initial field says that it was made in
    shards, the shard's account lies on ``iterate.fused``, the new
    reader is found by name and reads.  The CPU has no device plane, so
    the recording is the kept one."""
    from benchmark.tests.test_trace import recording
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = tiny_run.main(["--workload", "tgv384.zsplit", "--seed", "9",
                        "--seconds", "1.0", "--trace", "1"])
    result, _ = output_of(capsys)
    assert rc == 0 and result["correct"] is True
    m = result["metrics"]
    assert {"kernel_shard_roofline", "kernel_hbm_roofline",
            "kernel_ns_per_update", "halo_bytes_per_step", "failcheck_ms",
            "engine_fallbacks", "compiles_in_window"} <= set(m)
    assert "kernel_tile_roofline" not in m
    assert m["engine_fallbacks"]["value"] == 0.0
    events = trace.read_events(os.path.join(
        tiny_run.OUT, "tgv384.zsplit.seed9.trace1.events.jsonl"))
    element = [e for e in trace.spans(events, "startup.element")
               if "bytes" in e]
    assert [(e["element"], e["sharded"], e["bytes"]) for e in element] \
        == [("CallPython", True, 27 * 32 * 32 * 64 * 4)]
    # a segment of 3 steps: two calls of one step on 8 x 4 windows of
    # 1 slab x 8 rows of each shard, then the trailing step (the probed
    # first call's account lies on its probe)
    fused = [e for e in trace.spans(events, "iterate.fused")
             if "kernel_calls" in e]
    assert {(e["kernel_calls"], e["remainder_steps"], e["shards"],
             e["z_bands"], e["band_slabs"], e["halo_slabs"], e["y_bands"],
             e["band_rows"], e["halo_rows"], e["halo_operand_slabs"])
            for e in fused} == {(2, 0, 4, 8, 1, 1, 4, 8, 8, 1)}
    # a call: 34 planes of one slab a side of 32 x 64 nodes in float32;
    # a call of iterate: the flags' one slab a side once
    sent = 2 * 32 * 64 * (2 * 34 * 4 + 4)
    assert {e["halo_bytes"] for e in fused} == {sent}
    assert m["halo_bytes_per_step"]["value"] == sent / 2


def test_the_guard_refuses_a_program_without_the_engine(monkeypatch):
    """What the parent does on the cell: its dispatch lists no
    ``pallas_sharded[`` engine for a shard whose plane has to be tiled,
    and the guard ends the run at set-up with no result."""
    from types import SimpleNamespace

    from benchmark import require_mesh
    monkeypatch.setenv("TCLB_FASTPATH", "force")

    def solver(chain):
        return SimpleNamespace(lattice=SimpleNamespace(
            _build_fast=lambda: chain))
    assert require_mesh.pallas_sharded_engine(solver(
        [SimpleNamespace(tag=ENGINE)])) == 0
    for chain in ([], [SimpleNamespace(tag="pallas_d3q[d3q27_cumulant]")]):
        with pytest.raises(SystemExit, match="no result"):
            require_mesh.pallas_sharded_engine(solver(chain))
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    assert require_mesh.pallas_sharded_engine(solver([])) == 0


def _tiny_case(seed):
    config = dict(casegen.load_json("configs", "tgv384"),
                  template="tiny_tgv384")
    root, drawn = casegen.generate(
        tiny.template_path(config), casegen.load_json("traffic", "zsplit"),
        seed)
    return config, root, drawn


def test_mesh_reference_is_the_one_device_reference():
    """Laid over four devices along z, the reference gives what
    ``d3q27_cumulant_tgv.run`` gives on one: the layout is its only
    departure."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import d3q27_cumulant_tgv, \
        d3q27_cumulant_tgv_mesh
    config, root, drawn = _tiny_case(2**31 + 54321)
    assert 0.00153 <= drawn["nu"] <= 0.00229
    assert 0.045 <= drawn["velocity"] <= 0.055
    on = d3q27_cumulant_tgv_mesh.layout(32)
    assert len(on.mesh.devices.ravel()) == max(
        k for k in range(1, len(jax.devices()) + 1) if 32 % k == 0)
    mesh = check.reference_fields(config, root, 12)
    one = d3q27_cumulant_tgv.run(root, 12, jnp.float32)
    assert mesh.shape == one.shape == (27, 32, 32, 64)
    assert mesh.dtype == one.dtype == np.float32
    # the same arithmetic a node; the compiler may fuse the partitioned
    # program differently
    assert np.abs(mesh - one).max() < 1e-6
    assert np.abs(one - d3q27_cumulant_tgv.run(root, 0, jnp.float32)
                  ).max() > 1e-4


def test_the_control_fails_the_tiny_check():
    """bfloat16 storage in the reference's place: far outside the limit
    the configuration states, as at 384^3 on the chips."""
    from benchmark import control
    config, root, _ = _tiny_case(3)
    assert control.control_difference(config, root, 20) \
        > 10 * config["tolerance"]


def test_kernel_shard_roofline_by_hand():
    from benchmark.tests.test_phases import events, recording
    events, rec = events(), recording()
    cell = dict(CELL, nodes=2 * 8 * 32 * 128,
                engine="pallas_sharded[{'z': 2, 'y': 1, 'x': 1},fuse=3,by=8]")

    def with_the_account(**more):
        return [dict(e, **{**ACCOUNT, **more})
                if e.get("name") == "iterate.fused" else e for e in events]
    # a program that says no account, or a one-chip engine's without
    # ``shards``: nothing to read, never a number
    assert kernel_shard_roofline.read(events, rec, cell) is None
    assert kernel_shard_roofline.read(with_the_account(), rec, cell) is None
    # two fused spans in a window of 200 steps, all traced: on each of
    # two shards 2 x 33 fused calls of 8 windows of (10 x 24 x 140 +
    # 4 x 8 x 136) x 128 B, and 2 leftover steps at 274 B a node of the
    # whole lattice; 7.6 s of kernels summed over the chips, held
    # against ONE chip's 819 GB/s
    moved = 2 * (2 * 33 * 8 * (10 * 24 * 140 + 4 * 8 * 136) * 128
                 + 2 * 8 * 32 * 128 * 274)
    share = kernel_shard_roofline.read(with_the_account(shards=2), rec, cell)
    assert share == pytest.approx(100 * moved / 819e9 / 7.6)
    many = with_the_account(shards=2, kernel_calls=34_000_000_000_000)
    with pytest.raises(AssertionError):
        kernel_shard_roofline.read(many, rec, cell)
