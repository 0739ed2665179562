"""What PR 32 added to the benchmark: the cells ``tgv256.decay`` and
``karman4096.logonly`` rehearsed on the CPU through run.py (the first
with its plane tiled in y, as at 256^3), the plain Taylor-Green
reference against the program in float64 on the tiny case,
``tile_bytes.py``'s counts by hand, and the reader
``kernel_tile_roofline`` on the kept recording of ``test_phases.py`` with
the engine's account written into its events."""

import copy
import json
import os
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from benchmark import bytes_model, casegen, check, tile_bytes, trace
from benchmark.layer_metrics import kernel_tile_roofline
from benchmark.tests import tiny

# the new cells cut to sizes a CPU holds: entries for ``tiny.py``'s tables
# (the file stays as it is; the fixture below puts them in for a test)
SHAPES = {"tgv256": [8, 32, 128]}
INTERVALS = {"decay": {250: 2, 500: 4}, "logonly": {500: 2}}
# VMEM the 3D planner may count on in the rehearsal: no kernel then
# holds a whole 32 x 128 plane, so the plane is tiled (at one step a call:
# the fused windows of 256^3 want more rows than the CPU can afford)
SMALL_VMEM = 4_500_000


@pytest.fixture
def tiny_run(monkeypatch):
    """run.py with the no-TPU refusal lifted and the cells cut to a tiny
    size; Pallas in interpret mode."""
    import jax

    from benchmark import run
    from tclb_tpu.ops import pallas_d3q
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


def output_of(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("cell,engine,per,kinds", [
    ("tgv256.decay", "pallas_d3q[d3q27_cumulant,fuse=1,by=8]", 4,
     {"Log", "Failcheck+Log"}),
    ("karman4096.logonly", "pallas_sharded[{'y': 4, 'x': 1},fuse=2]", 2,
     {"Log"})])
def test_rehearsal(tiny_run, capsys, cell, engine, per, kinds):
    rc = tiny_run.main(["--workload", cell, "--seed", "4294967311",
                        "--seconds", "0.3", "--trace", "0"])
    result, lines = output_of(capsys)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"mlups", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert f"engine: {engine}; fields" in "\n".join(lines)
    assert any("check: engine_fallback events = 0.0" in ln for ln in lines)
    with open(os.path.join(tiny_run.OUT, cell + ".seed4294967311.trace0."
                           "segments.json")) as f:
        rec = json.load(f)
    assert rec["summary"]["steps"] % per == 0
    assert {k for _, _, k in rec["segments"]} == kinds


def test_traced_rehearsal_reports_the_new_metric(tiny_run, capsys,
                                                 monkeypatch):
    """The traced run of ``tgv256.decay``: the initial field has its
    span, the engine's account lies on ``iterate.fused``, the new reader
    is found by name and reads.  The CPU has no device plane, so the
    recording is the kept one."""
    from benchmark.tests.test_trace import recording
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = tiny_run.main(["--workload", "tgv256.decay", "--seed", "9",
                        "--seconds", "1.0", "--trace", "1"])
    result, _ = output_of(capsys)
    assert rc == 0 and result["correct"] is True
    m = result["metrics"]
    assert {"kernel_tile_roofline", "kernel_hbm_roofline",
            "kernel_ns_per_update", "failcheck_ms", "compile_s",
            "engine_fallbacks", "compiles_in_window"} <= set(m)
    assert m["engine_fallbacks"]["value"] == 0.0
    events = trace.read_events(os.path.join(
        tiny_run.OUT, "tgv256.decay.seed9.trace1.events.jsonl"))
    first = trace.spans(events, "callpython")
    assert [(e["function"], e["dur_s"] > 0) for e in first] \
        == [("taylor_green", True)]
    fused = trace.spans(events, "iterate.fused")
    # a segment of 2 steps: one call of the one-step plan on 8 x 4
    # windows of 1 slab x 8 rows, then the trailing step
    assert {tuple(e[k] for k in kernel_tile_roofline.FIELDS)
            for e in fused} == {(1, 0, 8, 1, 1, 4, 8, 8, 1)}
    assert len(trace.spans(events, "iterate.globals_step")) == len(fused)


def program_fields(root, steps, model_name, tmp_path):
    """As ``test_reference.py``'s, but the case keeps the
    ``<CallPython>`` that sets its initial field (the clock, which has
    ``Iterations``, goes)."""
    import jax.numpy as jnp

    from tclb_tpu.control.solver import run_config_string
    from tclb_tpu.models import get_model
    root = copy.deepcopy(root)
    for el in list(root):
        if el.tag in ("Failcheck", "Log", "VTK") or (
                el.tag == "CallPython" and el.get("Iterations")):
            root.remove(el)
        elif el.tag == "Solve":
            el.set("Iterations", str(steps))
    return run_config_string(ET.tostring(root, encoding="unicode"),
                             get_model(model_name), dtype=jnp.float64,
                             output=str(tmp_path) + "/")


def test_reference_is_the_programs_semantics(tmp_path, monkeypatch):
    import jax
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    config = dict(casegen.load_json("configs", "tgv256"),
                  template="tiny_tgv256", dtype="float64")
    root, drawn = casegen.generate(
        tiny.template_path(config), casegen.load_json("traffic", "decay"),
        2**31 + 12345)
    assert set(drawn) == {"nu", "velocity"}
    assert 0.00106 <= drawn["nu"] <= 0.00159
    assert root.find("Model/Params[@Velocity]").get("Velocity") \
        == repr(drawn["velocity"])
    with jax.enable_x64(True):
        solver = program_fields(root, 50, config["model"], tmp_path)
        program = np.asarray(solver.lattice.state.fields)
        ref = check.reference_fields(config, root, 50)
        assert ref.dtype == np.float64 and ref.shape == (27, 8, 32, 128)
        assert check.largest_difference(program, ref) < 1e-13
        # the vortex is there and has moved: not the uniform Init field
        start = check.reference_fields(config, root, 0)
        assert np.abs(ref - start).max() > 1e-4
        assert np.ptp(start[13]) > 1e-4       # the rest population
        # every node collides: no wall, no inlet
        m = solver.model
        flags = np.asarray(solver.lattice.state.flags)
        assert (flags == m.flag_for("MRT")).all()
    # a case that does not name the initial field is not this reference's
    bare = copy.deepcopy(root)
    for el in bare.findall("CallPython"):
        bare.remove(el)
    with pytest.raises(ValueError):
        check.reference_fields(config, bare, 1)


def test_the_control_fails_the_tiny_check(tmp_path):
    """bfloat16 storage in the reference's place: far outside the limit
    the configuration states, as at 256^3 on the chip."""
    from benchmark import control
    config = dict(casegen.load_json("configs", "tgv256"),
                  template="tiny_tgv256")
    root, _ = casegen.generate(
        tiny.template_path(config), casegen.load_json("traffic", "decay"), 3)
    assert control.control_difference(config, root, 20) \
        > 10 * config["tolerance"]


def test_tile_bytes_by_hand():
    """34 planes of float32 and the int32 flag plane."""
    # the channel's plan: 256 bands of 2 slabs, 3 halo slabs, whole
    # 48 x 256 planes
    assert tile_bytes.window_read_bytes(256, 2, 3, 48, 0, 34, 4, 1) \
        == 8 * 48 * 256 * 140 == 13_762_560
    assert tile_bytes.window_write_bytes(256, 2, 48, 34, 4) \
        == 2 * 48 * 256 * 136 == 3_342_336
    call = tile_bytes.call_bytes(512 * 48 * 256, 256, 2, 3, 1, 48, 0, 34,
                                 4, 1)
    assert call == 256 * (13_762_560 + 3_342_336) == 4_378_853_376
    assert call / (512 * 48 * 256) == 696.0        # bytes a node and call
    assert call / (512 * 48 * 256) / 3 == 232.0    # an update, at fuse 3
    # 256^3 in windows of 4 slabs x 32 rows, 3 halo slabs, 8 halo rows:
    # each window reads 10 x 48 rows and writes 4 x 32
    tiled = tile_bytes.call_bytes(256 ** 3, 64, 4, 3, 8, 32, 8, 34, 4, 1)
    assert tiled == 64 * 8 * 256 * (10 * 48 * 140 + 4 * 32 * 136)
    assert tiled / 256 ** 3 == 661.0
    assert tiled / 256 ** 3 / 3 == pytest.approx(220.33, abs=0.01)
    # bfloat16 storage halves the field planes, not the flags
    assert tile_bytes.window_read_bytes(256, 4, 3, 32, 8, 34, 2, 1) \
        == 10 * 48 * 256 * 72
    with pytest.raises(ValueError):
        tile_bytes.call_bytes(1000, 64, 4, 3, 8, 32, 8, 34, 4, 1)


# -- the reader on the kept recording of test_phases.py -------------------- #

CELL = {"window": {"first_iteration": 100, "last_iteration": 300},
        "nodes": 8 * 32 * 128, "planes": 34, "itemsize": 4, "chips": 2,
        "device_kind": "TPU v5 lite",
        "engine": "pallas_d3q[d3q27_cumulant,fuse=3,by=8]",
        "traced_steps": 200}
ACCOUNT = dict(kernel_calls=34, remainder_steps=1, z_bands=2, band_slabs=4,
               halo_slabs=3, y_bands=4, band_rows=8, halo_rows=8,
               aux_planes=1)


def with_the_account(events):
    return [dict(e, **ACCOUNT) if e.get("name") == "iterate.fused" else e
            for e in events]


def test_kernel_tile_roofline_by_hand():
    from benchmark.tests.test_phases import events, recording
    events, rec = events(), recording()
    assert kernel_tile_roofline.read(events, rec, CELL) is None  # the parent
    # two fused spans in a window of 200 steps, all 200 traced: 2 x 33
    # fused calls of 8 windows of (10 x 24 x 140 + 4 x 8 x 136) x 128 B
    # and 2 leftover steps at 274 B a node; 7.6 s of kernels on two
    # chips of 819 GB/s
    moved = 2 * (33 * 8 * (10 * 24 * 140 + 4 * 8 * 136) * 128
                 + 8 * 32 * 128 * 274)
    share = kernel_tile_roofline.read(with_the_account(events), rec, CELL)
    assert share == pytest.approx(100 * moved / (2 * 819e9) / 7.6)
    # half the window traced: half the bytes
    assert kernel_tile_roofline.read(
        with_the_account(events), rec, dict(CELL, traced_steps=100)) \
        == pytest.approx(share / 2)
    # an engine that runs no fused call says less: nothing to read
    single = [{k: v for k, v in e.items() if k != "z_bands"}
              for e in with_the_account(events)]
    assert kernel_tile_roofline.read(single, rec, CELL) is None
    # no kernel in the trace: nothing to hold the bytes against
    bare = trace.Recording(devices={"0": [["fusion.1_fusion", 0.0, 1.0]]},
                           host=[[trace.TRACED, 0.0, 2.0]])
    assert kernel_tile_roofline.read(with_the_account(events), bare,
                                     CELL) is None
    # bytes counted a millionfold: over 100 %, and the run fails
    many = [dict(e, kernel_calls=34_000_000_000_000)
            if e.get("name") == "iterate.fused" else e
            for e in with_the_account(events)]
    with pytest.raises(AssertionError):
        kernel_tile_roofline.read(many, rec, CELL)
