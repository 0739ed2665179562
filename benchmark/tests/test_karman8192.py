"""What PR 49 added to the benchmark: the configuration ``karman8192``
and its cell ``karman8192.longrun``, rehearsed on the CPU through
run.py, untraced and traced, on a 64 x 2048 stand-in whose rows are
still wide enough that the tuned band engine plans under the raised
scoped-VMEM limit and is probed (interpret mode); the traffic file
``longrun.json``; and ``kernel_dma_roofline`` on the tuned band's
account, the first cell of that engine to list it."""

import json
import os

import pytest

from benchmark import band_bytes, casegen, trace
from benchmark.layer_metrics import kernel_dma_roofline, kernel_hbm_roofline
from benchmark.tests import tiny
from benchmark.tests.test_karman_resident import output_of

SHAPE = [64, 2048]
INTERVALS = {250: 4, 500: 8}
ENGINE = "pallas_2d[d2q9,fuse=2]"
CELL = "karman8192.longrun"
# the account of one segment of 4 steps, as the engine says it: of the 3
# it is handed, one two-step call and one one-step call; the fourth step
# is the tail engine's.  The two-step band of 2048-wide rows: 32 rows
VMEM = (11 + 3) * 48 * 2048 * 4 + 2 * 11 * 32 * 2048 * 4 + 31 * 42 * 2048 * 4
ACCOUNT = dict(kernel_calls=2, remainder_steps=0, paired_calls=0,
               aux_planes=3, bands=2, band_rows=32, halo_rows=8,
               pad_rows=0, vmem_bytes=VMEM,
               vmem_limit_bytes=100 * 1024 * 1024)


@pytest.fixture
def tiny_run(monkeypatch):
    """run.py with the no-TPU refusal lifted and the cell cut to a tiny
    size; Pallas in interpret mode."""
    import jax

    from benchmark import run
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    monkeypatch.setitem(tiny.SHAPES, "karman8192", SHAPE)
    monkeypatch.setitem(tiny.INTERVALS, "longrun", INTERVALS)
    shrunk = tiny.shrink(run.load_cell)

    def load_cell(name):
        cell, config, traffic = shrunk(name)
        for rule in traffic["seeded"]:      # the tiny channel's walk
            if "int" in rule:
                rule["int"] = [-4, 4]
        return cell, config, traffic

    monkeypatch.setattr(run, "load_cell", load_cell)
    monkeypatch.setattr(run, "template_path", tiny.template_path)
    monkeypatch.setattr(run, "find_chips", lambda chips: jax.devices())
    return run


def test_the_cell_and_its_files():
    from benchmark import run
    cell, config, traffic = run.load_cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "longrun")
    assert set(cell["end_to_end"]) == {"mlups", "setup_s"}
    assert config["shape"] == [8192, 8192]
    assert config["reduced"] == ["nx", "ny", "Wedge"]
    assert casegen.segment_steps(traffic) == 250
    assert traffic["handlers"] == [{"tag": "Failcheck", "Iterations": 500},
                                   {"tag": "Log", "Iterations": 250}]
    # seeded exactly as the shipped mix
    assert traffic["seeded"] == casegen.load_json(
        "traffic", "shipped")["seeded"]
    for name in ("kernel_ns_per_update", "kernel_hbm_roofline",
                 "kernel_dma_roofline", "kernel_wrap_share",
                 "globals_step_ms", "failcheck_ms", "compile_s",
                 "compiles_in_window", "probe_s", "segment_host_ms",
                 "log_ms", "dispatch_ms", "idle_unnamed_share",
                 "handlers_share", "engine_fallbacks", "first_call_s",
                 "xla_tail_share", "device_idle_share"):
        assert name in cell["per_layer"]
    for name in ("vtk_ms", "sample_ms", "kernel_tile_roofline",
                 "kernel_resident_roofline", "halo_bytes_per_step"):
        assert name not in cell["per_layer"]
    # the steady mix, whose name the queue gave the cell, seeds a force
    # the karman template has not
    with pytest.raises(ValueError, match="finds nothing"):
        casegen.generate(run.template_path(config),
                         casegen.load_json("traffic", "steady"), 1)


def test_rehearsal(tiny_run, capsys):
    rc = tiny_run.main(["--workload", CELL, "--seed", "4294967345",
                        "--seconds", "0.3", "--trace", "0"])
    result, lines = output_of(capsys)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"mlups", "setup_s"}
    text = "\n".join(lines)
    assert f"engine: {ENGINE}; fields (11, 64, 2048)" in text
    assert "check: engine_fallback events = 0.0" in text
    assert f"check: engine {ENGINE} outside family pallas_2d = 0.0" in text
    assert "after 4 steps" in text
    with open(os.path.join(tiny_run.OUT, CELL + ".seed4294967345."
                           "trace0.segments.json")) as f:
        rec = json.load(f)
    kinds = [k for _, _, k in rec["segments"]]
    assert set(kinds) == {"Log", "Failcheck+Log"}
    assert kinds.count("Log") == kinds.count("Failcheck+Log")


def test_traced_rehearsal_reports_the_plan(tiny_run, capsys, monkeypatch):
    """The traced run: the probed first call and every ``iterate.fused``
    after it carry the tuned band's account and its plan's VMEM, and
    every reader the cell lists is found by name.  The CPU has no device
    plane, so the run reduces a kept recording."""
    from benchmark import bytes_model
    from benchmark.tests.test_trace import recording
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = tiny_run.main(["--workload", CELL, "--seed", "7",
                        "--seconds", "1.0", "--trace", "1"])
    result, _ = output_of(capsys)
    assert rc == 0 and result["correct"] is True
    m = result["metrics"]
    assert {"kernel_ns_per_update", "kernel_hbm_roofline",
            "kernel_dma_roofline", "compile_s", "compiles_in_window",
            "probe_s", "segment_host_ms", "log_ms", "dispatch_ms",
            "failcheck_ms", "device_idle_share", "engine_fallbacks",
            "handlers_share", "first_call_s"} <= set(m)
    assert m["engine_fallbacks"]["value"] == 0.0
    assert m["compiles_in_window"]["value"] == 0.0
    assert m["probe_s"]["value"] > 0
    events = trace.read_events(os.path.join(
        tiny_run.OUT, CELL + ".seed7.trace1.events.jsonl"))
    fused = trace.spans(events, "iterate.fused")
    assert {e["engine"] for e in fused} == {ENGINE}
    probes = [e for e in trace.spans(events, "engine.probe")
              if e["engine"] == ENGINE]
    assert len(probes) == 1
    assert (probes[0]["attempts"], probes[0]["rungs"],
            probes[0]["result"]) == (1, [32], ENGINE)
    # the first call's account lies on the probe that made the calls
    for span in probes + fused[1:]:
        assert {k: span[k] for k in ACCOUNT} == ACCOUNT
    tails = trace.spans(events, "iterate.globals_step")
    assert {e["engine"] for e in tails} == {"pallas_generic[d2q9,fuse=1]"}
    sel = [e for e in events if e.get("kind") == "engine_selected"]
    assert (sel[0]["engine"], sel[0]["probed"]) == (ENGINE, True)


def test_dma_roofline_reads_the_tuned_bands_account():
    """At the real size: 256 bands of 32 rows under 16 halo rows, 125
    calls an ``iterate(250)``'s 249 engine steps (124 two-step calls and
    the one-step call, counted at the looped band): 128 B a node and
    call, 64.3 B an update against the least 45."""
    nodes = 8192 * 8192
    call = band_bytes.call_bytes(nodes, 256, 32, 8, 0, 11, 4, 3)
    assert call == nodes * (48 * 56 + 32 * 44) // 32 == nodes * 128
    spans = [dict(kind="span", name="iterate.fused", id=i, iters=249,
                  iteration=250 * i, kernel_calls=125, bands=256,
                  band_rows=32, halo_rows=8, pad_rows=0, aux_planes=3)
             for i in (4, 5)]
    kernel_s = 2 * 125 * 0.020
    rec = trace.Recording(
        devices={"0": [["d2q9_band_fuse2.3_custom-call_tpu_custom_call",
                        1.0 + 0.021 * k, 0.020] for k in range(250)]},
        host=[[trace.TRACED, 0.5, 7.0]])
    cell = {"window": {"first_iteration": 1000, "last_iteration": 1500},
            "nodes": nodes, "planes": 11, "itemsize": 4, "chips": 1,
            "device_kind": "TPU v5 lite", "engine": ENGINE, "fuse": 2,
            "traced_steps": 500}
    dma = kernel_dma_roofline.read(spans, rec, cell)
    hbm = kernel_hbm_roofline.read(spans, rec, cell)
    moved = 2 * 125 * call / 498 * 500
    assert dma == pytest.approx(100 * moved / 819e9 / kernel_s)
    assert hbm == pytest.approx(100 * nodes * 500 * 45 / 819e9 / kernel_s)
    assert dma / hbm == pytest.approx(128 * 250 / 498 / 45, rel=1e-6)
