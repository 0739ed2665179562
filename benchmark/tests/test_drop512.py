"""What PR 42 added to the benchmark: the cell ``drop512.relax``
rehearsed on the CPU through run.py, untraced and traced (the generic
VMEM-resident engine in interpret mode on a stand-in of two chunks), the
family check under the tag without a K, the account on the span, and
``kernel_resident_roofline`` and ``kernel_remainder_share`` by hand for
the real segment: one resident call of 498 steps and two band steps in
float32.  No new reader, no new traffic file: the cell is data."""

import json
import os

import pytest

from benchmark import band_bytes, bytes_model, casegen, resident_bytes, trace
from benchmark.layer_metrics import (kernel_hbm_roofline,
                                     kernel_remainder_share,
                                     kernel_resident_roofline)
from benchmark.tests import tiny
from benchmark.tests.test_karman_resident import output_of

SHAPE = [128, 128]
# a segment of 8 steps: one resident call of 6 and 2 left over, as
# 500 = 498 + 2 at the real intervals
INTERVALS = {500: 8, 1000: 16}
# the drop's ranges are for 512 nodes a side: a quarter of them here
RANGE = [-8, 8]
ENGINE = "pallas_resident_generic[d2q9_kuper]"
# the account of one such segment, as the engine says it: at 128 rows
# the band kernel's calls are 4 bands of 32 rows
ACCOUNT = dict(kernel_calls=3, resident_calls=1, resident_steps=6,
               remainder_steps=2, aux_planes=2, remainder_aux_planes=1,
               chunk_rows=64, vmem_bytes=88 * 128 * 128, bands=4,
               band_rows=32, halo_rows=8, pad_rows=0, stages_per_step=2)
# and of a segment of 500 steps at the real size: eight chunks of 64
# rows on-chip, the two steps left over on 16 bands of 32 rows
NODES = 512 * 512
FULL = dict(ACCOUNT, resident_steps=498, vmem_bytes=23_068_672, bands=16)


@pytest.fixture
def tiny_run(monkeypatch):
    """run.py with the no-TPU refusal lifted and the cell cut to a tiny
    size; Pallas in interpret mode."""
    import jax

    from benchmark import run
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    monkeypatch.setitem(tiny.SHAPES, "drop512", SHAPE)
    monkeypatch.setitem(tiny.INTERVALS, "relax", INTERVALS)
    shrunk = tiny.shrink(run.load_cell)

    def load_cell(name):
        cell, config, traffic = shrunk(name)
        for rule in traffic["seeded"]:
            rule["int"] = RANGE
        return cell, config, traffic

    monkeypatch.setattr(run, "load_cell", load_cell)
    monkeypatch.setattr(run, "template_path", tiny.template_path)
    monkeypatch.setattr(run, "find_chips", lambda chips: jax.devices())
    return run


def test_the_cell_is_data():
    """One configuration file, one template, the traffic file that
    ``drop1024.relax`` has; nothing reduced."""
    from benchmark import run
    cell, config, traffic = run.load_cell("drop512.relax")
    assert (cell["chips"], cell["traffic"]) == (1, "relax")
    assert set(cell["end_to_end"]) == {"mlups", "setup_s"}
    assert config["reduced"] == [] and config["reduced_why"] == {}
    assert config["shape"] == [512, 512]
    assert config["engine_family"] == "pallas_resident_generic"
    assert traffic == casegen.load_json("traffic", "relax")
    assert casegen.segment_steps(traffic) == 500
    for name in ("kernel_resident_roofline", "kernel_remainder_share",
                 "kernel_ns_per_update", "kernel_wrap_share", "failcheck_ms",
                 "probe_s", "segment_host_ms", "log_ms", "dispatch_ms"):
        assert name in cell["per_layer"]
    # no K in the tag, no trailing XLA step, no bands of its own, no VTK
    for name in ("kernel_hbm_roofline", "globals_step_ms",
                 "kernel_dma_roofline", "kernel_tile_roofline", "vtk_ms",
                 "halo_bytes_per_step"):
        assert name not in cell["per_layer"]
    # the tag states no depth: the least-bytes reader has nothing to
    # divide by, for this engine and for the parent's name of it alike
    assert bytes_model.fuse_of(ENGINE) == 0
    assert kernel_hbm_roofline.read(
        [], synthetic_recording(1, FULL), CELL) is None


def test_rehearsal(tiny_run, capsys):
    rc = tiny_run.main(["--workload", "drop512.relax", "--seed",
                        "4294967311", "--seconds", "0.3", "--trace", "0"])
    result, lines = output_of(capsys)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"mlups", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    text = "\n".join(lines)
    assert f"engine: {ENGINE}; fields (10, 128, 128)" in text
    assert "check: engine_fallback events = 0.0" in text
    assert (f"check: engine {ENGINE} outside family "
            "pallas_resident_generic = 0.0") in text
    assert "after 8 steps" in text
    with open(os.path.join(tiny_run.OUT, "drop512.relax.seed4294967311."
                           "trace0.segments.json")) as f:
        rec = json.load(f)
    assert rec["summary"]["steps"] % 16 == 0
    kinds = [k for _, _, k in rec["segments"]]
    assert set(kinds) == {"Log", "Failcheck+Log"}
    assert kinds.count("Log") == kinds.count("Failcheck+Log")


def synthetic_recording(segments: int, account: dict,
                        resident_s=25e-3, band_s=60e-6) -> trace.Recording:
    """A device line of ``segments`` calls as the account describes them
    (the chip's operation names), under one traced span."""
    steps = account["resident_steps"]
    evs, t = [], 1.0
    for _ in range(segments):
        evs.append(["fusion.3_fusion", t, 5e-6])
        evs.append([f"generic_resident_fuse{steps}.4_custom-call_"
                    "tpu_custom_call", t + 5e-6, resident_s])
        t += resident_s + 1e-4
        for _ in range(account["remainder_steps"]):
            evs.append(["generic_band_fuse1.9_custom-call_tpu_custom_call",
                        t, band_s])
            t += 1e-4
        t += 3e-3
    return trace.Recording(devices={"0": evs},
                           host=[[trace.TRACED, 0.5, t]])


def test_traced_rehearsal_reports_the_account(tiny_run, capsys,
                                              monkeypatch):
    """The traced run: the program's spans carry the generic resident
    engine's account and the readers the cell lists are found by name.
    The CPU has no device plane, so the run reduces a kept recording
    (whose operations are another cell's: ``kernel_remainder_share``
    reads nothing there); the readers then read a recording made to the
    rehearsal's own events."""
    from benchmark.tests.test_trace import recording
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = tiny_run.main(["--workload", "drop512.relax", "--seed", "9",
                        "--seconds", "1.0", "--trace", "1"])
    result, _ = output_of(capsys)
    assert rc == 0 and result["correct"] is True
    m = result["metrics"]
    assert {"kernel_resident_roofline", "kernel_ns_per_update",
            "failcheck_ms", "probe_s", "compile_s",
            "compiles_in_window", "segment_host_ms", "log_ms",
            "dispatch_ms", "device_idle_share", "engine_fallbacks",
            "handlers_share"} <= set(m)
    assert not {"kernel_hbm_roofline", "kernel_remainder_share",
                "globals_step_ms", "kernel_dma_roofline", "vtk_ms"} & set(m)
    assert m["engine_fallbacks"]["value"] == 0.0
    assert 0 < m["probe_s"]["value"] < m["first_call_s"]["value"]
    events = trace.read_events(os.path.join(
        tiny_run.OUT, "drop512.relax.seed9.trace1.events.jsonl"))
    fused = trace.spans(events, "iterate.fused")
    probe = trace.spans(events, "engine.probe")[0]
    # the first call's account lies on the probe that made the calls
    assert "kernel_calls" not in fused[0]
    assert probe["parent"] == fused[0]["id"]
    assert (probe["engine"], probe["result"]) == (ENGINE, ENGINE)
    for span in [probe] + fused[1:]:
        assert {k: span[k] for k in ACCOUNT} == ACCOUNT
    assert {e["iters"] for e in fused} == {8}
    # in-kernel globals: the engine has no trailing XLA step
    assert not trace.spans(events, "iterate.globals_step")
    counters = [e for e in events if e.get("kind") == "counters"]
    if counters:
        said = counters[-1]["counters"]
        assert said["engine.resident_calls"] == len(fused)
        # every segment is one length: one program, built at the probe
        assert said["engine.resident_programs"] == 1

    # the readers on the rehearsal's own events: two periods traced
    its = trace.spans(events, "iterate")
    first, last = its[5]["iteration"], its[-1]["iteration"] + 8
    cell = {"window": {"first_iteration": first, "last_iteration": last},
            "nodes": 128 * 128, "planes": 10, "itemsize": 4, "chips": 1,
            "device_kind": "TPU v5 lite", "engine": ENGINE, "fuse": 0,
            "traced_steps": 32}
    rec = synthetic_recording(4, ACCOUNT, resident_s=300e-6, band_s=30e-6)
    share = kernel_remainder_share.read(events, rec, cell)
    assert share == pytest.approx(100 * 2 * 30 / (2 * 30 + 300))
    moved = 4 * (88 * 16_384 + 2 * band_bytes.call_bytes(
        16_384, 4, 32, 8, 0, 10, 4, 1))
    assert kernel_resident_roofline.read(events, rec, cell) \
        == pytest.approx(100 * moved / 819e9 / (4 * (300e-6 + 2 * 30e-6)))


# -- the real segment, by hand --------------------------------------------- #

CELL = {"window": {"first_iteration": 1000, "last_iteration": 3000},
        "nodes": NODES, "planes": 10, "itemsize": 4, "chips": 1,
        "device_kind": "TPU v5 lite", "engine": ENGINE, "fuse": 0,
        "traced_steps": 2000}


def spans_of(account: dict) -> list[dict]:
    """Six segments of 500 steps, the first probed; four in the window."""
    out = []
    for k in range(6):
        at = {"kind": "span", "iteration": 500 * k, "ts": 1.0 + k,
              "dur_s": 0.03}
        out.append(dict(at, name="iterate", iters=500))
        out.append(dict(at, name="iterate.fused", iters=500,
                        **(account if k else {})))
    return out


def test_the_segments_bytes_by_hand():
    """512 x 512, 10 planes of float32, the flags and one zonal plane
    (``Density``) beside them on-chip; the band kernel reads the flag
    plane alone."""
    assert resident_bytes.resident_call_bytes(NODES, 10, 4, 2) \
        == 88 * NODES == 23_068_672
    band = band_bytes.call_bytes(NODES, 16, 32, 8, 0, 10, 4, 1)
    assert band == 16 * (48 * 512 * 44 + 32 * 512 * 40) == 27_787_264
    assert resident_bytes.iterate_bytes(FULL, NODES, 10, 4) \
        == 23_068_672 + 2 * 27_787_264 == 78_643_200
    # an update of the resident call moves 88 / 498 B, where the tag's
    # old `fuse=8` made the least-bytes reader reckon 82 / 8
    assert 23_068_672 / NODES / 498 == pytest.approx(0.1767, abs=1e-4)
    assert bytes_model.bytes_per_update(10, 4, 8) == 10.25


def test_kernel_resident_roofline_by_hand():
    rec = synthetic_recording(4, FULL)
    kernel_s = 4 * (25e-3 + 2 * 60e-6)
    share = kernel_resident_roofline.read(spans_of(FULL), rec, CELL)
    assert share == pytest.approx(100 * 4 * 78_643_200 / 819e9 / kernel_s)
    assert 0.3 < share < 0.5          # low by design: 0.1 ms of 25
    # a program without the account, or no kernel traced
    assert kernel_resident_roofline.read(spans_of({}), rec, CELL) is None
    bare = trace.Recording(devices={"0": [["fusion.1_fusion", 0.0, 1.0]]},
                           host=[[trace.TRACED, 0.0, 2.0]])
    assert kernel_resident_roofline.read(spans_of(FULL), bare, CELL) is None


def test_kernel_remainder_share_by_hand():
    rec = synthetic_recording(4, FULL)
    # one resident and two band operations an iterate, as the account says
    assert kernel_remainder_share.read(spans_of(FULL), rec, CELL) \
        == pytest.approx(100 * 2 * 60e-6 / (2 * 60e-6 + 25e-3))
    assert kernel_remainder_share.read(spans_of({}), rec, CELL) is None
    # a trace whose operations are not what the account says reads nothing
    other = dict(FULL, remainder_steps=1)
    assert kernel_remainder_share.read(spans_of(other), rec, CELL) is None
