"""What PR 40 added to the benchmark: the cell ``karman.resident``
rehearsed on the CPU through run.py, untraced and traced (the tuned
VMEM-resident engine in interpret mode at the published height of 100
rows), ``resident_bytes.py``'s counts by hand, the readers
``kernel_resident_roofline`` and ``kernel_remainder_share`` on synthetic
spans and on the rehearsal's own events, and the seeded obstacle's
extremes at the real size."""

import json
import os

import pytest

from benchmark import band_bytes, casegen, resident_bytes, trace
from benchmark.layer_metrics import (kernel_remainder_share,
                                     kernel_resident_roofline)
from benchmark.reference import geometry
from benchmark.tests import tiny

SHAPE = [100, 128]
# a segment of 16 steps: the hybrid hands the engine 15, one resident
# call of 8 and 7 left over, as 999 = 124 x 8 + 7 at the real intervals
INTERVALS = {1000: 16, 5000: 80}
ENGINE = "pallas_resident[d2q9,fuse=8]"
# the account of one such segment, as the engine says it: at 128
# columns the 120 padded rows of the band kernel are one band
ACCOUNT = dict(kernel_calls=8, resident_calls=1, resident_steps=8,
               remainder_steps=7, aux_planes=3, remainder_aux_planes=3,
               chunk_rows=50, vmem_bytes=(3 * 11 + 3) * 100 * 128 * 4,
               bands=1, band_rows=120, halo_rows=8, pad_rows=20)


# and of a segment of 1000 steps at the real size: 3 bands of 40 rows
FULL = dict(ACCOUNT, kernel_calls=131, resident_calls=124,
            vmem_bytes=14_745_600, bands=3, band_rows=40)


@pytest.fixture
def tiny_run(monkeypatch):
    """run.py with the no-TPU refusal lifted and the cell cut to a tiny
    size; Pallas in interpret mode."""
    import jax

    from benchmark import run
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    monkeypatch.setitem(tiny.SHAPES, "karman", SHAPE)
    monkeypatch.setitem(tiny.INTERVALS, "resident", INTERVALS)
    shrunk = tiny.shrink(run.load_cell)

    def load_cell(name):
        cell, config, traffic = shrunk(name)
        for rule in traffic["seeded"]:      # an eighth of the length
            if rule["var"] == "ox":
                rule["int"] = [-4, 4]
        return cell, config, traffic

    monkeypatch.setattr(run, "load_cell", load_cell)
    monkeypatch.setattr(run, "template_path", tiny.template_path)
    monkeypatch.setattr(run, "find_chips", lambda chips: jax.devices())
    return run


def output_of(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def test_rehearsal(tiny_run, capsys):
    rc = tiny_run.main(["--workload", "karman.resident", "--seed",
                        "4294967311", "--seconds", "0.3", "--trace", "0"])
    result, lines = output_of(capsys)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"mlups", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    text = "\n".join(lines)
    assert f"engine: {ENGINE}; fields (11, 100, 128)" in text
    assert "check: engine_fallback events = 0.0" in text
    assert "after 16 steps" in text
    assert "finite, of the case size" in text
    with open(os.path.join(tiny_run.OUT, "karman.resident.seed4294967311."
                           "trace0.segments.json")) as f:
        rec = json.load(f)
    assert rec["summary"]["steps"] % 80 == 0
    kinds = [k for _, _, k in rec["segments"]]
    assert set(kinds) == {"Log", "Log+VTK"}
    assert kinds.count("Log") == 4 * kinds.count("Log+VTK")


def synthetic_recording(segments: int, account: dict,
                        resident_s=80e-6, band_s=30e-6) -> trace.Recording:
    """A device line of ``segments`` calls as the account describes them
    (the chip's operation names), each kernel after the copy XLA puts
    before it, under one traced span."""
    evs, t = [], 1.0
    for _ in range(segments):
        for _ in range(account["resident_calls"]):
            evs.append(["copy.24_copy", t, 5e-6])
            evs.append(["d2q9_resident_fuse8.3_custom-call_tpu_custom_call",
                        t + 5e-6, resident_s])
            t += 1e-4
        for _ in range(account["remainder_steps"]):
            evs.append(["d2q9_band_fuse1.7_custom-call_tpu_custom_call",
                        t, band_s])
            t += 1e-4
        evs.append(["fusion.9_fusion", t, 1e-5])
        t += 1e-3
    return trace.Recording(devices={"0": evs},
                           host=[[trace.TRACED, 0.5, t]])


def test_traced_rehearsal_reports_the_account(tiny_run, capsys,
                                              monkeypatch):
    """The traced run: the program's spans carry the resident engine's
    account, both new readers are found by name.  The CPU has no device
    plane, so the run reduces a kept recording (whose operations are
    another cell's: ``kernel_remainder_share`` reads nothing there); the
    readers then read a recording made to the rehearsal's own events."""
    from benchmark import bytes_model
    from benchmark.tests.test_trace import recording
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = tiny_run.main(["--workload", "karman.resident", "--seed", "9",
                        "--seconds", "1.0", "--trace", "1"])
    result, _ = output_of(capsys)
    assert rc == 0 and result["correct"] is True
    m = result["metrics"]
    assert {"kernel_resident_roofline", "kernel_hbm_roofline",
            "kernel_ns_per_update", "probe_s", "compile_s",
            "compiles_in_window", "segment_host_ms", "log_ms",
            "dispatch_ms", "device_idle_share", "engine_fallbacks",
            "handlers_share"} <= set(m)
    assert not {"kernel_remainder_share", "failcheck_ms", "vtk_ms",
                "vtk_encode_ms", "kernel_dma_roofline"} & set(m)
    assert m["engine_fallbacks"]["value"] == 0.0
    assert 0 < m["probe_s"]["value"] < m["first_call_s"]["value"]
    events = trace.read_events(os.path.join(
        tiny_run.OUT, "karman.resident.seed9.trace1.events.jsonl"))
    fused = trace.spans(events, "iterate.fused")
    probe = trace.spans(events, "engine.probe")[0]
    # the first call's account lies on the probe that made the calls
    assert "kernel_calls" not in fused[0]
    assert probe["parent"] == fused[0]["id"]
    for span in [probe] + fused[1:]:
        assert {k: span[k] for k in ACCOUNT} == ACCOUNT
    assert {e["iters"] for e in fused} == {15}
    assert len(trace.spans(events, "iterate.globals_step")) == len(fused)
    counters = [e for e in events if e.get("kind") == "counters"]
    if counters:
        assert counters[-1]["counters"]["engine.resident_calls"] \
            == len(fused)

    # the readers on the rehearsal's own events: two periods traced
    its = trace.spans(events, "iterate")
    first, last = its[5]["iteration"], its[-1]["iteration"] + 16
    cell = {"window": {"first_iteration": first, "last_iteration": last},
            "nodes": 100 * 128, "planes": 11, "itemsize": 4, "chips": 1,
            "device_kind": "TPU v5 lite", "engine": ENGINE, "fuse": 8,
            "traced_steps": 160}
    rec = synthetic_recording(10, ACCOUNT)
    share = kernel_remainder_share.read(events, rec, cell)
    assert share == pytest.approx(100 * 7 * 30 / (7 * 30 + 80))
    moved = 10 * (100 * 12_800 + 7 * band_bytes.call_bytes(
        12_800, 1, 120, 8, 20, 11, 4, 3))
    assert kernel_resident_roofline.read(events, rec, cell) \
        == pytest.approx(100 * moved / 819e9 / (10 * (80e-6 + 7 * 30e-6)))


def test_resident_bytes_by_hand():
    """1024 x 100, 11 planes of float32, flags and two zonal planes."""
    nodes = 102_400
    assert resident_bytes.resident_call_bytes(nodes, 11, 4, 3) \
        == nodes * (88 + 12) == 10_240_000
    assert 10_240_000 / nodes / 8 == 12.5          # bytes an update
    # a remainder step: 3 bands of 40 rows for 100 physical ones
    band = band_bytes.call_bytes(nodes, 3, 40, 8, 20, 11, 4, 3)
    assert band == 3 * (56 * 1024 * 56 + 40 * 1024 * 44) == 15_040_512
    assert resident_bytes.iterate_bytes(FULL, nodes, 11, 4) \
        == 124 * 10_240_000 + 7 * 15_040_512
    # no step left over: the band's shape is not looked at
    whole = dict(FULL, remainder_steps=0, bands=0)
    assert resident_bytes.iterate_bytes(whole, nodes, 11, 4) \
        == 124 * 10_240_000
    # the generic engine: one call of any length, bfloat16 storage, one
    # aux plane for the state and the band alike
    generic = dict(resident_calls=1, resident_steps=498, remainder_steps=2,
                   aux_planes=1, remainder_aux_planes=1, bands=8,
                   band_rows=64, halo_rows=8, pad_rows=0)
    assert resident_bytes.iterate_bytes(generic, 512 * 512, 10, 2) \
        == 512 * 512 * (40 + 4) + 2 * band_bytes.call_bytes(
            512 * 512, 8, 64, 8, 0, 10, 2, 1)


# -- the readers on synthetic spans ---------------------------------------- #

CELL = {"window": {"first_iteration": 1000, "last_iteration": 5000},
        "nodes": 102_400, "planes": 11, "itemsize": 4, "chips": 1,
        "device_kind": "TPU v5 lite", "engine": ENGINE, "fuse": 8,
        "traced_steps": 3000}


def spans_of(account: dict) -> list[dict]:
    """Six segments of 1000 steps, the first probed; four in the
    window."""
    out = []
    for k in range(6):
        at = {"kind": "span", "iteration": 1000 * k, "ts": 1.0 + k,
              "dur_s": 0.01}
        out.append(dict(at, name="iterate", iters=1000))
        out.append(dict(at, name="iterate.fused", iters=999,
                        **(account if k else {})))
    return out


def test_kernel_resident_roofline_by_hand():
    # 3000 traced steps are three segments' calls: a segment's 1000th
    # step is the XLA step, which moves none of these bytes
    rec = synthetic_recording(3, FULL)
    kernel_s = 3 * (124 * 80e-6 + 7 * 30e-6)
    moved = 3 * (124 * 10_240_000 + 7 * 15_040_512)
    assert kernel_resident_roofline.read(spans_of(FULL), rec, CELL) \
        == pytest.approx(100 * moved / 819e9 / kernel_s)
    # a program without the account (the parent), or no kernel traced
    assert kernel_resident_roofline.read(spans_of({}), rec, CELL) is None
    bare = trace.Recording(devices={"0": [["fusion.1_fusion", 0.0, 1.0]]},
                           host=[[trace.TRACED, 0.0, 2.0]])
    assert kernel_resident_roofline.read(spans_of(FULL), bare, CELL) is None
    # kernels a thousand times faster than HBM allows: the run fails
    with pytest.raises(AssertionError):
        kernel_resident_roofline.read(
            spans_of(FULL), synthetic_recording(3, FULL, 80e-9, 30e-9), CELL)


def test_kernel_remainder_share_by_hand():
    rec = synthetic_recording(3, FULL)
    assert kernel_remainder_share.read(spans_of(FULL), rec, CELL) \
        == pytest.approx(100 * 7 * 30 / (7 * 30 + 124 * 80))
    # the parent's spans say nothing
    assert kernel_remainder_share.read(spans_of({}), rec, CELL) is None
    # a trace whose operations are not what the account says (another
    # split, or names that do not hold the kernel's) reads nothing
    other = dict(FULL, resident_calls=120, remainder_steps=39)
    assert kernel_remainder_share.read(spans_of(other), rec, CELL) is None
    renamed = trace.Recording(
        devices={"0": [[n.replace("d2q9_resident_fuse8", "closed_call"),
                        s, d] for n, s, d in rec.devices["0"]]},
        host=rec.host)
    assert kernel_remainder_share.read(spans_of(FULL), renamed, CELL) is None
    # a call or two clipped at the traced span's ends is within the slack
    clipped = trace.Recording(devices={"0": rec.devices["0"][4:]},
                              host=rec.host)
    assert kernel_remainder_share.read(spans_of(FULL), clipped, CELL) \
        == pytest.approx(100 * 21 * 30 / (21 * 30 + 370 * 80))
    # no step left over: nothing else than the resident kernel ran
    whole = dict(FULL, remainder_steps=0, kernel_calls=124)
    assert kernel_remainder_share.read(
        spans_of(whole), synthetic_recording(3, whole), CELL) == 0.0


def test_the_obstacle_stays_in_the_channel_for_every_draw():
    """``why_ranges`` of resident.json, at the real size: the extremes
    and a few seeds; the diamond keeps its size and touches no wall."""
    traffic = casegen.load_json("traffic", "resident")
    template = os.path.join(os.path.dirname(tiny.DATA), "..", "cases",
                            "karman.xml")
    ranges = {r["var"]: r["int"] for r in traffic["seeded"] if "int" in r}
    assert ranges == {"ox": [-32, 32], "oy": [-8, 8]}

    def obstacle(root):
        painted = geometry.paint(root.find("Geometry"))
        wall = painted["wall"].copy()
        wall[0, :] = wall[-1, :] = False        # the channel's own walls
        rows, cols = wall.nonzero()
        return wall.sum(), rows.min(), rows.max(), cols.min(), cols.max()

    import xml.etree.ElementTree as ET
    plain = obstacle(ET.parse(template).getroot())
    assert plain[1:] == (30, 69, 120, 159)
    seen = set()
    for seed in range(40):
        root, drawn = casegen.generate(template, traffic, seed)
        seen.add((drawn["ox"], drawn["oy"]))
        n, r0, r1, c0, c1 = obstacle(root)
        assert n == plain[0]
        assert (r0, r1) == (30 + drawn["oy"], 69 + drawn["oy"])
        assert 22 <= r0 and r1 <= 78 and 88 <= c0 and c1 <= 192
        assert 0.0098 <= drawn["velocity"] <= 0.0102
    assert len(seen) > 30
    for ox in ranges["ox"]:
        for oy in ranges["oy"]:
            root = ET.parse(template).getroot()
            for w in root.findall("Geometry/Wall/Wedge"):
                w.set("dx", str(int(w.get("dx")) + ox))
                w.set("dy", str(int(w.get("dy")) + oy))
            n, r0, r1, c0, c1 = obstacle(root)
            assert n == plain[0] and 22 <= r0 and r1 <= 77
