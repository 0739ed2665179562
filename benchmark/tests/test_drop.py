"""What PR 28 added to the benchmark: the cells ``drop1024.relax`` and
``karman1024.logonly`` rehearsed on the CPU through run.py, the plain
reference of d2q9_kuper and its zones painter against the program in
float64 on the tiny case, ``band_bytes.py``'s counts by hand, and the
readers ``probe_s`` and ``kernel_dma_roofline`` on the kept recording of
``test_phases.py`` with the new spans and fields written into its
events."""

import json
import os
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from benchmark import band_bytes, casegen, check, trace
from benchmark.layer_metrics import kernel_dma_roofline, probe_s
from benchmark.reference import zones
from benchmark.tests import tiny
from benchmark.tests.test_reference import program_fields

DATA = tiny.DATA
# the new cells cut to sizes a CPU holds: entries for ``tiny.py``'s tables
# (the file stays as it is; the fixture below puts them in for a test)
SHAPE = [64, 128]
INTERVALS = {"relax": {500: 2, 1000: 4}, "logonly": {500: 2}}
# the drop's ranges are for 1024 nodes a side: an eighth of them here
RANGE = [-4, 4]


def tiny_relax() -> dict:
    traffic = casegen.load_json("traffic", "relax")
    for rule in traffic["seeded"]:
        rule["int"] = RANGE
    return traffic


@pytest.fixture
def tiny_run(monkeypatch):
    """run.py with the no-TPU refusal lifted and the cells cut to a tiny
    size; Pallas in interpret mode.  At 64 x 128 the drop would fit the
    resident engine: its budget is set to nothing, so that the band
    engine runs, as at 1024 x 1024."""
    import jax

    from benchmark import run
    from tclb_tpu.ops import pallas_generic
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    monkeypatch.setattr(pallas_generic, "_RESIDENT_BUDGET", 0)
    # a run is a process of its own: no verdict of an earlier probe
    monkeypatch.setattr(pallas_generic, "_cfg_cache", {})
    monkeypatch.setitem(tiny.SHAPES, "drop1024", SHAPE)
    for name, table in INTERVALS.items():
        monkeypatch.setitem(tiny.INTERVALS, name, table)
    shrunk = tiny.shrink(run.load_cell)

    def load_cell(name):
        cell, config, traffic = shrunk(name)
        if cell["traffic"] == "relax":
            traffic["seeded"] = tiny_relax()["seeded"]
        return cell, config, traffic

    monkeypatch.setattr(run, "load_cell", load_cell)
    monkeypatch.setattr(run, "template_path", tiny.template_path)
    monkeypatch.setattr(run, "find_chips", lambda chips: jax.devices())
    return run


def output_of(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("cell,engine,per", [
    ("drop1024.relax", "pallas_generic[d2q9_kuper,fuse=4]", 4),
    ("karman1024.logonly", "pallas_2d[d2q9,fuse=2]", 2)])
def test_rehearsal(tiny_run, capsys, cell, engine, per):
    rc = tiny_run.main(["--workload", cell, "--seed", "4294967311",
                        "--seconds", "0.3", "--trace", "0"])
    result, lines = output_of(capsys)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"mlups", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert f"engine: {engine}; fields" in "\n".join(lines)
    assert any("check: engine_fallback events = 0.0" in ln for ln in lines)
    planes = 10 if cell.startswith("drop") else 9
    assert any(f"fields ({planes + (0 if planes == 10 else 2)}, " in ln
               for ln in lines)
    with open(os.path.join(tiny_run.OUT, cell + ".seed4294967311.trace0."
                           "segments.json")) as f:
        rec = json.load(f)
    assert rec["summary"]["steps"] % per == 0
    kinds = {k for _, _, k in rec["segments"]}
    assert kinds == ({"Log", "Failcheck+Log"} if per == 4 else {"Log"})


def test_traced_rehearsal_reports_the_new_metrics(tiny_run, capsys,
                                                  monkeypatch):
    """The traced run of ``drop1024.relax``: the program's spans carry
    the band's account, both new readers are found by name and read.
    The CPU has no device plane, so the recording is the kept one."""
    from benchmark import bytes_model
    from benchmark.tests.test_trace import recording
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = tiny_run.main(["--workload", "drop1024.relax", "--seed", "9",
                        "--seconds", "1.0", "--trace", "1"])
    result, _ = output_of(capsys)
    assert rc == 0 and result["correct"] is True
    m = result["metrics"]
    assert {"probe_s", "kernel_dma_roofline", "kernel_hbm_roofline",
            "kernel_ns_per_update", "failcheck_ms", "device_idle_share",
            "engine_fallbacks", "compiles_in_window"} <= set(m)
    assert "globals_step_ms" not in m
    assert m["engine_fallbacks"]["value"] == 0.0
    assert 0 < m["probe_s"]["value"] < m["first_call_s"]["value"]
    events = trace.read_events(os.path.join(
        tiny_run.OUT, "drop1024.relax.seed9.trace1.events.jsonl"))
    fused = trace.spans(events, "iterate.fused")
    # a segment of 2 steps: one remainder call and the globals flavor
    assert {(e["kernel_calls"], e["remainder_steps"], e["band_rows"],
             e["halo_rows"], e["stages_per_step"], e["aux_planes"])
            for e in fused[1:]} == {(2, 2, 32, 8, 2, 1)}
    assert not trace.spans(events, "iterate.globals_step")


def test_reference_is_the_programs_semantics(tmp_path, monkeypatch):
    import jax
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    config = dict(casegen.load_json("configs", "drop1024"),
                  template="tiny_drop1024", dtype="float64")
    root, drawn = casegen.generate(tiny.template_path(config), tiny_relax(),
                                   2**31 + 12345)
    assert set(drawn) == {"ox", "oy", "d"}
    sphere = root.find("Geometry/None/Sphere")
    assert int(sphere.get("nx")) == int(sphere.get("ny")) == 36 + drawn["d"]
    assert int(sphere.get("dx")) == 44 + drawn["ox"]
    with jax.enable_x64(True):
        solver = program_fields(root, 50, config["model"], tmp_path)
        program = np.asarray(solver.lattice.state.fields)
        ref = check.reference_fields(config, root, 50)
        assert ref.dtype == np.float64 and ref.shape == program.shape
        assert ref.shape[0] == 10
        assert check.largest_difference(program, ref) < 1e-13
        m = solver.model
        flags = np.asarray(solver.lattice.state.flags)
        painted = zones.paint(root.find("Geometry"))
        assert ((flags >> m.zone_shift) == painted["zone"]).all()
        assert painted["collide"].all()
        assert (flags & m.node_types["MRT"].mask
                == m.node_types["MRT"].value).all()


def test_the_drop_stays_in_the_box_for_every_draw():
    """``why_ranges`` of relax.json, at the real size: the extremes."""
    traffic = casegen.load_json("traffic", "relax")
    template = ET.parse(os.path.join(
        os.path.dirname(DATA), "..", "cases", "drop1024.xml")).getroot()
    sphere = template.find("Geometry/None/Sphere")
    lo = {r["attr"]: r["int"][0] for r in traffic["seeded"]}
    hi = {r["attr"]: r["int"][1] for r in traffic["seeded"]}
    for axis in ("x", "y"):
        d, n = int(sphere.get("d" + axis)), int(sphere.get("n" + axis))
        assert d + lo["d" + axis] == 288
        assert d + hi["d" + axis] + n + hi["n" + axis] == 768
        assert n + lo["n" + axis] == 352
    for seed in (0, 7, 2**31 + 99):
        root, drawn = casegen.generate(os.path.join(
            os.path.dirname(DATA), "..", "cases", "drop1024.xml"),
            traffic, seed)
        painted = zones.paint(root.find("Geometry"))
        assert painted["zone"].sum() > 90_000     # a disc of 352 across


def test_band_bytes_by_hand():
    """by 32, halo 8, 10 planes of float32, one aux plane, 1024 x 1024."""
    assert band_bytes.band_read_bytes(1024, 32, 8, 10, 4, 1) \
        == 48 * 1024 * 44 == 2_162_688
    assert band_bytes.band_write_bytes(1024, 32, 10, 4) \
        == 32 * 1024 * 40 == 1_310_720
    call = band_bytes.call_bytes(1024 * 1024, 32, 32, 8, 0, 10, 4, 1)
    assert call == 32 * (2_162_688 + 1_310_720) == 111_149_056
    assert call / 1024 ** 2 == 106.0           # bytes a node and call
    assert call / 1024 ** 2 / 4 == 26.5        # an update, at fuse 4
    # the full aux stack of a model with one zonal setting: 4 B more a
    # node read; bfloat16 storage halves the field planes only
    assert band_bytes.call_bytes(1024 * 1024, 32, 32, 8, 0, 10, 4, 2) \
        - call == 48 * 1024 * 4 * 32
    assert band_bytes.band_read_bytes(1024, 32, 8, 10, 2, 1) \
        == 48 * 1024 * 24
    # ghost rows are moved too: 40 rows of bands for 36 physical ones
    assert band_bytes.call_bytes(36 * 128, 5, 8, 8, 4, 9, 4, 1) \
        == 5 * (24 * 128 * 40 + 8 * 128 * 36)
    with pytest.raises(ValueError):
        band_bytes.call_bytes(1000, 32, 32, 8, 0, 10, 4, 1)


# -- the readers on the kept recording of test_phases.py ------------------- #

CELL = {"window": {"first_iteration": 100, "last_iteration": 300},
        "nodes": 64 * 128, "planes": 10, "itemsize": 4, "chips": 2,
        "device_kind": "TPU v5 lite", "engine": "pallas_generic[x,fuse=4]",
        "traced_steps": 198}
ACCOUNT = dict(kernel_calls=26, bands=2, band_rows=32, halo_rows=8,
               pad_rows=0, aux_planes=1, stages_per_step=2,
               remainder_steps=3)


def kept():
    from benchmark.tests.test_phases import events, recording
    return events(), recording()


def with_the_new_spans(events):
    out = []
    for e in events:
        e = dict(e)
        if e.get("name") == "iterate.fused":
            e.update(ACCOUNT)
        out.append(e)
    out.append({"kind": "span", "ts": 60.5, "name": "engine.probe",
                "id": 3, "parent": 1, "t0": 51.0, "dur_s": 9.5,
                "iteration": 0, "engine": "pallas_generic[x,fuse=4]",
                "attempts": 1, "rungs": [32],
                "result": "pallas_generic[x,fuse=4]"})
    # a probe after the window opened is no part of set-up
    out.append({"kind": "span", "ts": 104.0, "name": "engine.probe",
                "id": 4, "parent": 11, "t0": 101.0, "dur_s": 3.0,
                "iteration": 100, "attempts": 1, "rungs": []})
    return out


def test_probe_s_reads_the_probes_before_the_window():
    events, rec = kept()
    assert probe_s.read(events, rec, CELL) is None      # an older program
    assert probe_s.read(with_the_new_spans(events), rec, CELL) \
        == pytest.approx(9.5)


def test_kernel_dma_roofline_by_hand():
    events, rec = kept()
    assert kernel_dma_roofline.read(events, rec, CELL) is None
    # 2 x 26 calls over 2 x 99 steps of the window, 198 traced steps: 52
    # calls of 2 x (48 x 44 + 32 x 40) x 128 B; 7.6 s of kernels on two
    # chips of 819 GB/s
    moved = 52 * 2 * (48 * 44 + 32 * 40) * 128
    share = kernel_dma_roofline.read(with_the_new_spans(events), rec, CELL)
    assert share == pytest.approx(100 * moved / (2 * 819e9) / 7.6)
    # no kernel in the trace: nothing to hold the bytes against
    bare = trace.Recording(devices={"0": [["fusion.1_fusion", 0.0, 1.0]]},
                           host=[[trace.TRACED, 0.0, 2.0]])
    assert kernel_dma_roofline.read(with_the_new_spans(events), bare,
                                    CELL) is None
    # bytes counted a millionfold: over 100 %, and the run fails
    many = [dict(e, kernel_calls=26_000_000_000_000)
            if e.get("name") == "iterate.fused" else e
            for e in with_the_new_spans(events)]
    with pytest.raises(AssertionError):
        kernel_dma_roofline.read(many, rec, CELL)


# -- and on a cut of a traced chip run of the cell itself ------------------ #


def chip_run():
    with open(os.path.join(DATA, "drop_recording.json")) as f:
        text = f.read()
    events = trace.read_events(os.path.join(DATA, "drop_events.jsonl"))
    return events, trace.Recording.from_json(text), json.loads(text)["about"]


def test_the_readers_on_the_cells_own_recording():
    """One ``iterate(500)`` of ``pallas_generic[d2q9_kuper,fuse=4]`` at
    1024 x 1024 as the chip ran it: 128 kernel calls, each moving 106 B
    a node, against the kernels' device time."""
    from benchmark.layer_metrics import (globals_step_ms,
                                         kernel_hbm_roofline,
                                         kernel_ns_per_update)
    events, rec, about = chip_run()
    cell = {"window": about["window"], "nodes": 1024 * 1024, "planes": 10,
            "itemsize": 4, "chips": 1, "device_kind": "TPU v5 lite",
            "engine": "pallas_generic[d2q9_kuper,fuse=4]", "fuse": 4,
            "traced_steps": about["traced_steps"]}
    t = trace.by_class(rec)
    assert t["calls"] == about["kernel_calls"] == 128
    fused = trace.spans(events, "iterate.fused")
    # the first call's account lies on the probe that made the calls
    assert [e.get("kernel_calls") for e in fused] == [None, 128, 128]
    dma = kernel_dma_roofline.read(events, rec, cell)
    assert dma == pytest.approx(
        100 * 128 * 111_149_056 / 819e9 / about["kernel_s"])
    least = kernel_hbm_roofline.read(events, rec, cell)
    assert dma / least == pytest.approx(128 * 106 / (500 * 20.5))
    assert 10 < least < dma < 20            # bound by neither
    assert kernel_ns_per_update.read(events, rec, cell) \
        == pytest.approx(1e9 * about["kernel_s"] / (500 * 1024 ** 2))
    # the generic engine has no trailing step: nothing to read
    assert globals_step_ms.read(events, rec, cell) is None
    probe = trace.spans(events, "engine.probe")[0]
    assert (probe["attempts"], probe["rungs"]) == (1, [32])
    assert probe["kernel_calls"] == 128 and probe["parent"] == fused[0]["id"]
    assert probe_s.read(events, rec, cell) == probe["dur_s"]
