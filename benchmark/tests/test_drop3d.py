"""What PR 34 added to the benchmark: the cells ``drop3d256.settle`` and
``channel3d512.fields`` rehearsed on the CPU through run.py (the first
with its plane tiled in y, as at 256^3), the plain reference of
d3q19_kuper and its 3D zones painter against the program in float64 on
the tiny case, the drop's extent for the extreme draws,
``tile_bytes.py``'s counts for the generic engine's plan by hand, and
``kernel_tile_roofline`` on a cut of a traced chip run of the new cell."""

import json
import os
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from benchmark import bytes_model, casegen, check, tile_bytes, trace
from benchmark.layer_metrics import kernel_tile_roofline
from benchmark.reference import zones3d
from benchmark.tests import tiny
from benchmark.tests.test_reference import program_fields

DATA = tiny.DATA
CASES = os.path.join(os.path.dirname(DATA), "..", "cases")
# the new cells cut to sizes a CPU holds: entries for ``tiny.py``'s tables
# (the file stays as it is; the fixture below puts them in for a test)
SHAPES = {"drop3d256": [8, 32, 128]}
INTERVALS = {"settle": {250: 2, 500: 4}, "fields": {500: 2, 1000: 4}}
# the drop's ranges are for 256 nodes a side: one node either way here
RANGE = [-1, 1]
# VMEM a tiled window may count on in the rehearsal: an 8 x 32 x 128 box
# is then cut into 4 x 4 windows of 2 slabs x 8 rows, the wrap in both
SMALL_VMEM = 7_000_000


def tiny_settle() -> dict:
    traffic = casegen.load_json("traffic", "settle")
    for rule in traffic["seeded"]:
        rule["int"] = RANGE
    return traffic


@pytest.fixture
def tiny_run(monkeypatch):
    """run.py with the no-TPU refusal lifted and the cells cut to a tiny
    size; Pallas in interpret mode.  At 8 x 32 x 128 a whole-plane plan
    would take the drop: it is refused, so that the planner tiles the
    plane, as at 256^3."""
    import jax

    from benchmark import run
    from tclb_tpu.ops import pallas_generic
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    plan = pallas_generic.tile_plan_3d
    monkeypatch.setattr(pallas_generic, "_whole_plane_3d",
                        lambda *a, **k: False)
    monkeypatch.setattr(
        pallas_generic, "tile_plan_3d",
        lambda model, shape, itemsize=4, fuse=None, cap=None, budget=None:
        plan(model, shape, itemsize, fuse, cap, SMALL_VMEM))
    # a run is a process of its own: no verdict of an earlier probe
    monkeypatch.setattr(pallas_generic, "_cfg_cache", {})
    for name, shape in SHAPES.items():
        monkeypatch.setitem(tiny.SHAPES, name, shape)
    for name, table in INTERVALS.items():
        monkeypatch.setitem(tiny.INTERVALS, name, table)
    shrunk = tiny.shrink(run.load_cell)

    def load_cell(name):
        cell, config, traffic = shrunk(name)
        if cell["traffic"] == "settle":
            traffic["seeded"] = tiny_settle()["seeded"]
        return cell, config, traffic

    monkeypatch.setattr(run, "load_cell", load_cell)
    monkeypatch.setattr(run, "template_path", tiny.template_path)
    monkeypatch.setattr(run, "find_chips", lambda chips: jax.devices())
    return run


def output_of(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("cell,engine,kinds", [
    ("drop3d256.settle", "pallas_generic[d3q19_kuper,fuse=1,by=8]",
     {"Log", "Failcheck+Log"}),
    ("channel3d512.fields", "pallas_d3q[d3q27_cumulant,fuse=8]",
     {"Failcheck+Log", "Failcheck+Log+VTK"})])
def test_rehearsal(tiny_run, capsys, cell, engine, kinds):
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
    assert rec["summary"]["steps"] % 4 == 0
    assert {k for _, _, k in rec["segments"]} == kinds
    if cell.endswith("fields"):
        # the file the cell writes is the case's: U and Rho, uncompressed
        assert any("output: newest VTK" in ln
                   and "finite, of the case size" in ln for ln in lines)


def test_traced_rehearsal_reports_the_engines_account(tiny_run, capsys,
                                                      monkeypatch):
    """The traced run of ``drop3d256.settle``: the tiled generic engine's
    account lies on ``iterate.fused``, the readers the cell lists are
    found by name and read.  The CPU has no device plane, so the
    recording is the kept one."""
    from benchmark.tests.test_trace import recording
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = tiny_run.main(["--workload", "drop3d256.settle", "--seed", "9",
                        "--seconds", "1.0", "--trace", "1"])
    result, _ = output_of(capsys)
    assert rc == 0 and result["correct"] is True
    m = result["metrics"]
    assert {"kernel_tile_roofline", "kernel_hbm_roofline",
            "kernel_ns_per_update", "failcheck_ms", "probe_s", "compile_s",
            "engine_fallbacks", "compiles_in_window"} <= set(m)
    assert "globals_step_ms" not in m and "kernel_dma_roofline" not in m
    assert m["engine_fallbacks"]["value"] == 0.0
    assert 0 < m["probe_s"]["value"] < m["first_call_s"]["value"]
    events = trace.read_events(os.path.join(
        tiny_run.OUT, "drop3d256.settle.seed9.trace1.events.jsonl"))
    fused = trace.spans(events, "iterate.fused")
    # a segment of 2 steps: two calls of the one-step plan on 4 x 4
    # windows of 2 slabs x 8 rows, 2 halo slabs, 8 halo rows; the first
    # call's account lies on its probe
    assert {tuple(e[k] for k in kernel_tile_roofline.FIELDS)
            + (e["stages_per_step"], e["paired_calls"])
            for e in fused[1:]} == {(2, 0, 4, 2, 2, 4, 8, 8, 1, 2, 0)}
    probe = trace.spans(events, "engine.probe")[0]
    assert (probe["attempts"], probe["rungs"], probe["band_rows"]) \
        == (1, [8], 8)
    assert not trace.spans(events, "iterate.globals_step")


def test_reference_is_the_programs_semantics(tmp_path, monkeypatch):
    import jax
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    config = dict(casegen.load_json("configs", "drop3d256"),
                  template="tiny_drop3d256", dtype="float64")
    root, drawn = casegen.generate(tiny.template_path(config), tiny_settle(),
                                   2**31 + 12345)
    assert set(drawn) == {"ox", "oy", "oz", "d"}
    sphere = root.find("Geometry/None/Sphere")
    assert int(sphere.get("nx")) == 24 + drawn["d"]
    assert int(sphere.get("nz")) == 4 + drawn["d"]
    assert int(sphere.get("dz")) == 2 + drawn["oz"]
    with jax.enable_x64(True):
        solver = program_fields(root, 50, config["model"], tmp_path)
        program = np.asarray(solver.lattice.state.fields)
        ref = check.reference_fields(config, root, 50)
        assert ref.dtype == np.float64 and ref.shape == program.shape
        assert ref.shape == (20, 8, 32, 128)
        assert check.largest_difference(program, ref) < 1e-13
        # the drop is there and has moved: not the Init field
        start = check.reference_fields(config, root, 0)
        assert np.abs(ref - start).max() > 1e-3
        # mass is conserved
        assert ref[:19].sum() == pytest.approx(start[:19].sum(), rel=1e-12)
        # the painter agrees with the program's, node for node
        m = solver.model
        flags = np.asarray(solver.lattice.state.flags)
        painted = zones3d.paint(root.find("Geometry"))
        assert ((flags >> m.zone_shift) == painted["zone"]).all()
        assert 0 < (painted["zone"] == 1).sum() < painted["zone"].size // 8
        assert painted["collide"].all()
        assert (flags & m.node_types["MRT"].mask
                == m.node_types["MRT"].value).all()


def test_the_painter_refuses_what_it_does_not_know():
    geom = ET.fromstring(
        '<Geometry nx="16" ny="8" nz="4"><MRT><Box/></MRT>'
        '<None name="a"><Sphere dx="0" nx="16" dy="0" ny="8" dz="0" '
        'nz="4"/></None></Geometry>')
    zone = zones3d.paint(geom)["zone"]
    assert zone.shape == (4, 8, 16)
    # the inscribed ellipsoid: the centre is in, the corners are out
    assert zone[2, 4, 8] == 1 and zone[0, 0, 0] == 0 == zone[3, 7, 15]
    for bad in ('<Wall mask="ALL"><Box/></Wall>',
                '<None name="a"><Sphere dx="0" nx="4" dy="0" ny="4"/></None>',
                '<None name="a"><Sphere dx="14" nx="4" dy="0" ny="4" dz="0" '
                'nz="4"/></None>'):
        with pytest.raises(ValueError):
            zones3d.paint(ET.fromstring(
                f'<Geometry nx="16" ny="8" nz="4">{bad}</Geometry>'))


def test_the_control_fails_the_tiny_check():
    """bfloat16 storage in the reference's place: far outside the limit
    the configuration states, as at 256^3 on the chip."""
    from benchmark import control
    config = dict(casegen.load_json("configs", "drop3d256"),
                  template="tiny_drop3d256")
    root, _ = casegen.generate(tiny.template_path(config), tiny_settle(), 3)
    # the drop is 24 x 12 x 4 nodes here and most of the box is vapour
    # (populations of 5e-3): 1.0e-2 after 100 steps, where float32 against
    # float64 reads 3.6e-6
    assert control.control_difference(config, root, 100) \
        > 5 * config["tolerance"]


def test_the_drop_stays_in_the_box_for_every_draw():
    """``why_ranges`` of settle.json, at the real size: the extremes."""
    traffic = casegen.load_json("traffic", "settle")
    path = os.path.join(CASES, "drop3d256.xml")
    sphere = ET.parse(path).getroot().find("Geometry/None/Sphere")
    lo = {r["attr"]: r["int"][0] for r in traffic["seeded"]}
    hi = {r["attr"]: r["int"][1] for r in traffic["seeded"]}
    for axis in ("x", "y", "z"):
        d, n = int(sphere.get("d" + axis)), int(sphere.get("n" + axis))
        assert d + lo["d" + axis] == 64
        assert d + hi["d" + axis] + n + hi["n" + axis] == 208
        assert (n + lo["n" + axis], n + hi["n" + axis]) == (80, 112)
    root, drawn = casegen.generate(path, traffic, 2**31 + 99)
    s = root.find("Geometry/None/Sphere")
    assert s.get("nx") == s.get("ny") == s.get("nz") == str(96 + drawn["d"])
    assert casegen.segment_steps(traffic) == 250


def test_tile_bytes_of_the_generic_plan_by_hand():
    """20 planes of float32 and one float32 flag plane, 256^3."""
    # windows of 8 slabs x 32 rows, 2 halo slabs, 8 halo rows: each
    # reads 12 x 48 rows of 84 B a node and writes 8 x 32 of 80 B
    call = tile_bytes.call_bytes(256 ** 3, 32, 8, 2, 8, 32, 8, 20, 4, 1)
    assert call == 32 * 8 * 256 * (12 * 48 * 84 + 8 * 32 * 80)
    assert call / 256 ** 3 == 269.0          # bytes an update, at fuse 1
    assert bytes_model.round_trip_bytes(20, 4) == 162
    # the planner's first choice, 4 slabs x 64 rows: 290 B an update;
    # the issue's window of 4 slabs x 32 rows: 332
    assert tile_bytes.call_bytes(256 ** 3, 64, 4, 2, 4, 64, 8, 20, 4, 1) \
        / 256 ** 3 == 290.0
    assert tile_bytes.call_bytes(256 ** 3, 64, 4, 2, 8, 32, 8, 20, 4, 1) \
        / 256 ** 3 == 332.0
    # the series flavour's aux stack (flags, Density, its d/dt): 8 B more
    assert tile_bytes.window_read_bytes(256, 8, 2, 32, 8, 20, 4, 3) \
        - tile_bytes.window_read_bytes(256, 8, 2, 32, 8, 20, 4, 1) \
        == 12 * 48 * 256 * 8


def test_the_guard_refuses_a_program_without_the_engine(monkeypatch):
    """``benchmark/require.py``: a program whose dispatch lists no
    ``pallas_generic`` engine for the case (the parent of PR 34) stops at
    set-up; one told to stay off its fast paths is not asked."""
    from benchmark import require

    class Candidate:
        def __init__(self, tag):
            self.tag = tag

    class Lattice:
        def __init__(self, chain):
            self._build_fast = lambda: chain

    class Solver:
        def __init__(self, chain):
            self.lattice = Lattice(chain)

    monkeypatch.setenv("TCLB_FASTPATH", "force")
    ours = [Candidate("pallas_generic[d3q19_kuper,fuse=1,by=32]")]
    assert require.pallas_generic_engine(Solver(ours)) == 0
    for chain in ([], [Candidate("pallas_d3q[d3q19,fuse=3]")]):
        with pytest.raises(SystemExit, match="pallas_generic"):
            require.pallas_generic_engine(Solver(chain))
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    assert require.pallas_generic_engine(Solver([])) == 0
    # a program older than the dispatch chain cannot say: not refused
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    solver = Solver([])
    del solver.lattice._build_fast
    assert require.pallas_generic_engine(solver) == 0


# -- the readers on a cut of a traced chip run of the cell itself ---------- #


def chip_run():
    with open(os.path.join(DATA, "drop3d_recording.json")) as f:
        text = f.read()
    events = trace.read_events(os.path.join(DATA, "drop3d_events.jsonl"))
    return events, trace.Recording.from_json(text), json.loads(text)["about"]


def test_the_readers_on_the_cells_own_recording():
    """The traced period of ``drop3d256.settle`` as the chip ran it: two
    ``iterate(250)`` of the tiled generic slab kernel at 256^3, each call
    moving ``tile_bytes.call_bytes`` of its plan, against the kernels'
    device time."""
    from benchmark.layer_metrics import (globals_step_ms,
                                         kernel_hbm_roofline,
                                         kernel_ns_per_update, probe_s)
    events, rec, about = chip_run()
    fuse = bytes_model.fuse_of(about["engine"])
    assert about["engine"].startswith("pallas_generic[d3q19_kuper,fuse=")
    cell = {"window": about["window"], "nodes": 256 ** 3, "planes": 20,
            "itemsize": 4, "chips": 1, "device_kind": "TPU v5 lite",
            "engine": about["engine"], "fuse": fuse,
            "traced_steps": about["traced_steps"]}
    t = trace.by_class(rec)
    assert t["calls"] == about["kernel_calls"]
    assert t["kernel"] == pytest.approx(about["kernel_s"])
    fused = [e for e in trace.spans(events, "iterate.fused")
             if "z_bands" in e]
    plans = {tuple(e[k] for k in kernel_tile_roofline.FIELDS[1:])
             for e in fused}
    assert len(plans) == 1
    rest, zb, bz, hz, yb, by, hy, aux = plans.pop()
    assert (rest, aux, hy) == (0, 1, 8) and zb * bz == yb * by == 256
    assert hz == 2 * fuse                # reach 2 a repetition
    assert {e["stages_per_step"] for e in fused} == {2}
    per_call = tile_bytes.call_bytes(256 ** 3, zb, bz, hz, yb, by, hy,
                                     20, 4, 1)
    calls = about["traced_steps"] // fuse
    assert calls == about["kernel_calls"]
    tile = kernel_tile_roofline.read(events, rec, cell)
    assert tile == pytest.approx(
        100 * calls * per_call / 819e9 / about["kernel_s"])
    least = kernel_hbm_roofline.read(events, rec, cell)
    assert tile / least == pytest.approx(per_call / 256 ** 3 / fuse / 162)
    assert 0 < least < tile < 100
    assert kernel_ns_per_update.read(events, rec, cell) == pytest.approx(
        1e9 * about["kernel_s"] / (about["traced_steps"] * 256 ** 3))
    # in-kernel: no trailing step to read
    assert globals_step_ms.read(events, rec, cell) is None
    probe = trace.spans(events, "engine.probe")[0]
    assert (probe["attempts"], probe["rungs"]) == (1, [by])
    assert probe_s.read(events, rec, cell) == probe["dur_s"]
