"""What PR 46 added to the benchmark: the configuration
``karman1024probes`` and its cell ``karman1024probes.sampled`` rehearsed
on the CPU through run.py, untraced and traced (the tuned band's sampled
one-step flavour in interpret mode on a 64 x 128 stand-in), the plain
reference of the probes (``reference/probes.py``), the guard
(``probe_check.py``) passing on sound rows and refusing a missed step, a
wrong node, the bfloat16 control and a program that ran the sampled
segment off its fused engines, the reader ``sample_ms``, and the seeded
probes' extremes at the real size."""

import json
import os
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from benchmark import casegen, probe_check, trace
from benchmark.layer_metrics import kernel_hbm_roofline, sample_ms
from benchmark.reference import d2q9, geometry, probes
from benchmark.tests import tiny
from benchmark.tests.test_karman_resident import output_of

SHAPE = [64, 128]
INTERVALS = {500: 8}
ENGINE = "pallas_2d[d2q9,fuse=1]"
CELL = "karman1024probes.sampled"
# the account of one such segment, as the engine says it: 7 one-step
# calls (three paired trips and an odd call); the eighth step is the
# tail engine's
ACCOUNT = dict(kernel_calls=7, remainder_steps=0, paired_calls=6,
               aux_planes=3, bands=1, band_rows=64, halo_rows=8,
               pad_rows=0, sample_points=8, sample_rows=7,
               sample_bytes=7 * 11 * 8 * 4)


@pytest.fixture
def tiny_run(monkeypatch):
    """run.py with the no-TPU refusal lifted and the cell cut to a tiny
    size; Pallas in interpret mode."""
    import jax

    from benchmark import run
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    monkeypatch.setitem(tiny.SHAPES, "karman1024probes", SHAPE)
    monkeypatch.setitem(tiny.INTERVALS, "sampled", INTERVALS)
    shrunk = tiny.shrink(run.load_cell)

    def load_cell(name):
        cell, config, traffic = shrunk(name)
        for rule in traffic["seeded"]:      # an eighth of the length
            if "int" in rule:
                rule["int"] = [-4, 4]
        return cell, config, traffic

    monkeypatch.setattr(run, "load_cell", load_cell)
    monkeypatch.setattr(run, "template_path", tiny.template_path)
    monkeypatch.setattr(run, "find_chips", lambda chips: jax.devices())
    probe_check._DONE.clear()
    return run


def test_the_cell_and_its_configuration():
    """One configuration file, one template, one traffic file; the
    template is ``karman1024``'s plus the ``<Sample>`` block and the
    guard; every probe and the flush interval are listed as assumed."""
    from benchmark import run
    cell, config, traffic = run.load_cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "sampled")
    assert set(cell["end_to_end"]) == {"mlups", "setup_s"}
    assert config["reduced"] == ["ny", "Wedge"]
    assert config["shape"] == [1024, 1024]
    assert config["engine_family"] == "pallas_2d"
    assert 0 < config["sample_tolerance"] <= config["tolerance"]
    base = casegen.load_json("configs", "karman1024")
    for key in ("model", "reference", "dtype", "check_segments",
                "tolerance", "chips", "mesh"):
        assert config[key] == base[key]
    assert casegen.segment_steps(traffic) == 500
    assert traffic["handlers"] == [{"tag": "Log", "Iterations": 500}]
    for name in ("sample_ms", "kernel_ns_per_update", "kernel_wrap_share",
                 "globals_step_ms", "compile_s", "compiles_in_window",
                 "segment_host_ms", "log_ms", "dispatch_ms",
                 "idle_unnamed_share", "handlers_share", "engine_fallbacks"):
        assert name in cell["per_layer"]
    # the one-step kernel already runs under the HBM time bytes_model.py
    # asks of the state (PERF.md section 7): the share is not listed
    for name in ("kernel_hbm_roofline", "probe_s", "failcheck_ms",
                 "vtk_ms", "kernel_dma_roofline"):
        assert name not in cell["per_layer"]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mine = ET.parse(os.path.join(here, "cases", "karman1024probes.xml"))
    theirs = ET.parse(os.path.join(here, "cases", "karman1024.xml"))
    for tag in ("Geometry", "Model"):
        assert ET.tostring(mine.getroot().find(tag)).split() \
            == ET.tostring(theirs.getroot().find(tag)).split()
    extra = [el.tag for el in mine.getroot()
             if el.tag not in ("Geometry", "Model")]
    assert extra == ["Sample", "CallPython"]
    smp = mine.getroot().find("Sample")
    assert (smp.get("Iterations"), smp.get("what")) == ("500", "U,Rho")
    want = [(p["dx"], p["dy"]) for p in config["assumed"]["Point"]]
    assert [(int(p.get("dx")), int(p.get("dy")))
            for p in smp.findall("Point")] == want and len(want) == 8
    guard = mine.getroot().find("CallPython")
    assert guard.get("config") == config["name"]
    assert guard.get("Iterations") == smp.get("Iterations")
    # the shipped example is the same case with its own handlers
    shipped = ET.parse(os.path.join(os.path.dirname(here), "example",
                                    "karman_1024_probes.xml")).getroot()
    for tag in ("Geometry", "Model"):
        assert ET.tostring(shipped.find(tag)).split() \
            == ET.tostring(mine.getroot().find(tag)).split()
    ours = shipped.find("Sample")
    assert ours.attrib == smp.attrib
    assert [(int(p.get("dx")), int(p.get("dy")))
            for p in ours.findall("Point")] == want
    assert [el.tag for el in shipped][2:] == ["Sample", "Log", "VTK",
                                              "Solve"]


def test_seeded_probes_keep_their_place_and_stay_inside():
    """The same offset moves the obstacle and every probe; over all
    draws the probes lie at x 80 to 952, y 380 to 644, inside the box
    and never on the obstacle."""
    from benchmark import run
    _, config, traffic = run.load_cell(CELL)
    template = run.template_path(config)
    seen_x, seen_y = set(), set()
    for seed in (1, 7, 2147483653, 3999999979, 4600000101, 4600000102):
        root, drawn = casegen.generate(template, traffic, seed)
        pts = probes.points(root)
        base = [(p["dy"] + drawn["oy"], p["dx"] + drawn["ox"])
                for p in config["assumed"]["Point"]]
        assert pts == base
        wedge = root.find("Geometry/Wall/Wedge")
        assert int(wedge.get("dx")) == 120 + drawn["ox"]
        masks = geometry.paint(root.find("Geometry"))
        assert not any(masks["wall"][y, x] for y, x in pts)
        seen_x |= {x for _, x in pts}
        seen_y |= {y for y, _ in pts}
        assert 0.0098 <= drawn["velocity"] <= 0.0102
    assert 80 <= min(seen_x) and max(seen_x) <= 952
    assert 380 <= min(seen_y) and max(seen_y) <= 644
    assert len(casegen.draw(traffic, 5)) == 3     # ox, oy, velocity: once


def _tiny_case(seed=3):
    from benchmark import run
    _, _, traffic = run.load_cell(CELL)
    traffic = json.loads(json.dumps(traffic))
    for rule in traffic["seeded"]:
        if "int" in rule:
            rule["int"] = [-4, 4]
    for h in traffic["handlers"]:
        h["Iterations"] = 8
    root, _ = casegen.generate(
        os.path.join(tiny.DATA, "tiny_karman1024probes.xml"), traffic, seed)
    return root


def test_reference_probes_by_hand():
    """The probes against the populations of the plain d2q9 reference,
    step by step: Rho the sum, U the first moments over it, a point
    after a point in the CSV's column order."""
    root = _tiny_case()
    pts = probes.points(root)
    assert len(pts) == 8 and probes.quantities(root) == ["U", "Rho"]
    assert probes.columns(root)[:5] == ["U_0_x", "U_0_y", "U_0_z",
                                        "Rho_0", "U_1_x"]
    rows = probes.run(root, 12)
    assert rows.shape == (12, 32) and rows.dtype == np.float32
    for steps in (1, 12):
        f = d2q9.run(root, steps).astype(np.float64)
        for k, (y, x) in enumerate(pts):
            at = f[:, y, x]
            rho = at.sum()
            ux = (d2q9.E[:, 0] * at).sum() / rho
            uy = (d2q9.E[:, 1] * at).sum() / rho
            np.testing.assert_allclose(
                rows[steps - 1, 4 * k:4 * k + 4], [ux, uy, 0.0, rho],
                rtol=2e-6, atol=2e-9)
    # the control moves them by far more than any limit
    low = probes.run(root, 12, storage="bfloat16")
    assert np.abs(low - rows).max() > 1e-3


def test_rehearsal(tiny_run, capsys):
    rc = tiny_run.main(["--workload", CELL, "--seed", "4294967311",
                        "--seconds", "0.3", "--trace", "0"])
    result, lines = output_of(capsys)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"mlups", "setup_s"}
    text = "\n".join(lines)
    assert f"engine: {ENGINE}; fields (11, 64, 128)" in text
    assert "check: engine_fallback events = 0.0" in text
    assert f"check: engine {ENGINE} outside family pallas_2d = 0.0" in text
    # the guard ran once, on the first segment's eight rows
    said = [ln for ln in lines if "max |sample - reference|" in ln]
    assert len(said) == 1 and said[0].endswith("ok")
    assert "over 8 rows" in said[0]
    with open(os.path.join(tiny_run.OUT, CELL + ".seed4294967311."
                           "trace0.segments.json")) as f:
        rec = json.load(f)
    assert {k for _, _, k in rec["segments"]} == {"Log"}


def test_traced_rehearsal_reports_the_account(tiny_run, capsys,
                                              monkeypatch):
    """The traced run: ``iterate.fused`` carries the sampled engine's
    account and the sample fields, the flush its two children, and every
    reader the cell lists is found by name.  The CPU has no device
    plane, so the run reduces a kept recording."""
    from benchmark import bytes_model
    from benchmark.tests.test_trace import recording
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = tiny_run.main(["--workload", CELL, "--seed", "9",
                        "--seconds", "1.0", "--trace", "1"])
    result, _ = output_of(capsys)
    assert rc == 0 and result["correct"] is True
    m = result["metrics"]
    assert {"sample_ms", "kernel_ns_per_update", "compile_s",
            "compiles_in_window", "segment_host_ms", "log_ms",
            "dispatch_ms", "device_idle_share", "engine_fallbacks",
            "handlers_share", "first_call_s"} <= set(m)
    assert "kernel_hbm_roofline" not in m and "probe_s" not in m
    assert m["engine_fallbacks"]["value"] == 0.0
    assert m["compiles_in_window"]["value"] == 0.0
    assert 0 < m["sample_ms"]["value"] < 50
    events = trace.read_events(os.path.join(
        tiny_run.OUT, CELL + ".seed9.trace1.events.jsonl"))
    fused = trace.spans(events, "iterate.fused")
    assert {e["engine"] for e in fused} == {ENGINE}
    for span in fused:
        assert {k: span[k] for k in ACCOUNT} == ACCOUNT
    tails = trace.spans(events, "iterate.globals_step")
    assert {e["engine"] for e in tails} == {"pallas_generic[d2q9,fuse=1]"}
    handlers = [e for e in trace.spans(events, "handler")
                if e.get("handler") == "cbSample"]
    d2h = trace.spans(events, "sample.d2h")
    out = trace.spans(events, "output.sample")
    assert len(handlers) == len(d2h) >= len(fused) - 1
    # the copy and the block's write, both under the handler
    assert len(out) == len(d2h)
    by_id = {e["id"]: e for e in handlers}
    assert all(e["parent"] in by_id for e in d2h + out)
    assert {e["rows"] for e in out} == {8}
    # one copy a flush: the rows and their iteration numbers
    assert {e["bytes"] for e in d2h} == {8 * (32 + 1) * 4}
    counters = [e for e in events if e.get("kind") == "counters"]
    if counters:
        said = counters[-1]["counters"]
        assert said["sampler.rows"] == 8 * len(fused)
        assert said["output.sample.flushes"] == len(out)

    # the reader on the rehearsal's own events
    its = trace.spans(events, "iterate")
    first, last = its[3]["iteration"], its[-1]["iteration"] + 8
    cell = {"window": {"first_iteration": first, "last_iteration": last}}
    inside = [e["dur_s"] for e in handlers
              if first < e["iteration"] <= last]
    assert sample_ms.read(events, None, cell) \
        == pytest.approx(1e3 * sorted(inside)[len(inside) // 2], rel=0.5)
    assert sample_ms.read([], None, cell) is None
    # a program without the sampler's handler: nothing to read
    assert sample_ms.read([e for e in events
                           if e.get("handler") != "cbSample"],
                          None, cell) is None


def test_sample_ms_by_hand():
    spans = [{"kind": "span", "name": "handler", "handler": h,
              "iteration": it, "dur_s": d}
             for it, h, d in ((500, "cbSample", 9.0),       # before
                              (1000, "cbSample", 0.004),
                              (1000, "cbLog", 0.001),
                              (1500, "cbSample", 0.003),
                              (2000, "cbSample", 0.005),
                              (2500, "cbSample", 7.0))]      # after
    cell = {"window": {"first_iteration": 500, "last_iteration": 2000}}
    assert sample_ms.read(spans, None, cell) == pytest.approx(4.0)


def test_guard_refuses(tiny_run, capsys, monkeypatch):
    """The guard exits with no result, before the window, where the rows
    are not the reference's: here a limit the rounding cannot meet; and
    where the program ran the sampled segment off its fused engines (the
    parent of PR 46: the XLA scan, selected by nothing)."""
    real = casegen.load_json
    monkeypatch.setattr(
        casegen, "load_json",
        lambda kind, name: dict(real(kind, name), sample_tolerance=1e-12)
        if (kind, name) == ("configs", "karman1024probes")
        else real(kind, name))
    with pytest.raises(SystemExit, match="not the reference's"):
        tiny_run.main(["--workload", CELL, "--seed", "11", "--seconds",
                       "0.3", "--trace", "0"])
    assert "FAILED" in capsys.readouterr().out
    monkeypatch.setattr(casegen, "load_json", real)

    class Lattice:
        _fast_name = None

    class Solver:
        lattice = Lattice()
        iter = 8            # the guard looks at its first call
        conf_name = "case"

        def __init__(self, out):
            self.output_prefix = str(out) + os.sep

    probe_check._DONE.clear()
    import tempfile
    with tempfile.TemporaryDirectory() as out:
        ET.ElementTree(_tiny_case()).write(os.path.join(out, "case.xml"))
        with pytest.raises(SystemExit, match="engine 'xla', not on one "
                                             "of family pallas_2d"):
            probe_check.rows_match_reference(Solver(out))
        # later calls return at once
        assert probe_check.rows_match_reference(Solver(out)) == 0


def test_hbm_share_is_not_listed_for_the_cell():
    """``kernel_hbm_roofline`` reckons 90 B a node and call: at the 98.4
    us the one-step kernel takes (ledger, PR 45) the share is 117 %, and
    the reader raises over 100; so the cell does not list it."""
    from benchmark import bytes_model
    bench = json.load(open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "BENCHMARK.json")))
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert CELL not in listed["kernel_hbm_roofline"]
    assert listed["sample_ms"] == [CELL]
    assert bytes_model.bytes_per_update(11, 4, 1) == 90.0
    least_s = 90.0 * 1024 * 1024 / 819e9
    assert 100 * least_s / 98.4e-6 > 105
    assert callable(kernel_hbm_roofline.read)
