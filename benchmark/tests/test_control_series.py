"""What PR 55 added to the benchmark: the configuration
``karman1024control`` and its cell ``karman1024control.logonly``
rehearsed on the CPU through run.py, untraced and traced (the tuned
band's series flavour in interpret mode on a 64 x 128 stand-in), the
plain reference under a series (``reference/d2q9_control.py``) against
the program's XLA step in float64 over a wrap, the three controls of the
limit (bfloat16 storage, the series frozen, the series one step late)
failing where a sound run passes, the template under ``logonly.json``,
the guard (``require_tuned.py``) and the reader
``series_bytes_per_step``."""

import json
import os
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from benchmark import casegen, check, control, require_tuned, \
    series_control, trace
from benchmark.layer_metrics import series_bytes_per_step
from benchmark.reference import d2q9_control, geometry
from benchmark.tests import tiny
from benchmark.tests.test_karman_resident import output_of

SHAPE = [64, 128]
INTERVALS = {500: 8}
ENGINE = "pallas_2d[d2q9,fuse=2]"
CELL = "karman1024control.logonly"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the account of one such segment, as the engine says it: seven engine
# steps, three two-step calls (two of them a loop's pair) and one
# one-step call; the eighth step is the tail engine's
ACCOUNT = dict(kernel_calls=4, remainder_steps=0, paired_calls=0,
               aux_planes=3, bands=2, band_rows=32, halo_rows=8,
               pad_rows=0, series_rows=1, series_horizon=32,
               series_bytes_per_step=0)


@pytest.fixture
def tiny_run(monkeypatch):
    """run.py with the no-TPU refusal lifted and the cell cut to a tiny
    size; Pallas in interpret mode; started at the repository's root,
    where the template's CSV path resolves."""
    import jax

    from benchmark import run
    monkeypatch.chdir(ROOT)
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    monkeypatch.setitem(tiny.SHAPES, "karman1024control", SHAPE)
    monkeypatch.setitem(tiny.INTERVALS, "logonly", INTERVALS)
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
    return run


def _tiny_case(seed=3) -> ET.Element:
    from benchmark import run
    _, _, traffic = run.load_cell(CELL)
    traffic = json.loads(json.dumps(traffic))
    for rule in traffic["seeded"]:
        if "int" in rule:
            rule["int"] = [-4, 4]
    for h in traffic["handlers"]:
        h["Iterations"] = 8
    root, _ = casegen.generate(
        os.path.join(tiny.DATA, "tiny_karman1024control.xml"), traffic, seed)
    return root


def test_the_cell_and_its_configuration():
    """One configuration file, one template, ``logonly.json`` as it is;
    the template is ``karman1024``'s plus the ``<Control>`` block and
    the guard; the waveform is the file's and is what the configuration
    says: 80 rows, from 0.01, within 0.006 .. 0.016, a flank of 2e-4 a
    step that ends at step 1000, no step at the wrap."""
    from benchmark import run
    cell, config, traffic = run.load_cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "logonly")
    assert set(cell["end_to_end"]) == {"mlups", "setup_s"}
    assert config["reduced"] == ["ny", "Wedge", "period"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert config["shape"] == [1024, 1024]
    assert (config["engine_family"], config["reference"],
            config["check_segments"]) == ("pallas_2d", "d2q9_control", 2)
    base = casegen.load_json("configs", "karman1024")
    for key in ("model", "dtype", "chips", "mesh"):
        assert config[key] == base[key]
    assert 0 < config["tolerance"] < base["tolerance"]
    assert traffic == casegen.load_json("traffic", "logonly")
    assert casegen.segment_steps(traffic) == 500
    twin = run.load_cell("karman1024.logonly")[0]
    assert set(cell["per_layer"]) \
        == set(twin["per_layer"]) | {"series_bytes_per_step"}
    assert "kernel_hbm_roofline" in cell["per_layer"]
    mine = ET.parse(os.path.join(ROOT, "benchmark", "cases",
                                 "karman1024control.xml")).getroot()
    theirs = ET.parse(os.path.join(ROOT, "benchmark", "cases",
                                   "karman1024.xml")).getroot()
    for tag in ("Geometry", "Model"):
        assert ET.tostring(mine.find(tag)).split() \
            == ET.tostring(theirs.find(tag)).split()
    assert [el.tag for el in mine] == ["Geometry", "Model", "Control",
                                       "CallPython"]
    shipped = ET.parse(os.path.join(ROOT, "example",
                                    "karman_1024_control.xml")).getroot()
    assert [el.tag for el in shipped] == ["Geometry", "Model", "Control",
                                          "Log", "Solve"]
    for tag in ("Geometry", "Model"):
        assert ET.tostring(shipped.find(tag)).split() \
            == ET.tostring(mine.find(tag)).split()
    with open(os.path.join(ROOT, "example", "karman_1024_control.csv")) as a, \
            open(os.path.join(ROOT, "benchmark", "cases",
                              "karman1024control.csv")) as b:
        assert a.read() == b.read()
    wave = d2q9_control.series(mine)
    assert wave.shape == (4000,) and wave[0] == 0.01
    assert 0.006 <= wave.min() and wave.max() <= 0.016
    steps = np.diff(np.concatenate([wave, wave[:1]]))    # the wrap's too
    assert np.isclose(steps.max(), 2e-4) and steps.min() > -2e-5
    assert np.allclose(steps[950:1000], 2e-4)
    assert wave[1000] == 0.016 and wave[950] == 0.006


def test_template_generates_under_logonly():
    """Two seeds: the obstacle and the initial field's velocity move,
    the ``<Control>`` block and the guard stay as the file has them, and
    the handlers come after them."""
    from benchmark import run
    _, config, traffic = run.load_cell(CELL)
    seen = set()
    for seed in (5500000201, 5500000202):
        root, drawn = casegen.generate(run.template_path(config), traffic,
                                       seed)
        assert [el.tag for el in root] == [
            "Geometry", "Model", "Control", "CallPython", "Log",
            "CallPython", "Solve"]
        assert int(root.find("Geometry/Wall/Wedge").get("dx")) \
            == 120 + drawn["ox"]
        assert 0.0098 <= drawn["velocity"] <= 0.0102
        assert geometry.params(root)["Velocity"] == drawn["velocity"]
        wave = d2q9_control.series(root)
        assert wave[0] == 0.01 and len(wave) == 4000
        seen.add((drawn["ox"], drawn["oy"]))
    assert len(seen) == 2


def test_reference_is_the_xla_step_in_float64_over_a_wrap(monkeypatch,
                                                          tmp_path):
    """32 x 128, float64, 40 steps of a horizon of 32: the plain
    reference and the program's XLA step, given the same case through
    the program's own ``<Control>`` handler (its interpolation, its
    ``series_overrides``), agree to rounding; one step late they do
    not."""
    import jax
    import jax.numpy as jnp

    from benchmark.tests.test_reference import program_fields
    monkeypatch.chdir(ROOT)
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    root = _tiny_case()
    root.find("Geometry").set("ny", "32")
    for w in root.findall("Geometry/Wall/Wedge"):
        w.set("dy", str(int(w.get("dy")) // 2))
        w.set("ny", "4")
    with jax.enable_x64(True):
        solver = program_fields(root, 40, "d2q9", tmp_path)
        got = np.asarray(solver.lattice.state.fields)[:9]
        assert got.dtype == np.float64
        assert int(solver.lattice.state.iteration) == 40
        assert solver.lattice.params.time_series.shape == (1, 32)
        ref = d2q9_control.run(root, 40, jnp.float64)
        late = d2q9_control.run(root, 40, jnp.float64, lag=1)
    assert np.abs(got - ref).max() < 1e-13
    assert np.abs(got - late).max() > 1e-4


def test_controls_fail_where_a_sound_run_passes():
    """At 64 x 128 over the tiny case's 16 checked steps: the bfloat16
    control, the series frozen at its first value and the series one
    step late each differ from the reference by far more than the
    configuration's limit, as a sound float32 run does not."""
    import jax.numpy as jnp
    config = casegen.load_json("configs", "karman1024control")
    root = _tiny_case()
    assert control.control_difference(config, root, 16) \
        > 100 * config["tolerance"]
    said = series_control.control_differences(config, root, 16)
    assert set(said) == {"frozen", "late"}
    assert said["frozen"] > 100 * config["tolerance"]
    assert said["late"] > 10 * config["tolerance"]
    sound = check.reference_fields(config, root, 16)
    again = d2q9_control.run(root, 16, jnp.float32, lag=0)
    assert check.largest_difference(again, sound) == 0.0


def test_reference_refuses_what_it_does_not_read():
    root = _tiny_case()
    par = root.find("Control/Params")
    par.set("Velocity-Inlet", "vel*2")
    assert np.allclose(d2q9_control.series(root)[0], 0.02)
    for expr in ("vel+0.01", "vel*x", "other"):
        par.set("Velocity-Inlet", expr)
        with pytest.raises(ValueError):
            d2q9_control.series(root)
    par.set("Velocity-Inlet", "vel")
    ET.SubElement(root.find("Control"), "Params", {"Density-Outlet": "vel"})
    with pytest.raises(ValueError, match="unsupported <Control>"):
        d2q9_control.series(root)


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
    assert "after 16 steps" in text


def test_traced_rehearsal_reports_the_series(tiny_run, capsys, monkeypatch):
    """The traced run: ``iterate.fused`` carries the band engine's
    account with the series' three fields, the tail's span the generic
    flavour's, ``startup.element`` the table, and every reader the cell
    lists is found by name.  The CPU has no device plane, so the run
    reduces a kept recording."""
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
    assert {"series_bytes_per_step", "kernel_ns_per_update", "compile_s",
            "compiles_in_window", "segment_host_ms", "log_ms",
            "dispatch_ms", "engine_fallbacks", "handlers_share",
            "first_call_s"} <= set(m)
    assert m["series_bytes_per_step"] == {"value": 0.0, "unit": "B"}
    assert m["engine_fallbacks"]["value"] == 0.0
    assert m["compiles_in_window"]["value"] == 0.0
    events = trace.read_events(os.path.join(
        tiny_run.OUT, CELL + ".seed9.trace1.events.jsonl"))
    fused = trace.spans(events, "iterate.fused")
    assert {e["engine"] for e in fused} == {ENGINE}
    for span in fused[1:]:
        assert {k: span[k] for k in ACCOUNT} == ACCOUNT
    tails = trace.spans(events, "iterate.globals_step")
    assert {e["engine"] for e in tails} == {"pallas_generic[d2q9,fuse=1]"}
    assert {e["series_bytes_per_step"] for e in tails[1:]} \
        == {4 * 64 * 128 * 4}
    element, = [e for e in trace.spans(events, "startup.element")
                if e.get("element") == "Control"]
    assert (element["series"], element["horizon"], element["bytes"]) \
        == (1, 32, 32 * 4)
    assert not [e for e in events if e.get("kind") == "fused_rejected"]
    # the reader: the median over the window's spans; nothing where no
    # span says it (the parent, a case without a series)
    its = trace.spans(events, "iterate")
    cell = {"window": {"first_iteration": its[3]["iteration"],
                       "last_iteration": its[-1]["iteration"] + 8}}
    assert series_bytes_per_step.read(events, None, cell) == 0
    silent = [{k: v for k, v in e.items() if k != "series_bytes_per_step"}
              for e in events]
    assert series_bytes_per_step.read(silent, None, cell) is None
    assert series_bytes_per_step.read([], None, cell) is None


def test_guard_refuses_a_chain_without_the_tuned_band(monkeypatch):
    """The parent of PR 55 lists the generic band first under a series:
    the guard ends the run with no result; a chain that starts with
    ``pallas_2d[`` passes, and a program told to stay off its fast paths
    is not asked."""
    class Candidate:
        def __init__(self, tag):
            self.tag = tag

    class Lattice:
        def __init__(self, tags):
            self._build_fast = lambda: [Candidate(t) for t in tags]

    class Solver:
        def __init__(self, tags):
            self.lattice = Lattice(tags)

    monkeypatch.setenv("TCLB_FASTPATH", "force")
    for tags in (["pallas_generic[d2q9,fuse=1]",
                  "pallas_generic[d2q9,fuse=1,by<=16]"], []):
        with pytest.raises(SystemExit, match="not one of family pallas_2d"
                                             ".*no result"):
            require_tuned.pallas_2d_engine(Solver(tags))
    assert require_tuned.pallas_2d_engine(
        Solver(["pallas_2d[d2q9,fuse=2]"])) == 0
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    assert require_tuned.pallas_2d_engine(
        Solver(["pallas_generic[d2q9,fuse=1]"])) == 0
