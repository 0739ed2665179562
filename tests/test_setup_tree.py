"""The span tree from the process's start to the first segment: the
``boot`` record and its backlog, the ``startup.*`` spans of ``tclb run``,
``engine.build``, and the cache verdict on every ``compile`` event; a
tiny case through ``main(["run", ...])`` in this process, and twice
through a process of its own against one cache directory."""

import json
import os
import subprocess
import sys
import time

import pytest

from tclb_tpu import compile_cache, telemetry
from tclb_tpu.__main__ import main
from tclb_tpu.telemetry import events, report
from tclb_tpu.telemetry.spans import NOOP_SPAN

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASE = """<?xml version="1.0"?>
<CLBConfig version="2.0" model="d2q9" output="output/">
    <Geometry nx="64" ny="32">
        <MRT><Box/></MRT>
        <WVelocity name="Inlet"><Inlet/></WVelocity>
        <EPressure name="Outlet"><Outlet/></EPressure>
        <Inlet nx='1' dx='2'><Box/></Inlet>
        <Outlet nx='1' dx='-2'><Box/></Outlet>
        <Wall mask="ALL"><Channel/></Wall>
    </Geometry>
    <Model>
        <Params Velocity="0.01"/>
        <Params nu="0.02"/>
    </Model>
    <Solve Iterations="20">
        <Log Iterations="10"/>
        <Failcheck Iterations="20"/>
    </Solve>
</CLBConfig>
"""


@pytest.fixture(autouse=True)
def _sink_off():
    """Telemetry is process-global: every test starts and ends disabled."""
    telemetry.disable()
    yield
    telemetry.disable()


def _case(tmp, monkeypatch):
    """The arguments of ``tclb run`` on the tiny case.  This process
    keeps its own compile-cache state: ``main`` places none."""
    monkeypatch.setattr(compile_cache, "place_compile_cache", lambda: None)
    monkeypatch.setenv("TCLB_FASTPATH", "force")    # interpret mode
    xml = tmp / "tiny.xml"
    xml.write_text(CASE)
    return ["run", str(xml), "--output", str(tmp / "out") + os.sep]


@pytest.fixture
def case(tmp_path, monkeypatch):
    return _case(tmp_path, monkeypatch)


def _spans(docs, name):
    return [e for e in docs if e["kind"] == "span" and e["name"] == name]


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    """The events of one traced ``tclb run`` of the tiny case, on the
    fused engines in interpret mode; read by four tests."""
    docs = []
    with pytest.MonkeyPatch.context() as monkeypatch:
        args = _case(tmp_path_factory.mktemp("ran"), monkeypatch)
        telemetry.subscribe(docs.append)
        try:
            assert main(args) == 0
        finally:
            telemetry.unsubscribe(docs.append)
    return docs


def test_the_spans_own_the_time_from_main_to_the_first_segment(ran):
    setup = report.summarize(ran)["setup"]
    assert setup["entry_to_segment_s"] > 0
    assert setup["unowned_s"] < 0.05 * setup["entry_to_segment_s"]
    boot, = [e for e in ran if e["kind"] == "boot"]
    assert boot["t_process"] <= boot["t_package"] <= boot["t_main"]
    # every import block of the run and the devices, under their names
    # (the package's own comes with the first traced main of a process)
    assert [r["module"] for r in setup["imports"]
            if r["module"] != "tclb_tpu"] == [
        "tclb_tpu.__main__", "tclb_tpu.control.solver",
        "tclb_tpu.models.d2q9"]
    dev, = setup["devices"]
    assert dev["count"] >= 1 and dev["device_kind"] == "cpu"
    assert dev["preloaded"] in (True, False)    # this process's history
    assert setup["case"][0]["shape"] == [32, 64]
    # and the report prints them
    text = report.format_text(report.summarize(ran))
    assert "set-up (seconds" in text and "under no span" in text


def test_every_element_has_its_span_and_the_model_owns_its_compiles(ran):
    elements = _spans(ran, "startup.element")
    assert [e["element"] for e in sorted(elements, key=lambda e: e["t0"])] \
        == ["Geometry", "Model", "Params", "Params", "Log", "Failcheck"]
    geometry, = [e for e in elements if e["element"] == "Geometry"]
    assert (geometry["nodes"], geometry["zones"]) == (32 * 64, 3)
    model, = [e for e in elements if e["element"] == "Model"]
    assert all(e["parent"] == model["id"] for e in elements
               if e["element"] == "Params")
    # <Solve> holds the loop: no span of its own, its passes are segments
    assert len(_spans(ran, "segment")) == 2
    # the init program was traced, lowered and compiled under <Model>
    mine = [e for e in ran if e["kind"] == "compile"
            and e["parent"] == model["id"] and "step" in e["program"]]
    assert {"trace", "lower", "backend_compile"} <= {e["stage"] for e in mine}
    rows = report.summarize(ran)["setup"]["elements"]
    assert [(r["element"], r["depth"]) for r in rows][1:4] == [
        ("Model", 0), ("Params", 1), ("Params", 1)]


def test_the_engine_is_built_under_the_first_iterate(ran):
    build, = _spans(ran, "engine.build")
    first = min(_spans(ran, "iterate"), key=lambda e: e["t0"])
    assert build["parent"] == first["id"]
    assert build["selected"] == build["candidates"][0] \
        == "pallas_resident[d2q9,fuse=8]"
    assert build["tail"] == "xla"
    # one candidate a run of a probe, inside it; the engine's account
    # stays where it was, on the probe
    probe, = _spans(ran, "engine.probe")
    run, = _spans(ran, "engine.probe.candidate")
    assert run["parent"] == probe["id"]
    assert (run["tag"], run["cap"], run["result"]) == (
        build["selected"], 0, "ran")
    assert 0 <= run["copy_s"] <= run["dur_s"]
    assert "kernel_calls" in probe and "kernel_calls" not in run


def test_every_compile_event_says_whose_it_is_and_where_from(ran):
    compiles = [e for e in ran if e["kind"] == "compile"]
    assert compiles
    assert all(e["program"] and e["cache"] in ("hit", "miss", "off")
               and "fun_name" not in e for e in compiles)
    rows = report.summarize(ran)["setup"]["compiles"]
    assert sum(r["count"] for r in rows) == len(compiles)
    assert rows[0]["seconds"] == max(r["seconds"] for r in rows)
    assert {"program", "stage", "cache", "under"} <= set(rows[0])
    # the two process-wide counters are gone: the verdict replaced them
    assert not hasattr(events, "_CACHE_COUNTERS")
    assert not [e for e in ran if e["kind"] == "counters"
                and any(k.startswith("compile.") for k in e["counters"])]


def test_with_telemetry_off_nothing_is_emitted(case, monkeypatch):
    def boom(*a, **k):
        raise AssertionError("telemetry is off")

    assert not telemetry.enabled()
    for name in ("startup.devices", "startup.case", "startup.element",
                 "engine.build", "engine.probe.candidate"):
        assert telemetry.span(name) is NOOP_SPAN
    assert telemetry.import_span("tclb_tpu.control.solver") is NOOP_SPAN
    monkeypatch.setattr(telemetry.Span, "__init__", boom)
    monkeypatch.setattr(events, "_fanout_locked", boom)
    assert main(case) == 0
    # the boot it recorded on the side is forgotten when main returns
    assert events._backlog is None and not events._in_main


def test_the_backlog_reaches_sinks_enabled_after_import_once(
        case, monkeypatch):
    """The harness's order: the package is imported, then a file sink
    and a subscriber arrive, then ``main`` is entered."""
    kept = {"kind": "span", "name": "startup.import", "module": "tclb_tpu",
            "id": 0, "parent": None, "t0": 1.0, "dur_s": 2.0, "ts": 3.0}
    monkeypatch.setattr(events, "_backlog", [kept])
    first, second = [], []
    telemetry.subscribe(first.append)
    telemetry.subscribe(second.append)
    try:
        assert first == second == []        # not before main is entered
        assert main(case) == 0
        for docs in (first, second):
            assert docs[0] == kept and docs[1]["kind"] == "boot"
            assert sum(e == kept for e in docs) == 1
        n = len(first)
        assert main(case) == 0              # a later main: its own boot
        # (a snapshot of the counters rides on the first event that
        # comes COUNTER_SNAPSHOT_S after the last snapshot: on a loaded
        # host this one, and it goes out before the event it rides on)
        assert [e["kind"] for e in first[n:]
                if e["kind"] != "counters"][:1] == ["boot"]
        assert sum(e == kept for e in first) == 1
    finally:
        telemetry.unsubscribe(first.append)
        telemetry.unsubscribe(second.append)


def test_a_sink_that_arrives_inside_main_gets_the_boot(monkeypatch):
    kept = {"kind": "span", "name": "startup.import", "ts": 3.0}
    monkeypatch.setattr(events, "_backlog", [kept])
    monkeypatch.setattr(events, "_in_main", False)
    t = time.time()
    telemetry.boot(t)                       # nobody listens yet
    assert [e["kind"] for e in events._backlog] == ["span", "boot"]
    assert events._backlog[1]["t_main"] == round(t, 6)
    assert events._backlog[1]["process_from"] == "proc"
    first, second = [], []
    try:
        telemetry.subscribe(first.append)   # as --monitor does, in run_case
        telemetry.subscribe(second.append)
        assert [e["kind"] for e in first] == ["span", "boot"]
        assert second == [] and events._backlog is None
    finally:
        telemetry.unsubscribe(first.append)
        telemetry.unsubscribe(second.append)
        telemetry.boot_over()
    # the backlog is small and fixed
    monkeypatch.setattr(events, "_backlog", [])
    for _ in range(3 * events.BACKLOG_MAX):
        events.boot_event("boot")
    assert len(events._backlog) == events.BACKLOG_MAX


def test_where_proc_cannot_be_read_the_package_stands_in(monkeypatch):
    import tclb_tpu
    started = events.process_start()
    monkeypatch.setattr(events, "process_start", lambda: None)
    monkeypatch.setattr(events, "_backlog", [])
    monkeypatch.setattr(events, "_in_main", False)
    telemetry.boot(time.time())
    boot, = events._backlog
    telemetry.boot_over()
    assert boot["process_from"] == "package"
    assert boot["t_process"] == boot["t_package"] \
        == round(tclb_tpu.T_PACKAGE, 6)
    # and where it can, it lies before the package's first line, by less
    # than this interpreter could have taken to get there
    assert 0 <= tclb_tpu.T_PACKAGE - started < 3600


# -- two processes against one cache directory ------------------------------ #

_HARNESS = """
import json, sys
from tclb_tpu.compile_cache import place_compile_cache  # the package, first
place_compile_cache()
import jax
jax.devices()                                   # the caller's backend
from tclb_tpu import telemetry
from tclb_tpu.__main__ import main
telemetry.enable(sys.argv[1])                   # a sink after the imports
docs = []
telemetry.subscribe(docs.append)
rc = main(["run", sys.argv[2], "--output", sys.argv[3]])
telemetry.disable()
print(json.dumps({"rc": rc, "head": docs[:2]}))
"""


@pytest.fixture(scope="module")
def two_runs(tmp_path_factory):
    """``python -m tclb_tpu run`` with ``TCLB_TELEMETRY`` on a fresh
    cache directory, then the same case driven as the benchmark's
    harness drives it, on the directory the first run filled.  JAX is
    told to keep every program, however short its compile."""
    tmp = tmp_path_factory.mktemp("setup_tree")
    xml = tmp / "tiny.xml"
    xml.write_text(CASE)
    env = dict(os.environ, JAX_PLATFORMS="cpu", TCLB_FASTPATH="0",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               PYTHONPATH=REPO)
    env.pop("TCLB_TELEMETRY", None)
    cold = subprocess.run(
        [sys.executable, "-m", "tclb_tpu", "run", str(xml),
         "--output", str(tmp / "cold") + os.sep],
        env=dict(env, TCLB_TELEMETRY=str(tmp / "cold.jsonl")), cwd=str(tmp),
        capture_output=True, text=True, timeout=300)
    assert cold.returncode == 0, cold.stderr[-2000:]
    warm = subprocess.run(
        [sys.executable, "-c", _HARNESS, str(tmp / "warm.jsonl"), str(xml),
         str(tmp / "warm") + os.sep],
        env=env, cwd=str(tmp), capture_output=True, text=True, timeout=300)
    assert warm.returncode == 0, warm.stderr[-2000:]
    return (report.load(str(tmp / "cold.jsonl")),
            report.load(str(tmp / "warm.jsonl")),
            json.loads(warm.stdout.strip().splitlines()[-1]))


def _backend_compiles(docs):
    return [e for e in docs if e["kind"] == "compile"
            and e["stage"] == "backend_compile"]


def test_a_second_run_against_the_same_cache_reads_hit(two_runs):
    cold, warm, _ = two_runs
    assert {e["cache"] for e in _backend_compiles(cold)} == {"miss"}
    assert {e["cache"] for e in _backend_compiles(warm)} == {"hit"}
    assert {e["program"] for e in _backend_compiles(warm)} \
        == {e["program"] for e in _backend_compiles(cold)}
    # a load goes out with the compile it lies in, under its name
    loads = [e for e in warm if e["kind"] == "compile"
             and e["stage"] == "cache_load"]
    assert len(loads) == len(_backend_compiles(warm))
    assert all(e["program"] and e["cache"] == "hit" for e in loads)
    assert not [e for e in cold if e["kind"] == "compile"
                and e["stage"] == "cache_load"]


def test_a_process_of_its_own_boots_under_spans(two_runs):
    cold, _, _ = two_runs
    boot, = [e for e in cold if e["kind"] == "boot"]
    assert boot["process_from"] == "proc"
    assert boot["t_process"] < boot["t_package"] < boot["t_main"]
    package = min(_spans(cold, "startup.import"), key=lambda e: e["t0"])
    assert package["module"] == "tclb_tpu" and package["preloaded"] is False
    # the package's block is the time from its first line to main's entry
    assert package["dur_s"] == pytest.approx(
        boot["t_main"] - boot["t_package"], abs=0.25)
    dev, = _spans(cold, "startup.devices")
    assert dev["preloaded"] is False
    setup = report.summarize(cold)["setup"]
    assert setup["unowned_s"] < 0.05 * setup["entry_to_segment_s"]


def test_the_harness_receives_the_boot_it_did_not_wait_for(two_runs):
    _, warm, said = two_runs
    assert said["rc"] == 0
    # the subscriber that came second got the backlog too, first of all
    assert [e["kind"] for e in said["head"]] == ["span", "boot"]
    assert said["head"][0]["module"] == "tclb_tpu"
    assert said["head"][0]["preloaded"] is False
    # and the file has it once, with the caller's backend called preloaded
    assert len([e for e in _spans(warm, "startup.import")
                if e["module"] == "tclb_tpu"]) == 1
    dev, = _spans(warm, "startup.devices")
    assert dev["preloaded"] is True
