"""The seven set-up readers of PR 51 on the events of one recorded run
(``data/setup_events.jsonl``: ``python -m tclb_tpu run`` of a 64 x 32
karman channel, 40 steps, ``<Log 10>``, ``<Failcheck 20>`` and
``<VTK 10>``, on the CPU with the fused engines in interpret mode and a
compile cache a first run had filled; the traces and lowerings under
5 ms taken out), and on the kept events of a program from before the
spans, where they read nothing."""

import os

import pytest

from benchmark import phases, trace
from benchmark.layer_metrics import (cache_load_s, compile_misses,
                                     engine_build_s, import_s, pre_entry_s,
                                     program_start_s, setup_unnamed_s)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# the window opens with the third of the four segments
CELL = {"window": {"first_iteration": 20, "last_iteration": 40}}


@pytest.fixture(scope="module")
def events():
    return trace.read_events(os.path.join(DATA, "setup_events.jsonl"))


def _roots(events):
    return [e for e in trace.spans(events) if e["parent"] is None]


def test_pre_entry_is_the_boot_events_first_interval(events):
    boot, = [e for e in events if e["kind"] == "boot"]
    assert boot["process_from"] == "proc"
    assert pre_entry_s.read(events, None, CELL) == pytest.approx(2.429861)
    # the package's import block fills it but for the interpreter's start
    package = trace.spans(events, "startup.import")[0]
    assert package["module"] == "tclb_tpu"
    assert 0 < pre_entry_s.read(events, None, CELL) - package["dur_s"] < 0.05


def test_import_s_sums_the_four_blocks(events):
    blocks = trace.spans(events, "startup.import")
    assert [e["module"] for e in blocks] == [
        "tclb_tpu", "tclb_tpu.__main__", "tclb_tpu.control.solver",
        "tclb_tpu.models.d2q9"]
    assert import_s.read(events, None, CELL) == pytest.approx(
        2.392398 + 0.0747 + 0.016109 + 0.007087)


def test_program_start_counts_each_root_once(events):
    # devices, case, Geometry, Model (its two Params inside it), and the
    # three handlers of <Solve>
    want = 0.023003 + 0.374495 + 0.005706 + 0.61755 + 2.3e-5 + 1.6e-5 + 1.4e-5
    assert program_start_s.read(events, None, CELL) == pytest.approx(want)
    nested = [e for e in trace.spans(events, "startup.element")
              if e["parent"] is not None]
    assert [e["element"] for e in nested] == ["Params", "Params"]


def test_engine_build_is_under_the_first_iterate(events):
    build, = trace.spans(events, "engine.build")
    first = min(trace.spans(events, "iterate"), key=phases.start_of)
    assert build["parent"] == first["id"]
    assert engine_build_s.read(events, None, CELL) == build["dur_s"]


def test_compile_misses_and_loads_before_the_window(events):
    opening = phases.window_bounds(events, CELL["window"])[0]
    done = [e for e in phases.compile_events(events, ("backend_compile",))
            if e["ts"] < opening]
    assert len(done) == 20
    # two programs came from the cache; JAX had kept what took a second
    assert sorted(e["program"] for e in done if e["cache"] == "hit") == [
        "jit(_iterate_jit)", "jit(step)"]
    assert compile_misses.read(events, None, CELL) == 18.0
    assert cache_load_s.read(events, None, CELL) == pytest.approx(
        0.191515 + 0.660875)
    # a window that opens with the first segment has the element's
    # compiles behind it and the engine's still to come
    early = {"window": {"first_iteration": 0, "last_iteration": 40}}
    assert compile_misses.read(events, None, early) == 8.0
    assert cache_load_s.read(events, None, early) == pytest.approx(0.191515)


def test_setup_unnamed_leaves_the_writers_thread_out(events):
    boot, = [e for e in events if e["kind"] == "boot"]
    opening = phases.window_bounds(events, CELL["window"])[0]
    got = setup_unnamed_s.read(events, None, CELL)
    assert 0 < got < 0.1
    # the gaps between the roots of the main thread, one by one
    mine = sorted((e for e in _roots(events)
                   if e["name"] not in setup_unnamed_s.OTHER_THREADS
                   and boot["t_main"] <= e["t0"] < opening),
                  key=lambda e: e["t0"])
    gaps, at = 0.0, boot["t_main"]
    for e in mine:
        gaps += max(0.0, e["t0"] - at)
        at = max(at, e["t0"] + e["dur_s"])
    assert got == pytest.approx(gaps + max(0.0, opening - at), abs=1e-6)
    # the list of other threads' roots is the whole of it today
    assert {e["name"] for e in _roots(events)} == {
        "startup.import", "startup.devices", "startup.case",
        "startup.element", "segment", "output.vtk.drain",
        "output.vtk.write"}
    # a write of the output thread lies over a segment: counted as a
    # root of the main thread it would cover nothing new
    write = trace.spans(events, "output.vtk.write")[0]
    assert write["parent"] is None and write["t0"] < opening


def test_an_older_program_reads_nothing():
    """``phases_events.jsonl``: spans with ``t0`` and ``compile`` events
    without a verdict, no ``boot``, no ``startup.*``, no
    ``engine.build``.  Its ``cache_load`` events are what they are
    today, so that one reader reads there too."""
    old = trace.read_events(os.path.join(DATA, "phases_events.jsonl"))
    cell = {"window": {"first_iteration": 100, "last_iteration": 300}}
    for reader in (pre_entry_s, import_s, program_start_s, engine_build_s,
                   compile_misses, setup_unnamed_s):
        assert reader.read(old, None, cell) is None, reader.__name__
    assert cache_load_s.read(old, None, cell) == pytest.approx(0.4)
    # and where no window is found, nothing that needs one reads
    nowhere = {"window": {"first_iteration": 900, "last_iteration": 950}}
    for reader in (compile_misses, cache_load_s, setup_unnamed_s):
        assert reader.read(old, None, nowhere) is None


from benchmark.tests.test_window import last_line, tiny_run  # noqa: E402,F401


def test_the_traced_rehearsal_reports_all_seven(tiny_run, capsys,  # noqa: F811
                                                monkeypatch):
    """The harness's own order on the CPU (a sink after the imports,
    then ``main``): every new reader finds its events in the list the
    harness keeps, and what they name leaves little of the time from
    ``main`` to the window unnamed."""
    from benchmark import bytes_model
    from benchmark.tests.test_trace import recording
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = tiny_run.main(["--workload", "karman1024.shipped", "--seed", "11",
                        "--seconds", "1.5", "--trace", "1"])
    result, _ = last_line(capsys)
    assert rc == 0 and result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"pre_entry_s", "import_s", "program_start_s", "engine_build_s",
            "compile_misses", "cache_load_s", "setup_unnamed_s"} <= set(got)
    assert got["pre_entry_s"] > 0 and got["import_s"] > 0
    assert got["engine_build_s"] < got["first_call_s"]
    assert 0 <= got["setup_unnamed_s"] < 0.1 * (
        got["program_start_s"] + got["first_call_s"])
    assert got["cache_load_s"] <= got["compile_s"]
