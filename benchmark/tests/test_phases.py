"""``phases.py``, the readers PR 26 added and ``phase_table.py`` on a
hand-made recording with nested annotations and its events
(``data/phases_recording.json``, ``data/phases_events.jsonl``; the
recording's ``about`` entry says what they hold), and on the kept files of
a program from before PR 26, where every new reader finds nothing."""

import os

import pytest

from benchmark import phase_table, phases, trace
from benchmark.layer_metrics import (compile_s, compiles_in_window,
                                     failcheck_ms, globals_step_ms,
                                     halo_bytes_per_step, kernel_wrap_share,
                                     vtk_encode_ms)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = {"window": {"first_iteration": 100, "last_iteration": 300}}


def recording() -> trace.Recording:
    with open(os.path.join(DATA, "phases_recording.json")) as f:
        return trace.Recording.from_json(f.read())


def events() -> list[dict]:
    return trace.read_events(os.path.join(DATA, "phases_events.jsonl"))


def test_device_seconds_by_the_annotation_that_holds_an_operations_middle():
    fused = phases.device_seconds_in(recording(), "iterate.fused")
    assert [(e["start"], e["end"]) for e in fused] == [
        (pytest.approx(0.1), pytest.approx(3.0)),
        (pytest.approx(6.1), pytest.approx(8.6))]
    # two chips; the while's own 0.1 s counts as other, its body once
    assert fused[0]["kernel"] == pytest.approx(2 * 1.8)
    assert fused[0]["other"] == pytest.approx(2 * (0.5 + 0.1))
    assert fused[0]["collective"] == pytest.approx(2 * 0.1)
    assert fused[0]["busy"] == pytest.approx(2.5)       # the while's span
    assert phases.totals(fused) == {
        "kernel": pytest.approx(7.6), "collective": pytest.approx(0.2),
        "other": pytest.approx(1.6)}
    assert phases.device_seconds_in(recording(), "output.vtk") == []
    # the device's clock 0.15 s ahead of the host's: the first kernel now
    # starts before its annotation, and stays with it
    early = recording()
    early.devices = {k: [[n, s - 0.15, d] for n, s, d in v]
                     for k, v in early.devices.items()}
    assert phases.totals(phases.device_seconds_in(
        early, "iterate.fused")) == pytest.approx(phases.totals(fused))


def test_device_readers():
    rec = recording()
    assert kernel_wrap_share.read([], rec, CELL) == pytest.approx(
        100 * 1.6 / 9.4)
    # 0.4 + 0.2 s in the first trailing step, 0.2 s in the second
    assert globals_step_ms.read([], rec, CELL) == pytest.approx(400.0)


def test_phase_table_adds_up():
    t = phase_table.device_table(recording())
    assert t["busy_self_s"] == pytest.approx(5.85)
    assert t["busy_union_s"] == pytest.approx(5.85)     # nothing overlaps
    assert t["iterate.globals_step"]["other"] == pytest.approx(0.8)
    assert t["quantity.eval"]["other"] == pytest.approx(0.3)
    assert t["quantity.eval"]["annotations"] == 1
    assert t["outside"] == pytest.approx(0.05)      # chip 0's copy, halved
    host = phase_table.host_table(events(), CELL["window"])
    fc = host["cbFailcheck"]
    assert fc["calls"] == 2 and fc["median_ms"] == pytest.approx(1950.0)
    assert fc["self_ms"]["quantity.eval"] == pytest.approx(500.0)
    assert fc["self_ms"]["failcheck.scan"] == pytest.approx(900.0)
    # calls of 2.0 and 1.9 s, 1.8 s of each under the children
    assert fc["self_ms"]["handler"] == pytest.approx(150.0)
    vtk = host["cbVTK"]["self_ms"]
    assert vtk["output.vtk.encode"] == pytest.approx(800.0)
    assert vtk["output.vtk"] == pytest.approx(1300.0 - 1000.0)


def test_window_bounds_and_event_readers():
    ev = events()
    assert phases.window_bounds(ev, CELL["window"]) == (
        pytest.approx(100.0), pytest.approx(113.0))
    assert compile_s.read(ev, None, CELL) == pytest.approx(1.5 + 0.5)
    # one trace under failcheck.scan; its lowering is not counted, the
    # compile after the window neither
    assert compiles_in_window.read(ev, None, CELL) == 1.0
    assert failcheck_ms.read(ev, None, CELL) == pytest.approx(1950.0)
    assert vtk_encode_ms.read(ev, None, CELL) == pytest.approx(800.0)
    assert halo_bytes_per_step.read(ev, None, CELL) == pytest.approx(
        2 * 50024 / 198)
    later = {"window": {"first_iteration": 300, "last_iteration": 500}}
    for reader in (compile_s, compiles_in_window, failcheck_ms,
                   vtk_encode_ms, halo_bytes_per_step):
        assert reader.read(ev, None, later) is None


def test_a_span_without_t0_starts_at_ts_less_its_duration():
    assert phases.start_of({"ts": 12.5, "dur_s": 2.0}) == 10.5
    assert phases.start_of({"ts": 12.5, "dur_s": 2.0, "t0": 10.4}) == 10.4


def test_a_program_from_before_the_spans_prints_none_of_them():
    """The kept files of PR 25's program: no ``iterate.fused``, no
    ``compile`` event, no ``t0``.  Its ``handler`` spans name their
    handler already, so ``failcheck_ms`` alone reads there."""
    from benchmark.tests.test_trace import recording as kept
    ev = trace.read_events(os.path.join(DATA, "events.jsonl"))
    cell = {"window": {"first_iteration": 15000, "last_iteration": 16000}}
    for reader in (kernel_wrap_share, globals_step_ms, compile_s,
                   compiles_in_window, vtk_encode_ms, halo_bytes_per_step):
        assert reader.read(ev, kept(), cell) is None, reader.__name__
    assert failcheck_ms.read(ev, kept(), cell) == pytest.approx(37.022)
