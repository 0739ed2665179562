"""The readers PR 37 added (``segment_host_ms``, ``log_ms``,
``dispatch_ms``, ``idle_unnamed_share``) on a hand-made events file and
recording of their own (``data/segment_events.jsonl``: a probed first
segment before the window and two segments inside it;
``data/segment_recording.json``: its ``about`` entry says what it holds),
and on the kept files of programs from before the spans, where a reader
of a span that is not there finds nothing."""

import os

import pytest

from benchmark import trace
from benchmark.layer_metrics import (dispatch_ms, idle_unnamed_share,
                                     log_ms, segment_host_ms)
from benchmark.tests.test_window import tiny_run     # noqa: F401 (fixture)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = {"window": {"first_iteration": 100, "last_iteration": 300}}


def events(name: str = "segment_events.jsonl") -> list[dict]:
    return trace.read_events(os.path.join(DATA, name))


def recording(name: str = "segment_recording.json") -> trace.Recording:
    with open(os.path.join(DATA, name)) as f:
        return trace.Recording.from_json(f.read())


def test_a_segments_host_time_is_its_span_less_every_fence_under_it():
    # 5.0 s less 0.1 + 2.6 + 0.2 (iterate and its two launches) + 0.25
    # (the Failcheck's quantity.eval); 4.0 s less 0.1 + 2.4 + 0.2; the
    # probed segment that ends where the window starts is left out
    assert segment_host_ms.read(events(), None, CELL) == pytest.approx(
        1e3 * (1.85 + 1.3) / 2)
    first = {"window": {"first_iteration": 0, "last_iteration": 100}}
    assert segment_host_ms.read(events(), None, first) == pytest.approx(
        1e3 * (20.1 - 0.8))


def test_the_log_and_the_launches_of_the_window():
    ev = events()
    assert log_ms.read(ev, None, CELL) == pytest.approx(600.0)
    # 0.3 + 0.05 and 0.2 + 0.04 s: each iterate's two launches
    assert dispatch_ms.read(ev, None, CELL) == pytest.approx(295.0)
    # the probed call says no dispatch_s: its trailing step alone
    first = {"window": {"first_iteration": 0, "last_iteration": 100}}
    assert dispatch_ms.read(ev, None, first) == pytest.approx(50.0)
    later = {"window": {"first_iteration": 300, "last_iteration": 500}}
    for reader in (segment_host_ms, log_ms, dispatch_ms):
        assert reader.read(ev, None, later) is None


def test_idle_seconds_that_no_span_covers():
    # chip 0 idles 3.5 s; the second between the segments and the one
    # after the last are under no span
    assert idle_unnamed_share.read([], recording(), CELL) == pytest.approx(
        100 * 2.0 / 3.5)
    named = [g for g in trace.idle_gaps(recording()) if g[0] != "none"]
    assert named == [["segment", pytest.approx(1.0)],
                     ["output.log", pytest.approx(0.5)]]
    # a device that is never idle has no share
    busy = recording()
    busy.devices = {"0": busy.devices["1"]}
    assert idle_unnamed_share.read([], busy, CELL) is None


@pytest.mark.parametrize("name,cell", [
    ("events.jsonl", {"first_iteration": 15000, "last_iteration": 16000}),
    ("phases_events.jsonl", CELL["window"]),
    ("drop_events.jsonl", {"first_iteration": 0, "last_iteration": 10**9}),
])
def test_a_program_from_before_the_spans_prints_none_of_them(name, cell):
    """A parent's trace: no ``segment``, no ``dispatch_s``; where the
    ``output.log`` span is there (it has been since PR 25) ``log_ms``
    reads, and the idle share reads any recording."""
    ev = events(name)
    assert segment_host_ms.read(ev, None, {"window": cell}) is None
    assert dispatch_ms.read(ev, None, {"window": cell}) is None
    logs = trace.spans_in_window(ev, "output.log", cell)
    got = log_ms.read(ev, None, {"window": cell})
    assert (got is None) == (not logs)
    if name == "events.jsonl":
        assert got == pytest.approx(0.9375)


def test_the_kept_recording_of_pr25_has_its_vtk_under_a_span():
    share = idle_unnamed_share.read([], recording("recording.json"), CELL)
    assert 0.0 <= share < 5.0


def test_traced_rehearsal_reports_the_new_metrics(tiny_run, capsys,
                                                  monkeypatch):
    """The traced run of ``karman1024.shipped`` on the CPU: the program's
    spans are there, the four readers are found by name and read; the
    events file holds whole lines.  The CPU has no device plane, so the
    recording is this file's."""
    import json

    from benchmark import bytes_model
    run = tiny_run
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = run.main(["--workload", "karman1024.shipped", "--seed", "37",
                   "--seconds", "1.5", "--trace", "1"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    m = result["metrics"]
    assert {"segment_host_ms", "log_ms", "dispatch_ms",
            "idle_unnamed_share"} <= set(m)
    assert 0 < m["dispatch_ms"]["value"] < m["segment_host_ms"]["value"]
    assert 0 < m["log_ms"]["value"] < m["segment_host_ms"]["value"]
    assert m["idle_unnamed_share"]["value"] == pytest.approx(100 * 2 / 3.5)
    ev = trace.read_events(os.path.join(
        run.OUT, "karman1024.shipped.seed37.trace1.events.jsonl"))
    roots = {e["id"] for e in trace.spans(ev, "segment")}
    assert roots and all(e["parent"] in roots
                         for e in trace.spans(ev, "iterate"))
