"""The window handler: opens and closes on period boundaries, holds
whole periods only, returns ITERATION_STOP; first as a unit with a fake
clock, then inside a CPU run of a tiny case through run.py."""

import json

import pytest

from benchmark import window

HANDLERS = [{"tag": "Failcheck", "Iterations": 1000},
            {"tag": "Log", "Iterations": 500},
            {"tag": "VTK", "Iterations": 2000}]


class FakeSolver:
    class lattice:
        class state:
            class fields:
                shape = (11, 8, 8)

                class dtype:
                    itemsize = 4

    def __init__(self):
        self.iter = 0

    def out_path(self, name, ext):
        return f"/nonexistent/{name}_{self.iter:08d}.{ext}"


def drive(win, seconds_per_segment, limit=10000):
    """Run the solve loop's side of the protocol against a fake clock."""
    t = [0.0]
    win.clock = lambda: t[0]
    win.block = lambda solver: None
    s = FakeSolver()
    for _ in range(limit):
        s.iter += win.segment
        t[0] += seconds_per_segment(s.iter)
        if win.tick(s) == window.ITERATION_STOP:
            return s
    raise AssertionError("the window never closed")


def test_periods_and_segments():
    win = window.Window(HANDLERS, 10.0, warmup_periods=2, check_segments=5)
    assert (win.segment, win.period, win.open_at) == (500, 2000, 6000)
    assert win.kinds(500) == "Log"
    assert win.kinds(1000) == "Failcheck+Log"
    assert win.kinds(2000) == "Failcheck+Log+VTK"


def test_whole_periods_only():
    win = window.Window(HANDLERS, 1.0, warmup_periods=2)
    # a VTK segment takes 0.2 s, the others 0.06: a period is 0.38 s
    s = drive(win, lambda it: 0.2 if it % 2000 == 0 else 0.06)
    assert win.closed and s.iter % win.period == 0
    its = [it for it, _, _ in win.segments]
    assert its[0] == win.open_at + win.segment
    assert its[-1] == s.iter
    assert len(its) % (win.period // win.segment) == 0
    summ = win.summary(nodes=64)
    # three whole periods are the first to reach 1.0 s
    assert summ["periods"] == 3 and summ["steps"] == 6000
    assert summ["wall_s"] == pytest.approx(3 * 0.38)
    assert summ["mlups"] == pytest.approx(64 * 6000 / (3 * 0.38) / 1e6)
    assert summ["segment_p95_ms"] == pytest.approx(200.0)
    # every run of a cell holds the same work per period
    per_period = [k for _, _, k in win.segments[:4]]
    assert per_period == ["Log", "Failcheck+Log", "Log",
                          "Failcheck+Log+VTK"]


def test_warmup_is_not_measured():
    win = window.Window(HANDLERS, 0.5, warmup_periods=3)
    drive(win, lambda it: 5.0 if it <= 8000 else 0.1)
    assert win.open_at == 8000
    assert max(sec for _, sec, _ in win.segments) == pytest.approx(0.1)


def test_needs_two_warmup_periods():
    with pytest.raises(ValueError):
        window.Window(HANDLERS, 1.0, warmup_periods=1)


def test_tick_without_a_window():
    window.install(None)
    with pytest.raises(RuntimeError):
        window.tick(FakeSolver())


# -- rehearsals: each cell end to end on the CPU, through run.py ---------- #


@pytest.fixture
def tiny_run(monkeypatch):
    """run.py with the no-TPU refusal lifted and every cell cut to a
    tiny size; Pallas kernels in interpret mode, so the engine tags are
    the chip's."""
    import jax

    from benchmark import run
    from benchmark.tests import tiny
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    monkeypatch.setattr(run, "load_cell", tiny.shrink(run.load_cell))
    monkeypatch.setattr(run, "template_path", tiny.template_path)
    monkeypatch.setattr(run, "find_chips", lambda chips: jax.devices())
    return run


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("cell,metrics", [
    ("karman1024.shipped", {"mlups", "segment_p95_ms", "setup_s"}),
    ("channel3d512.steady", {"mlups", "setup_s"}),
    ("karman4096.mesh4x1", {"mlups", "segment_p95_ms", "setup_s"}),
])
def test_rehearsal(tiny_run, capsys, cell, metrics):
    rc = tiny_run.main(["--workload", cell, "--seed", "4294967311",
                        "--seconds", "0.3", "--trace", "0"])
    result, lines = last_line(capsys)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == metrics
    assert result["attempted"] >= 1
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert any(line.startswith("output: ") for line in lines)
    assert any("check: max |program - reference|" in line for line in lines)
    import glob
    import os
    seg = glob.glob(os.path.join(tiny_run.OUT, cell + ".seed4294967311."
                                 "trace0.segments.json"))
    with open(seg[0]) as f:
        rec = json.load(f)
    per = {"shipped": 8, "steady": 2, "mesh4x1": 4}[cell.split(".")[1]]
    assert rec["summary"]["steps"] % per == 0
    assert rec["segments"][-1][0] % per == 0
    # no output file outlasts a run
    out_dirs = [line.split()[1] for line in lines
                if line.startswith("output: /")]
    assert out_dirs and not os.path.exists(out_dirs[0])


def test_no_tpu_no_result(monkeypatch, capsys):
    from benchmark import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "karman1024.shipped", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code == 2
    assert "correct" not in capsys.readouterr().out


def test_traced_rehearsal(tiny_run, capsys, monkeypatch):
    """Control flow of the traced run: the profiler starts and stops on
    period boundaries, the events file is read, each reader is found by
    name.  The CPU has no device plane, so the recording is the kept
    one."""
    from benchmark import trace
    from benchmark.tests.test_trace import recording
    monkeypatch.setattr(trace, "load_xplane",
                        lambda path, names: recording())
    from benchmark import bytes_model
    v5e = bytes_model.peak("TPU v5 lite")
    monkeypatch.setattr(bytes_model, "peak", lambda kind: v5e)
    rc = tiny_run.main(["--workload", "karman1024.shipped", "--seed", "7",
                        "--seconds", "1.5", "--trace", "1"])
    result, _ = last_line(capsys)
    assert rc == 0 and result["correct"] is True
    assert {"handlers_share", "vtk_ms", "engine_fallbacks", "first_call_s",
            "device_idle_share"} <= set(result["metrics"])
    assert "halo_exposed_share" not in result["metrics"]
    assert result["metrics"]["engine_fallbacks"]["value"] == 0.0
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] > result["device"]["busy_s"]
    assert len(result["breakdown"]["device_ops"]) <= 10
