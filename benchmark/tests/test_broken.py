"""`correct` comes out false when the timed path is broken underneath,
and when the reference is computed one precision down (the control)."""

import json

import pytest

from benchmark.tests.test_window import last_line, tiny_run  # noqa: F401


def test_step_that_returns_its_state_unchanged(tiny_run, capsys,  # noqa: F811
                                               monkeypatch):
    """The rest of a run, with a Lattice.iterate that advances nothing."""
    from tclb_tpu.core.lattice import Lattice
    monkeypatch.setattr(Lattice, "_iterate_impl", lambda self, niter: None)
    rc = tiny_run.main(["--workload", "karman1024.shipped", "--seed", "11",
                        "--seconds", "0.2", "--trace", "0"])
    result, lines = last_line(capsys)
    assert rc == 0 and result["correct"] is False
    assert any("max |program - reference|" in line and "FAILED" in line
               for line in lines)


def test_shard_left_out(tiny_run, capsys, monkeypatch):  # noqa: F811
    """The mesh cell with a part of the domain altered where it is
    produced: a quarter of the rows of the kept fields is zeroed."""
    import numpy as np

    from benchmark import window
    real = np.asarray

    def broken(x, *a, **k):
        out = np.array(real(x, *a, **k))
        if out.ndim == 3 and out.shape[0] == 11:
            out[:, : out.shape[1] // 4] = 0.0
        return out
    orig_tick = window.Window.tick

    def tick(self, solver):
        if int(solver.iter) == self.check_at:
            monkeypatch.setattr(np, "asarray", broken)
        try:
            return orig_tick(self, solver)
        finally:
            monkeypatch.setattr(np, "asarray", real)
    monkeypatch.setattr(window.Window, "tick", tick)
    tiny_run.main(["--workload", "karman4096.mesh4x1", "--seed", "12",
                   "--seconds", "0.2", "--trace", "0"])
    result, _ = last_line(capsys)
    assert result["correct"] is False


@pytest.mark.parametrize("cell,steps", [("karman1024.shipped", 40),
                                        ("channel3d512.steady", 40)])
def test_bfloat16_control_fails_the_limit(tiny_run, cell, steps):  # noqa: F811
    """The control at a size a test holds: the reference with bfloat16
    storage is outside the configuration's tolerance, float32 inside."""
    from benchmark import casegen, check
    from benchmark.control import control_difference
    _, config, traffic = tiny_run.load_cell(cell)
    root, _ = casegen.generate(tiny_run.template_path(config), traffic, 5)
    assert control_difference(config, root, steps) > config["tolerance"]
    a = check.reference_fields(config, root, steps)
    assert check.largest_difference(a, a) == 0.0


def test_wrong_engine_family_is_not_correct(tiny_run, capsys,  # noqa: F811
                                            monkeypatch):
    """Without TCLB_FASTPATH=force the CPU run takes the XLA step: the
    fields agree, the engine is not of the configuration's family."""
    monkeypatch.delenv("TCLB_FASTPATH")
    tiny_run.main(["--workload", "karman1024.shipped", "--seed", "13",
                   "--seconds", "0.2", "--trace", "0"])
    result, lines = last_line(capsys)
    assert result["correct"] is False
    assert any("outside family" in line and "FAILED" in line
               for line in lines)
    json.dumps(result)
