"""The step a hybrid engine leaves for the Globals: the tail engine.

A hybrid engine (``pallas_d3q``, ``pallas_2d``, ``pallas_resident``)
advances ``niter - 1`` steps and leaves the last one, which reduces the
Globals, to another engine: the generic Pallas engine's one-step flavour
with in-kernel globals wherever it takes the case
(``Lattice._build_tail``), else the XLA step.  These tests force the
dispatch on CPU (interpret mode) and pin ``Lattice.iterate`` against the
XLA engine, and what the run says of itself.  (The 2D composition,
resident engine and tail: ``test_fastpath.py::
test_engine_dispatch_matches_xla``.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu import telemetry
from tclb_tpu.core.lattice import Lattice
from tclb_tpu.models import get_model
from tclb_tpu.ops import pallas_generic

from test_fastpath import (  # noqa: F401 (seen is a fixture)
    _karman_lattice, _says_tail, _spans, seen)


def _cumulant_lattice(shape, storage_dtype=None):
    """d3q27_cumulant between walls in y with a turbulent inlet and a
    pressure outlet in x: the synthetic-turbulence coupling planes
    (``SynthT*``, which the ``<SyntheticTurbulence>`` handler fills in a
    run) and the averages (``avg*``) all move, and ``Flux`` is reduced."""
    m = get_model("d3q27_cumulant")
    lat = Lattice(m, shape, dtype=jnp.float32, storage_dtype=storage_dtype,
                  settings={"nu": 0.05, "Velocity": 0.03,
                            "Turbulence": 0.01})
    flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0, :] = flags[:, -1, :] = m.flag_for("Wall")
    flags[:, 1:-1, 0] = m.flag_for("WVelocityTurbulent", "MRT")
    flags[:, 1:-1, -1] = m.flag_for("EPressure", "MRT")
    lat.set_flags(flags)
    lat.init()
    rng = np.random.default_rng(0)
    lat.set_density_planes({nm: rng.standard_normal(shape)
                            for nm in ("SynthTX", "SynthTY", "SynthTZ")})
    return m, lat


_TAIL_CASES = {
    # shape, the fused engine, the tail engine
    "whole_plane": ((8, 16, 128), "pallas_d3q[d3q27_cumulant,fuse=4]",
                    "pallas_generic[d3q27_cumulant,fuse=1]"),
    # a plane neither engine holds whole: both cut it into bands of 32
    # rows, the tail with 8 wrapped halo rows a side
    "y_tiled": ((4, 256, 256), "pallas_d3q[d3q27_cumulant,fuse=2,by=32]",
                "pallas_generic[d3q27_cumulant,fuse=1,by=32]"),
}


@pytest.mark.parametrize("case", list(_TAIL_CASES))
def test_tail_engine_matches_the_xla_step(monkeypatch, seen, case):
    """``Lattice.iterate(n)`` on a hybrid engine, whose last step runs on
    the generic Pallas engine's in-kernel-globals flavour, against the
    XLA engine's ``n`` steps: every storage plane (``avg*`` and
    ``SynthT*`` among them), ``Flux``, the iteration; and what the run
    says of itself: the engine on ``iterate.globals_step``, one
    ``engine.tail_calls`` a call, no fallback.  (The 2D case is
    ``test_engine_dispatch_matches_xla``.)"""
    shape, fused, tail = _TAIL_CASES[case]
    niter, calls = 5, 2
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    m, lat_x = _cumulant_lattice(shape)
    flux = []
    for _ in range(calls):
        lat_x.iterate(niter)
        flux.append(lat_x.get_globals()["Flux"])
    assert lat_x._fast_name is None and lat_x._tail_name is None
    assert not _spans(seen, "iterate.globals_step")
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    _, lat_f = _cumulant_lattice(shape)
    before = telemetry.counters().get("engine.tail_calls", 0)
    for want in flux:
        lat_f.iterate(niter)
        assert want != 0
        np.testing.assert_allclose(lat_f.get_globals()["Flux"], want,
                                   rtol=1e-4)
    fx, ff = np.asarray(lat_x.state.fields), np.asarray(lat_f.state.fields)
    np.testing.assert_allclose(ff, fx, rtol=2e-5, atol=2e-6)
    for plane in m.storage_names:
        if plane.startswith(("avg", "SynthT")):
            assert np.abs(fx[m.storage_index[plane]]).max() > 0, plane
    assert int(lat_f.state.iteration) == niter * calls
    _says_tail(seen, lat_f, fused, tail, calls)
    assert telemetry.counters()["engine.tail_calls"] - before == calls


def _tail_on_a_mesh():
    from tclb_tpu.parallel.mesh import make_mesh
    m, ref = _karman_lattice(64)
    mesh = make_mesh((64, 128), devices=jax.devices()[:4],
                     decomposition={"y": 4, "x": 1})
    lat = Lattice(m, (64, 128), dtype=jnp.float32,
                  settings={"nu": 0.05, "Velocity": 0.03}, mesh=mesh)
    lat.set_flags(np.asarray(ref.state.flags))
    lat.init()
    return lat, "pallas_sharded"


def _tail_with_a_series():
    _, lat = _karman_lattice(64)
    lat.set_setting_series(
        "Velocity", 0.03 + 0.001 * np.sin(np.arange(16) * 0.3), zone=0)
    return lat, "pallas_generic"


def _tail_of_a_refused_dtype(monkeypatch):
    # bfloat16 storage, which the tuned 3D engine takes and (here) the
    # generic engine refuses
    monkeypatch.setattr(pallas_generic, "STORAGE_DTYPES", (jnp.float32,))
    return _bgk_lattice(jnp.bfloat16)[1], "pallas_d3q"


def _tail_of_half_a_lane_tile():
    # 64 columns: the generic kernel reduces no Globals on them
    return _bgk_lattice(nx=64)[1], "pallas_d3q"


@pytest.mark.parametrize("case", ["mesh", "series", "refused_dtype",
                                  "no_kernel_globals"])
def test_tail_engine_stays_off(monkeypatch, seen, case):
    """Where the generic engine's one-step flavour does not apply, the
    trailing step stays the XLA step: on a mesh (the sharded engine's
    own step), with a ``<Control>`` series (the series-aware generic
    engine reduces the Globals itself: no trailing step at all), with a
    storage dtype the generic engine refuses, and on a shape whose
    generic kernel has no flavour that reduces Globals."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    before = telemetry.counters().get("engine.tail_calls", 0)
    lat, family = (_tail_of_a_refused_dtype(monkeypatch)
                   if case == "refused_dtype"
                   else {"mesh": _tail_on_a_mesh,
                         "series": _tail_with_a_series,
                         "no_kernel_globals": _tail_of_half_a_lane_tile,
                         }[case]())
    lat.iterate(6)
    assert lat._fast_name.startswith(family + "[")
    assert lat._tail is None and lat._tail_name is None
    steps = _spans(seen, "iterate.globals_step")
    assert [e["engine"] for e in steps] == ([] if case == "series"
                                            else ["xla"])
    assert telemetry.counters().get("engine.tail_calls", 0) == before
    assert all(e["engine"] == lat._fast_name
               for e in _spans(seen, "engine.probe"))
    assert not [e for e in seen if e["kind"] == "engine_fallback"]
    assert np.isfinite(np.asarray(lat.state.fields, np.float32)).all()


def _bgk_lattice(storage_dtype=None, nx=128):
    m = get_model("d3q27_BGK")
    shape = (8, 16, nx)
    lat = Lattice(m, shape, dtype=jnp.float32, storage_dtype=storage_dtype,
                  settings={"omega": 1.0, "GravitationX": 1e-5})
    flags = np.full(shape, m.flag_for("BGK"), dtype=np.uint16)
    flags[:, 0, :] = flags[:, -1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    return m, lat


@pytest.mark.parametrize("fails", ["build", "first_call"])
def test_tail_engine_falls_back_to_the_xla_step(monkeypatch, seen, fails):
    """A tail that cannot be built, or whose first call fails, hands the
    trailing step to XLA with one ``engine_fallback`` event (from its tag
    to ``xla``); the tail's one call does not donate, so the state is
    intact and the run goes on to the XLA engine's result.  The failure
    is this lattice's alone: the generic engine's process-wide verdict,
    which the fused chain's rungs read, is not touched, and a later
    lattice probes its own tail."""
    monkeypatch.setattr(pallas_generic, "_mosaic_verdict", {})
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    m, lat_x = _bgk_lattice()
    lat_x.iterate(10)
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    real = Lattice._generic_cand

    def broken(self, *a, **k):
        cand = real(self, *a, **k)

        def build():
            if fails == "build":
                raise ValueError("no slab depth fits")
            it = cand.build()

            def iterate(state, params, niter):
                jax.block_until_ready(state)    # not donated: alive
                raise RuntimeError("scoped vmem exceeded")
            return dataclasses.replace(it, run=iterate)
        return dataclasses.replace(cand, build=build)
    monkeypatch.setattr(Lattice, "_generic_cand", broken)
    _, lat = _bgk_lattice()
    lat.iterate(5)
    monkeypatch.setattr(Lattice, "_generic_cand", real)
    lat.iterate(5)
    assert lat._fast_name.startswith("pallas_d3q[")
    assert lat._tail is None and lat._tail_name is None
    fell, = [e for e in seen if e["kind"] == "engine_fallback"]
    assert (fell["from"], fell["to"]) == (
        "pallas_generic[d3q27_BGK,fuse=1]", "xla")
    assert [e["engine"] for e in _spans(seen, "iterate.globals_step")] \
        == ["xla", "xla"]
    assert pallas_generic.mosaic_ok(m, lat.shape)
    # a later lattice of the model and shape tries its own tail
    _, lat2 = _bgk_lattice()
    lat2.iterate(5)
    assert lat2._tail_name == "pallas_generic[d3q27_BGK,fuse=1]"
    assert len([e for e in seen if e["kind"] == "engine_fallback"]) == 1
    np.testing.assert_allclose(np.asarray(lat.state.fields),
                               np.asarray(lat_x.state.fields),
                               rtol=2e-5, atol=2e-6)
    for k, v in lat_x.get_globals().items():
        np.testing.assert_allclose(lat.get_globals()[k], v, rtol=1e-4,
                                   atol=1e-6, err_msg=f"global {k}")


