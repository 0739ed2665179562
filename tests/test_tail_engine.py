"""The step a hybrid engine leaves for the Globals: the tail engine.

A hybrid engine (``pallas_d3q``, ``pallas_2d``, ``pallas_resident``,
``pallas_sharded``) advances ``niter - 1`` steps and leaves the last one,
which reduces the Globals, to another engine: the generic Pallas engine's
one-step flavour with in-kernel globals wherever it takes the case
(``Lattice._build_tail``; on a y-split 2D mesh and on a z-split 3D one on
each shard, the partial sums reduced across it:
``parallel/halo.make_sharded_pallas_tail``), else the XLA step.  These tests force the
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


def _two_chip_mesh(shape, split):
    """``shape`` split along the mesh axis ``split`` over two of the
    CPU's devices; None for no ``split``."""
    from tclb_tpu.parallel.mesh import make_mesh
    if not split:
        return None
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    return make_mesh(shape, devices=jax.devices()[:2],
                     decomposition={"z": 1, "y": 1, "x": 1, split: 2})


def _cumulant_lattice(shape, storage_dtype=None, split=None):
    """d3q27_cumulant between walls in y with a turbulent inlet and a
    pressure outlet in x: the synthetic-turbulence coupling planes
    (``SynthT*``, which the ``<SyntheticTurbulence>`` handler fills in a
    run) and the averages (``avg*``) all move, and ``Flux`` is reduced.
    ``split``: the mesh axis the lattice is split along, over two of the
    CPU's devices (the planes differ from slab to slab, so a shard that
    read its own slabs for a neighbour's would show)."""
    m = get_model("d3q27_cumulant")
    lat = Lattice(m, shape, dtype=jnp.float32, storage_dtype=storage_dtype,
                  settings={"nu": 0.05, "Velocity": 0.03,
                            "Turbulence": 0.01},
                  mesh=_two_chip_mesh(shape, split))
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


def _karman_on_a_mesh(chips, ny=64, axis="y"):
    """``_karman_lattice(ny)`` (inlet, outlet, walls, a box, the
    objective columns the Globals are reduced on) split over ``chips``
    of the CPU's devices along ``axis``."""
    from tclb_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    m, ref = _karman_lattice(ny)
    split = {"y": 1, "x": 1, axis: chips}
    mesh = make_mesh((ny, 128), devices=jax.devices()[:chips],
                     decomposition=split)
    lat = Lattice(m, (ny, 128), dtype=jnp.float32,
                  settings={"nu": 0.05, "Velocity": 0.03}, mesh=mesh)
    lat.set_flags(np.asarray(ref.state.flags))
    lat.init()
    return m, lat


def _mesh_tags(chips):
    split = {"y": chips, "x": 1}
    return (f"pallas_sharded[{split},fuse=2]",
            f"pallas_sharded[generic,{split},fuse=1,globals]")


_ZSPLIT = {"z": 2, "y": 1, "x": 1}
_MESH_3D_TAIL = f"pallas_sharded[generic,{_ZSPLIT},fuse=1,globals]"
_MESH_3D_CASES = {
    # a z-split 3D mesh of two chips, shards of whole planes and of
    # planes tiled in y: the tail is the slab kernel on each shard as it
    # is, the neighbour's slab a side an operand of its own
    "mesh_3d": ((16, 16, 128), f"pallas_sharded[{_ZSPLIT},fuse=4]"),
    # shards of two slabs: the fused engine's plan is K = 1 on bands of
    # 128 rows, the tail's bands of 32 rows as on one chip
    "mesh_3d_y_tiled": ((4, 256, 256),
                        f"pallas_sharded[{_ZSPLIT},fuse=1,by=128]"),
}

_TAIL_LATTICES = {
    **{case: (lambda shape=shape: _cumulant_lattice(shape), fused, tail,
              ("Flux",))
       for case, (shape, fused, tail) in _TAIL_CASES.items()},
    **{case: (lambda shape=shape: _cumulant_lattice(shape, split="z"),
              fused, _MESH_3D_TAIL, ("Flux",))
       for case, (shape, fused) in _MESH_3D_CASES.items()},
    # a y-split 2D mesh: the tail is the same kernel on each shard under
    # shard_map, its partial sums reduced across the mesh
    **{f"mesh{chips}x1": (lambda chips=chips: _karman_on_a_mesh(chips),
                          *_mesh_tags(chips),
                          ("PressureLoss", "OutletFlux", "InletFlux"))
       for chips in (4, 2)},
    # one chip under a <Control> series (PR 55): the tuned band takes
    # the series' values as scalars, and the tail is the generic band's
    # series flavour, which assembles that one step's zonal planes
    "series": (lambda: _with_a_series(*_karman_lattice(64)),
               "pallas_2d[d2q9,fuse=2]", "pallas_generic[d2q9,fuse=1]",
               ("PressureLoss", "OutletFlux", "InletFlux")),
}


@pytest.mark.parametrize("case", list(_TAIL_LATTICES))
def test_tail_engine_matches_the_xla_step(monkeypatch, seen, case):
    """``Lattice.iterate(n)`` on a hybrid engine, whose last step runs on
    the generic Pallas engine's in-kernel-globals flavour, against the
    XLA engine's ``n`` steps: every storage plane (``avg*`` and
    ``SynthT*`` among them), the Globals, the iteration; and what the run
    says of itself: the engine on ``iterate.globals_step``, one
    ``engine.tail_calls`` a call, no fallback.  On a mesh (4 and 2 of the
    CPU's devices in 2D, 2 in 3D: shards of whole planes and y-tiled
    ones) both sides are sharded: the XLA side is the sharded XLA step
    this tail replaces.  (The one-chip 2D case without a series is
    ``test_engine_dispatch_matches_xla``; under one, both calls of its
    16 values, it is here.)"""
    lattice, fused, tail, reduced = _TAIL_LATTICES[case]
    niter, calls = 5, 2
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    m, lat_x = lattice()
    wanted = []
    for _ in range(calls):
        lat_x.iterate(niter)
        wanted.append(lat_x.get_globals())
    assert lat_x._fast_name is None and lat_x._tail_name is None
    assert not _spans(seen, "iterate.globals_step")
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    _, lat_f = lattice()
    before = telemetry.counters().get("engine.tail_calls", 0)
    under_a_series = telemetry.counters().get("engine.series_steps", 0)
    for want in wanted:
        lat_f.iterate(niter)
        got = lat_f.get_globals()
        for name in reduced:
            assert want[name] != 0
            np.testing.assert_allclose(got[name], want[name], rtol=1e-4,
                                       err_msg=name)
    fx, ff = np.asarray(lat_x.state.fields), np.asarray(lat_f.state.fields)
    np.testing.assert_allclose(ff, fx, rtol=2e-5, atol=2e-6)
    for plane in m.storage_names:
        if plane.startswith(("avg", "SynthT")):
            assert np.abs(fx[m.storage_index[plane]]).max() > 0, plane
    assert int(lat_f.state.iteration) == niter * calls
    # the tuned 2D engines, sharded or not, are the fused engines not
    # probed (a plan at Mosaic's default limit)
    _says_tail(seen, lat_f, fused, tail, calls, fused_probed=m.ndim == 3)
    assert telemetry.counters()["engine.tail_calls"] - before == calls
    if case == "series":
        # the series' account: every step of both engines counted, the
        # table, and the planes made for a step because of it (none on
        # the band; the tail's value and _DT planes of two zonal
        # settings)
        assert telemetry.counters()["engine.series_steps"] \
            - under_a_series == niter * calls
        for span, planes in ((_spans(seen, "iterate.fused")[-1], 0),
                             (_spans(seen, "iterate.globals_step")[-1], 4)):
            assert (span["series_rows"], span["series_horizon"],
                    span["series_bytes_per_step"]) \
                == (1, 16, planes * 64 * 128 * 4)
    if lat_f.mesh is not None:
        # replicated, as the sharded XLA step returns them
        assert lat_f.state.globals_.sharding.is_fully_replicated
        build, = [e for e in _spans(seen, "engine.build")
                  if e["selected"] == fused]
        assert build["tail"] == tail


def _tail_on_a_y_split_3d_mesh():
    # the slab kernels keep the (ny, nx) plane whole (no sharded fused
    # engine either)
    return _cumulant_lattice((8, 16, 128), split="y")[1], None


def _tail_on_shards_of_odd_rows():
    # 48 rows over 4 chips: shards of 12 rows, no multiple of 8 (no
    # sharded fused engine either: the XLA engine runs every step)
    return _karman_on_a_mesh(4, ny=48)[1], None


def _tail_on_an_x_split():
    # the kernels keep the lane plane whole
    return _karman_on_a_mesh(2, axis="x")[1], None


def _with_a_series(m, lat):
    lat.set_setting_series(
        "Velocity", 0.03 + 0.001 * np.sin(np.arange(16) * 0.3), zone=0)
    return m, lat


def _tail_of_a_refused_dtype(monkeypatch):
    # bfloat16 storage, which the tuned 3D engine takes and (here) the
    # generic engine refuses
    monkeypatch.setattr(pallas_generic, "STORAGE_DTYPES", (jnp.float32,))
    return _bgk_lattice(jnp.bfloat16)[1], "pallas_d3q"


def _tail_of_half_a_lane_tile():
    # 64 columns: the generic kernel reduces no Globals on them
    return _bgk_lattice(nx=64)[1], "pallas_d3q"


def _tail_on_a_mesh_with_a_series():
    # the sharded engines take no <Control> series: XLA runs every step
    return _with_a_series(*_karman_on_a_mesh(4))[1], "pallas_sharded"


@pytest.mark.parametrize("case", ["mesh_3d_y_split", "mesh_odd_rows",
                                  "mesh_x_split",
                                  "mesh_series", "refused_dtype",
                                  "no_kernel_globals"])
def test_tail_engine_stays_off(monkeypatch, seen, case):
    """Where the generic engine's one-step flavour does not apply, the
    trailing step stays the XLA step: on a mesh the sharded tail cannot
    take (a 3D one split in y, shards of 12 rows, a split in x: none of
    them has a sharded fused engine either, and XLA runs every step),
    with a ``<Control>`` series on a mesh (the sharded engines take
    none and the XLA engine runs the whole call: no trailing step at
    all; on one chip the tail runs its series flavour,
    ``test_tail_engine_matches_the_xla_step[series]``), with a storage
    dtype the generic
    engine refuses, and on a shape whose generic kernel has no flavour
    that reduces Globals."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    before = telemetry.counters().get("engine.tail_calls", 0)
    lat, family = (_tail_of_a_refused_dtype(monkeypatch)
                   if case == "refused_dtype"
                   else {"mesh_3d_y_split": _tail_on_a_y_split_3d_mesh,
                         "mesh_odd_rows": _tail_on_shards_of_odd_rows,
                         "mesh_x_split": _tail_on_an_x_split,
                         "mesh_series": _tail_on_a_mesh_with_a_series,
                         "no_kernel_globals": _tail_of_half_a_lane_tile,
                         }[case]())
    lat.iterate(6)
    if family is None:
        assert lat._fast_name is None
    else:
        assert lat._fast_name.startswith(family + "[")
    assert lat._tail is None and lat._tail_name is None
    steps = _spans(seen, "iterate.globals_step")
    assert [e["engine"] for e in steps] == (
        ["xla"] if case in ("refused_dtype", "no_kernel_globals")
        else [])
    assert telemetry.counters().get("engine.tail_calls", 0) == before
    assert all(e["engine"] == lat._fast_name
               for e in _spans(seen, "engine.probe"))
    assert not [e for e in seen if e["kind"] == "engine_fallback"]
    assert np.isfinite(np.asarray(lat.state.fields, np.float32)).all()


def _bgk_lattice(storage_dtype=None, nx=128, split=None):
    m = get_model("d3q27_BGK")
    shape = (8, 16, nx)
    lat = Lattice(m, shape, dtype=jnp.float32, storage_dtype=storage_dtype,
                  settings={"omega": 1.0, "GravitationX": 1e-5},
                  mesh=_two_chip_mesh(shape, split))
    flags = np.full(shape, m.flag_for("BGK"), dtype=np.uint16)
    flags[:, 0, :] = flags[:, -1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    return m, lat


_FALLBACK_CASES = {
    # the lattice, the method of Lattice that names the tail's candidate,
    # the fused family, the tail's tag
    "chip": (lambda: _bgk_lattice(), "_generic_cand", "pallas_d3q",
             "pallas_generic[d3q27_BGK,fuse=1]"),
    "mesh": (lambda: _karman_on_a_mesh(4), "_sharded_tail_cand",
             "pallas_sharded", _mesh_tags(4)[1]),
    "mesh_3d": (lambda: _bgk_lattice(split="z"), "_sharded_tail_cand",
                "pallas_sharded", _MESH_3D_TAIL),
}


@pytest.mark.parametrize("where,fails", [
    (where, fails) for where in _FALLBACK_CASES
    for fails in ("first_call", "build")
    # on a 3D mesh the candidate is the 2D mesh's, built alike: the
    # first call alone, which runs the 3D program on the live state
    if (where, fails) != ("mesh_3d", "build")])
def test_tail_engine_falls_back_to_the_xla_step(monkeypatch, seen, where,
                                                fails):
    """A tail that cannot be built, or whose first call fails, hands the
    trailing step to XLA with one ``engine_fallback`` event (from its tag
    to ``xla``); the tail's one call does not donate, so the state is
    intact and the run goes on to the XLA engine's result: on one chip,
    and on a 2D and a 3D mesh, where the step that takes over is the
    sharded XLA step.  The failure is this lattice's alone: the generic engine's
    process-wide verdict, which the fused chain's rungs read, is not
    touched, and a later lattice probes its own tail."""
    lattice, names_cand, family, tail = _FALLBACK_CASES[where]
    monkeypatch.setattr(pallas_generic, "_mosaic_verdict", {})
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    m, lat_x = lattice()
    lat_x.iterate(10)
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    real = getattr(Lattice, names_cand)

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
    monkeypatch.setattr(Lattice, names_cand, broken)
    _, lat = lattice()
    lat.iterate(5)
    monkeypatch.setattr(Lattice, names_cand, real)
    lat.iterate(5)
    assert lat._fast_name.startswith(family + "[")
    assert lat._tail is None and lat._tail_name is None
    fell, = [e for e in seen if e["kind"] == "engine_fallback"]
    assert (fell["from"], fell["to"]) == (tail, "xla")
    assert [e["engine"] for e in _spans(seen, "iterate.globals_step")] \
        == ["xla", "xla"]
    assert all(pallas_generic._mosaic_verdict.values())
    # a later lattice of the model and shape tries its own tail
    _, lat2 = lattice()
    lat2.iterate(5)
    assert lat2._tail_name == tail
    assert len([e for e in seen if e["kind"] == "engine_fallback"]) == 1
    np.testing.assert_allclose(np.asarray(lat.state.fields),
                               np.asarray(lat_x.state.fields),
                               rtol=2e-5, atol=2e-6)
    for k, v in lat_x.get_globals().items():
        np.testing.assert_allclose(lat.get_globals()[k], v, rtol=1e-4,
                                   atol=1e-6, err_msg=f"global {k}")
