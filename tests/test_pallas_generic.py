"""Parity of the registry-driven generic Pallas engine vs the XLA step.

The generic engine (ops/pallas_generic.py) traces every model's OWN stage
functions inside a Pallas band kernel — the round-4 equivalent of the
reference guarantee that its code generator emits a tuned kernel for every
model (reference src/cuda.cu.Rt:81-283).  Because kernel and XLA path run
the SAME physics callables, parity must be essentially exact; these tests
pin it over all eligible 2D models, multi-stage actions, Field stencils,
zonal settings and the ghost-row padded path.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu.core.lattice import Lattice, make_iterate
from tclb_tpu.models import get_model, list_models
from tclb_tpu.ops import pallas_generic
from tclb_tpu.ops.lbm import present_types

# models with enough default-settings stability for a short parity lap;
# the full sweep below covers the rest
_KEY_MODELS = ["d2q9_heat", "d2q9_kuper", "d2q9_pf", "d2q9_adj"]

_SETTINGS = {
    "d2q9_heat": {"nu": 0.05, "InletVelocity": 0.02, "FluidAlfa": 0.05},
    "d2q9_heat_adj": {"nu": 0.05, "InletVelocity": 0.02,
                      "FluidAlfa": 0.05},
    "d2q9_heat_conjugate": {"nu": 0.05, "InletVelocity": 0.02,
                            "FluidAlfa": 0.05, "SolidAlfa": 0.02},
    "d2q9_kuper": {"nu": 0.1, "Temperature": 0.9, "Magic": 0.01,
                   "Density": 1.0},
    "d2q9_kuper_adj": {"nu": 0.1, "Temperature": 0.9, "Magic": 0.01,
                       "Density": 1.0},
    "d2q9_pf": {"nu": 0.1, "Velocity": 0.01},
    "d2q9_adj": {"nu": 0.05, "Velocity": 0.02},
    "d2q9": {"nu": 0.05, "Velocity": 0.02},
    "d2q9_lee": {"nu": 1 / 6, "LiquidDensity": 1.0,
                 "VaporDensity": 0.1, "Beta": 0.02, "Kappa": 0.02,
                 "InitDensity": 1.0, "WallDensity": 1.0},
    "d2q9_pp_MCMP": {"nu": 1 / 6, "nu_g": 1 / 6, "Gc": 1.8,
                     "Gad1": 0.0, "Gad2": 0.0,
                     "Density": 1.0, "Density_dry": 1.0},
    "d2q9_pp_LBL": {"nu": 1 / 6, "Density": 0.5, "T": 0.35},
    "sw": {"nu": 0.05},
}


def _models_of(ndim):
    """The registry's models of this dimension, by name.  Whether the
    generic engine takes one (``supports`` traces the model: 40 s over
    the registry) is asked where a case runs, not where the cases are
    collected: every worker of every run collects these lists, and the
    cases they feed are ``slow``."""
    return [name for name in list_models() if get_model(name).ndim == ndim]


def _paint(m, ny, nx):
    """Generic geometry: collision interior, walls top/bottom, W/E BCs
    when the model declares them, and a second settings zone."""
    coll = "MRT" if "MRT" in m.node_types else "BGK"
    flags = np.full((ny, nx), m.flag_for(coll), dtype=np.uint16)
    if "Wall" in m.node_types:
        flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    if "WVelocity" in m.node_types:
        flags[1:-1, 0] = m.flag_for("WVelocity", coll)
    if "EPressure" in m.node_types:
        flags[1:-1, -1] = m.flag_for("EPressure", coll)
    # a zone stripe exercises zonal-setting gathering
    flags[ny // 4:ny // 2, nx // 4:nx // 2] = m.flag_for(coll, zone=1)
    return flags


def _parity(name, ny=16, nx=64, niter=6, atol=1e-5):
    m = get_model(name)
    assert pallas_generic.supports(m, (ny, nx), jnp.float32), name
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings=_SETTINGS.get(name, {}))
    flags = _paint(m, ny, nx)
    lat.set_flags(flags)
    lat.init()
    present = present_types(m, flags)

    it_p = pallas_generic.make_pallas_iterate(
        m, (ny, nx), jnp.float32, interpret=True, present=present)
    s_p = it_p(jax.tree.map(jnp.copy, lat.state), lat.params, niter)

    it_x = jax.jit(make_iterate(m, present=present),
                   static_argnames=("niter",))
    s_x = it_x(lat.state, lat.params, niter)

    a = np.asarray(s_p.fields)
    b = np.asarray(s_x.fields)
    assert np.isfinite(b).all(), f"{name}: XLA reference went non-finite"
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=atol,
                               err_msg=f"{name} generic-pallas vs XLA")
    assert int(s_p.iteration) == int(s_x.iteration)


@pytest.mark.parametrize("name", _KEY_MODELS)
def test_generic_parity_key_models(name):
    """Fast-lap pin: the VERDICT r3 headline models (multi-lattice heat,
    Field-stencil kuper, 18-plane pf, adjoint-primal adj) match the XLA
    engine through the generic band kernel."""
    _parity(name)


@pytest.mark.slow
@pytest.mark.parametrize("name", [n for n in _models_of(2)
                                  if n not in _KEY_MODELS])
def test_generic_parity_all(name):
    """Every trace-eligible 2D model matches the XLA engine."""
    if not pallas_generic.supports(get_model(name), (16, 64), jnp.float32):
        pytest.skip("not trace-eligible")
    _parity(name)


def test_generic_padded_height():
    """ny % 8 != 0 runs via mirror-ghost padding and stays exact (the
    generalized reach-m scheme of pallas_generic._pad_rows)."""
    _parity("d2q9_heat", ny=20, nx=64)


def test_generic_multistage_field_stencil():
    """kuper's two-stage action (Run + CalcPhi) with the phi +-1 Field
    stencil — the in-band stage pipeline must reproduce the XLA stage
    composition including the inter-stage phi refresh."""
    _parity("d2q9_kuper", ny=24, nx=64, niter=8)


def test_engine_dispatch_generic(monkeypatch):
    """Lattice.iterate auto-selects the generic engine for a model the
    tuned d2q9 kernels don't cover (TCLB_FASTPATH=force exercises the
    dispatch under interpret mode on CPU)."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m = get_model("d2q9_heat")
    lat = Lattice(m, (16, 64), dtype=jnp.float32,
                  settings=_SETTINGS["d2q9_heat"])
    lat.set_flags(_paint(m, 16, 64))
    lat.init()
    lat.iterate(5)
    # the fuse depth comes from the shared traffic planner (>= 2 at this
    # reach), so the tag tracks choose_fuse instead of a pinned constant
    fz = pallas_generic.choose_fuse(m)
    assert fz >= 2
    assert lat._fast_name == f"pallas_generic[d2q9_heat,fuse={fz}]"
    assert np.isfinite(np.asarray(lat.state.fields)).all()
    # globals refreshed by the hybrid's trailing XLA step
    g = lat.get_globals()
    assert "OutFlux" in g


def test_supports_structure():
    m = get_model("d2q9_heat")
    assert pallas_generic.supports(m, (16, 64), jnp.float32)
    assert not pallas_generic.supports(m, (16, 64), jnp.float64)
    assert not pallas_generic.supports(m, (4, 64), jnp.float32)
    # 3D models route to the z-slab engine (since round 4)
    assert pallas_generic.supports(get_model("d3q27_cumulant"),
                                   (16, 16, 64), jnp.float32)
    assert not pallas_generic.supports(get_model("d3q27_cumulant"),
                                       (16, 16, 64), jnp.float64)


def test_inkernel_globals_match_xla():
    """The generic engine's full contract: iterate() returns the LAST
    step's SUM Globals from the in-kernel accumulation (no trailing XLA
    step), matching the XLA engine's reductions (nx=128 — the partial-
    sums output needs whole lanes)."""
    name, ny, nx, niter = "d2q9", 16, 128, 6
    m = get_model(name)
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings=_SETTINGS[name])
    flags = _paint(m, ny, nx)
    flags[1:-1, 2] = m.flag_for("MRT", "Inlet")
    flags[1:-1, -3] = m.flag_for("MRT", "Outlet")
    lat.set_flags(flags)
    lat.init()
    present = present_types(m, flags)

    it_p = pallas_generic.make_pallas_iterate(
        m, (ny, nx), jnp.float32, interpret=True, present=present)
    assert it_p.full_globals
    s_p = it_p(jax.tree.map(jnp.copy, lat.state), lat.params, niter)

    it_x = jax.jit(make_iterate(m, present=present),
                   static_argnames=("niter",))
    s_x = it_x(lat.state, lat.params, niter)
    np.testing.assert_allclose(np.asarray(s_p.fields),
                               np.asarray(s_x.fields), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_p.globals_),
                               np.asarray(s_x.globals_),
                               rtol=1e-4, atol=1e-6)
    assert float(np.abs(np.asarray(s_x.globals_)).sum()) > 0.0, \
        "vacuous: the case must actually accumulate globals"


def test_inkernel_globals_padded_height():
    """Ghost-row padding must not leak mirror/wall rows into the Globals
    (the in-kernel row mask)."""
    name, ny, nx, niter = "d2q9", 20, 128, 5
    m = get_model(name)
    lat = Lattice(m, (ny, nx), dtype=jnp.float32, settings=_SETTINGS[name])
    flags = _paint(m, ny, nx)
    flags[1:-1, 2] = m.flag_for("MRT", "Inlet")
    flags[1:-1, -3] = m.flag_for("MRT", "Outlet")
    lat.set_flags(flags)
    lat.init()
    present = present_types(m, flags)
    it_p = pallas_generic.make_pallas_iterate(
        m, (ny, nx), jnp.float32, interpret=True, present=present)
    s_p = it_p(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    it_x = jax.jit(make_iterate(m, present=present),
                   static_argnames=("niter",))
    s_x = it_x(lat.state, lat.params, niter)
    np.testing.assert_allclose(np.asarray(s_p.globals_),
                               np.asarray(s_x.globals_),
                               rtol=1e-4, atol=1e-6)


def test_control_series_on_fast_path(monkeypatch):
    """A <Control> time series (per-iteration zonal settings) runs the
    generic engine (the series kernel flavor gathers value + _DT planes
    per step) and matches the XLA path exactly: the path of every model
    outside the tuned 2D family, whose band takes ``d2q9``'s own series
    since PR 55 (tests/test_control_band.py) and is kept out of the
    chain here."""
    from tclb_tpu.ops import pallas_d2q9
    monkeypatch.setattr(pallas_d2q9, "covers", lambda *a: False)
    ny, nx, niter = 16, 64, 7
    m = get_model("d2q9")
    series = 0.02 + 0.005 * np.sin(np.arange(11) * 0.7)

    def build():
        lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                      settings=_SETTINGS["d2q9"])
        lat.set_flags(_paint(m, ny, nx))
        lat.init()
        lat.set_setting_series("Velocity", series, zone=0)
        return lat

    monkeypatch.setenv("TCLB_FASTPATH", "0")
    ref = build()
    ref.iterate(niter)

    monkeypatch.setenv("TCLB_FASTPATH", "force")
    fast = build()
    fast.iterate(niter)
    assert fast._fast_name is not None and "pallas_generic" in fast._fast_name
    np.testing.assert_allclose(np.asarray(fast.state.fields),
                               np.asarray(ref.state.fields),
                               rtol=1e-5, atol=1e-6)
    assert int(fast.state.iteration) == int(ref.state.iteration)


def test_control_series_with_inkernel_globals(monkeypatch):
    """The combined series + globals kernel flavor (call_sg): at nx=128
    the engine runs the full contract under a Control series — fields
    AND last-step Globals must match the XLA path.  (With the tuned
    band kept out of the chain, as above; beside it this flavour is the
    one-step tail of a series run: tests/test_tail_engine.py.)"""
    from tclb_tpu.ops import pallas_d2q9
    monkeypatch.setattr(pallas_d2q9, "covers", lambda *a: False)
    ny, nx, niter = 16, 128, 6
    m = get_model("d2q9")
    series = 0.02 + 0.004 * np.sin(np.arange(9) * 0.9)

    def build():
        lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                      settings=_SETTINGS["d2q9"])
        flags = _paint(m, ny, nx)
        flags[1:-1, 2] = m.flag_for("MRT", "Inlet")
        flags[1:-1, -3] = m.flag_for("MRT", "Outlet")
        lat.set_flags(flags)
        lat.init()
        lat.set_setting_series("Velocity", series, zone=0)
        return lat

    monkeypatch.setenv("TCLB_FASTPATH", "0")
    ref = build()
    ref.iterate(niter)

    monkeypatch.setenv("TCLB_FASTPATH", "force")
    fast = build()
    fast.iterate(niter)
    assert "pallas_generic" in (fast._fast_name or "")
    assert getattr(fast._fast, "full_globals", False)
    np.testing.assert_allclose(np.asarray(fast.state.fields),
                               np.asarray(ref.state.fields),
                               rtol=1e-5, atol=1e-6)
    g_ref, g_fast = ref.get_globals(), fast.get_globals()
    for k in g_ref:
        np.testing.assert_allclose(g_fast[k], g_ref[k], rtol=1e-4,
                                   atol=1e-6, err_msg=k)
    assert sum(abs(v) for v in g_ref.values()) > 0.0


def test_action_plan_reach():
    """Stage plan arithmetic: kuper's Run (pull 1 + phi stencil 1) then
    CalcPhi (pointwise) needs a 1-row input halo with CalcPhi running on
    the plain band; heat's single stage pulls reach 1."""
    m = get_model("d2q9_kuper")
    plan, reach = pallas_generic.action_plan(m, "Iteration", fuse=1)
    assert [s for s, _ in plan] == ["BaseIteration", "CalcPhi"]
    # CalcPhi is last (out_ext 0); Run must cover CalcPhi's pointwise
    # read of the f it stores -> out_ext 0 as well; input halo = Run's
    # own reach
    assert plan[-1][1] == 0
    assert reach == plan[0][1] + 1

    m2 = get_model("d2q9_heat")
    plan2, reach2 = pallas_generic.action_plan(m2, "Iteration", fuse=1)
    assert plan2 == [("BaseIteration", 0)]
    assert reach2 == 1


# ------------------------------------------------------------------------- #
# 3D generic engine
# ------------------------------------------------------------------------- #

_3D_SETTINGS = {
    "d3q19_heat": {"nu": 0.05, "Velocity": 0.02, "FluidAlfa": 0.05},
    "d3q19_heat_adj": {"nu": 0.05, "Velocity": 0.02, "FluidAlfa": 0.05},
    "d3q19_heat_adj_art": {"nu": 0.05, "Velocity": 0.02, "FluidAlfa": 0.05},
    "d3q19_heat_adj_prop": {"nu": 0.05, "Velocity": 0.02,
                            "FluidAlfa": 0.05},
    "d3q19_kuper": {"nu": 0.1, "Temperature": 0.9, "Magic": 0.01},
    "d3q19_adj": {"nu": 0.1, "Velocity": 0.02, "Porocity": 0.5},
    "d3q19_les": {"nu": 0.01, "Smag": 0.16},
    "d3q27_cumulant": {"nu": 0.01, "ForceX": 1e-5},
    "d3q27_viscoplastic": {"nu": 0.1},
}


def _parity_3d(name, shape=(6, 16, 128), niter=4):
    m = get_model(name)
    lat = Lattice(m, shape, dtype=jnp.float32,
                  settings=_3D_SETTINGS.get(name, {}))
    coll = "MRT" if "MRT" in m.node_types else "BGK"
    flags = np.full(shape, m.flag_for(coll), dtype=np.uint16)
    flags[:, 0, :] = flags[:, -1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    present = present_types(m, flags)
    it_p = pallas_generic.make_pallas_iterate(
        m, shape, jnp.float32, interpret=True, present=present)
    s_p = it_p(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    it_x = jax.jit(make_iterate(m, present=present),
                   static_argnames=("niter",))
    s_x = it_x(lat.state, lat.params, niter)
    b = np.asarray(s_x.fields)
    assert np.isfinite(b).all(), f"{name}: XLA reference went non-finite"
    np.testing.assert_allclose(np.asarray(s_p.fields), b,
                               rtol=1e-5, atol=1e-5, err_msg=name)
    np.testing.assert_allclose(np.asarray(s_p.globals_),
                               np.asarray(s_x.globals_),
                               rtol=1e-3, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("name", ["d3q19_heat", "d3q19_kuper"])
def test_generic3d_parity_key_models(name):
    """Fast-lap pin: 3D multi-lattice (heat) and Field-stencil (kuper)
    models on the z-slab generic engine."""
    _parity_3d(name)


@pytest.mark.slow
@pytest.mark.parametrize("name", [n for n in _models_of(3)
                                  if n not in ("d3q19_heat", "d3q19_kuper")])
def test_generic3d_parity_all(name):
    """Every trace-eligible 3D model matches the XLA engine."""
    if not pallas_generic.supports_3d(get_model(name), (6, 16, 128),
                                      jnp.float32):
        pytest.skip("not trace-eligible")
    _parity_3d(name)


def test_generic3d_halo_straddle():
    """bz=1 with reach 2 (kuper's field stencil under a fused plan): the
    per-slab halo copies must wrap the periodic boundary slab by slab —
    a block copy starting at (base - R) mod nz would read out of bounds
    (the bug that NaN'd d3q19_kuper at 48x48x256 on TPU)."""
    _parity_3d("d3q19_kuper", shape=(12, 16, 128), niter=4)


@pytest.mark.parametrize("window", [(2, 16), (1, 8)],
                         ids=["whole_planes", "y_bands"])
def test_generic3d_ext_halo_block_is_the_one_chip_kernel(window):
    """The ``ext_halo`` flavour of the slab kernel (one z-block of a
    lattice split over devices: the block as it is, the neighbours'
    ``R1`` slabs as operands of their own, the flag plane extended by
    them) on a whole lattice with its own wrapped slabs handed in as the
    neighbours' is the one-chip kernel bit for bit, fields and Globals:
    at two z bands of whole planes (band 0 reads its lower halo from the
    lower neighbour's slab and its upper one from the block, band 1 the
    other way round) and at bands of one slab cut into two y bands of 8
    rows with 8 wrapped halo rows a side (every window reads both
    neighbours' slabs or neither)."""
    m = get_model("d3q19")
    shape = (4, 16, 128)
    lat = Lattice(m, shape, dtype=jnp.float32,
                  settings={"nu": 0.05, "Velocity": 0.02})
    flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0, :] = flags[:, -1, :] = m.flag_for("Wall")
    flags[:, 1:-1, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, 1:-1, -1] = m.flag_for("EPressure", "MRT")
    flags[:, 1:-1, 2] = m.flag_for("MRT", "Inlet")
    flags[:, 1:-1, -3] = m.flag_for("MRT", "Outlet")
    lat.set_flags(flags)
    lat.init()
    # every slab its own, so that a slab read from the wrong place shows
    noise = np.random.default_rng(0).standard_normal(lat.state.fields.shape)
    state = lat.state.replace(fields=lat.state.fields * jnp.asarray(
        1 + 0.01 * noise, jnp.float32))
    params = lat.params
    build = partial(pallas_generic.make_pallas_iterate_3d, m, shape,
                    interpret=True, window=window)
    want = build()(state, params, 1)
    call, call_g, plan, zonal = build(ext_halo=True)
    assert plan == (*window, 1) and zonal == list(m.zonal_settings)
    f = state.fields
    aux = state.flags.astype(jnp.int32).astype(jnp.float32)[None]
    operands = (
        params.settings, state.iteration[None],
        jnp.concatenate([params.zone_table[m.setting_index[nm]]
                         for nm in zonal]),
        f, f[:, -1:], f[:, :1],
        jnp.concatenate([aux[:, -1:], aux, aux[:, :1]], axis=1))
    fields, g = call_g(*operands)
    np.testing.assert_array_equal(np.asarray(fields),
                                  np.asarray(want.fields))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(want.globals_))
    assert np.all(np.asarray(g)[1:] > 1.0)      # the two fluxes
    if window[1] < shape[1]:
        # the NoGlobals flavour: the same windows, one output less
        np.testing.assert_array_equal(np.asarray(call(*operands)),
                                      np.asarray(want.fields))
    # the flavour is one step of an f32 block
    for refused in (dict(fuse=2), dict(dtype=jnp.bfloat16)):
        with pytest.raises(ValueError, match="ext_halo"):
            build(ext_halo=True, **refused)


def test_sharded_generic_matches_single(monkeypatch):
    """The generic kernel as the sharded building block: a y-sharded
    2-device mesh running d2q9_heat (a model the tuned sharded kernels
    do not cover) matches the single-device engine."""
    import jax
    from tclb_tpu.parallel.mesh import make_mesh
    ny, nx, niter = 32, 64, 9

    monkeypatch.setenv("TCLB_FASTPATH", "0")
    m = get_model("d2q9_heat")
    ref = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings=_SETTINGS["d2q9_heat"])
    flags = _paint(m, ny, nx)
    ref.set_flags(flags)
    ref.init()
    ref.iterate(niter)

    monkeypatch.setenv("TCLB_FASTPATH", "force")
    mesh = make_mesh((ny, nx), devices=jax.devices()[:2],
                     decomposition={"y": 2, "x": 1})
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings=_SETTINGS["d2q9_heat"], mesh=mesh)
    lat.set_flags(flags)
    lat.init()
    lat.iterate(niter)
    assert lat._fast_name is not None and "sharded" in lat._fast_name
    np.testing.assert_allclose(np.asarray(lat.state.fields),
                               np.asarray(ref.state.fields),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------- #
# deep temporal fusion (tier-1): K in {4, 8} bit-exact vs XLA
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("name,K", [
    ("d2q9_heat", 4), ("d2q9_heat", 8),
    # kuper: reach 2/step (the CalcPhi gradient stencil), so fuse=4
    # saturates the 8-row band halo — this IS the fused Run+CalcPhi
    # deep-fusion case (phi rebuilt in-VMEM, no second HBM pass)
    ("d2q9_kuper", 4),
])
def test_fused_deep_bit_exact(name, K):
    """fuse=K band output is BIT-IDENTICAL (assert_array_equal, not
    allclose) to the same engine unfused: the progressive-extension
    windows replay each step's arithmetic exactly, so any reassociation
    or halo slip at the deeper depths fails at == level.  (The engine's
    parity vs the XLA step is the existing allclose contract `_parity`
    pins — the zonal where-chain reassociates by ~1 ulp.)"""
    ny, nx = 16, 64
    m = get_model(name)
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings=_SETTINGS[name])
    flags = _paint(m, ny, nx)
    lat.set_flags(flags)
    lat.init()
    present = present_types(m, flags)

    # K + 2 forces one fused chunk plus remainder single steps
    niter = K + 2
    it_p = pallas_generic.make_pallas_iterate(
        m, (ny, nx), jnp.float32, interpret=True, present=present,
        fuse=K)
    s_p = it_p(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    it_1 = pallas_generic.make_pallas_iterate(
        m, (ny, nx), jnp.float32, interpret=True, present=present,
        fuse=1)
    s_1 = it_1(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    np.testing.assert_array_equal(np.asarray(s_p.fields),
                                  np.asarray(s_1.fields))
    assert int(s_p.iteration) == int(s_1.iteration)
    # and the fused output still matches the XLA step at the engine's
    # established allclose tolerance
    it_x = jax.jit(make_iterate(m, present=present),
                   static_argnames=("niter",))
    s_x = it_x(lat.state, lat.params, niter)
    np.testing.assert_allclose(np.asarray(s_p.fields),
                               np.asarray(s_x.fields),
                               rtol=1e-5, atol=1e-5)


def test_choose_fuse_deep_depths():
    """The planner now extends past 2: reach-1 models saturate FUSE_MAX
    (8) and kuper's reach-2 plan caps at 4 (reach 8 == the band halo)."""
    assert pallas_generic.choose_fuse(get_model("d2q9_heat")) == 8
    assert pallas_generic.choose_fuse(get_model("d2q9_kuper")) == 4


# --------------------------------------------------------------------- #
# precision ladder: bf16 storage through the generic engines
# --------------------------------------------------------------------- #


def test_storage_dtype_is_opt_in():
    """Never silently narrowed: the default Lattice stores in the
    compute dtype, and non-float / widening storage dtypes are
    rejected up front."""
    m = get_model("d2q9_heat")
    lat = Lattice(m, (16, 64), dtype=jnp.float32,
                  settings=_SETTINGS["d2q9_heat"])
    assert lat.storage_dtype == jnp.dtype(jnp.float32)
    assert lat.state.fields.dtype == jnp.dtype(jnp.float32)
    with pytest.raises(ValueError, match="storage_dtype"):
        Lattice(m, (16, 64), dtype=jnp.float32, storage_dtype=jnp.int8,
                settings=_SETTINGS["d2q9_heat"])
    with pytest.raises(ValueError, match="storage_dtype"):
        Lattice(m, (16, 64), dtype=jnp.float32,
                storage_dtype=jnp.float64,
                settings=_SETTINGS["d2q9_heat"])


def test_storage_dtype_bf16_xla_close_to_f32():
    """bf16 storage on the XLA path: fields stay bf16 across iterate,
    compute happens in f32 (error stays at bf16-rounding scale instead
    of compounding catastrophically)."""
    m = get_model("d2q9_heat")

    def run(storage_dtype):
        lat = Lattice(m, (16, 64), dtype=jnp.float32,
                      settings=_SETTINGS["d2q9_heat"],
                      storage_dtype=storage_dtype)
        lat.set_flags(_paint(m, 16, 64))
        lat.init()
        lat.iterate(20)
        return lat

    ref = run(None)
    alt = run(jnp.bfloat16)
    assert alt.state.fields.dtype == jnp.dtype(jnp.bfloat16)
    # compare in the raw representation: the bf16 rung defaults to
    # shifted at-rest storage (f_i - w_i), so the raw stacks are the
    # representation-independent physics
    a = alt.fields_raw()
    b = ref.fields_raw()
    assert np.isfinite(a).all()
    denom = max(float(np.max(np.abs(b))), 1e-30)
    assert float(np.max(np.abs(a - b))) / denom < 2e-2


def test_storage_dtype_bf16_band_matches_xla_cast_path():
    """The generic band kernel under bf16 storage (widen-on-read,
    f32 accumulate, narrow-on-write) matches the XLA narrowed-carry
    reference bit-for-bit: both paths run f32 arithmetic between
    identical bf16 round trips."""
    m = get_model("d2q9_heat")
    lat = Lattice(m, (16, 64), dtype=jnp.float32,
                  settings=_SETTINGS["d2q9_heat"],
                  storage_dtype=jnp.bfloat16)
    flags = _paint(m, 16, 64)
    lat.set_flags(flags)
    lat.init()
    present = present_types(m, flags)
    niter = 6

    it_p = pallas_generic.make_pallas_iterate(
        m, (16, 64), jnp.bfloat16, interpret=True, present=present)
    s_p = it_p(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    assert s_p.fields.dtype == jnp.dtype(jnp.bfloat16)

    it_x = jax.jit(make_iterate(m, present=present,
                                storage_dtype=jnp.bfloat16),
                   static_argnames=("niter",))
    s_x = it_x(lat.state, lat.params, niter)
    np.testing.assert_array_equal(
        np.asarray(s_p.fields, dtype=np.float32),
        np.asarray(s_x.fields, dtype=np.float32))


def test_bf16_dispatch_skips_f32_only_kernels(monkeypatch, tmp_path):
    """Engine dispatch under bf16 storage routes past the f32-only tuned
    d2q9 kernels to a narrowed-capable engine, and stamps the storage
    dtype on iterate spans."""
    import json as _json
    from tclb_tpu import telemetry
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m = get_model("d2q9")
    lat = Lattice(m, (16, 64), dtype=jnp.float32,
                  settings=_SETTINGS["d2q9"],
                  storage_dtype=jnp.bfloat16)
    lat.set_flags(_paint(m, 16, 64))
    lat.init()
    trace = tmp_path / "t.jsonl"
    telemetry.enable(str(trace))
    try:
        lat.iterate(2)
    finally:
        telemetry.disable()
    assert lat._fast_name is not None
    assert "generic" in lat._fast_name   # tuned d2q9 kernels are f32-only
    evts = [_json.loads(x) for x in trace.read_text().splitlines()
            if x.strip()]
    spans = [e for e in evts
             if e.get("kind") == "span" and e.get("name") == "iterate"]
    assert spans and spans[0]["storage_dtype"] == "bfloat16"
