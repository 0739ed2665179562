"""Parity of the 3D fused Pallas kernel (ops/pallas_d3q.py) vs the XLA
step, for the d3q27 BGK and cumulant models — same contract as
tests/test_pallas.py pins for d2q9."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu.core.lattice import Lattice
from tclb_tpu.models import get_model
from tclb_tpu.ops import pallas_d3q

# the pre-existing single-step parity tests stay in the full-coverage
# (slow) job; the fused-K bit-exactness tests at the bottom are tier-1 —
# the acceptance contract of the multi-step kernel is CPU-checkable
slow = pytest.mark.slow

# (nz, ny, nx) — small for CPU interpret mode; on a real TPU backend the
# lane dimension must be tile-aligned (nx % 128) or supports() rejects it
# and the parity tests would test nothing
SHAPE = (8, 16, 128) if jax.default_backend() == "tpu" else (8, 16, 64)


def _channel_flags(m, shape, wall_axis=1):
    flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
    if wall_axis == 1:
        flags[:, 0, :] = m.flag_for("Wall")
        flags[:, -1, :] = m.flag_for("Wall")
    else:
        flags[0] = m.flag_for("Wall")
        flags[-1] = m.flag_for("Wall")
    return flags


def _compare(lat, it_pallas, niter=10, rtol=2e-5, atol=2e-6):
    s_p = it_pallas(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    # explicit XLA step: lat.iterate would auto-select the Pallas
    # path on TPU, making the comparison vacuous there
    lat.state = lat._iterate(lat.state, lat.params, niter)
    a = np.asarray(lat.state.fields)
    b = np.asarray(s_p.fields)
    assert np.isfinite(b).all()
    np.testing.assert_allclose(b, a, rtol=rtol, atol=atol)
    assert int(s_p.iteration) == int(lat.state.iteration)


@slow
def test_supports():
    m = get_model("d3q27_BGK")
    assert pallas_d3q.supports(m, SHAPE, jnp.float32)
    assert not pallas_d3q.supports(m, SHAPE, jnp.float64)
    assert not pallas_d3q.supports(m, (16, 64), jnp.float32)
    assert pallas_d3q.supports(get_model("d3q19"), SHAPE, jnp.float32)
    assert not pallas_d3q.supports(get_model("d3q19_heat"), SHAPE,
                                   jnp.float32)
    assert pallas_d3q.supports(get_model("d3q27_cumulant"), SHAPE,
                               jnp.float32)


@slow
def test_present_types():
    m = get_model("d3q27_BGK")
    flags = _channel_flags(m, SHAPE)
    p = pallas_d3q.present_types(m, flags)
    assert "Wall" in p and "MRT" in p
    assert "EPressure" not in p


@pytest.mark.parametrize("name", ["d3q27_BGK", "d3q27_BGK_galcor"])
@slow
def test_bgk_forced_channel(name):
    m = get_model(name)
    lat = Lattice(m, SHAPE, dtype=jnp.float32,
                  settings={"nu": 0.05, "GravitationX": 1e-5})
    flags = _channel_flags(m, SHAPE)
    lat.set_flags(flags)
    lat.init()
    it = pallas_d3q.make_pallas_iterate(
        m, SHAPE, present=pallas_d3q.present_types(m, flags))
    _compare(lat, it)


@pytest.mark.parametrize("name,extra", [
    ("d3q19", {"S_high": 1.0}),
    ("d3q19", {"S_high": 1.3}),
    ("d3q19_les", {"Smag": 0.17}),
])
@slow
def test_d3q19_forced_channel(name, extra):
    """19-velocity family through the generalized z-slab kernel: MRT with
    free high-moment rates and the Smagorinsky LES variant."""
    m = get_model(name)
    lat = Lattice(m, SHAPE, dtype=jnp.float32,
                  settings={"nu": 0.05, "GravitationX": 1e-5, **extra})
    flags = _channel_flags(m, SHAPE)
    lat.set_flags(flags)
    lat.init()
    it = pallas_d3q.make_pallas_iterate(
        m, SHAPE, present=pallas_d3q.present_types(m, flags))
    _compare(lat, it)


@slow
def test_d3q19_faces():
    m = get_model("d3q19")
    lat = Lattice(m, SHAPE, dtype=jnp.float32,
                  settings={"nu": 0.05, "Velocity": 0.02})
    flags = np.full(SHAPE, m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0, :] = m.flag_for("Wall")
    flags[:, -1, :] = m.flag_for("Wall")
    flags[:, :, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, :, -1] = m.flag_for("EPressure", "MRT")
    lat.set_flags(flags)
    lat.init()
    it = pallas_d3q.make_pallas_iterate(m, SHAPE)
    _compare(lat, it)


@slow
def test_bgk_faces_and_symmetry():
    m = get_model("d3q27_BGK")
    lat = Lattice(m, SHAPE, dtype=jnp.float32,
                  settings={"nu": 0.05, "Velocity": 0.02})
    flags = np.full(SHAPE, m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0, :] = m.flag_for("SSymmetry")
    flags[:, -1, :] = m.flag_for("NSymmetry")
    flags[:, :, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, :, -1] = m.flag_for("EPressure", "MRT")
    lat.set_flags(flags)
    lat.init()
    # full case set (present=None): every declared type must be buildable
    it = pallas_d3q.make_pallas_iterate(m, SHAPE)
    _compare(lat, it)


@slow
def test_cumulant_forced_channel_with_buffer():
    m = get_model("d3q27_cumulant")
    lat = Lattice(m, SHAPE, dtype=jnp.float32,
                  settings={"nu": 0.05, "ForceX": 1e-5, "nubuffer": 0.2,
                            "GalileanCorrection": 1.0})
    flags = _channel_flags(m, SHAPE)
    # a buffer (sponge) layer near the outlet exercises the omega select
    flags[:, :, -8:] |= m.flag_for("Buffer")
    lat.set_flags(flags)
    lat.init()
    it = pallas_d3q.make_pallas_iterate(
        m, SHAPE, present=pallas_d3q.present_types(m, flags))
    _compare(lat, it)


@slow
def test_cumulant_turbulent_inlet_and_averages():
    m = get_model("d3q27_cumulant")
    lat = Lattice(m, SHAPE, dtype=jnp.float32,
                  settings={"nu": 0.05, "Velocity": 0.03,
                            "Turbulence": 0.01})
    flags = _channel_flags(m, SHAPE)
    flags[:, 1:-1, 0] = m.flag_for("WVelocityTurbulent", "MRT")
    flags[:, 1:-1, -1] = m.flag_for("EPressure", "MRT")
    lat.set_flags(flags)
    lat.init()
    # fill the SynthT coupling planes with a deterministic fluctuation
    # field (normally the <SyntheticTurbulence> handler does this)
    rng = np.random.default_rng(0)
    fields = np.array(lat.state.fields)
    for nm in ("SynthTX", "SynthTY", "SynthTZ"):
        fields[m.storage_index[nm]] = rng.standard_normal(SHAPE)
    lat.state = lat.state.replace(fields=jnp.asarray(fields))
    it = pallas_d3q.make_pallas_iterate(
        m, SHAPE, present=pallas_d3q.present_types(m, flags))
    _compare(lat, it)
    # averages accumulated: avgU nonzero after 10 steps of driven flow
    assert np.abs(np.asarray(
        lat.state.fields[m.storage_index["avgUX"]])).max() > 1e-6


# --------------------------------------------------------------------- #
# fused-K bit-exactness (tier-1: runs in interpret mode on CPU)
# --------------------------------------------------------------------- #

# nz=12 is NOT divisible by bz*K for (bz=4, K=2) etc., exercising the
# remainder fuse=1 steps and the wrapped-halo modular indexing
FUSED_SHAPE = (12, 8, 64)


def _fused_lat(name):
    m = get_model(name)
    sett = {"nu": 0.05, "GravitationX": 1e-5}
    if name == "d3q27_cumulant":
        sett = {"nu": 0.05, "ForceX": 1e-5}
    lat = Lattice(m, FUSED_SHAPE, dtype=jnp.float32, settings=sett)
    flags = np.full(FUSED_SHAPE, m.flag_for("MRT"), dtype=np.uint16)
    # walls on z-edge planes: boundary nodes sit INSIDE the fused
    # kernel's wrapped halo reach, so a halo-handling bug shows up as a
    # physics difference rather than a silent stale read
    flags[0] = m.flag_for("Wall")
    flags[-1] = m.flag_for("Wall")
    flags[:, 0, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    return m, lat, flags


def _pairing_edges(K, rests):
    """Step counts at the edges of the two-call loop body: 0 to 4 fused
    calls (no loop, one call, one pair unrolled whole, a one-trip loop
    and an odd call, a loop of two trips), each with ``rests`` steps
    left over.  A case is a compile of its own in interpret mode, so the
    whole-plane test takes the remainders 0 and K - 1 and the y-tiled
    one the remainder 1: the loops are the same code in both."""
    return [K * fused + rest for fused in range(5) for rest in rests]


# niter=5: for K=2 -> 2 fused calls + 1 remainder step; for K=4 ->
# 1 fused call + 1 remainder
@pytest.mark.parametrize("name,K,niter", [
    (name, K, 5) for K in (1, 2, 4) for name in ("d3q19", "d3q27_cumulant")
] + [("d3q19", 3, niter) for niter in _pairing_edges(3, (0, 2))])
def test_fused_bit_exact_vs_xla(name, K, niter):
    """fuse=K output is BIT-IDENTICAL to the XLA path (not allclose):
    the kernel spells rho/u/collision exactly as the model does, and the
    progressive-extension windows must reproduce each step's values
    exactly — any reassociation or halo slip fails at == level.  Two
    calls a loop body and the odd call after the loop are the same calls
    in the same order, whatever the count."""
    m, lat, flags = _fused_lat(name)
    it = pallas_d3q.make_pallas_iterate(
        m, FUSED_SHAPE, present=pallas_d3q.present_types(m, flags),
        fuse=K)
    s_p = it(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    s_x = lat._iterate(lat.state, lat.params, niter)
    np.testing.assert_array_equal(np.asarray(s_p.fields),
                                  np.asarray(s_x.fields))
    assert int(s_p.iteration) == int(s_x.iteration) == niter


def test_fused_bz_override_indivisible():
    """Explicit fuse_bz that leaves nz % (bz*K) != 0 still bit-matches:
    the band grid covers nz by bz-slabs; K only widens halos."""
    m, lat, flags = _fused_lat("d3q19")
    it = pallas_d3q.make_pallas_iterate(
        m, FUSED_SHAPE, present=pallas_d3q.present_types(m, flags),
        fuse=2, fuse_bz=2)
    s_p = it(jax.tree.map(jnp.copy, lat.state), lat.params, 4)
    s_x = lat._iterate(lat.state, lat.params, 4)
    np.testing.assert_array_equal(np.asarray(s_p.fields),
                                  np.asarray(s_x.fields))


def test_choose_fuse_planner():
    """The shared planner proposes K>=2 at the production bench shape
    (that is the tentpole's whole point) and its config passes its own
    VMEM predicate."""
    m = get_model("d3q19")
    cfg = pallas_d3q.fused_cfg(m, (48, 48, 256))
    assert cfg is not None
    bz, K = cfg
    assert K >= 2
    assert pallas_d3q._fused_fits(m, 48, 48, 256, bz, K)
    # fused traffic must beat the single-step engine's model
    assert pallas_d3q._fused_cost(m, bz, K) \
        < pallas_d3q._base_cost(m, 48, 48, 256)


# niter=9: one fused chunk + one remainder step; 15: seven steps over,
# the only depth whose remainder loop has whole pairs (three and an odd)
@pytest.mark.parametrize("name,niter", [
    ("d3q19", 9), ("d3q27_cumulant", 9), ("d3q19", 15)])
def test_fused_bit_exact_K8(name, niter):
    """fuse=8 (the raised FUSE_MAX) stays bit-identical to the XLA step.
    Needs nz >= 2*K halo slabs, so this runs on a taller domain than
    FUSED_SHAPE."""
    shape = (16, 8, 64)
    m = get_model(name)
    sett = {"nu": 0.05, "GravitationX": 1e-5}
    if name == "d3q27_cumulant":
        sett = {"nu": 0.05, "ForceX": 1e-5}
    lat = Lattice(m, shape, dtype=jnp.float32, settings=sett)
    flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
    flags[0] = flags[-1] = m.flag_for("Wall")
    flags[:, 0, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    it = pallas_d3q.make_pallas_iterate(
        m, shape, present=pallas_d3q.present_types(m, flags), fuse=8)
    s_p = it(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    s_x = lat._iterate(lat.state, lat.params, niter)
    np.testing.assert_array_equal(np.asarray(s_p.fields),
                                  np.asarray(s_x.fields))
    assert int(s_p.iteration) == int(s_x.iteration)


def test_fused_cfg_engages_at_bench_shape():
    """The planner selects K>=2 for BOTH tuned 3D families at the bench
    shape 48x48x256 — the d3q27(_cumulant) non-engagement this PR fixes
    (the VMEM predicate priced the cumulant's collision temporaries as
    if every plane were resident per-q at full K depth)."""
    shape = (48, 48, 256)
    for name in ("d3q19", "d3q27_cumulant"):
        cfg, why = pallas_d3q.fused_cfg_explain(get_model(name), shape)
        assert cfg is not None and why is None, (name, why)
        assert cfg[1] >= 2, (name, cfg)
    # bf16 storage halves the field-plane VMEM term, so the planner may
    # only go deeper, never shallower
    for name in ("d3q19", "d3q27_cumulant"):
        cfg32, _ = pallas_d3q.fused_cfg_explain(get_model(name), shape)
        cfg16, _ = pallas_d3q.fused_cfg_explain(get_model(name), shape,
                                                itemsize=2)
        assert cfg16 is not None
        assert cfg16[0] * cfg16[1] >= cfg32[0] * cfg32[1]


def test_fused_cfg_explain_reasons():
    """Rejections carry the failing predicate term, so single-step
    demotion can never recur silently (the d3q27 bench-tag regression
    this PR closes)."""
    cfg, why = pallas_d3q.fused_cfg_explain(get_model("d3q19"),
                                            (2, 8, 128))
    assert cfg is None and why.startswith("vmem")
    # plain d3q27 (BGK) is outside the tuned family
    cfg, why = pallas_d3q.fused_cfg_explain(get_model("d3q27"),
                                            (48, 48, 256))
    assert cfg is None and why.startswith("unsupported")


def test_fused_rejected_event(monkeypatch, tmp_path):
    """When dispatch demotes the tuned 3D engine to fuse=1, the trace
    carries a fused_rejected event naming the failing predicate term."""
    import json
    from tclb_tpu import telemetry
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m = get_model("d3q19")
    lat = Lattice(m, (2, 8, 64), dtype=jnp.float32,
                  settings={"nu": 0.05, "GravitationX": 1e-5})
    flags = np.full((2, 8, 64), m.flag_for("MRT"), dtype=np.uint16)
    lat.set_flags(flags)
    lat.init()
    trace = tmp_path / "t.jsonl"
    telemetry.enable(str(trace))
    try:
        lat.iterate(1)
    finally:
        telemetry.disable()
    evts = [json.loads(x) for x in trace.read_text().splitlines()
            if x.strip()]
    rej = [e for e in evts if e.get("kind") == "fused_rejected"]
    assert rej, "demoted fused engine must emit fused_rejected"
    assert rej[0]["engine"] == "pallas_d3q"
    assert rej[0]["model"] == "d3q19"
    assert rej[0]["reason"].startswith("vmem")


# --------------------------------------------------------------------- #
# the (y, x) plane tiled in y (tier-1: interpret mode on CPU)
# --------------------------------------------------------------------- #

# a plane that no kernel may hold whole once the planner is given this
# little VMEM to count on; nz = 12 and ny = 64 leave room for every plan
# the tests pin.  The planner takes bands that divide nz and ny (rows in
# whole sublane tiles) and nothing else, so there is no uneven band to
# test: the uneven case is the remainder (niter % K != 0)
TILED_SHAPE = (12, 64, 64)
TILED_VMEM = {"d3q19": 6_000_000, "d3q27_BGK": 8_000_000,
              "d3q27_cumulant": 12_000_000}
# at K = 1 the cumulant's 12 MB hold two whole planes a window (counted
# with the 3 temporary planes Mosaic holds, not 6), and a whole plane
# moves less than any tiling: this is what tiles the K = 1 plan too
TILED_VMEM_K1 = 5_500_000


def _tiled_lat(name, wall):
    m = get_model(name)
    sett = {"nu": 0.05, "GravitationX": 1e-5}
    if name == "d3q27_cumulant":
        sett = {"nu": 0.05, "ForceX": 1e-5}
    lat = Lattice(m, TILED_SHAPE, dtype=jnp.float32, settings=sett)
    flags = np.full(TILED_SHAPE, m.flag_for("MRT"), dtype=np.uint16)
    if wall:
        # a wall on the row the first band starts with, which is also a
        # wrapped halo row of the last band, and walls on the z faces
        flags[:, 0, :] = m.flag_for("Wall")
        flags[0] = flags[-1] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    # a field that differs from node to node: a halo row or slab taken
    # from the wrong place then shows
    f = np.array(lat.state.fields)
    f[:len(m.groups["f"])] *= (1.0 + 0.01 * np.random.default_rng(3)
                               .standard_normal(f[:len(m.groups["f"])].shape)
                               ).astype(np.float32)
    lat.state = lat.state.replace(fields=jnp.asarray(f))
    return m, lat, flags


# 2 K + 1 steps: two fused calls and, for K > 1, one step over; then the
# pairing's edges at K = 2 with one step over (5 steps is among the first)
@pytest.mark.parametrize("name,K,wall,niter", [
    (name, K, wall, 2 * K + 1) for name, K, wall in [
        ("d3q27_cumulant", 1, False), ("d3q27_cumulant", 1, True),
        ("d3q27_cumulant", 2, False), ("d3q27_cumulant", 2, True),
        ("d3q27_cumulant", 3, False), ("d3q27_cumulant", 3, True),
        ("d3q19", 2, True), ("d3q27_BGK", 3, True)]
] + [("d3q19", 2, True, niter) for niter in _pairing_edges(2, (1,))
     if niter != 5])
def test_y_tiled_bit_exact_vs_xla(name, K, wall, niter):
    """The fused kernel on windows of ``bz`` slabs x ``by`` rows with
    wrapped halo rows is BIT-IDENTICAL to the XLA step, periodic in y
    and with a wall on the seam: the tiling comes from the planner, given
    less VMEM than a whole plane takes, not from a knob."""
    m, lat, flags = _tiled_lat(name, wall)
    budget = TILED_VMEM_K1 if K == 1 else TILED_VMEM[name]
    bz, by, k = pallas_d3q.tile_plan(m, TILED_SHAPE, 4, K, budget)
    assert k == K and by < TILED_SHAPE[1] and by % 8 == 0
    assert pallas_d3q._fused_fits(m, *TILED_SHAPE, bz, K, by=by,
                                  budget=budget)
    it = pallas_d3q.make_pallas_iterate(
        m, TILED_SHAPE, present=pallas_d3q.present_types(m, flags),
        fuse=K, vmem_budget=budget)
    s_p = it(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    s_x = lat._iterate(lat.state, lat.params, niter)
    np.testing.assert_array_equal(np.asarray(s_p.fields),
                                  np.asarray(s_x.fields))
    assert int(s_p.iteration) == int(s_x.iteration) == niter


@pytest.mark.parametrize("fuse,budget,niter,fused,rest,paired", [
    (3, None, 3 * fused + rest, fused, rest, 4 if fused == 4 else 0)
    for fused in range(5) for rest in (0, 1, 2)
] + [
    (3, None, 499, 166, 1, 166),    # the channel cell's call
    (3, None, 250, 83, 1, 82),      # the vortex cell's: the 83rd is odd
    (8, None, 15, 1, 7, 6),         # a remainder loop of three pairs
    (8, None, 47, 5, 7, 4 + 6),
    (1, None, 5, 0, 5, 4),          # fuse=1: the ring/block kernel's loop
    (1, 12_000_000, 5, 5, 0, 4),    # a plane only the fused kernel's K = 1
                                    # plan takes: windows of 2 whole planes
    (1, 5_500_000, 5, 5, 0, 4),     # y-tiled at K = 1
    (3, 12_000_000, 14, 4, 2, 4)])
def test_account_counts_the_paired_calls(fuse, budget, niter, fused, rest,
                                         paired):
    """``account`` mirrors ``_iterate_jit``'s schedule: ``paired_calls``
    are the kernel calls issued from a two-call loop body (a loop of one
    trip or none is unrolled whole and counts nothing);
    ``kernel_calls`` and ``remainder_steps`` are what they were."""
    m = get_model("d3q27_cumulant")
    shape, kw = (((16, 8, 64), {}) if budget is None
                 else (TILED_SHAPE, {"vmem_budget": budget}))
    it = pallas_d3q.make_pallas_iterate(m, shape, fuse=fuse, **kw)
    did = it.account(niter)
    assert (did["kernel_calls"], did["remainder_steps"],
            did["paired_calls"]) == (fused + rest, rest, paired)
    assert ("z_bands" in did) == bool(fused)


def test_y_tiled_needs_a_plan():
    """A budget that no window fits is refused, not run whole-plane."""
    m, lat, flags = _tiled_lat("d3q19", False)
    with pytest.raises(ValueError, match="no plan tiles"):
        pallas_d3q.make_pallas_iterate(m, TILED_SHAPE, fuse=2,
                                       vmem_budget=200_000)


@pytest.mark.parametrize("name,bz", [
    ("d3q27_cumulant", 2), ("d3q27_cumulant", 4), ("d3q19", 4)])
def test_channel_plan_slab_by_slab_halo(name, bz):
    """The channel cell's plans in small at ``fuse=3``: (bz, K) = (2, 3),
    the plan until PR 41, whose halo is deeper than the band and is
    copied slab by slab, each index wrapped; and (4, 3), the plan since,
    whose halo goes as one block a side.  Both bit-identical to the XLA
    step: the plan is a shape, not a result."""
    m, lat, flags = _fused_lat(name)
    it = pallas_d3q.make_pallas_iterate(
        m, FUSED_SHAPE, present=pallas_d3q.present_types(m, flags),
        fuse=3, fuse_bz=bz)
    did = it.account(7)
    assert (did["band_slabs"], did["halo_slabs"], did["z_bands"]) \
        == (bz, 3, FUSED_SHAPE[0] // bz)
    assert did["vmem_bytes"] == pallas_d3q._fused_vmem(
        m, *FUSED_SHAPE[1:], bz, 3)
    s_p = it(jax.tree.map(jnp.copy, lat.state), lat.params, 7)
    s_x = lat._iterate(lat.state, lat.params, 7)
    np.testing.assert_array_equal(np.asarray(s_p.fields),
                                  np.asarray(s_x.fields))


@pytest.mark.parametrize("shape,want,ext", [
    ((512, 48, 256), (4, 3), True),      # channel3d512: (2, 3) until PR 41
    ((48, 48, 256), (4, 3), True),       # 3d_channel.xml: (3, 2) until then
    ((256, 128, 128), (2, 3), False),    # a ring-only plane, whole: (2, 2)
    ((256, 256, 256), (4, 32, 3), True),      # tgv256: tiled, as it was
    ((128, 128, 256), (4, 32, 3), True)])
def test_planner_keeps_whole_planes_and_tiles_the_rest(shape, want, ext):
    """Where a whole plane fits a single-step kernel the plan is the one
    ``PERF.md`` records (section 6, PR 41: planned with the temporaries
    Mosaic holds), to the tuple; where none does the tiled plan is what
    it was and passes the planner's own VMEM account; the sharded
    building block (a z-shard of this shape) takes whole planes and,
    since PR 53, tiled windows, and only the ring-only plane not."""
    m = get_model("d3q27_cumulant")
    nz, ny, nx = shape
    assert pallas_d3q.supports(m, shape, jnp.float32)
    assert pallas_d3q.supports(m, shape, jnp.float32, ext_halo=True) == ext
    plan = pallas_d3q.tile_plan(m, shape)
    if len(want) == 2:          # (bz, K): whole planes
        assert plan is None
        assert pallas_d3q.fused_cfg(m, shape) == want
        assert pallas_d3q._fused_fits(m, nz, ny, nx, *want)
        assert pallas_d3q.choose_fuse(m, shape) == want[1]
        return
    assert plan == want
    bz, by, K = plan
    assert K >= 2 and nz % bz == 0 and ny % by == 0 and by % 8 == 0
    assert by < ny
    assert pallas_d3q._fused_fits(m, nz, ny, nx, bz, K, by=by)
    assert pallas_d3q.choose_fuse(m, shape) == K
    # the steps a fused call leaves over have a plan of their own
    bz1, by1, K1 = pallas_d3q.tile_plan(m, shape, fuse=1)
    assert K1 == 1 and pallas_d3q._fused_fits(m, nz, ny, nx, bz1, 1,
                                              by=by1)
    # and what the account admits moves less than the single-step plan
    assert pallas_d3q._fused_cost(m, bz, K, by) \
        < pallas_d3q._fused_cost(m, bz1, 1, by1)
    # the static audit follows the planner
    from tclb_tpu.analysis import resources
    said = [f for f in resources.check_resources(m, shape)
            if f.check == "resources.fused_slab" and "tuned" in f.message]
    assert [(f.details["bz"], f.details["by"], f.details["fuse"])
            for f in said] == [(bz, by, K)]


@pytest.mark.parametrize("shape", [
    (512, 48, 256), (48, 48, 256), (256, 128, 128), (64, 64, 128)])
@pytest.mark.parametrize("name", pallas_d3q._SUPPORTED)
def test_whole_planes_are_planned_by_the_tiled_cost(name, shape):
    """One rule plans every window of the fused kernel: a whole-plane
    plan is the least of ``_tile_cost`` (no halo rows) over the deepest
    band of every depth, so a cheaper VMEM account never buys depth
    whose recomputed node steps cost more than the bytes it saves: no
    shallower feasible plan is cheaper than the one chosen, and a deeper
    one only where it is no dearer by bytes and by arithmetic both."""
    m = get_model(name)
    nz = shape[0]
    bz, K = pallas_d3q.fused_cfg(m, shape)
    # what the planner chooses among: the deepest band of every depth
    plans = {k: pallas_d3q._deepest_band(m, *shape, k)
             for k in range(2, pallas_d3q.fusion.FUSE_MAX + 1)
             if nz >= 2 * k}
    plans = {k: b for k, b in plans.items() if b}
    assert plans[K] == bz
    cost = {k: pallas_d3q._tile_cost(m, b, k) for k, b in plans.items()}
    assert cost[K] == min(cost.values())
    assert all(cost[k] > cost[K] for k in plans if k < K)
    # the rule is the tiled windows' own, with no halo rows
    assert pallas_d3q._tile_cost(m, bz, K) == max(
        pallas_d3q._fused_cost(m, bz, K),
        pallas_d3q._RECOMPUTE_PLANES * (1.0 + (K - 1) / bz))
    # bytes alone would go deeper at the channel's shape for the 19
    # populations: (4, 6), 2.25 node steps a useful one
    if name == "d3q19" and shape == (512, 48, 256):
        by_bytes = min(plans, key=lambda k: pallas_d3q._fused_cost(
            m, plans[k], k))
        assert (plans[by_bytes], by_bytes) == (4, 6) and K < by_bytes
