"""The sharded tuned-2D loop (``parallel/halo.make_sharded_pallas_iterate``,
``ops/pallas_d2q9``'s ``ext_halo`` flavour): the kernel takes the shard's
field stack as it is and the neighbours' 8 halo rows as operands of their
own.  Held here, on the CPU's devices in interpret mode, to the one-device
reference and, to the bit, to the contract it replaced: the kernel fed from
the padded operand ``_exchange_axis`` builds, ``[halo(8) | shard | halo(8)]``.
"""

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tclb_tpu.core.lattice import Lattice, make_iterate
from tclb_tpu.models import get_model
from tclb_tpu.ops import lbm, pallas_d2q9
from tclb_tpu.parallel import halo
from tclb_tpu.parallel.mesh import field_spec, flag_spec, make_mesh

# 72 rows a shard: three bands of 24 in the fused kernel, so a shard has
# a first band (its top block is the lower neighbour's), a middle one
# (both blocks the shard's own rows) and a last
ROWS, NX = 72, 128


@lru_cache(maxsize=None)
def _case(chips: int):
    """A tall d2q9 channel split by rows over ``chips`` devices (inlet,
    outlet, walls, and a wedge that lies across the first seam): the
    model, the mesh, the node types present, the sharded lattice at its
    initial state, the engine, and the one-device XLA engine's fields
    after each of 8 steps."""
    ny = ROWS * chips
    m = get_model("d2q9")
    flags = np.full((ny, NX), m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    rows, cols = np.mgrid[0:ny, 0:NX]
    wedge = ((abs(rows - ROWS) < 12) & (cols >= 28)
             & (cols - 28 < 12 - abs(rows - ROWS)))
    flags[wedge] = m.flag_for("Wall")
    assert wedge[ROWS - 1].any() and wedge[ROWS].any()

    def lattice(mesh=None):
        lat = Lattice(m, (ny, NX), dtype=jnp.float32, mesh=mesh,
                      settings={"nu": 0.05, "Velocity": 0.03})
        lat.set_flags(flags)
        lat.init()
        return lat

    mesh = make_mesh(flags.shape, devices=jax.devices()[:chips],
                     decomposition={"y": chips, "x": 1})
    present = lbm.present_types(m, flags)
    it = halo.make_sharded_pallas_iterate(
        m, mesh, flags.shape, jnp.float32, present=present, interpret=True)
    ref, after = lattice(), []
    step = make_iterate(m)
    for _ in range(8):
        ref.state = step(ref.state, ref.params, 1)
        after.append(np.asarray(ref.state.fields))
    return m, mesh, present, lattice(mesh), it, after


@lru_cache(maxsize=None)
def _padded_calls(chips: int):
    """The contract as it was: a kernel call on the operand
    ``_exchange_axis`` builds round the shard, here handed to the kernel
    as that operand's three row blocks.  ``(two_steps, one_step)``, each
    ``(fields, flags, params) -> fields``, one ``shard_map`` program a
    call (compiled once, whatever the length of the run)."""
    m, mesh, present = _case(chips)[:3]
    call1, call2, _, by2 = pallas_d2q9.make_pallas_iterate(
        m, (ROWS, NX), jnp.float32, interpret=True, fuse=2,
        present=present, ext_halo=True)
    assert ROWS // by2 == 3

    def exch(arr):
        return halo._exchange_axis(arr, "y", 1, 8, chips)

    def local_call(fields, flags, params, steps):
        flags_i32 = flags.astype(jnp.int32)
        vel, den = pallas_d2q9.zonal_planes(
            m, params, flags_i32 >> m.zone_shift, jnp.float32)
        sett = params.settings.astype(jnp.float32)
        ext = exch(fields)
        blocks = ext[:, 8:-8], ext[:, :8], ext[:, -8:]
        if steps == 1:
            return call1(sett, *blocks, flags_i32, vel, den)
        return call2(sett, *blocks, exch(jnp.stack(
            [flags_i32.astype(jnp.float32), vel, den])))

    return tuple(jax.jit(jax.shard_map(
        partial(local_call, steps=steps), mesh=mesh,
        in_specs=(field_spec(mesh), flag_spec(mesh), P()),
        out_specs=field_spec(mesh), check_vma=False)) for steps in (2, 1))


@pytest.mark.parametrize("niter", [1, 2, 3, 4, 5, 8])
@pytest.mark.parametrize("chips", [4, 2])
def test_halo_operands_match_reference_and_padded_loop(chips, niter):
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    lat, it, after = _case(chips)[3:]
    assert it.fuse == 2 and not it.unproven
    assert it.account(niter)["halo_operand_rows"] == 8
    two_steps, one_step = _padded_calls(chips)
    was = lat.state.fields
    for call in [two_steps] * (niter // 2) + [one_step] * (niter % 2):
        was = call(was, lat.state.flags, lat.params)
    # the engine donates its state: a copy of the initial one
    got = it(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    assert int(got.iteration) == niter
    # to the bit: the kernel's arithmetic is the same, only where three
    # DMAs of the first and the last band read from differs
    np.testing.assert_array_equal(np.asarray(got.fields), np.asarray(was))
    # and the one-device XLA engine on the whole domain
    np.testing.assert_allclose(np.asarray(got.fields), after[niter - 1],
                               rtol=2e-5, atol=2e-6)


# -- the generic building block's two flavours (``ops/pallas_generic``'s
#    ``ext_halo`` mode): the trailing globals step of a mesh ------------

@lru_cache(maxsize=None)
def _generic_case(chips: int):
    """``_case``'s channel with the objective columns the Globals are
    reduced on (``Inlet`` / ``Outlet``), after 3 steps of the one-device
    XLA engine so that the sums are of a flow: the model, the mesh, the
    node types, the sharded state and parameters, the one-device ones."""
    m, mesh, _, lat = _case(chips)[:4]
    flags = np.asarray(lat.state.flags).copy()
    flags[1:-1, 2] = m.flag_for("MRT", "Inlet")
    flags[1:-1, -3] = m.flag_for("MRT", "Outlet")

    def lattice(mesh=None):
        lat = Lattice(m, flags.shape, dtype=jnp.float32, mesh=mesh,
                      settings={"nu": 0.05, "Velocity": 0.03})
        lat.set_flags(flags)
        lat.init()
        lat.state = make_iterate(m)(lat.state, lat.params, 3) \
            if mesh is None else lat.state
        return lat

    one, many = lattice(), lattice(mesh)
    many.state = many.state.replace(
        fields=jax.device_put(one.state.fields, many.state.fields.sharding),
        iteration=jax.device_put(one.state.iteration,
                                 many.state.iteration.sharding))
    return m, mesh, lbm.present_types(m, flags), many, one


@pytest.mark.parametrize("chips", [4, 2])
def test_ext_halo_globals_flavour_matches_one_device(chips):
    """The step ``make_sharded_pallas_tail`` composes (exchange, ONE call
    of the generic band kernel's in-kernel-globals flavour a shard, lane
    sum, ``psum``) against the single-device generic engine's one step
    with in-kernel globals on the whole lattice: the same arithmetic a
    node, so the fields to the bit; the Globals, each shard's sums over
    its own rows alone (no halo row counted twice), to the order of the
    sums; replicated; the state not donated."""
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    from tclb_tpu.ops import pallas_generic
    m, mesh, present, many, one = _generic_case(chips)
    tail = halo.make_sharded_pallas_tail(
        m, mesh, one.shape, jnp.float32, present=present, interpret=True)
    assert tail.full_globals and tail.unproven and tail.fuse == 1
    assert tail.account(1)["kernel_calls"] == 1
    ref = pallas_generic.make_pallas_iterate(
        m, one.shape, jnp.float32, interpret=True, fuse=1, present=present)
    assert ref.full_globals and not ref.pad_rows
    want = ref(one.state, one.params, 1)
    got = tail(many.state, many.params, 1)
    jax.block_until_ready(many.state)           # not donated: alive
    assert int(got.iteration) == int(want.iteration) == 4
    np.testing.assert_array_equal(np.asarray(got.fields),
                                  np.asarray(want.fields))
    assert got.globals_.sharding.is_fully_replicated
    assert np.all(np.asarray(want.globals_) != 0)
    np.testing.assert_allclose(np.asarray(got.globals_),
                               np.asarray(want.globals_), rtol=2e-5)
    with pytest.raises(ValueError):
        tail(many.state, many.params, 2)


@pytest.mark.parametrize("chips", [4, 2])
def test_ext_halo_noglobals_call_is_what_it_was(chips):
    """The NoGlobals ``ext_halo`` call, the sharded generic 2D loop's
    building block, beside the globals flavour it now shares a builder
    with: one output and no partial-sums block, its fields those of the
    globals flavour's call to the bit (the kernels differ only in what
    they accumulate), and ``call_g`` only where the kernel reduces the
    Globals (``fuse`` 1)."""
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    from tclb_tpu.ops import pallas_generic
    m, mesh, present, many, _ = _generic_case(chips)
    build = partial(pallas_generic.make_pallas_iterate, m, (ROWS, NX),
                    jnp.float32, interpret=True, present=present,
                    ext_halo=True)
    call, call_g, by, zonal_names = build(fuse=1)
    assert build(fuse=2)[1] is None
    assert ROWS % by == 0 and zonal_names == list(m.zonal_settings)
    gz_si = [m.setting_index[nm] for nm in zonal_names]

    def local_calls(fields, flags, params):
        flags_i32 = flags.astype(jnp.int32)
        operands = (
            params.settings.astype(jnp.float32), jnp.zeros((1,), jnp.int32),
            halo._exchange_axis(fields, "y", 1, 8, chips),
            halo._exchange_axis(halo._generic_aux(
                params, flags_i32, flags_i32 >> m.zone_shift, gz_si,
                jnp.float32), "y", 1, 8, chips))
        out = call(*operands)
        assert isinstance(out, jax.Array)       # one output: the fields
        return out, call_g(*operands)[0]

    plain, with_globals = jax.jit(jax.shard_map(
        local_calls, mesh=mesh,
        in_specs=(field_spec(mesh), flag_spec(mesh), P()),
        out_specs=(field_spec(mesh),) * 2, check_vma=False))(
            many.state.fields, many.state.flags, many.params)
    np.testing.assert_array_equal(np.asarray(plain),
                                  np.asarray(with_globals))
    assert np.abs(np.asarray(plain)
                  - np.asarray(many.state.fields)).max() > 0
