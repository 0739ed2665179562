"""The tuned 2D band engine's paired loops against one call a body.

``ops/pallas_d2q9.py:make_pallas_iterate`` loops its kernel calls two a
``lax.scan`` body, and at ``fuse`` 2 its call ends in the one-step
kernel, once or twice (PR 48).  Interpret mode on the CPU: the kernels,
their order and their operands are those of the one-call-a-body loop,
and two steps of the one-step kernel are one call of the two-step
kernel, so the states are equal to the last bit.  ``tests/test_fastpath.py`` holds the same for the
resident engine, ``tests/test_mosaic_compile.py`` what the chip's
compiler makes of the loop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu.ops import lbm, pallas_d2q9

from test_fastpath import _karman_lattice


@pytest.fixture(scope="module")
def bands():
    """The tuned band engine and its one-call-a-body reference (the same
    builder with ``paired=False``: the same ``call`` / ``call2`` in the
    same order, looped singly, which is the program every run paid
    until PR 48, but for an even length's last two steps), a (fuse,
    rows) at a time: 16 rows are aligned, 20 stand on ghost rows that
    ``refresh`` rewrites between the two calls of a body."""
    built = {}

    def get(fuse, ny):
        if (fuse, ny) not in built:
            m, lat = _karman_lattice(ny)
            present = lbm.present_types(m, np.asarray(lat.state.flags))
            built[fuse, ny] = lat, tuple(
                pallas_d2q9.make_pallas_iterate(
                    m, (ny, 128), jnp.float32, interpret=True, fuse=fuse,
                    present=present, paired=paired)
                for paired in (True, False))
        return built[fuse, ny]
    return get


@pytest.mark.parametrize("ny", [16, 20], ids=["aligned", "ghost_rows"])
@pytest.mark.parametrize("fuse", [1, 2])
@pytest.mark.parametrize("niter", [1, 2, 3, 4, 5, 11, 12])
def test_band_loop_pairs_its_calls_bit_for_bit(niter, fuse, ny, bands):
    """The tuned band engine's loops run two kernel calls a body and an
    odd call after the loop: the same calls in the same order as one
    call a body, so the state is equal to the last bit at either depth,
    on aligned rows and on ghost rows; and the fuse-2 engine, whose call
    ends in one one-step call or two, gives the one-step engine's state.
    ``account`` says which calls a two-call body issued (a loop of four
    calls or more, less its odd one) and the band of the kernel it
    loops."""
    lat, (it, single) = bands(fuse, ny)
    assert (it.pad_rows > 0) == (ny == 20)
    twos = (niter - 1) // 2 if fuse == 2 else 0
    ones = niter - 2 * twos
    assert ones in (1, 2) or fuse == 1
    did = it.account(niter)
    assert (did["kernel_calls"], did["remainder_steps"], did["aux_planes"],
            did["pad_rows"]) == (twos + ones, 0, 3, it.pad_rows)
    looped = twos if fuse == 2 else ones
    assert did["paired_calls"] == (looped - looped % 2 if looped >= 4 else 0)
    assert single.account(niter) == dict(did, paired_calls=0)
    state = it(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    assert int(state.iteration) == int(lat.state.iteration) + niter
    assert state.fields.shape == lat.state.fields.shape
    # fuse 2 is held to the one-step engine's single loop, and to its
    # own where the length makes a loop of the two-step calls
    refs = [single] if fuse == 1 else [bands(1, ny)[1][1]] + [single] * (
        niter >= 11)
    for ref in refs:
        want = ref(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
        assert np.abs(np.asarray(state.fields)
                      - np.asarray(want.fields)).max() == 0.0
    assert np.abs(np.asarray(state.fields)
                  - np.asarray(lat.state.fields)).max() > 1e-6  # it has moved
