"""The DMA windows of the 3D slab kernels, shared by ``ops/pallas_d3q``'s
fused kernel and ``ops/pallas_generic``'s slab kernel: how a band and its
halos are cut into copies along a periodic axis, and the copy of a halo
piece of one z-block of a lattice split over devices, which comes from
the block where it lies inside it and from a neighbour's slabs where it
does not.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def pieces(band: int, halo: int) -> list:
    """(offset from the band's first index, buffer index, length) of a
    band and its wrapped halos along one axis.  A halo no longer than the
    band (which divides the axis) never straddles the periodic seam and
    goes as one block; a longer one index by index (a block copy of R
    slabs starting at (base - R) mod nz would read out of bounds, e.g.
    bz=1, R=2, band 1)."""
    if not halo:
        return [(0, 0, band)]
    if band >= halo:
        return [(0, halo, band), (-halo, 0, halo),
                (band, halo + band, halo)]
    return [(0, halo, band)] + [
        p for h in range(1, halo + 1)
        for p in ((-h, halo - h, 1),
                  (band - 1 + h, halo + band - 1 + h, 1))]


def wrap(base, off: int, n: int):
    """``base + off`` on a periodic axis of ``n``."""
    return base if not off else jax.lax.rem(
        base + jnp.int32(off + n), jnp.int32(n))


class EitherCopy:
    """One of two copies into the same window on the same semaphore:
    ``copy(a)`` where ``first`` holds, else ``copy(b)``; waited for
    through ``copy(done)``, a copy of their size whose source indices
    are static.  Each is made where it is used (a descriptor never
    started nor waited for is an error to Pallas)."""

    def __init__(self, first, copy: Callable, a, b, done):
        self.first, self.copy = first, copy
        self.a, self.b, self.done = a, b, done

    def start(self) -> None:
        pl.when(self.first)(lambda: self.copy(*self.a).start())
        pl.when(jnp.logical_not(self.first))(
            lambda: self.copy(*self.b).start())

    def wait(self) -> None:
        self.copy(*self.done).wait()


def field_copy(window: Callable, block, halos, z0, oz: int, sz, lz: int,
               nz: int, depth: int):
    """The copy of ``lz`` slabs of the fields, ``oz`` from the first slab
    ``z0`` of a band (slab ``sz`` of a lattice on one chip, z periodic
    inside ``block``), into a kernel's window: ``window(ref, z)`` makes
    the copy from slab ``z`` of ``ref``.  With ``halos`` (the neighbours'
    ``depth`` slabs below and above a ``block`` of ``nz`` slabs, one
    z-block of a lattice split over devices) a halo piece comes from the
    block where it lies inside it, else from the neighbour's slabs: a
    piece is a block no longer than the band, or one slab
    (:func:`pieces`), and never straddles the block's end."""
    if not halos:
        return window(block, sz)
    if not oz:
        return window(block, z0)
    zs = z0 + jnp.int32(oz)
    if oz < 0:
        inside, halo, zh = zs >= 0, halos[0], zs + jnp.int32(depth)
    else:
        inside, halo, zh = (zs + jnp.int32(lz) <= nz, halos[1],
                            zs - jnp.int32(nz))
    return EitherCopy(inside, window, (block, zs), (halo, zh), (halo, 0))
