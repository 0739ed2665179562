"""What a fused engine is to dispatch, and how its kernel calls loop.

Every ``make_*iterate`` of ``ops/pallas_*.py`` and
``parallel/halo.make_sharded_pallas_iterate`` returns an :class:`Engine`;
``core/lattice.py`` decides on its fields and reports its ``account``.
The loops that carry the state through a kernel all go through
:func:`scan_calls`, and an account counts their paired calls with
:func:`paired_calls`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

# kernel calls a loop body of the scans that carry the state through a
# kernel.  A loop's carry is one buffer and a custom call cannot write
# the buffer it reads: with one call a body XLA copies the whole carry
# before every call (a third of the device's time at 512 x 48 x 256 and
# at 256^3, PR 33); with two, state A -> B -> A, the call that writes the
# carry is not the one that reads it.  Right for every plan, so a constant
PAIR = 2


def scan_calls(body: Callable, carry, trips: int, paired: bool,
               taps: bool = False):
    """``carry`` after ``trips`` kernel calls, ``body(carry, None) ->
    (carry, None)`` each: one ``lax.scan``, ``PAIR`` calls a loop body
    and an odd call after the loop where ``paired``, one call a body
    otherwise.  With ``taps`` the body's second value is what the call
    left at the sample points and the scan's ``ys`` come back beside the
    carry, ``(carry, ys)``, a row a trip."""
    out = jax.lax.scan(body, carry, None, length=trips,
                       unroll=PAIR if paired else 1)
    return out if taps else out[0]


def tap(fields, points):
    """The stored planes at the sample points, ``(planes, P)``: what a
    sampled loop's body returns beside the state after every step.
    ``points`` is (P, ndim) in array index order, static; ghost rows a
    band stands on lie behind the physical rows, which keep their
    indices.  A static slice a point, never a gather: XLA's gather wants
    the planes' axis inside the rows' and copies the whole state into
    that layout before every call of it (compiled for a described v5e at
    11 x 1024 x 1024, PR 46)."""
    return jnp.stack([fields[(slice(None),) + tuple(int(i) for i in p)]
                      for p in np.asarray(points)], axis=-1)


def paired_calls(*trips: int) -> int:
    """Of paired loops of ``trips`` kernel calls each, the calls a
    two-call loop body issues: a loop's calls less its odd one; a loop
    of one trip or none is no loop (``lax.scan`` unrolls it whole)."""
    return sum(n - n % PAIR for n in trips if n >= 2 * PAIR)


@dataclass(frozen=True, eq=False)
class Engine:
    """A fused engine: ``engine(state, params, niter) -> state``, and what
    dispatch (``core/lattice.py``) decides on.  An engine is its own
    identity (``eq=False``): it hashes like the closure it wraps."""

    run: Callable       # (state, params, niter) -> state (or: samples)
    # account(niter, has_series=False) -> dict: what one call issues,
    # reckoned host-side from the same split of ``niter`` its schedule
    # runs; dispatch counts and annotates it.  None: nothing is reported
    account: Optional[Callable] = None
    # the call returns the LAST step's Globals: no trailing step
    full_globals: bool = False
    # the engine gathers a <Control> time series per iteration itself
    supports_series: bool = False
    # built with sample points: its call is one step a kernel call and
    # returns ``(state, taps)``, the stored planes at the points after
    # every step, (niter, planes, P)
    samples: bool = False
    # nothing has shown that its kernel compiles: the first call is probed
    unproven: bool = False
    fuse: Optional[int] = None      # steps a kernel call, where the tag says
    pad_rows: int = 0               # ghost rows its band stands on
    plan: Optional[tuple] = None    # (bz, by, K) of a 3D slab engine
    # what its plan counted of scoped VMEM and the limit its looped
    # kernel compiles under (``vmem_bytes``, ``vmem_limit_bytes``), where
    # the account does not say: dispatch annotates it beside the account
    vmem: Optional[dict] = None
    impl: Optional[dict] = None     # internals for sibling builders

    def __call__(self, state, params, niter: int):
        return self.run(state, params, niter)
