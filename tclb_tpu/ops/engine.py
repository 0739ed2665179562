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

# kernel calls a loop body of the scans that carry the state through a
# kernel.  A loop's carry is one buffer and a custom call cannot write
# the buffer it reads: with one call a body XLA copies the whole carry
# before every call (a third of the device's time at 512 x 48 x 256 and
# at 256^3, PR 33); with two, state A -> B -> A, the call that writes the
# carry is not the one that reads it.  Right for every plan, so a constant
PAIR = 2


def scan_calls(body: Callable, carry, trips: int, paired: bool):
    """``carry`` after ``trips`` kernel calls, ``body(carry, None) ->
    (carry, None)`` each: one ``lax.scan``, ``PAIR`` calls a loop body
    and an odd call after the loop where ``paired``, one call a body
    otherwise."""
    return jax.lax.scan(body, carry, None, length=trips,
                        unroll=PAIR if paired else 1)[0]


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

    run: Callable       # (state, params, niter) -> state
    # account(niter, has_series=False) -> dict: what one call issues,
    # reckoned host-side from the same split of ``niter`` its schedule
    # runs; dispatch counts and annotates it.  None: nothing is reported
    account: Optional[Callable] = None
    # the call returns the LAST step's Globals: no trailing step
    full_globals: bool = False
    # the engine gathers a <Control> time series per iteration itself
    supports_series: bool = False
    # nothing has shown that its kernel compiles: the first call is probed
    unproven: bool = False
    fuse: Optional[int] = None      # steps a kernel call, where the tag says
    pad_rows: int = 0               # ghost rows its band stands on
    plan: Optional[tuple] = None    # (bz, by, K) of a 3D slab engine
    impl: Optional[dict] = None     # internals for sibling builders

    def __call__(self, state, params, niter: int):
        return self.run(state, params, niter)
