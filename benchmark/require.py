"""Set-up guards a case template can call with ``<CallPython>`` (no
``Iterations``: once, where the element stands), so that a cell fails
cleanly and soon on a program that cannot give it what its configuration
states, instead of measuring another engine for the whole run.

``drop3d256`` states the engine family ``pallas_generic``.  A program
whose dispatch gives a 256 x 256 plane of ``d3q19_kuper`` no such engine
(the parent of PR 34: its generic 3D slab planner held whole planes only)
runs the case on the XLA step at 21.6 MLUPS (my chip run, PR 34): three
periods of warm-up and one of window are 26 minutes, after which
``run.py``'s own check of the family fails the run anyway.  The guard
asks the program's dispatch what it would select before the first step.
It reads, and changes nothing; where the program was told to stay off its
fast paths (``TCLB_FASTPATH=0``, or no TPU and no ``force``) there is
nothing to require.
"""

from __future__ import annotations

import os


def _selected(solver) -> str | None:
    """The tag of the engine the lattice's dispatch lists first, ``xla``
    where it lists none, None where the program cannot say."""
    import jax
    mode = os.environ.get("TCLB_FASTPATH", "auto")
    if mode == "0" or (mode != "force" and jax.default_backend() != "tpu"):
        return None
    build = getattr(solver.lattice, "_build_fast", None)
    if build is None:
        return None
    chain = build()
    return chain[0].tag if chain else "xla"


def pallas_generic_engine(solver) -> int:
    tag = _selected(solver)
    if tag is not None and not tag.startswith("pallas_generic["):
        raise SystemExit(
            f"benchmark: this program gives the case engine {tag!r}, not "
            "one of family pallas_generic; the cell cannot be measured on "
            "it; no result")
    return 0
