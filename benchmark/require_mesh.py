"""Set-up guard of the cells that state the engine family
``pallas_sharded`` for a case no other engine can carry through a window
(``require.py``'s kind; a file of its own, the yardstick's stays as it
is).  A case template calls it once with ``<CallPython>`` (no
``Iterations``), after ``<Geometry>`` and before anything of the
lattice's size is made.

``tgv384`` is 56.6 M nodes of ``d3q27_cumulant`` over four chips.  A
program whose dispatch gives a shard of 96 x 384 x 384 no sharded Pallas
engine (the parent of PR 53: its 3D mode held whole planes only) runs the
case on the sharded XLA step, at the 21.6 MLUPS a chip PR 34 read for it
some eleven minutes a period of 500 steps, three periods before the
window and one in it, after which ``run.py``'s own check of the family
fails the run anyway.  The guard asks the program's dispatch what it
would list first, before the first step.  It reads, and changes nothing;
where the program was told to stay off its fast paths
(``TCLB_FASTPATH=0``, or no TPU and no ``force``) there is nothing to
require.
"""

from __future__ import annotations

from benchmark.require import _selected


def pallas_sharded_engine(solver) -> int:
    tag = _selected(solver)
    if tag is not None and not tag.startswith("pallas_sharded["):
        raise SystemExit(
            f"benchmark: this program gives the case engine {tag!r}, not "
            "one of family pallas_sharded; the cell cannot be measured on "
            "it; no result")
    return 0
