"""Set-up guard of the cells that state the engine family ``pallas_2d``
for a case an older program keeps off it by name (``require.py``'s kind;
a file of its own, the yardstick's stays as it is).  A case template
calls it once with ``<CallPython>`` (no ``Iterations``), after the
element that decides the dispatch: ``karman1024control`` after its
``<Control>``, which has attached the series by then.

A program whose dispatch drops the tuned 2D band under a ``<Control>``
series (the parent of PR 55: ``_build_fast`` skipped it by ``has_series``)
runs the case on the generic band's series loop, one step a call with
five aux planes assembled before every step: warm-up and window would
run for minutes on another engine, after which ``run.py``'s own check of
the family fails the run anyway.  The guard asks the program's dispatch
what it would list first, before the first step.  It reads, and changes
nothing; where the program was told to stay off its fast paths
(``TCLB_FASTPATH=0``, or no TPU and no ``force``) there is nothing to
require.
"""

from __future__ import annotations

from benchmark.require import _selected


def pallas_2d_engine(solver) -> int:
    tag = _selected(solver)
    if tag is not None and not tag.startswith("pallas_2d["):
        raise SystemExit(
            f"benchmark: this program gives the case engine {tag!r}, not "
            "one of family pallas_2d; the cell cannot be measured on "
            "it; no result")
    return 0
