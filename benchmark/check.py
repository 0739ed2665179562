"""The comparison that decides ``correct``.  Part of the yardstick.

What is compared, once the window has closed and the program's state is
freed: the populations the timed path produced after the run's first
``check_segments`` segments (through the window's own ``<Solve>`` loop,
its ``Lattice.iterate(segment)`` calls and compiled programs, at the
timed size, on the cell's chips) against the configuration's plain
reference (``benchmark/reference/<reference>.py``) advanced the same
number of steps from the same generated case.  The number is the largest
absolute difference over all populations; its limit is the
configuration file's ``tolerance``.  Beside it, exact conditions: the
engine tag is of the configuration's family, no ``engine_fallback`` was
counted, Failcheck never fired (the window closed), and the newest VTK
file is finite and of the case's size.
"""

from __future__ import annotations

import importlib

import numpy as np


def reference_fields(config: dict, root, steps: int, storage=None
                     ) -> np.ndarray:
    ref = importlib.import_module(
        "benchmark.reference." + config["reference"])
    import jax.numpy as jnp
    return ref.run(root, steps, jnp.dtype(config["dtype"]),
                   storage=storage)


def largest_difference(program: np.ndarray, reference: np.ndarray) -> float:
    """Over the reference's planes, which are the program's first."""
    n = reference.shape[0]
    if program.shape[0] < n or program.shape[1:] != reference.shape[1:]:
        raise ValueError(f"program fields {program.shape} against "
                         f"reference {reference.shape}")
    worst = 0.0
    for i in range(n):          # plane by plane: no second full copy
        d = np.abs(program[i].astype(np.float64) - reference[i]).max()
        worst = max(worst, float(d))
    return worst


def decide(numbers: list[tuple[str, float, float]]) -> bool:
    """``numbers``: (what, value, limit); a value that is not finite or
    above its limit fails.  Prints each beside its limit."""
    ok = True
    for what, value, limit in numbers:
        good = bool(np.isfinite(value)) and value <= limit
        print(f"check: {what} = {value!r} (limit {limit!r}) "
              f"{'ok' if good else 'FAILED'}", flush=True)
        ok = ok and good
    return ok
