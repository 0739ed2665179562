#!/usr/bin/env python3
"""The two controls of a ``<Control>`` cell's limit that ``control.py``
does not make: the plain reference put in the program's place with its
series **frozen** at its first value (a program that ignores
``<Control>``) and with its series **one step late** (``lag=1``: the
mistake of a kernel that advances two steps a call on the first step's
value, or of a loop that reads the value before it moves on).

    python3 benchmark/series_control.py --workload <cell> --seeds 1 2 3

For each seed it prints the largest difference between each control's
populations and the reference's after the cell's check steps, beside the
configuration's ``tolerance``.  A sound limit lies below the smallest of
each.  Needs the cell's chip like a run does; the benchmark's own runs
never call it.  ``benchmark/tests/test_control_series.py`` keeps the same
controls at a size a test run can hold.
"""

import argparse
import importlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def control_differences(config: dict, root, steps: int) -> dict:
    """``{"frozen": d, "late": d}``: each control against the sound
    reference after ``steps`` steps."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark import check
    ref = importlib.import_module(
        "benchmark.reference." + config["reference"])
    dtype = jnp.dtype(config["dtype"])
    sound = ref.run(root, steps, dtype)
    wave = ref.series(root)
    first = np.full_like(wave, wave[0])
    return {
        "frozen": check.largest_difference(
            ref.run(root, steps, dtype, values=first), sound),
        "late": check.largest_difference(
            ref.run(root, steps, dtype, lag=1), sound)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from benchmark import casegen, run
    cell, config, traffic = run.load_cell(args.workload)
    run.find_chips(1)           # the reference runs on one chip
    steps = config["check_segments"] * casegen.segment_steps(traffic)
    limit = config["tolerance"]
    worst: dict = {}
    for seed in args.seeds:
        root, drawn = casegen.generate(run.template_path(config), traffic,
                                       seed)
        for kind, d in control_differences(config, root, steps).items():
            worst[kind] = min(worst.get(kind, d), d)
            print(f"control: {cell['name']} seed {seed} {drawn}: the "
                  f"series {kind} differs by {d!r} after {steps} steps "
                  f"(tolerance {limit!r})", flush=True)
    for kind, d in worst.items():
        print(f"control: {kind}: smallest {d!r}; "
              f"{'fails the check' if d > limit else 'PASSES: the limit is too loose'}")
    return 0 if all(d > limit for d in worst.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
