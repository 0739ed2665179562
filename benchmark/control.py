#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, at a cell's
own size: the plain reference put in the program's place and computed
with its populations stored in bfloat16 between steps (the nearest
precision below the configuration's float32, and the step a later PR
would be tempted by: half the bytes of a bandwidth-bound kernel).

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3

For each seed it prints the largest difference between the control's
populations and the reference's after the cell's check steps, beside
the configuration's ``tolerance``.  A sound limit lies below the
smallest of these.  Needs the cell's chip like a run does; the
benchmark's own runs never call it.  ``benchmark/tests`` keeps the same
control at a size a test run can hold.
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def control_difference(config: dict, root, steps: int) -> float:
    import jax.numpy as jnp
    from benchmark import check
    ref = check.reference_fields(config, root, steps)
    low = check.reference_fields(config, root, steps, storage=jnp.bfloat16)
    return check.largest_difference(low, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from benchmark import casegen, run
    cell, config, traffic = run.load_cell(args.workload)
    run.find_chips(1)           # the reference runs on one chip
    steps = config["check_segments"] * casegen.segment_steps(traffic)
    worst = None
    for seed in args.seeds:
        root, drawn = casegen.generate(run.template_path(config), traffic,
                                       seed)
        d = control_difference(config, root, steps)
        worst = d if worst is None else min(worst, d)
        print(f"control: {cell['name']} seed {seed} {drawn}: bfloat16 "
              f"storage differs by {d!r} after {steps} steps "
              f"(tolerance {config['tolerance']!r})", flush=True)
    print(f"control: smallest {worst!r}; "
          f"{'fails the check' if worst > config['tolerance'] else 'PASSES: the limit is too loose'}")
    return 0 if worst > config["tolerance"] else 1


if __name__ == "__main__":
    sys.exit(main())
