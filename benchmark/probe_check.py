"""The guard of a cell whose case samples point probes (``<Sample>``):
``run.py``'s check compares populations and never reads the CSV, so the
template calls this with ``<CallPython Iterations=N config=...>`` placed
after ``<Sample Iterations=N>``, and **every run of the cell holds its
samples to the plain reference, on the cell's chip, at the timed size**.

At iteration N (the first segment's end, long before the window: the
flush just before it has written the N rows; later calls return at once)
:func:`rows_match_reference`

* asks the program which engine ran the sampled segment, and exits with
  no result where it is none of the configuration's ``engine_family``
  (a program that sends a sampled run off its fused engines is another
  deployment, twenty-six times slower on the chip; nothing to require where the program
  was told to stay off its fast paths, as ``benchmark/require.py``);
* reads the file, advances ``benchmark/reference/probes.py`` N steps
  from the generated case, and exits with no result where the header
  differs, a row is missing, doubled or out of order, or any value of
  the first N rows differs from the reference's by more than the
  configuration's ``sample_tolerance``.

The case is the file ``run.py`` wrote beside the output
(``<output>/<name>.xml``); the configuration is named by the ``config``
attribute of the ``<CallPython>`` element that calls this (the program
reads ``module``, ``function`` and ``Iterations`` and nothing else).
What it costs is set-up: the reference's compile and N steps.

    python3 benchmark/probe_check.py --workload <cell> --seeds 1 2 3

prints the control of the limit: how far the reference's own probes move
when its populations are stored in bfloat16 between steps.
"""

from __future__ import annotations

import os
import sys
import xml.etree.ElementTree as ET

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
MODULE = "benchmark.probe_check"
_DONE: set = set()      # the cases whose first segment has been looked at


def _case(path: str):
    """(root of the generated case, this guard's own element)."""
    root = ET.parse(path).getroot()
    mine = [el for el in root.findall("CallPython")
            if el.get("module") == MODULE]
    if len(mine) != 1:
        raise SystemExit(f"benchmark: {path} calls {MODULE} "
                         f"{len(mine)} times, not once; no result")
    return root, mine[0]


def _engine_of(solver) -> str | None:
    """The tag of the engine that ran the segment, ``xla`` where none
    did, None where the program was told to stay off its fast paths."""
    import jax
    mode = os.environ.get("TCLB_FASTPATH", "auto")
    if mode == "0" or (mode != "force" and jax.default_backend() != "tpu"):
        return None
    return getattr(solver.lattice, "_fast_name", None) or "xla"


def read_rows(path: str) -> tuple[list[str], np.ndarray]:
    """(header, rows) of a ``<Sample>`` CSV."""
    with open(path) as f:
        header = f.readline().strip().split(",")
    rows = np.loadtxt(path, delimiter=",", skiprows=1, ndmin=2)
    return header, rows


def largest_difference(root, header, rows, steps: int, dtype,
                       storage=None) -> float:
    """The largest absolute difference between the values of the CSV's
    first ``steps`` rows and the reference's; raises ``ValueError``
    where the file's rows are not the steps 1, 2, ..., one each, in
    order, ``steps`` of them at least."""
    from benchmark.reference import probes
    want = ["Iteration"] + probes.columns(root)
    if header != want:
        raise ValueError(f"header {header[:4]}... of {len(header)} columns, "
                         f"not {want[:4]}... of {len(want)}")
    if rows.shape[0] < steps or not np.array_equal(
            rows[:, 0], np.arange(1, rows.shape[0] + 1)):
        raise ValueError(f"{rows.shape[0]} rows for {steps} steps, or "
                         "their iterations are not 1, 2, ... in order")
    ref = probes.run(root, steps, dtype, storage=storage)
    return float(np.abs(rows[:steps, 1:] - ref.astype(np.float64)).max())


def rows_match_reference(solver) -> int:
    case = os.path.join(solver.output_prefix, solver.conf_name + ".xml")
    if case in _DONE:
        return 0
    _DONE.add(case)
    root, me = _case(case)
    steps = int(me.get("Iterations"))
    from benchmark import casegen
    config = casegen.load_json("configs", me.get("config"))
    family = config["engine_family"]
    engine = _engine_of(solver)
    if engine is not None and not engine.startswith(family + "["):
        raise SystemExit(
            f"benchmark: this program ran the sampled segment on engine "
            f"{engine!r}, not on one of family {family}; the cell cannot "
            "be measured on it; no result")
    import jax.numpy as jnp
    path = solver.out_path("Sample", "csv", with_iter=False)
    limit = float(config["sample_tolerance"])
    try:
        worst = largest_difference(root, *read_rows(path), steps,
                                   jnp.dtype(config["dtype"]))
    except ValueError as e:
        raise SystemExit(f"benchmark: {path}: {e}; no result")
    good = bool(np.isfinite(worst)) and worst <= limit
    print(f"check: max |sample - reference| over {steps} rows = {worst!r} "
          f"(limit {limit!r}) {'ok' if good else 'FAILED'}", flush=True)
    if not good:
        raise SystemExit("benchmark: the samples are not the reference's; "
                         "no result")
    return 0


def main(argv=None) -> int:
    import argparse
    sys.path.insert(0, os.path.dirname(HERE))
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax.numpy as jnp
    from benchmark import casegen, run
    from benchmark.reference import probes
    cell, config, traffic = run.load_cell(args.workload)
    run.find_chips(1)
    steps = casegen.segment_steps(traffic)
    limit, worst = float(config["sample_tolerance"]), None
    for seed in args.seeds:
        root, drawn = casegen.generate(run.template_path(config), traffic,
                                       seed)
        ref = probes.run(root, steps, jnp.dtype(config["dtype"]))
        low = probes.run(root, steps, jnp.dtype(config["dtype"]),
                         storage=jnp.bfloat16)
        d = float(np.abs(low.astype(np.float64) - ref).max())
        worst = d if worst is None else min(worst, d)
        print(f"control: {cell['name']} seed {seed} {drawn}: bfloat16 "
              f"storage moves the samples by {d!r} over {steps} steps "
              f"(sample_tolerance {limit!r})", flush=True)
    print(f"control: smallest {worst!r}; "
          f"{'fails the guard' if worst > limit else 'PASSES: the limit is too loose'}")
    return 0 if worst > limit else 1


if __name__ == "__main__":
    sys.exit(main())
