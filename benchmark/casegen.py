"""From a configuration's template, a traffic file and a seed to the case
XML the program is given.  One general generator: a traffic mix is data.

A traffic file (``benchmark/traffic/<traffic>.json``) holds

* ``handlers``: the periodic handlers of the case, in order, each a tag
  with its attributes (``Iterations`` is the interval in steps);
* ``seeded``: what the seed sets.  Each entry draws one variable
  (``int``: whole number in [lo, hi], ``uniform``: real in [lo, hi]),
  once per ``var`` name, and applies it to one attribute of every
  element that ``select`` (an ElementTree path under the root) finds:
  ``op`` ``set`` writes it, ``add`` adds it to the whole number there;
* ``warmup_periods``, ``trace_periods``: see ``benchmark/window.py``.

The generator appends the handlers, then the benchmark's own
``<CallPython>`` clock last among them, with ``Iterations`` equal to the
greatest common divisor of the intervals, then a ``<Solve>`` far longer
than any window: the clock's handler ends the run.
"""

from __future__ import annotations

import copy
import json
import math
import os
import xml.etree.ElementTree as ET

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SOLVE_ITERATIONS = 2_000_000_000   # never reached: the window stops the run
CLOCK_MODULE, CLOCK_FUNCTION = "benchmark.window", "tick"


def load_json(kind: str, name: str) -> dict:
    """``benchmark/<kind>/<name>.json``; the lookup by name that makes a
    new cell a matter of new files."""
    path = os.path.join(HERE, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def intervals(traffic: dict) -> list[int]:
    return [int(h["Iterations"]) for h in traffic["handlers"]]


def segment_steps(traffic: dict) -> int:
    return math.gcd(*intervals(traffic))


def draw(traffic: dict, seed: int) -> dict:
    """The seeded variables, drawn in the order of first appearance."""
    rng = np.random.default_rng(int(seed))
    out: dict = {}
    for rule in traffic.get("seeded", []):
        if rule["var"] in out:
            continue
        if "int" in rule:
            lo, hi = rule["int"]
            out[rule["var"]] = int(rng.integers(lo, hi + 1))
        else:
            lo, hi = rule["uniform"]
            out[rule["var"]] = float(rng.uniform(lo, hi))
    return out


def generate(template_path: str, traffic: dict, seed: int
             ) -> tuple[ET.Element, dict]:
    """The case's root element and the values the seed drew."""
    root = copy.deepcopy(ET.parse(template_path).getroot())
    values = draw(traffic, seed)
    for rule in traffic.get("seeded", []):
        found = root.findall(rule["select"])
        if not found:
            raise ValueError(f"{rule['select']!r} finds nothing in "
                             f"{template_path}")
        for el in found:
            v = values[rule["var"]]
            if rule["op"] == "add":
                v = int(el.get(rule["attr"])) + v
            elif rule["op"] != "set":
                raise ValueError(f"unknown op {rule['op']!r}")
            el.set(rule["attr"], repr(v))
    for h in traffic["handlers"]:
        ET.SubElement(root, h["tag"], {k: str(v) for k, v in h.items()
                                       if k != "tag"})
    ET.SubElement(root, "CallPython", {
        "module": CLOCK_MODULE, "function": CLOCK_FUNCTION,
        "Iterations": str(segment_steps(traffic))})
    ET.SubElement(root, "Solve", {"Iterations": str(SOLVE_ITERATIONS)})
    return root, values
