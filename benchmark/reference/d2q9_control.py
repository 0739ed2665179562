"""Plain reference of the d2q9 MRT step under a ``<Control>`` series on
the inlet's velocity (the configuration karman1024control).

``reference/d2q9.py``'s step (``make_step``, given each iteration's value
as its ``Velocity``) in a loop that has the iteration in hand; like it,
written from the textbook scheme and the case file alone, and it imports
nothing of the program.  The case's

    <Control Iterations="T">
        <CSV file="rows.csv"/>
        <Params Velocity-Inlet="col"/>      (or "col*number")
    </Control>

is read as the element's documentation says: the CSV's rows are spread
evenly over ``[0, T)`` (row ``i`` stands at ``i * T / rows``), the
column is interpolated linearly onto the whole iterations ``0 .. T-1``
(``np.interp``: past the last row its value holds), and the step that
starts at iteration ``t`` gives the inlet the value at ``t mod T``.
Anything else in the element (a second series, another zone or setting,
a ``Time`` column, a sum of terms) is refused, not guessed.

``lag`` delays the series by whole steps (the step at ``t`` reads the
value of ``t - lag``): with 1, the mistake of a kernel that advances two
steps a call on the first step's value (``benchmark/series_control.py``).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import d2q9, geometry

N_PLANES = d2q9.N_PLANES
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _column(path: str, name: str) -> np.ndarray:
    """The column ``name`` of the CSV at ``path``.  The program opens
    the path as it stands, from the directory it was started in; the
    benchmark is started at the repository's root, which is where a
    path that is not found as it stands is looked for."""
    if not os.path.exists(path):
        path = os.path.join(ROOT, path)
    with open(path) as f:
        header = [h.strip().strip('"') for h in f.readline().split(",")]
        if name not in header:
            raise ValueError(f"no column {name!r} in {path}: {header}")
        at = header.index(name)
        return np.array([float(line.split(",")[at]) for line in f
                         if line.strip()])


def series(root) -> np.ndarray:
    """The inlet velocity at the iterations ``0 .. T-1``, float64."""
    controls = root.findall("Control")
    if len(controls) != 1:
        raise ValueError(f"{len(controls)} <Control> elements: one wanted")
    control = controls[0]
    horizon = int(control.get("Iterations"))
    csvs, pars = control.findall("CSV"), control.findall("Params")
    if (len(csvs) != 1 or len(pars) != 1 or len(control) != 2
            or set(csvs[0].attrib) != {"file"} or len(csvs[0])
            or list(pars[0].attrib) != ["Velocity-Inlet"]):
        raise ValueError("unsupported <Control>: one <CSV file=> and one "
                         "<Params Velocity-Inlet=> wanted")
    name, _, factor = pars[0].get("Velocity-Inlet").partition("*")
    rows = _column(csvs[0].get("file"), name.strip())
    rows = rows * (float(factor) if factor else 1.0)   # a number, or refused
    at = np.arange(len(rows), dtype=np.float64) * (horizon / len(rows))
    return np.interp(np.arange(horizon, dtype=np.float64), at, rows)


def run(root, steps: int, dtype=jnp.float32, storage=None, lag: int = 0,
        start: int = 0, values=None) -> np.ndarray:
    """The populations after ``steps`` steps of the case ``root`` (the
    parsed XML) from iteration ``start``, as a host array of ``dtype``.
    ``storage`` narrows the populations between steps (the bfloat16
    control); ``lag`` delays the series; ``values`` stands in for the
    case's series (``series_control.py``'s frozen one)."""
    masks = geometry.paint(root.find("Geometry"))
    par = geometry.params(root)
    table = jnp.asarray(series(root) if values is None else values, dtype)
    horizon = table.shape[0]

    def one(t, f):
        # d2q9's own step, its inlet velocity this iteration's value
        vel = table[jnp.mod(t - lag, horizon)]
        f = d2q9.make_step(masks, {**par, "Velocity": vel})(f)
        return f if storage is None else f.astype(storage).astype(dtype)

    return np.asarray(jax.jit(lambda f: jax.lax.fori_loop(
        start, start + steps, one, f))(d2q9.initial(masks, par, dtype)))
