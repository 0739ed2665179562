"""Plain reference of ``<Sample>``'s per-step point probes on the d2q9
karman configurations: what the probes read after every step.

Written from the case file and the model's published quantities alone;
it imports nothing of the program.  From a case's root it takes the
``<Point dx= dy=/>`` children of ``<Sample>`` in array order (row ``dy``,
column ``dx``), steps ``reference/d2q9.py``'s ``make_step`` from its
``initial`` state and, after every step, reads at each point

* ``Rho``: the sum of the nine populations;
* ``U``: their first moments over ``Rho``, with half the body force
  added (``GravitationX`` / ``GravitationY`` of ``<Model>/<Params>``,
  zero in the karman cases: the model's measured velocity), and a zero
  third component,

in the order ``<Sample what=>`` names them (a vector's components as
consecutive columns), a point after a point: the columns of the CSV the
program writes.  Float32, ``jax.default_matmul_precision("highest")``
(nothing here is a matrix product: the moments are written out).
``storage`` narrows the populations between steps, which is what the
control does with bfloat16; the probes read the narrowed state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import d2q9, geometry

COLUMNS = {"Rho": 1, "U": 3}


def sample(root):
    """The case's ``<Sample>`` element."""
    el = root.find("Sample")
    if el is None:
        raise ValueError("the case has no <Sample> element")
    return el


def points(root) -> list[tuple[int, int]]:
    """(row, column) of every ``<Point>``, in document order."""
    return [(int(p.get("dy", "0")), int(p.get("dx", "0")))
            for p in sample(root).findall("Point")]


def quantities(root) -> list[str]:
    what = sample(root).get("what").split(",")
    for q in what:
        if q not in COLUMNS:
            raise ValueError(f"no plain reference of the quantity {q!r}")
    return what


def columns(root) -> list[str]:
    """The header the program's CSV has after ``Iteration``."""
    out = []
    for i in range(len(points(root))):
        for q in quantities(root):
            out += [f"{q}_{i}_{c}" for c in "xyz"] if COLUMNS[q] == 3 \
                else [f"{q}_{i}"]
    return out


def _probe(f, ys, xs, what, gx, gy):
    at = f[:, ys, xs]                                   # (9, P)
    rho = sum(at[i] for i in range(9))
    cols = []
    for q in what:
        if q == "Rho":
            cols.append(rho[:, None])
            continue
        ux = sum(float(d2q9.E[i, 0]) * at[i]
                 for i in range(9) if d2q9.E[i, 0]) / rho + 0.5 * gx
        uy = sum(float(d2q9.E[i, 1]) * at[i]
                 for i in range(9) if d2q9.E[i, 1]) / rho + 0.5 * gy
        cols.append(jnp.stack([ux, uy, jnp.zeros_like(ux)], axis=-1))
    return jnp.concatenate(cols, axis=-1).reshape(-1)   # point-major


def run(root, steps: int, dtype=jnp.float32, storage=None) -> np.ndarray:
    """``(steps, len(columns(root)))``: row k what the probes read after
    step k + 1 of the case ``root`` (the parsed XML)."""
    masks = geometry.paint(root.find("Geometry"))
    par = geometry.params(root)
    step = d2q9.make_step(masks, par)
    pts, what = points(root), quantities(root)
    ny, nx = masks["wall"].shape
    if any(not (0 <= y < ny and 0 <= x < nx) for y, x in pts):
        raise ValueError("a <Point> lies outside the domain")
    ys = np.array([y for y, _ in pts])
    xs = np.array([x for _, x in pts])
    gx, gy = par.get("GravitationX", 0.0), par.get("GravitationY", 0.0)

    def one(f, _):
        f = step(f)
        if storage is not None:
            f = f.astype(storage).astype(dtype)
        return f, _probe(f, ys, xs, what, gx, gy)

    with jax.default_matmul_precision("highest"):
        rows = jax.jit(lambda f: jax.lax.scan(one, f, None,
                                              length=steps)[1])(
            d2q9.initial(masks, par, dtype))
    return np.asarray(rows)
