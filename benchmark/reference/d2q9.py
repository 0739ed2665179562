"""Plain reference of the d2q9 MRT step (karman configurations).

Written from the textbook scheme and the case file alone; it imports
nothing of the program.  One step, for every node at once:

1. pull streaming, periodic: ``f_i(x) <- f_i(x - e_i)``;
2. wall nodes: full bounce-back (``f_i <- f_opp(i)``);
   the x = 0 face: Zou/He with the velocity given;
   the x = nx-1 face: Zou/He with the density given (1.0);
3. every node that is not a wall collides: the non-equilibrium part of
   ``f`` goes to the Lallemand-Luo moment basis, each moment is scaled by
   its factor (conserved: 0; energy: -1/3; energy square and energy flux:
   0; stress: 1 - omega, omega = 1 / (3 nu + 1/2)), and comes back on top
   of the equilibrium.

No matrix product is used (a TPU would take it in bfloat16 passes): the
9 x 9 transforms are written out as sums with scalar coefficients.
``storage`` narrows the populations between steps, which is what the
control does with bfloat16.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmark.reference import advance, geometry

E = np.array([(0, 0), (1, 0), (0, 1), (-1, 0), (0, -1),
              (1, 1), (-1, 1), (-1, -1), (1, -1)])
W = np.array([4 / 9] + [1 / 9] * 4 + [1 / 36] * 4)
OPP = [0, 3, 4, 1, 2, 7, 8, 5, 6]
N_PLANES = 9


def _basis() -> np.ndarray:
    ex, ey = E[:, 0].astype(float), E[:, 1].astype(float)
    e2 = ex * ex + ey * ey
    return np.stack([np.ones(9), ex, ey, 3 * e2 - 4,
                     4.5 * e2 * e2 - 10.5 * e2 + 4,
                     (3 * e2 - 5) * ex, (3 * e2 - 5) * ey,
                     ex * ex - ey * ey, ex * ey])


M = _basis()
M_INV = (M / (M * M).sum(axis=1)[:, None]).T


def _matvec(mat, planes):
    out = []
    for row in mat:
        acc = 0.0
        for c, p in zip(row, planes):
            if c != 0.0:
                acc = acc + float(c) * p
        out.append(acc)
    return out


def _equilibrium(rho, ux, uy):
    usq = ux * ux + uy * uy
    out = []
    for (ex, ey), w in zip(E, W):
        eu = float(ex) * ux + float(ey) * uy
        out.append(float(w) * rho * (1.0 + 3.0 * eu + 4.5 * eu * eu
                                     - 1.5 * usq))
    return out


def _zou_he_west_velocity(f, ux):
    rho = (f[0] + f[2] + f[4] + 2.0 * (f[3] + f[7] + f[6])) / (1.0 - ux)
    ru = rho * ux
    g = list(f)
    g[1] = f[3] + (2.0 / 3.0) * ru
    g[5] = f[7] + (1.0 / 6.0) * ru + 0.5 * (f[4] - f[2])
    g[8] = f[6] + (1.0 / 6.0) * ru + 0.5 * (f[2] - f[4])
    return g


def _zou_he_east_pressure(f, rho):
    ux = -1.0 + (f[0] + f[2] + f[4] + 2.0 * (f[1] + f[5] + f[8])) / rho
    ru = rho * ux
    g = list(f)
    g[3] = f[1] - (2.0 / 3.0) * ru
    g[7] = f[5] - (1.0 / 6.0) * ru + 0.5 * (f[2] - f[4])
    g[6] = f[8] - (1.0 / 6.0) * ru + 0.5 * (f[4] - f[2])
    return g


def make_step(masks: dict, par: dict):
    """``step(f) -> f`` on a (9, ny, nx) stack."""
    omega = 1.0 / (3.0 * par.get("nu", 1 / 6) + 0.5)
    scale = [0.0, 0.0, 0.0, -1 / 3, 0.0, 0.0, 0.0, 1 - omega, 1 - omega]
    vel, den = par.get("Velocity", 0.0), par.get("Density", 1.0)
    wall, inlet, outlet, collide = (jnp.asarray(masks[k]) for k in
                                    ("wall", "inlet", "outlet", "collide"))

    def step(f):
        p = [jnp.roll(f[i], (int(E[i, 1]), int(E[i, 0])), (0, 1))
             for i in range(9)]
        bounced = [p[OPP[i]] for i in range(9)]
        west = _zou_he_west_velocity(p, vel)
        east = _zou_he_east_pressure(p, den)
        p = [jnp.where(wall, bounced[i],
                       jnp.where(inlet, west[i],
                                 jnp.where(outlet, east[i], p[i])))
             for i in range(9)]
        rho = sum(p)
        ux = sum(float(E[i, 0]) * p[i] for i in range(9) if E[i, 0]) / rho
        uy = sum(float(E[i, 1]) * p[i] for i in range(9) if E[i, 1]) / rho
        feq = _equilibrium(rho, ux, uy)
        mom = _matvec(M, [a - b for a, b in zip(p, feq)])
        back = _matvec(M_INV, [s * m for s, m in zip(scale, mom)])
        return jnp.stack([jnp.where(collide, feq[i] + back[i], p[i])
                          for i in range(9)])

    return step


def initial(masks: dict, par: dict, dtype) -> jnp.ndarray:
    shape = masks["wall"].shape
    rho = jnp.full(shape, par.get("Density", 1.0), dtype)
    ux = jnp.full(shape, par.get("Velocity", 0.0), dtype)
    return jnp.stack(_equilibrium(rho, ux, jnp.zeros(shape, dtype)))


def run(root, steps: int, dtype=jnp.float32, storage=None) -> np.ndarray:
    """The populations after ``steps`` steps of the case ``root`` (the
    parsed XML), as a host array of ``dtype``."""
    masks = geometry.paint(root.find("Geometry"))
    par = geometry.params(root)
    return advance(make_step(masks, par), initial(masks, par, dtype),
                   steps, storage)
