"""Plain reference of the d2q9_kuper step (the drop configurations):
the Kupershtokh pseudopotential multiphase model on the d2q9 lattice.

Written from Kupershtokh, Medvedev & Karpov, "On equations of state in a
lattice Boltzmann method" (Comput. Math. Appl. 58, 2009) and from the
upstream model's ``src/d2q9_kuper/Dynamics.c.Rt`` as
``tclb_tpu/models/d2q9_kuper.py`` cites it (the upstream tree is not in
this sandbox); it imports nothing of the program.  The state is 10
planes: the 9 populations and the pseudopotential ``phi``, which is the
program's storage order.  One step, for every node at once (the
``Iteration`` action: ``BaseIteration``, then ``CalcPhi``):

1. pull streaming, periodic: ``f_i(x) <- f_i(x - e_i)``; ``phi`` stays;
2. density and velocity of the streamed populations, their equilibrium,
   and the non-equilibrium part in the Lallemand-Luo moment basis, each
   moment multiplied by its keep factor ``S0..S8`` (as shipped: -1/3 for
   the energy, 0 for all others, which is omega = 1);
3. the interaction force from ``phi`` of the step before:
   ``F = MagicF sum_i g_i e_i R_i`` over the 8 neighbours, with
   ``R_i = A phi_i^2 + (1 - 2A) phi_i phi_0``, ``g_i`` = 1 on the axes
   and 1/4 on the diagonals, ``A`` = ``MagicA``;
4. exact-difference forcing: the equilibrium at the velocity shifted by
   ``F / rho`` (plus ``GravitationX``, ``GravitationY``) is added to the
   kept moments, and the sum goes back to populations;
5. ``phi = FAcc sqrt(max(rho / 3 - Magic p(rho, T), 0))`` from the new
   density, which is the density the next step's collision sees: the
   sum of the new populations after their streaming,
   ``rho(x) = sum_i f_i(x - e_i)`` (upstream's ``CalcPhi`` is a stage of
   its own and reads the populations through the pull).

The initial state (the ``Init`` action) is the equilibrium at each
node's zonal ``Density`` and zero velocity, then ``phi`` from it in the
same way.

Departures from the published description, each as upstream has it:

* ``phi_i`` is sampled at ``x - e_i`` while the sum weights it with
  ``+e_i``; with ``MagicF`` = -2/3 that gives the attraction its sign.
  Sampling at ``x + e_i`` with the same weights inverts the interaction
  (``models/d2q9_kuper.py:_force`` records that this blew up large
  domains).  The paper writes the sum over ``x + e_i`` with a positive
  coefficient, which is the same force.
* The paper's coefficient of the force is folded into two settings,
  ``MagicF`` on the sum and ``FAcc`` on ``phi``; ``Magic`` scales the
  pressure inside the root (the paper's ``k``).
* The equation of state the issue and the program's docstring call van
  der Waals is, as written upstream and here, the Carnahan-Starling
  form ``p = c rho T (1 + b + b^2 - b^3) / (1 - b)^3 - a rho^2`` with
  ``b = B2 rho / 4`` and upstream's constants.
* The relaxation is the keep factors alone: ``nu`` sets ``S7`` and
  ``S8`` to ``1 - 1 / (3 nu + 1/2)``, ``omega`` itself is read by
  nothing in the collision (as shipped, omega = 1, both agree).
* No boundary node types: every node collides (``zones.paint`` raises
  on anything but ``<MRT><Box/></MRT>`` and zones of spheres).

No matrix product is used, for ``reference/d2q9.py``'s reason: the 9 x 9
transforms are that module's sums with scalar coefficients.
``storage`` narrows all 10 planes between steps (the control).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmark.reference import advance, geometry, zones
from benchmark.reference.d2q9 import E, M, M_INV, _equilibrium, _matvec

N_PLANES = 10
SHELL = [0.0, 1.0, 1.0, 1.0, 1.0, 0.25, 0.25, 0.25, 0.25]    # g_i
# constants of the equation of state (upstream Dynamics.c.Rt:291-293)
A2 = 3.852462271644162
B2 = 0.1304438860971524 * 4.0
C2 = 2.785855170470555
KEEP = [0.0, 0.0, 0.0, -1.0 / 3.0, 0.0, 0.0, 0.0, 0.0, 0.0]
DEFAULTS = {"Temperature": 0.9, "FAcc": 1.0, "Magic": 0.01,
            "MagicA": -0.152, "MagicF": -2.0 / 3.0, "GravitationX": 0.0,
            "GravitationY": 0.0, "Density": 1.0}


def settings(par: dict) -> dict:
    """The case's parameters over the model's defaults, with the keep
    factors as a list ``S``; a parameter this reference does not know
    raises."""
    # ``omega`` is a setting of the model that its collision never reads
    known = set(DEFAULTS) | {"omega", "nu"} | {f"S{i}" for i in range(9)}
    for key in par:
        if key not in known and not key.startswith("Density-"):
            raise ValueError(f"unsupported parameter {key!r}")
    out = {k: float(par.get(k, v)) for k, v in DEFAULTS.items()}
    keep = list(KEEP)
    if "nu" in par:
        keep[7] = keep[8] = 1.0 - 1.0 / (3.0 * float(par["nu"]) + 0.5)
    out["S"] = [float(par.get(f"S{i}", keep[i])) for i in range(9)]
    return out


def pressure(rho, temperature: float):
    b = (B2 / 4.0) * rho
    return (rho * (-b * b * b + b * b + b + 1.0) * temperature * C2
            / ((1.0 - b) * (1.0 - b) * (1.0 - b)) - A2 * rho * rho)


def pull(f: list) -> list:
    """Periodic pull streaming: ``f_i(x) <- f_i(x - e_i)``."""
    return [jnp.roll(f[i], (int(E[i, 1]), int(E[i, 0])), (0, 1))
            for i in range(9)]


def pseudopotential(f: list, s: dict):
    """``phi`` from the density of the populations ``f`` once streamed."""
    rho = sum(pull(f))
    inside = rho / 3.0 - s["Magic"] * pressure(rho, s["Temperature"])
    return s["FAcc"] * jnp.sqrt(jnp.maximum(inside, 0.0))


def force(phi, s: dict):
    a = s["MagicA"]
    fx = fy = 0.0
    for i in range(1, 9):
        ex, ey = int(E[i, 0]), int(E[i, 1])
        phi_i = jnp.roll(phi, (ey, ex), (0, 1))         # phi(x - e_i)
        r = SHELL[i] * (a * phi_i * phi_i + (1.0 - 2.0 * a) * phi_i * phi)
        if ex:
            fx = fx + float(ex) * r
        if ey:
            fy = fy + float(ey) * r
    return s["MagicF"] * fx, s["MagicF"] * fy


def make_step(collide, s: dict):
    """``step(state) -> state`` on a (10, ny, nx) stack."""
    collide = jnp.asarray(collide)

    def step(state):
        p = pull(state)
        rho = sum(p)
        ux = sum(float(E[i, 0]) * p[i] for i in range(9) if E[i, 0]) / rho
        uy = sum(float(E[i, 1]) * p[i] for i in range(9) if E[i, 1]) / rho
        feq = _equilibrium(rho, ux, uy)
        mom = _matvec(M, [a - b for a, b in zip(p, feq)])
        fx, fy = force(state[9], s)
        shifted = _equilibrium(rho, ux + fx / rho + s["GravitationX"],
                               uy + fy / rho + s["GravitationY"])
        post = [k * m + e for k, m, e in
                zip(s["S"], mom, _matvec(M, shifted))]
        f = [jnp.where(collide, c, q)
             for c, q in zip(_matvec(M_INV, post), p)]
        return jnp.stack(f + [pseudopotential(f, s)])

    return step


def initial(density, s: dict, dtype) -> jnp.ndarray:
    rho = jnp.asarray(density, dtype)
    zero = jnp.zeros(rho.shape, dtype)
    f = _equilibrium(rho, zero, zero)
    return jnp.stack(f + [pseudopotential(f, s)])


def run(root, steps: int, dtype=jnp.float32, storage=None) -> np.ndarray:
    """The 10 planes after ``steps`` steps of the case ``root`` (the
    parsed XML), as a host array of ``dtype``."""
    painted = zones.paint(root.find("Geometry"))
    par = geometry.params(root)
    s = settings(par)
    density = zones.zonal(par, painted, "Density", DEFAULTS["Density"])
    return advance(make_step(painted["collide"], s),
                   initial(density, s, dtype), steps, storage)
