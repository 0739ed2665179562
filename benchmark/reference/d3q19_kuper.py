"""Plain reference of the d3q19_kuper step (the ``drop3d256``
configuration): the Kupershtokh pseudopotential multiphase model on the
d3q19 lattice, the three-dimensional form of ``reference/d2q9_kuper.py``.

Written from Kupershtokh, Medvedev & Karpov, "On equations of state in a
lattice Boltzmann method" (Comput. Math. Appl. 58, 2009) and from the
model as ``tclb_tpu/models/d3q19_kuper.py`` describes it (the upstream
tree is not in this sandbox); it imports nothing of the program.  The
state is 20 planes: the 19 populations and the pseudopotential ``phi``,
which is the program's storage order.  The velocities are ordered by
shell: rest, the six axis vectors (+x, -x, +y, -y, +z, -z), then the
twelve edge vectors pair by pair of axes (xy, xz, yz), each pair as
(+,+), (+,-), (-,+), (-,-).  One step, for every node at once (the
``Iteration`` action: ``BaseIteration``, then ``CalcPhi``):

1. pull streaming, periodic: ``f_i(x) <- f_i(x - e_i)``; ``phi`` stays;
2. density and velocity of the streamed populations and their
   second-order equilibrium; the collision is BGK at the rate ``omega``
   (``nu`` sets it to ``1 / (3 nu + 1/2)``);
3. the interaction force from ``phi`` of the step before, over the 18
   neighbours: ``F = MagicF sum_i g_i e_i R_i`` with
   ``R_i = A phi_i^2 + (1 - 2A) phi_i phi_0``, ``g_i`` = 1 on the axes
   and 1/2 on the edges (18 times the lattice weight), ``A`` =
   ``MagicA``, ``phi_i`` sampled at ``x - e_i`` as in two dimensions
   (``reference/d2q9_kuper.py`` says why);
4. exact-difference forcing: the equilibrium at the velocity shifted by
   ``F / rho`` (plus ``GravitationX``, ``-Y``, ``-Z``) less the
   equilibrium at the velocity itself is added to the relaxed
   populations;
5. ``phi = FAcc sqrt(max(rho / 3 - Magic p(rho, T), 0))`` from the
   density the next step's collision sees, the sum of the new
   populations after their streaming (``CalcPhi`` is a stage of its own
   and reads the populations through the pull), with the
   Carnahan-Starling pressure of ``reference/d2q9_kuper.py``.

The initial state (the ``Init`` action) is the equilibrium at each
node's zonal ``Density`` and zero velocity, then ``phi`` from it in the
same way.  No boundary node types: every node collides
(``zones3d.paint`` raises on anything but ``<MRT><Box/></MRT>`` and zones
of spheres).

With ``g_i`` = (1, 1/2) the sum ``sum_i g_i e_i e_i`` is 6 a direction
where the d2q9 shell (1, 1/4) gives 3, so the coefficient that makes the
force the gradient of the equation of state's potential is 1/3 here
(Kupershtokh's alpha = 3 for D3Q19) where it is 2/3 in two dimensions:
``MagicF`` is the case's to set, and this reference takes it as given.

A 256^3 state is 1.34 GB: :func:`run` advances it with one jitted loop
whose argument is donated, and no Python loop runs over planes at run
time (the loops below unroll while the step is traced).  ``storage``
narrows all 20 planes between steps (the control).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import geometry, zones, zones3d
from benchmark.reference.d2q9_kuper import pressure

N_PLANES = 20


def _velocities() -> np.ndarray:
    e = [(0, 0, 0)]
    for a in range(3):
        for s in (1, -1):
            v = [0, 0, 0]
            v[a] = s
            e.append(tuple(v))
    for a in range(3):
        for b in range(a + 1, 3):
            for sa in (1, -1):
                for sb in (1, -1):
                    v = [0, 0, 0]
                    v[a], v[b] = sa, sb
                    e.append(tuple(v))
    return np.array(e)


E = _velocities()                       # columns: x, y, z
W = np.array([1 / 3] + [1 / 18] * 6 + [1 / 36] * 12)
SHELL = 18.0 * W                        # g_i: 1 on the axes, 1/2 on the edges
DEFAULTS = {"omega": 1.0, "Temperature": 0.56, "FAcc": 1.0, "Magic": 0.01,
            "MagicA": -0.152, "MagicF": -2.0 / 3.0, "GravitationX": 0.0,
            "GravitationY": 0.0, "GravitationZ": 0.0, "Density": 3.26}


def settings(par: dict) -> dict:
    """The case's parameters over the model's defaults; a parameter this
    reference does not know raises."""
    for key in par:
        if key not in set(DEFAULTS) | {"nu"} \
                and not key.startswith("Density-"):
            raise ValueError(f"unsupported parameter {key!r}")
    if "nu" in par and "omega" in par:
        raise ValueError("give omega or nu, not both")
    out = {k: float(par.get(k, v)) for k, v in DEFAULTS.items()}
    if "nu" in par:
        out["omega"] = 1.0 / (3.0 * float(par["nu"]) + 0.5)
    return out


def _shift(a, e):
    """``a(x - e)`` on the periodic box; planes are indexed [z, y, x]."""
    return jnp.roll(a, (int(e[2]), int(e[1]), int(e[0])), (0, 1, 2))


def pull(f: list) -> list:
    return [_shift(f[i], E[i]) for i in range(19)]


def _dot(axis: int, planes: list):
    return sum(float(E[i, axis]) * planes[i] for i in range(19)
               if E[i, axis])


def _equilibrium(rho, u: tuple) -> list:
    usq = u[0] * u[0] + u[1] * u[1] + u[2] * u[2]
    out = []
    for e, w in zip(E, W):
        eu = sum(float(e[a]) * u[a] for a in range(3) if e[a])
        out.append(float(w) * rho * (1.0 + 3.0 * eu + 4.5 * eu * eu
                                     - 1.5 * usq))
    return out


def pseudopotential(f: list, s: dict):
    """``phi`` from the density of the populations ``f`` once streamed."""
    rho = sum(pull(f))
    inside = rho / 3.0 - s["Magic"] * pressure(rho, s["Temperature"])
    return s["FAcc"] * jnp.sqrt(jnp.maximum(inside, 0.0))


def force(phi, s: dict) -> tuple:
    a = s["MagicA"]
    acc = [0.0, 0.0, 0.0]
    for i in range(1, 19):
        phi_i = _shift(phi, E[i])                        # phi(x - e_i)
        r = float(SHELL[i]) * (a * phi_i * phi_i
                               + (1.0 - 2.0 * a) * phi_i * phi)
        for ax in range(3):
            if E[i, ax]:
                acc[ax] = acc[ax] + float(E[i, ax]) * r
    return tuple(s["MagicF"] * c for c in acc)


def make_step(collide, s: dict):
    """``step(state) -> state`` on a (20, nz, ny, nx) stack."""
    collide = jnp.asarray(collide)
    grav = (s["GravitationX"], s["GravitationY"], s["GravitationZ"])

    def step(state):
        p = pull(state)
        rho = sum(p)
        u = tuple(_dot(ax, p) / rho for ax in range(3))
        feq = _equilibrium(rho, u)
        frc = force(state[19], s)
        shifted = _equilibrium(rho, tuple(
            u[ax] + frc[ax] / rho + grav[ax] for ax in range(3)))
        f = [jnp.where(collide,
                       q + s["omega"] * (e - q) + (sh - e), q)
             for q, e, sh in zip(p, feq, shifted)]
        return jnp.stack(f + [pseudopotential(f, s)])

    return step


def initial(density, s: dict, dtype) -> jnp.ndarray:
    rho = jnp.asarray(density, dtype)
    zero = jnp.zeros(rho.shape, dtype)
    f = _equilibrium(rho, (zero, zero, zero))
    return jnp.stack(f + [pseudopotential(f, s)])


def advance(step, f0, steps: int, storage=None) -> np.ndarray:
    """``reference.advance`` with its argument donated: the loop's carry
    takes the initial state's buffer."""
    dtype = f0.dtype

    def one(_, f):
        f = step(f)
        return f if storage is None else f.astype(storage).astype(dtype)

    loop = jax.jit(lambda f: jax.lax.fori_loop(0, steps, one, f),
                   donate_argnums=0)
    return np.asarray(loop(f0))


def run(root, steps: int, dtype=jnp.float32, storage=None) -> np.ndarray:
    """The 20 planes after ``steps`` steps of the case ``root`` (the
    parsed XML), as a host array of ``dtype``."""
    painted = zones3d.paint(root.find("Geometry"))
    par = geometry.params(root)
    s = settings(par)
    density = zones.zonal(par, painted, "Density", DEFAULTS["Density"])
    return advance(make_step(painted["collide"], s),
                   initial(density, s, dtype), steps, storage)
