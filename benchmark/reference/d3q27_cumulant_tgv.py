"""Plain reference of the Taylor-Green configuration (``tgv256``): the
d3q27 cumulant step of ``d3q27_cumulant.py``, imported as it stands,
from the Taylor-Green initial field.

Written from the case file and the published initial condition (Brachet
et al., J. Fluid Mech. 130, 1983; HiOCFD workshop case C3.5) alone; it
imports nothing of the program.  The case names the program's function
in a ``<CallPython>`` element; what that function has to produce is
stated here a second time.  With ``L_a = n_a / (2 pi)`` along each axis,
node ``(x, y, z)`` at its integer index and ``U0`` the case's
``Velocity``:

    u   =  U0 sin(x/Lx) cos(y/Ly) cos(z/Lz)
    v   = -U0 cos(x/Lx) sin(y/Ly) cos(z/Lz)
    w   =  0
    rho =  1 + 3 (U0^2 / 16) (cos(2x/Lx) + cos(2y/Ly)) (cos(2z/Lz) + 2)

and every population at its second-order equilibrium
``w rho (1 + 3 e.u + 4.5 (e.u)^2 - 1.5 u.u)``.  That is a departure
from a consistent start (the non-equilibrium part the velocity gradient
calls for is left out, by the program and here alike): the first steps
carry a small acoustic transient.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from benchmark.reference import advance, geometry
from benchmark.reference.d3q27_cumulant import E, make_step

FUNCTION = "taylor_green"


def initial(shape, U0: float, dtype) -> jnp.ndarray:
    """The 27 populations of the initial field on ``(nz, ny, nx)``."""
    def angle(n, axis):
        a = jnp.arange(n, dtype=dtype) * jnp.asarray(2.0 * math.pi / n, dtype)
        return a.reshape([-1 if k == axis else 1 for k in range(3)])
    z, y, x = (angle(n, k) for k, n in enumerate(shape))
    U0 = jnp.asarray(U0, dtype)
    u = (U0 * jnp.sin(x) * jnp.cos(y) * jnp.cos(z),
         -U0 * jnp.cos(x) * jnp.sin(y) * jnp.cos(z),
         jnp.zeros(shape, dtype))
    rho = 1.0 + 3.0 * (U0 * U0 / 16.0) * (
        jnp.cos(2.0 * x) + jnp.cos(2.0 * y)) * (jnp.cos(2.0 * z) + 2.0)
    usq = u[0] * u[0] + u[1] * u[1]
    out = []
    for e in E:
        w = {0: 8 / 27, 1: 2 / 27, 2: 1 / 54, 3: 1 / 216}[int((e * e).sum())]
        eu = sum(float(e[a]) * u[a] for a in range(3) if e[a])
        out.append((w * rho * (1.0 + 3.0 * eu + 4.5 * eu * eu - 1.5 * usq)
                    ).astype(dtype))
    return jnp.stack(out)


def run(root, steps: int, dtype=jnp.float32, storage=None) -> np.ndarray:
    """The 27 populations after ``steps`` steps of the case ``root``."""
    if not any(el.get("function") == FUNCTION and not el.get("Iterations")
               for el in root.findall("CallPython")):
        raise ValueError(f"the case sets no {FUNCTION} initial field")
    masks = geometry.paint(root.find("Geometry"))
    par = geometry.params(root)
    f0 = initial(masks["collide"].shape, par.get("Velocity", 0.0), dtype)
    return advance(make_step(masks, par), f0, steps, storage)
