"""Initial fields that are not constant per zone, for ``<CallPython>``.

A case names one of these functions in an element without
``Iterations``; it then runs once where the element stands, after
``<Model>`` has run the model's own Init (``control/handlers.py:
acCallPython``)::

    <CallPython module="tclb_tpu.control.initial" function="taylor_green"/>
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from tclb_tpu import telemetry
from tclb_tpu.ops import lbm


def taylor_green(solver) -> int:
    """The Taylor-Green vortex in a periodic box (Brachet et al., J.
    Fluid Mech. 130, 1983).  With ``L_a = n_a / (2 pi)`` along each axis
    and node ``(x, y, z)`` at its integer index::

        u   =  U0 sin(x/Lx) cos(y/Ly) cos(z/Lz)
        v   = -U0 cos(x/Lx) sin(y/Ly) cos(z/Lz)
        w   =  0
        rho =  1 + 3 (U0^2 / 16) (cos(2x/Lx) + cos(2y/Ly)) (cos(2z/Lz) + 2)

    ``U0`` is the model's ``Velocity`` setting.  The populations of the
    ``f`` group are set to their second-order equilibrium at (rho, u):
    a departure from a consistent start, which would add the
    non-equilibrium part that the velocity gradient calls for.  Every
    other plane stays as Init left it.  Any 3D model whose ``f`` group
    streams along its own velocity set takes it."""
    lat = solver.lattice
    m = lat.model
    if m.ndim != 3:
        raise ValueError("taylor_green needs a 3D model")
    idx = list(m.groups["f"])
    E = np.array([[m.densities[i].dx, m.densities[i].dy, m.densities[i].dz]
                  for i in idx])
    W = lbm.weights(E)
    nz, ny, nx = lat.shape
    dt = jnp.dtype(lat.dtype)
    # on a mesh the field is made in the lattice's own shards: at 384^3
    # the 27 planes are 6.1 GB, more than a chip holds beside its share
    # of the lattice
    on = None if lat.mesh is None else lat.state.fields.sharding

    @partial(jax.jit, out_shardings=on)
    def populations(U0):
        def angle(n, axis):
            a = jnp.arange(n, dtype=dt) * jnp.asarray(2.0 * math.pi / n, dt)
            return a.reshape([-1 if k == axis else 1 for k in range(3)])
        z, y, x = angle(nz, 0), angle(ny, 1), angle(nx, 2)
        u = U0 * jnp.sin(x) * jnp.cos(y) * jnp.cos(z)
        v = -U0 * jnp.cos(x) * jnp.sin(y) * jnp.cos(z)
        rho = 1.0 + 3.0 * (U0 * U0 / 16.0) * (
            jnp.cos(2.0 * x) + jnp.cos(2.0 * y)) * (jnp.cos(2.0 * z) + 2.0)
        return lbm.equilibrium(E, W, rho.astype(dt),
                               (u.astype(dt), v.astype(dt),
                                jnp.zeros((nz, ny, nx), dt)))

    U0 = jnp.asarray(lat.params.settings[m.setting_index["Velocity"]], dt)
    f = populations(U0)
    # on the element's own span: the set-up table reads its rows there
    telemetry.annotate("startup.element", sharded=on is not None,
                       bytes=int(f.nbytes))
    lat.set_density_planes({m.densities[i].name: f[k]
                            for k, i in enumerate(idx)})
    return 0
