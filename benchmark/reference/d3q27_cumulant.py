"""Plain reference of the d3q27 cumulant step (channel configurations).

Written from the scheme (Geier et al. 2015, all rates of order three and
above equal to one) and the case file alone; it imports nothing of the
program.  Populations are indexed ``n = 9 a + 3 b + c`` with velocity
``(a - 1, b - 1, c - 1)`` in (x, y, z).  One step:

1. pull streaming, periodic;
2. wall nodes: full bounce-back;
3. every other node collides: raw moments up to order two, the velocity,
   the six second-order central moments; the trace relaxes with
   ``omega_bulk`` (1.0), the deviatoric part and the off-diagonals with
   ``omega = 1 / (3 nu + 1/2)``, with the Galilean correction of the
   diagonal; every higher central moment is rebuilt from the relaxed
   covariance as that of a Gaussian (Isserlis), odd ones vanish; the
   body force shifts the velocity of the back transform.

Only the 27 populations are evolved; the model's seven further planes
(synthetic-turbulence buffers, running averages) never feed back into
them and are not compared.
"""

from __future__ import annotations

import itertools

import jax.numpy as jnp
import numpy as np

from benchmark.reference import advance, geometry

C = (-1, 0, 1)
E = np.array(list(itertools.product(C, C, C)))        # (27, 3): x, y, z
OPP = [int(np.where((E == -e).all(axis=1))[0][0]) for e in E]
N_PLANES = 27
T_INV = np.linalg.inv(np.array([[1.0, 1, 1], [-1, 0, 1], [1, 0, 1]]))


def _raw(F, p, q, r):
    """sum over a, b, c of cx^p cy^q cz^r F[a][b][c]."""
    acc = 0.0
    for a, b, c in itertools.product(range(3), repeat=3):
        k = C[a] ** p * C[b] ** q * C[c] ** r
        if k:
            acc = acc + float(k) * F[a][b][c]
    return acc


def _shift_axis(K, u, axis):
    """Central -> raw along one axis: m0 = k0, m1 = k1 + u k0,
    m2 = k2 + 2 u k1 + u^2 k0."""
    out = {}
    for idx in itertools.product(range(3), repeat=3):
        lo = list(idx)
        get = lambda o: K[tuple(lo[:axis] + [o] + lo[axis + 1:])]  # noqa: E731
        k0, k1, k2 = get(0), get(1), get(2)
        out[idx] = (k0, k1 + u * k0, k2 + 2.0 * u * k1 + u * u * k0)[
            idx[axis]]
    return out


def _invert_axis(Mm, axis):
    out = {}
    for idx in itertools.product(range(3), repeat=3):
        lo = list(idx)
        acc = 0.0
        for p in range(3):
            k = T_INV[idx[axis], p]
            if k:
                acc = acc + float(k) * Mm[tuple(lo[:axis] + [p]
                                                + lo[axis + 1:])]
        out[idx] = acc
    return out


def collide(F, omega, omega_bulk, force, galilean):
    rho = _raw(F, 0, 0, 0)
    jx, jy, jz = _raw(F, 1, 0, 0), _raw(F, 0, 1, 0), _raw(F, 0, 0, 1)
    ux, uy, uz = jx / rho, jy / rho, jz / rho
    kxx = _raw(F, 2, 0, 0) - jx * ux
    kyy = _raw(F, 0, 2, 0) - jy * uy
    kzz = _raw(F, 0, 0, 2) - jz * uz
    kxy = _raw(F, 1, 1, 0) - jx * uy
    kxz = _raw(F, 1, 0, 1) - jx * uz
    kyz = _raw(F, 0, 1, 1) - jy * uz

    cxx, cyy, czz = kxx / rho, kyy / rho, kzz / rho
    a = (1.0 - omega) * (cxx - cyy)
    b = (1.0 - omega) * (cxx - czz)
    cc = omega_bulk + (1.0 - omega_bulk) * (cxx + cyy + czz)
    uxh, uyh, uzh = (ux + 0.5 * force[0], uy + 0.5 * force[1],
                     uz + 0.5 * force[2])
    dxu = (-0.5 * omega * (2.0 * cxx - cyy - czz)
           - 0.5 * omega_bulk * (cxx + cyy + czz - 1.0))
    dyv = dxu + 1.5 * omega * (cxx - cyy)
    dzw = dxu + 1.5 * omega * (cxx - czz)
    a = a - galilean * 3.0 * (1.0 - 0.5 * omega) * (
        uxh * uxh * dxu - uyh * uyh * dyv)
    b = b - galilean * 3.0 * (1.0 - 0.5 * omega) * (
        uxh * uxh * dxu - uzh * uzh * dzw)
    cc = cc - galilean * 3.0 * (1.0 - 0.5 * omega_bulk) * (
        uxh * uxh * dxu + uyh * uyh * dyv + uzh * uzh * dzw)
    sxx = rho * (a + b + cc) / 3.0
    syy = rho * (cc - 2.0 * a + b) / 3.0
    szz = rho * (cc - 2.0 * b + a) / 3.0
    sxy, sxz, syz = ((1.0 - omega) * kxy, (1.0 - omega) * kxz,
                     (1.0 - omega) * kyz)

    zero = jnp.zeros_like(rho)
    K = {idx: zero for idx in itertools.product(range(3), repeat=3)}
    K[0, 0, 0] = rho
    K[2, 0, 0], K[0, 2, 0], K[0, 0, 2] = sxx, syy, szz
    K[1, 1, 0], K[1, 0, 1], K[0, 1, 1] = sxy, sxz, syz
    K[2, 2, 0] = (sxx * syy + 2.0 * sxy * sxy) / rho
    K[2, 0, 2] = (sxx * szz + 2.0 * sxz * sxz) / rho
    K[0, 2, 2] = (syy * szz + 2.0 * syz * syz) / rho
    K[2, 1, 1] = (sxx * syz + 2.0 * sxy * sxz) / rho
    K[1, 2, 1] = (syy * sxz + 2.0 * sxy * syz) / rho
    K[1, 1, 2] = (szz * sxy + 2.0 * sxz * syz) / rho
    K[2, 2, 2] = (sxx * syy * szz
                  + 2.0 * (sxx * syz * syz + syy * sxz * sxz
                           + szz * sxy * sxy)
                  + 8.0 * sxy * sxz * syz) / (rho * rho)

    for axis, u in enumerate((ux + force[0], uy + force[1], uz + force[2])):
        K = _shift_axis(K, u, axis)
    for axis in range(3):
        K = _invert_axis(K, axis)
    return K


def make_step(masks: dict, par: dict):
    """``step(f) -> f`` on a (27, nz, ny, nx) stack."""
    omega = 1.0 / (3.0 * par.get("nu", 1 / 6) + 0.5)
    omega_bulk = par.get("omega_bulk", 1.0)
    galilean = par.get("GalileanCorrection", 1.0)
    force = tuple(par.get(f"Force{a}", 0.0) + par.get(f"Gravitation{a}", 0.0)
                  for a in "XYZ")
    wall, collide_at = jnp.asarray(masks["wall"]), jnp.asarray(
        masks["collide"])
    if masks["inlet"].any() or masks["outlet"].any():
        raise ValueError("this reference has walls and periodic faces only")

    def step(f):
        p = [jnp.roll(f[n], (int(E[n, 2]), int(E[n, 1]), int(E[n, 0])),
                      (0, 1, 2)) for n in range(27)]
        p = [jnp.where(wall, p[OPP[n]], p[n]) for n in range(27)]
        F = [[[p[9 * a + 3 * b + c] for c in range(3)] for b in range(3)]
             for a in range(3)]
        K = collide(F, omega, omega_bulk, force, galilean)
        return jnp.stack([jnp.where(collide_at,
                                    K[n // 9, (n // 3) % 3, n % 3], p[n])
                          for n in range(27)])

    return step


def initial(masks: dict, par: dict, dtype) -> jnp.ndarray:
    shape = masks["wall"].shape
    rho = jnp.full(shape, par.get("Density", 1.0), dtype)
    u = (jnp.full(shape, par.get("Velocity", 0.0), dtype),
         jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
    usq = sum(c * c for c in u)
    out = []
    for e in E:
        w = {0: 8 / 27, 1: 2 / 27, 2: 1 / 54, 3: 1 / 216}[int((e * e).sum())]
        eu = sum(float(e[a]) * u[a] for a in range(3))
        out.append(w * rho * (1.0 + 3.0 * eu + 4.5 * eu * eu - 1.5 * usq))
    return jnp.stack(out)


def run(root, steps: int, dtype=jnp.float32, storage=None) -> np.ndarray:
    """The 27 populations after ``steps`` steps of the case ``root``."""
    masks = geometry.paint(root.find("Geometry"))
    par = geometry.params(root)
    return advance(make_step(masks, par), initial(masks, par, dtype),
                   steps, storage)
