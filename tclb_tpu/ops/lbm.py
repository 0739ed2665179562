"""Shared symbolic-free LBM math — the TPU-side equivalent of the reference's
R algebra library (reference src/lib/feq.R, src/lib/cumulant.R,
src/lib/lattice.R).  Where the reference emits closed-form C expressions from
symbolic algebra at build time, we compute the same quantities numerically
with numpy (constants) + jnp (traced), and let XLA do the fusing.
"""

from __future__ import annotations

import contextvars
import functools
import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

CS2 = 1.0 / 3.0  # lattice speed of sound squared


# True while a kernel body is being traced for a compiled (Mosaic)
# ``pallas_call``: Mosaic has no lowering for ``optimization_barrier``,
# so inside such a body ``pin`` must not emit one.
_IN_MOSAIC_BODY = contextvars.ContextVar("tclb_in_mosaic_body", default=False)


def pin(x):
    """Identity that pins ``x`` to one canonical evaluation: the
    compiler may not fuse ``x``'s producers into its consumers, so the
    multiply-add contraction of the producing graph no longer depends on
    where the value is used.  The engines' bit-parity contract (same
    model arithmetic on the XLA path and inside an interpret-mode Pallas
    kernel) needs this at fusion-sensitive seams.  Differentiable in
    reverse mode (the cotangent is pinned the same way), which the raw
    ``lax.optimization_barrier`` primitive is not.

    Inside a kernel body traced for the chip (``mosaic_body``) it is the
    plain identity: there the contract with the XLA step is a tolerance,
    not bit equality."""
    if _IN_MOSAIC_BODY.get():
        return x
    return _pin(x)


@jax.custom_vjp
def _pin(x):
    return jax.lax.optimization_barrier(x)


def _pin_fwd(x):
    return _pin(x), None


def _pin_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_pin.defvjp(_pin_fwd, _pin_bwd)


def mosaic_body(kernel, interpret: bool):
    """The kernel body to hand ``pl.pallas_call``: ``kernel`` itself in
    interpret mode (barriers kept, bit-parity with the XLA step), and
    for a compiled call a wrapper under which ``pin`` is the identity
    while the body is traced."""
    if interpret:
        return kernel

    @functools.wraps(kernel)
    def body(*refs, **kw):
        token = _IN_MOSAIC_BODY.set(True)
        try:
            return kernel(*refs, **kw)
        finally:
            _IN_MOSAIC_BODY.reset(token)

    return body


def present_types(model, flags: np.ndarray) -> set:
    """Node-type names actually present in a host flag field — used by the
    Pallas kernels to skip absent boundary cases (the reference gets the
    same effect from compile-time specialization of the generated kernel
    on the model's boundary set)."""
    flags = np.asarray(flags)
    out = set()
    for name, t in model.node_types.items():
        if ((flags & np.uint16(t.mask)) == np.uint16(t.value)).any():
            out.add(name)
    return out


def opposite(E: np.ndarray) -> np.ndarray:
    """Index i -> index of -e_i (bounce-back pairing)."""
    opp = np.zeros(len(E), dtype=np.int32)
    for i, e in enumerate(E):
        (j,) = np.where((E == -e).all(axis=1))
        opp[i] = j[0]
    return opp


def weights(E: np.ndarray) -> np.ndarray:
    """Standard lattice weights by speed shell (works for d2q9/d3q19/d3q27)."""
    q, d = E.shape
    table = {
        (9, 2): {0: 4 / 9, 1: 1 / 9, 2: 1 / 36},
        (19, 3): {0: 1 / 3, 1: 1 / 18, 2: 1 / 36},
        (27, 3): {0: 8 / 27, 1: 2 / 27, 2: 1 / 54, 3: 1 / 216},
        (5, 2): {0: 1 / 3, 1: 1 / 6},
        (7, 3): {0: 1 / 4, 1: 1 / 8},
    }[(q, d)]
    return np.array([table[int((e * e).sum())] for e in E])


def edot(vec, stack) -> jnp.ndarray:
    """``sum_i vec[i] * stack[i]`` over the leading (population) axis,
    unrolled with SCALAR coefficients and exact-zero terms skipped.

    The kernel-safe replacement for
    ``jnp.tensordot(jnp.asarray(vec, dt), stack, axes=1)``: Pallas
    rejects kernels that capture constant ARRAYS (the materialized
    ``vec``), and the tiny q-length contraction would otherwise become a
    padded MXU pass.  Works identically under XLA (constant-folded
    adds), so model code uses this one form for both engines."""
    acc = None
    for i, v in enumerate(np.asarray(vec)):
        v = float(v)
        if v == 0.0:
            continue
        t = stack[i] if v == 1.0 else (-stack[i] if v == -1.0
                                       else v * stack[i])
        acc = t if acc is None else acc + t
    return acc if acc is not None else jnp.zeros_like(stack[0])


def perm(stack, idx) -> jnp.ndarray:
    """Reorder the leading (population) axis by a CONSTANT permutation:
    ``stack[idx]`` as a static unstack/restack — the only form Mosaic
    accepts inside a Pallas kernel (no gather, no captured index
    array); XLA folds it to the same free layout change."""
    return jnp.stack([stack[int(k)] for k in np.asarray(idx)])


def wstack(w, value) -> jnp.ndarray:
    """``(q, *shape)`` stack of ``w[i] * value`` with SCALAR weight
    coefficients — the kernel-safe replacement for broadcasting a
    materialized ``(q,1,1)`` weight-vector constant (which Pallas rejects
    as a captured array).  ``value`` may be a plane or a traced scalar."""
    return jnp.stack([float(wi) * value for wi in np.asarray(w)])


def equilibrium(E: np.ndarray, W: np.ndarray, rho, u):
    """Second-order Maxwell equilibrium
    f_i = w_i rho (1 + e.u/cs2 + (e.u)^2/(2 cs4) - u^2/(2 cs2)).

    ``u`` is a tuple of velocity planes; returns a (Q, *shape) stack.
    """
    dt = rho.dtype
    usq = sum(c * c for c in u)
    out = []
    for i in range(len(E)):
        # skip exact-zero velocity components so XLA sees fewer ops
        eu = sum(float(E[i, a]) * u[a] for a in range(len(u)) if E[i, a])
        if isinstance(eu, int):  # rest population: e.u == 0
            common = 1.0 - usq / (2 * CS2)
        else:
            common = 1.0 + eu / CS2 + eu * eu / (2 * CS2 * CS2) \
                - usq / (2 * CS2)
        out.append(jnp.asarray(float(W[i]), dt) * rho * common)
    # pinned so f_eq gets ONE canonical evaluation: fused into its
    # consumers (f - feq, relax + feq2, ...) the compiler contracts the
    # multiply-add chains differently depending on the surrounding
    # graph, so the same source gives 1-ULP-different values in the XLA
    # step vs a Pallas kernel — which breaks the engines' bit-parity
    # contract.  Costs one materialized (q, *shape) temp.
    return pin(jnp.stack(out))


def mrt_basis_d2q9(E: np.ndarray) -> np.ndarray:
    """Orthogonal (Gram-Schmidt) d2q9 moment basis of Lallemand & Luo:
    rows = (rho, jx, jy, e, eps, qx, qy, pxx, pxy) as integer polynomials of
    the velocity set.  Matches the basis the reference builds symbolically in
    src/lib/feq.R (used at src/d2q9/Dynamics.c.Rt:234-243)."""
    ex, ey = E[:, 0].astype(np.float64), E[:, 1].astype(np.float64)
    e2 = ex * ex + ey * ey
    M = np.stack([
        np.ones_like(ex),               # rho
        ex,                             # jx
        ey,                             # jy
        3.0 * e2 - 4.0,                 # e (energy)
        4.5 * e2 * e2 - 10.5 * e2 + 4.0,  # eps (energy squared)
        (3.0 * e2 - 5.0) * ex,          # qx (energy flux)
        (3.0 * e2 - 5.0) * ey,          # qy
        ex * ex - ey * ey,              # pxx
        ex * ey,                        # pxy
    ])
    # sanity: rows orthogonal
    g = M @ M.T
    assert np.allclose(g - np.diag(np.diag(g)), 0.0), "basis not orthogonal"
    return M


def d3q19_velocities() -> np.ndarray:
    """Standard 19-velocity set: rest, 6 axis, 12 edge vectors (reference
    src/lib/d3q19.R ordering is its own; ours is shell-ordered)."""
    E = [(0, 0, 0)]
    for a in range(3):
        for s in (1, -1):
            v = [0, 0, 0]
            v[a] = s
            E.append(tuple(v))
    for a in range(3):
        for b in range(a + 1, 3):
            for sa in (1, -1):
                for sb in (1, -1):
                    v = [0, 0, 0]
                    v[a], v[b] = sa, sb
                    E.append(tuple(v))
    return np.array(E, dtype=np.int32)


def d3q27_velocities() -> np.ndarray:
    """Tensor-product 27-velocity set (cumulant reshape order)."""
    from tclb_tpu.ops.cumulant import velocity_set
    return velocity_set(3)


def gram_schmidt_basis(E: np.ndarray) -> np.ndarray:
    """Orthogonal moment basis over a velocity set by Gram-Schmidt on the
    monomials 1, ex, ey[, ez], exey, ... in graded order — the numerical
    equivalent of the reference's symbolically-built MRT bases
    (src/lib/feq.R MRT_polyMatrix).  Rows ordered by total degree; the
    first 1+d rows are the conserved (rho, j) moments."""
    q, d = E.shape
    polys = []
    degs = []
    for total in range(0, 3 * d + 1):
        for px in range(total + 1):
            for py in range(total - px + 1):
                pz = total - px - py
                if d == 2 and pz:
                    continue
                p = (px, py) if d == 2 else (px, py, pz)
                if max(p) > 2:   # velocities in {-1,0,1}: e^3 == e
                    continue
                polys.append(p)
                degs.append(total)
    cols = []
    M = []
    for p in polys:
        row = np.ones(q)
        for a, pw in enumerate(p):
            row = row * E[:, a].astype(np.float64) ** pw
        # orthogonalize against accepted rows
        for r in M:
            row = row - r * (row @ r) / (r @ r)
        if (np.abs(row) > 1e-9).any():
            M.append(row)
            cols.append(p)
        if len(M) == q:
            break
    assert len(M) == q, f"basis incomplete: {len(M)}/{q}"
    return np.stack(M)


def bgk_collide(E: np.ndarray, W: np.ndarray, f: jnp.ndarray, omega,
                force=None, rho_u=None):
    """Plain BGK with optional velocity-shift (exact-difference) forcing.
    Returns (f', rho, u-tuple)."""
    rho = jnp.sum(f, axis=0)
    d = E.shape[1]
    u = tuple(edot(E[:, a], f) / rho for a in range(d))
    feq = equilibrium(E, W, rho, u)
    out = f + omega * (feq - f)
    if force is not None:
        u2 = tuple(u[a] + force[a] for a in range(d))
        out = out + (equilibrium(E, W, rho, u2) - feq)
    return out, rho, u


def nebb_boundary(E: np.ndarray, W: np.ndarray, OPP: np.ndarray,
                  f: jnp.ndarray, axis: int, side: int, kind: str, value,
                  vt: Optional[dict] = None):
    """Generic straight-wall velocity/pressure boundary by non-equilibrium
    bounce-back (Zou & He's closure generalized to any face/velocity set —
    the role of the reference's per-model ZouHe() template,
    src/lib/boundary.R).

    ``axis``: face normal axis (0=x, 1=y, 2=z); ``side``: +1 if the fluid
    lies in +axis direction (a "low" face), -1 for a "high" face;
    ``kind``: 'velocity' (``value`` = signed +axis velocity component) or
    'pressure' (``value`` = density).  Unknown populations (e.axis == side)
    get ``f_opp + 2 w rho (e.u)/cs2`` for the normal velocity, minus the
    tangential-momentum correction ``(e.t)(Q_t/2 - cs2 rho u_t)`` with
    ``Q_t`` the tangential momentum carried by the wall-parallel knowns
    (Zou & He's d2q9 ``0.5 (f[2]-f[4])`` terms, generalized to 3D a la
    Hecht & Harting) — the closure the reference ZouHe applies
    (src/lib/boundary.R); the imposed tangential velocity defaults to zero.

    ``vt`` optionally imposes NONZERO tangential velocities:
    ``{axis: value}`` planes/scalars — the reference lib ZouHe's ``V3``
    argument (used by the turbulent inlet,
    src/d3q27_cumulant/Dynamics.c.Rt:210-222): each adds ``rho v_t`` to
    the corresponding tangential momentum target.
    """
    # Unrolled over populations with float-scalar coefficients (no
    # constant coefficient VECTORS are materialized): identical algebra,
    # and the form Mosaic accepts when this runs inside a Pallas kernel
    # (ops/pallas_d3q.py) — Pallas rejects captured non-scalar constants.
    q = len(E)
    en = E[:, axis].astype(np.int64)
    tang_k = [k for k in range(q) if en[k] == 0]
    out_k = [k for k in range(q) if en[k] == -side]  # known, entering wall
    s_t = sum(f[k] for k in tang_k)
    s_o = sum(f[k] for k in out_k)
    if kind == "velocity":
        # value is the signed +axis velocity component at the wall
        un = value
        rho = (s_t + 2.0 * s_o) / (1.0 - side * un)
    else:
        rho = value
        un = side * (1.0 - (s_t + 2.0 * s_o) / rho)
    # non-equilibrium bounce-back: f_i = f_opp(i) + 6 w_i rho e_i.u
    corr = [6.0 * float(W[k]) * float(en[k]) * rho * un
            if en[k] else None for k in range(q)]
    # tangential closure: redistribute the excess tangential momentum of
    # the wall-parallel populations onto the unknowns, weight-proportional:
    # corr_i += 6 w_i e_t J_t with J_t = -3 q_t + rho v_t — exactly the
    # reference lib ZouHe's solved tangential moment + V3 shift
    # (src/lib/boundary.R:83-101; the hand-written d3q27 BCs' Jy/Jz =
    # tangential sums / (-1/3) are the same solve).  In d2q9 this reduces
    # to the classic 0.5 (f[2]-f[4]) terms (6 w_diag 3 = 1/2); a flat
    # 0.5 q_t per unknown would over-correct 3x on d3q19/d3q27 faces and
    # blow up under sheared/turbulent inflow.
    for t_ax in range(E.shape[1]):
        if t_ax == axis:
            continue
        et = E[:, t_ax].astype(np.int64)
        if not et.any():
            continue
        q_t = sum(float(et[k]) * f[k] for k in tang_k if et[k])
        j_t = -3.0 * q_t
        if vt and t_ax in vt:
            # full imposition: the j_t -> total-momentum slope of the 6 w
            # distribution is 1/3, so the target needs 3 rho v_t.  (The
            # reference lib ZouHe adds only rho V3 here — lib/boundary.R:
            # 83-101 — which imposes a third of the requested tangential
            # velocity; deliberate deviation, documented.)
            j_t = j_t + 3.0 * rho * vt[t_ax]
        for k in range(q):
            if en[k] == side and et[k]:
                add = 6.0 * float(W[k]) * float(et[k]) * j_t
                corr[k] = add if corr[k] is None else corr[k] + add
    return jnp.stack([
        f[int(OPP[k])] + (corr[k] if corr[k] is not None
                          else jnp.zeros_like(rho))
        if en[k] == side else f[k]
        for k in range(q)])


def _unrolled_matvec(mat: np.ndarray, f) -> jnp.ndarray:
    """mat @ f over the leading axis, unrolled with SCALAR coefficients.

    The moment matrices are tiny (q x q) with many +-1/0 entries; an
    einsum would become an MXU matmul with contraction dim q (padded to
    the 128 tile, then multiplied into several passes by the "highest"
    precision the engine demands) — measured ~2.5x slower than the
    equivalent unrolled VPU elementwise form on the d2q9 step.  Exact
    f32 arithmetic, and XLA constant-folds the 0/±1 entries."""
    rows = []
    for row in np.asarray(mat):
        acc = None
        for c, p in zip(row, f):
            c = float(c)
            if c == 0.0:
                continue
            t = p if c == 1.0 else (-p if c == -1.0 else c * p)
            acc = t if acc is None else acc + t
        rows.append(acc if acc is not None else jnp.zeros_like(f[0]))
    return jnp.stack(rows)


def smagorinsky_omega_unrolled(E: np.ndarray, f, feq, rho, omega0, smag):
    """Smagorinsky eddy-viscosity relaxation rate (Hou et al.):
    ``tau_eff = (tau0 + sqrt(tau0^2 + 18 sqrt(2) Cs^2 |Pi|/rho)) / 2``
    with ``|Pi|`` the Frobenius norm of the non-equilibrium momentum
    flux — the closed form the reference's LES models compute in-kernel
    (src/d2q9_les/Dynamics.c.Rt, src/d3q19_les).  The contraction is
    unrolled with SCALAR coefficients (Pallas rejects materialized
    constant coefficient vectors) — the one implementation every LES
    user (XLA models and Pallas kernels, 2D and 3D) shares."""
    d = E.shape[1]
    pi2 = None
    for a in range(d):
        for b in range(a, d):
            ks = [k for k in range(len(E)) if E[k, a] * E[k, b]]
            if not ks:
                continue
            pab = sum(float(E[k, a] * E[k, b]) * (f[k] - feq[k])
                      for k in ks)
            term = pab * pab * (1.0 if a == b else 2.0)
            pi2 = term if pi2 is None else pi2 + term
    tau0 = 1.0 / omega0
    tau_eff = 0.5 * (tau0 + jnp.sqrt(
        tau0 * tau0 + 18.0 * math.sqrt(2.0) * smag * smag
        * jnp.sqrt(pi2) / rho))
    return 1.0 / tau_eff


def two_rate_relax(M: np.ndarray, lo: int, hi: int, fneq,
                   keep_stress, keep_high) -> jnp.ndarray:
    """Relaxed non-equilibrium for a two-rate MRT: rows ``lo:hi`` of the
    orthogonal basis ``M`` (the stress group) keep ``keep_stress``, every
    higher row keeps ``keep_high``, conserved rows (0:lo) drop out.

    Uses the exact projection identity
    ``Minv @ (keep * M @ fneq) == keep_high * fneq
    + (keep_stress - keep_high) * P_s @ fneq``
    (valid because the conserved moments of ``fneq = f - feq`` vanish for
    a mass/momentum-conserving equilibrium), so only the |stress| = hi-lo
    rank-one projections are computed instead of a full q x (q - lo)
    moment transform pair — ~3x fewer multiply-adds on d3q19, identical
    algebra (the reference generator gets the same effect by emitting the
    symbolically simplified closed form, src/lib/feq.R MRT)."""
    norms = (M * M).sum(axis=1)
    mn = _unrolled_matvec(M[lo:hi], fneq)
    back = _unrolled_matvec((M[lo:hi] / norms[lo:hi, None]).T, mn)
    d = keep_stress - keep_high
    return jnp.stack([keep_high * fneq[k] + d * back[k]
                      for k in range(len(M))])


def moments(M: np.ndarray, f: jnp.ndarray) -> jnp.ndarray:
    """m = M f over the leading (population) axis."""
    return _unrolled_matvec(M, f)


def from_moments(M: np.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`moments` for an orthogonal (row) basis."""
    norm = (M * M).sum(axis=1)
    Minv = (M / norm[:, None]).T
    return _unrolled_matvec(Minv, m)
