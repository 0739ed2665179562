"""Differentiable Pallas fast path: ``custom_vjp`` around the fused
action chunk with a Pallas BACKWARD band kernel.

The reference's adjoint is itself a tuned device kernel: Tapenade emits
``Run_b`` and the generated adjoint streaming scatters through the margins
(reference src/cuda.cu.Rt:240-256 ``RunKernel<..., adjoint>``, transpose
access in src/LatticeAccess.inc.cpp.Rt:227-261), with a dedicated settings
tape for control gradients (src/cuda.cu.Rt:216 ``DynamicsS_b``,
tools/makeAD:24).  Here BOTH sweeps run the registry-driven band machinery
of the generic engine (ops/pallas_generic):

* the FORWARD is the generic kernel's in-kernel-globals flavor, fused
  ``k`` iterations per band pass (one HBM round trip per ``k`` steps);
* the BACKWARD band kernel re-traces the SAME action chain
  (``run_action_plan`` — the exact collide semantics of the forward
  kernel) on a band extended by the chain's total reach ``R`` and takes
  ``jax.vjp`` of it in-band.  A band of ``lambda_in`` rows ``[a, b)``
  receives cotangent only from output rows within ``R``; computing the
  chain on ``[a-R, b+R)`` from inputs on ``[a-2R, b+2R)`` (all inside the
  8-row DMA halo blocks) covers that cone exactly, so no cross-band
  scatter is needed — the transposed streaming falls out of the VJP of
  the in-band pull slices.

Because the VJP differentiates the full traced chain, the scope is the
generic engine's own: multi-stage actions, Field stencils, zonal
settings, and — unlike round 4 — cotangents for SETTINGS (accumulated
in-kernel across bands, the ``DynamicsS_b`` analogue) and for the aux
stack (zonal planes + Control ``_DT`` planes), which chain to
``params.time_series`` so OptimalControl/Fourier/BSpline control
gradients run fused too (``series=True`` flavor, one step per chunk).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tclb_tpu.core.lattice import LatticeState, SimParams
from tclb_tpu.core.registry import Model
from tclb_tpu.ops import fusion, pallas_generic
from tclb_tpu.ops.pallas_generic import (_HALO, KernelCtx, action_plan,
                                         run_action_plan)

_probe_cache: dict = {}


def max_chunk(model: Model, cap: int = 4) -> int:
    """Largest per-chunk iteration count ``k`` whose fused chain reach
    fits the backward kernel's halo budget (``2*R <= 8``: the in-band
    chain needs inputs ``2R`` beyond the band)."""
    best = 0
    for k in range(1, cap + 1):
        _, reach = action_plan(model, "Iteration", fuse=k)
        if 2 * max(reach, 1) <= _HALO:
            best = k
    return best


def supports_diff(model: Model, shape, dtype, series: bool = False) -> bool:
    """Whether the differentiable Pallas chunk covers this configuration:
    everything the forward generic kernel needs, plus aligned unpadded
    shapes (the backward band kernel has no ghost-row machinery), chain
    reach within the halo budget, and SUM Globals (the objective).

    3D models (d3q19_adj and friends) route to the z-slab flavor: the
    forward sweep runs the fused 3D Pallas engine, the backward the XLA
    whole-array chain (see :func:`_make_diff_step_3d`)."""
    if model.ndim == 3 and len(shape) == 3:
        return _supports_diff_3d(model, shape, dtype, series)
    if model.ndim != 2 or len(shape) != 2:
        return False
    if not pallas_generic.supports(model, shape, dtype, probe=False):
        return False
    ny, nx = (int(s) for s in shape)
    if ny % 8 or nx % 128:
        return False
    if pallas_generic._pad_rows(model, ny, nx, 1) != 0:
        return False
    if max_chunk(model) < 1:
        return False
    if not (1 <= model.n_globals <= 8) \
            or any(g.op != "SUM" for g in model.globals_):
        return False
    if len(model.settings) > 1024:
        return False   # the (8, 128) in-kernel settings-tape accumulator
    if series and not model.zonal_settings:
        return False
    # static gates from the analyzer: the backward kernel's scratch at
    # this width (ineligibility decided before any compile), and the
    # stencil-footprint safety verdict (a stage reading beyond its
    # declaration would make the band chain silently wrong)
    from tclb_tpu import analysis
    from tclb_tpu.analysis import resources
    if not resources.adjoint_static_ok(model, nx, series):
        return False
    if not analysis.kernel_safety_ok(model):
        return False
    # cache on the structural fingerprint, not id(model): rebuilt-but-
    # identical models share the verdict, and a recycled address can
    # never inherit a stale one.  Probe at the PRODUCTION chunk
    # k=max_chunk — the fused-chain trace the engine actually builds
    # (the historical k=1 probe validated a chain nobody runs).
    key = (model.fingerprint, nx, series)
    if key not in _probe_cache:
        try:
            step = make_diff_step(model, (16, nx), dtype, interpret=True,
                                  series=series,
                                  k=1 if series else max_chunk(model))
            n_aux = 1 + (2 if series else 1) * len(model.zonal_settings)
            fields = jax.ShapeDtypeStruct((model.n_storage, 16, nx), dtype)
            sett = jax.ShapeDtypeStruct((len(model.settings),), dtype)
            aux = jax.ShapeDtypeStruct((n_aux, 16, nx), dtype)

            def loss(f, s, a):
                out, g, g_last = step.arrays(f, s, a,
                                             jnp.zeros((1,), jnp.int32))
                return jnp.sum(out) + jnp.sum(g) + jnp.sum(g_last)

            jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)),
                           fields, sett, aux)
            _probe_cache[key] = True
        except Exception as e:  # noqa: BLE001 — untraceable = ineligible
            from tclb_tpu.utils import log
            log.debug(f"pallas_adjoint: {model.name} diff probe failed: "
                      f"{type(e).__name__}: {str(e)[:200]}")
            _probe_cache[key] = False
    return _probe_cache[key]


def _supports_diff_3d(model: Model, shape, dtype,
                      series: bool = False) -> bool:
    """3D eligibility: the generic z-slab engine must cover the
    configuration (its in-kernel-globals flavor is the forward sweep),
    the objective must be SUM Globals, and the traced grad probe at the
    production chunk size must go through.  When
    :func:`adjoint_slab_plan` finds a feasible ``(k, bz)`` the backward
    runs the fused z-slab ``Run_b`` kernel; otherwise the step degrades
    to the XLA-chain backward (still eligible — the forward sweep
    dominates a revolve adjoint).  The Control-series flavor is 2D-only
    for now."""
    if series:
        return False
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return False
    if not pallas_generic.supports(model, shape, dtype, probe=False):
        return False
    nz, ny, nx = (int(s) for s in shape)
    if ny % 8 or nx % 128:
        return False
    if not (1 <= model.n_globals <= 8) \
            or any(g.op != "SUM" for g in model.globals_):
        return False
    if len(model.settings) > 1024:
        return False   # the (8, 128) in-kernel settings-tape accumulator
    from tclb_tpu import analysis
    if not analysis.kernel_safety_ok(model):
        return False
    key = (model.fingerprint, tuple(shape), "3d")
    if key not in _probe_cache:
        try:
            step = make_diff_step(model, shape, dtype, interpret=True,
                                  k=max_chunk(model))
            fields = jax.ShapeDtypeStruct((model.n_storage,) + tuple(shape),
                                          dtype)
            flags = jax.ShapeDtypeStruct(tuple(shape), jnp.uint16)

            def loss(f):
                from tclb_tpu.core.lattice import LatticeState
                st = LatticeState(
                    fields=f,
                    flags=jnp.zeros(tuple(shape), jnp.uint16),
                    globals_=jnp.zeros((model.n_globals,), dtype),
                    iteration=jnp.zeros((), jnp.int32))
                st2, ginc = step.prepare(st, _probe_params(model, dtype))(
                    st, _probe_params(model, dtype))
                return jnp.sum(st2.fields) + jnp.sum(ginc)

            jax.eval_shape(jax.grad(loss), fields)
            del flags
            _probe_cache[key] = True
        except Exception as e:  # noqa: BLE001 — untraceable = ineligible
            from tclb_tpu.utils import log
            log.debug(f"pallas_adjoint: {model.name} 3d diff probe "
                      f"failed: {type(e).__name__}: {str(e)[:200]}")
            _probe_cache[key] = False
    return _probe_cache[key]


def _probe_params(model: Model, dtype):
    from tclb_tpu.core.lattice import SimParams
    n_sett = len(model.settings)
    return SimParams(settings=jnp.zeros((n_sett,), dtype),
                     zone_table=jnp.zeros((n_sett, model.zone_max), dtype))


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _roll3_prim(x, s, nx):
    return pltpu.roll(x, s, axis=2)


def _roll3_fwd(x, s, nx):
    return _roll3_prim(x, s, nx), None


def _roll3_bwd(s, nx, _res, ct):
    # same linearity argument as the 2D _roll_prim, lane axis 2: the
    # transpose of out[..., i] = x[..., i - s] is the opposite roll
    return (_roll3_prim(ct, (nx - s) % nx, nx),)


_roll3_prim.defvjp(_roll3_fwd, _roll3_bwd)


def _lane_roll3(sl, shift, nx):
    s = shift % nx
    return _roll3_prim(sl, s, nx) if s else sl


def adjoint_slab_plan(model: Model, shape, k: Optional[int] = None,
                      budget: Optional[int] = None):
    """The fused 3D backward's ``(k, bz)`` — None when no chunk/slab
    config fits the VMEM budget (the builder then degrades to the XLA
    backward).  Thin model-aware wrapper over
    :func:`tclb_tpu.ops.fusion.adjoint_slab_plan` so the builder, the
    eligibility gate and the static analyzers all plan identically."""
    nz, ny, nx = (int(s) for s in shape)
    if k is None:
        k = max_chunk(model)
    if k < 1:
        return None
    # the backward aux stack is one flags plane either way: zonal models
    # run the lean flavor (planes rebuilt in-kernel from the SMEM zone
    # table), zonal-free models have nothing beyond flags
    return fusion.adjoint_slab_plan(
        nz, model.n_storage, ny * nx * 4,
        lambda f: action_plan(model, "Iteration", fuse=f)[1], k,
        n_aux=1, budget=budget)


def _make_diff_step_3d(model: Model, shape, dtype=jnp.float32,
                       interpret: Optional[bool] = None,
                       present: Optional[set] = None,
                       k: Optional[int] = None,
                       bwd: str = "auto"):
    """The 3D differentiable chunk: ``custom_vjp`` pairing the z-slab
    Pallas engine's in-kernel-globals flavor (forward) with a z-slab
    Pallas BACKWARD band kernel — the 3D ``Run_b``.

    The backward mirrors the forward's DMA pipeline on slabs haloed by
    ``2*R`` (the adjoint-band rule: the in-band chain recomputes the
    forward cone AND transposes it, each costing reach ``R``), pulls the
    chunk-input primal + the output cotangent + the flags plane on three
    double-buffered stacks, re-traces the fused action chain FULL-SLAB
    (every per-row op identical to the windowed forward on the rows the
    window mask keeps) and takes ``jax.vjp`` of it in-band; the settings
    tape accumulates per-slab so band overlaps never double-count.
    ``bwd="xla"`` keeps the PR 9 hybrid (Pallas forward / XLA-chain
    backward), the baseline the fused backward is compared against;
    ``"auto"`` takes the fused kernel whenever :func:`adjoint_slab_plan`
    finds a feasible config."""
    nz, ny, nx = (int(s) for s in shape)
    if k is None:
        k = max_chunk(model)
    plan3 = adjoint_slab_plan(model, shape, k) if bwd != "xla" else None
    if bwd == "pallas" and plan3 is None:
        raise ValueError(f"{model.name} {shape}: no (k, bz) fits the "
                         "fused 3D backward's VMEM budget")
    fused = plan3 is not None
    if fused:
        # the chunk the WHOLE diff step (forward loop included) runs at:
        # a divisor of the requested k, so the caller's niter % k == 0
        # guarantee carries over
        k = plan3[0]
    base = pallas_generic.make_pallas_iterate_3d(
        model, shape, dtype, interpret=interpret, fuse=1, present=present)
    impl = base.impl
    call_g = impl["call_g"]
    if call_g is None:
        raise ValueError(f"{model.name}: 3D diff step needs the "
                         "in-kernel-globals flavor (SUM globals, "
                         "nx % 128 == 0)")
    lean = impl["lean_aux"]
    zonal_si, zshift = impl["zonal_si"], impl["zshift"]
    adv, cdtype = impl["adv"], impl["cdtype"]
    n_globals = model.n_globals
    from tclb_tpu.core.lattice import make_action_step
    xla_step = make_action_step(model, "Iteration", present=present)

    call_bwd = _mk_call_bwd_3d(model, shape, cdtype, interpret, present,
                               k, plan3[1], lean) if fused else None
    n_sett = len(model.settings)

    def _mk_step(params: SimParams, flags):
        if params.time_series is not None:
            raise ValueError(
                "the 3D diff step has no Control-series flavor; use "
                "engine='xla' for series designs")
        @jax.custom_vjp
        def chunk(fields, p, fl, itv):
            flags_i32 = fl.astype(jnp.int32)
            sett = p.settings.astype(cdtype)
            if lean:
                ztab = jnp.concatenate(
                    [p.zone_table[j].astype(cdtype) for j in zonal_si])
                aux = flags_i32.astype(cdtype)[None]

                def call(f, it):
                    return call_g(sett, it[None], ztab, f, aux)
            else:
                zones = flags_i32 >> zshift
                aux = jnp.stack(
                    [flags_i32.astype(cdtype)]
                    + [fusion.zone_plane(p.zone_table[j].astype(cdtype),
                                         zones) for j in zonal_si])

                def call(f, it):
                    return call_g(sett, it[None], f, aux)
            f, gs, gl = fields, None, None
            for j in range(k):
                f, gpart = call(f, itv + adv * j)
                g_now = gpart[:n_globals].sum(axis=1)
                gs = g_now if gs is None else gs + g_now
                gl = g_now
            return f, gs, gl

        def chunk_fwd(fields, p, fl, itv):
            return chunk(fields, p, fl, itv), (fields, p, fl, itv)

        def chunk_bwd_xla(res, cot):
            fields, p, fl, itv = res
            cot_f, cot_g, cot_gl = cot

            def ref(fs, pp):
                st = LatticeState(
                    fields=fs, flags=fl,
                    globals_=jnp.zeros((n_globals,), cdtype),
                    iteration=itv)
                gs = None
                for _ in range(k):
                    st = xla_step(st, pp)
                    gs = st.globals_ if gs is None else gs + st.globals_
                return st.fields, gs, st.globals_

            (_, gs_ref, gl_ref), vjp = jax.vjp(ref, fields, p)
            cf, cp = vjp((cot_f.astype(fields.dtype),
                          cot_g.astype(gs_ref.dtype),
                          cot_gl.astype(gl_ref.dtype)))
            return (cf, cp,
                    np.zeros(np.shape(fl), jax.dtypes.float0),
                    np.zeros(np.shape(itv), jax.dtypes.float0))

        def chunk_bwd_pallas(res, cot):
            fields, p, fl, itv = res
            cot_f, cot_g, cot_gl = cot
            lg = jnp.stack([cot_g.astype(cdtype), cot_gl.astype(cdtype)])
            sett = p.settings.astype(cdtype)
            flags_i32 = fl.astype(jnp.int32)
            it_arr = jnp.asarray(itv, jnp.int32).reshape((1,))
            lam_f_ct = cot_f.astype(cdtype)
            if lean:
                ztab = jnp.concatenate(
                    [p.zone_table[j].astype(cdtype) for j in zonal_si])
                aux = flags_i32.astype(cdtype)[None]
                lam_f, sett_acc = call_bwd(sett, lg, it_arr, ztab,
                                           fields.astype(cdtype),
                                           lam_f_ct, aux)
            else:
                zones = flags_i32 >> zshift
                aux = jnp.stack(
                    [flags_i32.astype(cdtype)]
                    + [fusion.zone_plane(p.zone_table[j].astype(cdtype),
                                         zones) for j in zonal_si])
                lam_f, sett_acc = call_bwd(sett, lg, it_arr,
                                           fields.astype(cdtype),
                                           lam_f_ct, aux)
            lam_sett = sett_acc.reshape(-1)[:n_sett]
            # non-series 3D: cotangents flow to the scalar settings (the
            # in-kernel tape); the zone-table/aux cotangent is zero —
            # the same aux_grad=False contract as the 2D default
            cp = jax.tree.map(jnp.zeros_like, p)
            cp = cp.replace(settings=lam_sett.astype(p.settings.dtype))
            return (lam_f.astype(fields.dtype), cp,
                    np.zeros(np.shape(fl), jax.dtypes.float0),
                    np.zeros(np.shape(itv), jax.dtypes.float0))

        chunk.defvjp(chunk_fwd,
                     chunk_bwd_pallas if fused else chunk_bwd_xla)

        def step(state: LatticeState, p2: SimParams):
            new_fields, g, g_last = chunk(state.fields, p2, state.flags,
                                          state.iteration)
            return LatticeState(
                fields=new_fields, flags=state.flags,
                globals_=g_last.astype(state.globals_.dtype),
                iteration=state.iteration + adv * k), g
        return step

    def step(state: LatticeState, params: SimParams):
        return _mk_step(params, state.flags)(state, params)

    def prepare(state: LatticeState, params: SimParams):
        return _mk_step(params, state.flags)

    step.prepare = prepare
    step.chunk = k
    step.returns_inc = True
    if fused:
        step.engine_name = (f"pallas_adjoint[{model.name},k={k},"
                            f"bz={plan3[1]},3d]")
    else:
        step.engine_name = (f"pallas_adjoint3d[{model.name},k={k},"
                            f"bz={impl['bz']},bwd=xla]")
    return step


def _mk_call_bwd_3d(model: Model, shape, cdtype, interpret, present,
                    k: int, bz: int, lean: bool):
    """Build the z-slab backward band kernel (``Run_b``): one grid step
    per slab band, halo = ``2 * R(k)`` slabs per side (adjoint-band
    rule), three double-buffered DMA stacks (chunk-input primal, output
    cotangent, flags/aux), in-band ``jax.vjp`` of the full-slab fused
    action chain.  Returns ``call(sett, lg, it, [ztab,] primal, lam_out,
    aux) -> (lam_in, settings_tape)``."""
    nz, ny, nx = (int(s) for s in shape)
    plan_k, reach_k = action_plan(model, "Iteration", fuse=k)
    Rk = max(reach_k, 1)
    Hb = bz + 4 * Rk
    ns = model.n_storage
    n_globals = model.n_globals
    n_sett = len(model.settings)
    zonal_names = list(model.zonal_settings)
    zone_max = model.zone_max
    zshift = model.zone_shift
    n_aux = 1 if lean else 1 + len(zonal_names)
    n_sem = 1 + 4 * Rk
    ei = model.ei
    stage_fns = {nm: model.stage_fns[model.stages[nm].main]
                 for nm in model.actions["Iteration"]}
    loads_density = {nm: model.stages[nm].load_densities
                     for nm in model.actions["Iteration"]}
    nt_present = set(model.node_types) if present is None else set(present)
    n_per_rep = len(model.actions["Iteration"])
    adv = int(any(model.stages[s].load_densities
                  for s in model.actions["Iteration"]))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def bwd_kernel(sett, lg_ref, it_ref, *rest):
        if lean:
            ztab, p_hbm, l_hbm, a_hbm, *refs = rest
        else:
            ztab = None
            p_hbm, l_hbm, a_hbm, *refs = rest
        out_lam, out_sett, bufp, bufl, bufa, sems = refs
        i = pl.program_id(0)
        n = pl.num_programs(0)

        def band_dmas(slot, band):
            # halo slabs one at a time with modular indices (a block
            # copy straddling the periodic z boundary would read out of
            # bounds — same scheme as the forward slab kernel, halo 2R)
            base = band * jnp.int32(bz)
            out = []
            for si_, (hbm, buf, nplanes) in enumerate((
                    (p_hbm, bufp, ns), (l_hbm, bufl, ns),
                    (a_hbm, bufa, n_aux))):
                out.append(pltpu.make_async_copy(
                    hbm.at[pl.ds(0, nplanes), pl.ds(base, bz)],
                    buf.at[slot, :, pl.ds(2 * Rk, bz)],
                    sems.at[slot, n_sem * si_]))
                for r in range(2 * Rk):
                    zm_r = jax.lax.rem(
                        base - jnp.int32(2 * Rk - r) + jnp.int32(nz),
                        jnp.int32(nz))
                    zp_r = jax.lax.rem(base + jnp.int32(bz + r),
                                       jnp.int32(nz))
                    out.append(pltpu.make_async_copy(
                        hbm.at[pl.ds(0, nplanes), pl.ds(zm_r, 1)],
                        buf.at[slot, :, pl.ds(r, 1)],
                        sems.at[slot, n_sem * si_ + 1 + r]))
                    out.append(pltpu.make_async_copy(
                        hbm.at[pl.ds(0, nplanes), pl.ds(zp_r, 1)],
                        buf.at[slot, :, pl.ds(2 * Rk + bz + r, 1)],
                        sems.at[slot, n_sem * si_ + 1 + 2 * Rk + r]))
            return out

        slot = jax.lax.rem(i, jnp.int32(2))
        nxt = jax.lax.rem(i + jnp.int32(1), jnp.int32(2))

        @pl.when(i == 0)
        def _():
            for d in band_dmas(jnp.int32(0), i):
                d.start()

        @pl.when(i + 1 < n)
        def _():
            for d in band_dmas(nxt, i + jnp.int32(1)):
                d.start()

        for d in band_dmas(slot, i):
            d.wait()

        sv = jnp.stack([sett[j] for j in range(n_sett)])
        it0 = it_ref[0]
        # settings enter the trace PER SLAB: the cotangent seeds span the
        # R-extended window overlapping the neighbor bands' windows, so a
        # scalar settings cotangent would double-count the margin slabs;
        # slab-resolved cotangents can be band-trimmed before the
        # cross-band accumulation (the 2D tape's argument, z-banded)
        sv_rows = jnp.broadcast_to(sv[None, :], (Hb, n_sett))

        class _RowSett3:
            def __init__(self, rows):
                self._rows = rows

            def __getitem__(self, j):
                return self._rows[:, j][:, None, None]

        flags_full = bufa[slot, 0].astype(jnp.int32)
        if ztab is not None:
            zones_full = flags_full >> zshift
            zonal_full = {nm: fusion.zone_plane(ztab, zones_full,
                                                zone_max, col=j)
                          for j, nm in enumerate(zonal_names)}
        else:
            zonal_full = {nm: bufa[slot, 1 + j]
                          for j, nm in enumerate(zonal_names)}

        def _rollyx(sl, dy, dx):
            if dy:
                sl = jnp.roll(sl, dy, axis=1)
            if dx % nx:
                sl = _lane_roll3(sl, dx, nx)
            return sl

        def C(work, sv_rows_):
            """The forward chunk traced FULL-SLAB from this band's
            buffers: per-row ops identical to the windowed forward
            kernel (z pulls become axis-0 rolls whose wrap garbage stays
            in the outermost ``Rk`` slabs), so rows inside the window
            mask below linearize exactly the physics that ran."""
            work = list(work)
            g_acc: dict = {}
            g_lst: dict = {}
            for st_i, (stage_name, _ext) in enumerate(plan_k):
                rep = st_i // n_per_rep
                if loads_density[stage_name]:
                    planes = []
                    for k_ in range(ns):
                        dxk, dyk, dzk = (int(v) for v in ei[k_])
                        sl = jnp.roll(work[k_], dzk, axis=0) if dzk \
                            else work[k_]
                        planes.append(_rollyx(sl, dyk, dxk))
                else:
                    planes = list(work)

                def loader(index, dx, dy, dz=0):
                    sl = work[index]
                    if dz:
                        sl = jnp.roll(sl, -dz, axis=0)
                    return _rollyx(sl, -dy, -dx)

                ctx = KernelCtx(
                    model, planes, loader, flags_full, dict(zonal_full),
                    _RowSett3(sv_rows_), cdtype, it0 + adv * rep,
                    nt_present, compute_globals=True)
                res = stage_fns[stage_name](ctx)
                for nm, plane in ctx._globals.items():
                    g_acc[nm] = plane if nm not in g_acc \
                        else g_acc[nm] + plane
                    if rep == k - 1:
                        g_lst[nm] = plane if nm not in g_lst \
                            else g_lst[nm] + plane

                if isinstance(res, dict):
                    updates: dict[int, jnp.ndarray] = {}
                    for name, stack in res.items():
                        if name in model.groups:
                            idx = model.groups[name]
                            if len(idx) == 1 and stack.ndim == 3:
                                updates[idx[0]] = stack
                            else:
                                for j, k_ in enumerate(idx):
                                    updates[k_] = stack[j]
                        else:
                            updates[model.storage_index[name]] = stack
                else:
                    updates = {k_: res[k_] for k_ in range(ns)}
                for k_, new in updates.items():
                    work[k_] = new
            zero_pl = jnp.zeros((Hb, ny, nx), cdtype)
            gpl = [g_acc.get(g.name, zero_pl) for g in model.globals_]
            gll = [g_lst.get(g.name, zero_pl) for g in model.globals_]
            return jnp.stack(work), jnp.stack(gpl), jnp.stack(gll)

        pst = [bufp[slot, j] for j in range(ns)]
        _, vjp_fn = jax.vjp(C, pst, sv_rows)
        # cotangent seeds live on the R-extended output window
        # [band - R, band + bz + R): slabs beyond it either belong to the
        # neighbor bands' lambda_in (they own those output slabs) or hold
        # full-slab roll garbage — both masked to zero
        rows = jax.lax.broadcasted_iota(jnp.int32, (Hb, ny, nx), 0)
        win = (rows >= Rk) & (rows < bz + 3 * Rk)
        zero_pl = jnp.zeros((Hb, ny, nx), cdtype)
        lam_win = jnp.stack(
            [jnp.where(win, bufl[slot, j], zero_pl) for j in range(ns)])
        lgpl = jnp.stack(
            [jnp.where(win, jnp.full((Hb, ny, nx), lg_ref[0, gi], cdtype),
                       zero_pl) for gi in range(n_globals)])
        lgll = jnp.stack(
            [jnp.where(win, jnp.full((Hb, ny, nx), lg_ref[1, gi], cdtype),
                       zero_pl) for gi in range(n_globals)])
        lam_p, lam_sv_rows = vjp_fn((lam_win, lgpl, lgll))

        for j in range(ns):
            out_lam[j] = lam_p[j][2 * Rk:2 * Rk + bz]

        @pl.when(i == 0)
        def _():
            out_sett[...] = jnp.zeros((8, 128), cdtype)
        # band slabs only: margin slabs belong to the neighbor bands
        lam_sv = lam_sv_rows[2 * Rk:2 * Rk + bz, :].sum(axis=0)
        pad_s = jnp.concatenate(
            [lam_sv, jnp.zeros((1024 - n_sett,), cdtype)]).reshape((8, 128))
        out_sett[...] = out_sett[...] + pad_s

    return pl.pallas_call(
        bwd_kernel,
        grid=(nz // bz,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ] + ([pl.BlockSpec(memory_space=pltpu.SMEM)] if lean else [])
        + [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((ns, bz, ny, nx), lambda i: (0, i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, 128), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ns, nz, ny, nx), cdtype),
            jax.ShapeDtypeStruct((8, 128), cdtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, ns, Hb, ny, nx), cdtype),
            pltpu.VMEM((2, ns, Hb, ny, nx), cdtype),
            pltpu.VMEM((2, n_aux, Hb, ny, nx), cdtype),
            pltpu.SemaphoreType.DMA((2, 3 * n_sem)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )


def make_diff_step(model: Model, shape, dtype=jnp.float32,
                   interpret: Optional[bool] = None,
                   present: Optional[set] = None,
                   k: Optional[int] = None,
                   series: bool = False,
                   aux_grad: Optional[bool] = None,
                   by_bwd: Optional[int] = None,
                   bwd: str = "auto"):
    """Build ``step(state, params) -> (state, chunk_globals)`` advancing
    ``step.chunk`` iterations on the fused Pallas kernels,
    differentiable end-to-end: forward = the generic engine's
    in-kernel-globals flavor at ``fuse=k``, backward = the in-band VJP
    of the same chain (module docstring).  Plugs into
    :func:`tclb_tpu.adjoint.run.make_objective_run` via the
    ``returns_inc`` protocol: ``state.globals_`` keeps LAST-iteration
    semantics (matching the per-step engines) while ``chunk_globals``
    is the k-step sum the time-integrated objective accumulates.

    ``series=True`` builds the Control-series flavor: one step per
    chunk, per-iteration zonal + ``_DT`` aux planes rebuilt (and
    differentiated) each step, cotangents flowing to
    ``params.time_series`` — the reference's control-gradient tape.
    ``aux_grad`` (default = ``series``) controls whether the backward
    kernel emits the aux-stack cotangent at all (an extra HBM write).

    3D shapes dispatch to :func:`_make_diff_step_3d` (z-slab Pallas
    forward AND backward; ``bwd="xla"`` keeps the PR 9 hybrid as the
    measured baseline; no series flavor)."""
    if len(shape) == 3:
        if series:
            raise ValueError("3D diff step: no Control-series flavor")
        return _make_diff_step_3d(model, shape, dtype,
                                  interpret=interpret, present=present,
                                  k=k, bwd=bwd)
    ny, nx = (int(s) for s in shape)
    if series:
        k = 1
    if k is None:
        k = max_chunk(model)
    if aux_grad is None:
        aux_grad = series
    plan_k, reach = action_plan(model, "Iteration", fuse=k)
    R = max(reach, 1)
    if 2 * R > _HALO:
        raise ValueError(f"chunk k={k} reach {reach} exceeds halo budget")
    if ny % 8 or nx % 128:
        raise ValueError(f"diff step needs aligned shape, got {shape}")

    # full_band: all-aligned stage windows — measurably faster at fuse=k
    # and REQUIRED for the backward chain (the VJP cone arithmetic below
    # assumes full-height stages)
    base = pallas_generic.make_pallas_iterate(
        model, shape, dtype, interpret=interpret, fuse=1, present=present,
        full_band=True)
    impl = base.impl
    if impl["pad"] != 0:
        raise ValueError("diff step requires an unpadded band layout")
    mk_call = impl["mk_call"]
    call_f = mk_call(plan_k, with_dt=series, with_globals="split")
    zonal_si, zshift = impl["zonal_si"], impl["zshift"]
    nt_present = impl["nt_present"]
    # backward bands default WIDER than the forward's (64 vs 32): the
    # halo margin is pure compute waste for the in-band chain, and the
    # k=4/by=64 point measured fastest on v5e (raised vmem ceiling
    # below).  The default scales down with nx so the three
    # double-buffered scratch stacks stay within ~1/4 of the raised
    # ceiling, leaving room for the VJP chain's live temporaries.
    if by_bwd is None:
        n_aux_b = 1 + (2 if series else 1) * len(model.zonal_settings)
        per_row = (2 * model.n_storage + n_aux_b) * nx * 4
        by_bwd = 64
        while by_bwd > 8 and 2 * (by_bwd + 2 * _HALO) * per_row \
                > 24 * 1024 * 1024:
            by_bwd -= 8
    by = max(8, (by_bwd // 8) * 8)
    while by > 8 and ny % by:
        by -= 8
    if ny % by:
        raise ValueError(f"no 8-aligned backward band divides ny={ny}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    ns = model.n_storage
    n_globals = model.n_globals
    n_sett = len(model.settings)
    zonal_names = list(model.zonal_settings)
    n_aux = 1 + (2 if series else 1) * len(zonal_names)
    n_per_rep = len(model.actions["Iteration"])
    adv = int(any(model.stages[s].load_densities
                  for s in model.actions["Iteration"]))

    def bwd_kernel(sett, lg_ref, it_ref, p_hbm, l_hbm, aux_hbm, *refs):
        """One band pass of the reverse sweep: pulled primal chunk-input
        + lambda_out + aux on 8-row-haloed bands, in-band VJP of the
        traced action chain, emitting the band's lambda_in rows plus the
        accumulated settings tape (and optionally the aux cotangent)."""
        if aux_grad:
            out_lam, out_sett, out_laux, bufp, bufl, bufa, sems = refs
        else:
            (out_lam, out_sett, bufp, bufl, bufa, sems), out_laux = \
                refs, None
        i = pl.program_id(0)
        n = pl.num_programs(0)

        def band_dmas(slot, band):
            base_r = pl.multiple_of(band * jnp.int32(by), 8)
            top8 = pl.multiple_of(
                jax.lax.rem(base_r - jnp.int32(_HALO) + jnp.int32(ny),
                            jnp.int32(ny)), 8)
            bot8 = pl.multiple_of(
                jax.lax.rem(base_r + jnp.int32(by), jnp.int32(ny)), 8)
            out = []
            for si_, (hbm, buf) in enumerate(
                    ((p_hbm, bufp), (l_hbm, bufl), (aux_hbm, bufa))):
                out += [
                    pltpu.make_async_copy(
                        hbm.at[:, pl.ds(base_r, by), :],
                        buf.at[slot, :, pl.ds(_HALO, by), :],
                        sems.at[slot, 3 * si_]),
                    pltpu.make_async_copy(
                        hbm.at[:, pl.ds(top8, _HALO), :],
                        buf.at[slot, :, pl.ds(0, _HALO), :],
                        sems.at[slot, 3 * si_ + 1]),
                    pltpu.make_async_copy(
                        hbm.at[:, pl.ds(bot8, _HALO), :],
                        buf.at[slot, :, pl.ds(_HALO + by, _HALO), :],
                        sems.at[slot, 3 * si_ + 2]),
                ]
            return out

        slot = jax.lax.rem(i, jnp.int32(2))
        nxt = jax.lax.rem(i + jnp.int32(1), jnp.int32(2))

        @pl.when(i == 0)
        def _():
            for d in band_dmas(jnp.int32(0), i):
                d.start()

        @pl.when(i + 1 < n)
        def _():
            for d in band_dmas(nxt, i + jnp.int32(1)):
                d.start()

        for d in band_dmas(slot, i):
            d.wait()

        sv = jnp.stack([sett[j] for j in range(n_sett)])
        it0 = it_ref[0]
        H = by + 2 * _HALO
        # settings enter the trace PER ROW: the cotangent seeds below span
        # the R-extended window, which overlaps the neighboring bands'
        # windows — a scalar settings cotangent would double-count the
        # margin rows across bands.  Row-resolved cotangents can be
        # band-masked before the cross-band accumulation.
        sv_rows = jnp.broadcast_to(sv[None, :], (H, n_sett))

        class _RowSett:
            def __init__(self, rows):
                self._rows = rows

            def __getitem__(self, i):
                return self._rows[:, i][:, None]

        def C(work, aux_pl, sv_rows_):
            """The forward chunk traced full-band from this band's
            buffers — run_action_plan is the SAME function the forward
            kernel executes, so the VJP transposes exactly the physics
            that ran.  full_band keeps every op tile-aligned; the edge
            rows beyond the chain's reach hold garbage, which the
            WINDOW-MASKED seeds below exclude from the cotangent."""
            flags_full = aux_pl[0].astype(jnp.int32)
            zonal_full = {nm: aux_pl[1 + j]
                          for j, nm in enumerate(zonal_names)}
            dt_full = {nm: aux_pl[1 + len(zonal_names) + j]
                       for j, nm in enumerate(zonal_names)} if series else {}
            work, g_acc, g_lst = run_action_plan(
                model, plan_k, list(work), flags_full, zonal_full,
                dt_full, _RowSett(sv_rows_), it0, nt_present, _HALO, nx,
                dtype, n_per_rep=n_per_rep, collect_globals=True,
                full_band=True)
            gpl = [g_acc.get(g.name, jnp.zeros((H, nx), dtype))
                   for g in model.globals_]
            gll = [g_lst.get(g.name, jnp.zeros((H, nx), dtype))
                   for g in model.globals_]
            return jnp.stack(work), jnp.stack(gpl), jnp.stack(gll)

        pst = [bufp[slot, j] for j in range(ns)]
        apl = [bufa[slot, j] for j in range(n_aux)]
        _, vjp_fn = jax.vjp(C, pst, apl, sv_rows)
        # cotangent seeds live on the R-extended output window
        # [band - R, band + by + R): rows beyond it either belong to the
        # neighboring bands' lambda_in (they own those output rows) or
        # hold full-band garbage — both masked to zero
        rows = jax.lax.broadcasted_iota(jnp.int32, (H, nx), 0)
        win = (rows >= _HALO - R) & (rows < _HALO + by + R)
        lam_win = jnp.stack(
            [jnp.where(win, bufl[slot, j], jnp.zeros((H, nx), dtype))
             for j in range(ns)])
        zero_pl = jnp.zeros((H, nx), dtype)
        lgpl = jnp.stack(
            [jnp.where(win, jnp.full((H, nx), lg_ref[0, gi], dtype),
                       zero_pl) for gi in range(n_globals)])
        lgll = jnp.stack(
            [jnp.where(win, jnp.full((H, nx), lg_ref[1, gi], dtype),
                       zero_pl) for gi in range(n_globals)])
        lam_p, lam_aux, lam_sv_rows = vjp_fn((lam_win, lgpl, lgll))

        for j in range(ns):
            out_lam[j] = lam_p[j][_HALO:_HALO + by, :]
        if out_laux is not None:
            for j in range(n_aux):
                out_laux[j] = lam_aux[j][_HALO:_HALO + by, :]

        @pl.when(i == 0)
        def _():
            out_sett[...] = jnp.zeros((8, 128), dtype)
        # band rows only: margin rows belong to the neighboring bands
        lam_sv = lam_sv_rows[_HALO:_HALO + by, :].sum(axis=0)
        pad_s = jnp.concatenate(
            [lam_sv, jnp.zeros((1024 - n_sett,), dtype)]).reshape((8, 128))
        out_sett[...] = out_sett[...] + pad_s

    out_specs = [
        pl.BlockSpec((ns, by, nx), lambda i: (0, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((8, 128), lambda i: (0, 0), memory_space=pltpu.VMEM),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((ns, ny, nx), dtype),
        jax.ShapeDtypeStruct((8, 128), dtype),
    ]
    if aux_grad:
        out_specs.append(pl.BlockSpec((n_aux, by, nx), lambda i: (0, i, 0),
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((n_aux, ny, nx), dtype))

    call_bwd = pl.pallas_call(
        bwd_kernel,
        grid=(ny // by,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((2, ns, by + 2 * _HALO, nx), dtype),
            pltpu.VMEM((2, ns, by + 2 * _HALO, nx), dtype),
            pltpu.VMEM((2, n_aux, by + 2 * _HALO, nx), dtype),
            pltpu.SemaphoreType.DMA((2, 9)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )

    @jax.custom_vjp
    def step_arrays(fields, sett, aux, itv):
        out, gpart = call_f(sett, itv, fields, aux)
        # [0] chunk-summed globals (the objective increment over the k
        # fused steps), [1] last-iteration globals (state.globals_ —
        # same semantics as the per-step engines)
        return (out, gpart[0, :n_globals].sum(axis=1),
                gpart[1, :n_globals].sum(axis=1))

    def step_f(fields, sett, aux, itv):
        out = step_arrays(fields, sett, aux, itv)
        return out, (fields, sett, aux, itv)

    def step_b(res, cot):
        fields, sett, aux, itv = res
        lam_f, lam_g, lam_gl = cot
        lg = jnp.stack([lam_g.astype(dtype), lam_gl.astype(dtype)])
        outs = call_bwd(sett, lg, itv, fields, lam_f, aux)
        if aux_grad:
            lam_fields, sett_acc, lam_aux = outs
        else:
            lam_fields, sett_acc = outs
            lam_aux = jnp.zeros_like(aux)
        lam_sett = sett_acc.reshape(-1)[:n_sett]
        return (lam_fields, lam_sett, lam_aux,
                np.zeros((1,), jax.dtypes.float0))

    step_arrays.defvjp(step_f, step_b)

    def _aux_base(params: SimParams, flags):
        flags_i32 = flags.astype(jnp.int32)
        zones = flags_i32 >> zshift
        base = [fusion.zone_plane(params.zone_table[j].astype(dtype), zones)
                for j in zonal_si]
        return flags_i32.astype(dtype), zones, base

    def _aux_series(params: SimParams, flags_f, zones, base, it):
        return pallas_generic.assemble_aux(params, zones, flags_f, base,
                                           zonal_si, it, dtype,
                                           with_dt=True)

    def _mk_step(params: SimParams, flags):
        sett = params.settings.astype(dtype)
        flags_f, zones, base = _aux_base(params, flags)
        if series:
            def step(state: LatticeState, p2: SimParams):
                it = state.iteration
                aux = _aux_series(p2, flags_f, zones, base, it)
                new_fields, g, g_last = step_arrays(
                    state.fields, sett, aux,
                    it[None].astype(jnp.int32) if it.ndim == 0 else it)
                return LatticeState(
                    fields=new_fields, flags=state.flags,
                    globals_=g_last.astype(state.globals_.dtype),
                    iteration=state.iteration + adv * k), g
            return step
        if params.time_series is not None:
            raise ValueError(
                "this diff step was built without Control-series support "
                "(series=False) but params carry a time series — the "
                "schedule would be silently dropped; build with "
                "series=True (auto engine: pass has_series=True to "
                "make_unsteady_gradient) or use engine='xla'")
        aux = jnp.stack([flags_f] + base)

        def step(state: LatticeState, p2: SimParams):
            it = state.iteration
            new_fields, g, g_last = step_arrays(
                state.fields, sett, aux,
                it[None].astype(jnp.int32) if it.ndim == 0 else it)
            return LatticeState(
                fields=new_fields, flags=state.flags,
                globals_=g_last.astype(state.globals_.dtype),
                iteration=state.iteration + adv * k), g
        return step

    def step(state: LatticeState, params: SimParams):
        # slow path (loop invariants re-derived per call) — drivers bind
        # them once via prepare().  Returns (state, chunk_globals): the
        # state carries LAST-iteration globals (per-step engine
        # semantics); the second value is the k-step objective increment.
        return _mk_step(params, state.flags)(state, params)

    def prepare(state: LatticeState, params: SimParams):
        """Bind the loop-invariant inputs ONCE per (jitted) gradient
        call: the zonal planes, settings cast and aux assembly must
        happen OUTSIDE the step scan — as scan-carry derived values they
        would re-run every step (flags ride the carry, so XLA cannot
        hoist them).  Called INSIDE the differentiated trace, so
        cotangents still flow to ``params`` through the bindings."""
        return _mk_step(params, state.flags)

    step.prepare = prepare
    step.chunk = k
    step.returns_inc = True
    step.arrays = step_arrays
    step.engine_name = (f"pallas_adjoint[{model.name},k={k}"
                        + (",series" if series else "") + f",by={by}]")
    return step
