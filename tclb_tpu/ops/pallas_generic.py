"""Registry-driven Pallas engine: ANY 2D model's own physics in the fused
collide-stream kernel.

This is the round-4 answer to the reference's defining property: its code
generator emits a tuned device kernel for EVERY model
(reference src/cuda.cu.Rt:81-283 ``RunKernel`` templated over the model's
``Node_Run``, src/LatticeContainer.inc.cpp.Rt:247-266), so no model pays an
interpreted-path tax.  Here the same guarantee comes from tracing instead of
generation: the model's registered stage functions (the SAME ``run(ctx)``
callables the XLA engine traces — one source of physics, automatic parity)
are traced INSIDE a Pallas band kernel against a band-local
:class:`KernelCtx`, and the registry metadata drives everything the
generator would have emitted:

* per-plane streaming vectors (``model.ei``) become static row-slices of the
  band buffer + lane rolls (pull scheme);
* declared Field stencils (``Field.dy_range``) bound the in-band halo reach,
  exactly like the reference's ``stencil2d`` bounds its margins
  (src/conf.R:134);
* multi-stage actions (e.g. d2q9_kuper's Run + CalcPhi) run back-to-back in
  one band pass on progressively-shrinking row extensions, so multi-stage
  models stream their state from HBM ONCE per iteration;
* zonal settings are built into per-node planes (``fusion.zone_plane``)
  that ride the aux DMA (the reference reads them per node through the
  flag's zone bits, src/LatticeContainer.h.Rt:89-108);
* the ``present`` node-type set specializes the trace on the painted
  boundary types (reference compile-time kernel zoo specialization).

Eligibility is capability-probed, not allowlisted: :func:`supports` traces
one band-kernel call abstractly (which rejects models whose code captures
constant arrays or uses untraceable ops) and the Lattice engine compile-
probes the result on TPU, falling back to the XLA path when Mosaic cannot
lower an op (e.g. ``arccos``).  The hand-tuned d2q9-family kernels
(ops/pallas_d2q9.py) keep priority for the 9-plane models they cover.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tclb_tpu import telemetry
from tclb_tpu.core import shift as ddf
from tclb_tpu.core.lattice import (LatticeState, NodeCtx, SimParams,
                                   series_dt_overrides, series_overrides)
from tclb_tpu.core.registry import Model
from tclb_tpu.ops import fusion, lbm, slab_dma
from tclb_tpu.ops.engine import Engine, paired_calls, scan_calls, tap
from tclb_tpu.ops.lbm import present_types  # noqa: F401  (re-export)

_HALO = 8   # DMA halo block height: one (8, 128) f32 tile per side
HALO = _HALO  # public: max per-action reach a caller can plan against
# storage dtypes the generic engines can keep in HBM.  Compute is ALWAYS
# f32: field planes are widened right after the VMEM read and narrowed
# on the output write, and the aux stack (flags + zonal planes) stays
# f32 outright — bf16's 8 mantissa bits cannot represent uint16 flag
# values exactly.  At f32 storage the casts are traced no-ops, so the
# bit-parity contract with the XLA path is untouched; bf16 runs are
# validated by the error-vs-f32 harness (tclb_tpu/precision.py), not by
# bit-parity.  analysis/precision.py keys its unsafe-accumulation scan
# on this marker.
STORAGE_DTYPES = (jnp.float32, jnp.bfloat16)
_COMPUTE_DTYPE = jnp.float32


def _lane_sum(model: Model, gpart: jnp.ndarray) -> jnp.ndarray:
    """The model's Globals from a ``with_globals`` call's (8, 128) block
    of partial sums: a row a Global, summed over its lanes."""
    return gpart[:model.n_globals].sum(axis=1)


def _donating_unless_one_call(schedule: Callable) -> Callable:
    """``schedule(state, params, niter)`` compiled twice, and
    ``program(calls, donate=True)``, which picks the one to run from the
    kernel calls of the schedule: donating the state for every schedule
    of two kernel calls and more (a call reads what the call before it
    wrote), not donating it for a schedule of one call, nor where the
    caller says ``donate=False``.  That call reads halos
    of the state while it writes, so where its output has to be the
    donated input's buffer XLA copies the whole state first (0.86 GB at
    34 x 512 x 48 x 256: 2 ms on a v5e); not donated, it writes a
    buffer of its own and the caller drops the input: the same peak, no
    copy."""
    jit = partial(jax.jit, schedule, static_argnames=("niter",))
    donating, once = jit(donate_argnums=0), jit()
    return lambda calls, donate=True: (
        donating if donate and calls != 1 else once)


def kernel_reduces_globals(model: Model, nx: int) -> bool:
    """Whether the kernels have a flavour that reduces the model's
    Globals (``with_globals``): SUM only — MAX would need max-combining
    across bands/stages (no model uses MAX) — into the (8, 128) block of
    partial sums, a row a Global, over rows of whole lane tiles."""
    return (0 < model.n_globals <= 8 and nx % 128 == 0
            and all(g.op == "SUM" for g in model.globals_))


def _shard_calls(model: Model, nx: int, fuse: int, mk_call: Callable
                 ) -> tuple:
    """``(call, call_g)`` of an ``ext_halo`` building block from its
    kernel flavours ``mk_call(with_globals=)``: the NoGlobals call, and
    (``fuse`` 1 and :func:`kernel_reduces_globals`, else None) one step
    of the in-kernel-globals flavour, ``-> (fields, globals)`` with the
    block's lanes summed."""
    call_g = None
    if fuse == 1 and kernel_reduces_globals(model, nx):
        kernel_g = mk_call(with_globals=True)

        def call_g(*operands):
            fields, gpart = kernel_g(*operands)
            return fields, _lane_sum(model, gpart)
    return mk_call(), call_g


def _scheduled_engine(model: Model, dtype, nx: int, fuse: int,
                      mk_call: Callable, mk_call1: Callable,
                      window: Callable, impl: dict,
                      ghost: Optional[tuple] = None,
                      series_paired: bool = True,
                      points: Optional[np.ndarray] = None,
                      **fields) -> Engine:
    """The :class:`Engine` of the generic band (2D) and slab (3D)
    builders, from their kernel flavours: one split of ``niter``, the
    schedule that loops the calls by it, the account of what it issues
    and the program that runs it.  ``mk_call(lean=)`` builds the call of
    ``fuse`` steps, ``mk_call1(with_dt=, with_globals=, lean=)`` those
    of one.  ``window(fused)``: the shape fields of the account of
    ``fused`` looped calls.  ``impl``: the builder's internals for the
    differentiable wrappers of ``ops/pallas_adjoint``, which drive the
    forward globals kernel (``call_g``, added here) outside the
    scanning iterate.  ``ghost``: ``(enter, refresh, leave)`` of a band
    that stands on ghost rows: ``enter(flags, fields)`` appends them,
    ``refresh(fields)`` renews them before a call, ``leave(fields)``
    drops them; None where there are none.  ``series_paired``: whether
    the series loop runs two calls a body.  ``points`` ((P, ndim) in
    array index order; ``fuse`` 1): the sampled flavour of a
    ``<Sample>`` run, whose every step is one call and which returns
    ``(state, taps)``, the stored planes at the points after every
    step, the final Globals call's too, (niter, planes, P).
    ``fields``: what else the engine declares."""
    cdtype = _COMPUTE_DTYPE
    zonal_names = list(model.zonal_settings)
    zonal_si = [model.setting_index[nm] for nm in zonal_names]
    zshift = model.zone_shift
    # aux diet: the non-series flavors DMA ONLY the flag plane — zonal
    # settings are iteration-invariant there, a pure function of the
    # flag zone bits, so they are reconstructed in-kernel from the SMEM
    # zone table (fusion.zone_plane) instead of riding every HBM round
    # trip as full planes.  Series flavors keep the full aux stack (the
    # per-iteration _DT overrides genuinely change per step).
    lean_aux = len(zonal_names) > 0
    call = mk_call(lean=lean_aux)
    call1 = call if fuse == 1 else mk_call1(lean=lean_aux)
    # in-kernel globals flavor (final step of an iterate call)
    can_globals = kernel_reduces_globals(model, nx)
    call_g = mk_call1(with_globals=True, lean=lean_aux) \
        if can_globals else None
    # Control-series flavors: per-iteration zonal + _DT planes, fuse=1
    # (fused steps would reuse iteration t's settings at t+1)
    call_s = mk_call1(with_dt=True)
    call_sg = mk_call1(with_dt=True, with_globals=True) \
        if can_globals else None
    # one action rep advances the iteration counter iff any stage streams
    adv = int(any(model.stages[s].load_densities
                  for s in model.actions["Iteration"]))
    enter, refresh, leave = ghost or (
        lambda flags, fields: (flags, fields), lambda f: f, lambda f: f)
    sampled = points is not None
    if sampled and fuse != 1:
        raise ValueError("the sampled flavour reads the state after "
                         "every step: fuse=1 only")

    def split(niter: int, has_series: bool = False) -> tuple:
        """``niter`` steps as the trips of the two loops and the final
        Globals call: ``fused`` calls of ``fuse`` steps (none under a
        series: fused steps would reuse iteration t's settings at t+1),
        ``rest`` calls of one step, ``final`` 0 or 1."""
        final = int(niter > 0
                    and (call_sg if has_series else call_g) is not None)
        main = max(niter, 0) - final
        fused = 0 if has_series else main // fuse
        return fused, main - fused * fuse, final

    def _schedule(state: LatticeState, params: SimParams, niter: int
                  ) -> LatticeState:
        flags_i32, fields = enter(state.flags.astype(jnp.int32),
                                  state.fields.astype(dtype))
        zones = flags_i32 >> zshift
        sett = params.settings.astype(cdtype)
        has_series = params.time_series is not None
        if sampled and has_series:
            # dispatch keeps such a run on the XLA scan
            # (Lattice._samples_on_engine): taps on the series loop are
            # neither dispatched nor tested
            raise NotImplementedError(
                "the sampled flavour does not take a <Control> series")

        # loop-invariant pieces (XLA hoists them out of the step scan):
        # the base zonal planes and the affected-zone masks.  Per step
        # only scalar masked selects remain — indexing a modified zone
        # table with the zone ids inside the scan was an unhoistable
        # gather, ~25 ms/step at 1024^2
        flags_f = flags_i32.astype(cdtype)
        base_planes = [fusion.zone_plane(
            params.zone_table[k].astype(cdtype), zones) for k in zonal_si]

        def aux_of(it):
            return assemble_aux(params, zones, flags_f, base_planes,
                                zonal_si, it, cdtype, with_dt=has_series)

        if niter <= 0:
            return (state, jnp.zeros((0, fields.shape[0], len(points)),
                                     fields.dtype)) if sampled else state
        fused, rest, final = split(niter, has_series)
        carry = (fields, state.iteration)
        rows = []   # the sampled flavour's taps, a stack a loop

        def loop(c, steps, carry, trips, paired):
            """``carry`` after ``trips`` calls of ``c``, ``steps`` steps
            each; sampled, what each left at the points goes to
            ``rows``."""
            def body(carry, _):
                fields, it = carry
                out = invoke(c, it, fields)
                return (out, it + adv * steps), \
                    (tap(out, points) if sampled else None)

            out = scan_calls(body, carry, trips, paired, taps=sampled)
            if sampled:
                rows.append(out[1])
                return out[0]
            return out

        if has_series:
            # series flavors keep the full host-assembled aux stack: the
            # dt planes depend on the Control series, not just zone bits
            def invoke(c, it, fields):
                return c(sett, it[None], refresh(fields), aux_of(it))

            fields, it = loop(call_s, 1, carry, rest, series_paired)
        else:
            if lean_aux:
                # the DMA'd aux stack is the flag plane alone, every
                # step, however many zonal settings the model declares;
                # the zone table rides in SMEM and the kernel rebuilds
                # the (iteration-invariant) zonal planes itself
                ztab = jnp.concatenate(
                    [params.zone_table[k].astype(cdtype) for k in zonal_si])
                aux = flags_f[None]

                def invoke(c, it, fields):
                    return c(sett, it[None], ztab, refresh(fields), aux)
            else:
                aux = aux_of(state.iteration)

                def invoke(c, it, fields):
                    return c(sett, it[None], refresh(fields), aux)

            fields, it = loop(call, fuse, carry, fused, True)
            if fuse > 1:
                fields, it = loop(call1, 1, (fields, it), rest, True)

        globals_ = jnp.zeros_like(state.globals_)
        if final:
            fields, gpart = invoke(call_sg if has_series else call_g,
                                   it, fields)
            it = it + adv
            globals_ = _lane_sum(model, gpart).astype(state.globals_.dtype)
            if sampled:
                rows.append(tap(fields, points)[None])
        out = LatticeState(fields=leave(fields), flags=state.flags,
                           globals_=globals_, iteration=it)
        return (out, jnp.concatenate(rows)) if sampled else out

    def account(niter: int, has_series: bool = False) -> dict:
        """What one ``iterate(niter)`` issues, reckoned host-side from
        the plan and ``_schedule``'s own split: the calls, the windows
        of the looped kernel, the steps left over."""
        fused, rest, final = split(int(niter), has_series)
        return dict(
            stages_per_step=len(model.actions["Iteration"]),
            kernel_calls=fused + rest + final, remainder_steps=rest + final,
            paired_calls=paired_calls(fused, rest)
            if series_paired or not has_series else 0,
            **window(fused),
            # the f32 flag plane (and what rides beside it) of each window
            aux_planes=(1 + 2 * len(zonal_names) if has_series
                        else 1 if lean_aux else 1 + len(zonal_names)),
            # the planes ``assemble_aux`` makes for every step of a
            # series and the kernel reads beside the flags: value and _DT
            **(dict(series_planes=2 * len(zonal_names)) if has_series
               else {}))

    program = _donating_unless_one_call(_schedule)

    def iterate(state: LatticeState, params: SimParams, niter: int
                ) -> LatticeState:
        calls = sum(split(int(niter), params.time_series is not None))
        # the sampled flavour never donates: donated, every trip of its
        # loop copies the state out of the compiler's fast memory into
        # the caller's buffer (ops/pallas_d2q9.py, the same finding)
        return program(calls, donate=not sampled)(state, params, niter)

    # the engine handles Control time series itself, and (when the
    # globals flavor exists) returns the LAST step's Globals — no trailing
    # step needed (and a hybrid engine's trailing step can be this
    # engine's iterate(.., 1))
    return Engine(iterate, account, supports_series=True, samples=sampled,
                  full_globals=bool(model.n_globals == 0
                                    or call_g is not None),
                  impl=dict(impl, call_g=call_g, lean_aux=lean_aux,
                            zonal_si=zonal_si, zshift=zshift, adv=adv,
                            cdtype=cdtype), **fields)


def _storage_ok(dtype) -> bool:
    return jnp.dtype(dtype) in {jnp.dtype(d) for d in STORAGE_DTYPES}


# --------------------------------------------------------------------------- #
# Registry-derived stage plan
# --------------------------------------------------------------------------- #


def _stage_reach(model: Model, stage_name: str,
                 axis: Optional[str] = None) -> int:
    """Reach of one stage's reads along ``axis``: pull distance of
    streamed densities (when the stage streams) and the declared Field
    stencil extents.  The default is the banded axis (y rows in 2D, z
    slabs in 3D): x-reach is free (lane rolls wrap the whole row), and in
    3D the whole (ny, nx) plane rides the band so y is free too, unless
    the plane is tiled (``axis="y"``, :func:`_reach_y`)."""
    axis = axis or ("y" if model.ndim == 2 else "z")
    stage = model.stages[stage_name]
    r = 0
    if stage.load_densities:
        r = max((abs(int(getattr(d, "d" + axis))) for d in model.densities),
                default=0)
    for f in model.fields:
        lo, hi = getattr(f, f"d{axis}_range")
        r = max(r, abs(lo), abs(hi))
    return r


def action_plan(model: Model, action: str = "Iteration", fuse: int = 1
                ) -> tuple[list[tuple[str, int]], int]:
    """Execution plan for ``fuse`` repetitions of an action: a list of
    (stage_name, out_ext) in execution order, plus the input halo width R.

    ``out_ext`` is how many EXTRA rows beyond the output band the stage
    must compute so that every later stage's reads stay within valid
    rows; R is the extension the very first stage's reads need of the
    input.  (The reference never needs this arithmetic: each CUDA stage
    is a separate global kernel launch.  Fusing the whole action into one
    band pass is the TPU-side traffic win — state is read once per
    iteration, not once per stage.)"""
    names = list(model.actions[action]) * fuse
    plan: list[tuple[str, int]] = [("", 0)] * len(names)
    ext = 0
    for i in range(len(names) - 1, -1, -1):
        plan[i] = (names[i], ext)
        ext += _stage_reach(model, names[i])
    return plan, ext


def choose_fuse(model: Model, fmax: int = fusion.FUSE_MAX) -> int:
    """Fusion depth for the 2D band engine: the deepest fuse whose
    fused-plan reach still fits the fixed 8-row DMA halo.  The halo
    (and so the per-call HBM traffic) is constant in fuse, so the win
    is linear — K steps amortize one band round trip."""
    return fusion.choose_fuse_band(
        lambda f: action_plan(model, "Iteration", fuse=f)[1], _HALO, fmax)


# --------------------------------------------------------------------------- #
# Band sizing / ghost-row padding (generalized from ops/pallas_d2q9.py)
# --------------------------------------------------------------------------- #


_DEFAULT_BY_CAP = 32


# what a band's DMA scratch may fill of scoped VMEM as the kernel is built
# by default (Mosaic's own 16 MiB limit), and under the raised limit a
# band asks for where that holds none, or only one that reads its halo
# rows too often (:func:`fusion.plan_band`: rows of 2048 nodes and more).
# Half of either: the rest holds the pipelined out blocks and the traced
# physics' temporaries, which the band sizing cannot see
_BAND_SCRATCH = (8 * 1024 * 1024, 50 * 1024 * 1024)
_BAND_VMEM_LIMIT = 100 * 1024 * 1024


def _band_scratch(model: Model, by: int, nx: int, itemsize: int = 4) -> int:
    """The two-slot DMA scratch of a band of ``by`` rows: state and aux
    stacks, band plus two 8-row halo blocks."""
    # Budget against the LARGEST kernel flavor (the Control-series
    # variant carries value + _DT planes per zonal setting): all flavors
    # of one engine share `by` (the padded height and grid must agree),
    # and a series run attaching mid-process reuses the cached build cfg
    # WITHOUT a compile probe — an overflow there would escape the
    # fallback ladder.  Costs at most one `by` notch on zonal-heavy
    # models vs budgeting the plain flavor only.
    n_aux = 1 + 2 * len(model.zonal_settings)
    # field planes scale with the storage itemsize; the aux stack is
    # always f32 (flags must survive the float round trip exactly)
    per_row = (model.n_storage * itemsize + n_aux * 4) * nx
    return 2 * (by + 2 * _HALO) * per_row


def _band_plan(model: Model, ny: int, nx: int,
               by_cap: Optional[int] = None,
               itemsize: int = 4) -> Optional[tuple]:
    """``(rows, budget)`` of the band on ``ny`` rows, by the rule the
    tuned d2q9 band kernels plan by (:func:`fusion.plan_band`), over the
    band's scratch (:func:`_band_scratch`) and ``_BAND_SCRATCH``.

    ``by_cap`` bounds the band height: the model's traced physics holds
    its live temporaries in scoped VMEM, which the band sizing cannot see
    — the default cap keeps typical models inside the budget and the
    Lattice's first-call probe retries with a halved cap when a complex
    model still overflows (Mosaic's scoped-vmem limit error)."""
    return fusion.plan_band(
        ny, lambda by: _band_scratch(model, by, nx, itemsize),
        _DEFAULT_BY_CAP if by_cap is None else by_cap, _BAND_SCRATCH, _HALO)


def _band_rows(model: Model, ny: int, nx: int,
               by_cap: Optional[int] = None,
               itemsize: int = 4) -> Optional[int]:
    """The rows of :func:`_band_plan`'s band, None where it has none."""
    plan = _band_plan(model, ny, nx, by_cap, itemsize)
    return plan and plan[0]


def _pad_rows(model: Model, ny: int, nx: int, mirror: int,
              by_cap: Optional[int] = None,
              itemsize: int = 4) -> Optional[int]:
    """Ghost-row padding lifting ny % 8, generalized to mirror width
    ``mirror`` (= the plan's total reach): the first/last ``mirror`` ghost
    rows replicate the physical edge rows so the kernel's periodic wrap
    over the padded height reproduces the exact periodic pull of the
    physical height (same scheme as ops/pallas_d2q9._pad_rows, reach
    parameterized).  Returns pad rows (0 if aligned), None if impossible."""
    if ny % 8 == 0 and _band_rows(model, ny, nx, by_cap,
                                  itemsize) is not None:
        return 0
    lo = ny + 2 * mirror
    best, best_score = None, None
    for ny_pad in range(((lo + 7) // 8) * 8, 2 * ny + 64, 8):
        by = _band_rows(model, ny_pad, nx, by_cap, itemsize)
        if by is None:
            continue
        score = ny_pad * (1.0 + 2.0 * _HALO / by)
        if best_score is None or score < best_score:
            best, best_score = ny_pad - ny, score
        if ny_pad >= ny + 64 and best is not None:
            break
    return best


# --------------------------------------------------------------------------- #
# Band-local NodeCtx
# --------------------------------------------------------------------------- #


class _DtypeShim:
    """Stands in for the full field stack in ``ctx._fields.dtype`` uses."""

    def __init__(self, dtype):
        self.dtype = dtype


def assemble_aux(params, zones, flags_f, base_planes, zonal_si, it, dtype,
                 with_dt: bool):
    """The aux plane stack: flags + per-node zonal-setting planes, with
    any registered <Control> series overrides applied at iteration ``it``
    (+ the per-iteration ``_DT`` planes when ``with_dt``).  ONE
    implementation shared by the 2D/3D generic engines and the
    differentiable step — the override scalars come from the same
    series_overrides/series_dt_overrides the XLA NodeCtx uses, so the
    engines cannot drift."""
    has = params.time_series is not None
    planes = [flags_f]
    for j, k in enumerate(zonal_si):
        p = base_planes[j]
        if has:
            for z, v in series_overrides(params, k, it):
                p = jnp.where(zones == z, v.astype(dtype), p)
        planes.append(p)
    if with_dt:
        for k in zonal_si:
            p = jnp.zeros_like(flags_f)
            if has:
                for z, v in series_dt_overrides(params, k, it):
                    p = jnp.where(zones == z, v.astype(dtype), p)
            planes.append(p)
    return jnp.stack(planes)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _roll_prim(x, s, nx):
    return pltpu.roll(x, s, axis=1)


def _roll_fwd(x, s, nx):
    return _roll_prim(x, s, nx), None


def _roll_bwd(s, nx, _res, ct):
    # roll is linear: out[i] = x[i - s], so the transpose is the
    # opposite roll (the adjoint band kernel differentiates through the
    # streaming slices; pltpu.roll itself has no AD rule)
    return (_roll_prim(ct, (nx - s) % nx, nx),)


_roll_prim.defvjp(_roll_fwd, _roll_bwd)


def _lane_roll(sl, shift, nx):
    s = shift % nx
    return _roll_prim(sl, s, nx) if s else sl


def run_action_plan(model: Model, plan, work: list, flags_full, zonal_full,
                    dt_full, sett, it0, nt_present, halo: int, nx: int,
                    dtype, n_per_rep: int, collect_globals: bool = False,
                    extra: int = 0, full_band: bool = False):
    """Execute ``plan``'s stages over band-buffer VALUE arrays (2D).

    ``work`` is one ``(H, nx)`` array per storage plane with the output
    band at rows ``[halo, H - halo)``; the list is updated in place so
    later stages read earlier stages' writes.  ``extra`` widens every
    stage's output window by that many rows: the adjoint band kernel
    (ops/pallas_adjoint) computes the action on a band extended by the
    plan's total reach so the VJP dependency cone of the band rows is
    fully covered; the forward kernel uses ``extra=0``.

    Returns ``(work, g_planes, g_last_planes)`` where ``g_planes`` maps
    each Global's name to its ``(by + 2*extra, nx)`` contribution plane
    over the extended output window (stages with larger extents are
    trimmed to the window — rows beyond it lie outside the band's
    dependency cone) summed over ALL fused repetitions, and
    ``g_last_planes`` holds the LAST repetition's contributions only
    (the last-iteration globals the per-step engines report).

    ``full_band=True`` computes EVERY stage over the whole (tile-aligned)
    buffer height instead of progressively-shrinking windows: the pull
    becomes a sublane roll (whose wrap lands garbage only in the outermost
    rows, which stay within the ``halo`` margin callers discard), stage
    updates replace whole planes (no row-concats), and every op keeps the
    aligned ``(H, nx)`` shape — much friendlier Mosaic tiling.  Globals
    planes then come back full-height and the CALLER must mask rows
    outside its valid window.

    This is THE collide semantics of the 2D generic engine — the forward
    band kernel and the adjoint's in-band chain both trace it, so the
    two can never drift apart.
    """
    ns = model.n_storage
    ei = model.ei
    by = work[0].shape[0] - 2 * halo
    n_reps = max(len(plan) // max(n_per_rep, 1), 1)
    g_acc: dict = {}
    g_last: dict = {}
    for st_i, (stage_name, out_ext) in enumerate(plan):
        stage = model.stages[stage_name]
        fn = model.stage_fns[stage.main]
        eff = halo if full_band else out_ext + extra
        n_i = by + 2 * eff
        lo = halo - eff                # first row of this stage's window
        rep = st_i // n_per_rep        # fused action repetition index

        if stage.load_densities:
            planes = []
            for k in range(ns):
                dxk, dyk = int(ei[k, 0]), int(ei[k, 1])
                if full_band:
                    sl = jnp.roll(work[k], dyk, axis=0) if dyk else work[k]
                else:
                    sl = work[k][lo - dyk:lo - dyk + n_i, :]
                planes.append(_lane_roll(sl, dxk, nx))
        else:
            planes = [w[lo:lo + n_i, :] for w in work]

        if full_band:
            def loader(index, dx, dy, dz=0):
                assert dz == 0, "2D band kernel: no z loads"
                sl = work[index]
                if dy:
                    sl = jnp.roll(sl, -dy, axis=0)
                return _lane_roll(sl, -dx, nx)
        else:
            def loader(index, dx, dy, dz=0, _lo=lo, _n=n_i):
                assert dz == 0, "2D band kernel: no z loads"
                sl = work[index][_lo + dy:_lo + dy + _n, :]
                return _lane_roll(sl, -dx, nx)

        ctx = KernelCtx(
            model, planes, loader,
            flags_full[lo:lo + n_i, :],
            {nm: p[lo:lo + n_i, :] for nm, p in zonal_full.items()},
            sett, dtype, it0 + rep, nt_present,
            dt_planes={nm: p[lo:lo + n_i, :] for nm, p in dt_full.items()},
            compute_globals=collect_globals)
        res = fn(ctx)
        if collect_globals:
            # SUM Globals accumulate across the action's stages, trimmed
            # to the output window (rows beyond it belong to other bands
            # or lie outside the band's dependency cone); in full_band
            # mode the caller masks invalid rows instead
            for nm, plane in ctx._globals.items():
                if not full_band:
                    plane = plane[out_ext:out_ext + by + 2 * extra, :]
                g_acc[nm] = plane if nm not in g_acc else g_acc[nm] + plane
                if rep == n_reps - 1:
                    # last-repetition-only accumulation: the chunked diff
                    # step reports these as state.globals_ so the final
                    # state matches the per-step engines' last-iteration
                    # semantics (the chunk SUM would be ~k-fold inflated)
                    g_last[nm] = plane if nm not in g_last \
                        else g_last[nm] + plane

        if isinstance(res, dict):
            updates: dict[int, jnp.ndarray] = {}
            for name, stack in res.items():
                if name in model.groups:
                    idx = model.groups[name]
                    if len(idx) == 1 and stack.ndim == 2:
                        updates[idx[0]] = stack
                    else:
                        for j, k in enumerate(idx):
                            updates[k] = stack[j]
                else:
                    updates[model.storage_index[name]] = stack
        else:
            updates = {k: res[k] for k in range(ns)}
        for k, new in updates.items():
            if full_band:
                work[k] = new
            else:
                w = work[k]
                work[k] = jnp.concatenate([w[:lo], new, w[lo + n_i:]],
                                          axis=0)
    return work, g_acc, g_last


class KernelCtx(NodeCtx):
    """A :class:`NodeCtx` whose world is one VMEM row band.

    The model's stage function cannot tell the difference: ``group`` /
    ``density`` return the streamed band planes, ``load`` reaches into the
    band's halo rows, zonal ``setting``s are prebuilt planes, node-type
    tests run on the band's flag rows.  (The reference's ``Node_Run`` object
    plays this role per thread; here it's per band.)"""

    def __init__(self, model: Model, planes: list, loader: Callable,
                 flags_i32, zonal: dict, sett, dtype,
                 iteration, present: Optional[set],
                 dt_planes: Optional[dict] = None,
                 compute_globals: bool = False):
        # deliberately NOT calling NodeCtx.__init__: the band context has
        # list-of-planes storage and SMEM-backed settings
        self.model = model
        self._planes = planes          # streamed view, one 2D array per plane
        self._loader_fn = loader       # load(index, dx, dy) on the RAW band
        self.flags = flags_i32
        self._zonal = zonal            # zonal setting name -> band plane
        self._dt = dt_planes or {}     # zonal setting name -> d/dt band plane
        self._sett = sett              # SMEM settings ref/array
        self._fields = _DtypeShim(dtype)
        self.iteration = iteration
        self.avg_start = 0
        self._globals: dict = {}
        self.present = present
        self.compute_globals = compute_globals

    # -- field access -------------------------------------------------- #

    def group(self, name: str) -> jnp.ndarray:
        idx = self.model.groups[name]
        return jnp.stack([self._planes[i] for i in idx])

    def density(self, name: str) -> jnp.ndarray:
        return self._planes[self.model.storage_index[name]]

    def load(self, name: str, dx: int = 0, dy: int = 0, dz: int = 0
             ) -> jnp.ndarray:
        return self._loader_fn(self.model.storage_index[name], dx, dy, dz)

    # -- settings ------------------------------------------------------ #

    def setting(self, name: str) -> jnp.ndarray:
        m = self.model
        i = m.setting_index[name]
        if m.settings[i].zonal:
            return self._zonal[name]
        return self._sett[i]

    def setting_dt(self, name: str) -> jnp.ndarray:
        # the series-aware kernel flavor carries per-iteration _DT planes
        # in its aux stack; without a Control series every derivative is
        # identically zero
        if name in self._dt:
            return self._dt[name]
        return jnp.zeros_like(self._planes[0])

    # -- node types ---------------------------------------------------- #

    def nt_is(self, name: str) -> jnp.ndarray:
        t = self.model.node_types[name]
        return (self.flags & jnp.int32(t.mask)) == jnp.int32(t.value)

    def nt_in_group(self, group: str) -> jnp.ndarray:
        m = self.model.group_masks[group]
        return (self.flags & jnp.int32(m)) != jnp.int32(0)


# --------------------------------------------------------------------------- #
# Eligibility
# --------------------------------------------------------------------------- #

_probe_cache: dict = {}
_mosaic_verdict: dict = {}
_cfg_cache: dict = {}


def mosaic_ok(model: Model, shape) -> bool:
    """Process-wide memo of whether this model/shape's kernel survived
    Mosaic lowering on TPU (unknown counts as OK — the Lattice's
    first-call probe settles it).  Keyed per shape: a VMEM overflow at a
    huge nx must not disable the engine for small lattices."""
    return _mosaic_verdict.get((model.name, tuple(shape)), True)


def set_mosaic_ok(model: Model, shape, ok: bool) -> None:
    _mosaic_verdict[(model.name, tuple(shape))] = ok


def get_build_cfg(model: Model, shape) -> Optional[tuple]:
    """(fuse, by_cap) that survived this model/shape's scoped-VMEM
    pressure on a previous build (None = untested; default config)."""
    return _cfg_cache.get((model.name, tuple(shape)))


def set_build_cfg(model: Model, shape, fuse: int,
                  by_cap: Optional[int]) -> None:
    _cfg_cache[(model.name, tuple(shape))] = (fuse, by_cap)


def supports(model: Model, shape, dtype, probe: bool = True) -> bool:
    """Whether the generic band kernel can run this model/shape.

    Structural checks from the registry, then (``probe=True``) an abstract
    trace of one band-kernel call — the capability test that replaces the
    old per-model name allowlist.  Mosaic lowering failures (TPU compile)
    are caught later by the Lattice's compile probe."""
    if model.ndim == 3:
        return supports_3d(model, shape, dtype, probe=probe)
    if model.ndim != 2 or len(shape) != 2 or not _storage_ok(dtype):
        return False
    if "Iteration" not in model.actions:
        return False
    for s in model.actions["Iteration"]:
        st = model.stages.get(s)
        if st is None or st.fixed_point or model.stage_fns.get(st.main) is None:
            return False
    plan, reach = action_plan(model, "Iteration", fuse=1)
    if reach > _HALO:
        return False
    ny, nx = (int(v) for v in shape)
    itemsize = jnp.dtype(dtype).itemsize
    if ny < 8:
        return False
    if jax.default_backend() == "tpu" and nx % 128:
        return False
    if _pad_rows(model, ny, nx, max(reach, 1), itemsize=itemsize) is None:
        return False
    if not probe:
        return True
    key = (model.name, nx, itemsize)
    if key not in _probe_cache:
        try:
            iterate = make_pallas_iterate(model, (8 if ny % 8 else min(ny, 64),
                                                  nx), dtype, interpret=True)
            state = LatticeState(
                fields=jax.ShapeDtypeStruct(
                    (model.n_storage, 8 if ny % 8 else min(ny, 64), nx), dtype),
                flags=jax.ShapeDtypeStruct(
                    (8 if ny % 8 else min(ny, 64), nx), jnp.uint16),
                globals_=jax.ShapeDtypeStruct((model.n_globals,), dtype),
                iteration=jax.ShapeDtypeStruct((), jnp.int32))
            params = SimParams(
                settings=jax.ShapeDtypeStruct((len(model.settings),), dtype),
                zone_table=jax.ShapeDtypeStruct(
                    (len(model.settings), model.zone_max), dtype))
            jax.eval_shape(partial(iterate, niter=2), state, params)
            _probe_cache[key] = True
        except Exception as e:  # noqa: BLE001 — any trace failure = ineligible
            from tclb_tpu.utils import log
            log.debug(f"pallas_generic: {model.name} trace probe failed: "
                      f"{type(e).__name__}: {str(e)[:200]}")
            _probe_cache[key] = False
    return _probe_cache[key]


# --------------------------------------------------------------------------- #
# Kernel builder
# --------------------------------------------------------------------------- #


def make_pallas_iterate(model: Model, shape, dtype=jnp.float32,
                        interpret: Optional[bool] = None,
                        fuse: int = 1,
                        present: Optional[set] = None,
                        ext_halo: bool = False,
                        by_cap: Optional[int] = None,
                        full_band: bool = False,
                        shift: Optional[np.ndarray] = None,
                        points: Optional[np.ndarray] = None):
    """Build ``iterate(state, params, niter) -> state`` running the model's
    full Iteration action as one fused Pallas band kernel per step.

    ``points`` builds the sampled flavour (:func:`_scheduled_engine`).

    ``ext_halo=True`` builds the sharded building block instead (the
    domain is one device's y-block carrying 8 exchanged halo rows at each
    end); returns ``(call, call_g, by, zonal_names)`` for
    :mod:`tclb_tpu.parallel.halo` to compose with ``ppermute``: ``call``
    is the NoGlobals kernel call of ``fuse`` steps, ``call_g`` (``fuse``
    1 and :func:`kernel_reduces_globals`, else None) one step of the
    in-kernel-globals flavour, ``-> (fields, globals)`` with the block's
    lanes summed.  The block has no ghost rows and the accumulated planes
    are its own ``by`` rows, never the halo rows: a shard's Globals are
    the sums over its own nodes, which the composer ``psum``s.  A 3D
    model's block is one device's z-block, which comes as it is with the
    neighbours' slabs beside it (:func:`make_pallas_iterate_3d`, which
    says its operands; ``fuse`` 1 only)."""
    if model.ndim == 3:
        return make_pallas_iterate_3d(model, shape, dtype,
                                      interpret=interpret, present=present,
                                      fuse=fuse, by_cap=by_cap,
                                      shift=shift, points=points,
                                      ext_halo=ext_halo)
    if not supports(model, shape, dtype, probe=False):
        raise ValueError(f"pallas_generic unsupported: {model.name} {shape}")
    cdtype = _COMPUTE_DTYPE
    itemsize = jnp.dtype(dtype).itemsize
    if ext_halo and jnp.dtype(dtype) != jnp.dtype(cdtype):
        raise ValueError("ext_halo (sharded) blocks are f32-only")
    plan, reach = action_plan(model, "Iteration", fuse=fuse)
    if reach > _HALO:
        raise ValueError(f"fuse={fuse} needs reach {reach} > halo {_HALO}")
    mirror = max(reach, 1)
    ny_phys, nx = (int(s) for s in shape)
    if ext_halo:
        if ny_phys % 8:
            raise ValueError("ext_halo blocks need ny % 8 == 0")
        pad = 0
    else:
        pad = _pad_rows(model, ny_phys, nx, mirror, by_cap, itemsize)
        if pad is None:
            raise ValueError(f"no valid band height for {shape}")
    ny = ny_phys + pad
    by, scratch = _band_plan(model, ny, nx, by_cap, itemsize)
    # a band planned under the raised budget says so to Mosaic; one under
    # the default states no limit, and its program is what it was
    raised = pltpu.CompilerParams(vmem_limit_bytes=_BAND_VMEM_LIMIT) \
        if scratch > _BAND_SCRATCH[0] else None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    n_storage = model.n_storage
    # per-plane DDF shift at the DMA seams (None = raw: pure astype, so
    # the f32/raw path traces bit-identically to the pre-shift kernel)
    _shifts = ([None] * n_storage if shift is None
               else [float(w) or None for w in shift])
    zonal_names = list(model.zonal_settings)
    zshift = model.zone_shift
    zone_max = model.zone_max
    nt_present = set(model.node_types) if present is None else set(present)
    if pad > 2 * mirror:
        nt_present = nt_present | {"Wall"}   # middle ghost rows are walls

    def _mk_kernel(plan, with_dt=False, with_globals=False, lean=False):
        """Kernel flavor factory: ``with_dt`` adds per-iteration _DT
        planes to the aux stack (the Control-series flavor), and
        ``with_globals`` accumulates the model's SUM Globals in-kernel
        into an extra (8, 128) partial-sums output (the reference's
        in-kernel Globals accumulation, src/cuda.cu.Rt:176-202);
        ``with_globals="split"`` emits a (2, 8, 128) block instead —
        [0] the whole fused chunk's sums (the objective increment), [1]
        the LAST repetition's only (last-iteration globals semantics,
        used by the chunked diff step).  ``lean`` is the aux-diet
        flavor: an extra SMEM zone-table input, flags-only aux stack,
        zonal planes rebuilt in-kernel."""
        def kern(sett, it_ref, *rest):
            if lean:
                ztab, f_hbm, aux_hbm, *refs = rest
            else:
                ztab = None
                f_hbm, aux_hbm, *refs = rest
            if with_globals:
                out_ref, g_ref, buff, bufa, sems = refs
            else:
                (out_ref, buff, bufa, sems), g_ref = refs, None
            kernel(plan, with_dt, with_globals, ztab, sett, it_ref, f_hbm,
                   aux_hbm, out_ref, g_ref, buff, bufa, sems)
        return kern

    def kernel(plan, with_dt, with_globals, ztab, sett, it_ref, f_hbm,
               aux_hbm, out_ref, g_ref, buff, bufa, sems):
        """One band pass = the whole Iteration action (x fuse).  The band
        plus 8-row halo blocks land in ONE contiguous (by+16)-row buffer
        per stack, so every extended-row access below is a single slice;
        double-slotted: band i+1's DMA is issued before band i's compute,
        overlapping HBM fetch with VPU work across grid steps (same scheme
        as ops/pallas_d2q9.kernel — the reference gets the overlap from
        its border/interior split + async memcpy streams,
        src/Lattice.cu.Rt:424-456)."""
        i = pl.program_id(0)
        n = pl.num_programs(0)

        def band_dmas(slot, band):
            base = pl.multiple_of(band * jnp.int32(by), 8)
            if ext_halo:
                mid8 = pl.multiple_of(base + jnp.int32(_HALO), 8)
                top8 = base
                bot8 = pl.multiple_of(base + jnp.int32(_HALO + by), 8)
            else:
                mid8 = base
                top8 = pl.multiple_of(
                    jax.lax.rem(base - jnp.int32(_HALO) + jnp.int32(ny),
                                jnp.int32(ny)), 8)
                bot8 = pl.multiple_of(
                    jax.lax.rem(base + jnp.int32(by), jnp.int32(ny)), 8)
            return (
                pltpu.make_async_copy(f_hbm.at[:, pl.ds(mid8, by), :],
                                      buff.at[slot, :, pl.ds(_HALO, by), :],
                                      sems.at[slot, 0]),
                pltpu.make_async_copy(f_hbm.at[:, pl.ds(top8, _HALO), :],
                                      buff.at[slot, :, pl.ds(0, _HALO), :],
                                      sems.at[slot, 1]),
                pltpu.make_async_copy(
                    f_hbm.at[:, pl.ds(bot8, _HALO), :],
                    buff.at[slot, :, pl.ds(_HALO + by, _HALO), :],
                    sems.at[slot, 2]),
                pltpu.make_async_copy(aux_hbm.at[:, pl.ds(mid8, by), :],
                                      bufa.at[slot, :, pl.ds(_HALO, by), :],
                                      sems.at[slot, 3]),
                pltpu.make_async_copy(aux_hbm.at[:, pl.ds(top8, _HALO), :],
                                      bufa.at[slot, :, pl.ds(0, _HALO), :],
                                      sems.at[slot, 4]),
                pltpu.make_async_copy(
                    aux_hbm.at[:, pl.ds(bot8, _HALO), :],
                    bufa.at[slot, :, pl.ds(_HALO + by, _HALO), :],
                    sems.at[slot, 5]),
            )

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

        # working stack: one (by+16, nx) array per plane; band row 0 is
        # buffer row _HALO.  Stages update their stored planes in place
        # (functionally — row-concat), later stages read the updates.
        # Planes are widened to the compute dtype at the read (a traced
        # no-op at f32 storage) and narrowed on the output write — the
        # whole fused action accumulates in f32.
        work = [ddf.widen_plane(buff[slot, k], cdtype, _shifts[k])
                for k in range(n_storage)]
        flags_full = bufa[slot, 0].astype(jnp.int32)
        if ztab is not None:
            zones_full = flags_full >> zshift
            zonal_full = {nm: fusion.zone_plane(ztab, zones_full,
                                                zone_max, col=j)
                          for j, nm in enumerate(zonal_names)}
            dt_full = {}
        else:
            zonal_full = {nm: bufa[slot, 1 + j]
                          for j, nm in enumerate(zonal_names)}
            dt_full = {nm: bufa[slot, 1 + len(zonal_names) + j]
                       for j, nm in enumerate(zonal_names)} \
                if with_dt else {}

        work, g_acc, g_last = run_action_plan(
            model, plan, work, flags_full, zonal_full, dt_full, sett,
            it_ref[0], nt_present, _HALO, nx, cdtype,
            n_per_rep=len(model.actions["Iteration"]),
            collect_globals=g_ref is not None, full_band=full_band)

        for k in range(n_storage):
            out_ref[k] = ddf.narrow_plane(work[k][_HALO:_HALO + by, :],
                                          dtype, _shifts[k])

        if g_ref is not None:
            split = with_globals == "split"

            @pl.when(i == 0)
            def _():
                g_ref[...] = jnp.zeros((2, 8, 128) if split else (8, 128),
                                       cdtype)
            if pad:
                # ghost rows must not contribute (mirror rows would
                # double-count, wall rows are unphysical)
                rows = jax.lax.broadcasted_iota(jnp.int32, (by, nx), 0) \
                    + i * jnp.int32(by)
                gmask = (rows < jnp.int32(ny_phys)).astype(cdtype)
            for blk, acc in enumerate((g_acc, g_last) if split
                                      else (g_acc,)):
                for gi, g in enumerate(model.globals_):
                    if g.name not in acc:
                        continue
                    plane = acc[g.name]
                    if full_band:
                        plane = plane[_HALO:_HALO + by, :]
                    if pad:
                        plane = plane * gmask
                    part = plane.reshape((by * (nx // 128),
                                          128)).sum(axis=0)
                    if split:
                        g_ref[blk, gi] = g_ref[blk, gi] + part
                    else:
                        g_ref[gi] = g_ref[gi] + part

    grid = (ny // by,)

    def _mk_call(plan_n, with_dt=False, with_globals=False, lean=False):
        n_aux_k = 1 if lean \
            else 1 + (2 if with_dt else 1) * len(zonal_names)
        out_specs = pl.BlockSpec((n_storage, by, nx), lambda i: (0, i, 0),
                                 memory_space=pltpu.VMEM)
        out_shape = jax.ShapeDtypeStruct((n_storage, ny, nx), dtype)
        if with_globals:
            gshape = (2, 8, 128) if with_globals == "split" else (8, 128)
            out_specs = [out_specs,
                         pl.BlockSpec(gshape,
                                      (lambda i: (0, 0, 0))
                                      if with_globals == "split"
                                      else (lambda i: (0, 0)),
                                      memory_space=pltpu.VMEM)]
            out_shape = [out_shape, jax.ShapeDtypeStruct(gshape, cdtype)]
        return pl.pallas_call(
            lbm.mosaic_body(
                _mk_kernel(plan_n, with_dt, with_globals, lean), interpret),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ] + ([pl.BlockSpec(memory_space=pltpu.SMEM)] if lean else [])
            + [
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((2, n_storage, by + 2 * _HALO, nx), dtype),
                pltpu.VMEM((2, n_aux_k, by + 2 * _HALO, nx), cdtype),
                pltpu.SemaphoreType.DMA((2, 6)),
            ],
            interpret=interpret,
            compiler_params=raised,
            name=f"generic_band_fuse{fuse if plan_n is plan else 1}",
        )

    if ext_halo:
        # the sharded building block keeps the full-aux convention: the
        # halo composer assembles + exchanges aux planes host-side
        return (*_shard_calls(model, nx, fuse, partial(_mk_call, plan)),
                by, zonal_names)

    plan1 = plan if fuse == 1 \
        else action_plan(model, "Iteration", fuse=1)[0]

    # the ghost rows of a band that is no multiple of its rows
    def enter(flags_i32, fields):
        # ghost layout: [mirror rows 0..m-1, walls, mirror ny-m..ny-1]
        mid = pad - 2 * mirror
        init_src = jnp.asarray(np.array(
            list(range(mirror)) + [0] * mid
            + list(range(ny_phys - mirror, ny_phys))))
        gflags = flags_i32[init_src]
        if mid:
            wall = jnp.int32(model.flag_for("Wall"))
            gflags = gflags.at[mirror:mirror + mid].set(wall)
        return (jnp.concatenate([flags_i32, gflags], axis=0),
                jnp.concatenate([fields, fields[:, init_src, :]], axis=1))

    def refresh(fields):
        f = fields.at[:, ny_phys:ny_phys + mirror, :].set(
            fields[:, 0:mirror, :])
        return f.at[:, ny - mirror:, :].set(
            fields[:, ny_phys - mirror:ny_phys, :])

    return _scheduled_engine(
        model, dtype, nx, fuse, partial(_mk_call, plan),
        partial(_mk_call, plan1),
        window=lambda fused: dict(band_rows=by, halo_rows=_HALO,
                                  pad_rows=pad, bands=ny // by),
        impl=dict(by=by, pad=pad, nt_present=nt_present, mk_call=_mk_call),
        ghost=(enter, refresh, lambda f: f[:, :ny_phys, :]) if pad else None,
        points=points, pad_rows=pad)


# --------------------------------------------------------------------------- #
# Generic VMEM-resident engine (2D): whole lattice on-chip, one kernel
# launch per iterate(n)
# --------------------------------------------------------------------------- #

_RESIDENT_BUDGET = 72 * 1024 * 1024   # state+aux residency budget (v5e
#                          VMEM is 128 MiB; the rest holds the chunk
#                          temporaries Mosaic scopes)


def resident_vmem_bytes(model: Model, ny: int, nx: int, dtype) -> int:
    """What a resident call holds on-chip: the two ping-pong field
    stacks, which narrow with the storage dtype, and the aux planes,
    which stay f32 (flags + zonal settings)."""
    n_aux = 1 + len(model.zonal_settings)
    return (2 * model.n_storage * jnp.dtype(dtype).itemsize
            + n_aux * 4) * ny * nx


def supports_resident(model: Model, shape, dtype) -> bool:
    """Whether the generic VMEM-resident engine covers this model/shape:
    any fused-engine-eligible 2D model whose two ping-pong stacks + aux
    planes fit the residency budget.  This generalizes the d2q9-family
    resident kernel (ops/pallas_d2q9.make_resident_iterate) to EVERY
    registry model — the deep temporal fusion the band kernels cannot do
    (their VMEM holds only a band; the reference has no analogue, its GPU
    has no software-managed on-chip tier)."""
    if model.ndim != 2 or len(shape) != 2 or not _storage_ok(dtype):
        return False
    if not supports(model, shape, dtype, probe=False):
        return False
    ny, nx = (int(v) for v in shape)
    if ny % 8 or nx % 128:
        return False   # residency keeps the exact periodic wrap: no
        #                ghost-row machinery, so the shape must be aligned
    if resident_vmem_bytes(model, ny, nx, dtype) > _RESIDENT_BUDGET:
        return False
    plan, reach = action_plan(model, "Iteration", fuse=1)
    if reach > _HALO:
        return False
    return supports(model, shape, dtype, probe=True)


def make_resident_iterate(model: Model, shape, dtype=jnp.float32,
                          interpret: Optional[bool] = None,
                          present: Optional[set] = None,
                          chunk_cap: int = 64,
                          shift: Optional[np.ndarray] = None):
    """Generic VMEM-resident engine: ONE kernel launch advances a whole
    ``iterate(n)``: the first part of ``split(n)`` (an even length that
    leaves the band engine's globals flavour a step where the model
    declares Globals) ride the kernel's grid with the state ping-ponging
    between two on-chip stacks, and the one or two steps left over run on
    the fuse-1 band kernel.  HBM traffic is one read and one write of
    the state a call, whatever its length (the band engines pay a launch
    per 1-2 steps, measured ~40 us of gap each on v5e).  The grid is
    static, so every distinct length is a program of its own, compiled
    at its first call: counter ``engine.resident_programs``.

    Physics is the SAME ``run_action_plan`` trace as the band kernels,
    applied to row chunks of the resident stack; chunk halos are sliced
    from the resident neighbors with exact periodic wrap (``_circ``), so
    full-band's roll-wrap garbage stays in the discarded margin."""
    if not supports_resident(model, shape, dtype):
        raise ValueError(f"generic resident unsupported: {model.name} "
                         f"{shape}")
    cdtype = _COMPUTE_DTYPE
    ny, nx = (int(s) for s in shape)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ns = model.n_storage
    _shifts = ([None] * ns if shift is None
               else [float(w) or None for w in shift])
    zonal_names = list(model.zonal_settings)
    n_aux = 1 + len(zonal_names)
    nt_present = set(model.node_types) if present is None else set(present)
    plan1, reach = action_plan(model, "Iteration", fuse=1)
    n_per_rep = len(model.actions["Iteration"])
    adv = int(any(model.stages[s].load_densities
                  for s in model.actions["Iteration"]))

    # largest multiple-of-8 chunk dividing ny under the cap (bounds the
    # per-chunk temporaries exactly like the band kernels' bands do)
    chunk = 8
    for c in range(8, min(ny, chunk_cap) + 1, 8):
        if ny % c == 0:
            chunk = c

    def _circ(src, k, lo, hi):
        """Rows [lo, hi) of resident plane ``k`` with periodic wrap
        (static indices; at most one end wraps)."""
        if lo >= 0 and hi <= ny:
            return src[k, lo:hi, :]
        parts = []
        if lo < 0:
            parts.append(src[k, ny + lo:ny, :])
            lo = 0
        parts.append(src[k, lo:min(hi, ny), :])
        if hi > ny:
            parts.append(src[k, 0:hi - ny, :])
        return jnp.concatenate(parts, axis=0)

    def kernel(sett, it_ref, f_ref, aux_ref, out_ref, buf):
        """Time rides the GRID: step t's src/dst are picked by parity
        (f_ref only feeds step 0), so the whole horizon runs in ONE
        kernel launch with the state resident on-chip — the in/out
        blocks and scratch have constant index maps, so pallas keeps
        them in VMEM across grid steps and writes HBM once at the end."""
        t = pl.program_id(0)

        def one_step(src, dst):
            for c0 in range(0, ny, chunk):
                c1 = c0 + chunk
                work = [ddf.widen_plane(
                    _circ(src, k, c0 - _HALO, c1 + _HALO), cdtype,
                    _shifts[k]) for k in range(ns)]
                fl = _circ(aux_ref, 0, c0 - _HALO, c1 + _HALO).astype(
                    jnp.int32)
                zon = {nm: _circ(aux_ref, 1 + j, c0 - _HALO, c1 + _HALO)
                       for j, nm in enumerate(zonal_names)}
                work, _, _ = run_action_plan(
                    model, plan1, work, fl, zon, {}, sett,
                    it_ref[0] + t * adv, nt_present, _HALO, nx, cdtype,
                    n_per_rep=n_per_rep, full_band=True)
                for k in range(ns):
                    dst[k, c0:c1, :] = ddf.narrow_plane(
                        work[k][_HALO:_HALO + chunk, :], dtype,
                        _shifts[k])

        # ping-pong scratch <-> out (saves a third whole-lattice stack);
        # an EVEN grid length lands the final step in out_ref
        @pl.when(t == 0)
        def _():
            one_step(f_ref, buf)

        @pl.when(jnp.logical_and(t > 0, jax.lax.rem(t, jnp.int32(2)) == 1))
        def _():
            one_step(buf, out_ref)

        @pl.when(jnp.logical_and(t > 0, jax.lax.rem(t, jnp.int32(2)) == 0))
        def _():
            one_step(out_ref, buf)

    @lru_cache(maxsize=None)
    def _call_for(nsteps: int):
        # runs once a length (while _resident_jit traces it): a mix of
        # segment lengths shows its compiles under the engine's name
        telemetry.counter("engine.resident_programs")
        return pl.pallas_call(
            lbm.mosaic_body(kernel, interpret),
            grid=(nsteps,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((ns, ny, nx), dtype),
            scratch_shapes=[pltpu.VMEM((ns, ny, nx), dtype)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=120 * 1024 * 1024),
            interpret=interpret,
            name=f"generic_resident_fuse{nsteps}",
        )

    zshift = model.zone_shift
    zonal_si = [model.setting_index[nm] for nm in zonal_names]
    # the band engine supplies the trailing in-kernel-globals step (and
    # any remainder), making the composition full_globals
    band = make_pallas_iterate(model, shape, dtype, interpret=interpret,
                               fuse=1, present=present, full_band=True,
                               shift=shift)

    @partial(jax.jit, static_argnames=("niter",), donate_argnums=0)
    def _resident_jit(state: LatticeState, params: SimParams, niter: int
                      ) -> LatticeState:
        flags_i32 = state.flags.astype(jnp.int32)
        zones = flags_i32 >> zshift
        sett = params.settings.astype(cdtype)
        aux = jnp.stack(
            [flags_i32.astype(cdtype)]
            + [fusion.zone_plane(params.zone_table[j].astype(cdtype), zones)
               for j in zonal_si])
        fields = _call_for(niter)(sett, state.iteration[None],
                                  state.fields.astype(dtype), aux)
        return LatticeState(fields=fields, flags=state.flags,
                            globals_=jnp.zeros_like(state.globals_),
                            iteration=state.iteration + adv * niter)

    # EVEN resident length (ping-pong parity) leaving >=1 step for the
    # band engine's globals flavor when the model declares Globals
    # (full_globals contract)
    tail_min = 1 if band.full_globals and model.n_globals else 0

    def split(niter: int) -> tuple:
        """``niter`` steps as the steps of the one resident call and
        the steps left to the band engine."""
        main = max(niter - tail_min, 0) // 2 * 2
        return main, niter - main

    def account(niter: int, has_series: bool = False) -> dict:
        """What one ``iterate(niter)`` issues, reckoned host-side from
        ``iterate``'s own split: one resident call of ``main`` steps,
        and the band engine's own account of the steps left to it,
        whose ``aux_planes`` goes by ``remainder_aux_planes``."""
        main, left = split(int(niter))
        rest = band.account(left)
        return dict(
            rest, kernel_calls=int(main > 0) + rest["kernel_calls"],
            resident_calls=int(main > 0), resident_steps=main,
            remainder_steps=left, aux_planes=n_aux,
            remainder_aux_planes=rest["aux_planes"], chunk_rows=chunk,
            vmem_bytes=resident_vmem_bytes(model, ny, nx, dtype))

    def iterate(state: LatticeState, params: SimParams, niter: int
                ) -> LatticeState:
        if params.time_series is not None:
            raise ValueError("generic resident engine does not support "
                             "Control time series")
        main, left = split(niter)
        if main:
            state = _resident_jit(state, params, main)
        if left:
            state = band(state, params, left)
        return state

    return Engine(iterate, account, full_globals=band.full_globals)


# --------------------------------------------------------------------------- #
# 3D: z-slab bands (the generic counterpart of ops/pallas_d3q's block kernel)
# --------------------------------------------------------------------------- #


# fused (fuse>=2) 3D calls budget a larger scratch against the raised
# 100 MB scoped-vmem ceiling they always compile with — the wider K*R
# halo is what buys the K-fold traffic amortization
_FUSED3D_BUDGET = 28 * 1024 * 1024
_VMEM3D_LIMIT = 100 * 1024 * 1024
# Mosaic's own scoped-vmem limit on a v5e, which a kernel built without
# the raised ceiling compiles under
_VMEM3D_DEFAULT = 16 * 1024 * 1024
# a plane no whole-plane plan holds is cut into y bands: windows of bz
# slabs x by rows with _HALO wrapped halo rows a side (one sublane tile,
# so every DMA window starts on a tile boundary), always compiled under
# the raised ceiling.  _TILED3D_BUDGET is what _window_fits lets a window
# fill of it: DMA scratch, the pipelined out blocks and the traced
# physics' temporaries, counted as _TILE3D_TEMP_PLANES f32 planes a
# storage plane over the widest stage window (from Mosaic's own reports
# for d3q19_kuper and d3q19_heat compiled for a described v5e, PR 34)
_TILED3D_BUDGET = 80 * 1024 * 1024
_TILE3D_TEMP_PLANES = 3
# what a node step computed again costs, in the f32 planes of
# _tile_cost_3d's traffic: a window computes every stage on its halo
# rows and on the slabs later stages read.  Measured for d3q19_kuper on
# a v5e only (PR 34's sweep of ten plans at 256^3): the five one-step
# plans run at 0.0056 to 0.0059 ns a plane and node moved (83 % of the
# HBM peak, in the order of their traffic), the five deeper ones at 0.21
# to 0.27 ns a computed node step, which is 41 planes
_RECOMPUTE3D_PLANES = 41


def _slab_depth_gen(model: Model, nz: int, ny: int, nx: int,
                    reach: int, cap: Optional[int] = None,
                    n_aux: Optional[int] = None,
                    budget: Optional[int] = None,
                    itemsize: int = 4) -> Optional[int]:
    """Largest slab depth BZ dividing nz whose double-slotted scratch
    (state + aux, band + ``reach`` halo slabs each side) fits the budget.
    Unlike the 2D rows, z is NOT a tiled axis, so halos are exactly
    ``reach`` slabs — no 8-alignment games."""
    if n_aux is None:
        n_aux = 1 + 2 * len(model.zonal_settings)   # series flavor's aux
    # field slabs scale with the storage itemsize; aux stays f32
    per_slab = (model.n_storage * itemsize + n_aux * 4) * ny * nx
    if budget is None:
        budget = 12 * 1024 * 1024
    best = None
    for bz in range(1, (nz if cap is None else min(nz, cap)) + 1):
        if nz % bz:
            continue
        # double-slotted scratch; compute temporaries live in the rest of
        # VMEM (the same ~15 MB working budget the tuned 3D kernel uses)
        if 2 * (bz + 2 * reach) * per_slab > budget:
            break
        best = bz
    return best


def _whole_plane_3d(model: Model, nz: int, ny: int, nx: int,
                    itemsize: int = 4) -> bool:
    """Whether the single-step kernel holds whole (ny, nx) planes: then
    every plan of the shape keeps the plane whole, as before y tiling."""
    r1 = max(action_plan(model, "Iteration", fuse=1)[1], 1)
    return _slab_depth_gen(model, nz, ny, nx, r1,
                           itemsize=itemsize) is not None


def _reach_y(model: Model, fuse: int) -> int:
    """Rows a side that ``fuse`` repetitions of the action spoil in a y
    window: every pull and every Field load rolls the window in y inside
    its own rows, so a stage's y reach is lost at either end."""
    return fuse * sum(_stage_reach(model, s, "y")
                      for s in model.actions["Iteration"])


def _window_vmem(model: Model, nx: int, bz: int, rows: int, by: int,
                 plan: list, reach: int, itemsize: int = 4) -> int:
    """VMEM account of a tiled window of ``bz`` slabs x ``by`` rows
    (``rows`` with its halo rows) under an action ``plan`` of ``reach``
    halo slabs: the double-slotted state + aux scratch (the series
    flavor's aux stack: every flavor shares the window), the pipelined
    out blocks, and the temporaries of the widest stage window."""
    ns = model.n_storage
    n_aux = 1 + 2 * len(model.zonal_settings)
    scratch = 2 * (ns * itemsize + n_aux * 4) * (bz + 2 * reach) * rows * nx
    out = 2 * ns * itemsize * bz * by * nx
    widest = bz + 2 * max(ext for _, ext in plan)
    temp = _TILE3D_TEMP_PLANES * ns * widest * rows * nx * 4
    return scratch + out + temp


def _window_fits(model: Model, nx: int, bz: int, rows: int, by: int,
                 plan: list, reach: int, itemsize: int = 4,
                 budget: int = _TILED3D_BUDGET) -> bool:
    """Whether :func:`_window_vmem` of the window is within ``budget``."""
    return _window_vmem(model, nx, bz, rows, by, plan, reach,
                        itemsize) <= budget


def _tile_cost_3d(model: Model, bz: int, rows: int, by: int,
                  plan: list, reach: int, K: int) -> tuple:
    """What decides between tiled plans, in f32 planes a node step: the
    planes a window moves (state + flag plane read with its halos, state
    written) or the node steps it computes (every stage on its extended
    slabs and all its rows), whichever binds; the traffic breaks ties."""
    ns = model.n_storage
    wide = rows / by
    traffic = ((ns + 1) * (bz + 2 * reach) * wide + ns * bz) / (K * bz)
    again = wide * sum(bz + 2 * ext for _, ext in plan) / (len(plan) * bz)
    return max(traffic, _RECOMPUTE3D_PLANES * again), traffic


def tile_plan_3d(model: Model, shape, itemsize: int = 4,
                 fuse: Optional[int] = None, cap: Optional[int] = None,
                 budget: int = _TILED3D_BUDGET) -> Optional[tuple]:
    """Plan ``(bz, by, K)`` of the slab kernel for a shape whose plane no
    whole-plane plan holds: windows of ``bz`` slabs x ``by`` rows (a
    multiple of 8 dividing ny, x whole) with ``_HALO`` wrapped halo rows a
    side, ``K`` action repetitions a round trip (``fuse`` pins K).  The
    in-window y roll spoils :func:`_reach_y` rows a side, which the halo
    bounds; ``by == ny`` keeps the plane whole (no halo rows) where only
    the raised ceiling takes it.  The least :func:`_tile_cost_3d` wins,
    ties go to the taller band.  ``cap`` is a rung of the Lattice's probe
    ladder: bands of at most ``|cap|`` rows and ``|cap| // 8`` slabs.
    None where a whole-plane plan exists or nothing fits."""
    nz, ny, nx = (int(s) for s in shape)
    if _whole_plane_3d(model, nz, ny, nx, itemsize):
        return None
    by_max = ny if cap is None else min(ny, abs(cap))
    bz_max = nz if cap is None else max(1, abs(cap) // 8)
    bys = [b for b in range(ny, 0, -_HALO)
           if ny % b == 0 and b % _HALO == 0 and b <= by_max]
    best, best_c = None, None
    for K in ([fuse] if fuse else range(1, fusion.FUSE_MAX + 1)):
        plan, reach = action_plan(model, "Iteration", fuse=K)
        reach = max(reach, 1)
        if nz < 2 * reach:
            break
        for by in bys:
            hy = 0 if by == ny else _HALO
            if hy and _reach_y(model, K) > hy:
                continue
            rows = by + 2 * hy
            bz = max((b for b in range(1, min(nz, bz_max) + 1)
                      if nz % b == 0 and _window_fits(
                          model, nx, b, rows, by, plan, reach, itemsize,
                          budget)),
                     default=None)
            if bz is None:
                continue
            c = _tile_cost_3d(model, bz, rows, by, plan, reach, K)
            if best_c is None or c < best_c:
                best, best_c = (bz, by, K), c
    return best


def window_account_3d(model: Model, shape, plan: tuple, itemsize: int = 4
                      ) -> dict:
    """The windows one call of the slab kernel at ``plan`` =
    ``(bz, by, K)`` cuts ``shape`` into (on a mesh: one shard's), under
    the names an engine's account reports them by, and what
    :func:`_window_vmem` counts of one."""
    nz, ny, nx = (int(s) for s in shape)
    bz, by, K = plan
    stages, reach = action_plan(model, "Iteration", fuse=K)
    R, hy = max(reach, 1), _HALO if by < ny else 0
    return dict(
        z_bands=nz // bz, band_slabs=bz, halo_slabs=R,
        y_bands=ny // by, band_rows=by, halo_rows=hy,
        vmem_bytes=_window_vmem(model, nx, bz, by + 2 * hy, by, stages, R,
                                itemsize))


def choose_fuse_3d(model: Model, shape,
                   fmax: int = fusion.FUSE_MAX,
                   itemsize: int = 4) -> int:
    """Fusion depth for the 3D generic z-slab engine: deepest K whose
    fused plan both fits the (raised-ceiling) VMEM budget at some slab
    depth AND beats the single-step engine's modeled traffic.  3D halos
    are real slabs (not fixed-height row blocks), so unlike 2D the halo
    cost grows with K and the planner must weigh it.  A plane that is
    tiled gets :func:`tile_plan_3d`'s K."""
    nz, ny, nx = (int(s) for s in shape)
    _, r1 = action_plan(model, "Iteration", fuse=1)
    R1 = max(r1, 1)
    ns = model.n_storage
    if not _whole_plane_3d(model, nz, ny, nx, itemsize):
        tiled = tile_plan_3d(model, shape, itemsize)
        return tiled[2] if tiled else 1
    bz1 = _slab_depth_gen(model, nz, ny, nx, R1, itemsize=itemsize)
    # lean aux: the non-series kernels move ns + 1 planes per slab
    best, best_c = 1, ((ns + 1) * (bz1 + 2 * R1) + ns * bz1) / bz1
    for K in range(2, fmax + 1):
        _, rK = action_plan(model, "Iteration", fuse=K)
        RK = max(rK, 1)
        if nz < 2 * RK:
            break
        bzK = _slab_depth_gen(model, nz, ny, nx, RK, n_aux=1,
                              budget=_FUSED3D_BUDGET, itemsize=itemsize)
        if bzK is None:
            continue
        c = ((ns + 1) * (bzK + 2 * RK) + ns * bzK) / (K * bzK)
        if c < best_c:
            best, best_c = K, c
    return best


def supports_3d(model: Model, shape, dtype, probe: bool = True) -> bool:
    """3D eligibility: same registry checks as 2D, z-banded; a plane no
    whole-plane plan holds needs a tiled one (:func:`tile_plan_3d`)."""
    if model.ndim != 3 or len(shape) != 3 or not _storage_ok(dtype):
        return False
    if "Iteration" not in model.actions:
        return False
    for s in model.actions["Iteration"]:
        st = model.stages.get(s)
        if st is None or st.fixed_point \
                or model.stage_fns.get(st.main) is None:
            return False
    plan, reach = action_plan(model, "Iteration", fuse=1)
    nz, ny, nx = (int(v) for v in shape)
    itemsize = jnp.dtype(dtype).itemsize
    if nz < 2 * max(reach, 1):
        return False
    if jax.default_backend() == "tpu" and (nx % 128 or ny % 8):
        return False  # (ny, nx) is the (sublane, lane) tile
    if not _whole_plane_3d(model, nz, ny, nx, itemsize) \
            and tile_plan_3d(model, shape, itemsize) is None:
        return False
    if not probe:
        return True
    key = (model.name, "3d", ny, nx, itemsize)
    if key not in _probe_cache:
        try:
            it = make_pallas_iterate_3d(model, (4 * max(reach, 1), ny, nx),
                                        dtype, interpret=True)
            shp = (4 * max(reach, 1), ny, nx)
            state = LatticeState(
                fields=jax.ShapeDtypeStruct((model.n_storage,) + shp, dtype),
                flags=jax.ShapeDtypeStruct(shp, jnp.uint16),
                globals_=jax.ShapeDtypeStruct((model.n_globals,), dtype),
                iteration=jax.ShapeDtypeStruct((), jnp.int32))
            params = SimParams(
                settings=jax.ShapeDtypeStruct((len(model.settings),), dtype),
                zone_table=jax.ShapeDtypeStruct(
                    (len(model.settings), model.zone_max), dtype))
            jax.eval_shape(partial(it, niter=2), state, params)
            _probe_cache[key] = True
        except Exception as e:  # noqa: BLE001
            from tclb_tpu.utils import log
            log.debug(f"pallas_generic 3d: {model.name} probe failed: "
                      f"{type(e).__name__}: {str(e)[:200]}")
            _probe_cache[key] = False
    return _probe_cache[key]


def make_pallas_iterate_3d(model: Model, shape, dtype=jnp.float32,
                           interpret: Optional[bool] = None,
                           present: Optional[set] = None,
                           fuse: int = 1,
                           by_cap: Optional[int] = None,
                           shift: Optional[np.ndarray] = None,
                           window: Optional[tuple] = None,
                           points: Optional[np.ndarray] = None,
                           ext_halo: bool = False):
    """3D generic engine: the model's full Iteration action per z-slab
    band pass, with the same registry-driven machinery as the 2D builder
    (multi-stage extension plan, zonal aux planes, in-kernel SUM globals
    flavor, Control-series flavor).  ``fuse=K`` runs K action reps per
    HBM round trip: the fused action plan's progressive windows already
    encode the shrinking interiors, so the kernel machinery is identical
    — only the halo widens to the fused plan's reach and the non-series
    scan advances K iterations per call (remainder steps use a fuse=1
    flavor).

    The plan is ``(bz, by, K)``.  A shape whose single-step kernel holds
    whole planes keeps them (``by == ny``, a 1-D grid, ``bz`` from
    :func:`_slab_depth_gen` as before y tiling).  Any other plane is cut
    into bands of ``by`` rows with ``_HALO`` wrapped halo rows a side
    (:func:`tile_plan_3d` at this ``fuse``; grid z-major, y-minor): the
    in-window y roll spoils :func:`_reach_y` halo rows a side, never a
    band row.  Every flavor runs on the same windows: the remainder, the
    in-kernel globals flavor (it sums a band's own rows only) and the
    Control-series flavors at ``fuse=1`` inside the fused plan's
    ``(bz, by)``, whose account holds the series' aux stack.
    ``window=(bz, by)`` pins the window (tests and sweeps); ``points``
    builds the sampled flavour (:func:`_scheduled_engine`).

    ``ext_halo=True`` builds the sharded building block instead, for
    ``parallel/halo.make_sharded_pallas_tail`` (``fuse`` 1, f32):
    ``shape`` is one device's z-block of a lattice split in z, planned
    and cut into windows as a lattice of that shape is, and z no longer
    wraps inside the array.  The kernel takes the block as it is and the
    lower and the upper neighbour's ``R1`` slabs (the plan's reach, at
    least 1) as operands of their own, ``(ns, R1, ny, nx)`` each: a z
    halo piece of a window is copied from the block where it lies inside
    it, else from the neighbour's slabs (``slab_dma.field_copy``, as
    ``ops/pallas_d3q``'s ``ext`` flavour); no padded copy of the block is
    made.  The aux stack comes extended by ``R1`` slabs a side; y still
    wraps inside the block.  Returns ``(call, call_g, (bz, by, 1),
    zonal_names)`` as the 2D builder's ``ext_halo`` does, the calls
    ``(settings, iteration[None], [zone table,] block, lower slabs,
    upper slabs, aux) ->`` the block a step on (``call_g``: and its
    Globals, summed over the block's own nodes; None where
    :func:`kernel_reduces_globals` is false).  The aux diet is the
    one-chip engine's: with zonal settings the flavours are lean (the
    flattened zone table in SMEM, the f32 flag plane the only aux
    plane), without any the aux stack is the flag plane too."""
    if not supports_3d(model, shape, dtype, probe=False):
        raise ValueError(f"pallas_generic 3d unsupported: {model.name} "
                         f"{shape}")
    cdtype = _COMPUTE_DTYPE
    itemsize = jnp.dtype(dtype).itemsize
    if ext_halo and (fuse != 1 or jnp.dtype(dtype) != jnp.dtype(cdtype)):
        raise ValueError("ext_halo (sharded) blocks are f32, fuse=1 only")
    plan, reach = action_plan(model, "Iteration", fuse=fuse)
    R = max(reach, 1)
    plan1, r1 = (plan, reach) if fuse == 1 \
        else action_plan(model, "Iteration", fuse=1)
    R1 = max(r1, 1)
    nz, ny, nx = (int(s) for s in shape)
    if nz < 2 * R:
        raise ValueError(f"fuse={fuse} needs nz >= {2 * R}")
    # the Lattice probe ladder passes row-oriented caps (16, 8); for
    # z-slabs interpret them as a slab-depth cap (8 rows ~ 1 slab) so the
    # retry actually shrinks the scoped-VMEM working set.  NEGATIVE caps
    # are the last-resort rungs: |cap| plus a raised scoped-vmem ceiling.
    # Fused (K>=2) builds always compile with the raised ceiling: their
    # K*R halo scratch is budgeted against it (_FUSED3D_BUDGET); so do
    # tiled windows (_TILED3D_BUDGET), whose rungs cap rows and slabs
    # both; and so does a whole-plane window whose own account
    # (_window_fits: _slab_depth_gen budgets the DMA scratch alone)
    # passes Mosaic's default limit: d3q27_cumulant's one slab of 48 x
    # 256 needs 16.14 MiB of 16 (v5e compile, PR 44).
    whole = window is None and _whole_plane_3d(model, nz, ny, nx, itemsize)
    if window is not None:
        bz, by = (int(v) for v in window)
    elif whole:
        cap = None if by_cap is None else max(1, abs(by_cap) // 8)
        by = ny
        bz = _slab_depth_gen(model, nz, ny, nx, R, cap, n_aux=1,
                             budget=_FUSED3D_BUDGET, itemsize=itemsize) \
            if fuse >= 2 \
            else _slab_depth_gen(model, nz, ny, nx, R, cap,
                                 itemsize=itemsize)
    else:
        tiled = tile_plan_3d(model, shape, itemsize, fuse, by_cap)
        bz, by = tiled[:2] if tiled else (None, ny)
    if bz is None:
        raise ValueError(f"no slab depth fits fuse={fuse} for "
                         f"{model.name} {shape}")
    if nz % bz or ny % by or (by < ny and (by % _HALO
                                           or _reach_y(model, fuse) > _HALO)):
        raise ValueError(f"window {(bz, by)} does not tile {shape} at "
                         f"fuse={fuse}")
    hy = _HALO if by < ny else 0   # wrapped halo rows a side
    rows = by + 2 * hy             # rows of a window
    nzb, nyb = nz // bz, ny // by
    vmem_ceiling = (by_cap is not None and by_cap < 0) or fuse >= 2 \
        or not whole or not _window_fits(model, nx, bz, rows, by, plan, R,
                                         itemsize, _VMEM3D_DEFAULT)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    ns = model.n_storage
    _shifts = ([None] * ns if shift is None
               else [float(w) or None for w in shift])
    zonal_names = list(model.zonal_settings)
    zshift = model.zone_shift
    zone_max = model.zone_max
    ei = model.ei
    stage_fns = {nm: model.stage_fns[model.stages[nm].main]
                 for nm in model.actions["Iteration"]}
    loads_density = {nm: model.stages[nm].load_densities
                     for nm in model.actions["Iteration"]}
    nt_present = set(model.node_types) if present is None else set(present)
    y_pieces = slab_dma.pieces(by, hy)
    own = slice(hy, hy + by) if hy else slice(None)   # a band's own rows

    def _mk_kernel(plan, R, with_dt=False, with_globals=False, lean=False):
        n_aux_k = 1 if lean \
            else 1 + (2 if with_dt else 1) * len(zonal_names)
        z_pieces = slab_dma.pieces(bz, R)
        n_sem = len(z_pieces) * len(y_pieces)

        def kern(sett, it_ref, *rest):
            if lean:
                ztab, f_hbm, *refs = rest
            else:
                ztab = None
                f_hbm, *refs = rest
            # ext_halo: the two neighbours' slabs stand before the aux
            halos = [refs.pop(0) for _ in range(2 * ext_halo)]
            aux_hbm = refs.pop(0)
            if with_globals:
                out_ref, g_ref, buff, bufa, sems = refs
            else:
                (out_ref, buff, bufa, sems), g_ref = refs, None
            i = pl.program_id(0)
            j = pl.program_id(1) if nyb > 1 else jnp.int32(0)
            t = i * jnp.int32(nyb) + j     # windows run z-major, y-minor

            def band_dmas(slot, bi, bj):
                z0 = bi * jnp.int32(bz)
                y0 = bj * jnp.int32(by)
                out = []
                for hbm, buf, nplanes, sides in (
                        (f_hbm, buff, ns, halos),
                        (aux_hbm, bufa, n_aux_k, ())):
                    for oz, dz, lz in z_pieces:
                        # the extended aux stack holds slab z at z + R
                        sz = (z0 + jnp.int32(oz + R) if ext_halo
                              else slab_dma.wrap(z0, oz, nz))
                        for oy, dy_, ly in y_pieces:
                            src_y = dst_y = ()      # whole planes
                            if hy:
                                # bands and halos are whole sublane tiles
                                sy = pl.multiple_of(
                                    slab_dma.wrap(y0, oy, ny), _HALO)
                                src_y, dst_y = ((pl.ds(sy, ly),),
                                                (pl.ds(dy_, ly),))

                            dst = buf.at[(slot, slice(None),
                                          pl.ds(dz, lz)) + dst_y]

                            def window(ref, z, lz=lz, nplanes=nplanes,
                                       src_y=src_y, dst=dst,
                                       sem=sems.at[slot, len(out)]):
                                return pltpu.make_async_copy(
                                    ref.at[(pl.ds(0, nplanes),
                                            pl.ds(z, lz)) + src_y],
                                    dst, sem)

                            out.append(slab_dma.field_copy(
                                window, hbm, sides, z0, oz, sz, lz, nz, R))
                return out

            slot = jax.lax.rem(t, jnp.int32(2))
            nxt = jax.lax.rem(t + jnp.int32(1), jnp.int32(2))
            if nyb > 1:
                turn = j + jnp.int32(1) == jnp.int32(nyb)
                ni = jnp.where(turn, i + jnp.int32(1), i)
                nj = jnp.where(turn, jnp.int32(0), j + jnp.int32(1))
            else:
                ni, nj = i + jnp.int32(1), j

            @pl.when(t == 0)
            def _():
                for d in band_dmas(jnp.int32(0), i, j):
                    d.start()

            @pl.when(t + 1 < nzb * nyb)
            def _():
                for d in band_dmas(nxt, ni, nj):
                    d.start()

            for d in band_dmas(slot, i, j):
                d.wait()

            def _rollyx(sl, dy, dx):
                # in a y window the roll wraps inside the window's own
                # rows: one halo row a side is spoiled per unit of reach
                if dy:
                    sl = jnp.roll(sl, dy, axis=1)
                if dx % nx:
                    sl = pltpu.roll(sl, dx % nx, axis=2)
                return sl

            # widen to the compute dtype at the read (traced no-op at f32
            # storage); the whole fused action accumulates in f32 and the
            # output write narrows back to the storage dtype
            # each plane with the first buffer slab it covers: a stage
            # replaces a plane by the slabs it computed, and every later
            # read lies inside them (the plan's extensions see to that),
            # so the stale slabs outside are dropped, not carried along
            work = [(ddf.widen_plane(buff[slot, k], cdtype, _shifts[k]), 0)
                    for k in range(ns)]

            def slabs(k, first, n):
                arr, z0 = work[k]
                return arr[first - z0:first - z0 + n]

            flags_full = bufa[slot, 0].astype(jnp.int32)
            if ztab is not None:
                zones_full = flags_full >> zshift
                zonal_full = {nm: fusion.zone_plane(ztab, zones_full,
                                                    zone_max, col=c)
                              for c, nm in enumerate(zonal_names)}
                dt_full = {}
            else:
                zonal_full = {nm: bufa[slot, 1 + c]
                              for c, nm in enumerate(zonal_names)}
                dt_full = {nm: bufa[slot, 1 + len(zonal_names) + c]
                           for c, nm in enumerate(zonal_names)} \
                    if with_dt else {}
            g_acc: dict = {}

            n_per_rep = len(model.actions["Iteration"])
            for st_i, (stage_name, out_ext) in enumerate(plan):
                n_i = bz + 2 * out_ext
                lo = R - out_ext
                rep = st_i // n_per_rep

                if loads_density[stage_name]:
                    planes = []
                    for k in range(ns):
                        dxk, dyk, dzk = (int(v) for v in ei[k])
                        planes.append(_rollyx(slabs(k, lo - dzk, n_i),
                                              dyk, dxk))
                else:
                    planes = [slabs(k, lo, n_i) for k in range(ns)]

                def loader(index, dx, dy, dz=0, _lo=lo, _n=n_i):
                    return _rollyx(slabs(index, _lo + dz, _n), -dy, -dx)

                ctx = KernelCtx(
                    model, planes, loader,
                    flags_full[lo:lo + n_i],
                    {nm: p[lo:lo + n_i] for nm, p in zonal_full.items()},
                    sett, cdtype, it_ref[0] + rep, nt_present,
                    dt_planes={nm: p[lo:lo + n_i]
                               for nm, p in dt_full.items()},
                    compute_globals=g_ref is not None)
                res = stage_fns[stage_name](ctx)
                if g_ref is not None:
                    for nm, plane in ctx._globals.items():
                        part = plane[out_ext:out_ext + bz, own]
                        g_acc[nm] = part if nm not in g_acc \
                            else g_acc[nm] + part

                if isinstance(res, dict):
                    updates: dict[int, jnp.ndarray] = {}
                    for name, stack in res.items():
                        if name in model.groups:
                            idx = model.groups[name]
                            if len(idx) == 1 and stack.ndim == 3:
                                updates[idx[0]] = stack
                            else:
                                for c, k in enumerate(idx):
                                    updates[k] = stack[c]
                        else:
                            updates[model.storage_index[name]] = stack
                else:
                    updates = {k: res[k] for k in range(ns)}
                for k, new in updates.items():
                    work[k] = (new, lo)

            for k in range(ns):
                out_ref[k] = ddf.narrow_plane(slabs(k, R, bz)[:, own], dtype,
                                              _shifts[k])

            if g_ref is not None:
                @pl.when(t == 0)
                def _():
                    g_ref[...] = jnp.zeros((8, 128), cdtype)
                for gi, g in enumerate(model.globals_):
                    if g.name not in g_acc:
                        continue
                    part = g_acc[g.name].reshape(
                        (bz * by * (nx // 128), 128)).sum(axis=0)
                    g_ref[gi] = g_ref[gi] + part

        return kern, n_aux_k, n_sem

    def _mk_call(plan_k, R_k, with_dt=False, with_globals=False,
                 lean=False):
        kern, n_aux_k, n_sem = _mk_kernel(plan_k, R_k, with_dt,
                                          with_globals, lean)
        tiled = nyb > 1
        out_specs = pl.BlockSpec(
            (ns, bz, by, nx),
            (lambda i, j: (0, i, j, 0)) if tiled
            else (lambda i: (0, i, 0, 0)),
            memory_space=pltpu.VMEM)
        out_shape = jax.ShapeDtypeStruct((ns, nz, ny, nx), dtype)
        if with_globals:
            out_specs = [out_specs,
                         pl.BlockSpec((8, 128),
                                      (lambda i, j: (0, 0)) if tiled
                                      else (lambda i: (0, 0)),
                                      memory_space=pltpu.VMEM)]
            out_shape = [out_shape,
                         jax.ShapeDtypeStruct((8, 128), cdtype)]
        return pl.pallas_call(
            lbm.mosaic_body(kern, interpret),
            grid=(nzb, nyb) if tiled else (nzb,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ] + ([pl.BlockSpec(memory_space=pltpu.SMEM)] if lean else [])
            + [pl.BlockSpec(memory_space=pl.ANY)] * (4 if ext_halo else 2),
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((2, ns, bz + 2 * R_k, rows, nx), dtype),
                pltpu.VMEM((2, n_aux_k, bz + 2 * R_k, rows, nx), cdtype),
                pltpu.SemaphoreType.DMA((2, 2 * n_sem)),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM3D_LIMIT)
            if vmem_ceiling else None,
            interpret=interpret,
            name=f"generic_slab_fuse{fuse if plan_k is plan else 1}",
        )

    if ext_halo:
        return (*_shard_calls(model, nx, fuse, partial(
            _mk_call, plan, R, lean=bool(zonal_names))),
            (bz, by, fuse), zonal_names)

    return _scheduled_engine(
        model, dtype, nx, fuse, partial(_mk_call, plan, R),
        partial(_mk_call, plan1, R1),
        window=lambda fused: dict(
            z_bands=nzb, band_slabs=bz, halo_slabs=R if fused else R1,
            y_bands=nyb, band_rows=by, halo_rows=hy),
        impl=dict(bz=bz),
        # the series loop runs one call a body: pairing it is not
        # measured (ROADMAP M2)
        series_paired=False, points=points, plan=(bz, by, fuse))
