"""Pallas fused collide-stream kernel for the d2q9 model family.

This is the TPU equivalent of the reference's tuned CUDA hot loop
(reference src/LatticeContainer.inc.cpp.Rt:247-266 ``RunKernel`` and
src/cuda.cu.Rt:236-274 ``RunElement``): one kernel performs pull-streaming,
boundary handling and MRT collision in a single pass, reading each density
once from HBM and writing it once — the 1R+1W-per-density traffic model the
reference prints as GB/s (src/main.cpp.Rt:126).

Design (TPU-first, not a CUDA translation):

* the lattice is tiled into row bands of ``BY`` rows; each grid step DMAs its
  band plus one wrapped halo row above and below from HBM into VMEM scratch
  (the reference instead splits storage into 27 margin blocks — here the halo
  is re-read from the neighbouring band, a 2/BY traffic overhead);
* pull-streaming is static slicing in y (the halo rows make ``y ± 1`` local)
  and a lane-roll in x (``pltpu.roll`` — x is the lane dimension and stays
  whole, exactly like the reference keeps x unsplit for coalescing,
  src/Solver.cpp.Rt:274);
* per-node ``switch (NodeType)`` dispatch is mask/select algebra on an int32
  copy of the flag field (branchless, VPU-friendly);
* the 9x9 MRT moment transforms are unrolled sparse multiply-adds on the VPU
  (the matrices are ±small-integer constants; an MXU matmul would waste a
  128x128 systolic pass on a 9-vector);
* scalar Settings ride in SMEM; zonal Settings (Velocity/Density) are
  built into per-node planes outside the kernel, once a call, by selects
  over the zones (``fusion.zone_plane`` — the reference reads them per
  node from const memory through the zone bits,
  src/LatticeContainer.h.Rt:89-108).  Without a ``<Control>`` series
  they are constant across an ``Iterate`` call.  Under one (the
  reference's zonal time tables, src/ZoneSettings.h:9-120) the band
  kernels' series flavour takes, beside those planes, the value each
  series row has at the iteration of EACH step of the call, as scalars
  in SMEM sliced from the ``(n_series, T)`` table in HBM before the call
  (``series_flavour``), and selects them over their zones in the kernel
  (``_with_series``): no plane is made or read for a step, whatever
  ``T`` is.  The sharded (``ext_halo``), sampled and VMEM-resident
  flavours take no series; dispatch keeps such runs off them.

This path is the reference's "NoGlobals" kernel specialization
(src/cuda.cu.Rt Globals-mode template parameter): per-iteration Globals are
not accumulated; ``state.globals_`` is zeroed.  Use the XLA path when
objectives/monitors are needed per step.

The physics here intentionally mirrors ``models/d2q9.py`` op for op;
``tests/test_pallas.py`` pins the two paths together.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tclb_tpu.core.lattice import LatticeState, SimParams
from tclb_tpu.core.registry import Model
from tclb_tpu.ops import fusion, lbm
from tclb_tpu.ops.engine import Engine, paired_calls, scan_calls, tap
from tclb_tpu.ops.lbm import equilibrium, present_types  # noqa: F401

_HALO = 8            # halo rows a side of a band: one f32 sublane tile
_AUX_PLANES = 3      # what a kernel call reads beside the state: the
#                      int32 flags, the f32 Velocity and Density planes

# Mosaic's own scoped-VMEM limit on a v5e: a kernel built without
# ``compiler_params`` compiles under it.  A plan that fits it states no
# limit of its own, and its program is the one the chip records are of
_VMEM_DEFAULT = 16 * 1024 * 1024
# what a plan may ask for instead (``vmem_limit_bytes``; the chip has
# 128 MiB), where the default holds no band or only one that reads its
# halo rows too often (:func:`_rows`): rows of 1536 nodes and more.  It
# holds the two-step kernel's 32-row band at 8192 nodes a row with both
# slots of its input (104.7 MiB by the account, 102.2 by Mosaic's count)
_VMEM_RAISED = 106 * 1024 * 1024
# the two-step kernel's band stops here: 56 rows and more showed no gain
# over 48 at 1024 nodes a row (chip, round 3, on the kernel that waited
# for its input copies; not read again since it prefetches them)
_FUSED_ROWS_MAX = 48
_BAND_SLOTS = 2      # scratch slots of a band kernel's input: the band
#                      computed from and the next one's copies in flight
# Mosaic's temporaries, in f32 planes of the band, read off compiles for
# a described v5e (the limit raised step by step until the compile
# passed, nx 512 to 8192, bands of 8 to 128 rows, every boundary type
# present; tests/test_tuned_band_plan.py keeps the readings): the one-step
# kernel's by model, and more of them a plane where a plane is small
# (d2q9: 11.5 at 32 KiB, 10.3 at 128 KiB, 6.0 at 256 KiB); the two-step
# kernel's 27.3 to 30.3 planes of ``band + 10`` rows for every model
# (read again since it holds two slots of its band: 18 readings)
_TEMP_PLANES_1 = {"d2q9": (12, 7), "d2q9_SRT": (21, 21),
                  "d2q9_inc": (21, 21), "d2q9_cumulant": (21, 21),
                  "d2q9_les": (36, 36), "d2q9_new": (30, 30)}
_TEMP_SMALL_PLANE = 128 * 1024
_TEMP_PLANES_2 = 31


def band_vmem(model: Model, rows: int, nx: int, steps: int) -> int:
    """The scoped VMEM a band kernel of ``rows`` rows needs, by the
    planner's own account: the DMA scratch, the pipelined blocks (each
    double-buffered) and Mosaic's temporaries.  ``steps`` 1: the
    one-step kernel (two scratch slots of band and halos; the three aux
    blocks and the out block); 2: the two-step kernel (two slots of the
    state and of the aux stack, each with its halo blocks; the out
    block)."""
    ns, row, plane = model.n_storage, nx * 4, rows * nx * 4
    if steps == 1:
        small, large = _TEMP_PLANES_1[model.name]
        return (_BAND_SLOTS * ns * (rows + 2 * _HALO) * row
                + 2 * (_AUX_PLANES + ns) * plane
                + max(small * min(plane, _TEMP_SMALL_PLANE), large * plane))
    return (_BAND_SLOTS * (ns + _AUX_PLANES) * (rows + 2 * _HALO) * row
            + 2 * ns * plane + _TEMP_PLANES_2 * (rows + 10) * row)


def _rows(model: Model, ny: int, nx: int, steps: int,
          rows_cap: Optional[int] = None) -> Optional[tuple]:
    """``(rows, limit)`` of the kernel advancing ``steps`` steps a call
    on ``ny`` (padded) rows: :func:`fusion.plan_band` over the account
    of :func:`band_vmem`; the limit is Mosaic's default or the raised
    ceiling."""
    cap = min(rows_cap or ny, _FUSED_ROWS_MAX if steps == 2 else ny)
    return fusion.plan_band(
        ny, lambda rows: band_vmem(model, rows, nx, steps), cap,
        (_VMEM_DEFAULT, _VMEM_RAISED), _HALO)


@dataclasses.dataclass(frozen=True)
class BandPlan:
    """How the band kernels cut a lattice of (ny, nx): ghost rows under
    it, the rows of the one-step and of the two-step kernel's band, what
    each needs of VMEM by :func:`band_vmem`, and the scoped-VMEM limit
    each is compiled under (``_VMEM_DEFAULT``: none is stated)."""
    pad_rows: int
    band_rows: tuple      # (one-step, two-step)
    vmem_bytes: tuple
    vmem_limit_bytes: tuple

    def raised(self, fuse: int) -> bool:
        """Whether a kernel an engine of ``fuse`` runs (the one-step
        kernel, at 2 the two-step kernel too) asks for more than the
        default limit: nothing has shown yet that it compiles."""
        return max(self.vmem_limit_bytes[:fuse]) > _VMEM_DEFAULT

    def compiler_params(self, steps: int):
        """What the kernel's ``pallas_call`` is given: nothing where the
        default limit is enough, so those programs stay as they were."""
        limit = self.vmem_limit_bytes[steps - 1]
        return (pltpu.CompilerParams(vmem_limit_bytes=limit)
                if limit > _VMEM_DEFAULT else None)


def band_plan(model: Model, ny: int, nx: int, ext_halo: bool = False,
              rows_cap: Optional[int] = None) -> Optional[BandPlan]:
    """The plan of the band kernels for ``ny`` physical rows of ``nx``
    nodes, or None where no band fits the raised ceiling.

    By cost, the bytes a step moves: a band of ``rows`` rows reads
    ``rows + 16`` (its two 8-row halo blocks) and writes ``rows``, over
    the padded height.  So each kernel takes the tallest band that
    divides the height and fits its ceiling by :func:`band_vmem`, and a
    height that is no multiple of 8 (the f32 sublane tile the DMA
    offsets need) is padded with ghost rows to the height that moves
    the least: the reference's karman.xml (1024 x 100) gets 20, three
    bands of 40.  The first two ghost rows mirror physical rows 0, 1
    and the last two rows ny-2, ny-1, refreshed before every kernel
    call, so the kernel's wrap over the padded height is the EXACT
    periodic pull of the physical height (reach <= 2 for the two-step
    kernel); ghost rows between them (pad > 4) are never read by a
    physical row: static Wall flags, evolving freely.

    ``ext_halo``: one shard of a y-split lattice, taken as it is (no
    ghost rows).  ``rows_cap``: the rung under a plan that did not
    compile (``Lattice._build_fast``)."""
    def at(ny_pad):
        one = _rows(model, ny_pad, nx, 1, rows_cap)
        two = _rows(model, ny_pad, nx, 2, rows_cap)
        return one and two and BandPlan(
            ny_pad - ny, (one[0], two[0]),
            (band_vmem(model, one[0], nx, 1), band_vmem(model, two[0], nx, 2)),
            (one[1], two[1]))

    if ny % 8 == 0 or ext_halo:
        return at(ny)
    best, best_score = None, None
    for ny_pad in range((ny + 4 + 7) // 8 * 8, 2 * ny + 64, 8):
        plan = at(ny_pad)
        if plan:
            rows = plan.band_rows[1]
            score = ny_pad * (1.0 + (rows + 2.0 * _HALO) / rows)
            if best_score is None or score < best_score:
                best, best_score = plan, score
        if ny_pad >= ny + 64 and best is not None:
            break   # diminishing returns; keep the search bounded
    return best


# family models whose collision the kernel implements via per-model
# branches (same pattern as ops/pallas_d3q.py); d2q9 itself keeps its
# hand-tuned MRT path with the BC coupling planes
_FAMILY_2D = ("d2q9_SRT", "d2q9_les", "d2q9_inc", "d2q9_cumulant",
              "d2q9_new")


def covers(model: Model, shape, dtype) -> bool:
    """Whether the fused kernels implement this model on a lattice of
    this kind, whatever its size: ``d2q9`` plus the pure-f family models
    whose collisions the kernel implements as dedicated branches
    (``_FAMILY_2D`` — including d2q9_new's raw-moment/LES/entropic
    collision, which shares models.d2q9_new.collision_core with the XLA
    path), two dimensions, f32."""
    if model.name == "d2q9":
        pass
    elif model.name in _FAMILY_2D and model.n_storage == 9:
        pass
    else:
        return False
    if len(shape) != 2 or dtype != jnp.float32:
        return False
    ny, nx = shape
    if ny < 8:
        return False
    if jax.default_backend() == "tpu" and nx % 128:
        return False  # x is the lane dimension; keep it tile-aligned
    return True


def supports(model: Model, shape, dtype) -> bool:
    """Whether the fused kernels can run this configuration: a model and
    a lattice they cover (:func:`covers`) of a shape that has a plan
    (:func:`band_plan`)."""
    return (covers(model, shape, dtype)
            and band_plan(model, *(int(s) for s in shape)) is not None)


def why_no_plan(model: Model, shape) -> str:
    """What :func:`band_plan` found too large, for the ``fused_rejected``
    event of a shape it refuses."""
    nx = int(shape[1])
    return (f"vmem: a band of 8 rows of {nx} nodes needs "
            f"{band_vmem(model, 8, nx, 1)} B (one step) and "
            f"{band_vmem(model, 8, nx, 2)} B (two steps) of "
            f"_VMEM_RAISED={_VMEM_RAISED}")


def _sparse_matvec(mat: np.ndarray, planes: list) -> list:
    """y = mat @ planes, unrolled over the (static, mostly-zero) matrix.
    ``planes`` entries may be None (= identically-zero plane, skipped)."""
    out = []
    for row in mat:
        acc = None
        for c, p in zip(row, planes):
            c = float(c)
            if c == 0.0 or p is None:
                continue
            t = p if c == 1.0 else (-p if c == -1.0 else c * p)
            acc = t if acc is None else acc + t
        out.append(acc if acc is not None else jnp.zeros_like(
            next(p for p in planes if p is not None)))
    return out


def zonal_planes(model: Model, params, zones, dtype):
    """Per-node (velocity, density) planes from the zonal tables — the
    kernels' static per-call inputs, built from selects
    (:func:`fusion.zone_plane`), never by indexing the table with the
    zone ids.  Models without a Density setting (d2q9_new) parameterize
    the boundary density via zonal Pressure, rho = 1 + 3 p."""
    si = model.setting_index

    def plane(name):
        return fusion.zone_plane(params.zone_table[si[name]].astype(dtype),
                                 zones)
    vel = plane("Velocity")
    den = plane("Density") if "Density" in si \
        else 1.0 + 3.0 * plane("Pressure")
    return vel, den


def series_rows(model: Model, series_map: tuple) -> Optional[tuple]:
    """The rows of a ``<Control>`` table (``SimParams.series_map``) as
    the band kernels apply them: ``(plane, zone)`` of each row in the
    table's order, ``plane`` the one of :func:`zonal_planes` its setting
    fills (``"vel"`` or ``"den"``).  None where a row is of a setting
    the kernels read from no plane: the band engine cannot take that
    series.  (No model of the family has such a zonal setting today, and
    none reads a ``_DT`` plane: ``NodeCtx.setting_dt`` has no caller in
    ``models/``.)"""
    names = {i: n for n, i in model.setting_index.items()}
    planes = {"Velocity": "vel",
              "Density" if "Density" in model.setting_index
              else "Pressure": "den"}
    rows = [None] * len(series_map)
    for si, z, r in series_map:
        if names[si] not in planes:
            return None
        rows[r] = (planes[names[si]], int(z))
    return tuple(rows)


def resident_vmem_bytes(model: Model, ny: int, nx: int) -> int:
    """What a resident call holds on-chip: the input block, the out block
    (doubles as the second ping-pong buffer) and one scratch stack, and
    the static planes; per-chunk temporaries live in the scoped budget
    like the band kernels'."""
    return (3 * model.n_storage + _AUX_PLANES) * ny * nx * 4


def supports_resident(model: Model, shape, dtype) -> bool:
    """Whether the VMEM-resident multi-step kernel can run this
    configuration: the whole lattice (two ping-pong stacks + statics)
    must fit the on-chip budget.  Small-ny domains like the reference's
    karman.xml (1024x100) qualify — the band kernels there pay 16 halo
    rows of DMA per band, while the resident kernel streams the state
    from HBM once per FUSE_R steps."""
    if not supports(model, shape, dtype):
        return False
    ny, nx = (int(s) for s in shape)
    return resident_vmem_bytes(model, ny, nx) <= 15 * 1024 * 1024


_RESIDENT_FUSE = 8   # lattice steps per kernel invocation (MUST be even:
#                      the in-kernel ping-pong ends in the out block)


def make_resident_iterate(model: Model, shape, dtype=jnp.float32,
                          interpret: Optional[bool] = None,
                          present: Optional[set] = None):
    """VMEM-resident engine for small domains: ONE kernel invocation runs
    ``_RESIDENT_FUSE`` lattice steps on the whole lattice held in VMEM
    (ping-pong stacks), so HBM traffic per step drops to (1R+1W)/FUSE_R
    and the periodic wrap is exact row arithmetic — no ghost padding, no
    halo DMA, any ny.  This is the deep temporal fusion the band kernels
    cannot do (their VMEM only holds a band); the reference has no
    analogue (its GPU has no software-managed on-chip tier).

    A call of ``n`` steps dispatches up to two programs: ``n // 8``
    resident calls in one ``lax.scan`` (two calls a loop body,
    ``engine.PAIR``: no copy of the carry), and, where 8 does not divide
    ``n``, the ``n % 8`` steps left over on a second engine, the
    single-step band kernel of :func:`make_pallas_iterate` with its
    ghost rows (an XLA pad before its calls and a slice after them).
    The Lattice hybrid hands this engine ``niter - 1`` steps (the last,
    which produces the globals, runs on the Lattice's tail engine: the
    generic band kernel's one-step flavour, or XLA), so a handler interval
    that is a multiple of 8 (100, 500, 1000) takes the second engine
    for 3 or 7 steps in every call: three programs a segment.  With
    telemetry on, dispatch says what the call issued on the open span
    (``account``).

    Same NoGlobals contract as the band kernels; unlike them it takes
    no ``<Control>`` series (eight steps on-chip a call): dispatch leaves
    it out of the chain under one (``Lattice._band_chain``)."""
    if not supports_resident(model, shape, dtype):
        raise ValueError(f"resident kernel unsupported: {model.name} "
                         f"{shape}")
    ny, nx = (int(s) for s in shape)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # borrow the band builder's per-model physics closure (_lbm_step):
    # one source of in-kernel physics for both engines
    step_ctx = _make_step_ctx(model, present)
    _lbm_step, bc_idx, n_storage = (step_ctx["step"], step_ctx["bc_idx"],
                                    model.n_storage)
    # row chunks bound the per-chunk temporaries like the band kernels'
    # fused bands do
    chunk = ny
    while chunk > 56:
        chunk = (chunk + 1) // 2
    bounds = list(range(0, ny, chunk)) + [ny]

    def _circ_rows(ref_or_val, k, lo, hi):
        """Rows [lo, hi) of plane ``k`` with periodic wrap (static
        indices; at most one end wraps for multi-chunk layouts)."""
        src = ref_or_val
        if lo >= 0 and hi <= ny:
            return src[k, lo:hi, :]
        parts = []
        if lo < 0:
            parts.append(src[k, ny + lo:ny, :])
            lo = 0
        mid_hi = min(hi, ny)
        parts.append(src[k, lo:mid_hi, :])
        if hi > ny:
            parts.append(src[k, 0:hi - ny, :])
        return jnp.concatenate(parts, axis=0)

    def kernel(sett, f_ref, flags_ref, vel_ref, den_ref, out_ref,
               bufa):
        flags = flags_ref[:]
        vel = vel_ref[:]
        den = den_ref[:]

        def one_step(src, dst):
            """src -> dst (refs); BC planes copied through."""
            for c0, c1 in zip(bounds[:-1], bounds[1:]):
                pulled = []
                for k in range(9):
                    dx, dy = int(E_[k, 0]), int(E_[k, 1])
                    ext = _circ_rows(src, k, c0 - dy, c1 - dy)
                    pulled.append(pltpu.roll(ext, dx % nx, axis=1)
                                  if dx else ext)
                f = jnp.stack(pulled)
                bc0 = src[bc_idx[0], c0:c1, :] if bc_idx else 0.0
                bc1 = src[bc_idx[1], c0:c1, :] if bc_idx else 0.0
                fnew = _lbm_step(f, flags[c0:c1], vel[c0:c1], den[c0:c1],
                                 bc0, bc1, sett)
                for k in range(9):
                    dst[k, c0:c1, :] = fnew[k]
            for k in range(9, n_storage):
                dst[k] = src[k]

        # ping-pong between the scratch stack and the OUT block (saves a
        # whole-lattice buffer); _RESIDENT_FUSE is even, so the final
        # step lands in out_ref
        one_step(f_ref, bufa)
        src, dst = bufa, out_ref
        for _ in range(_RESIDENT_FUSE - 1):
            one_step(src, dst)
            src, dst = dst, src

    # velocity set for the pull slices (the registry's streaming vectors
    # ARE the model's E for the 9 f planes)
    E_ = model.ei[:9, :2]

    call = pl.pallas_call(
        lbm.mosaic_body(kernel, interpret),
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_storage, ny, nx), dtype),
        scratch_shapes=[
            pltpu.VMEM((n_storage, ny, nx), dtype),
        ],
        interpret=interpret,
        name=f"d2q9_resident_fuse{_RESIDENT_FUSE}",
    )

    zshift = model.zone_shift

    def split(niter: int) -> tuple:
        """``niter`` steps as the resident calls and the steps left
        over, which cannot be more resident calls (the fuse is baked
        in): ``iterate`` runs them through the single-step band kernel
        of make_pallas_iterate.  The Lattice hybrid hands over niter - 1
        steps, so that happens in every call whose length is a multiple
        of 8."""
        return divmod(niter, _RESIDENT_FUSE)

    @partial(jax.jit, static_argnames=("niter",), donate_argnums=0)
    def _iterate_jit(state: LatticeState, params: SimParams, niter: int
                     ) -> LatticeState:
        flags_i32 = state.flags.astype(jnp.int32)
        zones = flags_i32 >> zshift
        vel, den = zonal_planes(model, params, zones, dtype)
        sett = params.settings.astype(dtype)

        def body(fields, _):
            return call(sett, fields, flags_i32, vel, den), None

        calls, _ = split(niter)
        return LatticeState(
            fields=scan_calls(body, state.fields, calls, True),
            flags=state.flags,
            globals_=jnp.zeros_like(state.globals_),
            iteration=state.iteration + calls * _RESIDENT_FUSE,
        )

    band = make_pallas_iterate(model, shape, dtype, interpret=interpret,
                               fuse=1, present=present, paired=False)

    def account(niter: int, has_series: bool = False) -> dict:
        """What one ``iterate(niter)`` issues, reckoned host-side from
        ``iterate``'s own split: the resident calls, and the steps left
        over with the shape of the band calls that run them (the band
        engine's loop of them is single)."""
        calls, rest = split(int(niter))
        return dict(
            band.impl["band_shape"], kernel_calls=calls + rest,
            paired_calls=paired_calls(calls),
            resident_calls=calls, resident_steps=_RESIDENT_FUSE,
            remainder_steps=rest, aux_planes=_AUX_PLANES,
            remainder_aux_planes=_AUX_PLANES, chunk_rows=chunk,
            vmem_bytes=resident_vmem_bytes(model, ny, nx))

    def iterate(state: LatticeState, params: SimParams, niter: int
                ) -> LatticeState:
        if params.time_series is not None:
            raise ValueError(
                "pallas iterate does not support Control time series; "
                "use the XLA path for time-dependent zonal settings")
        rest = split(niter)[1]
        state = _iterate_jit(state, params, niter - rest)
        if rest:
            state = band(state, params, rest)
        return state

    return Engine(iterate, account)


def _shard_halo_rows(base, rows: int, ny: int) -> tuple:
    """First rows of the two 8-row halo blocks of the band of ``rows``
    rows at ``base`` in a shard of ``ny`` rows taken as it is: the 8 rows
    above and below the band, held inside the shard where the band is its
    first or its last (there the neighbour's block is read instead,
    :func:`_start_sharded`)."""
    return (pl.multiple_of(
                jnp.maximum(base - jnp.int32(8), jnp.int32(0)), 8),
            pl.multiple_of(
                jnp.minimum(base + jnp.int32(rows), jnp.int32(ny - 8)), 8))


def _start_sharded(own, theirs, first, last) -> None:
    """Start a band's three field DMAs on one shard of a y-split lattice:
    ``own`` (band, top block, bottom block) from the shard's own rows,
    but the top block of the shard's ``first`` band and the bottom block
    of its ``last`` from the neighbours' exchanged blocks, ``theirs``
    (top, bottom): to the same destination under the same semaphore, so
    the wait of ``own`` serves either source."""
    own[0].start()
    for mine, other, at_edge in ((own[1], theirs[0], first),
                                 (own[2], theirs[1], last)):
        pl.when(at_edge)(other.start)
        pl.when(jnp.logical_not(at_edge))(mine.start)


def _make_step_ctx(model: Model, present=None):
    """Per-model physics closures (the band builder's _lbm_step + BC
    plane indices), extracted for the resident kernel to share — one
    source of in-kernel physics for both engines."""
    return make_pallas_iterate(model, (8, 256), jnp.float32,
                               interpret=True, fuse=1, present=present,
                               _want_step_ctx=True)


def make_pallas_iterate(model: Model, shape, dtype=jnp.float32,
                        interpret: Optional[bool] = None,
                        fuse: int = 1,
                        present: Optional[set] = None,
                        ext_halo: bool = False,
                        _want_step_ctx: bool = False,
                        points: Optional[np.ndarray] = None,
                        paired: bool = True,
                        rows_cap: Optional[int] = None):
    """Build ``iterate(state, params, niter) -> state`` running the fused
    Pallas collide-stream kernel.  Caller must check :func:`supports` first.

    The kernel calls loop two a ``lax.scan`` body (``engine.scan_calls``),
    so XLA copies no carry before a call, in **one program that donates
    its state and ends in the one-step kernel**.  At 11 x 1024 x 1024 two
    state buffers and the aux stack (105 MB) fill the compiler's fast
    memory (``S(1)``) to its edge, and the ``kernel2`` that waited for
    its input copies took 215.7 us a call on a state in ``S(1)``, 309.3
    on one in HBM (chip, PR 48; it prefetches its band since PR 50, at
    8192 x 8192, both buffers in HBM, 15.4 ms a call for 26.0; on a
    state in ``S(1)`` the copies had cost little and it reads 1 % more:
    the placement below is still what these programs are built for).
    Both buffers stay in ``S(1)`` only where the one-step
    kernel follows the loop in the same donated program: it reads the
    loop's result there and writes the caller's buffer in HBM, which
    costs it nothing.  The loop alone (``parallel/halo.py``'s cure on a
    mesh), the same program not donated, or an even length all in
    fuse-2 calls keeps one buffer in HBM: 66.4 ms an ``iterate(499)``
    for 55.3, where one call a body with its copy of the carry took
    58.2 (``tests/test_mosaic_compile.py`` pins the placement).
    ``paired=False`` keeps one call a body: the resident engine's
    remainder of at most seven steps, not changed with this loop.

    ``points`` ((P, 2) in array index order; ``fuse`` 1) builds the
    sampled flavour for a ``<Sample>`` run: every step is one call of
    the single-step kernel and the engine returns ``(state, taps)``, the
    stored planes at the points after every step, (niter, planes, P).

    ``fuse=2`` runs TWO lattice steps per kernel band pass (halving the
    HBM traffic per step); the call ends in the single-step kernel: the
    odd step, or an even length's last two (``split``).

    Under a ``<Control>`` series (``params.time_series``) the same
    program shape runs the kernels' series flavour (``series_flavour``):
    each step reads the value of its own iteration, the two-step kernel
    two values a call.  The flavour is chosen where the program is
    traced, so one engine serves a lattice before and after a series is
    attached, and without one the programs are what they were.

    ``present`` restricts which boundary node types are materialized
    (every case is full-band compute-then-select, so skipping absent
    types is pure win); parity holds whenever it is a superset of the
    types actually painted — :func:`present_types` computes that set.

    The bands, the ghost rows and the scoped-VMEM limit of each kernel
    are :func:`band_plan`'s; ``rows_cap`` bounds the bands' rows (the
    rung dispatch builds under a plan that did not compile).

    ``ext_halo=True`` builds the SHARDED building block instead: the
    domain is one device's block of a y-sharded lattice, and the kernels
    read their halos from the neighbours' exchanged rows instead of
    wrapping periodically.  Returns ``(call1, call2, by, by2)`` raw band
    calls for :mod:`tclb_tpu.parallel.halo` to compose with ``ppermute``
    (the reference's equivalent composition is
    RunBorder/MPIStream/RunInterior, src/Lattice.cu.Rt:424-456).
    Both take the shard's field stack as it is, (ns, ny, nx), and the
    lower and the upper neighbour's 8 rows as operands of their own,
    (ns, 8, nx) each (:func:`_start_sharded`: nothing of the shard's size
    is built round them): ``call2(sett, f, lo, hi, aux)``, whose aux
    stack, exchanged once an ``iterate``, carries the 8 rows at each end,
    (3, ny+16, nx), and ``call1(sett, f, lo, hi, flags, vel, den)``."""
    from tclb_tpu.models import d2q9 as mod
    from tclb_tpu.models import d2q9_inc as inc_mod
    from tclb_tpu.models import d2q9_new as new_mod
    from tclb_tpu.models import family
    from tclb_tpu.ops import cumulant

    if not covers(model, shape, dtype):
        raise ValueError(f"pallas path unsupported for {model.name} {shape}")
    if fuse not in (1, 2):
        raise ValueError(f"fuse={fuse}: only 1 (single-step) and 2 "
                         "(temporally-fused pair) kernels exist")
    if points is not None and fuse != 1:
        raise ValueError("the sampled flavour reads the state after "
                         "every step: fuse=1 only")
    ny_phys, nx = (int(s) for s in shape)
    if ext_halo and ny_phys % 8:
        raise ValueError("ext_halo blocks need ny % 8 == 0")
    plan = band_plan(model, ny_phys, nx, ext_halo, rows_cap)
    if plan is None:
        raise ValueError(f"no valid band height for shape {shape}")
    pad, (by, by2) = plan.pad_rows, plan.band_rows
    ny = ny_phys + pad
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    is_d2q9 = model.name == "d2q9"
    if is_d2q9:
        E, W, OPP, M = mod.E, mod.W, mod.OPP, mod.M
        norm = (M * M).sum(axis=1)
        Minv = (M / norm[:, None]).T
        bc_idx = list(model.groups["BC"])
    else:
        E = model.ei[:9, :2]
        W = lbm.weights(E)
        OPP = lbm.opposite(E)
        bc_idx = None
    n_storage = model.n_storage
    f_idx = list(model.groups["f"])
    assert f_idx == list(range(9)), "kernel assumes f planes lead the stack"

    si = model.setting_index
    i_gx = si.get("GravitationX")
    i_gy = si.get("GravitationY")
    coll_mask = int(model.group_masks["COLLISION"])
    nt = {n: (int(t.mask), int(t.value)) for n, t in model.node_types.items()}
    present = set(nt) if present is None else set(present)

    def _is(flags, name):
        mask, val = nt[name]
        return (flags & jnp.int32(mask)) == jnp.int32(val)

    def _apply_family_boundaries(f, flags, vel, den):
        """Mask-dispatch family.boundary_cases, skipping absent types —
        the identical closures the XLA path applies (same contract as
        ops/pallas_d3q.py)."""
        cases = family.boundary_cases(model, E, W, OPP, vel, den)
        return family.dispatch_boundary_cases(
            cases, f, lambda n: _is(flags, n), present)

    def _zouhe_boundaries(f, flags, vel, den):
        """d2q9-style explicit boundary list (models/d2q9.run order),
        shared by the d2q9 and d2q9_new branches; absent node types
        (``present``) are skipped entirely — each case is a full-band
        compute, so this mirrors the reference's compile-time
        specialization on the model's boundary set."""
        def apply(mask, new, cur):
            return jnp.where(mask[None], new, cur)

        def mask_of(*names):
            names = [n for n in names if n in present and n in nt]
            if not names:
                return None
            m = _is(flags, names[0])
            for n in names[1:]:
                m = m | _is(flags, n)
            return m

        ws = mask_of("Wall", "Solid")
        if ws is not None:
            f = apply(ws, jnp.stack([f[int(OPP[k])] for k in range(9)]), f)
        for name, plane, kind, side in (
                ("EVelocity", vel, "velocity", "E"),
                ("WPressure", den, "pressure", "W"),
                ("WVelocity", vel, "velocity", "W"),
                ("EPressure", den, "pressure", "E")):
            if name in present and name in nt:
                f = apply(_is(flags, name),
                          mod._zou_he_x(f, plane, kind, side), f)
        if "TopSymmetry" in present and "TopSymmetry" in nt:
            f = apply(_is(flags, "TopSymmetry"),
                      mod._symmetry(f, top=True), f)
        if "BottomSymmetry" in present and "BottomSymmetry" in nt:
            f = apply(_is(flags, "BottomSymmetry"),
                      mod._symmetry(f, top=False), f)
        return f

    def _lbm_step_d2q9(f, flags, vel, den, bc0, bc1, sett):
        """One collide step on an arbitrary row band: d2q9-style boundary
        dispatch, then the MRT collision (mirrors
        models.d2q9._collision_mrt, sans globals)."""
        i_s3, i_s4 = si["S3"], si["S4"]
        i_s56, i_s78 = si["S56"], si["S78"]
        f = _zouhe_boundaries(f, flags, vel, den)

        rho = sum(f[k] for k in range(9))
        ux = sum(float(E[k, 0]) * f[k] for k in range(9) if E[k, 0]) / rho
        uy = sum(float(E[k, 1]) * f[k] for k in range(9) if E[k, 1]) / rho
        s3, s4 = sett[i_s3], sett[i_s4]
        s56, s78 = sett[i_s56], sett[i_s78]
        feq = equilibrium(E, W, rho, (ux, uy))
        fneq = [f[k] - feq[k] for k in range(9)]
        # moment rates: rows 0-2 (density/momentum) relax at rate 0, so
        # their moments need not be computed and their Minv columns drop
        # out — exact, the conserved moments never enter the update
        rates = [s3, s4, s56, s56, s78, s78]
        mn = _sparse_matvec(M[3:], fneq)
        m_neq = [None, None, None] + [m * o for m, o in zip(mn, rates)]
        ux2 = ux + sett[i_gx] + bc0
        uy2 = uy + sett[i_gy] + bc1
        feq2 = equilibrium(E, W, rho, (ux2, uy2))
        # Minv @ (m_neq + M @ feq2) == Minv @ m_neq + feq2 — one matvec
        # saved vs the naive moment-space form (exact algebra, not an
        # approximation)
        relax = _sparse_matvec(Minv, m_neq)
        coll = [r + q for r, q in zip(relax, feq2)]
        mrt = _is(flags, "MRT")
        return jnp.stack([jnp.where(mrt, coll[k], f[k]) for k in range(9)])

    def _lbm_step_family(f, flags, vel, den, bc0, bc1, sett):
        """Family-model collide step: shared boundary dispatch + the
        model's own collision, op-for-op the XLA model code (minus
        globals) — BGK (d2q9_SRT), Smagorinsky (d2q9_les, in-kernel
        unrolled |Pi|), He-Luo incompressible (d2q9_inc), central-moment
        cumulant (d2q9_cumulant via ops/cumulant.py)."""
        if model.name == "d2q9_new":
            # d2q9-style explicit Zou-He list (the model's own run(),
            # models/d2q9_new.py), then the shared raw-moment collision
            # core — one source of physics for both engines
            f = _zouhe_boundaries(f, flags, vel, den)
            fc = new_mod.collision_core(
                f, sett[si["omega"]], sett[si["Smag"]],
                _is(flags, "Smagorinsky"), _is(flags, "Stab"))
            mrt = _is(flags, "MRT")
            return jnp.where(mrt[None], fc, f)
        f = _apply_family_boundaries(f, flags, vel, den)
        coll = (flags & jnp.int32(coll_mask)) != jnp.int32(0)
        gx, gy = sett[i_gx], sett[i_gy]
        if model.name == "d2q9_cumulant":
            F = f.reshape((3, 3) + f.shape[1:])
            Fp, _, _ = cumulant.collide_d2q9(
                F, sett[si["omega"]], sett[si["omega_bulk"]],
                force=(gx, gy))
            fc = Fp.reshape(f.shape)
        elif model.name == "d2q9_inc":
            rho = jnp.sum(f, axis=0)
            ux = sum(float(E[k, 0]) * f[k] for k in range(9)
                     if E[k, 0]) / inc_mod.RHO0
            uy = sum(float(E[k, 1]) * f[k] for k in range(9)
                     if E[k, 1]) / inc_mod.RHO0
            feq = inc_mod._inc_equilibrium(rho, ux, uy)
            fc = f + sett[si["omega"]] * (feq - f)
            fc = fc + (inc_mod._inc_equilibrium(rho, ux + gx, uy + gy)
                       - feq)
        else:   # d2q9_SRT / d2q9_les
            rho = jnp.sum(f, axis=0)
            ux = sum(float(E[k, 0]) * f[k] for k in range(9)
                     if E[k, 0]) / rho
            uy = sum(float(E[k, 1]) * f[k] for k in range(9)
                     if E[k, 1]) / rho
            feq = equilibrium(E, W, rho, (ux, uy))
            if model.name == "d2q9_les":
                om = lbm.smagorinsky_omega_unrolled(
                    E, f, feq, rho, sett[si["omega"]], sett[si["Smag"]])
            else:
                om = sett[si["omega"]]
            fc = f + om * (feq - f)
            fc = fc + (equilibrium(E, W, rho, (ux + gx, uy + gy)) - feq)
        return jnp.where(coll[None], fc, f)

    _lbm_step = _lbm_step_d2q9 if is_d2q9 else _lbm_step_family
    if _want_step_ctx:
        # the resident kernel borrows the per-model physics closure
        return {"step": _lbm_step, "bc_idx": bc_idx}

    def kernel(sett, f_hbm, flags_ref, vel_ref, den_ref, out_ref,
               buf2, sems, halos=None, series=None):
        # One CONTIGUOUS scratch buffer of by+16 rows per slot: the band
        # lands at rows [8, 8+by), its 8-row halo blocks at [0, 8) and
        # [8+by, 16+by) — all three DMA destinations are (8, 128)-tile
        # aligned, and every pull below is a single SLICE of the buffer
        # (rows 7..7+by for y-1, 9..9+by for y+1) instead of the former
        # per-plane concatenate of halo and band pieces (pure VPU copies,
        # round-2 VERDICT Weak #2's named suspect).  Double-slotted: band
        # i+1's DMA is issued before band i's compute, overlapping HBM
        # fetch with VPU work across grid steps (the reference gets the
        # same overlap from its border/interior kernel split + async
        # memcpy streams, src/Lattice.cu.Rt:424-456).  ``halos`` (the
        # sharded flavour, ``sharded``): the neighbours' 8-row blocks.
        # ``series`` (the series flavour, ``band_calls``): the values the
        # <Control> series have at this call's step, ``_with_series``.
        i = pl.program_id(0)
        n = pl.num_programs(0)

        def band_dmas(slot, band):
            base = pl.multiple_of(band * jnp.int32(by), 8)
            if halos is not None:
                top8, bot8 = _shard_halo_rows(base, by, ny)
            else:
                top8 = pl.multiple_of(
                    jax.lax.rem(base - jnp.int32(8) + jnp.int32(ny),
                                jnp.int32(ny)), 8)
                bot8 = pl.multiple_of(
                    jax.lax.rem(base + jnp.int32(by), jnp.int32(ny)), 8)
            return (
                pltpu.make_async_copy(f_hbm.at[:, pl.ds(base, by), :],
                                      buf2.at[slot, :, pl.ds(8, by), :],
                                      sems.at[slot, 0]),
                pltpu.make_async_copy(f_hbm.at[:, pl.ds(top8, 8), :],
                                      buf2.at[slot, :, pl.ds(0, 8), :],
                                      sems.at[slot, 1]),
                pltpu.make_async_copy(f_hbm.at[:, pl.ds(bot8, 8), :],
                                      buf2.at[slot, :, pl.ds(8 + by, 8), :],
                                      sems.at[slot, 2]),
            )

        def halo_dmas(slot):
            return (
                pltpu.make_async_copy(halos[0],
                                      buf2.at[slot, :, pl.ds(0, 8), :],
                                      sems.at[slot, 1]),
                pltpu.make_async_copy(halos[1],
                                      buf2.at[slot, :, pl.ds(8 + by, 8), :],
                                      sems.at[slot, 2]),
            )

        def start_band(slot, band):
            dmas = band_dmas(slot, band)
            if halos is None:
                for d in dmas:
                    d.start()
            else:
                _start_sharded(dmas, halo_dmas(slot), band == 0,
                               band == n - 1)

        slot = jax.lax.rem(i, jnp.int32(_BAND_SLOTS))
        nxt = jax.lax.rem(i + jnp.int32(1), jnp.int32(_BAND_SLOTS))

        @pl.when(i == 0)
        def _():
            start_band(jnp.int32(0), i)

        @pl.when(i + 1 < n)
        def _():
            start_band(nxt, i + jnp.int32(1))

        for d in band_dmas(slot, i):
            d.wait()

        def mid(k):
            return buf2[slot, k, 8:8 + by, :]

        # pull-streaming: f_i(x) <- f_i(x - e_i); halo rows make y +- 1 a
        # plain row-shifted slice, lane-roll covers the periodic x wrap
        # (matches core.lattice.pull_stream)
        pulled = []
        for k in range(9):
            dx, dy = int(E[k, 0]), int(E[k, 1])
            sl = buf2[slot, k, 8 - dy:8 - dy + by, :]
            pulled.append(pltpu.roll(sl, dx % nx, axis=1) if dx else sl)
        f = jnp.stack(pulled)
        bc0 = mid(bc_idx[0]) if bc_idx else 0.0
        bc1 = mid(bc_idx[1]) if bc_idx else 0.0
        flags, vel, den = flags_ref[:], vel_ref[:], den_ref[:]
        if series is not None:
            vel, den = _with_series(flags, vel, den, series, 0)
        fnew = _lbm_step(f, flags, vel, den, bc0, bc1, sett)
        for k in range(9):
            out_ref[k] = fnew[k]
        if bc_idx:
            out_ref[bc_idx[0]] = bc0
            out_ref[bc_idx[1]] = bc1

    def kernel2(sett, f_hbm, aux_hbm, out_ref, buff, bufa, sems,
                halos=None, series=None):
        """Temporally-fused kernel: TWO collide-stream steps per band pass
        (the esoteric-twist-style traffic saving flagged in SURVEY §7's
        hard parts — each density is read/written once per TWO steps).
        Step 1 runs on an extended band of by+2 rows so step 2's pull has
        valid neighbours; the 8-row aligned halo blocks already cover the
        2-row reach.  ``aux_hbm`` stacks (flags-as-f32, Velocity, Density)
        so the statics ride the same contiguous-buffer DMA scheme (flag
        values < 2^16 are exact in f32).  Like kernel, the band+halos land
        in ONE contiguous (by2+16)-row buffer a slot so extended-row
        access is a single slice, not a concatenate, and like kernel it is
        double-slotted: band i+1's six copies are issued before band i's
        arithmetic.  ``halos`` (the sharded flavour,
        :func:`kernel2_sharded`): the neighbours' two 8-row blocks.
        ``series`` (the series flavour, :func:`band_calls`): the values
        the <Control> series have at the call's first step, then those
        at its second (:func:`_with_series`)."""
        i = pl.program_id(0)
        n = pl.num_programs(0)

        def band_dmas(slot, band):
            base = pl.multiple_of(band * jnp.int32(by2), 8)
            if halos is not None:
                # the aux stack's rows are [halo(8) | local ny | halo(8)]:
                # a band lives at base+8, its halos at base and
                # base+8+by2 — no wrap, the exchanged rows ARE the
                # neighbors.  The field stack is the shard as it is
                a_mid8 = pl.multiple_of(base + jnp.int32(8), 8)
                a_top8 = base
                a_bot8 = pl.multiple_of(base + jnp.int32(8 + by2), 8)
                top8, bot8 = _shard_halo_rows(base, by2, ny)
            else:
                top8 = pl.multiple_of(
                    jax.lax.rem(base - jnp.int32(8) + jnp.int32(ny),
                                jnp.int32(ny)), 8)
                bot8 = pl.multiple_of(
                    jax.lax.rem(base + jnp.int32(by2), jnp.int32(ny)), 8)
                a_mid8, a_top8, a_bot8 = base, top8, bot8
            return tuple(
                pltpu.make_async_copy(src.at[:, pl.ds(at, rows), :],
                                      buf.at[slot, :, pl.ds(to, rows), :],
                                      sems.at[slot, j])
                for j, (src, buf, at, to, rows) in enumerate((
                    (f_hbm, buff, base, 8, by2),
                    (f_hbm, buff, top8, 0, 8),
                    (f_hbm, buff, bot8, 8 + by2, 8),
                    (aux_hbm, bufa, a_mid8, 8, by2),
                    (aux_hbm, bufa, a_top8, 0, 8),
                    (aux_hbm, bufa, a_bot8, 8 + by2, 8))))

        def start_band(slot, band):
            dmas = band_dmas(slot, band)
            if halos is None:
                for d in dmas:
                    d.start()
                return
            theirs = (
                pltpu.make_async_copy(
                    halos[0], buff.at[slot, :, pl.ds(0, 8), :],
                    sems.at[slot, 1]),
                pltpu.make_async_copy(
                    halos[1], buff.at[slot, :, pl.ds(8 + by2, 8), :],
                    sems.at[slot, 2]))
            _start_sharded(dmas[:3], theirs, band == 0, band == n - 1)
            for d in dmas[3:]:
                d.start()

        slot = jax.lax.rem(i, jnp.int32(_BAND_SLOTS))
        nxt = jax.lax.rem(i + jnp.int32(1), jnp.int32(_BAND_SLOTS))

        @pl.when(i == 0)
        def _():
            start_band(jnp.int32(0), i)

        @pl.when(i + 1 < n)
        def _():
            start_band(nxt, i + jnp.int32(1))

        for d in band_dmas(slot, i):
            d.wait()

        def ext(buf, k, lo, hi):
            """Rows [lo, hi) of the band-extended plane k (band row 0 is
            buffer row 8) — a single slice of the slot's contiguous
            buffer."""
            return buf[slot, k, 8 + lo:8 + hi, :]

        # ---- step 1 on rows [-1, by+1) ---------------------------------- #
        pulled = []
        for k in range(9):
            dx, dy = int(E[k, 0]), int(E[k, 1])
            sl = ext(buff, k, -1 - dy, by2 + 1 - dy)
            pulled.append(pltpu.roll(sl, dx % nx, axis=1) if dx else sl)
        f = jnp.stack(pulled)
        flags_e = ext(bufa, 0, -1, by2 + 1).astype(jnp.int32)
        vel_e = ext(bufa, 1, -1, by2 + 1)
        den_e = ext(bufa, 2, -1, by2 + 1)
        bc0_e = ext(buff, bc_idx[0], -1, by2 + 1) if bc_idx else 0.0
        bc1_e = ext(buff, bc_idx[1], -1, by2 + 1) if bc_idx else 0.0
        vel_1, den_1 = vel_e, den_e
        if series is not None:
            vel_1, den_1 = _with_series(flags_e, vel_e, den_e, series, 0)
        f1 = _lbm_step(f, flags_e, vel_1, den_1, bc0_e, bc1_e, sett)

        # ---- step 2 on rows [0, by) ------------------------------------- #
        pulled = []
        for k in range(9):
            dx, dy = int(E[k, 0]), int(E[k, 1])
            sl = f1[k, 1 - dy:1 - dy + by2, :]
            pulled.append(pltpu.roll(sl, dx % nx, axis=1) if dx else sl)
        f = jnp.stack(pulled)
        flags_2, vel_2, den_2 = (flags_e[1:by2 + 1], vel_e[1:by2 + 1],
                                 den_e[1:by2 + 1])
        if series is not None:
            # the second step reads the values of ITS iteration, over
            # the planes of the call, not the first step's
            vel_2, den_2 = _with_series(flags_2, vel_2, den_2, series, 1)
        f2 = _lbm_step(f, flags_2, vel_2, den_2,
                       bc0_e[1:by2 + 1] if bc_idx else 0.0,
                       bc1_e[1:by2 + 1] if bc_idx else 0.0,
                       sett)

        for k in range(9):
            out_ref[k] = f2[k]
        if bc_idx:
            out_ref[bc_idx[0]] = ext(buff, bc_idx[0], 0, by2)
            out_ref[bc_idx[1]] = ext(buff, bc_idx[1], 0, by2)

    def kernel2_sharded(sett, f_hbm, lo_hbm, hi_hbm, aux_hbm, out_ref,
                        buff, bufa, sems):
        """``kernel2`` on one shard of a y-split lattice (``ext_halo``):
        ``f_hbm`` is the shard's field stack as it is, (ns, ny, nx), and
        ``lo_hbm`` / ``hi_hbm`` the exchanged last / first 8 rows of the
        lower / upper neighbour, (ns, 8, nx) each."""
        kernel2(sett, f_hbm, aux_hbm, out_ref, buff, bufa, sems,
                halos=(lo_hbm, hi_hbm))

    def kernel_sharded(sett, f_hbm, lo_hbm, hi_hbm, flags_ref, vel_ref,
                       den_ref, out_ref, buf2, sems):
        """``kernel`` on one shard, as :func:`kernel2_sharded`."""
        kernel(sett, f_hbm, flags_ref, vel_ref, den_ref, out_ref, buf2,
               sems, halos=(lo_hbm, hi_hbm))

    zshift = model.zone_shift

    def _with_series(flags, vel, den, series, k: int):
        """``vel`` and ``den`` as step ``k`` of a series call reads them:
        over the call's planes, by a select on the zone ids the flags
        hold, the value each series row has at that step's iteration
        (``series``: the SMEM operand, a call's steps row after row, and
        :func:`series_rows`'s ``(plane, zone)`` of each row).  What
        ``core.lattice.series_overrides`` does to the XLA step's
        setting."""
        sv, rows = series
        zones = flags >> jnp.int32(zshift)
        for r, (plane, z) in enumerate(rows):
            hit = zones == jnp.int32(z)
            v = sv[k * len(rows) + r]
            if plane == "vel":
                vel = jnp.where(hit, v, vel)
            else:
                den = jnp.where(hit, v, den)
        return vel, den

    def band_calls(rows: Optional[tuple] = None) -> tuple:
        """The one-step and the two-step ``pallas_call``.  ``rows``
        (:func:`series_rows`): the series flavour, which takes the
        series' values at the call's steps as a second SMEM operand
        behind ``sett``, ``(steps * len(rows),)``; without, the kernels
        and operands a lattice with no series has always had."""
        if rows:
            def one(sett, sv, *refs):
                kernel(sett, *refs, series=(sv, rows))

            def two(sett, sv, *refs):
                kernel2(sett, *refs, series=(sv, rows))
        else:
            one, two = ((kernel_sharded, kernel2_sharded) if ext_halo
                        else (kernel, kernel2))
        smem = [pl.BlockSpec(memory_space=pltpu.SMEM)] * (2 if rows else 1)
        flavour = "_series" if rows else ""
        # the field stack and, in the sharded flavour, the neighbours'
        # blocks
        f_specs = [pl.BlockSpec(memory_space=pl.ANY)] * (
            3 if ext_halo else 1)

        # both kernels start band i + 1's copies at grid step i and wait
        # for them at step i + 1: the grid is walked in order, on one
        # core (the default, "arbitrary", of a grid dimension; a v5e has
        # one core)
        call2 = pl.pallas_call(
            lbm.mosaic_body(two, interpret),
            grid=(ny // by2,),
            in_specs=smem + f_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((n_storage, by2, nx),
                                   lambda i: (0, i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((n_storage, ny, nx), dtype),
            scratch_shapes=[
                pltpu.VMEM((_BAND_SLOTS, n_storage, by2 + 16, nx), dtype),
                pltpu.VMEM((_BAND_SLOTS, _AUX_PLANES, by2 + 16, nx), dtype),
                pltpu.SemaphoreType.DMA((_BAND_SLOTS, 6)),
            ],
            interpret=interpret,
            compiler_params=plan.compiler_params(2),
            name="d2q9_band_fuse2" + flavour,
        )

        call = pl.pallas_call(
            lbm.mosaic_body(one, interpret),
            grid=(ny // by,),
            in_specs=smem + f_specs + [
                pl.BlockSpec((by, nx), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((by, nx), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((by, nx), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((n_storage, by, nx), lambda i: (0, i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((n_storage, ny, nx), dtype),
            scratch_shapes=[
                pltpu.VMEM((_BAND_SLOTS, n_storage, by + 16, nx), dtype),
                pltpu.SemaphoreType.DMA((_BAND_SLOTS, 3)),
            ],
            interpret=interpret,
            compiler_params=plan.compiler_params(1),
            name="d2q9_band_fuse1" + flavour,
        )
        return call, call2

    call, call2 = band_calls()

    if ext_halo:
        return call, call2, by, by2

    def split(niter: int) -> tuple:
        """``niter`` steps as the calls of the two-step kernel and the
        one-step calls after them.  At ``fuse`` 2 a call ends in the
        one-step kernel: once for an odd length, twice for an even one
        (one two-step call less), which is what keeps the loop's state
        in the compiler's fast memory (the builder's docstring; the same
        steps to the last bit, 55.5 ms an ``iterate(500)`` at 1024 x
        1024 where 250 two-step calls took 66.3 paired and 69.7 single:
        chip, PR 48)."""
        twos = max(niter - 1, 0) // 2 if fuse == 2 else 0
        return twos, niter - 2 * twos

    series_built: dict = {}

    def series_flavour(params) -> tuple:
        """``(call, call2, values)`` of a call under a ``<Control>``
        series: the series flavour of the kernels (:func:`band_calls`)
        and ``values(it, steps)``, the SMEM operand of a call of
        ``steps`` steps that starts at iteration ``it``: its steps'
        columns of the device's ``(n_series, T)`` table, each sliced at
        its own iteration mod ``T`` (a call's two steps may lie on
        either side of the wrap).  The values go in as scalars; ``vel``
        and ``den`` stay the planes of the whole call and no plane is
        made for a step."""
        rows = series_rows(model, params.series_map)
        if rows not in series_built:
            series_built[rows] = band_calls(rows)
        table = params.time_series.astype(dtype)
        horizon = table.shape[1]
        # zonal_planes' own rule where the model has no Density: the
        # density plane is 1 + 3 p
        of_pressure = np.array([plane == "den" for plane, _ in rows]) \
            & ("Density" not in model.setting_index)

        def values(it, steps):
            v = jnp.concatenate([
                jax.lax.dynamic_slice_in_dim(
                    table, jnp.mod(it + k, horizon), 1, axis=1)[:, 0]
                for k in range(steps)])
            if of_pressure.any():
                v = jnp.where(np.tile(of_pressure, steps),
                              1.0 + 3.0 * v, v)
            return v

        return (*series_built[rows], values)

    # the sampled flavour does not donate its state: donated, the loop's
    # carry is the caller's HBM buffer and every trip of two calls ends
    # in a copy of the whole state out of the compiler's fast memory
    # (copy-done, 70 us a trip at 11 x 1024 x 1024: a quarter of the
    # device's time, chip, PR 46); not donated, the state is copied in
    # once before the loop and out once after it
    @partial(jax.jit, static_argnames=("niter",),
             donate_argnums=() if points is not None else 0)
    def _iterate_jit(state: LatticeState, params: SimParams, niter: int
                     ) -> LatticeState:
        flags_i32 = state.flags.astype(jnp.int32)
        fields = state.fields
        if pad:
            # ghost layout: [mirror 0, mirror 1, walls..., mirror ny-2,
            # mirror ny-1]; middle ghosts are Wall nodes (bounce-back in
            # place — unconditionally stable, and never read by physical
            # rows)
            init_src = jnp.asarray(np.array(
                [0, 1] + [0] * (pad - 4) + [ny_phys - 2, ny_phys - 1]))
            gflags = flags_i32[init_src]
            if pad > 4:
                wall = jnp.int32(model.flag_for("Wall"))
                gflags = gflags.at[2:pad - 2].set(wall)
            flags_i32 = jnp.concatenate([flags_i32, gflags], axis=0)
            fields = jnp.concatenate([fields, fields[:, init_src, :]],
                                     axis=1)
        zones = flags_i32 >> zshift
        vel, den = zonal_planes(model, params, zones, dtype)
        sett = params.settings.astype(dtype)

        def refresh(fields):
            if not pad:
                return fields
            with jax.named_scope("d2q9_ghost_refresh"):
                f = fields.at[:, ny_phys:ny_phys + 2, :].set(
                    fields[:, 0:2, :])
                return f.at[:, ny - 2:, :].set(
                    fields[:, ny_phys - 2:ny_phys, :])

        def body(fields, _):
            return call(sett, refresh(fields), flags_i32, vel, den), None

        def body_t(fields, _):
            out = body(fields, None)[0]
            return out, tap(out, points)

        taps = None
        if points is not None:
            # every step its own call, and what it left at the points the
            # scan's ys; two calls a loop body, so the carry is not
            # copied before a call (ops/engine.py)
            fields, taps = scan_calls(body_t, fields, niter, True,
                                      taps=True)
        else:
            # two calls a loop body, so no copy of the carry before a
            # call (ops/engine.py): 10.6 us a call of 215.7 at 1024 x
            # 1024, 61.4 where the carry lay in HBM (chip, PR 48).
            # Under a <Control> series the same loops run the kernels'
            # series flavour and carry the iteration beside the state:
            # before a call its steps' values are sliced from the table
            values = None
            c1, c2, carry = call, call2, fields
            if params.time_series is not None:
                c1, c2, values = series_flavour(params)
                carry = (fields, jnp.asarray(state.iteration, jnp.int32))

            def calls_of(c, steps, *operands):
                def one_call(carry, _):
                    if values is None:
                        return c(sett, refresh(carry), *operands), None
                    fields, it = carry
                    return (c(sett, values(it, steps), refresh(fields),
                              *operands), it + steps), None
                return one_call

            twos, ones = split(niter)
            if twos:
                aux = jnp.stack([flags_i32.astype(dtype), vel, den])
                carry = scan_calls(calls_of(c2, 2, aux), carry, twos,
                                   paired)
            carry = scan_calls(calls_of(c1, 1, flags_i32, vel, den), carry,
                               ones, paired)
            fields = carry if values is None else carry[0]
        if pad:
            fields = fields[:, :ny_phys, :]
        out = LatticeState(
            fields=fields,
            flags=state.flags,
            globals_=jnp.zeros_like(state.globals_),
            iteration=state.iteration + niter,
        )
        return out if taps is None else (out, taps)

    def iterate(state: LatticeState, params: SimParams, niter: int
                ) -> LatticeState:
        if params.time_series is not None and points is not None:
            # dispatch keeps such a run on the XLA scan
            # (Lattice._samples_on_engine)
            raise NotImplementedError(
                "the sampled flavour does not take a <Control> series")
        return _iterate_jit(state, params, niter)

    # the bands of the kernel the engine loops; band_shape: the
    # single-step kernel's, for the resident engine's account of the
    # steps it leaves to this engine
    def shape_of(steps):
        rows = plan.band_rows[steps - 1]
        return dict(bands=ny // rows, band_rows=rows, halo_rows=_HALO,
                    pad_rows=pad)

    band_shape, looped = shape_of(1), shape_of(fuse)

    def account(niter: int, has_series: bool = False) -> dict:
        """One call's kernel calls, two-step and one-step, those a
        two-call loop body issues, and the looped kernel's bands and
        the scratch slots it holds of one."""
        twos, ones = split(niter)
        # series_planes: the planes made for a step because of a series
        # (none: its values go in as scalars, ``series_flavour``)
        return dict(kernel_calls=twos + ones, remainder_steps=0,
                    paired_calls=paired_calls(twos, ones) if paired else 0,
                    aux_planes=_AUX_PLANES, band_slots=_BAND_SLOTS, **looped,
                    **(dict(series_planes=0) if has_series else {}))

    # vmem: the looped kernel's part of the plan, beside the account on
    # the span; impl: the jitted program, for the compile tests
    return Engine(iterate, account, samples=points is not None,
                  supports_series=points is None, pad_rows=pad,
                  vmem=dict(vmem_bytes=plan.vmem_bytes[fuse - 1],
                            vmem_limit_bytes=plan.vmem_limit_bytes[fuse - 1]),
                  impl=dict(band_shape=band_shape, program=_iterate_jit,
                            plan=plan))
