"""Pallas fused collide-stream kernel for the 3D d3q27 model family
(d3q27_BGK, d3q27_BGK_galcor, d3q27_cumulant).

The 3D counterpart of ops/pallas_d2q9.py — the TPU equivalent of the
reference's tuned CUDA hot loop (reference
src/LatticeContainer.inc.cpp.Rt:247-266 ``RunKernel``, the d3q27 cumulant
kernel src/d3q27_cumulant/Dynamics.c.Rt): one kernel per z-slab band does
pull-streaming, boundary handling and collision in a single pass, reading
each density once from HBM and writing it once.

Design (TPU-first):

* the lattice (nz, ny, nx) is tiled into **z-slab bands** of ``BZ`` slabs;
  each grid step DMAs its band plus one wrapped halo slab above and below
  into VMEM.  The (ny, nx) plane is the natural (sublane, lane) tile and
  stays whole where it fits — the baseline-scale 3D cases (e.g. the
  reference's 256x48x48 forced channel, example/
  3d_channel_test_periodic_force_driven.xml) fit whole planes
  comfortably.  A plane that fits no engine whole (a 256^3 box) is tiled
  in y too: the fused kernel's windows become bands of ``BY`` rows with
  one sublane tile (``_HALO_Y`` rows) of wrapped halo rows a side, x
  stays whole (the lane axis) — :func:`tile_plan`;
* pull-streaming is slab-select in z (the halo slabs make ``z ± 1``
  local), a static 1-row roll in y (sublane shift) and a lane-roll in x;
* the boundary dispatch reuses ``family.boundary_cases`` — the IDENTICAL
  closure the XLA path applies — masked over an int32 flag block, and the
  collision reuses ``ops.cumulant.collide_d3q27`` / the BGK equilibrium
  verbatim (those modules are written in Mosaic-safe primitives);
* scalar Settings ride in SMEM; zonal Velocity/Density (+Turbulence) are
  built into per-node planes outside the kernel (``fusion.zone_plane``);
* like the d2q9 kernel this is the "NoGlobals" specialization
  (src/cuda.cu.Rt Globals-mode template): ``state.globals_`` is zeroed.
  The cumulant model's running averages (avgP/avgU) ARE accumulated, and
  SynthT coupling planes pass through untouched.

``present`` (an iterable of node-type names) restricts which boundary
cases are materialized: every case is full-plane compute-then-select, so
skipping absent types is pure win; parity holds whenever the caller passes
(a superset of) the types actually painted — :func:`present_types`
computes that set from the host flag field.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Iterable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tclb_tpu.core import shift as ddf
from tclb_tpu.core.lattice import LatticeState, SimParams
from tclb_tpu.core.registry import Model
from tclb_tpu.models import family
from tclb_tpu.ops import cumulant, fusion, lbm, slab_dma
from tclb_tpu.ops.engine import Engine, paired_calls, scan_calls

_SUPPORTED = ("d3q27_BGK", "d3q27_BGK_galcor", "d3q27_cumulant",
              "d3q19", "d3q19_les")
# storage dtypes this family can keep in HBM.  Compute is ALWAYS f32:
# fields are cast up right after the VMEM read and cast back down on the
# output write, so bf16 halves HBM bytes per node without touching the
# collision arithmetic (the precision-ladder contract; bf16 runs are
# validated by the error-vs-f32 harness in tclb_tpu/precision.py, not
# by bit-parity).  The marker is also what analysis/precision.py keys
# its unsafe-accumulation scan on.
STORAGE_DTYPES = (jnp.float32, jnp.bfloat16)
_COMPUTE_DTYPE = jnp.float32
_VMEM_BUDGET = 15 * 1024 * 1024
# the fused kernel budgets against a raised Mosaic ceiling: its scratch is
# deliberately larger (K halo slabs per side, 2 slots) and the widest fused
# window's collision intermediates must coexist with it
_FUSED_BUDGET = 80 * 1024 * 1024
_FUSED_VMEM_LIMIT = 100 * 1024 * 1024
# what Mosaic holds beyond the declared scratch and the pipelined out
# blocks, in f32 planes a population over the widest fused window (bz +
# 2 (K - 1) slabs).  Read off the chip's compiler (a described v5e: the
# scoped allocation it reports against a limit it cannot meet; PR 41,
# the table in PERF.md section 6): whole planes 2.0 to 2.6 for
# d3q27_cumulant over seven plans at three shapes ((4, 3) at 512 x 48 x
# 256: 2.52), 2.2 to 2.6 for d3q19 and d3q19_les, 1.6 and 1.8 for the two
# d3q27 BGK models; y-tiled windows 2.5 to 2.7 at K >= 2 and 1.3 at K = 1
# (PR 32).  One number for every window of the kernel: the next whole
# one above the largest reading (the channel's (4, 3) window of 48 x 256
# planes is 71 MiB by Mosaic's count, 76 by this one)
_TEMP_PLANES = 3
# what recomputed node steps cost, in the planes of _fused_cost: a window
# computes (1 + (K-1)/bz) node steps for a useful one, a y-tiled one
# (by + 2 _HALO_Y)/by times that, at 0.12 to 0.16 ns each for the
# cumulant collision on a v5e (PR 32's sweep of nine plans at 256^3),
# which is 21 to 28 planes of DMA traffic at the 86 % of the HBM peak
# the kernel's copies reach.  d3q19's node step reads 0.125 to 0.139 ns
# (PR 41's three whole-plane plans at 512 x 48 x 256): the same; the BGK
# models' is not measured
_RECOMPUTE_PLANES = 23
# wrapped halo rows a side of a y band: one sublane tile, so every DMA
# window starts on a tile boundary; it covers any K <= fusion.FUSE_MAX
# (the in-window y roll spoils one row a side per step)
_HALO_Y = 8

E = cumulant.velocity_set(3)
W = lbm.weights(E)
OPP = lbm.opposite(E)

E19 = lbm.d3q19_velocities()
W19 = lbm.weights(E19)
OPP19 = lbm.opposite(E19)
M19 = lbm.gram_schmidt_basis(E19)


def _q_of(model: Model) -> int:
    return 19 if model.name.startswith("d3q19") else 27


_RING = 4   # ring capacity: slab j lives in slot j % 4 for its 3-step life


def _ring_ok(model: Model, nz: int, ny: int, nx: int,
             itemsize: int = 4, budget: int = _VMEM_BUDGET) -> bool:
    """Whether the rolling-window (neighbor-slab reuse) kernel applies:
    one z-slab per grid step, ring of 4 resident slabs, each slab DMA'd
    from HBM ONCE per lattice step (vs (bz+2)/bz read amplification of
    the block kernel — the round-3 d3q27 number was exactly 3x-read
    bound).  Needs nz % 4 == 0 so the three live slabs always occupy
    distinct ring slots (consecutive slab indices are distinct mod 4,
    including across the periodic wrap)."""
    ns = model.n_storage
    q = _q_of(model)
    naux = ns - q
    per = ny * nx * itemsize
    need = (_RING * q + 2 * naux + 2 * ns + 2 * 4) * per
    return nz % _RING == 0 and nz >= 2 * _RING and need <= budget


def _slab_depth(model: Model, nz: int, ny: int, nx: int,
                itemsize: int = 4,
                budget: int = _VMEM_BUDGET) -> Optional[int]:
    """Largest band depth BZ dividing nz whose working set fits VMEM:
    scratch (ns, BZ+2) slabs + output block + flag/zonal blocks + the
    collision's live intermediates (~6 stacked q-plane tensors)."""
    ns = model.n_storage
    q = _q_of(model)
    naux = ns - q
    per = ny * nx * itemsize
    best = None
    for bz in range(1, nz + 1):
        if nz % bz:
            continue
        # 2-slot f scratch (halo'd) + 2-slot aux scratch + pipelined
        # out/flags/zonal blocks; collision intermediates live in what
        # remains of the ~16 MB VMEM (Mosaic errors loudly if they don't)
        need = (2 * q * (bz + 2) + 2 * naux * bz + 2 * ns * bz
                + 2 * 4 * bz) * per
        if need > budget:
            break
        best = bz
    return best


def _n_zonal(model: Model) -> int:
    return 3 if model.name == "d3q27_cumulant" else 2


def _fused_vmem(model: Model, ny: int, nx: int, bz: int, K: int,
                itemsize: int = 4, by: Optional[int] = None) -> int:
    """The VMEM the planner counts for the fused kernel at (bz, K), in
    bytes: 2-slot halo'd f+aux buffers + 2-slot flag buffers + pipelined
    out blocks + the widest fused window's collision intermediates.  The
    DMA scratch scales with the storage itemsize; the collision
    temporaries are always compute-dtype (f32) planes.  ``by`` tiles the
    plane: the windows hold ``by`` rows and ``_HALO_Y`` wrapped halo rows
    a side, the out blocks ``by`` rows."""
    ns = model.n_storage
    q = _q_of(model)
    rows, band = (ny, ny) if by is None else (by + 2 * _HALO_Y, by)
    H = bz + 2 * K
    scratch = (2 * ns * H * rows + 2 * ns * bz * band) * nx * itemsize
    flagbuf = 2 * H * rows * nx * 4   # int32 flags, itemsize-invariant
    temp = _TEMP_PLANES * q * (bz + 2 * (K - 1)) * rows * nx * 4
    return scratch + flagbuf + temp


def _fused_fits(model: Model, nz: int, ny: int, nx: int,
                bz: int, K: int, itemsize: int = 4,
                by: Optional[int] = None,
                budget: int = _FUSED_BUDGET) -> bool:
    """VMEM predicate for the fused kernel at (bz, K): whether
    :func:`_fused_vmem` fits ``budget``."""
    return _fused_vmem(model, ny, nx, bz, K, itemsize, by) <= budget


def _deepest_band(model: Model, nz: int, ny: int, nx: int, K: int,
                  itemsize: int = 4, by: Optional[int] = None,
                  budget: int = _FUSED_BUDGET) -> Optional[int]:
    """The deepest band ``bz`` dividing nz that :func:`_fused_fits`
    admits at depth K (traffic falls with bz), None where none fits."""
    return max((b for b in range(1, nz + 1) if nz % b == 0
                and _fused_fits(model, nz, ny, nx, b, K, itemsize, by,
                                budget)), default=None)


def _wide(by: Optional[int]) -> float:
    """Rows a window holds for a row of its band: a y-tiled window reads
    ``_HALO_Y`` wrapped halo rows a side, a whole plane none."""
    return 1.0 if by is None else (by + 2 * _HALO_Y) / by


def _fused_cost(model: Model, bz: int, K: int,
                by: Optional[int] = None) -> float:
    """Modeled HBM planes per lattice step of the fused kernel: the
    f+aux stack and the flag plane are read with K halo slabs per side
    (and, tiled, ``_HALO_Y`` halo rows a side of ``by``), the ns output
    planes written halo-free, all amortized over K steps."""
    ns = model.n_storage
    return ((ns + 1) * (bz + 2 * K) * _wide(by) + ns * bz) / (K * bz)


def _tile_cost(model: Model, bz: int, K: int,
               by: Optional[int] = None) -> float:
    """What decides between the plans of the fused kernel, whole planes
    (``by`` None: no halo rows) and y-tiled windows alike: the traffic of
    :func:`_fused_cost` or the arithmetic of the node steps a window
    computes more than once, whichever binds."""
    again = (1.0 + (K - 1) / bz) * _wide(by)
    return max(_fused_cost(model, bz, K, by), _RECOMPUTE_PLANES * again)


def _base_cost(model: Model, nz: int, ny: int, nx: int,
               itemsize: int = 4) -> float:
    """Best single-step engine's HBM planes per step (the bar a fused
    config must beat): the ring kernel reads each plane once; the block
    kernel pays (bz+2)/bz read amplification on the f planes."""
    ns = model.n_storage
    q = _q_of(model)
    zn = _n_zonal(model)
    if _ring_ok(model, nz, ny, nx, itemsize):
        return 2.0 * ns + 1 + zn
    bz = _slab_depth(model, nz, ny, nx, itemsize)
    if bz is None:
        return float("inf")
    return (q * (bz + 2) + (ns - q) * bz + (1 + zn) * bz + ns * bz) / bz


def fused_cfg(model: Model, shape, itemsize: int = 4) -> Optional[tuple]:
    """Production fused-kernel config ``(bz, K)`` for this shape, or
    None when single-step is the better (or only feasible) plan.
    Shared with analysis/resources.py so the static VMEM check audits
    exactly what the engine will build."""
    cfg, _ = fused_cfg_explain(model, shape, itemsize)
    return cfg


def fused_cfg_explain(model: Model, shape, itemsize: int = 4
                      ) -> tuple[Optional[tuple], Optional[str]]:
    """Planner verdict WITH its reason: ``((bz, K), None)`` when a fused
    config wins, else ``(None, reason)`` naming the failing predicate
    term — either no (bz, K) fits ``_FUSED_BUDGET`` (VMEM) or the best
    feasible fused plan does not beat the single-step engine (cost:
    :func:`_tile_cost`, the rule that also plans the y-tiled windows).
    The Lattice dispatch forwards the reason as a ``fused_rejected``
    telemetry event so a silent single-step demotion (once seen as an
    untagged d3q27 engine) can never recur unnoticed."""
    if model.name not in _SUPPORTED or len(shape) != 3:
        return None, "unsupported: model/shape outside the tuned 3D family"
    nz, ny, nx = (int(s) for s in shape)
    base = _base_cost(model, nz, ny, nx, itemsize)
    cfg = fusion.choose_fuse_slab(
        nz,
        lambda bz, K: _fused_fits(model, nz, ny, nx, bz, K, itemsize),
        lambda bz, K: _tile_cost(model, bz, K),
        base)
    if cfg is not None:
        return cfg, None
    # no K >= 2 selected: re-walk the search recording WHY
    feasible = [(bz, K) for K in range(2, fusion.FUSE_MAX + 1)
                if nz >= 2 * K
                for bz in [_deepest_band(model, nz, ny, nx, K, itemsize)]
                if bz]
    if not feasible:
        return None, (
            f"vmem: no (bz, K) fits _FUSED_BUDGET="
            f"{_FUSED_BUDGET // (1024 * 1024)}MB at shape "
            f"{(nz, ny, nx)} (scratch + {_TEMP_PLANES} temp planes/q over "
            f"the widest window, the next whole number above what "
            f"Mosaic holds)")
    bz_b, K_b = min(feasible,
                    key=lambda c: _tile_cost(model, c[0], c[1]))
    return None, (
        f"cost: best fused (bz={bz_b}, K={K_b}) models "
        f"{_tile_cost(model, bz_b, K_b):.2f} planes/step >= "
        f"single-step {base:.2f}")


def _whole_plane(model: Model, nz: int, ny: int, nx: int,
                 itemsize: int = 4, budget: int = _VMEM_BUDGET) -> bool:
    """Whether a single-step engine holds whole (ny, nx) planes in VMEM:
    then every plan (block, ring, fused) keeps the plane whole."""
    return (_slab_depth(model, nz, ny, nx, itemsize, budget) is not None
            or _ring_ok(model, nz, ny, nx, itemsize, budget))


def _single_share(budget: int) -> int:
    """What the single-step kernels may fill when the fused kernel may
    fill ``budget``: the default scoped VMEM against the raised limit."""
    return budget * _VMEM_BUDGET // _FUSED_BUDGET


def tile_plan(model: Model, shape, itemsize: int = 4,
              fuse: Optional[int] = None,
              budget: int = _FUSED_BUDGET) -> Optional[tuple]:
    """Plan ``(bz, by, K)`` of the fused kernel for a shape whose plane
    no single-step engine holds whole: bands of ``bz`` slabs x ``by``
    rows advanced ``K`` steps per HBM round trip (``fuse`` pins K; K = 1
    is the same kernel at one step).  ``by == ny`` keeps the plane whole
    (no halo rows) where only the fused budget takes it.  The least of
    :func:`_tile_cost` wins; ties go to the taller band.
    ``budget`` is the VMEM the fused kernel may fill; the single-step
    engines get the share of it that ``_VMEM_BUDGET`` is of
    ``_FUSED_BUDGET`` (:func:`_single_share`).
    None where :func:`fused_cfg` plans the shape or nothing fits."""
    if model.name not in _SUPPORTED or len(shape) != 3:
        return None
    nz, ny, nx = (int(s) for s in shape)
    if _whole_plane(model, nz, ny, nx, itemsize, _single_share(budget)):
        return None
    bys = [ny] + [b for b in range(ny - _HALO_Y, 0, -_HALO_Y)
                  if ny % b == 0 and ny % _HALO_Y == 0]
    best, best_c = None, float("inf")
    for K in ([fuse] if fuse else range(1, fusion.FUSE_MAX + 1)):
        if nz < 2 * K:
            break
        for by in bys:
            tile = None if by == ny else by
            bz = _deepest_band(model, nz, ny, nx, K, itemsize, tile, budget)
            if bz is None:
                continue
            c = _tile_cost(model, bz, K, tile)
            if c < best_c:
                best, best_c = (bz, by, K), c
    return best


def choose_fuse(model: Model, shape, itemsize: int = 4) -> int:
    """Fusion depth K the engine will run at (1 = single-step)."""
    plan = tile_plan(model, shape, itemsize)
    if plan:
        return plan[2]
    cfg = fused_cfg(model, shape, itemsize)
    return cfg[1] if cfg else 1


def supports(model: Model, shape, dtype, ext_halo: bool = False) -> bool:
    """Whether the fused 3D kernel can run this configuration.

    ``ext_halo=True`` asks about the sharded building block: ``shape``
    is one device's z-block, which the fused kernel advances on whole
    planes where a single-step block kernel would hold them
    (:func:`_slab_depth`) and on y-tiled windows where :func:`tile_plan`
    takes the block.  Ring-only shapes (the rolling window wraps z
    inside the array it reads) answer False there so parallel/halo.py
    falls back cleanly instead of building a kernel Mosaic will
    reject."""
    if model.name not in _SUPPORTED:
        return False
    if len(shape) != 3 or jnp.dtype(dtype) not in (
            jnp.dtype(d) for d in STORAGE_DTYPES):
        return False
    if ext_halo and jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return False   # the sharded composition is f32-only (bit-parity)
    itemsize = jnp.dtype(dtype).itemsize
    nz, ny, nx = (int(s) for s in shape)
    if jax.default_backend() == "tpu" and (nx % 128 or ny % 8):
        return False  # (ny, nx) is the (sublane, lane) tile
    if _slab_depth(model, nz, ny, nx, itemsize) is not None:
        return True
    return ((not ext_halo and _ring_ok(model, nz, ny, nx, itemsize))
            or tile_plan(model, shape, itemsize) is not None)


present_types = lbm.present_types   # shared helper (re-exported)


def window_account(model: Model, shape, plan: tuple, itemsize: int = 4
                   ) -> dict:
    """The windows one call of the fused kernel at ``plan`` =
    ``(bz, by, K)`` cuts ``shape`` into (on a mesh: one shard's), under
    the names an engine's account reports them by."""
    nz, ny, nx = (int(s) for s in shape)
    bz, by, K = plan
    return dict(
        z_bands=nz // bz, band_slabs=bz, halo_slabs=K,
        y_bands=ny // by, band_rows=by,
        halo_rows=_HALO_Y if by < ny else 0,
        aux_planes=1,      # the int32 flag plane rides each window
        # what the planner's account admitted the window at
        vmem_bytes=_fused_vmem(model, ny, nx, bz, K, itemsize,
                               by if by < ny else None))


class ShardKernels(NamedTuple):
    """``make_pallas_iterate(ext_halo=True)``: the fused kernel on one
    z-block of a lattice split over devices.  ``call`` advances the
    block ``plan[2]`` = K steps, ``rest`` one step (the same kernel
    where K is 1); both are ``(settings, zone table, block, lower
    neighbour's slabs, upper neighbour's, int32 flags extended by K
    slabs a side) -> block``, the neighbours' slabs K of them for
    ``call`` and one for ``rest``.  ``zonal_si``: the settings whose
    zone-table rows make the zone table, in order."""

    plan: tuple             # (bz, by, K) of ``call``
    call: Callable
    rest: Callable
    zonal_si: tuple
    account: dict           # :func:`window_account` of ``plan``


def make_pallas_iterate(model: Model, shape, dtype=jnp.float32,
                        interpret: Optional[bool] = None,
                        present: Optional[Iterable[str]] = None,
                        ext_halo: bool = False,
                        fuse: Optional[int] = None,
                        fuse_bz: Optional[int] = None,
                        shift: Optional[np.ndarray] = None,
                        vmem_budget: int = _FUSED_BUDGET):
    """Build ``iterate(state, params, niter) -> state`` running the fused
    3D Pallas kernel.  Caller must check :func:`supports` first.

    ``fuse=K`` runs K lattice steps per HBM round trip (temporal fusion:
    K wrapped halo slabs per side, valid interior shrinking one slab per
    step — the progressive-extension scheme the 2D band engines use);
    ``fuse=None`` picks (bz, K) from the VMEM budget via the shared
    planner (:func:`fused_cfg`), ``fuse=1`` forces the single-step
    block/ring kernels.  ``fuse_bz`` overrides the fused band depth
    (tests use it to exercise nz % (bz*K) != 0 layouts).

    ``ext_halo=True`` builds the sharded building block: ``shape`` is one
    device's z-block and the kernels are the fused kernel's ``ext``
    flavour (:func:`fused_call`), which takes the block as it is, the
    two neighbours' K exchanged slabs as operands of their own
    ((ns, K, ny, nx) each) and the int32 flags extended by K slabs a
    side, and reads those where the one-chip kernel wraps z; y and x
    wrap inside the block.  Returns a :class:`ShardKernels` for
    parallel/halo.py to compose with ``ppermute``."""
    if not supports(model, shape, dtype, ext_halo):
        raise ValueError(f"pallas path unsupported for {model.name} {shape}")
    # storage dtype (what HBM holds) vs compute dtype (what the collision
    # arithmetic runs in).  At f32 storage the casts below are traced
    # no-ops, so the bit-parity contract with the XLA path is untouched;
    # at bf16 every field value is widened right after the VMEM read and
    # narrowed on the output write (accumulate-in-f32 — the
    # precision.unsafe_accum contract)
    cdtype = _COMPUTE_DTYPE
    itemsize = jnp.dtype(dtype).itemsize
    nz, ny, nx = (int(s) for s in shape)
    bz = _slab_depth(model, nz, ny, nx, itemsize) or 1
    # a plane no single-step engine holds whole: the fused kernel on
    # (bz, by) windows does every step, the remainder at its K = 1 plan
    tiled = tile_plan(model, shape, itemsize, fuse, vmem_budget)
    if tiled is None and not _whole_plane(
            model, nz, ny, nx, itemsize, _single_share(vmem_budget)):
        raise ValueError(f"no plan tiles {shape} at fuse={fuse} within "
                         f"{vmem_budget} B of VMEM")
    if tiled is not None:
        cfg = tiled
        rem_cfg = tiled if tiled[2] == 1 else tile_plan(
            model, shape, itemsize, 1, vmem_budget)
    elif fuse is None:
        cfg = fused_cfg(model, shape, itemsize)
    else:
        cfg = None
        if fuse >= 2:
            bzf = fuse_bz
            if bzf is None:
                bzf = _deepest_band(model, nz, ny, nx, fuse, itemsize) or 1
            if nz % bzf:
                raise ValueError(f"fused band depth {bzf} must divide {nz}")
            cfg = (bzf, fuse)
    if cfg is not None and len(cfg) == 2:
        cfg = (cfg[0], ny, cfg[1])       # a whole plane is one y band
    if ext_halo and tiled is None:
        # every step of a shard goes through the fused kernel: on whole
        # planes at K = 1 the steps a fused call leaves over, and every
        # step where no fused plan wins
        rem_cfg = (_deepest_band(model, nz, ny, nx, 1, itemsize), ny, 1)
        cfg = cfg or rem_cfg
    K = cfg[2] if cfg else 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    is_cumulant = model.name == "d3q27_cumulant"
    galcor = model.name.endswith("galcor")
    q = _q_of(model)
    is_les = model.name == "d3q19_les"
    E_, W_, OPP_ = (E19, W19, OPP19) if q == 19 else (E, W, OPP)

    ns = model.n_storage
    f_idx = list(model.groups["f"])
    assert f_idx == list(range(q)), "kernel assumes f planes lead the stack"
    # per-plane DDF shift at the DMA seams: the f group widens/narrows
    # by its lattice weight, aux planes (SynthT/avg) by nothing — with
    # shift=None every helper call is a pure astype (raw contract)
    _shifts = ([None] * ns if shift is None
               else [float(w) or None for w in shift])
    si = model.setting_index
    sidx = model.storage_index
    nt = {n: (int(t.mask), int(t.value)) for n, t in model.node_types.items()}
    coll_mask = int(model.group_masks["COLLISION"])
    present = set(nt) if present is None else set(present)

    zonal_names = ["Velocity", "Density"] + \
        (["Turbulence"] if is_cumulant else [])
    if is_cumulant:
        synth_idx = [sidx[n] for n in ("SynthTX", "SynthTY", "SynthTZ")]
        avgp_idx = sidx["avgP"]
        avgu_idx = [sidx[n] for n in ("avgUX", "avgUY", "avgUZ")]
        aux_idx = synth_idx + [avgp_idx] + avgu_idx
    else:
        aux_idx = []
    # aux planes are DMA'd in storage order and read back by position:
    # the kernel's scra indexing assumes aux_idx IS ascending q..ns-1,
    # not merely covering it (a model registering avg/SynthT densities in
    # a different order would silently read wrong planes)
    assert f_idx + aux_idx == list(range(ns))
    zshift = model.zone_shift
    zone_max = model.zone_max
    zonal_si = [si[n] for n in zonal_names]

    def _is(flags, name):
        mask, val = nt[name]
        return (flags & jnp.int32(mask)) == jnp.int32(val)

    def _step(f, flags, zonal, synth, sett):
        """Boundaries + collision on one band — op-for-op the model's
        ``run`` (models/d3q27_bgk.py, models/d3q27_cumulant.py), minus
        globals."""
        vel, den = zonal[0], zonal[1]
        extra = None
        if is_cumulant:
            turb = zonal[2]
            turb_u = vel + turb * synth[0]
            extra = {"WVelocityTurbulent": lambda f: lbm.nebb_boundary(
                E, W, OPP, f, 0, +1, "velocity", turb_u,
                vt={1: turb * synth[1], 2: turb * synth[2]})}
        cases = family.boundary_cases(model, E_, W_, OPP_, vel, den, extra)
        f = family.dispatch_boundary_cases(
            cases, f, lambda n: _is(flags, n), present)

        coll = (flags & jnp.int32(coll_mask)) != jnp.int32(0)
        if is_cumulant:
            om = jnp.where(
                _is(flags, "Buffer"),
                1.0 / (3.0 * sett[si["nubuffer"]] + 0.5),
                sett[si["omega"]]).astype(f.dtype)
            force = tuple(sett[si[f"Force{a}"]] + sett[si[f"Gravitation{a}"]]
                          for a in "XYZ")
            F = f.reshape((3, 3, 3) + f.shape[1:])
            Fp, rho, (ux, uy, uz) = cumulant.collide_d3q27(
                F, om, sett[si["omega_bulk"]], force=force, correlated=True,
                galilean=sett[si["GalileanCorrection"]])
            f = jnp.where(coll[None], Fp.reshape(f.shape), f)
            return f, ((rho - 1.0) / 3.0, (ux, uy, uz))
        if q == 19:
            # rho/u spelled exactly as models/d3q19.py computes them
            # (jnp.sum reduce + edot) so the kernel is bit-identical to
            # the XLA path, not merely allclose.  The barriers pin the
            # collision's input (the boundary select chain) and output
            # (before the coll select): fused, either select alters the
            # FMA contraction of the relaxation arithmetic, which in the
            # XLA path lowers contraction-free — same 1-ULP class as the
            # streaming-roll barrier above
            f = lbm.pin(f)
            rho = jnp.sum(f, axis=0)
            u = tuple(lbm.edot(E19[:, a], f) / rho for a in range(3))
            feq = lbm.equilibrium(E19, W19, rho, u)
            g = tuple(sett[si[f"Gravitation{a}"]] for a in "XYZ")
            u2 = tuple(u[a] + g[a] for a in range(3))
            feq2 = lbm.equilibrium(E19, W19, rho, u2)
            if is_les:
                # BGK + Smagorinsky (models/d3q19_les.py), shared
                # Mosaic-safe unrolled |Pi| helper
                om_eff = lbm.smagorinsky_omega_unrolled(
                    E19, f, feq, rho, sett[si["omega"]], sett[si["Smag"]])
                fc = jnp.stack([f[k] + om_eff * (feq[k] - f[k])
                                + (feq2[k] - feq[k]) for k in range(19)])
            else:
                # MRT (models/d3q19.py): the shared two-rate
                # stress-projection relaxation — only 6 rank-one
                # projections instead of the 15-row transform pair
                fneq = [f[k] - feq[k] for k in range(19)]
                relax = lbm.two_rate_relax(
                    M19, 4, 10, fneq,
                    1.0 - sett[si["omega"]], 1.0 - sett[si["S_high"]])
                fc = jnp.stack([relax[k] + feq2[k] for k in range(19)])
            fc = lbm.pin(fc)
            return jnp.where(coll[None], fc, f), None
        from tclb_tpu.models.d3q27_bgk import _equilibrium
        rho = jnp.sum(f, axis=0)
        u = tuple(lbm.edot(E[:, a], f) / rho for a in range(3))
        om = sett[si["omega"]]
        feq = _equilibrium(rho, u, galcor)
        fc = f + om * (feq - f)
        g = tuple(sett[si[f"Gravitation{a}"]] for a in "XYZ")
        u2 = tuple(u[a] + g[a] for a in range(3))
        fc = fc + (_equilibrium(rho, u2, galcor) - feq)
        return jnp.where(coll[None], fc, f), None

    naux = len(aux_idx)
    ring_mode = _ring_ok(model, nz, ny, nx, itemsize)

    def kernel_ring(sett, f_hbm, flags_ref, zonal_ref, out_ref, ring, scra,
                    sems, sems_a):
        """Rolling-window kernel: one z-slab per grid step, 4-slot ring of
        resident slabs (slab j lives in slot j % 4 for its 3-step life:
        prefetched at step j-2, read as z+1 / z / z-1 at steps j-1, j,
        j+1).  Each slab is DMA'd from HBM ONCE per lattice step — the
        neighbor-slab reuse that removes the block kernel's (bz+2)/bz
        read amplification (round-3 VERDICT Weak #2: the d3q27 cumulant
        was exactly 3x-read bound at bz=1).  The periodic wrap re-fetches
        slab 0 at step nz-2 (slot nz % 4 == 0 — hence the nz % 4 == 0
        eligibility), so no stale slot is ever read."""
        i = pl.program_id(0)
        n = pl.num_programs(0)   # == nz
        R = jnp.int32(_RING)

        def slab_dma(j, slot):
            return pltpu.make_async_copy(
                f_hbm.at[pl.ds(0, q), pl.ds(j, 1)],
                ring.at[slot], sems.at[slot])

        def aux_dma(j, slot):
            return pltpu.make_async_copy(
                f_hbm.at[pl.ds(q, naux), pl.ds(j, 1)],
                scra.at[slot], sems_a.at[slot])

        zm = jax.lax.rem(i - 1 + jnp.int32(n), jnp.int32(n))
        zp = jax.lax.rem(i + 1, jnp.int32(n))
        slot_m = jax.lax.rem(zm, R)
        slot_0 = jax.lax.rem(i, R)
        slot_p = jax.lax.rem(zp, R)

        @pl.when(i == 0)
        def _():
            # initial fill: the first step's three slabs
            slab_dma(zm, slot_m).start()
            slab_dma(jnp.int32(0), jnp.int32(0)).start()
            if naux:
                aux_dma(jnp.int32(0), jnp.int32(0)).start()

        @pl.when(i + 1 < n)
        def _():
            # prefetch slab i+2 for step i+1's z+1 read (slot (i+2)%4 is
            # free: its previous occupant, slab i-2, was last read at
            # step i-1; the wrap re-fetch of slab 0 lands in slot 0 at
            # step nz-2, after slot 0's occupant was last read)
            nxt_slab = jax.lax.rem(i + 2, jnp.int32(n))
            slab_dma(nxt_slab, jax.lax.rem(nxt_slab, R)).start()
            if naux:
                aux_dma(zp, jax.lax.rem(zp, jnp.int32(2))).start()

        @pl.when(i == 0)
        def _():
            # slab 1 (step 0's z+1) — the prefetch chain starts at slab 2
            slab_dma(jnp.int32(1), jnp.int32(1)).start()

        # waits: first use of each slab decrements its slot's semaphore
        @pl.when(i == 0)
        def _():
            slab_dma(zm, slot_m).wait()
            slab_dma(jnp.int32(0), jnp.int32(0)).wait()
            if naux:
                aux_dma(jnp.int32(0), jnp.int32(0)).wait()
        slab_dma(zp, slot_p).wait()
        aslot = jax.lax.rem(i, jnp.int32(2))
        if naux:
            @pl.when(i > 0)
            def _():
                aux_dma(i, aslot).wait()

        pulled = []
        for k in range(q):
            dx, dy, dz = int(E_[k, 0]), int(E_[k, 1]), int(E_[k, 2])
            slot = slot_m if dz == 1 else (slot_p if dz == -1 else slot_0)
            sl = ring[slot, k]          # (1, ny, nx)
            if dy:
                sl = jnp.roll(sl, dy, axis=1)
            if dx:
                sl = pltpu.roll(sl, dx % nx, axis=2)
            pulled.append(sl)
        # the barrier pins the streamed values before collision: without
        # it the compiler fuses the rolls into the collide arithmetic,
        # changing FMA contraction and breaking bit-parity with the XLA
        # path (where streaming materializes before the collide fusion).
        # the widen seam restores bf16 storage to the f32 compute dtype
        # (+ the per-plane DDF shift under the shifted representation —
        # scalar immediates, a Pallas kernel cannot capture an array
        # constant; no-op at f32/raw storage, so the parity contract is
        # untouched)
        f = lbm.pin(
            jnp.stack([ddf.widen_plane(p, cdtype, _shifts[k])
                       for k, p in enumerate(pulled)]))
        flags = flags_ref[:]
        zonal = zonal_ref[:]
        synth = [ddf.widen_plane(scra[aslot, aux_idx.index(j)], cdtype,
                                 _shifts[j])
                 for j in synth_idx] if is_cumulant else None
        fnew, extras = _step(f, flags, zonal, synth, sett)
        for k in range(q):
            out_ref[k] = ddf.narrow_plane(fnew[k], dtype, _shifts[k])
        if is_cumulant:
            for j in synth_idx:
                out_ref[j] = scra[aslot, aux_idx.index(j)]
            p_inc, (ux, uy, uz) = extras
            out_ref[avgp_idx] = ddf.narrow_plane(
                ddf.widen_plane(scra[aslot, aux_idx.index(avgp_idx)],
                                cdtype, _shifts[avgp_idx])
                + p_inc, dtype, _shifts[avgp_idx])
            for j, u in zip(avgu_idx, (ux, uy, uz)):
                out_ref[j] = ddf.narrow_plane(
                    ddf.widen_plane(scra[aslot, aux_idx.index(j)], cdtype,
                                    _shifts[j])
                    + u, dtype, _shifts[j])

    def kernel(sett, f_hbm, flags_ref, zonal_ref, out_ref, scrf, scra, sems):
        # 2-slot double buffering: band i+1's DMAs are issued before band
        # i's compute, overlapping HBM fetch with VPU work across grid
        # steps (the reference gets the same overlap from its border/
        # interior kernel split + async memcpy streams,
        # src/Lattice.cu.Rt:424-456).  f planes get z±1 halo slabs; aux
        # planes (SynthT/avg) are local-only and skip the halo.
        i = pl.program_id(0)
        n = pl.num_programs(0)

        def band_dmas(slot, band):
            base = band * jnp.int32(bz)
            mid1 = base
            zm = jax.lax.rem(base - jnp.int32(1) + jnp.int32(nz),
                             jnp.int32(nz))
            zp = jax.lax.rem(base + jnp.int32(bz), jnp.int32(nz))
            copies = [
                pltpu.make_async_copy(f_hbm.at[pl.ds(0, q), pl.ds(mid1, bz)],
                                      scrf.at[slot, :, pl.ds(1, bz)],
                                      sems.at[slot, 0]),
                pltpu.make_async_copy(f_hbm.at[pl.ds(0, q), pl.ds(zm, 1)],
                                      scrf.at[slot, :, pl.ds(0, 1)],
                                      sems.at[slot, 1]),
                pltpu.make_async_copy(f_hbm.at[pl.ds(0, q), pl.ds(zp, 1)],
                                      scrf.at[slot, :, pl.ds(bz + 1, 1)],
                                      sems.at[slot, 2]),
            ]
            if naux:
                copies.append(pltpu.make_async_copy(
                    f_hbm.at[pl.ds(q, naux), pl.ds(mid1, bz)],
                    scra.at[slot], sems.at[slot, 3]))
            return copies

        slot = jax.lax.rem(i, jnp.int32(2))
        nxt = jax.lax.rem(i + jnp.int32(1), jnp.int32(2))

        @pl.when(i == 0)
        def _():
            for c in band_dmas(jnp.int32(0), i):
                c.start()

        @pl.when(i + 1 < n)
        def _():
            for c in band_dmas(nxt, i + jnp.int32(1)):
                c.start()

        for c in band_dmas(slot, i):
            c.wait()

        # pull-streaming: f_k(z,y,x) <- f_k(z-dz, y-dy, x-dx); halo slabs
        # cover z +- 1, a static sublane roll covers y, a lane-roll x
        # (matches core.lattice.pull_stream's periodic jnp.roll semantics)
        pulled = []
        for k in range(q):
            dx, dy, dz = int(E_[k, 0]), int(E_[k, 1]), int(E_[k, 2])
            sl = scrf[slot, k, 1 - dz:1 - dz + bz]
            if dy:
                sl = jnp.roll(sl, dy, axis=1)
            if dx:
                sl = pltpu.roll(sl, dx % nx, axis=2)
            pulled.append(sl)
        # the barrier pins the streamed values before collision: without
        # it the compiler fuses the rolls into the collide arithmetic,
        # changing FMA contraction and breaking bit-parity with the XLA
        # path (where streaming materializes before the collide fusion);
        # the widen seam restores bf16 storage to the f32 compute dtype
        # (+ the per-plane DDF shift under the shifted representation)
        f = lbm.pin(
            jnp.stack([ddf.widen_plane(p, cdtype, _shifts[k])
                       for k, p in enumerate(pulled)]))
        flags = flags_ref[:]
        zonal = zonal_ref[:]
        synth = [ddf.widen_plane(scra[slot, aux_idx.index(j)], cdtype,
                                 _shifts[j])
                 for j in synth_idx] if is_cumulant else None
        fnew, extras = _step(f, flags, zonal, synth, sett)
        for k in range(q):
            out_ref[k] = ddf.narrow_plane(fnew[k], dtype, _shifts[k])
        if is_cumulant:
            # SynthT passthrough; running averages accumulate per step
            # (reference average=T densities + Lattice::resetAverage)
            for j in synth_idx:
                out_ref[j] = scra[slot, aux_idx.index(j)]
            p_inc, (ux, uy, uz) = extras
            out_ref[avgp_idx] = ddf.narrow_plane(
                ddf.widen_plane(scra[slot, aux_idx.index(avgp_idx)],
                                cdtype, _shifts[avgp_idx])
                + p_inc, dtype, _shifts[avgp_idx])
            for j, u in zip(avgu_idx, (ux, uy, uz)):
                out_ref[j] = ddf.narrow_plane(
                    ddf.widen_plane(scra[slot, aux_idx.index(j)], cdtype,
                                    _shifts[j])
                    + u, dtype, _shifts[j])

    if ring_mode:
        call = pl.pallas_call(
            lbm.mosaic_body(kernel_ring, interpret),
            grid=(nz,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, ny, nx), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((len(zonal_names), 1, ny, nx),
                             lambda i: (0, i, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((ns, 1, ny, nx), lambda i: (0, i, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((ns, nz, ny, nx), dtype),
            scratch_shapes=[
                pltpu.VMEM((_RING, q, 1, ny, nx), dtype),
                pltpu.VMEM((2, max(naux, 1), 1, ny, nx), dtype),
                pltpu.SemaphoreType.DMA((_RING,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            interpret=interpret,
            name="d3q_ring_fuse1",
        )
    else:
        call = pl.pallas_call(
            lbm.mosaic_body(kernel, interpret),
            grid=(nz // bz,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((bz, ny, nx), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((len(zonal_names), bz, ny, nx),
                             lambda i: (0, i, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((ns, bz, ny, nx), lambda i: (0, i, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((ns, nz, ny, nx), dtype),
            scratch_shapes=[
                pltpu.VMEM((2, q, bz + 2, ny, nx), dtype),
                pltpu.VMEM((2, max(naux, 1), bz, ny, nx), dtype),
                pltpu.SemaphoreType.DMA((2, 4)),
            ],
            interpret=interpret,
            name="d3q_slab_fuse1",
        )

    def fused_call(bzK: int, byK: int, K: int, ext: int = 0):
        """The multi-step fused band kernel at one plan: K lattice steps
        per HBM round trip on windows of ``bzK`` slabs x ``byK`` rows
        (``byK == ny``: whole planes, one y band).  ``ext`` (slabs, 0:
        the lattice on one chip, z periodic inside the array) builds the
        flavour of one z-block of a lattice split over devices: beside
        the block as it is, the lower and the upper neighbour's K slabs
        are operands of their own ((ns, K, ny, nx): what a window's
        halo reads past either end of the block), and the flags come
        extended by ``ext >= K`` slabs a side; y still wraps inside the
        block."""
        hy = _HALO_Y if byK < ny else 0
        H = bzK + 2 * K       # buffer depth: band + K wrapped halo slabs/side
        R = byK + 2 * hy      # buffer rows: band + hy wrapped halo rows/side
        nzb, nyb = nz // bzK, ny // byK

        z_pieces = slab_dma.pieces(bzK, K)
        y_pieces = slab_dma.pieces(byK, hy)
        wrap = slab_dma.wrap

        def kernel_fused(sett, ztab, f_hbm, *refs):
            """The DMA'd buffer carries K wrapped halo slabs per side
            (f + aux stack AND flags — boundary dispatch in the halo
            region needs true node types so the recomputed halo sites
            agree with their home band's values); step j (0-based)
            computes buffer slabs [j+1, H-(j+1)) from slabs [j, H-j) of
            the step-(j-1) state, so after K steps slabs [K, K+bz) hold
            the valid K-step-advanced band.  A y-tiled window rolls in y
            inside its own rows: each step spoils one more row at either
            end, K <= hy of them, never a band row.  Zonal settings
            never ride the DMA: they are a pure function of the flag zone
            bits and the SMEM zone table, so they are reconstructed
            in-kernel (fusion.zone_plane) — the same aux diet the generic
            engine runs.  The 2-slot double-buffered band pipeline is
            kept: the next band's (wider) blocks prefetch under this
            band's K-step compute.  ``refs``: the flags, the out block
            and the scratch, after the two neighbours' slabs where the
            flavour is ``ext``."""
            *halos, flags_hbm, out_ref, scrf, scrg, sems = refs
            i = pl.program_id(0)
            j = pl.program_id(1) if nyb > 1 else jnp.int32(0)
            t = i * jnp.int32(nyb) + j       # bands run z-major, y-minor

            def band_dmas(slot, bi, bj):
                z0 = bi * jnp.int32(bzK)
                y0 = bj * jnp.int32(byK) if nyb > 1 else 0
                copies = []
                for oz, dz, lz in z_pieces:
                    for oy, dy_, ly in y_pieces:
                        # the extended flags hold slab z at z + ext
                        sz = (z0 + jnp.int32(oz + ext) if ext
                              else wrap(z0, oz, nz))
                        sy = wrap(y0, oy, ny)
                        if hy:      # bands and halos are whole sublane tiles
                            sy = pl.multiple_of(sy, _HALO_Y)
                        s = len(copies)
                        rows = pl.ds(sy, ly)
                        dst = scrf.at[slot, :, pl.ds(dz, lz), pl.ds(dy_, ly)]

                        def window(ref, z, lz=lz, rows=rows, dst=dst,
                                   sem=sems.at[slot, s]):
                            return pltpu.make_async_copy(
                                ref.at[:, pl.ds(z, lz), rows], dst, sem)

                        copies += [
                            slab_dma.field_copy(window, f_hbm, halos, z0,
                                                oz, sz, lz, nz, K),
                            pltpu.make_async_copy(
                                flags_hbm.at[pl.ds(sz, lz), pl.ds(sy, ly)],
                                scrg.at[slot, pl.ds(dz, lz), pl.ds(dy_, ly)],
                                sems.at[slot, s + 1]),
                        ]
                return copies

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
                for c in band_dmas(jnp.int32(0), i, j):
                    c.start()

            @pl.when(t + 1 < nzb * nyb)
            def _():
                for c in band_dmas(nxt, ni, nj):
                    c.start()

            for c in band_dmas(slot, i, j):
                c.wait()

            rows = slice(hy, hy + byK) if hy else slice(None)   # the band's

            flagbuf = scrg[slot]
            zones = flagbuf >> zshift
            zonalbuf = [fusion.zone_plane(ztab, zones, zone_max, col=c)
                        for c in range(len(zonal_names))]
            synthbuf = [ddf.widen_plane(scrf[slot, j_], cdtype, _shifts[j_])
                        for j_ in synth_idx] if is_cumulant else None
            if is_cumulant:
                # widen ONCE, accumulate all K steps in f32, narrow on the
                # output write (the precision.unsafe_accum contract)
                acc_p = ddf.widen_plane(
                    scrf[slot, avgp_idx, K:K + bzK, rows], cdtype,
                    _shifts[avgp_idx])
                acc_u = [ddf.widen_plane(scrf[slot, j_, K:K + bzK, rows],
                                         cdtype, _shifts[j_])
                         for j_ in avgu_idx]

            # slabs [0, H); widened to the compute dtype for the step chain
            # (the DDF shift restores once here and removes once at the
            # final narrow: all K in-between steps run on raw f in f32)
            cur = [ddf.widen_plane(scrf[slot, k], cdtype, _shifts[k])
                   for k in range(q)]
            for st in range(K):
                lo = st + 1                  # output window in buffer slabs
                n_j = bzK + 2 * (K - 1 - st)
                pulled = []
                for k in range(q):
                    dx, dy, dz = int(E_[k, 0]), int(E_[k, 1]), int(E_[k, 2])
                    a = lo - dz - st         # cur[k] covers slabs [st, H-st)
                    sl = cur[k][a:a + n_j]
                    if dy:
                        sl = jnp.roll(sl, dy, axis=1)
                    if dx:
                        sl = pltpu.roll(sl, dx % nx, axis=2)
                    pulled.append(sl)
                # barrier before collision, same reason as the single-step
                # kernels: keep the rolls out of the collide fusion so every
                # fused step's arithmetic is bit-identical to an XLA step
                f = lbm.pin(jnp.stack(pulled))
                flags = flagbuf[lo:lo + n_j]
                zonal = [zb[lo:lo + n_j] for zb in zonalbuf]
                synth = [sb[lo:lo + n_j] for sb in synthbuf] \
                    if is_cumulant else None
                fnew, extras = _step(f, flags, zonal, synth, sett)
                cur = [fnew[k] for k in range(q)]   # slabs [lo, lo + n_j)
                if is_cumulant:
                    # running averages accumulate on the central band only,
                    # in the same left-fold order as K single XLA steps
                    c0 = K - lo
                    p_inc, us = extras
                    acc_p = acc_p + p_inc[c0:c0 + bzK, rows]
                    acc_u = [au + u[c0:c0 + bzK, rows]
                             for au, u in zip(acc_u, us)]

            for k in range(q):
                out_ref[k] = ddf.narrow_plane(cur[k][:, rows], dtype,
                                              _shifts[k])
            if is_cumulant:
                for j_ in synth_idx:
                    out_ref[j_] = scrf[slot, j_, K:K + bzK, rows]
                out_ref[avgp_idx] = ddf.narrow_plane(acc_p, dtype,
                                                     _shifts[avgp_idx])
                for j_, au in zip(avgu_idx, acc_u):
                    out_ref[j_] = ddf.narrow_plane(au, dtype, _shifts[j_])

        return pl.pallas_call(
            lbm.mosaic_body(kernel_fused, interpret),
            grid=(nzb,) if nyb == 1 else (nzb, nyb),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
            + [pl.BlockSpec(memory_space=pl.ANY)] * (4 if ext else 2),
            out_specs=pl.BlockSpec(
                (ns, bzK, byK, nx),
                (lambda i: (0, i, 0, 0)) if nyb == 1
                else (lambda i, j: (0, i, j, 0)),
                memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((ns, nz, ny, nx), dtype),
            scratch_shapes=[
                pltpu.VMEM((2, ns, H, R, nx), dtype),
                pltpu.VMEM((2, H, R, nx), jnp.int32),
                pltpu.SemaphoreType.DMA(
                    (2, 2 * len(z_pieces) * len(y_pieces))),
            ],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_FUSED_VMEM_LIMIT),
            name=f"d3q_slab_fuse{K}",
        )

    if ext_halo:
        return ShardKernels(
            cfg, fused_call(*cfg, ext=K), fused_call(*rem_cfg, ext=K),
            tuple(zonal_si), window_account(model, shape, cfg, itemsize))

    call_f = fused_call(*cfg) if cfg else None
    # the steps a fused call leaves over: the single-step block/ring
    # kernel where a plane fits it whole, else the fused kernel at K = 1
    call_r = (None if tiled is None else call_f if K == 1
              else fused_call(*rem_cfg))

    def split(niter: int) -> tuple:
        """``niter`` steps as the trips of the two loops: ``fused``
        calls of K steps and ``rest`` calls of one."""
        fused = niter // K if cfg else 0
        return fused, niter - fused * K

    @partial(jax.jit, static_argnames=("niter",), donate_argnums=0)
    def _iterate_jit(state: LatticeState, params: SimParams,
                     niter: int) -> LatticeState:
        flags_i32 = state.flags.astype(jnp.int32)
        # zonal planes, settings and the SMEM zone table ride in the
        # COMPUTE dtype: only the field stack pays the storage narrowing
        sett = params.settings.astype(cdtype)
        fields = state.fields.astype(dtype)
        ztab = jnp.concatenate(
            [params.zone_table[j].astype(cdtype) for j in zonal_si])

        def body_f(call_k):
            return lambda fields, _: (
                call_k(sett, ztab, fields, flags_i32), None)

        fused, rest = split(niter)
        if cfg:
            fields = scan_calls(body_f(call_f), fields, fused, True)
        if tiled is not None:
            body = body_f(call_r)
        else:
            zones = flags_i32 >> zshift
            zonal = jnp.stack([fusion.zone_plane(
                params.zone_table[j].astype(cdtype), zones)
                for j in zonal_si])

            def body(fields, _):
                return call(sett, fields, flags_i32, zonal), None

        fields = scan_calls(body, fields, rest, True)
        return LatticeState(
            fields=fields,
            flags=state.flags,
            globals_=jnp.zeros_like(state.globals_),
            iteration=state.iteration + niter,
        )

    def account(niter: int, has_series: bool = False) -> dict:
        """What one ``iterate(niter)`` issues, reckoned host-side from
        the plan and ``_iterate_jit``'s own split: the fused calls'
        windows, and the steps left to the single-step kernel."""
        fused, rest = split(int(niter))
        did = dict(kernel_calls=fused + rest, remainder_steps=rest,
                   paired_calls=paired_calls(fused, rest))
        if fused:
            did.update(window_account(model, shape, cfg, itemsize))
        return did

    def iterate(state: LatticeState, params: SimParams, niter: int
                ) -> LatticeState:
        if params.time_series is not None:
            raise ValueError(
                "pallas iterate does not support Control time series; "
                "use the XLA path for time-dependent zonal settings")
        return _iterate_jit(state, params, niter)

    return Engine(iterate, account)
