"""Shared temporal-fusion planner for the band/slab Pallas engines.

Temporal fusion runs ``K`` lattice steps per HBM round trip: the DMA'd
band carries ``K * reach`` halo rows/slabs per side and each fused step
shrinks the valid interior by one reach (the progressive-extension
scheme ops/pallas_generic.py introduced in 2D).  Amortized traffic per
step drops from ``reads + writes`` to roughly
``(reads * (b + 2*K*reach) / b + writes) / K`` planes, which is why the
fused 2D engines sit at ~0.9x roofline while unfused band kernels are
read-amplification bound.

This module holds the *planning* logic — picking the fusion depth ``K``
(and slab depth ``bz`` in 3D) from the VMEM budget and the traffic
model — so the 2D band engine, the 3D generic slab engine and the tuned
d3q slab engine all make the same decision the same way.  It also holds
the zonal-plane reconstruction (:func:`zone_plane`): in the lean aux
kernels (flags are DMA'd; zonal settings are a pure function of the zone
bits and the SMEM zone table, so shipping them as planes is wasted HBM
traffic), and wherever an XLA program needs a zonal setting per node.
"""

from __future__ import annotations

from typing import Callable, Iterable, Optional, Tuple

import jax.numpy as jnp

FUSE_MAX = 8   # halo growth is priced by the planners; beyond 8 the
#                amortized read term (b + 2*K*reach)/b stops improving
#                faster than the halo cost grows for every model we ship


def choose_fuse_band(reach_of: Callable[[int], int], halo: int,
                     fmax: int = FUSE_MAX) -> int:
    """Largest fuse depth whose fused-plan reach fits a fixed band halo.

    ``reach_of(f)`` returns the total stencil reach of the f-step fused
    action plan (monotone in ``f``); ``halo`` is the rows the band
    kernel DMAs per side.  Used by the 2D band engines, where the halo
    is a fixed 8-row (sublane-aligned) block.
    """
    best = 1
    for f in range(2, fmax + 1):
        try:
            r = reach_of(f)
        except Exception:
            break
        if r > halo:
            break
        best = f
    return best


BAND_AMPLIFICATION_OK = 1.5   # rows a band reads over rows it advances
#                the level of the 1024-wide chip records (32 rows under
#                16 halo rows): a band that reads more is worth a
#                raised scoped-VMEM limit and the probe that comes with it


def plan_band(ny: int, vmem_of: Callable[[int], int], cap: int,
              ceilings: Tuple[int, int], halo: int
              ) -> Optional[Tuple[int, int]]:
    """``(rows, ceiling)`` of a 2D band kernel on ``ny`` rows, or None:
    the one rule of the tuned d2q9 band kernels and the generic band.

    A band of ``rows`` rows reads ``rows + 2 * halo`` and writes
    ``rows``, so the bytes an update moves fall with the band's height:
    the plan is the tallest multiple of 8 (the f32 sublane tile) up to
    ``cap`` that divides ``ny`` and whose VMEM by the caller's account,
    ``vmem_of(rows)``, is under the ceiling.  ``ceilings``: what the
    kernel may fill as it is built by default, and under a raised
    scoped-VMEM limit.  The default's band stands where it reads no
    more than ``BAND_AMPLIFICATION_OK`` times what it advances, and
    where the raised ceiling holds no taller one; so a lattice whose
    plan compiled under the default keeps plan and program."""
    def tallest(ceiling):
        best = None
        for rows in range(8, min(ny, cap) + 1, 8):
            if ny % rows == 0 and vmem_of(rows) <= ceiling:
                best = rows
        return best

    low, high = ceilings
    rows = tallest(low)
    if rows is not None and rows + 2 * halo <= BAND_AMPLIFICATION_OK * rows:
        return rows, low
    taller = tallest(high)
    if taller is not None and (rows is None or taller > rows):
        return taller, high
    return None if rows is None else (rows, low)


def choose_fuse_slab(nz: int, fits: Callable[[int, int], bool],
                     cost: Callable[[int, int], float],
                     base_cost: float, reach: int = 1,
                     fmax: int = FUSE_MAX) -> Optional[Tuple[int, int]]:
    """Pick ``(bz, K)`` minimizing amortized HBM traffic for a fused
    z-slab kernel, or None when no ``K >= 2`` config is feasible and
    cheaper than the best single-step engine.

    ``fits(bz, K)`` is the VMEM-budget predicate (monotone in ``bz``);
    ``cost(bz, K)`` the modeled planes-per-step traffic; ``base_cost``
    the best available K=1 engine's traffic — a fused config must beat
    it to be worth the wider halo.  For each K the largest feasible
    band depth dividing ``nz`` is used (traffic is decreasing in bz).
    """
    best, best_c = None, base_cost
    for K in range(2, fmax + 1):
        if nz < 2 * K * max(reach, 1):
            break
        bz_best = None
        for bz in range(1, nz + 1):
            if nz % bz:
                continue
            if not fits(bz, K):
                break
            bz_best = bz
        if bz_best is None:
            continue
        c = cost(bz_best, K)
        if c < best_c:
            best, best_c = (bz_best, K), c
    return best


ADJ_HALO_MAX = 8   # max halo slabs per side the fused 3D backward DMAs:
#                    the adjoint band needs 2*reach(K) slabs per side
#                    (cotangent cone + recompute cone), and past 8 the
#                    one-slab-at-a-time modular halo copies cost more
#                    HBM round trips than the fused chunk saves


def adjoint_slab_plan(nz: int, n_storage: int, plane_bytes: int,
                      reach_of: Callable[[int], int], k_max: int,
                      n_aux: int = 1,
                      budget: Optional[int] = None,
                      halo_max: int = ADJ_HALO_MAX
                      ) -> Optional[Tuple[int, int]]:
    """Pick ``(K, bz)`` for the fused 3D BACKWARD slab kernel, or None.

    The backward band holds THREE double-buffered stacks (chunk-input
    primal, output-cotangent, flags/aux) at height ``bz + 4*reach(K)``
    — 2R halo slabs per side, twice the forward's R, because the
    in-band VJP both recomputes the forward cone AND widens it again
    transposing it (the adjoint-band rule analysis/footprint.py pins).
    ``K`` is restricted to divisors of ``k_max`` so the caller's chunk
    loop (``niter % k == 0`` from the engine picker) stays exact, and
    to ``2*reach(K) <= halo_max`` / ``nz >= 2*reach(K)`` so the modular
    halo DMAs index true slabs.  Among feasible configs the amortized
    planes-per-step traffic decides; ties go to the deeper chunk.
    """
    if budget is None:
        budget = 24 * 1024 * 1024
    best, best_c = None, None
    for k in range(1, max(1, k_max) + 1):
        if k_max % k:
            continue
        try:
            r = max(int(reach_of(k)), 1)
        except Exception:
            break
        if 2 * r > halo_max or nz < 2 * r:
            continue
        per_slab = (2 * n_storage + n_aux) * plane_bytes
        bz_best = None
        for bz in range(1, nz + 1):
            if nz % bz:
                continue
            if 2 * (bz + 4 * r) * per_slab > budget:
                break
            bz_best = bz
        if bz_best is None:
            continue
        c = ((2 * n_storage + n_aux) * (bz_best + 4 * r)
             + n_storage * bz_best) / float(k * bz_best)
        if best_c is None or c < best_c - 1e-9:
            best, best_c = (k, bz_best), c
    return best


ENSEMBLE_BATCH_MAX = 256   # scheduling sanity cap, not a memory bound


def ensemble_batch_cap(n_storage: int, shape: Tuple[int, ...],
                       itemsize: int,
                       budget_bytes: Optional[int] = None,
                       bmax: int = ENSEMBLE_BATCH_MAX) -> int:
    """Largest ensemble batch whose working set fits the serving budget.

    The same shape of reasoning as the slab engines' VMEM predicates
    (pallas_d3q ``_fused_fits``), applied at the device-memory level the
    batched XLA engine lives at: per case the scan keeps the stacked
    fields twice (carry in + carry out — donation collapses the steady
    state to ~2x) plus one streamed temporary, and flags ride along.

    ``budget_bytes`` defaults to ``TCLB_SERVE_BUDGET_MB`` (MB) or 2 GiB —
    deliberately a fraction of any real device so a full sweep never
    OOMs the executor that also holds the compiled-executable cache.
    Always returns at least 1 (a single case must run regardless; if even
    that thrashes, the budget was a lie the allocator will report).
    """
    if budget_bytes is None:
        import os
        mb = os.environ.get("TCLB_SERVE_BUDGET_MB")
        budget_bytes = (int(mb) * 1024 * 1024 if mb
                        else 2 * 1024 * 1024 * 1024)
    nodes = 1
    for s in shape:
        nodes *= int(s)
    per_case = nodes * (3 * n_storage * itemsize + 2)
    return max(1, min(int(bmax), budget_bytes // max(per_case, 1)))


def snapshot_mem_slots(n_storage: int, shape: Tuple[int, ...],
                       itemsize: int,
                       budget_bytes: Optional[int] = None) -> int:
    """How many adjoint checkpoints (full field stacks) fit the HOST
    snapshot budget — the memory tier of the revolve two-tier store
    (adjoint/revolve.py); snapshots past this count spill to disk.

    ``budget_bytes`` defaults to ``TCLB_ADJOINT_BUDGET_MB`` (MB) or
    4 GiB of host RAM: snapshots are host-side numpy (the forward sweep
    parks them off-device precisely so device memory stays O(one chunk's
    remat tree)), so the budget is a host-RAM predicate, not an HBM one.
    Always at least 1 — revolve degenerates to the quadratic
    single-snapshot sweep rather than refusing to run.
    """
    if budget_bytes is None:
        import os
        mb = os.environ.get("TCLB_ADJOINT_BUDGET_MB")
        budget_bytes = (int(mb) * 1024 * 1024 if mb
                        else 4 * 1024 * 1024 * 1024)
    nodes = 1
    for s in shape:
        nodes *= int(s)
    per_snap = max(1, nodes * n_storage * itemsize)
    return max(1, int(budget_bytes) // per_snap)


def zone_plane(ztab, zones, zone_max: Optional[int] = None, col: int = 0,
               zones_present: Optional[Iterable[int]] = None):
    """One zonal setting's per-node plane, ``row[zones]`` bit for bit,
    built from selects — in a kernel and in the XLA programs round it.

    ``ztab`` holds the setting's per-zone values in the compute dtype at
    ``ztab[col * zone_max + z]``: a row of ``SimParams.zone_table``
    (``col`` 0, ``zone_max`` its length) or, in a kernel, the flattened
    SMEM zone table of all zonal settings; ``zones`` the flag-derived
    zone ids
    (``flags >> zone_shift``, always in ``[0, zone_max)`` by bit width).
    A where-chain over the present zones returns the table's own values;
    ``zones_present=None`` means all zones (exact with no host
    knowledge).  Nothing indexes ``ztab`` by ``zones``: above 64 zones
    XLA lowers that on the TPU to a true gather at 6 to 10 ns a node,
    where the chain fuses into one elementwise pass.  The gradient with
    respect to ``ztab`` is a masked sum per zone (the gather's
    scatter-add up to summation order).
    """
    if zone_max is None:
        zone_max = ztab.shape[0]
    zs = list(zones_present) if zones_present is not None \
        else list(range(zone_max))
    v0 = ztab[col * zone_max + zs[0]]
    plane = jnp.zeros(zones.shape, v0.dtype) + v0
    for z in zs[1:]:
        plane = jnp.where(zones == jnp.int32(z),
                          ztab[col * zone_max + z], plane)
    return plane
