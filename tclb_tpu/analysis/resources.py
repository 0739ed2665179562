"""Kernel-resource estimation: per-engine VMEM/tile budgets, statically.

Mirrors the sizing arithmetic each engine applies at build time
(``_band_rows``/``_pad_rows``/``_slab_depth_gen``/the backward kernel's
``by_bwd`` heuristic) and evaluates it at PRODUCTION shapes — including
the ``k = max_chunk`` fused-chain widths that ``supports_diff``'s cheap
k=1 probe historically never exercised.  That turns "auto fell back
because the first TPU compile died" into a finding the analyzer (and the
eligibility caches) can report before anything compiles.

``adjoint_static_ok`` is the verdict ``supports_diff`` consults: the
backward band kernel's three double-buffered scratch stacks at the
minimum band height, against its VMEM ceiling.
"""

from __future__ import annotations

from tclb_tpu.analysis.findings import Finding
from tclb_tpu.core.registry import Model

# the backward band kernel raises the compiler's VMEM ceiling to 100 MB
# (ops/pallas_adjoint); its scratch must leave room for the VJP chain's
# live temporaries, so the static gate draws the line well below that.
_ADJ_SCRATCH_LIMIT = 64 * 1024 * 1024


def default_shape(model: Model) -> tuple:
    """Representative production shape (the scale of the example
    cases the kernels' budgets were set at)."""
    return (512, 1024) if model.ndim == 2 else (48, 48, 256)


def _adjoint_scratch_bytes(model: Model, nx: int, by: int,
                           series: bool) -> int:
    """Bytes of the backward kernel's double-buffered primal + lambda +
    aux band stacks at band height ``by`` (mirrors make_diff_step)."""
    halo = 8
    n_aux = 1 + (2 if series else 1) * len(model.zonal_settings)
    per_row = (2 * model.n_storage + n_aux) * nx * 4
    return 2 * (by + 2 * halo) * per_row


def adjoint_static_ok(model: Model, nx: int, series: bool = False) -> bool:
    """Whether the backward band kernel can possibly fit VMEM at this
    width: even the minimum 8-row band must stay under the scratch
    limit.  Consulted by ``supports_diff`` so ineligibility is decided
    statically instead of by a compile failure."""
    return _adjoint_scratch_bytes(model, nx, 8, series) \
        <= _ADJ_SCRATCH_LIMIT


def check_resources(model: Model, shape=None) -> list:
    findings: list = []
    from tclb_tpu.ops import pallas_generic

    shape = tuple(int(s) for s in (shape or default_shape(model)))
    if len(shape) != model.ndim:
        findings.append(Finding(
            "resources.bad_shape", "warning", model.name,
            f"shape {shape} does not match model ndim={model.ndim}; "
            "resource checks skipped"))
        return findings
    try:
        _, reach = pallas_generic.action_plan(model, "Iteration", fuse=1)
    except Exception:  # noqa: BLE001 — no Iteration action / broken plan
        return findings
    where = f"shape:{'x'.join(str(s) for s in shape)}"

    if model.ndim == 2:
        ny, nx = shape
        # -- forward band engine ---------------------------------------- #
        pad = pallas_generic._pad_rows(model, ny, nx, max(reach, 1))
        if pad is None:
            findings.append(Finding(
                "resources.band_vmem", "warning", model.name,
                f"no band height fits the "
                f"{pallas_generic._BAND_SCRATCH[1] >> 20} MB scratch "
                f"budget at {ny}x{nx} ({model.n_storage} storage planes): "
                "generic band engine ineligible, XLA fallback", where,
                {"n_storage": model.n_storage, "shape": list(shape)}))
        else:
            by = pallas_generic._band_rows(model, ny + pad, nx)
            n_aux = 1 + 2 * len(model.zonal_settings)
            est = 2 * (by + 16) * (model.n_storage + n_aux) * nx * 4
            findings.append(Finding(
                "resources.band_layout", "info", model.name,
                f"band engine: by={by} pad={pad} scratch~{est >> 10} KiB",
                where, {"by": by, "pad": pad, "scratch_bytes": est}))
        # -- resident engine -------------------------------------------- #
        n_aux_r = 1 + len(model.zonal_settings)
        res_bytes = (2 * model.n_storage + n_aux_r) * ny * nx * 4
        res_ok = (ny % 8 == 0 and nx % 128 == 0
                  and res_bytes <= pallas_generic._RESIDENT_BUDGET
                  and reach <= pallas_generic.HALO)
        findings.append(Finding(
            "resources.resident", "info", model.name,
            f"VMEM-resident engine {'eligible' if res_ok else 'ineligible'}"
            f" at {ny}x{nx} (state+aux {res_bytes >> 20} MiB / "
            f"{pallas_generic._RESIDENT_BUDGET >> 20} MiB budget)", where,
            {"eligible": res_ok, "resident_bytes": res_bytes}))
        # -- adjoint backward kernel at the production chunk ------------ #
        from tclb_tpu.ops import pallas_adjoint
        k = pallas_adjoint.max_chunk(model)
        if k >= 1:
            for series in (False, True):
                if series and not model.zonal_settings:
                    continue
                if not adjoint_static_ok(model, nx, series):
                    findings.append(Finding(
                        "resources.adjoint_vmem", "warning", model.name,
                        f"backward band kernel cannot fit VMEM at width "
                        f"nx={nx}"
                        + (" (series flavor)" if series else "")
                        + f": minimum-band scratch "
                        f"{_adjoint_scratch_bytes(model, nx, 8, series) >> 20}"
                        f" MiB > {_ADJ_SCRATCH_LIMIT >> 20} MiB — "
                        "engine='auto' adjoint falls back to XLA "
                        "statically", where,
                        {"series": series, "nx": nx,
                         "scratch_bytes":
                             _adjoint_scratch_bytes(model, nx, 8, series)}))
                else:
                    # the default by_bwd the builder would pick at k
                    n_aux = 1 + (2 if series else 1) \
                        * len(model.zonal_settings)
                    per_row = (2 * model.n_storage + n_aux) * nx * 4
                    by = 64
                    while by > 8 and 2 * (by + 16) * per_row \
                            > 24 * 1024 * 1024:
                        by -= 8
                    findings.append(Finding(
                        "resources.adjoint_layout", "info", model.name,
                        f"adjoint kernel at production chunk k={k}"
                        + (" (series: k=1)" if series else "")
                        + f": by_bwd={by} scratch~"
                        f"{2 * (by + 16) * per_row >> 20} MiB", where,
                        {"k": 1 if series else k, "by_bwd": by,
                         "series": series}))
    else:
        nz, ny, nx = shape
        bz = pallas_generic._slab_depth_gen(model, nz, ny, nx,
                                            max(reach, 1))
        tiled = pallas_generic.tile_plan_3d(model, shape) \
            if bz is None else None
        if tiled is not None:
            findings.append(Finding(
                "resources.slab_tiled", "info", model.name,
                f"3D slab engine tiles the plane: windows of {tiled[0]} "
                f"slabs x {tiled[1]} rows at fuse={tiled[2]}", where,
                {"bz": tiled[0], "by": tiled[1], "fuse": tiled[2]}))
        elif bz is None:
            findings.append(Finding(
                "resources.slab_vmem", "warning", model.name,
                f"no whole-plane z-slab depth fits the 12 MB scratch "
                f"budget at {nz}x{ny}x{nx} ({model.n_storage} storage "
                "planes) and no tiled window the raised ceiling: generic "
                "3D engine ineligible, XLA fallback", where,
                {"n_storage": model.n_storage, "shape": list(shape)}))
        else:
            n_aux = 1 + 2 * len(model.zonal_settings)
            est = 2 * (bz + 2 * max(reach, 1)) \
                * (model.n_storage + n_aux) * ny * nx * 4
            findings.append(Finding(
                "resources.slab_layout", "info", model.name,
                f"3D slab engine: bz={bz} scratch~{est >> 20} MiB",
                where, {"bz": bz, "scratch_bytes": est}))
        # -- fused (K>=2) working sets at the PRODUCTION fusion depth -- #
        # the planners only propose configs their own fits() predicate
        # accepts, so a config exceeding its engine's budget here means
        # planner and builder have drifted apart — an error, because the
        # first TPU compile would die where the probe ladder can't see it
        K3 = pallas_generic.choose_fuse_3d(model, shape) \
            if bz is not None else 1     # a tiled plan is its own account
        if K3 >= 2:
            _, rK = pallas_generic.action_plan(model, "Iteration",
                                               fuse=K3)
            RK = max(rK, 1)
            bzK = pallas_generic._slab_depth_gen(
                model, nz, ny, nx, RK, n_aux=1,
                budget=pallas_generic._FUSED3D_BUDGET)
            estK = None if bzK is None else \
                2 * (bzK + 2 * RK) * (model.n_storage + 1) * ny * nx * 4
            if bzK is None or estK > pallas_generic._FUSED3D_BUDGET:
                findings.append(Finding(
                    "resources.fused_vmem", "error", model.name,
                    f"generic 3D planner picked fuse={K3} but no slab "
                    f"depth fits the "
                    f"{pallas_generic._FUSED3D_BUDGET >> 20} MB fused "
                    f"scratch budget at {nz}x{ny}x{nx}: planner/builder "
                    "drift, first TPU compile will fail", where,
                    {"fuse": K3, "reach": RK}))
            else:
                findings.append(Finding(
                    "resources.fused_slab", "info", model.name,
                    f"generic 3D fused engine: fuse={K3} bz={bzK} "
                    f"reach={RK} scratch~{estK >> 20} MiB", where,
                    {"fuse": K3, "bz": bzK, "reach": RK,
                     "scratch_bytes": estK}))
        from tclb_tpu.ops import pallas_d3q
        # the plan the engine builds: whole planes in bands of bz slabs
        # (fused_cfg), or a plane no single-step kernel holds tiled in y
        # too (tile_plan); one account, the planner's own, audits both
        tile = pallas_d3q.tile_plan(model, shape)
        whole = pallas_d3q.fused_cfg(model, shape)
        if tile is not None or whole is not None:
            bzD, byD, KD = tile or (whole[0], None, whole[1])
            by = byD if byD is not None and byD < ny else None
            rows = ny if by is None else by + 2 * pallas_d3q._HALO_Y
            said = f"(bz={bzD}, K={KD})" if by is None \
                else f"(bz={bzD}, by={by}, K={KD})"
            if not pallas_d3q._fused_fits(model, nz, ny, nx, bzD, KD,
                                          by=by):
                findings.append(Finding(
                    "resources.fused_vmem", "error", model.name,
                    f"tuned d3q planner picked {said} but "
                    f"its working set exceeds the "
                    f"{pallas_d3q._FUSED_BUDGET >> 20} MB fused budget "
                    f"at {nz}x{ny}x{nx}: planner/builder drift", where,
                    {"fuse": KD, "bz": bzD, "by": by}))
            else:
                H = bzD + 2 * KD
                estD = (2 * (model.n_storage + 1) * H * rows
                        + 2 * model.n_storage * bzD * (by or ny)) * nx * 4
                findings.append(Finding(
                    "resources.fused_slab", "info", model.name,
                    f"tuned d3q fused engine: fuse={KD} bz={bzD}"
                    + (f" by={by}" if by else "")
                    + f" scratch~{estD >> 20} MiB (+ collision "
                    "temporaries)", where,
                    {"fuse": KD, "bz": bzD, "by": by,
                     "scratch_bytes": estD}))
        # -- fused 3D backward kernel at the production chunk ----------- #
        # mirror the 2D adjoint_layout finding: evaluate the Run_b slab
        # planner at the shape production actually runs, so an infeasible
        # plan surfaces as a finding instead of a silent XLA-chain sweep
        from tclb_tpu.ops import pallas_adjoint
        if model.name.endswith("_adj") \
                and pallas_adjoint.max_chunk(model) >= 1:
            k3 = pallas_adjoint.max_chunk(model)
            plan3 = pallas_adjoint.adjoint_slab_plan(model, shape, k=k3)
            if plan3 is None:
                findings.append(Finding(
                    "resources.adjoint_vmem", "warning", model.name,
                    f"fused 3D backward: no (k, bz) fits the slab "
                    f"scratch budget at {nz}x{ny}x{nx} "
                    f"({model.n_storage} storage planes) — reverse "
                    "sweeps degrade to the XLA chain", where,
                    {"k_max": k3, "shape": list(shape)}))
            else:
                kb, bzb = plan3
                _, rb = pallas_generic.action_plan(model, "Iteration",
                                                   fuse=kb)
                Rb = max(rb, 1)
                estB = 2 * (bzb + 4 * Rb) \
                    * (2 * model.n_storage + 1) * ny * nx * 4
                findings.append(Finding(
                    "resources.adjoint_slab", "info", model.name,
                    f"fused 3D backward kernel: k={kb} bz={bzb} "
                    f"reach={Rb} scratch~{estB >> 20} MiB", where,
                    {"k": kb, "bz": bzb, "reach": Rb,
                     "scratch_bytes": estB}))
    return findings
