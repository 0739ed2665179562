"""Benchmark: the ENGINE entry point (Lattice.iterate — what `tclb run`
executes), measured exactly the way the reference measures itself:
MLUPS = nx*ny*nz*iters/elapsed/1e6 (reference src/main.cpp.Rt:100-126).

Headline: d2q9 MRT channel with walls/inlet/outlet/obstacle (the reference's
karman.xml boundary family on a 1024x1024 lattice — square for steady
bandwidth measurement; karman.xml itself is 1024x100).  The solver path
auto-selects the fused Pallas kernel with the hybrid globals refresh, so this
measures the product, not a bench-only artifact.  Components (pure XLA, pure
Pallas fuse=1/2) and the 3D d3q27 cases are reported as extra keys.

Prints ONE JSON line: metric/value/unit/vs_baseline.  ``vs_baseline`` is the
achieved fraction of this chip's HBM streaming roofline for the same traffic
model the reference prints as GB/s (2 x n_storage x sizeof(real) + flag read
per node update, src/main.cpp.Rt:126) — the reference publishes no absolute
numbers (BASELINE.md), so roofline fraction is the honest comparison axis.
"""

import json
import os
import sys
import time

import numpy as np

from tclb_tpu import telemetry

# known per-chip HBM bandwidths (GB/s) — shared with the telemetry spans
# layer so a trace's vs_roofline and this file's credibility asserts can
# never drift; unknown kinds fall back to an ESTIMATE and skip the
# asserts (round-2 VERDICT Weak #5: a wrong fallback must not make the
# assert fire or silently pass on new hardware)
from tclb_tpu.telemetry.spans import HBM_GBS  # noqa: F401 (re-export)

# pinned per-case roofline-fraction floors (re-pinned BENCH_r07, the
# first run with the deep-K generic fusion, the fused kuper Run+CalcPhi
# band kernel and the engaged d3q27 z-slab planner).  The bench exits
# nonzero when a case lands more than 5% below its floor — same
# contract as the adjoint_regressed guard: the JSON still prints (a
# regression hunt needs the numbers), the exit code fails the run.
# Only enforced where the chip's roofline is known (TPU).
BENCH_FLOORS = {
    "solver_vs_roofline": 0.90,
    "karman_vs_roofline": 0.90,
    # 0.43 -> 0.60: the fused Run+CalcPhi kernel retires the second
    # HBM round trip the gradient stencil used to cost every step
    "kuper_drop_vs_roofline": 0.60,
    "heat_adj_vs_roofline": 0.88,
    # 0.75 -> 0.78: fused_cfg now engages (K>=2) at the bench shape
    # instead of silently demoting the cumulant to single-step slabs
    "d3q27_vs_roofline": 0.78,
    "d3q19_vs_roofline": 0.80,
    "d3q19_heat_vs_roofline": 0.66,
    # 3D adjoint tentpole: fused z-slab backward (Run_b band kernel)
    # vs the Pallas-forward/XLA-backward hybrid on the same gradient.
    # The XLA reverse chain round-trips the 19-plane working set
    # through HBM per step; the fused kernel keeps the band resident —
    # under 2x means the backward kernel degraded (or silently fell
    # back to the hybrid, which the engine-tag assert catches first).
    "adjoint3d_speedup": 2.0,
    # serving: batched-32 aggregate throughput vs cached batch-1 serial
    # dispatches of the same cases (a speedup ratio, not a roofline
    # fraction) — the ensemble engine's reason to exist is amortizing
    # the per-dispatch host round trip across the batch, so a batch of
    # 32 tiny cases under 2x the serial rate means the lax.map engine
    # or the compiled-executable cache regressed.  TPU-gated like every
    # floor; the CPU smoke run prints the number informationally.
    "ensemble_speedup_b32": 2.0,
    # gradient serving: a width-8 line-search fan batched into ONE
    # dispatch of the lax.map'd VJP executable vs the same 8 evals as
    # cached batch-1 dispatches.  The grad bin exists to amortize the
    # per-dispatch round trip across the fan — under 2x the serial rate
    # means GradSpec binning or the AOT VJP cache regressed.  TPU-gated
    # like every floor; the CPU smoke prints the ratio informationally.
    "grad_batch_speedup": 2.0,
    # precision ladder: MLUPS(bf16 storage) / MLUPS(f32 storage) on the
    # same engine+geometry, measured over the default *shifted*
    # representation (DDF shifting: the per-plane w_i shift is a
    # compile-time constant folded into the existing widen/narrow
    # seams, so it moves no extra bytes).  Halving the field bytes cuts
    # the per-node traffic from 2*Q*4+2 to 2*Q*2+2, so a bandwidth-
    # bound engine must deliver close to that ratio (1.9x for d2q9) —
    # under 1.6x means the narrow path is spilling casts (or shift
    # adds) to HBM instead of folding them into the DMA pipeline.
    "bf16_effective_bw": 1.6,
    # fleet: the 16-small-cavity-job workload through the per-device
    # FleetDispatcher (one serving lane per local device, double-buffered
    # host staging) vs the single-worker Scheduler, same max_batch, both
    # warmed (serve/fleet_bench.py — the exact workload CI smokes).  N
    # real devices must buy close to N lanes' worth of throughput; 4.0
    # on 8 devices leaves headroom for binning/staging overheads.
    # TPU-gated like every floor: forced-host CPU "devices" timeshare
    # the same cores, so the CPU run prints the ratio informationally.
    "fleet_speedup_d8": 4.0,
    # staging overlap (percent of host-staging time hidden under device
    # execution, first-fill batches excluded): under 90% means batch k+1
    # device_put is no longer overlapping batch k's execute
    "fleet_staging_overlap_pct": 90.0,
}


def engine_cap(engine) -> float:
    """Physical MLUPS ceiling of an engine, as a multiple of the 1R+1W
    streaming roofline: a fuse=K engine pays one HBM round trip per K
    steps, so its credible ceiling is Kx (the VMEM-resident engines tag
    fuse=8 — one round trip per 8-step call)."""
    return float(max(telemetry.fuse_of(engine), 1))


def timed(nodes, iterate_fn, state, params, niter):
    """Time one `niter`-step chunk; returns (mlups, final_state).
    Materializes a device->host scalar INSIDE the timed region: a Python
    float cannot exist until the step chain actually executed, so
    asynchronous dispatch can't fake this.  One big chunk with one end
    checksum, so no per-chunk sync is billed to the kernel.  Warmup runs
    the same niter — niter is a static jit arg, a different value would
    recompile inside the timed region."""
    import jax.numpy as jnp
    state = iterate_fn(state, params, niter)   # warmup / compile
    float(jnp.sum(state.fields))
    t0 = time.perf_counter()
    state = iterate_fn(state, params, niter)
    checksum = float(jnp.sum(state.fields))
    dt = time.perf_counter() - t0
    assert np.isfinite(checksum), \
        f"simulation blew up inside the timed region ({checksum})"
    return nodes * niter / dt / 1e6, state


def timed_solver(lat, niter):
    """Time the engine entry point itself (Lattice.iterate: auto-selected
    fast path + hybrid globals refresh — what a user's <Solve> runs).
    Same measurement protocol as timed(), via an adapter."""
    def run(state, params, n):
        lat.state = state
        lat.iterate(n)
        return lat.state
    mlups, _ = timed(float(np.prod(lat.shape)), run,
                     lat.state, lat.params, niter)
    return mlups


def bench_d2q9(results):
    import jax
    import jax.numpy as jnp
    from tclb_tpu.core.lattice import Lattice
    from tclb_tpu.models import get_model
    from tclb_tpu.ops import pallas_d2q9

    ny = nx = int(os.environ.get("TCLB_BENCH_N", 1024))
    iters = int(os.environ.get("TCLB_BENCH_ITERS", 2000))
    m = get_model("d2q9")
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.02, "Velocity": 0.01})
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    flags[0, :] = m.flag_for("Wall")
    flags[-1, :] = m.flag_for("Wall")
    flags[ny//3:2*ny//3, nx//10:nx//5] = m.flag_for("Wall")
    flags[1:-1, 2] = m.flag_for("MRT", "Inlet")       # globals accumulate
    flags[1:-1, -3] = m.flag_for("MRT", "Outlet")
    lat.set_flags(flags)
    lat.init()
    nodes = float(ny * nx)

    # the product path: hybrid fast engine (on TPU), ~5x iterations to
    # amortize dispatch overhead of the much faster kernel
    solver_iters = iters * (5 if jax.default_backend() == "tpu" else 1)
    mlups_solver = timed_solver(lat, solver_iters)
    results["solver_mlups"] = round(mlups_solver, 1)
    results["solver_engine"] = lat._fast_name or "xla"

    mlups_xla, _ = timed(nodes, lambda s, p, n: lat._iterate(s, p, n),
                         jax.tree.map(jnp.copy, lat.state), lat.params,
                         iters)
    results["xla_mlups"] = round(mlups_xla, 1)

    mlups_pallas = mlups_fused = None
    if pallas_d2q9.supports(m, (ny, nx), jnp.float32):
        it_p = pallas_d2q9.make_pallas_iterate(m, (ny, nx))
        mlups_pallas, _ = timed(nodes, it_p, jax.tree.map(jnp.copy, lat.state),
                                lat.params, iters * 5)
        it_f = pallas_d2q9.make_pallas_iterate(m, (ny, nx), fuse=2)
        mlups_fused, _ = timed(nodes, it_f, jax.tree.map(jnp.copy, lat.state),
                               lat.params, iters * 5)
        results["pallas_mlups"] = round(mlups_pallas, 1)
        results["pallas_fused2_mlups"] = round(mlups_fused, 1)

    # the 2D cumulant family kernel (best roofline fraction in the repo)
    mc = get_model("d2q9_cumulant")
    latc = Lattice(mc, (ny, nx), dtype=jnp.float32,
                   settings={"nu": 0.02, "Velocity": 0.01,
                             "omega_bulk": 1.0})
    fc = np.full((ny, nx), mc.flag_for("BGK"), dtype=np.uint16)
    fc[:, 0] = mc.flag_for("WVelocity", "BGK")
    fc[:, -1] = mc.flag_for("EPressure", "BGK")
    fc[0, :] = fc[-1, :] = mc.flag_for("Wall")
    latc.set_flags(fc)
    latc.init()
    mlups_cum = timed_solver(latc, solver_iters)
    results["d2q9_cumulant_mlups"] = round(mlups_cum, 1)
    results["d2q9_cumulant_engine"] = latc._fast_name or "xla"

    # sharded fast path on a 1-device mesh: measures the per-step
    # ppermute + shard_map machinery overhead vs the single-device
    # kernels (multi-chip hardware is not available here; the identity
    # exchange is the overhead floor a real mesh adds per step)
    mlups_sharded = None
    try:
        from tclb_tpu.parallel.mesh import make_mesh
        mesh1 = make_mesh((ny, nx), devices=jax.devices()[:1],
                          decomposition={"y": 1, "x": 1})
        lat_s = Lattice(m, (ny, nx), dtype=jnp.float32,
                        settings={"nu": 0.02, "Velocity": 0.01},
                        mesh=mesh1)
        lat_s.set_flags(flags)
        lat_s.init()
        mlups_sharded = timed_solver(lat_s, iters * 2)
        results["sharded_1dev_mlups"] = round(mlups_sharded, 1)
        results["sharded_1dev_engine"] = lat_s._fast_name or "xla"
    except Exception as e:      # never let the overhead probe kill bench
        results["sharded_1dev_error"] = str(e)[:200]

    bytes_per_update = 2 * m.n_storage * 4 + 2
    return (ny, nx), bytes_per_update, [
        ("solver", mlups_solver,
         engine_cap(results["solver_engine"])),
        ("xla", mlups_xla, 1.0),
        ("pallas", mlups_pallas, 1.0),
        ("pallas_fused2", mlups_fused, 2.0),
        ("d2q9_cumulant", mlups_cum,
         engine_cap(results["d2q9_cumulant_engine"])),
        ("sharded_1dev", mlups_sharded,
         engine_cap(results.get("sharded_1dev_engine", "xla")))]


def bench_baseline_cases(results):
    """The driver-designated BASELINE geometries (BASELINE.md), on the
    ENGINE path at their real shapes — not friendlier stand-ins:

    * karman: the reference's headline karman.xml at 1024x100 (d2q9 MRT,
      Zou/He inlet/outlet, wedge obstacle) — the small-ny case that
      stresses the band-DMA halo amplification;
    * kuper drop: drop.xml's physics at the reference's original 512^2
      (two Density zones, 225x density ratio) on the generic engine;
    * heat_adj: the d2q9_heat_adj primal (Brinkman-penalized flow +
      temperature) at channel scale on the generic engine.
    """
    import jax
    import jax.numpy as jnp
    from tclb_tpu.core.lattice import Lattice
    from tclb_tpu.models import get_model

    on_tpu = jax.default_backend() == "tpu"
    checks = []

    # ---- karman.xml geometry: 1024 x 100 ------------------------------ #
    nx, ny = (1024, 100) if on_tpu else (128, 20)
    iters = int(os.environ.get("TCLB_BENCH_ITERS_KARMAN",
                               30000 if on_tpu else 4))
    m = get_model("d2q9")
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.02, "Velocity": 0.01})
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    if on_tpu:   # the karman.xml wedge obstacle (octagon bounding box)
        flags[30:70, 120:160] = m.flag_for("Wall")
        flags[1:-1, 5] = m.flag_for("MRT", "Inlet")
        flags[1:-1, -6] = m.flag_for("MRT", "Outlet")
    lat.set_flags(flags)
    lat.init()
    v = timed_solver(lat, iters)
    results["karman_mlups"] = round(v, 1)
    results["karman_engine"] = lat._fast_name or "xla"
    results["karman_shape"] = f"{nx}x{ny}"
    # ceiling from the selected engine's fuse tag (resident tags fuse=8:
    # one HBM round trip per 8-step call; band engines tag their planner
    # depth; XLA has no tag -> 1x)
    checks.append(("karman_solver", v,
                   engine_cap(results["karman_engine"]),
                   2 * m.n_storage * 4 + 2))

    # ---- drop.xml physics at the reference's original 512^2 ----------- #
    n = 512 if on_tpu else 32
    iters = int(os.environ.get("TCLB_BENCH_ITERS_DROP",
                               10000 if on_tpu else 4))
    mk = get_model("d2q9_kuper")
    latk = Lattice(mk, (n, n), dtype=jnp.float32,
                   settings={"omega": 1.0, "Temperature": 0.56,
                             "FAcc": 1.0, "Magic": 0.01,
                             "MagicA": -0.152, "MagicF": -2.0 / 3.0,
                             "Density": 3.2600529440452366})
    latk.set_setting("Density", 0.014500641645077492, zone=1)
    fk = np.full((n, n), mk.flag_for("MRT"), dtype=np.uint16)
    yy, xx = np.mgrid[0:n, 0:n]
    drop = (yy - n / 2) ** 2 + (xx - n / 2) ** 2 < (n / 5) ** 2
    fk[drop] = mk.flag_for("MRT", zone=1)
    latk.set_flags(fk)
    latk.init()
    v = timed_solver(latk, iters)
    results["kuper_drop_mlups"] = round(v, 1)
    results["kuper_drop_engine"] = latk._fast_name or "xla"
    checks.append(("kuper_drop_solver", v,
                   engine_cap(results["kuper_drop_engine"]),
                   2 * mk.n_storage * 4 + 2))

    # ---- heat_adj primal at channel scale ----------------------------- #
    ny2, nx2 = (512, 1024) if on_tpu else (16, 128)
    iters = int(os.environ.get("TCLB_BENCH_ITERS_HEATADJ",
                               6000 if on_tpu else 4))
    mh = get_model("d2q9_heat_adj")
    lath = Lattice(mh, (ny2, nx2), dtype=jnp.float32,
                   settings={"nu": 0.05, "InletVelocity": 0.02,
                             "FluidAlfa": 0.05})
    fh = np.full((ny2, nx2), mh.flag_for("MRT"), dtype=np.uint16)
    fh[0, :] = fh[-1, :] = mh.flag_for("Wall")
    lath.set_flags(fh)
    lath.init()
    v = timed_solver(lath, iters)
    results["heat_adj_mlups"] = round(v, 1)
    results["heat_adj_engine"] = lath._fast_name or "xla"
    checks.append(("heat_adj_solver", v,
                   engine_cap(results["heat_adj_engine"]),
                   2 * mh.n_storage * 4 + 2))
    return checks


def bench_adjoint(results):
    """Unsteady adjoint wall-clock: the Pallas primal+adjoint kernels
    (ops/pallas_adjoint custom_vjp step — the reference's tuned ``Run_b``
    analogue) vs the XLA reverse-mode, 1000-step horizon on d2q9_adj at
    512x1024.  Reported as MLUPS-primal-equivalents (nodes*niter/time —
    a gradient costs ~3 primal sweeps, so ~1/3 of the primal rate is the
    engine-quality bar)."""
    import jax
    import jax.numpy as jnp
    from tclb_tpu.adjoint import InternalTopology, make_unsteady_gradient
    from tclb_tpu.core.lattice import Lattice
    from tclb_tpu.models import get_model

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        return []
    m = get_model("d2q9_adj")
    ny, nx = 512, 1024
    niter = int(os.environ.get("TCLB_BENCH_ITERS_ADJ", 1000))
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.1, "Velocity": 0.05, "Porocity": 0.5,
                            "DragInObj": 1.0})
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    flags[128:384, 300:700] |= m.flag_for("DesignSpace")
    lat.set_flags(flags)
    lat.init()
    design = InternalTopology(m)
    theta0 = design.get(lat.state, lat.params)

    def timed_grad(engine):
        # production defaults: levels auto (no-recompute when the chunk
        # inputs fit HBM), chunked fused kernels on the pallas engine
        gf = make_unsteady_gradient(m, design, niter, levels=None,
                                    engine=engine, shape=(ny, nx))
        obj, g, _ = gf(theta0, lat.state, lat.params)
        float(obj)
        best = 0.0
        for _ in range(2):   # first post-compile call pays one-time costs
            t0 = time.perf_counter()
            obj, g, _ = gf(theta0, lat.state, lat.params)
            s = float(obj) + float(jnp.sum(g))
            dt = time.perf_counter() - t0
            assert np.isfinite(s)
            best = max(best, ny * nx * niter / dt / 1e6)
        return best

    try:
        results["adjoint_pallas_mlups"] = round(timed_grad("pallas"), 1)
        results["adjoint_xla_mlups"] = round(timed_grad("xla"), 1)
        results["adjoint_speedup"] = round(
            results["adjoint_pallas_mlups"]
            / results["adjoint_xla_mlups"], 2)
    except Exception as e:      # never let the adjoint probe kill bench
        results["adjoint_error"] = str(e)[:200]
        return []
    # wall-clock regression guard (round-4 weak #8): flag instead of
    # asserting mid-run — the full results JSON (the diagnostics a
    # regression hunt needs) still prints, and main() exits nonzero
    if results["adjoint_speedup"] <= 1.5:
        results["adjoint_regressed"] = True
    return []


def bench_adjoint3d(results):
    """3D fused-backward adjoint: the z-slab banded ``Run_b`` kernel
    (ops/pallas_adjoint ``bwd="pallas"``) vs the PR 9 hybrid (Pallas
    forward, XLA reverse chain) on the same d3q19_adj gradient.  The
    XLA chain round-trips the 19-plane working set through HBM on
    every reverse step; the fused kernel keeps the band resident in
    VMEM, so ``adjoint3d_speedup`` is floor-gated at 2.0 on TPU.  The
    engine tag is asserted first — a silent fallback to the hybrid
    would otherwise report a flattering 1.0x."""
    import jax
    import jax.numpy as jnp
    from tclb_tpu.adjoint import InternalTopology, make_unsteady_gradient
    from tclb_tpu.core.lattice import Lattice
    from tclb_tpu.models import get_model
    from tclb_tpu.ops import pallas_adjoint

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        return []   # interpret-mode 3D backward: minutes of compile
    m = get_model("d3q19_adj")
    nz, ny, nx = 32, 64, 256
    niter = int(os.environ.get("TCLB_BENCH_ITERS_ADJ3D", 200))
    lat = Lattice(m, (nz, ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.05, "Velocity": 0.02, "Porocity": 0.5,
                            "DragInObj": 1.0})
    flags = np.full((nz, ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0, :] = flags[:, -1, :] = m.flag_for("Wall")
    flags[nz // 4:3 * nz // 4, ny // 4:3 * ny // 4,
          nx // 3:2 * nx // 3] |= m.flag_for("DesignSpace")
    lat.set_flags(flags)
    lat.init()
    design = InternalTopology(m)
    theta0 = design.get(lat.state, lat.params)

    def timed_grad():
        gf = make_unsteady_gradient(m, design, niter, levels=None,
                                    engine="pallas", shape=(nz, ny, nx))
        obj, g, _ = gf(theta0, lat.state, lat.params)
        float(obj)
        best = 0.0
        for _ in range(2):
            t0 = time.perf_counter()
            obj, g, _ = gf(theta0, lat.state, lat.params)
            s = float(obj) + float(jnp.sum(g))
            dt = time.perf_counter() - t0
            assert np.isfinite(s)
            best = max(best, nz * ny * nx * niter / dt / 1e6)
        return best, gf.engine_name

    try:
        v_fused, tag = timed_grad()
        assert tag.startswith("pallas_adjoint[d3q19_adj") \
            and ",3d]" in tag, f"fused 3D backward not engaged: {tag}"
        results["adjoint3d_fused_mlups"] = round(v_fused, 1)
        results["adjoint3d_engine"] = tag
        # hybrid baseline: deny the slab planner so the auto path
        # builds the Pallas-forward / XLA-backward step (the PR 9 path)
        orig = pallas_adjoint.adjoint_slab_plan
        pallas_adjoint.adjoint_slab_plan = lambda *a, **k: None
        try:
            v_hyb, tag_h = timed_grad()
        finally:
            pallas_adjoint.adjoint_slab_plan = orig
        assert "bwd=xla" in tag_h, f"hybrid baseline not engaged: {tag_h}"
        results["adjoint3d_hybrid_mlups"] = round(v_hyb, 1)
        results["adjoint3d_speedup"] = round(v_fused / v_hyb, 2)
    except Exception as e:   # never let the 3D adjoint probe kill bench
        results["adjoint3d_error"] = str(e)[:200]
    return []


def bench_unsteady_adjoint(results):
    """Production unsteady adjoint: the revolve-checkpointed gradient
    (adjoint/revolve — binomial schedule, host-mem snapshot tier) at a
    fixed snapshot budget S, reported as gradient MLUPS-primal-
    equivalents plus the sweep's measured recompute factor (which must
    track the planner's binomial bound — a drift means the executor is
    re-advancing segments it already paid for).  CPU runs a small smoke
    geometry informationally; TPU runs the production shape."""
    import jax
    import jax.numpy as jnp
    from tclb_tpu.adjoint import InternalTopology, make_revolve_gradient
    from tclb_tpu.core.lattice import Lattice
    from tclb_tpu.models import get_model

    on_tpu = jax.default_backend() == "tpu"
    ny, nx = (512, 1024) if on_tpu else (64, 128)
    niter = int(os.environ.get("TCLB_BENCH_ITERS_REVOLVE",
                               1000 if on_tpu else 48))
    snaps = int(os.environ.get("TCLB_BENCH_REVOLVE_SNAPSHOTS", 8))
    m = get_model("d2q9_adj")
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.1, "Velocity": 0.05, "Porocity": 0.5,
                            "DragInObj": 1.0})
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    flags[ny // 4:3 * ny // 4, nx // 3:2 * nx // 3] |= \
        m.flag_for("DesignSpace")
    lat.set_flags(flags)
    lat.init()
    design = InternalTopology(m)
    theta0 = design.get(lat.state, lat.params)
    try:
        rev = make_revolve_gradient(m, design, niter, snapshots=snaps,
                                    engine="auto", shape=(ny, nx),
                                    dtype=jnp.float32)
        obj, g, _ = rev(theta0, lat.state, lat.params)
        float(obj)                                    # warmup / compile
        t0 = time.perf_counter()
        obj, g, _ = rev(theta0, lat.state, lat.params)
        s = float(obj) + float(jnp.sum(g))
        dt = time.perf_counter() - t0
        assert np.isfinite(s)
        results["unsteady_adjoint_mlups"] = round(
            ny * nx * niter / dt / 1e6, 3)
        results["unsteady_adjoint_snapshots"] = snaps
        results["unsteady_adjoint_recompute"] = round(
            rev.last["recompute_factor"], 3)
        results["unsteady_adjoint_peak_snapshots"] = \
            rev.last["peak_snapshots"]
        results["unsteady_adjoint_engine"] = rev.engine_name

        # D2D spill overhead: the identical sweep with all but one
        # snapshot forced through the peer-HBM tier (device_put onto a
        # leased fleet lane) vs the all-mem run above.  The CI gate
        # (telemetry report --compare) holds this under 5%; here it is
        # reported so the JSON row carries the measured cost.  Needs a
        # second device to park on — single-chip runs skip.
        if len(jax.devices()) >= 2:
            from tclb_tpu.serve import FleetDispatcher
            with FleetDispatcher(devices=jax.devices()[:2]) as disp:
                rev_p = make_revolve_gradient(
                    m, design, niter, snapshots=snaps, engine="auto",
                    shape=(ny, nx), dtype=jnp.float32,
                    mem_slots=1, peer_slots=snaps - 1, dispatcher=disp)
                obj_p, g_p, _ = rev_p(theta0, lat.state, lat.params)
                float(obj_p)                          # warmup / compile
                t0 = time.perf_counter()
                obj_p, g_p, _ = rev_p(theta0, lat.state, lat.params)
                sp = float(obj_p) + float(jnp.sum(g_p))
                dtp = time.perf_counter() - t0
                assert np.isfinite(sp)
                # the tier split must not change the arithmetic: the
                # bit-invariance contract is what makes the overhead
                # number a pure transport cost
                assert sp == s, "peer-tier gradient diverged from all-mem"
                results["d2d_spill_bytes"] = rev_p.last["spill_peer"]
                results["d2d_spill_overhead_pct"] = round(
                    100.0 * (dtp - dt) / dt, 2)
        else:
            results["d2d_spill_overhead_pct"] = None
    except Exception as e:   # never let the revolve probe kill bench
        results["unsteady_adjoint_error"] = str(e)[:200]
    return []


def bench_grad_batch(results):
    """Batched gradient serving: W same-class gradient evals (one
    line-search fan) through serve's grad mode — ONE dispatch of the
    lax.map'd VJP executable — vs the same W evals as cached batch-1
    dispatches.  Tiny grids are the serving regime: per-dispatch host
    round trips dominate, and batching pays one for the whole fan.
    ``grad_batch_speedup`` is floor-gated on TPU."""
    import jax.numpy as jnp
    from tclb_tpu.adjoint import InternalTopology
    from tclb_tpu.core.lattice import Lattice
    from tclb_tpu.models import get_model
    from tclb_tpu.serve import (Case, GradSpec, JobSpec, Scheduler,
                                make_grad_evaluator)

    ny, nx = 32, 64
    iters = int(os.environ.get("TCLB_BENCH_ITERS_GRADBATCH", 16))
    width = int(os.environ.get("TCLB_BENCH_GRADBATCH_W", 8))
    m = get_model("d2q9_adj")
    settings = {"nu": 0.1, "Velocity": 0.05, "Porocity": 0.5,
                "DragInObj": 1.0}
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    flags[8:24, 20:44] |= m.flag_for("DesignSpace")
    lat = Lattice(m, (ny, nx), dtype=jnp.float32, settings=settings)
    lat.set_flags(flags)
    lat.init()
    design = InternalTopology(m)
    theta0 = design.get(lat.state, lat.params)
    thetas = [jnp.clip(theta0 + 0.01 * i, 0.0, 1.0) for i in range(width)]
    sched = Scheduler(autostart=False)
    try:
        spec = JobSpec(model=m, shape=(ny, nx), case=Case(), niter=iters,
                       flags=flags, dtype=jnp.float32,
                       base_settings=settings,
                       grad=GradSpec(design=design), name="bench")
        ev = make_grad_evaluator(sched, spec)
        ev([thetas[0]])                     # compile the batch-1 VJP
        t0 = time.perf_counter()
        for th in thetas:
            out = ev([th])
            assert np.isfinite(out[0][0])
        dt_seq = time.perf_counter() - t0
        ev(thetas)                          # compile the batch-W VJP
        t0 = time.perf_counter()
        out = ev(thetas)
        assert all(np.isfinite(o) for o, _ in out)
        dt_batch = time.perf_counter() - t0
        results["grad_batch_width"] = width
        results["grad_batch_seq_evals_per_s"] = round(width / dt_seq, 2)
        results["grad_batch_evals_per_s"] = round(width / dt_batch, 2)
        results["grad_batch_speedup"] = round(dt_seq / dt_batch, 2)
        results["grad_batch_cache"] = sched.cache.stats()
    except Exception as e:   # never let the serving probe kill bench
        results["grad_batch_error"] = str(e)[:200]
    finally:
        sched.close()
    return []


def bench_d3q27(results):
    """d3q27_cumulant forced channel (the BASELINE north-star case,
    reference example/3d_channel_test_periodic_force_driven.xml geometry
    family) + a d3q19 XLA number."""
    import jax
    import jax.numpy as jnp
    from tclb_tpu.core.lattice import Lattice
    from tclb_tpu.models import get_model

    on_tpu = jax.default_backend() == "tpu"
    nz, ny, nx = (48, 48, 256) if on_tpu else (8, 16, 128)
    # long runs: the fixed per-call sync cost would otherwise dominate
    # (the 3D case is only ~0.6M nodes)
    iters = int(os.environ.get("TCLB_BENCH_ITERS3D", 4000 if on_tpu else 4))
    m = get_model("d3q27_cumulant")
    lat = Lattice(m, (nz, ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.01, "ForceX": 1e-5})
    flags = np.full((nz, ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0, :] = m.flag_for("Wall")
    flags[:, -1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    mlups = timed_solver(lat, iters)
    results["d3q27_mlups"] = round(mlups, 1)
    results["d3q27_engine"] = lat._fast_name or "xla"
    results["d3q27_shape"] = f"{nz}x{ny}x{nx}"
    # the z-slab kernels fuse K steps per HBM round trip (planner-chosen,
    # tagged fuse=K in the engine name): the credible ceiling scales with
    # the tag, same as the 2D band engines
    checks = [("d3q27_solver", mlups,
               engine_cap(results["d3q27_engine"]),
               2 * m.n_storage * 4 + 2)]

    m19 = get_model("d3q19")
    lat19 = Lattice(m19, (nz, ny, nx), dtype=jnp.float32,
                    settings={"nu": 0.01, "GravitationX": 1e-5})
    f19 = np.full((nz, ny, nx), m19.flag_for("MRT"), dtype=np.uint16)
    f19[:, 0, :] = m19.flag_for("Wall")
    f19[:, -1, :] = m19.flag_for("Wall")
    lat19.set_flags(f19)
    lat19.init()
    mlups19 = timed_solver(lat19, iters)
    results["d3q19_mlups"] = round(mlups19, 1)
    results["d3q19_engine"] = lat19._fast_name or "xla"
    checks.append(("d3q19_solver", mlups19,
                   engine_cap(results["d3q19_engine"]),
                   2 * m19.n_storage * 4 + 2))

    # a model with NO hand-tuned kernel: the registry-driven generic 3D
    # engine (multi-lattice d3q19_heat, 26 planes) — was XLA-only
    mh = get_model("d3q19_heat")
    lath = Lattice(mh, (nz, ny, nx), dtype=jnp.float32,
                   settings={"nu": 0.05, "Velocity": 0.02,
                             "FluidAlfa": 0.05})
    fh = np.full((nz, ny, nx), mh.flag_for("MRT"), dtype=np.uint16)
    fh[:, 0, :] = fh[:, -1, :] = mh.flag_for("Wall")
    lath.set_flags(fh)
    lath.init()
    mlupsh = timed_solver(lath, iters)
    results["d3q19_heat_mlups"] = round(mlupsh, 1)
    results["d3q19_heat_engine"] = lath._fast_name or "xla"
    checks.append(("d3q19_heat_solver", mlupsh,
                   engine_cap(results["d3q19_heat_engine"]),
                   2 * mh.n_storage * 4 + 2))
    return checks


def bench_ensemble(results):
    """Serving throughput: N independent tiny-d2q9 cases per dispatch
    through serve.EnsemblePlan (the bit-parity ``mode="map"`` engine,
    AOT-compiled via CompiledCache) vs the same cases served as cached
    batch-1 dispatches.  Tiny grids are the serving regime — dispatch
    latency dominates the per-case kernel time, and batching pays one
    round trip for the whole batch.  Reports aggregate and per-case
    MLUPS for batch sizes 1/8/32 plus the throughput-oriented
    ``mode="vmap"`` engine at batch 8 as an informational extra."""
    import jax.numpy as jnp
    from tclb_tpu.models import get_model
    from tclb_tpu.serve import Case, CompiledCache, EnsemblePlan

    ny = nx = int(os.environ.get("TCLB_BENCH_ENSEMBLE_N", 64))
    iters = int(os.environ.get("TCLB_BENCH_ITERS_ENSEMBLE", 50))
    m = get_model("d2q9")
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    base_settings = {"nu": 0.02, "Velocity": 0.01}
    cases = [Case(settings={"nu": 0.02 + 0.0005 * i}, name=f"c{i}")
             for i in range(32)]
    nodes = float(ny * nx)
    cache = CompiledCache(capacity=8)
    plan = EnsemblePlan(m, (ny, nx), flags=flags,
                        base_settings=base_settings)

    def timed_run(p, batch_cases):
        # same protocol as timed(): warmup compiles (and fills the
        # cache); plan.run pulls per-case globals to host, so the timed
        # region cannot return before the batch actually executed
        p.run(batch_cases, iters, cache=cache)
        t0 = time.perf_counter()
        res = p.run(batch_cases, iters, cache=cache)
        dt = time.perf_counter() - t0
        assert all(np.isfinite(v) for r in res for v in r.globals.values())
        return nodes * len(batch_cases) * iters / dt / 1e6

    # serial baseline: the 8-case workload as batch-1 dispatches of the
    # SAME cached executable (what serving looks like without binning)
    plan.run(cases[:1], iters, cache=cache)      # compile batch-1 once
    t0 = time.perf_counter()
    for c in cases[:8]:
        plan.run([c], iters, cache=cache)
    dt = time.perf_counter() - t0
    seq = nodes * 8 * iters / dt / 1e6
    results["ensemble_seq_mlups"] = round(seq, 2)

    for b in (1, 8, 32):
        v = timed_run(plan, cases[:b])
        results[f"ensemble_b{b}_mlups"] = round(v, 2)
        results[f"ensemble_b{b}_per_case_mlups"] = round(v / b, 2)
        if b > 1:
            results[f"ensemble_speedup_b{b}"] = round(v / seq, 2)

    vplan = EnsemblePlan(m, (ny, nx), flags=flags,
                         base_settings=base_settings, mode="vmap")
    results["ensemble_vmap_b8_mlups"] = round(timed_run(vplan, cases[:8]), 2)

    # precision-ladder batch caps: narrowing storage to bf16 shrinks the
    # per-case working set, so the SAME serve budget admits a deeper bin
    # (the scheduler keys bins by storage dtype+repr and recomputes this
    # cap; the shifted representation is free here — the shift is a
    # compile-time constant, not stored state, so the doubled cap holds
    # on the default shifted rung)
    from tclb_tpu.ops.fusion import ensemble_batch_cap
    sweep_n = 2048
    results["ensemble_cap_2048_f32"] = ensemble_batch_cap(
        m.n_storage, (sweep_n, sweep_n), 4)
    results["ensemble_cap_2048_bf16"] = ensemble_batch_cap(
        m.n_storage, (sweep_n, sweep_n), 2)
    results["ensemble_cap_2048_bf16_gain"] = round(
        results["ensemble_cap_2048_bf16"]
        / max(results["ensemble_cap_2048_f32"], 1), 2)
    bplan = EnsemblePlan(m, (ny, nx), flags=flags,
                         base_settings=base_settings,
                         storage_dtype=jnp.bfloat16)
    results["ensemble_bf16_b8_mlups"] = round(timed_run(bplan, cases[:8]), 2)
    results["ensemble_cache"] = cache.stats()
    return []


def bench_fleet(results):
    """Pod-scale serving: the fleet workload from serve/fleet_bench.py —
    single-worker Scheduler vs per-device FleetDispatcher throughput,
    staging overlap / occupancy from a dedicated telemetry trace, one
    large job routed to the sharded engine, and bit-parity of every
    sampled lane result against the sequential path.  With fewer than 2
    local devices the workload re-launches itself in a subprocess with 8
    forced host devices so the dispatcher logic is exercised everywhere;
    the speedup/overlap floors stay TPU-gated (virtual CPU devices
    timeshare the same cores)."""
    import subprocess

    import jax

    jobs = int(os.environ.get("TCLB_BENCH_FLEET_JOBS", 16))
    iters = int(os.environ.get("TCLB_BENCH_ITERS_FLEET", 60))
    multi = len(jax.devices()) >= 2
    if multi:
        from tclb_tpu.serve.fleet_bench import run_fleet
        doc = run_fleet(jobs=jobs, niter=iters)
    else:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
        env.pop("TCLB_TELEMETRY", None)  # keeps its own internal trace
        out = subprocess.run(
            [sys.executable, "-m", "tclb_tpu.serve.fleet_bench",
             "--jobs", str(jobs), "--niter", str(iters)],
            capture_output=True, text=True, env=env, check=True)
        doc = json.loads(out.stdout)
    assert doc.get("parity_ok"), \
        "fleet lanes lost bit-parity vs the sequential path"
    assert doc.get("devices_evicted", 0) == 0, \
        f"fleet bench evicted {doc['devices_evicted']} healthy device(s)"
    results["fleet_devices"] = doc["devices"]
    results["fleet_lanes_active"] = doc.get("lanes_active")
    results["fleet_occupancy_pct"] = doc.get("mean_occupancy_pct")
    results["fleet_route_sharded"] = doc.get("route_sharded_events")
    # floor keys only from a real multi-device run — the forced-host
    # fallback's numbers describe core timesharing, not the dispatcher
    spd = "fleet_speedup_d8" if multi else "fleet_speedup_forced_host"
    ovl = ("fleet_staging_overlap_pct" if multi
           else "fleet_staging_overlap_forced_host")
    results[spd] = doc.get("fleet_speedup_d8")
    results[ovl] = doc.get("staging_overlap_pct")
    return []


def bench_precision_ladder(results):
    """The bf16 storage ladder on its flagship case: the d2q9 channel at
    the headline bench shape, same auto-selected engine, f32 vs bf16
    storage.  ``bf16_effective_bw`` is MLUPS(bf16)/MLUPS(f32) — on a
    bandwidth-bound engine the credible ceiling is the bytes-per-node
    ratio (2*Q*4+2)/(2*Q*2+2) = 1.9x for d2q9, and the pinned floor is
    1.6x (below that the narrow path is round-tripping casts through
    HBM).  The bf16 rung runs in its default *shifted* representation
    (DDF shifting, ``core/shift.py``): the per-plane shift folds into
    the existing widen/narrow seams as compile-time constants, so the
    floor is pinned over the shifted rung — same bytes, same cap.  The
    bf16 row also gets its own roofline attribution at its own (halved)
    bytes-per-node.

    A low-Mach accuracy sidebar (the Ma~0.02 cavity from
    ``tclb_tpu.precision``, short run) records velocity-Linf for the
    raw and shifted rungs side by side — the number that justifies
    shifted-by-default."""
    import jax.numpy as jnp
    from tclb_tpu.core.lattice import Lattice
    from tclb_tpu.models import get_model

    import jax
    on_tpu = jax.default_backend() == "tpu"
    ny = nx = int(os.environ.get("TCLB_BENCH_N", 1024)) if on_tpu else 64
    iters = int(os.environ.get("TCLB_BENCH_ITERS",
                               10000 if on_tpu else 8))
    m = get_model("d2q9")

    def run(storage_dtype, storage_repr=None):
        lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                      settings={"nu": 0.02, "Velocity": 0.01},
                      storage_dtype=storage_dtype,
                      storage_repr=storage_repr)
        flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
        flags[0, :] = flags[-1, :] = m.flag_for("Wall")
        lat.set_flags(flags)
        lat.init()
        return timed_solver(lat, iters), lat._fast_name or "xla"

    v32, _ = run(None)
    v16, engine16 = run(jnp.bfloat16)          # default repr: shifted
    v16raw, _ = run(jnp.bfloat16, "raw")
    results["bf16_d2q9_mlups"] = round(v16, 1)
    results["bf16_d2q9_engine"] = engine16
    results["bf16_d2q9_repr"] = "shifted"
    results["bf16_effective_bw"] = round(v16 / v32, 3)
    results["bf16_raw_effective_bw"] = round(v16raw / v32, 3)

    from tclb_tpu.precision import compare_reprs
    err_iters = int(os.environ.get("TCLB_BENCH_ERR_ITERS", 100))
    raw_rep, shifted_rep = compare_reprs(
        "cavity", niter=err_iters, n=64, checkpoints=(err_iters,))
    results["bf16_cavity_raw_u_linf"] = float(
        f"{raw_rep['checkpoints'][-1]['u_linf']:.3g}")
    results["bf16_cavity_shifted_u_linf"] = float(
        f"{shifted_rep['checkpoints'][-1]['u_linf']:.3g}")
    return [("bf16_d2q9_solver", v16, engine_cap(engine16),
             2 * m.n_storage * 2 + 2)]


def bench_gateway(results):
    """Serving front door overhead: a parameter sweep submitted through
    the HTTP gateway (validation + journal + admission + scheduler
    rails) vs the same cases run directly on an EnsemblePlan.  The
    interesting number is the per-job overhead the network path adds —
    it should be dominated by the solve itself, with ONE compiled
    executable shared by every case either way."""
    import tempfile
    import urllib.request

    from tclb_tpu.control.sweep import expand_grid
    from tclb_tpu.gateway.http import GatewayServer
    from tclb_tpu.gateway.service import GatewayService
    from tclb_tpu.models import get_model
    from tclb_tpu.serve import EnsemblePlan

    ny = nx = int(os.environ.get("TCLB_BENCH_GATEWAY_N", 64))
    iters = int(os.environ.get("TCLB_BENCH_ITERS_GATEWAY", 50))
    n_cases = int(os.environ.get("TCLB_BENCH_GATEWAY_CASES", 8))
    grid = {"nu": f"0.02:0.08:{n_cases}"}
    nodes = float(ny * nx)

    # in-process baseline, warm AOT cache
    from tclb_tpu.serve import CompiledCache
    cache = CompiledCache(capacity=4)
    plan = EnsemblePlan(get_model("d2q9"), (ny, nx),
                        base_settings={"Velocity": 0.01})
    cases = expand_grid(grid)
    plan.run(cases, iters, cache=cache)
    t0 = time.perf_counter()
    plan.run(cases, iters, cache=cache)
    direct_s = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as root:
        srv = GatewayServer(GatewayService(root)).start()
        try:
            body = json.dumps({
                "model": "d2q9", "shape": [ny, nx], "niter": iters,
                "params": {"Velocity": 0.01}, "sweep": grid}).encode()

            def submit_and_wait():
                req = urllib.request.Request(
                    srv.url + "/v1/jobs", data=body, method="POST",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=600) as r:
                    jid = json.loads(r.read())["job"]["id"]
                with urllib.request.urlopen(
                        srv.url + f"/v1/jobs/{jid}/result?wait=600",
                        timeout=600) as r:
                    doc = json.loads(r.read())
                assert doc["job"]["status"] == "done", doc
                return doc

            submit_and_wait()        # warmup: compile via the gateway
            t0 = time.perf_counter()
            submit_and_wait()
            gw_s = time.perf_counter() - t0
            stats = srv.service.cache.stats()
        finally:
            srv.stop()

    assert stats["misses"] == 1, \
        f"gateway sweep should compile once, saw {stats['misses']} misses"
    results["gateway_direct_mlups"] = round(
        nodes * n_cases * iters / direct_s / 1e6, 2)
    results["gateway_http_mlups"] = round(
        nodes * n_cases * iters / gw_s / 1e6, 2)
    results["gateway_overhead_ms_per_job"] = round(
        1e3 * (gw_s - direct_s), 2)
    return []


def main():
    import jax

    # each bench section runs under a telemetry span (active only when
    # TCLB_TELEMETRY is set), so every BENCH row carries a trace whose
    # iterate spans attribute the row to an engine and roofline fraction
    results = {}
    with telemetry.span("bench.d2q9"):
        shape2d, bytes_d2q9, checks2d = bench_d2q9(results)
    with telemetry.span("bench.d3q27"):
        checks3d = bench_d3q27(results)
    with telemetry.span("bench.baseline_cases"):
        checks3d += bench_baseline_cases(results)
    with telemetry.span("bench.adjoint"):
        checks3d += bench_adjoint(results)
    with telemetry.span("bench.adjoint3d"):
        checks3d += bench_adjoint3d(results)
    with telemetry.span("bench.unsteady_adjoint"):
        checks3d += bench_unsteady_adjoint(results)
    with telemetry.span("bench.grad_batch"):
        checks3d += bench_grad_batch(results)
    with telemetry.span("bench.precision_ladder"):
        checks3d += bench_precision_ladder(results)
    with telemetry.span("bench.ensemble"):
        checks3d += bench_ensemble(results)
    with telemetry.span("bench.fleet"):
        checks3d += bench_fleet(results)
    with telemetry.span("bench.gateway"):
        checks3d += bench_gateway(results)

    dev = jax.devices()[0]
    hbm = HBM_GBS.get(dev.device_kind)
    results["device_kind"] = dev.device_kind
    results["roofline_known"] = hbm is not None

    def vs_roofline(mlups, bpu):
        # a device kind that is not in HBM_GBS has no roofline
        return None if hbm is None else mlups / (hbm * 1e9 / bpu / 1e6)

    # LBM is bandwidth-bound under the classical 1R+1W-per-step traffic
    # model; the temporally-fused kernel legitimately halves traffic per
    # step, so its physical ceiling is 2x that roofline.  EVERY reported
    # component must sit under its own ceiling — beyond it the timing
    # itself is broken and must not be reported.  Only assert when this
    # chip's bandwidth is actually known.
    for label, v, cap in checks2d:
        if v is None:
            continue
        r = vs_roofline(v, bytes_d2q9)
        if r is None:
            continue
        if label == "solver":
            results["solver_vs_roofline"] = round(r, 4)
        assert 0.0 < r <= cap, \
            f"{label}: {v:.0f} MLUPS = {r:.2f}x the HBM roofline on " \
            f"{dev.device_kind} (cap {cap}x): timing is not credible, " \
            "refusing to report"
    for label, v, cap, bpu in checks3d:
        if v is None:
            continue
        r = vs_roofline(v, bpu)
        if r is None:
            continue
        results[label.replace("solver", "vs_roofline")] = round(r, 4)
        assert 0.0 < r <= cap, \
            f"{label}: {v:.0f} MLUPS = {r:.2f}x roofline " \
            f"(cap {cap}x): timing not credible"

    mlups = results["solver_mlups"]
    ratio = vs_roofline(mlups, bytes_d2q9)
    ny, nx = shape2d
    print(json.dumps({
        "metric": f"MLUPS d2q9 channel {ny}x{nx} f32 (engine path)",
        "value": mlups,
        "unit": "MLUPS",
        "vs_baseline": None if ratio is None else round(ratio, 4),
        **results,
    }))
    failed = False
    if results.get("adjoint_regressed"):
        print("FAIL: pallas adjoint regressed to XLA-class "
              f"(speedup {results.get('adjoint_speedup')}x <= 1.5x)",
              file=sys.stderr)
        failed = True
    # roofline-fraction floors: only judged where the roofline itself is
    # known — on any other device no fraction is reported
    if hbm is not None:
        for key, floor in BENCH_FLOORS.items():
            got = results.get(key)
            if got is not None and got < floor * 0.95:
                print(f"FAIL: {key} = {got:.3f} dropped >5% below its "
                      f"pinned floor {floor:.2f}", file=sys.stderr)
                failed = True
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
