"""serve/ subsystem tests: the batched ensemble engine's bit-parity
contract (plain + zonal-settings models), the compiled-executable cache
(fingerprint keys, LRU eviction, env-var capacity), the job scheduler's
fault tolerance (retry -> degrade, timeouts surface as failed jobs, not
hung callers), the sweep CLI's param expansion, the checkpoint shard
codecs that ride along in this PR, the ensemble_unsafe hygiene check,
and the telemetry Serving table.
"""

import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu import checkpoint as ckpt
from tclb_tpu import telemetry
from tclb_tpu.analysis import hygiene
from tclb_tpu.checkpoint import CheckpointManager, manifest as mf, writer
from tclb_tpu.control.sweep import expand_cases, load_setup, parse_param
from tclb_tpu.core.lattice import Lattice
from tclb_tpu.models import get_model
from tclb_tpu.serve import (Case, CompiledCache, EnsemblePlan, JobSpec,
                            JobTimeout, Scheduler, run_ensemble)
from tclb_tpu.serve.scheduler import DONE, FAILED, PENDING
from tclb_tpu.telemetry import report

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _sink_off():
    """Telemetry is process-global: every test starts and ends disabled."""
    telemetry.disable()
    yield
    telemetry.disable()


def _channel_flags(m, ny, nx):
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    return flags


def _d2q9_plan(ny=12, nx=24, **kw):
    m = get_model("d2q9")
    return EnsemblePlan(m, (ny, nx), flags=_channel_flags(m, ny, nx),
                        base_settings={"nu": 0.05, "Velocity": 0.02}, **kw)


def _assert_case_matches(batched, seq):
    """Bit-parity: the batched run's per-case output equals the
    sequential single-case run exactly — fields, clock and globals."""
    np.testing.assert_array_equal(np.asarray(batched.state.fields),
                                  np.asarray(seq.state.fields))
    assert int(np.asarray(batched.state.iteration)) \
        == int(np.asarray(seq.state.iteration))
    assert batched.globals == seq.globals


# --------------------------------------------------------------------------- #
# Ensemble engine: bit-parity
# --------------------------------------------------------------------------- #


def test_ensemble_parity_d2q9():
    plan = _d2q9_plan()
    cases = [Case(settings={"nu": v}, name=f"nu={v}")
             for v in (0.02, 0.05, 0.11)]
    batched = plan.run(cases, niter=10)
    assert [r.case.name for r in batched] == [c.name for c in cases]
    for b, c in zip(batched, cases):
        _assert_case_matches(b, plan.run_sequential(c, 10))


def test_ensemble_parity_zonal_kuper():
    """A zonal-settings model with per-case zone-table differences: the
    kuper drop with each case carrying its own drop density."""
    n = 16
    m = get_model("d2q9_kuper")
    flags = np.full((n, n), m.flag_for("MRT"), dtype=np.uint16)
    yy, xx = np.mgrid[0:n, 0:n]
    drop = (yy - n / 2) ** 2 + (xx - n / 2) ** 2 < (n / 4) ** 2
    flags[drop] = m.flag_for("MRT", zone=1)
    plan = EnsemblePlan(m, (n, n), flags=flags, base_settings={
        "omega": 1.0, "Temperature": 0.56, "FAcc": 1.0, "Magic": 0.01,
        "MagicA": -0.152, "MagicF": -2.0 / 3.0, "Density": 3.26})
    cases = [Case(zonal={("Density", 1): v}, name=f"rho={v}")
             for v in (0.0145, 0.02, 0.05)]
    batched = plan.run(cases, niter=10)
    # the per-case zone tables actually differ (the test has teeth)
    assert not np.array_equal(np.asarray(batched[0].state.fields),
                              np.asarray(batched[1].state.fields))
    for b, c in zip(batched, cases):
        _assert_case_matches(b, plan.run_sequential(c, 10))


def test_ensemble_parity_through_cache():
    """The AOT-compiled path (what serving actually dispatches) keeps
    the same bit-parity as the jit path."""
    plan = _d2q9_plan()
    cache = CompiledCache(capacity=4)
    cases = [Case(settings={"nu": v}) for v in (0.03, 0.07)]
    for b, c in zip(plan.run(cases, niter=8, cache=cache), cases):
        _assert_case_matches(b, plan.run_sequential(c, 8))
    assert cache.stats()["misses"] == 1


def test_ensemble_vmap_mode_runs():
    """mode='vmap' is the throughput engine: no parity promise, but it
    must run, keep per-case independence and tag itself distinctly."""
    plan = _d2q9_plan(mode="vmap")
    assert ",vmap,b=2]" in plan.engine_tag(2)
    res = plan.run([Case(settings={"nu": 0.02}),
                    Case(settings={"nu": 0.2})], niter=5)
    assert all(np.isfinite(np.asarray(r.state.fields)).all() for r in res)
    assert not np.array_equal(np.asarray(res[0].state.fields),
                              np.asarray(res[1].state.fields))


def test_case_params_matches_set_setting():
    """Per-case params derive with the exact set_setting host math —
    including derived-setting updates (nu -> omega etc.)."""
    m = get_model("d2q9")
    plan = _d2q9_plan()
    lat = Lattice(m, plan.shape, dtype=plan.dtype,
                  settings={"nu": 0.05, "Velocity": 0.02})
    lat.set_setting("nu", 0.123)
    from tclb_tpu.serve.ensemble import case_params
    p = case_params(m, plan.base_params, Case(settings={"nu": 0.123}),
                    plan.dtype)
    np.testing.assert_array_equal(np.asarray(p.settings),
                                  np.asarray(lat.params.settings))
    np.testing.assert_array_equal(np.asarray(p.zone_table),
                                  np.asarray(lat.params.zone_table))


def test_run_ensemble_requires_shape():
    with pytest.raises(ValueError, match="shape"):
        run_ensemble(get_model("d2q9"), [Case()], 1)


# --------------------------------------------------------------------------- #
# Compiled-executable cache
# --------------------------------------------------------------------------- #


def test_cache_hits_across_plan_rebuilds():
    """Keys on Model.fingerprint + program shape, never object id(): a
    second plan built from scratch for the same class reuses the first
    plan's executable."""
    cache = CompiledCache(capacity=4)
    case = [Case(settings={"nu": 0.04})]
    _d2q9_plan().run(case, niter=6, cache=cache)
    _d2q9_plan().run(case, niter=6, cache=cache)
    s = cache.stats()
    assert (s["hits"], s["misses"]) == (1, 1)


def test_cache_distinct_programs_miss():
    cache = CompiledCache(capacity=8)
    plan = _d2q9_plan()
    case = [Case(settings={"nu": 0.04})]
    plan.run(case, niter=6, cache=cache)
    plan.run(case, niter=7, cache=cache)          # different static niter
    plan.run(case * 2, niter=6, cache=cache)      # different batch
    assert cache.stats() == {"hits": 0, "misses": 3, "evictions": 0,
                             "size": 3, "capacity": 8}


def test_cache_lru_eviction():
    cache = CompiledCache(capacity=1)
    plan = _d2q9_plan()
    case = [Case(settings={"nu": 0.04})]
    plan.run(case, niter=6, cache=cache)
    plan.run(case * 2, niter=6, cache=cache)      # evicts the b=1 entry
    plan.run(case, niter=6, cache=cache)          # miss again
    s = cache.stats()
    assert (s["misses"], s["evictions"], s["size"]) == (3, 2, 1)


def test_cache_capacity_from_env(monkeypatch):
    monkeypatch.setenv("TCLB_SERVE_CACHE_CAP", "3")
    assert CompiledCache().capacity == 3


# --------------------------------------------------------------------------- #
# Scheduler: binning, fault tolerance, timeouts
# --------------------------------------------------------------------------- #


def _specs(plan, nus, **kw):
    return [JobSpec(model=plan.model, shape=plan.shape,
                    case=Case(settings={"nu": v}, name=f"nu={v}"),
                    niter=6, flags=plan.flags,
                    base_settings={"nu": 0.05, "Velocity": 0.02},
                    name=f"nu={v}", **kw) for v in nus]


def test_scheduler_bins_one_batch_bit_exact():
    plan = _d2q9_plan()
    cache = CompiledCache(capacity=4)
    with Scheduler(max_batch=4, cache=cache, autostart=False) as sched:
        jobs = sched.run(_specs(plan, (0.02, 0.05, 0.11)))
    assert [j.status for j in jobs] == [DONE] * 3
    assert all(j.attempts == 1 and not j.degraded for j in jobs)
    # the whole burst binned into ONE batched dispatch (one compile)
    assert cache.stats()["misses"] == 1
    for j in jobs:
        _assert_case_matches(j.result(),
                             plan.run_sequential(j.spec.case, 6))


def test_scheduler_retry_then_succeed():
    calls = {"n": 0}

    def flaky(plan, cases, niter):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected transient failure")
        return ["ok"] * len(cases)

    with Scheduler(max_batch=4, retries=2, batch_runner=flaky,
                   autostart=False) as sched:
        jobs = sched.run(_specs(_d2q9_plan(), (0.02, 0.05)))
    assert calls["n"] == 2
    assert [j.status for j in jobs] == [DONE] * 2
    assert all(j.attempts == 2 and not j.degraded for j in jobs)
    assert jobs[0].result() == "ok"


def test_scheduler_degrades_to_sequential_after_retries():
    """Batched compile poisoned -> bounded retries -> every job served
    individually on the sequential path, marked degraded, still DONE."""
    seen = []

    def broken(plan, cases, niter):
        raise RuntimeError("injected poisoned batch")

    def seq(plan, case, niter):
        seen.append(case.name)
        return f"seq:{case.name}"

    streamed = []
    with Scheduler(max_batch=4, retries=1, batch_runner=broken,
                   sequential_runner=seq, on_result=streamed.append,
                   autostart=False) as sched:
        jobs = sched.run(_specs(_d2q9_plan(), (0.02, 0.05, 0.11)))
    assert [j.status for j in jobs] == [DONE] * 3
    assert all(j.degraded and j.attempts == 2 for j in jobs)
    assert jobs[1].result() == "seq:nu=0.05"
    assert seen == ["nu=0.02", "nu=0.05", "nu=0.11"]
    assert [j.id for j in streamed] == [j.id for j in jobs]


def test_scheduler_per_job_failure_does_not_kill_batchmates():
    def broken(plan, cases, niter):
        raise RuntimeError("no batch today")

    def seq(plan, case, niter):
        if case.name == "nu=0.05":
            raise RuntimeError("this one case is genuinely bad")
        return "ok"

    with Scheduler(max_batch=4, retries=0, batch_runner=broken,
                   sequential_runner=seq, autostart=False) as sched:
        jobs = sched.run(_specs(_d2q9_plan(), (0.02, 0.05, 0.11)))
    assert [j.status for j in jobs] == [DONE, FAILED, DONE]
    with pytest.raises(RuntimeError, match="genuinely bad"):
        jobs[1].result()


def test_scheduler_timeout_is_failed_not_hung():
    def stuck(plan, cases, niter):
        time.sleep(5.0)
        return ["late"] * len(cases)

    with Scheduler(max_batch=2, batch_runner=stuck) as sched:
        job = sched.submit(_specs(_d2q9_plan(), (0.02,),
                                  timeout_s=0.3)[0])
        t0 = time.monotonic()
        with pytest.raises(JobTimeout):
            job.result()
        assert time.monotonic() - t0 < 2.0
        assert job.status == FAILED


def test_scheduler_close_sweeps_inflight_past_deadline():
    """The close(wait=True) vs in-flight-timeout race: the worker is
    stuck inside a batch whose job deadline passes while close() is
    draining.  close must not return leaving the job PENDING forever —
    it sweeps in-flight jobs past their deadline into JobTimeout, so a
    caller that trusted close() never hangs on result() afterwards."""
    release = time.monotonic() + 3.0

    def stuck(plan, cases, niter):
        while time.monotonic() < release:   # worker wedged mid-batch
            time.sleep(0.05)
        return ["late"] * len(cases)

    sched = Scheduler(max_batch=2, batch_runner=stuck)
    job = sched.submit(_specs(_d2q9_plan(), (0.02,), timeout_s=0.2)[0])
    time.sleep(0.4)                          # rot past the deadline
    t0 = time.monotonic()
    sched.close(wait=True, join_timeout=0.5)
    assert time.monotonic() - t0 < 5.0       # close returned, not hung
    assert job.status == FAILED
    with pytest.raises(JobTimeout, match="during close"):
        job.result(timeout=0.1)


def test_scheduler_close_leaves_undeadlined_jobs_pending():
    """Queued jobs with no timeout_s are NOT swept by close — a late
    background finish may still legitimately flip them (the documented
    Job.result() semantics); close only resolves the timeout race."""
    with Scheduler(max_batch=2, autostart=False) as sched:
        job = sched.submit(_specs(_d2q9_plan(), (0.02,))[0])
    # never started, no deadline: still pending, error-free
    assert job.status == PENDING and job.error is None


def test_scheduler_expires_jobs_that_rotted_in_queue():
    specs = _specs(_d2q9_plan(), (0.02,), timeout_s=0.05)
    with Scheduler(max_batch=2, autostart=False) as sched:
        job = sched.submit(specs[0])
        time.sleep(0.2)              # rot past the deadline, then start
        sched.start()
        with pytest.raises(JobTimeout, match="expired in queue"):
            job.result(timeout=10.0)
    assert job.status == FAILED


def test_scheduler_incompatible_specs_split_batches():
    plan = _d2q9_plan()
    cache = CompiledCache(capacity=4)
    specs = _specs(plan, (0.02, 0.05))
    specs[1].niter = 7               # different program class
    with Scheduler(max_batch=4, cache=cache, autostart=False) as sched:
        jobs = sched.run(specs)
    assert [j.status for j in jobs] == [DONE] * 2
    assert cache.stats()["misses"] == 2


# --------------------------------------------------------------------------- #
# Sweep: param expansion + CLI
# --------------------------------------------------------------------------- #


def test_parse_param_range_and_list():
    name, vals = parse_param("nu=0.01:0.05:5")
    assert name == "nu" and len(vals) == 5
    assert np.allclose([float(v) for v in vals],
                       np.linspace(0.01, 0.05, 5))
    assert parse_param("Velocity=1,2") == ("Velocity", ["1", "2"])
    for bad in ("nu", "nu=", "=3", "nu=1:2", "nu=1:2:0"):
        with pytest.raises(ValueError):
            parse_param(bad)


def test_expand_cases_product_and_zones():
    setup = load_setup(os.path.join(REPO, "example", "drop.xml"))
    assert setup.model.name == "d2q9_kuper"
    assert "zdrop" in setup.zone_names
    cases = expand_cases(setup, ["Magic=0.01,0.02",
                                 "Density-zdrop=0.0145:0.05:3"])
    assert len(cases) == 6           # 2 x 3 cartesian product
    zid = setup.zone_names["zdrop"]
    assert cases[0].settings == {"Magic": 0.01}
    assert ("Density", zid) in cases[0].zonal
    assert "Density@" in cases[0].name and "Magic=" in cases[0].name
    with pytest.raises(ValueError, match="settings-zone"):
        expand_cases(setup, ["Density-nosuch=1"])
    with pytest.raises(ValueError, match="no setting"):
        expand_cases(setup, ["NotASetting=1"])
    assert expand_cases(setup, [])[0].name == "case0"


def test_sweep_cli_end_to_end(tmp_path):
    """The CI smoke invariant: 4 cases at batch 2 share one compiled
    executable — the second batch hits the cache."""
    out = subprocess.run(
        [sys.executable, "-m", "tclb_tpu", "sweep",
         os.path.join(REPO, "example", "cavity.xml"),
         "--param", "nu=0.1,0.12,0.14,0.16", "--iters", "2",
         "--batch", "2"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["model"] == "d2q9_kuper" and doc["iterations"] == 2
    assert [c["status"] for c in doc["cases"]] == ["done"] * 4
    assert doc["cases"][0]["settings"] == {"nu": 0.1}
    assert all(np.isfinite(v) for c in doc["cases"]
               for v in c["globals"].values())
    assert doc["cache"]["misses"] == 1 and doc["cache"]["hits"] >= 1


# --------------------------------------------------------------------------- #
# Checkpoint shard codecs
# --------------------------------------------------------------------------- #


def _small_lattice():
    m = get_model("d2q9")
    lat = Lattice(m, (8, 16), dtype=jnp.float64,
                  settings={"nu": 0.05, "Velocity": 0.02})
    lat.set_flags(_channel_flags(m, 8, 16))
    lat.init()
    return lat


def test_checkpoint_zlib_roundtrip(tmp_path):
    lat = _small_lattice()
    lat.iterate(10)
    d = str(tmp_path / "ck")
    ckpt.save_checkpoint(d, lat, compress="zlib")
    assert any(f.endswith(".npy.zlib") for f in os.listdir(d))
    assert not any(f.endswith(".npy") for f in os.listdir(d))
    assert mf.verify_checkpoint(d) == []
    lat2 = _small_lattice()
    ckpt.restore_lattice(lat2, d)
    np.testing.assert_array_equal(np.asarray(lat.state.fields),
                                  np.asarray(lat2.state.fields))


def test_checkpoint_zlib_corruption_detected(tmp_path):
    lat = _small_lattice()
    d = str(tmp_path / "ck")
    ckpt.save_checkpoint(d, lat, compress="zlib")
    shard = next(os.path.join(d, f) for f in sorted(os.listdir(d))
                 if f.endswith(".npy.zlib"))
    with open(shard, "r+b") as f:     # flip one payload byte
        f.seek(-1, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    assert mf.verify_checkpoint(d) != []


def test_checkpoint_manager_compresses(tmp_path):
    lat = _small_lattice()
    lat.iterate(5)
    mgr = CheckpointManager(str(tmp_path), async_saves=False,
                            compress="zlib")
    mgr.save(lat)
    path = mgr.latest()
    assert path is not None
    assert any(f.endswith(".npy.zlib") for f in os.listdir(path))
    lat2 = _small_lattice()
    mgr.restore(lat2, path)
    np.testing.assert_array_equal(np.asarray(lat.state.fields),
                                  np.asarray(lat2.state.fields))


def test_codec_resolution_and_zstd_fallback():
    assert writer.resolve_codec(None) == "none"
    assert writer.resolve_codec("zlib") == "zlib"
    with pytest.raises(ValueError, match="unknown checkpoint codec"):
        writer.resolve_codec("lz4")
    try:
        import zstandard  # noqa: F401
        have_zstd = True
    except ImportError:
        have_zstd = False
    # zstd-without-package must degrade to an uncompressed save, never
    # fail the save
    assert writer.resolve_codec("zstd") == ("zstd" if have_zstd
                                            else "none")


def test_crc_covers_uncompressed_bytes(tmp_path):
    """The manifest CRC is over the UNCOMPRESSED npy bytes: the same
    array yields the same crc32 whatever the codec."""
    arr = np.arange(24, dtype=np.float64).reshape(4, 6)
    r0 = writer.write_npy(str(tmp_path / "a.npy"), arr)
    r1 = writer.write_npy(str(tmp_path / "b.npy"), arr, codec="zlib")
    assert r0["crc32"] == r1["crc32"]
    assert "codec" not in r0 and r1["codec"] == "zlib"
    assert r1["file"] == "b.npy.zlib"
    np.testing.assert_array_equal(
        writer.read_npy(str(tmp_path / "b.npy.zlib"), "zlib"), arr)


# --------------------------------------------------------------------------- #
# Hygiene: ensemble_unsafe
# --------------------------------------------------------------------------- #

_BAD_STAGE = '''
def stage_bgk(ctx, f):
    nu = ctx.setting("nu")
    omega = 1.0 / (3.0 * nu + 0.5)
    a = float(nu)                     # host cast of a per-case value
    b = omega.item()                  # host pull of a derived value
    if float(omega) > 1.0:            # cast AND branch on one line
        f = f * omega
    return f
'''

_CLEAN_STAGE = '''
import numpy as np
E = np.ones((9, 2))

def stage_bgk(ctx, f, i):
    c = float(E[i, 0])                # numpy stencil constant: fine
    nu = ctx.setting("nu")
    quad = None
    if quad is None:                  # is-None structure test: fine
        quad = nu
    nu = 0.05                         # strong update clears the taint
    d = float(nu)
    return f * (c + d + quad)
'''


def test_hygiene_ensemble_unsafe_fires(tmp_path):
    p = tmp_path / "badmodel.py"
    p.write_text(_BAD_STAGE)
    fs = hygiene.scan_ensemble_unsafe(paths=[str(p)])
    assert all(f.check == "hygiene.ensemble_unsafe" for f in fs)
    assert all(f.severity == "error" for f in fs)
    # float(nu), omega.item(), and BOTH violations on the if-line
    assert len(fs) == 4


def test_hygiene_ensemble_unsafe_clean_patterns(tmp_path):
    p = tmp_path / "okmodel.py"
    p.write_text(_CLEAN_STAGE)
    assert hygiene.scan_ensemble_unsafe(paths=[str(p)]) == []


def test_hygiene_ensemble_unsafe_in_check_repo():
    """The shipped model tree is clean AND the check actually runs as
    part of check_repo (a fixture-only check protects nothing)."""
    assert [f for f in hygiene.check_repo()
            if f.check == "hygiene.ensemble_unsafe"] == []


# --------------------------------------------------------------------------- #
# Telemetry: the Serving table
# --------------------------------------------------------------------------- #


def _serving_trace(batch2_outcome="ok", hits=1, misses=1):
    evts = [{"kind": "span", "name": "serve.batch", "dur_s": 0.5,
             "batch": 4, "capacity": 4, "outcome": "ok",
             "wait_s": [0.1, 0.2, 0.3, 0.4]},
            {"kind": "span", "name": "serve.batch", "dur_s": 0.5,
             "batch": 2, "capacity": 4, "outcome": batch2_outcome,
             "wait_s": [0.1, 0.5]}]
    evts += [{"kind": "span", "name": "serve.compile", "cache": "miss",
              "dur_s": 2.0}] * misses
    evts += [{"kind": "span", "name": "serve.compile", "cache": "hit",
              "dur_s": 0.001}] * hits
    return evts


def test_serving_summary():
    s = report.summarize(_serving_trace(batch2_outcome="degraded"))
    sv = s["serving"]
    assert sv["batches"] == 2 and sv["jobs"] == 6
    assert sv["occupancy_pct"] == 75.0
    assert sv["degraded_batches"] == 1
    assert sv["queue_wait_p50_s"] == pytest.approx(0.25)
    assert sv["queue_wait_p95_s"] <= 0.5
    assert sv["compile_lookups"] == 2
    assert sv["cache_hit_rate_pct"] == 50.0
    assert sv["compile_miss_s"] == pytest.approx(2.0)
    assert "serving" in report.format_text(s)
    # a trace with no serving activity renders no serving section
    assert report.summarize([])["serving"] == {}


def test_serving_compare_flags_regressions():
    base = report.summarize(_serving_trace(hits=9, misses=1))
    bad = [dict(e) for e in _serving_trace(hits=1, misses=9)]
    for e in bad:
        if e["name"] == "serve.batch":
            e["batch"] = 1            # fleet fell back to singletons
    other = report.summarize(bad)
    diff = report.compare(base, other, threshold=0.05)
    whats = {r["what"] for r in diff["regressions"]}
    assert {"batch_occupancy", "compile_cache_hit_rate"} <= whats
    assert "serving" in report.format_compare_text(diff)
    # and no serving regressions when the candidate matches the base
    same = report.compare(base, base, threshold=0.05)
    assert not {r["what"] for r in same["regressions"]} \
        & {"batch_occupancy", "compile_cache_hit_rate"}


def test_scheduler_emits_serving_spans(tmp_path):
    """Live integration: a real scheduler run under an enabled sink
    produces a trace whose report has the Serving numbers."""
    trace = str(tmp_path / "t.jsonl")
    telemetry.enable(trace)
    plan = _d2q9_plan()
    with Scheduler(max_batch=4, autostart=False) as sched:
        jobs = sched.run(_specs(plan, (0.02, 0.05)))
    cnt = dict(telemetry.counters())
    telemetry.disable()
    assert [j.status for j in jobs] == [DONE] * 2
    with open(trace) as fh:
        evts = [json.loads(line) for line in fh]
    sv = report.summarize(evts)["serving"]
    assert sv["jobs"] == 2 and sv["batches"] == 1
    assert sv["compile_lookups"] == 1
    assert sv["cache_hit_rate_pct"] == 0.0
    assert cnt.get("serve.jobs.submitted") == 2
    assert cnt.get("serve.jobs.done") == 2


# --------------------------------------------------------------------------- #
# persistent compile cache placement (tclb_tpu/compile_cache.py)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("given", ["/some/dir", None])
def test_compile_cache_placed_from_outside(monkeypatch, given):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and no directory
    set in code; unset: the fixed path inside the checkout."""
    import jax

    from tclb_tpu import compile_cache
    if given:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", given)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = []
    # record instead of applying: this process keeps its own cache state
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    got = compile_cache.place_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if given:
        assert got == given and updates == []
    else:
        assert got == os.path.join(repo, ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", got)]
    # the same answer in the next process: nothing of this one in the path
    assert got == compile_cache.place_compile_cache()
