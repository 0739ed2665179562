"""Live observability plane tests: metrics-registry semantics, the
Prometheus text exposition, scrape-during-solve safety, flight-recorder
ring bounds + auto-dump triggers, job-correlated timelines, and the
strict no-op contract when no sink is attached.

The conftest forces 8 host devices, so the dispatcher tests here run
against a real multi-lane fleet (with injected runners where the test
needs failure, mirroring tests/test_fleet.py).
"""

import json
import os
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from tclb_tpu import telemetry
from tclb_tpu.models import get_model
from tclb_tpu.serve import Case, EnsemblePlan, FleetDispatcher, JobSpec
from tclb_tpu.serve.scheduler import DONE, Scheduler
from tclb_tpu.telemetry import events, live, report
from tclb_tpu.telemetry.http import MonitorServer
from tclb_tpu.telemetry.live import FlightRecorder, MetricsRegistry


@pytest.fixture(autouse=True)
def _sink_off():
    telemetry.disable()
    live.registry().reset()
    yield
    telemetry.disable()
    live.registry().reset()


def _channel_flags(m, ny, nx):
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    return flags


def _d2q9_plan(ny=12, nx=24, **kw):
    m = get_model("d2q9")
    return EnsemblePlan(m, (ny, nx), flags=_channel_flags(m, ny, nx),
                        base_settings={"nu": 0.05, "Velocity": 0.02}, **kw)


def _specs(plan, nus, niter=6, **kw):
    return [JobSpec(model=plan.model, shape=plan.shape,
                    case=Case(settings={"nu": v}, name=f"nu={v}"),
                    niter=niter, flags=plan.flags,
                    base_settings={"nu": 0.05, "Velocity": 0.02},
                    name=f"nu={v}", **kw) for v in nus]


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.headers.get("Content-Type", ""), \
            r.read().decode("utf-8")


# --------------------------------------------------------------------------- #
# MetricsRegistry semantics
# --------------------------------------------------------------------------- #


def test_registry_gauge_counter_histogram():
    reg = MetricsRegistry()
    reg.gauge("g", 1.5, engine="xla")
    reg.gauge("g", 2.5, engine="xla")          # gauges overwrite
    reg.count("c", 1.0, lane="0")
    reg.count("c", 2.0, lane="0")              # counters accumulate
    reg.count("c", 5.0, lane="1")              # per-label series
    reg.observe("h", 0.003)
    reg.observe("h", 0.02)
    reg.observe("h", 999.0)                    # lands in +Inf
    snap = reg.snapshot()
    assert snap["gauges"]["g{engine=xla}"] == 2.5
    assert snap["counters"]["c{lane=0}"] == 3.0
    assert snap["counters"]["c{lane=1}"] == 5.0
    h = snap["histograms"]["h"]
    assert h["count"] == 3 and h["sum"] == pytest.approx(999.023)
    reg.set_info("last", {"engine": "xla"})
    assert reg.info("last") == {"engine": "xla"}
    assert reg.info("missing", 42) == 42
    reg.reset()
    empty = reg.snapshot()
    assert empty["gauges"] == {} and empty["counters"] == {} \
        and empty["histograms"] == {} and empty["info"] == {}


def test_registry_label_order_is_canonical():
    reg = MetricsRegistry()
    reg.count("c", 1.0, a="1", b="2")
    reg.count("c", 1.0, b="2", a="1")          # same series, any kw order
    assert reg.snapshot()["counters"]["c{a=1,b=2}"] == 2.0


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.gauge("tclb_mlups", 123.0, engine="xla", model="d2q9")
    reg.count("tclb_lane_batches_total", 4, lane="0")
    reg.observe("tclb_iterate_seconds", 0.003)
    reg.observe("tclb_iterate_seconds", 0.02)
    txt = reg.to_prometheus(extra_counters={"serve.jobs.submitted": 7})
    lines = txt.splitlines()
    assert "# HELP tclb_mlups MLUPS of the last iterate span, " \
        "by engine/model" in lines
    assert "# TYPE tclb_mlups gauge" in lines
    assert 'tclb_mlups{engine="xla",model="d2q9"} 123' in lines
    assert "# TYPE tclb_lane_batches_total counter" in lines
    assert 'tclb_lane_batches_total{lane="0"} 4' in lines
    # histogram buckets are cumulative and end with +Inf/_sum/_count
    assert 'tclb_iterate_seconds_bucket{le="0.005"} 1' in lines
    assert 'tclb_iterate_seconds_bucket{le="0.025"} 2' in lines
    assert 'tclb_iterate_seconds_bucket{le="+Inf"} 2' in lines
    assert "tclb_iterate_seconds_count 2" in lines
    assert any(l.startswith("tclb_iterate_seconds_sum ") for l in lines)
    # events.counter totals surface as tclb_counter_total{name=...}
    assert 'tclb_counter_total{name="serve.jobs.submitted"} 7' in lines
    assert txt.endswith("\n")
    assert live.CONTENT_TYPE.startswith("text/plain; version=0.0.4")


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.gauge("g", 1.0, path='a\\b"c\nd')
    txt = reg.to_prometheus()
    assert 'g{path="a\\\\b\\"c\\nd"} 1' in txt.splitlines()


def test_observe_derives_metrics_from_events():
    reg = live.registry()
    live._observe({"kind": "span", "name": "iterate", "dur_s": 0.25,
                   "engine": "fused", "model": "d2q9", "mlups": 88.0,
                   "iters": 10, "nodes": 1000,
                   "iteration": 50, "ts": 123.0})
    live._observe({"kind": "span", "name": "serve.lane_batch", "lane": 2,
                   "batch": 3, "dur_s": 0.5, "stage_s": 0.1,
                   "stall_s": 0.01, "wait_s": [0.2, 0.3]})
    live._observe({"kind": "failcheck", "iteration": 5})
    live._observe({"kind": "serve.device_evicted", "lane": 2})
    live._observe({"kind": "serve.job_done", "status": "done"})
    snap = reg.snapshot()
    assert snap["gauges"]["tclb_mlups{engine=fused,model=d2q9}"] == 88.0
    assert snap["counters"]["tclb_iterations_total"] == 10
    assert snap["counters"]["tclb_node_updates_total"] == 10000
    assert snap["counters"]["tclb_lane_batches_total{lane=2}"] == 1
    assert snap["counters"]["tclb_lane_jobs_total{lane=2}"] == 3
    assert snap["counters"]["tclb_failchecks_total"] == 1
    assert snap["counters"]["tclb_devices_evicted_total{lane=2}"] == 1
    assert snap["counters"]["tclb_jobs_total{status=done}"] == 1
    assert snap["histograms"]["tclb_queue_wait_seconds"]["count"] == 2
    last = reg.info("last_iterate")
    assert last["engine"] == "fused" and last["mlups"] == 88.0


# --------------------------------------------------------------------------- #
# Strict no-op when disabled
# --------------------------------------------------------------------------- #


def test_monitor_disabled_is_strict_noop():
    assert not telemetry.enabled()
    telemetry.event("should_vanish", x=1)
    telemetry.counter("should_vanish")
    assert telemetry.counters() == {}
    assert telemetry.path() is None
    # a live subscriber flips the single-boolean gate; dropping it
    # restores the no-op path
    live.enable_live()
    assert telemetry.enabled()
    live.disable_live()
    assert not telemetry.enabled()


def test_scheduler_lifecycle_gates_telemetry():
    # the flight recorder attaches for the Scheduler's lifetime and
    # releases the gate on close
    assert not telemetry.enabled()
    sched = Scheduler(max_batch=2)
    assert telemetry.enabled()
    sched.close()
    assert not telemetry.enabled()


# --------------------------------------------------------------------------- #
# Flight recorder
# --------------------------------------------------------------------------- #


def test_flight_ring_is_bounded():
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record({"kind": "tick", "i": i})
    assert len(fr) == 8
    assert [e["i"] for e in fr.events()] == list(range(12, 20))


def test_flight_dump_on_failcheck(tmp_path):
    fr = FlightRecorder(capacity=16, dump_dir=str(tmp_path))
    fr.record({"kind": "span", "name": "iterate", "dur_s": 0.1})
    fr.record({"kind": "failcheck", "iteration": 7, "quantity": "Rho",
               "job_id": 3, "engine": "fused"})
    dumps = fr.dumps
    assert len(dumps) == 1
    path = dumps[0]
    assert os.path.basename(path) == f"flight-{os.getpid()}.jsonl"
    with open(path) as fh:
        docs = [json.loads(line) for line in fh]
    assert docs[-1]["kind"] == "flight_dump"
    assert docs[-1]["reason"] == "failcheck"
    fc = [d for d in docs if d.get("kind") == "failcheck"]
    assert fc and fc[0]["job_id"] == 3 and fc[0]["engine"] == "fused"


def test_flight_explicit_dump_with_context(tmp_path):
    fr = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
    assert fr.dump(reason="nothing_recorded") is None   # empty ring: no file
    fr.record({"kind": "serve.job_queued", "job_id": 9})
    path = fr.dump(reason="scheduler_exception", error="boom", job_ids=[9])
    with open(path) as fh:
        docs = [json.loads(line) for line in fh]
    assert docs[-1] == pytest.approx(docs[-1])  # valid json round-trip
    assert docs[-1]["reason"] == "scheduler_exception"
    assert docs[-1]["error"] == "boom" and docs[-1]["job_ids"] == [9]


def test_flight_attach_is_refcounted_and_env_gated(monkeypatch):
    fr = FlightRecorder(capacity=4)
    fr.attach()
    fr.attach()
    assert fr.attached and telemetry.enabled()
    telemetry.event("ping")
    assert len(fr) == 1
    fr.detach()
    assert fr.attached                  # one ref left
    fr.detach()
    assert not fr.attached and not telemetry.enabled()
    monkeypatch.setenv("TCLB_FLIGHT", "0")
    off = FlightRecorder(capacity=4)
    off.attach()
    assert not off.attached             # opt-out honored


def test_flight_dump_on_device_eviction(tmp_path, monkeypatch):
    """A poisoned lane must leave a readable post-mortem: the eviction
    event lands in the ring and triggers flight-<pid>.jsonl even though
    no JSONL trace was ever enabled."""
    monkeypatch.setenv("TCLB_FLIGHT_DIR", str(tmp_path))

    def bad(lane, plan, cases, niter, staged):
        raise RuntimeError("poisoned device")

    def bad_seq(lane, plan, case, niter):
        raise RuntimeError("poisoned device")

    plan = _d2q9_plan()
    fleet = FleetDispatcher(devices=jax.devices()[:1], max_batch=2,
                            retries=0, evict_after=1, batch_runner=bad,
                            sequential_runner=bad_seq)
    jobs = fleet.run(_specs(plan, (0.02, 0.03), niter=2))
    fleet.close()
    assert all(j.status != DONE for j in jobs)
    path = tmp_path / f"flight-{os.getpid()}.jsonl"
    assert path.exists(), "eviction must dump the flight ring"
    with open(path) as fh:
        docs = [json.loads(line) for line in fh]
    kinds = [d.get("kind") for d in docs]
    assert "serve.device_evicted" in kinds
    assert kinds[-1] == "flight_dump"
    assert docs[-1]["reason"] == "serve.device_evicted"


# --------------------------------------------------------------------------- #
# HTTP monitor
# --------------------------------------------------------------------------- #


def test_monitor_endpoints():
    with MonitorServer(port=0) as mon:
        st, ctype, body = _get(mon.url + "/")
        assert st == 200 and "/metrics" in body
        st, ctype, body = _get(mon.url + "/metrics")
        assert st == 200 and ctype == live.CONTENT_TYPE
        st, ctype, body = _get(mon.url + "/status")
        assert st == 200 and ctype == "application/json"
        doc = json.loads(body)
        assert doc["pid"] == os.getpid()
        assert "flight_recorder" in doc and "counters" in doc
        with pytest.raises(urllib.request.HTTPError) as ei:
            _get(mon.url + "/nope")
        assert ei.value.code == 404
    # stopped: the port no longer answers
    with pytest.raises(OSError):
        _get(mon.url + "/status")


def test_monitor_scrape_during_solve():
    """Scrapes racing a real solve must all succeed, and the metrics
    they return must reflect the solve's iterate spans; the handler
    thread never blocks on device work (hygiene check covers the
    static side, this covers the dynamic one)."""
    plan = _d2q9_plan()
    results: list = []
    stop = threading.Event()

    def scraper(url):
        while not stop.is_set():
            st1, ctype, body = _get(url + "/metrics")
            st2, _t, _b = _get(url + "/status")
            results.append((st1, st2, body))
            time.sleep(0.005)

    with MonitorServer(port=0) as mon:
        t = threading.Thread(target=scraper, args=(mon.url,), daemon=True)
        t.start()
        try:
            with Scheduler(max_batch=2) as sched:
                jobs = sched.run(_specs(plan, (0.03, 0.05, 0.07), niter=4))
        finally:
            stop.set()
            t.join(timeout=10)
    assert all(j.status == DONE for j in jobs)
    assert results and all(s1 == 200 and s2 == 200
                           for s1, s2, _ in results)
    # the last scrape saw the solve's event traffic
    assert "tclb_events_total" in results[-1][2]


def test_status_occupancy_matches_stats():
    """/status lane occupancy must track the dispatcher's own busy
    accounting (the acceptance bound is 5% vs the post-hoc table; here
    both views read the same busy_s, so they agree exactly)."""
    plan = _d2q9_plan()
    with FleetDispatcher(max_batch=2, monitor="127.0.0.1:0") as fleet:
        jobs = fleet.run(_specs(plan, (0.03, 0.05, 0.07, 0.09), niter=4))
        st, _t, body = _get(fleet.monitor_url + "/status")
        doc = json.loads(body)
    assert all(j.status == DONE for j in jobs)
    fstat = doc["fleet"]
    assert len(fstat["lanes"]) == len(fleet.lanes)
    assert fstat["jobs_submitted"] == 4
    served = {l["lane"]: l for l in fstat["lanes"]}
    for lane in fleet.lanes:
        if lane.busy_s > 0:
            got = served[lane.index]
            assert got["jobs"] == lane.jobs_served
            assert got["busy_s"] <= lane.busy_s + 1e-6
    assert sum(l["jobs"] for l in fstat["lanes"]) == 4


def test_capture_profile_is_single_flight(tmp_path):
    assert live._profile_lock.acquire(blocking=False)
    try:
        with pytest.raises(RuntimeError, match="already running"):
            live.capture_profile(0.1, outdir=str(tmp_path))
    finally:
        live._profile_lock.release()


def test_parse_monitor_spec():
    assert live.parse_monitor_spec("8080") == ("127.0.0.1", 8080)
    assert live.parse_monitor_spec(":9100") == ("127.0.0.1", 9100)
    assert live.parse_monitor_spec("0.0.0.0:9100") == ("0.0.0.0", 9100)
    for bad in ("", "host:", "host:port", "1:2:3:x", "99999"):
        with pytest.raises(ValueError):
            live.parse_monitor_spec(bad)


# --------------------------------------------------------------------------- #
# events: counters snapshots + array truncation
# --------------------------------------------------------------------------- #


def test_counters_periodic_snapshot(tmp_path, monkeypatch):
    monkeypatch.setattr(events, "COUNTER_SNAPSHOT_S", 0.0)
    trace = str(tmp_path / "t.jsonl")
    telemetry.enable(trace)
    telemetry.counter("work.done")
    telemetry.event("tick")            # piggybacks a cumulative snapshot
    telemetry.counter("work.done")
    telemetry.event("tick")
    telemetry.disable()
    with open(trace) as fh:
        evts = [json.loads(line) for line in fh]
    snaps = [e for e in evts if e.get("kind") == "counters"]
    periodic = [e for e in snaps if not e.get("final")]
    finals = [e for e in snaps if e.get("final")]
    assert periodic and periodic[0]["counters"]["work.done"] == 1
    assert len(finals) == 1 and finals[0]["counters"]["work.done"] == 2
    # cumulative snapshots aggregate to the final total, not the sum
    assert report.summarize(evts)["counters"]["work.done"] == 2


def test_json_default_truncates_large_arrays(tmp_path):
    class Chatty:                   # non-serializable, huge repr
        def __str__(self):
            return "x" * 2000

    trace = str(tmp_path / "t.jsonl")
    telemetry.enable(trace)
    telemetry.event("blob",
                    big=np.zeros((128, 64), dtype=np.float32),
                    small=np.arange(3),
                    obj=Chatty())
    telemetry.disable()
    with open(trace) as fh:
        evts = [json.loads(line) for line in fh]
    blob = next(e for e in evts if e.get("kind") == "blob")
    assert blob["big"] == "<array shape=(128, 64) dtype=float32>"
    assert blob["small"] == [0, 1, 2]       # small arrays stay inline
    assert blob["obj"].endswith("chars)") and len(blob["obj"]) < 600


def test_failcheck_stamps_job_context(tmp_path):
    trace = str(tmp_path / "t.jsonl")
    telemetry.enable(trace)
    with telemetry.job_context(42):
        telemetry.failcheck(iteration=9, quantity="Rho", n_bad=3,
                            engine="fused")
    telemetry.failcheck(iteration=10, quantity="Rho", n_bad=1,
                        engine="xla")
    telemetry.disable()
    with open(trace) as fh:
        evts = [json.loads(line) for line in fh]
    fcs = [e for e in evts if e.get("kind") == "failcheck"]
    assert fcs[0]["job_id"] == 42 and fcs[0]["engine"] == "fused"
    assert "job_id" not in fcs[1]


# --------------------------------------------------------------------------- #
# Job-correlated timeline (report --job)
# --------------------------------------------------------------------------- #


def test_job_timeline_over_fleet_trace(tmp_path, capsys):
    trace = str(tmp_path / "fleet.jsonl")
    telemetry.enable(trace)
    plan = _d2q9_plan()
    with FleetDispatcher(max_batch=2) as fleet:
        jobs = fleet.run(_specs(plan, (0.03, 0.05), niter=3))
    telemetry.disable()
    assert all(j.status == DONE for j in jobs)

    jid = jobs[0].id
    rc = report.main(["report", trace, "--job", str(jid)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "queued" in out and "done" in out
    assert "dispatched" in out or "staged" in out

    rc = report.main(["report", trace, "--job", "999999"])
    capsys.readouterr()
    assert rc == 3                       # no events for that job


def test_job_timeline_includes_degrades(tmp_path, capsys):
    """A job that fails its batch and degrades to sequential must show
    the degrade and the retry count in its timeline."""
    trace = str(tmp_path / "deg.jsonl")
    telemetry.enable(trace)
    calls = {"n": 0}

    def flaky_batch(lane, plan, cases, niter, staged):
        raise RuntimeError("batch always fails")

    def seq_ok(lane, plan, case, niter):
        calls["n"] += 1
        return "ok"

    plan = _d2q9_plan()
    fleet = FleetDispatcher(devices=jax.devices()[:2], max_batch=2,
                            retries=0, evict_after=100,
                            batch_runner=flaky_batch,
                            sequential_runner=seq_ok)
    jobs = fleet.run(_specs(plan, (0.03,), niter=2))
    fleet.close()
    telemetry.disable()
    assert jobs[0].status == DONE and calls["n"] == 1

    rc = report.main(["report", trace, "--job", str(jobs[0].id)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "degraded" in out and "done" in out


# --------------------------------------------------------------------------- #
# Drain hooks: shutdown work chained ahead of SIGTERM death
# --------------------------------------------------------------------------- #


def test_drain_hooks_run_and_claim_sigterm(tmp_path, monkeypatch):
    """A registered drain hook runs on SIGTERM before the flight dump;
    a truthy return claims the shutdown so _on_sigterm returns (clean
    exit path) instead of re-raising the signal."""
    monkeypatch.setenv("TCLB_FLIGHT_DIR", str(tmp_path))
    ran = []
    live.register_drain_hook("svc", lambda reason: ran.append(reason)
                             or True)
    try:
        # call the handler directly: with the hook claiming, it must
        # NOT fall through to the re-raise (which would kill pytest)
        live._on_sigterm(15, None)
    finally:
        live.unregister_drain_hook("svc")
    assert ran == ["sigterm"]


def test_drain_hooks_unclaimed_and_errors_contained(tmp_path,
                                                    monkeypatch):
    """run_drain_hooks returns False when no hook claims; a raising hook
    is contained (the shutdown path must not crash) and later hooks
    still run, in registration order."""
    monkeypatch.setenv("TCLB_FLIGHT_DIR", str(tmp_path))
    order = []

    def boom(reason):
        order.append("boom")
        raise RuntimeError("drain hook exploded")

    live.register_drain_hook("a", boom)
    live.register_drain_hook("b", lambda r: order.append("b"))  # falsy
    try:
        assert live.run_drain_hooks("test") is False
        assert order == ["boom", "b"]
        live.register_drain_hook("c", lambda r: True)
        assert live.run_drain_hooks("test") is True
    finally:
        live.unregister_drain_hook("a")
        live.unregister_drain_hook("b")
        live.unregister_drain_hook("c")


def test_drain_hook_unregister_is_exact():
    """unregister(name, fn) only evicts that exact fn — a closing
    component cannot evict its replacement — and last registration per
    name wins."""
    first, second = (lambda r: "one"), (lambda r: "two")
    live.register_drain_hook("gw", first)
    live.register_drain_hook("gw", second)        # replaces first
    live.unregister_drain_hook("gw", first)       # stale: no-op
    try:
        assert live.run_drain_hooks("x") is True  # second still wired
    finally:
        live.unregister_drain_hook("gw", second)
    assert live.run_drain_hooks("x") is False


def test_metrics_registry_concurrent_observe_and_scrape():
    """Satellite stress for the registry lock discipline: 8 writer
    threads x 10k events racing /metrics scrape threads.  Counters are
    lock-guarded read-modify-write — any unguarded window would lose
    increments; any iteration-during-mutation bug would raise in the
    scrapers.  Asserts the exact total and zero exceptions."""
    reg = live.registry()
    n_threads, n_events = 8, 10_000
    errors = []
    done = threading.Event()

    def writer(idx):
        try:
            for i in range(n_events):
                live._observe({"kind": "stress", "idx": idx, "i": i})
        except Exception as e:  # noqa: BLE001 — surfaced in the assert
            errors.append(e)

    def scraper():
        try:
            while not done.is_set():
                text = reg.to_prometheus()
                assert "tclb_events_total" in text or text == "" or True
                reg.snapshot()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    writers = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    scrapers = [threading.Thread(target=scraper) for _ in range(2)]
    for t in scrapers + writers:
        t.start()
    for t in writers:
        t.join(timeout=120.0)
    done.set()
    for t in scrapers:
        t.join(timeout=30.0)
    assert not errors, errors
    assert all(not t.is_alive() for t in writers + scrapers)
    snap = reg.snapshot()
    assert snap["counters"]["tclb_events_total{kind=stress}"] == \
        n_threads * n_events
