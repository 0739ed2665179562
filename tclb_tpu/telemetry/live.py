"""Live observability plane: in-process metrics registry + flight recorder.

Post-hoc JSONL traces (:mod:`tclb_tpu.telemetry.events`) answer "what did
this run do"; this module answers "what is this process doing *right now*"
and "what was it doing when it died" — the live counterpart of the
reference's in-situ Catalyst monitoring:

* :class:`MetricsRegistry` — gauges, monotonic counters, and fixed-bucket
  histograms derived from the already-instrumented event/span seams
  (iterate wall, MLUPS, queue wait, stage/stall, compile time).  It is a
  fan-out subscriber on :mod:`events`; the HTTP monitor
  (:mod:`tclb_tpu.telemetry.http`) serves its snapshots — the handler
  thread never touches jax or device state.
* :class:`FlightRecorder` — a bounded in-memory ring of the last ~4k
  events (deque append, no I/O), on by default inside ``serve/``, dumped
  to ``flight-<pid>.jsonl`` on failcheck, device eviction, unhandled
  dispatcher/scheduler exceptions, and SIGTERM, so a crashed serving
  process yields a post-mortem even when ``TCLB_TELEMETRY`` was never
  set.
* **status providers** — components (FleetDispatcher, Scheduler) publish
  plain-python callables that report queue depth / lane occupancy /
  inflight ages from their own thread-safe state; :func:`status_snapshot`
  assembles the ``/status`` document from those plus the registry.

Nothing here imports jax at module scope; the on-demand profiler capture
(:func:`capture_profile`) imports ``jax.profiler`` lazily on a background
thread — never on the monitor handler thread.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from tclb_tpu.telemetry import events
from tclb_tpu.telemetry import locks

_T0 = time.time()

# -- metric metadata ---------------------------------------------------------- #

#: fixed log-ish buckets for wall-time histograms (seconds)
SECONDS_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

_META = {
    "tclb_iterate_seconds": ("histogram",
                             "Wall time of iterate spans (fenced)"),
    "tclb_mlups": ("gauge",
                   "MLUPS of the last iterate span, by engine/model"),
    "tclb_iterations_total": ("counter", "Lattice iterations completed"),
    "tclb_node_updates_total": ("counter", "Lattice node updates completed"),
    "tclb_batch_seconds": ("histogram",
                           "Wall time of serve batches (scheduler and "
                           "fleet lanes)"),
    "tclb_stage_seconds": ("histogram",
                           "Host-to-device staging time per lane batch"),
    "tclb_stall_seconds": ("histogram",
                           "Staging stall exposed on the lane critical "
                           "path"),
    "tclb_queue_wait_seconds": ("histogram",
                                "Job queue wait before dispatch"),
    "tclb_compile_seconds": ("histogram",
                             "Compile (cache-miss) time of serve "
                             "executables"),
    "tclb_lane_batches_total": ("counter", "Batches served, by lane"),
    "tclb_lane_jobs_total": ("counter", "Jobs served, by lane"),
    "tclb_jobs_total": ("counter", "Serve jobs by terminal status"),
    "tclb_failchecks_total": ("counter", "NaN/Inf failcheck events"),
    "tclb_engine_fallbacks_total": ("counter", "Engine dispatch fallbacks"),
    "tclb_devices_evicted_total": ("counter",
                                   "Devices evicted from the fleet"),
    "tclb_devices_reinstated_total": ("counter",
                                      "Evicted devices probed healthy and "
                                      "returned to the fleet"),
    "tclb_faults_injected_total": ("counter",
                                   "Chaos faults injected, by point/mode"),
    "tclb_checkpoint_last_unix_ts": ("gauge",
                                     "Unix time of the last checkpoint "
                                     "save"),
    "tclb_counter_total": ("counter",
                           "Process counters from telemetry.counter(), "
                           "by name"),
    "tclb_events_total": ("counter", "Telemetry events observed, by kind"),
    "tclb_gateway_admissions_total": ("counter",
                                      "Gateway jobs admitted, by tenant"),
    "tclb_gateway_rejections_total": ("counter",
                                      "Gateway submissions rejected, by "
                                      "reason/tenant"),
    "tclb_gateway_resumed_total": ("counter",
                                   "Gateway jobs resumed from a "
                                   "checkpoint instead of iteration 0"),
    "tclb_gateway_jobs_total": ("counter",
                                "Gateway jobs finished, by terminal "
                                "status"),
    "tclb_gateway_queue_wait_seconds": ("histogram",
                                        "Gateway job wait from admission "
                                        "to first dispatch"),
    "tclb_gateway_unauthorized_total": ("counter",
                                        "Gateway submissions refused for a "
                                        "missing/wrong bearer token, by "
                                        "tenant"),
    "tclb_pool_workers_spawned_total": ("counter",
                                        "Pool worker subprocesses spawned, "
                                        "by lane"),
    "tclb_pool_workers_hung_total": ("counter",
                                     "Pool workers declared hung (missed "
                                     "heartbeat), by lane"),
    "tclb_pool_workers_killed_total": ("counter",
                                       "Pool workers killed by the "
                                       "supervisor (SIGTERM/SIGKILL "
                                       "escalation), by lane"),
    "tclb_pool_workers_restarted_total": ("counter",
                                          "Pool workers respawned after a "
                                          "crash or hang, by lane"),
    "tclb_gateway_phase_seconds": ("histogram",
                                   "Gateway job phase latency (queue_wait/"
                                   "stage/solve/d2h/e2e), by phase"),
    "tclb_cluster_hosts_enrolled_total": ("counter",
                                          "Pod host-agents enrolled, by "
                                          "host"),
    "tclb_cluster_hosts_lost_total": ("counter",
                                      "Pod host-agents lost (channel "
                                      "death or heartbeat timeout), by "
                                      "host"),
    "tclb_cluster_hosts_rejoined_total": ("counter",
                                          "Pod host-agents re-enrolled "
                                          "after a loss, by host"),
    "tclb_cluster_jobs_requeued_total": ("counter",
                                         "Cluster jobs requeued after a "
                                         "host death, by host"),
}

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def _escape_label(v: Any) -> str:
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt(v: float) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


class _Hist:
    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)  # +1 for +Inf
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        v = float(value)
        self.sum += v
        self.count += 1
        for i, le in enumerate(self.buckets):
            if v <= le:
                self.counts[i] += 1
                return
        self.counts[-1] += 1


class MetricsRegistry:
    """Thread-safe store of gauges / counters / fixed-bucket histograms.

    Series are keyed by ``(name, sorted(labels))``.  All values are plain
    python floats — reading a snapshot never touches jax, devices, or
    files, so the HTTP monitor thread can scrape mid-solve.
    """

    def __init__(self) -> None:
        self._lock = locks.make_lock("telemetry.live.MetricsRegistry._lock")
        self._gauges: dict[tuple, float] = {}
        self._counters: dict[tuple, float] = {}
        self._hists: dict[tuple, _Hist] = {}
        self._info: dict[str, Any] = {}

    @staticmethod
    def _key(name: str, labels: dict) -> tuple:
        return (name, tuple(sorted(labels.items())))

    def gauge(self, name: str, value: float, **labels: Any) -> None:
        with self._lock:
            self._gauges[self._key(name, labels)] = float(value)

    def count(self, name: str, inc: float = 1.0, **labels: Any) -> None:
        with self._lock:
            k = self._key(name, labels)
            self._counters[k] = self._counters.get(k, 0.0) + float(inc)

    def observe(self, name: str, value: float,
                buckets=SECONDS_BUCKETS, **labels: Any) -> None:
        with self._lock:
            k = self._key(name, labels)
            h = self._hists.get(k)
            if h is None:
                h = self._hists[k] = _Hist(buckets)
            h.observe(value)

    def set_info(self, key: str, value: Any) -> None:
        """Stash a plain-python status fragment (e.g. last-iterate doc)."""
        with self._lock:
            self._info[key] = value

    def info(self, key: str, default: Any = None) -> Any:
        with self._lock:
            return self._info.get(key, default)

    def snapshot(self) -> dict:
        """Plain-dict copy of every series (for /status and tests)."""
        def label_str(lbl):
            return ",".join("%s=%s" % (k, v) for k, v in lbl)
        with self._lock:
            return {
                "gauges": {"%s{%s}" % (n, label_str(l)) if l else n: v
                           for (n, l), v in self._gauges.items()},
                "counters": {"%s{%s}" % (n, label_str(l)) if l else n: v
                             for (n, l), v in self._counters.items()},
                "histograms": {
                    "%s{%s}" % (n, label_str(l)) if l else n:
                        {"count": h.count, "sum": h.sum}
                    for (n, l), h in self._hists.items()},
                "info": dict(self._info),
            }

    def reset(self) -> None:
        with self._lock:
            self._gauges.clear()
            self._counters.clear()
            self._hists.clear()
            self._info.clear()

    # -- Prometheus text exposition ------------------------------------------ #

    def to_prometheus(self,
                      extra_counters: Optional[dict] = None) -> str:
        """Render the registry (plus ``events.counter`` totals, mapped to
        ``tclb_counter_total{name=...}``) as Prometheus text exposition
        format 0.0.4."""
        with self._lock:
            gauges = dict(self._gauges)
            counters = dict(self._counters)
            hists = {k: (h.buckets, list(h.counts), h.sum, h.count)
                     for k, h in self._hists.items()}
        if extra_counters:
            for cname, v in sorted(extra_counters.items()):
                counters[("tclb_counter_total",
                          (("name", cname),))] = float(v)

        out: list[str] = []
        seen_help: set[str] = set()

        def header(name: str, mtype: str) -> None:
            if name in seen_help:
                return
            seen_help.add(name)
            meta = _META.get(name)
            if meta:
                out.append("# HELP %s %s" % (name, meta[1]))
            out.append("# TYPE %s %s" % (name, meta[0] if meta else mtype))

        def series(name: str, labels: tuple, value: float,
                   extra_label: Optional[tuple] = None) -> None:
            lbl = list(labels)
            if extra_label:
                lbl.append(extra_label)
            if lbl:
                body = ",".join('%s="%s"' % (k, _escape_label(v))
                                for k, v in lbl)
                out.append("%s{%s} %s" % (name, body, _fmt(value)))
            else:
                out.append("%s %s" % (name, _fmt(value)))

        for (name, labels), v in sorted(gauges.items()):
            header(name, "gauge")
            series(name, labels, v)
        for (name, labels), v in sorted(counters.items()):
            header(name, "counter")
            series(name, labels, v)
        for (name, labels), (buckets, counts, hsum, hcount) in \
                sorted(hists.items()):
            header(name, "histogram")
            cum = 0
            for le, c in zip(buckets, counts):
                cum += c
                series(name + "_bucket", labels, cum, ("le", _fmt(le)))
            series(name + "_bucket", labels, hcount, ("le", "+Inf"))
            series(name + "_sum", labels, hsum)
            series(name + "_count", labels, hcount)
        return "\n".join(out) + "\n"


_registry = MetricsRegistry()
_live_refs = 0
_live_lock = locks.make_lock("telemetry.live._live_lock")


def registry() -> MetricsRegistry:
    """The process-wide default registry."""
    return _registry


def _observe(doc: dict) -> None:
    """events subscriber: derive registry metrics from one event doc.
    Runs under the events lock — plain arithmetic only."""
    reg = _registry
    kind = doc.get("kind")
    reg.count("tclb_events_total", 1.0, kind=str(kind))
    if kind == "span":
        name = doc.get("name")
        dur = doc.get("dur_s")
        if name == "iterate":
            # relayed worker spans carry a worker_pid stamp — and,
            # through an enrolled host-agent, a host stamp; both become
            # labels so per-process series survive worker restarts and
            # two hosts reusing a pid stay distinct series
            wp = doc.get("worker_pid")
            wlbl: dict = {}
            if wp is not None:
                wlbl["worker_pid"] = str(wp)
                if doc.get("host") is not None:
                    wlbl["host"] = str(doc["host"])
            if dur is not None:
                reg.observe("tclb_iterate_seconds", dur, **wlbl)
            engine = str(doc.get("engine", "?"))
            model = str(doc.get("model", "?"))
            if doc.get("mlups") is not None:
                reg.gauge("tclb_mlups", doc["mlups"], engine=engine,
                          model=model, **wlbl)
            iters = doc.get("iters")
            if iters:
                reg.count("tclb_iterations_total", iters)
                nodes = doc.get("nodes")
                if nodes:
                    reg.count("tclb_node_updates_total",
                              float(nodes) * float(iters))
            last = {
                "engine": engine, "model": model,
                "mlups": doc.get("mlups"),
                "iteration": doc.get("iteration"),
                "dur_s": dur, "ts": doc.get("ts"),
            }
            if wp is not None:
                last["worker_pid"] = wp
                last["lane"] = doc.get("lane")
                if doc.get("host") is not None:
                    last["host"] = doc["host"]
            reg.set_info("last_iterate", last)
        elif name in ("serve.batch", "serve.lane_batch"):
            if dur is not None:
                reg.observe("tclb_batch_seconds", dur)
            lane = doc.get("lane")
            if lane is not None:
                reg.count("tclb_lane_batches_total", 1.0, lane=str(lane))
                if doc.get("batch"):
                    reg.count("tclb_lane_jobs_total", float(doc["batch"]),
                              lane=str(lane))
            if doc.get("stage_s") is not None:
                reg.observe("tclb_stage_seconds", doc["stage_s"])
            if doc.get("stall_s") is not None:
                reg.observe("tclb_stall_seconds", doc["stall_s"])
            for w in (doc.get("wait_s") or ()):
                reg.observe("tclb_queue_wait_seconds", w)
        elif name == "serve.compile":
            if dur is not None:
                reg.observe("tclb_compile_seconds", dur)
        elif name in ("checkpoint.save", "checkpoint.restore"):
            if name == "checkpoint.save" and doc.get("ts") is not None:
                reg.gauge("tclb_checkpoint_last_unix_ts", doc["ts"])
    elif kind == "failcheck":
        reg.count("tclb_failchecks_total", 1.0)
    elif kind == "engine_fallback":
        reg.count("tclb_engine_fallbacks_total", 1.0)
    elif kind == "serve.device_evicted":
        reg.count("tclb_devices_evicted_total", 1.0,
                  lane=str(doc.get("lane", "?")))
    elif kind == "serve.device_reinstated":
        reg.count("tclb_devices_reinstated_total", 1.0,
                  lane=str(doc.get("lane", "?")))
    elif kind == "fault.injected":
        reg.count("tclb_faults_injected_total", 1.0,
                  point=str(doc.get("point", "?")),
                  mode=str(doc.get("mode", "?")))
    elif kind == "serve.job_done":
        reg.count("tclb_jobs_total", 1.0,
                  status=str(doc.get("status", "?")))
    elif kind == "gateway.admitted":
        reg.count("tclb_gateway_admissions_total", 1.0,
                  tenant=str(doc.get("tenant", "?")))
    elif kind == "gateway.unauthorized":
        reg.count("tclb_gateway_unauthorized_total", 1.0,
                  tenant=doc.get("tenant", ""))
    elif kind == "gateway.rejected":
        reg.count("tclb_gateway_rejections_total", 1.0,
                  reason=str(doc.get("reason", "?")),
                  tenant=str(doc.get("tenant", "?")))
    elif kind == "gateway.resumed":
        reg.count("tclb_gateway_resumed_total", 1.0)
    elif kind == "serve.worker_spawned":
        reg.count("tclb_pool_workers_spawned_total", 1.0,
                  lane=str(doc.get("lane", "?")))
    elif kind == "serve.worker_hung":
        reg.count("tclb_pool_workers_hung_total", 1.0,
                  lane=str(doc.get("lane", "?")))
    elif kind == "serve.worker_killed":
        reg.count("tclb_pool_workers_killed_total", 1.0,
                  lane=str(doc.get("lane", "?")))
    elif kind == "serve.worker_restarted":
        reg.count("tclb_pool_workers_restarted_total", 1.0,
                  lane=str(doc.get("lane", "?")))
    elif kind == "gateway.host_enrolled":
        reg.count("tclb_cluster_hosts_enrolled_total", 1.0,
                  host=str(doc.get("host", "?")))
    elif kind == "gateway.host_lost":
        reg.count("tclb_cluster_hosts_lost_total", 1.0,
                  host=str(doc.get("host", "?")))
    elif kind == "gateway.host_rejoined":
        reg.count("tclb_cluster_hosts_rejoined_total", 1.0,
                  host=str(doc.get("host", "?")))
    elif kind == "cluster.job_requeued":
        reg.count("tclb_cluster_jobs_requeued_total", 1.0,
                  host=str(doc.get("host", "?")))
    elif kind == "gateway.job_done":
        reg.count("tclb_gateway_jobs_total", 1.0,
                  status=str(doc.get("status", "?")))
        if doc.get("queue_wait_s") is not None:
            reg.observe("tclb_gateway_queue_wait_seconds",
                        doc["queue_wait_s"])
        # per-phase SLO histograms: one series per phase of the job's
        # door-to-result path
        for phase, field in (("queue_wait", "queue_wait_s"),
                             ("stage", "stage_s"),
                             ("solve", "solve_s"),
                             ("d2h", "d2h_s"),
                             ("e2e", "wall_s")):
            v = doc.get(field)
            if v is not None:
                reg.observe("tclb_gateway_phase_seconds", float(v),
                            phase=phase)


def enable_live() -> MetricsRegistry:
    """Subscribe the default registry to the event fan-out (refcounted);
    returns the registry."""
    global _live_refs
    with _live_lock:
        _live_refs += 1
        if _live_refs == 1:
            events.subscribe(_observe)
    return _registry


def disable_live() -> None:
    """Drop one live reference; unsubscribes the registry at zero."""
    global _live_refs
    with _live_lock:
        if _live_refs > 0:
            _live_refs -= 1
            if _live_refs == 0:
                events.unsubscribe(_observe)


def prometheus_text() -> str:
    """The full /metrics payload: registry series + process counters."""
    return _registry.to_prometheus(extra_counters=events.counters())


# -- flight recorder ---------------------------------------------------------- #

#: event kinds that trigger an automatic ring dump
DUMP_KINDS = frozenset({"failcheck", "serve.device_evicted",
                        "gateway.host_lost"})

FLIGHT_CAPACITY = 4096


class FlightRecorder:
    """Bounded in-memory ring of the last events (deque append, no I/O),
    dumped to ``flight-<pid>.jsonl`` on failcheck / eviction / unhandled
    serve exceptions / SIGTERM.  Attach/detach are refcounted so nested
    Scheduler-inside-FleetDispatcher setups share one ring."""

    def __init__(self, capacity: int = FLIGHT_CAPACITY,
                 dump_dir: Optional[str] = None) -> None:
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self._lock = locks.make_lock("telemetry.live.FlightRecorder._lock")
        self._refs = 0
        self._dumps: list[str] = []
        self._dump_dir = dump_dir

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def attached(self) -> bool:
        return self._refs > 0

    @property
    def dumps(self) -> list[str]:
        return list(self._dumps)

    def record(self, doc: dict) -> None:
        self._ring.append(doc)
        kind = doc.get("kind")
        if kind in DUMP_KINDS:
            self.dump(reason=str(kind))
        elif kind == "fault.injected":
            # crash-mode injections (error/enospc/torn) get a dump so
            # every injected failure leaves a forensic trail; `slow`
            # injections are latency, not crashes — no dump
            from tclb_tpu import faults
            if doc.get("mode") in faults.CRASH_MODES:
                self.dump(reason=f"fault.injected:{doc.get('point')}")

    def events(self) -> list[dict]:
        return list(self._ring)

    def attach(self) -> None:
        """Subscribe the ring to the event fan-out (refcounted).  Opt out
        process-wide with ``TCLB_FLIGHT=0``."""
        if os.environ.get("TCLB_FLIGHT", "1") == "0":
            return
        with self._lock:
            self._refs += 1
            if self._refs == 1:
                events.subscribe(self.record)
        _install_sigterm_handler()

    def detach(self) -> None:
        with self._lock:
            if self._refs > 0:
                self._refs -= 1
                if self._refs == 0:
                    events.unsubscribe(self.record)

    def dump(self, reason: str, **extra: Any) -> Optional[str]:
        """Write the ring (plus one trailing ``flight_dump`` marker) to
        ``flight-<pid>.jsonl`` under ``TCLB_FLIGHT_DIR`` (default: cwd).
        Returns the path, or None when the ring is empty."""
        ring = list(self._ring)
        if not ring:
            return None
        d = self._dump_dir or os.environ.get("TCLB_FLIGHT_DIR") or os.getcwd()
        path = os.path.join(d, "flight-%d.jsonl" % os.getpid())
        marker = {"kind": "flight_dump", "ts": round(time.time(), 6),
                  "reason": reason, "events": len(ring)}
        marker.update(extra)
        try:
            os.makedirs(d, exist_ok=True)
            # concurrency-ok[signal]: dumping on the dying path is the
            # flight recorder's purpose; failures are contained below
            with open(path, "w") as fh:
                for doc in ring:
                    fh.write(json.dumps(doc,
                                        default=events._json_default) + "\n")
                fh.write(json.dumps(marker,
                                    default=events._json_default) + "\n")
        except Exception:  # noqa: BLE001 — the crash path must not crash
            return None
        if path not in self._dumps:
            self._dumps.append(path)
        return path


_recorder = FlightRecorder()
_sigterm_installed = False
_prev_sigterm: Any = None


def flight_recorder() -> FlightRecorder:
    """The process-wide default flight recorder."""
    return _recorder


# -- drain hooks: shutdown work that must run before SIGTERM kills us -------- #

_drain_hooks: dict[str, Callable[[str], Any]] = {}
# reentrant: run_drain_hooks executes inside the SIGTERM handler on the
# main thread — if the signal interrupts register/unregister_drain_hook
# mid-critical-section, a plain Lock would self-deadlock the shutdown
_drain_lock = locks.make_rlock("telemetry.live._drain_lock")


def register_drain_hook(name: str, fn: Callable[[str], Any]) -> None:
    """Register shutdown work to run on SIGTERM *before* the process
    dies (stop admission, checkpoint in-flight jobs, snapshot the
    store).  ``fn(reason)`` runs on the signal-handling main thread; a
    truthy return claims the shutdown — the handler then returns instead
    of re-raising, letting the registrant drive a clean ``exit 0``.
    Last registration per name wins; hooks run in registration order."""
    with _drain_lock:
        _drain_hooks[name] = fn


def unregister_drain_hook(name: str,
                          fn: Optional[Callable] = None) -> None:
    """Remove a drain hook; with ``fn`` given, only if it is the current
    one (a closing component can't evict its replacement)."""
    with _drain_lock:
        cur = _drain_hooks.get(name)
        if cur is not None and (fn is None or cur is fn):
            del _drain_hooks[name]


def run_drain_hooks(reason: str) -> bool:
    """Run every registered drain hook (exceptions contained — the
    shutdown path must not crash); True when any hook claimed the
    shutdown."""
    with _drain_lock:
        hooks = list(_drain_hooks.items())
    claimed = False
    for name, fn in hooks:
        try:
            if fn(reason):
                claimed = True
        except Exception as e:  # noqa: BLE001 — dying cleanly beats
            try:                # dying loudly
                _recorder.dump(reason=f"drain_hook_error:{name}",
                               error=repr(e))
            except Exception:  # noqa: BLE001
                pass
    return claimed


def _on_sigterm(signum, frame):  # pragma: no cover — exercised in CI smoke
    # drain first (stop admission, checkpoint, snapshot) while the
    # process is still healthy, then dump the forensic ring; only
    # re-raise when no hook claimed the shutdown
    claimed = run_drain_hooks("sigterm")
    _recorder.dump(reason="sigterm")
    if claimed:
        return
    prev = _prev_sigterm
    if callable(prev):
        prev(signum, frame)
    else:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        os.kill(os.getpid(), signal.SIGTERM)


def _install_sigterm_handler() -> None:
    global _sigterm_installed, _prev_sigterm
    if _sigterm_installed:
        return
    if threading.current_thread() is not threading.main_thread():
        return
    try:
        _prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)
        _sigterm_installed = True
    except (ValueError, OSError):  # pragma: no cover — exotic hosts
        pass


# -- status providers --------------------------------------------------------- #

_providers: dict[str, Callable[[], dict]] = {}
_providers_lock = locks.make_lock("telemetry.live._providers_lock")


def register_status(name: str, fn: Callable[[], dict]) -> None:
    """Publish a plain-python status callable under ``name`` (last one
    wins); it must read only thread-safe python state — never jax."""
    with _providers_lock:
        _providers[name] = fn


def unregister_status(name: str,
                      fn: Optional[Callable[[], dict]] = None) -> None:
    """Remove a provider; with ``fn`` given, only if it is the current
    one (so a closing component can't evict its replacement)."""
    with _providers_lock:
        cur = _providers.get(name)
        if cur is not None and (fn is None or cur is fn):
            del _providers[name]


def status_snapshot() -> dict:
    """Assemble the ``/status`` document from registry info, process
    counters, and registered providers.  Plain python only — safe to
    call from the monitor handler thread mid-solve."""
    now = time.time()
    doc: dict[str, Any] = {
        "pid": os.getpid(),
        "time": round(now, 3),
        "uptime_s": round(now - _T0, 3),
        "telemetry": {"enabled": events.enabled(),
                      "trace": events.path()},
        "counters": events.counters(),
        "last_iterate": _registry.info("last_iterate"),
        "flight_recorder": {"attached": _recorder.attached,
                            "events": len(_recorder),
                            "dumps": _recorder.dumps},
    }
    ckpt_ts = None
    snap = _registry.snapshot()
    g = snap["gauges"].get("tclb_checkpoint_last_unix_ts")
    if g is not None:
        ckpt_ts = g
    doc["checkpoint_age_s"] = (round(now - ckpt_ts, 3)
                               if ckpt_ts is not None else None)
    with _providers_lock:
        providers = dict(_providers)
    for name, fn in providers.items():
        try:
            doc[name] = fn()
        except Exception as e:  # noqa: BLE001 — a dying component must
            doc[name] = {"error": repr(e)}   # not take /status down
    return doc


# -- on-demand profiler capture ----------------------------------------------- #

# raw on purpose: acquired by the caller thread, released by the worker
# thread — per-thread sanitizer tracking cannot model cross-thread release
_profile_lock = threading.Lock()


def capture_profile(secs: float, outdir: Optional[str] = None) -> str:
    """Start an on-demand ``jax.profiler`` capture of ``secs`` seconds on
    a background thread; returns the artifact dir immediately.  Raises
    RuntimeError if a capture is already running.  This is the only
    jax-touching path in the live plane, and it never runs on the
    monitor handler thread."""
    if not _profile_lock.acquire(blocking=False):
        raise RuntimeError("a profiler capture is already running")
    secs = max(0.1, min(float(secs), 300.0))
    if outdir is None:
        outdir = os.path.join(
            os.environ.get("TCLB_TRACE_DIR") or os.getcwd(),
            "tclb-profile-%d-%d" % (os.getpid(), int(time.time())))

    def _run():  # pragma: no cover — needs a real profiler backend
        try:
            import jax
            jax.profiler.start_trace(outdir)
            time.sleep(secs)
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 — capture failure is non-fatal
            pass
        finally:
            _profile_lock.release()

    threading.Thread(target=_run, name="tclb-profile-capture",
                     daemon=True).start()
    return outdir


def parse_monitor_spec(spec: str) -> tuple[str, int]:
    """Parse ``--monitor [host]:port`` (``8080``, ``:8080``,
    ``0.0.0.0:9100``) into ``(host, port)``; host defaults to
    127.0.0.1."""
    s = str(spec).strip()
    host, sep, port = s.rpartition(":")
    if not sep:
        host, port = "", s
    host = host or "127.0.0.1"
    try:
        p = int(port)
    except ValueError:
        raise ValueError("--monitor expects [host]:port, got %r" % spec)
    if not (0 <= p <= 65535):
        raise ValueError("--monitor port out of range: %r" % spec)
    return host, p
