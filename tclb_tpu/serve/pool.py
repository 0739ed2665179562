"""Process-isolated worker pool: supervision, hang detection, backoff.

One solver worker **subprocess** per lane (see
:mod:`tclb_tpu.serve.worker`) — the process-isolation analogue of the
reference TCLB's MPI rank.  The failure unit becomes one worker: a hung
XLA compile, a wedged device, or a native crash kills (at most) one
child process, and the supervisor restarts it while sibling lanes keep
serving and the gateway front door stays responsive.

Supervision contract, per worker:

* **heartbeats** — workers beat *on progress* (once per solve chunk);
  a beat older than ``heartbeat_timeout_s`` mid-job is a hang
  (``serve.worker_hung``), and the worker is killed;
* **escalation** — SIGTERM first (the worker's flight recorder dumps on
  it), SIGKILL after ``term_grace_s`` (``serve.worker_killed``);
* **crash-loop backoff** — respawns run through
  :class:`~tclb_tpu.serve.retry.RetryPolicy` (the
  ``hygiene.unpoliced_retry`` contract); a worker that stays up
  ``stable_after_s`` or completes a job resets the failure streak, and
  a lane that exhausts the policy is marked dead;
* **no lost jobs** — a job in flight on a dead/hung worker is re-queued
  (up to ``job_attempts``); resumable jobs re-enter via
  ``CheckpointManager.latest()`` bit-identically.

Job specs and results cross the pipe as plain JSON + ``.npy`` payloads
(never pickled device arrays).  Fault points fired on the supervisor
side: ``pool.spawn`` (spawn attempt) and ``pool.ipc`` (frame send /
result receive); ``pool.heartbeat`` / ``pool.worker_exit`` /
``pool.telemetry_relay`` fire inside the worker — the installed plan
crosses the process boundary because :func:`_spawn` re-serializes it
into the child's ``TCLB_FAULTS``.

Cross-process telemetry relay (on by default, ``relay=False`` to opt
out): workers batch their telemetry events into ``{"t": "telemetry"}``
frames between solve chunks, and the supervisor re-emits each event
into the parent fan-out stamped with ``worker_pid`` / ``lane`` /
``incarnation`` — so worker iterate spans, engine fallbacks, and
failchecks reach the gateway's ``/metrics``, ``/status``, flight ring,
and JSONL trace, and ``telemetry report --job <id>`` renders one
timeline spanning both processes.  ``{"t": "progress"}`` frames land on
the in-flight :class:`PoolJob` (``job.progress`` + ``on_progress``
callback) for the gateway's ``/stream`` long-poll.  Unknown frame kinds
are counted (``pool.unknown_frame``) and warned once per kind, so
supervisor/worker protocol drift is visible.

Monitor contract: the pool registers a ``pool`` ``/status`` provider
(per-worker pid / state / restarts / last-heartbeat age + recent worker
post-mortems with their ``flight-<pid>.jsonl`` paths) and attaches the
flight recorder; every worker attaches its own recorder in-process, so
a worker crash leaves its own dump.
"""

from __future__ import annotations

import os
import queue
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Optional

from tclb_tpu import faults, telemetry
from tclb_tpu.serve.retry import RetryPolicy
# the !II frame protocol lives in cluster/wire.py (shared between the
# worker pipe here and the pod control channel); worker re-exports it
from tclb_tpu.cluster.wire import (IpcError, npy_load, read_frame,
                                   write_frame)
from tclb_tpu.telemetry import live as tlive
from tclb_tpu.telemetry import locks
from tclb_tpu.utils import log


class PoolJobError(RuntimeError):
    """A pool job failed terminally (worker error or attempts exhausted)."""


class PoolJob:
    """Handle for one submitted job: wait on :meth:`result`."""

    def __init__(self, jid: str, doc: dict,
                 on_done: Optional[Callable[["PoolJob"], None]] = None,
                 on_progress: Optional[Callable[["PoolJob"], None]] = None):
        self.id = jid
        self.doc = doc
        self.attempts = 0
        self.status = "queued"
        self.error: Optional[BaseException] = None
        #: latest worker progress sample (iter / mlups / wall_s
        #: [/ reductions]) — updated in place as frames arrive
        self.progress: Optional[dict] = None
        self._result: Optional[dict] = None
        self._on_done = on_done
        self._on_progress = on_progress
        self._evt = threading.Event()

    @property
    def done(self) -> bool:
        return self._evt.is_set()

    def _finish(self, result: Optional[dict],
                error: Optional[BaseException]) -> None:
        self._result = result
        self.error = error
        self.status = "done" if error is None else "failed"
        self._evt.set()
        if self._on_done is not None:
            try:
                self._on_done(self)
            except Exception as e:  # noqa: BLE001 — callback is advisory
                log.warning(f"pool: on_done callback failed: {e!r}")

    def result(self, timeout: Optional[float] = None) -> dict:
        """The result doc (globals / digest / iteration / resumed_from
        [/ fields]); raises on job failure or timeout."""
        if not self._evt.wait(timeout):
            raise TimeoutError(f"pool job {self.id} still in flight")
        if self.error is not None:
            raise self.error
        return self._result


class PoolResult:
    """Host-side outcome of a process-isolated job: plain-python globals
    and an optional ``state_sha256`` digest / fields array — NOT a live
    device :class:`EnsembleResult` (device arrays never cross the worker
    pipe)."""

    def __init__(self, case, doc: dict):
        self.case = case
        self.globals = doc.get("globals") or {}
        self.state_sha256 = doc.get("state_sha256")
        self.iteration = doc.get("iteration")
        self.resumed_from = doc.get("resumed_from")
        self.lane = doc.get("lane")
        self.pid = doc.get("pid")
        #: pod host id when the job came back through a cluster control
        #: channel (None for local lanes) — lane/pid alone are ambiguous
        #: across hosts
        self.host = doc.get("host")
        self.fields = doc.get("fields")


class _Worker:
    """Mutable per-lane supervisor state (owned by one manager thread)."""

    def __init__(self, lane: int):
        self.lane = lane
        self.proc: Optional[subprocess.Popen] = None
        self.pid: Optional[int] = None
        self.state = "starting"   # starting/idle/busy/backoff/dead/stopped
        self.restarts = 0
        self.jobs_done = 0
        self.life_jobs = 0
        self.spawned_at = 0.0
        self.last_beat = time.monotonic()
        self.job: Optional[PoolJob] = None
        self.frames: "queue.Queue[tuple[dict, bytes]]" = queue.Queue()


class WorkerPool:
    """Supervised fleet of solver worker subprocesses (one per lane)."""

    def __init__(self, workers: int = 1,
                 heartbeat_timeout_s: float = 60.0,
                 spawn_timeout_s: float = 180.0,
                 term_grace_s: float = 5.0,
                 retry_policy: Optional[RetryPolicy] = None,
                 job_attempts: int = 2,
                 stable_after_s: float = 30.0,
                 worker_cmd: Optional[list] = None,
                 env: Optional[dict] = None,
                 autostart: bool = True,
                 relay: bool = True) -> None:
        self.n = max(1, int(workers))
        #: ask workers to relay their telemetry events over the pipe
        #: (TCLB_POOL_RELAY=1 at spawn); off = strict no-op worker-side
        self.relay = bool(relay)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.term_grace_s = float(term_grace_s)
        self.retry_policy = retry_policy if retry_policy is not None \
            else RetryPolicy(max_attempts=8, base_delay_s=0.1,
                             max_delay_s=5.0)
        self.job_attempts = max(1, int(job_attempts))
        self.stable_after_s = float(stable_after_s)
        self.worker_cmd = list(worker_cmd) if worker_cmd else None
        self.env = dict(env) if env else {}
        self._queue: "queue.Queue[PoolJob]" = queue.Queue()
        self._workers = [_Worker(i) for i in range(self.n)]
        self._threads: list[threading.Thread] = []
        self._lock = locks.make_lock("serve.pool.WorkerPool._lock")
        self._closing = False
        self._started = False
        self._jobs = 0
        self._done = 0
        self._failed = 0
        self._requeued = 0
        self._unknown_kinds: set = set()    # warned-once frame kinds
        self._worker_dumps: list[dict] = []  # recent flight post-mortems
        self._status_fn = self._status
        if autostart:
            self.start()

    # -- lifecycle ---------------------------------------------------------- #

    def start(self) -> "WorkerPool":
        with self._lock:
            if self._started or self._closing:
                return self
            self._started = True
        tlive.enable_live()
        tlive.flight_recorder().attach()
        tlive.register_status("pool", self._status_fn)
        for w in self._workers:
            t = threading.Thread(target=self._manage, args=(w,),
                                 name=f"tclb-pool-sup-{w.lane}",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def close(self, wait: bool = True, timeout: float = 30.0) -> None:
        with self._lock:
            if self._closing:
                return
            self._closing = True
            started = self._started
        if wait and started:
            deadline = time.monotonic() + timeout
            for t in self._threads:
                t.join(timeout=max(0.1, deadline - time.monotonic()))
        # belt and braces: no child outlives the pool
        for w in self._workers:
            proc = w.proc
            if proc is not None and proc.poll() is None:
                proc.kill()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:  # pragma: no cover
                    pass
        self._fail_queued("pool is closed")
        if started:
            tlive.unregister_status("pool", self._status_fn)
            tlive.flight_recorder().detach()
            tlive.disable_live()

    def __enter__(self) -> "WorkerPool":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # -- submission --------------------------------------------------------- #

    def submit(self, doc: dict,
               on_done: Optional[Callable[[PoolJob], None]] = None,
               on_progress: Optional[Callable[[PoolJob], None]] = None
               ) -> PoolJob:
        """Enqueue one plain-JSON job spec; returns a :class:`PoolJob`.
        ``on_progress`` fires on each worker progress frame with the
        handle (latest sample on ``job.progress``)."""
        if self._closing:
            raise RuntimeError("pool is closed")
        with self._lock:
            self._jobs += 1
            jid = f"pj-{self._jobs}"
        job = PoolJob(jid, dict(doc), on_done, on_progress)
        if self._started and all(w.state in ("dead", "stopped")
                                 for w in self._workers):
            # nobody will ever drain the queue: fail fast instead of
            # stranding the caller on result()
            job._finish(None, PoolJobError(
                f"job {jid}: all pool lanes dead"))
            with self._lock:
                self._failed += 1
            return job
        self._queue.put(job)
        if not self._started:
            self.start()
        return job

    def run(self, docs, timeout: Optional[float] = None) -> list:
        """Submit all, wait for all; failures stay on the handles."""
        jobs = [self.submit(d) for d in docs]
        for j in jobs:
            try:
                j.result(timeout=timeout)
            except Exception:  # noqa: BLE001 — surfaced on the handle
                pass
        return jobs

    def live_workers(self) -> int:
        """Workers currently able to serve (spawned and not dead)."""
        return sum(1 for w in self._workers
                   if w.state in ("idle", "busy"))

    def stats(self) -> dict:
        with self._lock:
            return {"submitted": self._jobs, "done": self._done,
                    "failed": self._failed, "requeued": self._requeued,
                    "live": self.live_workers(),
                    "restarts": sum(w.restarts for w in self._workers)}

    # -- supervisor --------------------------------------------------------- #

    def _spawn(self, w: _Worker) -> None:
        faults.fire("pool.spawn", lane=w.lane)
        cmd = self.worker_cmd or [sys.executable, "-m",
                                  "tclb_tpu.serve.worker"]
        cmd = cmd + ["--lane", str(w.lane)]
        env = dict(os.environ)
        env.update(self.env)
        env["TCLB_POOL_LANE"] = str(w.lane)
        if self.relay:
            env["TCLB_POOL_RELAY"] = "1"
        else:
            env.pop("TCLB_POOL_RELAY", None)
        # the installed fault plan crosses the process boundary, so
        # worker-side points (pool.heartbeat / pool.worker_exit) fire
        # under the same seeded schedule
        spec = faults.current_spec()
        if spec:
            env["TCLB_FAULTS"] = spec
        else:
            env.pop("TCLB_FAULTS", None)
        proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE, env=env)
        w.proc = proc
        w.pid = proc.pid
        w.frames = queue.Queue()
        w.life_jobs = 0
        w.last_beat = time.monotonic()
        threading.Thread(target=self._read_loop, args=(w, proc),
                         name=f"tclb-pool-read-{w.lane}",
                         daemon=True).start()
        deadline = time.monotonic() + self.spawn_timeout_s
        while True:
            budget = deadline - time.monotonic()
            if budget <= 0:
                self._kill_proc(w, "spawn_timeout")
                raise PoolJobError(
                    f"worker lane {w.lane} never sent ready "
                    f"(pid {proc.pid})")
            try:
                doc, _ = w.frames.get(timeout=min(budget, 0.5))
            except queue.Empty:
                continue
            if doc.get("t") == "_eof":
                raise PoolJobError(
                    f"worker lane {w.lane} died during startup "
                    f"(rc {proc.poll()})")
            if doc.get("t") == "ready":
                break
        w.spawned_at = time.monotonic()
        w.state = "idle"
        telemetry.event("serve.worker_spawned", lane=w.lane, pid=w.pid,
                        restarts=w.restarts)
        telemetry.counter("pool.workers.spawned")

    def _read_loop(self, w: _Worker, proc: subprocess.Popen) -> None:
        """Per-incarnation reader: frames -> queue, beats -> timestamp.
        Bound to its own queue object, so a stale reader from a dead
        incarnation can never feed the replacement's queue."""
        frames = w.frames
        fh = proc.stdout
        while True:
            try:
                doc, payload = read_frame(fh)
            except (EOFError, IpcError, OSError, ValueError):
                frames.put(({"t": "_eof"}, b""))
                return
            w.last_beat = time.monotonic()
            frames.put((doc, payload))

    def _manage(self, w: _Worker) -> None:
        """One lane's supervisor loop: spawn, serve, reap, backoff."""
        fails = 0
        respawn = False
        while not self._closing:
            try:
                self._spawn(w)
            except Exception as e:  # noqa: BLE001 — spawn is a retried seam
                w.state = "backoff"
                fails += 1
                d = self.retry_policy.next_delay(
                    fails - 1, key=f"pool-spawn-{w.lane}")
                if d is None:
                    self._mark_dead(w, f"spawn crash-loop: {e!r}")
                    return
                log.warning(f"pool: lane {w.lane} spawn failed "
                            f"({e!r}); retry in {d:.2f}s")
                time.sleep(d)
                continue
            if respawn:
                telemetry.event("serve.worker_restarted", lane=w.lane,
                                pid=w.pid, restarts=w.restarts)
                telemetry.counter("pool.workers.restarted")
            reason = self._serve(w)
            if reason is None:      # pool closing: clean shutdown
                return
            respawn = True
            w.restarts += 1
            stable = (w.life_jobs > 0
                      or (time.monotonic() - w.spawned_at)
                      >= self.stable_after_s)
            fails = 0 if stable else fails + 1
            if fails:
                d = self.retry_policy.next_delay(
                    fails - 1, key=f"pool-respawn-{w.lane}")
                if d is None:
                    self._mark_dead(w, f"crash-loop ({reason})")
                    return
                w.state = "backoff"
                time.sleep(d)
        self._shutdown_worker(w)

    def _serve(self, w: _Worker) -> Optional[str]:
        """Feed jobs to one live worker until it fails (returns the
        failure reason) or the pool closes (returns None)."""
        while not self._closing:
            if w.proc.poll() is not None:
                return self._reap(w, "exit")
            try:
                job = self._queue.get(timeout=0.1)
            except queue.Empty:
                continue
            if self._closing:
                self._queue.put(job)
                break
            w.job = job
            w.state = "busy"
            w.last_beat = time.monotonic()
            job.status = "running"
            job.attempts += 1
            try:
                faults.fire("pool.ipc", lane=w.lane, job=job.id,
                            op="send")
                write_frame(w.proc.stdin,
                            {"t": "job", "id": job.id, "spec": job.doc})
            except Exception as e:  # noqa: BLE001 — IPC failure = lane
                self._requeue(w, job, f"ipc send: {e!r}")   # failure
                return self._reap(w, "ipc")
            telemetry.event("serve.pool_job_started", job=job.id,
                            job_id=job.doc.get("job_id"), lane=w.lane,
                            pid=w.pid, incarnation=w.restarts,
                            attempt=job.attempts)
            verdict = self._await_result(w, job)
            if verdict == "done":
                w.jobs_done += 1
                w.life_jobs += 1
                w.job = None
                w.state = "idle"
                continue
            self._requeue(w, job, verdict)
            return self._reap(w, verdict)
        self._shutdown_worker(w)
        return None

    def _await_result(self, w: _Worker, job: PoolJob) -> str:
        """Pump frames for one in-flight job; verdicts: ``done`` /
        ``hung`` / ``exit`` / ``ipc``."""
        while True:
            now = time.monotonic()
            budget = self.heartbeat_timeout_s - (now - w.last_beat)
            if budget <= 0:
                telemetry.event("serve.worker_hung", lane=w.lane,
                                pid=w.pid, job=job.id,
                                beat_age_s=round(now - w.last_beat, 3))
                telemetry.counter("pool.workers.hung")
                return "hung"
            try:
                doc, payload = w.frames.get(timeout=min(budget, 0.2))
            except queue.Empty:
                continue
            t = doc.get("t")
            if t == "_eof":
                return "exit"
            if t == "hb":
                continue
            if t == "result" and doc.get("id") == job.id:
                try:
                    faults.fire("pool.ipc", lane=w.lane, job=job.id,
                                op="recv")
                except Exception:  # noqa: BLE001 — injected IPC fault
                    return "ipc"
                if doc.get("ok"):
                    res = {k: v for k, v in doc.items()
                           if k not in ("t", "id", "ok")}
                    if payload:
                        res["fields"] = npy_load(payload)
                    job._finish(res, None)
                    with self._lock:
                        self._done += 1
                else:
                    job._finish(None, PoolJobError(
                        f"job {job.id} failed in worker lane "
                        f"{w.lane}: {doc.get('error')}"))
                    with self._lock:
                        self._failed += 1
                telemetry.event("serve.pool_job_done", job=job.id,
                                job_id=job.doc.get("job_id"),
                                lane=w.lane, ok=bool(doc.get("ok")),
                                attempts=job.attempts)
                return "done"
            if t == "telemetry":
                self._reemit(w, doc)
                continue
            if t == "progress" and doc.get("id") == job.id:
                job.progress = {k: v for k, v in doc.items()
                                if k not in ("t", "id")}
                if job._on_progress is not None:
                    try:
                        job._on_progress(job)
                    except Exception as e:  # noqa: BLE001 — advisory
                        log.warning(
                            f"pool: on_progress callback failed: {e!r}")
                continue
            # unknown frame kinds are protocol drift between supervisor
            # and worker versions: count them, warn once per kind
            telemetry.counter("pool.unknown_frame")
            if t not in self._unknown_kinds:
                self._unknown_kinds.add(t)
                log.warning(f"pool: ignoring unknown IPC frame kind "
                            f"{t!r} from lane {w.lane} (pid {w.pid})")

    def _reemit(self, w: _Worker, doc: dict) -> None:
        """Re-emit one relayed telemetry batch into the parent fan-out,
        stamped with the worker's identity — this is what carries iterate
        spans, fallbacks, and failchecks across the process boundary into
        ``/metrics``, ``/status``, the flight ring, and the trace."""
        evs = doc.get("events") or ()
        dropped = doc.get("dropped") or 0
        if dropped:
            telemetry.counter("pool.relay_dropped", int(dropped))
        if evs:
            telemetry.counter("pool.relay_events", len(evs))
        for ev in evs:
            if not isinstance(ev, dict):
                continue
            fields = dict(ev)
            kind = fields.pop("kind", None)
            if not kind:
                continue
            # event() preserves a passed `ts`, so the worker's original
            # timestamps survive re-emission and the merged timeline
            # keeps true ordering
            fields.setdefault("worker_pid", w.pid)
            fields.setdefault("lane", w.lane)
            fields.setdefault("incarnation", w.restarts)
            telemetry.event(str(kind), **fields)

    def _requeue(self, w: _Worker, job: PoolJob, reason: str) -> None:
        """A job lost to a worker failure goes back in the queue (up to
        ``job_attempts``) — never silently dropped."""
        w.job = None
        if job.attempts >= self.job_attempts:
            job._finish(None, PoolJobError(
                f"job {job.id} failed after {job.attempts} attempts "
                f"(last worker failure: {reason})"))
            with self._lock:
                self._failed += 1
        else:
            job.status = "queued"
            with self._lock:
                self._requeued += 1
            telemetry.event("serve.pool_job_requeued", job=job.id,
                            lane=w.lane, reason=reason,
                            attempts=job.attempts)
            self._queue.put(job)
            if self._closing:
                # close() may already have drained the backlog — a job
                # requeued after that must still fail fast, not strand
                # its waiter on a queue nobody serves
                self._fail_queued("pool is closed")

    def _flight_path(self, pid: Optional[int]) -> Optional[str]:
        """Where a dead worker's flight-recorder dump lands (same rule
        as ``FlightRecorder.dump``: TCLB_FLIGHT_DIR, else cwd)."""
        if pid is None:
            return None
        d = (self.env.get("TCLB_FLIGHT_DIR")
             or os.environ.get("TCLB_FLIGHT_DIR") or os.getcwd())
        return os.path.join(d, f"flight-{pid}.jsonl")

    def _note_dump(self, w: _Worker, reason: str,
                   flight: Optional[str]) -> None:
        """Remember a dead worker's post-mortem for the ``/status``
        provider, so triage doesn't hunt the flight dir by pid."""
        rec = {"lane": w.lane, "pid": w.pid, "reason": reason,
               "flight": (flight if flight and os.path.exists(flight)
                          else None),
               "ts": round(time.time(), 3)}
        with self._lock:
            self._worker_dumps.append(rec)
            del self._worker_dumps[:-8]

    def _kill_proc(self, w: _Worker, reason: str) -> None:
        """SIGTERM-then-SIGKILL escalation (SIGTERM lets the worker's
        flight recorder dump its ring first)."""
        proc = w.proc
        if proc is None or proc.poll() is not None:
            return
        proc.terminate()
        try:
            proc.wait(timeout=self.term_grace_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:  # pragma: no cover
                pass
        flight = self._flight_path(w.pid)
        telemetry.event("serve.worker_killed", lane=w.lane, pid=w.pid,
                        reason=reason, flight=flight)
        telemetry.counter("pool.workers.killed")
        self._note_dump(w, reason, flight)

    def _reap(self, w: _Worker, reason: str) -> str:
        w.state = "respawning"
        proc = w.proc
        if proc is not None and reason == "exit" and proc.poll() is None:
            # the pipe's end arrives before the exit status does: wait
            # for the exit it announces before calling it a kill
            try:
                proc.wait(timeout=self.term_grace_s)
            except subprocess.TimeoutExpired:
                pass
        if proc is not None and proc.poll() is None:
            self._kill_proc(w, reason)
        else:
            flight = self._flight_path(w.pid)
            telemetry.event("serve.worker_exit", lane=w.lane, pid=w.pid,
                            returncode=(None if proc is None
                                        else proc.returncode),
                            reason=reason, flight=flight)
            telemetry.counter("pool.workers.exited")
            self._note_dump(w, reason, flight)
        for fh in (getattr(proc, "stdin", None),
                   getattr(proc, "stdout", None)):
            try:
                if fh is not None:
                    fh.close()
            except OSError:  # pragma: no cover — already torn down
                pass
        return reason

    def _shutdown_worker(self, w: _Worker) -> None:
        proc = w.proc
        w.state = "stopped"
        if proc is None or proc.poll() is not None:
            return
        try:
            write_frame(proc.stdin, {"t": "shutdown"})
            proc.stdin.close()
        except (OSError, ValueError):  # pragma: no cover — pipe gone
            pass
        try:
            proc.wait(timeout=self.term_grace_s)
        except subprocess.TimeoutExpired:
            self._kill_proc(w, "shutdown_timeout")

    def _mark_dead(self, w: _Worker, why: str) -> None:
        w.state = "dead"
        log.warning(f"pool: lane {w.lane} marked dead — {why}")
        telemetry.event("serve.worker_dead", lane=w.lane, reason=why)
        if self.live_workers() == 0 and all(
                x.state in ("dead", "stopped") for x in self._workers):
            # nobody left to serve: fail the backlog instead of letting
            # callers wait forever
            self._fail_queued(f"all pool lanes dead (last: {why})")

    def _fail_queued(self, why: str) -> None:
        while True:
            try:
                job = self._queue.get_nowait()
            except queue.Empty:
                return
            job._finish(None, PoolJobError(f"job {job.id}: {why}"))
            with self._lock:
                self._failed += 1

    # -- observability ------------------------------------------------------ #

    def _status(self) -> dict:
        """Plain-python ``/status`` fragment — monitor-thread safe."""
        now = time.monotonic()
        with self._lock:
            jobs = {"submitted": self._jobs, "done": self._done,
                    "failed": self._failed, "requeued": self._requeued}
            dumps = list(self._worker_dumps)
        return {
            "workers": [{
                "lane": w.lane, "pid": w.pid, "state": w.state,
                "restarts": w.restarts, "jobs_done": w.jobs_done,
                "job": None if w.job is None else w.job.id,
                "last_heartbeat_age_s": round(now - w.last_beat, 3),
            } for w in self._workers],
            "live": self.live_workers(),
            "queue_depth": self._queue.qsize(),
            "jobs": jobs,
            "worker_dumps": dumps,
            "heartbeat_timeout_s": self.heartbeat_timeout_s,
            "closing": self._closing,
        }


def pool_doc_from_spec(spec) -> dict:
    """A :class:`~tclb_tpu.serve.scheduler.JobSpec` as a plain-JSON pool
    job doc.  Only self-contained solve specs cross the process
    boundary — a custom plan or gradient spec holds live Python/device
    objects and must use the in-process lanes."""
    if getattr(spec, "plan", None) is not None \
            or getattr(spec, "grad", None) is not None:
        raise ValueError(
            "process-isolated lanes serve plain solve specs only: a "
            "custom EnsemblePlan or GradSpec cannot cross the worker "
            "pipe (JSON + npy payloads, never pickled objects)")
    import jax.numpy as jnp
    dtype = "f64" if spec.dtype == jnp.float64 else "f32"
    sdt = {jnp.bfloat16: "bf16", jnp.float32: "f32",
           jnp.float64: "f64"}.get(spec.storage_dtype)
    case = spec.case
    return {"model": spec.model.name,
            "shape": [int(s) for s in spec.shape],
            "niter": int(spec.niter),
            "dtype": dtype, "storage_dtype": sdt,
            "storage_repr": getattr(spec, "storage_repr", None),
            "params": dict(spec.base_settings or {}),
            "case": {"name": case.name,
                     "settings": dict(case.settings)},
            "timeout_s": spec.timeout_s,
            "digest": True}
