"""Fleet dispatcher: one concurrent serving lane per local device.

The single-worker :class:`~tclb_tpu.serve.scheduler.Scheduler` drives
one device; on an 8-device host 7/8 of the fleet idles while jobs
queue.  This layer turns ``jax.devices()`` into N concurrent lanes:

* **lanes** — one worker lane per device.  Jobs bin by the scheduler's
  ``_bin_key`` and the memory-predicated ``ensemble_batch_cap``, but a
  burst spreads one-batch-per-device (fair-share cap) instead of one
  lane swallowing the queue.  Every lane owns a device-pinned
  :class:`CompiledCache` (AOT inputs carry a ``SingleDeviceSharding``),
  so executables never migrate between devices;
* **double-buffered host staging** — each lane pairs a staging thread
  with its execute thread: while the device runs batch k, batch k+1's
  stacked case params/fields are already built host-side and
  ``device_put`` onto the lane's device; results start their D2H copy
  asynchronously right after dispatch.  ``serve.lane_batch`` spans
  carry ``stage_s``/``stall_s`` so ``telemetry report`` can prove the
  staging is hidden under execution (``fleet_bench`` wants >90%);
* **size-aware routing** — a cost model compares lane time (~cells x
  niter) against the sharded engine's (~work x (1+overhead)/n, with
  ``decomposition_overhead`` from the mesh divisor search): swarms of
  small cases go to per-device ensemble lanes, a single large case is
  routed to the multi-device ``parallel/halo.py`` engine.  The fleet
  temporarily *coalesces* for a sharded job — lanes pause between
  batches, the job runs over all devices, lane mode resumes;
* **device eviction** — the degradation ladder's last rung: a lane
  whose batches repeatedly fail (batched retries exhausted AND every
  sequential degrade failed) is drained, its queued work redistributed
  to the surviving lanes, and a ``serve.device_evicted`` event emitted.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tclb_tpu import faults, telemetry
from tclb_tpu.telemetry import live as tlive
from tclb_tpu.telemetry import locks
from tclb_tpu.core.lattice import Lattice
from tclb_tpu.ops import fusion
from tclb_tpu.parallel.mesh import (choose_decomposition,
                                    decomposition_overhead, make_mesh)
from tclb_tpu.serve.cache import CompiledCache
from tclb_tpu.serve.ensemble import Case, EnsemblePlan, EnsembleResult
from tclb_tpu.serve.retry import RetryPolicy
from tclb_tpu.serve.scheduler import (DONE, Job, JobSpec, JobTimeout,
                                      PENDING, RUNNING, _bin_key)
from tclb_tpu.utils import log

# below this many node-updates (cells x niter) a job is not worth
# coalescing the whole fleet for — it stays on a single lane
DEFAULT_SHARD_MIN_WORK = int(
    os.environ.get("TCLB_FLEET_SHARD_MIN_WORK", str(1 << 26)))


def route_job(spec: JobSpec, n_devices: int,
              shard_min_work: Optional[int] = None) -> tuple[str, dict]:
    """Size-aware routing verdict for one job: ``("lane", info)`` or
    ``("sharded", info)``.

    The cost model: a lane serves the job in ~``work = cells x niter``
    node-update units; the sharded engine in ~``work x (1+overhead) /
    n_devices`` plus a fleet-coalescing pause, where ``overhead`` is the
    halo-to-volume ratio of the best decomposition.  Sharding wins only
    when the job is big enough to amortize the pause (``shard_min_work``)
    and the halo tax doesn't eat the device fan-out."""
    if shard_min_work is None:
        shard_min_work = DEFAULT_SHARD_MIN_WORK
    cells = int(np.prod(spec.shape))
    work = cells * max(1, int(spec.niter))
    info: dict[str, Any] = {"cells": cells, "work": work}
    if n_devices < 2:
        return "lane", dict(info, reason="single_device")
    if spec.plan is not None:
        # a prebuilt ensemble plan (zonal XML base) only exists on the
        # batched path; the sharded Lattice can't replay it
        return "lane", dict(info, reason="plan_base")
    if spec.grad is not None:
        # the batched adjoint is a lane program (the sharded Lattice has
        # no reverse sweep); N gradient cases amortize on one lane
        return "lane", dict(info, reason="grad")
    if spec.storage_dtype is not None and \
            jnp.dtype(spec.storage_dtype) != jnp.dtype(spec.dtype):
        # halo building block is f32-only (core/lattice.py rejects it)
        return "lane", dict(info, reason="narrowed_storage")
    if work < shard_min_work:
        return "lane", dict(info, reason="below_work_floor")
    try:
        decomp = choose_decomposition(spec.shape, n_devices)
    except ValueError:
        return "lane", dict(info, reason="indivisible")
    overhead = decomposition_overhead(spec.shape, decomp)
    info["overhead"] = round(overhead, 6)
    if (1.0 + overhead) >= n_devices:
        return "lane", dict(info, reason="overhead_dominates")
    info["reason"] = "above_work_floor"
    return "sharded", info


class _Staged:
    """One lane batch, staged: host work done, inputs on the device."""

    __slots__ = ("batch", "plan", "inputs", "stage_s", "cap", "waits")

    def __init__(self, batch, plan, inputs, stage_s, cap, waits):
        self.batch = batch
        self.plan = plan
        self.inputs = inputs
        self.stage_s = stage_s
        self.cap = cap
        self.waits = waits


class LaneLease:
    """A reservation of one fleet lane's DEVICE by a non-serving tenant
    (the revolve peer-HBM spill tier).  While held, the lane's stager
    takes no batches — serving jobs and spill tenants never fight for
    the device's memory.  The dispatcher may *revoke* the lease when
    serving demand needs the lane back; the tenant's ``on_revoke``
    callback must then migrate its data off the device (the revolve
    store re-spills peer snapshots to disk) before the lane resumes."""

    def __init__(self, disp: "FleetDispatcher", lane: "Lane", tenant: str,
                 on_revoke: Optional[Callable[["LaneLease", str], None]]
                 = None):
        self.disp = disp
        self.lane = lane
        self.tenant = tenant
        self.on_revoke = on_revoke
        self.revoked = False
        self.released = False

    @property
    def device(self):
        return self.lane.device

    def release(self) -> None:
        self.disp.release_lane(self)


class Lane:
    """One device's serving lane: a staging thread feeding an execute
    thread through a one-slot buffer (the double buffer)."""

    def __init__(self, dispatcher: "FleetDispatcher", index: int, device):
        self.disp = dispatcher
        self.index = index
        self.device = device
        # precomputed so the monitor thread never repr()s a live device
        self.device_str = str(device)
        self.cache = CompiledCache()
        self.evicted = False
        # tenant name while a LaneLease holds this lane, else None
        # (written under the dispatcher lock; the stager polls it)
        self.reserved: Optional[str] = None
        self.batches = 0
        self.jobs_served = 0
        self.busy_s = 0.0
        self.failstreak = 0
        self._current_job_ids: list[int] = []
        # one slot: batch k+1 stages while batch k executes
        self._staged: queue.Queue[Optional[_Staged]] = queue.Queue(maxsize=1)
        self._idle = threading.Event()
        self._idle.set()
        self._stager: Optional[threading.Thread] = None
        self._exec: Optional[threading.Thread] = None

    def start(self) -> None:
        self._stager = threading.Thread(
            target=self._stage_loop, name=f"tclb-fleet-stage-{self.index}",
            daemon=True)
        self._exec = threading.Thread(
            target=self._exec_loop, name=f"tclb-fleet-exec-{self.index}",
            daemon=True)
        self._stager.start()
        self._exec.start()

    # -- staging thread ----------------------------------------------------- #

    def _stage_loop(self) -> None:
        d = self.disp
        try:
            self._stage_loop_inner()
        except BaseException as e:  # noqa: BLE001 - post-mortem first
            tlive.flight_recorder().dump("stage_loop_exception",
                                         lane=self.index, error=repr(e))
            raise
        finally:
            self._staged.put(None)  # release the execute thread

    def _stage_loop_inner(self) -> None:
        d = self.disp
        while not self.evicted:
            batch = d._take_batch(self)
            if batch is None:
                if d._closing:
                    return
                continue
            if not batch:
                continue
            spec = batch[0].spec
            key = _bin_key(spec)
            cap = d.batch_cap(spec)
            now = time.monotonic()
            waits = [round(now - j.submitted, 6) for j in batch]
            t0 = time.perf_counter()
            try:
                plan = d._plan_for(spec, key)
                with telemetry.span("serve.stage",
                                    device=str(self.device),
                                    lane=self.index, batch=len(batch),
                                    job_ids=[j.id for j in batch]):
                    faults.fire("serve.stage", lane=self.index,
                                batch=len(batch))
                    inputs = jax.device_put(
                        plan.host_stacked_cases(
                            [j.spec.case for j in batch]),
                        self.device)
                    jax.block_until_ready(inputs)
            except Exception as e:  # noqa: BLE001 - per-batch verdict
                for j in batch:
                    j._finish(None, e)
                    d._stream(j)
                continue
            stage_s = time.perf_counter() - t0
            self._staged.put(_Staged(batch, plan, inputs, stage_s,
                                     cap, waits))

    # -- execute thread ----------------------------------------------------- #

    def _exec_loop(self) -> None:
        try:
            self._exec_loop_inner()
        except BaseException as e:  # noqa: BLE001 - post-mortem first
            tlive.flight_recorder().dump("exec_loop_exception",
                                         lane=self.index, error=repr(e))
            raise

    def _exec_loop_inner(self) -> None:
        d = self.disp
        while True:
            t0 = time.perf_counter()
            item = self._staged.get()
            wait_s = time.perf_counter() - t0
            if item is None:
                return
            d._gate.wait()  # a sharded job may hold the whole fleet
            if self.evicted:
                d._redistribute(item.batch)
                continue
            self._idle.clear()
            try:
                self._serve(item, wait_s)
            finally:
                self._idle.set()

    def _serve(self, item: _Staged, wait_s: float) -> None:
        d = self.disp
        batch, plan = item.batch, item.plan
        spec = batch[0].spec
        # stall = the part of the staging latency the execute thread
        # actually waited out; a lane's first fill has nothing to hide
        # under, so the report excludes first=True rows from the overlap
        stall_s = min(wait_s, item.stage_s)
        first = self.batches == 0
        job_ids = [j.id for j in batch]
        for j in batch:
            j.status = RUNNING
        results: Optional[list[EnsembleResult]] = None
        err: Optional[BaseException] = None
        busy_t0 = time.perf_counter()
        telemetry.set_job(job_ids[0] if len(job_ids) == 1 else None)
        with telemetry.span("serve.lane_batch", device=str(self.device),
                            lane=self.index, batch=len(batch),
                            capacity=item.cap, model=spec.model.name,
                            niter=int(spec.niter),
                            engine=plan.engine_tag(len(batch)),
                            stage_s=round(item.stage_s, 6),
                            stall_s=round(stall_s, 6), first=first,
                            wait_s=item.waits, job_ids=job_ids) as sp:
            self._current_job_ids = job_ids
            # the batch deadline is the earliest member's: a retry may
            # never start past the moment any co-batched caller times out
            bd = None
            for j in batch:
                if j.spec.timeout_s is not None:
                    t = j.submitted + j.spec.timeout_s
                    bd = t if bd is None else min(bd, t)
            policy = d.retry_policy
            for attempt in range(policy.max_attempts):
                for j in batch:
                    j.attempts += 1
                try:
                    results = d._batch_runner(
                        self, plan, [j.spec.case for j in batch],
                        spec.niter, item.inputs)
                    break
                except Exception as e:  # noqa: BLE001 - degrade below
                    err = e
                    delay = policy.next_delay(
                        attempt, deadline=bd,
                        key=f"lane{self.index}:{job_ids[0]}")
                    if delay is None:
                        break
                    telemetry.counter("serve.batch.retry")
                    telemetry.event(
                        "serve.batch.retry", lane=self.index,
                        attempt=attempt + 1, delay_s=round(delay, 6),
                        job_ids=job_ids,
                        deadline_in_s=(None if bd is None else
                                       round(bd - time.monotonic(), 6)))
                    log.warning(f"fleet lane {self.index}: batched run "
                                f"failed (attempt {attempt + 1}): {e!r};"
                                f" retrying in {delay:.3f}s")
                    time.sleep(delay)
            self.batches += 1
            if results is not None:
                sp.add(outcome="ok", retries=attempt)
                telemetry.set_job(None)
                self.busy_s += time.perf_counter() - busy_t0
                self.jobs_served += len(batch)
                self.failstreak = 0
                for j, r in zip(batch, results):
                    j._finish(r, None)
                    d._stream(j)
                return
            sp.add(outcome="degraded", error=repr(err))
            telemetry.counter("serve.batch.degraded")
            log.warning(f"fleet lane {self.index}: batched run failed after "
                        f"{attempt + 1} attempt(s) ({err!r}); degrading "
                        f"{len(batch)} job(s) to sequential")
        telemetry.set_job(None)
        any_ok = False
        for j in batch:
            j.degraded = True
            telemetry.event("serve.job_degraded", job_id=j.id,
                            lane=self.index, error=repr(err))
            with telemetry.job_context(j.id):
                try:
                    r = d._seq_runner(self, plan, j.spec.case, spec.niter)
                    j._finish(r, None)
                    any_ok = True
                except Exception as e:  # noqa: BLE001 - per-job verdict
                    j._finish(None, e)
            d._stream(j)
        self.busy_s += time.perf_counter() - busy_t0
        self.jobs_served += len(batch)
        if any_ok:
            self.failstreak = 0
        else:
            self.failstreak += 1
            if self.failstreak >= d.evict_after:
                self._evict(err)

    def _evict(self, cause: Optional[BaseException]) -> None:
        self.evicted = True
        telemetry.event("serve.device_evicted", device=str(self.device),
                        lane=self.index, failstreak=self.failstreak,
                        cause=repr(cause))
        telemetry.counter("serve.device_evicted")
        log.warning(f"fleet: evicting lane {self.index} ({self.device}) "
                    f"after {self.failstreak} consecutive failed batches: "
                    f"{cause!r}")
        self.disp._lane_evicted(self)


class FleetDispatcher:
    """Device-aware dispatcher: N lanes over N devices + a sharded rail.

    Drop-in surface of :class:`Scheduler` (``submit``/``run``/``close``,
    same :class:`Job` handles, same retry/degrade ladder) plus routing:
    jobs above the work floor with a worthwhile decomposition run on the
    all-device sharded engine, everything else bins onto per-device
    ensemble lanes.  ``batch_runner`` / ``sequential_runner`` are
    injectable for fault testing with lane-aware signatures
    ``(lane, plan, cases, niter, staged_inputs) -> [EnsembleResult]``
    and ``(lane, plan, case, niter) -> EnsembleResult``."""

    def __init__(self, devices: Optional[Sequence] = None,
                 max_batch: Optional[int] = None, retries: int = 1,
                 evict_after: int = 2,
                 shard_min_work: Optional[int] = None,
                 batch_runner: Optional[Callable] = None,
                 sequential_runner: Optional[Callable] = None,
                 on_result: Optional[Callable[[Job], None]] = None,
                 autostart: bool = True,
                 monitor: Optional[str] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 probe_interval_s: Optional[float] = None,
                 probe_runner: Optional[Callable] = None,
                 process_isolation: bool = False,
                 pool: Optional[Any] = None):
        self.devices = list(devices) if devices is not None \
            else list(jax.devices())
        # process isolation: one supervised worker SUBPROCESS per lane
        # instead of in-process device lanes — a wedged device or a
        # native crash kills one child, not the dispatcher.  Jobs cross
        # as plain JSON (pool_doc_from_spec); results come back as
        # host-side dicts (globals + sha256 digest), not live device
        # arrays, so plan/grad specs must use the in-process lanes.
        self._pool = None
        if process_isolation or pool is not None:
            from tclb_tpu.serve.pool import WorkerPool
            self._pool = pool if pool is not None else WorkerPool(
                workers=max(1, len(self.devices)),
                retry_policy=retry_policy, autostart=False)
        self.max_batch = max_batch
        self.retry_policy = retry_policy if retry_policy is not None \
            else RetryPolicy.from_retries(retries)
        self.retries = self.retry_policy.retries
        self.evict_after = max(1, int(evict_after))
        # lane probation: when set, an evicted lane is re-probed every
        # `probe_interval_s` seconds with a canary and reinstated on
        # success.  Opt-in (constructor or TCLB_FLEET_PROBE_S) — the
        # default fleet keeps permanent eviction and its all-evicted
        # fast-fail contract.
        if probe_interval_s is None:
            env = os.environ.get("TCLB_FLEET_PROBE_S")
            probe_interval_s = float(env) if env else None
        self.probe_interval_s = probe_interval_s
        self._probe_runner = probe_runner or self._default_probe
        # how long a reinstatement waits for the evicted lane's old
        # threads to finish dying before deferring to the next probe
        self.reinstate_join_s = 10.0
        self._probe_threads: list[threading.Thread] = []
        self._stop_probes = threading.Event()
        self.shard_min_work = shard_min_work
        self.autostart = autostart
        self._batch_runner = batch_runner or self._run_batched
        self._seq_runner = sequential_runner or (
            lambda lane, plan, case, niter:
            plan.run_sequential(case, niter, device=lane.device))
        self._on_result = on_result
        self.lanes = [Lane(self, i, dev)
                      for i, dev in enumerate(self.devices)]
        self._queue: queue.Queue[Job] = queue.Queue()
        self._sharded: queue.Queue[Job] = queue.Queue()
        self._gate = threading.Event()
        self._gate.set()
        self._plans: dict[tuple, EnsemblePlan] = {}
        self._plan_lock = locks.make_lock("serve.dispatcher.FleetDispatcher._plan_lock")
        self._jobs = 0
        self._leases: list[LaneLease] = []
        self._lock = locks.make_lock("serve.dispatcher.FleetDispatcher._lock")
        self._inflight: dict[int, Job] = {}
        self._closing = False
        self._started = False
        self._shard_worker: Optional[threading.Thread] = None
        self._t0 = time.monotonic()
        self._monitor_spec = monitor
        self._monitor = None
        # flight recorder on by default inside serve/: a crashed fleet
        # yields a post-mortem ring dump even without a trace
        self._flight_attached = True
        tlive.flight_recorder().attach()
        tlive.register_status("fleet", self._status)

    # -- admission ---------------------------------------------------------- #

    def start(self) -> None:
        with self._lock:
            if self._started:
                return
            self._started = True
        if self._monitor_spec is not None and self._monitor is None:
            from tclb_tpu.telemetry.http import MonitorServer
            self._monitor = MonitorServer.from_spec(
                self._monitor_spec).start()
            log.notice(f"fleet: monitor at {self._monitor.url}/status")
        if self._pool is not None:
            # process isolation: worker subprocesses ARE the lanes; the
            # parent never starts in-process device threads
            self._pool.start()
            return
        for lane in self.lanes:
            lane.start()
        self._shard_worker = threading.Thread(
            target=self._sharded_loop, name="tclb-fleet-sharded", daemon=True)
        self._shard_worker.start()

    @property
    def monitor_url(self) -> Optional[str]:
        """Base URL of the live monitor, or None when not enabled."""
        return self._monitor.url if self._monitor is not None else None

    def _status(self) -> dict:
        """Plain-python /status fragment: per-lane occupancy, queue
        depths, inflight job ages, evicted devices.  Reads only
        thread-safe python state — monitor-thread safe by construction
        (and enforced by hygiene.device_work_in_monitor)."""
        now = time.monotonic()
        wall = max(now - self._t0, 1e-9)
        with self._lock:
            inflight = [{"job_id": j.id, "name": j.spec.name,
                         "status": j.status,
                         "age_s": round(now - j.submitted, 3)}
                        for j in list(self._inflight.values())[:64]]
        return {
            "queue_depth": self._queue.qsize(),
            "sharded_queue_depth": self._sharded.qsize(),
            "jobs_submitted": self._jobs,
            "inflight": inflight,
            "lanes": [{"lane": l.index, "device": l.device_str,
                       "batches": l.batches, "jobs": l.jobs_served,
                       "busy_s": round(l.busy_s, 6),
                       "occupancy_pct": round(100.0 * l.busy_s / wall, 2),
                       "failstreak": l.failstreak,
                       "evicted": l.evicted,
                       "reserved": l.reserved} for l in self.lanes],
            "reserved_lanes": sum(1 for l in self.lanes
                                  if l.reserved is not None),
            "evicted_devices": [l.device_str for l in self.lanes
                                if l.evicted],
            "uptime_s": round(wall, 3),
            "closing": self._closing,
        }

    def submit(self, spec: JobSpec, lane: Optional[int] = None) -> Job:
        """Route + enqueue one job; ``lane`` pins it to a specific lane
        (parity tests / targeted draining)."""
        if self._closing:
            raise RuntimeError("dispatcher is closed")
        if self._pool is not None:
            return self._submit_pooled(spec)
        with self._lock:
            self._jobs += 1
            job = Job(spec, self._jobs)
            self._inflight[job.id] = job
        telemetry.counter("serve.jobs.submitted")
        if lane is not None:
            job.pin = int(lane)
            route, info = "lane", {"reason": "pinned"}
        else:
            route, info = route_job(spec, len(self.devices),
                                    self.shard_min_work)
        telemetry.event("serve.job_queued", job_id=job.id,
                        name=spec.name, model=spec.model.name,
                        shape=list(spec.shape), niter=int(spec.niter),
                        route=route, reason=info.get("reason"))
        if route == "sharded":
            telemetry.event("serve.route_sharded", job=job.id,
                            job_id=job.id, model=spec.model.name,
                            shape=list(spec.shape), niter=int(spec.niter),
                            **info)
            telemetry.counter("serve.route_sharded")
            self._sharded.put(job)
        else:
            telemetry.counter("serve.route_lane")
            if all(l.evicted for l in self.lanes) \
                    and self.probe_interval_s is None:
                # no probation: the fleet is permanently dead, fail fast
                job._finish(None, RuntimeError(
                    "fleet: all lanes evicted; no device can serve the job"))
                self._stream(job)
            else:
                self._queue.put(job)
        if self.autostart:
            self.start()
        return job

    def _submit_pooled(self, spec: JobSpec) -> Job:
        """Route one job through the process-isolated pool: the spec
        crosses as plain JSON, the result comes back as a host-side
        :class:`~tclb_tpu.serve.pool.PoolResult`.  Anything speaking
        the pool protocol slots in via the ``pool=`` constructor arg —
        a local :class:`WorkerPool` or a whole pod behind a
        :class:`~tclb_tpu.cluster.server.ClusterServer` (the result
        then carries its serving ``host``)."""
        from tclb_tpu.serve.pool import PoolResult, pool_doc_from_spec
        doc = pool_doc_from_spec(spec)   # rejects plan/grad specs early
        with self._lock:
            self._jobs += 1
            job = Job(spec, self._jobs)
            self._inflight[job.id] = job
        telemetry.counter("serve.jobs.submitted")
        telemetry.event("serve.job_queued", job_id=job.id,
                        name=spec.name, model=spec.model.name,
                        shape=list(spec.shape), niter=int(spec.niter),
                        route="pool", reason="process_isolation")

        def _done(pj) -> None:
            job.attempts = pj.attempts
            if pj.error is None:
                job._finish(PoolResult(spec.case, pj._result), None)
            else:
                job._finish(None, pj.error)
            self._stream(job)

        self._pool.submit(doc, on_done=_done)
        if self.autostart:
            self.start()
        return job

    def run(self, specs: Sequence[JobSpec]) -> list[Job]:
        """Submit all, wait for all; failed jobs keep their error on the
        handle instead of raising."""
        jobs = [self.submit(s) for s in specs]
        self.start()
        for j in jobs:
            try:
                j.result()
            except Exception:  # noqa: BLE001 - surfaced on the handle
                pass
        return jobs

    def close(self, wait: bool = True, join_timeout: float = 60.0) -> None:
        self._closing = True
        self._stop_probes.set()
        if self._pool is not None:
            # finishes or fails every pool job first, so the pending
            # sweep below only sees what the pool could not deliver
            self._pool.close(wait=wait)
        if wait and self._started:
            deadline = time.monotonic() + join_timeout
            for t in self._probe_threads:
                t.join(timeout=1.0)
            if self._shard_worker is not None:
                # first: it may degrade a failed sharded job back onto
                # the lane queue, which the stagers must still drain
                self._shard_worker.join(
                    timeout=max(0.1, deadline - time.monotonic()))
            for lane in self.lanes:
                if lane._stager is not None:
                    lane._stager.join(
                        timeout=max(0.1, deadline - time.monotonic()))
                if lane._exec is not None:
                    lane._exec.join(
                        timeout=max(0.1, deadline - time.monotonic()))
        # same close/timeout contract as Scheduler.close: anything still
        # unfinished surfaces as failed-not-hung
        now = time.monotonic()
        with self._lock:
            pending = [j for j in self._inflight.values()
                       if not j._done.is_set()]
            self._inflight.clear()
        for job in pending:
            t = job.spec.timeout_s
            if t is not None and now >= job.submitted + t:
                job._finish(None, JobTimeout(
                    f"job {job.id} timed out during close "
                    f"(waited {now - job.submitted:.2f}s)"))
                telemetry.counter("serve.jobs.timeout")
            else:
                job._finish(None, RuntimeError(
                    f"job {job.id}: dispatcher closed before it finished"))
        telemetry.event("span", name="serve.fleet",
                        dur_s=round(now - self._t0, 6),
                        lanes=len(self.lanes), jobs=self._jobs,
                        evicted=sum(1 for l in self.lanes if l.evicted))
        tlive.unregister_status("fleet", self._status)
        if self._monitor is not None:
            self._monitor.stop()
            self._monitor = None
        if self._flight_attached:
            self._flight_attached = False
            tlive.flight_recorder().detach()

    def __enter__(self) -> "FleetDispatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- binning ------------------------------------------------------------ #

    def batch_cap(self, spec: JobSpec) -> int:
        sdt = spec.storage_dtype if spec.storage_dtype is not None \
            else spec.dtype
        cap = fusion.ensemble_batch_cap(
            spec.model.n_storage, tuple(spec.shape),
            jnp.dtype(sdt).itemsize)
        if self.max_batch is not None:
            cap = min(cap, int(self.max_batch))
        return max(1, cap)

    def _plan_for(self, spec: JobSpec, key: tuple) -> EnsemblePlan:
        with self._plan_lock:
            plan = self._plans.get(key)
            if plan is None:
                plan = spec.plan if spec.plan is not None else EnsemblePlan(
                    spec.model, spec.shape, flags=spec.flags,
                    dtype=spec.dtype, base_settings=spec.base_settings,
                    storage_dtype=spec.storage_dtype, grad=spec.grad)
                self._plans[key] = plan
            return plan

    def _take_batch(self, lane: Lane) -> Optional[list[Job]]:
        """One compatible batch for ``lane`` off the shared queue.  The
        cap is the memory predicate AND a fair share of the visible
        burst, so 16 queued jobs land one-batch-per-device instead of
        one lane swallowing them all."""
        if lane.reserved is not None:
            # a spill tenant holds the device; don't pull work the lane
            # cannot run — the queue stays for the unreserved lanes
            time.sleep(0.05)
            return None
        try:
            first = self._queue.get(timeout=0.1)
        except queue.Empty:
            return None
        if getattr(first, "pin", None) not in (None, lane.index):
            self._queue.put(first)
            return []
        now = time.monotonic()
        t = first.spec.timeout_s
        if t is not None and now > first.submitted + t:
            first._finish(None, JobTimeout(
                f"job {first.id} expired in queue "
                f"(waited {now - first.submitted:.2f}s)"))
            telemetry.counter("serve.jobs.timeout")
            self._stream(first)
            return []
        key = _bin_key(first.spec)
        active = max(1, sum(1 for l in self.lanes
                            if not l.evicted and l.reserved is None))
        fair = -(-(self._queue.qsize() + 1) // active)  # ceil
        cap = max(1, min(self.batch_cap(first.spec), fair))
        batch, requeue = [first], []
        while len(batch) < cap:
            try:
                j = self._queue.get_nowait()
            except queue.Empty:
                break
            if getattr(j, "pin", None) not in (None, lane.index) \
                    or _bin_key(j.spec) != key:
                requeue.append(j)
            else:
                batch.append(j)
        for j in requeue:
            self._queue.put(j)
        return batch

    # -- lane runners ------------------------------------------------------- #

    def _run_batched(self, lane: Lane, plan: EnsemblePlan,
                     cases: Sequence[Case], niter: int,
                     inputs: tuple) -> list[EnsembleResult]:
        faults.fire("serve.lane_dispatch", rail="lane", lane=lane.index,
                    batch=len(cases))
        compiled = lane.cache.get(plan, batch=len(cases), niter=int(niter),
                                  fn=plan.build_fn(init=True), init=True,
                                  device=lane.device)
        out = compiled(*inputs)
        # kick off the D2H copies while the lane stages its next batch;
        # results_from's np.asarray then finds the bytes already landing
        try:
            jax.tree.map(lambda x: x.copy_to_host_async(), out)
        except Exception:  # noqa: BLE001 - an optimization, never a verdict
            pass
        with telemetry.span("serve.d2h", lane=lane.index,
                            batch=len(cases),
                            job_ids=list(lane._current_job_ids)):
            return plan.results_from(cases, out)

    # -- sharded rail ------------------------------------------------------- #

    def _sharded_loop(self) -> None:
        try:
            self._sharded_loop_inner()
        except BaseException as e:  # noqa: BLE001 - post-mortem first
            tlive.flight_recorder().dump("sharded_loop_exception",
                                         error=repr(e))
            raise

    def _sharded_loop_inner(self) -> None:
        while True:
            try:
                job = self._sharded.get(timeout=0.1)
            except queue.Empty:
                if self._closing:
                    return
                continue
            now = time.monotonic()
            t = job.spec.timeout_s
            if t is not None and now > job.submitted + t:
                job._finish(None, JobTimeout(
                    f"job {job.id} expired in queue "
                    f"(waited {now - job.submitted:.2f}s)"))
                telemetry.counter("serve.jobs.timeout")
                self._stream(job)
                continue
            # coalesce: hold the lanes between batches, wait for in-
            # flight batches to finish, then take the whole fleet
            self._gate.clear()
            try:
                for lane in self.lanes:
                    lane._idle.wait(timeout=120.0)
                job.status = RUNNING
                job.attempts += 1
                spec = job.spec
                with telemetry.job_context(job.id), \
                        telemetry.span("serve.sharded_job",
                                       model=spec.model.name,
                                       shape=list(spec.shape),
                                       niter=int(spec.niter),
                                       devices=len(self.devices),
                                       job_id=job.id) as sp:
                    result = self._run_sharded(spec)
                    sp.add(outcome="ok")
                job._finish(result, None)
                self._stream(job)
            except Exception as e:  # noqa: BLE001 - ladder below
                if not job.degraded:
                    # next rung of the ladder: one lane instead of the
                    # whole fleet
                    job.degraded = True
                    telemetry.event("serve.job_degraded", job_id=job.id,
                                    rail="sharded", error=repr(e))
                    telemetry.counter("serve.sharded.degraded")
                    log.warning(f"fleet: sharded job {job.id} failed "
                                f"({e!r}); degrading to a single lane")
                    self._queue.put(job)
                else:
                    job._finish(None, e)
                    self._stream(job)
            finally:
                self._gate.set()

    def _run_sharded(self, spec: JobSpec) -> EnsembleResult:
        mesh = make_mesh(spec.shape, devices=self.devices)
        lat = Lattice(spec.model, spec.shape, dtype=spec.dtype,
                      settings=spec.base_settings, mesh=mesh)
        if spec.flags is not None:
            lat.set_flags(np.asarray(spec.flags, dtype=np.uint16))
        for name, value in spec.case.settings.items():
            lat.set_setting(name, float(value))
        for (name, zone), value in spec.case.zonal.items():
            lat.set_setting(name, float(value), zone=int(zone))
        lat.init()
        if spec.niter > 0:
            lat.iterate(spec.niter)
        return EnsembleResult(case=spec.case, state=lat.state,
                              globals=lat.get_globals())

    # -- lane reservation (spill tenants) ------------------------------------ #

    def reserve_lane(self, tenant: str = "adjoint",
                     on_revoke: Optional[Callable] = None
                     ) -> Optional[LaneLease]:
        """Lease one idle lane's device to a non-serving tenant (the
        revolve peer-HBM spill tier), or None when no lane can be
        spared.  At least one healthy lane always stays unreserved so
        serving never starves; evicted lanes are never leased (their
        device already failed).  The lease is revocable: serving demand
        may reclaim the lane via :meth:`revoke_lease`, after the
        tenant's ``on_revoke`` migrated its data off the device."""
        with self._lock:
            free = [l for l in self.lanes
                    if not l.evicted and l.reserved is None]
            if len(free) < 2:
                return None   # keep the last healthy lane serving
            # prefer an idle lane: leasing mid-batch would co-host the
            # tenant's buffers with a running batch's working set
            lane = next((l for l in free if l._idle.is_set()), free[0])
            lane.reserved = tenant
            lease = LaneLease(self, lane, tenant, on_revoke)
            self._leases.append(lease)
        telemetry.counter("serve.lane_reserved")
        telemetry.event("serve.lane_reserved", lane=lane.index,
                        device=lane.device_str, tenant=tenant)
        return lease

    def release_lane(self, lease: LaneLease) -> None:
        """Return a leased lane to serving (idempotent)."""
        with self._lock:
            if lease.released:
                return
            lease.released = True
            if lease in self._leases:
                self._leases.remove(lease)
            lease.lane.reserved = None
        telemetry.counter("serve.lane_released")
        telemetry.event("serve.lane_released", lane=lease.lane.index,
                        device=lease.lane.device_str, tenant=lease.tenant)

    def revoke_lease(self, lease: LaneLease, reason: str = "demand") -> None:
        """Reclaim a leased lane for serving: notify the tenant (which
        must migrate its device-resident data — the revolve store
        re-spills peer snapshots to disk), then release the lane.  The
        callback runs OUTSIDE the dispatcher lock: it does device work
        (D2H fetches + disk writes)."""
        with self._lock:
            if lease.released or lease.revoked:
                return
            lease.revoked = True
        telemetry.counter("serve.lane_revoked")
        telemetry.event("serve.lane_revoked", lane=lease.lane.index,
                        device=lease.lane.device_str, tenant=lease.tenant,
                        reason=reason)
        if lease.on_revoke is not None:
            try:
                lease.on_revoke(lease, reason)
            except Exception as e:  # noqa: BLE001 - reclaim regardless
                log.warning(f"fleet: lease revoke callback failed "
                            f"({lease.tenant}): {e!r}")
        self.release_lane(lease)

    # -- eviction / bookkeeping --------------------------------------------- #

    def _redistribute(self, batch: Sequence[Job]) -> None:
        """Hand an evicted lane's staged-but-unexecuted jobs back to the
        shared queue for the surviving lanes.  With no survivor left the
        jobs fail here — re-queueing after the all-evicted drain would
        strand them (nobody polls a dead fleet's queue) — unless lane
        probation is on, in which case they wait for a reinstatement."""
        if all(l.evicted for l in self.lanes) \
                and self.probe_interval_s is None:
            for j in batch:
                if not j._done.is_set():
                    j._finish(None, RuntimeError(
                        "fleet: all lanes evicted; no device can serve "
                        "the job"))
                    self._stream(j)
            return
        for j in batch:
            j.status = PENDING
            if getattr(j, "pin", None) is not None:
                j.pin = None  # its lane is gone; any survivor may serve
            self._queue.put(j)
        telemetry.counter("serve.jobs.redistributed", inc=len(batch))

    def _lane_evicted(self, lane: Lane) -> None:
        if self.probe_interval_s is not None and not self._closing:
            t = threading.Thread(target=self._probe_loop, args=(lane,),
                                 name=f"tclb-fleet-probe-{lane.index}",
                                 daemon=True)
            self._probe_threads.append(t)
            t.start()
            return  # probation: queued jobs wait for a reinstatement
        if all(l.evicted for l in self.lanes):
            log.warning("fleet: ALL lanes evicted; failing queued jobs")
            while True:
                try:
                    j = self._queue.get_nowait()
                except queue.Empty:
                    return
                if not j._done.is_set():
                    j._finish(None, RuntimeError(
                        "fleet: all lanes evicted; no device can serve "
                        "the job"))
                    self._stream(j)

    # -- lane probation ------------------------------------------------------ #

    def _default_probe(self, lane: Lane) -> None:
        """Canary: land a tiny buffer on the lane device and fence it.
        Raises when the device is still unhealthy."""
        jax.block_until_ready(
            jax.device_put(np.zeros(8, np.float32), lane.device))

    def _probe_loop(self, lane: Lane) -> None:
        interval = self.probe_interval_s
        while not self._closing and lane.evicted:
            if self._stop_probes.wait(interval):
                return
            if self._closing or not lane.evicted:
                return
            try:
                self._probe_runner(lane)
            except Exception as e:  # noqa: BLE001 - still unhealthy
                telemetry.event("serve.device_probe_failed",
                                lane=lane.index, device=lane.device_str,
                                error=repr(e))
                continue
            if self._reinstate(lane):
                return
            # old threads still alive: keep the lane on probation and
            # retry the whole probe/reinstate cycle next interval

    def _reinstate(self, lane: Lane) -> bool:
        """Rejoin a probed-healthy lane: restart its stage/exec threads
        (both exited on eviction) and let it pull from the shared queue
        again — redistribution back happens by construction.  Returns
        False (lane stays evicted) when an old thread outlives the join
        timeout: starting duplicates would let the fresh exec thread
        consume the old stager's trailing None sentinel and exit
        immediately, leaving staged batches nobody executes."""
        # the old threads exited on eviction (stage loop breaks, its
        # final None sentinel makes exec return); join them and drain
        # the sentinel so the fresh exec thread doesn't eat it
        me = threading.current_thread()
        for t in (lane._stager, lane._exec):
            if t is not None and t is not me:
                t.join(timeout=self.reinstate_join_s)
                if t.is_alive():
                    telemetry.event("serve.device_reinstate_deferred",
                                    lane=lane.index,
                                    device=lane.device_str,
                                    thread=t.name)
                    log.warning(f"fleet: lane {lane.index} thread "
                                f"{t.name} still alive after "
                                f"{self.reinstate_join_s}s; deferring "
                                "reinstatement to the next probe cycle")
                    return False
        while True:
            try:
                item = lane._staged.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                self._redistribute(item.batch)
        lane.failstreak = 0
        lane.evicted = False
        # emit BEFORE start(): once the lane threads run, a parked job can
        # complete and its observer must already see the reinstatement
        telemetry.event("serve.device_reinstated", device=lane.device_str,
                        lane=lane.index)
        telemetry.counter("serve.device_reinstated")
        lane.start()
        log.warning(f"fleet: lane {lane.index} ({lane.device_str}) "
                    "probed healthy; reinstated")
        return True

    def _stream(self, job: Job) -> None:
        self._inflight.pop(job.id, None)
        telemetry.counter("serve.jobs.done" if job.status == DONE
                          else "serve.jobs.failed")
        telemetry.event(
            "serve.job_done", job_id=job.id, status=job.status,
            attempts=job.attempts, degraded=job.degraded,
            wall_s=(None if job.finished_at is None else
                    round(job.finished_at - job.submitted, 6)))
        if self._on_result is not None:
            try:
                self._on_result(job)
            except Exception as e:  # noqa: BLE001 - callback is advisory
                log.warning(f"fleet: on_result callback failed: {e!r}")

    def stats(self) -> dict[str, Any]:
        """Per-lane counters for smoke checks and the sweep CLI."""
        return {
            "devices": [str(d) for d in self.devices],
            "lanes": [{"lane": l.index, "device": str(l.device),
                       "batches": l.batches, "evicted": l.evicted,
                       "reserved": l.reserved,
                       "cache": l.cache.stats()} for l in self.lanes],
            "jobs": self._jobs,
        }
