"""Solver worker subprocess: one process per pool lane.

``python -m tclb_tpu.serve.worker --lane N`` is the child half of
:class:`~tclb_tpu.serve.pool.WorkerPool` — the process-isolation unit
that mirrors the reference TCLB's MPI rank: a wedged device, a hung XLA
compile, or a native crash kills *this* process, and the supervisor in
the parent restarts it without taking down sibling lanes or the serving
front door.

IPC protocol (length-prefixed pipes, stdin/stdout):

* every frame is an 8-byte ``!II`` header (JSON length, payload length)
  followed by a UTF-8 JSON document and an optional raw binary payload
  (``.npy`` bytes for array data) — **never** pickled device arrays, so
  a malicious or corrupt peer can at worst feed bad numbers, not code;
* parent -> worker: ``{"t": "job", "id": ..., "spec": {...}}`` and
  ``{"t": "shutdown"}``;
* worker -> parent: ``{"t": "ready"}`` once importable, ``{"t": "hb"}``
  heartbeats *during* execution (progress-based: one per solve chunk, so
  a wedged device stops the beat), optional ``{"t": "telemetry"}``
  frames (batched event docs relayed to the parent fan-out — only when
  the supervisor requested relay via ``TCLB_POOL_RELAY=1``), optional
  ``{"t": "progress"}`` frames (iteration / MLUPS / wall and opt-in
  downsampled quantity reductions, when the spec asks for them), and
  ``{"t": "result"}`` with globals, per-phase wall times, an optional
  ``state_sha256`` digest, and an optional ``.npy`` payload of the
  final fields.

Telemetry relay discipline: the relay sink is a bounded queue
(:data:`RELAY_QUEUE_CAP`; overflow is dropped and counted), flushed only
*between* solve chunks right after the heartbeat — never mid-kernel, and
never before the beat, so a wedged relay (its own chaos point,
``pool.telemetry_relay``) can delay telemetry but not liveness.  When
the supervisor does not request relay, no queue, subscriber, or clock
read exists at all.

Resumable jobs (``spec["ckpt_root"]``) save through
:class:`~tclb_tpu.checkpoint.manager.CheckpointManager` at deterministic
absolute segment boundaries and re-enter via ``latest()`` on restart, so
a SIGKILLed worker's job finishes bit-identical to an uninterrupted run.

Fault points fired *inside* the worker (the plan crosses the process
boundary via ``TCLB_FAULTS``, re-serialized by the pool at spawn):
``pool.heartbeat`` (``error`` wedges the worker mid-solve — the missed
heartbeat the supervisor must catch; ``slow`` delays the beat) and
``pool.worker_exit`` (``error`` hard-exits the process at a job start or
segment boundary — the crash the supervisor must absorb).

The worker claims the real stdout fd for frames at startup and rebinds
``sys.stdout``/fd 1 to stderr, so a stray ``print`` (or a chatty
library) can never corrupt the frame stream.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Any, BinaryIO, Optional

# the frame protocol grew up here and moved to cluster/wire.py when the
# control channel adopted it; re-exported so existing imports
# (`from tclb_tpu.serve.worker import read_frame`, the pool, tests)
# keep working
from tclb_tpu.cluster.wire import (MAX_FRAME, IpcError,  # noqa: F401
                                   npy_bytes, npy_load, read_frame,
                                   write_frame)


# --------------------------------------------------------------------------- #
# Telemetry relay: worker events -> supervisor pipe (between chunks only)
# --------------------------------------------------------------------------- #

#: bounded relay queue: events accumulated between two solve-chunk
#: flushes beyond this cap are dropped (and counted) rather than growing
#: worker memory while the supervisor-side reader is slow or blocked
RELAY_QUEUE_CAP = 512


class _TelemetryRelay:
    """Worker-side bridge from the in-process telemetry fan-out to the
    supervisor pipe.

    :meth:`sink` is an ``events.subscribe`` subscriber (subscribing it is
    what turns the worker's telemetry on): it appends event docs to a
    bounded deque and counts overflow — O(1), no I/O, safe under the
    events lock.  :meth:`flush` drains the queue into one
    ``{"t": "telemetry"}`` frame and runs only between solve chunks,
    right *after* a heartbeat.  A failed or faulted flush (the
    ``pool.telemetry_relay`` chaos point) drops the batch and counts it
    — relay loss is observable via ``pool.relay_dropped``, but the relay
    can never block a heartbeat or fail the job.
    """

    def __init__(self, lane: int, cap: int = RELAY_QUEUE_CAP) -> None:
        from collections import deque

        from tclb_tpu.telemetry import locks
        self.lane = lane
        self.cap = max(1, int(cap))
        self._q: "Any" = deque()
        # deque append/popleft are atomic; the lock guards only the
        # dropped counters (checkpoint async-save threads emit too)
        self._lock = locks.make_lock("serve.worker._TelemetryRelay._lock")
        self.dropped_total = 0
        self._dropped_pending = 0

    def __len__(self) -> int:
        return len(self._q)

    def sink(self, doc: dict) -> None:
        # counters snapshots stay worker-local: the parent folds its own
        # counter sessions, and relaying a child's cumulative snapshot
        # would double-count in `telemetry report`
        if doc.get("kind") == "counters":
            return
        if len(self._q) >= self.cap:
            with self._lock:
                self.dropped_total += 1
                self._dropped_pending += 1
            return
        self._q.append(doc)

    def flush(self, out: BinaryIO, jid: str, trace_id: str,
              parent_span: Optional[str] = None) -> None:
        """Drain queued events into one relay frame (between chunks
        only).  Injected faults and write failures are contained here:
        the batch is dropped and counted, nothing propagates."""
        from tclb_tpu import faults
        q = self._q
        batch: list = []
        while q:
            try:
                batch.append(q.popleft())
            except IndexError:  # pragma: no cover — flush is the lone consumer
                break
        with self._lock:
            dropped = self._dropped_pending
            self._dropped_pending = 0
        if not batch and not dropped:
            return
        docs = []
        for ev in batch:
            d = dict(ev)  # subscribers share the doc: stamp a copy
            d.setdefault("job_id", trace_id)
            if parent_span is not None:
                d.setdefault("parent_span", parent_span)
            docs.append(d)
        try:
            verdict = faults.fire("pool.telemetry_relay", lane=self.lane,
                                  job=jid, batch=len(docs))
            if verdict == "torn":
                # a half-written relay frame would desync the whole
                # pipe; the contained truncation writes nothing at all
                raise IpcError("torn relay frame")
            write_frame(out, {"t": "telemetry", "id": jid,
                              "events": docs, "dropped": dropped})
        except Exception:  # noqa: BLE001 — relay loss is counted, never fatal
            with self._lock:
                self.dropped_total += len(docs)
                self._dropped_pending += len(docs) + dropped


# --------------------------------------------------------------------------- #
# Solve execution (the only jax-touching half; imports stay lazy so the
# protocol helpers above are importable from the device-free supervisor)
# --------------------------------------------------------------------------- #


def _stream_sample(lat, stream_spec) -> Optional[dict]:
    """Downsampled quantity reduction for one progress frame — computed
    at a segment boundary (the iterate fence has already synced), so the
    extract never races device execution.  Kilobytes, never a full
    field dump."""
    import numpy as np

    from tclb_tpu.utils.render import downsample
    cfg = stream_spec if isinstance(stream_spec, dict) else {}
    qty = cfg.get("quantity")
    used = qty
    try:
        arr = None
        if qty:
            try:
                arr = np.asarray(lat.get_quantity(qty))
            except Exception:  # noqa: BLE001 — tolerate case drift
                names = {q.name.lower(): q.name
                         for q in getattr(lat.model, "quantities", ())}
                used = names.get(str(qty).lower())
                if used:
                    arr = np.asarray(lat.get_quantity(used))
        if arr is None:
            used = "field0"
            arr = np.asarray(lat.state.fields)[0]
        arr = np.asarray(arr, dtype=np.float64)
        while arr.ndim > 2:
            arr = arr[arr.shape[0] // 2]
        if arr.ndim < 2:
            arr = np.atleast_2d(arr)
        coarse = downsample(arr, int(cfg.get("max_dim") or 32))
        return {"quantity": used or "field0",
                "mean": round(float(np.nanmean(arr)), 6),
                "min": round(float(np.nanmin(arr)), 6),
                "max": round(float(np.nanmax(arr)), 6),
                "shape": [int(s) for s in coarse.shape],
                "data": [[round(float(v), 6) for v in row]
                         for row in coarse]}
    except Exception:  # noqa: BLE001 — a reduction must never fail a job
        return None


def _solve(spec: dict, jid: str, lane: int, beat,
           progress=None) -> tuple[dict, bytes]:
    """Run one solve job from a plain-JSON spec; returns the result doc
    + optional ``.npy`` payload of the final fields.  ``progress``
    (optional) is called at each chunk boundary with
    ``(lat, done, start, solve_wall_s)`` to emit progress frames."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tclb_tpu import faults, telemetry
    from tclb_tpu.core.lattice import Lattice
    from tclb_tpu.models import get_model

    t_stage = time.perf_counter()
    with telemetry.span("serve.stage", job=jid, lane=lane):
        model = get_model(spec["model"])
        shape = tuple(int(s) for s in spec["shape"])
        precision = spec.get("dtype", "f32")
        if precision == "f64":
            jax.config.update("jax_enable_x64", True)
        dtype = jnp.float64 if precision == "f64" else jnp.float32
        sdt = {"bf16": jnp.bfloat16, "f32": jnp.float32,
               "f64": jnp.float64}.get(spec.get("storage_dtype"))
        settings = dict(spec.get("params") or {})
        settings.update((spec.get("case") or {}).get("settings") or {})
        niter = int(spec["niter"])

        lat = Lattice(model, shape, dtype=dtype, storage_dtype=sdt,
                      storage_repr=spec.get("storage_repr"),
                      settings=settings or None)
        mgr = None
        resumed_from: Optional[int] = None
        start = 0
        ckpt_root = spec.get("ckpt_root")
        if ckpt_root:
            from tclb_tpu.checkpoint.manager import CheckpointManager
            mgr = CheckpointManager(ckpt_root,
                                    keep_last=int(spec.get("checkpoint_keep")
                                                  or 2))
            newest = mgr.latest()
            if newest is not None:
                mgr.restore(lat, newest)
                start = int(np.asarray(lat.state.iteration))
                resumed_from = start
            else:
                lat.init()
        else:
            lat.init()
    stage_s = time.perf_counter() - t_stage
    beat(phase="built", iter=start)

    every = int(spec.get("checkpoint_every") or 0) if mgr else 0
    hb_every = int(spec.get("hb_iters") or 0) or every \
        or max(1, niter // 8)
    done = start
    t_solve = time.perf_counter()
    while done < niter:
        # chunk boundaries are ABSOLUTE multiples of the cadence, so a
        # resumed run (which starts at a checkpoint step) replays the
        # exact boundary sequence of an uninterrupted one — the
        # bit-identity contract
        nxt = min(niter, (done // hb_every + 1) * hb_every)
        if every:
            nxt = min(nxt, (done // every + 1) * every)
        lat.iterate(nxt - done)
        done = nxt
        if mgr and every and (done % every == 0 or done == niter):
            mgr.save(lat, step=done)
            try:
                faults.fire("pool.worker_exit", lane=lane, job=jid,
                            at="segment", step=done)
            except faults.InjectedFault:
                mgr.wait()
                os._exit(17)
        beat(iter=done)
        if progress is not None:
            progress(lat, done, start, time.perf_counter() - t_solve)
    solve_s = time.perf_counter() - t_solve
    if mgr:
        mgr.wait()

    t_d2h = time.perf_counter()
    with telemetry.span("serve.d2h", job=jid, lane=lane):
        doc: dict[str, Any] = {"globals": lat.get_globals(),
                               "iteration": done,
                               "resumed_from": resumed_from,
                               "lane": lane, "pid": os.getpid()}
        if spec.get("digest"):
            import hashlib
            arr = np.ascontiguousarray(np.asarray(lat.state.fields))
            doc["state_sha256"] = hashlib.sha256(arr.tobytes()).hexdigest()
        payload = b""
        if spec.get("return_state"):
            payload = npy_bytes(lat.state.fields)
    doc["phases"] = {"stage_s": round(stage_s, 6),
                     "solve_s": round(solve_s, 6),
                     "d2h_s": round(time.perf_counter() - t_d2h, 6)}
    return doc, payload


def _run_job(out: BinaryIO, lane: int, doc: dict,
             relay: Optional[_TelemetryRelay] = None) -> None:
    from tclb_tpu import faults
    jid = str(doc.get("id"))
    spec = doc.get("spec") or {}
    # the gateway threads its record id + parent span through the job
    # doc; relayed events are stamped with them so `telemetry report
    # --job <id>` stitches one cross-process timeline
    trace_id = str(spec.get("job_id") or jid)
    parent_span = spec.get("parent_span")

    def beat(**kw) -> None:
        try:
            faults.fire("pool.heartbeat", lane=lane, job=jid)
        except faults.InjectedFault:
            # a wedged worker: stop beating without exiting — the
            # supervisor's missed-heartbeat watchdog must catch this
            time.sleep(3600.0)
        write_frame(out, {"t": "hb", "id": jid, **kw})
        # relay flushes AFTER the beat, never before: a wedged relay
        # can delay telemetry, not liveness
        if relay is not None:
            relay.flush(out, jid, trace_id, parent_span)

    progress = None
    if spec.get("progress") or spec.get("stream"):
        stream_spec = spec.get("stream")
        niter = int(spec.get("niter") or 0)
        nodes = 1
        for s in (spec.get("shape") or ()):
            nodes *= int(s)

        def progress(lat, done, start, wall):  # noqa: F811
            frame = {"t": "progress", "id": jid, "iter": done,
                     "niter": niter, "wall_s": round(wall, 6)}
            if wall > 0 and done > start:
                frame["mlups"] = round(
                    nodes * (done - start) / wall / 1e6, 3)
            if stream_spec:
                sample = _stream_sample(lat, stream_spec)
                if sample is not None:
                    frame["reductions"] = sample
            write_frame(out, frame)

    try:
        if relay is not None:
            from tclb_tpu.telemetry import events
            events.set_job(trace_id)
        try:
            faults.fire("pool.worker_exit", lane=lane, job=jid,
                        at="start")
        except faults.InjectedFault:
            out.flush()
            os._exit(17)
        beat(phase="accepted")
        result, payload = _solve(spec, jid, lane, beat, progress)
        if relay is not None:
            # FIFO pipe: trailing telemetry lands before the parent's
            # own `serve.pool_job_done`, keeping the timeline ordered
            relay.flush(out, jid, trace_id, parent_span)
        write_frame(out, dict({"t": "result", "id": jid, "ok": True},
                              **result), payload)
    except BaseException as e:  # noqa: BLE001 — per-job verdict: a bad
        # spec fails the job, not the worker
        if relay is not None:
            relay.flush(out, jid, trace_id, parent_span)
        write_frame(out, {"t": "result", "id": jid, "ok": False,
                          "error": repr(e),
                          "error_kind": type(e).__name__})
    finally:
        if relay is not None:
            from tclb_tpu.telemetry import events
            events.set_job(None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tclb-worker",
        description="pool solver worker (speaks the WorkerPool frame "
                    "protocol on stdin/stdout; not for interactive use)")
    ap.add_argument("--lane", type=int, default=0,
                    help="pool lane index this worker serves")
    args = ap.parse_args(argv)

    # claim the frame channel, then point fd 1 (and sys.stdout) at
    # stderr so no library print can corrupt the protocol stream
    out = os.fdopen(os.dup(1), "wb")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    inp = os.fdopen(os.dup(0), "rb")

    from tclb_tpu.compile_cache import place_compile_cache
    from tclb_tpu.telemetry import live as tlive

    place_compile_cache()
    # a crashing worker leaves its own flight-<pid>.jsonl post-mortem
    tlive.flight_recorder().attach()

    # relay is opt-in by the supervisor: when unset, no queue, no
    # subscriber, no clock reads — the strict no-op discipline
    relay: Optional[_TelemetryRelay] = None
    if os.environ.get("TCLB_POOL_RELAY") == "1":
        from tclb_tpu.telemetry import events
        relay = _TelemetryRelay(args.lane)
        events.subscribe(relay.sink)

    write_frame(out, {"t": "ready", "pid": os.getpid(),
                      "lane": args.lane})
    while True:
        try:
            doc, _payload = read_frame(inp)
        except (EOFError, IpcError):
            return 0
        t = doc.get("t")
        if t == "shutdown":
            return 0
        if t == "job":
            _run_job(out, args.lane, doc, relay)


if __name__ == "__main__":
    sys.exit(main())
