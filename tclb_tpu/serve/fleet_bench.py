"""The fleet-throughput workload, driven by the CI fast and chaos jobs'
dispatcher smoke (``python -m tclb_tpu.serve.fleet_bench --smoke``):

* **throughput** — the 16-small-cavity-job workload through the
  single-worker :class:`Scheduler` vs the :class:`FleetDispatcher`
  (same ``max_batch``, both warmed), reported as ``fleet_speedup_d8``;
* **staging overlap / occupancy** — a deeper run (several batches per
  lane) under a dedicated telemetry trace, summarized by the report
  CLI's Fleet table (``staging_overlap_pct`` must exceed 90% on the
  bench workload: host staging hides under device execution);
* **routing** — one large job whose ``cells x niter`` clears the work
  floor, which must route to the all-device sharded engine
  (``serve.route_sharded``) while the swarm stays on the lanes;
* **bit-parity** — per-lane results are compared bit-exactly against
  the sequential ``Lattice`` path (the serving contract).

Run standalone (CI smoke)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m tclb_tpu.serve.fleet_bench --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from tclb_tpu import telemetry
from tclb_tpu.models import get_model
from tclb_tpu.serve import (Case, EnsemblePlan, FleetDispatcher, JobSpec,
                            Scheduler)
from tclb_tpu.telemetry import report

DONE = "done"


def _cavity_flags(model, shape):
    flags = np.full(shape, model.flag_for("MRT"), np.uint16)
    flags[0] = model.flag_for("Wall")
    flags[-1] = model.flag_for("Wall")
    return flags


def make_specs(model, n: int, shape, niter: int) -> list[JobSpec]:
    """n cavity-class jobs in one bin (same flags/shape/niter, a nu
    ladder of cases)."""
    flags = _cavity_flags(model, shape)
    return [JobSpec(model=model, shape=shape,
                    case=Case(settings={"nu": 0.04 + 0.005 * (i % 12)},
                              name=f"cavity{i}"),
                    niter=niter, flags=flags,
                    base_settings={"nu": 0.05})
            for i in range(n)]


def _scrape(url: str) -> tuple[int, str, str]:
    """(status, content-type, body) for one monitor endpoint."""
    import urllib.request
    with urllib.request.urlopen(url, timeout=10) as r:
        return (r.status, r.headers.get("Content-Type", ""),
                r.read().decode("utf-8"))


def run_fleet(jobs: int = 16, shape=(24, 32), niter: int = 60,
              max_batch: int = 2, repeats: int = 2,
              overlap_batches: int = 4, smoke: bool = False,
              trace_out: Optional[str] = None,
              monitor: Optional[str] = None) -> dict:
    """Run the fleet workload; returns the JSON-ready result doc.

    With ``monitor`` set (a ``[host]:port`` spec; port 0 picks a free
    one) the telemetry-phase dispatcher serves the live HTTP plane and
    the workload scrapes ``/metrics`` + ``/status`` mid-run, embedding
    the scrape verdicts in the result doc — the CI smoke asserts them."""
    import jax
    devices = jax.devices()
    n_dev = len(devices)
    model = get_model("d2q9")
    if smoke:
        niter, repeats = min(niter, 10), 0
    specs = make_specs(model, jobs, shape, niter)
    plan = EnsemblePlan(model, shape, flags=_cavity_flags(model, shape),
                        base_settings={"nu": 0.05})
    doc: dict = {"devices": n_dev, "jobs": jobs, "niter": niter,
                 "max_batch": max_batch, "shape": list(shape)}

    # -- aggregate throughput: single worker vs fleet ----------------------- #
    if repeats > 0:
        sched = Scheduler(max_batch=max_batch)
        sched.run(specs)  # warm the compile cache
        t_sched = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            js = sched.run(specs)
            dt = time.perf_counter() - t0
            assert all(j.status == DONE for j in js), \
                [(j.status, repr(j.error)) for j in js if j.status != DONE]
            t_sched = dt if t_sched is None else min(t_sched, dt)
        sched.close()
        fleet = FleetDispatcher(max_batch=max_batch)
        fleet.run(specs)  # warm every lane's device-pinned cache
        t_fleet = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            js = fleet.run(specs)
            dt = time.perf_counter() - t0
            assert all(j.status == DONE for j in js), \
                [(j.status, repr(j.error)) for j in js if j.status != DONE]
            t_fleet = dt if t_fleet is None else min(t_fleet, dt)
        fleet.close()
        doc["t_scheduler_s"] = round(t_sched, 6)
        doc["t_fleet_s"] = round(t_fleet, 6)
        doc["fleet_speedup_d8"] = round(t_sched / t_fleet, 4)

    # -- telemetry phase: staging overlap, occupancy, routing --------------- #
    if trace_out is None:
        fd, trace = tempfile.mkstemp(prefix="fleet-trace-", suffix=".jsonl")
        os.close(fd)
    else:
        trace = trace_out
    prev_trace = telemetry.path()
    telemetry.enable(trace)
    try:
        n_tel = jobs if smoke else overlap_batches * n_dev * max_batch
        tel_specs = make_specs(model, n_tel, shape, niter)
        big_shape = (64, 64)  # y divisible by any n_devices <= 8
        # the routing work floor sits at 2x the swarm jobs' work, and the
        # big job is sized to clear it by another 2x — swarm on lanes,
        # big on the sharded rail, whatever jobs/niter the caller picked
        swarm_work = int(np.prod(shape)) * niter
        floor = 2 * swarm_work
        big_niter = max(50, -(-2 * floor // int(np.prod(big_shape))))
        big = JobSpec(model=model, shape=big_shape,
                      case=Case(settings={"nu": 0.05}, name="big"),
                      niter=big_niter, base_settings={"nu": 0.05})
        fleet2 = FleetDispatcher(max_batch=max_batch, shard_min_work=floor,
                                 monitor=monitor)
        if monitor is not None:
            # async submit so the scrape sees jobs genuinely in flight
            fjobs = [fleet2.submit(s) for s in tel_specs]
            fleet2.start()
            from tclb_tpu.telemetry import live as tlive
            st, ctype, body = _scrape(fleet2.monitor_url + "/metrics")
            doc["monitor_metrics_ok"] = bool(
                st == 200 and ctype == tlive.CONTENT_TYPE
                and "tclb_" in body)
            st, _ctype, body = _scrape(fleet2.monitor_url + "/status")
            status = json.loads(body) if st == 200 else {}
            fstat = status.get("fleet") or {}
            doc["monitor_status_ok"] = bool(
                st == 200 and len(fstat.get("lanes", [])) == n_dev)
            doc["monitor_status_jobs_submitted"] = \
                fstat.get("jobs_submitted")
            for j in fjobs:
                try:
                    j.result()
                except Exception:  # noqa: BLE001 - surfaced on handle
                    pass
        else:
            fjobs = fleet2.run(tel_specs)
        bjob = fleet2.submit(big)
        try:
            bjob.result(timeout=600)
        except Exception:  # noqa: BLE001 - surfaced via status below
            pass
        fleet2.close()
    finally:
        telemetry.disable()
        if prev_trace is not None:
            telemetry.enable(prev_trace)

    summary = report.summarize(report.load(trace))
    fl = summary.get("fleet") or {}
    doc["lanes_active"] = fl.get("lanes_active", 0)
    doc["staging_overlap_pct"] = fl.get("staging_overlap_pct")
    doc["mean_occupancy_pct"] = fl.get("mean_occupancy_pct")
    doc["route_sharded_events"] = fl.get("routed_sharded", 0)
    doc["devices_evicted"] = fl.get("devices_evicted", 0)
    doc["sharded_job_status"] = bjob.status
    doc["trace"] = trace if trace_out is not None else None
    if trace_out is None:
        os.unlink(trace)

    # -- bit-parity: lanes and the sharded rail vs sequential --------------- #
    parity_ok = all(j.status == DONE for j in fjobs) \
        and bjob.status == DONE
    # one job per active lane-batch sample + the sharded job; the full
    # sweep would re-run every case sequentially
    for j in fjobs[:: max(1, len(fjobs) // 4)]:
        seq = plan.run_sequential(j.spec.case, niter)
        got = j.result()
        parity_ok = parity_ok and np.array_equal(
            np.asarray(got.state.fields), np.asarray(seq.state.fields)) \
            and got.globals == seq.globals
    if bjob.status == DONE:
        big_plan = EnsemblePlan(model, big_shape,
                                base_settings={"nu": 0.05})
        seq = big_plan.run_sequential(big.case, big_niter)
        got = bjob.result()
        parity_ok = parity_ok and np.array_equal(
            np.asarray(got.state.fields), np.asarray(seq.state.fields))
    doc["parity_ok"] = bool(parity_ok)
    return doc


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tclb_tpu.serve.fleet_bench",
        description="Fleet dispatcher throughput workload / CI smoke.")
    p.add_argument("--smoke", action="store_true",
                   help="CI mode: skip the timing laps, tiny niter")
    p.add_argument("--jobs", type=int, default=16)
    p.add_argument("--niter", type=int, default=60)
    p.add_argument("--max-batch", type=int, default=2)
    p.add_argument("--repeats", type=int, default=2)
    p.add_argument("--trace-out", default=None,
                   help="keep the telemetry trace at this path")
    p.add_argument("--monitor", default=None, metavar="[HOST]:PORT",
                   help="serve the live HTTP monitor during the "
                   "telemetry phase and scrape it mid-run (port 0 "
                   "picks a free one)")
    args = p.parse_args(argv)
    doc = run_fleet(jobs=args.jobs, niter=args.niter,
                    max_batch=args.max_batch, repeats=args.repeats,
                    smoke=args.smoke, trace_out=args.trace_out,
                    monitor=args.monitor)
    print(json.dumps(doc, indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
