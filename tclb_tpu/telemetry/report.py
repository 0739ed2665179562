"""Trace aggregation and diffing: ``python -m tclb_tpu.telemetry report``.

Turns a JSONL trace (telemetry/events.py) into the attribution the
BENCH/ROADMAP triage loop needs:

* **per-engine iterate summary** — for every engine the dispatch ran
  (``iterate`` spans grouped by their ``engine`` field): chunks, total
  iterations, wall time and aggregate MLUPS (total node-updates / total
  time); and the engine of the step a hybrid engine leaves for the
  Globals (``iterate.globals_step`` spans by their ``engine``: the
  generic Pallas tail, or ``xla``);
* **per-span table** — every span name with count/total/mean/max;
* **segments** — the ``segment`` spans of ``<Solve>``'s loop by the
  handlers that ran in them: the span, what its fences waited, the
  host's own time and where it went by span name;
* **set-up** — from the process's start to the first segment: the
  ``boot`` record, the ``startup.*`` spans (imports, devices, case,
  elements), ``engine.build``, the ``engine.probe.candidate`` runs, the
  ``compile`` events by program with their cache verdict and the span
  they fell under, and the seconds from ``main``'s entry to the first
  segment that no span owns;
* **dispatch history** — ``engine_selected`` decisions and the
  ``engine_fallback`` chain with each fallback's exception cause (the
  information the old free-form log strings swallowed);
* **failchecks and counters**.

``--compare other.jsonl`` diffs two traces engine-by-engine and
span-by-span, flagging slowdowns beyond ``--threshold`` (default 5%) —
the intended first tool for localizing a regression to an engine or a
span.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from typing import Optional


def load(path: str) -> list[dict]:
    """Parse a JSONL trace, skipping malformed lines (a crashed run may
    truncate its last line mid-write)."""
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(doc, dict) and "kind" in doc:
                out.append(doc)
    return out


def _percentile(vals: list, q: float) -> Optional[float]:
    if not vals:
        return None
    vals = sorted(vals)
    i = (len(vals) - 1) * q
    lo = int(i)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (i - lo)


def _serving_summary(evts: list[dict]) -> dict:
    """The serving health numbers (from ``serve.batch``/``serve.compile``
    spans): batch occupancy, queue wait percentiles, compile-cache hit
    rate.  Empty dict when the trace has no serving activity."""
    batches = [e for e in evts if e.get("kind") == "span"
               and e.get("name") == "serve.batch"]
    compiles = [e for e in evts if e.get("kind") == "span"
                and e.get("name") == "serve.compile"]
    if not batches and not compiles:
        return {}
    out: dict = {}
    if batches:
        jobs = sum(int(b.get("batch", 0)) for b in batches)
        cap = sum(int(b.get("capacity", 0)) for b in batches)
        waits = [float(w) for b in batches
                 for w in (b.get("wait_s") or [])]
        out["batches"] = len(batches)
        out["jobs"] = jobs
        out["occupancy_pct"] = (round(100.0 * jobs / cap, 2)
                                if cap else None)
        out["degraded_batches"] = sum(
            1 for b in batches if b.get("outcome") == "degraded")
        p50, p95 = _percentile(waits, 0.50), _percentile(waits, 0.95)
        out["queue_wait_p50_s"] = None if p50 is None else round(p50, 6)
        out["queue_wait_p95_s"] = None if p95 is None else round(p95, 6)
    if compiles:
        hits = sum(1 for c in compiles if c.get("cache") == "hit")
        out["compile_lookups"] = len(compiles)
        out["cache_hit_rate_pct"] = round(100.0 * hits / len(compiles), 2)
        out["compile_miss_s"] = round(sum(
            float(c.get("dur_s", 0.0)) for c in compiles
            if c.get("cache") == "miss"), 6)
    return out


def _adjoint_summary(evts: list[dict]) -> dict:
    """The gradient-engine health numbers (from ``adjoint.sweep``
    spans): per (model, mode) sweep counts, wall time, snapshots held,
    recompute factor and spilled bytes.  Empty dict when the trace has
    no adjoint activity."""
    sweeps = [e for e in evts if e.get("kind") == "span"
              and e.get("name") == "adjoint.sweep"]
    if not sweeps:
        return {}
    rows: dict[str, dict] = {}
    for s in sweeps:
        key = f"{s.get('model', '?')}/{s.get('mode', '?')}"
        row = rows.setdefault(key, {
            "sweeps": 0, "total_s": 0.0, "peak_snapshots": 0,
            "spill_bytes": 0, "spill_mem": 0, "spill_peer": 0,
            "spill_disk": 0, "recompute_factor": None,
            "engine": s.get("engine")})
        row["sweeps"] += 1
        row["total_s"] += float(s.get("dur_s", 0.0))
        row["peak_snapshots"] = max(row["peak_snapshots"],
                                    int(s.get("peak_snapshots", 0) or 0))
        row["spill_bytes"] += int(s.get("spill_bytes", 0) or 0)
        for tier in ("spill_mem", "spill_peer", "spill_disk"):
            row[tier] += int(s.get(tier, 0) or 0)
        if s.get("recompute_factor") is not None:
            row["recompute_factor"] = float(s["recompute_factor"])
        if s.get("engine") is not None:
            row["engine"] = s["engine"]
    for row in rows.values():
        row["total_s"] = round(row["total_s"], 6)
    return {"modes": dict(sorted(rows.items())),
            "sweeps": sum(r["sweeps"] for r in rows.values())}


def _fleet_summary(evts: list[dict]) -> dict:
    """The fleet dispatcher's health numbers: per-device occupancy (lane
    busy time over the ``serve.fleet`` lifetime span), queue waits, the
    staging-overlap fraction, and the routing/eviction event counts.

    Staging overlap is the fraction of host-staging time hidden under
    device execution, ``1 - sum(stall_s)/sum(stage_s)`` over
    ``serve.lane_batch`` spans — a lane's first fill has nothing to
    overlap with and is excluded (``first=True`` rows).
    ``serve.fleet_bench`` wants >90% on its workload."""
    lanes = [e for e in evts if e.get("kind") == "span"
             and e.get("name") == "serve.lane_batch"]
    fleet = [e for e in evts if e.get("kind") == "span"
             and e.get("name") == "serve.fleet"]
    routed = sum(1 for e in evts if e.get("kind") == "serve.route_sharded")
    evicted = sum(1 for e in evts
                  if e.get("kind") == "serve.device_evicted")
    if not lanes and not fleet and not routed:
        return {}
    wall = sum(float(f.get("dur_s", 0.0)) for f in fleet) or None
    per: dict[str, dict] = {}
    stage_tot = stall_tot = 0.0
    waits: list[float] = []
    for b in lanes:
        dev = str(b.get("device", "?"))
        row = per.setdefault(dev, {"batches": 0, "jobs": 0, "busy_s": 0.0})
        row["batches"] += 1
        row["jobs"] += int(b.get("batch", 0))
        row["busy_s"] += float(b.get("dur_s", 0.0))
        waits.extend(float(w) for w in (b.get("wait_s") or []))
        if not b.get("first"):
            stage_tot += float(b.get("stage_s", 0.0))
            stall_tot += float(b.get("stall_s", 0.0))
    for row in per.values():
        row["busy_s"] = round(row["busy_s"], 6)
        row["occupancy_pct"] = (round(100.0 * row["busy_s"] / wall, 2)
                                if wall else None)
    occ = [r["occupancy_pct"] for r in per.values()
           if r["occupancy_pct"] is not None]
    p50, p95 = _percentile(waits, 0.50), _percentile(waits, 0.95)
    return {
        "lanes": dict(sorted(per.items())),
        "lanes_active": sum(1 for r in per.values() if r["jobs"] > 0),
        "batches": len(lanes),
        "jobs": sum(r["jobs"] for r in per.values()),
        "wall_s": None if wall is None else round(wall, 6),
        "mean_occupancy_pct": (round(sum(occ) / len(occ), 2)
                               if occ else None),
        "staging_overlap_pct": (
            round(100.0 * (1.0 - stall_tot / stage_tot), 2)
            if stage_tot > 0 else None),
        "queue_wait_p50_s": None if p50 is None else round(p50, 6),
        "queue_wait_p95_s": None if p95 is None else round(p95, 6),
        "routed_sharded": routed,
        "devices_evicted": evicted,
    }


def _gateway_summary(evts: list[dict]) -> dict:
    """The serving front door's health numbers (from ``gateway.*``
    events): admissions, rejections by reason, per-tenant queue-wait
    percentiles and the resumed-job count.  Empty dict when the trace
    has no gateway activity."""
    admitted = [e for e in evts if e.get("kind") == "gateway.admitted"]
    rejected = [e for e in evts if e.get("kind") == "gateway.rejected"]
    resumed = [e for e in evts if e.get("kind") == "gateway.resumed"]
    done = [e for e in evts if e.get("kind") == "gateway.job_done"]
    recovered = sum(1 for e in evts
                    if e.get("kind") == "gateway.recovered")
    if not admitted and not rejected and not done:
        return {}
    by_reason: dict[str, int] = {}
    for e in rejected:
        r = str(e.get("reason", "?"))
        by_reason[r] = by_reason.get(r, 0) + 1
    by_status: dict[str, int] = {}
    waits: dict[str, list] = {}
    for e in done:
        by_status[str(e.get("status", "?"))] = \
            by_status.get(str(e.get("status", "?")), 0) + 1
        if e.get("queue_wait_s") is not None:
            waits.setdefault(str(e.get("tenant", "?")), []).append(
                float(e["queue_wait_s"]))
    tenants: dict[str, dict] = {}
    for t, vals in sorted(waits.items()):
        p50, p95 = _percentile(vals, 0.50), _percentile(vals, 0.95)
        tenants[t] = {
            "jobs": len(vals),
            "queue_wait_p50_s": None if p50 is None else round(p50, 6),
            "queue_wait_p95_s": None if p95 is None else round(p95, 6)}
    total = len(admitted) + len(rejected)
    return {
        "admitted": len(admitted),
        "rejected": len(rejected),
        "admission_rate_pct": (round(100.0 * len(admitted) / total, 2)
                               if total else None),
        "rejections_by_reason": dict(sorted(by_reason.items())),
        "jobs_by_status": dict(sorted(by_status.items())),
        "resumed": len(resumed),
        "recovered": recovered,
        "tenants": tenants,
    }


def _faults_summary(evts: list[dict]) -> dict:
    """Chaos-injection accounting (``fault.injected`` events) next to
    the recovery signals the faults should have triggered: retries,
    evictions/reinstatements, store degradations, checkpoint ENOSPC
    prunes.  Empty dict when the trace has no injected faults."""
    injected = [e for e in evts if e.get("kind") == "fault.injected"]
    if not injected:
        return {}
    by_point: dict[str, int] = {}
    for e in injected:
        key = f"{e.get('point', '?')}:{e.get('mode', '?')}"
        by_point[key] = by_point.get(key, 0) + 1
    def count(k: str) -> int:
        return sum(1 for e in evts if e.get("kind") == k)
    return {
        "injected": len(injected),
        "by_point_mode": dict(sorted(by_point.items())),
        "retries": count("serve.batch.retry"),
        "devices_evicted": count("serve.device_evicted"),
        "devices_reinstated": count("serve.device_reinstated"),
        "store_degraded": count("gateway.store_degraded"),
        "checkpoint_enospc": count("checkpoint.enospc"),
    }


_SLO_PHASES = (("queue_wait", "queue_wait_s"), ("stage", "stage_s"),
               ("solve", "solve_s"), ("d2h", "d2h_s"), ("e2e", "wall_s"))


def _slo_summary(evts: list[dict]) -> dict:
    """Per-phase latency distribution of finished gateway jobs (from
    ``gateway.job_done`` events: queue-wait, stage, solve, d2h, and
    door-to-result end-to-end).  Empty dict when the trace has no
    finished gateway jobs."""
    vals: dict[str, list] = {phase: [] for phase, _ in _SLO_PHASES}
    for e in evts:
        if e.get("kind") != "gateway.job_done":
            continue
        for phase, field in _SLO_PHASES:
            v = e.get(field)
            if v is not None:
                vals[phase].append(float(v))
    out: dict = {}
    for phase, _ in _SLO_PHASES:
        vs = vals[phase]
        if not vs:
            continue
        out[phase] = {
            "count": len(vs),
            "p50_s": round(_percentile(vs, 0.50), 6),
            "p95_s": round(_percentile(vs, 0.95), 6),
            "max_s": round(max(vs), 6)}
    return out


def _segments_summary(evts: list[dict]) -> dict:
    """The ``segment`` spans (one pass of ``<Solve>``'s loop each, the
    root of everything the pass did), grouped by the handlers that ran
    in them.  Per group the medians, in milliseconds: ``span_ms``;
    ``wait_ms``, what the fences of the span and all its descendants
    blocked (their ``wait_s``); ``host_ms``, the span less that: the
    host's own time, in which a fenced device had nothing to run;
    ``pre_sync_ms`` and ``dispatch_ms``, those fields summed over the
    segment (the device read before ``iterate``, the launches); and
    ``self_ms`` by span name, ``dur_s`` less children less ``wait_s``."""
    # span ids restart with every process: a file that sessions were
    # appended to holds each id once a session
    session, spans = 0, []
    for e in evts:
        if e.get("kind") == "trace_start":
            session += 1
        elif e.get("kind") == "span" and "id" in e:
            spans.append((session, e))
    kids: dict = {}
    for session, e in spans:
        kids.setdefault((session, e["parent"]), []).append(e)
    groups: dict = {}
    for session, seg in spans:
        if seg.get("name") != "segment":
            continue
        row = {"span_ms": 1e3 * seg["dur_s"], "wait_ms": 0.0,
               "pre_sync_ms": 0.0, "dispatch_ms": 0.0}
        own: dict = {}
        todo = [seg]
        while todo:
            e = todo.pop()
            mine = kids.get((session, e["id"]), [])
            todo += mine
            wait = e.get("wait_s", 0.0)
            row["wait_ms"] += 1e3 * wait
            row["pre_sync_ms"] += 1e3 * e.get("pre_sync_s", 0.0)
            row["dispatch_ms"] += 1e3 * e.get("dispatch_s", 0.0)
            own[e["name"]] = own.get(e["name"], 0.0) + 1e3 * (
                e["dur_s"] - sum(k["dur_s"] for k in mine) - wait)
        row["host_ms"] = row["span_ms"] - row["wait_ms"]
        shape = "+".join(k.get("handler", "?")
                         for k in kids.get((session, seg["id"]), [])
                         if k["name"] == "handler") or "none"
        groups.setdefault(shape, []).append((row, own))
    out = {}
    for shape, rows in groups.items():
        g = {k: round(statistics.median(r[k] for r, _ in rows), 4)
             for k in rows[0][0]}
        names = sorted({n for _, own in rows for n in own})
        g["self_ms"] = {n: round(statistics.median(
            own.get(n, 0.0) for _, own in rows), 4) for n in names}
        g["count"] = len(rows)
        out[shape] = g
    return out


#: roots of other threads' span trees (a span event carries no thread)
OTHER_THREAD_ROOTS = ("output.vtk.write",)


def _union_s(intervals: list, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` that the ``(start, end)`` intervals cover."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def _setup_summary(evts: list[dict]) -> dict:
    """The set-up tree of the file's first process, the reader of every
    span and field that covers the time before ``<Solve>``'s loop:

    * ``boot``: the three stamps as two intervals, ``process_to_package_s``
      and ``package_to_main_s``, and ``process_from``;
    * ``imports``: the ``startup.import`` spans (``module``,
      ``preloaded``); ``devices``: ``startup.devices``; ``case``:
      ``startup.case``; ``elements``: the ``startup.element`` spans in
      the order they started, with their ``depth`` under each other;
    * ``engine_build``: the ``engine.build`` spans; ``candidates``: the
      ``engine.probe.candidate`` runs (``tag``, ``cap``, ``result``,
      ``copy_s``);
    * ``compiles``: the ``compile`` events by ``program``, ``stage``,
      ``cache`` and the name of the span they fell ``under``, with their
      count and seconds, the longest first;
    * ``entry_to_segment_s``, from ``main``'s entry to the start of the
      first ``segment`` span, and ``unowned_s``, the part of it under no
      span without ``parent`` (the roots of other threads left out).

    Empty where the trace holds none of these."""
    # span ids restart with every process: the first session only
    starts = [i for i, e in enumerate(evts)
              if e.get("kind") == "trace_start"]
    first = evts[:starts[1]] if len(starts) > 1 else evts
    spans = [e for e in first if e.get("kind") == "span" and "id" in e]
    by_id = {e["id"]: e for e in spans}

    def start(e: dict) -> float:
        return e.get("t0", e["ts"] - e["dur_s"])

    def named(name: str) -> list:
        return sorted((e for e in spans if e["name"] == name), key=start)

    def row(e: dict, *keys: str) -> dict:
        return {"seconds": e["dur_s"],
                **{k: e[k] for k in keys if k in e}}

    out: dict = {}
    boot = next((e for e in first if e.get("kind") == "boot"), None)
    if boot is not None:
        out["boot"] = {
            "process_to_package_s": round(
                boot["t_package"] - boot["t_process"], 6),
            "package_to_main_s": round(
                boot["t_main"] - boot["t_package"], 6),
            "process_from": boot.get("process_from")}
    for key, name, fields in (
            ("imports", "startup.import", ("module", "preloaded")),
            ("devices", "startup.devices",
             ("count", "device_kind", "preloaded")),
            ("case", "startup.case", ("model", "shape")),
            ("engine_build", "engine.build",
             ("candidates", "selected", "tail")),
            ("candidates", "engine.probe.candidate",
             ("tag", "cap", "result", "copy_s"))):
        rows = [row(e, *fields) for e in named(name)]
        if rows:
            out[key] = rows
    elements = []
    for e in named("startup.element"):
        depth, up = 0, by_id.get(e.get("parent"))
        while up is not None and up["name"] == "startup.element":
            depth, up = depth + 1, by_id.get(up.get("parent"))
        elements.append({**row(e, "element", "nodes", "zones", "sharded",
                               "bytes", "series", "horizon"),
                         "depth": depth})
    if elements:
        out["elements"] = elements
    groups: dict = {}
    for e in first:
        if e.get("kind") != "compile":
            continue
        under = by_id.get(e.get("parent"))
        key = (e.get("program", e.get("fun_name")), e.get("stage"),
               e.get("cache"), under["name"] if under else None)
        g = groups.setdefault(key, {"count": 0, "seconds": 0.0})
        g["count"] += 1
        g["seconds"] += float(e.get("dur_s", 0.0))
    if groups:
        out["compiles"] = sorted(
            ({"program": p, "stage": st, "cache": c, "under": u,
              "count": g["count"], "seconds": round(g["seconds"], 6)}
             for (p, st, c, u), g in groups.items()),
            key=lambda r: -r["seconds"])
    segments = named("segment")
    if boot is not None and segments:
        lo, hi = boot["t_main"], start(segments[0])
        roots = [(start(e), start(e) + e["dur_s"]) for e in spans
                 if e.get("parent") is None
                 and e["name"] not in OTHER_THREAD_ROOTS]
        out["entry_to_segment_s"] = round(hi - lo, 6)
        out["unowned_s"] = round(hi - lo - _union_s(roots, lo, hi), 6)
    return out


# what an engine's account puts on ``iterate.fused`` (a tail engine's on
# ``iterate.globals_step``): counts of one call, which add up over a run,
# and the plan of its kernel's windows (on a mesh: of ONE shard), which
# does not change
ACCOUNT_SUMS = ("kernel_calls", "paired_calls", "remainder_steps",
                "resident_calls", "halo_bytes")
ACCOUNT_PLAN = ("shards", "z_bands", "band_slabs", "halo_slabs", "y_bands",
                "band_rows", "halo_rows", "aux_planes", "bands",
                "halo_operand_rows", "halo_operand_slabs", "vmem_bytes",
                "vmem_limit_bytes", "series_rows", "series_horizon",
                "series_bytes_per_step")


def summarize(evts: list[dict]) -> dict:
    """Aggregate one trace into the report structure (all plain dicts,
    JSON-serializable as-is)."""
    spans: dict[str, dict] = {}
    engines: dict[str, dict] = {}
    tails: dict[str, dict] = {}
    accounts: dict[str, dict] = {}
    selected: list[dict] = []
    fallbacks: list[dict] = []
    failchecks: list[dict] = []
    cnt: dict[str, float] = {}
    sess_cnt: dict[str, float] = {}
    kinds: dict[str, int] = {}

    def _fold_session() -> None:
        # counters snapshots are cumulative within one enable()..disable()
        # session (periodic + final flush), so a session contributes its
        # max per key; sessions (delimited by trace_start) add up
        for k, v in sess_cnt.items():
            cnt[k] = cnt.get(k, 0) + v
        sess_cnt.clear()

    for e in evts:
        kind = e.get("kind", "")
        kinds[kind] = kinds.get(kind, 0) + 1
        if kind == "span":
            name = e.get("name", "?")
            dt = float(e.get("dur_s", 0.0))
            s = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                        "max_s": 0.0})
            s["count"] += 1
            s["total_s"] += dt
            s["max_s"] = max(s["max_s"], dt)
            if name == "iterate":
                eng = e.get("engine", "?")
                g = engines.setdefault(eng, {
                    "chunks": 0, "iters": 0, "node_updates": 0.0,
                    "total_s": 0.0,
                    "storage_dtype": e.get("storage_dtype"),
                    "storage_repr": e.get("storage_repr")})
                if e.get("storage_dtype") is not None:
                    g["storage_dtype"] = e["storage_dtype"]
                if e.get("storage_repr") is not None:
                    g["storage_repr"] = e["storage_repr"]
                g["chunks"] += 1
                g["iters"] += int(e.get("iters", 0))
                g["node_updates"] += (float(e.get("nodes", 0.0))
                                      * float(e.get("iters", 0)))
                g["total_s"] += dt
            if (name in ("iterate.fused", "iterate.globals_step")
                    and "kernel_calls" in e):
                # the engine's own account of its calls
                # (Lattice._run_engine; the tail engine's lies on the
                # trailing step's span): the counts add up, the plan of
                # its windows is the newest call's
                g = accounts.setdefault(e.get("engine", "?"), {"calls": 0})
                g["calls"] += 1
                for k in ACCOUNT_SUMS:
                    if k in e:
                        g[k] = g.get(k, 0) + e[k]
                g.update({k: e[k] for k in ACCOUNT_PLAN if k in e})
            if name == "iterate.globals_step":
                # the step a hybrid engine leaves for the Globals, by
                # the engine that ran it (a trace from before the span
                # said so: "?")
                g = tails.setdefault(e.get("engine", "?"),
                                     {"steps": 0, "total_s": 0.0})
                g["steps"] += int(e.get("iters", 1))
                g["total_s"] += dt
        elif kind == "engine_selected":
            selected.append(e)
        elif kind == "engine_fallback":
            fallbacks.append(e)
        elif kind == "failcheck":
            failchecks.append(e)
        elif kind == "trace_start":
            _fold_session()
        elif kind == "counters":
            for k, v in (e.get("counters") or {}).items():
                sess_cnt[k] = max(sess_cnt.get(k, 0), v)
    _fold_session()
    for s in spans.values():
        s["total_s"] = round(s["total_s"], 6)
        s["mean_s"] = round(s["total_s"] / max(s["count"], 1), 6)
        s["max_s"] = round(s["max_s"], 6)
    for g in tails.values():
        g["total_s"] = round(g["total_s"], 6)
    for g in engines.values():
        if g["total_s"] > 0 and g["node_updates"] > 0:
            # significant digits, not decimals: tiny smoke domains sit
            # far below 1 MLUPS and must not collapse to 0
            g["mlups"] = float(f"{g['node_updates'] / g['total_s'] / 1e6:.6g}")
        else:
            g["mlups"] = None
        g["total_s"] = round(g["total_s"], 6)
        del g["node_updates"]
    return {"engines": engines, "globals_steps": tails,
            "accounts": accounts, "spans": spans,
            "segments": _segments_summary(evts),
            "setup": _setup_summary(evts),
            "serving": _serving_summary(evts),
            "adjoint": _adjoint_summary(evts),
            "fleet": _fleet_summary(evts),
            "gateway": _gateway_summary(evts),
            "slo": _slo_summary(evts),
            "faults": _faults_summary(evts),
            "engine_selected": [
                {k: v for k, v in e.items() if k not in ("kind",)}
                for e in selected],
            "fallbacks": [
                {k: v for k, v in e.items() if k not in ("kind",)}
                for e in fallbacks],
            "failchecks": failchecks,
            "counters": cnt,
            "event_counts": kinds}


def compare(base: dict, other: dict, threshold: float = 0.05) -> dict:
    """Diff two summaries (``base`` = reference, ``other`` = candidate).
    Positive deltas mean the candidate is faster/higher.  Entries whose
    MLUPS dropped (or span time grew) by more than ``threshold`` land in
    ``regressions``."""
    out: dict = {"engines": {}, "spans": {}, "regressions": [],
                 "threshold": threshold}
    for eng in sorted(set(base["engines"]) | set(other["engines"])):
        a = base["engines"].get(eng)
        b = other["engines"].get(eng)
        row: dict = {"base_mlups": a and a.get("mlups"),
                     "other_mlups": b and b.get("mlups")}
        if a and b and (a.get("storage_repr") or "raw") \
                != (b.get("storage_repr") or "raw"):
            # a storage-representation switch is a different compiled
            # program — like an engine change, it is a note, never a
            # throughput regression
            row["note"] = (
                f"storage repr changed "
                f"({a.get('storage_repr') or 'raw'} -> "
                f"{b.get('storage_repr') or 'raw'}) — not comparable")
        elif a and b and a.get("mlups") and b.get("mlups"):
            delta = (b["mlups"] - a["mlups"]) / a["mlups"]
            row["mlups_delta_pct"] = round(100 * delta, 2)
            if delta < -threshold:
                out["regressions"].append({
                    "what": "engine_mlups", "engine": eng,
                    "base": a["mlups"], "other": b["mlups"],
                    "delta_pct": row["mlups_delta_pct"]})
        elif a and not b:
            row["note"] = "engine absent in other trace"
        elif b and not a:
            row["note"] = "engine absent in base trace"
        out["engines"][eng] = row
    for name in sorted(set(base["spans"]) | set(other["spans"])):
        a = base["spans"].get(name)
        b = other["spans"].get(name)
        row = {"base_total_s": a and a["total_s"],
               "other_total_s": b and b["total_s"],
               "base_mean_s": a and a["mean_s"],
               "other_mean_s": b and b["mean_s"]}
        if a and b and a["mean_s"] > 0:
            delta = (b["mean_s"] - a["mean_s"]) / a["mean_s"]
            row["mean_delta_pct"] = round(100 * delta, 2)
            if delta > threshold:
                out["regressions"].append({
                    "what": "span_time", "span": name,
                    "base_mean_s": a["mean_s"], "other_mean_s": b["mean_s"],
                    "delta_pct": row["mean_delta_pct"]})
        out["spans"][name] = row
    # serving health: flag occupancy and cache-hit-rate drops (an
    # ensemble fleet quietly falling back to singleton batches is a
    # throughput regression timing alone may hide behind retries)
    sa = base.get("serving") or {}
    sb = other.get("serving") or {}
    if sa or sb:
        row = {"base_occupancy_pct": sa.get("occupancy_pct"),
               "other_occupancy_pct": sb.get("occupancy_pct"),
               "base_cache_hit_rate_pct": sa.get("cache_hit_rate_pct"),
               "other_cache_hit_rate_pct": sb.get("cache_hit_rate_pct")}
        for what, key in (("batch_occupancy", "occupancy_pct"),
                          ("compile_cache_hit_rate",
                           "cache_hit_rate_pct")):
            av, bv = sa.get(key), sb.get(key)
            if av and bv is not None:
                delta = (bv - av) / av
                row[f"{key}_delta_pct"] = round(100 * delta, 2)
                if delta < -threshold:
                    out["regressions"].append({
                        "what": what, "base": av, "other": bv,
                        "delta_pct": row[f"{key}_delta_pct"]})
        out["serving"] = row
    # fleet health: a shrinking per-device occupancy or a staging
    # overlap that stops hiding under execution is the multi-device
    # analogue of the batch-occupancy regression above
    fa = base.get("fleet") or {}
    fb = other.get("fleet") or {}
    if fa or fb:
        row = {"base_mean_occupancy_pct": fa.get("mean_occupancy_pct"),
               "other_mean_occupancy_pct": fb.get("mean_occupancy_pct"),
               "base_staging_overlap_pct": fa.get("staging_overlap_pct"),
               "other_staging_overlap_pct": fb.get("staging_overlap_pct"),
               "base_lanes_active": fa.get("lanes_active"),
               "other_lanes_active": fb.get("lanes_active")}
        for what, key in (("fleet_occupancy", "mean_occupancy_pct"),
                          ("fleet_staging_overlap",
                           "staging_overlap_pct")):
            av, bv = fa.get(key), fb.get(key)
            if av and bv is not None:
                delta = (bv - av) / av
                row[f"{key}_delta_pct"] = round(100 * delta, 2)
                if delta < -threshold:
                    out["regressions"].append({
                        "what": what, "base": av, "other": bv,
                        "delta_pct": row[f"{key}_delta_pct"]})
        la, lb = fa.get("lanes_active"), fb.get("lanes_active")
        if la and lb is not None and lb < la:
            out["regressions"].append({
                "what": "fleet_lanes_active", "base": la, "other": lb})
        out["fleet"] = row
    # gateway health: a falling admission rate means quota/saturation
    # rejections grew; a growing queue-wait p95 (worst tenant) means
    # jobs sit admitted-but-undispatched longer — both are front-door
    # regressions the span timings cannot see
    ga = base.get("gateway") or {}
    gb = other.get("gateway") or {}
    if ga or gb:
        def worst_p95(g: dict):
            vals = [t.get("queue_wait_p95_s")
                    for t in (g.get("tenants") or {}).values()
                    if t.get("queue_wait_p95_s") is not None]
            return max(vals) if vals else None
        row = {"base_admission_rate_pct": ga.get("admission_rate_pct"),
               "other_admission_rate_pct": gb.get("admission_rate_pct"),
               "base_queue_wait_p95_s": worst_p95(ga),
               "other_queue_wait_p95_s": worst_p95(gb)}
        av, bv = ga.get("admission_rate_pct"), gb.get("admission_rate_pct")
        if av and bv is not None:
            delta = (bv - av) / av
            row["admission_rate_delta_pct"] = round(100 * delta, 2)
            if delta < -threshold:
                out["regressions"].append({
                    "what": "gateway_admission_rate", "base": av,
                    "other": bv,
                    "delta_pct": row["admission_rate_delta_pct"]})
        wa, wb = worst_p95(ga), worst_p95(gb)
        if wa and wb is not None:
            delta = (wb - wa) / wa
            row["queue_wait_p95_delta_pct"] = round(100 * delta, 2)
            if delta > threshold:
                out["regressions"].append({
                    "what": "gateway_queue_wait_p95", "base": wa,
                    "other": wb,
                    "delta_pct": row["queue_wait_p95_delta_pct"]})
        out["gateway"] = row
    # per-phase SLO drift: a p95 that grew beyond the threshold names
    # WHICH phase of the door-to-result path regressed (queue vs stage
    # vs solve vs d2h) instead of just "jobs got slower"
    sa = base.get("slo") or {}
    sb = other.get("slo") or {}
    if sa or sb:
        rows: dict = {}
        for phase in (p for p, _ in _SLO_PHASES
                      if p in sa or p in sb):
            pa = (sa.get(phase) or {}).get("p95_s")
            pb = (sb.get(phase) or {}).get("p95_s")
            row = {"base_p95_s": pa, "other_p95_s": pb}
            if pa and pb is not None:
                delta = (pb - pa) / pa
                row["p95_delta_pct"] = round(100 * delta, 2)
                if delta > threshold:
                    out["regressions"].append({
                        "what": "slo_phase_p95", "phase": phase,
                        "base": pa, "other": pb,
                        "delta_pct": row["p95_delta_pct"]})
            rows[phase] = row
        out["slo"] = rows
    # adjoint tier split: parking snapshots on a peer device (or disk)
    # must stay cheap — a sweep whose mean wall time grew past the
    # threshold while the candidate's spill columns carry bytes
    # localizes the regression to a TIER, not just "gradients got
    # slower" (the CI spill-overhead gate keys on exactly this row)
    aa = (base.get("adjoint") or {}).get("modes") or {}
    ab = (other.get("adjoint") or {}).get("modes") or {}
    if aa or ab:
        def _tiers(r):
            return None if r is None else {
                "mem": int(r.get("spill_mem", 0) or 0),
                "peer": int(r.get("spill_peer", 0) or 0),
                "disk": int(r.get("spill_disk", 0) or 0)}

        def _mean(r):
            return None if not r or not r.get("sweeps") else \
                r["total_s"] / r["sweeps"]
        rows = {}
        for key in sorted(set(aa) | set(ab)):
            ra, rb = aa.get(key), ab.get(key)
            ma, mb = _mean(ra), _mean(rb)
            row = {"base_spill": _tiers(ra), "other_spill": _tiers(rb),
                   "base_mean_s": None if ma is None else round(ma, 6),
                   "other_mean_s": None if mb is None else round(mb, 6)}
            if ma and mb is not None:
                delta = (mb - ma) / ma
                row["mean_delta_pct"] = round(100 * delta, 2)
                if delta > threshold:
                    out["regressions"].append({
                        "what": "adjoint_sweep_time", "mode": key,
                        "base_mean_s": round(ma, 6),
                        "other_mean_s": round(mb, 6),
                        "delta_pct": row["mean_delta_pct"],
                        "other_spill": _tiers(rb)})
            rows[key] = row
        out["adjoint"] = rows
    # fallback-chain drift is a regression signal of its own (an engine
    # newly failing to compile shows up here before any timing does)
    fb_a = [(f.get("from"), f.get("to")) for f in base.get("fallbacks", [])]
    fb_b = [(f.get("from"), f.get("to")) for f in other.get("fallbacks", [])]
    if fb_a != fb_b:
        out["fallback_drift"] = {"base": fb_a, "other": fb_b}
        new = [f for f in fb_b if f not in fb_a]
        if new:
            out["regressions"].append({
                "what": "new_fallbacks", "fallbacks": new})
    return out


# -- per-job timeline --------------------------------------------------------- #


def job_events(evts: list[dict], job_id) -> list[dict]:
    """Every event attributed to ``job_id`` — via its own ``job_id`` /
    ``job`` field or membership in a batch's ``job_ids`` list."""
    jid = str(job_id)
    out = []
    for e in evts:
        ids = {str(e[k]) for k in ("job_id", "job") if e.get(k) is not None}
        ids.update(str(x) for x in (e.get("job_ids") or ()))
        if jid in ids:
            out.append(e)
    return out


_TIMELINE_VERBS = {
    "serve.job_queued": "queued",
    "serve.stage": "staged",
    "serve.batch": "dispatched",
    "serve.lane_batch": "dispatched",
    "serve.d2h": "d2h",
    "serve.sharded_job": "sharded",
    "serve.route_sharded": "routed",
    "serve.job_degraded": "degraded",
    "serve.job_done": "done",
    "failcheck": "failcheck",
    # gateway + pool verbs: with the cross-process relay, one --job
    # timeline runs gateway door -> worker kernel and back
    "gateway.admitted": "queued",
    "gateway.resumed": "resumed",
    "gateway.parked": "parked",
    "gateway.job_done": "done",
    "serve.pool_job_started": "worker-sent",
    "serve.pool_job_requeued": "requeued",
    "serve.pool_job_done": "pool-done",
    # cluster verbs: on a pod the same timeline crosses hosts —
    # admission -> host dispatch -> worker iterate spans -> done, each
    # relayed event carrying its `host` stamp
    "cluster.job_dispatched": "host-sent",
    "cluster.job_requeued": "requeued",
    "cluster.job_done": "host-done",
    "gateway.host_enrolled": "host-enroll",
    "gateway.host_lost": "host-lost",
    "gateway.host_rejoined": "host-rejoin",
}


def format_job_timeline(evts: list[dict], job_id) -> str:
    """One job's end-to-end timeline (queued -> staged -> dispatched ->
    d2h -> done, with retries/degrades/failchecks), offsets relative to
    its first event.  Span rows are placed at their *start* time
    (``ts - dur_s``; the trace stamps spans on exit)."""
    rows = job_events(evts, job_id)
    if not rows:
        return f"job {job_id}: no matching events in trace"

    def start_ts(e: dict) -> float:
        ts = float(e.get("ts", 0.0))
        if e.get("kind") == "span" and e.get("dur_s") is not None:
            return ts - float(e["dur_s"])
        return ts

    rows = sorted(rows, key=start_ts)
    t0 = start_ts(rows[0])
    lines = [f"job {job_id} timeline ({len(rows)} events)"]
    skip = {"kind", "ts", "name", "job_id", "job", "job_ids", "dur_s"}
    for e in rows:
        kind = e.get("kind")
        label = e.get("name") if kind == "span" else kind
        verb = _TIMELINE_VERBS.get(label, label)
        fields = " ".join(f"{k}={e[k]}" for k in e if k not in skip)
        if len(fields) > 120:
            fields = fields[:120] + "..."
        dur = (f"  ({float(e['dur_s']):.4f}s)"
               if e.get("dur_s") is not None else "")
        lines.append(f"  +{start_ts(e) - t0:8.4f}s  {verb:<11} "
                     f"{fields}{dur}")
    return "\n".join(lines)


# -- rendering --------------------------------------------------------------- #


def _fmt(v, nd=2) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}f}"
    return str(v)


def _format_setup(su: dict) -> list:
    lines = ["set-up (seconds; from the process's start to the first "
             "segment)"]
    if "boot" in su:
        b = su["boot"]
        lines.append(
            f"  boot: process to package "
            f"{_fmt(b['process_to_package_s'], 3)}, package to main "
            f"{_fmt(b['package_to_main_s'], 3)} (process start from "
            f"{b['process_from']})")
    for r in su.get("imports", []):
        lines.append(f"  import   {str(r.get('module')):<44} "
                     f"{_fmt(r['seconds'], 3):>9}  "
                     f"jax preloaded: {r.get('preloaded')}")
    for r in su.get("devices", []):
        lines.append(f"  devices  {r.get('count')} x "
                     f"{str(r.get('device_kind')):<39} "
                     f"{_fmt(r['seconds'], 3):>9}  "
                     f"backend preloaded: {r.get('preloaded')}")
    for r in su.get("case", []):
        lines.append(f"  case     {str(r.get('model')) + ' ' + str(r.get('shape')):<44} "
                     f"{_fmt(r['seconds'], 3):>9}")
    for r in su.get("elements", []):
        name = "  " * r["depth"] + str(r.get("element"))
        extra = (f"  nodes {r['nodes']} zones {r.get('zones')}"
                 if "nodes" in r else "")
        if "series" in r:   # a <Control>: its table
            extra += (f"  series {r['series']} horizon {r.get('horizon')}"
                      f" bytes {r.get('bytes')}")
        elif "bytes" in r:  # an initial field, and whether made in shards
            extra += f"  sharded {r.get('sharded')} bytes {r['bytes']}"
        lines.append(f"  element  {name:<44} "
                     f"{_fmt(r['seconds'], 3):>9}{extra}")
    for r in su.get("engine_build", []):
        lines.append(f"  build    {str(r.get('selected')):<44} "
                     f"{_fmt(r['seconds'], 3):>9}  "
                     f"of {r.get('candidates')}; tail {r.get('tail')}")
    for r in su.get("candidates", []):
        copy = (f"  copy {_fmt(r['copy_s'], 3)}" if "copy_s" in r else "")
        lines.append(f"  probe    {str(r.get('tag')):<44} "
                     f"{_fmt(r['seconds'], 3):>9}  cap {r.get('cap')} "
                     f"{r.get('result')}{copy}")
    if "compiles" in su:
        lines.append(f"  {'compiles by program':<34} {'stage':<16} "
                     f"{'cache':<5} {'count':>5} {'seconds':>9}  under")
        for r in su["compiles"][:24]:
            lines.append(f"  {str(r['program'])[:34]:<34} "
                         f"{str(r['stage']):<16} {str(r['cache']):<5} "
                         f"{r['count']:>5} {_fmt(r['seconds'], 3):>9}  "
                         f"{r['under']}")
        if len(su["compiles"]) > 24:
            rest = su["compiles"][24:]
            lines.append(f"  ... and {len(rest)} more rows, "
                         f"{_fmt(sum(r['seconds'] for r in rest), 3)} s")
    if "unowned_s" in su:
        lines.append(
            f"  main's entry to the first segment "
            f"{_fmt(su['entry_to_segment_s'], 3)}, under no span "
            f"{_fmt(su['unowned_s'], 3)}")
    return lines


def format_text(summary: dict) -> str:
    lines = []
    if summary["engines"]:
        lines.append("per-engine iterate summary")
        lines.append(f"  {'engine':<44} {'storage':>17} {'chunks':>6} "
                     f"{'iters':>9} {'time_s':>10} {'MLUPS':>10}")
        for eng, g in sorted(summary["engines"].items()):
            sdt = g.get("storage_dtype")
            # dtype/repr: the at-rest layout in one cell (repr only
            # matters on a narrowed rung, where it names the encoding)
            storage = "-" if sdt is None else (
                f"{sdt}/{g['storage_repr']}" if g.get("storage_repr")
                else str(sdt))
            lines.append(
                f"  {eng:<44} {storage:>17} "
                f"{g['chunks']:>6} {g['iters']:>9} "
                f"{_fmt(g['total_s'], 3):>10} {_fmt(g['mlups'], 1):>10}")
        lines.append("")
    if summary.get("globals_steps"):
        lines.append("trailing globals steps of the hybrid engines")
        lines.append(f"  {'engine':<44} {'steps':>6} {'time_s':>10}")
        for eng, g in sorted(summary["globals_steps"].items()):
            lines.append(f"  {eng:<44} {g['steps']:>6} "
                         f"{_fmt(g['total_s'], 4):>10}")
        lines.append("")
    if summary.get("accounts"):
        lines.append("fused calls by engine (the engine's account on "
                     "iterate.fused, a tail engine's on "
                     "iterate.globals_step; windows of one shard)")
        for eng, g in sorted(summary["accounts"].items()):
            lines.append(f"  {eng}")
            lines.append("      " + "  ".join(
                f"{k} {g[k]}" for k in ("calls",) + ACCOUNT_SUMS
                if k in g))
            plan = "  ".join(f"{k} {g[k]}" for k in ACCOUNT_PLAN if k in g)
            if plan:
                lines.append("      " + plan)
        lines.append("")
    if summary["spans"]:
        lines.append("spans")
        lines.append(f"  {'name':<32} {'count':>6} {'total_s':>10} "
                     f"{'mean_s':>10} {'max_s':>10}")
        for name, s in sorted(summary["spans"].items(),
                              key=lambda kv: -kv[1]["total_s"]):
            lines.append(f"  {name:<32} {s['count']:>6} "
                         f"{_fmt(s['total_s'], 4):>10} "
                         f"{_fmt(s['mean_s'], 4):>10} "
                         f"{_fmt(s['max_s'], 4):>10}")
        lines.append("")
    if summary.get("segments"):
        cols = ("span_ms", "wait_ms", "host_ms", "pre_sync_ms",
                "dispatch_ms")
        lines.append("segments (medians; host = span - wait; then self "
                     "time by span name, ms)")
        lines.append(f"  {'handlers':<40} {'count':>6} "
                     + " ".join(f"{c:>11}" for c in cols))
        for shape, g in sorted(summary["segments"].items(),
                               key=lambda kv: -kv[1]["count"]):
            lines.append(f"  {shape:<40} {g['count']:>6} "
                         + " ".join(f"{_fmt(g[c], 3):>11}" for c in cols))
            lines.append("      " + "  ".join(
                f"{n} {_fmt(v, 3)}" for n, v in sorted(
                    g["self_ms"].items(), key=lambda kv: -kv[1])))
        lines.append("")
    if summary.get("setup"):
        lines += _format_setup(summary["setup"])
        lines.append("")
    if summary.get("serving"):
        sv = summary["serving"]
        lines.append("serving")
        if "batches" in sv:
            lines.append(
                f"  batches {sv['batches']}  jobs {sv['jobs']}  "
                f"occupancy {_fmt(sv['occupancy_pct'], 1)}%  "
                f"degraded {sv['degraded_batches']}")
            lines.append(
                f"  queue wait p50 {_fmt(sv['queue_wait_p50_s'], 4)}s  "
                f"p95 {_fmt(sv['queue_wait_p95_s'], 4)}s")
        if "compile_lookups" in sv:
            lines.append(
                f"  compile cache: {sv['compile_lookups']} lookups, "
                f"hit rate {_fmt(sv['cache_hit_rate_pct'], 1)}%, "
                f"{_fmt(sv['compile_miss_s'], 3)}s compiling")
        lines.append("")
    if summary.get("adjoint"):
        ad = summary["adjoint"]
        lines.append("adjoint")
        lines.append(f"  {'model/mode':<28} {'sweeps':>6} {'time_s':>10} "
                     f"{'peak_snaps':>10} {'recompute':>10} "
                     f"{'mem_MB':>8} {'peer_MB':>8} {'disk_MB':>8}")
        for key, r in ad["modes"].items():
            lines.append(
                f"  {key:<28} {r['sweeps']:>6} "
                f"{_fmt(r['total_s'], 3):>10} "
                f"{r['peak_snapshots']:>10} "
                f"{_fmt(r['recompute_factor'], 3):>10} "
                f"{_fmt(r.get('spill_mem', 0) / 1e6, 2):>8} "
                f"{_fmt(r.get('spill_peer', 0) / 1e6, 2):>8} "
                f"{_fmt(r.get('spill_disk', 0) / 1e6, 2):>8}")
        lines.append("")
    if summary.get("fleet"):
        fl = summary["fleet"]
        lines.append("fleet")
        if fl.get("lanes"):
            lines.append(f"  {'device':<28} {'batches':>8} {'jobs':>6} "
                         f"{'busy_s':>10} {'occupancy':>10}")
            for dev, r in fl["lanes"].items():
                occ = (_fmt(r["occupancy_pct"], 1) + "%"
                       if r.get("occupancy_pct") is not None else "-")
                lines.append(f"  {dev:<28} {r['batches']:>8} "
                             f"{r['jobs']:>6} {_fmt(r['busy_s'], 4):>10} "
                             f"{occ:>10}")
        lines.append(
            f"  lanes active {fl['lanes_active']}  "
            f"staging overlap {_fmt(fl['staging_overlap_pct'], 1)}%  "
            f"routed sharded {fl['routed_sharded']}  "
            f"evicted {fl['devices_evicted']}")
        lines.append(
            f"  queue wait p50 {_fmt(fl['queue_wait_p50_s'], 4)}s  "
            f"p95 {_fmt(fl['queue_wait_p95_s'], 4)}s")
        lines.append("")
    if summary.get("gateway"):
        gw = summary["gateway"]
        lines.append("gateway")
        lines.append(
            f"  admitted {gw['admitted']}  rejected {gw['rejected']}  "
            f"admission rate {_fmt(gw['admission_rate_pct'], 1)}%  "
            f"resumed {gw['resumed']}  recovered {gw['recovered']}")
        if gw["rejections_by_reason"]:
            lines.append("  rejections: " + "  ".join(
                f"{r}={n}" for r, n in gw["rejections_by_reason"].items()))
        if gw["jobs_by_status"]:
            lines.append("  outcomes:   " + "  ".join(
                f"{s}={n}" for s, n in gw["jobs_by_status"].items()))
        if gw["tenants"]:
            lines.append(f"  {'tenant':<28} {'jobs':>6} {'wait_p50_s':>11} "
                         f"{'wait_p95_s':>11}")
            for t, r in gw["tenants"].items():
                lines.append(
                    f"  {t:<28} {r['jobs']:>6} "
                    f"{_fmt(r['queue_wait_p50_s'], 4):>11} "
                    f"{_fmt(r['queue_wait_p95_s'], 4):>11}")
        lines.append("")
    if summary.get("slo"):
        slo = summary["slo"]
        lines.append("gateway SLO (per-phase latency)")
        lines.append(f"  {'phase':<14} {'jobs':>6} {'p50_s':>10} "
                     f"{'p95_s':>10} {'max_s':>10}")
        for phase, _ in _SLO_PHASES:
            r = slo.get(phase)
            if r is None:
                continue
            lines.append(f"  {phase:<14} {r['count']:>6} "
                         f"{_fmt(r['p50_s'], 4):>10} "
                         f"{_fmt(r['p95_s'], 4):>10} "
                         f"{_fmt(r['max_s'], 4):>10}")
        lines.append("")
    if summary.get("faults"):
        fa = summary["faults"]
        lines.append("injected faults (chaos)")
        lines.append("  " + "  ".join(
            f"{k}={n}" for k, n in fa["by_point_mode"].items()))
        lines.append(
            f"  recovery: retries {fa['retries']}  "
            f"evicted {fa['devices_evicted']}  "
            f"reinstated {fa['devices_reinstated']}  "
            f"store degraded {fa['store_degraded']}  "
            f"ckpt enospc {fa['checkpoint_enospc']}")
        lines.append("")
    if summary["engine_selected"]:
        lines.append("engine selections")
        for e in summary["engine_selected"]:
            lines.append(f"  {e.get('engine')}  model={e.get('model')} "
                         f"shape={e.get('shape')} "
                         f"backend={e.get('backend')}")
        lines.append("")
    if summary["fallbacks"]:
        lines.append("fallback chain")
        for f in summary["fallbacks"]:
            lines.append(f"  {f.get('from')} -> {f.get('to')}: "
                         f"{f.get('cause')}")
        lines.append("")
    if summary["failchecks"]:
        lines.append("failchecks")
        for f in summary["failchecks"]:
            lines.append(f"  iteration {f.get('iteration')}: "
                         f"{f.get('quantity')} has {f.get('n_bad')} "
                         "non-finite values")
        lines.append("")
    if summary["counters"]:
        lines.append("counters")
        for k, v in sorted(summary["counters"].items()):
            lines.append(f"  {k:<40} {v}")
        lines.append("")
    lines.append("events: " + ", ".join(
        f"{k}={v}" for k, v in sorted(summary["event_counts"].items())))
    return "\n".join(lines)


def format_compare_text(diff: dict) -> str:
    lines = ["trace comparison (base -> other)"]
    if diff["engines"]:
        lines.append(f"  {'engine':<44} {'base MLUPS':>12} "
                     f"{'other MLUPS':>12} {'delta':>9}")
        for eng, row in sorted(diff["engines"].items()):
            d = row.get("mlups_delta_pct")
            lines.append(
                f"  {eng:<44} {_fmt(row['base_mlups'], 1):>12} "
                f"{_fmt(row['other_mlups'], 1):>12} "
                f"{(_fmt(d, 2) + '%') if d is not None else '-':>9}"
                + (f"  ({row['note']})" if "note" in row else ""))
    slow_spans = [(n, r) for n, r in sorted(diff["spans"].items())
                  if r.get("mean_delta_pct") is not None]
    if slow_spans:
        lines.append(f"  {'span':<44} {'base mean_s':>12} "
                     f"{'other mean_s':>12} {'delta':>9}")
        for name, row in slow_spans:
            lines.append(f"  {name:<44} {_fmt(row['base_mean_s'], 4):>12} "
                         f"{_fmt(row['other_mean_s'], 4):>12} "
                         f"{_fmt(row['mean_delta_pct'], 2):>8}%")
    if diff.get("serving"):
        sv = diff["serving"]
        lines.append(
            "  serving: occupancy "
            f"{_fmt(sv['base_occupancy_pct'], 1)}% -> "
            f"{_fmt(sv['other_occupancy_pct'], 1)}%, cache hit rate "
            f"{_fmt(sv['base_cache_hit_rate_pct'], 1)}% -> "
            f"{_fmt(sv['other_cache_hit_rate_pct'], 1)}%")
    if diff.get("fleet"):
        fl = diff["fleet"]
        lines.append(
            "  fleet: occupancy "
            f"{_fmt(fl['base_mean_occupancy_pct'], 1)}% -> "
            f"{_fmt(fl['other_mean_occupancy_pct'], 1)}%, "
            "staging overlap "
            f"{_fmt(fl['base_staging_overlap_pct'], 1)}% -> "
            f"{_fmt(fl['other_staging_overlap_pct'], 1)}%, lanes "
            f"{_fmt(fl['base_lanes_active'])} -> "
            f"{_fmt(fl['other_lanes_active'])}")
    if diff.get("gateway"):
        gw = diff["gateway"]
        lines.append(
            "  gateway: admission rate "
            f"{_fmt(gw['base_admission_rate_pct'], 1)}% -> "
            f"{_fmt(gw['other_admission_rate_pct'], 1)}%, "
            "queue wait p95 "
            f"{_fmt(gw['base_queue_wait_p95_s'], 4)}s -> "
            f"{_fmt(gw['other_queue_wait_p95_s'], 4)}s")
    if diff.get("slo"):
        for phase, row in diff["slo"].items():
            d = row.get("p95_delta_pct")
            lines.append(
                f"  slo {phase}: p95 {_fmt(row['base_p95_s'], 4)}s -> "
                f"{_fmt(row['other_p95_s'], 4)}s"
                + (f" ({_fmt(d, 2)}%)" if d is not None else ""))
    if diff.get("fallback_drift"):
        lines.append("  fallback drift: "
                     f"base={diff['fallback_drift']['base']} "
                     f"other={diff['fallback_drift']['other']}")
    if diff["regressions"]:
        lines.append(f"REGRESSIONS (>{100 * diff['threshold']:.0f}%):")
        for r in diff["regressions"]:
            lines.append("  " + json.dumps(r))
    else:
        lines.append("no regressions beyond threshold")
    return "\n".join(lines)


# -- CLI --------------------------------------------------------------------- #


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tclb_tpu.telemetry",
        description="Aggregate and diff tclb_tpu telemetry traces.")
    sub = p.add_subparsers(dest="cmd", required=True)
    rp = sub.add_parser("report", help="summarize a JSONL trace")
    rp.add_argument("trace", help="trace file (JSONL)")
    rp.add_argument("--format", choices=("text", "json"), default="text")
    rp.add_argument("--compare", metavar="OTHER", default=None,
                    help="second trace to diff against (trace = base)")
    rp.add_argument("--threshold", type=float, default=0.05,
                    help="relative slowdown flagged as regression "
                         "(default 0.05)")
    rp.add_argument("--fail-on-regression", action="store_true",
                    help="exit 4 if the comparison finds regressions")
    rp.add_argument("--job", metavar="ID", default=None,
                    help="render one job's end-to-end timeline instead "
                         "of the aggregate report (exit 3 if the trace "
                         "has no events for that job)")
    args = p.parse_args(argv)

    try:
        evts = load(args.trace)
    except OSError as e:
        print(f"error: cannot read {args.trace}: {e}", file=sys.stderr)
        return 2
    if args.job is not None:
        txt = format_job_timeline(evts, args.job)
        print(txt)
        return 3 if not job_events(evts, args.job) else 0
    base = summarize(evts)
    if args.compare is None:
        if args.format == "json":
            print(json.dumps(base, indent=2, sort_keys=True))
        else:
            print(format_text(base))
        return 0
    try:
        other = summarize(load(args.compare))
    except OSError as e:
        print(f"error: cannot read {args.compare}: {e}", file=sys.stderr)
        return 2
    diff = compare(base, other, threshold=args.threshold)
    if args.format == "json":
        print(json.dumps({"base": base, "other": other, "compare": diff},
                         indent=2, sort_keys=True))
    else:
        print(format_compare_text(diff))
    if args.fail_on_regression and diff["regressions"]:
        return 4
    return 0
