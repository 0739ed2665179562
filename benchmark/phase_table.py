#!/usr/bin/env python3
"""The by-phase tables of ``PERF.md`` section 5, from the files a traced
run leaves when it is given ``--keep-trace``:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace 1 --keep-trace
    python3 benchmark/phase_table.py --workload <cell> --seed <n>

Prints one JSON object: device seconds by the program's phase spans (a
mean over the chips; the rows add up to ``busy_self_s``), and per handler
type the host time of one call by the span it was spent in (self time:
a span's duration less what its children cover), medians over the
window's calls.  It measures nothing itself and is no part of a run.
"""

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import phases, trace   # noqa: E402

PHASES = ("iterate.fused", "iterate.globals_step", "quantity.eval")


def device_table(rec: trace.Recording) -> dict:
    chips = len(rec.devices)
    t = trace.by_class(rec)
    out = {"busy_self_s": sum(t[c] for c in phases.CLASSES) / chips,
           "busy_union_s": trace.busy_seconds(rec)[0]}
    inside = 0.0
    for name in PHASES:
        entries = phases.device_seconds_in(rec, name)
        row = {c: v / chips for c, v in phases.totals(entries).items()}
        row["annotations"] = len(entries)
        out[name] = row
        inside += sum(row[c] for c in phases.CLASSES)
    out["outside"] = out["busy_self_s"] - inside
    return out


def children(spans: list[dict]) -> dict:
    """``id -> child spans``."""
    kids: dict = {}
    for e in spans:
        kids.setdefault(e.get("parent"), []).append(e)
    return kids


def self_seconds(span: dict, kids: dict) -> float:
    """``dur_s`` less the union of the children's intervals."""
    return span["dur_s"] - trace.union_seconds(
        [[k["name"], phases.start_of(k), k["dur_s"]]
         for k in kids.get(span["id"], [])])


def host_table(events, window: dict) -> dict:
    """Per handler type: calls in the window, the median call, and the
    median over the calls of the self time spent under each span name."""
    kids = children([e for e in trace.spans(events) if "id" in e])
    calls: dict = {}
    for h in trace.spans_in_window(events, "handler", window):
        if "id" not in h:
            continue
        by_name: dict = {}
        todo = [h]
        while todo:
            e = todo.pop()
            by_name[e["name"]] = (by_name.get(e["name"], 0.0)
                                  + self_seconds(e, kids))
            todo += kids.get(e["id"], [])
        calls.setdefault(h.get("handler"), []).append((h["dur_s"], by_name))
    table = {}
    for handler, mine in calls.items():
        names = sorted({n for _, by in mine for n in by})
        table[handler] = {
            "calls": len(mine),
            "median_ms": 1e3 * statistics.median(d for d, _ in mine),
            "self_ms": {n: 1e3 * statistics.median(
                by.get(n, 0.0) for _, by in mine) for n in names}}
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    tag = os.path.join(HERE, "out", f"{args.workload}.seed{args.seed}.trace1")
    events = trace.read_events(tag + ".events.jsonl")
    with open(tag + ".segments.json") as f:
        seg = json.load(f)
    window = {"first_iteration": seg["warmup"][-1][0],
              "last_iteration": seg["segments"][-1][0]}
    rec = trace.load_xplane(trace.newest_xplane(tag + ".profile"),
                            {e["name"] for e in trace.spans(events)})
    print(json.dumps({"cell": args.workload, "seed": args.seed,
                      "device_s": device_table(rec),
                      "host": host_table(events, window),
                      "idle_gaps": trace.idle_gaps(rec)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
