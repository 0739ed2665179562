#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips.  It reads the cell from
``BENCHMARK.json`` and its configuration and traffic files by name,
makes the case XML from the configuration's template and the seed, and
gives that file to the program's normal entry
(``tclb_tpu.__main__.main(["run", xml, "--output", dir])``).  The case
clocks itself (``benchmark/window.py``).  After the window has closed
the run checks what the timed path produced (``benchmark/check.py``)
and prints one JSON object as the last line of its output.

Without a TPU, or with fewer chips than the cell asks for, the run
exits with code 2 and prints no result.  See ``benchmark/README.md``.
"""

import time

T0_WALL, T0 = time.time(), time.perf_counter()

import argparse          # noqa: E402
import gc                # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
import xml.etree.ElementTree as ET   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")              # git-ignored, small files


def load_cell(name: str):
    """(cell, configuration, traffic) of a workload of BENCHMARK.json:
    the lookups by name that keep this file free of tables."""
    from benchmark import casegen
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = dict(cells[name])
    for group in ("end_to_end", "per_layer"):     # metric name -> unit
        cell[group] = {m["name"]: m["unit"] for m in bench[group]
                       if name in m.get("workloads", [name])}
    return (cell, casegen.load_json("configs", cell["config"]),
            casegen.load_json("traffic", cell["traffic"]))


def template_path(config: dict) -> str:
    return os.path.join(HERE, "cases", config["template"] + ".xml")


def find_chips(chips: int):
    """The devices of this machine, or exit 2: no number is ever taken
    on anything but a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: needs {chips} TPU chip(s), found {len(devs)} x "
              f"{devs[0].platform}; no result", file=sys.stderr)
        raise SystemExit(2)
    return devs


def filesystem_of(path: str) -> str:
    best = ("", "unknown", "unknown")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                dev, mnt, typ = line.split()[:3]
                if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best[0]):
                    best = (mnt, typ, dev)
    except OSError:
        pass
    return f"{best[1]} ({best[2]} on {best[0] or '?'})"


class Profiler:
    """Starts and stops the JAX profiler around the traced periods, with
    the harness's own annotation as the span's two ends."""

    def __init__(self, directory: str):
        self.directory = directory
        self._span = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # no per-call Python events
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        from benchmark.trace import TRACED
        self._span = jax.profiler.TraceAnnotation(TRACED)
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def per_layer(units: dict, events, recording, info) -> dict:
    """Each metric from its own reader, ``benchmark/layer_metrics/
    <name>.py``; a reader that finds nothing returns None and the metric
    is left out of the line."""
    out = {}
    for name, unit in units.items():
        reader = importlib.import_module("benchmark.layer_metrics." + name)
        value = reader.read(events, recording, info)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under benchmark/out/")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "tclb_tpu")):
        print("benchmark: no program (tclb_tpu/) beside benchmark/; "
              "no result", file=sys.stderr)
        return 3
    from benchmark import bytes_model, casegen, check, trace, vti, window
    cell, config, traffic = load_cell(args.workload)

    from tclb_tpu.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    devs = find_chips(int(cell["chips"]))
    import jax
    import numpy as np
    from tclb_tpu import telemetry
    from tclb_tpu.__main__ import main as tclb_main
    t_ready = time.perf_counter()
    kind = devs[0].device_kind
    print(f"setup: imports and device start {t_ready - T0:.3f} s; "
          f"{len(devs)} x {kind}; compile cache {cache_dir}", flush=True)

    tag = f"{cell['name']}.seed{args.seed}.trace{args.trace}"
    os.makedirs(OUT, exist_ok=True)
    outdir = tempfile.mkdtemp(prefix="tclb-bench-")
    trace_dir = os.path.join(OUT, tag + ".profile")
    events_path = os.path.join(OUT, tag + ".events.jsonl")
    events: list[dict] = []
    rc = 1
    try:
        print(f"output: {outdir} on {filesystem_of(outdir)}", flush=True)
        root, drawn = casegen.generate(template_path(config), traffic,
                                       args.seed)
        xml = os.path.join(outdir, cell["name"].replace(".", "_") + ".xml")
        ET.ElementTree(root).write(xml)
        shape = tuple(int(v) for v in config["shape"])
        nodes = int(np.prod(shape))
        print(f"case: {config['model']} {shape} seed {args.seed} -> "
              f"{drawn}", flush=True)

        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            if os.path.exists(events_path):
                os.unlink(events_path)
            telemetry.enable(events_path)
        telemetry.subscribe(events.append)
        win = window.Window(
            traffic["handlers"], args.seconds,
            warmup_periods=traffic["warmup_periods"],
            check_segments=config["check_segments"],
            trace_periods=traffic["trace_periods"] if args.trace else 0,
            profiler=Profiler(trace_dir) if args.trace else None,
            # end-to-end runs measure with the program's telemetry off:
            # it is on only until the engine has been selected and probed
            after_first_call=None if args.trace else
            (lambda: telemetry.unsubscribe(events.append)))
        window.install(win)
        gc.callbacks.append(win.watch_gc)
        argv_run = ["run", xml, "--output", outdir + os.sep]
        if config.get("mesh"):
            argv_run += ["--mesh", config["mesh"]]
        t_main = time.perf_counter()
        tclb_rc = tclb_main(argv_run)
        t_done = time.perf_counter()
        telemetry.unsubscribe(events.append)
        if args.trace:
            telemetry.disable()
        window.install(None)
        gc.callbacks.remove(win.watch_gc)
        peak = memory_peak(devs[:int(cell["chips"])])
        snapshot, win.snapshot = win.snapshot, None
        gc.collect()

        # -- set-up, itemised -------------------------------------------- #
        selected = [e for e in events if e.get("kind") == "engine_selected"]
        engine = selected[0]["engine"] if selected else "none"
        first_iter = trace.spans(events, "iterate")[:1]
        first_call = first_iter[0]["dur_s"] if first_iter else float("nan")
        if win.t_first is None:
            print("benchmark: the case never reached its first segment",
                  file=sys.stderr)
            return 1
        t_open = win.t_open if win.t_open is not None else t_done
        print(f"setup: case file {t_main - t_ready:.3f} s; program start "
              f"(parse, geometry, init, engine selection) "
              f"{win.t_first - t_main - first_call:.3f} s; first call "
              f"(compile or cache load, {win.segment} steps) "
              f"{first_call:.3f} s; warm-up to the window "
              f"({(win.open_at - win.segment) // win.segment} segments) "
              f"{t_open - win.t_first:.3f} s", flush=True)
        print(f"engine: {engine}; fields {win.fields_shape} "
              f"x {win.fields_itemsize} B", flush=True)

        # -- the check, outside the window ------------------------------- #
        t_chk = time.perf_counter()
        numbers = []
        steps = win.check_at
        if snapshot is None:
            numbers.append((f"fields kept after {steps} steps", 1.0, 0.0))
        else:
            ref = check.reference_fields(config, root, steps)
            numbers.append((
                f"max |program - reference| over populations after "
                f"{steps} steps", check.largest_difference(snapshot, ref),
                float(config["tolerance"])))
            del ref, snapshot
        fallbacks = sum(e.get("kind") == "engine_fallback" for e in events)
        numbers.append(("engine_fallback events", float(fallbacks), 0.0))
        family = config["engine_family"]
        numbers.append((f"engine {engine} outside family {family}",
                        0.0 if engine.startswith(family + "[") else 1.0,
                        0.0))
        failchecks = sum(e.get("kind") == "failcheck" for e in events)
        bad_file = None
        if win.newest_vtk:
            bad_file = vti.check_file(win.newest_vtk[0], nodes)
            print(f"output: newest VTK {os.path.basename(win.newest_vtk[0])}"
                  f" {os.path.getsize(win.newest_vtk[0])} B: "
                  f"{bad_file or 'finite, of the case size'}", flush=True)
        elif any(h["tag"] == "VTK" for h in traffic["handlers"]):
            bad_file = "no VTK file was written"
        failed = int(not win.closed or tclb_rc != 0 or failchecks > 0) \
            + int(bad_file is not None)
        numbers.append(("periods with a Failcheck hit, an early stop or a "
                        "bad output file", float(failed), 0.0))
        correct = check.decide(numbers)
        print(f"check: took {time.perf_counter() - t_chk:.3f} s "
              "(not part of setup_s)", flush=True)
        if not win.closed:
            print("benchmark: the window never closed; no result",
                  file=sys.stderr)
            return 1

        # -- metrics ----------------------------------------------------- #
        s = win.summary(nodes)
        setup_s = (win.t_open - T0)
        print(f"window: {s['periods']} periods of {win.period} steps, "
              f"{s['segments']} segments, {s['wall_s']:.4f} s; segment "
              f"median {s['segment_median_ms']:.3f} ms, p95 "
              f"{s['segment_p95_ms']:.3f} ms with {s['beyond_p95']} "
              f"samples beyond it", flush=True)
        print(f"window: {len(win.gc_full)} full garbage collections of the "
              f"interpreter, {sum(d for _, d in win.gc_full):.4f} s "
              "(observed, not steered)", flush=True)
        with open(os.path.join(OUT, tag + ".segments.json"), "w") as f:
            json.dump({"cell": cell["name"], "seed": args.seed,
                       "seconds": args.seconds, "trace": args.trace,
                       "engine": engine, "summary": s, "setup_s": setup_s,
                       "warmup": win.warmup, "segments": win.segments,
                       "gc_full": win.gc_full}, f)
        device = {"platform": devs[0].platform, "kind": kind,
                  "count": len(devs), "memory_peak_bytes": peak}
        result = {"correct": bool(correct), "attempted": int(s["periods"]),
                  "failed": failed}
        if not args.trace:
            measured = dict(s, setup_s=setup_s)
            result["metrics"] = {n: {"value": measured[n], "unit": unit}
                                 for n, unit in cell["end_to_end"].items()}
        else:
            if win.traced is None or win.traced[1] is None:
                print("benchmark: the window was too short to trace "
                      f"{traffic['trace_periods']} periods; no result",
                      file=sys.stderr)
                return 1
            rec = trace.load_xplane(
                trace.newest_xplane(trace_dir),
                {e["name"] for e in trace.spans(events)})
            busy, span = trace.busy_seconds(rec)
            device["busy_s"], device["window_s"] = busy, span
            fuse = bytes_model.fuse_of(engine) or int(config.get("fuse", 0))
            info = {
                "cell": cell["name"], "chips": int(cell["chips"]),
                "nodes": nodes, "device_kind": kind, "engine": engine,
                "fuse": fuse, "planes": win.fields_shape[0],
                "itemsize": win.fields_itemsize,
                "window": {"first_iteration": win.open_at,
                           "last_iteration": win.segments[-1][0],
                           "wall_s": s["wall_s"],
                           "overhead_s": win.overhead_s},
                "traced_steps": win.traced[1] - win.traced[0],
            }
            result["metrics"] = per_layer(cell["per_layer"], events, rec,
                                          info)
            result["breakdown"] = {"device_ops": trace.top_ops(rec),
                                   "idle_gaps": trace.idle_gaps(rec)}
            if not args.keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
        result["device"] = device
        print(json.dumps(result), flush=True)
        rc = 0
    finally:
        shutil.rmtree(outdir, ignore_errors=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
