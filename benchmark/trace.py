"""From a profiler trace and the program's telemetry events to numbers.
Part of the yardstick: every PR reduces its trace with this code.

Two stages, so that the second can be checked on a small recording:

* :func:`load_xplane` reads the ``.xplane.pb`` the JAX profiler wrote
  into a :class:`Recording`: per device the events of its operations
  line, and the host's annotation events, all in seconds on the
  profiler's one clock;
* the functions below it reduce a :class:`Recording`.

A recording is plain data and round-trips through JSON
(:meth:`Recording.to_json`), which is what ``tests/data`` keeps.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TRACED = "bench.traced"      # annotation the harness puts around the span
KERNEL = re.compile(r"tpu_custom_call")      # a Pallas (Mosaic) kernel
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|"
    r"collective-broadcast|\bsend\b|\brecv\b|send-done|recv-done", re.I)
# ops that only wrap others on the operations line
WRAPPERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


@dataclass
class Recording:
    """``devices[i]`` is a list of ``[name, start_s, dur_s]`` of device
    i's operations; ``host`` the same for host annotations."""
    devices: dict = field(default_factory=dict)
    host: list = field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps({"devices": self.devices, "host": self.host})

    @classmethod
    def from_json(cls, text: str) -> "Recording":
        d = json.loads(text)
        return cls({str(k): v for k, v in d["devices"].items()}, d["host"])


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_op_name(full: str) -> str:
    """``%closed_call.8 = f32[..] custom-call(..), custom_call_target=
    "tpu_custom_call"`` (what the TPU's operations line calls an event)
    -> ``closed_call.8_custom-call_tpu_custom_call``: the instruction's
    name, its opcode, and the target of a custom call."""
    full = str(full)
    if " = " not in full:
        return full
    name, rest = full.split(" = ", 1)
    name = name.lstrip("%")
    if rest.startswith("("):            # a tuple type: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    m = re.match(r"\s*([\w\-]+)\(", rest)
    out = f"{name}_{m.group(1)}" if m else name
    t = re.search(r'custom_call_target="([\w\-]+)"', full)
    return f"{out}_{t.group(1)}" if t else out


def load_xplane(path: str, host_names) -> Recording:
    """``host_names``: the annotation names worth keeping (the
    program's span names and the harness's own)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rec = Recording()
    keep = set(host_names) | {TRACED}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    rec.devices[m.group(1)] = [
                        [short_op_name(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9] for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        rec.host.append([str(e.name), e.start_ns * 1e-9,
                                         e.duration_ns * 1e-9])
    rec.host.sort(key=lambda e: e[1])
    if not rec.devices:
        raise ValueError(
            f"no '{OPS_LINE}' line on a /device:TPU:n plane in {path}; "
            f"planes: {[(p.name, [l.name for l in p.lines]) for p in data.planes]}")
    return rec


def describe_xplane(path: str, limit: int = 12) -> dict:
    """Planes, lines and the first events with their stats: what one
    looks at by hand before trusting :func:`load_xplane`."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {"events": len(evs), "first": [
                [e.name, e.start_ns, e.duration_ns,
                 {str(k): str(v)[:80] for k, v in e.stats}]
                for e in evs[:limit]]}
        out[plane.name] = lines
    return out


# -- reduction --------------------------------------------------------------- #


def traced_span(rec: Recording) -> tuple[float, float]:
    """Start and end of the harness's annotation around the traced
    periods; without it, the span of all device operations."""
    for name, start, dur in rec.host:
        if name == TRACED:
            return start, start + dur
    starts = [e[1] for evs in rec.devices.values() for e in evs]
    ends = [e[1] + e[2] for evs in rec.devices.values() for e in evs]
    return min(starts), max(ends)


def clip(events, lo: float, hi: float) -> list:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def self_times(events) -> list:
    """Events of one line with the time of the events nested inside
    them taken out, so that a ``while`` does not count its body twice.
    Returns ``[name, start, self_seconds]``."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[n, s, d] for n, s, d in evs]
    stack: list[int] = []
    for i, (_, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s + 1e-12:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= d
        stack.append(i)
    for e in out:
        e[2] = max(e[2], 0.0)
    return out


def union_seconds(events) -> float:
    total, end = 0.0, None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if end is None or s > end:
            total += d
            end = s + d
        elif s + d > end:
            total += s + d - end
            end = s + d
    return total


def busy_seconds(rec: Recording) -> tuple[float, float]:
    """(seconds in which an operation ran, averaged over the devices;
    seconds of the traced span)."""
    lo, hi = traced_span(rec)
    busy = [union_seconds(clip(evs, lo, hi)) for evs in rec.devices.values()]
    return sum(busy) / len(busy), hi - lo


def is_kernel(name: str) -> bool:
    return bool(KERNEL.search(name))


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name)) and not is_kernel(name)


def by_class(rec: Recording) -> dict:
    """Self seconds of kernels, collectives and everything else, summed
    over the devices, inside the traced span; wrappers' own time (what is
    left of a ``while`` once its body is taken out) counts as other."""
    lo, hi = traced_span(rec)
    out = {"kernel": 0.0, "collective": 0.0, "other": 0.0, "calls": 0}
    for evs in rec.devices.values():
        for name, _, d in self_times(clip(evs, lo, hi)):
            if is_kernel(name):
                out["kernel"] += d
                out["calls"] += 1
            elif is_collective(name):
                out["collective"] += d
            else:
                out["other"] += d
    return out


def top_ops(rec: Recording, n: int = 10) -> list:
    lo, hi = traced_span(rec)
    total: dict = {}
    for evs in rec.devices.values():
        for name, _, d in self_times(clip(evs, lo, hi)):
            total[name] = total.get(name, 0.0) + d
    k = len(rec.devices)
    return [[name, s / k] for name, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def exposed_collective_seconds(rec: Recording, device: str = "0") -> float:
    """Seconds in which a collective runs on ``device`` and no other
    operation does."""
    lo, hi = traced_span(rec)
    evs = [e for e in clip(rec.devices[device], lo, hi)
           if not WRAPPERS.match(e[0].split("_")[0])]
    coll = [e for e in evs if is_collective(e[0])]
    rest = [e for e in evs if not is_collective(e[0])]
    return union_seconds(coll) - _overlap(coll, rest)


def _overlap(a, b) -> float:
    """Seconds of the union of ``a`` that the union of ``b`` covers."""
    return union_seconds(a) + union_seconds(b) - union_seconds(a + b)


def idle_gaps(rec: Recording, n: int = 10, device: str = "0") -> list:
    """The longest gaps between operations on ``device`` inside the
    traced span, each named by the innermost host annotation that covers
    its middle (``none`` where none does)."""
    lo, hi = traced_span(rec)
    evs = sorted(clip(rec.devices[device], lo, hi), key=lambda e: e[1])
    gaps, end = [], lo
    for _, s, d in evs:
        if s > end:
            gaps.append((end, s))
        end = max(end, s + d)
    if hi > end:
        gaps.append((end, hi))
    host = [e for e in rec.host if e[0] != TRACED]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (a + b)
        cover = [e for e in host if e[1] <= mid <= e[1] + e[2]]
        name = min(cover, key=lambda e: e[2])[0] if cover else "none"
        out.append([name, b - a])
    return out


# -- the program's telemetry ------------------------------------------------ #


def read_events(path: str) -> list[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def spans(events, name: str | None = None) -> list[dict]:
    return [e for e in events if e.get("kind") == "span"
            and (name is None or e.get("name") == name)]


def spans_in_window(events, name: str, window: dict) -> list[dict]:
    """The spans called ``name`` whose ``iteration`` lies in the window
    (after its first iteration, up to its last)."""
    return [e for e in spans(events, name)
            if window["first_iteration"] < e.get("iteration", -1)
            <= window["last_iteration"]]


if __name__ == "__main__":      # python3 -m benchmark.trace <file.xplane.pb>
    import sys
    print(json.dumps(describe_xplane(sys.argv[1]), indent=1))
