"""The bytes a lattice-Boltzmann kernel call has to move, and the peaks
they are held against.  Part of the yardstick.

A kernel call that advances N nodes by K steps must read the state once
and write it once: per node ``2 * planes * itemsize`` bytes of
populations and 2 bytes of the uint16 flag field, whatever K is.  Per
update (one node, one step) that is divided by K.  d2q9 (11 planes, f32):
90 B per round trip, 45 B per update at K = 2.  d3q27_cumulant (34
planes, f32): 274 B, 91.33 B per update at K = 3.
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
FLAG_BYTES = 2


def round_trip_bytes(planes: int, itemsize: int) -> int:
    """Least bytes per node of one kernel call: read + write of every
    plane, and the flags read once."""
    return 2 * int(planes) * int(itemsize) + FLAG_BYTES


def bytes_per_update(planes: int, itemsize: int, fuse: int) -> float:
    if fuse < 1:
        raise ValueError("fuse must be 1 or more")
    return round_trip_bytes(planes, itemsize) / float(fuse)


def fuse_of(engine_tag: str) -> int:
    """K of an engine tag such as ``pallas_2d[d2q9,fuse=2]``; a tag that
    names no fuse (``pallas_sharded[...]``) is reckoned at the K the
    configuration file states, so this returns 0 for it."""
    m = re.search(r"fuse=(\d+)", engine_tag or "")
    return int(m.group(1)) if m else 0


def peak(device_kind: str) -> dict:
    """The entry of ``peaks.json`` for this kind; a kind that is not in
    the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       "benchmark/peaks.json")
    return table[device_kind]


def least_hbm_seconds(updates: float, planes: int, itemsize: int,
                      fuse: int, device_kind: str, chips: int = 1) -> float:
    """Least time ``chips`` chips need for ``updates`` node updates."""
    gbs = peak(device_kind)["hbm_gbs"] * chips
    return updates * bytes_per_update(planes, itemsize, fuse) / (gbs * 1e9)
