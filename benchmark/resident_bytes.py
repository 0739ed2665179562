"""The bytes the calls of a VMEM-resident engine really move between HBM
and the chip's on-chip memory.

A resident call (``ops/pallas_d2q9.make_resident_iterate``, and the
generic ``ops/pallas_generic.make_resident_iterate``) copies the whole
state in once, advances it ``resident_steps`` steps on-chip and writes
it back once: per node ``2 * planes * itemsize`` bytes of populations,
and 4 bytes of each of the ``aux_planes`` planes it reads beside them
(the int32 flags and the float32 zonal planes), whatever the number of
steps.  Not counted: the settings in SMEM, a few dozen bytes a call.

The steps a call leaves over run on a single-step band kernel, one
kernel call a step; those calls move what ``band_bytes.call_bytes``
counts for their bands.  (The tuned d2q9 band kernel reads its three aux
planes without halo rows, which ``call_bytes`` counts with them: 16 rows
of 12 B a band too many, 4 % of a remainder call.)

d2q9 at 1024 x 100 (11 planes f32, 3 aux planes): 100 B a node and
resident call, 10,240,000 B a call, 12.5 B an update at 8 steps a call;
a remainder step on 3 bands of 40 rows with 8 halo rows and 20 ghost
rows moves 15,040,512 B, 146.9 B an update.
"""

from __future__ import annotations

from benchmark import band_bytes, phases

# what the engine's account has to say for the bytes to be counted
FIELDS = ("resident_calls", "remainder_steps", "aux_planes", "bands",
          "band_rows", "halo_rows", "pad_rows", "remainder_aux_planes")


def resident_call_bytes(nodes: int, planes: int, itemsize: int,
                        aux_planes: int) -> int:
    """Bytes one resident call moves: the state in and out, the aux
    planes in."""
    per_node = 2 * int(planes) * int(itemsize) \
        + band_bytes.AUX_ITEMSIZE * int(aux_planes)
    return int(nodes) * per_node


def window_accounts(events, window: dict):
    """``(accounts, steps)`` of the window: its ``iterate.fused`` spans,
    each with the engine's account (``FIELDS``), and the steps of its
    ``iterate`` spans, which count the hybrid's trailing XLA step too.
    None where a span lacks the account (a program or an engine that
    does not say what it issued) or the window holds no step."""
    fused = phases.iterate_spans_in_window(events, "iterate.fused", window)
    steps = sum(e["iters"] for e in phases.iterate_spans_in_window(
        events, "iterate", window))
    if not fused or not steps \
            or not all(k in e for e in fused for k in FIELDS):
        return None
    return fused, steps


def iterate_bytes(account: dict, nodes: int, planes: int, itemsize: int
                  ) -> int:
    """Bytes all kernel calls of one ``iterate`` move, from the account
    the engine put on its ``iterate.fused`` span (``FIELDS``)."""
    moved = account["resident_calls"] * resident_call_bytes(
        nodes, planes, itemsize, account["aux_planes"])
    if account["remainder_steps"]:
        moved += account["remainder_steps"] * band_bytes.call_bytes(
            nodes, account["bands"], account["band_rows"],
            account["halo_rows"], account["pad_rows"], planes, itemsize,
            account["remainder_aux_planes"])
    return moved
