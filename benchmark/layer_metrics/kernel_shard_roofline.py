"""HBM time of the bytes the shards' fused 3D kernel calls really move
(``benchmark/tile_bytes.py``: halo slabs and halo rows read again by
every window, the flag plane, the write) at ONE chip's HBM peak over the
kernels' device time summed over the chips, in percent: the mesh's form
of ``kernel_tile_roofline``.  The calls and the windows come from the
window's ``iterate.fused`` spans, where the sharded engine puts the
account of ONE shard (``z_bands``, ``band_slabs``, ``halo_slabs``,
``y_bands``, ``band_rows``, ``halo_rows``, ``aux_planes``) beside
``kernel_calls``, ``remainder_steps`` and ``shards``; a shard's call
moves what a lattice of the shard's nodes cut into those windows moves
(a window at a shard's end reads its halo slabs from the neighbour's
exchanged slabs, and they count as read like any other), and ``shards``
of them run at once.  A step left over by the fused calls is counted at
the least a call moves.  Every period does the same work, so the bytes
per step of the window times the traced steps are the traced bytes.  A
program or an engine that does not say ``shards`` reads nothing.  Layer:
kernels.  A reading over 100 % fails the run."""

from benchmark import bytes_model, phases, tile_bytes, trace
from benchmark.layer_metrics.kernel_tile_roofline import FIELDS


def read(events, device_trace, cell):
    window = cell["window"]
    fused = [e for e in phases.iterate_spans_in_window(
        events, "iterate.fused", window)
        if "shards" in e and all(k in e for k in FIELDS)]
    steps = window["last_iteration"] - window["first_iteration"]
    t = trace.by_class(device_trace)
    if not fused or steps < 1 or not t["calls"]:
        return None
    least = cell["nodes"] * bytes_model.round_trip_bytes(
        cell["planes"], cell["itemsize"])
    moved = sum(
        (e["kernel_calls"] - e["remainder_steps"]) * e["shards"]
        * tile_bytes.call_bytes(
            cell["nodes"] // e["shards"], e["z_bands"], e["band_slabs"],
            e["halo_slabs"], e["y_bands"], e["band_rows"], e["halo_rows"],
            cell["planes"], cell["itemsize"], e["aux_planes"])
        + e["remainder_steps"] * least
        for e in fused) / steps * cell["traced_steps"]
    gbs = bytes_model.peak(cell["device_kind"])["hbm_gbs"]
    share = 100.0 * moved / (gbs * 1e9) / t["kernel"]
    if share > 100.0:
        raise AssertionError(
            f"kernel_shard_roofline reads {share:.2f} %: the bytes of "
            f"{cell['engine']}'s calls are counted too high, or kernel "
            "operations are missing from the trace")
    return share
