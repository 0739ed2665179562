"""HBM time of the bytes the tuned 3D engine's kernel calls really move
(``benchmark/tile_bytes.py``: halo slabs and halo rows read again by
every window, the flag plane, the write) over the kernels' device time,
in percent.  The calls and the windows come from the window's
``iterate.fused`` spans (``kernel_calls``, ``remainder_steps``,
``z_bands``, ``band_slabs``, ``halo_slabs``, ``y_bands``, ``band_rows``,
``halo_rows``, ``aux_planes``, which the engine's ``iterate`` puts
there); every period does the same work, so the bytes per step of the
window times the traced steps are the traced bytes.  A step left over by
the fused calls is counted at the least a call moves.  A program or an
engine that does not say so reads nothing.  With ``kernel_hbm_roofline``
(the least bytes) it tells traffic amplified by the halos from
arithmetic: both low means the kernel is bound by neither.  Layer:
kernels.  A reading over 100 % fails the run, as
``kernel_hbm_roofline``'s does."""

from benchmark import bytes_model, phases, tile_bytes, trace

FIELDS = ("kernel_calls", "remainder_steps", "z_bands", "band_slabs",
          "halo_slabs", "y_bands", "band_rows", "halo_rows", "aux_planes")


def read(events, device_trace, cell):
    window = cell["window"]
    fused = [e for e in phases.iterate_spans_in_window(
        events, "iterate.fused", window) if all(k in e for k in FIELDS)]
    steps = window["last_iteration"] - window["first_iteration"]
    t = trace.by_class(device_trace)
    if not fused or steps < 1 or not t["calls"]:
        return None
    least = cell["nodes"] * bytes_model.round_trip_bytes(
        cell["planes"], cell["itemsize"])
    moved = sum(
        (e["kernel_calls"] - e["remainder_steps"]) * tile_bytes.call_bytes(
            cell["nodes"], e["z_bands"], e["band_slabs"], e["halo_slabs"],
            e["y_bands"], e["band_rows"], e["halo_rows"], cell["planes"],
            cell["itemsize"], e["aux_planes"])
        + e["remainder_steps"] * least
        for e in fused) / steps * cell["traced_steps"]
    gbs = bytes_model.peak(cell["device_kind"])["hbm_gbs"] * cell["chips"]
    share = 100.0 * moved / (gbs * 1e9) / t["kernel"]
    if share > 100.0:
        raise AssertionError(
            f"kernel_tile_roofline reads {share:.2f} %: the bytes of "
            f"{cell['engine']}'s calls are counted too high, or kernel "
            "operations are missing from the trace")
    return share
