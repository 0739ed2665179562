"""HBM time of the bytes the band engine's kernel calls really move
(``benchmark/band_bytes.py``: halo rows read again by every band, the
aux planes, the write) over the kernels' device time, in percent.  The
calls and the band's shape come from the window's ``iterate.fused``
spans (``kernel_calls``, ``bands``, ``band_rows``, ``halo_rows``,
``pad_rows``, ``aux_planes``, which the generic band engine puts there);
every period does the same work, so calls per step of the window times
the traced steps are the traced calls.  A program or an engine that
does not say so reads nothing.  With ``kernel_hbm_roofline`` (the least
bytes) it tells traffic amplified by the halo from arithmetic: both low
means the kernel is bound by neither.  Layer: kernels.  A reading over
100 % fails the run, as ``kernel_hbm_roofline``'s does."""

from benchmark import band_bytes, bytes_model, phases, trace

FIELDS = ("kernel_calls", "bands", "band_rows", "halo_rows", "pad_rows",
          "aux_planes")


def read(events, device_trace, cell):
    fused = [e for e in phases.iterate_spans_in_window(
        events, "iterate.fused", cell["window"])
        if all(k in e for k in FIELDS)]
    steps = sum(e["iters"] for e in fused)
    t = trace.by_class(device_trace)
    if not steps or not t["calls"]:
        return None
    moved = sum(e["kernel_calls"] * band_bytes.call_bytes(
        cell["nodes"], e["bands"], e["band_rows"], e["halo_rows"],
        e["pad_rows"], cell["planes"], cell["itemsize"], e["aux_planes"])
        for e in fused) / steps * cell["traced_steps"]
    gbs = bytes_model.peak(cell["device_kind"])["hbm_gbs"] * cell["chips"]
    share = 100.0 * moved / (gbs * 1e9) / t["kernel"]
    if share > 100.0:
        raise AssertionError(
            f"kernel_dma_roofline reads {share:.2f} %: the bytes of "
            f"{cell['engine']}'s calls are counted too high, or kernel "
            "operations are missing from the trace")
    return share
