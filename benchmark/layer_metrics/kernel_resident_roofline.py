"""HBM time of the bytes a resident engine's kernel calls really move
(``benchmark/resident_bytes.py``: the state in and out once a resident
call and its aux planes in; the band calls of the steps left over as
``band_bytes.py`` counts them) over the kernels' device time, in
percent.  The calls come from the window's ``iterate.fused`` spans
(``resident_calls``, ``remainder_steps``, ``aux_planes`` and the
remainder's band shape, which the resident engines put there); every
period does the same work, so bytes per step of the window times the
traced steps are the traced bytes.  A program or an engine that does not
say so reads nothing.  It is this kernel's share of its roofline, and it
is low by design (an eighth of a round trip a step): beside a low
``kernel_hbm_roofline`` it says the kernel is bound by the vector unit
or by its on-chip memory, which no metric reckons yet.  Layer: kernels.
A reading over 100 % fails the run, as ``kernel_hbm_roofline``'s does."""

from benchmark import bytes_model, resident_bytes, trace


def read(events, device_trace, cell):
    said = resident_bytes.window_accounts(events, cell["window"])
    t = trace.by_class(device_trace)
    if said is None or not t["calls"]:
        return None
    fused, steps = said
    moved = sum(resident_bytes.iterate_bytes(
        e, cell["nodes"], cell["planes"], cell["itemsize"])
        for e in fused) / steps * cell["traced_steps"]
    gbs = bytes_model.peak(cell["device_kind"])["hbm_gbs"] * cell["chips"]
    share = 100.0 * moved / (gbs * 1e9) / t["kernel"]
    if share > 100.0:
        raise AssertionError(
            f"kernel_resident_roofline reads {share:.2f} %: the bytes of "
            f"{cell['engine']}'s calls are counted too high, or kernel "
            "operations are missing from the trace")
    return share
