"""Least HBM time for the kernels' node updates over their device time,
in percent (``benchmark/bytes_model.py``; HBM is the only bound
reckoned).  Layer: kernels.  A reading over 100 % means the bytes are
counted too high or the time leaves work out: the run fails on it."""

from benchmark import bytes_model, trace


def read(events, device_trace, cell):
    t = trace.by_class(device_trace)
    if not t["calls"] or not cell["fuse"]:
        return None
    least = bytes_model.least_hbm_seconds(
        cell["nodes"] * cell["traced_steps"], cell["planes"],
        cell["itemsize"], cell["fuse"], cell["device_kind"])
    share = 100.0 * least / t["kernel"]
    if share > 100.0:
        raise AssertionError(
            f"kernel_hbm_roofline reads {share:.2f} %: the byte count of "
            f"{cell['engine']} (fuse {cell['fuse']}) is stale, or kernel "
            "operations are missing from the trace")
    return share
