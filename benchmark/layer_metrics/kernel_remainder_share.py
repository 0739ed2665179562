"""Device time of the kernel operations (``tpu_custom_call``) that are
not the resident kernel's over all kernel device time of the traced
periods, in percent: what the steps cost that a resident engine leaves
to its second engine, the single-step band kernel (7 of every 1000 in
``karman.resident``: the hybrid hands the engine 999 steps, 124 resident
calls of 8 and 7 over).  A resident kernel's operation carries the
kernel's name, which holds ``resident`` (``d2q9_resident_fuse8``,
``generic_resident_fuse<n>``).  The operation counts are checked
against the account on the window's ``iterate.fused`` spans
(``resident_calls``, ``remainder_steps`` a step of the window, times the
traced steps): where either count is off by more than one in a hundred
(what the profiler's two clocks can clip at the traced span's ends) the
names do not mean what this reader takes them to mean, and it reads
nothing; so does a program or an engine without the account.  The
remainder's XLA wrappers (the ghost-row pad, gather and slice) are not
kernels: ``kernel_wrap_share`` reads them.  Layer: kernels."""

from benchmark import resident_bytes, trace

RESIDENT = "resident"
SLACK = 0.01


def read(events, device_trace, cell):
    said = resident_bytes.window_accounts(events, cell["window"])
    if said is None:
        return None
    fused, steps = said
    lo, hi = trace.traced_span(device_trace)
    seconds = {True: 0.0, False: 0.0}
    calls = {True: 0, False: 0}
    for evs in device_trace.devices.values():
        for name, _, own in trace.self_times(trace.clip(evs, lo, hi)):
            if trace.is_kernel(name):
                seconds[RESIDENT in name] += own
                calls[RESIDENT in name] += 1
    for field, mine in (("resident_calls", True),
                        ("remainder_steps", False)):
        expected = (sum(e[field] for e in fused) / steps
                    * cell["traced_steps"] * cell["chips"])
        if abs(calls[mine] - expected) > SLACK * max(expected, 1.0):
            return None
    total = seconds[True] + seconds[False]
    return 100.0 * seconds[False] / total if total > 0 else None
