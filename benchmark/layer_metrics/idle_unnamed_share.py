"""Of device 0's idle seconds inside the traced span, the share that
lies in gaps whose middle no program span covers (``trace.idle_gaps``
names those ``none``), in percent: whether the program's spans still
cover the host.  Layer: device."""

import sys

from benchmark import trace


def read(events, device_trace, cell):
    gaps = trace.idle_gaps(device_trace, n=sys.maxsize)
    idle = sum(seconds for _, seconds in gaps)
    if idle <= 0:
        return None
    return 100.0 * sum(s for name, s in gaps if name == "none") / idle
