"""``engine_fallback`` events of the whole run: a step down the probe
ladder is a several-fold loss.  Layer: dispatch."""


def read(events, device_trace, cell):
    return float(sum(e.get("kind") == "engine_fallback" for e in events))
