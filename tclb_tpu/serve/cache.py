"""AOT compiled-executable cache for the ensemble engine.

Every distinct (model, shape, engine tag, batch size, dtype) class costs
one trace + XLA compile; a sweep that re-uses the class must not pay it
again.  The cache AOT-compiles via ``jax.jit(...).lower().compile()``
and keys on ``Model.fingerprint`` — never ``id()`` (the
``hygiene.id_keyed_cache`` scan errors on any id()-keyed cache: ids
recycle and would alias unrelated models) — plus the trace-shaping
extras the spec'd key implies: the present-node-type set (the trace
specializes on painted types), the static ``niter`` and whether Init is
fused in.

A *new* process warm-starts from JAX's persistent compilation cache,
which the entry points place (:mod:`tclb_tpu.compile_cache`).
"""

from __future__ import annotations

import os
from collections import OrderedDict
from typing import Any, Callable, Optional

import jax

from tclb_tpu import faults, telemetry

class CompiledCache:
    """LRU cache of AOT-compiled ensemble executables.

    ``capacity`` bounds live executables (each pins device memory for
    its program); default from ``TCLB_SERVE_CACHE_CAP`` or 16.  Hits and
    misses are counted on the instance and mirrored to telemetry
    (``serve.cache.hit``/``serve.cache.miss`` counters + a
    ``serve.compile`` span per lookup carrying ``cache="hit"|"miss"`` —
    the report CLI derives the serving hit rate from those spans)."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            capacity = int(os.environ.get("TCLB_SERVE_CACHE_CAP", "16"))
        self.capacity = max(1, int(capacity))
        self._entries: OrderedDict[tuple, Callable] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def key_for(self, plan, batch: int, niter: int, init: bool,
                device: Any = None) -> tuple:
        grad = getattr(plan, "grad", None)
        return (plan.model.fingerprint,
                plan.shape,
                plan.engine_tag(batch),
                int(batch),
                str(jax.numpy.dtype(plan.dtype)),
                int(niter),
                bool(init),
                frozenset(plan.present or ()),
                str(device),
                None if grad is None else grad.key())

    def get(self, plan, batch: int, niter: int, fn: Callable,
            init: bool = True, device: Any = None) -> Callable:
        """Compiled ``(states, params) -> states`` executable for this
        plan/batch/niter class, compiling on miss.  ``device`` pins the
        executable to one device via input shardings (a fleet lane's
        cache compiles against its own device so executables never
        migrate)."""
        key = self.key_for(plan, batch, niter, init, device=device)
        hit = key in self._entries
        fields = dict(cache="hit" if hit else "miss",
                      engine=plan.engine_tag(batch),
                      model=plan.model.name, batch=int(batch),
                      niter=int(niter))
        if device is not None:
            fields["device"] = str(device)
        with telemetry.span("serve.compile", **fields):
            if hit:
                self._entries.move_to_end(key)
                self.hits += 1
                telemetry.counter("serve.cache.hit")
                return self._entries[key]
            self.misses += 1
            telemetry.counter("serve.cache.miss")
            faults.fire("serve.compile", model=plan.model.name,
                        batch=int(batch))
            # forward plans lower on (states, params); gradient plans on
            # (thetas, states, params) — the plan owns the input tuple
            abstract = plan.abstract_inputs(batch, device=device)
            lowered = jax.jit(fn, static_argnames=("niter",)).lower(
                *abstract, niter=niter)
            compiled = lowered.compile()
        self._entries[key] = compiled
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self.evictions += 1
            telemetry.counter("serve.cache.evict")
        return compiled

    def stats(self) -> dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "size": len(self._entries),
                "capacity": self.capacity}


_default_cache: Optional[CompiledCache] = None


def default_cache() -> CompiledCache:
    """Process-wide cache shared by the sweep CLI and the scheduler."""
    global _default_cache
    if _default_cache is None:
        _default_cache = CompiledCache()
    return _default_cache
