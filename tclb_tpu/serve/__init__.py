"""High-throughput case serving: batched ensembles, compiled-executable
caching, and a fault-tolerant job scheduler.

The serving stack turns the one-case ``Lattice`` runtime into a
many-case engine:

* :mod:`tclb_tpu.serve.ensemble` — run N independent cases of one
  ``(model, shape, engine)`` class in a single device dispatch, with
  per-case output bit-identical to N sequential runs; gradient-mode
  plans (:class:`GradSpec`) batch N whole unsteady-adjoint sweeps the
  same way;
* :mod:`tclb_tpu.serve.cache` — LRU cache of AOT-compiled ensemble
  executables keyed on ``Model.fingerprint``;
* :mod:`tclb_tpu.serve.scheduler` — in-process queue that bins
  compatible jobs into batches, retries failed batched runs and
  degrades to the sequential path rather than failing a whole batch;
* :mod:`tclb_tpu.serve.dispatcher` — the fleet layer: one concurrent
  serving lane per local device (device-pinned compiled caches,
  double-buffered host staging) plus size-aware routing of large jobs
  onto the multi-device sharded engine.

CLI: ``python -m tclb_tpu sweep case.xml --param "nu=0.01:0.05:8"``.
"""

from tclb_tpu.serve.cache import CompiledCache, default_cache
from tclb_tpu.serve.dispatcher import FleetDispatcher, route_job
from tclb_tpu.serve.ensemble import (Case, EnsemblePlan, EnsembleResult,
                                     GradSpec, run_ensemble)
from tclb_tpu.serve.retry import RetryPolicy
from tclb_tpu.serve.scheduler import (Job, JobSpec, JobTimeout, Scheduler,
                                      make_grad_evaluator)

__all__ = [
    "Case",
    "CompiledCache",
    "EnsemblePlan",
    "EnsembleResult",
    "FleetDispatcher",
    "GradSpec",
    "Job",
    "JobSpec",
    "JobTimeout",
    "RetryPolicy",
    "Scheduler",
    "default_cache",
    "make_grad_evaluator",
    "route_job",
    "run_ensemble",
]
