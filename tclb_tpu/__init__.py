"""tclb_tpu — a TPU-native adjoint Lattice-Boltzmann CFD framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of TCLB
(reference: /root/reference, an MPI+CUDA adjoint LBM solver driven by an
R-template metaprogramming pipeline).  Where the reference generates
model-specialized CUDA programs from an R DSL (reference src/conf.R), this
framework registers models as Python model definitions traced by `jax.jit`;
where the reference exchanges halos over MPI (reference src/Lattice.cu.Rt:304-366),
this framework shards the lattice over a `jax.sharding.Mesh` and exchanges
halos with `lax.ppermute` over ICI; where the reference differentiates
kernels with Tapenade (reference tools/makeAD), this framework uses `jax.grad`
with checkpoint policies.
"""

import time as _time

#: the boot record's second stamp (``telemetry.events.boot``): this line
T_PACKAGE = _time.time()

__version__ = "0.2.0"

from tclb_tpu.telemetry import events as _events  # noqa: E402
from tclb_tpu.telemetry.spans import import_span as _import_span  # noqa: E402

with _import_span("tclb_tpu", boot=True):
    from tclb_tpu.core.registry import ModelDef, Model  # noqa: F401
    from tclb_tpu.core.lattice import Lattice  # noqa: F401
    from tclb_tpu.models import get_model, list_models  # noqa: F401
if _events.enabled():
    _events._listen_for_compiles()     # jax is there now
