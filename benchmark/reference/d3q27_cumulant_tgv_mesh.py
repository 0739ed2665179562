"""Plain reference of the Taylor-Green configuration on a mesh
(``tgv384``): ``d3q27_cumulant.make_step`` and
``d3q27_cumulant_tgv.initial``, both imported as they stand, in plain
``jax.numpy`` and float32, with the populations laid over the machine's
devices along z by a ``NamedSharding``.

That layout is its only departure from ``d3q27_cumulant_tgv.run``: at
384^3 the 27 populations are 6.1 GB, and the loop holds them twice, so
they fit no single chip beside the step's temporaries.  The step itself
says nothing of devices: the compiler partitions it (the periodic
``roll`` along z becomes an exchange between neighbours), as it would
partition any array program.  It imports nothing of the program, and
what the case's ``<CallPython>`` has to produce is stated in
``d3q27_cumulant_tgv.py``.  On one device it is that module's ``run``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.reference import geometry
from benchmark.reference.d3q27_cumulant import make_step
from benchmark.reference.d3q27_cumulant_tgv import FUNCTION, initial


def layout(nz: int) -> NamedSharding:
    """The populations ``(27, nz, ny, nx)`` split along z over as many of
    the machine's devices as divide ``nz``."""
    devs = jax.devices()
    n = max(k for k in range(1, len(devs) + 1) if nz % k == 0)
    return NamedSharding(Mesh(np.asarray(devs[:n]), ("z",)), P(None, "z"))


def run(root, steps: int, dtype=jnp.float32, storage=None) -> np.ndarray:
    """The 27 populations after ``steps`` steps of the case ``root``.
    ``storage`` narrows them between steps (the control stores them in
    bfloat16)."""
    if not any(el.get("function") == FUNCTION and not el.get("Iterations")
               for el in root.findall("CallPython")):
        raise ValueError(f"the case sets no {FUNCTION} initial field")
    masks = geometry.paint(root.find("Geometry"))
    par = geometry.params(root)
    shape = masks["collide"].shape
    on = layout(shape[0])
    step = make_step(masks, par)
    f0 = jax.jit(lambda: initial(shape, par.get("Velocity", 0.0), dtype),
                 out_shardings=on)()

    def one(_, f):
        f = step(f)
        return f if storage is None else f.astype(storage).astype(f0.dtype)

    return np.asarray(jax.jit(
        lambda f: jax.lax.fori_loop(0, steps, one, f),
        donate_argnums=0, out_shardings=on)(f0))
