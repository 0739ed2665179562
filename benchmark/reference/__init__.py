"""Plain references, one module per model, found by the name in a
configuration file.  Each has ``run(root, steps, dtype, storage=None)``
and imports nothing of the program."""


def advance(step, f0, steps: int, storage=None):
    """``steps`` applications of ``step`` to ``f0`` in one jitted loop, as
    a host array.  ``storage`` narrows the populations between steps (the
    control stores them in bfloat16)."""
    import jax
    import numpy as np
    dtype = f0.dtype

    def one(_, f):
        f = step(f)
        return f if storage is None else f.astype(storage).astype(dtype)

    return np.asarray(jax.jit(
        lambda f: jax.lax.fori_loop(0, steps, one, f))(f0))
