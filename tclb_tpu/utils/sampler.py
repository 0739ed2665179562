"""Point sampler: high-frequency probes flushed to CSV.

Parity target: reference Sampler (src/Sampler.{h,cpp.Rt}, C16 in SURVEY.md):
points registered from the <Sample><Point .../></Sample> element, quantities
gathered every iteration into a device buffer (here: the scan-ys of a
sampled engine's one-step loop, turned into the quantities' columns by
``core/lattice.py:taps_program``, or those of ``make_sampled_iterate``),
flushed to a CSV by the callback (writeHistory, src/Sampler.cpp.Rt:35-58).
The rows stay on the device until the flush, which is one copy and one
formatted write.
"""

from __future__ import annotations

import os

import jax
import numpy as np

from tclb_tpu import telemetry


class Sampler:
    def __init__(self, model, quantities: list[str],
                 points: np.ndarray, path: str, units=None):
        """``points`` is (npoints, ndim) in array index order."""
        self.model = model
        self.quantities = list(quantities)
        self.points = np.asarray(points, dtype=np.int32)
        self.path = path
        self.units = units
        self._rows: list = []   # (its, samples) a chunk, see append()
        self._wrote_header = False
        # column names: per point, per quantity (vector -> 3 columns)
        self.columns: list[str] = []
        for i in range(len(self.points)):
            for q in self.quantities:
                spec = next(x for x in model.quantities if x.name == q)
                if spec.vector:
                    self.columns += [f"{q}_{i}_{c}" for c in "xyz"]
                else:
                    self.columns.append(f"{q}_{i}")

    def append(self, its, samples) -> None:
        """``samples``: (nsteps, npoints, ncols-per-point), the rows of
        the steps that ended at the iterations ``its`` (nsteps,).  Both
        may still be on the device: nothing is copied before
        :meth:`flush`."""
        self._rows.append((its, samples))

    def flush(self) -> None:
        """Write the rows kept since the last flush to the file: one copy
        from the device (``sample.d2h``) and one formatted write of the
        block (``output.sample``), the header before the first.  The file
        is current when this returns.  Nothing where no row waits and
        the header is written."""
        if not self._rows and self._wrote_header:
            return
        got = []
        if self._rows:
            with telemetry.span("sample.d2h",
                                copies=2 * len(self._rows)) as sp:
                sp.sync(self._rows)     # what the device still computes
                got = jax.device_get(self._rows)
                sp.add(bytes=sum(i.nbytes + v.nbytes for i, v in got))
        # iteration first, then the row's values: float64 holds both
        block = np.concatenate(
            [np.column_stack([i, v.reshape(len(i), -1)]) for i, v in got]
            or [np.zeros((0, 1 + len(self.columns)))]).astype(np.float64)
        with telemetry.span("output.sample", rows=len(block)) as sp:
            # the iteration as a whole number, every value as "%g"
            line = "%d" + ",%g" * (block.shape[1] - 1) + "\n"
            text = (line * len(block)) % tuple(block.ravel().tolist())
            if not self._wrote_header:
                text = ",".join(["Iteration"] + self.columns) + "\n" + text
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            with open(self.path, "a" if self._wrote_header else "w") as f:
                f.write(text)
            self._wrote_header = True
            sp.add(bytes=len(text))
        self._rows.clear()      # kept until the file has them
        telemetry.counter("output.sample.flushes")
