"""Test environment: force CPU with 8 virtual devices (multi-chip emulation —
the reference tests its MPI path by running any-rank-count CPU builds on one
box, SURVEY.md §4.8; we do the same with XLA host devices) and enable f64 so
goldens can use the reference's 1e-10 tolerance model (tools/csvdiff).

Note: the env vars below only count if jax has not been imported yet;
``jax.config.update`` also works after import, as long as no backend has
been initialized — so both are set.
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# flight-recorder dumps (telemetry/live.py) go to a scratch dir, not the
# repo checkout, when eviction/failcheck tests trigger them
os.environ.setdefault("TCLB_FLIGHT_DIR",
                      tempfile.mkdtemp(prefix="tclb-flight-"))
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
