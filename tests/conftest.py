"""Test environment: force CPU with 8 virtual devices (multi-chip emulation —
the reference tests its MPI path by running any-rank-count CPU builds on one
box, SURVEY.md §4.8; we do the same with XLA host devices) and enable f64 so
goldens can use the reference's 1e-10 tolerance model (tools/csvdiff).

Note: the env vars below only count if jax has not been imported yet;
``jax.config.update`` also works after import, as long as no backend has
been initialized — so both are set.
"""

import collections
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
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


# The driver runs these tests on six xdist workers under ``--dist loadfile``
# inside a time limit: a worker is handed whole files in the order the files
# are collected (xdist's own reordering, most cases first, is taken off
# below), so the run is as long as the last worker's last file.  By case
# count a heavy file of few cases started last and the run waited on it
# (the schedule replayed from a run's junit times, PR 55: 1547 s against
# 1400 in this order).  The order: files of many short cases first (a case
# every 2.5 s or faster, and any file not listed: its weight is not
# known), so that a run cut at its limit has lost few cases; then the
# heaviest first, which keeps the workers' ends within a short file's
# length of each other.  Seconds a file's tier-1 cases took beside five
# other workers (the mean of PR 55's two runs, 8 shared cores), files
# under 25 s left out.
_FILE_SECONDS = {
    "test_pallas3d": 1027, "test_mosaic_compile": 972,
    "test_tail_engine": 743, "test_kuper_reference": 527,
    "test_pallas_generic": 511, "test_fastpath": 459, "test_sampler": 410,
    "test_sharded_slab": 321, "test_tuned_band_plan": 312,
    "test_band_pairing": 270, "test_quantity_program": 241,
    "test_telemetry": 220, "test_checkpoint": 219,
    "test_sharded_band": 201, "test_serve": 191,
    "test_kuper3d_reference": 183, "test_resident_account": 154,
    "test_fleet": 118, "test_setup_tree": 118, "test_precision": 95,
    "test_engine_protocol": 81, "test_vtk_async": 80, "test_zone_plane": 73,
    "test_taylor_green": 63, "test_chaos": 61, "test_live": 54,
    "test_physics_constitutive": 53, "test_analysis": 52,
    "test_control_band": 52, "test_shift": 51, "test_gateway": 50,
    "test_d2q9": 49, "test_golden": 44, "test_karman8192_reference": 31,
    "test_sharding": 30, "test_external_goldens": 29,
}


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


@pytest.hookimpl(trylast=True)      # behind ``-m``'s deselection
def pytest_collection_modifyitems(items):
    cases = collections.Counter(item.path.stem for item in items)

    def place(item):
        seconds = _FILE_SECONDS.get(item.path.stem)
        if seconds is None or cases[item.path.stem] >= 0.4 * seconds:
            return 0, 0
        return 1, -seconds

    # stable: a file's cases stay together and in their order
    items.sort(key=place)
