"""The Taylor-Green initial field (``tclb_tpu/control/initial.py``) set
by a ``<CallPython>`` without ``Iterations``, through the program's
normal entry, against the plain reference of the ``tgv256`` configuration
(``benchmark/reference/d3q27_cumulant_tgv.py``, which imports nothing of
the program): the XLA step in float64, the tuned 3D engine in float32,
and the span round the call."""

import xml.etree.ElementTree as ET

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import d3q27_cumulant_tgv as reference
from tclb_tpu import telemetry
from tclb_tpu.control.solver import run_config_string
from tclb_tpu.models import get_model
from tclb_tpu.ops import pallas_d3q

SEED = 2**31 + 32
STEPS = 50


def case_xml(n: int, tail: str = "") -> str:
    nu, velocity = (float(v) for v in np.random.default_rng(SEED).uniform(
        [0.00106, 0.045], [0.00159, 0.055]))
    return f"""<CLBConfig version="2.0" model="d3q27_cumulant" output="output/">
    <Geometry nx="{n}" ny="{n}" nz="{n}"><MRT><Box/></MRT></Geometry>
    <Model>
        <Params Velocity="{velocity!r}"/>
        <Params nu="{nu!r}"/>
    </Model>
    <CallPython module="tclb_tpu.control.initial" function="taylor_green"/>
    {tail}
</CLBConfig>"""


def solver_of(n, dtype, tmp_path, steps=None):
    tail = f'<Solve Iterations="{steps}"/>' if steps else ""
    return run_config_string(case_xml(n, tail), get_model("d3q27_cumulant"),
                             dtype=dtype, output=str(tmp_path) + "/")


def worst(program, ref) -> float:
    assert program[:27].shape == ref.shape
    return float(np.abs(program[:27].astype(np.float64) - ref).max())


def test_initial_field_is_the_published_one(tmp_path, monkeypatch):
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    n = 16
    solver = solver_of(n, jnp.float64, tmp_path)
    lat = solver.lattice
    start = reference.run(ET.fromstring(case_xml(n)), 0, jnp.float64)
    assert worst(np.asarray(lat.state.fields), start) < 1e-15
    # planes beyond the populations stay as Init left them
    assert not np.asarray(lat.state.fields[27:]).any()
    U0 = float(lat.params.settings[solver.model.setting_index["Velocity"]])
    u = np.asarray(lat.get_quantity("U"))
    rho = np.asarray(lat.get_quantity("Rho"))
    z, y, x = np.meshgrid(*[2 * np.pi * np.arange(n) / n] * 3, indexing="ij")
    np.testing.assert_allclose(u[0], U0 * np.sin(x) * np.cos(y) * np.cos(z),
                               atol=1e-15)
    np.testing.assert_allclose(u[1], -U0 * np.cos(x) * np.sin(y) * np.cos(z),
                               atol=1e-15)
    assert np.abs(u[2]).max() < 1e-16
    np.testing.assert_allclose(
        rho, 1 + 3 * U0 ** 2 / 16 * (np.cos(2 * x) + np.cos(2 * y))
        * (np.cos(2 * z) + 2), atol=1e-15)


@pytest.mark.parametrize("n", [16, 32])
def test_xla_float64_is_the_reference(n, tmp_path, monkeypatch):
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    solver = solver_of(n, jnp.float64, tmp_path, STEPS)
    program = np.asarray(solver.lattice.state.fields)
    root = ET.fromstring(case_xml(n))
    ref = reference.run(root, STEPS, jnp.float64)
    assert ref.dtype == np.float64 and np.isfinite(ref).all()
    assert worst(program, ref) < 1e-13
    # the vortex has moved, and mass is conserved to rounding in both
    start = reference.run(root, 0, jnp.float64)
    assert worst(start, ref) > 1e-4
    for f in (ref, program[:27]):
        assert abs(f.sum() - start.sum()) < 1e-9 * start.sum()


def test_tuned_engine_float32_and_the_span(tmp_path, monkeypatch):
    """`tclb run`'s path on the tuned 3D engine (interpret mode), float32
    against the float32 reference; the call that set the field has its
    span, with the function's name and its seconds."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    seen = []
    trace = tmp_path / "events.jsonl"
    telemetry.enable(str(trace))
    telemetry.subscribe(seen.append)
    try:
        solver = solver_of(16, jnp.float32, tmp_path, 20)
    finally:
        telemetry.unsubscribe(seen.append)
        telemetry.disable()
    tags = [e["engine"] for e in seen if e.get("kind") == "engine_selected"]
    assert len(tags) == 1 and tags[0].startswith("pallas_d3q[d3q27_cumulant")
    assert not [e for e in seen if e.get("kind") == "engine_fallback"]
    ref = reference.run(ET.fromstring(case_xml(16)), 20, jnp.float32)
    assert worst(np.asarray(solver.lattice.state.fields), ref) < 2e-6
    spans = [e for e in seen if e.get("kind") == "span"
             and e.get("name") == "callpython"]
    assert [(e["module"], e["function"]) for e in spans] \
        == [("tclb_tpu.control.initial", "taylor_green")]
    assert spans[0]["dur_s"] > 0
    # the engine says what its calls were, on the innermost span open
    # round them (the first call's probe): 19 steps at fuse 4 (the depth
    # whose recomputed node steps and bytes balance: 8 computes 1.44 node
    # steps a useful one) are four fused calls on one band of 16 whole
    # planes and three steps over
    said = [e for e in seen if e.get("kind") == "span"
            and "kernel_calls" in e]
    assert [e["name"] for e in said] == ["engine.probe"]
    assert {k: said[0][k] for k in (
        "kernel_calls", "remainder_steps", "paired_calls", "z_bands",
        "band_slabs", "halo_slabs", "y_bands", "band_rows", "halo_rows",
        "aux_planes")} \
        == dict(kernel_calls=7, remainder_steps=3, paired_calls=4, z_bands=1,
                band_slabs=16, halo_slabs=4, y_bands=1, band_rows=16,
                halo_rows=0, aux_planes=1)
    assert said[0]["vmem_bytes"] == pallas_d3q._fused_vmem(
        get_model("d3q27_cumulant"), 16, 16, 16, 4)
