"""The configuration ``karman8192`` (the karman channel in rows of 8192
nodes) against the benchmark's plain reference
(``benchmark/reference/d2q9.py``, which imports nothing of the program),
on a tiny wide-row copy of its template
(``benchmark/tests/data/tiny_karman8192.xml``: 64 x 2048, seeded as the
cell's traffic seeds it): the engines dispatch picks there, in interpret
mode, through the program's normal entry."""

import copy
import os
import xml.etree.ElementTree as ET

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import casegen
from benchmark.reference import d2q9 as reference
from tclb_tpu import telemetry
from tclb_tpu.control.solver import run_config_string
from tclb_tpu.models import get_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "benchmark", "tests", "data",
                    "tiny_karman8192.xml")
SHAPE = (64, 2048)
STEPS = 20          # 19 on the band engine (9 two-step calls and one
#                     one-step call) and the tail's one
SEED = 2**31 + 49


def tiny_case() -> tuple:
    """The tiny template as the cell's traffic seeds it (the obstacle's
    walk cut to the tiny channel), without handlers: root and drawn."""
    traffic = copy.deepcopy(casegen.load_json("traffic", "longrun"))
    for rule in traffic["seeded"]:
        if "int" in rule:
            rule["int"] = [-4, 4]
    traffic["handlers"] = [{"tag": "Log", "Iterations": STEPS}]
    root, drawn = casegen.generate(TINY, traffic, SEED)
    for tag in ("Log", "CallPython", "Solve"):
        root.remove(root.find(tag))
    return root, drawn


def test_the_real_template_is_the_tiny_one_grown():
    """Same elements in the same order; the real one's obstacle is four
    wedges of 800 nodes a fifth of the way down an 8192 x 8192 channel."""
    real = ET.parse(os.path.join(ROOT, "benchmark", "cases",
                                 "karman8192.xml")).getroot()
    tiny = ET.parse(TINY).getroot()
    assert [e.tag for e in real.iter()] == [e.tag for e in tiny.iter()]
    geom = real.find("Geometry")
    assert (geom.get("ny"), geom.get("nx")) == ("8192", "8192")
    wedges = geom.findall("Wall/Wedge")
    assert {(w.get("nx"), w.get("ny")) for w in wedges} == {("800", "800")}
    assert sorted((int(w.get("dx")), int(w.get("dy"))) for w in wedges) \
        == [(960, 3296), (960, 4096), (1760, 3296), (1760, 4096)]
    # the shipped example is the same case with its own handlers
    shipped = ET.parse(os.path.join(ROOT, "example",
                                    "karman_8192.xml")).getroot()
    for tag in ("Geometry", "Model"):
        assert ET.tostring(shipped.find(tag)).split() \
            == ET.tostring(real.find(tag)).split()
    assert [(e.tag, e.get("Iterations")) for e in shipped
            if e.tag in ("Log", "Failcheck")] \
        == [("Failcheck", "500"), ("Log", "250")]


def test_program_is_the_reference_on_wide_rows(tmp_path, monkeypatch):
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    root, drawn = tiny_case()
    assert set(drawn) == {"ox", "oy", "velocity"}
    ET.SubElement(root, "Solve", {"Iterations": str(STEPS)})
    events = []
    telemetry.subscribe(events.append)
    try:
        solver = run_config_string(
            ET.tostring(root, encoding="unicode"), get_model("d2q9"),
            dtype=jnp.float32, output=str(tmp_path) + "/")
    finally:
        telemetry.unsubscribe(events.append)
    lat = solver.lattice
    assert lat.shape == SHAPE
    assert lat._fast_name == "pallas_2d[d2q9,fuse=2]"
    assert lat._tail_name == "pallas_generic[d2q9,fuse=1]"
    assert not [e for e in events if e.get("kind") == "engine_fallback"]
    plan = lat._fast.impl["plan"]
    assert plan.band_rows == (64, 32) and plan.raised(2)
    program = np.asarray(lat.state.fields)
    ref = reference.run(root, STEPS, jnp.float32)
    assert ref.shape == (9,) + SHAPE and np.isfinite(ref).all()
    worst = float(np.abs(program[:9].astype(np.float64) - ref).max())
    # karman1024's limit, which the configuration takes over
    assert worst <= casegen.load_json("configs", "karman8192")["tolerance"]
    assert worst < 1e-5
    # and the flow has moved: the start is far from the end
    start = reference.run(root, 0, jnp.float32)
    assert float(np.abs(start - ref).max()) > 1e-3


@pytest.mark.parametrize("key", ["model", "reference", "dtype", "tolerance",
                                 "check_segments", "chips", "mesh",
                                 "engine_family"])
def test_the_configuration_is_karman1024s_but_for_its_size(key):
    mine = casegen.load_json("configs", "karman8192")
    base = casegen.load_json("configs", "karman1024")
    assert mine[key] == base[key]
    assert mine["shape"] == [8192, 8192]
    assert mine["reduced"] == ["nx", "ny", "Wedge"]
