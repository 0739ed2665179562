"""The plain references against the program's own XLA step in float64
on tiny cases: the same operations on the same data give the same
populations to rounding, so the references state the program's
semantics independently.  (The references import nothing of the
program; this test imports both.)"""

import copy
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from benchmark import casegen, check
from benchmark.reference import geometry
from benchmark.tests import tiny


def program_fields(root, steps, model_name, tmp_path):
    import jax.numpy as jnp

    from tclb_tpu.control.solver import run_config_string
    from tclb_tpu.models import get_model
    root = copy.deepcopy(root)
    for el in list(root):
        if el.tag in ("Failcheck", "Log", "VTK", "CallPython"):
            root.remove(el)
        elif el.tag == "Solve":
            el.set("Iterations", str(steps))
    solver = run_config_string(ET.tostring(root, encoding="unicode"),
                               get_model(model_name), dtype=jnp.float64,
                               output=str(tmp_path) + "/")
    return solver


@pytest.mark.parametrize("config_name,traffic_name,steps", [
    ("karman1024", "shipped", 50), ("channel3d512", "steady", 20)])
def test_reference_is_the_programs_semantics(config_name, traffic_name,
                                             steps, tmp_path, monkeypatch):
    import jax
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    config = casegen.load_json("configs", config_name)
    traffic = casegen.load_json("traffic", traffic_name)
    config["template"] = "tiny_" + config["template"]
    config["dtype"] = "float64"
    root, _ = casegen.generate(tiny.template_path(config), traffic,
                               2**31 + 12345)
    with jax.enable_x64(True):
        solver = program_fields(root, steps, config["model"], tmp_path)
        program = np.asarray(solver.lattice.state.fields)
        ref = check.reference_fields(config, root, steps)
        assert ref.dtype == np.float64
        assert check.largest_difference(program, ref) < 1e-13
        # the painter agrees with the program's, node for node
        m = solver.model
        flags = np.asarray(solver.lattice.state.flags)
        masks = geometry.paint(root.find("Geometry"))
        for node_type, key in (("Wall", "wall"), ("MRT", "collide"),
                               ("WVelocity", "inlet"),
                               ("EPressure", "outlet")):
            if node_type not in m.node_types:
                continue
            t = m.node_types[node_type]
            assert ((flags & t.mask) == t.value).sum() == masks[key].sum()
            assert (((flags & t.mask) == t.value) == masks[key]).all()


def test_seed_changes_the_case_not_the_work():
    traffic = casegen.load_json("traffic", "shipped")
    path = tiny.template_path({"template": "tiny_karman1024"})
    a, va = casegen.generate(path, traffic, 1)
    b, vb = casegen.generate(path, traffic, 2**31 + 7)
    a2, va2 = casegen.generate(path, traffic, 1)
    assert va == va2 and va != vb
    assert ET.tostring(a) == ET.tostring(a2)
    walls = [geometry.paint(r.find("Geometry"))["wall"].sum()
             for r in (a, b)]
    assert walls[0] == walls[1]
    tags = [el.tag for el in a]
    assert tags[-2:] == ["CallPython", "Solve"]
    assert a.find("CallPython").get("Iterations") == "500"
