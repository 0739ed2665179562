"""Every shipped example case runs end to end — the reference treats its
example/*.xml set as its smoke suite (SURVEY §4.3); ours plays the same
role.  Iteration counts are scaled down for CI: the full cases run on
real hardware via ``tclb run example/<case>.xml``."""

import re
import xml.etree.ElementTree as ET
from pathlib import Path

import numpy as np
import pytest

EXAMPLES = sorted(Path(__file__).parent.parent.glob("example/*.xml"))


def _shrink(tree: ET.ElementTree) -> None:
    """Scale iteration-bearing handlers down to CI size (keep ratios:
    Control horizons stay >= the Solve length so series semantics hold)."""
    root = tree.getroot()
    for el in root.iter():
        for attr in ("Iterations",):
            v = el.get(attr)
            if v is None or not re.fullmatch(r"\d+", v):
                continue
            n = int(v)
            if el.tag in ("Solve", "Log", "VTK", "TXT", "BIN", "Failcheck",
                          "Catalyst", "Sample", "Average"):
                el.set(attr, str(max(2, min(n, 20))))
            elif el.tag in ("Optimize", "FDTest", "Adjoint"):
                el.set(attr, str(max(2, min(n, 4))))
            elif el.tag == "Control":
                el.set(attr, str(max(4, min(n, 20))))
        for attr in ("MaxEvaluations", "Checks"):
            v = el.get(attr)
            if v is not None and re.fullmatch(r"\d+", v):
                el.set(attr, str(min(int(v), 2)))
    # geometry stays as authored: with the iteration counts capped, even
    # the 1024-wide cases run in under a second on CPU, and shrinking
    # the domain would clip the authored obstacles/zones out of the case.
    # One exception: the chip-size 3D channel (6.3 M nodes) is cut along
    # z, its periodic axis, where there is nothing to clip
    geom = root.find("Geometry")
    if geom is not None and int(geom.get("nz", "1")) > 64:
        geom.set("nz", "64")


@pytest.mark.slow
@pytest.mark.parametrize("case", EXAMPLES, ids=[c.stem for c in EXAMPLES])
def test_example_runs(case, tmp_path, monkeypatch):
    from tclb_tpu.control import run_config_string
    from tclb_tpu.models import get_model

    tree = ET.parse(case)
    root = tree.getroot()
    _shrink(tree)
    root.set("output", str(tmp_path) + "/")
    # file references inside cases are repo-relative
    monkeypatch.chdir(Path(__file__).parent.parent)
    xml = ET.tostring(root, encoding="unicode")
    solver = run_config_string(xml, get_model(root.get("model")))
    fields = np.asarray(solver.lattice.state.fields)
    assert np.isfinite(fields).all(), f"{case.stem} went non-finite"
