"""d3q19_kuper against its plain reference (``benchmark/reference/
d3q19_kuper.py``, which imports nothing of the program): the XLA step in
float64, the generic 3D slab engine on y-tiled windows (interpret mode,
float32) at fuse 1 and 2 with the paired loop and a remainder, tiled
against whole-plane results bit for bit, the whole-plane plans the parent
had, the 3D zones painter, mass, the plan at 256^3, and the account on
``iterate.fused``.  The case is the drop of ``example/drop3d_256.xml`` at
8 x 32 x 128, off centre, across the seams of the bands in z and in y."""

import os
import xml.etree.ElementTree as ET

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import d3q19_kuper as reference
from benchmark.reference import zones, zones3d
from tclb_tpu import telemetry
from tclb_tpu.control.solver import run_config_string
from tclb_tpu.models import get_model
from tclb_tpu.ops import pallas_generic
from tclb_tpu.ops.lbm import present_types
from tclb_tpu.utils.geometry import Geometry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE = (8, 32, 128)
SEED = 2**31 + 34
STEPS64 = 50
# float32 against the float32 reference after 9 steps: the engines agree
# with the XLA step to the bit in interpret mode; the rest is sums taken
# in another order on populations up to 1.09 inside the drop; a force of the wrong sign
# or a phi one step stale reads 0.1
TOL32 = 1e-5
# windows of 4 slabs x 16 rows: 2 bands in z, 2 in y, the wrap in both
WINDOW = (4, 16)
# VMEM a tiled window may count on where the Lattice plans the case
# itself: 4 x 4 windows of 2 slabs x 8 rows
SMALL_VMEM = 7_000_000


def case_xml(tail: str = "") -> str:
    ox, oy, oz = np.random.default_rng(SEED).integers(-1, 2, 3)
    return f"""<CLBConfig version="2.0" model="d3q19_kuper" output="output/">
    <Geometry nx="{SHAPE[2]}" ny="{SHAPE[1]}" nz="{SHAPE[0]}">
        <MRT><Box/></MRT>
        <None name="zdrop">
            <Sphere dx="{40 + ox}" nx="24" dy="{9 + oy}" ny="12"
                    dz="{2 + oz}" nz="5"/>
        </None>
    </Geometry>
    <Model>
        <Params omega="1"/>
        <Params Density="0.014500641645077492"
                Density-zdrop="3.2600529440452366"
                Temperature="0.56" FAcc="1" Magic="0.01"
                MagicA="-0.152" MagicF="-0.3333333333333"/>
    </Model>{tail}
</CLBConfig>"""


def solver_of(dtype, tmp_path, steps=None):
    """The case through the program's normal entry, initialised and,
    with ``steps``, solved."""
    tail = f'<Solve Iterations="{steps}"/>' if steps else ""
    return run_config_string(case_xml(tail), get_model("d3q19_kuper"),
                             dtype=dtype, output=str(tmp_path) + "/")


def worst(program, ref) -> float:
    assert program.shape == ref.shape == (20,) + SHAPE
    return float(np.abs(program.astype(np.float64) - ref).max())


def mass(fields) -> float:
    return float(np.asarray(fields[:19], np.float64).sum())


def test_xla_float64_is_the_reference(tmp_path, monkeypatch):
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    solver = solver_of(jnp.float64, tmp_path, STEPS64)
    program = np.asarray(solver.lattice.state.fields)
    root = ET.fromstring(case_xml())
    ref = reference.run(root, STEPS64, jnp.float64)
    assert ref.dtype == np.float64 and np.isfinite(ref).all()
    assert worst(program, ref) < 1e-13
    # the drop lies across the seams of WINDOW's bands, and it has moved
    painted = zones3d.paint(root.find("Geometry"))
    inside = painted["zone"] == painted["names"]["zdrop"]
    assert inside[:4].any() and inside[4:].any()
    assert inside[:, :16].any() and inside[:, 16:].any()
    start = reference.run(root, 0, jnp.float64)
    assert worst(start, ref) > 0.1
    # total mass is conserved to rounding, in both
    assert abs(mass(ref) - mass(start)) < 1e-9 * mass(start)
    assert abs(mass(program) - mass(start)) < 1e-9 * mass(start)


def test_painter_is_the_programs(tmp_path, monkeypatch):
    """Zone for zone and density for density, node for node."""
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    solver = solver_of(jnp.float64, tmp_path)
    m, lat = solver.model, solver.lattice
    root = ET.fromstring(case_xml())
    painted = zones3d.paint(root.find("Geometry"))
    flags = np.asarray(lat.state.flags)
    assert painted["names"] == solver.geometry.setting_zones
    assert ((flags >> m.zone_shift) == painted["zone"]).all()
    mrt = m.node_types["MRT"]
    assert (((flags & mrt.mask) == mrt.value) == painted["collide"]).all()
    table = np.asarray(lat.params.zone_table)[m.setting_index["Density"]]
    density = zones.zonal({"Density": 0.014500641645077492,
                           "Density-zdrop": 3.2600529440452366},
                          painted, "Density", 1.0)
    assert (table[flags >> m.zone_shift] == density).all()
    assert 300 < (density > 1).sum() < 900
    with pytest.raises(ValueError):
        zones3d.paint(ET.fromstring(
            '<Geometry nx="8" ny="8" nz="8"><Wall><Box/></Wall></Geometry>'))


@pytest.mark.parametrize("fuse,steps,calls", [
    # five calls of the one-step plan: two trips of the two-call body
    # and an odd call after the loop
    (1, 5, dict(kernel_calls=5, remainder_steps=0, paired_calls=4)),
    # four fused calls (two trips, no odd call) and a step over
    (2, 9, dict(kernel_calls=5, remainder_steps=1, paired_calls=4))])
def test_tiled_engine_float32(fuse, steps, calls, tmp_path, monkeypatch):
    """The y-tiled windows against the float32 reference, and against the
    whole-plane plan of the same depth bit for bit."""
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    lat = solver_of(jnp.float32, tmp_path).lattice
    root = ET.fromstring(case_xml())
    start = reference.run(root, 0, jnp.float32)
    assert worst(np.asarray(lat.state.fields), start) < 1e-6
    m = lat.model
    present = present_types(m, np.asarray(lat.state.flags))
    out = {}
    for name, how in (("tiled", dict(window=WINDOW)), ("whole", {})):
        it = pallas_generic.make_pallas_iterate_3d(
            m, SHAPE, jnp.float32, interpret=True, fuse=fuse,
            present=present, **how)
        out[name] = (it, np.asarray(it(jax.tree.map(jnp.copy, lat.state),
                                       lat.params, steps).fields))
    it, program = out["tiled"]
    assert it.plan == WINDOW + (fuse,) and it.full_globals
    did = it.account(steps)
    assert {k: did[k] for k in calls} == calls
    assert (did["z_bands"], did["y_bands"], did["halo_rows"],
            did["halo_slabs"]) == (2, 2, 8, 2 * fuse)
    assert out["whole"][0].plan == (8, 32, fuse)
    assert out["whole"][0].account(steps)["halo_rows"] == 0
    assert np.isfinite(program).all()
    ref = reference.run(root, steps, jnp.float32)
    assert worst(program, ref) < TOL32
    assert worst(start, ref) > 0.1
    assert abs(mass(program) - mass(start)) < 1e-5 * mass(start)
    assert (program == out["whole"][1]).all()


@pytest.mark.parametrize("name,shape,plan", [
    ("d3q19_kuper", (48, 48, 256), (6, 48, 2)),
    ("d3q19_kuper", (6, 16, 128), (6, 16, 1)),
    ("d3q19_heat", (48, 48, 256), (4, 48, 3))])
def test_whole_plane_plans_are_the_parents(name, shape, plan):
    """A shape a whole-plane plan took before y tiling keeps it to the
    tuple: slab depth, the whole plane, the fuse the Lattice asks for."""
    m = get_model(name)
    assert pallas_generic.tile_plan_3d(m, shape) is None
    K = pallas_generic.choose_fuse_3d(m, shape)
    it = pallas_generic.make_pallas_iterate_3d(m, shape, jnp.float32,
                                               interpret=True, fuse=K)
    assert it.plan == plan
    assert it.impl["bz"] == plan[0]
    did = it.account(10)
    assert (did["y_bands"], did["band_rows"], did["halo_rows"]) \
        == (1, shape[1], 0)


def test_the_plan_at_256_cubed():
    """``example/drop3d_256.xml`` by its shape alone: nothing is
    allocated.  No whole-plane plan holds a 256 x 256 plane of this model
    (13,677 nodes at the most), so the planner tiles it."""
    m = get_model("d3q19_kuper")
    shape = (256, 256, 256)
    assert pallas_generic.supports_3d(m, (256, 48, 256), jnp.float32,
                                      probe=False)
    assert not pallas_generic._whole_plane_3d(m, 256, 64, 256)
    assert pallas_generic._whole_plane_3d(m, 256, 48, 256)
    assert pallas_generic.supports_3d(m, shape, jnp.float32, probe=False)
    bz, by, K = pallas_generic.tile_plan_3d(m, shape)
    assert 256 % bz == 0 and 256 % by == 0 and by % 8 == 0 and by < 256
    assert pallas_generic._reach_y(m, K) <= pallas_generic.HALO
    assert pallas_generic.choose_fuse_3d(m, shape) == K
    # the rungs of the probe ladder cap rows and slabs both
    assert pallas_generic.tile_plan_3d(m, shape, fuse=K, cap=16)[:2] \
        == (2, 16)
    assert pallas_generic.tile_plan_3d(m, shape, fuse=1, cap=8) == (1, 8, 1)
    # four pulls a step-pair: fuse 5 would spoil 10 halo rows of 8
    assert pallas_generic._reach_y(m, 4) == 8
    assert pallas_generic.tile_plan_3d(m, shape, fuse=5) is None \
        or pallas_generic.tile_plan_3d(m, shape, fuse=5)[1] == 256


@pytest.fixture
def tiled_lattice(tmp_path, monkeypatch):
    """The case on the tiled engine through the Lattice's own dispatch:
    the whole-plane plan this small box would get is refused and the
    window's VMEM cut, as ``benchmark/tests/test_drop3d.py`` does."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    plan = pallas_generic.tile_plan_3d
    monkeypatch.setattr(pallas_generic, "_whole_plane_3d",
                        lambda *a, **k: False)
    monkeypatch.setattr(
        pallas_generic, "tile_plan_3d",
        lambda model, shape, itemsize=4, fuse=None, cap=None, budget=None:
        plan(model, shape, itemsize, fuse, cap, SMALL_VMEM))
    # no verdict of an earlier probe, and none left behind
    probed = ("d3q19_kuper", "3d") + SHAPE[1:] + (4,)
    monkeypatch.delitem(pallas_generic._cfg_cache,
                        ("d3q19_kuper", SHAPE), raising=False)
    monkeypatch.delitem(pallas_generic._probe_cache, probed, raising=False)
    yield solver_of(jnp.float32, tmp_path).lattice
    pallas_generic._cfg_cache.pop(("d3q19_kuper", SHAPE), None)
    pallas_generic._probe_cache.pop(probed, None)



def test_build_fast_picks_the_tiled_engine(tiled_lattice):
    """What a plane no whole-plane plan holds gets from ``_build_fast``
    alone, as ``tclb run example/drop3d_256.xml`` does on the chip: the
    planner's window first, probed, then windows of fewer rows and
    slabs, all under the raised ceiling (no negative rung)."""
    geom = ET.parse(os.path.join(ROOT, "example", "drop3d_256.xml")
                    ).getroot().find("Geometry")
    assert [geom.get(k) for k in ("nz", "ny", "nx")] == ["256"] * 3
    painter = Geometry(tiled_lattice.model, SHAPE)
    painter.load(ET.fromstring(case_xml()).find("Geometry"))
    assert (painter.result() == np.asarray(tiled_lattice.state.flags)).all()
    chain = tiled_lattice._build_fast()
    assert chain[0].tag == "pallas_generic[d3q19_kuper,fuse=1,by=8]"
    assert chain[0].probe and chain[0].cap == 8
    assert chain[0].verdict == (1, None)
    assert [(c.tag, c.cap, c.verdict) for c in chain[1:]] == [
        (f"pallas_generic[d3q19_kuper,fuse=1,by<={cap}]", cap, (1, cap))
        for cap in (16, 8)]
    assert chain[0].build().plan == (2, 8, 1)
    assert chain[2].build().plan == (1, 8, 1)


def test_spans_and_annotations(tiled_lattice):
    events = []
    before = telemetry.counters()
    telemetry.subscribe(events.append)
    try:
        tiled_lattice.iterate(5)
        tiled_lattice.iterate(5)
        counters = {k: v - before.get(k, 0)
                    for k, v in telemetry.counters().items()}
    finally:
        telemetry.unsubscribe(events.append)
    tag = "pallas_generic[d3q19_kuper,fuse=1,by=8]"
    assert tiled_lattice._fast_name == tag
    assert tiled_lattice._fast.plan == (2, 8, 1)
    assert sum(e.get("kind") == "engine_fallback" for e in events) == 0
    spans = [e for e in events if e.get("kind") == "span"]
    fused = [e for e in spans if e["name"] == "iterate.fused"]
    probes = [e for e in spans if e["name"] == "engine.probe"]
    assert len(fused) == 2 and len(probes) == 1
    probe = probes[0]
    assert probe["parent"] == fused[0]["id"]
    assert (probe["engine"], probe["result"]) == (tag, tag)
    assert (probe["attempts"], probe["rungs"]) == (1, [8])
    did = dict(stages_per_step=2, kernel_calls=5, remainder_steps=0,
               paired_calls=4, z_bands=4, band_slabs=2, halo_slabs=2,
               y_bands=4, band_rows=8, halo_rows=8, aux_planes=1)
    # the first call's account lies on the probe that made the calls
    for span in (probe, fused[1]):
        assert {k: span[k] for k in did} == did
    assert fused[1]["iters"] == 5 and fused[1]["engine"] == tag
    assert counters["engine.kernel_calls"] == 10
    assert counters["engine.paired_calls"] == 8
    assert counters["engine.probe_attempts"] == 1
    # in-kernel: no trailing XLA step for a model without Globals
    assert not [e for e in spans if e["name"] == "iterate.globals_step"]
    # and the engine the spans describe is the one the reference holds
    ref = reference.run(ET.fromstring(case_xml()), 10, jnp.float32)
    assert worst(np.asarray(tiled_lattice.state.fields), ref) < TOL32


def test_nothing_is_recorded_with_telemetry_off(tiled_lattice):
    assert not telemetry.enabled()
    before = telemetry.counters()
    tiled_lattice.iterate(5)
    assert telemetry.counters() == before
    assert tiled_lattice._fast_name \
        == "pallas_generic[d3q19_kuper,fuse=1,by=8]"
    assert not tiled_lattice._fast_probing
