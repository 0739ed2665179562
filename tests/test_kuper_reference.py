"""d2q9_kuper against its plain reference (``benchmark/reference/
d2q9_kuper.py``, which imports nothing of the program): the XLA step in
float64, the generic Pallas engines (band at fuse 4 and 1, resident) in
interpret mode in float32, the zones painter, mass, the engine
``_build_fast`` picks for the drop cases as shipped, and the spans and
annotations of PR 28.  The case is the drop of ``example/drop.xml`` at
64 x 128 with the drop across the seam of the two 32-row bands."""

import os
import xml.etree.ElementTree as ET

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import d2q9_kuper as reference
from benchmark.reference import zones
from tclb_tpu import telemetry
from tclb_tpu.control.solver import run_config_string
from tclb_tpu.core.lattice import Lattice, make_iterate
from tclb_tpu.models import get_model
from tclb_tpu.ops import pallas_generic
from tclb_tpu.ops.lbm import present_types
from tclb_tpu.utils.geometry import Geometry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE = (64, 128)
SEED = 2**31 + 28
STEPS64 = 50
# 23 = 5 calls at fuse 4, a remainder of 2 and the globals flavor's 1
STEPS32 = 23
# float32 against the float32 reference after 23 steps: all three
# engines read 9.5e-7 on this case (they agree to the bit in interpret
# mode; the rest is sums taken in another order on populations up to
# 1.45); a force of the wrong sign or a phi one step stale reads 0.1
TOL32 = 1e-5


MODEL_XML = """    <Model>
        <Params omega="1"/>
        <Params Density="3.2600529440452366"
                Density-zdrop="0.014500641645077492"
                Temperature="0.56" FAcc="1" Magic="0.01"
                MagicA="-0.152" MagicF="-0.6666666666666"/>
    </Model>"""


def case_xml(tail: str = "") -> str:
    ox, oy, d = np.random.default_rng(SEED).integers(-4, 5, 3)
    return f"""<CLBConfig version="2.0" model="d2q9_kuper" output="output/">
    <Geometry nx="{SHAPE[1]}" ny="{SHAPE[0]}">
        <MRT><Box/></MRT>
        <None name="zdrop">
            <Sphere dx="{44 + ox}" nx="{36 + d}" dy="{14 + oy}" ny="{36 + d}"/>
        </None>
    </Geometry>
{MODEL_XML}{tail}
</CLBConfig>"""


def solver_of(dtype, tmp_path, steps=None):
    """The case through the program's normal entry, initialised and,
    with ``steps``, solved."""
    tail = f'<Solve Iterations="{steps}"/>' if steps else ""
    return run_config_string(case_xml(tail), get_model("d2q9_kuper"),
                             dtype=dtype, output=str(tmp_path) + "/")


def worst(program, ref, shape=SHAPE) -> float:
    assert program.shape == ref.shape == (10,) + shape
    return float(np.abs(program.astype(np.float64) - ref).max())


def mass(fields) -> float:
    return float(np.asarray(fields[:9], np.float64).sum())


@pytest.fixture(scope="module")
def reference32():
    root = ET.fromstring(case_xml())
    return (reference.run(root, 0, jnp.float32),
            reference.run(root, STEPS32, jnp.float32))


def test_xla_float64_is_the_reference(tmp_path, monkeypatch):
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    solver = solver_of(jnp.float64, tmp_path, STEPS64)
    program = np.asarray(solver.lattice.state.fields)
    root = ET.fromstring(case_xml())
    ref = reference.run(root, STEPS64, jnp.float64)
    assert ref.dtype == np.float64 and np.isfinite(ref).all()
    assert worst(program, ref) < 1e-13
    # the drop straddles the seam of the bands, and it has moved
    painted = zones.paint(root.find("Geometry"))
    inside = painted["zone"] == painted["names"]["zdrop"]
    assert inside[:32].any() and inside[32:].any()
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
    painted = zones.paint(root.find("Geometry"))
    flags = np.asarray(lat.state.flags)
    assert painted["names"] == solver.geometry.setting_zones
    assert ((flags >> m.zone_shift) == painted["zone"]).all()
    mrt = m.node_types["MRT"]
    assert (((flags & mrt.mask) == mrt.value) == painted["collide"]).all()
    table = np.asarray(lat.params.zone_table)[m.setting_index["Density"]]
    density = zones.zonal({"Density": 3.2600529440452366,
                           "Density-zdrop": 0.014500641645077492},
                          painted, "Density", 1.0)
    assert (table[flags >> m.zone_shift] == density).all()
    assert 500 < (density < 1).sum() < 1500
    with pytest.raises(ValueError):
        zones.zonal({"Density-nowhere": 1.0}, painted, "Density", 1.0)
    with pytest.raises(ValueError):
        zones.paint(ET.fromstring(
            '<Geometry nx="8" ny="8"><Wall><Box/></Wall></Geometry>'))


@pytest.mark.parametrize("engine", ["band_fuse4", "band_fuse1", "resident"])
def test_pallas_float32_against_the_reference(engine, reference32, tmp_path,
                                              monkeypatch):
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    start, ref = reference32
    lat = solver_of(jnp.float32, tmp_path).lattice
    assert worst(np.asarray(lat.state.fields), start) < 1e-6
    if engine == "resident":
        lat.iterate(STEPS32)
        assert lat._fast_name == "pallas_resident_generic[d2q9_kuper]"
        state = lat.state
    else:
        fuse = int(engine[-1])
        m = lat.model
        it = pallas_generic.make_pallas_iterate(
            m, SHAPE, jnp.float32, fuse=fuse,
            present=present_types(m, np.asarray(lat.state.flags)))
        assert it.full_globals and it.impl["by"] == 32
        assert it.impl["pad"] == 0
        state = it(jax.tree.map(jnp.copy, lat.state), lat.params, STEPS32)
    program = np.asarray(state.fields)
    assert np.isfinite(program).all()
    assert worst(program, ref) < TOL32
    assert abs(mass(program) - mass(start)) < 1e-5 * mass(start)


@pytest.fixture(scope="module")
def xla_steps(tmp_path_factory):
    """The case's state after 0 to 24 XLA steps, float32, and the
    lattice they started from."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("TCLB_FASTPATH", "0")
        lat = solver_of(jnp.float32, tmp_path_factory.mktemp("xla")).lattice
    m = lat.model
    present = present_types(m, np.asarray(lat.state.flags))
    step = jax.jit(make_iterate(m, present=present),
                   static_argnames=("niter",))
    states = [jax.tree.map(jnp.copy, lat.state)]
    for _ in range(24):
        states.append(step(jax.tree.map(jnp.copy, states[-1]), lat.params,
                           1))
    return lat, present, [np.asarray(s.fields) for s in states]


@pytest.mark.parametrize("over", [0, 3], ids=["even", "over3"])
@pytest.mark.parametrize("calls", range(6))
def test_band_loops_pair_their_calls_bit_for_bit(calls, over, xla_steps):
    """The band engine's loops run two kernel calls a body and an odd
    call after the loop (``_PAIR``): ``calls`` calls at fuse 4, ``over``
    single steps and the globals flavor's one are the same calls in the
    same order as one a body, so the state equals the XLA step's to the
    last bit (as it did), and ``account`` says which calls a two-call
    body issued: those of a loop of four trips or more, less its odd
    one (``lax.scan`` unrolls a shorter loop whole)."""
    lat, present, after = xla_steps
    m = lat.model
    it = pallas_generic.make_pallas_iterate(m, SHAPE, jnp.float32, fuse=4,
                                            present=present)
    niter = 4 * calls + over + 1
    did = it.account(niter, False)
    assert (did["kernel_calls"], did["remainder_steps"],
            did["paired_calls"]) == (calls + over + 1, over + 1,
                                     {4: 4, 5: 4}.get(calls, 0))
    state = it(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    assert int(state.iteration) == int(lat.state.iteration) + niter
    assert np.abs(np.asarray(state.fields) - after[niter]).max() == 0.0
    assert np.abs(after[niter] - after[0]).max() > 1e-3     # it has moved


def test_band_account_of_the_cells_segment(xla_steps):
    """``drop1024.relax``'s ``iterate(500)``: 499 steps at fuse 4 are 124
    looped calls (62 trips of the two-call body) and 3 steps over (a loop
    ``lax.scan`` unrolls whole: no loop, nothing paired), then the
    globals flavor's step.  A ``<Control>`` series runs every step but
    the last in one loop of single steps, paired too."""
    lat, present, _ = xla_steps
    it = pallas_generic.make_pallas_iterate(lat.model, SHAPE, jnp.float32,
                                            fuse=4, present=present)
    did = it.account(500, False)
    assert (did["kernel_calls"], did["remainder_steps"],
            did["paired_calls"]) == (128, 4, 124)
    did = it.account(500, True)
    assert (did["kernel_calls"], did["remainder_steps"],
            did["paired_calls"]) == (500, 500, 498)
    assert it.account(0, False)["paired_calls"] == 0


# the resident engine cuts its rows into 64-row chunks of its own, each
# with halo rows pulled from its neighbours on-chip: at 128 x 128 two of
# them, which the 64-row case above (one chunk) never compares
SHAPE2 = (128, 128)


def two_chunk_xml() -> str:
    """One drop across the seam of the two chunks (rows 63 | 64), seeded,
    and four small ones on the box's corners: vapour faces vapour across
    the periodic wrap in y (rows 127 | 0, the first chunk's upper halo
    and the last one's lower) and in x."""
    ox, oy, d = np.random.default_rng(SEED + 14).integers(-4, 5, 3)
    return f"""<CLBConfig version="2.0" model="d2q9_kuper" output="output/">
    <Geometry nx="{SHAPE2[1]}" ny="{SHAPE2[0]}">
        <MRT><Box/></MRT>
        <None name="zdrop">
            <Sphere dx="{44 + ox}" nx="{40 + d}" dy="{44 + oy}" ny="{40 + d}"/>
            <Sphere dx="0" nx="24" dy="0" ny="20"/>
            <Sphere dx="0" nx="24" dy="108" ny="20"/>
            <Sphere dx="104" nx="24" dy="0" ny="20"/>
            <Sphere dx="104" nx="24" dy="108" ny="20"/>
        </None>
    </Geometry>
{MODEL_XML}
</CLBConfig>"""


@pytest.mark.parametrize("steps,main", [(23, 22), (24, 22)])
def test_resident_two_chunks_against_the_reference(steps, main, tmp_path,
                                                   monkeypatch):
    """The generic resident engine as ``_build_fast`` gives it, on two
    chunks: an odd call is one resident call of ``steps - 1`` and one
    band step, an even one ``steps - 2`` and two (both branches of
    ``resident_length``; the band remainder runs either way).  Both read
    1.07e-6 against the float32 reference (the single-chunk case 9.5e-7):
    ``TOL32``, for the reason given beside it."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    xml = two_chunk_xml()
    root = ET.fromstring(xml)
    inside = zones.paint(root.find("Geometry"))["zone"] == 1
    assert inside[63].any() and inside[64].any()        # the seam
    assert inside[0].any() and inside[127].any()        # the wrap in y
    assert inside[:, 0].any() and inside[:, 127].any()  # and in x
    lat = run_config_string(xml, get_model("d2q9_kuper"), dtype=jnp.float32,
                            output=str(tmp_path) + "/").lattice
    start = reference.run(root, 0, jnp.float32)
    assert worst(np.asarray(lat.state.fields), start, SHAPE2) < 1e-6
    lat.iterate(steps)
    assert lat._fast_name == "pallas_resident_generic[d2q9_kuper]"
    did = lat._fast.account(steps)
    assert (did["resident_calls"], did["resident_steps"],
            did["remainder_steps"], did["chunk_rows"]) \
        == (1, main, steps - main, 64)
    program = np.asarray(lat.state.fields)
    assert np.isfinite(program).all()
    ref = reference.run(root, steps, jnp.float32)
    assert worst(start, ref, SHAPE2) > 0.1              # it has moved
    assert worst(program, ref, SHAPE2) < TOL32
    assert abs(mass(program) - mass(start)) < 1e-5 * mass(start)


def test_the_drop512_draws_stay_in_the_box():
    """``benchmark/cases/drop512.xml`` under ``traffic/relax.json``: the
    template is ``example/drop_512.xml`` without its handlers, and for
    every draw the drop (diameter 160 to 224 nodes, low corner 128 to
    192) lies between 128 and 416 of the 512 nodes of each axis, which
    the reference's painter would refuse otherwise."""
    from benchmark import casegen
    template = os.path.join(ROOT, "benchmark", "cases", "drop512.xml")
    shipped = ET.parse(os.path.join(ROOT, "example", "drop_512.xml")
                       ).getroot()
    mine = ET.parse(template).getroot()

    def plain(el):
        return (el.tag, dict(el.attrib), [plain(k) for k in el])
    for tag in ("Geometry", "Model"):
        assert plain(mine.find(tag)) == plain(shipped.find(tag))
    assert [el.tag for el in mine] == ["Geometry", "Model"]
    traffic = casegen.load_json("traffic", "relax")
    lo = {r["attr"]: r["int"][0] for r in traffic["seeded"]}
    hi = {r["attr"]: r["int"][1] for r in traffic["seeded"]}
    sphere = mine.find("Geometry/None/Sphere")
    for axis in ("x", "y"):
        d, n = int(sphere.get("d" + axis)), int(sphere.get("n" + axis))
        assert (d, n) == (160, 192)
        assert d + lo["d" + axis] == 128
        assert d + hi["d" + axis] + n + hi["n" + axis] == 416
        assert (n + lo["n" + axis], n + hi["n" + axis]) == (160, 224)
    seen = set()
    for seed in list(range(24)) + [2**31 + 99, 4200000101]:
        root, drawn = casegen.generate(template, traffic, seed)
        seen.add(tuple(sorted(drawn.items())))
        zone = zones.paint(root.find("Geometry"))["zone"]
        rows, cols = zone.nonzero()
        assert 128 <= rows.min() and rows.max() < 416
        assert 128 <= cols.min() and cols.max() < 416
        assert rows.max() - rows.min() + 1 == 192 + drawn["d"]
        assert cols.max() - cols.min() + 1 == 192 + drawn["d"]
    assert len(seen) > 20


@pytest.mark.parametrize("example,tag", [
    ("drop_1024.xml", "pallas_generic[d2q9_kuper,fuse=4]"),
    ("drop_512.xml", "pallas_resident_generic[d2q9_kuper]")])
def test_build_fast_picks_the_engine(example, tag, monkeypatch):
    """What ``tclb run example/<example>`` gets on the chip, from
    ``_build_fast`` alone: nothing is built but the engine."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m = get_model("d2q9_kuper")
    geom = ET.parse(os.path.join(ROOT, "example", example)
                    ).getroot().find("Geometry")
    shape = (int(geom.get("ny")), int(geom.get("nx")))
    painter = Geometry(m, shape)
    painter.load(geom)
    lat = Lattice(m, shape, dtype=jnp.float32)
    lat.set_flags(painter.result())
    assert lat._fast_path() is not None
    assert lat._fast_name == tag and lat._fast_probing
    if "resident" not in tag:
        plan, reach = pallas_generic.action_plan(m, "Iteration", fuse=4)
        assert (len(plan), reach) == (8, pallas_generic.HALO)
        assert lat._fast.impl["by"] == 32 and lat._fast.impl["pad"] == 0
        assert lat._fast.full_globals


@pytest.fixture
def band_lattice(tmp_path, monkeypatch):
    """The case on the band engine through the Lattice's own dispatch:
    the resident engine's budget is set to nothing, and the verdict of
    an earlier probe of this shape is forgotten."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    monkeypatch.setattr(pallas_generic, "_RESIDENT_BUDGET", 0)
    monkeypatch.delitem(pallas_generic._cfg_cache,
                        ("d2q9_kuper", SHAPE), raising=False)
    # and supports() traces its abstract probe call again
    monkeypatch.delitem(pallas_generic._probe_cache,
                        ("d2q9_kuper", SHAPE[1], 4), raising=False)
    yield solver_of(jnp.float32, tmp_path).lattice
    pallas_generic._cfg_cache.pop(("d2q9_kuper", SHAPE), None)


def test_spans_and_annotations(band_lattice, reference32):
    events = []
    before = telemetry.counters()
    telemetry.subscribe(events.append)
    try:
        band_lattice.iterate(STEPS32)
        band_lattice.iterate(STEPS32)
        counters = {k: v - before.get(k, 0)
                    for k, v in telemetry.counters().items()}
    finally:
        telemetry.unsubscribe(events.append)
    tag = "pallas_generic[d2q9_kuper,fuse=4]"
    assert band_lattice._fast_name == tag
    assert sum(e.get("kind") == "engine_fallback" for e in events) == 0
    spans = [e for e in events if e.get("kind") == "span"]
    fused = [e for e in spans if e["name"] == "iterate.fused"]
    probes = [e for e in spans if e["name"] == "engine.probe"]
    assert len(fused) == 2 and len(probes) == 1
    probe = probes[0]
    assert probe["parent"] == fused[0]["id"]
    assert (probe["engine"], probe["result"]) == (tag, tag)
    assert (probe["attempts"], probe["rungs"]) == (1, [32])
    assert 0 < probe["dur_s"] <= fused[0]["dur_s"]
    did = dict(stages_per_step=2, band_rows=32, halo_rows=8, pad_rows=0,
               bands=2, kernel_calls=8, remainder_steps=3, paired_calls=4,
               aux_planes=1)
    # the first call's account lies on the probe that made the calls
    for span in (probe, fused[1]):
        assert {k: span[k] for k in did} == did
    assert fused[1]["iters"] == STEPS32 and fused[1]["engine"] == tag
    assert counters["engine.kernel_calls"] == 16
    assert counters["engine.paired_calls"] == 8
    assert counters["engine.probe_attempts"] == 1
    assert not [e for e in spans if e["name"] == "iterate.globals_step"]
    # supports()'s abstract trace of the engine issues no call
    top = [e for e in spans if e["name"] == "iterate"]
    assert len(top) == 2 and not any("kernel_calls" in e for e in top)
    # and the engine the spans describe is the one the reference holds
    again = reference.run(ET.fromstring(case_xml()), 2 * STEPS32,
                          jnp.float32)
    assert worst(np.asarray(band_lattice.state.fields), again) < 2 * TOL32


def test_nothing_is_recorded_with_telemetry_off(band_lattice):
    assert not telemetry.enabled()
    before = telemetry.counters()
    band_lattice.iterate(STEPS32)
    assert telemetry.counters() == before
    assert band_lattice._fast_name == "pallas_generic[d2q9_kuper,fuse=4]"
    assert not band_lattice._fast_probing
