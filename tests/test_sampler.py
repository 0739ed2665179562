"""``<Sample>``: per-step point probes through the fused engines.

A sampler used to send the whole run to an XLA scan
(``core/lattice.py:make_sampled_iterate``) in front of dispatch.  Now
``Lattice._build_fast`` knows of it: the tuned 2D band and the generic
band (2D) and slab (3D) engines run their one-step flavour, which
returns the stored planes at the probes' nodes after every step (the
taps, ``lax.scan``'s ys), ``taps_program`` turns them into the
quantities' columns on the device, and the rows stay there until the
flush.  These tests force the dispatch on the CPU (interpret mode) and
hold the CSV of ``tclb run`` against the XLA scan's, the plain reference
(``benchmark/reference/probes.py``) and what the run says of itself.
"""

import io
import os
import xml.etree.ElementTree as ET

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu import telemetry
from tclb_tpu.control import run_config_string
from tclb_tpu.core.lattice import Lattice, make_sampled_iterate
from tclb_tpu.models import get_model
from tclb_tpu.ops import lbm, pallas_d2q9, pallas_generic
from tclb_tpu.utils.sampler import Sampler

from test_fastpath import _karman_lattice, _spans, seen  # noqa: F401

KARMAN = """<CLBConfig version="2.0" model="d2q9" output="{out}/">
    <Geometry nx="128" ny="{ny}">
        <MRT><Box/></MRT>
        <WVelocity name="Inlet"><Inlet/></WVelocity>
        <EPressure name="Outlet"><Outlet/></EPressure>
        <Inlet nx="1" dx="5"><Box/></Inlet>
        <Outlet nx="1" dx="-5"><Box/></Outlet>
        <Wall mask="ALL">
            <Channel/>
            <Wedge dx="16" nx="8" dy="{mid}" ny="8" direction="LowerRight"/>
            <Wedge dx="16" nx="8" dy="{low}" ny="8" direction="UpperRight"/>
            <Wedge dx="24" nx="8" dy="{mid}" ny="8" direction="LowerLeft"/>
            <Wedge dx="24" nx="8" dy="{low}" ny="8" direction="UpperLeft"/>
        </Wall>
    </Geometry>
    <Model><Params Velocity="0.03" nu="0.02"/></Model>
    <Sample Iterations="{flush}" what="U,Rho">
        <Point dx="12" dy="{mid}"/>
        <Point dx="20" dy="{mid}"/>
        <Point dx="0" dy="3"/>
        <Point dx="127" dy="{last}"/>
        <Point dx="64" dy="0"/>
        <Point dx="40" dy="{last}"/>
    </Sample>
    <Log Iterations="{log}"/>
    <Solve Iterations="{solve}"/>
</CLBConfig>"""

KUPER = """<CLBConfig version="2.0" model="d2q9_kuper" output="{out}/">
    <Geometry nx="128" ny="{ny}">
        <MRT><Box/></MRT>
        <None name="zdrop"><Sphere dx="40" nx="48" dy="8" ny="48"/></None>
    </Geometry>
    <Model>
        <Params omega="1"/>
        <Params Density="3.2600529440452366"
                Density-zdrop="0.014500641645077492"
                Temperature="0.56" FAcc="1" Magic="0.01"
                MagicA="-0.152" MagicF="-0.6666666666666"/>
    </Model>
    <Sample Iterations="{flush}" what="{what}">
        <Point dx="64" dy="32"/>
        <Point dx="40" dy="32"/>
        <Point dx="0" dy="0"/>
        <Point dx="127" dy="{last}"/>
    </Sample>
    <Log Iterations="{log}"/>
    <Solve Iterations="{solve}"/>
</CLBConfig>"""

KUPER3D = """<CLBConfig version="2.0" model="d3q19_kuper" output="{out}/">
    <Geometry nx="128" ny="16" nz="8">
        <MRT><Box/></MRT>
        <None name="zdrop">
            <Sphere dx="48" nx="32" dy="4" ny="8" dz="2" nz="4"/>
        </None>
    </Geometry>
    <Model>
        <Params omega="1"/>
        <Params Density="0.014500641645077492"
                Density-zdrop="3.2600529440452366"
                Temperature="0.56" FAcc="1" Magic="0.01"
                MagicA="-0.152" MagicF="-0.3333333333333"/>
    </Model>
    <Sample Iterations="{flush}" what="U,Rho">
        <Point dx="64" dy="8" dz="4"/>
        <Point dx="48" dy="8" dz="4"/>
        <Point dx="0" dy="0" dz="0"/>
        <Point dx="127" dy="15" dz="7"/>
    </Sample>
    <Log Iterations="{log}"/>
    <Solve Iterations="{solve}"/>
</CLBConfig>"""

# case -> (template, model, its fields, the engine of the sampled run,
#          the engine of the last step of every iterate)
CASES = {
    "tuned_band": (KARMAN, "d2q9", dict(ny=64, mid=32, low=24, last=63),
                   "pallas_2d[d2q9,fuse=1]", "pallas_generic[d2q9,fuse=1]"),
    # 100 rows: the tuned band stands on 20 ghost rows (bands of 40), the
    # generic band would on 28, so the last step stays XLA's
    "tuned_band_ghost_rows": (
        KARMAN, "d2q9", dict(ny=100, mid=48, low=40, last=99),
        "pallas_2d[d2q9,fuse=1]", "xla"),
    "generic_band": (KUPER, "d2q9_kuper", dict(ny=64, last=63, what="U,Rho"),
                     "pallas_generic[d2q9_kuper,fuse=1]", None),
    # 72 rows: bands of 24 rows do not divide by 32; the band engine pads
    "generic_band_ghost_rows": (
        KUPER, "d2q9_kuper", dict(ny=72, last=71, what="U,Rho,P"),
        "pallas_generic[d2q9_kuper,fuse=1]", None),
    "generic_slab_3d": (KUPER3D, "d3q19_kuper", {},
                        "pallas_generic[d3q19_kuper,fuse=1]", None),
}


def _run(case, out, mode, monkeypatch, flush=20, log=10, solve=40,
         xml=None):
    """``tclb run`` of the case with the fast path ``mode``; the solver,
    the CSV's header and its rows."""
    template, model, fields, _, _ = CASES[case]
    monkeypatch.setenv("TCLB_FASTPATH", mode)
    text = (xml or template).format(out=out, flush=flush, log=log,
                                    solve=solve, **fields)
    solver = run_config_string(text, get_model(model), dtype=jnp.float32,
                               output=f"{out}/", conf_name="case")
    return (solver,) + _csv(os.path.join(out, "case_Sample.csv"))


def _csv(path):
    from benchmark.probe_check import read_rows
    return read_rows(path)


@pytest.mark.parametrize("case", list(CASES))
def test_sampled_run_keeps_its_fused_engine(case, tmp_path, monkeypatch,
                                            seen):
    """The CSV of a ``<Sample>`` run on a fused engine against the XLA
    scan's: one row a step with consecutive iteration numbers across
    segments (the last row of every ``iterate`` is the tail engine's or
    ``call_g``'s step) and across flushes (every 20 steps beside a log
    every 10), the values the scan's to the six digits the file holds;
    points on a wall, on the first and the last row, in a ghost-padded
    band.  And what the run says: ``engine_selected`` names the fused
    tag with the K that runs, ``iterate`` carries it, ``iterate.fused``
    the account and the sample fields."""
    _, _, _, engine, tail = CASES[case]
    _, hx, rx = _run(case, tmp_path / "x", "0", monkeypatch)
    assert [e["engine"] for e in seen
            if e["kind"] == "engine_selected"] == ["xla"]
    del seen[:]
    solver, hf, rf = _run(case, tmp_path / "f", "force", monkeypatch)
    assert hf == hx and hf[0] == "Iteration" and len(hf) > 8
    assert rf.shape == rx.shape == (40, len(hf))
    # (the kuper models' Init action streams once: their first step is
    # iteration 2, on either engine)
    first = int(rx[0, 0])
    assert first in (1, 2)
    assert np.array_equal(rf[:, 0], np.arange(first, first + 40))
    assert np.array_equal(rx[:, 0], rf[:, 0])
    # the two ENGINES agree to the populations' rounding (as
    # test_engine_dispatch_matches_xla holds them), and "%g" keeps six
    # digits; the sampler's own arithmetic is held to a few ulp in
    # test_rows_are_get_quantity_at_the_node
    np.testing.assert_allclose(rf, rx, rtol=1.1e-5, atol=5e-7)
    assert np.abs(rf[:, 1:]).max() > 0 and np.isfinite(rf).all()
    selected = [e["engine"] for e in seen if e["kind"] == "engine_selected"]
    # attach, then cbSample.finish: the chain without the sampler
    assert selected[0] == engine and len(selected) == 1
    assert {e["engine"] for e in _spans(seen, "iterate")} == {engine}
    fused = _spans(seen, "iterate.fused")
    npts = len(hf) // 4 if "P" not in ",".join(hf) else 4
    probed = _spans(seen, "engine.probe")
    for span in fused[1:] if probed else fused:
        assert span["engine"] == engine
        assert span["sample_points"] == npts
        assert span["sample_rows"] == span["iters"] == span["kernel_calls"]
        planes = solver.model.n_storage
        assert span["sample_bytes"] == 4 * planes * npts * span["iters"]
    steps = _spans(seen, "iterate.globals_step")
    if tail is None:
        assert not steps and {s["iters"] for s in fused} == {10}
    else:
        assert {s["iters"] for s in fused} == {9}
        assert {s["engine"] for s in steps} == {tail}
        # (the tail's first call is probed: its span holds that one's)
        assert all(s["sample_rows"] == 1 for s in steps[1:])
    assert not [e for e in seen if e["kind"] == "engine_fallback"]
    # a flush: one copy and one write of its block, both under the
    # handler (cbSample.finish finds no row left and does neither)
    d2h, out = _spans(seen, "sample.d2h"), _spans(seen, "output.sample")
    assert [s["rows"] for s in out] == [20, 20]
    assert len(d2h) == len(out) and all(s["bytes"] > 0 for s in d2h + out)
    by_id = {e["id"]: e["name"] for e in seen if e["kind"] == "span"}
    assert {by_id[s["parent"]] for s in d2h + out} == {"handler"}
    # after the run the sampler is gone and the engine invalidated
    assert solver.lattice.sampler is None
    assert not solver.lattice._fast_tried


@pytest.mark.parametrize("family", ["tuned_band", "tuned_band_ghost_rows",
                                    "generic_band", "generic_slab_3d"])
def test_the_taps_are_reads(family):
    """The state after a sampled ``iterate(n)`` is bit-equal to the state
    after the same engine's unsampled ``iterate(n)`` at the same K, the
    Globals too; the taps are the stored planes at the points after
    every step."""
    if family.startswith("tuned"):
        ny = 64 if family == "tuned_band" else 100
        m, lat = _karman_lattice(ny)
        shape = (ny, 128)
        make = pallas_d2q9.make_pallas_iterate
        pts = np.array([[ny // 2, 5], [0, 3], [ny - 1, 127], [ny // 3, 20]])
    else:
        name, shape = (("d2q9_kuper", (64, 128)) if family == "generic_band"
                       else ("d3q19_kuper", (8, 16, 128)))
        m = get_model(name)
        lat = Lattice(m, shape, dtype=jnp.float32)
        flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
        flags[..., 4:12, 40:80] |= 1 << m.zone_shift
        lat.set_flags(flags)
        lat.init()
        make = pallas_generic.make_pallas_iterate
        pts = np.array([[0] * len(shape), [s - 1 for s in shape],
                        [s // 2 for s in shape]])
    present = lbm.present_types(m, np.asarray(lat.state.flags))
    plain = make(m, shape, jnp.float32, fuse=1, present=present)
    sampled = make(m, shape, jnp.float32, fuse=1, present=present,
                   points=pts)
    assert sampled.samples and not plain.samples
    n = 5
    copy = jax.tree.map(jnp.copy, lat.state)
    want = plain(copy, lat.params, n)
    # step by step on the plain engine: what each step left at the points
    state, rows = jax.tree.map(jnp.copy, lat.state), []
    for _ in range(n):
        state = plain(state, lat.params, 1)
        rows.append(np.asarray(state.fields)[
            (slice(None),) + tuple(pts[:, k] for k in range(pts.shape[1]))])
    got, taps = sampled(jax.tree.map(jnp.copy, lat.state), lat.params, n)
    assert taps.shape == (n, m.n_storage, len(pts))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(taps), np.stack(rows))
    did = sampled.account(n)
    assert did["kernel_calls"] == n and did["paired_calls"] == 4


def test_rows_are_get_quantity_at_the_node(monkeypatch):
    """A sample is the arithmetic of ``get_quantity`` at that node:
    ``taps_program`` on the gathered nodes against the whole plane,
    within a few ulp (XLA fuses the two programs differently)."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m, lat = _karman_lattice(64)
    pts = np.array([[32, 5], [0, 3], [63, 127], [22, 20], [10, 0]])

    class Keep:
        points, quantities = pts, ["U", "Rho"]
        rows = []

        def append(self, its, samples):
            self.rows.append((np.asarray(its), np.asarray(samples)))

        def write(self):
            pass

    lat.attach_sampler(Keep())
    lat.iterate(6)
    lat.iterate(6)
    assert lat._fast_name == "pallas_2d[d2q9,fuse=1]"
    (i0, s0), (i1, s1) = Keep.rows
    assert list(i0) + list(i1) == list(range(1, 13))
    assert s1.shape == (6, 5, 4)
    u = np.asarray(lat.get_quantity("U"))[:, pts[:, 0], pts[:, 1]].T
    rho = np.asarray(lat.get_quantity("Rho"))[pts[:, 0], pts[:, 1]]
    np.testing.assert_allclose(s1[-1, :, :3], u, rtol=0, atol=2e-9)
    np.testing.assert_allclose(s1[-1, :, 3], rho, rtol=4e-7)
    # and the XLA scan's rows over the same steps, a few ulp
    monkeypatch.setenv("TCLB_FASTPATH", "0")
    _, lat_x = _karman_lattice(64)
    scan = jax.jit(make_sampled_iterate(m, pts, ["U", "Rho"]),
                   static_argnames=("niter",))
    _, (sx, ix) = scan(lat_x.state, lat_x.params, 12)
    assert list(np.asarray(ix)) == list(range(1, 13))
    np.testing.assert_allclose(np.concatenate([s0, s1]), np.asarray(sx),
                               rtol=1e-6, atol=3e-9)


# the written tolerance of the program's rows against the plain
# reference over 60 steps at 64 x 128 (float32 both; the largest reading
# on this host 4.8e-7): a hundred times the rounding, a thousand times
# under a wrong node or a missed step
REFERENCE_TOLERANCE = 2e-5


@pytest.mark.parametrize("case", ["tuned_band", "tuned_band_ghost_rows"])
def test_rows_against_the_plain_reference(case, tmp_path, monkeypatch):
    """``tclb run``'s CSV on the fused engine against
    ``benchmark/reference/probes.py``, which steps the plain d2q9
    reference from the same case file and imports nothing of the
    program; and the guard of the benchmark's cell on the same rows."""
    from benchmark import probe_check
    from benchmark.reference import probes
    template, _, fields, _, _ = CASES[case]
    _, header, rows = _run(case, tmp_path, "force", monkeypatch, flush=30,
                           log=30, solve=60)
    root = ET.fromstring(template.format(out=tmp_path, flush=30, log=30,
                                         solve=60, **fields))
    assert header == ["Iteration"] + probes.columns(root)
    assert probes.points(root)[0] == (fields["mid"], 12)
    ref = probes.run(root, 60)
    assert ref.shape == (60, 24)
    worst = np.abs(rows[:, 1:] - ref).max()
    assert 0 < worst < REFERENCE_TOLERANCE
    assert probe_check.largest_difference(
        root, header, rows, 60, jnp.float32) == pytest.approx(worst)
    # a missed step, a doubled row, another node: all far outside
    assert np.abs(rows[1:, 1:] - ref[:-1]).max() > 50 * REFERENCE_TOLERANCE
    with pytest.raises(ValueError, match="rows for 60 steps"):
        probe_check.largest_difference(root, header, rows[:-1], 60,
                                       jnp.float32)
    with pytest.raises(ValueError, match="in order"):
        probe_check.largest_difference(
            root, header, np.concatenate([rows[:30], rows[29:59]]), 60,
            jnp.float32)
    # the control: bfloat16 storage moves the probes out of the limit
    low = probes.run(root, 60, storage=jnp.bfloat16)
    assert np.abs(low - ref).max() > 50 * REFERENCE_TOLERANCE


def test_what_no_engine_takes_says_xla(tmp_path, monkeypatch, seen):
    """Chosen before the first step and named by ``engine_selected``: a
    quantity that reads its neighbours (d2q9_kuper's ``F``: the force
    from ``phi`` at the nine nodes round it) keeps the XLA scan, and the
    rows are the old ones; so does a mesh."""
    _, hx, rx = _run("generic_band", tmp_path / "x", "0", monkeypatch,
                     xml=KUPER.replace("{what}", "F,Rho"))
    del seen[:]
    solver, hf, rf = _run("generic_band", tmp_path / "f", "force",
                          monkeypatch, xml=KUPER.replace("{what}", "F,Rho"))
    assert hf == hx and np.array_equal(rf, rx)
    assert [e["engine"] for e in seen
            if e["kind"] == "engine_selected"] == ["xla"]
    assert {e["engine"] for e in _spans(seen, "iterate")} == {"xla"}
    fused = _spans(seen, "iterate.fused")
    assert {e["engine"] for e in fused} == {"xla"}
    assert all(e["sample_rows"] == e["iters"] == 10 for e in fused)
    assert not _spans(seen, "iterate.globals_step")
    # the same lattice without the neighbour read takes the band engine
    m = get_model("d2q9_kuper")
    lat = solver.lattice
    lat.attach_sampler(Sampler(m, ["F"], np.array([[3, 3]]), "unused"))
    assert not lat._samples_on_engine() and lat._build_fast() == []
    lat.attach_sampler(Sampler(m, ["U"], np.array([[3, 3]]), "unused"))
    assert lat._samples_on_engine()
    assert [c.tag for c in lat._build_fast()][0] \
        == "pallas_generic[d2q9_kuper,fuse=1]"
    # under a <Control> series the run keeps the scan too, and the
    # sampled flavour says that it does not take one
    engine = lat._build_fast()[0].build()
    lat.set_setting_series("Density", np.linspace(1.0, 1.1, 4))
    assert not lat._samples_on_engine() and lat._build_fast() == []
    with pytest.raises(NotImplementedError, match="series"):
        engine(lat.state, lat.params, 2)


def test_sampler_on_a_mesh_keeps_the_xla_scan(monkeypatch):
    from jax.sharding import Mesh
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m = get_model("d2q9")
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("y", "x"))
    lat = Lattice(m, (64, 128), dtype=jnp.float32, mesh=mesh,
                  settings={"nu": 0.05})
    lat.set_flags(np.full((64, 128), m.flag_for("MRT"), dtype=np.uint16))
    lat.init()
    assert lat._build_fast() != []
    lat.attach_sampler(Sampler(m, ["Rho"], np.array([[3, 3]]), "unused"))
    assert lat._build_fast() == []


def test_dispatch_with_and_without_the_sampler(monkeypatch, seen):
    """The sampler's presence is the only input: attached, the chain is
    the one-step flavour alone (no resident engine: its call is 8 steps
    on-chip), its tag the K that runs; detached (``cbSample.finish``),
    the next ``iterate`` runs the unsampled chain again."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m, lat = _karman_lattice(64)
    assert [c.tag for c in lat._build_fast()] == [
        "pallas_resident[d2q9,fuse=8]", "pallas_2d[d2q9,fuse=2]"]
    rows = []

    class Keep:
        points, quantities = np.array([[32, 5], [1, 1]]), ["Rho"]

        def append(self, its, samples):
            rows.append(np.asarray(its))

        def write(self):
            pass

    lat.attach_sampler(Keep())
    assert [(c.tag, c.probe) for c in lat._build_fast()] == [
        ("pallas_2d[d2q9,fuse=1]", False)]
    lat.iterate(4)
    assert lat._fast_name == "pallas_2d[d2q9,fuse=1]"
    assert lat._fast.samples and lat._tail.samples
    assert list(rows[0]) == [1, 2, 3, 4]
    lat.detach_sampler()
    lat.iterate(4)
    assert lat._fast_name == "pallas_resident[d2q9,fuse=8]"
    assert not lat._fast.samples and len(rows) == 1
    assert [e["engine"] for e in seen if e["kind"] == "engine_selected"] \
        == ["pallas_2d[d2q9,fuse=1]", "pallas_resident[d2q9,fuse=8]"]
    its = _spans(seen, "iterate")
    assert [(e["engine"], e["fuse"]) for e in its] == [
        ("pallas_2d[d2q9,fuse=1]", 1), ("pallas_resident[d2q9,fuse=8]", 8)]
    assert "sample_rows" in _spans(seen, "iterate.fused")[0]
    assert "sample_rows" not in _spans(seen, "iterate.fused")[1]
    # one step alone has no fused part: the XLA scan samples it
    lat.attach_sampler(Keep())
    lat.iterate(1)
    assert list(rows[-1]) == [9]


def test_generic_sampled_verdict_is_not_remembered(monkeypatch):
    """A sampled run's one step a call is not what a later lattice of
    the shape should run: the probe's verdict stays out of the cache."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m = get_model("d2q9_kuper")
    shape = (1024, 1024)        # too large for the resident engine
    pallas_generic._cfg_cache.pop((m.name, shape), None)
    lat = Lattice(m, shape, dtype=jnp.float32)
    lat.set_flags(np.full(shape, m.flag_for("MRT"), dtype=np.uint16))
    lat.init()
    fuse = pallas_generic.choose_fuse(m)
    assert fuse > 1
    assert lat._build_fast()[0].verdict == (fuse, None)
    lat.attach_sampler(Sampler(m, ["Rho"], np.array([[3, 3]]), "unused"))
    chain = lat._build_fast()
    assert chain[0].tag == "pallas_generic[d2q9_kuper,fuse=1]"
    assert all(c.verdict is None and "fuse=1" in c.tag for c in chain)
    # a shape that has proved itself keeps its band cap, at one step
    pallas_generic.set_build_cfg(m, shape, fuse, 16)
    try:
        assert [c.tag for c in lat._build_fast()] == [
            "pallas_generic[d2q9_kuper,fuse=1]"]
    finally:
        pallas_generic._cfg_cache.pop((m.name, shape), None)


def _old_flush_text(columns, its, samples) -> str:
    """``Sampler.flush`` as it was before PR 46: a Python loop over the
    rows, ``f"{v:g}"`` a value."""
    f = io.StringIO()
    f.write(",".join(["Iteration"] + columns) + "\n")
    flat = samples.reshape(samples.shape[0], -1)
    for it, row in zip(its, flat):
        f.write(str(it) + "," + ",".join(f"{v:g}" for v in row) + "\n")
    return f.getvalue()


def test_flush_writes_the_same_text(tmp_path):
    """One formatted write of the block, byte for byte the text of the
    old row loop: header, whole-number iterations, ``%g`` values
    (exponents, negative zero, infinities, NaN, denormals and all)."""
    m = get_model("d2q9")
    rng = np.random.default_rng(7)
    samples = (rng.standard_normal((50, 3, 4))
               * 10.0 ** rng.integers(-12, 9, (50, 3, 4))).astype(np.float32)
    samples[0, 0] = [0.0, -0.0, np.inf, -np.inf]
    samples[1, 0] = [np.nan, 1e-45, 123456.5, 1234567.0]
    samples[2, 0] = [0.1, 1.0, 100000.0, 999999.5]
    its = np.arange(999_990, 1_000_040, dtype=np.int32)
    smp = Sampler(m, ["U", "Rho"], np.zeros((3, 2), int),
                  str(tmp_path / "out" / "s.csv"))
    smp.append(jnp.asarray(its[:20]), jnp.asarray(samples[:20]))
    smp.append(its[20:], samples[20:])
    smp.flush()
    with open(smp.path) as f:
        assert f.read() == _old_flush_text(smp.columns, its, samples)
    # a second flush appends, without the header; an empty one is nothing
    smp.append(its[:2] + 50, samples[:2])
    smp.flush()
    smp.flush()
    with open(smp.path) as f:
        text = f.read()
    assert text == _old_flush_text(smp.columns, its, samples) \
        + _old_flush_text(smp.columns, its[:2] + 50,
                          samples[:2]).split("\n", 1)[1]


def test_resumed_run_appends(tmp_path, monkeypatch):
    """``restorable_state`` flushes and remembers that the header is
    written; a handler restored from it appends to the CSV."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    template, model, fields, _, _ = CASES["tuned_band"]
    xml = template.format(out=tmp_path, flush=20, log=10, solve=20,
                          **fields)
    solver = run_config_string(xml, get_model(model), dtype=jnp.float32,
                               output=f"{tmp_path}/", conf_name="case")
    path = os.path.join(tmp_path, "case_Sample.csv")
    _, first = _csv(path)
    assert first.shape[0] == 20
    # the same handler class on the same solver, as a resume replays it
    from tclb_tpu.control.handlers import cbSample
    node = ET.fromstring(xml).find("Sample")
    h = cbSample(node, solver)
    h.init()
    assert solver.lattice.sampler is h.sampler
    solver.lattice.iterate(5)
    saved = dict(h.restorable_state())           # flushes: a fresh file
    assert saved == {"wrote_header": True}
    h2 = cbSample(node, solver)
    h2.init()
    h2.restore_state(saved)
    solver.lattice.iterate(5)
    h2.finish()
    header, rows = _csv(path)
    assert np.array_equal(rows[:, 0], np.arange(21, 31))
    with open(path) as f:
        assert f.read().count("Iteration") == 1


def test_report_prints_the_flush(tmp_path, monkeypatch):
    """``telemetry report``'s segments table has the sampler's spans and
    the counters say what was sampled and flushed."""
    from tclb_tpu.telemetry import report
    events = tmp_path / "events.jsonl"
    telemetry.enable(str(events))
    try:
        _run("tuned_band", tmp_path / "run", "force", monkeypatch)
        said = telemetry.counters()
    finally:
        telemetry.disable()
    assert said["sampler.rows"] == 40
    assert said["output.sample.flushes"] == 2
    assert said["engine.kernel_calls"] == 40    # every step one call
    evts = report.load(str(events))
    summary = report.summarize(evts)
    groups = summary["segments"]
    # the copy in the segments that flush, the write in the one after
    flushing = [g for shape, g in groups.items() if "cbSample" in shape]
    assert flushing and all("sample.d2h" in g["self_ms"] for g in flushing)
    assert any("output.sample" in g["self_ms"] for g in groups.values())
    text = report.format_text(summary)
    assert "sample.d2h" in text and "output.sample" in text
