"""The fused Pallas kernel as the ENGINE, not a bench artifact.

``Lattice.iterate`` auto-selects the fused fast path (hybrid: Pallas for
niter-1 steps + one step refreshing globals, on the generic Pallas
engine's in-kernel-globals flavour or on XLA) the way the reference's
tuned kernel IS its engine (reference src/Lattice.cu.Rt:414-457 →
src/LatticeContainer.inc.cpp.Rt:247-266).  These tests force the dispatch on
CPU (interpret mode) and pin the engine entry point — fields AND globals —
against the pure-XLA path on a boundary-rich case.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu.core.lattice import Lattice
from tclb_tpu.models import get_model
from tclb_tpu.ops import lbm, pallas_d2q9, pallas_d3q, pallas_generic


@pytest.fixture
def seen():
    """The event documents of a test, through a subscriber of its own."""
    from tclb_tpu import telemetry
    docs = []
    telemetry.subscribe(docs.append)
    yield docs
    telemetry.unsubscribe(docs.append)


def _spans(docs, name):
    return [e for e in docs if e["kind"] == "span" and e["name"] == name]


def _says_tail(seen, lat, fused, tail, calls, fused_probed=True):
    """What a run on a hybrid engine with the Pallas tail says of
    itself after ``calls`` calls of ``iterate``; ``fused_probed``:
    whether the fused engine's first call is probed too (the sharded
    tuned engine's is not)."""
    assert (lat._fast_name, lat._tail_name) == (fused, tail)
    steps = _spans(seen, "iterate.globals_step")
    assert [e["engine"] for e in steps] == [tail] * calls
    # the tail's account lands on its own span (the first call's on the
    # probe's), never on the fused one: the rooflines read that
    assert all("stages_per_step" in e for e in steps[1:])
    assert not any("stages_per_step" in e
                   for e in _spans(seen, "iterate.fused"))
    assert [e["engine"] for e in _spans(seen, "engine.probe")] \
        == [fused] * fused_probed + [tail]
    assert not [e for e in seen if e["kind"] == "engine_fallback"]


def _karman_lattice(ny=64, nx=128, wedge=False):
    m = get_model("d2q9")
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.05, "Velocity": 0.03})
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    flags[0, :] = m.flag_for("Wall")
    flags[-1, :] = m.flag_for("Wall")
    flags[ny // 3:2 * ny // 3, nx // 8:nx // 4] = m.flag_for("Wall")
    if wedge:
        # the box cut to a wedge, its slope facing the inlet
        rows, cols = np.mgrid[0:ny, 0:nx]
        flags[(rows - ny // 3 < nx // 4 - cols) & (rows >= ny // 3)
              & (cols >= nx // 8) & (rows < 2 * ny // 3)] = m.flag_for("MRT")
    # objective columns: globals (fluxes/pressure loss) accumulate here
    flags[1:-1, 2] = m.flag_for("MRT", "Inlet")
    flags[1:-1, -3] = m.flag_for("MRT", "Outlet")
    lat.set_flags(flags)
    lat.init()
    return m, lat


def test_supports_only_implemented_models():
    """supports() must not claim models whose physics the kernel does not
    implement (round-2 VERDICT Weak #1: a false claim crashed on build
    and would have been silently wrong physics if it built).  d2q9_new is
    now genuinely implemented — its kernel branch shares
    models.d2q9_new.collision_core with the XLA path and is pinned by
    tests/test_pallas.py::test_pallas_family_models — while multi-lattice
    models stay rejected."""
    assert pallas_d2q9.supports(get_model("d2q9_new"), (64, 128),
                                jnp.float32)
    for name in ("d2q9_heat", "d2q9_hb", "d2q9_kuper", "d2q9_adj"):
        assert not pallas_d2q9.supports(get_model(name), (64, 128),
                                        jnp.float32), name


@pytest.mark.parametrize("ny,niter,wedge", [
    (64, 21, False),
    # the awkward part of the published 1024 x 100: two chunks of 50
    # rows (no multiple of 8), the periodic pull built by concatenation
    (100, 17, True)], ids=["64", "100"])
def test_engine_dispatch_matches_xla(monkeypatch, seen, ny, niter, wedge):
    """Solver-path == pallas-path on the boundary-rich Kármán case:
    the engine entry point (Lattice.iterate) with the fast path forced
    must reproduce the XLA engine's fields AND globals.  The hybrid's
    last step, which reduces them, runs on the generic Pallas engine's
    one-step flavour; at 100 rows, where that engine's band would stand
    on 28 ghost rows, it stays the XLA step."""
    monkeypatch.setenv("TCLB_FASTPATH", "0")   # pin pure XLA (even on TPU)
    _, lat_x = _karman_lattice(ny, wedge=wedge)
    lat_x.iterate(niter)

    monkeypatch.setenv("TCLB_FASTPATH", "force")
    _, lat_f = _karman_lattice(ny, wedge=wedge)
    lat_f.iterate(niter)
    # small domains select the VMEM-resident deep-fusion engine
    assert lat_f._fast_name == "pallas_resident[d2q9,fuse=8]"

    np.testing.assert_allclose(np.asarray(lat_f.state.fields),
                               np.asarray(lat_x.state.fields),
                               rtol=2e-5, atol=2e-6)
    gx, gf = lat_x.get_globals(), lat_f.get_globals()
    assert gx.keys() == gf.keys()
    for k in gx:
        np.testing.assert_allclose(gf[k], gx[k], rtol=1e-4, atol=1e-6,
                                   err_msg=f"global {k}")
    # the hybrid's trailing step produced REAL (nonzero) globals
    assert any(abs(v) > 0 for v in gf.values())
    assert int(lat_f.state.iteration) == niter
    if ny == 64:
        _says_tail(seen, lat_f, "pallas_resident[d2q9,fuse=8]",
                   "pallas_generic[d2q9,fuse=1]", 1)
        return
    assert pallas_generic.make_pallas_iterate(
        lat_f.model, (ny, 128), fuse=1).account(1, False)["pad_rows"] == 28
    assert lat_f._tail is None and lat_f._tail_name is None
    assert [e["engine"] for e in _spans(seen, "iterate.globals_step")] \
        == ["xla"]
    assert [e["engine"] for e in _spans(seen, "engine.probe")] \
        == ["pallas_resident[d2q9,fuse=8]"]
    assert not [e for e in seen if e["kind"] == "engine_fallback"]


@pytest.fixture(scope="module")
def resident_16():
    """The Kármán case at 16 rows on the resident engine, and its state
    after 0 to 5 resident calls issued one at a time (``iterate(8)``: a
    loop of one trip, which is no loop), each then advanced 7 steps by
    the band kernel (``iterate(7)``)."""
    m, lat = _karman_lattice(16)
    present = lbm.present_types(m, np.asarray(lat.state.flags))
    it = pallas_d2q9.make_resident_iterate(m, (16, 128), jnp.float32,
                                           interpret=True, present=present)
    one_by_one, state = {}, jax.tree.map(jnp.copy, lat.state)
    for calls in range(6):
        if calls:
            state = it(state, lat.params, 8)
        one_by_one[calls, 0] = np.asarray(state.fields)
        one_by_one[calls, 7] = np.asarray(
            it(jax.tree.map(jnp.copy, state), lat.params, 7).fields)
    return lat, it, one_by_one


@pytest.mark.parametrize("over", [0, 7], ids=["even", "over7"])
@pytest.mark.parametrize("calls", range(6))
def test_resident_loop_pairs_its_calls_bit_for_bit(calls, over, resident_16):
    """The resident engine's loop runs two kernel calls a body and an odd
    call after the loop (``_PAIR``): the same calls in the same order as
    one at a time, so the state is equal to the last bit, with and
    without the band kernel's steps after them; ``account`` says which
    calls a two-call body issued (a loop of four trips or more, less its
    odd one; the band kernel's loop is single)."""
    lat, it, one_by_one = resident_16
    niter = 8 * calls + over
    did = it.account(niter)
    assert (did["kernel_calls"], did["resident_calls"],
            did["remainder_steps"], did["paired_calls"]) \
        == (calls + over, calls, over, {4: 4, 5: 4}.get(calls, 0))
    state = it(jax.tree.map(jnp.copy, lat.state), lat.params, niter)
    assert int(state.iteration) == int(lat.state.iteration) + niter
    assert np.abs(np.asarray(state.fields)
                  - one_by_one[calls, over]).max() == 0.0
    if niter:
        assert np.abs(one_by_one[calls, over]
                      - one_by_one[0, 0]).max() > 1e-4      # it has moved


def test_engine_dispatch_3d(monkeypatch):
    """3D dispatch: d3q27_BGK routes through the z-slab kernel."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m = get_model("d3q27_BGK")
    shape = (8, 16, 64)

    def build():
        lat = Lattice(m, shape, dtype=jnp.float32,
                      settings={"omega": 1.0, "GravitationX": 1e-5})
        flags = np.full(shape, m.flag_for("BGK"), dtype=np.uint16)
        flags[:, 0, :] = m.flag_for("Wall")
        flags[:, -1, :] = m.flag_for("Wall")
        lat.set_flags(flags)
        lat.init()
        return lat

    lat_f = build()
    lat_f.iterate(5)
    # fuse tag comes from the shared planner, not a pinned constant —
    # a VMEM-budget retune must not break dispatch tests
    k3 = pallas_d3q.choose_fuse(m, shape)
    assert lat_f._fast_name == f"pallas_d3q[d3q27_BGK,fuse={k3}]"

    monkeypatch.setenv("TCLB_FASTPATH", "0")
    lat_x = build()
    lat_x.iterate(5)
    assert lat_x._fast_name is None
    np.testing.assert_allclose(np.asarray(lat_f.state.fields),
                               np.asarray(lat_x.state.fields),
                               rtol=2e-5, atol=2e-6)


def test_generic_resident_dispatch_matches_xla(monkeypatch):
    """Models outside the tuned d2q9 family route through the generic
    VMEM-resident engine on small aligned domains (the engine existed
    since round 5 but nothing dispatched to it): fields and globals must
    match the XLA path, and the engine name must pin the resident flavor
    (nx % 128 == 0 is its alignment gate — the band-engine tests at
    nx=64 stay on pallas_generic)."""
    niter = 9
    m = get_model("d2q9_heat")

    def build():
        lat = Lattice(m, (16, 128), dtype=jnp.float32,
                      settings={"nu": 0.05, "FluidAlfa": 0.05,
                                "InletVelocity": 0.02})
        flags = np.full((16, 128), m.flag_for("BGK"), dtype=np.uint16)
        flags[0, :] = m.flag_for("Wall")
        flags[-1, :] = m.flag_for("Wall")
        lat.set_flags(flags)
        lat.init()
        return lat

    monkeypatch.setenv("TCLB_FASTPATH", "force")
    lat_f = build()
    lat_f.iterate(niter)
    assert lat_f._fast_name == "pallas_resident_generic[d2q9_heat]"

    monkeypatch.setenv("TCLB_FASTPATH", "0")
    lat_x = build()
    lat_x.iterate(niter)
    assert lat_x._fast_name is None

    np.testing.assert_allclose(np.asarray(lat_f.state.fields),
                               np.asarray(lat_x.state.fields),
                               rtol=2e-5, atol=2e-6)
    gx, gf = lat_x.get_globals(), lat_f.get_globals()
    assert gx.keys() == gf.keys()
    for k in gx:
        np.testing.assert_allclose(gf[k], gx[k], rtol=1e-4, atol=1e-6,
                                   err_msg=f"global {k}")
    assert int(lat_f.state.iteration) == niter


def test_fallbacks(monkeypatch):
    """A Control time series (per-iteration zonal settings) no longer
    falls back: since PR 55 dispatch keeps it on the tuned band engine
    (the VMEM-resident engine, which takes none, leaves the chain) with
    the generic band's series flavour as its tail; the run itself is
    held to the XLA step in ``test_tail_engine_matches_the_xla_step
    [series]`` and ``tests/test_control_band.py``.  What the fused
    families do not cover still runs the XLA path transparently."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m, lat = _karman_lattice()
    series = 0.03 + 0.001 * np.sin(np.arange(16) * 0.3)
    lat.set_setting_series("Velocity", series, zone=0)
    assert lat._fast_path().supports_series
    assert [c.tag for c in lat._fast_chain] == ["pallas_2d[d2q9,fuse=2]"]
    assert lat._tail_name == "pallas_generic[d2q9,fuse=1]"
    assert lat._tail.supports_series

    # d2q9_heat used to be the fallback example; since round 4 the
    # registry-driven generic engine covers it — assert it dispatches
    m2 = get_model("d2q9_heat")
    lat2 = Lattice(m2, (32, 64), dtype=jnp.float32, settings={"nu": 0.05})
    lat2.init()
    lat2.iterate(4)
    fz = pallas_generic.choose_fuse(m2)
    assert fz >= 2
    assert lat2._fast_name == f"pallas_generic[d2q9_heat,fuse={fz}]"
    assert np.isfinite(np.asarray(lat2.state.fields)).all()

    # f64 stays off every Pallas path (kernels are f32-only)
    lat3 = Lattice(get_model("d2q9"), (32, 64), dtype=jnp.float64,
                   settings={"nu": 0.05})
    lat3.init()
    lat3.iterate(4)
    assert lat3._fast_name is None
    assert np.isfinite(np.asarray(lat3.state.fields)).all()


def test_sharded_pallas_matches_single(monkeypatch):
    """The sharded fast path (ppermute halo + per-shard band kernel under
    shard_map) reproduces the single-device engine on the boundary-rich
    case — fields AND globals (the trailing sharded XLA step psums)."""
    from tclb_tpu.parallel.mesh import make_mesh
    ny, nx = 64, 128
    niter = 21

    monkeypatch.setenv("TCLB_FASTPATH", "0")
    m, lat_ref = _karman_lattice(ny, nx)
    lat_ref.iterate(niter)

    monkeypatch.setenv("TCLB_FASTPATH", "force")
    mesh = make_mesh((ny, nx), devices=jax.devices()[:4],
                     decomposition={"y": 4, "x": 1})
    lat_s = Lattice(m, (ny, nx), dtype=jnp.float32,
                    settings={"nu": 0.05, "Velocity": 0.03}, mesh=mesh)
    flags = np.asarray(lat_ref.state.flags)
    lat_s.set_flags(flags)
    lat_s.init()
    lat_s.iterate(niter)
    assert lat_s._fast_name.startswith("pallas_sharded")

    np.testing.assert_allclose(np.asarray(lat_s.state.fields),
                               np.asarray(lat_ref.state.fields),
                               rtol=2e-5, atol=2e-6)
    gr, gs = lat_ref.get_globals(), lat_s.get_globals()
    for k in gr:
        np.testing.assert_allclose(gs[k], gr[k], rtol=1e-4, atol=1e-6,
                                   err_msg=f"global {k}")
    assert any(abs(v) > 0 for v in gs.values())


def test_sharded_pallas_3d(monkeypatch):
    """3D sharded fast path: z-sharded d3q27 slab kernel parity."""
    from tclb_tpu.parallel.mesh import make_mesh
    shape = (8, 16, 64)
    m = get_model("d3q27_BGK")

    def build(mesh):
        lat = Lattice(m, shape, dtype=jnp.float32,
                      settings={"omega": 1.0, "GravitationX": 1e-5},
                      mesh=mesh)
        flags = np.full(shape, m.flag_for("BGK"), dtype=np.uint16)
        flags[:, 0, :] = m.flag_for("Wall")
        flags[:, -1, :] = m.flag_for("Wall")
        lat.set_flags(flags)
        lat.init()
        return lat

    monkeypatch.setenv("TCLB_FASTPATH", "0")
    lat_ref = build(None)
    lat_ref.iterate(7)

    monkeypatch.setenv("TCLB_FASTPATH", "force")
    mesh = make_mesh(shape, devices=jax.devices()[:4],
                     decomposition={"z": 4, "y": 1, "x": 1})
    lat_s = build(mesh)
    lat_s.iterate(7)
    assert lat_s._fast_name.startswith("pallas_sharded")
    np.testing.assert_allclose(np.asarray(lat_s.state.fields),
                               np.asarray(lat_ref.state.fields),
                               rtol=2e-5, atol=2e-6)


def test_sharded_fallback_when_x_split(monkeypatch):
    """A mesh that splits x can't run the band kernels: dispatch must fall
    back to the sharded XLA path, still correct."""
    from tclb_tpu.parallel.mesh import make_mesh
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    ny, nx = 32, 64
    m = get_model("d2q9")
    mesh = make_mesh((ny, nx), devices=jax.devices()[:4],
                     decomposition={"y": 2, "x": 2})
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.05, "GravitationX": 1e-5}, mesh=mesh)
    lat.init()
    lat.iterate(4)
    assert lat._fast_name is None
    assert np.isfinite(np.asarray(lat.state.fields)).all()


def test_xml_log_stop_on_fast_path(monkeypatch, tmp_path):
    """<Log>/<Stop> configs run on the fast path with globals matching the
    XLA path (round-2 VERDICT item #3's done criterion): the hybrid's
    trailing XLA step feeds every handler event real integrals."""
    import csv
    from tclb_tpu.control import run_config_string

    xml = """<CLBConfig output="{out}/">
    <Geometry nx="128" ny="32">
        <MRT><Box/></MRT>
        <WVelocity name="in"><Inlet/></WVelocity>
        <EPressure name="out"><Outlet/></EPressure>
        <Inlet nx="1" dx="2"><Box/></Inlet>
        <Outlet nx="1" dx="-3"><Box/></Outlet>
        <Wall mask="ALL"><Channel/></Wall>
    </Geometry>
    <Model><Params Velocity="0.03" nu="0.05"/></Model>
    <Log Iterations="8"/>
    <Stop InletFluxChange="1e-9" Times="3" Iterations="8"/>
    <Solve Iterations="64"/>
    </CLBConfig>"""

    def rows(tag):
        monkeypatch.setenv("TCLB_FASTPATH", tag)
        out = tmp_path / tag
        run_config_string(xml.format(out=out), get_model("d2q9"),
                          dtype=jnp.float32, output=f"{out}/",
                          conf_name="case")
        with open(out / "case_Log.csv") as f:
            return list(csv.DictReader(f))

    r_xla = rows("0")
    r_fast = rows("force")
    assert len(r_fast) == len(r_xla)
    for a, b in zip(r_xla, r_fast):
        for col in ("InletFlux", "OutletFlux", "PressureLoss"):
            va, vb = float(a[col]), float(b[col])
            assert abs(va - vb) <= 1e-6 + 1e-4 * abs(va), \
                f"iter {a['Iteration']}: {col} xla={va} fast={vb}"
    # the monitors are nonzero (the Log rows carry real integrals)
    assert any(abs(float(r["InletFlux"])) > 0 for r in r_fast)


def test_single_step_uses_xla(monkeypatch):
    """niter=1 goes straight to the XLA step (the hybrid needs nothing)."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    _, lat = _karman_lattice()
    lat.iterate(1)
    _, lat_x = _karman_lattice()
    lat_x._fast_tried = True   # pin pure XLA
    lat_x.iterate(1)
    np.testing.assert_allclose(np.asarray(lat.state.fields),
                               np.asarray(lat_x.state.fields),
                               rtol=1e-6, atol=1e-7)


# --------------------------------------------------------------------------- #
# chip_smoke.py: the parts that need no chip
# --------------------------------------------------------------------------- #


def _chip_smoke():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_without_accelerator(capsys):
    """No accelerator: non-zero exit and no result line (conftest holds
    this process to the CPU)."""
    assert _chip_smoke().main([]) == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no accelerator" in out.err


@pytest.mark.parametrize("compress", [False, True])
def test_chip_smoke_reads_back_vti(tmp_path, compress):
    """The smoke's output check reads what utils/vtk.py writes."""
    from tclb_tpu.utils.vtk import write_vti
    rng = np.random.default_rng(3)
    rho = rng.standard_normal((6, 40)).astype(np.float32)
    u = rng.standard_normal((3, 6, 40)).astype(np.float32)
    p = write_vti(str(tmp_path / "x"), {"Rho": rho, "U": u},
                  compress=compress)
    back = _chip_smoke().read_vti(p)
    assert back["__cells__"] == 6 * 40
    assert (back["Rho"] == rho.ravel()).all()
    assert (back["U"].reshape(6, 40, 3) == np.moveaxis(u, 0, -1)).all()


def test_chip_smoke_rehearses_the_guard_phase(tmp_path, monkeypatch,
                                              capsys):
    """`failcheck_fires` at its rehearsal size: the planted NaN and
    infinity stop the run at the second firing, `Rho` counts two, one
    rescue file; a rehearsal never prints ok."""
    import json
    import sys

    from tclb_tpu import telemetry
    mod = _chip_smoke()
    monkeypatch.setattr(mod, "OUT", str(tmp_path / "smoke"))
    monkeypatch.setitem(sys.modules, "chip_smoke", mod)   # <CallPython>
    # the smoke subscribes for its process's life: here it must not
    # leave telemetry on for the tests that run after it
    added, subscribe = [], telemetry.subscribe
    monkeypatch.setattr(telemetry, "subscribe",
                        lambda fn: (added.append(fn), subscribe(fn))[1])
    try:
        assert mod.main(["--rehearse", "--only", "failcheck_fires"]) == 0
    finally:
        for fn in added:
            telemetry.unsubscribe(fn)
        telemetry.disable()
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    phase, = [x for x in lines if x.get("phase")]
    assert phase["phase"] == "failcheck_fires"
    assert phase["failcheck"] == [8, "Rho", 2] and phase["steps"] == 8
    assert phase["bytes_to_host"] == [8, 8]
    assert phase["rescue"].endswith("_VTK_00000008.vti")
    assert lines[-2]["failed"] == []
    assert lines[-1]["ok"] is False and lines[-1]["rehearsal"] == "passed"


def test_cli_output_flag_wins_over_config_attribute(tmp_path):
    """`tclb run --output DIR/` must not be overridden by the case file's
    own output= attribute."""
    from tclb_tpu.control import run_config
    case = tmp_path / "mini.xml"
    case.write_text("""<?xml version="1.0"?>
<CLBConfig version="2.0" model="d2q9" output="{elsewhere}/">
    <Geometry nx="16" ny="8"><MRT><Box/></MRT></Geometry>
    <Model><Params Velocity="0.0" nu="0.1"/></Model>
    <VTK Iterations="2"/>
    <Solve Iterations="2"/>
</CLBConfig>
""".replace("{elsewhere}", str(tmp_path / "elsewhere")))
    out = tmp_path / "given"
    run_config(str(case), get_model("d2q9"), output=str(out) + "/")
    assert list(out.glob("*_VTK_*.vti"))
    assert not (tmp_path / "elsewhere").exists()
