"""The plan of the tuned 2D band kernels (``pallas_d2q9.band_plan``):
which bands, ghost rows and scoped-VMEM limit a lattice gets, that the
plans of the chip's records are what they were, that the account covers
what Mosaic reported, that every rung of a wide-row plan is the XLA step
to the bits, and what dispatch does with a plan over the default limit
and with a shape no plan holds.  The compiles for a described v5e are
in ``tests/test_mosaic_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from tclb_tpu import telemetry
from tclb_tpu.core.lattice import Lattice
from tclb_tpu.models import get_model
from tclb_tpu.ops import fusion, lbm, pallas_d2q9, pallas_generic
from tclb_tpu.ops.engine import Engine

MIB = 1024 * 1024
DEFAULT, RAISED = pallas_d2q9._VMEM_DEFAULT, pallas_d2q9._VMEM_RAISED


# (ny, nx, ext_halo) -> the parent's (pad, one-step rows, two-step rows)
# where the parent compiled: the plans of the chip's records
PLANS = [
    ((1024, 1024, False), (0, 64, 32)),    # the karman1024 cells
    ((100, 1024, False), (20, 40, 40)),    # karman.xml; the resident
    #                                        engine's remainder band
    ((1024, 1024, True), (0, 64, 32)),     # a shard of karman4096
    ((800, 1024, False), None),            # PR 48's open finding: an
    ((1280, 1024, False), None),           # 80-row band, 18 MiB
    ((1024, 2048, False), None),
    ((1024, 4096, False), None),
    ((64, 8192, False), None),
    ((8192, 8192, False), None),           # the cell karman8192.longrun
]


@pytest.mark.parametrize("case,pinned", PLANS,
                         ids=["x".join(map(str, c[:2])) + "-shard" * c[2]
                              for c, _ in PLANS])
def test_plan(case, pinned):
    ny, nx, ext_halo = case
    m = get_model("d2q9")
    plan = pallas_d2q9.band_plan(m, ny, nx, ext_halo)
    assert plan is not None and pallas_d2q9.supports(m, (ny, nx),
                                                     jnp.float32)
    if pinned:
        assert (plan.pad_rows, *plan.band_rows) == pinned
        # and their kernels are built as they were: no limit is stated
        assert plan.vmem_limit_bytes == (DEFAULT, DEFAULT)
        assert plan.compiler_params(1) is plan.compiler_params(2) is None
    for steps, rows, vmem, limit in zip((1, 2), plan.band_rows,
                                        plan.vmem_bytes,
                                        plan.vmem_limit_bytes):
        assert rows % 8 == 0 and (ny + plan.pad_rows) % rows == 0
        assert vmem == pallas_d2q9.band_vmem(m, rows, nx, steps) <= limit
        assert limit in (DEFAULT, RAISED)
        assert (plan.compiler_params(steps) is None) == (limit == DEFAULT)
    # the two-step band reads no more than the 1024-wide records do:
    # 32 rows under 16 halo rows
    rows = plan.band_rows[1]
    assert (rows + 16) / rows <= fusion.BAND_AMPLIFICATION_OK
    # rows of 2048 nodes and more need the raised limit, and say so
    assert plan.raised(2) == (nx >= 2048)


def test_wide_rows_plan_the_bands_of_the_1024_records():
    """At 8192 nodes a row the parent's formula fell to 8 rows under 16
    halo rows, three times the bytes, and no kernel compiled."""
    plan = pallas_d2q9.band_plan(get_model("d2q9"), 8192, 8192)
    assert (plan.pad_rows, plan.band_rows) == (0, (32, 32))
    # two slots of 14 planes of 48 rows, the out block twice, 31 planes
    # of 42 rows of temporaries: 104.7 MiB (Mosaic's own count: 102.17)
    assert plan.vmem_bytes == (71_303_168, 109_772_800)
    assert plan.vmem_limit_bytes == (RAISED, RAISED) == (106 * MIB,) * 2


# what the compile for a described v5e asked for, MiB, with every
# boundary type present (found by raising the limit until it passed; the
# two-step rows read again off the kernel that holds two slots of its
# band): (model, nx, steps, rows, MiB)
REPORTED = [
    ("d2q9", 1024, 1, 64, 15.39), ("d2q9", 1024, 1, 32, 8.91),
    ("d2q9", 1024, 1, 8, 3.30), ("d2q9", 1024, 2, 32, 12.76),
    ("d2q9", 1024, 2, 48, 17.75), ("d2q9", 512, 1, 128, 14.72),
    ("d2q9", 512, 2, 64, 11.47), ("d2q9", 1536, 2, 32, 19.00),
    ("d2q9", 2048, 1, 64, 30.51), ("d2q9", 2048, 2, 48, 35.68),
    ("d2q9", 4096, 1, 48, 46.88), ("d2q9", 4096, 2, 32, 51.10),
    ("d2q9", 8192, 1, 32, 66.20), ("d2q9", 8192, 2, 32, 102.17),
    ("d2q9", 8192, 2, 8, 39.64), ("d2q9_SRT", 1024, 1, 64, 16.38),
    ("d2q9_les", 1024, 1, 32, 10.79), ("d2q9_les", 1024, 2, 64, 20.47),
    ("d2q9_new", 1024, 1, 64, 18.28), ("d2q9_new", 1024, 2, 64, 20.77),
    ("d2q9_inc", 1024, 1, 64, 16.42), ("d2q9_cumulant", 1024, 2, 64, 20.55),
    # the band of karman.xml's 120 padded rows (15.33 of Mosaic's 16),
    # two more family models, and bands whose planes are 0.5 to 0.75 MiB
    ("d2q9", 1024, 2, 40, 15.33), ("d2q9_SRT", 1024, 2, 32, 10.92),
    ("d2q9_inc", 1024, 2, 48, 15.28), ("d2q9", 8192, 2, 16, 61.21),
    ("d2q9", 4096, 2, 40, 61.54), ("d2q9", 8192, 2, 24, 81.69),
    ("d2q9", 4096, 2, 48, 71.69),
]


@pytest.mark.parametrize("name,nx,steps,rows,mib", REPORTED)
def test_account_covers_what_mosaic_reported(name, nx, steps, rows, mib):
    """The account is an upper bound of the compiler's own, and a close
    one: a plan it admits compiles, and it refuses few that would."""
    said = pallas_d2q9.band_vmem(get_model(name), rows, nx, steps) / MIB
    assert mib <= said <= 1.25 * mib + 0.5


def test_what_the_parent_could_not_compile_is_planned_lower():
    """Bands the parent picked and Mosaic refused (17.04 and 18.83 MiB
    of 16): 72 and 80 rows at 1024 nodes a row."""
    m = get_model("d2q9")
    for ny, rows in ((144, 48), (800, 40), (1280, 64)):
        assert pallas_d2q9.band_plan(m, ny, 1024).band_rows[0] == rows
    assert pallas_d2q9.band_vmem(m, 72, 1024, 1) > DEFAULT


def test_the_generic_band_plans_by_the_same_rule():
    """``pallas_generic``'s band at rows of 8192 nodes: none under the
    default scratch budget (where the parent stopped), 32 rows under the
    raised one; at 1024 nodes a row what it was."""
    m = get_model("d2q9")
    low, high = pallas_generic._BAND_SCRATCH
    assert pallas_generic._band_scratch(m, 8, 8192) > low
    assert pallas_generic._band_plan(m, 8192, 8192) == (32, high)
    assert pallas_generic.supports(m, (8192, 8192), jnp.float32,
                                   probe=False)
    k = get_model("d2q9_kuper")
    assert pallas_generic._band_plan(k, 1024, 1024) == (32, low)
    # a rung of the probe ladder stays a rung
    assert pallas_generic._band_rows(m, 8192, 8192, by_cap=16) == 16
    assert pallas_generic._band_rows(m, 16, 131072) is None


# --------------------------------------------------------------------------- #
# every rung of a wide-row plan is the XLA step, to the bits
# --------------------------------------------------------------------------- #

WIDE = (48, 2048)


def _channel(shape, periodic=False):
    """A d2q9 channel of ``shape`` with a wedge in it (``periodic``: no
    walls along x, so rows 0 and ny - 1 are neighbours): the model, the
    lattice at its initial state, the node types present."""
    m = get_model("d2q9")
    ny, nx = shape
    lat = Lattice(m, shape, dtype=jnp.float32,
                  settings={"nu": 0.02, "Velocity": 0.01})
    flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    if not periodic:
        flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    rows, cols = np.mgrid[0:ny, 0:nx]
    flags[np.abs(rows - ny // 2 + 0.5) + np.abs(cols - 40.5) < ny // 5] = \
        m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    return m, lat, lbm.present_types(m, flags)


def _wide_channel():
    return _channel(WIDE)[:2]


@pytest.fixture(scope="module")
def wide_xla():
    """The wide channel after 7 steps of the XLA engine."""
    m, lat = _wide_channel()
    start = jax.tree.map(jnp.copy, lat.state)
    end = lat._iterate(lat.state, lat.params, 7)
    return m, lat.params, start, np.asarray(end.fields)


# the ceiling the planner is given -> the bands it plans at 48 x 2048
RUNGS = [(RAISED, (48, 48)), (22 * MIB, (24, 24)), (20 * MIB, (24, 16)),
         (DEFAULT, (24, 8))]


@pytest.mark.parametrize("ceiling,bands,fuse", [
    (c, b, f) for c, b in RUNGS for f in (1, 2)
    if f == 2 or c in (RAISED, DEFAULT)],
    ids=lambda v: f"{v // MIB}MiB" if isinstance(v, int) and v > 2 else None)
def test_every_rung_is_the_xla_step(monkeypatch, wide_xla, ceiling, bands,
                                    fuse):
    """(The one-step engine at the two ends of the ladder only: its
    kernel is the last call of every two-step engine's program.)"""
    m, params, start, want = wide_xla
    monkeypatch.setattr(pallas_d2q9, "_VMEM_RAISED", ceiling)
    it = pallas_d2q9.make_pallas_iterate(m, WIDE, jnp.float32, fuse=fuse,
                                         interpret=True)
    assert it.impl["plan"].band_rows == bands
    got = it(jax.tree.map(jnp.copy, start), params, 7)
    np.testing.assert_array_equal(np.asarray(got.fields), want)


def test_the_rung_under_a_plan_is_the_next_band_down():
    """``rows_cap``, what dispatch builds under a plan that failed: the
    bands of the 22 MiB ceiling above, which ran."""
    m = get_model("d2q9")
    assert pallas_d2q9.band_plan(m, *WIDE, rows_cap=40).band_rows == (24, 24)
    it = pallas_d2q9.make_pallas_iterate(m, WIDE, jnp.float32, fuse=2,
                                         interpret=True, rows_cap=40)
    assert it.account(7)["band_rows"] == 24
    assert it.vmem == dict(
        vmem_bytes=pallas_d2q9.band_vmem(m, 24, 2048, 2),
        vmem_limit_bytes=RAISED)


# --------------------------------------------------------------------------- #
# the two-step kernel prefetches its band: the same steps, to the bits
# --------------------------------------------------------------------------- #


# what a parity case runs the kernels in: the plain interpreter, whose
# copies are done where they are started, and the TPU interpreter, whose
# copies are done where they are waited for and which looks for races: a
# slot written or read on the wrong side of a wait is a wrong result
# there
MODES = {"plain": True,
         "on_wait": pltpu.InterpretParams(detect_races=True)}

# shape -> the two-step kernel's bands a call
PREFETCH = [((32, 256), 1),     # one band a call: nothing to prefetch
            ((64, 256), 2), ((120, 256), 3),
            ((100, 1024), 3)]   # karman.xml: 20 ghost rows, bands of 40


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("shape,bands", PREFETCH,
                         ids=["%dx%d" % s for s, _ in PREFETCH])
def test_the_prefetching_two_step_kernel_is_the_xla_step(capsys, shape,
                                                         bands, mode):
    """Band i + 1's six copies are in flight into the other slot while
    band i is computed.  Five steps: two calls of the two-step kernel
    and the one-step kernel after them."""
    m, lat, present = _channel(shape)
    it = pallas_d2q9.make_pallas_iterate(m, shape, jnp.float32, fuse=2,
                                         interpret=MODES[mode],
                                         present=present)
    said = it.account(5)
    assert (said["bands"], said["band_slots"], said["kernel_calls"]) \
        == (bands, 2, 3)
    got = it(jax.tree.map(jnp.copy, lat.state), lat.params, 5)
    want = lat._iterate(lat.state, lat.params, 5)
    np.testing.assert_array_equal(np.asarray(got.fields),
                                  np.asarray(want.fields))
    assert "RACE DETECTED" not in capsys.readouterr().out


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("rows,bands", [(32, 1), (72, 3)])
def test_the_prefetching_two_step_kernel_on_a_shard(capsys, rows, bands,
                                                    mode):
    """The sharded flavour on rows [40, 40 + ``rows``) of a periodic
    lattice, the 8 rows on either side handed over as the neighbours'
    blocks: where the shard is one band, that band is its first and its
    last (both halo blocks the neighbours'); where it is three, the first
    takes the lower neighbour's, the last the upper one's, the middle one
    the shard's own rows, and the rule goes with the band that is
    started, a grid step ahead of the one computed."""
    nx, a = 256, 40
    m, lat, present = _channel((rows + 80, nx), periodic=True)
    _, call2, _, by2 = pallas_d2q9.make_pallas_iterate(
        m, (rows, nx), jnp.float32, fuse=2, interpret=MODES[mode],
        present=present, ext_halo=True)
    assert rows // by2 == bands
    flags_i32 = lat.state.flags.astype(jnp.int32)
    vel, den = pallas_d2q9.zonal_planes(
        m, lat.params, flags_i32 >> m.zone_shift, jnp.float32)
    aux = jnp.stack([flags_i32.astype(jnp.float32), vel, den])
    f = lat.state.fields
    got = call2(lat.params.settings.astype(jnp.float32),
                f[:, a:a + rows], f[:, a - 8:a],
                f[:, a + rows:a + rows + 8], aux[:, a - 8:a + rows + 8])
    want = lat._iterate(lat.state, lat.params, 2)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want.fields)[:, a:a + rows])
    assert "RACE DETECTED" not in capsys.readouterr().out


# --------------------------------------------------------------------------- #
# dispatch
# --------------------------------------------------------------------------- #


@pytest.fixture
def band_first(monkeypatch):
    """Dispatch in interpret mode, with the VMEM-resident engine (which
    would hold all 48 rows of the wide channel on-chip) out of the way."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    monkeypatch.setattr(pallas_d2q9, "supports_resident",
                        lambda *a, **k: False)


def _events(tmp_path, run):
    from tclb_tpu.telemetry import report
    trace = tmp_path / "t.jsonl"
    before = telemetry.counters()
    telemetry.enable(str(trace))
    try:
        run()
        counters = {k: v - before.get(k, 0)
                    for k, v in telemetry.counters().items()}
    finally:
        telemetry.disable()
    return report.load(str(trace)), counters


def test_a_plan_at_the_default_limit_is_not_probed(monkeypatch):
    """The chains of the accepted cells are what they were."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m = get_model("d2q9")
    lat = Lattice(m, (1024, 1024), dtype=jnp.float32)
    assert [(c.tag, c.probe, c.cap) for c in lat._build_fast()] == [
        ("pallas_2d[d2q9,fuse=2]", False, 0)]


@pytest.mark.parametrize("shape,rows,under", [
    ((1024, 2048), 32, 16), ((48, 2048), 48, 24)])
def test_a_plan_over_the_default_limit_is_probed_with_a_rung_under_it(
        monkeypatch, band_first, shape, rows, under):
    """(8192 x 8192 plans the bands of 1024 x 2048, ``test_plan``; its
    state is 3 GB.)"""
    monkeypatch.setattr(
        pallas_d2q9, "make_pallas_iterate",
        lambda *a, **k: pytest.fail("a chain is listed, not built"))
    lat = Lattice(get_model("d2q9"), shape, dtype=jnp.float32)
    assert [(c.tag, c.probe, c.cap) for c in lat._build_fast()] == [
        ("pallas_2d[d2q9,fuse=2]", True, rows),
        (f"pallas_2d[d2q9,fuse=2,by<={under}]", True, under)]


def test_a_probed_rung_that_fails_steps_down(monkeypatch, band_first,
                                             tmp_path, wide_xla):
    """The plan's kernel does not compile: the rung under it runs the
    call, one ``engine_fallback`` says so, the probe counts both, and the
    later calls' ``iterate.fused`` spans carry the rung's account and
    its plan's VMEM."""
    m, params, start, want = wide_xla
    make = pallas_d2q9.make_pallas_iterate

    def failing_first(model, shape, dtype, **kw):
        it = make(model, shape, dtype, **kw)
        if kw.get("rows_cap") is not None or kw.get("fuse") != 2:
            return it

        def refuse(state, params, niter):
            raise RuntimeError("synthetic mosaic failure")
        return Engine(refuse, it.account, vmem=it.vmem, impl=it.impl)

    monkeypatch.setattr(pallas_d2q9, "make_pallas_iterate", failing_first)
    _, lat = _wide_channel()
    evts, counters = _events(tmp_path,
                             lambda: (lat.iterate(8), lat.iterate(8)))
    under = "pallas_2d[d2q9,fuse=2,by<=24]"
    sel = [e for e in evts if e["kind"] == "engine_selected"]
    assert (sel[0]["engine"], sel[0]["probed"]) \
        == ("pallas_2d[d2q9,fuse=2]", True)
    assert lat._fast_name == under and not lat._fast_probing
    fb = [e for e in evts if e["kind"] == "engine_fallback"
          and e["from"].startswith("pallas_2d")]
    assert [(e["from"], e["to"]) for e in fb] == [
        ("pallas_2d[d2q9,fuse=2]", under)]
    probe = [e for e in evts if e["kind"] == "span"
             and e["name"] == "engine.probe"
             and e["engine"] == "pallas_2d[d2q9,fuse=2]"]
    assert len(probe) == 1
    assert (probe[0]["attempts"], probe[0]["rungs"], probe[0]["result"]) \
        == (2, [48, 24], under)
    assert counters["engine.probe_attempts"] >= 2
    # the rung's account and its plan lie on the probe that ran it, and
    # on the fused span of the call after it
    fused = [e for e in evts if e["kind"] == "span"
             and e["name"] == "iterate.fused"]
    assert len(fused) == 2 and fused[1]["engine"] == under
    said = dict(bands=2, band_rows=24, halo_rows=8, pad_rows=0,
                aux_planes=3, kernel_calls=4,
                vmem_bytes=pallas_d2q9.band_vmem(m, 24, 2048, 2),
                vmem_limit_bytes=RAISED)
    for span in (probe[0], fused[1]):
        assert {k: span[k] for k in said} == said
    # seven steps on the rung and the trailing one, twice: the XLA
    # engine's sixteen
    _, ref = _wide_channel()
    ref.state = ref._iterate(ref.state, ref.params, 16)
    np.testing.assert_array_equal(np.asarray(lat.state.fields),
                                  np.asarray(ref.state.fields))


def test_a_shape_no_plan_holds_runs_on_xla_and_says_why(monkeypatch,
                                                        tmp_path):
    """Rows of 131072 nodes: an 8-row band is over the raised ceiling.
    ``supports`` says no, dispatch selects ``xla`` and emits
    ``fused_rejected``; nothing fails at the first call."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    shape = (16, 131072)
    m = get_model("d2q9")
    assert pallas_d2q9.covers(m, shape, jnp.float32)
    assert pallas_d2q9.band_plan(m, *shape) is None
    assert not pallas_d2q9.supports(m, shape, jnp.float32)
    lat = Lattice(m, shape, dtype=jnp.float32, settings={"nu": 0.02})
    lat.set_flags(np.full(shape, m.flag_for("MRT"), dtype=np.uint16))
    lat.init()
    evts, _ = _events(tmp_path, lambda: lat.iterate(2))
    assert lat._fast_name is None
    assert np.isfinite(np.asarray(lat.state.fields)).all()
    sel = [e for e in evts if e["kind"] == "engine_selected"]
    assert [e["engine"] for e in sel] == ["xla"]
    rej = [e for e in evts if e["kind"] == "fused_rejected"]
    assert len(rej) == 1
    assert (rej[0]["engine"], rej[0]["model"], rej[0]["shape"]) \
        == ("pallas_2d", "d2q9", list(shape))
    assert rej[0]["reason"].startswith("vmem")
