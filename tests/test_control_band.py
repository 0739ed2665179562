"""The tuned 2D band engine under a ``<Control>`` series (PR 55): every
step of a call reads the series' value of ITS OWN iteration, as a scalar
beside the call's planes (``ops/pallas_d2q9.py:series_flavour``).

Interpret mode at 64 x 128 (and 52 rows: ghost rows under the band), a
handful of steps, against the XLA step (bit for bit: the interpret-mode
contract) and against the benchmark's plain reference
(``benchmark/reference/d2q9_control.py``).  A program costs a second and
more to trace and compile for every step of every kernel call it holds,
whatever its size, so the calls are as short as the points they hold
allow (three steps: a two-step call over the wrap and a one-step call
behind it), the cases share their lattices, engines, XLA and reference
runs, and one case holds several of the issue's points.
The series is a **sawtooth** whose neighbouring values differ by 1e-3: a
value used one step late, or the first step's value reused for the
second step of a two-step call, moves the inlet's populations by 100
times ``TOL`` and more.  A lattice without a series must
build the programs it always built: same kernels, same operands, the
state alone in the loops' carry.
"""

import xml.etree.ElementTree as ET
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import d2q9, d2q9_control, geometry
from tclb_tpu.core.lattice import Lattice, make_iterate
from tclb_tpu.models import get_model
from tclb_tpu.ops import lbm, pallas_d2q9

TOL = 2e-6          # f32 program against the f32 reference, a few steps
SERIES_TAG = "pallas_2d[d2q9,fuse=2]"    # what dispatch runs a series on
INLET, OUTLET = 1, 2     # the zones of the two faces
CASE = """<CLBConfig version="2.0" model="d2q9">
    <Geometry nx="128" ny="{ny}">
        <MRT><Box/></MRT>
        <WVelocity name="Inlet"><Inlet/></WVelocity>
        <EPressure name="Outlet"><Outlet/></EPressure>
        <Wall mask="ALL">
            <Channel/>
            <Wedge dx="20" nx="8" dy="{dy}" ny="8" direction="LowerRight"/>
            <Wedge dx="28" nx="8" dy="{dy}" ny="8" direction="LowerLeft"/>
        </Wall>
    </Geometry>
    <Model><Params Velocity="0.02"/><Params nu="0.05"/></Model>
    <Control Iterations="{horizon}">
        <CSV file="{csv}"/>
        <Params Velocity-Inlet="vel"/>
    </Control>
</CLBConfig>"""


HORIZON = 1_000_000      # 2D-3's own order; the table stays in HBM
# a call of odd length whose two-step kernel call straddles the wrap: its
# steps read the table's last value, its first and (the one-step call,
# at the iteration the loops carried on) its second
START, NITER = HORIZON - 1, 3


def sawtooth(horizon: int, base=0.02) -> np.ndarray:
    """Neighbouring values 1e-3 apart, the wrap's two as well."""
    return base + 1e-3 * (np.arange(horizon) % 5)


@lru_cache(maxsize=None)
def case(ny: int, name="d2q9") -> tuple:
    """``(root, model, lattice, present)``: the case as the reference
    reads it and the program's lattice of it, the reference's own masks
    as its flags (the inlet face the zone ``INLET``, the outlet face
    ``OUTLET``) and the reference's initial field as its state, at
    iteration ``START``.  The reference never opens the CSV here: the
    tests hand it the table (``values``)."""
    root = ET.fromstring(CASE.format(ny=ny, dy=ny // 2, horizon=HORIZON,
                                     csv="unread.csv"))
    masks = geometry.paint(root.find("Geometry"))
    par = geometry.params(root)
    m = get_model(name)
    flags = np.full(masks["wall"].shape, m.flag_for("MRT"), np.uint16)
    flags[masks["inlet"]] = (m.flag_for("WVelocity", "MRT")
                             | INLET << m.zone_shift)
    flags[masks["outlet"]] = (m.flag_for("EPressure", "MRT")
                              | OUTLET << m.zone_shift)
    flags[masks["wall"]] = m.flag_for("Wall")
    lat = Lattice(m, flags.shape, dtype=jnp.float32, settings=par)
    lat.set_flags(flags)
    f0 = d2q9.initial(masks, par, jnp.float32)
    lat.state = lat.state.replace(
        fields=jnp.concatenate([f0, jnp.zeros(
            (m.n_storage - 9,) + flags.shape, jnp.float32)]),
        iteration=jnp.asarray(START, jnp.int32))
    return root, m, lat, lbm.present_types(m, flags)


@lru_cache(maxsize=None)
def band(ny: int, fuse: int, name="d2q9"):
    _, m, lat, present = case(ny, name)
    return pallas_d2q9.make_pallas_iterate(
        m, lat.shape, jnp.float32, interpret=True, fuse=fuse,
        present=present)


@lru_cache(maxsize=None)
def xla_step(ny: int, name="d2q9"):
    _, m, _, present = case(ny, name)
    step = jax.jit(make_iterate(m, present=present),
                   static_argnames=("niter",))
    return lambda state, params, niter: step(state, params, niter=niter)


def fields_of(run, lat, params, niter=NITER) -> np.ndarray:
    """The fields ``run`` (an engine, or the XLA step) leaves after
    ``niter`` steps from the shared lattice's state, which it keeps."""
    out = run(jax.tree.map(jnp.copy, lat.state), params, niter)
    assert int(out.iteration) == START + niter
    return np.asarray(out.fields)


def series_on(lat, **tables):
    """The lattice's parameters with these series attached (``zone`` by
    the setting: Velocity the inlet's, anything else the outlet's); the
    lattice itself is shared and stays without."""
    was = lat.params, dict(lat._series)
    try:
        for setting, values in tables.items():
            lat.set_setting_series(
                setting, values,
                zone=INLET if setting == "Velocity" else OUTLET)
        return lat.params
    finally:
        lat.params, lat._series = was
        lat._fast_tried = False


@lru_cache(maxsize=None)
def sound(ny: int) -> tuple:
    """The XLA step's fields under the sawtooth on the inlet, and the
    parameters that say so."""
    _, _, lat, _ = case(ny)
    params = series_on(lat, Velocity=sawtooth(HORIZON))
    return fields_of(xla_step(ny), lat, params), params


@lru_cache(maxsize=None)
def wrong() -> dict:
    """What a program that misread the sawtooth would leave, each 100
    times the tolerance from the sound answer: every value one step
    ``late`` (the reference's ``lag``), the first step's value of the
    two-step call ``reused`` for its second, and a table that does not
    wrap (its last value ``held``); beside them ``ref``, the reference's
    sound answer."""
    root, _, lat, _ = case(64)
    saw = sawtooth(HORIZON)
    assert abs(saw[-1] - saw[0]) > 9e-4       # the wrap is a tooth too
    reused, held = saw.copy(), saw.copy()
    reused[(START + 1) % HORIZON] = saw[START]
    held[:2] = saw[-1]
    out = {name: fields_of(xla_step(64), lat,
                           series_on(lat, Velocity=table))[:9]
           for name, table in (("reused", reused), ("held", held))}
    for name, lag in (("ref", 0), ("late", 1)):
        out[name] = np.asarray(d2q9_control.run(
            root, NITER, jnp.float32, start=START, values=saw, lag=lag))
    return out


@pytest.mark.parametrize("fuse", [2, 1])
def test_every_step_reads_its_own_value_over_the_wrap(fuse):
    """T = 1,000,000 and a call of three steps that starts at T - 1: the
    two-step kernel call reads the table's last value and its first, the
    one-step call behind it the second, at the iteration the loops
    carried on (at ``fuse`` 1: a loop of two one-step calls and one
    after it).  The table is an operand in HBM, sliced a column a step;
    no kernel sees more than its call's values.  Bit for bit the XLA
    step, whose ``NodeCtx.setting`` reads ``series_overrides`` at every
    step, and the plain reference within rounding."""
    _, _, lat, _ = case(64)
    want, params = sound(64)
    it = band(64, fuse)
    assert it.supports_series and it.pad_rows == 0
    got = fields_of(it, lat, params)
    np.testing.assert_array_equal(got, want)
    others = dict(wrong())
    assert np.abs(got[:9] - others.pop("ref")).max() < TOL
    for name, other in others.items():
        assert np.abs(got[:9] - other).max() > 100 * TOL, name
    # the account names what the series costs: no plane a step
    assert it.account(NITER, True)["series_planes"] == 0
    assert "series_planes" not in it.account(NITER)


def test_two_series_two_zones_on_ghost_rows():
    """52 rows are no multiple of a band: the band stands on ghost rows,
    refreshed before every call.  ``Velocity`` on the inlet's zone and
    ``Density`` on the outlet's at once, each its own sawtooth: two rows
    of the table, two selects in the kernel (the two-step call's values
    are a step's rows after a step's), at an even length (which ends in
    two one-step calls)."""
    _, m, lat, _ = case(52)
    params = series_on(lat, Velocity=sawtooth(HORIZON),
                       Density=sawtooth(HORIZON, 1.0)[::-1])
    rows = pallas_d2q9.series_rows(m, params.series_map)
    assert sorted(rows) == [("den", OUTLET), ("vel", INLET)]
    it = band(52, 2)
    assert it.pad_rows > 0
    got = fields_of(it, lat, params, 4)
    np.testing.assert_array_equal(
        got, fields_of(xla_step(52), lat, params, 4))
    # either series frozen at its first value is another answer
    for r in range(2):
        table = params.time_series
        frozen = params.replace(time_series=table.at[r].set(table[r, 0]))
        assert np.abs(got - fields_of(xla_step(52), lat, frozen, 4)
                      ).max() > 100 * TOL


@pytest.mark.slow
def test_a_pressure_series_fills_the_density_plane():
    """``d2q9_new`` has no Density: its kernels' density plane is
    1 + 3 p (``zonal_planes``), and so is a series on ``Pressure``."""
    _, m, lat, _ = case(64, "d2q9_new")
    params = series_on(lat, Velocity=sawtooth(HORIZON),
                       Pressure=sawtooth(HORIZON, 0.0)[::-1])
    assert sorted(pallas_d2q9.series_rows(m, params.series_map)) \
        == [("den", OUTLET), ("vel", INLET)]
    np.testing.assert_array_equal(
        fields_of(band(64, 2, "d2q9_new"), lat, params),
        fields_of(xla_step(64, "d2q9_new"), lat, params))


def test_a_series_attached_after_the_first_selection_reselects(
        monkeypatch):
    """Through dispatch: a lattice on the tuned band is given a series
    and selects the same family again, series and all, with nothing
    rejected: ``set_setting_series`` invalidates the engine, the
    VMEM-resident engine (which takes no series) leaves the chain, and
    the tail stays the generic band's one step.  What the fused span
    then says of a run: ``tests/test_tail_engine.py::
    test_tail_engine_matches_the_xla_step[series]``."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    _, m, lat, _ = case(64)
    try:
        assert lat._fast_path() is not None
        assert [c.tag for c in lat._fast_chain] == [
            "pallas_resident[d2q9,fuse=8]", "pallas_2d[d2q9,fuse=2]"]
        lat.set_setting_series("Velocity", sawtooth(HORIZON), zone=INLET)
        assert lat._fast_path().supports_series
        assert [c.tag for c in lat._fast_chain] == [SERIES_TAG]
        assert lat._tail_name == "pallas_generic[d2q9,fuse=1]"
        # a plan at Mosaic's default limit: the proven engine, unprobed
        assert not lat._fast_probing
    finally:
        lat.params = lat.params.replace(time_series=None, series_map=())
        lat._series, lat._fast_tried = {}, False


def _calls(jaxpr, found=None) -> list:
    """``(name, operand shapes)`` of every ``pallas_call`` of a jaxpr,
    in order."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"],
                          [tuple(v.aval.shape) for v in eqn.invars]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _calls(sub, found)
    return found


def _carries(jaxpr, found=None) -> list:
    """The shapes each ``scan`` of a jaxpr carries, in order."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            k, n = eqn.params["num_consts"], eqn.params["num_carry"]
            found.append([tuple(v.aval.shape)
                          for v in eqn.invars[k:k + n]])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _carries(sub, found)
    return found


def test_without_a_series_the_programs_are_what_they_were():
    """No new operand on the kernels of a lattice without a series, and
    nothing beside the state in its loops: the two-step kernel takes
    (sett, state, aux), the one-step kernel (sett, state, flags, vel,
    den), under the names the chip's records know, and no table is
    sliced.  With a series each takes the call's values as ONE more
    operand, ``(steps * rows,)`` in SMEM, and the loops carry the
    iteration.  The same engine object runs both: the flavour is the
    program's, chosen where it is traced."""
    _, m, lat, _ = case(64)
    it = band(64, 2)
    state, plane = (11, 64, 128), (64, 128)
    n_sett = tuple(lat.params.settings.shape)

    def program(params):
        return jax.make_jaxpr(lambda s, p: it(s, p, NITER))(
            lat.state, params).jaxpr

    plain = program(lat.params)
    assert _calls(plain) == [
        ("d2q9_band_fuse2", [n_sett, state, (3,) + plane]),
        ("d2q9_band_fuse1", [n_sett, state, plane, plane, plane])]
    assert _carries(plain) == [[state]] * 2      # one loop a kernel
    assert "dynamic_slice" not in str(plain)
    series = program(sound(64)[1])
    assert _calls(series) == [
        ("d2q9_band_fuse2_series", [n_sett, (2,), state, (3,) + plane]),
        ("d2q9_band_fuse1_series",
         [n_sett, (1,), state, plane, plane, plane])]
    assert _carries(series) == [[state, ()]] * 2
    assert "dynamic_slice" in str(series)
