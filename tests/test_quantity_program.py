"""`Lattice.get_quantity` runs one compiled program a quantity
(`core/lattice.py:quantity_program`): the same values as the quantity
function called operation by operation, built once for a model and
reused by every lattice of it, partitioned on a mesh, and short enough
for `<Failcheck>` to keep its behaviour."""

import functools
import xml.etree.ElementTree as ET

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu import Lattice, get_model, telemetry
from tclb_tpu.core import shift as ddf
from tclb_tpu.core.lattice import NodeCtx, quantity_program
from tclb_tpu.parallel.mesh import make_mesh

COUNTER = "quantity.programs_built"

CASES = {
    "d2q9": ((16, 24), {"nu": 0.05, "Velocity": 0.02}, "MRT"),
    "d2q9_kuper": ((16, 24), {"nu": 0.1, "Density": 1.0, "Magic": 0.01,
                              "Temperature": 0.56}, "MRT"),
    "d3q27_cumulant": ((6, 8, 12), {"nu": 0.05, "ForceX": 1e-5}, "MRT"),
}


@pytest.fixture(autouse=True)
def _sink_off():
    """Telemetry is process-global: every test starts and ends disabled."""
    telemetry.disable()
    yield
    telemetry.disable()


@pytest.fixture
def seen():
    """The event documents of a test, through a subscriber of its own."""
    docs = []
    telemetry.subscribe(docs.append)
    yield docs
    telemetry.unsubscribe(docs.append)


def _lattice(name, seed=0, shape=None, **kw):
    """A small lattice of `name` a few steps in, its populations stirred
    so that no quantity is a constant plane."""
    m = get_model(name)
    default, settings, collision = CASES[name]
    shape = shape or default
    lat = Lattice(m, shape, dtype=jnp.float32, settings=settings, **kw)
    flags = np.full(shape, m.flag_for(collision), dtype=np.uint16)
    flags[0] = flags[-1] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    noise = np.random.default_rng(seed).uniform(
        -0.01, 0.01, lat.state.fields.shape)
    stirred = (np.asarray(lat.state.fields, dtype=np.float64)
               + noise).astype(lat.state.fields.dtype)
    lat.state = lat.state.replace(
        fields=jax.device_put(stirred, lat.state.fields.sharding))
    lat.iterate(2)
    return lat


@functools.lru_cache(maxsize=None)
def _read_only(name, storage):
    """One lattice a model and storage for every quantity's comparison:
    evaluating a quantity leaves the state as it was."""
    kw = {} if storage == "f32" else {"storage_dtype": jnp.bfloat16}
    return _lattice(name, **kw)


def _eager(lat, name):
    """The quantity function on the same NodeCtx, operation by operation:
    what `get_quantity` was before it compiled."""
    fields = ddf.widen_stack(lat.state.fields, lat.dtype, lat._shift_block)
    ctx = NodeCtx(lat.model, fields, fields, lat.state.flags, lat.params,
                  iteration=lat.state.iteration, avg_start=lat.avg_start)
    with jax.default_matmul_precision("highest"):
        return np.asarray(lat.model.quantity_fns[name](ctx))


def _few_ulp(lat, name, want):
    """Four f32 ulp of the largest value that enters the quantity: the
    plane's own, but for kuper's `F`, a difference of eight products of
    `phi` that cancel to a hundredth of their size, and the cumulant's
    `P`, the populations' sum less 1."""
    scale = np.abs(want).max()
    if (lat.model.name, name) == ("d2q9_kuper", "F"):
        phi = np.asarray(lat.state.fields[lat.model.storage_index["phi"]],
                         dtype=np.float32)
        scale = max(scale, (phi * phi).max())
    if (lat.model.name, name) == ("d3q27_cumulant", "P"):
        scale = 1.0
    return 4 * np.finfo(np.float32).eps * scale


def _quantities():
    return [(name, q.name) for name in CASES
            for q in get_model(name).quantities if not q.adjoint]


@pytest.mark.parametrize("storage", ["f32", "shifted_bf16"])
@pytest.mark.parametrize("model,quantity", _quantities())
def test_compiled_equals_eager(model, quantity, storage):
    """Scalars and vectors, `F` with its eight rolls of `phi`, to a few
    ulp of the plane's largest value: fusing may contract a multiply and
    an add, nothing more."""
    lat = _read_only(model, storage)
    assert lat.storage_repr == ("raw" if storage == "f32" else "shifted")
    got = lat.get_quantity(quantity)
    want = _eager(lat, quantity)
    spec = next(q for q in lat.model.quantities if q.name == quantity)
    assert got.dtype == jnp.float32
    assert got.shape == ((3,) if spec.vector else ()) + lat.shape
    assert got.shape == want.shape
    assert np.isfinite(want).all() and np.ptp(want) > 0
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=_few_ulp(lat, quantity, want))


def _evals(docs):
    return [e for e in docs if e["kind"] == "span"
            and e["name"] == "quantity.eval"]


def _eval(lat, name):
    """`get_quantity` under the span the Solver opens round it."""
    with telemetry.span("quantity.eval", quantity=name) as sp:
        return sp.sync(lat.get_quantity(name))


def test_one_program_for_every_lattice_of_a_model(seen):
    """After `iterate`, `set_setting`, `reset_average` and from a second
    lattice of the same model and shape nothing compiles: the counter
    stands still and the span says `reused`."""
    shape = (6, 10, 14)     # this test's own: the first call builds
    lat = _lattice("d3q27_cumulant", shape=shape)
    names = ["U", "avgU", "Rho"]
    for name in names:
        _eval(lat, name)
    built = telemetry.counters().get(COUNTER, 0)
    assert built == len(names)
    assert [e["program"] for e in _evals(seen)] == ["built"] * len(names)
    first = {name: np.asarray(lat.get_quantity(name)) for name in names}

    del seen[:]
    lat.iterate(3)
    for name in names:
        _eval(lat, name)
    lat.set_setting("ForceX", 3e-5)
    lat.set_setting("nu", 0.07, zone=0)
    for name in names:
        _eval(lat, name)
    assert lat.avg_start == 0
    lat.reset_average()
    assert lat.avg_start == 5
    for name in names:
        _eval(lat, name)
    other = _lattice("d3q27_cumulant", seed=1, shape=shape)
    values = {name: np.asarray(_eval(other, name)) for name in names}
    assert [e["program"] for e in _evals(seen)] == ["reused"] * 12
    assert telemetry.counters().get(COUNTER, 0) == built
    # the arguments are read, not baked in: a second lattice's state and
    # the new averaging window give other values from the same executable
    assert np.abs(values["U"] - first["U"]).max() > 0
    for name in names:
        np.testing.assert_allclose(
            values[name], _eager(other, name), rtol=0,
            atol=4e-7 * np.abs(values[name]).max())
    lat.iterate(2)
    np.testing.assert_allclose(      # two samples since the reset
        np.asarray(lat.get_quantity("avgU")), _eager(lat, "avgU"),
        rtol=1e-6, atol=0)
    # a new shape is a new executable of the same program
    _eval(_lattice("d3q27_cumulant", shape=(6, 10, 18)), "U")
    assert _evals(seen)[-1]["program"] == "built"
    assert telemetry.counters()[COUNTER] == built + 1


def test_program_is_keyed_by_what_it_depends_on():
    m = get_model("d2q9")
    a = Lattice(m, (8, 16), dtype=jnp.float32)
    b = Lattice(m, (12, 20), dtype=jnp.float32)

    def key(lat, q):
        return quantity_program(lat.model, q, jnp.dtype(lat.dtype),
                                lat.storage_repr)

    assert key(a, "U") is key(b, "U")
    assert key(a, "U") is not key(a, "Rho")
    wide = Lattice(m, (8, 16), dtype=jnp.float64)
    assert key(wide, "U") is not key(a, "U")
    narrow = Lattice(m, (8, 16), dtype=jnp.float32,
                     storage_dtype=jnp.bfloat16)
    assert narrow.storage_repr == "shifted"
    assert key(narrow, "U") is not key(a, "U")


def test_nothing_is_recorded_with_telemetry_off(seen):
    telemetry.unsubscribe(seen.append)
    assert not telemetry.enabled()
    lat = _lattice("d2q9", shape=(10, 18))      # a shape that compiles
    _eval(lat, "U")
    assert seen == [] and telemetry.counters() == {}
    telemetry.subscribe(seen.append)
    _eval(lat, "U")
    assert [e["program"] for e in _evals(seen)] == ["reused"]
    assert COUNTER not in telemetry.counters()


@pytest.mark.parametrize("model", ["d2q9", "d2q9_kuper"])
def test_mesh_4x1_equals_one_device_and_stays_sharded(model):
    shape = (32, 24)
    mesh = make_mesh(shape, devices=jax.devices()[:4],
                     decomposition={"y": 4, "x": 1})
    one = _lattice(model, shape=shape)
    split = _lattice(model, shape=shape, mesh=mesh)
    for q in one.model.quantities:
        if q.adjoint:
            continue
        got = split.get_quantity(q.name)
        want = np.asarray(one.get_quantity(q.name))
        # sharded over y like the state's planes, every chip its rows
        assert len(got.sharding.device_set) == 4
        rows = sorted((s.index[-2].start or 0, s.data.shape[-2])
                      for s in got.addressable_shards)
        assert rows == [(0, 8), (8, 8), (16, 8), (24, 8)]
        assert all(s.data.shape[-1] == 24 for s in got.addressable_shards)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=_few_ulp(one, q.name, want))


RESCUE = '<Failcheck Iterations="2"><TXT/></Failcheck>'


def test_failcheck_names_the_quantity_counts_and_rescues(tmp_path, seen):
    from tclb_tpu.control.handlers import cbFailcheck
    from tclb_tpu.control.solver import ITERATION_STOP, Solver

    m = get_model("d2q9_kuper")
    s = Solver(m, output=str(tmp_path / "out") + "/")
    s.set_size((12, 16))
    s.lattice.set_flags(
        np.full((12, 16), m.flag_for("MRT"), dtype=np.uint16))
    for name, value in CASES["d2q9_kuper"][1].items():
        s.lattice.set_setting(name, value)
    s.lattice.init()
    h = cbFailcheck(ET.fromstring(RESCUE), s)
    h.init()
    assert h.do_it() == 0
    assert not any(e["kind"] == "failcheck" for e in seen)
    assert not list(tmp_path.rglob("*TXT_*"))

    f = np.asarray(s.lattice.state.fields).copy()
    f[0, 2, 3] = np.nan         # one rest population: Rho of one node
    f[0, 7, 9] = np.inf
    s.lattice.state = s.lattice.state.replace(fields=jnp.asarray(f))
    assert h.do_it() == ITERATION_STOP
    fc, = [e for e in seen if e["kind"] == "failcheck"]
    assert fc["quantity"] == "Rho" and fc["n_bad"] == 2
    assert fc["iteration"] == 0
    # the scan stopped at the first bad quantity; the rescue child ran
    # and wrote every quantity, through the same compiled programs
    at = seen.index(fc)
    scans = [e["quantity"] for e in seen[:at] if e["kind"] == "span"
             and e["name"] == "failcheck.scan"]
    names = [q.name for q in m.quantities if not q.adjoint]
    assert scans == names + ["Rho"]
    rescue = _evals(seen[at:])
    assert {e["quantity"] for e in rescue} == set(names)
    assert all(e["program"] == "reused" for e in rescue)
    written = sorted(p.name for p in tmp_path.rglob("*TXT_*"))
    assert len(written) == len(names)
