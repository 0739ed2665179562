"""`Lattice.get_quantity` runs one compiled program a quantity
(`core/lattice.py:quantity_program`): the same values as the quantity
function called operation by operation, built once for a model and
reused by every lattice of it, partitioned on a mesh.  `<Failcheck>` runs
its twin (`nonfinite_program`: the same body, then `sum(~isfinite)` in
the same `jit`): a count a quantity comes to the host, never a plane."""

import functools
import xml.etree.ElementTree as ET

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu import Lattice, get_model, telemetry
from tclb_tpu.core import shift as ddf
from tclb_tpu.core.lattice import (NodeCtx, nonfinite_program,
                                   quantity_program)
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


# --------------------------------------------------------------------------- #
# <Failcheck>: the count programs, and the handler over them
# --------------------------------------------------------------------------- #

SCANS = "failcheck.device_scans"

# (storage plane, node) -> value, by the model's dimension: one rest
# population, or several populations and several nodes
PLANTED = {
    "one": {2: [(0, (2, 3), np.nan), (0, (7, 9), np.inf)],
            3: [(0, (2, 3, 5), np.nan), (0, (4, 6, 9), np.inf)]},
    "several": {2: [(0, (2, 3), np.nan), (3, (2, 3), -np.inf),
                    (5, (9, 20), np.nan), (8, (14, 0), np.inf)],
                3: [(0, (2, 3, 5), np.nan), (7, (2, 3, 5), -np.inf),
                    (12, (3, 0, 11), np.nan), (26, (4, 7, 0), np.inf)]},
}


def _plant(lat, where):
    """`lat` with the listed values written into its stored planes, on
    the sharding the state had."""
    f = np.asarray(lat.state.fields).copy()
    for plane, node, value in where:
        f[(plane,) + tuple(node)] = value
    lat.state = lat.state.replace(
        fields=jax.device_put(f, lat.state.fields.sharding))
    return lat


@functools.lru_cache(maxsize=None)
def _spoiled(name, planted, storage="f32"):
    kw = {} if storage == "f32" else {"storage_dtype": jnp.bfloat16}
    lat = _lattice(name, **kw)
    return _plant(lat, PLANTED[planted][lat.model.ndim])


def _host_scan(lat, name):
    """What `<Failcheck>` counted when the plane came to the host."""
    plane = np.asarray(lat.get_quantity(name))
    return int(plane.size - np.isfinite(plane).sum())


@pytest.mark.parametrize("planted", ["one", "several"])
@pytest.mark.parametrize("model,quantity", _quantities())
def test_count_equals_the_host_scan(model, quantity, planted):
    """NaN and infinity of either sign, in one population and in
    several: the device's count is the host scan's, to the digit; a
    vector quantity counts every component."""
    lat = _spoiled(model, planted)
    got = lat.count_nonfinite(quantity)
    assert got.shape == () and got.dtype == jnp.int32
    want = _host_scan(lat, quantity)
    assert int(got) == want
    spec = next(q for q in lat.model.quantities if q.name == quantity)
    if quantity == "Rho":
        assert want == len({n for _, n, _ in
                            PLANTED[planted][lat.model.ndim]})
    if quantity == "U":     # both in-plane components of a NaN node
        assert spec.vector and want >= lat.model.ndim
    # the sound lattice of the same model, through the same executable
    assert int(_read_only(model, "f32").count_nonfinite(quantity)) == 0


@pytest.mark.parametrize("model,quantity", [
    (name, q) for name, q in _quantities() if name != "d3q27_cumulant"])
def test_bfloat16_storage_counts_as_f32_does(model, quantity):
    """The count program widens through `ddf.widen_stack` as the plane's
    does: NaN and infinity survive the shifted bfloat16 rung."""
    narrow = _spoiled(model, "several", "shifted_bf16")
    assert narrow.storage_repr == "shifted"
    assert narrow.state.fields.dtype == jnp.bfloat16
    got = int(narrow.count_nonfinite(quantity))
    assert got == _host_scan(narrow, quantity)
    assert got == int(_spoiled(model, "several").count_nonfinite(quantity))
    assert int(_read_only(model, "shifted_bf16")
               .count_nonfinite(quantity)) == 0


@pytest.mark.parametrize("model", ["d2q9", "d2q9_kuper"])
def test_count_on_a_4x1_mesh_is_one_replicated_scalar(model):
    """The bad nodes in the last shard's rows: the state's sharding
    carries through the program and the sum ends on every device."""
    shape = (32, 24)
    mesh = make_mesh(shape, devices=jax.devices()[:4],
                     decomposition={"y": 4, "x": 1})
    where = [(0, (30, 5), np.nan), (4, (25, 23), np.inf)]
    one = _plant(_lattice(model, shape=shape), where)
    split = _plant(_lattice(model, shape=shape, mesh=mesh), where)
    assert len(split.state.fields.sharding.device_set) == 4
    for q in one.model.quantities:
        if q.adjoint:
            continue
        got = split.count_nonfinite(q.name)
        assert got.shape == () and got.dtype == jnp.int32
        assert got.sharding.is_fully_replicated
        assert len(got.sharding.device_set) == 4
        assert int(got) == _host_scan(one, q.name) \
            == int(one.count_nonfinite(q.name))
    assert int(split.count_nonfinite("Rho")) == 2
    sound = _lattice(model, shape=shape, mesh=mesh)
    assert int(sound.count_nonfinite("Rho")) == 0


def test_count_program_is_built_once_and_reused(seen):
    """Keyed as `quantity_program` is and apart from it; a second
    lattice of the model and shape reuses the executable."""
    m = get_model("d2q9")
    a = Lattice(m, (8, 16), dtype=jnp.float32)
    narrow = Lattice(m, (8, 16), dtype=jnp.float32,
                     storage_dtype=jnp.bfloat16)

    def key(lat, q, programs=nonfinite_program):
        return programs(lat.model, q, jnp.dtype(lat.dtype),
                        lat.storage_repr)

    assert key(a, "U") is key(Lattice(m, (12, 20), dtype=jnp.float32), "U")
    assert key(a, "U") is not key(a, "Rho")
    assert key(a, "U") is not key(narrow, "U")
    assert key(a, "U") is not key(Lattice(m, (8, 16), dtype=jnp.float64),
                                  "U")
    assert key(a, "U") is not key(a, "U", quantity_program)

    shape = (10, 14)        # this test's own: the first call builds
    lat = _lattice("d2q9", shape=shape)
    built = telemetry.counters().get(COUNTER, 0)

    def count(lattice, name):
        with telemetry.span("quantity.eval", quantity=name) as sp:
            return int(sp.sync(lattice.count_nonfinite(name)))

    assert [count(lat, q) for q in ("Rho", "U")] == [0, 0]
    lat.iterate(2)
    other = _plant(_lattice("d2q9", seed=1, shape=shape),
                   [(1, (3, 3), np.nan)])
    assert [count(lat, "Rho"), count(other, "Rho"), count(other, "U")] \
        == [0, 1, 2]
    assert [e["program"] for e in _evals(seen)] \
        == ["built", "built", "reused", "reused", "reused"]
    assert telemetry.counters()[COUNTER] == built + 2
    # the plane's program is another: its first call builds
    _eval(lat, "Rho")
    assert _evals(seen)[-1]["program"] == "built"


RESCUE = '<Failcheck Iterations="2"><TXT/></Failcheck>'


def _solver(tmp_path, model="d2q9_kuper", shape=(12, 16)):
    from tclb_tpu.control.solver import Solver
    m = get_model(model)
    s = Solver(m, output=str(tmp_path / "out") + "/")
    s.set_size(shape)
    s.lattice.set_flags(
        np.full(shape, m.flag_for("MRT"), dtype=np.uint16))
    for name, value in CASES[model][1].items():
        s.lattice.set_setting(name, value)
    s.lattice.init()
    return s


def _failcheck(s, xml=RESCUE):
    from tclb_tpu.control.handlers import cbFailcheck
    h = cbFailcheck(ET.fromstring(xml), s)
    h.init()
    return h


def _run(h, iteration=0):
    """`do_it` under the span `<Solve>`'s loop opens round a handler,
    then the drain `<Solve>` ends with: a rescue `<VTK>` is whole."""
    with telemetry.span("handler", handler="cbFailcheck",
                        iteration=iteration):
        ret = h.do_it()
    h.solver.drain_output("solve_end")
    return ret


def _under(docs, parent, name):
    return [e for e in docs if e["kind"] == "span" and e["name"] == name
            and e["parent"] == parent["id"]]


def _handlers(docs):
    return [e for e in docs if e["kind"] == "span"
            and e["name"] == "handler"]


def test_failcheck_names_the_quantity_counts_and_rescues(tmp_path, seen):
    from tclb_tpu.control.solver import ITERATION_STOP

    s = _solver(tmp_path)
    m = s.model
    names = [q.name for q in m.quantities if not q.adjoint]
    for name in names:      # the planes' programs, as an earlier <VTK> would
        s.lattice.get_quantity(name)
    h = _failcheck(s)
    assert _run(h) == 0
    assert not any(e["kind"] == "failcheck" for e in seen)
    assert not list(tmp_path.rglob("*TXT_*"))

    _plant(s.lattice, [(0, (2, 3), np.nan),    # one rest population:
                       (0, (7, 9), np.inf)])   # Rho of two nodes
    del seen[:]
    assert _run(h) == ITERATION_STOP
    fc, = [e for e in seen if e["kind"] == "failcheck"]
    assert fc["quantity"] == "Rho" and fc["n_bad"] == 2
    assert fc["iteration"] == 0 and fc["engine"] == "xla"
    # every quantity's count program was dispatched, none scanned on the
    # host, and the counts came down together before the verdict
    at = seen.index(fc)
    spans = [e for e in seen[:at] if e["kind"] == "span"]
    assert [(e["name"], e.get("quantity")) for e in spans] \
        == [("quantity.eval", n) for n in names] + [("quantity.d2h", None)]
    assert all(e["reduce"] == "nonfinite" and e["program"] == "reused"
               for e in spans[:-1])
    assert spans[-1]["bytes"] == 4 * len(names)
    assert not any(e.get("name") == "failcheck.scan" for e in seen)
    # the rescue child ran once and wrote every quantity, through the
    # planes' compiled programs
    rescue = _evals(seen[at:])
    assert [e["quantity"] for e in rescue] == names
    assert all(e["program"] == "reused" and "reduce" not in e
               for e in rescue)
    written = sorted(p.name for p in tmp_path.rglob("*TXT_*"))
    assert len(written) == len(names)


def test_a_sound_state_brings_four_bytes_a_quantity_to_the_host(
        tmp_path, seen):
    s = _solver(tmp_path)
    names = [q.name for q in s.model.quantities if not q.adjoint]
    h = _failcheck(s)
    scans = telemetry.counters().get(SCANS, 0)
    for i in range(3):
        assert _run(h, iteration=i) == 0
        assert telemetry.counters()[SCANS] == scans + i + 1
    assert not any(e["kind"] == "failcheck" for e in seen)
    assert not list(tmp_path.rglob("*TXT_*"))
    for handler in _handlers(seen):
        assert (handler["scan"], handler["quantities"],
                handler["bytes_to_host"]) == ("device", len(names),
                                              4 * len(names))
        d2h = _under(seen, handler, "quantity.d2h")
        assert sum(e["bytes"] for e in d2h) == 4 * len(names)
        evals = _under(seen, handler, "quantity.eval")
        assert [e["quantity"] for e in evals] == names
        assert not any("bytes" in e for e in evals)     # no plane


@pytest.mark.parametrize("plane,what,checked", [
    ("phi", "Rho", ["Rho"]), ("phi", None, None),
    ("f[3]", "F,U", ["U", "F"]), ("f[3]", "P,U", ["U", "P"]),
    ("f[3]", "all", None)])
def test_what_selects_the_quantities_in_the_models_order(
        tmp_path, seen, plane, what, checked):
    """`phi` spoiled and no population: only `F` is bad, so a check of
    `Rho` alone passes.  A population spoiled: every quantity but `F`
    is bad, and of those checked the first in the model's order is
    named, whatever the attribute's order."""
    from tclb_tpu.control.solver import ITERATION_STOP

    s = _solver(tmp_path)
    m = s.model
    _plant(s.lattice, [(m.storage_index[plane], (5, 5), np.nan)])
    names = [q.name for q in m.quantities if not q.adjoint]
    bad = {n: _host_scan(s.lattice, n) for n in names}
    assert [n for n in names if bad[n]] \
        == (["F"] if plane == "phi" else ["Rho", "U", "P"])
    attr = "" if what is None else f' what="{what}"'
    h = _failcheck(s, f'<Failcheck Iterations="2"{attr}/>')
    del seen[:]
    got = _run(h)
    handler, = _handlers(seen)
    checked = checked or names
    assert [e["quantity"] for e in _under(seen, handler, "quantity.eval")] \
        == checked
    assert handler["quantities"] == len(checked)
    hits = [e for e in seen if e["kind"] == "failcheck"]
    first = next((n for n in checked if bad[n]), None)
    if first is None:
        assert got == 0 and hits == []
    else:
        assert got == ITERATION_STOP
        assert [(e["quantity"], e["n_bad"]) for e in hits] \
            == [(first, bad[first])]


def test_failcheck_with_telemetry_off_records_nothing(tmp_path, seen):
    from tclb_tpu.control.solver import ITERATION_STOP

    telemetry.unsubscribe(seen.append)
    assert not telemetry.enabled()
    s = _solver(tmp_path, model="d2q9", shape=(10, 22))
    h = _failcheck(s)
    assert h.do_it() == 0
    _plant(s.lattice, [(2, (4, 4), np.inf)])
    assert h.do_it() == ITERATION_STOP
    assert seen == [] and telemetry.counters() == {}
    assert len(list(tmp_path.rglob("*TXT_*"))) == 2


@pytest.mark.parametrize("children,files,spans", [
    ("<VTK/>", {"vti": 1, "pvti": 1}, {"output.vtk": 1}),
    ("<TXT/>", {"gz": 2}, {"output.txt": 1}),
    ('<VTK/><TXT what="Rho"/>', {"vti": 1, "pvti": 1, "gz": 1},
     {"output.vtk": 1, "output.txt": 1}),
    ('<VTK Iterations="5"/>', {"vti": 1, "pvti": 1}, {"output.vtk": 1}),
])
def test_each_rescue_child_runs_once(tmp_path, seen, children, files,
                                     spans):
    """A child without `Iterations` runs in its `init`, one with them in
    `do_it`: one file a child either way."""
    from tclb_tpu.control.solver import ITERATION_STOP

    s = _solver(tmp_path, model="d2q9")
    _plant(s.lattice, [(1, (3, 3), np.nan)])
    h = _failcheck(s, f'<Failcheck Iterations="2">{children}</Failcheck>')
    assert _run(h) == ITERATION_STOP
    written = [p.name.rsplit(".", 1)[-1]
               for p in (tmp_path / "out").iterdir()]
    assert {ext: written.count(ext) for ext in set(written)} == files
    outputs = [e["name"] for e in seen if e["kind"] == "span"
               and e["name"] in ("output.vtk", "output.txt")]
    assert {n: outputs.count(n) for n in set(outputs)} == spans
