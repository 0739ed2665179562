"""The sharded 3D loop (``parallel/halo.make_sharded_pallas_iterate``'s 3D
mode on ``ops/pallas_d3q``'s ``ext`` flavour of the fused kernel): each
z-shard is advanced K steps a call on windows of its own slabs and the
neighbours' K exchanged slabs, which the kernel takes as operands of
their own.  Held here, on the CPU's devices in interpret mode, to the
one-device engines (the XLA step and the one-chip fused kernel: to the
bit, as the 2D mode's tests hold theirs) and to the plain reference
(``benchmark/reference/d3q27_cumulant.py``, which imports nothing of the
program) within a stated bound; at K = 1, 2 and 3, on y-tiled windows
and on whole planes, with steps left over; and through ``Lattice``: the
chain of dispatch, the engine's account on ``iterate.fused``, and the
initial field made in shards."""

import json
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import d3q27_cumulant as reference
from tclb_tpu import telemetry
from tclb_tpu.control.solver import run_config_string
from tclb_tpu.core.lattice import Lattice, make_iterate
from tclb_tpu.models import get_model
from tclb_tpu.ops import lbm, pallas_d3q
from tclb_tpu.parallel import halo
from tclb_tpu.parallel.mesh import make_mesh

CHIPS = 4
LOCAL = (8, 32, 64)                 # one shard
SHAPE = (CHIPS * LOCAL[0],) + LOCAL[1:]
PAR = {"nu": 0.02, "ForceX": 1e-5}
# float32 against the float32 plain reference after 7 steps: the engine
# agrees with the XLA step to the bit; the rest is the same arithmetic
# in another order on populations up to 0.3.  A halo slab taken from the
# wrong place reads 1e-3 and more on the perturbed field
TOL32 = 2e-6
# (K, the VMEM the planner may count on, the plan of one shard, that of
# the steps left over).  With little VMEM no kernel holds a 32 x 64
# plane whole and the planner tiles the shard; None: whole planes
PLANS = {
    "tiled-K1": (1, 2_000_000, (1, 8, 1), (1, 8, 1)),
    # bands of 2 slabs no shorter than the halo: the halo goes as a block
    "tiled-K2": (2, 5_000_000, (2, 8, 2), (2, 32, 1)),
    # bands of 1 slab under 3 halo slabs: the halo goes slab by slab,
    # and the second band's reaches past the shard's end too
    "tiled-K3": (3, 6_000_000, (1, 8, 3), (2, 32, 1)),
    "whole-windows-K2": (2, 6_000_000, (1, 32, 2), (2, 32, 1)),
    "whole-K1": (1, None, (8, 32, 1), (8, 32, 1)),
    "whole-K2": (2, None, (8, 32, 2), (8, 32, 1)),
    "whole-K3": (3, None, (8, 32, 3), (8, 32, 1)),
}


def _need_chips():
    if len(jax.devices()) < CHIPS:
        pytest.skip(f"needs {CHIPS} devices")


@lru_cache(maxsize=None)
def _case():
    """A walled d3q27_cumulant channel split in z over four devices,
    with wall blocks across the first seam and across the periodic seam
    of the whole box, from a field that differs from node to node: the
    model, the mesh, the node types present, the flags, the initial
    fields, and the one-device XLA engine's and the plain reference's
    populations after each of 7 steps."""
    m = get_model("d3q27_cumulant")
    flags = np.full(SHAPE, m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0, :] = flags[:, -1, :] = m.flag_for("Wall")
    flags[LOCAL[0] - 2:LOCAL[0] + 2, 6:12, 10:20] = m.flag_for("Wall")
    flags[-1:, 20:26, 30:40] = flags[:2, 20:26, 30:40] = m.flag_for("Wall")
    lat = Lattice(m, SHAPE, dtype=jnp.float32, settings=PAR)
    lat.set_flags(flags)
    lat.init()
    f = np.array(lat.state.fields)
    nf = len(m.groups["f"])
    f[:nf] *= (1.0 + 0.01 * np.random.default_rng(53).standard_normal(
        f[:nf].shape)).astype(np.float32)
    present = lbm.present_types(m, flags)
    mesh = make_mesh(SHAPE, devices=jax.devices()[:CHIPS],
                     decomposition={"z": CHIPS, "y": 1, "x": 1})
    wall = flags == m.flag_for("Wall")
    plain = jax.jit(reference.make_step(
        {"wall": wall, "collide": ~wall, "inlet": np.zeros(SHAPE, bool),
         "outlet": np.zeros(SHAPE, bool)}, PAR))
    step = jax.jit(make_iterate(m, present=present),
                   static_argnames=("niter",))
    state, p = lat.state.replace(fields=jnp.asarray(f)), jnp.asarray(f[:nf])
    xla, ref = [], []
    for _ in range(7):
        state = step(state, lat.params, 1)
        p = plain(p)
        xla.append(np.asarray(state.fields))
        ref.append(np.asarray(p))
    return m, mesh, present, flags, f, xla, ref


def _sharded_lattice(fields=None):
    m, mesh, _, flags, f = _case()[:5]
    lat = Lattice(m, SHAPE, dtype=jnp.float32, settings=PAR, mesh=mesh)
    lat.set_flags(flags)
    lat.init()
    lat.state = lat.state.replace(fields=jax.device_put(
        f if fields is None else fields, lat.state.fields.sharding))
    return lat


@pytest.mark.parametrize("plan,niter", [(p, 7) for p in sorted(PLANS)]
                         + [("tiled-K2", 4), ("whole-K3", 4)])
def test_sharded_fused_matches_one_device_and_reference(plan, niter):
    """``niter`` steps on the mesh: 7 is two calls and a step over at
    K = 3 and three and one at K = 2; 4 is one and one at K = 3, and two
    and none at K = 2."""
    _need_chips()
    K, budget, shard_plan, rest_plan = PLANS[plan]
    m, mesh, present, _, _, xla, ref = _case()
    it = halo.make_sharded_pallas_iterate(
        m, mesh, SHAPE, jnp.float32, present=present, interpret=True,
        fuse=K, vmem_budget=budget)
    assert it.fuse == K and it.plan == shard_plan and it.unproven
    did = it.account(niter)
    assert (did["kernel_calls"], did["remainder_steps"], did["shards"],
            did["halo_operand_slabs"]) == (niter // K + niter % K,
                                           niter % K, CHIPS, K)
    assert (did["z_bands"], did["band_slabs"], did["halo_slabs"]) \
        == (LOCAL[0] // shard_plan[0], shard_plan[0], K)
    assert (did["y_bands"], did["band_rows"], did["halo_rows"]) \
        == (LOCAL[1] // shard_plan[1], shard_plan[1],
            8 if shard_plan[1] < LOCAL[1] else 0)
    if budget is not None:
        assert pallas_d3q.tile_plan(m, LOCAL, 4, 1, budget) == rest_plan
    lat = _sharded_lattice()
    got = it(lat.state, lat.params, niter)
    assert int(got.iteration) == niter
    assert got.fields.sharding == lat.state.flags.sharding.update(
        spec=halo.field_spec(mesh))
    fields = np.asarray(got.fields)
    # to the bit: the kernel's arithmetic is the XLA step's, only where
    # the windows at a shard's ends read their halo slabs from differs
    np.testing.assert_array_equal(fields, xla[niter - 1])
    assert np.abs(fields[:27] - ref[niter - 1]).max() < TOL32
    assert np.abs(ref[niter - 1] - _case()[4][:27]).max() > 1e-4


def test_sharded_fused_is_the_one_chip_kernel(plan="tiled-K3"):
    """The same plan on one device, z periodic inside the array: the
    one-chip fused kernel, bit for bit."""
    _need_chips()
    K, budget, shard_plan, _ = PLANS[plan]
    m, mesh, present, flags, f = _case()[:5]
    kw = {} if budget is None else {"vmem_budget": budget}
    it = halo.make_sharded_pallas_iterate(
        m, mesh, SHAPE, jnp.float32, present=present, interpret=True,
        fuse=K, **kw)
    one = pallas_d3q.make_pallas_iterate(
        m, SHAPE, jnp.float32, interpret=True, present=present, fuse=K,
        fuse_bz=None if budget else shard_plan[0], **kw)
    lat = _sharded_lattice()
    ref = Lattice(m, SHAPE, dtype=jnp.float32, settings=PAR)
    ref.set_flags(flags)
    ref.state = ref.state.replace(fields=jnp.asarray(f))
    got = it(lat.state, lat.params, 2 * K + 1)
    want = one(ref.state, ref.params, 2 * K + 1)
    np.testing.assert_array_equal(np.asarray(got.fields),
                                  np.asarray(want.fields))


def test_supports_answers_by_the_shard_plan():
    m = get_model("d3q27_cumulant")
    # the shard of tgv384: no whole plane, a tiled plan
    assert pallas_d3q._slab_depth(m, 96, 384, 384) is None
    assert pallas_d3q.supports(m, (96, 384, 384), jnp.float32,
                               ext_halo=True)
    assert not pallas_d3q.supports(m, (96, 384, 384), jnp.bfloat16,
                                   ext_halo=True)     # f32 only
    # a shard too thin for any fused depth's halo
    assert not pallas_d3q.supports(m, (1, 384, 384), jnp.float32,
                                   ext_halo=True)
    assert pallas_d3q.supports(m, LOCAL, jnp.float32, ext_halo=True)


TGV = """<CLBConfig version="2.0" model="d3q27_cumulant" output="output/">
    <Geometry nx="64" ny="32" nz="32"><MRT><Box/></MRT></Geometry>
    <Model><Params Velocity="0.05"/><Params nu="0.002"/></Model>
    <CallPython module="tclb_tpu.control.initial" function="taylor_green"/>
    <Log Iterations="5"/>
    <Solve Iterations="10"/>
</CLBConfig>"""


def _tgv(tmp_path, monkeypatch, mesh, fastpath, trace=None):
    monkeypatch.setenv("TCLB_FASTPATH", fastpath)
    if trace is not None:
        telemetry.enable(str(trace))
    try:
        return run_config_string(TGV, get_model("d3q27_cumulant"),
                                 dtype=jnp.float32, mesh=mesh,
                                 output=str(tmp_path) + "/")
    finally:
        if trace is not None:
            telemetry.disable()


def test_lattice_on_a_z_split_mesh(tmp_path, monkeypatch):
    """``tclb run <3D case> --mesh 4x1x1`` as ``run_case`` drives it: the
    chain of dispatch (the fused plan probed, the K = 1 plan under it),
    no fallback, the shard's account on ``iterate.fused``, the initial
    field's element saying that it was made in shards; and the fields
    equal to the one-device XLA run's."""
    _need_chips()
    mesh = make_mesh(SHAPE, devices=jax.devices()[:CHIPS],
                     decomposition={"z": CHIPS, "y": 1, "x": 1})
    trace = tmp_path / "t.jsonl"
    solver = _tgv(tmp_path, monkeypatch, mesh, "force", trace)
    lat = solver.lattice
    tags = [c.tag for c in lat._fast_chain]
    K = lat._fast.fuse
    where = "{'z': 4, 'y': 1, 'x': 1}"
    assert K >= 2 and tags == [f"pallas_sharded[{where},fuse={K}]",
                               f"pallas_sharded[{where},fuse=1]"]
    assert [c.probe for c in lat._fast_chain] == [True, True]
    assert lat._fast_name == tags[0] and lat._tail_name is None
    events = [json.loads(x) for x in trace.read_text().splitlines()]
    assert not [e for e in events if e.get("kind") == "engine_fallback"]
    fused = [e for e in events if e.get("name") == "iterate.fused"
             and "kernel_calls" in e]
    assert fused and all(
        (e["shards"], e["halo_operand_slabs"], e["halo_slabs"],
         e["kernel_calls"], e["remainder_steps"])
        == (CHIPS, K, K, 4 // K + 4 % K, 4 % K) for e in fused)
    assert all(e["halo_bytes"] > 0 for e in fused)
    element = [e for e in events if e.get("name") == "startup.element"
               and "bytes" in e]
    assert [(e["element"], e["sharded"], e["bytes"]) for e in element] \
        == [("CallPython", True, 27 * int(np.prod(SHAPE)) * 4)]
    from tclb_tpu.telemetry import report
    text = report.format_text(report.summarize(events))
    assert f"halo_operand_slabs {K}" in text and "shards 4" in text
    assert "sharded True bytes" in text
    one = _tgv(tmp_path, monkeypatch, None, "0")
    np.testing.assert_array_equal(np.asarray(lat.state.fields),
                                  np.asarray(one.lattice.state.fields))


def test_sharded_initial_field_is_the_one_device_field(tmp_path,
                                                       monkeypatch):
    """The Taylor-Green field made in the lattice's shards
    (``out_shardings``) equals the one made on one device, plane by
    plane, and every array of the lattice's size lies in shards from the
    start: no device holds the whole lattice."""
    _need_chips()
    mesh = make_mesh(SHAPE, devices=jax.devices()[:CHIPS],
                     decomposition={"z": CHIPS, "y": 1, "x": 1})
    m = get_model("d3q27_cumulant")
    fresh = Lattice(m, SHAPE, dtype=jnp.float32, mesh=mesh)
    for arr in (fresh.state.fields, fresh.state.flags):
        assert {s.data.shape[-3] for s in arr.addressable_shards} \
            == {LOCAL[0]}
        assert len({s.device for s in arr.addressable_shards}) == CHIPS
    case = TGV.replace('<Solve Iterations="10"/>', "")
    monkeypatch.setenv("TCLB_FASTPATH", "0")

    def fields(mesh):
        return run_config_string(case, m, dtype=jnp.float32, mesh=mesh,
                                 output=str(tmp_path) + "/"
                                 ).lattice.state.fields
    sharded, one = fields(mesh), fields(None)
    assert {s.data.shape for s in sharded.addressable_shards} \
        == {(m.n_storage,) + LOCAL}
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(one))
    assert np.ptp(np.asarray(one)[13]) > 1e-4


def test_a_refused_shard_says_why(monkeypatch, tmp_path):
    """A mesh the sharded engine cannot take (split in y): dispatch
    lists nothing, says ``fused_rejected`` with the reason, and the
    sharded XLA step runs."""
    _need_chips()
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    mesh = make_mesh(SHAPE, devices=jax.devices()[:CHIPS],
                     decomposition={"z": 2, "y": 2, "x": 1})
    m = get_model("d3q27_cumulant")
    lat = Lattice(m, SHAPE, dtype=jnp.float32, settings=PAR, mesh=mesh)
    lat.set_flags(np.full(SHAPE, m.flag_for("MRT"), dtype=np.uint16))
    trace = tmp_path / "t.jsonl"
    telemetry.enable(str(trace))
    try:
        assert lat._build_fast() == []
    finally:
        telemetry.disable()
    rej = [json.loads(x) for x in trace.read_text().splitlines()
           if '"fused_rejected"' in x]
    assert [(e["engine"], e["reason"].split(":")[0]) for e in rej] \
        == [("pallas_sharded", "mesh")]
