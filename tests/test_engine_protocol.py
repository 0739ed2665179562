"""Every builder the engine chain can return gives an ``Engine``
(``ops/engine.py``), and its ``account`` is held to its schedule: the
kernel calls it reports are the ``pallas_call`` executions of the program
it runs, counted in the jaxpr (each equation times the lengths of the
scans round it), and its ``paired_calls`` are those a two-call loop body
issues (scans of unroll ``PAIR``).  Nothing runs: the programs are traced
in interpret mode at small shapes."""

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu.core.lattice import Lattice
from tclb_tpu.models import get_model
from tclb_tpu.ops import lbm, pallas_d2q9, pallas_d3q, pallas_generic
from tclb_tpu.ops.engine import PAIR, Engine
from tclb_tpu.parallel import halo
from tclb_tpu.parallel.mesh import make_mesh


def _lattice(name, shape, series=False, mesh=None):
    m = get_model(name)
    lat = Lattice(m, shape, dtype=jnp.float32, mesh=mesh)
    flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
    if "Wall" in m.node_types:
        flags[..., 0, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    if series:
        lat.set_setting_series(list(m.zonal_settings)[0],
                               np.linspace(0.01, 0.02, 5))
    lat.init()
    return m, lat, lbm.present_types(m, flags)


def _tuned_band():
    m, lat, present = _lattice("d2q9", (16, 128))
    return pallas_d2q9.make_pallas_iterate(
        m, (16, 128), interpret=True, fuse=2, present=present), lat


def _tuned_resident():
    # 20 rows: the band engine of the steps left over stands on ghost rows
    m, lat, present = _lattice("d2q9", (20, 128))
    return pallas_d2q9.make_resident_iterate(
        m, (20, 128), interpret=True, present=present), lat


def _tuned_3d_whole():
    m, lat, present = _lattice("d3q19", (8, 8, 128))
    return pallas_d3q.make_pallas_iterate(
        m, (8, 8, 128), interpret=True, present=present, fuse=2), lat


def _tuned_3d_tiled():
    shape, budget = (12, 64, 64), 6_000_000
    m, lat, present = _lattice("d3q19", shape)
    assert pallas_d3q.tile_plan(m, shape, 4, 3, budget)[1] < shape[1]
    return pallas_d3q.make_pallas_iterate(
        m, shape, interpret=True, present=present, fuse=3,
        vmem_budget=budget), lat


def _generic_band(shape=(32, 128), series=False):
    m, lat, present = _lattice("d2q9_kuper", shape, series)
    return pallas_generic.make_pallas_iterate(
        m, shape, interpret=True, fuse=2, present=present), lat


def _generic_resident():
    m, lat, present = _lattice("d2q9_kuper", (32, 128))
    return pallas_generic.make_resident_iterate(
        m, (32, 128), interpret=True, present=present), lat


def _generic_3d(series=False, **kw):
    m, lat, present = _lattice("d3q19_kuper", (8, 16, 128), series)
    return pallas_generic.make_pallas_iterate_3d(
        m, (8, 16, 128), interpret=True, fuse=2, present=present, **kw), lat


def _sharded(name):
    shape = (32, 128)
    mesh = make_mesh(shape, devices=jax.devices()[:2])
    m, lat, present = _lattice(name, shape, mesh=mesh)
    it = halo.make_sharded_pallas_iterate(m, mesh, shape, jnp.float32,
                                          present=present, interpret=True)
    assert it.unproven == (name != "d2q9")
    assert it.fuse == (2 if name == "d2q9" else 1)
    # the generic mode still pads the shard round its halo rows
    assert it.account(4)["halo_operand_rows"] == (8 if name == "d2q9" else 0)
    return it, lat


def _sharded_tail():
    shape = (32, 128)
    mesh = make_mesh(shape, devices=jax.devices()[:2])
    m, lat, present = _lattice("d2q9", shape, mesh=mesh)
    it = halo.make_sharded_pallas_tail(m, mesh, shape, jnp.float32,
                                       present=present, interpret=True)
    # one step that returns its Globals, on a kernel nothing has compiled
    assert it.full_globals and it.unproven and it.fuse == 1
    return it, lat


def _sharded_tail_3d():
    # shards of 4 slabs: two z bands of whole planes a shard
    shape = (8, 16, 128)
    mesh = make_mesh(shape, devices=jax.devices()[:2],
                     decomposition={"z": 2, "y": 1, "x": 1})
    m, lat, present = _lattice("d3q19", shape, mesh=mesh)
    it = halo.make_sharded_pallas_tail(m, mesh, shape, jnp.float32,
                                       present=present, interpret=True)
    assert it.full_globals and it.unproven and it.fuse == 1
    # the windows of ONE shard, the neighbour's slab an operand
    did = it.account(1)
    assert (did["shards"], did["z_bands"] * did["band_slabs"],
            did["halo_operand_slabs"], did["aux_planes"]) == (2, 4, 1, 1)
    return it, lat


# builder, whether it reports (an account), the lengths to trace: empty
# loops, loops of one trip, odd and even loops of either kernel
BUILDERS = {
    "tuned_band": (_tuned_band, True, (1, 2, 8, 11, 12)),
    "tuned_resident": (_tuned_resident, True, (7, 8, 33, 47)),
    "tuned_3d_whole": (_tuned_3d_whole, True, (1, 2, 9, 10)),
    "tuned_3d_tiled": (_tuned_3d_tiled, True, (2, 3, 14, 15)),
    "generic_band": (_generic_band, True, (1, 2, 4, 10, 12)),
    "generic_band_ghost_rows": (lambda: _generic_band((20, 128)), True,
                                (1, 11)),
    "generic_band_series": (lambda: _generic_band(series=True), True,
                            (1, 2, 5, 6)),
    "generic_resident": (_generic_resident, True, (1, 2, 6, 7)),
    "generic_3d": (_generic_3d, True, (1, 2, 9, 10)),
    "generic_3d_tiled": (lambda: _generic_3d(window=(2, 8)), True, (9,)),
    "generic_3d_series": (lambda: _generic_3d(series=True), True,
                          (1, 2, 5, 6)),
    "sharded_tuned": (lambda: _sharded("d2q9"), True, (1, 5, 8, 11)),
    "sharded_generic": (lambda: _sharded("d2q9_kuper"), True, (3,)),
    "sharded_tail": (_sharded_tail, True, (1,)),
    "sharded_tail_3d": (_sharded_tail_3d, True, (1,)),
}


@lru_cache(maxsize=None)
def _built(name):
    if name.startswith("sharded") and len(jax.devices()) < 2:
        pytest.skip("the sharded wrapper needs two devices")
    return BUILDERS[name][0]()


def _count(jaxpr) -> tuple:
    """``(calls, paired calls)`` of one run of a jaxpr: its
    ``pallas_call`` equations, each times the lengths of the scans round
    it; paired are the calls a two-call body of a lowered loop issues:
    of a scan of unroll ``PAIR`` the whole pairs of its trips, where
    they are two and more (fewer is no loop)."""
    calls = paired = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls += 1
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            c, p = _count(sub)
            if eqn.primitive.name == "scan":
                length, unroll = eqn.params["length"], eqn.params["unroll"]
                assert unroll in (1, PAIR)
                if unroll == PAIR and length >= 2 * PAIR:
                    assert p == 0
                    p = (length - length % PAIR) * c
                else:
                    p *= length
                c *= length
            calls, paired = calls + c, paired + p
    return calls, paired


@pytest.mark.parametrize("name,niter", [
    (name, n) for name, (_, _, lengths) in BUILDERS.items() for n in lengths])
def test_account_is_its_schedule(name, niter):
    it, lat = _built(name)
    assert isinstance(it, Engine)
    jaxpr = jax.make_jaxpr(lambda s, p: it(s, p, niter))(
        lat.state, lat.params).jaxpr
    calls, paired = _count(jaxpr)
    assert calls >= 1
    if not BUILDERS[name][1]:
        assert it.account is None
        return
    did = it.account(niter, lat.params.time_series is not None)
    assert (did["kernel_calls"], did["paired_calls"]) == (calls, paired)
    # one signature: has_series is False where it is left out
    if lat.params.time_series is None:
        assert it.account(niter) == did


@pytest.mark.parametrize("name", list(BUILDERS))
def test_engine_declares_what_dispatch_reads(name):
    """The fields ``core/lattice.py`` decides on are declared, each of
    its type: a misspelt one is an ``AttributeError``, not ``False``."""
    it, _ = _built(name)
    assert isinstance(it.full_globals, bool)
    assert isinstance(it.supports_series, bool)
    assert isinstance(it.unproven, bool)
    assert isinstance(it.pad_rows, int)
    if name.startswith("generic_band"):
        assert it.pad_rows == it.account(1)["pad_rows"]
        assert (it.pad_rows > 0) == (name == "generic_band_ghost_rows")
    # the engines that give a step the Control series' values of its
    # own iteration: the generic ones and (PR 55) the tuned 2D band
    assert it.supports_series == name.startswith(("generic_band",
                                                  "generic_3d",
                                                  "tuned_band"))
    assert (it.plan is not None) == name.startswith("generic_3d")
    with pytest.raises(AttributeError):
        it.uses_generic
    with pytest.raises(AttributeError):
        it._impl
