"""Zonal setting planes are built from selects, never by indexing the zone
table with the zone ids (``ops/fusion.py:zone_plane``): above 64 zones
XLA lowers ``row[zones]`` on the TPU to a true gather, 6 to 10 ns a node.

The helper is held to ``row[zones]`` bit for bit, and to its gradient;
the programs that used to hold the lookup are held to lowering without a
gather on a row of the zone table.  The mechanism is unconditional, so
the lowered text is its evidence that it engages.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu.core.lattice import Lattice, make_iterate
from tclb_tpu.models import get_model
from tclb_tpu.ops import fusion, lbm, pallas_d2q9
from tclb_tpu.parallel import halo
from tclb_tpu.parallel.mesh import make_mesh


@pytest.mark.parametrize("painted", ["all", "three"])
@pytest.mark.parametrize("shape", [(16, 128), (4, 8, 128)],
                         ids=["2d", "3d"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("zone_max", [64, 128])
def test_zone_plane_is_the_lookup(zone_max, dtype, shape, painted):
    rng = np.random.default_rng(zone_max + len(shape))
    row = jnp.asarray(rng.normal(size=zone_max), dtype)
    n = int(np.prod(shape))
    if painted == "all":
        ids, present = np.arange(n) % zone_max, None
    else:
        present = [0, 5, zone_max - 1]
        ids = np.asarray(present)[np.arange(n) % 3]
    zones = jnp.asarray(rng.permutation(ids).reshape(shape), jnp.int32)

    def chain(r):
        return fusion.zone_plane(r, zones, zone_max, zones_present=present)

    plane = jax.jit(chain)(row)
    want = row[zones]
    assert plane.dtype == want.dtype and plane.shape == want.shape
    np.testing.assert_array_equal(np.asarray(plane.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    # the gradient with respect to the row: masked sums against the
    # gather's scatter-add.  bfloat16 gets whole weights on every 16th
    # node, whose sums (at most 86 nodes a zone, times 2) it holds
    # exactly in any order; float32 gets the 1e-5 the order is worth
    if dtype == jnp.bfloat16:
        w = jnp.asarray(rng.integers(1, 3, size=n)
                        * (np.arange(n) % 16 == 0), dtype).reshape(shape)
    else:
        w = jnp.asarray(rng.normal(size=shape), dtype)
    g = jax.grad(lambda r: jnp.sum(w * chain(r)))(row)
    g_want = jax.grad(lambda r: jnp.sum(w * r[zones]))(row)
    scale = float(jnp.max(jnp.abs(g_want.astype(jnp.float32))))
    np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)),
                               np.asarray(g_want.astype(jnp.float32)),
                               rtol=0, atol=1e-5 * scale)


def _karman(shape, mesh=None):
    """d2q9 with a painted ``WVelocity`` / ``EPressure`` pair, the inlet
    split over two zones: what every karman cell runs."""
    m = get_model("d2q9")
    lat = Lattice(m, shape, dtype=jnp.float32, mesh=mesh,
                  settings={"nu": 0.05, "Velocity": 0.02})
    ny = shape[0]
    flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:ny // 2, 0] = m.flag_for("WVelocity", "MRT", zone=1)
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.set_setting("Velocity", 0.01, zone=1)
    lat.init()
    return m, lat, lbm.present_types(m, flags)


def _xla_step(shape):
    m, lat, present = _karman(shape)
    return make_iterate(m, present=present), lat, 1


def _band_engine(shape):
    m, lat, present = _karman(shape)
    it = pallas_d2q9.make_pallas_iterate(m, shape, jnp.float32,
                                         interpret=True, fuse=2,
                                         present=present)
    return it, lat, 5


def _sharded_engine(shape):
    mesh = make_mesh(shape, devices=jax.devices()[:1])
    m, lat, present = _karman(shape, mesh=mesh)
    it = halo.make_sharded_pallas_iterate(m, mesh, shape, jnp.float32,
                                          present=present, interpret=True)
    assert it is not None and it.fuse == 2
    return it, lat, 5


def _table_gathers(text: str, zone_max: int) -> list:
    """The gathers of a lowered program whose operand is one row of the
    zone table (other gathers, such as ``NodeCtx.group``'s, read the
    field stack)."""
    return [ln.strip() for ln in text.splitlines()
            if "stablehlo.gather" in ln
            and re.search(rf"\(tensor<{zone_max}xf\d+>", ln)]


@pytest.mark.parametrize("build", [_xla_step, _band_engine,
                                   _sharded_engine],
                         ids=["xla_step", "band_iterate_jit",
                              "sharded_local_iterate"])
def test_no_gather_on_the_zone_table(build):
    shape = (32, 128)
    it, lat, niter = build(shape)
    m = lat.model
    text = jax.jit(lambda s, p: it(s, p, niter)).lower(
        lat.state, lat.params).as_text()
    assert "stablehlo.select" in text
    assert _table_gathers(text, m.zone_max) == []
    # the detector sees what it is looking for: the lookup this replaced
    row, zones = lat.params.zone_table[0], lat.state.flags.astype(jnp.int32)
    lookup = jax.jit(lambda r, z: r[z >> m.zone_shift]).lower(
        row, zones).as_text()
    assert len(_table_gathers(lookup, m.zone_max)) == 1
