"""What the VMEM-resident engines say they did.

With telemetry on, a resident engine's ``iterate`` puts its account on
the open span (``iterate.fused``; the probed first call's lies on its
``engine.probe``): the resident calls and the steps each advances, the
steps left to the single-step band kernel with the shape of its calls,
the planes read beside the state and what ``supports_resident`` counted.
Both engines use the same names, so one reader serves both.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu import telemetry
from tclb_tpu.core.lattice import Lattice
from tclb_tpu.models import get_model
from tclb_tpu.ops import pallas_d2q9, pallas_generic

SHAPE = (16, 128)
NAMES = ("kernel_calls", "resident_calls", "paired_calls", "resident_steps",
         "remainder_steps", "aux_planes", "remainder_aux_planes",
         "chunk_rows", "vmem_bytes", "bands", "band_rows", "halo_rows",
         "pad_rows")


def _lattice(name):
    m = get_model(name)
    settings = ({"nu": 0.05, "Velocity": 0.03} if name == "d2q9" else
                {"nu": 0.05, "FluidAlfa": 0.05, "InletVelocity": 0.02})
    lat = Lattice(m, SHAPE, dtype=jnp.float32, settings=settings)
    fluid = m.flag_for("MRT" if name == "d2q9" else "BGK")
    flags = np.full(SHAPE, fluid, dtype=np.uint16)
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    return m, lat


def _expected(name, m, n):
    """The account of ``Lattice.iterate(n)`` by hand."""
    if name == "d2q9":
        # the hybrid keeps the last step for XLA; 8 steps a resident call
        calls, rest = divmod(n - 1, 8)
        return dict(
            kernel_calls=calls + rest, resident_calls=calls,
            # a loop of four trips runs two calls a body, a shorter one
            # is unrolled whole
            paired_calls=4 if calls == 4 else 0,
            resident_steps=8, remainder_steps=rest, aux_planes=3,
            remainder_aux_planes=3, chunk_rows=16,
            vmem_bytes=(3 * m.n_storage + 3) * 16 * 128 * 4,
            bands=1, band_rows=16, halo_rows=8, pad_rows=0)
    # one resident call of an even length that leaves the band engine's
    # globals flavor a step
    main = (n - 1) // 2 * 2
    n_aux = 1 + len(m.zonal_settings)
    return dict(
        kernel_calls=1 + n - main, resident_calls=1, resident_steps=main,
        # the band engine's one or two steps are no loop
        paired_calls=0,
        remainder_steps=n - main, aux_planes=n_aux,
        # the band kernel builds its zonal planes from the zone table
        # and reads the flag plane alone
        remainder_aux_planes=1, chunk_rows=16,
        vmem_bytes=(2 * m.n_storage * 4 + n_aux * 4) * 16 * 128,
        bands=1, band_rows=16, halo_rows=8, pad_rows=0,
        stages_per_step=len(m.actions["Iteration"]))


@pytest.mark.parametrize("n", [17, 32, 33])
@pytest.mark.parametrize("name,tag", [
    ("d2q9", "pallas_resident[d2q9,fuse=8]"),
    ("d2q9_heat", "pallas_resident_generic[d2q9_heat]")])
def test_account_on_the_fused_span(monkeypatch, name, tag, n):
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    m, lat = _lattice(name)
    events = []
    before = telemetry.counters()
    telemetry.subscribe(events.append)
    try:
        lat.iterate(n)
        lat.iterate(n)
        counters = {k: v - before.get(k, 0)
                    for k, v in telemetry.counters().items()}
    finally:
        telemetry.unsubscribe(events.append)
    assert lat._fast_name == tag
    assert not [e for e in events if e.get("kind") == "engine_fallback"]
    spans = [e for e in events if e.get("kind") == "span"]
    fused = [e for e in spans if e["name"] == "iterate.fused"]
    # the engine's own probe; the tuned family's trailing step, on the
    # generic band kernel, has another
    probes = [e for e in spans if e["name"] == "engine.probe"
              and e["engine"] == tag]
    assert len(fused) == 2 and len(probes) == 1
    did = _expected(name, m, n)
    assert set(NAMES) <= set(did)
    # the first call's account lies on the probe that made the calls
    assert "kernel_calls" not in fused[0]
    for span in (probes[0], fused[1]):
        assert {k: span[k] for k in did} == did
    assert did["resident_calls"] * did["resident_steps"] \
        + did["remainder_steps"] == fused[1]["iters"]
    assert did["kernel_calls"] \
        == did["resident_calls"] + did["remainder_steps"]
    # (the tuned family's trailing step is a kernel call of its own)
    tail_calls = 2 * (name == "d2q9")
    assert counters.get("engine.tail_calls", 0) == tail_calls
    assert counters["engine.kernel_calls"] \
        == 2 * did["kernel_calls"] + tail_calls
    assert counters["engine.resident_calls"] == 2 * did["resident_calls"]
    assert counters.get("engine.paired_calls", 0) == 2 * did["paired_calls"]
    # the tuned family's hybrid step is there, the generic engine's not
    steps = [e for e in spans if e["name"] == "iterate.globals_step"]
    assert len(steps) == (2 if name == "d2q9" else 0)


def test_a_resident_program_is_counted_once_a_length(monkeypatch):
    """The generic engine's one call is a program of its own for every
    resident length (a static grid): ``engine.resident_programs`` rises
    when a length is built, not when it is run again, and two calls
    whose lengths differ only in the steps left to the band kernel
    share the program."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    _, lat = _lattice("d2q9_heat")
    events = []
    before = telemetry.counters().get("engine.resident_programs", 0)
    telemetry.subscribe(events.append)
    try:
        seen = []
        for n in (17, 17, 18, 33, 17):      # resident lengths 16, 16, 16, 32
            lat.iterate(n)
            seen.append(telemetry.counters()["engine.resident_programs"]
                        - before)
    finally:
        telemetry.unsubscribe(events.append)
    assert lat._fast_name == "pallas_resident_generic[d2q9_heat]"
    assert seen == [1, 1, 1, 2, 2]
    fused = [e for e in events if e.get("kind") == "span"
             and e["name"] == "iterate.fused"]
    assert [e["resident_steps"] for e in fused[1:]] == [16, 16, 32, 16]


def test_nothing_is_recorded_with_telemetry_off(monkeypatch):
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    assert not telemetry.enabled()
    _, lat = _lattice("d2q9")
    before = telemetry.counters()
    lat.iterate(17)
    assert lat._fast_name == "pallas_resident[d2q9,fuse=8]"
    assert telemetry.counters().get("engine.resident_calls", 0) \
        == before.get("engine.resident_calls", 0)


def test_the_account_of_the_published_karman_shape():
    """1024 x 100 as ``example/karman.xml`` ships it: what
    ``supports_resident`` counts, two chunks of 50 rows, and the steps a
    ``<Log Iterations="1000">`` segment leaves to three 40-row bands."""
    m = get_model("d2q9")
    assert pallas_d2q9.supports_resident(m, (100, 1024), jnp.float32)
    assert pallas_d2q9.resident_vmem_bytes(m, 100, 1024) == 14_745_600
    it = pallas_d2q9.make_resident_iterate(m, (100, 1024), jnp.float32,
                                           interpret=True)
    assert it.account(999) == dict(
        kernel_calls=131, resident_calls=124, paired_calls=124,
        resident_steps=8, remainder_steps=7, aux_planes=3,
        remainder_aux_planes=3, chunk_rows=50, vmem_bytes=14_745_600,
        bands=3, band_rows=40, halo_rows=8, pad_rows=20)
    # the generic engine's count of its own budget, by the same name
    k = get_model("d2q9_kuper")
    assert pallas_generic.resident_vmem_bytes(k, 512, 512, jnp.float32) \
        == (2 * k.n_storage * 4 + 4 * (1 + len(k.zonal_settings))) * 512 ** 2
