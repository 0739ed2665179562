"""The forward kernels of the main path, compiled for a *described* TPU
v5e by the chip's own compiler (Mosaic + XLA:TPU), with no chip attached.

Interpret mode — every other Pallas test here — cannot see what Mosaic
refuses: a primitive without a lowering rule (``optimization_barrier``,
which is why ``lbm.pin`` is the identity inside a compiled kernel body),
a misaligned slice, a scoped-VMEM overflow.  These compiles can.  Nothing
runs, so they say nothing about results or times.

This is the only file that describes the chip.  The topology is described
inside a fixture (loading the TPU library while a module is imported would
break multi-worker collection), compiles happen in this process, and the
persistent compilation cache is off around them (a TPU executable written
to it cannot be read back without a chip).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from tclb_tpu.core.lattice import Lattice
from tclb_tpu.models import get_model
from tclb_tpu.ops import lbm, pallas_d2q9, pallas_d3q, pallas_generic


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is locked
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _as_on_the_chip():
    """The process configuration of a chip run: 32-bit (conftest turns
    x64 on for the CPU goldens, and Mosaic refuses the i64 indices that
    gives), and no persistent cache around the compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was_cache = jax.config.jax_enable_compilation_cache
    was_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_x64", was_x64)
    jax.config.update("jax_enable_compilation_cache", was_cache)
    cc.reset_cache()


def _channel(name, shape, **settings):
    """A walled channel of ``name`` at ``shape`` (host side only: the
    state gives the compile its shapes)."""
    m = get_model(name)
    lat = Lattice(m, shape, dtype=jnp.float32, settings=settings)
    flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
    if "Wall" in m.node_types:
        flags[..., 0, :] = m.flag_for("Wall")
        flags[..., -1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    return m, lat, lbm.present_types(m, flags)


def _spec(lat, one_chip) -> tuple:
    """The shapes of a lattice's state and parameters on the described
    chip: what a compile takes in place of arrays."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (lat.state, lat.params))


def _compile(iterate, lat, niter, one_chip) -> str:
    compiled = jax.jit(lambda s, p: iterate(s, p, niter)).lower(
        *_spec(lat, one_chip)).compile()
    return compiled.as_text()


def test_d2q9_band_fused_1024(one_chip):
    shape = (1024, 1024)
    m, lat, present = _channel("d2q9", shape, nu=0.02)
    it = pallas_d2q9.make_pallas_iterate(m, shape, jnp.float32,
                                         interpret=False, fuse=2,
                                         present=present)
    # 5 steps: two fused pairs + the single-step kernel for the odd one,
    # each under the name of its family and depth (what a trace shows)
    text = _compile(it, lat, 5, one_chip)
    assert "tpu_custom_call" in text
    assert "d2q9_band_fuse2/pallas_call" in text
    assert "d2q9_band_fuse1/pallas_call" in text


def test_d2q9_resident_karman_as_shipped(one_chip, monkeypatch):
    """``example/karman.xml`` at its own size, 1024 x 100: the whole
    lattice fits ``supports_resident``'s budget, the dispatch puts the
    VMEM-resident engine first and probes it, and what the probe would
    compile compiles: the resident kernel on two chunks of 50 rows (no
    multiple of the sublane tile: the periodic pull is concatenations)
    and, for the 7 steps a segment leaves over, the single-step band
    kernel on 120 padded rows.  A compile that raised here would make
    the probe step down to the band engine on the chip.  47 steps: five
    resident calls (two trips of a two-call loop body and an odd call
    after the loop, so XLA puts no copy of the state before the kernel)
    and 7 left over.  (About 70 s on this host: the resident kernel is
    16 unrolled chunk steps.)"""
    shape = (100, 1024)
    m = get_model("d2q9")
    lat = Lattice(m, shape, dtype=jnp.float32,
                  settings={"nu": 0.02, "Velocity": 0.01})
    flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    rows, cols = np.mgrid[0:100, 0:1024]
    flags[np.abs(rows - 49.5) + np.abs(cols - 139.5) < 20] = \
        m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    assert pallas_d2q9.supports_resident(m, shape, jnp.float32)
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    chain = lat._build_fast()
    assert [(c.tag, c.probe) for c in chain] == [
        ("pallas_resident[d2q9,fuse=8]", True),
        ("pallas_2d[d2q9,fuse=2]", False)]
    it = pallas_d2q9.make_resident_iterate(
        m, shape, jnp.float32, interpret=False,
        present=lbm.present_types(m, flags))
    assert it.account(47) == dict(
        kernel_calls=12, resident_calls=5, paired_calls=4, resident_steps=8,
        remainder_steps=7, aux_planes=3, remainder_aux_planes=3,
        chunk_rows=50, vmem_bytes=14_745_600, bands=3, band_rows=40,
        halo_rows=8, pad_rows=20)
    text = _compile(it, lat, 47, one_chip)
    assert "tpu_custom_call" in text
    assert "d2q9_resident_fuse8/pallas_call" in text
    assert "d2q9_band_fuse1/pallas_call" in text
    # the names a device trace shows, which the benchmark's reader of
    # the remainder's share tells apart
    assert re.search(r"%d2q9_resident_fuse8[\w.]* = \S+ custom-call\(", text)
    assert re.search(r"%d2q9_band_fuse1[\w.]* = \S+ custom-call\(", text)
    body, calls = _kernel_loop_body(text, "d2q9_resident_fuse8")
    assert calls == 2
    assert not _state_copies(body, m, shape)


@pytest.mark.parametrize("fuse", [None, 1], ids=["fused", "fuse1"])
def test_d3q27_cumulant_48x48x256(one_chip, fuse):
    shape = (48, 48, 256)
    m, lat, present = _channel("d3q27_cumulant", shape, nu=0.01)
    if fuse is None:
        assert pallas_d3q.choose_fuse(m, shape, itemsize=4) >= 2
    it = pallas_d3q.make_pallas_iterate(m, shape, jnp.float32,
                                        interpret=False, present=present,
                                        fuse=fuse)
    text = _compile(it, lat, 6, one_chip)
    assert "tpu_custom_call" in text
    assert re.search(r"d3q_(slab|ring)_fuse%s/pallas_call"
                     % (fuse or r"[2-9]"), text)


def _by_shapes(one_chip, shape, present, **settings):
    """A ``d3q27_cumulant`` box by shapes only (a 256^3 state is 2.3 GB,
    the channel cell's 0.86)."""
    from tclb_tpu.core.lattice import LatticeState
    m = get_model("d3q27_cumulant")
    small = Lattice(m, (8, 8, 128), dtype=jnp.float32, settings=settings)

    def on_chip(x, dims=None):
        return jax.ShapeDtypeStruct(dims or x.shape, x.dtype,
                                    sharding=one_chip)
    st = small.state
    state = LatticeState(
        fields=on_chip(st.fields, (m.n_storage,) + shape),
        flags=on_chip(st.flags, shape), globals_=on_chip(st.globals_),
        iteration=on_chip(st.iteration))
    return m, shape, state, jax.tree.map(on_chip, small.params), present


def _tgv_256(one_chip):
    """The Taylor-Green box: every node collides."""
    return _by_shapes(one_chip, (256, 256, 256), {"MRT"}, nu=0.001273)


def _channel_512(one_chip):
    """``example/3d_channel_512.xml``: the walled, force-driven channel
    of the two ``channel3d512`` cells."""
    return _by_shapes(one_chip, (512, 48, 256), {"MRT", "Wall"},
                      nu=0.01, ForceX=1e-5)


def test_d3q27_cumulant_256_tiled(one_chip):
    """The plan of ``example/tgv_256.xml``: no kernel holds a 256 x 256
    plane whole, so the fused kernel runs on y-tiled windows, for the
    fused calls and for the step they leave over.  Only shapes are
    described: a 256^3 state is 2.3 GB."""
    m, shape, state, params, present = _tgv_256(one_chip)
    bz, by, K = pallas_d3q.tile_plan(m, shape)
    assert K >= 2 and by < 256
    assert pallas_d3q.tile_plan(m, shape, fuse=1)[1] < 256
    it = pallas_d3q.make_pallas_iterate(m, shape, jnp.float32,
                                        interpret=False, present=present)
    text = jax.jit(lambda s, p: it(s, p, K + 1)).lower(
        state, params).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"d3q_slab_fuse{K}/pallas_call" in text
    assert "d3q_slab_fuse1/pallas_call" in text


def _computations(text: str) -> dict:
    """The computations of a compiled module's text, name -> lines."""
    found, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if name is None and head:
            name = head.group(1)
            found[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            found[name].append(line)
    return found


def _kernel_loop_body(text: str, kernel: str):
    """The lines of the one ``while`` body of a compiled module that
    holds custom calls of the kernel ``kernel`` (a regex for its name),
    and how many of those calls it holds."""
    call = re.compile(r"= \S+ custom-call\(.*%s/" % kernel)
    bodies = [lines for name, lines in _computations(text).items()
              if any(call.search(line) for line in lines)
              and re.search(r"\bbody=%%?%s\b" % re.escape(name), text)]
    assert len(bodies) == 1
    return bodies[0], sum(bool(call.search(line)) for line in bodies[0])


def _state_copies(lines, m, shape) -> list:
    """The copies of the whole state among a computation's lines."""
    whole = "f32[%s]" % ",".join(str(n) for n in (m.n_storage,)
                                 + tuple(shape))
    copy = re.compile(r"= %s\S* copy(-start)?\(" % re.escape(whole))
    return [line.strip() for line in lines if copy.search(line)]


def _in_fast_memory(body) -> list:
    """Of the kernel calls among a loop body's lines, whether each one's
    result lives in the compiler's fast memory (``S(1)``)."""
    return ["S(1)}" in line.split(" custom-call(")[0] for line in body
            if " custom-call(" in line]


@pytest.mark.parametrize("case,fuse", [
    ("channel", None), ("channel", 1), ("tgv256", None),
    ("channel512", None)],
    ids=["channel-fused", "channel-fuse1", "tgv256-tiled",
         "channel512-fused"])
def test_d3q_loop_body_pairs_the_calls_and_copies_no_state(one_chip, case,
                                                           fuse):
    """The loop that carries the state through the kernel holds two
    kernel calls a body, so the call that writes the carry is not the one
    that reads it, and XLA puts no copy of the whole state before the
    kernel.  Five calls of the looped kernel: two trips and an odd call
    after the loop; one step over where the depth allows one.  The two
    cells' own shapes compile under the planner's own plans, the ones
    ``PERF.md`` records: whole planes in windows of (bz, K) = (4, 3) at
    512 x 48 x 256 (the buffer ``tgv256``'s (4, 32, 3) holds, to the
    byte), which the temporaries Mosaic holds leave room for."""
    if case == "channel":
        shape = (48, 48, 256)
        m, lat, present = _channel("d3q27_cumulant", shape, nu=0.01)
    else:
        m, shape, state, params, present = (
            _tgv_256 if case == "tgv256" else _channel_512)(one_chip)
    if case == "tgv256":
        assert pallas_d3q.tile_plan(m, shape) == (4, 32, 3)
    if case == "channel512":
        assert pallas_d3q.tile_plan(m, shape) is None
        assert pallas_d3q.fused_cfg(m, shape) == (4, 3)
    it = pallas_d3q.make_pallas_iterate(m, shape, jnp.float32,
                                        interpret=False, present=present,
                                        fuse=fuse)
    K = fuse or pallas_d3q.choose_fuse(m, shape)
    assert (K >= 2) == (fuse is None)
    niter = 5 * K + (K >= 2)
    did = it.account(niter)
    assert did["kernel_calls"] == 5 + (K >= 2) and did["paired_calls"] == 4
    if case == "channel512":
        assert (did["band_slabs"], did["halo_slabs"], did["z_bands"],
                did["y_bands"], did["halo_rows"]) == (4, 3, 128, 1, 0)
        assert did["vmem_bytes"] <= pallas_d3q._FUSED_BUDGET
    if case == "channel":
        text = _compile(it, lat, niter, one_chip)
    else:
        text = jax.jit(lambda s, p: it(s, p, niter)).lower(
            state, params).compile().as_text()
    assert re.search(r"d3q_(slab|ring)_fuse%d/pallas_call" % K, text)
    body, calls = _kernel_loop_body(text, "d3q_(slab|ring)_fuse%d" % K)
    assert calls == 2
    assert not _state_copies(body, m, shape)


def test_generic_d3q19_kuper_256_tiled(one_chip):
    """The plan of ``example/drop3d_256.xml``: no whole-plane plan of the
    generic 3D slab engine holds a 256 x 256 plane of this model, so the
    kernel runs on y-tiled windows under the raised ceiling, two calls a
    loop body: XLA puts no copy of the 1.34 GB state before the kernel.
    Only shapes are described."""
    from tclb_tpu.core.lattice import LatticeState
    shape = (256, 256, 256)
    m = get_model("d3q19_kuper")
    small = Lattice(m, (8, 8, 128), dtype=jnp.float32)

    def on_chip(x, dims=None):
        return jax.ShapeDtypeStruct(dims or x.shape, x.dtype,
                                    sharding=one_chip)
    st = small.state
    state = LatticeState(
        fields=on_chip(st.fields, (m.n_storage,) + shape),
        flags=on_chip(st.flags, shape), globals_=on_chip(st.globals_),
        iteration=on_chip(st.iteration))
    params = jax.tree.map(on_chip, small.params)
    assert pallas_generic.supports_3d(m, shape, jnp.float32, probe=False)
    bz, by, K = pallas_generic.tile_plan_3d(m, shape)
    assert by < 256
    it = pallas_generic.make_pallas_iterate_3d(
        m, shape, jnp.float32, interpret=False, present={"MRT"}, fuse=K)
    assert it.plan == (bz, by, K)
    niter = 5 * K + (K >= 2)
    did = it.account(niter)
    assert did["kernel_calls"] == 5 + (K >= 2) and did["paired_calls"] == 4
    text = jax.jit(lambda s, p: it(s, p, niter)).lower(
        state, params).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"generic_slab_fuse{K}/pallas_call" in text
    body, calls = _kernel_loop_body(text, "generic_slab_fuse%d" % K)
    assert calls == 2
    assert not _state_copies(body, m, shape)


@pytest.mark.parametrize("name", ["d2q9_kuper", "d2q9_heat"])
def test_generic_512(one_chip, name):
    shape = (512, 512)
    m, lat, present = _channel(name, shape)
    it = pallas_generic.make_pallas_iterate(
        m, shape, jnp.float32, interpret=False,
        fuse=pallas_generic.choose_fuse(m), present=present)
    text = _compile(it, lat, 4, one_chip)
    assert "tpu_custom_call" in text
    # 4 steps are fewer than the fused depth: the remainder kernel
    assert "generic_band_fuse1/pallas_call" in text


def _drop(n: int):
    """The drop of ``example/drop.xml`` in a periodic box of ``n`` x
    ``n`` nodes: every node collides, the drop is a settings zone."""
    shape = (n, n)
    m = get_model("d2q9_kuper")
    lat = Lattice(m, shape, dtype=jnp.float32)
    flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
    rows, cols = np.mgrid[0:n, 0:n]
    mid = (n - 1) / 2
    drop = (rows - mid) ** 2 + (cols - mid) ** 2 < (3 * n // 16) ** 2
    flags[drop] |= 1 << m.zone_shift        # the zone of Density-zdrop
    lat.set_flags(flags)
    lat.init()
    return m, lat, flags


def test_generic_band_drop_1024_pairs_the_calls(one_chip):
    """``example/drop_1024.xml`` (the cell ``drop1024.relax``): the band
    engine at the planner's fuse 4 with its lean aux stack (the flag
    plane; the zone table in SMEM).  The loop that carries the state
    through the kernel holds two calls a body, so XLA puts no copy of the
    42 MB state before a call (the parent's body: one call reading
    ``copy(carry)``).  22 steps: five looped calls (two trips and an odd
    call), a step over and the globals flavor's."""
    m, lat, flags = _drop(1024)
    shape = (1024, 1024)
    fuse = pallas_generic.choose_fuse(m)
    assert fuse == 4
    it = pallas_generic.make_pallas_iterate(
        m, shape, jnp.float32, interpret=False, fuse=fuse,
        present=lbm.present_types(m, flags))
    did = it.account(22, False)
    assert (did["kernel_calls"], did["remainder_steps"],
            did["paired_calls"], did["aux_planes"], did["bands"]) \
        == (7, 2, 4, 1, 32)
    text = _compile(it, lat, 22, one_chip)
    assert "generic_band_fuse1/pallas_call" in text
    body, calls = _kernel_loop_body(text, "generic_band_fuse4")
    assert calls == 2
    assert not _state_copies(body, m, shape)


@pytest.mark.parametrize("fuse,niter,twos,ones", [
    (2, 499, 249, 1), (2, 500, 249, 2), (1, 499, 0, 499)],
    ids=["fuse2-499", "fuse2-500", "fuse1-499"])
def test_d2q9_band_1024_pairs_the_calls(one_chip, fuse, niter, twos, ones):
    """The tuned band engine's unsampled loop at the size of the two
    ``karman1024`` cells, whose ``iterate(500)`` is 499 engine steps:
    **the engine's own jitted program, donating its state as the engine
    donates it** (an outer ``jit`` drops the inner donation and the
    compile says nothing of the chip's program).  The loop's body holds
    two kernel calls and no copy or move of the whole state (one call a
    body copied the 46 MB carry before every call: ``copy.18``, 2.64 ms
    an ``iterate(500)``).  At ``fuse`` 2 **both calls' results live in
    the compiler's fast memory** (``S(1)``): the ``kernel2`` that waited
    for its input copies took 309.3 us a call on a state it read from
    HBM for 215.7 (chip, PR 48: 66.4 ms an ``iterate(499)`` for 55.3;
    since PR 50 it prefetches its band, 12.8 MiB of scoped VMEM for 10.1
    under the same 16 MiB limit, and the placement holds).
    Two states and the aux stack are 105 MB of that memory, and **what
    tips the placement is the program's end**: donated and ending in the
    one-step kernel (which reads the loop's result in ``S(1)`` and
    writes the caller's buffer in HBM) both buffers stay there; the loop
    alone (``parallel/halo.py``'s cure on a mesh), the same program not
    donated, or an even length all in two-step calls puts one of them in
    HBM.  So an even length ends in two one-step calls (``split``): 249
    two-step calls, 248 of them looped, and two steps.  The one-step
    loop is held to the pairing alone: its kernel prefetches its band
    and read 98.3 and 98.1 us a call with one buffer in HBM (chip, PR
    48; 61.4 us a call of carry copy gone: 81.1 -> 50.3 ms)."""
    shape = (1024, 1024)
    m, lat, present = _channel("d2q9", shape, nu=0.02)
    it = pallas_d2q9.make_pallas_iterate(m, shape, jnp.float32,
                                         interpret=False, fuse=fuse,
                                         present=present)
    rows = {2: 32, 1: 64}[fuse]          # the looped kernel's band
    assert it.account(niter) == dict(
        kernel_calls=twos + ones, remainder_steps=0,
        paired_calls=248 if fuse == 2 else 498, aux_planes=3,
        bands=1024 // rows, band_rows=rows, halo_rows=8, pad_rows=0,
        band_slots=2)
    lowered = it.impl["program"].lower(*_spec(lat, one_chip), niter=niter)
    assert lowered.args_info[0][0].fields.donated
    text = lowered.compile().as_text()
    body, calls = _kernel_loop_body(text, "d2q9_band_fuse%d" % fuse)
    assert calls == 2
    assert not _state_copies(body, m, shape)
    assert not _state_moves(body, m, shape)
    if fuse == 1:
        return
    # the program ends in the one-step kernel, once or twice
    assert len(re.findall(r"= \S+ custom-call\(.*d2q9_band_fuse1/", text)) \
        == ones
    assert _in_fast_memory(body) == [True, True]


def test_d2q9_band_1024_series_pairs_the_calls(one_chip):
    """The same loop under a ``<Control>`` series, at the size of the
    cell ``karman1024control.logonly``: Mosaic takes the series flavour
    of both kernels (a second SMEM operand, a select a row on the zone
    ids) under its default limit, the loop's body holds two
    ``d2q9_band_fuse2_series`` calls, no copy or move of the whole
    state, **both results in the compiler's fast memory**, as without a
    series (the iteration carried beside the state tips nothing), and
    beside them three fusions of a few scalars: one that slices the
    body's four values from the table, each at its own iteration, and a
    concatenation a call.  No plane of the lattice's size is made inside
    the loop: a series costs scalars (57.1 ms an ``iterate(499)``
    against 56.0 without one, chip, PR 55)."""
    shape = (1024, 1024)
    m, lat, present = _channel("d2q9", shape, nu=0.02)
    lat.set_setting_series("Velocity", 0.01 + 1e-3 * (np.arange(4000) % 7),
                           zone=1)
    it = pallas_d2q9.make_pallas_iterate(m, shape, jnp.float32,
                                         interpret=False, fuse=2,
                                         present=present)
    assert it.supports_series
    lowered = it.impl["program"].lower(*_spec(lat, one_chip), niter=499)
    assert lowered.args_info[0][0].fields.donated
    text = lowered.compile().as_text()
    body, calls = _kernel_loop_body(text, "d2q9_band_fuse2_series")
    assert calls == 2
    assert not _state_copies(body, m, shape)
    assert not _state_moves(body, m, shape)
    assert _in_fast_memory(body) == [True, True]
    assert len(re.findall(
        r"= \S+ custom-call\(.*d2q9_band_fuse1_series/", text)) == 1
    assert "d2q9_band_fuse2/" not in text and "d2q9_band_fuse1/" not in text
    plane = re.compile(r"= \w+\[(\d+,)*1024,1024\]\S* fusion\(")
    assert not [line for line in body if plane.search(line)]
    assert sum(" fusion(" in line for line in body) == 3


# shapes the parent's band sizing could not compile, and the bands the
# plan gives them (one-step, two-step)
_PLANNED = [((800, 1024), (40, 40)), ((1280, 1024), (64, 40)),
            ((1024, 2048), (128, 32)), ((64, 8192), (32, 32))]


@pytest.mark.parametrize("shape,rows", _PLANNED,
                         ids=["%dx%d" % s for s, _ in _PLANNED])
def test_d2q9_band_plans_compile(one_chip, shape, rows):
    """What ``pallas_d2q9.band_plan`` admits compiles, where the parent
    failed at its first call on the chip: 800 and 1280 rows of 1024
    nodes (the parent took an 80-row band, 18 MiB of Mosaic's 16: PR
    48's finding) under the default limit, and rows of 2048 and 8192
    nodes (the parent's scratch alone passed the limit) under the raised
    one the plan states.  64 x 8192 is the band of the cell
    ``karman8192.longrun`` (8192 x 8192: the same 32 rows, which the
    host holds).  The engine's own donating program of 21 steps: ten
    two-step calls from a loop body of two calls with no copy of the
    state, and the one-step kernel at the end, so both kernels of the
    plan are compiled."""
    m, lat, present = _channel("d2q9", shape, nu=0.02)
    assert pallas_d2q9.supports(m, shape, jnp.float32)
    it = pallas_d2q9.make_pallas_iterate(m, shape, jnp.float32,
                                         interpret=False, fuse=2,
                                         present=present)
    plan = it.impl["plan"]
    assert plan.band_rows == rows
    assert plan.raised(2) == (shape[1] >= 2048)
    assert it.account(21)["paired_calls"] == 10
    text = it.impl["program"].lower(*_spec(lat, one_chip),
                                    niter=21).compile().as_text()
    assert "d2q9_band_fuse1/pallas_call" in text
    body, calls = _kernel_loop_body(text, "d2q9_band_fuse2")
    assert calls == 2
    assert not _state_copies(body, m, shape)


def _mosaic_modules(text: str) -> dict:
    """The Mosaic modules of a lowered program's kernel calls, as text,
    by the kernel's name (the calls of one kernel share a module)."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    found = {}
    for call in re.finditer(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22'
                            r'.*?kernel_name = "(\w+)"', text):
        name = call.group(2)
        with mlir.JaxIrContext() as ctx:
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True   # ``stable_mosaic``
            module = ir.Module.parse(base64.b64decode(call.group(1)))
            found[name] = module.operation.get_asm(enable_debug_info=False)
    return found


@pytest.mark.parametrize("sharded", [False, True], ids=["chip", "shard"])
def test_d2q9_two_step_kernel_prefetches_its_band(one_chip, sharded):
    """The two-step kernel's Mosaic module, read off the lowered program
    of 1024 x 1024 (nothing is compiled): **two slots** of the state's
    and of the aux stack's scratch (the band of 32 rows under its two
    8-row halo blocks) beside the pipelined out block, and **every copy
    of band 0 and of band 1 is started before the first wait**: six and
    six on one chip; on a shard eight and eight, the halo blocks' two
    sources each under its own branch; then the six waits of the band
    computed.  The grid is one dimension walked in order
    (``arbitrary``), which a copy started one grid step and waited for
    at the next relies on."""
    shape = (1024, 1024)
    m, lat, present = _channel("d2q9", shape, nu=0.02)
    built = pallas_d2q9.make_pallas_iterate(
        m, shape, jnp.float32, interpret=False, fuse=2, present=present,
        ext_halo=sharded)
    if sharded:
        text = jax.jit(built[1]).lower(*(
            jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)
            for dims in ((len(m.settings),), (11, 1024, 1024),
                         (11, 8, 1024), (11, 8, 1024),
                         (3, 1040, 1024)))).as_text()
    else:
        assert built.account(3)["band_slots"] == 2
        text = built.impl["program"].lower(*_spec(lat, one_chip),
                                           niter=3).as_text()
    module = _mosaic_modules(text)["d2q9_band_fuse2"]
    vmem = set(re.findall(r"memref<([0-9x]+)xf32, #tpu.memory_space<vmem>>",
                          module.split("function_type = ")[1]
                          .split(" -> ")[0]))
    # the state's and the aux stack's slots, and the out block's window
    assert vmem == {"2x11x48x1024", "2x3x48x1024", "11x32x1024"}
    assert "memref<2x6x!tpu.dma_semaphore" in module
    assert "dimension_semantics = [#tpu.dimension_semantics<arbitrary>]" \
        in module
    dmas = re.findall(r"tpu\.(enqueue_dma|wait_dma)", module)
    started = 16 if sharded else 12
    assert dmas == ["enqueue_dma"] * started + ["wait_dma"] * 6


# the eight probes of the cell karman1024probes.sampled, (row, column)
_PROBES = np.array([[512, 112], [512, 328], [512, 420], [512, 520],
                    [412, 520], [612, 520], [512, 720], [512, 920]])


def _gathers(text: str) -> list:
    return [line.strip() for line in text.splitlines()
            if re.search(r"= \S+ gather\(", line)]


def _state_moves(lines, m, shape) -> list:
    """The copies of the whole state among a computation's lines, those
    between the compiler's fast memory and HBM too (``copy-done``)."""
    whole = "f32[%s]" % ",".join(str(n) for n in (m.n_storage,)
                                 + tuple(shape))
    move = re.compile(r"= %s\S* copy(-done)?\(" % re.escape(whole))
    return [line.strip() for line in lines if move.search(line)]


def _compile_donating(iterate, lat, niter, one_chip) -> str:
    """As :func:`_compile`, the state donated: what a sampled flavour
    would compile to if its program donated like the unsampled one."""
    return jax.jit(lambda s, p: iterate(s, p, niter), donate_argnums=0
                   ).lower(*_spec(lat, one_chip)).compile().as_text()


def test_d2q9_band_1024_sampled_pairs_the_calls(one_chip):
    """The sampled flavour of the tuned band at the size and with the
    probes of the cell ``karman1024probes.sampled``: every step one call
    of ``d2q9_band_fuse1``, what it left at the eight points the scan's
    ys.  The loop body holds two kernel calls and **no copy of the whole
    state** (both state buffers stay in the compiler's fast memory), and
    the taps are static slices fused into one small fusion a call: read
    as an XLA gather they cost a copy of the 46 MB state into the
    gather's layout before every call (the first wiring of PR 46,
    compiled here).  **The flavour does not donate its state**: donated,
    the loop's carry is the caller's HBM buffer and every trip ends in a
    ``copy-done`` of the whole state out of the fast memory (70 us a
    trip, a quarter of the device's time on the chip: PR 46's first
    call); not donated, the state goes in once before the loop and out
    once after it.  499 steps, the cell's call: 249 trips of two calls
    and an odd call."""
    shape = (1024, 1024)
    m, lat, present = _channel("d2q9", shape, nu=0.02)
    it = pallas_d2q9.make_pallas_iterate(m, shape, jnp.float32,
                                         interpret=False, fuse=1,
                                         present=present, points=_PROBES)
    assert it.samples
    assert it.account(499) == dict(
        kernel_calls=499, remainder_steps=0, paired_calls=498, aux_planes=3,
        bands=16, band_rows=64, halo_rows=8, pad_rows=0, band_slots=2)
    text = _compile(it, lat, 499, one_chip)
    body, calls = _kernel_loop_body(text, "d2q9_band_fuse1")
    assert calls == 2
    assert not _state_moves(body, m, shape)
    assert not _gathers(text)
    assert "f32[249,2,11,8]" in text      # the ys of the paired trips
    # the engine's own program does not donate ...
    inner, = [e for e in jax.make_jaxpr(lambda s, p: it(s, p, 499))(
        lat.state, lat.params).eqns if e.primitive.name in ("pjit", "jit")]
    assert not any(inner.params["donated_invars"])
    # ... because donated it would move the state every trip
    body, _ = _kernel_loop_body(_compile_donating(it, lat, 499, one_chip),
                                "d2q9_band_fuse1")
    assert len(_state_moves(body, m, shape)) == 1


def test_generic_band_drop_1024_sampled_pairs_the_calls(one_chip):
    """The generic schedule's sampled flavour at ``drop1024``'s shape:
    one step a call of ``generic_band_fuse1``, two calls a loop body, no
    copy of the 42 MB state (not donated, as the tuned band's and for
    the same reason), no gather; the final Globals call is sampled from
    the state it returns.  500 steps: 499 looped calls and the globals
    flavour's."""
    m, lat, flags = _drop(1024)
    shape = (1024, 1024)
    it = pallas_generic.make_pallas_iterate(
        m, shape, jnp.float32, interpret=False, fuse=1,
        present=lbm.present_types(m, flags), points=_PROBES)
    did = it.account(500, False)
    assert it.samples and (did["kernel_calls"], did["paired_calls"],
                           did["remainder_steps"]) == (500, 498, 1)
    text = _compile(it, lat, 500, one_chip)
    body, calls = _kernel_loop_body(text, "generic_band_fuse1")
    assert calls == 2
    assert not _state_moves(body, m, shape)
    assert not _gathers(text)
    inner, = [e for e in jax.make_jaxpr(lambda s, p: it(s, p, 500))(
        lat.state, lat.params).eqns if e.primitive.name in ("pjit", "jit")]
    assert not any(inner.params["donated_invars"])
    body, _ = _kernel_loop_body(_compile_donating(it, lat, 500, one_chip),
                                "generic_band_fuse1")
    assert len(_state_moves(body, m, shape)) == 1


def test_generic_resident_drop_512(one_chip, monkeypatch):
    """``example/drop_512.xml``, upstream's drop at its own 512 x 512
    (the configuration ``drop512``): the state fits the generic
    VMEM-resident engine's budget, the dispatch puts that engine first
    and probes it, with the generic band engine under it, and what the
    probe would compile compiles for the chip: one kernel whose grid is
    the call's steps, eight unrolled 64-row chunks a grid step, and the
    fuse-1 band kernel for the two steps an even call leaves over.  The
    grid is short here (6 steps: 4 resident, 2 left over); the body is
    the one ``iterate(500)`` runs 498 times.  (106 s on this host with
    nothing beside it, nearly all of it the resident kernel: three
    parity branches of eight two-stage chunk bodies.  Under the two
    minutes the issue allows, so the real shape is compiled and not a
    smaller one of two chunks.)"""
    shape = (512, 512)
    m, lat, flags = _drop(512)
    assert pallas_generic.supports_resident(m, shape, jnp.float32)
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    chain = lat._build_fast()
    assert [(c.tag, c.probe) for c in chain] == [
        ("pallas_resident_generic[d2q9_kuper]", True),
        ("pallas_generic[d2q9_kuper,fuse=4]", False)]
    it = pallas_generic.make_resident_iterate(
        m, shape, jnp.float32, interpret=False,
        present=lbm.present_types(m, flags))
    # a <Log Iterations="500"> segment, as the cell drop512.relax runs it
    assert it.account(500) == dict(
        kernel_calls=3, resident_calls=1, paired_calls=0,
        resident_steps=498, remainder_steps=2, aux_planes=2,
        remainder_aux_planes=1,
        chunk_rows=64, vmem_bytes=23_068_672, stages_per_step=2,
        bands=16, band_rows=32, halo_rows=8, pad_rows=0)
    assert it.account(6)["resident_steps"] == 4
    text = _compile(it, lat, 6, one_chip)
    assert "tpu_custom_call" in text
    assert "generic_resident_fuse4/pallas_call" in text
    assert "generic_band_fuse1/pallas_call" in text
    # the names a device trace shows, which the benchmark's reader of
    # the remainder's share tells apart
    assert re.search(r"%generic_resident_fuse4[\w.]* = \S+ custom-call\(",
                     text)
    assert re.search(r"%generic_band_fuse1[\w.]* = \S+ custom-call\(", text)


def _karman_4096_on_4x1_mesh(topo) -> tuple:
    """The lattice of the two ``karman4096`` cells on a y-split mesh of
    four of the described chips: the model, the mesh, the node types
    present, and the shapes of its state and parameters with their
    shardings (what a compile takes in place of arrays)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tclb_tpu.parallel import halo
    shape = (4096, 1024)
    m, lat, present = _channel("d2q9", shape, nu=0.02)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("y", "x"))
    state = jax.tree.map(
        lambda x, sp: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, sp)),
        lat.state, halo._state_specs(mesh))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, P())),
        lat.params)
    return m, mesh, present, state, params


@pytest.mark.parametrize("niter", [500, 250, 2])
def test_sharded_d2q9_4096_on_4x1_mesh(topo, niter):
    """The four-chip path of chip_smoke.py and of the two ``karman4096``
    cells: the sharded Pallas step over a y-split mesh of the described
    topology's devices, kernel and halo exchange both present in what
    the chip would run.  Compiled are **the engine's own jitted
    programs, donating their state as the engine donates it** (an outer
    ``jit`` drops the inner donation and the compile says nothing of the
    chip's program), for the engine steps of ``Lattice.iterate(niter)``:
    one less, the last step being the tail's.  499 and 249 steps are a
    loop of 249 and 124 kernel calls, two a body, and the odd step in a
    program of its own (``iterate(2)`` runs that one alone).  The
    loop's body holds two ``d2q9_band_fuse2`` calls and four
    ``collective-permute`` of the neighbours' (11, 8, 1024) blocks,
    which the kernel takes as operands of their own: **no ``pad``,
    ``concatenate`` or copy of an array of the shard's size, and no move
    of the state in or out of the compiler's fast memory, inside the
    ``while``; the carry and the first call's result both live in that
    memory** (``S(1)``; the state goes in once before the loop and comes
    out once after it): the ``kernel2`` that waited for its input copies
    read 0.180 ns an update on a state in HBM for 0.103 (chip, PR 47;
    it prefetches its band since PR 50).
    With the one-step kernel in the same program the compiler keeps the
    first call's result in HBM: hence the two programs."""
    from tclb_tpu.parallel import halo
    m, mesh, present, state, params = _karman_4096_on_4x1_mesh(topo)
    it = halo.make_sharded_pallas_iterate(m, mesh, (4096, 1024), jnp.float32,
                                          present=present, interpret=False)
    assert it is not None
    trips, odd = divmod(niter - 1, 2)
    assert odd == 1
    assert it.account(niter - 1) == dict(
        kernel_calls=trips + 1, paired_calls=trips - trips % 2,
        halo_operand_rows=8)
    lowered = it.impl["program"](trips, int(not trips)).lower(state, params)
    assert lowered.args_info[0][0].fields.donated
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    assert "halo_exchange/" in text
    if not trips:
        # the odd step: one call of the one-step kernel on the shard as
        # it is and the two blocks, no shard extended by its halo rows
        assert "d2q9_band_fuse1/pallas_call" in text
        assert "d2q9_band_fuse2/pallas_call" not in text
        assert "f32[11,1040,1024]" not in text
        return
    assert "d2q9_band_fuse2/pallas_call" in text
    assert "d2q9_band_fuse1/pallas_call" not in text
    body, calls = _kernel_loop_body(text, "d2q9_band_fuse2")
    assert calls == 2
    permutes = [line for line in body
                if re.search(r"= \(.*\) collective-permute-start\(", line)]
    assert len(permutes) == 4
    assert all(line.split("= (")[1].startswith("f32[11,8,1024]")
               for line in permutes)
    # nothing of the shard's size (11 planes of 1024 rows and more) is
    # made in the body beside the two kernels' results
    made = [line.strip() for line in body
            if re.search(r"= f32\[11,1\d{3},1024\]\S* (?!custom-call|"
                         r"get-tuple-element|parameter)", line)]
    assert not made, made
    assert not _state_moves(body, m, (1024, 1024))
    # both of the loop's state buffers in the compiler's fast memory
    assert _in_fast_memory(body) == [True, True]


def test_sharded_tail_4096_on_4x1_mesh(topo):
    """The step under ``iterate.globals_step`` in the two ``karman4096``
    cells (``parallel/halo.make_sharded_pallas_tail``), compiled for the
    described 4 x 1 mesh at the cell's shard, 11 x 1024 x 1024: one
    program of ONE ``generic_band_fuse1`` call with in-kernel globals, the
    neighbours' 8 rows of the fields and of the aux stack by
    ``collective-permute``, one ``all-reduce`` of the Globals' partial
    sums, **not donating** (a failed probe leaves the state whole), and
    nothing of the state's size made beside the kernel's result but the
    one padded operand (11 x 1040 x 1024) the kernel reads."""
    from tclb_tpu.parallel import halo
    m, mesh, present, state, params = _karman_4096_on_4x1_mesh(topo)
    tail = halo.make_sharded_pallas_tail(m, mesh, (4096, 1024), jnp.float32,
                                         present=present, interpret=False)
    assert tail.full_globals and tail.unproven and tail.fuse == 1
    assert tail.account(1) == dict(
        kernel_calls=1, paired_calls=0, halo_operand_rows=0,
        stages_per_step=1, bands=32, band_rows=32, halo_rows=8, aux_planes=3)
    lowered = tail.impl["program"].lower(state, params)
    assert not lowered.args_info[0][0].fields.donated
    compiled = lowered.compile()
    assert compiled.output_shardings.globals_.is_fully_replicated
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "generic_band_fuse1/pallas_call" in text
    assert "halo_exchange/" in text
    permutes = re.findall(r"= \((f32\[\d+,8,1024\])\S*, .*\) "
                          r"collective-permute-start\(", text)
    assert sorted(permutes) == ["f32[11,8,1024]"] * 2 + ["f32[3,8,1024]"] * 2
    # the Globals: one all-reduce of three sums, no max beside it
    assert len(re.findall(r"= f32\[3\]\S* all-reduce(-start)?\(",
                          text)) == 1
    entry, = [lines for name, lines in _computations(text).items()
              if name.startswith("main")]
    assert not _state_copies(entry, m, (1024, 1024))
    made = [line.strip() for line in entry
            if re.search(r"= f32\[11,1\d{3},1024\]\S* (?!custom-call|"
                         r"get-tuple-element|parameter|bitcast)", line)]
    # the padded operand; what else there is of the state's size is the
    # input on its way into the compiler's fast memory, where the pad
    # reads it
    padded = [line for line in made if "f32[11,1040,1024]" in line]
    assert len(padded) == 1 and "halo_exchange/concatenate" in padded[0]
    assert all(re.search(r"S\(1\)\} copy-done\(", line)
               for line in made if line not in padded), made
    assert len(made) <= 2, made


def _quantity_on_4x1_mesh(topo, programs, name, quantity):
    """One of a quantity's two programs compiled at the mesh cell's size,
    the state split by rows over four of the described chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tclb_tpu.core.lattice import SimParams
    from tclb_tpu.parallel import halo
    shape = (4096, 1024)
    m = get_model(name)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("y", "x"))

    def on(spec, *dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))

    n = len(m.settings)
    program, _ = programs(m, quantity, jnp.dtype(jnp.float32), "raw")
    return program.lower(
        on(halo.field_spec(mesh), m.n_storage, *shape),
        on(halo.flag_spec(mesh), *shape, dtype=jnp.uint16),
        SimParams(settings=on(P(), n), zone_table=on(P(), n, m.zone_max)),
        on(P(), dtype=jnp.int32), on(P(), dtype=jnp.int32)).compile()


MESH_QUANTITIES = [("d2q9", "U"), ("d2q9_kuper", "F")]


@pytest.mark.parametrize("name,quantity", MESH_QUANTITIES)
def test_quantity_program_on_4x1_mesh(topo, name, quantity):
    """`Lattice.get_quantity`'s compiled program at the mesh cell's size:
    partitioned by the compiler, its result sharded by rows like the
    state (the gather to the host stays `quantity.d2h`'s), and no plane
    gathered between chips: `F`'s rolls of `phi` exchange edge rows only."""
    from jax.sharding import PartitionSpec as P

    from tclb_tpu.core.lattice import quantity_program
    compiled = _quantity_on_4x1_mesh(topo, quantity_program, name, quantity)
    assert compiled.output_shardings.spec == P(None, "y", "x")
    text = compiled.as_text()
    assert "all-gather" not in text
    edges = re.search(r"collective-permute|all-to-all", text)
    assert bool(edges) == (quantity == "F")


@pytest.mark.parametrize("name,quantity", MESH_QUANTITIES)
def test_count_program_on_4x1_mesh(topo, name, quantity):
    """`<Failcheck>`'s program there: every chip counts its own rows and
    one scalar all-reduce ends the sum on all four; what leaves the
    program is four bytes, and its temporaries are no more than the
    plane's program holds."""
    from tclb_tpu.core.lattice import nonfinite_program, quantity_program
    compiled = _quantity_on_4x1_mesh(topo, nonfinite_program, name, quantity)
    assert compiled.output_shardings.is_fully_replicated
    text = compiled.as_text()
    assert re.search(r"-> s32\[\] \{", text[text.index("ENTRY"):])
    assert "all-gather" not in text
    assert re.search(r"s32\[\]\S* all-reduce", text)
    edges = re.search(r"collective-permute|all-to-all", text)
    assert bool(edges) == (quantity == "F")
    memory = compiled.memory_analysis()
    plane = _quantity_on_4x1_mesh(topo, quantity_program, name,
                                  quantity).memory_analysis()
    assert memory.output_size_in_bytes < 1024 < plane.output_size_in_bytes
    assert memory.temp_size_in_bytes <= plane.temp_size_in_bytes + 2**20


def test_generic_d3q19_heat_builder_defaults(one_chip):
    """The 3D generic builder at its own defaults (fuse=1, every node
    type) is refused at 48x48x256 — 18.08M of scoped VMEM against a
    16.00M limit that the planner's budget does not see — while the fuse
    the Lattice picks there (choose_fuse_3d -> 3) compiles.  A whole-plane
    plan keeps the account it had (PR 34 tiles only planes no such plan
    holds).  Pinned as an expected failure so the planner fix (ROADMAP M1)
    has a target; any other error fails."""
    shape = (48, 48, 256)
    m, lat, _ = _channel("d3q19_heat", shape)
    it = pallas_generic.make_pallas_iterate(m, shape, jnp.float32,
                                            interpret=False)
    try:
        text = _compile(it, lat, 4, one_chip)
    except Exception as e:  # noqa: BLE001 — only the VMEM refusal is known
        refusal = re.search(r"Scoped allocation with size \S+ and limit "
                            r"\S+ exceeded scoped vmem limit by \S+M",
                            str(e))
        if refusal:
            pytest.xfail(refusal.group(0))
        raise
    assert "tpu_custom_call" in text


def test_pin_is_identity_only_inside_a_compiled_body():
    """The one mechanism that keeps optimization_barrier away from
    Mosaic: barrier under XLA and in an interpret-mode body, nothing in
    a body traced for a compiled pallas_call."""
    def body(x):
        return lbm.pin(x) + 1.0

    x = jnp.ones((8, 128), jnp.float32)
    assert "optimization_barrier" in str(jax.make_jaxpr(body)(x))
    assert "optimization_barrier" in str(
        jax.make_jaxpr(lbm.mosaic_body(body, interpret=True))(x))
    assert "optimization_barrier" not in str(
        jax.make_jaxpr(lbm.mosaic_body(body, interpret=False))(x))
    assert "optimization_barrier" in str(jax.make_jaxpr(body)(x))


@pytest.mark.parametrize("case", ["channel512", "tgv256", "karman1024",
                                  "karman1024_sampled", "karman8192"])
def test_tail_engine_is_one_call_and_copies_no_state(one_chip, monkeypatch,
                                                     case):
    """The one step the hybrid engines leave for the Globals, as the
    lattices of ``channel3d512``, ``tgv256`` and ``karman1024`` build it
    (``Lattice._build_tail``; in 3D by shapes only): the generic engine's
    one-step flavour that reduces the Globals in the kernel, one
    ``generic_slab_fuse1`` / ``generic_band_fuse1`` call.  The channel's
    one slab of 48 x 256 needs 16.14 MiB of scoped VMEM and compiles
    under the raised ceiling, by the window's own account.  The program
    of one call does not donate the state, so XLA puts no copy of it
    (0.86 and 2.28 GB in 3D) between the fused program's output and the
    kernel: donated, the call's output would have to be the buffer it
    reads halos from.  Under a sampler (``karman1024probes``) the
    tail is built with the probes and its one call returns their planes
    beside the state: the same one call, still no copy.  At rows of
    8192 nodes (``karman8192``: 64 rows stand for its 8192, the band is
    the same 32) the generic band had no plan and this step was XLA's;
    its band is planned under the raised limit now."""
    sampled = case == "karman1024_sampled"
    if case.startswith("karman"):
        shape = (64, 8192) if case == "karman8192" else (1024, 1024)
        m, lat, _ = _channel("d2q9", shape, nu=0.02)
        if sampled:
            from tclb_tpu.utils.sampler import Sampler
            lat.attach_sampler(Sampler(m, ["U", "Rho"], _PROBES, "unused"))
        state, params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip),
            (lat.state, lat.params))
        kernel = "generic_band_fuse1"
    else:
        m, shape, state, params, present = (
            _tgv_256 if case == "tgv256" else _channel_512)(one_chip)
        # a small lattice that says the cell's shape and node types
        lat = Lattice(m, (8, 8, 128), dtype=jnp.float32)
        flags = np.full(shape, m.flag_for("MRT"), dtype=np.uint16)
        if "Wall" in present:
            flags[:, 0, :] = flags[:, -1, :] = m.flag_for("Wall")
        lat.shape, lat._host_flags = shape, flags
        kernel = "generic_slab_fuse1"
    # the kernels are built to be compiled, as on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tail, tag = lat._build_tail()
    by = ",by=32" if case == "tgv256" else ""
    assert tag == f"pallas_generic[{m.name},fuse=1{by}]"
    assert tail.full_globals and tail.samples == sampled
    did = tail.account(1, False)
    assert (did["kernel_calls"], did["aux_planes"]) == (1, 1)
    if case == "karman8192":
        assert (did["band_rows"], did["bands"]) == (32, 2)
    if not case.startswith("karman"):
        assert tail.plan == {"channel512": (1, 48, 1),
                             "tgv256": (4, 32, 1)}[case]
    one = lambda s, p: tail(s, p, 1)    # noqa: E731
    # the program the engine picks for its one call does not donate
    inner, = [e for e in jax.make_jaxpr(one)(state, params).eqns
              if e.primitive.name in ("pjit", "jit")]
    assert not any(inner.params["donated_invars"])
    text = jax.jit(one).lower(state, params).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"{kernel}/pallas_call" in text
    assert not _state_copies(text.splitlines(), m, shape)
    if not case.startswith("karman"):
        # donated, as every schedule of two calls and more is, it would
        donated = jax.jit(one, donate_argnums=0)
        assert len(_state_copies(donated.lower(
            state, params).compile().as_text().splitlines(),
            m, shape)) == 1


def _tgv_384_on_4x1x1_mesh(topo) -> tuple:
    """``example/tgv_384.xml`` on a z-split mesh of four of the described
    chips, by shapes only (the lattice is 7.7 GB): the model, the mesh,
    and the shapes of its state and parameters with their shardings."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tclb_tpu.core.lattice import LatticeState
    from tclb_tpu.parallel import halo
    shape = (384, 384, 384)
    m = get_model("d3q27_cumulant")
    small = Lattice(m, (8, 8, 128), dtype=jnp.float32,
                    settings={"nu": 0.00191, "Velocity": 0.05})
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1, 1),
                ("z", "y", "x"))

    def on(x, spec, dims=None):
        return jax.ShapeDtypeStruct(dims or x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))
    st, specs = small.state, halo._state_specs(mesh)
    state = LatticeState(
        fields=on(st.fields, specs.fields, (m.n_storage,) + shape),
        flags=on(st.flags, specs.flags, shape),
        globals_=on(st.globals_, P()), iteration=on(st.iteration, P()))
    params = jax.tree.map(lambda x: on(x, P()), small.params)
    return m, mesh, shape, state, params


@pytest.mark.parametrize("fuse,niter", [(None, 249), (1, 5)],
                         ids=["fuse3", "fuse1"])
def test_sharded_d3q27_cumulant_384_on_4x1x1_mesh(topo, fuse, niter):
    """The four-chip path of the cell ``tgv384.zsplit``: the engine's own
    jitted program for the 249 engine steps of ``Lattice.iterate(250)``,
    donating its state, compiled for a z-split mesh of the described
    topology's devices at shards of 96 x 384 x 384.  No kernel holds a
    384 x 384 plane whole: the shard's plan is y-tiled, (3, 24, 3), 83
    calls of three steps and none left over, and under it in dispatch's
    chain stands the K = 1 plan (3, 48, 1).  The loop's body holds two
    calls of the fused kernel and four ``collective-permute`` of the
    neighbours' (34, K, 384, 384) slabs, which the kernel takes as
    operands of their own: nothing of the shard's size is padded,
    concatenated or copied inside the ``while``."""
    from tclb_tpu.parallel import halo
    m, mesh, shape, state, params = _tgv_384_on_4x1x1_mesh(topo)
    local = (96, 384, 384)
    assert pallas_d3q._slab_depth(m, *local) is None
    assert pallas_d3q.tile_plan(m, local) == (3, 24, 3)
    assert pallas_d3q.tile_plan(m, local, fuse=1) == (3, 48, 1)
    it = halo.make_sharded_pallas_iterate(m, mesh, shape, jnp.float32,
                                          present={"MRT"}, interpret=False,
                                          fuse=fuse)
    K = fuse or 3
    assert it is not None and it.fuse == K and it.unproven
    assert it.plan == pallas_d3q.tile_plan(m, local, fuse=fuse)
    trips, rest = divmod(niter, K)
    did = it.account(niter)
    assert (did["kernel_calls"], did["remainder_steps"], did["shards"],
            did["halo_operand_slabs"]) == (trips + rest, rest, 4, K)
    assert did["paired_calls"] == trips - trips % 2
    lowered = it.impl["program"](trips, rest).lower(state, params)
    assert lowered.args_info[0][0].fields.donated
    text = lowered.compile().as_text()
    assert "halo_exchange/" in text
    body, calls = _kernel_loop_body(text, f"d3q_slab_fuse{K}")
    assert calls == 2
    permutes = [line for line in body
                if re.search(r"= \(.*\) collective-permute-start\(", line)]
    assert len(permutes) == 4
    assert all(line.split("= (")[1].startswith(f"f32[34,{K},384,384]")
               for line in permutes)
    made = [line.strip() for line in body
            if re.search(r"= f32\[34,(9\d|1\d\d),384,384\]\S* (?!custom-call|"
                         r"get-tuple-element|parameter)", line)]
    assert not made, made


def test_sharded_tail_384_on_4x1x1_mesh(topo):
    """The step under ``iterate.globals_step`` in the cell
    ``tgv384.zsplit`` (``parallel/halo.make_sharded_pallas_tail`` on a 3D
    mesh), compiled for the described 4 x 1 x 1 mesh at the cell's shard,
    96 x 384 x 384, which the generic planner cuts as it cuts ``tgv256``:
    windows of 4 slabs x 32 rows.  One program of ONE
    ``generic_slab_fuse1`` call with in-kernel globals on the shard as it
    is; the neighbours' one slab a side of the fields and of the f32 flag
    plane by ``collective-permute``; one ``all-reduce`` of ``Flux``'s
    partial sums; **not donating** (a failed probe leaves the state
    whole); and nothing of the shard's size (34 x 96 x 384 x 384, 1.93
    GB) made beside the kernel's result: no padded copy of the fields."""
    from tclb_tpu.parallel import halo
    m, mesh, shape, state, params = _tgv_384_on_4x1x1_mesh(topo)
    local = (96, 384, 384)
    assert pallas_generic.tile_plan_3d(m, local, fuse=1) == (4, 32, 1)
    tail = halo.make_sharded_pallas_tail(m, mesh, shape, jnp.float32,
                                         present={"MRT"}, interpret=False)
    assert tail.full_globals and tail.unproven and tail.fuse == 1
    did = tail.account(1)
    assert did.pop("vmem_bytes") <= pallas_generic._TILED3D_BUDGET
    assert did == dict(
        kernel_calls=1, paired_calls=0, remainder_steps=0, shards=4,
        stages_per_step=1, z_bands=24, band_slabs=4, halo_slabs=1,
        y_bands=12, band_rows=32, halo_rows=8, aux_planes=1,
        halo_operand_slabs=1)
    lowered = tail.impl["program"].lower(state, params)
    assert not lowered.args_info[0][0].fields.donated
    compiled = lowered.compile()
    assert compiled.output_shardings.globals_.is_fully_replicated
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "generic_slab_fuse1/pallas_call" in text
    assert "halo_exchange/" in text
    permutes = re.findall(r"= \((f32\[\d+,1,384,384\])\S*, .*\) "
                          r"collective-permute-start\(", text)
    assert sorted(permutes) == (["f32[1,1,384,384]"] * 2
                                + ["f32[34,1,384,384]"] * 2)
    # the Globals: one all-reduce of one sum, no max beside it
    assert len(re.findall(r"= f32\[1\]\S* all-reduce(-start)?\(",
                          text)) == 1
    entry, = [lines for name, lines in _computations(text).items()
              if name.startswith("main")]
    assert not _state_copies(entry, m, local)
    made = [line.strip() for line in entry
            if re.search(r"= f32\[34,(9\d|1\d\d),384,384\]\S* (?!custom-call|"
                         r"get-tuple-element|parameter|bitcast)", line)]
    assert not made, made
    # the shard and the kernel's result: nothing else of that size is
    # on a chip while the program runs
    mem = compiled.memory_analysis()
    fields = 34 * 96 * 384 * 384 * 4
    assert mem.temp_size_in_bytes < 0.1 * fields
