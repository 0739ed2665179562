"""Telemetry contract tests: strict no-op when disabled, JSONL schema
when enabled, engine dispatch events on the real Lattice (including the
forced-fallback path), failcheck events, report aggregation, and the
--compare regression detector on synthetic traces.
"""

import json
import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tclb_tpu import telemetry
from tclb_tpu.core import lattice as lattice_mod
from tclb_tpu.core.lattice import Lattice
from tclb_tpu.models import get_model
from tclb_tpu.ops import pallas_d2q9
from tclb_tpu.ops.engine import Engine
from tclb_tpu.telemetry import report
from tclb_tpu.telemetry import spans as spans_mod
from tclb_tpu.telemetry.spans import NOOP_SPAN
from tclb_tpu.utils import log


@pytest.fixture(autouse=True)
def _sink_off():
    """Telemetry is process-global: every test starts and ends disabled."""
    telemetry.disable()
    yield
    telemetry.disable()


class _Untouchable:
    """Stands in for a module a disabled site must not use."""

    def __init__(self, what):
        self._what = what

    def __getattr__(self, name):
        raise AssertionError(f"telemetry is off: {self._what}.{name}")


def _mrt_lattice(ny=8, nx=16):
    m = get_model("d2q9")
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.05})
    lat.set_flags(np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16))
    lat.init()
    return m, lat


def _karman_lattice(ny=64, nx=128):
    m = get_model("d2q9")
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.05, "Velocity": 0.03})
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[:, 0] = m.flag_for("WVelocity", "MRT")
    flags[:, -1] = m.flag_for("EPressure", "MRT")
    flags[0, :] = m.flag_for("Wall")
    flags[-1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    return m, lat


# --------------------------------------------------------------------------- #
# Disabled mode: strict no-op
# --------------------------------------------------------------------------- #


def test_disabled_is_strict_noop(monkeypatch):
    assert not telemetry.enabled()
    assert telemetry.path() is None
    telemetry.event("anything", x=1)          # must not raise or write
    telemetry.counter("c", 5)
    assert telemetry.counters() == {}

    # the disabled span is the shared no-op singleton: no clock, no jax
    sp = telemetry.span("iterate", iters=10)
    assert sp is NOOP_SPAN
    sentinel = object()

    def boom(_):
        raise AssertionError("disabled span must never touch jax")

    monkeypatch.setattr(jax, "block_until_ready", boom)
    monkeypatch.setattr(spans_mod, "time", _Untouchable("the clock"))
    with sp:
        sp.add(engine="xla")
        sp.mark("dispatch_s")
        assert sp.sync(sentinel) is sentinel


def test_disabled_lattice_iterate_never_syncs(monkeypatch):
    _, lat = _mrt_lattice()

    real = jax.block_until_ready

    def boom(_):
        raise AssertionError("disabled iterate must not fence")

    monkeypatch.setattr(jax, "block_until_ready", boom)
    # nor read the clock (pre_sync_s), nor the device (its iteration)
    monkeypatch.setattr(lattice_mod, "time", _Untouchable("the clock"))
    lat.iterate(2)                             # telemetry disabled
    monkeypatch.setattr(jax, "block_until_ready", real)
    assert int(lat.state.iteration) == 2


# --------------------------------------------------------------------------- #
# Enabled mode: JSONL schema
# --------------------------------------------------------------------------- #


def test_enabled_schema_golden(tmp_path):
    trace = tmp_path / "t.jsonl"
    telemetry.enable(str(trace))
    assert telemetry.enabled() and telemetry.path() == str(trace)
    telemetry.event("custom", n=np.int64(3), arr=np.arange(2))
    telemetry.counter("halo.exchanges", 4)
    telemetry.counter("halo.exchanges", 2)
    with telemetry.span("work", nodes=1000.0, iters=100) as sp:
        sp.add(engine="xla")
    telemetry.disable()
    assert not telemetry.enabled()

    lines = [json.loads(x) for x in trace.read_text().splitlines()]
    kinds = [e["kind"] for e in lines]
    assert kinds == ["trace_start", "custom", "span", "counters"]
    head = lines[0]
    assert head["schema"] == 1
    assert head["pid"] == os.getpid()
    assert isinstance(head["version"], str)
    assert all(isinstance(e["ts"], float) for e in lines)
    assert lines[1]["n"] == 3 and lines[1]["arr"] == [0, 1]  # numpy coerced
    span_evt = lines[2]
    assert span_evt["name"] == "work" and span_evt["engine"] == "xla"
    assert span_evt["dur_s"] >= 0 and "mlups" in span_evt
    assert lines[3]["counters"] == {"halo.exchanges": 6}


def test_load_skips_truncated_lines(tmp_path):
    trace = tmp_path / "t.jsonl"
    trace.write_text('{"kind": "a", "ts": 1.0}\n'
                     '{"kind": "b", "ts": 2.0'        # crash mid-write
                     '\n\n{"kind": "c", "ts": 3.0}\n')
    assert [e["kind"] for e in report.load(str(trace))] == ["a", "c"]


# --------------------------------------------------------------------------- #
# Lattice dispatch events
# --------------------------------------------------------------------------- #


def test_lattice_iterate_emits_engine_and_span(tmp_path, monkeypatch):
    monkeypatch.delenv("TCLB_FASTPATH", raising=False)
    trace = tmp_path / "t.jsonl"
    telemetry.enable(str(trace))
    m, lat = _mrt_lattice()
    lat.iterate(3)
    lat.iterate(2)
    telemetry.disable()

    evts = report.load(str(trace))
    sel = [e for e in evts if e["kind"] == "engine_selected"]
    assert len(sel) == 1                      # once per built engine
    assert sel[0]["engine"] == "xla"          # CPU + auto => XLA path
    assert sel[0]["model"] == "d2q9" and sel[0]["shape"] == [8, 16]

    it = [e for e in evts if e["kind"] == "span" and e["name"] == "iterate"]
    assert [e["iters"] for e in it] == [3, 2]
    assert [e["iteration"] for e in it] == [0, 3]
    for e in it:
        assert e["engine"] == "xla"
        assert e["nodes"] == 8 * 16
        assert e["mlups"] > 0
        # the device read of the iteration before the span opened, and
        # the span's own fence
        assert 0 <= e["pre_sync_s"] and 0 <= e["wait_s"] <= e["dur_s"]
        # no roofline from a host-fenced wall over a fixed byte count
        assert not {"bytes_per_node", "vs_roofline", "roofline_known",
                    "device_kind"} & set(e)


def _failing_engine(*args, **kw):
    def it(state, params, niter):
        raise RuntimeError("synthetic mosaic failure")
    return Engine(it)


def _chain_resident(monkeypatch):
    """tuned resident -> tuned 2D band"""
    monkeypatch.setattr(pallas_d2q9, "make_resident_iterate",
                        _failing_engine)
    return (_karman_lattice()[1], "pallas_resident[d2q9,fuse=8]",
            "pallas_2d[d2q9,fuse=2]")


def _chain_d3q(monkeypatch):
    """pallas_d3q fuse K >= 2 -> fuse 1"""
    from tclb_tpu.ops import pallas_d3q
    real = pallas_d3q.make_pallas_iterate
    monkeypatch.setattr(
        pallas_d3q, "make_pallas_iterate",
        lambda *a, **kw: (real if kw.get("fuse") == 1
                          else _failing_engine)(*a, **kw))
    m = get_model("d3q27_BGK")
    shape = (8, 16, 64)
    lat = Lattice(m, shape, dtype=jnp.float32,
                  settings={"omega": 1.0, "GravitationX": 1e-5})
    flags = np.full(shape, m.flag_for("BGK"), dtype=np.uint16)
    flags[:, 0, :] = flags[:, -1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    k3 = pallas_d3q.choose_fuse(m, shape)
    assert k3 >= 2
    return (lat, f"pallas_d3q[d3q27_BGK,fuse={k3}]",
            "pallas_d3q[d3q27_BGK,fuse=1]")


def _chain_generic_resident(monkeypatch):
    """generic resident -> generic band, as the planner fuses it"""
    from tclb_tpu.ops import pallas_generic
    monkeypatch.setattr(pallas_generic, "make_resident_iterate",
                        _failing_engine)
    m = get_model("d2q9_heat")
    lat = Lattice(m, (16, 128), dtype=jnp.float32,
                  settings={"nu": 0.05, "FluidAlfa": 0.05,
                            "InletVelocity": 0.02})
    flags = np.full((16, 128), m.flag_for("BGK"), dtype=np.uint16)
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    return (lat, "pallas_resident_generic[d2q9_heat]",
            f"pallas_generic[d2q9_heat,fuse={pallas_generic.choose_fuse(m)}]")


@pytest.mark.parametrize("chain", [_chain_resident, _chain_d3q,
                                   _chain_generic_resident])
def test_forced_fallback_emits_events(tmp_path, monkeypatch, chain):
    """Break the probe of a chain's first engine: the dispatch must land
    on the engine under it AND leave one engine_fallback breadcrumb with
    the cause."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    lat, selected, under = chain(monkeypatch)
    it0 = int(lat.state.iteration)
    trace = tmp_path / "t.jsonl"
    telemetry.enable(str(trace))
    niter = 5
    lat.iterate(niter)
    telemetry.disable()

    assert lat._fast_name == under and not lat._fast_probing
    assert int(lat.state.iteration) == it0 + niter

    evts = report.load(str(trace))
    sel = [e for e in evts if e["kind"] == "engine_selected"]
    assert sel and sel[0]["engine"] == selected
    assert sel[0]["probed"] is True
    fb = [e for e in evts if e["kind"] == "engine_fallback"]
    assert len(fb) == 1
    assert (fb[0]["from"], fb[0]["to"]) == (selected, under)
    assert "synthetic mosaic failure" in fb[0]["cause"]
    # the chain's own probe (the engine of a hybrid's trailing step has
    # one of its own)
    probe = [e for e in evts
             if e["kind"] == "span" and e["name"] == "engine.probe"
             and e["engine"] == selected]
    assert len(probe) == 1
    assert probe[0]["result"] == under
    assert (probe[0]["attempts"], probe[0]["rungs"]) == (2, [])
    # the iterate span records the engine that actually finished the chunk
    it = [e for e in evts if e["kind"] == "span" and e["name"] == "iterate"]
    assert it and it[-1]["engine"] == under
    # and the engine under it was handed the real state: a second call
    # goes straight to it
    lat.iterate(niter)
    assert int(lat.state.iteration) == it0 + 2 * niter


def test_a_probe_has_one_candidate_span_a_run(monkeypatch):
    """The chain's first engine fails its probe: two candidate runs
    under the one ``engine.probe``, each with its tag and cap, the first
    with the exception's class as ``result``; the account of the engine
    that ran stays on the probe."""
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    lat, selected, under = _chain_resident(monkeypatch)
    docs = []
    telemetry.subscribe(docs.append)
    try:
        lat.iterate(5)
    finally:
        telemetry.unsubscribe(docs.append)
    spans = [e for e in docs if e["kind"] == "span"]
    probe, = [e for e in spans if e["name"] == "engine.probe"
              and e["engine"] == selected]
    runs = [e for e in spans if e["name"] == "engine.probe.candidate"
            and e["parent"] == probe["id"]]
    assert [(e["tag"], e["cap"], e["result"]) for e in runs] == [
        (selected, 0, "RuntimeError"), (under, 0, "ran")]
    assert probe["attempts"] == len(runs) and probe["result"] == under
    # the probed one ran on a copy of the state, made and fenced first;
    # the proven band under it on the state itself
    assert 0 <= runs[0]["copy_s"] <= runs[0]["dur_s"]
    assert "wait_s" in runs[0] and "copy_s" not in runs[1]
    assert "kernel_calls" in probe
    assert not any("kernel_calls" in e for e in runs)
    # the tail engine's probe is one candidate too, with no copy
    tail, = [e for e in spans if e["name"] == "engine.probe"
             and e["engine"] != selected]
    mine, = [e for e in spans if e["name"] == "engine.probe.candidate"
             and e["parent"] == tail["id"]]
    assert (mine["tag"], mine["result"]) == (tail["engine"], "ran")
    assert "copy_s" not in mine


@pytest.mark.parametrize("name,shape,cached", [
    ("d2q9_kuper", (32, 128), None),
    ("d3q19_heat", (16, 16, 128), None),
    ("d2q9_kuper", (32, 128), (1, 16))])
def test_generic_band_ladder_is_data(monkeypatch, name, shape, cached):
    """The generic band engine's chain as ``_build_fast`` lists it, tags
    and caps, with no kernel built: the planner's choice first, then
    smaller bands, then no fusion, then (3D) the raised ceiling; one
    unprobed link where an earlier probe left its verdict."""
    from tclb_tpu.ops import pallas_generic
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    monkeypatch.setattr(pallas_generic, "_mosaic_verdict", {})
    monkeypatch.setattr(pallas_generic, "_cfg_cache", {})
    monkeypatch.setattr(pallas_generic, "supports_resident",
                        lambda *a, **k: False)
    monkeypatch.setattr(pallas_generic, "supports", lambda *a, **k: True)
    monkeypatch.setattr(
        pallas_generic, "make_pallas_iterate",
        lambda *a, **k: pytest.fail("a chain is listed, not built"))
    m = get_model(name)
    if cached:
        pallas_generic.set_build_cfg(m, shape, *cached)
    lat = Lattice(m, shape, dtype=jnp.float32)
    chain = lat._build_fast()
    if cached:
        assert [(c.tag, c.probe, c.cap, c.verdict) for c in chain] == [
            (f"pallas_generic[{name},fuse={cached[0]}]", False, 0, None)]
        return
    fz = (pallas_generic.choose_fuse(m) if len(shape) == 2
          else pallas_generic.choose_fuse_3d(m, shape))
    assert fz >= 2
    rungs = [(fz, 16), (fz, 8), (1, 16), (1, 8)]
    if len(shape) == 3:
        rungs += [(fz, -16), (fz, -8)]
    assert [c.tag for c in chain] == [f"pallas_generic[{name},fuse={fz}]"] + [
        f"pallas_generic[{name},fuse={f},by<={cap}]" for f, cap in rungs]
    # the planner's own band reads as the 2D default cap; 3D has none
    first_cap = pallas_generic._DEFAULT_BY_CAP if len(shape) == 2 else 0
    assert [c.cap for c in chain] == [first_cap] + [cap for _, cap in rungs]
    assert [c.verdict for c in chain] == [(fz, None)] + rungs
    assert all(c.probe for c in chain)


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_exhausted_ladder_raises_on_tpu(tmp_path, monkeypatch, backend):
    """Every rung of the probe ladder fails to compile: on a TPU backend
    the run must raise with the first exception (an XLA run under a
    Pallas name would read as a result); off the chip it still lands on
    XLA, with a breadcrumb."""
    from tclb_tpu.ops import pallas_generic
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    # process-wide verdict memos: keep this test's refusals out of others
    monkeypatch.setattr(pallas_generic, "_mosaic_verdict", {})
    monkeypatch.setattr(pallas_generic, "_cfg_cache", {})
    monkeypatch.setattr(pallas_generic, "supports_resident",
                        lambda *a, **k: False)
    # supports() trace-probes through the builder patched below
    monkeypatch.setattr(pallas_generic, "supports", lambda *a, **k: True)
    rungs, failed = [], []

    def bad_band(model, shape, dtype, **kw):
        # (the engine of a hybrid's trailing step is built here too,
        # before the first call, and never run: nothing reached it)
        rungs.append((kw.get("fuse"), kw.get("by_cap")))

        def it(state, params, niter):
            failed.append(rungs[-1])
            raise RuntimeError(f"synthetic mosaic failure #{len(failed)}")
        return Engine(it)

    monkeypatch.setattr(pallas_generic, "make_pallas_iterate", bad_band)
    m = get_model("d2q9_kuper")
    lat = Lattice(m, (32, 128), dtype=jnp.float32)
    lat.set_flags(np.full((32, 128), m.flag_for("MRT"), dtype=np.uint16))
    lat.init()
    it0 = int(lat.state.iteration)     # kuper's Init action counts a step
    trace = tmp_path / "t.jsonl"
    telemetry.enable(str(trace))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if backend == "tpu":
        with pytest.raises(RuntimeError, match="failed to compile on the "
                           "TPU backend") as ei:
            lat.iterate(4)
        # chained from the FIRST failure, not the last rung's
        assert "synthetic mosaic failure #1" in repr(ei.value.__cause__)
    else:
        lat.iterate(4)
        assert lat._fast_name is None
        assert int(lat.state.iteration) == it0 + 4
    telemetry.disable()
    assert len(failed) > 1                    # the ladder was walked
    fb = [e for e in report.load(str(trace))
          if e["kind"] == "engine_fallback"]
    assert [e["to"] for e in fb] == ([] if backend == "tpu" else ["xla"])


# --------------------------------------------------------------------------- #
# Failcheck events
# --------------------------------------------------------------------------- #


def test_failcheck_event(tmp_path):
    from tclb_tpu.control.handlers import cbFailcheck
    from tclb_tpu.control.solver import ITERATION_STOP, Solver

    trace = tmp_path / "t.jsonl"
    telemetry.enable(str(trace))
    m = get_model("d2q9")
    s = Solver(m, output=str(tmp_path / "out") + "/")
    s.set_size((8, 16))
    s.lattice.set_flags(
        np.full((8, 16), m.flag_for("MRT"), dtype=np.uint16))
    s.lattice.init()
    f = np.asarray(s.lattice.state.fields).copy()
    f[0, 2, 3] = np.nan
    s.lattice.state = s.lattice.state.replace(fields=jnp.asarray(f))

    h = cbFailcheck(ET.Element("Failcheck"), s)
    h.init()
    assert h.do_it() == ITERATION_STOP
    telemetry.disable()

    fc = [e for e in report.load(str(trace)) if e["kind"] == "failcheck"]
    assert len(fc) == 1
    assert fc[0]["iteration"] == 0
    assert fc[0]["n_bad"] >= 1
    assert isinstance(fc[0]["quantity"], str) and fc[0]["quantity"]


# --------------------------------------------------------------------------- #
# Report aggregation + compare
# --------------------------------------------------------------------------- #

_ENG = "pallas_2d[d2q9,fuse=2]"


def _iterate_span(dur_s, nodes=8192.0, iters=100, engine=_ENG):
    return {"kind": "span", "ts": 1.0, "name": "iterate", "dur_s": dur_s,
            "iters": iters, "nodes": nodes, "engine": engine,
            "mlups": round(nodes * iters / dur_s / 1e6, 3)}


def _write_trace(path, events):
    with open(path, "w") as fh:
        for e in events:
            fh.write(json.dumps(e) + "\n")
    return str(path)


def test_summarize_engine_table(tmp_path):
    evts = [{"kind": "trace_start", "ts": 0.0, "schema": 1},
            _iterate_span(0.01), _iterate_span(0.01),
            {"kind": "span", "ts": 1.0, "name": "output.vtk",
             "dur_s": 0.25},
            {"kind": "engine_selected", "ts": 0.5, "engine": _ENG,
             "model": "d2q9"},
            {"kind": "counters", "ts": 2.0,
             "counters": {"halo.exchanges": 12}}]
    s = report.summarize(report.load(_write_trace(tmp_path / "a.jsonl",
                                                  evts)))
    g = s["engines"][_ENG]
    assert g["chunks"] == 2 and g["iters"] == 200
    assert g["mlups"] == pytest.approx(8192 * 200 / 0.02 / 1e6, rel=1e-3)
    assert "vs_roofline" not in g
    assert s["spans"]["output.vtk"]["count"] == 1
    assert s["counters"] == {"halo.exchanges": 12}
    txt = report.format_text(s)
    assert "per-engine iterate summary" in txt and _ENG in txt


_TAIL_3D = "pallas_sharded[generic,{'z': 4, 'y': 1, 'x': 1},fuse=1,globals]"
# what parallel/halo.make_sharded_pallas_tail says of a call at the
# shards of tgv384 (one shard's windows)
_TAIL_3D_ACCOUNT = dict(
    kernel_calls=1, paired_calls=0, remainder_steps=0, shards=4,
    stages_per_step=1, z_bands=24, band_slabs=4, halo_slabs=1, y_bands=12,
    band_rows=32, halo_rows=8, aux_planes=1, halo_operand_slabs=1,
    vmem_bytes=79724544, halo_bytes=41287680)


@pytest.mark.parametrize("engines", [
    ["pallas_generic[d3q27_cumulant,fuse=1]"] * 3,
    ["xla", "pallas_generic[d2q9,fuse=1]", "pallas_generic[d2q9,fuse=1]"],
    [None, None], [_TAIL_3D] * 2],
    ids=["tail", "fell_back_once", "older_trace", "mesh_3d"])
def test_report_lists_the_trailing_steps_by_engine(engines):
    """``iterate.globals_step`` spans by the ``engine`` they say (a trace
    from before they said one reads ``?``), with the counter beside; a
    tail engine's account, which lies on that span, is listed with the
    fused engines' (``shards`` and the windows of one shard on a mesh)."""
    evts = [dict({"kind": "span", "ts": 1.0, "name": "iterate.globals_step",
                  "dur_s": 0.005, "iters": 1},
                 **({"engine": eng} if eng else {}),
                 **(_TAIL_3D_ACCOUNT if eng == _TAIL_3D else {}))
            for eng in engines]
    tail_calls = sum(bool(e) and e != "xla" for e in engines)
    evts.append({"kind": "counters", "ts": 2.0,
                 "counters": {"engine.tail_calls": tail_calls}})
    s = report.summarize(evts)
    assert s["globals_steps"] == {
        eng or "?": {"steps": engines.count(eng),
                     "total_s": round(0.005 * engines.count(eng), 6)}
        for eng in set(engines)}
    txt = report.format_text(s)
    assert "trailing globals steps of the hybrid engines" in txt
    assert all((eng or "?") in txt for eng in engines)
    assert f"engine.tail_calls{'':<23} {tail_calls}" in txt
    if _TAIL_3D in engines:
        n = len(engines)
        assert s["accounts"] == {_TAIL_3D: dict(
            {k: v for k, v in _TAIL_3D_ACCOUNT.items()
             if k in report.ACCOUNT_PLAN},
            calls=n, kernel_calls=n, paired_calls=0, remainder_steps=0,
            halo_bytes=n * _TAIL_3D_ACCOUNT["halo_bytes"])}
        assert "fused calls by engine" in txt
        assert ("shards 4  z_bands 24  band_slabs 4  halo_slabs 1  "
                "y_bands 12  band_rows 32  halo_rows 8") in txt
    else:
        assert not s["accounts"]


def test_compare_detects_injected_slowdown(tmp_path):
    base = _write_trace(tmp_path / "base.jsonl",
                        [_iterate_span(0.010) for _ in range(3)])
    # candidate runs the same work 40% slower — far beyond the 5% gate
    other = _write_trace(tmp_path / "other.jsonl",
                         [_iterate_span(0.014) for _ in range(3)])
    diff = report.compare(report.summarize(report.load(base)),
                          report.summarize(report.load(other)))
    regs = [r for r in diff["regressions"] if r["what"] == "engine_mlups"]
    assert len(regs) == 1 and regs[0]["engine"] == _ENG
    assert regs[0]["delta_pct"] < -25

    # identical traces: clean bill
    diff2 = report.compare(report.summarize(report.load(base)),
                           report.summarize(report.load(base)))
    assert diff2["regressions"] == []


def test_compare_flags_new_fallbacks(tmp_path):
    base = _write_trace(tmp_path / "base.jsonl", [_iterate_span(0.01)])
    other = _write_trace(
        tmp_path / "other.jsonl",
        [{"kind": "engine_fallback", "ts": 0.1, "from": _ENG, "to": "xla",
          "cause": "RuntimeError('mosaic')"},
         _iterate_span(0.01, engine="xla")])
    diff = report.compare(report.summarize(report.load(base)),
                          report.summarize(report.load(other)))
    assert diff["fallback_drift"]["other"] == [[_ENG, "xla"]] \
        or diff["fallback_drift"]["other"] == [(_ENG, "xla")]
    assert any(r["what"] == "new_fallbacks" for r in diff["regressions"])


def test_report_cli(tmp_path, capsys):
    base = _write_trace(tmp_path / "base.jsonl",
                        [_iterate_span(0.010) for _ in range(3)])
    other = _write_trace(tmp_path / "other.jsonl",
                         [_iterate_span(0.020) for _ in range(3)])

    assert report.main(["report", base]) == 0
    assert _ENG in capsys.readouterr().out

    assert report.main(["report", base, "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["engines"][_ENG]["chunks"] == 3

    assert report.main(["report", base, "--compare", other,
                        "--fail-on-regression"]) == 4
    out = capsys.readouterr().out
    assert "REGRESSIONS" in out

    assert report.main(["report", base, "--compare", other,
                        "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["compare"]["regressions"]

    assert report.main(["report", str(tmp_path / "missing.jsonl")]) == 2
    capsys.readouterr()


# --------------------------------------------------------------------------- #
# Env activation + log-level validation (satellite)
# --------------------------------------------------------------------------- #


def test_env_activation_and_bad_log_level(tmp_path):
    """TCLB_TELEMETRY turns the sink on at import; a bogus TCLB_LOG warns
    once (naming the value and the accepted levels) and falls back."""
    trace = tmp_path / "env.jsonl"
    env = dict(os.environ, TCLB_TELEMETRY=str(trace), TCLB_LOG="bogus",
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c",
         "from tclb_tpu.utils import log\n"
         "from tclb_tpu import telemetry\n"
         "assert telemetry.enabled()\n"
         "telemetry.event('ping', x=1)\n"
         "telemetry.disable()\n"],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "TCLB_LOG" in r.stderr and "'bogus'" in r.stderr
    assert "debug" in r.stderr and "error" in r.stderr   # accepted levels
    kinds = [e["kind"] for e in report.load(str(trace))]
    assert kinds[0] == "trace_start" and "ping" in kinds


def test_set_level_rejects_unknown():
    old = log._threshold
    try:
        with pytest.raises(ValueError, match="bogus"):
            log.set_level("bogus")
        assert log._threshold == old          # unchanged on error
        log.set_level("warning")
        assert log._threshold == log.LEVELS["warning"]
    finally:
        log._threshold = old


# --------------------------------------------------------------------------- #
# The span tree: id / parent / t0, phase spans, compile events, halo bytes
# --------------------------------------------------------------------------- #


@pytest.fixture
def seen():
    """The event documents of a test, through a subscriber of its own."""
    docs = []
    telemetry.subscribe(docs.append)
    yield docs
    telemetry.unsubscribe(docs.append)


def _spans(docs, name=None):
    return [e for e in docs if e["kind"] == "span"
            and name in (None, e["name"])]


def test_span_tree_ids_parents_and_inherited_iteration(seen):
    import threading

    def segment(iteration, job):
        with telemetry.job_context(job):
            with telemetry.span("outer", iteration=iteration, job_id=job):
                with telemetry.span("middle"):
                    telemetry.event("note", x=1)
                    with telemetry.span("inner", iteration=iteration + 1):
                        telemetry.annotate(halo_bytes=7)
                telemetry.annotate(tagged="outer")

    threads = [threading.Thread(target=segment, args=(10 * k, f"j{k}"))
               for k in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)

    spans = _spans(seen)
    assert len({e["id"] for e in spans}) == 6          # process-wide ids
    for job, it in (("j1", 10), ("j2", 20)):
        mine = {e["name"]: e for e in spans if e["job_id"] == job}
        outer, middle, inner = mine["outer"], mine["middle"], mine["inner"]
        assert outer["parent"] is None
        assert middle["parent"] == outer["id"]
        assert inner["parent"] == middle["id"]         # never the other thread's
        # a child takes its parent's identifiers unless it is given one
        assert (outer["iteration"], middle["iteration"],
                inner["iteration"]) == (it, it, it + 1)
        assert inner["halo_bytes"] == 7 and outer["tagged"] == "outer"
        assert "halo_bytes" not in middle
        for e in (outer, middle, inner):
            assert e["t0"] <= e["ts"]
            assert e["t0"] + e["dur_s"] == pytest.approx(e["ts"], abs=0.05)
        assert outer["t0"] <= middle["t0"] <= inner["t0"]
        note = [e for e in seen if e["kind"] == "note"
                and e["parent"] == middle["id"]]
        assert len(note) == 1
    # outside any span: no parent stamped, annotate goes nowhere
    telemetry.event("note", x=2)
    telemetry.annotate(lost=True)
    assert "parent" not in seen[-1]


_SOLVE_XML = """<CLBConfig output="{out}/">
<Geometry nx="128" ny="64"><MRT><Box/></MRT>
<WVelocity name="Inlet"><Inlet/></WVelocity>
<EPressure name="Outlet"><Outlet/></EPressure>
<Inlet nx="1" dx="2"><Box/></Inlet><Outlet nx="1" dx="-2"><Box/></Outlet>
<Wall mask="ALL"><Channel/></Wall></Geometry>
<Model><Params Velocity="0.01"/><Params nu="0.05"/></Model>
<Failcheck Iterations="3"/><VTK Iterations="6" compress="true"/>
<Solve Iterations="6"/></CLBConfig>"""


def test_solve_emits_phase_spans_with_the_right_parents(
        seen, tmp_path, monkeypatch):
    """A tiny <Solve> with <Failcheck> and a compressed <VTK>, on a
    Pallas engine in interpret mode: every phase span of the segment,
    each under the span that issued it, all sharing its iteration."""
    from tclb_tpu.control import run_config_string
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    run_config_string(_SOLVE_XML.format(out=tmp_path), get_model("d2q9"))
    spans = _spans(seen)
    by_id = {e["id"]: e for e in spans}

    def parent_of(e):
        return by_id[e["parent"]] if e["parent"] else None

    for n, it in enumerate(_spans(seen, "iterate")):
        kids = [e for e in spans if e["parent"] == it["id"]]
        # the first call builds the engine, ahead of its programs
        assert [e["name"] for e in kids] == ["engine.build"] * (n == 0) + [
            "iterate.fused", "iterate.globals_step"]
        kids = kids[-2:]
        fused, step = kids
        assert fused["iters"] == 2 and step["iters"] == 1
        # a domain this small gets the resident engine, probed on its
        # first call: that call as a whole is one iterate.fused
        assert fused["engine"] == it["engine"] \
            == "pallas_resident[d2q9,fuse=8]"
        assert fused["iteration"] == step["iteration"] == it["iteration"]
        assert it["t0"] <= fused["t0"] <= step["t0"] <= it["ts"]
    assert len(_spans(seen, "iterate")) == 2

    handlers = _spans(seen, "handler")
    assert [(h["handler"], h["iteration"]) for h in handlers] == [
        ("cbFailcheck", 3), ("cbFailcheck", 6), ("cbVTK", 6)]
    quantities = [q.name for q in get_model("d2q9").quantities
                  if not q.adjoint]
    n = len(quantities)
    for h in handlers[:2]:
        # one count program a quantity, then the counts in one copy of
        # four bytes each: no plane comes to the host, nothing scans there
        kids = [e for e in spans if e["parent"] == h["id"]]
        assert [e["name"] for e in kids] == ["quantity.eval"] * n \
            + ["quantity.d2h"]
        evals, d2h = kids[:-1], kids[-1]
        assert [e["quantity"] for e in evals] == quantities
        assert all(e["iteration"] == h["iteration"] for e in kids)
        assert all(e["reduce"] == "nonfinite" and "bytes" not in e
                   for e in evals)
        assert d2h["bytes"] == 4 * n and "quantity" not in d2h
        assert (h["scan"], h["quantities"], h["bytes_to_host"]) \
            == ("device", n, 4 * n)
        # the compiled count program says whether this call built it:
        # on quantity.eval alone, and only the first use in a process may
        assert all(e["program"] in ("built", "reused") for e in evals)
        assert "program" not in d2h
    assert all(e["program"] == "reused" for e in spans
               if e["parent"] == handlers[1]["id"]
               and e["name"] == "quantity.eval")
    vtk, = _spans(seen, "output.vtk")
    assert parent_of(vtk) is handlers[2]
    # the handler's own part: the planes' programs dispatched, then the
    # hand-off (nothing was in flight: no drain under it)
    kids = [e for e in spans if e["parent"] == vtk["id"]]
    assert [e["name"] for e in kids] == ["quantity.eval"] * n
    assert [e["quantity"] for e in kids] == quantities
    # the planes' programs are not Failcheck's: this write may build them
    assert all(e["program"] in ("built", "reused") and e["bytes"] > 0
               and "reduce" not in e for e in kids)
    # the writer's thread has a tree of its own, off the segment: its
    # root is given the write's iteration, the children take it from it
    write, = _spans(seen, "output.vtk.write")
    assert write["parent"] is None and write["iteration"] == 6
    kids = [e for e in spans if e["parent"] == write["id"]]
    assert [e["name"] for e in kids] == (
        ["quantity.d2h"] * n + ["output.vtk.encode", "output.vtk.file"])
    assert [e["quantity"] for e in kids[:n]] == quantities
    assert all(e["iteration"] == 6 for e in kids)
    encode, written = kids[-2:]
    assert 0 < encode["bytes_out"] < encode["bytes_in"]
    # one block of 32 KB each for Rho and Flag, three for U: too few for
    # a second thread
    assert encode["threads"] == 1 and encode["blocks"] == 5
    assert encode["bytes_in"] == vtk["queued_bytes"] >= sum(
        e["bytes"] for e in kids if e["name"] == "quantity.d2h")
    assert written["bytes"] == os.path.getsize(
        [str(p) for p in tmp_path.iterdir() if p.suffix == ".vti"][0])
    # <Solve> waited for it on its way out, under no segment
    drain, = _spans(seen, "output.vtk.drain")
    assert drain["parent"] is None and drain["reason"] == "solve_end"
    assert 0 <= drain["wait_s"] <= drain["dur_s"] + 1e-6
    assert write["ts"] <= drain["ts"]
    assert telemetry.counters()["output.vtk.async_writes"] == 1
    # self time: what a span's children do not cover is never negative
    for e in (handlers[2], vtk):
        own = e["dur_s"] - sum(k["dur_s"] for k in spans
                               if k["parent"] == e["id"])
        assert own >= -1e-3


_SOLVE_LOG_XML = _SOLVE_XML.replace("<Failcheck", '<Log Iterations="3"/>'
                                    "<Failcheck", 1)

#: the spans of a <Solve> that fence (Span.sync) or wait for the output
#: writer (Span.blocked), and so say wait_s
_FENCED = {"iterate", "iterate.fused", "engine.probe",
           "iterate.globals_step", "quantity.eval", "output.vtk.drain"}


class _SteppingClock:
    """``time`` for ``control/solver.py``: every reading is ``step``
    seconds after the one before."""

    def __init__(self, step):
        self.step, self.now = step, 1000.0

    def time(self):
        self.now += self.step
        return self.now


@pytest.fixture(scope="module")
def solved(tmp_path_factory):
    """One tiny <Solve> of two segments with <Log>, <Failcheck> and a
    <VTK>, on a Pallas engine in interpret mode: its events, its solver
    and its output directory."""
    from tclb_tpu.control import run_config_string
    out = tmp_path_factory.mktemp("solved")
    docs = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TCLB_FASTPATH", "force")
        telemetry.subscribe(docs.append)
        try:
            solver = run_config_string(_SOLVE_LOG_XML.format(out=out),
                                       get_model("d2q9"))
        finally:
            telemetry.unsubscribe(docs.append)
    return docs, solver, out


def test_segment_is_the_root_of_its_pass(solved):
    docs = solved[0]
    spans = _spans(docs)
    segments = _spans(docs, "segment")
    assert [(e["iteration"], e["steps"], e["parent"]) for e in segments] \
        == [(3, 3, None), (6, 3, None)]
    # iterate says where it started, the segment and its handlers where
    # the pass ends
    assert [e["iteration"] for e in _spans(docs, "iterate")] == [0, 3]
    for seg, start in zip(segments, (0, 3)):
        kids = [e for e in spans if e["parent"] == seg["id"]]
        names = [e["name"] for e in kids if e["name"] != "progress"]
        assert names[0] == "iterate" and set(names[1:]) == {"handler"}
        assert kids[0]["iteration"] == start
        assert all(e["iteration"] == seg["iteration"] for e in kids[1:])
        assert seg["t0"] <= kids[0]["t0"] and kids[-1]["ts"] <= seg["ts"]
    assert [[e["handler"] for e in spans if e["name"] == "handler"
             and e["parent"] == seg["id"]] for seg in segments] == [
        ["cbLog", "cbFailcheck"], ["cbLog", "cbFailcheck", "cbVTK"]]
    # every span of the solve's own thread hangs from a segment; the
    # other roots are the <VTK> write on the writer's thread and <Solve>
    # waiting for it on its way out
    # (and, ahead of the loop, the case and its elements)
    assert [e["name"] for e in spans if e["parent"] is None
            and e["name"] not in ("segment", "startup.case",
                                  "startup.element")] == [
        "output.vtk.write", "output.vtk.drain"]


def test_a_fence_says_what_it_waited_and_a_launch_what_it_cost(solved):
    spans = _spans(solved[0])
    assert {e["name"] for e in spans} >= _FENCED | {"segment", "handler"}
    for e in spans:
        # wait_s exactly on the spans that fenced (a probe's candidate
        # fences the copy of the state it runs on, where it makes one)
        assert ("wait_s" in e) == (e["name"] in _FENCED
                                   or "copy_s" in e), e["name"]
        wait = e.get("wait_s", 0.0)
        assert 0 <= wait <= e["dur_s"] + 2e-6
        # host time: what neither a child nor a fence covers
        own = e["dur_s"] - wait - sum(k["dur_s"] for k in spans
                                      if k["parent"] == e["id"])
        assert own >= -1e-3, e["name"]
    assert all(e["pre_sync_s"] >= 0 for e in spans if e["name"] == "iterate")
    probed, fused = [e for e in spans if e["name"] == "iterate.fused"]
    # the probed first call (a copy of the state, the compile, the
    # ladder) is no launch
    assert "dispatch_s" not in probed
    assert [e["name"] for e in spans if e["parent"] == probed["id"]] \
        == ["engine.probe"]
    # so is the first call of the engine of the hybrid's trailing step
    tail_probed, step = [e for e in spans
                         if e["name"] == "iterate.globals_step"]
    assert "dispatch_s" not in tail_probed
    assert [e["name"] for e in spans if e["parent"] == tail_probed["id"]] \
        == ["engine.probe"]
    for e in (fused, step):
        assert 0 < e["dispatch_s"] <= e["dur_s"] - e["wait_s"] + 2e-6


def test_output_log_has_its_fetch_and_its_write(solved):
    docs, solver, out = solved
    spans = _spans(docs)
    logs = _spans(docs, "output.log")
    assert [e["iteration"] for e in logs] == [3, 6]
    lat = solver.lattice
    three = (np.asarray(lat.params.settings).nbytes
             + np.asarray(lat.params.zone_table).nbytes
             + np.asarray(lat.state.globals_).nbytes)
    written = 0
    for log_span in logs:
        fetch, write = [e for e in spans if e["parent"] == log_span["id"]]
        assert (fetch["name"], write["name"]) == ("output.log.fetch",
                                                  "output.log.write")
        assert (fetch["copies"], fetch["bytes"]) == (3, three)
        assert fetch["iteration"] == write["iteration"] \
            == log_span["iteration"]
        written += write["bytes"]
    # the first write holds the header too
    csv, = [p for p in out.iterdir() if p.name.endswith("_Log.csv")]
    assert written == csv.stat().st_size


def test_report_prints_a_segments_host_time(solved):
    summary = report.summarize(solved[0])
    seg = summary["segments"]
    assert set(seg) == {"cbLog+cbFailcheck", "cbLog+cbFailcheck+cbVTK"}
    for g in seg.values():
        assert g["count"] == 1
        assert g["host_ms"] == pytest.approx(g["span_ms"] - g["wait_ms"],
                                             abs=1e-3)
        assert 0 < g["wait_ms"] < g["span_ms"] and g["pre_sync_ms"] >= 0
        assert {"segment", "iterate", "handler", "output.log.fetch",
                "output.log.write"} <= set(g["self_ms"])
        # the self times and the waits add up to the span
        assert sum(g["self_ms"].values()) + g["wait_ms"] \
            == pytest.approx(g["span_ms"], abs=0.05)
    # only the segment without the probe has both launches to count
    assert seg["cbLog+cbFailcheck+cbVTK"]["dispatch_ms"] > 0
    text = report.format_text(summary)
    assert "segments (medians" in text and "cbLog+cbFailcheck+cbVTK" in text
    # a session appended to the same file uses the ids again: kept apart
    twice = report.summarize(
        solved[0] + [{"kind": "trace_start", "ts": 0.0}] + solved[0])
    assert {k: (g["count"], g["self_ms"])
            for k, g in twice["segments"].items()} \
        == {k: (2, g["self_ms"]) for k, g in seg.items()}


_LOG_ONLY_XML = """<CLBConfig output="{out}/">
<Geometry nx="32" ny="16"><MRT><Box/></MRT></Geometry>
<Model><Params nu="0.05"/></Model>
<Log Iterations="2"/><Solve Iterations="6"/></CLBConfig>"""


@pytest.mark.parametrize("step,reports", [(0.0, []), (10.0, [4, 6])])
def test_progress_span_only_on_a_reporting_call(
        seen, tmp_path, monkeypatch, step, reports):
    """`Solver.progress` reports about once a second: with a clock that
    stands still never, with one that leaps on every pass after the
    first, and only then is there a span."""
    from tclb_tpu.control import run_config_string, solver as solver_mod
    monkeypatch.delenv("TCLB_FASTPATH", raising=False)
    monkeypatch.setattr(solver_mod, "time", _SteppingClock(step))
    run_config_string(_LOG_ONLY_XML.format(out=tmp_path), get_model("d2q9"))
    segments = {e["id"]: e for e in _spans(seen, "segment")}
    assert [e["iteration"] for e in segments.values()] == [2, 4, 6]
    progress = _spans(seen, "progress")
    assert [e["iteration"] for e in progress] == reports
    assert all(segments[e["parent"]]["iteration"] == e["iteration"]
               and "wait_s" not in e for e in progress)
    notes = [e for e in seen if e["kind"] == "progress"]
    assert [e["parent"] for e in notes] == [e["id"] for e in progress]


def test_jsonl_sink_is_flushed_by_the_outermost_span(tmp_path, monkeypatch):
    """Block-buffered: the spans inside a segment cost no write each; an
    event outside any span, the outermost span's own and a counters
    snapshot reach the file at once, whole lines only."""
    from tclb_tpu.telemetry import events
    trace = tmp_path / "t.jsonl"
    telemetry.enable(str(trace))

    def on_disk():
        text = trace.read_text()
        assert text.endswith("\n")
        return [e.get("name", e["kind"])
                for e in map(json.loads, text.splitlines())]

    assert on_disk() == ["trace_start"]
    with telemetry.span("segment"):
        with telemetry.span("iterate"):
            telemetry.event("note")
        with telemetry.span("handler"):
            pass
        assert on_disk() == ["trace_start"]
    whole = ["trace_start", "note", "iterate", "handler", "segment"]
    assert on_disk() == whole
    monkeypatch.setattr(events, "COUNTER_SNAPSHOT_S", 0.0)
    with telemetry.span("segment"):
        telemetry.counter("halo.exchanges")
        telemetry.event("note")             # a snapshot rides on it
        assert on_disk() == whole + ["counters", "note"]
    telemetry.disable()     # the segment's event took a snapshot along too
    assert on_disk() == whole + ["counters", "note", "counters", "segment",
                                 "counters"]


@pytest.mark.parametrize("native_on", [True, False])
def test_vtk_encode_span_says_how_it_was_encoded(
        seen, tmp_path, monkeypatch, native_on):
    """`threads`, `blocks` and `native` on `output.vtk.encode`: the native
    encoder on as many threads as the cores, less the two it leaves
    free, and the blocks allow; with the library off (what TCLB_NATIVE=0
    does in `get_lib`) Python on one."""
    from tclb_tpu import native
    from tclb_tpu.utils.vtk import write_vti
    if native_on and not native.available():
        pytest.skip("native lib not built (no g++?)")
    monkeypatch.setattr(native, "_usable_cores", lambda: 5)  # two stay free
    if not native_on:
        monkeypatch.setenv("TCLB_NATIVE", "0")
        monkeypatch.setattr(native, "_tried", False)
        monkeypatch.setattr(native, "_lib", None)
    rho = np.linspace(0, 1, 512 * 512, dtype=np.float32).reshape(512, 512)
    write_vti(str(tmp_path / "x"), {"Rho": rho, "U": np.stack([rho] * 3)},
              compress=True)
    encode, = _spans(seen, "output.vtk.encode")
    assert encode["blocks"] == 32 + 96
    assert encode["native"] is native_on
    assert encode["threads"] == (3 if native_on else 1)
    assert 0 < encode["bytes_out"] < encode["bytes_in"] == 4 * 512 * 512 * 4
    # the raw branch has no encoder to speak of
    write_vti(str(tmp_path / "y"), {"Rho": rho})
    raw = _spans(seen, "output.vtk.encode")[-1]
    assert raw["compress"] is False and "threads" not in raw


def test_compile_events_name_the_function_and_the_span(seen):
    @jax.jit
    def fresh_function_of_this_test(x):
        return jnp.tanh(x) * 3.0

    x = jnp.ones((4, 4), jnp.float32)
    with telemetry.span("first_call") as sp:
        fresh_function_of_this_test(x).block_until_ready()
    mine = [e for e in seen if e["kind"] == "compile"
            and "fresh_function_of_this_test" in (e["program"] or "")]
    assert {"trace", "lower", "backend_compile"} <= {e["stage"]
                                                     for e in mine}
    assert all(e["parent"] == sp.id and e["dur_s"] >= 0 for e in mine)
    # every one says where it came from; no cache serves a trace or a
    # lowering
    assert all(e["cache"] in ("hit", "miss", "off")
               for e in seen if e["kind"] == "compile")
    assert {e["cache"] for e in mine
            if e["stage"] in ("trace", "lower")} == {"off"}
    n = len([e for e in seen if e["kind"] == "compile"])
    fresh_function_of_this_test(x).block_until_ready()
    assert len([e for e in seen if e["kind"] == "compile"]) == n


@pytest.mark.parametrize("niter", [8, 12])
def test_halo_bytes_equal_the_formula_on_the_mesh(seen, monkeypatch, niter):
    """The tuned 2D sharded engine on a 4x1 mesh of the forced CPU
    devices: 2 x width x plane x planes x itemsize per exchange, one
    exchange per fused pair (and one for an odd step), the three-plane
    aux stack once per call.  The span carries the engine's account
    beside the bytes: its kernel calls, those a two-call loop body
    issues (none of three trips, four of five), and the 8 halo rows a
    side the kernel takes as operands of their own.  The trailing step
    is the sharded tail (``make_sharded_pallas_tail``): its one exchange
    of the fields' and the aux stack's 8 rows a side lies on
    ``iterate.globals_step`` with its account (the probed first call's
    on the probe's spans; the counters hold both calls)."""
    from tclb_tpu.parallel.mesh import make_mesh
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    ny, nx = 64, 128
    m = get_model("d2q9")
    mesh = make_mesh((ny, nx), devices=jax.devices()[:4],
                     decomposition={"y": 4, "x": 1})
    lat = Lattice(m, (ny, nx), dtype=jnp.float32,
                  settings={"nu": 0.05, "Velocity": 0.03}, mesh=mesh)
    flags = np.full((ny, nx), m.flag_for("MRT"), dtype=np.uint16)
    flags[0, :] = flags[-1, :] = m.flag_for("Wall")
    lat.set_flags(flags)
    lat.init()
    lat.iterate(niter)
    lat.iterate(niter)
    assert lat._fast_name == "pallas_sharded[{'y': 4, 'x': 1},fuse=2]"
    assert telemetry.fuse_of(lat._fast_name) == 2
    tail = "pallas_sharded[generic,{'y': 4, 'x': 1},fuse=1,globals]"
    assert lat._tail_name == tail and telemetry.fuse_of(tail) == 1

    plane = 2 * 8 * nx * 4                  # 8 rows each way, f32
    nfast = niter - 1
    want = (nfast // 2 + nfast % 2) * m.n_storage * plane + 3 * plane
    calls, paired = {8: (4, 0), 12: (6, 4)}[niter]
    for fused in _spans(seen, "iterate.fused"):
        assert fused["iters"] == nfast and fused["halo_bytes"] == want
        assert (fused["kernel_calls"], fused["paired_calls"],
                fused["halo_operand_rows"]) == (calls, paired, 8)
    assert telemetry.counters()["engine.kernel_calls"] == 2 * (calls + 1)
    # as it counted: a fused step an exchange, the tail's step one
    assert telemetry.counters()["halo.exchanges"] == 2 * (nfast + 1)
    probed, step = _spans(seen, "iterate.globals_step")
    assert probed["engine"] == step["engine"] == tail
    assert step["halo_bytes"] == (m.n_storage + 3) * plane
    assert (step["kernel_calls"], step["halo_operand_rows"],
            step["aux_planes"]) == (1, 0, 3)
    assert "halo_bytes" not in probed
    assert telemetry.counters()["halo.bytes"] \
        == 2 * (want + step["halo_bytes"])
    assert telemetry.counters()["engine.tail_calls"] == 2
    assert not [e for e in _spans(seen) if e["name"].startswith("halo.")]


def test_disabled_solve_never_syncs_and_listener_is_silent(
        tmp_path, monkeypatch):
    from tclb_tpu.control import run_config_string, solver as solver_mod
    from tclb_tpu.telemetry import events
    docs = []
    telemetry.subscribe(docs.append)        # registers the listener ...
    telemetry.unsubscribe(docs.append)      # ... which stays, gated
    assert events._compile_listening and not telemetry.enabled()
    assert telemetry.span("iterate.fused", iters=1) is NOOP_SPAN

    def boom(*a, **k):
        raise AssertionError("telemetry is off")

    # no span is made, fences, or marks a launch; nothing reads the
    # clock for pre_sync_s; the segment, progress (a clock that makes
    # every call after the first report) and the Log's two children
    # included
    for method in ("__init__", "sync", "mark", "blocked", "inherited"):
        monkeypatch.setattr(telemetry.Span, method, boom)
    monkeypatch.setattr(events, "_fanout_locked", boom)  # nothing emits
    monkeypatch.setattr(lattice_mod, "time", _Untouchable("the clock"))
    monkeypatch.setattr(solver_mod, "time", _SteppingClock(10.0))
    monkeypatch.setenv("TCLB_FASTPATH", "force")
    s = run_config_string(_SOLVE_LOG_XML.format(out=tmp_path),
                          get_model("d2q9"))
    assert s.iter == 6 and docs == []
    assert len((tmp_path / "run_Log.csv").read_text().splitlines()) == 3
    assert telemetry.counters() == {}
