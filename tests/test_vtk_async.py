"""`<VTK>` hands its arrays to the Solver's one-in-flight writer and
`<Solve>` goes on: the files are the same bytes as a direct
`write_vti` / `write_pvti`, whole wherever the case can look at them, a
failed write fails the run, and the writer's spans carry the write's
iteration on a tree of their own.
"""

import sys
import threading
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from tclb_tpu import telemetry
from tclb_tpu.control import run_config_string
from tclb_tpu.control.solver import OutputError
from tclb_tpu.models import get_model
from tclb_tpu.utils import vtk as vtk_mod

CASE = """<CLBConfig output="{out}/">
<Geometry {size}><MRT><Box/></MRT>
<Wall mask="ALL"><Box ny="1"/></Wall></Geometry>
<Model><Params Velocity="0.01"/><Params nu="0.05"/>
<Params GravitationX="1e-5"/></Model>
{handlers}</CLBConfig>"""

SIZES = {"d2q9": 'nx="48" ny="20"', "d3q19": 'nx="24" ny="10" nz="6"'}


@pytest.fixture(autouse=True)
def _sink_off():
    telemetry.disable()
    yield
    telemetry.disable()


@pytest.fixture
def seen():
    docs = []
    telemetry.subscribe(docs.append)
    yield docs
    telemetry.unsubscribe(docs.append)


def _case(out, model, handlers):
    return CASE.format(out=out, size=SIZES[model], handlers=handlers)


def _spans(docs, name=None):
    return [e for e in docs if e.get("kind") == "span"
            and (name is None or e["name"] == name)]


# -- the same bytes --------------------------------------------------------- #


@pytest.mark.parametrize("compress", [True, False],
                         ids=["compressed", "raw"])
@pytest.mark.parametrize("model", ["d2q9", "d3q19"])
def test_files_equal_a_direct_write_byte_for_byte(tmp_path, model,
                                                  compress):
    """The newest piece and its master, written through the writer while
    `<Solve>` went on, against `write_vti` / `write_pvti` called here on
    the same arrays (a scalar, a vector, the flags)."""
    attr = ' compress="true"' if compress else ""
    s = run_config_string(_case(
        tmp_path / "run", model, f'<VTK Iterations="3"{attr}/>'
        '<Solve Iterations="6"/>'), get_model(model))
    arrays = s.quantity_arrays()
    assert arrays["U"].shape[0] == 3 and arrays["Rho"].ndim == len(s.shape)
    arrays["Flag"] = np.asarray(s.lattice.state.flags)
    direct = tmp_path / "direct"
    direct.mkdir()
    piece = vtk_mod.write_vti(str(direct / "run_VTK_00000006.vti"), arrays,
                              compress=compress)
    vtk_mod.write_pvti(str(direct / "run_VTK_00000006.pvti"), piece, arrays)
    for ext in ("vti", "pvti"):
        name = f"run_VTK_00000006.{ext}"
        assert (tmp_path / "run" / name).read_bytes() \
            == (direct / name).read_bytes(), name
    # both writes of the run, and nothing left under a temporary name
    assert sorted(p.name for p in (tmp_path / "run").glob("*VTK*")) == [
        f"run_VTK_{it:08d}.{ext}" for it in (3, 6)
        for ext in ("pvti", "vti")]


def test_what_selects_the_arrays_and_leaves_the_flags_out(tmp_path):
    s = run_config_string(_case(
        tmp_path, "d2q9", '<VTK what="Rho"/>'), get_model("d2q9"))
    text = (tmp_path / "run_VTK_00000000.vti").read_bytes()
    assert b'Name="Rho"' in text and b'Name="U"' not in text \
        and b'Name="Flag"' not in text
    assert s._output_file is None       # the run's end waited for it


# -- whole wherever the case can look ---------------------------------------- #

_found = []


def look(solver) -> int:
    """`<CallPython>` after `<Solve>`: every file the case wrote so far,
    parsed to its end."""
    import glob
    import os
    base = solver.output_prefix
    assert not glob.glob(os.path.join(base, "*.tmp-*"))
    for path in sorted(glob.glob(os.path.join(base, "*_VTK_*.vti"))):
        with open(path, "rb") as f:
            data = f.read()
        assert data.endswith(b"\n</AppendedData>\n</VTKFile>\n"), path
        ET.parse(path[:-4] + ".pvti")   # the master beside it, whole
        _found.append(os.path.basename(path))
    return 0


def test_callpython_after_solve_finds_every_file_whole(
        tmp_path, monkeypatch):
    monkeypatch.setitem(sys.modules, "vtk_async_probe",
                        sys.modules[__name__])
    _found.clear()
    run_config_string(_case(
        tmp_path, "d2q9", '<VTK Iterations="2"/><Solve Iterations="6"/>'
        '<CallPython module="vtk_async_probe" function="look"/>'
        '<Solve Iterations="2"><VTK Iterations="1"/></Solve>'
        '<CallPython module="vtk_async_probe" function="look"/>'),
        get_model("d2q9"))
    first = [f"run_VTK_{it:08d}.vti" for it in (2, 4, 6)]
    assert _found == first + first + [
        f"run_VTK_{it:08d}.vti" for it in (7, 8)]


def test_failcheck_hit_leaves_the_rescue_file_whole(tmp_path, monkeypatch):
    """The hit stops `<Solve>`, whose way out waits for the rescue
    `<VTK/>` child's write: the `<CallPython>` after it reads the file."""
    monkeypatch.setitem(sys.modules, "vtk_async_probe",
                        sys.modules[__name__])
    _found.clear()
    s = run_config_string(_case(
        tmp_path, "d2q9",
        '<CallPython module="vtk_async_probe" function="spoil" '
        'Iterations="4"/><Failcheck Iterations="2"><VTK/></Failcheck>'
        '<Solve Iterations="10"/>'
        '<CallPython module="vtk_async_probe" function="look"/>'),
        get_model("d2q9"))
    assert s.iter == 4 and _found == ["run_VTK_00000004.vti"]
    from benchmark import vti
    # of the case's size, and the planted node in it
    cells, arrays = vti.read_vti(str(tmp_path / "run_VTK_00000004.vti"))
    assert cells == 48 * 20
    assert np.isnan(arrays["Rho"]).sum() == 1


def spoil(solver) -> int:
    lat = solver.lattice
    lat.state = lat.state.replace(
        fields=lat.state.fields.at[3, 5, 7].set(np.nan))
    return 0


# -- a failed write fails the run -------------------------------------------- #


def _break_the_encoder(monkeypatch, after=0):
    """`write_vti` raises from its call number ``after`` on."""
    real, calls = vtk_mod.write_vti, []

    def broken(path, *a, **k):
        calls.append(threading.current_thread().name)
        if len(calls) > after:
            raise OSError(28, "No space left on device", path)
        return real(path, *a, **k)

    monkeypatch.setattr(vtk_mod, "write_vti", broken)
    return calls


@pytest.mark.parametrize("handlers,reason,file", [
    ('<VTK Iterations="2"/><Solve Iterations="2"/>', "solve_end",
     "run_VTK_00000002.vti"),
    ('<VTK Iterations="1"/><Solve Iterations="4"/>', "next_write",
     "run_VTK_00000001.vti"),
    ("<VTK/>", "run_end", "run_VTK_00000000.vti"),
])
def test_a_failed_write_fails_the_run_at_the_next_drain(
        tmp_path, monkeypatch, seen, handlers, reason, file):
    calls = _break_the_encoder(monkeypatch)
    with pytest.raises(OutputError, match=file) as err:
        run_config_string(_case(tmp_path, "d2q9", handlers),
                          get_model("d2q9"))
    assert isinstance(err.value.__cause__, OSError)
    assert calls == ["tclb-output-writer"]      # and no write after it
    drains = _spans(seen, "output.vtk.drain")
    assert [e["reason"] for e in drains] == [reason]
    assert drains[0]["ok"] is False
    assert not list(tmp_path.glob("*.vti"))


def boom(solver) -> int:
    raise ZeroDivisionError("the case's own")


def test_an_exception_on_its_way_wins_and_the_write_is_logged(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setitem(sys.modules, "vtk_async_probe",
                        sys.modules[__name__])
    _break_the_encoder(monkeypatch)
    with pytest.raises(ZeroDivisionError, match="the case's own"):
        run_config_string(_case(
            tmp_path, "d2q9", '<VTK Iterations="2"/>'
            '<CallPython module="vtk_async_probe" function="boom" '
            'Iterations="2"/><Solve Iterations="4"/>'), get_model("d2q9"))
    logged = "".join(capsys.readouterr())
    assert "run_VTK_00000002.vti failed" in logged \
        and "No space left" in logged


# -- the spans and the counters ---------------------------------------------- #


def test_writer_spans_carry_the_iteration_off_the_segment(tmp_path, seen):
    run_config_string(_case(
        tmp_path, "d2q9", '<Log Iterations="2"/>'
        '<VTK Iterations="4" compress="true"/><Solve Iterations="12"/>'),
        get_model("d2q9"))
    spans = _spans(seen)
    by_id = {e["id"]: e for e in spans}

    def ancestors(e):
        while e["parent"] is not None:
            e = by_id[e["parent"]]
            yield e["name"]

    writes = _spans(seen, "output.vtk.write")
    assert [e["iteration"] for e in writes] == [4, 8, 12]
    for w in writes:
        assert w["parent"] is None
        kids = [e for e in spans if e["parent"] == w["id"]]
        assert [e["name"] for e in kids] == [
            "quantity.d2h", "quantity.d2h", "output.vtk.encode",
            "output.vtk.file"]
        assert all(e["iteration"] == w["iteration"] for e in kids)
    for name in ("output.vtk.write", "quantity.d2h", "output.vtk.encode",
                 "output.vtk.file"):
        assert all("segment" not in ancestors(e)
                   for e in _spans(seen, name)), name
    # the handler's own part stays in its segment, and says what it queued
    own = _spans(seen, "output.vtk")
    assert [e["iteration"] for e in own] == [4, 8, 12]
    assert all(list(ancestors(e)) == ["handler", "segment"] for e in own)
    assert all(e["queued_bytes"] == 48 * 20 * (4 + 12 + 2) for e in own)
    # a write waits for the one before (under output.vtk), <Solve> for
    # the last
    drains = _spans(seen, "output.vtk.drain")
    assert [(e["reason"], e.get("iteration")) for e in drains] == [
        ("next_write", 8), ("next_write", 12), ("solve_end", None)]
    assert all(by_id[e["parent"]]["name"] == "output.vtk"
               for e in drains[:2])
    counters = telemetry.counters()
    assert counters["output.vtk.async_writes"] == 3
    blocked = sum(e["wait_s"] > 1e-3 for e in drains)
    assert counters.get("output.vtk.drain_waits", 0) == blocked


def test_a_case_without_vtk_has_no_writer_and_no_drain(tmp_path, seen):
    s = run_config_string(_case(
        tmp_path, "d2q9", '<Log Iterations="2"/><Solve Iterations="4"/>'),
        get_model("d2q9"))
    assert s._output is None
    assert not _spans(seen, "output.vtk.drain")
    assert "tclb-output-writer" not in {
        t.name for t in threading.enumerate()}


def test_the_writer_thread_is_named_by_its_owner():
    from tclb_tpu.checkpoint.writer import AsyncWriter
    names = []
    for w in (AsyncWriter(), AsyncWriter("tclb-output-writer")):
        w.submit(lambda: names.append(threading.current_thread().name))
        w.wait()
    assert names == ["tclb-checkpoint-writer", "tclb-output-writer"]


# -- the yardstick's readers -------------------------------------------------- #


@pytest.mark.parametrize("metric", ["vtk_ms", "vtk_encode_ms"])
def test_the_benchmarks_readers_still_read_a_number(tmp_path, seen,
                                                    metric):
    """`benchmark/layer_metrics/vtk_ms.py` and `vtk_encode_ms.py` select
    by `iteration` (`trace.spans_in_window`): the writer thread's
    `output.vtk.encode` is only found because its root was given one."""
    import importlib
    run_config_string(_case(
        tmp_path, "d2q9", '<VTK Iterations="4" compress="true"/>'
        '<Solve Iterations="16"/>'), get_model("d2q9"))
    reader = importlib.import_module("benchmark.layer_metrics." + metric)
    window = {"first_iteration": 4, "last_iteration": 12}
    value = reader.read(seen, None, {"window": window})
    name = {"vtk_ms": "output.vtk", "vtk_encode_ms": "output.vtk.encode"}
    durs = sorted(e["dur_s"] for e in _spans(seen, name[metric])
                  if e["iteration"] in (8, 12))
    assert len(durs) == 2
    assert value == pytest.approx(1e3 * sum(durs) / 2) and value > 0
