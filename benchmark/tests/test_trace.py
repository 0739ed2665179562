"""The reduction from a recording to numbers, on a small recording kept
beside this file (``data/recording.json``, cut from a traced chip run of
karman1024.shipped; see its ``about`` entry) and on hand-made ones."""

import json
import os

import pytest

from benchmark import trace
from benchmark.layer_metrics import (device_idle_share, halo_exposed_share,
                                     kernel_hbm_roofline,
                                     kernel_ns_per_update, xla_tail_share)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def recording() -> trace.Recording:
    with open(os.path.join(DATA, "recording.json")) as f:
        return trace.Recording.from_json(f.read())


def about() -> dict:
    with open(os.path.join(DATA, "recording.json")) as f:
        return json.load(f)["about"]


def hand_made() -> trace.Recording:
    """One device, a span of 10 s: a while of 6 s holding two kernels of
    2 s and a fusion of 1 s, then a gap of 3 s under output.vtk, then a
    copy of 1 s."""
    return trace.Recording(
        devices={"0": [["while.1", 0.0, 6.0],
                       ["closed_call.8_custom-call_tpu_custom_call", 0.0, 2.0],
                       ["fusion.1_fusion", 2.5, 1.0],
                       ["closed_call.8_custom-call_tpu_custom_call", 4.0, 2.0],
                       ["copy.19_copy", 9.0, 1.0]]},
        host=[[trace.TRACED, 0.0, 10.0], ["iterate", 0.0, 6.1],
              ["handler", 6.1, 3.9], ["output.vtk", 6.2, 2.7]])


def test_self_times_take_the_body_out_of_the_while():
    t = trace.by_class(hand_made())
    assert t["kernel"] == pytest.approx(4.0) and t["calls"] == 2
    assert t["other"] == pytest.approx(1.0 + 1.0 + 1.0)   # fusion, while, copy
    assert trace.union_seconds(hand_made().devices["0"]) == pytest.approx(7.0)


def test_idle_share_and_gaps():
    rec = hand_made()
    busy, span = trace.busy_seconds(rec)
    assert (busy, span) == (pytest.approx(7.0), pytest.approx(10.0))
    assert device_idle_share.read([], rec, {}) == pytest.approx(30.0)
    gaps = trace.idle_gaps(rec)
    assert gaps[0][0] == "output.vtk" and gaps[0][1] == pytest.approx(3.0)
    ops = trace.top_ops(rec)
    assert ops[0] == ["closed_call.8_custom-call_tpu_custom_call", pytest.approx(4.0)]


def test_exposed_collective_time():
    rec = trace.Recording(
        devices={"0": [["collective-permute-start.1", 0.0, 1.0],
                       ["fusion.2_fusion", 0.5, 1.0],
                       ["collective-permute-done.1", 3.0, 0.5]],
                 "1": [["fusion.2_fusion", 0.0, 4.0]]},
        host=[[trace.TRACED, 0.0, 4.0]])
    assert trace.exposed_collective_seconds(rec) == pytest.approx(1.0)
    cell = {"chips": 4}
    assert halo_exposed_share.read([], rec, cell) == pytest.approx(25.0)
    assert halo_exposed_share.read([], rec, {"chips": 1}) is None


CELL = {"nodes": 1024 * 1024, "traced_steps": 2000, "planes": 11,
        "itemsize": 4, "fuse": 2, "device_kind": "TPU v5 lite",
        "engine": "pallas_2d[d2q9,fuse=2]", "chips": 1}


def test_roofline_and_the_100_percent_assertion():
    updates = CELL["nodes"] * CELL["traced_steps"]
    least = updates * 45.0 / 819e9
    rec = trace.Recording(devices={"0": [["k_custom-call_tpu_custom_call", 0.0, 2 * least]]},
                          host=[[trace.TRACED, 0.0, 1.0]])
    assert kernel_hbm_roofline.read([], rec, CELL) == pytest.approx(50.0)
    assert kernel_ns_per_update.read([], rec, CELL) == pytest.approx(
        2 * 45.0 / 819.0)
    too_fast = trace.Recording(
        devices={"0": [["k_custom-call_tpu_custom_call", 0.0, 0.9 * least]]},
        host=[[trace.TRACED, 0.0, 1.0]])
    with pytest.raises(AssertionError):
        kernel_hbm_roofline.read([], too_fast, CELL)
    assert xla_tail_share.read([], rec, CELL) == pytest.approx(0.0)


def test_kept_recording():
    """The numbers of the kept recording, as the chip run that made it
    printed them."""
    rec, a = recording(), about()
    busy, span = trace.busy_seconds(rec)
    assert span == pytest.approx(a["window_s"], rel=1e-6)
    assert busy == pytest.approx(a["busy_s"], rel=1e-6)
    t = trace.by_class(rec)
    assert t["kernel"] == pytest.approx(a["kernel_s"], rel=1e-6)
    assert t["calls"] == a["kernel_calls"]
    cell = dict(CELL, traced_steps=a["traced_steps"])
    assert kernel_hbm_roofline.read([], rec, cell) == pytest.approx(
        a["kernel_hbm_roofline"], rel=1e-6)
    assert kernel_hbm_roofline.read([], rec, cell) <= 100.0
    assert trace.idle_gaps(rec)[0][0] == a["longest_gap_under"]


def test_short_op_names():
    full = ('%closed_call.8 = f32[11,1024,1024]{2,1,0:T(8,128)S(1)} '
            'custom-call(f32[13]{0:T(128)S(1)} %get-tuple-element.75, '
            'f32[11,1024,1024]{2,1,0:T(8,128)S(1)} %copy.19), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert trace.short_op_name(full) == (
        "closed_call.8_custom-call_tpu_custom_call")
    assert trace.is_kernel(trace.short_op_name(full))
    tup = ('%slice_bitcast_fusion.1 = (f32[128]{0:T(128)}, f32[128]{0:T(128)'
           'S(1)}) fusion(f32[13,128]{1,0} %p), kind=kLoop')
    assert trace.short_op_name(tup) == "slice_bitcast_fusion.1_fusion"
    assert trace.short_op_name("%copy = s32[8]{0} copy(s32[8]{0} %x)") == (
        "copy_copy")
    cp = "%collective-permute-start.1 = (f32[8]{0}, f32[8]{0}) collective-permute-start(f32[8]{0} %x), channel_id=1"
    assert trace.is_collective(trace.short_op_name(cp))
    assert not trace.is_kernel(trace.short_op_name(cp))
    assert trace.short_op_name("while.1") == "while.1"


def test_json_round_trip():
    rec = hand_made()
    again = trace.Recording.from_json(rec.to_json())
    assert again.devices == rec.devices and again.host == rec.host


def test_readers_of_the_kept_events():
    """``data/events.jsonl``: the program's telemetry of the same run,
    cut to its start and to two segments of the window."""
    from benchmark.layer_metrics import (engine_fallbacks, first_call_s,
                                         handlers_share, vtk_ms)
    events = trace.read_events(os.path.join(DATA, "events.jsonl"))
    cell = {"window": {"first_iteration": 15000, "last_iteration": 16000,
                       "wall_s": 1.3, "overhead_s": 0.05}}
    assert first_call_s.read(events, None, cell) == pytest.approx(2.412)
    assert engine_fallbacks.read(events, None, cell) == 0.0
    assert vtk_ms.read(events, None, cell) == pytest.approx(1017.917)
    # Log, Failcheck, Log, VTK; the benchmark's own handler is left out
    spent = 0.001079 + 0.037022 + 0.000991 + 1.018051
    assert handlers_share.read(events, None, cell) == pytest.approx(
        100 * spent / 1.25)
    outside = {"window": dict(cell["window"], first_iteration=16000,
                              last_iteration=18000)}
    assert vtk_ms.read(events, None, outside) is None
    assert handlers_share.read(events, None, outside) is None
