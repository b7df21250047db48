"""trace_reduce on hand-made planes (busy union, op-kind sums, gap
attribution) and on a short trace recorded on a TPU v5e chip."""
from __future__ import annotations

import gzip
import json
import os

import pytest

from bench import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_stream_tick.planes.json.gz")


def _planes():
    ops = [("%sort.1 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %a), "
            "dimensions={0}", 100.0, 50.0),
           ("%fusion.2 = s32[8]{0:T(1024)} fusion(s32[8]{0} %b), "
            "kind=kLoop", 120.0, 60.0),          # overlaps the sort
           ("%sort.7 = (u32[8]{0:T(1024)S(1)}) sort(u32[8]{0})", 300.0, 20.0),
           ("%copy-start = (s32[8]{0}, u32[]{:S(2)}) copy-start(s32[8]{0})",
            390.0, 30.0)]                        # runs past the window
    return [
        dict(name="/device:TPU:0", lines=[
            dict(name="XLA Modules", events=[("jit_tick(1)", 90.0, 400.0)]),
            dict(name="XLA Ops", events=ops + [
                ("%while.3 = (u32[]) while((u32[]) %t), body=%b", 100.0,
                 80.0)]),
            dict(name="Async XLA Ops", events=[("%copy-start", 0.0, 999.0)]),
        ]),
        dict(name="/host:CPU", lines=[dict(name="python3", events=[
            ("window", 50.0, 350.0), ("tick", 60.0, 200.0),
            ("tick", 280.0, 200.0), ("PjitFunction(tick)", 61.0, 5.0)])]),
    ]


def test_op_label():
    assert tr.op_label("%fusion.636 = pred[69632]{0:T(1024)(128)} fusion("
                       "pred[5,112]{1,0} %g), kind=kCustom") == \
        "fusion.636 pred[69632]"
    assert tr.op_label("%fusion.74 = (u32[17825792]{0:T(1024)S(1)}, u32[8]"
                       "{0}) fusion(u32[8] %a)") == "fusion.74 u32[17825792]"
    assert tr.op_label("%sort.3") == "sort.3"


def test_opcode_and_short_name():
    assert tr.opcode(_planes()[0]["lines"][1]["events"][0][0]) == "sort"
    assert tr.opcode(_planes()[0]["lines"][1]["events"][1][0]) == "fusion"
    assert tr.opcode(_planes()[0]["lines"][1]["events"][3][0]) == \
        "copy-start"
    assert tr.opcode("%sort.3") == "sort"
    assert tr._short_name("%compare_select_fusion.5 = s32[2] fusion()") \
        == "compare_select_fusion"


def test_busy_is_the_union_of_ops_inside_the_window():
    s = tr.reduce(_planes(), (50.0, 400.0), ["tick"])
    # [100, 180] + [300, 320] + [390, 400] = 80 + 20 + 10 ns
    assert s["busy_s"] == pytest.approx(110e-9)
    assert s["window_s"] == pytest.approx(350e-9)
    assert s["op_kind_s"]["sort"] == pytest.approx(70e-9)
    assert s["op_kind_s"]["fusion"] == pytest.approx(60e-9)
    assert s["op_kind_s"]["copy-start"] == pytest.approx(10e-9)


def test_gaps_are_attributed_to_the_innermost_span():
    s = tr.reduce(_planes(), (50.0, 400.0), ["tick"])
    gaps = sorted(s["gaps"], key=lambda g: g[1])
    # [50,100] in tick 60-260 by its middle 75; [180,300] middle 240 in
    # tick; [320,390] middle 355 in the second tick
    assert [g[0] for g in gaps] == ["tick", "tick", "tick"]
    assert sum(g[1] for g in gaps) == pytest.approx(240e-9)
    s = tr.reduce(_planes(), (50.0, 400.0), [])
    assert {g[0] for g in s["gaps"]} == {tr.NO_SPAN}
    b = tr.breakdown(s)
    assert b["device_ops"][0][0] == "fusion.2 s32[8]"
    assert b["idle_gaps"] == [[tr.NO_SPAN, pytest.approx(240e-9)]]


def test_exposed_collective_time():
    planes = _planes()
    planes[0]["lines"][1]["events"] += [
        ("%all-to-all.1 = (s64[8]{0}) all-to-all(s64[8]{0} %x)", 170.0,
         40.0),                                   # 10 ns beside fusion.2
        ("%all-reduce-start = s32[4]{0} all-reduce-start(s32[4] %y)", 340.0,
         20.0)]                                   # nothing beside it
    s = tr.reduce(planes, (50.0, 400.0), ["tick"])
    assert s["collective_s"] == pytest.approx(60e-9)
    assert s["collective_exposed_s"] == pytest.approx(50e-9)
    assert tr.reduce(_planes(), (50.0, 400.0))["collective_s"] == 0


def test_device_time_by_program():
    s = tr.reduce(_planes(), (50.0, 400.0), ["tick"])
    # jit_tick(1) runs 90-490 ns, inside the window from 90 to 400
    assert s["module_s"] == {"jit_tick(1)": pytest.approx(310e-9)}
    assert s["module_n"] == {"jit_tick(1)": 1}


def test_no_device_is_an_error():
    with pytest.raises(RuntimeError):
        tr.reduce(_planes()[1:], (0.0, 1.0))


def test_recorded_v5e_trace():
    with gzip.open(FIXTURE, "rt") as f:
        planes = json.load(f)
    (window,) = tr.host_spans(planes, ["window"])
    s = tr.reduce(planes, window[1:], ["tick"])
    assert s["devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["op_kind_s"]["sort"] > 0
    assert "while" not in s["op_kind_s"]
    idle = sum(g[1] for g in s["gaps"])
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-9)
    assert {g[0] for g in s["gaps"]} <= {"tick", tr.NO_SPAN}
