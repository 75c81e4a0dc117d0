"""The trace reduction, the stage table and the roofline arithmetic."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import hlo_scopes  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402

RECORDED = os.path.join(HERE, "data", "wire_run_trace.json")


def ev(name, start, dur):
    return {"name": name, "start_ns": start, "dur_ns": dur}


def plane(dev, ops, mods):
    return {"name": f"/device:TPU:{dev}", "lines": [
        {"name": "XLA Modules", "events": mods}, {"name": "XLA Ops", "events": ops}]}


HLO = """HloModule jit__lambda, is_scheduled=true

%fused_computation.3 (param_0: u32[8]) -> u32[8] {
  %param_0 = u32[8]{0} parameter(0)
  ROOT %add.1 = u32[8]{0} add(u32[8]{0} %param_0, u32[8]{0} %param_0), metadata={op_name="jit(step)/ra.match/add"}
}

%body.5 (p: (s32[], u32[8])) -> (s32[], u32[8]) {
  %p = (s32[], u32[8]{0}) parameter(0)
  ROOT %fusion.2 = u32[8]{0} fusion(u32[8]{0} %p), kind=kLoop, calls=%fused_computation.3
}

ENTRY %main.9 (Arg_0.1: u32[8]) -> u32[8] {
  %Arg_0.1 = u32[8]{0} parameter(0)
  %while.4 = (s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t), condition=%cond.6, body=%body.5
  %scatter.7 = u32[8]{0} scatter(u32[8]{0} %Arg_0.1), metadata={op_name="jit(step)/ra.talk/ra.cms/scatter"}
  ROOT %copy.8 = u32[8]{0} copy(u32[8]{0} %Arg_0.1)
}
"""


def test_scopes_of_module_reads_own_and_called_metadata():
    name, table = hlo_scopes.scopes_of_module(HLO)
    assert name == "jit__lambda"
    assert table["add.1"] == "match"
    assert table["fusion.2"] == "match"  # from the computation it calls
    assert table["while.4"] == "match"  # through its body
    assert table["scatter.7"] == "talk"  # the outermost scope wins
    assert "copy.8" not in table


def test_union_of_overlapping_ops():
    total, gaps = trace_reduce.union_ns([(0, 10), (5, 20), (30, 40), (35, 38)])
    assert total == 30
    assert gaps == [(20, 30)]


def test_busy_self_time_and_stages():
    ops = [
        ev("%while.4 = (s32[], u32[8]) while(...)", 100, 50),
        ev("%fusion.2 = u32[8] fusion(...)", 110, 30),  # nested in the loop
        ev("%scatter.7 = u32[8] scatter(...)", 160, 20),
        ev("%copy.8 = u32[8] copy(...)", 175, 10),  # overlaps the scatter
        ev("%broadcast.1 = u32[8] broadcast(...)", 300, 10),  # another module
    ]
    mods = [ev("jit__lambda(123)", 90, 100), ev("jit_broadcast_in_dim(9)", 295, 20)]
    _, table = hlo_scopes.scopes_of_module(HLO)
    r = trace_reduce.reduce([plane(0, ops, mods)], 1, {"jit__lambda": table})
    assert r["busy_s"] == pytest.approx(85e-9)  # [100,150) [160,185) [300,310)
    assert r["stage_s"]["match"] == pytest.approx(50e-9)  # loop self 20 + body 30
    assert r["stage_s"]["talk"] == pytest.approx(20e-9)
    assert r["stage_s"]["unscoped"] == pytest.approx(20e-9)
    assert r["op_s"]["while.4"] == pytest.approx(20e-9)
    assert r["step_s"] == pytest.approx(100e-9)
    assert r["gaps"][0][1] == pytest.approx(115e-9)
    assert r["gaps"][0][0] == "idle after copy.8"


def test_busy_is_averaged_over_the_chips_asked_for():
    p0 = plane(0, [ev("%a.1 = x", 0, 100)], [])
    p1 = plane(1, [ev("%a.1 = x", 0, 50)], [])
    p2 = plane(2, [ev("%a.1 = x", 0, 1000)], [])
    r = trace_reduce.reduce([p1, p2, p0], 2)
    assert r["chips_seen"] == 2
    assert r["busy_s"] == pytest.approx(75e-9)


def test_roofline_least_time_names_its_bound():
    peak = {"vpu_u32_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    assert roofline.least_time(2e12, 1e10, peak) == (2.0, "vpu")
    assert roofline.least_time(1e9, 1e12, peak) == (10.0, "hbm")
    assert roofline.match_ops(10, 3) == 10 * 3 * roofline.MATCH_OPS_PER_PAIR
    assert roofline.match_bytes(10, 3, 2) == 10 * 20 + 2 * 3 * 48


def test_match_roofline_reader():
    import importlib.util

    path = os.path.join(os.path.dirname(HERE), "metrics", "match_roofline.py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    class Cell:
        config = {"analysis": {"batch_size": 1 << 20}}
        device = {"kind": "k"}
        peaks = {"k": {"vpu_u32_ops_per_s": 1e12, "hbm_bytes_per_s": 1e15}}

    ctx = {"cell": Cell(), "lines": 1 << 20, "rows_real": 1000, "chips": 1,
           "trace": {"stage_s": {"match": 1.0}}}
    want = 100.0 * (1 << 20) * 1000 * roofline.MATCH_OPS_PER_PAIR / 1e12
    assert mod.read(ctx) == pytest.approx(want)
    ctx["trace"] = {"stage_s": {}}
    assert mod.read(ctx) is None  # nothing to read: no number, never 0


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace():
    with open(RECORDED, encoding="utf-8") as f:
        rec = json.load(f)
    r = trace_reduce.reduce(rec["planes"], 1, rec["scopes"])
    ops = [e for ln in rec["planes"][0]["lines"] if ln["name"] == "XLA Ops"
           for e in ln["events"]]
    assert r["chips_seen"] == 1 and r["ops"] == len(ops)
    assert 0 < r["busy_s"] <= sum(e["dur_ns"] for e in ops) * 1e-9
    # self times tile the busy time where ops only nest
    assert sum(r["stage_s"].values()) == pytest.approx(r["busy_s"], rel=1e-3)
    assert r["stage_s"].get("match", 0) > 0
    assert r == rec["expected"] or _close(r, rec["expected"])


def _close(a, b):
    for k in ("busy_s", "step_s"):
        assert a[k] == pytest.approx(b[k])
    for s, v in b["stage_s"].items():
        assert a["stage_s"][s] == pytest.approx(v)
    return True
