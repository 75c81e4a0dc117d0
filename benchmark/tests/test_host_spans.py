"""The host spans against the device's idle, and the readers of the program's
own counters: a small fixture with host events, no chip."""

from __future__ import annotations

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import host_spans  # noqa: E402


def ev(name, start, dur):
    return {"name": name, "start_ns": start, "dur_ns": dur}


def span(name, line, start, end, batch=None):
    return {"name": name, "line": line, "start_ns": start, "dur_ns": end - start,
            "batch": batch}


def device(dev, ops, mods=()):
    return {"name": f"/device:TPU:{dev}", "lines": [
        {"name": "XLA Modules", "events": list(mods)}, {"name": "XLA Ops", "events": ops}]}


# one call on line 0, its producer on line 1; the device runs four ops
OPS = [ev("%a.1 = x", 3200, 1800), ev("%b.2 = x", 5000, 200),
       ev("%c.3 = x", 6000, 2000), ev("%d.4 = x", 8100, 100)]
HOST = [
    span("run.call", 0, 1000, 9000),
    span("run.setup", 0, 1000, 3000),
    span("ingest.starved", 0, 2000, 2900, 0),  # under half of the head
    span("step.dispatch", 0, 3000, 3100, 0),
    span("ingest.starved", 0, 5100, 6100, 1),
    span("run.finalize", 0, 8150, 9000),
    span("ingest.produce", 1, 1000, 2900, 0),
    span("ingest.produce", 1, 4000, 5150, 1),
    span("ingest.pack", 1, 5150, 5900, 1),
]


def test_head_tail_and_names():
    r = host_spans.reduce_host([device(0, OPS)], HOST, 1)
    assert r["head_s"] == pytest.approx(2200e-9)  # call start to the first op
    assert r["tail_s"] == pytest.approx(800e-9)  # the last op to the call's end
    assert r["idle_by_host"] == pytest.approx({
        "idle in run.setup": 2200e-9,
        "idle in ingest.starved / ingest.pack": 800e-9,
        "idle in run.call": 100e-9,  # [8000, 8100): in the call, in no finer span
        "idle in run.finalize": 800e-9,
    })
    assert r["idle_s"] == pytest.approx(3900e-9)
    assert r["named_s"] == pytest.approx(r["idle_s"])
    assert [g[0] for g in r["gaps"]] == ["idle in run.setup",
                                         "idle in ingest.starved / ingest.pack",
                                         "idle in run.finalize", "idle in run.call"]


def test_gap_no_span_covers_keeps_its_op_name():
    ops = OPS + [ev("%e.5 = x", 9100, 100), ev("%f.6 = x", 9300, 100)]
    r = host_spans.reduce_host([device(0, ops)], HOST, 1)
    assert r["tail_s"] == 0.0  # ops ran past the call
    assert r["idle_by_host"]["idle after e.5"] == pytest.approx(100e-9)
    assert r["named_s"] == pytest.approx(r["idle_s"] - 100e-9)


def test_averaged_over_the_chips_asked_for():
    late = [dict(o, start_ns=o["start_ns"] + 200) for o in OPS]
    r = host_spans.reduce_host([device(1, late), device(0, OPS), device(2, [])], HOST, 2)
    assert r["head_s"] == pytest.approx(2300e-9)
    assert r["tail_s"] == pytest.approx(700e-9)


def test_no_call_span_reads_nothing():
    r = host_spans.reduce_host([device(0, OPS)], HOST[1:], 1)
    assert r["idle_by_host"] == {} and r["head_s"] == 0.0


def test_metadata_stripped_from_span_names():
    assert host_spans.span_name("ingest.pack#batch=3#") == "ingest.pack"
    assert host_spans.span_name("run.call") == "run.call"


def test_dispatch_lead_pairs_kth_module_with_kth_dispatch():
    mods = [ev("jit__lambda(1)", 3200, 2000), ev("jit_convert(2)", 5300, 10),
            ev("jit__lambda(1)", 6000, 2100)]
    host = HOST + [span("step.dispatch", 0, 5500, 5600, 1)]
    r = host_spans.dispatch_lead([device(0, OPS, mods)], host, {"jit__lambda": {"a.1": "match"}})
    assert (r["modules"], r["dispatches"], r["before_their_dispatch"]) == (2, 2, 0)
    assert r["min_lead_us"] == pytest.approx(0.2)
    late = HOST + [span("step.dispatch", 0, 6500, 6600, 1)]
    r = host_spans.dispatch_lead([device(0, OPS, mods)], late, {"jit__lambda": {"a.1": "match"}})
    assert r["before_their_dispatch"] == 1
    assert r["early"] == [{"k": 1, "lead_us": pytest.approx(-0.5), "at_s": pytest.approx(3.5e-6)}]


# -- the readers of the program's own counters -------------------------------

REPORT = {"totals": {
    "ingest": {"parse_sec": 0.3, "pack_sec": 0.02, "produce_sec": 0.32},
    "phases": {"setup_sec": 0.5, "finalize_sec": 0.25},
    "compile": {"events": 0, "sec": 0.0, "fns": {}},
}}
READS = {
    "parse_ms_per_mline.text": 150.0,  # 300 ms over 2 million lines
    "pack_ms_per_mline.text": 10.0,
    "call_setup_ms.text": 500.0,
    "call_setup_ms.wire": 500.0,
    "report_ms.text": 250.0,
    "report_ms.wire": 250.0,
    "compile_s_in_call.text": 0.0,
    "compile_s_in_call.wire": 0.0,
}


def reader(metric):
    spec = importlib.util.spec_from_file_location("m", harness.reader_path(BENCH, metric))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("metric,want", sorted(READS.items()))
def test_counter_readers(metric, want):
    ctx = {"report": REPORT, "lines": 2_000_000, "wall_s": 1.0}
    assert reader(metric).read(ctx) == pytest.approx(want)
    # a program without the counter (the parent of the change that added
    # it): nothing to read, so no number and no error
    assert reader(metric).read({"report": {"totals": {}}, "lines": 2_000_000,
                                "wall_s": 1.0}) is None


def test_new_metrics_are_in_the_manifest_with_their_readers():
    import json

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"), encoding="utf-8") as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(READS) <= names
    for metric in READS:
        assert os.path.exists(harness.reader_path(BENCH, metric))
