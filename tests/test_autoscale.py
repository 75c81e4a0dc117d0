"""Metrics-driven elastic autoscaling + the hybrid DCN x ICI mesh.

Four surfaces (ISSUE 7; DESIGN §13):

- **Policy engine** (fast, no subprocesses): the decision table on
  synthetic samples — sustain windows, cooldown, budget, observe-only,
  scripted plans, flap accounting — plus the metrics-stream adapters
  (tail of a torn JSONL, signal differentiation) and the Prometheus
  text rendering of the one-source-of-truth gauges.
- **Reshard law** (property): the epoch cursor manifest round-trips
  across world sizes 1 -> 8 -> 3 -> 8 (grow AND shrink), registers
  bit-identical to a single-world replay — the law the autoscaler
  leans on for every planned scale event.
- **Hybrid mesh**: the 2x4 two-level DCN x ICI mesh produces reports
  bit-identical to the flat 8-way mesh (text + wire).
- **Serve / elastic actuation**: bursty load into ``serve --autoscale``
  scales out on the burst and in after it, every published window
  bit-identical to an offline replay; chaos schedules land injected
  faults at the decide->actuate seam (typed abort or intact service,
  never a half-applied scale event).  The subprocess elastic drills
  (scale events through real re-formations) are ``slow``-marked.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu.config import (
    AnalysisConfig, AutoscaleConfig, ServeConfig, SketchConfig,
)
from ruleset_analysis_tpu.errors import AnalysisError
from ruleset_analysis_tpu.hostside import aclparse, pack, synth
from ruleset_analysis_tpu.runtime import faults
from ruleset_analysis_tpu.runtime.autoscale import (
    AutoscaleController, MetricsTail, PolicyEngine, flap_count,
    ingest_signals, parse_plan, read_decision_log, render_prom, world_ladder,
)
from ruleset_analysis_tpu.runtime.stream import run_stream, run_stream_wire

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: report-totals keys excluded from bit-identity images (the chaos
#: harness list + the autoscale/world blocks this PR adds: scale
#: timings are wall-clock, never part of the answer)
# ONE volatile-keys list (runtime/report.py): the registry auditor
# (verify/registry.py) flags any module keeping a private copy.
from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS as VOLATILE


def report_image(rep) -> dict:
    j = rep if isinstance(rep, dict) else json.loads(rep.to_json())
    j = json.loads(json.dumps(j))
    for k in VOLATILE:
        j["totals"].pop(k, None)
    j["totals"].pop("window", None)
    return j


def acfg(**kw) -> AutoscaleConfig:
    base = dict(
        min_world=1, max_world=8, out_threshold=0.5, in_threshold=0.8,
        sustain_sec=1.0, cooldown_sec=2.0, reform_budget=4, poll_sec=0.1,
    )
    base.update(kw)
    return AutoscaleConfig(**base)


# ---------------------------------------------------------------------------
# Policy engine decision table (pure, synthetic samples — the fast tier)
# ---------------------------------------------------------------------------


def feed(eng, t0, t1, *, p=0.0, s=0.0, dt=0.25):
    """Feed constant-signal samples over [t0, t1); first decision wins."""
    t = t0
    while t < t1:
        d = eng.observe(now=t, pressure=p, starvation=s)
        if d is not None:
            return d, t
        t += dt
    return None, t


def test_engine_scales_out_on_sustained_pressure_only():
    eng = PolicyEngine(acfg(), world=2, ladder=[1, 2, 4, 8])
    # below-threshold pressure never decides, no matter how long
    d, t = feed(eng, 0.0, 10.0, p=0.45)
    assert d is None
    # sustained above-threshold pressure decides after >= sustain_sec
    d, t = feed(eng, 10.0, 20.0, p=0.9)
    assert d is not None and (d.direction, d.from_world, d.to_world) == (
        "out", 2, 4,
    )
    assert t - 10.0 >= eng.acfg.sustain_sec
    assert d.reason == "backpressure" and d.actuate
    # evidence rides the decision: window stats + thresholds
    assert d.evidence["pressure"]["min"] >= 0.9
    assert d.evidence["pressure"]["threshold"] == eng.acfg.out_threshold


def test_engine_one_dip_resets_the_sustain_window():
    eng = PolicyEngine(acfg(sustain_sec=2.0), world=1, ladder=[1, 2])
    t = 0.0
    for i in range(40):
        # a dip every ~1.5s keeps min(window) below threshold forever
        p = 0.1 if i % 6 == 5 else 0.95
        assert eng.observe(now=t, pressure=p, starvation=0.0) is None
        t += 0.25
    assert eng.decisions == []


def test_engine_cooldown_and_ladder_edges():
    eng = PolicyEngine(acfg(cooldown_sec=5.0), world=4, ladder=[2, 4, 8])
    d, t = feed(eng, 0.0, 30.0, p=1.0)
    assert (d.from_world, d.to_world) == (4, 8)
    # within cooldown: silent hold even under saturated pressure
    d2, _ = feed(eng, t, t + 4.9, p=1.0)
    assert d2 is None
    # at the top rung: pressure can never push past the ladder
    d3, _ = feed(eng, t + 5.0, t + 30.0, p=1.0)
    assert d3 is None
    # starvation brings it back down a rung
    d4, _ = feed(eng, t + 31.0, t + 60.0, s=1.0)
    assert (d4.direction, d4.to_world) == ("in", 4)


def test_engine_budget_exhaustion_and_observe_only():
    eng = PolicyEngine(acfg(reform_budget=1, cooldown_sec=1.0),
                       world=1, ladder=[1, 2, 4])
    d, t = feed(eng, 0.0, 30.0, p=1.0)
    assert d is not None and eng.budget_left == 0
    d2, _ = feed(eng, t + 1.5, t + 30.0, p=1.0)
    assert d2 is None  # budget gone: hold forever
    assert eng.suppressed_budget > 0
    assert eng.summary()["suppressed_by_budget"] == eng.suppressed_budget

    obs_only = PolicyEngine(acfg(reform_budget=0), world=1, ladder=[1, 2])
    d, _ = feed(obs_only, 0.0, 30.0, p=1.0)
    assert d is not None and not d.actuate
    assert obs_only.world == 1  # decisions recorded, never actuated
    assert obs_only.summary()["observe_only"]


def test_engine_flap_accounting_and_damping_window():
    a = acfg(sustain_sec=1.0, cooldown_sec=1.0)  # damping window = 4s
    eng = PolicyEngine(a, world=2, ladder=[2, 4])
    d1, t = feed(eng, 0.0, 30.0, p=1.0)
    # immediate reversal right after cooldown: inside 2*(cd+sus) = flap
    d2, t2 = feed(eng, t + 1.1, t + 30.0, s=1.0)
    assert d1.direction == "out" and d2.direction == "in"
    assert eng.flaps == 1
    # the cross-generation flap counter sees the same thing on t_wall
    log = [{"direction": "out", "t_wall": 100.0},
           {"direction": "in", "t_wall": 102.5}]
    assert flap_count(log, cooldown_sec=1.0, sustain_sec=1.0) == 1
    # reversal OUTSIDE the window is a legitimate load response, not a flap
    log[1]["t_wall"] = 104.5
    assert flap_count(log, cooldown_sec=1.0, sustain_sec=1.0) == 0
    # same direction twice is never a flap, whatever the spacing
    log[1] = {"direction": "out", "t_wall": 100.2}
    assert flap_count(log, cooldown_sec=1.0, sustain_sec=1.0) == 0


def test_engine_scripted_plan_bypasses_thresholds():
    eng = PolicyEngine(acfg(plan="out@1,out@2,in@5"),
                       world=2, ladder=[1, 2, 4, 8])
    decs = []
    t = 0.0
    while t < 10.0:
        d = eng.observe(now=t, pressure=0.0, starvation=0.0)
        if d:
            decs.append((round(t, 2), d.direction, d.to_world, d.reason))
        t += 0.25
    assert [(d, w) for _, d, w, _ in decs] == [("out", 4), ("out", 8), ("in", 4)]
    assert all(r == "plan" for *_, r in decs)
    # entries fire at (not before) their offsets
    assert [x[0] for x in decs] == [1.0, 2.0, 5.0]


def test_engine_window_resets_after_decision():
    """Post-reform signals describe new capacity: no instant double-fire."""
    eng = PolicyEngine(acfg(cooldown_sec=0.0), world=1, ladder=[1, 2, 4])
    d, t = feed(eng, 0.0, 30.0, p=1.0)
    assert d is not None
    # zero cooldown, but the window restarted: the next decision still
    # needs a FULL fresh sustain window
    d2, t2 = feed(eng, t + 0.25, t + 30.0, p=1.0)
    assert d2 is not None
    assert t2 - t >= eng.acfg.sustain_sec


def test_world_ladder_and_plan_parsing():
    assert world_ladder(1, 8) == [1, 2, 3, 4, 5, 6, 7, 8]
    assert world_ladder(2, 8, divisors_of=8) == [2, 4, 8]
    assert world_ladder(1, 6, divisors_of=12) == [1, 2, 3, 4, 6]
    with pytest.raises(AnalysisError, match="empty"):
        world_ladder(5, 7, divisors_of=8)
    assert parse_plan("out@1.5, in@3") == [("out", 1.5), ("in", 3.0)]
    with pytest.raises(AnalysisError):
        parse_plan("sideways@1")
    # config validation refuses malformed knobs eagerly
    with pytest.raises(ValueError):
        AutoscaleConfig(plan="out@nope")
    with pytest.raises(ValueError):
        AutoscaleConfig(min_world=0)
    with pytest.raises(ValueError):
        AutoscaleConfig(min_world=4, max_world=2)
    with pytest.raises(ValueError):
        AutoscaleConfig(out_threshold=1.5)
    rt = AutoscaleConfig.from_dict(acfg(plan="out@2").to_dict())
    assert rt == acfg(plan="out@2")


def test_engine_off_ladder_world_refused():
    with pytest.raises(AnalysisError, match="ladder"):
        PolicyEngine(acfg(), world=3, ladder=[2, 4, 8])


# ---------------------------------------------------------------------------
# Metrics adapters: JSONL tail, signal differentiation, Prometheus text
# ---------------------------------------------------------------------------


def test_metrics_tail_tolerates_torn_and_missing(tmp_path):
    p = str(tmp_path / "m.jsonl")
    tail = MetricsTail(p)
    assert tail.poll() == []  # not created yet: worker still starting
    with open(p, "w") as f:
        f.write('{"kind":"snapshot","t":1}\n{"kind":"snap')
        f.flush()
    recs = tail.poll()
    assert [r["t"] for r in recs] == [1]
    with open(p, "a") as f:
        f.write('shot","t":2}\n')
    assert [r["t"] for r in tail.poll()] == [2]  # torn line completed


def test_controller_tails_metrics_and_publishes_once(tmp_path):
    """The elastic leader's controller: differentiates the cumulative
    ingest counters over a smoothing stride, decides, publishes exactly
    ONE scale request, then stops (the re-formation replaces it)."""
    mpath = str(tmp_path / "metrics.jsonl")
    published = []
    ctrl = AutoscaleController(
        acfg(out_threshold=0.4, sustain_sec=0.4, cooldown_sec=0.1,
             poll_sec=0.05),
        world=2, ladder=[1, 2, 4],
        metrics_path=mpath, publish=published.append, budget_left=4,
    )
    ctrl.start()
    try:
        t0 = time.time()
        bp = 0.0
        with open(mpath, "w") as f:
            # ~8s of device-bound snapshots, written faster than real
            # time; the >=1s differentiation stride sees bp/dt ~= 0.9
            for i in range(28):
                bp += 0.27
                f.write(json.dumps({
                    "kind": "snapshot", "t": t0 + 0.3 * (i + 1),
                    "lines": 64 * i, "lines_per_sec_inst": 200.0,
                    "ingest": {
                        "backpressure_sec": round(bp, 3),
                        "starved_sec": 0.01 * i, "queue_depth": 2,
                    },
                }) + "\n")
                f.flush()
                time.sleep(0.05)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not published:
            time.sleep(0.05)
    finally:
        ctrl.stop()
        ctrl.join(timeout=10)
    assert ctrl.error is None
    assert len(published) == 1  # one request per controller, ever
    dec = published[0]
    assert (dec.direction, dec.from_world, dec.to_world) == ("out", 2, 4)
    assert dec.evidence["pressure"]["min"] >= 0.4
    assert not ctrl.is_alive()  # returned after publishing


def test_controller_observe_only_logs_without_publishing(tmp_path):
    """Budget 0 (the rollout drill): decisions land in the log with
    their evidence, but nothing is ever published/actuated."""
    mpath = str(tmp_path / "metrics.jsonl")
    published, logged = [], []
    ctrl = AutoscaleController(
        acfg(out_threshold=0.4, sustain_sec=0.4, cooldown_sec=0.2,
             poll_sec=0.05, reform_budget=0),
        world=2, ladder=[1, 2, 4],
        metrics_path=mpath, publish=published.append,
        log=logged.append, budget_left=0,
    )
    ctrl.start()
    try:
        t0 = time.time()
        bp = 0.0
        with open(mpath, "w") as f:
            for i in range(28):
                bp += 0.27
                f.write(json.dumps({
                    "kind": "snapshot", "t": t0 + 0.3 * (i + 1),
                    "ingest": {"backpressure_sec": round(bp, 3),
                               "starved_sec": 0.0},
                }) + "\n")
                f.flush()
                time.sleep(0.05)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not logged:
            time.sleep(0.05)
    finally:
        ctrl.stop()
        ctrl.join(timeout=10)
    assert ctrl.error is None
    assert published == []  # never actuated
    assert logged and not logged[0].actuate
    assert logged[0].evidence["pressure"]["min"] >= 0.4


def test_ingest_signals_differentiate_cumulative_counters():
    mk = lambda t, bp, st: {  # noqa: E731
        "t": t, "ingest": {"backpressure_sec": bp, "starved_sec": st},
    }
    assert ingest_signals(None, mk(0, 0, 0)) is None  # nothing to diff yet
    p, s = ingest_signals(mk(0, 0, 0), mk(2.0, 1.0, 0.5))
    assert (p, s) == (0.5, 0.25)
    # clamped to [0, 1] even when counters jump a whole blocked burst
    p, s = ingest_signals(mk(0, 0, 0), mk(1.0, 5.0, 0.0))
    assert (p, s) == (1.0, 0.0)
    assert ingest_signals(mk(5, 0, 0), mk(5, 1, 1)) is None  # dt <= 0
    assert ingest_signals(mk(0, 0, 0), {"t": 1}) is None  # no ingest gauge


def test_render_prom_exposition_format():
    text = render_prom(
        {"queue_depth": 12, "rate": 1.5, "name": "skipme", "ok": True},
        prefix="ra_serve_",
    )
    lines = text.strip().split("\n")
    assert "# TYPE ra_serve_queue_depth gauge" in lines
    assert "ra_serve_queue_depth 12" in lines
    assert "ra_serve_rate 1.5" in lines
    assert "ra_serve_ok 1" in lines  # booleans export as 0/1
    assert not any("skipme" in ln for ln in lines)  # non-numeric skipped
    assert text.endswith("\n")


def test_trace_summary_autoscale_block(tmp_path):
    """The trace alone answers what/why/how-fast for every scale event."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import trace_summary

    def decide(ts, seq, direction, frm, to, reason):
        return {
            "ph": "i", "name": "autoscale.decide", "ts": ts, "pid": 1,
            "args": {
                "seq": seq, "direction": direction, "from_world": frm,
                "to_world": to, "reason": reason, "actuate": True,
                "damping_window_sec": 3.0,
                "evidence": {
                    "window_sec": 0.6,
                    "pressure": {"min": 0.4, "threshold": 0.25},
                },
            },
        }

    events = [
        {"ph": "X", "name": "step.dispatch", "ts": 0, "dur": 1000, "pid": 1},
        decide(1_000_000, 1, "out", 2, 4, "backpressure"),
        {"ph": "X", "name": "autoscale.apply", "ts": 1_000_100,
         "dur": 25_000, "pid": 1},
        # a reversal INSIDE the 3s damping window: one flap
        decide(3_000_000, 2, "in", 4, 2, "starvation"),
        {"ph": "X", "name": "autoscale.apply", "ts": 3_000_100,
         "dur": 15_000, "pid": 1},
        # and one far outside it: a legitimate load response
        decide(9_000_000, 3, "out", 2, 4, "backpressure"),
        {"ph": "i", "name": "autoscale.retire", "ts": 9_100_000, "pid": 2},
        {"ph": "i", "name": "autoscale.standby", "ts": 9_200_000, "pid": 3},
    ]
    p = str(tmp_path / "trace.json")
    with open(p, "w") as f:
        json.dump({"traceEvents": events}, f)
    s = trace_summary.summarize(p)
    a = s["autoscale"]
    assert a["scale_out"] == 2 and a["scale_in"] == 1
    assert a["flaps"] == 1
    assert a["applies"] == 2
    assert a["time_to_effect_max_ms"] == 25.0
    assert a["retirements"] == 1 and a["standby_parks"] == 1
    assert [d["seq"] for d in a["decisions"]] == [1, 2, 3]
    assert a["decisions"][0]["evidence"]["pressure"]["min"] == 0.4
    text = trace_summary.render(s)
    assert "autoscale: 2 out / 1 in, 1 flap(s)" in text
    assert "#1 out 2->4 (backpressure)" in text
    assert "[min 0.4 >= thr 0.25" in text


# ---------------------------------------------------------------------------
# The reshard law: epoch cursors round-trip across world sizes
# 1 -> 8 -> 3 -> 8, registers bit-identical to a single-world replay.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def reshard_corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("reshard")
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=6, seed=51)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 960, seed=52)
    lines = synth.render_syslog(packed, tuples, seed=53, variety=0.3)
    shards = []
    for i in range(8):
        p = td / f"s{i}.log"
        p.write_text(
            "".join(ln + "\n" for ln in lines[i * 120:(i + 1) * 120]),
            encoding="utf-8",
        )
        shards.append(str(p))
    return packed, shards


def test_reshard_grow_and_shrink_registers_bit_identical(reshard_corpus):
    """Worlds 1 -> 8 -> 3 -> 8 over the same corpus == one world-1 pass.

    Each generation re-splits the REMAINING work from the merged cursor
    manifest (`assign_shards`, exactly what a planned scale re-formation
    does), consumes a bounded slice through the real device step, and
    merges its register contribution under the epoch-ring laws.  Any
    lost, duplicated, or re-ordered line shows up as a register diff.
    """
    import jax

    from ruleset_analysis_tpu.models import pipeline
    from ruleset_analysis_tpu.parallel import mesh as mesh_lib
    from ruleset_analysis_tpu.parallel.step import make_parallel_step
    from ruleset_analysis_tpu.runtime.elastic import assign_shards
    from ruleset_analysis_tpu.runtime.serve import (
        merge_register_arrays, zero_arrays,
    )
    from ruleset_analysis_tpu.runtime.stream import _ShardCursorSource

    packed, shards = reshard_corpus
    cfg = AnalysisConfig(
        batch_size=64,
        sketch=SketchConfig(cms_width=1 << 10, cms_depth=2, hll_p=6),
    )
    mesh = mesh_lib.make_mesh(list(jax.devices())[:1], axis=cfg.mesh_axis)
    step = make_parallel_step(mesh, cfg, packed.n_keys)
    rules = pipeline.ship_ruleset(packed)

    def consume(assignment, max_batches):
        """One rank of one generation: (register image, cursors, done)."""
        src = _ShardCursorSource(packed, assignment, native=False)
        state = pipeline.init_state(packed.n_keys, cfg)
        n = 0
        for batch, _n_raw in src.batches(0, 64):
            wire = pack.compact_batch(batch)
            state, _ = step(
                state, rules, mesh_lib.shard_batch(mesh, wire, cfg.mesh_axis)
            )
            n += 1
            if max_batches is not None and n >= max_batches:
                break
        return dict(pipeline.state_to_host(state)), src.cursors, src.done

    def staged(worlds_and_quota):
        cursors: dict[int, int] = {}
        done: set[int] = set()
        total = zero_arrays(packed.n_keys, cfg)
        consumed_epochs = []
        for world, quota in worlds_and_quota:
            parts = assign_shards(shards, cursors, done, world)
            images = []
            for assignment in parts:
                if not assignment:
                    continue  # more ranks than remaining shards
                img, cur, sub_done = consume(assignment, quota)
                images.append(img)
                # the epoch manifest merge: every rank's cursors union
                cursors.update(cur)
                done |= sub_done
            if images:
                total = merge_register_arrays([total] + images)
            consumed_epochs.append(
                (world, sum(cursors.values()), sorted(done))
            )
        # the final generation must have drained everything
        assert set(range(len(shards))) == done, consumed_epochs
        assert sum(cursors.values()) == 960
        return total

    # grow AND shrink: 1 -> 8 -> 3 -> 8 (bounded slices force mid-shard
    # cursors at every boundary), vs. one uninterrupted world-1 pass
    got = staged([(1, 3), (8, 1), (3, 2), (8, None)])
    want = staged([(1, None)])
    for name in sorted(want):
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


# ---------------------------------------------------------------------------
# Hybrid DCN x ICI mesh: bit-identical to the flat mesh over the same
# devices (the acceptance pin for `--mesh hybrid`).
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def hybrid_corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("hybrid")
    cfg_text = synth.synth_config(
        n_acls=2, rules_per_acl=8, seed=7, v6_fraction=0.25
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    t = synth.synth_tuples(packed, 700, seed=1)
    lines = synth.render_syslog(packed, t, seed=1)
    t6 = synth.synth_tuples6(packed, 150, seed=2)
    lines += synth.render_syslog6(packed, t6, seed=3)
    text = str(td / "mix.log")
    with open(text, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    from ruleset_analysis_tpu.hostside import wire as wire_mod

    wirep = str(td / "mix.rawire")
    wire_mod.convert_logs(packed, [text], wirep, block_rows=256)
    return packed, lines, wirep


def test_mesh_constructors_and_axes():
    import jax

    from ruleset_analysis_tpu.parallel import mesh as mesh_lib

    devs = list(jax.devices())
    flat = mesh_lib.make_mesh(devs, "data")
    assert mesh_lib.data_axes(flat) == "data"
    assert mesh_lib.data_extent(flat) == len(devs)
    hyb = mesh_lib.make_mesh(devs, "data", topology="hybrid", dcn=2)
    assert hyb.axis_names == ("dcn", "data")
    assert dict(hyb.shape) == {"dcn": 2, "data": len(devs) // 2}
    assert mesh_lib.data_axes(hyb) == ("dcn", "data")
    assert mesh_lib.data_extent(hyb) == len(devs)
    # device ORDER is preserved: slice placement identical to flat
    assert [d.id for d in hyb.devices.flat] == [d.id for d in flat.devices.flat]
    with pytest.raises(AnalysisError, match="divide"):
        mesh_lib.make_mesh(devs, "data", topology="hybrid", dcn=3)
    with pytest.raises(AnalysisError, match=">= 2"):
        mesh_lib.make_mesh(devs, "data", topology="hybrid", dcn=1)
    with pytest.raises(AnalysisError, match="topology"):
        mesh_lib.make_mesh(devs, "data", topology="weird")
    # the padded batch covers the PRODUCT of both axes
    assert mesh_lib.pad_batch_size(9, hyb, "data") == 16


@pytest.mark.parametrize("kind", ["text", "wire"])
def test_hybrid_mesh_report_bit_identical_to_flat(hybrid_corpus, kind):
    packed, lines, wirep = hybrid_corpus

    def run(shape, dcn=0):
        cfg = AnalysisConfig(batch_size=128, mesh_shape=shape, mesh_dcn=dcn)
        rep = (
            run_stream_wire(packed, wirep, cfg, topk=5)
            if kind == "wire"
            else run_stream(packed, iter(lines), cfg, topk=5)
        )
        return report_image(rep)

    flat = run("flat")
    assert run("hybrid") == flat          # auto dcn: 2 x 4
    assert run("hybrid", dcn=4) == flat   # 4 x 2: grouping-invariant


def test_hybrid_mesh_config_validation():
    with pytest.raises(ValueError, match="mesh_shape"):
        AnalysisConfig(mesh_shape="ring")
    with pytest.raises(ValueError, match="mesh_dcn"):
        AnalysisConfig(mesh_dcn=2)  # only applies to hybrid
    AnalysisConfig(mesh_shape="hybrid", mesh_dcn=2)  # ok


# ---------------------------------------------------------------------------
# Serve actuation: the e2e acceptance (bursty load -> out -> in, windows
# bit-identical) and the chaos seams.
# ---------------------------------------------------------------------------

SERVE_CFG = dict(
    batch_size=64,
    sketch=SketchConfig(cms_width=1 << 10, cms_depth=2, hll_p=6),
)


@pytest.fixture(scope="module")
def serve_corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("as_serve")
    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=8, seed=0)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    prefix = str(td / "rules")
    pack.save_packed(packed, prefix)
    t = synth.synth_tuples(packed, 3000, seed=1)
    lines = synth.render_syslog(packed, t, seed=1)
    return packed, prefix, lines


def _start(drv):
    out: dict = {}

    def runner():
        try:
            out["summary"] = drv.run()
        except BaseException as e:
            out["error"] = e

    th = threading.Thread(target=runner)
    th.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not (
        "error" in out or (drv.listeners.listeners and drv.listeners.alive())
    ):
        time.sleep(0.05)
    return th, out


def _wait(pred, timeout, msg):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {msg}")


def test_serve_autoscale_e2e_burst_out_idle_in(serve_corpus, tmp_path):
    """The acceptance run: a traffic burst scales the serve mesh OUT
    (threshold-driven, evidence attached), the post-burst idle scales it
    back IN after cooldown, zero flaps, zero drops — and every published
    window is bit-identical to an offline replay of exactly its lines.
    """
    import urllib.request

    from ruleset_analysis_tpu.runtime.serve import ServeDriver

    packed, prefix, lines = serve_corpus
    cfg = AnalysisConfig(**SERVE_CFG)
    a = acfg(
        min_world=2, max_world=8, initial_world=2,
        out_threshold=0.3, in_threshold=0.8,
        sustain_sec=0.5, cooldown_sec=1.0, reform_budget=4, poll_sec=0.1,
    )
    scfg = ServeConfig(
        listen=("tcp:127.0.0.1:0",), window_lines=1000, ring=4,
        serve_dir=str(tmp_path / "serve"), http="127.0.0.1:0",
        # queue bigger than the burst: pressure rises, nothing drops
        queue_lines=4096, checkpoint_every_windows=0, reload_watch=False,
        stop_after_sec=240,
    )
    drv = ServeDriver(prefix, cfg, scfg, topk=5, ascfg=a)
    th, out = _start(drv)
    assert "error" not in out, out.get("error")
    assert drv.world == 2  # starts at the configured initial rung

    # burst: the whole corpus at once — the device tier falls behind,
    # queue occupancy (the pressure signal) sustains above threshold
    s = socket.create_connection(drv.listeners.listeners[0].address)
    s.sendall(("\n".join(lines) + "\n").encode())
    s.close()
    _wait(
        lambda: any(d.direction == "out" for d in drv._engine.decisions),
        90, "scale-out under burst",
    )
    # idle: the queue drains, starvation sustains, the mesh comes back
    _wait(
        lambda: any(d.direction == "in" for d in drv._engine.decisions),
        120, "scale-in after the burst",
    )

    # the one-source-of-truth metrics surface, Prometheus variant
    host, port = drv.http_address
    with urllib.request.urlopen(
        f"http://{host}:{port}/metrics?format=prom", timeout=10
    ) as r:
        assert "version=0.0.4" in r.headers["Content-Type"]
        prom = r.read().decode()
    assert "ra_serve_queue_depth " in prom
    assert "ra_serve_world " in prom
    assert "ra_serve_autoscale_scale_out_total" in prom
    with urllib.request.urlopen(
        f"http://{host}:{port}/metrics", timeout=10
    ) as r:
        gauges = json.load(r)
    assert gauges["world"] == drv.world  # JSON variant: same gauges

    _wait(lambda: drv.windows_published >= 3, 120, "3 windows")
    drv.stop()
    th.join(timeout=120)
    assert not th.is_alive(), "serve hung after stop"
    assert "error" not in out, out.get("error")
    summary = out["summary"]

    # decisions carried their evidence and stayed within budget
    asum = summary["autoscale"]
    assert asum["scale_out"] >= 1 and asum["scale_in"] >= 1
    assert asum["flaps"] == 0
    assert asum["budget_left"] >= 0
    outs = [d for d in asum["decisions"] if d["direction"] == "out"]
    assert outs[0]["reason"] == "backpressure"
    assert outs[0]["evidence"]["pressure"]["min"] >= a.out_threshold
    assert outs[0]["evidence"]["time_to_effect_sec"] >= 0
    ins = [d for d in asum["decisions"] if d["direction"] == "in"]
    assert ins[0]["reason"] == "starvation"
    # every decision stays on the divisor ladder
    ladder = world_ladder(2, 8, divisors_of=8)
    assert all(d["to_world"] in ladder for d in asum["decisions"])
    assert summary["drops"] == 0

    # window fidelity ACROSS scale events: each published window is
    # bit-identical to an offline fixed-world replay of its lines
    for i in range(3):
        with open(
            os.path.join(scfg.serve_dir, f"window-{i:06d}.json"),
            encoding="utf-8",
        ) as f:
            got = json.load(f)
        seg = lines[i * 1000:(i + 1) * 1000]
        want = run_stream(packed, iter(seg), cfg, topk=5)
        assert report_image(got) == report_image(want), f"window {i}"


def test_serve_autoscale_rejects_bad_geometry(serve_corpus, tmp_path):
    from ruleset_analysis_tpu.runtime.serve import ServeDriver

    _packed, prefix, _lines = serve_corpus
    scfg = ServeConfig(
        listen=("tcp:127.0.0.1:0",), window_lines=100,
        serve_dir=str(tmp_path / "s"), http="off", reload_watch=False,
    )
    # the hybrid topology is the multi-host direction; serve autoscale
    # resizes a flat mesh — combining them is a config error
    with pytest.raises(AnalysisError, match="hybrid"):
        ServeDriver(
            prefix, AnalysisConfig(**SERVE_CFG, mesh_shape="hybrid"),
            scfg, ascfg=acfg(),
        )
    # off-ladder initial world (3 does not divide 8)
    drv = None
    with pytest.raises(AnalysisError, match="ladder"):
        drv = ServeDriver(
            prefix, AnalysisConfig(**SERVE_CFG), scfg,
            ascfg=acfg(min_world=1, max_world=8, initial_world=3),
        )
        drv.run()
    # more worlds than devices
    with pytest.raises(AnalysisError, match="devices"):
        drv = ServeDriver(
            prefix, AnalysisConfig(**SERVE_CFG), scfg,
            ascfg=acfg(max_world=64),
        )
        drv.run()


# ---------------------------------------------------------------------------
# Chaos: the decide->actuate seam.  Injected faults at autoscale.decide /
# autoscale.spawn — during rotation, checkpointing, and mid-stream — must
# end in a typed abort or an intact, bit-identical service.  Scripted
# plans make the decision times deterministic.
# ---------------------------------------------------------------------------

CHAOS_W = 100
CHAOS_LINES = 300


def chaos_schedule(seed: int):
    site = ["autoscale.decide", "autoscale.spawn"][seed % 2]
    # hit 1-2: land on the 1st/2nd scale decision (which interleave
    # with rotations and, for odd seeds, ring checkpoints); 99: a
    # never-fires schedule (the clean-run branch)
    at = [1, 2, 99][(seed // 2) % 3]
    return site, at, faults.FaultPlan([faults.FaultSpec(site, at)], seed=seed)


@pytest.mark.parametrize("seed", range(6))
def test_chaos_autoscale_seam(seed, serve_corpus, tmp_path):
    from ruleset_analysis_tpu.runtime.serve import ServeDriver

    packed, prefix, lines = serve_corpus
    lines = lines[:CHAOS_LINES]
    site, at, plan = chaos_schedule(seed)
    cfg = AnalysisConfig(**SERVE_CFG)
    a = acfg(
        min_world=2, max_world=8, initial_world=2,
        reform_budget=4, poll_sec=0.05,
        # scripted decisions, timed to interleave with the rotations the
        # 100-line windows force while the 300-line corpus drains
        plan="out@0.3,in@1.2,out@2.1",
    )
    scfg = ServeConfig(
        listen=("tcp:127.0.0.1:0",), window_lines=CHAOS_W, ring=4,
        serve_dir=str(tmp_path / "serve"), max_windows=3, http="off",
        queue_lines=10_000, reload_watch=False, stop_after_sec=90,
        # odd seeds checkpoint every rotation: scale events interleave
        # with ring checkpoint writes too
        checkpoint_every_windows=(1 if seed % 2 else 0),
        checkpoint_dir=str(tmp_path / "ck"),
    )
    out: dict = {}
    with faults.armed(plan):
        drv = ServeDriver(prefix, cfg, scfg, topk=5, ascfg=a)
        th, out = _start(drv)
        if "error" not in out:
            s = socket.create_connection(drv.listeners.listeners[0].address)
            # paced feed so decision offsets interleave mid-stream
            for i in range(0, CHAOS_LINES, 50):
                s.sendall(("\n".join(lines[i:i + 50]) + "\n").encode())
                time.sleep(0.05)
            s.close()
        th.join(timeout=150)
        assert not th.is_alive(), f"seed {seed} ({site}@{at}): serve HUNG"

    if "error" in out:
        # the typed-abort branch: the injected failure at the seam
        # surfaced as a typed error, never a half-applied scale event
        assert isinstance(out["error"], AnalysisError), (
            f"seed {seed} ({site}@{at}): untyped {out['error']!r}"
        )
        assert at <= 3, f"seed {seed}: never-fire schedule aborted"
        return

    # the clean branch: all 3 windows published, each bit-identical to
    # an offline replay over exactly its lines, drops still zero
    summary = out["summary"]
    assert summary["windows_published"] == 3
    assert summary["drops"] == 0
    for i in range(3):
        with open(
            os.path.join(scfg.serve_dir, f"window-{i:06d}.json"),
            encoding="utf-8",
        ) as f:
            got = json.load(f)
        seg = lines[i * CHAOS_W:(i + 1) * CHAOS_W]
        want = run_stream(packed, iter(seg), cfg, topk=5)
        assert report_image(got) == report_image(want), (
            f"seed {seed} ({site}@{at}): window {i} diverged"
        )


# ---------------------------------------------------------------------------
# Elastic actuation (subprocess drills — slow tier): planned scale
# events drive REAL re-formations through the epoch checkpoints, with
# warm standbys parking and promoting, report always bit-identical.
# ---------------------------------------------------------------------------


def _launcher_env(n_local_devices: int) -> dict:
    from cpuenv import cpu_env

    return cpu_env(n_local_devices)


@pytest.fixture(scope="module")
def elastic_corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("as_elastic")
    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=8, seed=41, egress_acls=True
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 1600, seed=42)
    lines = synth.render_syslog(packed, tuples, seed=43, variety=0.4)
    prefix = str(td / "packed")
    pack.save_packed(packed, prefix)
    shards = []
    for i in range(4):
        p = td / f"shard{i}.log"
        p.write_text(
            "".join(ln + "\n" for ln in lines[i * 400:(i + 1) * 400]),
            encoding="utf-8",
        )
        shards.append(str(p))
    return td, prefix, shards


def _spawn_autoscale_launchers(
    td, prefix, shards, *, n, flags, pace="0.3", fault_plan=None, timeout=400,
):
    env = _launcher_env(2)
    env["RA_ELASTIC_PACE"] = pace  # slow the stream so policy can react
    eldir = str(td / "eldir")
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "ruleset_analysis_tpu.cli", "run",
             "--ruleset", prefix, "--logs", *shards, "--backend", "tpu",
             "--distributed", "--elastic", "--elastic-dir", eldir,
             "--num-processes", str(n), "--process-id", str(pid),
             "--batch-size", "64", "--checkpoint-every", "2",
             "--autoscale", *flags,
             *(["--fault-plan", fault_plan] if fault_plan else []),
             "--json", "--out", str(td / f"rep{pid}.json")],
            env=env, cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in range(n)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError("autoscale launcher HUNG")
        outs.append((p.returncode, out, err))
    return outs, eldir


def _reference_report(prefix, shards):
    from ruleset_analysis_tpu.runtime.stream import run_stream_file

    packed = pack.load_packed(prefix)
    rep = run_stream_file(packed, shards, AnalysisConfig(batch_size=64))
    return json.loads(rep.to_json())


@pytest.mark.slow
def test_elastic_autoscale_plan_drill_bit_identical(elastic_corpus):
    """Scripted out@2,in@6 against a 4-member pool starting at world 2:
    two planned re-formations (2->3->2) with standbys parking/promoting,
    final report bit-identical to an uninterrupted fixed-world run."""
    td, prefix, shards = elastic_corpus
    outs, eldir = _spawn_autoscale_launchers(
        td, prefix, shards, n=4,
        flags=["--autoscale-min", "2", "--autoscale-max", "4",
               "--autoscale-initial", "2", "--autoscale-budget", "3",
               "--autoscale-plan", "out@2,in@6", "--autoscale-poll", "0.1"],
        pace="0.4",
    )
    for pid, (rc, _out, err) in enumerate(outs):
        assert rc == 0, f"launcher {pid} rc={rc}\n{err[-3000:]}"

    rep = json.load(open(td / "rep0.json"))
    t = rep["totals"]
    asum = t["autoscale"]
    assert asum["scale_events"] == 2
    assert asum["scale_out"] == 1 and asum["scale_in"] == 1
    assert asum["final_world"] == 2
    # every applied event records its time-to-effect
    assert all(e["time_to_effect_sec"] >= 0 for e in asum["applied"])
    # the shared decision log survives for trace tooling
    log = read_decision_log(os.path.join(eldir, "scale-log.jsonl"))
    assert sum(1 for r in log if r.get("kind") == "applied") == 2

    ref = _reference_report(prefix, shards)
    hits = lambda r: {  # noqa: E731
        (e["firewall"], e["acl"], e["index"]): e["hits"] for e in r["per_rule"]
    }
    assert hits(rep) == hits(ref)
    assert rep["unused"] == ref["unused"]
    assert t["lines_total"] == 1600


@pytest.mark.slow
def test_elastic_autoscale_threshold_scale_out(tmp_path_factory):
    """Signal-driven: the CPU device tier is the bottleneck, so rank 0's
    metrics shard shows sustained producer backpressure — the policy
    scales 2 -> 3 from the LIVE signals, no script.  A corpus long
    enough that the decision lands with work left to re-form over."""
    td = tmp_path_factory.mktemp("as_elastic_thr")
    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=8, seed=41, egress_acls=True
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 2400, seed=42)
    lines = synth.render_syslog(packed, tuples, seed=43, variety=0.4)
    prefix = str(td / "packed")
    pack.save_packed(packed, prefix)
    shards = []
    for i in range(4):
        p = td / f"shard{i}.log"
        p.write_text(
            "".join(ln + "\n" for ln in lines[i * 600:(i + 1) * 600]),
            encoding="utf-8",
        )
        shards.append(str(p))
    outs, _eldir = _spawn_autoscale_launchers(
        td, prefix, shards, n=3,
        flags=["--autoscale-min", "2", "--autoscale-max", "3",
               "--autoscale-initial", "2", "--autoscale-budget", "2",
               "--autoscale-out-threshold", "0.2",
               "--autoscale-sustain", "1.5", "--autoscale-cooldown", "5.0",
               "--autoscale-poll", "0.2"],
        pace="0.05",  # keep the producer fast: pressure, not starvation
    )
    for pid, (rc, _out, err) in enumerate(outs):
        assert rc == 0, f"launcher {pid} rc={rc}\n{err[-3000:]}"
    rep = json.load(open(td / "rep0.json"))
    asum = rep["totals"]["autoscale"]
    assert asum["scale_out"] >= 1
    assert asum["final_world"] == 3
    assert asum["flaps"] == 0
    d = next(x for x in asum["decisions"] if x["direction"] == "out")
    assert d["reason"] == "backpressure"
    assert d["evidence"]["pressure"]["min"] >= 0.2  # evidence attached

    ref = _reference_report(prefix, shards)
    hits = lambda r: {  # noqa: E731
        (e["firewall"], e["acl"], e["index"]): e["hits"] for e in r["per_rule"]
    }
    assert hits(rep) == hits(ref)
    assert rep["unused"] == ref["unused"]


@pytest.mark.slow
def test_elastic_autoscale_spawn_fault_aborts_typed(elastic_corpus, tmp_path_factory):
    """autoscale.spawn armed in every launcher: the first planned scale
    event's actuation fails — every member must exit with a TYPED error
    code in bounded time (no hang, no report), epoch checkpoint intact."""
    td = tmp_path_factory.mktemp("as_elastic_fault")
    _td, prefix, shards = elastic_corpus
    outs, eldir = _spawn_autoscale_launchers(
        td, prefix, shards, n=3,
        flags=["--autoscale-min", "2", "--autoscale-max", "3",
               "--autoscale-initial", "2", "--autoscale-budget", "2",
               "--autoscale-plan", "out@2", "--autoscale-poll", "0.1"],
        pace="0.4", fault_plan="autoscale.spawn@1",
    )
    # the members that processed the scale retirement hit the injected
    # actuation failure: the documented typed-abort exit (1, the
    # catch-all AnalysisError class InjectedFault maps to), with the
    # typed message on stderr — never a raw traceback or a hang
    rcs = sorted(rc for rc, _o, _e in outs)
    assert any(rc == 1 for rc in rcs), rcs
    assert all(rc in (0, 1, 6, 7) for rc in rcs), rcs
    assert not os.path.exists(td / "rep0.json"), "no report after abort"
    for rc, _out, err in outs:
        if rc == 1:
            assert "injected" in err.lower(), err[-1500:]
            assert "Traceback" not in err, err[-1500:]
    # the epoch checkpoint the abort left behind still loads cleanly
    from ruleset_analysis_tpu.runtime import checkpoint as ckpt

    snap = ckpt.load(os.path.join(eldir, "epoch"))
    assert snap is not None and snap.extra.get("elastic", {}).get("cursors")


@pytest.mark.slow
def test_elastic_autoscale_scale_plus_death_interleaving(elastic_corpus, tmp_path_factory):
    """A planned scale-out races a real node death (the re-formation
    interleaving): survivors still finish with a bit-identical report,
    both the scale event and the failure recovery accounted."""
    td = tmp_path_factory.mktemp("as_elastic_mix")
    _td, prefix, shards = elastic_corpus
    env_fault = "tag=3,after_batches=6"
    env = _launcher_env(2)
    env["RA_ELASTIC_PACE"] = "0.4"
    env["RA_ELASTIC_FAULT"] = env_fault
    eldir = str(td / "eldir")
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "ruleset_analysis_tpu.cli", "run",
             "--ruleset", prefix, "--logs", *shards, "--backend", "tpu",
             "--distributed", "--elastic", "--elastic-dir", eldir,
             "--num-processes", "4", "--process-id", str(pid),
             "--batch-size", "64", "--checkpoint-every", "2",
             "--max-reforms", "2",
             "--autoscale", "--autoscale-min", "2", "--autoscale-max", "4",
             "--autoscale-initial", "3", "--autoscale-budget", "2",
             "--autoscale-plan", "out@2", "--autoscale-poll", "0.1",
             "--json", "--out", str(td / f"rep{pid}.json")],
            env=env, cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for pid in range(4)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=400)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError("scale+death drill HUNG")
        outs.append((p.returncode, out, err))

    from ruleset_analysis_tpu.runtime.elastic import DIE_RC

    # tag 3 dies by injection (it may have been promoted into the world
    # by the scale-out, or die as a standby — both legal interleavings);
    # everyone else must complete
    for pid, (rc, _out, err) in enumerate(outs):
        if pid == 3:
            assert rc in (DIE_RC, 0), f"victim rc={rc}\n{err[-2000:]}"
        else:
            assert rc == 0, f"survivor {pid} rc={rc}\n{err[-3000:]}"
    rep = json.load(open(td / "rep0.json"))
    ref = _reference_report(prefix, shards)
    hits = lambda r: {  # noqa: E731
        (e["firewall"], e["acl"], e["index"]): e["hits"] for e in r["per_rule"]
    }
    assert hits(rep) == hits(ref)
    assert rep["unused"] == ref["unused"]
    assert rep["totals"]["lines_total"] == 1600
    asum = rep["totals"]["autoscale"]
    assert asum["scale_events"] >= 1


# ---------------------------------------------------------------------------
# CLI flag surface
# ---------------------------------------------------------------------------


def test_cli_autoscale_flag_validation():
    from ruleset_analysis_tpu import cli

    parser = cli.make_parser()
    # knobs without --autoscale are a usage error
    args = parser.parse_args(
        ["run", "--ruleset", "r", "--logs", "l", "--autoscale-min", "2"]
    )
    with pytest.raises(AnalysisError, match="--autoscale"):
        cli._autoscale_config(args)
    # armed: the flag family maps onto the frozen config
    args = parser.parse_args(
        ["run", "--ruleset", "r", "--logs", "l", "--autoscale",
         "--autoscale-min", "2", "--autoscale-max", "8",
         "--autoscale-plan", "out@1"]
    )
    a = cli._autoscale_config(args)
    assert (a.min_world, a.max_world, a.plan) == (2, 8, "out@1")
    assert cli._autoscale_config(
        parser.parse_args(["run", "--ruleset", "r", "--logs", "l"])
    ) is None
