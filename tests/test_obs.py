"""Observability plane: tracing + metrics under normal and chaos runs.

Three tiers of assertion (ISSUE 4 acceptance):

- **plumbing units** — disarmed no-ops, env-inherited lazy arming, merge
  tolerance of torn shards, Profiler/RecoveryMeter/ThroughputMeter
  satellite behavior;
- **end-to-end trace shape** — a prefetch + multi-worker-feeder run
  through the production CLI produces ONE merged Chrome trace holding
  spans from the main process, the prefetch producer thread, and a
  spawned feeder worker process, plus instant events where armed fault
  sites fired;
- **chaos x observability** — for seeded fault schedules the merged
  trace and the metrics JSONL stay well-formed (parseable, monotonic
  timestamps, no orphan open spans) even when the run ends in a typed
  abort.
"""

import json
import os
import random
import sys

import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.errors import AnalysisError
from ruleset_analysis_tpu.hostside import aclparse, fastparse, pack, synth
from ruleset_analysis_tpu.hostside import wire as wire_mod
from ruleset_analysis_tpu.runtime import faults, obs
from ruleset_analysis_tpu.runtime.stream import run_stream_file, run_stream_wire

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"),
)
import trace_summary  # noqa: E402


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends fully disarmed (env check included)."""
    obs._reset_for_tests()
    yield
    obs._reset_for_tests()


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("obs")
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=8, seed=7)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 2400, seed=8)
    lines = synth.render_syslog(packed, tuples, seed=9)
    log = str(td / "obs.log")
    with open(log, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    wirep = str(td / "obs.rawire")
    wire_mod.convert_logs(packed, [log], wirep, block_rows=512)
    prefix = str(td / "packed")
    pack.save_packed(packed, prefix)
    return packed, prefix, log, wirep


def _cfg(depth=2, cadence=0, ckpt_dir="", **kw):
    return AnalysisConfig(
        batch_size=512,
        sketch=SketchConfig(cms_width=1 << 10, cms_depth=2, hll_p=6),
        prefetch_depth=depth,
        checkpoint_every_chunks=cadence,
        **({"checkpoint_dir": ckpt_dir} if ckpt_dir else {}),
        stall_timeout_sec=3.0,
        **kw,
    )


def _load_trace(path: str) -> list[dict]:
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    return data["traceEvents"]


def _assert_well_formed(events: list[dict]) -> None:
    """The merge contract: parseable, sane stamps, no orphan open spans."""
    assert events, "merged trace is empty"
    for e in events:
        assert e["ph"] in ("X", "i", "M"), f"unexpected phase {e}"
        if e["ph"] == "X":
            assert e["ts"] > 0 and e["dur"] >= 0
    # the recorder only ever writes complete spans, so duration-style
    # begin/end events (which CAN orphan) must never appear
    assert not [e for e in events if e.get("ph") in ("B", "E")]
    # per-track monotonicity: the merge sorts by ts, so each (pid, tid)
    # track must read in nondecreasing time order
    last: dict = {}
    for e in events:
        if e["ph"] != "X":
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last.get(key, 0), f"time went backwards on {key}"
        last[key] = e["ts"]


def _assert_metrics_well_formed(path: str) -> list[dict]:
    recs = []
    with open(path, "r", encoding="utf-8") as f:
        for ln in f:
            ln = ln.strip()
            if ln:
                recs.append(json.loads(ln))
    assert recs, "metrics file is empty"
    stamps = [r["t"] for r in recs]
    assert stamps == sorted(stamps), "metrics timestamps not monotonic"
    finals = [r for r in recs if r.get("kind") == "final"]
    assert finals, "shutdown never wrote the final snapshot"
    assert all("lines" in r for r in recs if r["kind"] in ("snapshot", "final"))
    return recs


# ---------------------------------------------------------------------------
# Plumbing units
# ---------------------------------------------------------------------------


def test_disarmed_everything_is_noop(tmp_path):
    assert obs.active_tracer() is None
    obs.complete("x", 0.0, 1.0)
    obs.instant("x")
    obs.add_lines(10)
    obs.metric_event("x", a=1)
    obs.register_sampler("x", lambda: {})
    with obs.span("x"):
        pass
    assert obs.shutdown() is None
    assert not list(tmp_path.iterdir())


def test_trace_env_export_and_lazy_child_arm(tmp_path):
    d = str(tmp_path / "tr")
    obs.start_trace(d, role="main")
    assert os.environ[obs.ENV_VAR] == os.path.abspath(d)
    obs.complete("unit.span", 0.0, 0.001)
    merged = obs.shutdown()
    assert os.environ.get(obs.ENV_VAR) is None
    # simulate a freshly spawned child: module disarmed, env present
    obs._reset_for_tests()
    os.environ[obs.ENV_VAR] = os.path.abspath(d)
    try:
        obs.instant("child.mark")
        assert obs.active_tracer() is not None
    finally:
        obs.shutdown(merge=False)
        os.environ.pop(obs.ENV_VAR, None)
    merged = obs.merge_trace(d)
    names = [e["name"] for e in _load_trace(merged)]
    assert "unit.span" in names and "child.mark" in names


def test_owner_arm_prunes_previous_runs_shards(tmp_path):
    """Re-using a --trace-out dir must not merge last run's events in.

    Covers the immediate abort-and-retry loop: a dead writer's shard is
    pruned even with a FRESH mtime (liveness probe on the PID in the
    shard name), alongside an old-mtime shard and the stale merged
    file.  A live sibling rank's shard must survive.
    """
    d = str(tmp_path)
    # a crashed previous run, seconds ago: writer pid is dead, mtime fresh
    import subprocess

    proc = subprocess.Popen(["true"])
    proc.wait()
    dead_pid = proc.pid
    (tmp_path / f"trace-{dead_pid}.jsonl").write_text(
        f'{{"ph":"X","name":"crashed.run","pid":{dead_pid},"tid":1,"ts":5,"dur":1}}\n'
    )
    # an hour-old leftover (recycled-PID backstop clause)
    stale = tmp_path / "trace-99999.jsonl"
    stale.write_text(
        '{"ph":"X","name":"old.run","pid":99999,"tid":1,"ts":5,"dur":1}\n'
    )
    old = os.path.getmtime(stale) - 2 * obs.STALE_SHARD_SEC
    os.utime(stale, (old, old))
    (tmp_path / "trace.json").write_text("{}")
    # a LIVE sibling rank's shard must survive the prune; pid 1 (init:
    # alive, not ours — the probe's PermissionError branch) stands in
    live = tmp_path / "trace-1.jsonl"
    live.write_text(
        '{"ph":"X","name":"sibling.rank","pid":1,"tid":1,"ts":9,"dur":1}\n'
    )
    obs.start_trace(d, role="main")
    obs.complete("new.span", 0.0, 0.001)
    merged = obs.shutdown()
    names = {e["name"] for e in _load_trace(merged) if e["ph"] == "X"}
    assert names == {"new.span", "sibling.rank"}, (
        "prune kept a dead run's shard or dropped a live sibling's"
    )


def test_cli_unwritable_trace_out_is_usage_error(corpus, tmp_path):
    from ruleset_analysis_tpu import cli

    _packed, prefix, log, _wirep = corpus
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")  # a FILE where a directory is required
    rc = cli.main([
        "run", "--ruleset", prefix, "--logs", log,
        "--trace-out", str(blocker / "sub"),
    ])
    assert rc == 2  # typed usage error, not a raw traceback


def test_merge_skips_torn_shard_tail(tmp_path):
    d = str(tmp_path)
    tr = obs.start_trace(d, export_env=False)
    obs.complete("good.span", 0.0, 0.001)
    obs.shutdown(merge=False)
    # a worker killed mid-write leaves a torn final line in its shard
    with open(tr.path, "a", encoding="utf-8") as f:
        f.write('{"ph":"X","name":"torn...')
    events = _load_trace(obs.merge_trace(d))
    assert [e["name"] for e in events if e["ph"] == "X"] == ["good.span"]


def test_metrics_snapshot_and_samplers(tmp_path):
    path = str(tmp_path / "m.jsonl")
    obs.start_metrics(path, every_sec=60.0)  # snapshots forced, not timed
    obs.add_lines(1000)
    obs.register_sampler("gauge", lambda: {"depth": 3})
    obs.register_sampler("broken", lambda: 1 / 0)  # must not kill the run
    rec = obs.metrics_snapshot()
    assert rec["lines"] == 1000 and rec["gauge"] == {"depth": 3}
    assert "broken" not in rec
    assert rec["rss_bytes"] > 0
    obs.metric_event("checkpoint", bytes=123)
    obs.shutdown()
    recs = _assert_metrics_well_formed(path)
    kinds = [r["kind"] for r in recs]
    assert "checkpoint" in kinds and "final" in kinds


def test_recovery_meter_out_of_order_recovered():
    """recovered() with no prior detect() must not depend on attribute
    luck (satellite: _reason now initialized in __init__)."""
    from ruleset_analysis_tpu.runtime.metrics import RecoveryMeter

    m = RecoveryMeter()
    m.recovered(world=3)  # no detect() first — the out-of-order path
    assert m.events[0]["reason"] == ""
    assert m.events[0]["time_to_recover_sec"] == 0.0
    s = m.summary()
    assert s["recovery_events"] == 1


def test_throughput_meter_feeds_metrics_and_summary(tmp_path, capsys):
    from ruleset_analysis_tpu.runtime.metrics import ThroughputMeter

    path = str(tmp_path / "m.jsonl")
    obs.start_metrics(path, every_sec=60.0)
    meter = ThroughputMeter(report_every_chunks=2)
    for _ in range(4):
        meter.tick(100)
    obs.shutdown()
    assert meter.lines == 400 and meter.chunks == 4
    assert meter.elapsed() > 0
    recs = _assert_metrics_well_formed(path)
    through = [r for r in recs if r["kind"] == "throughput"]
    assert len(through) == 2  # chunk 2 and chunk 4 report lines
    assert through[-1]["lines"] == 400
    final = [r for r in recs if r["kind"] == "final"][0]
    assert final["lines"] == 400  # tick() fed the cumulative counter


# ---------------------------------------------------------------------------
# Profiler hardening (satellite)
# ---------------------------------------------------------------------------


class _FakeProfiler:
    def __init__(self, fail_stop=False):
        self.starts = 0
        self.stops = 0
        self._fail_stop = fail_stop

    def start_trace(self, d):
        self.starts += 1

    def stop_trace(self):
        self.stops += 1
        if self._fail_stop:
            raise RuntimeError("profiler teardown broke")


def _patched_profiler(monkeypatch, fake):
    import jax

    monkeypatch.setattr(jax, "profiler", fake)


def test_profiler_noop_without_dir():
    from ruleset_analysis_tpu.runtime.metrics import Profiler

    with Profiler(None):
        pass  # no jax.profiler calls, no output, no error


def test_profiler_double_start_is_typed(monkeypatch, tmp_path):
    from ruleset_analysis_tpu.runtime.metrics import Profiler

    fake = _FakeProfiler()
    _patched_profiler(monkeypatch, fake)
    p = Profiler(str(tmp_path))
    with p:
        with pytest.raises(AnalysisError, match="already started"):
            p.__enter__()
    assert fake.starts == 1 and fake.stops == 1


def test_profiler_stops_trace_on_body_exception(monkeypatch, tmp_path, capsys):
    from ruleset_analysis_tpu.runtime.metrics import Profiler

    fake = _FakeProfiler()
    _patched_profiler(monkeypatch, fake)
    with pytest.raises(ValueError, match="body failed"):
        with Profiler(str(tmp_path), out=sys.stderr):
            raise ValueError("body failed")
    assert fake.stops == 1  # the trace ALWAYS stops
    assert "tensorboard" not in capsys.readouterr().err.lower()


def test_profiler_stop_failure_does_not_mask_body_error(monkeypatch, tmp_path):
    from ruleset_analysis_tpu.runtime.metrics import Profiler

    fake = _FakeProfiler(fail_stop=True)
    _patched_profiler(monkeypatch, fake)
    with pytest.raises(ValueError, match="the real error"):
        with Profiler(str(tmp_path)):
            raise ValueError("the real error")
    # ... but a clean-exit stop failure is real and propagates
    fake2 = _FakeProfiler(fail_stop=True)
    _patched_profiler(monkeypatch, fake2)
    with pytest.raises(RuntimeError, match="teardown broke"):
        with Profiler(str(tmp_path)):
            pass


def test_profiler_prints_tensorboard_hint_on_clean_exit(monkeypatch, tmp_path, capsys):
    from ruleset_analysis_tpu.runtime.metrics import Profiler

    _patched_profiler(monkeypatch, _FakeProfiler())
    with Profiler(str(tmp_path), out=sys.stderr):
        pass
    err = capsys.readouterr().err
    assert str(tmp_path) in err and "tensorboard" in err.lower()


# ---------------------------------------------------------------------------
# End-to-end trace shape (acceptance: main + producer thread + feeder
# worker in ONE merged trace; fault instants; trace_summary occupancy)
# ---------------------------------------------------------------------------


def test_report_totals_carry_throughput(corpus):
    packed, _prefix, log, _wirep = corpus
    rep = run_stream_file(packed, log, _cfg(), topk=5)
    t = rep.totals
    assert t["lines_total"] == 2400 and t["chunks"] >= 1
    assert t["elapsed_sec"] > 0
    assert t["lines_per_sec"] == pytest.approx(
        t["lines_total"] / t["elapsed_sec"], rel=1e-2
    )


@pytest.mark.skipif(
    not fastparse.available(), reason="native parser not buildable here"
)
def test_merged_trace_spans_main_producer_and_feeder_worker(corpus, tmp_path):
    """The acceptance artifact: one merged timeline, three span origins."""
    from ruleset_analysis_tpu import cli

    packed, prefix, log, _wirep = corpus
    td = str(tmp_path / "trace")
    mf = str(tmp_path / "metrics.jsonl")
    rc = cli.main([
        "run", "--ruleset", prefix, "--logs", log, "--batch-size", "256",
        "--feed-workers", "2", "--feed-mode", "process",
        "--prefetch-depth", "2", "--trace-out", td, "--metrics-out", mf,
        "--metrics-every", "0.2", "--json",
        "--out", str(tmp_path / "rep.json"),
    ])
    assert rc == 0
    merged = os.path.join(td, "trace.json")
    assert os.path.exists(merged), "CLI did not merge the trace at exit"
    events = _load_trace(merged)
    _assert_well_formed(events)
    main_pid = os.getpid()
    spans = [e for e in events if e["ph"] == "X"]
    # device dispatches happen on the main thread of the main process
    steps = [e for e in spans if e["name"] == "step.dispatch"]
    assert steps and all(e["pid"] == main_pid for e in steps)
    # the prefetch producer runs on a different thread of the SAME process
    produce = [e for e in spans if e["name"] == "ingest.produce"]
    assert produce and all(e["pid"] == main_pid for e in produce)
    assert {e["tid"] for e in produce} != {e["tid"] for e in steps}
    # spawned feeder workers write their own per-PID shards
    feed = [e for e in spans if e["name"] == "feeder.parse"]
    assert feed and all(e["pid"] != main_pid for e in feed)
    assert len({e["pid"] for e in feed}) >= 1
    # worker tracks carry their role label
    roles = [
        e["args"]["name"] for e in events
        if e["ph"] == "M" and e.get("name") == "process_name"
    ]
    assert any(r.startswith("feeder-worker") for r in roles)
    assert any(r.startswith("main") for r in roles)
    # metrics: well-formed, and the ingest queue gauges made it in
    recs = _assert_metrics_well_formed(mf)
    assert any("ingest" in r for r in recs)
    # trace_summary attributes per-stage occupancy from the same file
    s = trace_summary.summarize(merged)
    assert s["processes"] >= 2
    assert "step.dispatch" in s["stages"] and "feeder.parse" in s["stages"]
    assert all(st["occupancy_pct"] >= 0 for st in s["stages"].values())


def test_fault_instants_land_in_merged_trace(corpus, tmp_path):
    """An armed site's firing is an instant event, and the typed abort
    still produces a merged, well-formed trace (CLI finally path)."""
    from ruleset_analysis_tpu import cli

    packed, prefix, log, _wirep = corpus
    td = str(tmp_path / "trace")
    rc = cli.main([
        "run", "--ruleset", prefix, "--logs", log, "--batch-size", "256",
        "--prefetch-depth", "2", "--trace-out", td,
        "--fault-plan", "ingest.producer.raise@2", "--json",
        "--out", str(tmp_path / "rep.json"),
    ])
    assert rc != 0  # typed abort (InjectedFault -> IngestError class 5)
    events = _load_trace(os.path.join(td, "trace.json"))
    _assert_well_formed(events)
    fires = [e for e in events if e["name"] == "fault.ingest.producer.raise"]
    assert len(fires) == 1 and fires[0]["ph"] == "i"
    assert fires[0]["args"]["hit"] == 2


# ---------------------------------------------------------------------------
# Chaos x observability: seeded schedules with the plane armed
# ---------------------------------------------------------------------------


def _chaos_schedule(seed: int):
    rng = random.Random(seed)
    inp = rng.choice(["text", "wire"])
    sites = ["stream.device_put.fail", "checkpoint.torn_state",
             "checkpoint.torn_manifest", "ingest.producer.raise"]
    if inp == "wire":
        sites.append("stream.wire.corrupt")
    site = rng.choice(sites)
    cadence = 2 if site.startswith("checkpoint.") else rng.choice([0, 2])
    plan = faults.FaultPlan([faults.FaultSpec(site, rng.randint(1, 3))], seed=seed)
    return inp, cadence, plan


@pytest.mark.parametrize("seed", [201, 202, 203, 204, 205])
def test_chaos_trace_and_metrics_stay_well_formed(seed, corpus, tmp_path):
    """Seeded fault schedules with tracing + metrics armed: whether the
    run aborts typed or completes, the merged trace and metrics JSONL
    parse, stamps are monotonic, and no open span is orphaned."""
    packed, _prefix, log, wirep = corpus
    inp, cadence, plan = _chaos_schedule(seed)
    td = str(tmp_path / "trace")
    mf = str(tmp_path / "metrics.jsonl")
    cfg = _cfg(depth=2, cadence=cadence, ckpt_dir=str(tmp_path / "ck"))
    obs.start_trace(td, role="main")
    obs.start_metrics(mf, every_sec=0.2)
    aborted = False
    try:
        with faults.armed(plan):
            try:
                if inp == "wire":
                    run_stream_wire(packed, wirep, cfg, topk=5)
                else:
                    run_stream_file(packed, log, cfg, topk=5)
            except AnalysisError:
                aborted = True  # the allowed chaos outcome
    finally:
        merged = obs.shutdown()
    assert merged and os.path.exists(merged)
    events = _load_trace(merged)
    _assert_well_formed(events)
    if aborted:
        # the armed site fired: its instant must be on the timeline
        site = next(iter(plan.specs))
        assert any(
            e["name"] == f"fault.{site}" for e in events if e["ph"] == "i"
        ), f"seed {seed}: fired site left no instant"
    _assert_metrics_well_formed(mf)
    # and the merged artifact stays summarizable after any outcome
    s = trace_summary.summarize(merged)
    assert s["wall_sec"] >= 0 and isinstance(s["stages"], dict)
