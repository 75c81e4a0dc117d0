"""Always-on flight recorder + crash forensics + latency SLO plane.

Pins the ISSUE 15 contract (DESIGN §20):

- **Dump-on-abort**: a chaos-killed run with NO trace/metrics flags
  armed leaves a complete, parseable ``postmortem.json`` naming the
  fired fault site and the failing stage; ``doctor`` turns it into a
  ranked diagnosis; a CLEAN exit leaves nothing behind.
- **Crash merge**: an injected worker kill (``feeder.worker.crash`` =
  the SIGKILL/OOM analog, ``os._exit`` with no teardown) yields a
  merged bundle from the surviving processes — the dying worker's ring
  dumps at the fault site, the survivors seal at teardown
  (``worker-exit``), and the supervising CLI merges them all.
- **Triggers**: every registered dump trigger — ``abort``, ``stall``,
  ``unhandled``, ``signal``, ``crash``, ``worker-exit`` — is exercised
  here (the registry auditor fails ``make lint`` if one loses its
  test).
- **Latency SLO**: the log2-bucket histograms are mergeable by
  addition, quantiles are conservative, and serve ``/metrics`` renders
  the SAME histogram as JSON p50/p90/p99 gauges and as a well-formed
  Prometheus histogram whose bucket-derived p99 equals the JSON gauge.
"""

import json
import os
import signal
import time
import urllib.request

import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu import cli
from ruleset_analysis_tpu.config import AnalysisConfig, ServeConfig
from ruleset_analysis_tpu.errors import AnalysisError, StallError
from ruleset_analysis_tpu.hostside import aclparse, fastparse, pack, synth
from ruleset_analysis_tpu.runtime import flightrec, obs
from ruleset_analysis_tpu.runtime.flightrec import (
    TRIGGERS, FlightRing, classify, diagnose, load_bundle, stage_occupancy,
)
from ruleset_analysis_tpu.runtime.metrics import (
    LATENCY_BUCKET_BOUNDS, LatencyHistogram, quantile_from_prom,
)

from test_serve import finish, get_json, start_serve, wait_for


@pytest.fixture(autouse=True)
def _reset_recorder():
    """Every test starts and ends disarmed (module state is global)."""
    flightrec._reset_for_tests()
    yield
    flightrec._reset_for_tests()


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Small packed ruleset + syslog corpus shared by every CLI run here
    (ONE geometry -> one specialized-step compile for the whole module)."""
    td = tmp_path_factory.mktemp("flightrec")
    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=8, seed=3)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    prefix = str(td / "rules")
    pack.save_packed(packed, prefix)
    t = synth.synth_tuples(packed, 2000, seed=3)
    lines = synth.render_syslog(packed, t, seed=3)
    log = str(td / "fw1.log")
    with open(log, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    return packed, prefix, log, lines


#: one geometry for every CLI run in this module (memoized step builders
#: make the second and later runs compile-free)
RUN_FLAGS = [
    "--batch-size", "256", "--cms-width", "4096", "--cms-depth", "2",
    "--hll-p", "6",
]


def run_cli(prefix, log, bb_dir, *extra):
    return cli.main([
        "run", "--ruleset", prefix, "--logs", log, *RUN_FLAGS,
        "--blackbox-dir", str(bb_dir), "--json",
        "--out", os.devnull, *extra,
    ])


# ---------------------------------------------------------------------------
# Ring + histogram units (no device work).
# ---------------------------------------------------------------------------


def test_ring_overwrites_in_place_oldest_first():
    r = FlightRing(capacity=8)
    for i in range(20):
        r.append({"i": i})
    assert r.total == 20 and r.capacity == 8
    got = [e["i"] for e in r.events()]
    assert got == list(range(12, 20))  # last 8, oldest first


def test_ring_refuses_tiny_capacity():
    with pytest.raises(AnalysisError):
        FlightRing(capacity=2)


def test_latency_histogram_buckets_and_conservative_quantiles():
    h = LatencyHistogram()
    # bucket upper bounds: quantiles never under-report
    h.record(0.9e-6)  # -> 1us bucket
    h.record(3e-6)    # -> 4us bucket
    for _ in range(98):
        h.record(100e-6)  # -> 128us bucket
    s = h.summary()
    assert s["count"] == 100
    assert s["p50_sec"] == 128e-6 and s["p99_sec"] == 128e-6
    assert h.quantile(0.001) == 1e-6
    # overflow clamps to the largest finite bound
    h2 = LatencyHistogram()
    h2.record(LATENCY_BUCKET_BOUNDS[-1] * 4)
    assert h2.counts[-1] == 1
    assert h2.quantile(0.99) == LATENCY_BUCKET_BOUNDS[-1]


def test_latency_histogram_merge_is_addition():
    a, b, both = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
    for us, n in ((5, 3), (700, 2), (90_000, 1)):
        a.record(us * 1e-6, n=n)
        both.record(us * 1e-6, n=n)
    for us, n in ((12, 4), (700, 5)):
        b.record(us * 1e-6, n=n)
        both.record(us * 1e-6, n=n)
    a.merge(b)
    assert a.counts == both.counts
    assert a.count == both.count == 15
    assert a.summary() == both.summary()


def test_latency_histogram_prom_rendering_matches_json():
    h = LatencyHistogram()
    for us in (3, 40, 40, 500, 2_000, 2_000, 70_000, 900_000):
        h.record(us * 1e-6)
    prom = h.render_prom("ra_t_seconds")
    # well-formed: TYPE line, cumulative non-decreasing buckets, +Inf
    assert prom.startswith("# TYPE ra_t_seconds histogram")
    cums = [
        int(line.rsplit(" ", 1)[1])
        for line in prom.splitlines()
        if line.startswith("ra_t_seconds_bucket")
    ]
    assert all(b >= a for a, b in zip(cums, cums[1:]))
    assert cums[-1] == h.count
    assert f"ra_t_seconds_count {h.count}" in prom
    for p in (0.5, 0.9, 0.99):
        assert quantile_from_prom(prom, "ra_t_seconds", p) == h.quantile(p)


# ---------------------------------------------------------------------------
# The obs tap: events reach the ring with the trace plane DISARMED.
# ---------------------------------------------------------------------------


def test_obs_tap_records_into_ring_without_trace(tmp_path):
    assert obs.active_tracer() is None
    rec = flightrec.arm(str(tmp_path / "bb"), role="main")
    t0 = time.perf_counter()
    obs.complete("step.dispatch", t0, time.perf_counter(), args={"kind": "v4"})
    obs.instant("fault.test", args={"hit": 1})
    with obs.span("ingest.produce", n_raw=7):
        pass
    names = [e["name"] for e in rec.ring.events()]
    assert names == ["step.dispatch", "fault.test", "ingest.produce"]
    # nothing touched disk: the ring only lands at a dump trigger
    assert not os.path.exists(str(tmp_path / "bb"))


def test_dump_and_merge_shard_roundtrip(tmp_path):
    d = str(tmp_path / "bb")
    flightrec.arm(d, role="main")
    obs.instant("fault.stream.device_put.fail", args={"hit": 1})
    with obs.span("ingest.backpressure"):
        time.sleep(0.002)
    flightrec.cursor(committed_batches=5, wal_seq=17)
    shard_path = flightrec.dump("abort", error=AnalysisError("x"), exit_code=1)
    assert shard_path and os.path.exists(shard_path)
    pm = flightrec.merge(d, trigger="abort", error=AnalysisError("x"), exit_code=1)
    bundle = load_bundle(d)  # a dir holding postmortem.json also loads
    assert bundle["kind"] == "ra-postmortem" and pm.endswith("postmortem.json")
    (shard,) = bundle["shards"]
    assert shard["cursors"] == {"committed_batches": 5, "wal_seq": 17}
    a = bundle["analysis"]
    assert a["fault_sites_fired"] == {"stream.device_put.fail": 1}
    assert a["failing_stage"] == "ingest.backpressure"
    occ = a["per_shard"][0]["stage_occupancy_pct"]
    assert occ.get("ingest.backpressure", 0) > 0
    # unregistered triggers refuse loudly
    with pytest.raises(AnalysisError):
        flightrec.dump("not-a-trigger")


def test_classifier_covers_registered_triggers():
    assert classify(StallError("x")) == "stall"
    assert classify(AnalysisError("x")) == "abort"
    assert classify(ValueError("x")) == "unhandled"
    for t in ("stall", "abort", "unhandled", "signal", "crash", "worker-exit"):
        assert t in TRIGGERS


def test_sigquit_dumps_a_live_snapshot(tmp_path):
    d = str(tmp_path / "bb")
    flightrec.arm(d, role="main")
    obs.instant("checkpoint.commit", args={"chunk": 3})
    os.kill(os.getpid(), signal.SIGQUIT)
    # CPython delivers the signal on the main thread at the next bytecode
    wait_for(
        lambda: os.path.exists(os.path.join(d, "postmortem.json")),
        timeout=10, msg="SIGQUIT postmortem",
    )
    bundle = load_bundle(d)
    assert bundle["trigger"] == "signal"
    assert bundle["shards"][0]["trigger"] == "signal"
    # the operator snapshot did NOT stop the process (we are still here)
    # and a later clean finalize keeps the signal-dumped evidence
    assert flightrec.finalize() is not None


def test_sigquit_while_main_thread_holds_ring_lock(tmp_path):
    # the handler fires ON the main thread; the snapshot must not run
    # inline there or it deadlocks on the very locks the interrupted
    # frame holds (ring/cursor/sampler critical sections)
    d = str(tmp_path / "bb")
    rec = flightrec.arm(d, role="main")
    with rec.ring._lock:
        os.kill(os.getpid(), signal.SIGQUIT)
        time.sleep(0.3)  # signal delivers here, inside the critical section
    wait_for(
        lambda: os.path.exists(os.path.join(d, "postmortem.json")),
        timeout=10, msg="SIGQUIT snapshot under held lock",
    )
    assert load_bundle(d)["trigger"] == "signal"


def test_keyboard_interrupt_is_teardown_not_a_crash(tmp_path):
    # operator Ctrl-C of an armed run must not leave forensics claiming
    # an unhandled crash for the doctor to misdiagnose
    d = str(tmp_path / "bb")
    flightrec.arm(d, role="main")
    try:
        raise KeyboardInterrupt()
    except KeyboardInterrupt:
        out = flightrec.finalize()
    assert out is None
    assert not (os.path.isdir(d) and os.listdir(d))


def test_noted_abort_merges_presealed_worker_shards(tmp_path):
    # the elastic supervisor catches typed aborts and returns an exit
    # code; its note_abort must make finalize MERGE the generation
    # workers' sealed shards instead of pruning them as a clean exit
    d = str(tmp_path / "bb")
    flightrec.arm(d, role="elastic-supervisor")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "blackbox-9999.json"), "w") as f:
        json.dump({
            "kind": "ra-blackbox-shard", "role": "elastic-worker",
            "pid": 9999, "trigger": "worker-exit", "ring_events": [],
            "cursors": {},
        }, f)
    flightrec.note_abort(AnalysisError("autoscale fault"), 2)
    pm = flightrec.finalize()
    assert pm is not None
    roles = {s.get("role") for s in load_bundle(d)["shards"]}
    assert roles == {"elastic-worker", "elastic-supervisor"}


def test_rearm_same_dir_forgets_previous_runs_failure(tmp_path):
    # two runs in one process sharing a blackbox dir (the default-path
    # case): run 1's noted abort must not leak a postmortem out of run
    # 2's clean finalize
    d = str(tmp_path / "bb")
    flightrec.arm(d, role="main")
    flightrec.note_abort(AnalysisError("run-1 failure"), 1)
    flightrec.arm(d, role="main")  # same dir -> idempotent early return
    assert flightrec.finalize() is None
    assert not (os.path.isdir(d) and os.listdir(d))


def test_stage_occupancy_empty_and_instant_only():
    assert stage_occupancy([]) == {}
    assert stage_occupancy([{"ph": "i", "name": "x", "ts": 1}]) == {}


# ---------------------------------------------------------------------------
# CLI end-to-end: abort -> bundle -> doctor; clean -> nothing.
# ---------------------------------------------------------------------------


def test_chaos_abort_leaves_parseable_postmortem_and_doctor_names_site(
    corpus, tmp_path, capsys
):
    _, prefix, log, _ = corpus
    bb = tmp_path / "bb"
    rc = run_cli(
        prefix, log, bb, "--fault-plan", "ingest.producer.raise@2",
    )
    assert rc == 1  # typed InjectedFault abort
    bundle = load_bundle(str(bb))
    assert bundle["exit_code"] == 1 and bundle["trigger"] == "abort"
    assert bundle["error_type"] == "InjectedFault"
    a = bundle["analysis"]
    assert a["fault_sites_fired"] == {"ingest.producer.raise": 1}
    assert a["failing_stage"]  # a concrete stage, not None
    # ring carried real pipeline spans from the UNARMED trace plane
    names = {e["name"] for s in bundle["shards"] for e in s["ring_events"]}
    assert "step.dispatch" in names and "ingest.produce" in names
    # doctor: ranked diagnosis names the injected site
    capsys.readouterr()
    assert cli.main(["doctor", str(bb)]) == 0
    text = capsys.readouterr().out
    assert "ingest.producer.raise" in text and "INJECTED" in text
    diags = diagnose(bundle)
    assert diags[0]["cause"] == "an armed fault plan fired"


def test_clean_exit_leaves_no_forensics(corpus, tmp_path):
    _, prefix, log, _ = corpus
    bb = tmp_path / "bb-clean"
    assert run_cli(prefix, log, bb) == 0
    # no shards, no postmortem — an unarmed-trace clean run is untouched
    assert not os.path.exists(str(bb)) or not os.listdir(str(bb))


def test_stall_bundle_attributes_the_starved_side(corpus, tmp_path, capsys):
    _, prefix, log, _ = corpus
    bb = tmp_path / "bb-stall"
    rc = run_cli(
        prefix, log, bb,
        "--fault-plan", "ingest.queue.stall@2", "--stall-timeout", "3",
    )
    assert rc == 6  # StallError: watchdog bounded the wedged producer
    bundle = load_bundle(str(bb))
    assert bundle["trigger"] == "stall"
    capsys.readouterr()
    assert cli.main(["doctor", str(bb)]) == 0
    text = capsys.readouterr().out
    assert "STARVED" in text or "stall" in text.lower()


@pytest.mark.skipif(not fastparse.available(), reason="needs native parser")
def test_worker_kill_merges_bundle_from_surviving_processes(
    corpus, tmp_path
):
    _, prefix, log, _ = corpus
    bb = tmp_path / "bb-kill"
    rc = run_cli(
        prefix, log, bb,
        "--feed-workers", "2", "--native-parse",
        "--fault-plan", "feeder.worker.crash@2",
    )
    assert rc == 5  # FeedWorkerError: the coordinator saw the dead worker
    bundle = load_bundle(str(bb))
    shards = bundle["shards"]
    roles = {s["role"] for s in shards}
    triggers = {s["trigger"] for s in shards}
    # the supervising process dumped on the typed abort...
    assert "main" in roles and "abort" in triggers
    # ...and the dying worker's ring dumped AT the crash site (os._exit
    # runs no teardown — the "crash" trigger is its only exit path), so
    # the merged bundle spans more than one process
    assert len(shards) >= 2
    assert "crash" in triggers
    # hit counters are per process: every worker reaching its Nth task
    # crashes, and every crash dump carries its own fault instant
    assert bundle["analysis"]["fault_sites_fired"].get(
        "feeder.worker.crash", 0
    ) >= 1
    diags = diagnose(bundle, exit_code=5)
    assert any("feed tier" in d["cause"] for d in diags)


# ---------------------------------------------------------------------------
# Serve: end-to-end latency SLO histograms on /metrics (JSON + prom).
# ---------------------------------------------------------------------------


def test_serve_latency_histograms_json_and_prom_agree(corpus, tmp_path):
    _, prefix, _, lines = corpus
    spool = tmp_path / "spool.log"
    spool.write_text("\n".join(lines[:200]) + "\n", encoding="utf-8")
    scfg = ServeConfig(
        listen=(f"tail0:{spool}",),
        window_lines=100,
        ring=4,
        serve_dir=str(tmp_path / "serve"),
        checkpoint_every_windows=0,
        reload_watch=False,
        stop_after_sec=120,
    )
    cfg = AnalysisConfig(batch_size=128, prefetch_depth=0)
    drv, th, out = start_serve(prefix, cfg, scfg)
    try:
        wait_for(
            lambda: drv.windows_published >= 2 or "error" in out,
            timeout=90, msg="two windows",
        )
        assert "error" not in out, out.get("error")
        http = drv.http_address
        # JSON gauges: the SLO percentiles
        m = get_json(http, "/metrics")
        assert m["latency_ingest_to_publish_count"] >= 200
        p99_json = m["latency_ingest_to_publish_p99_sec"]
        assert p99_json > 0
        # prom: a well-formed HISTOGRAM whose bucket-derived p99 matches
        host, port = http
        with urllib.request.urlopen(
            f"http://{host}:{port}/metrics?format=prom", timeout=10
        ) as r:
            prom = r.read().decode()
        name = "ra_serve_ingest_to_publish_seconds"
        assert f"# TYPE {name} histogram" in prom
        assert f'{name}_bucket{{le="+Inf"}}' in prom
        assert quantile_from_prom(prom, name, 0.99) == p99_json
        # the prom gauge rendering carries the same percentile gauges
        assert "ra_serve_latency_ingest_to_publish_p99_sec" in prom
        # window report: totals.latency.ingest_to_publish
        rep = get_json(http, "/report")
        lat = rep["totals"]["latency"]["ingest_to_publish"]
        assert lat["count"] >= 100 and lat["p99_sec"] > 0
        cum = get_json(http, "/report/cumulative")
        assert cum["totals"]["latency"]["ingest_to_publish"]["count"] >= 200
    finally:
        drv.stop()
    finish(th, out)


def test_ingest_latency_rides_metrics_sampler(corpus):
    """The batch-e2e histogram lands in the ingest sampler gauges."""
    from ruleset_analysis_tpu.runtime.ingest import PrefetchingSource
    from ruleset_analysis_tpu.runtime.stream import run_stream

    from ruleset_analysis_tpu.config import SketchConfig

    packed, _, _, lines = corpus
    # the module's ONE CLI geometry: the step compile is already cached
    cfg = AnalysisConfig(
        backend="tpu", batch_size=256, prefetch_depth=2,
        sketch=SketchConfig(cms_width=4096, cms_depth=2, hll_p=6),
    )
    src_holder = {}
    orig_init = PrefetchingSource.__init__

    def spy_init(self, *a, **kw):
        orig_init(self, *a, **kw)
        src_holder["src"] = self

    PrefetchingSource.__init__ = spy_init
    try:
        run_stream(packed, iter(lines[:600]), cfg)
    finally:
        PrefetchingSource.__init__ = orig_init
    src = src_holder["src"]
    assert src.latency.count >= 1
    gauges = src._sample_metrics()
    assert "latency_batch_e2e_p99_sec" in gauges
    assert src.latency_summary()["batch_e2e"]["count"] == src.latency.count


# ---------------------------------------------------------------------------
# trace_summary + registry audit.
# ---------------------------------------------------------------------------


def test_trace_summary_renders_blackbox_block(tmp_path):
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
    )
    import trace_summary

    d = str(tmp_path / "bb")
    flightrec.arm(d, role="main")
    with obs.span("ingest.produce", n_raw=3):
        time.sleep(0.001)
    obs.instant("fault.listener.drop", args={"hit": 1})
    flightrec.cursor(window=4)
    flightrec.dump("stall", error=StallError("wedged"), exit_code=6)
    pm = flightrec.merge(d, trigger="stall", error=StallError("wedged"),
                         exit_code=6)
    s = trace_summary.summarize(pm)
    bb = s["blackbox"]
    assert bb["trigger"] == "stall" and bb["exit_code"] == 6
    assert bb["fault_sites_fired"] == {"listener.drop": 1}
    assert bb["shards"][0]["cursors"] == {"window": 4}
    assert bb["shards"][0]["stage_occupancy_pct"].get("ingest.produce", 0) > 0
    text = trace_summary.render(s)
    assert "blackbox:" in text and "cursors: window=4" in text


def test_observability_registry_audit_is_clean():
    from ruleset_analysis_tpu.verify.registry import audit_observability

    findings = audit_observability()
    assert findings == [], [f"{f.kind}:{f.subject}" for f in findings]
