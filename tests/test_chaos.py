"""Chaos harness: seeded fault schedules over the full stream pipeline.

The system invariant (ISSUE 3; DESIGN §9): under ANY armed fault
schedule, a run either produces a report BIT-IDENTICAL to the fault-free
baseline or exits with a typed ``AnalysisError`` subclass — never a hang
(every wait is watchdog-bounded), never a silent wrong answer, and never
a leaked thread, worker process, or temp/rendezvous file (the autouse
conftest fixture enforces the leak half after every test here).

Tier-1 runs 20 deterministic seeded schedules across layout x input x
sync/prefetch plus the feeder tiers; the ``slow``-marked soak adds 20
more seeds and the multi-process elastic scenarios (worker death,
heartbeat drop), and can emit a chaos-pass-rate artifact via
``metrics.RecoveryMeter`` (RA_CHAOS_ARTIFACT=path).
"""

import json
import os
import random
import time

import numpy as np
import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.errors import (
    AnalysisError,
    EXIT_CHECKPOINT_CORRUPT,
    EXIT_CHECKPOINT_MISMATCH,
    EXIT_FEED,
    EXIT_REFORM_BUDGET,
    EXIT_STALL,
    CheckpointCorrupt,
    CheckpointMismatch,
    FeedWorkerError,
    IngestError,
    InjectedFault,
    ReformBudgetExhausted,
    ResumeInputMismatch,
    StallError,
    WireCorrupt,
    exit_code_for,
)
from ruleset_analysis_tpu.hostside import aclparse, fastparse, pack, synth
from ruleset_analysis_tpu.hostside import wire as wire_mod
from ruleset_analysis_tpu.runtime import faults
from ruleset_analysis_tpu.runtime.stream import run_stream_file, run_stream_wire

# ONE volatile-keys list (runtime/report.py): the registry auditor
# (verify/registry.py) flags any module keeping a private copy.
from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS as VOLATILE

CFG6 = """\
hostname fw1
access-list A extended permit tcp any host 10.0.0.5 eq 443
access-list A extended permit tcp any6 2001:db8:1::/48 eq 443
access-list A extended permit udp 2001:db8:2::/64 any6 eq 53
access-list A extended deny tcp any6 host 2001:db8::bad
access-list A extended permit ip any any
access-list B extended permit tcp any6 any6 range 8000 8100
access-group A in interface outside
"""

#: fast watchdog bound so injected stalls abort in seconds, not minutes
STALL_SEC = 3.0


def report_image(rep) -> dict:
    j = rep if isinstance(rep, dict) else json.loads(rep.to_json())
    j = json.loads(json.dumps(j))
    for k in VOLATILE:
        j["totals"].pop(k, None)
    return j


def _mixed_lines(n, seed=0, v6_share=0.3):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        acl = "A" if rng.random() < 0.8 else "B"
        if rng.random() < v6_share:
            src = f"2001:db8:2::{rng.randrange(1, 40):x}"
            dst = f"2001:db8:{rng.randrange(0, 4):x}:1::{rng.randrange(1, 99):x}"
            proto = rng.choice(["tcp", "udp"])
        else:
            src = f"10.1.{rng.randrange(256)}.{rng.randrange(1, 255)}"
            dst = "10.0.0.5" if rng.random() < 0.5 else "10.9.9.9"
            proto = "tcp"
        out.append(
            f"Jul 29 07:48:{i % 60:02d} fw1 : %ASA-6-106100: access-list {acl} "
            f"permitted {proto} inside/{src}({rng.randrange(1024, 60000)}) -> "
            f"outside/{dst}({rng.choice([443, 53, 8050, 80])}) "
            f"hit-cnt 1 first hit [0x0, 0x0]"
        )
    return out


@pytest.fixture(scope="module")
def chaos_corpus(tmp_path_factory):
    """Mixed v4+v6 corpus, text + wire forms, shared across schedules."""
    td = tmp_path_factory.mktemp("chaos")
    rs = aclparse.parse_asa_config(CFG6, "fw1")
    packed = pack.pack_rulesets([rs])
    text = str(td / "mix.log")
    with open(text, "w", encoding="utf-8") as f:
        f.write("\n".join(_mixed_lines(2500, seed=11)) + "\n")
    wirep = str(td / "mix.rawire")
    wire_mod.convert_logs(packed, [text], wirep, batch_size=512, block_rows=512)
    return packed, text, wirep


@pytest.fixture(scope="module")
def baselines(chaos_corpus, tmp_path_factory):
    """Lazy fault-free reference images keyed (layout, input, cadence)."""
    cache: dict = {}
    td = tmp_path_factory.mktemp("chaos_base")

    def get(layout: str, inp: str, cadence: int) -> dict:
        key = (layout, inp, cadence)
        if key not in cache:
            packed, text, wirep = chaos_corpus
            ck = str(td / f"ck-{layout}-{inp}-{cadence}")
            cfg = _cfg(0, layout, cadence, ck)
            rep = (
                run_stream_wire(packed, wirep, cfg, topk=5)
                if inp == "wire"
                else run_stream_file(packed, text, cfg, topk=5)
            )
            cache[key] = report_image(rep)
        return cache[key]

    return get


def _cfg(depth: int, layout: str, cadence: int, ckpt_dir: str, resume=False,
         coalesce="off"):
    return AnalysisConfig(
        batch_size=512,
        sketch=SketchConfig(cms_width=1 << 10, cms_depth=2, hll_p=6),
        prefetch_depth=depth,
        layout=layout,
        checkpoint_every_chunks=cadence,
        checkpoint_dir=ckpt_dir,
        resume=resume,
        stall_timeout_sec=STALL_SEC,
        coalesce=coalesce,
    )


def schedule_for(seed: int):
    """Deterministic schedule from a seed: combo + one armed site.

    Replaying any failure needs only its seed number — the whole point
    of seeded chaos (DESIGN §9).
    """
    rng = random.Random(seed)
    layout = rng.choice(["flat", "stacked"])
    inp = rng.choice(["text", "wire"])
    depth = rng.choice([0, 2])
    # the coalesced path joins the matrix on flat layouts, where its
    # reports are unconditionally bit-identical to the off baseline
    # (stacked emission cadence shifts candidate pools — DESIGN §11;
    # its identity regime is pinned separately in test_coalesce.py)
    coalesce = rng.choice(["off", "on"]) if layout == "flat" else "off"
    sites = ["stream.device_put.fail", "checkpoint.torn_state",
             "checkpoint.torn_manifest"]
    if depth:
        sites += ["ingest.producer.raise", "ingest.queue.stall"]
    if inp == "wire":
        sites += ["stream.wire.corrupt"]
    if coalesce != "off":
        sites += ["ingest.coalesce.fail"]
    site = rng.choice(sites)
    cadence = 2 if site.startswith("checkpoint.") else rng.choice([0, 2])
    plan = faults.FaultPlan([faults.FaultSpec(site, rng.randint(1, 4))], seed=seed)
    return layout, inp, depth, cadence, coalesce, plan


def run_schedule(seed, chaos_corpus, baseline_of, tmp_path) -> bool:
    """One seeded schedule end to end; returns invariant-held verdict.

    Shared by the tier-1 parametrization and the slow soak (which
    aggregates verdicts into the chaos pass-rate artifact).
    """
    packed, text, wirep = chaos_corpus
    layout, inp, depth, cadence, coalesce, plan = schedule_for(seed)
    ck = str(tmp_path / f"ck-{seed}")
    cfg = _cfg(depth, layout, cadence, ck, coalesce=coalesce)

    def run(c):
        return (
            run_stream_wire(packed, wirep, c, topk=5)
            if inp == "wire"
            else run_stream_file(packed, text, c, topk=5)
        )

    # the baseline is always the coalesce-OFF fault-free run: a coalesced
    # schedule asserts BOTH halves at once — fault invariant and the
    # tentpole's bit-identical-report claim
    base = baseline_of(layout, inp, cadence)
    aborted = False
    with faults.armed(plan):
        try:
            rep = run(cfg)
        except AnalysisError:
            aborted = True  # typed abort: the allowed failure outcome
        else:
            # no abort: the schedule's hit count never fired (or the
            # fault landed somewhere recoverable) — the report must be
            # bit-identical to the fault-free baseline
            assert report_image(rep) == base, f"seed {seed} silently diverged"
    if aborted and cadence:
        # recovery half: whatever the fault tore mid-save, the pointer
        # protocol + CRCs must serve a consistent prior epoch and the
        # resumed run must land bit-identical to the fault-free baseline
        resumed = run(
            _cfg(depth, layout, cadence, ck, resume=True, coalesce=coalesce)
        )
        assert report_image(resumed) == base, f"seed {seed} bad recovery"
        leftovers = [
            e for e in os.listdir(ck)
            if e.startswith(".tmp-") or e.endswith(".ptr.tmp")
        ]
        assert not leftovers, f"seed {seed} leaked checkpoint temp files: {leftovers}"
    return True


@pytest.mark.parametrize("seed", range(20))
def test_chaos_schedule(seed, chaos_corpus, baselines, tmp_path):
    assert run_schedule(seed, chaos_corpus, baselines, tmp_path)


# ---------------------------------------------------------------------------
# Feeder-tier chaos (native parser; separate because the feed tiers are
# selected per run, not per config)
# ---------------------------------------------------------------------------


@pytest.mark.skipif(
    not fastparse.available(), reason="native parser not buildable here"
)
def test_chaos_feeder_thread_stall_bounded(chaos_corpus, tmp_path):
    """A wedged feed worker thread bounds to StallError, never a hang."""
    packed, text, _ = chaos_corpus
    cfg = _cfg(0, "flat", 0, str(tmp_path / "ck"))
    t0 = time.monotonic()
    with faults.armed(faults.FaultPlan.parse("feeder.worker.stall@2")):
        with pytest.raises(StallError):
            run_stream_file(
                packed, text, cfg, topk=5, feed_workers=2, feed_mode="thread"
            )
    assert time.monotonic() - t0 < 10 * STALL_SEC


@pytest.mark.skipif(
    not fastparse.available(), reason="native parser not buildable here"
)
def test_chaos_feeder_process_crash_typed(chaos_corpus, tmp_path):
    """An OOM-killed feed worker process surfaces as FeedWorkerError.

    The plan reaches the spawned worker through the RA_FAULT_PLAN env
    export — the same channel production chaos drills use."""
    packed, text, _ = chaos_corpus
    cfg = _cfg(0, "flat", 0, str(tmp_path / "ck"))
    with faults.armed(faults.FaultPlan.parse("feeder.worker.crash@2")):
        with pytest.raises((FeedWorkerError, StallError)):
            run_stream_file(
                packed, text, cfg, topk=5, feed_workers=2, feed_mode="process"
            )


@pytest.mark.skipif(
    not fastparse.available(), reason="native parser not buildable here"
)
def test_chaos_feeder_under_prefetch_typed(chaos_corpus, tmp_path):
    """Feeder fault below the prefetch wrapper: still typed, still no
    leak — the producer shutdown must close the inner feeder generator
    so its worker pool is torn down deterministically."""
    packed, text, _ = chaos_corpus
    cfg = _cfg(2, "flat", 0, str(tmp_path / "ck"))
    with faults.armed(faults.FaultPlan.parse("feeder.worker.stall@3")):
        with pytest.raises((StallError, FeedWorkerError, IngestError)):
            run_stream_file(
                packed, text, cfg, topk=5, feed_workers=2, feed_mode="thread"
            )


@pytest.mark.skipif(
    not fastparse.available(), reason="native parser not buildable here"
)
def test_chaos_ring_stall_bounded_sync(chaos_corpus, tmp_path):
    """feeder.ring.stall (ISSUE 11): a wedged per-chip ring producer
    starves exactly one chip; the coordinator's watchdog must bound it
    to a typed StallError naming the dry ring — never a hang."""
    packed, text, _ = chaos_corpus
    cfg = _cfg(0, "flat", 0, str(tmp_path / "ck"))
    t0 = time.monotonic()
    with faults.armed(faults.FaultPlan.parse("feeder.ring.stall@2")):
        with pytest.raises(StallError, match="rings dry"):
            run_stream_file(
                packed, text, cfg, topk=5, feed_workers=2, feed_mode="ring"
            )
    assert time.monotonic() - t0 < 10 * STALL_SEC


@pytest.mark.skipif(
    not fastparse.available(), reason="native parser not buildable here"
)
def test_chaos_ring_stall_under_prefetch_typed(chaos_corpus, tmp_path):
    """Ring stall below the prefetch wrapper (the production path: the
    ring coordinator runs inside the pump): still a typed abort, still
    no leaked ring workers or shared memory (the autouse leak fixture
    enforces the latter)."""
    packed, text, _ = chaos_corpus
    cfg = _cfg(2, "flat", 0, str(tmp_path / "ck"))
    with faults.armed(faults.FaultPlan.parse("feeder.ring.stall@3")):
        with pytest.raises((StallError, FeedWorkerError, IngestError)):
            run_stream_file(
                packed, text, cfg, topk=5, feed_workers=2, feed_mode="ring"
            )


@pytest.mark.skipif(
    not fastparse.available(), reason="native parser not buildable here"
)
def test_chaos_ring_worker_crash_typed(chaos_corpus, tmp_path):
    """An OOM-killed ring worker surfaces as FeedWorkerError via the
    liveness probe (the plan reaches the spawned worker through the
    RA_FAULT_PLAN env export, as in production drills)."""
    packed, text, _ = chaos_corpus
    cfg = _cfg(0, "flat", 0, str(tmp_path / "ck"))
    with faults.armed(faults.FaultPlan.parse("feeder.worker.crash@2")):
        with pytest.raises((FeedWorkerError, StallError)):
            run_stream_file(
                packed, text, cfg, topk=5, feed_workers=2, feed_mode="ring"
            )


# ---------------------------------------------------------------------------
# Units: plan round-trips, exit codes, on-disk wire damage
# ---------------------------------------------------------------------------


def test_fault_plan_round_trip_every_registered_site():
    for site in faults.SITES:
        for at in (1, 3):
            plan = faults.FaultPlan.parse(f"{site}@{at}")
            assert plan.specs[site].at == at
            assert plan.specs[site].count == 1
            assert faults.FaultPlan.parse(plan.to_str()).to_str() == plan.to_str()
    multi = faults.FaultPlan.parse(
        "ingest.producer.raise@2,stream.wire.corrupt@1,seed=9"
    )
    assert set(multi.specs) == {"ingest.producer.raise", "stream.wire.corrupt"}
    assert multi.seed == 9
    assert faults.FaultPlan.parse(multi.to_str()).to_str() == multi.to_str()


def test_fault_plan_transient_grammar_round_trip():
    """``site@N:k`` (ISSUE 14): fire k consecutive hits, then clear."""
    plan = faults.FaultPlan.parse("stream.device_put.fail@2:3,seed=4")
    spec = plan.specs["stream.device_put.fail"]
    assert (spec.at, spec.count) == (2, 3)
    assert [spec.fires_on(n) for n in range(1, 7)] == [
        False, True, True, True, False, False,
    ]
    assert plan.to_str() == "stream.device_put.fail@2:3,seed=4"
    assert faults.FaultPlan.parse(plan.to_str()).to_str() == plan.to_str()
    # single-shot stays the historical serialization (no ':1' noise)
    assert faults.FaultPlan.parse("listener.drop@5:1").to_str() == "listener.drop@5"
    with pytest.raises(AnalysisError, match=">= 1"):
        faults.FaultPlan.parse("listener.drop@5:0")
    with pytest.raises(AnalysisError, match="site@N"):
        faults.FaultPlan.parse("listener.drop@5:x")


def test_fault_plan_rejects_unknown_site_and_bad_hit():
    with pytest.raises(AnalysisError, match="unknown fault site"):
        faults.FaultPlan.parse("no.such.site@1")
    with pytest.raises(AnalysisError, match=">= 1"):
        faults.FaultPlan.parse("ingest.producer.raise@0")
    with pytest.raises(AnalysisError, match="no sites"):
        faults.FaultPlan.parse("seed=4")


def test_fault_plan_random_deterministic_and_armable():
    a = faults.FaultPlan.random(123, n_faults=2)
    b = faults.FaultPlan.random(123, n_faults=2)
    assert a.to_str() == b.to_str()
    assert faults.FaultPlan.random(124, n_faults=2).to_str() != a.to_str()
    with faults.armed(a):
        assert os.environ[faults.ENV_VAR] == a.to_str()
    assert faults.ENV_VAR not in os.environ
    assert faults.active_plan() is None


def test_exit_codes_map_failure_classes():
    assert exit_code_for(CheckpointCorrupt("x")) == EXIT_CHECKPOINT_CORRUPT == 3
    assert exit_code_for(CheckpointMismatch("x")) == EXIT_CHECKPOINT_MISMATCH == 4
    assert exit_code_for(ResumeInputMismatch("x")) == 4
    assert exit_code_for(FeedWorkerError("x")) == EXIT_FEED == 5
    assert exit_code_for(IngestError("x")) == 5
    assert exit_code_for(WireCorrupt("x")) == 5
    assert exit_code_for(StallError("x")) == EXIT_STALL == 6
    assert exit_code_for(ReformBudgetExhausted("x")) == EXIT_REFORM_BUDGET == 7
    assert exit_code_for(AnalysisError("x")) == 1
    assert exit_code_for(InjectedFault("x")) == 1
    # the distributed stall face maps with its base class
    from ruleset_analysis_tpu.runtime.elastic import FormationTimeout

    assert exit_code_for(FormationTimeout("x")) == 6


def test_on_disk_wire_valid_bit_damage_refused(tmp_path):
    """Clear one stored row's valid bit in the FILE: typed WireCorrupt.

    The converter never stores an invalid row, so this byte pattern only
    exists through post-conversion damage — the reader must refuse, not
    skip-count (the pre-PR behavior silently absorbed it)."""
    from ruleset_analysis_tpu.hostside.pack import W_META, WIRE_COLS
    from ruleset_analysis_tpu.hostside.wire import HEADER_BYTES

    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=6, seed=3)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])  # pure-v4: v1 header, simple offsets
    tuples = synth.synth_tuples(packed, 900, seed=4)
    lines = synth.render_syslog(packed, tuples, seed=5)
    log = tmp_path / "w.log"
    log.write_text("\n".join(lines) + "\n", encoding="utf-8")
    wp = str(tmp_path / "w.rawire")
    stats = wire_mod.convert_logs(packed, [str(log)], wp, block_rows=512)
    r0 = min(512, stats["rows"])  # rows in block 0 ([WIRE_COLS, r0] plane)
    j = 5
    off = HEADER_BYTES + 4 * (W_META * r0 + j)
    with open(wp, "r+b") as f:
        f.seek(off)
        word = int.from_bytes(f.read(4), "little")
        assert word & (1 << 23), "picked a non-stored row; offset math wrong"
        f.seek(off)
        f.write((word & ~(1 << 23)).to_bytes(4, "little"))
    cfg = _cfg(0, "flat", 0, str(tmp_path / "ck"))
    with pytest.raises(WireCorrupt, match="valid bit"):
        run_stream_wire(packed, wp, cfg, topk=5)


def test_disarmed_sites_cost_nothing_and_change_nothing(chaos_corpus):
    """With no plan armed, fire() is a no-op returning its payload."""
    arr = np.arange(4, dtype=np.uint32)
    assert faults.fire("stream.wire.corrupt", payload=arr) is arr
    assert faults.fire("ingest.producer.raise") is None


# ---------------------------------------------------------------------------
# Serve-mode chaos (ISSUE 6): seeded schedules over the listener/reload
# tier.  The windowed invariant: every published window report is either
# bit-identical to an offline replay over exactly the lines that were
# DELIVERED to it, or carries an explicit WindowIncomplete marker with
# exact drop accounting — and a run under any schedule ends in a report
# or a typed abort, never a hang and never a silent zero-hit window.
# ---------------------------------------------------------------------------

SERVE_W = 100  # lines per window (deterministic rotation)
SERVE_LINES = 310  # 3 full windows + a tail that must never publish dirty


def serve_schedule(seed: int):
    """Seeded serve schedule: site from the seed, hit count from its rng.

    The site cycles so 12 seeds cover each of the four failure classes
    three times; hit counts above SERVE_LINES are deliberate never-fire
    schedules (the clean-run branch of the invariant).
    """
    sites = ["listener.drop", "listener.stall", "reload.midbatch",
             "stream.device_put.fail"]
    rng = random.Random(seed)
    site = sites[seed % len(sites)]
    if site == "listener.drop":
        at = rng.choice([5, 150, 205, 1000])
    elif site == "listener.stall":
        at = rng.choice([50, 1000])
    elif site == "reload.midbatch":
        at = 1
    else:  # stream.device_put.fail: lands in the first windows' chunks
        at = rng.randint(1, 4)
    return site, at, faults.FaultPlan([faults.FaultSpec(site, at)], seed=seed)


@pytest.fixture(scope="module")
def serve_chaos_corpus(chaos_corpus, tmp_path_factory):
    packed, _text, _wirep = chaos_corpus
    td = tmp_path_factory.mktemp("chaos_serve")
    prefix = str(td / "rules")
    pack.save_packed(packed, prefix)
    return packed, prefix, _mixed_lines(SERVE_LINES, seed=77)


@pytest.mark.parametrize("seed", range(12))
def test_chaos_serve_schedule(seed, serve_chaos_corpus, tmp_path):
    """One seeded listener/reload schedule against a live serve loop."""
    import socket
    import threading

    from ruleset_analysis_tpu.config import ServeConfig
    from ruleset_analysis_tpu.runtime.serve import ServeDriver, window_incomplete
    from ruleset_analysis_tpu.runtime.stream import run_stream

    packed, prefix, lines = serve_chaos_corpus
    site, at, plan = serve_schedule(seed)
    cfg = _cfg(0, "flat", 0, str(tmp_path / "ck"))
    scfg = ServeConfig(
        listen=("tcp:127.0.0.1:0",), window_lines=SERVE_W, ring=4,
        serve_dir=str(tmp_path / "serve"), max_windows=3,
        stop_after_sec=60, reload_watch=False,
        checkpoint_every_windows=0, http="off", queue_lines=10_000,
    )
    out: dict = {}
    with faults.armed(plan):
        drv = ServeDriver(prefix, cfg, scfg, topk=5)

        def runner():
            try:
                out["summary"] = drv.run()
            except BaseException as e:
                out["error"] = e

        th = threading.Thread(target=runner)
        th.start()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not (
            "error" in out or drv.listeners.alive()
        ):
            time.sleep(0.05)
        if site == "reload.midbatch":
            # the ruleset on disk is unchanged; the fault site fires
            # before the (identity) migration even starts
            drv.request_reload()
        if "error" not in out and drv.listeners.alive():
            s = socket.create_connection(drv.listeners.listeners[0].address)
            s.sendall(("\n".join(lines) + "\n").encode())
            s.close()
        th.join(timeout=120)
        assert not th.is_alive(), f"seed {seed} ({site}@{at}): serve HUNG"
    if "error" in out:
        # the typed-abort branch (injected device failure, or the
        # wedged-listener watchdog escalating a stalled ingress)
        assert isinstance(out["error"], AnalysisError), (
            f"seed {seed} ({site}@{at}): untyped abort {out['error']!r}"
        )
        return

    summary = out["summary"]
    dropped_idx = (
        at - 1 if site == "listener.drop" and at <= SERVE_LINES else None
    )
    delivered = [ln for i, ln in enumerate(lines) if i != dropped_idx]
    n_full = min(3, len(delivered) // SERVE_W)
    # the bounded stop (max_windows) discards the queued backlog as
    # COUNTED drops and publishes one final marked partial window for
    # it — never a silent discard
    backlog = len(delivered) - n_full * SERVE_W
    n_win = summary["windows_published"]
    assert n_win == n_full + (1 if backlog else 0), f"seed {seed} ({site}@{at})"
    marked = []
    for i in range(n_full):
        with open(
            os.path.join(scfg.serve_dir, f"window-{i:06d}.json"),
            encoding="utf-8",
        ) as f:
            rep = json.load(f)
        # registers answer for exactly the delivered lines — true with
        # or without the incompleteness marker (the marker is about the
        # lines that never arrived, not the ones analyzed)
        seg = delivered[i * SERVE_W:(i + 1) * SERVE_W]
        got = report_image(rep)
        want = report_image(run_stream(packed, iter(seg), cfg, topk=5))
        got["totals"].pop("window", None)
        want["totals"].pop("window", None)
        assert got == want, f"seed {seed} ({site}@{at}): window {i} diverged"
        inc = window_incomplete(rep)
        if inc:
            marked.append((i, inc))
    if backlog:
        with open(
            os.path.join(scfg.serve_dir, f"window-{n_full:06d}.json"),
            encoding="utf-8",
        ) as f:
            prep = json.load(f)
        inc = window_incomplete(prep)
        assert prep["totals"]["lines_total"] == 0, (
            f"seed {seed}: backlog window analyzed lines it should not have"
        )
        assert inc and inc["drops"] == backlog, (
            f"seed {seed}: shutdown backlog not marked ({inc})"
        )
    forced = 1 if dropped_idx is not None else 0
    assert summary["drops"] == forced + backlog, (
        f"seed {seed} ({site}@{at}): drop accounting off"
    )
    if dropped_idx is not None:
        # the drop is accounted exactly once, on exactly one full
        # window — never silently absorbed into a zero-hit report
        assert len(marked) == 1 and marked[0][1]["drops"] == 1, (
            f"seed {seed}: dropped line not marked ({marked})"
        )
    else:
        assert marked == [], f"seed {seed} ({site}@{at})"
    if site == "reload.midbatch":
        # atomic failed reload: nothing swapped, nothing quarantined
        assert summary["reload_errors"] == 1 and summary["reloads"] == 0
        assert summary["quarantine_hits"] == 0


# ---------------------------------------------------------------------------
# Slow soak: more seeds + the multi-process elastic scenarios; emits the
# chaos-robustness artifact (pass rate + mean time-to-recover).
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_chaos_soak_matrix(chaos_corpus, baselines, tmp_path):
    from ruleset_analysis_tpu.runtime.metrics import RecoveryMeter

    meter = RecoveryMeter()
    for seed in range(100, 120):
        meter.record_run(
            run_schedule(seed, chaos_corpus, baselines, tmp_path)
        )
    s = meter.summary()
    assert s["chaos_runs"] == 20 and s["chaos_pass_rate"] == 1.0
    out = os.environ.get("RA_CHAOS_ARTIFACT")
    if out:
        with open(out, "w", encoding="utf-8") as f:
            json.dump(
                {
                    "suite": "chaos_soak_matrix",
                    "seeds": [100, 119],
                    **s,
                },
                f,
                indent=2,
            )


def _spawn_elastic_chaos(td, prefix, shards, victim_plan, victim_tag,
                         timeout=400):
    """4 elastic launchers; the victim's fault plan rides ITS env only."""
    import subprocess
    import sys

    from cpuenv import REPO, cpu_env

    eldir = str(td / "eldir")
    procs = []
    for pid in range(4):
        env = cpu_env(2)
        if pid == victim_tag:
            env[faults.ENV_VAR] = victim_plan
        procs.append(
            subprocess.Popen(
                [sys.executable, "-m", "ruleset_analysis_tpu.cli", "run",
                 "--ruleset", prefix, "--logs", *shards, "--backend", "tpu",
                 "--distributed", "--elastic", "--elastic-dir", eldir,
                 "--num-processes", "4", "--process-id", str(pid),
                 "--batch-size", "64", "--checkpoint-every", "2",
                 "--json", "--out", str(td / f"rep{pid}.json")],
                env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError("elastic chaos launcher HUNG")
        outs.append((p.returncode, out, err))
    return eldir, outs


@pytest.fixture(scope="module")
def elastic_corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("chaos_elastic")
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=8, seed=41)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 1600, seed=42)
    lines = synth.render_syslog(packed, tuples, seed=43)
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


@pytest.mark.slow
def test_chaos_soak_elastic_worker_die(elastic_corpus, tmp_path_factory):
    """Plan-driven node death (elastic.worker.die): survivors re-form and
    the report is bit-identical; no rendezvous temp litter remains."""
    td = tmp_path_factory.mktemp("chaos_die")
    _td, prefix, shards = elastic_corpus
    eldir, outs = _spawn_elastic_chaos(
        td, prefix, shards, "elastic.worker.die@4", victim_tag=2
    )
    from ruleset_analysis_tpu.runtime.elastic import DIE_RC

    assert outs[2][0] == DIE_RC, outs[2][2][-2000:]
    for pid in (0, 1, 3):
        assert outs[pid][0] == 0, (
            f"survivor {pid} rc={outs[pid][0]}\n{outs[pid][2][-3000:]}"
        )
    rep = json.loads((td / "rep0.json").read_text(encoding="utf-8"))
    assert rep["totals"]["processes"] == 3
    rec = rep["totals"]["recovery"]
    assert rec["reforms_used"] >= 1 and rec["recovery_events"] >= 1
    assert rec["mean_time_to_recover_sec"] >= 0
    # fault-free reference over the same shards
    packed = pack.load_packed(prefix)
    ref = run_stream_file(packed, shards, AnalysisConfig(batch_size=64))
    ref = json.loads(ref.to_json())
    hits = lambda r: {  # noqa: E731
        (e["firewall"], e["acl"], e["index"]): e["hits"] for e in r["per_rule"]
    }
    assert hits(rep) == hits(ref) and rep["unused"] == ref["unused"]
    # rendezvous hygiene: no temp-write litter survives the run
    litter = [
        os.path.join(root, e)
        for root, _dirs, files in os.walk(eldir)
        for e in files
        if e.endswith(".tmp") or e.startswith(".tmp-")
    ]
    assert not litter, f"leaked rendezvous temp files: {litter}"


@pytest.mark.slow
def test_chaos_soak_elastic_heartbeat_drop(elastic_corpus, tmp_path_factory):
    """A partitioned member (heartbeat stops): peers re-form WITHOUT it at
    world 3 with a bit-identical report; the victim aborts typed instead
    of computing on as a zombie."""
    td = tmp_path_factory.mktemp("chaos_hb")
    _td, prefix, shards = elastic_corpus
    _eldir, outs = _spawn_elastic_chaos(
        td, prefix, shards, "elastic.heartbeat.drop@6", victim_tag=2,
        timeout=500,
    )
    assert outs[2][0] != 0, "partitioned member claimed success"
    for pid in (0, 1, 3):
        assert outs[pid][0] == 0, (
            f"survivor {pid} rc={outs[pid][0]}\n{outs[pid][2][-3000:]}"
        )
    rep = json.loads((td / "rep0.json").read_text(encoding="utf-8"))
    assert rep["totals"]["processes"] == 3
    packed = pack.load_packed(prefix)
    ref = run_stream_file(packed, shards, AnalysisConfig(batch_size=64))
    ref = json.loads(ref.to_json())
    hits = lambda r: {  # noqa: E731
        (e["firewall"], e["acl"], e["index"]): e["hits"] for e in r["per_rule"]
    }
    assert hits(rep) == hits(ref) and rep["unused"] == ref["unused"]


# ---------------------------------------------------------------------------
# Durable-WAL chaos (ISSUE 14): a seeded hard abort mid-window (a
# persistently-failing device_put past the retry budget — the on-disk
# state a SIGKILL leaves) followed by serve --resume.  Invariant: the
# interrupted window's delivered lines replay from the spool and publish
# bit-identical, with zero unaccounted drops.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0])
def test_chaos_serve_wal_hard_abort_resume(seed, serve_chaos_corpus, tmp_path):
    import threading

    from ruleset_analysis_tpu.config import ServeConfig
    from ruleset_analysis_tpu.runtime.serve import ServeDriver, window_incomplete
    from ruleset_analysis_tpu.runtime.stream import run_stream
    from ruleset_analysis_tpu.runtime.wal import WriteAheadLog

    packed, prefix, lines = serve_chaos_corpus
    rng = random.Random(seed)
    # window 0 = 3 full 32-line chunks + a rotation flush = 4 hits; the
    # seeded hit lands in window 1's chunks, :99 exhausts the budget
    at = 5 + rng.randrange(2)
    cfg = _cfg(0, "flat", 0, str(tmp_path / "ck")).replace(
        batch_size=32, fault_plan=f"stream.device_put.fail@{at}:99"
    )
    scfg = ServeConfig(
        listen=("tcp:127.0.0.1:0",), window_lines=SERVE_W, ring=4,
        serve_dir=str(tmp_path / "serve"), stop_after_sec=60,
        reload_watch=False, checkpoint_every_windows=1, http="off",
        queue_lines=10_000, wal=True,
    )

    def spin(drv, out):
        def runner():
            try:
                out["summary"] = drv.run()
            except BaseException as e:
                out["error"] = e
        th = threading.Thread(target=runner)
        th.start()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not (
            "error" in out or drv.listeners.alive()
        ):
            time.sleep(0.05)
        return th

    out: dict = {}
    drv = ServeDriver(prefix, cfg, scfg, topk=5)
    th = spin(drv, out)
    import socket

    s = socket.create_connection(drv.listeners.listeners[0].address)
    s.sendall(("\n".join(lines[:180]) + "\n").encode())
    s.close()
    th.join(timeout=120)
    assert not th.is_alive(), f"seed {seed}: serve HUNG"
    assert isinstance(out.get("error"), AnalysisError), out
    assert drv.windows_published == 1

    wal = WriteAheadLog(os.path.join(scfg.serve_dir, "wal"))
    delivered = [ln for _s, ln, _t in wal.replay(SERVE_W)]
    wal.close()
    assert delivered == lines[SERVE_W:SERVE_W + len(delivered)]

    out2: dict = {}
    drv2 = ServeDriver(
        prefix, _cfg(0, "flat", 0, str(tmp_path / "ck")).replace(
            batch_size=32, resume=True
        ), scfg, topk=5,
    )
    th2 = spin(drv2, out2)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and drv2.wal_replayed != len(delivered):
        time.sleep(0.05)
    drv2.stop()
    th2.join(timeout=120)
    assert not th2.is_alive() and "error" not in out2, out2.get("error")
    summary = out2["summary"]
    assert summary["wal"]["replayed"] == len(delivered)
    assert summary["wal"]["lost"] == 0
    base_cfg = _cfg(0, "flat", 0, str(tmp_path / "ckb")).replace(batch_size=32)
    for wid, seg in ((0, lines[:SERVE_W]), (1, delivered)):
        with open(
            os.path.join(scfg.serve_dir, f"window-{wid:06d}.json"),
            encoding="utf-8",
        ) as f:
            rep = json.load(f)
        got = report_image(rep)
        want = report_image(run_stream(packed, iter(seg), base_cfg, topk=5))
        got["totals"].pop("window", None)
        want["totals"].pop("window", None)
        assert got == want, f"seed {seed}: window {wid} diverged after replay"
        inc = window_incomplete(rep)
        # zero unaccounted drops: any marker must claim no loss
        assert inc is None or (
            inc["drops"] == 0 and "wal_lost" not in inc["reasons"]
        ), inc
    assert summary["drops"] == 0
