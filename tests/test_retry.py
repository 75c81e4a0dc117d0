"""Typed retry/backoff engine (ISSUE 14 / DESIGN §19).

Three layers:

- **Units**: policy-spec grammar, seeded-deterministic backoff (same
  seed -> same jitter sequence ACROSS PROCESSES — the property test
  spawns an interpreter), transient-vs-permanent classification, budget
  accounting.
- **Transient chaos schedules**: literal ``site@N:k`` plans (k below
  the attempt bound) over the batch drivers — the STRENGTHENED
  invariant: the run must NOT abort, the report must be bit-identical
  to the fault-free baseline, the retry counters must record the
  recovery, and drop accounting must be untouched (zero unaccounted
  drops — the totals are part of the compared image).
- **Escalation schedules**: literal ``site@N:99`` plans (past every
  attempt bound) proving an exhausted budget escalates to the EXISTING
  typed aborts — no hang, no leak, no new failure class.  The registry
  auditor (verify/registry.py::audit_retry) greps this file for both
  schedule shapes per registered retry site.
"""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu import errors
from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.errors import (
    AnalysisError,
    CheckpointCorrupt,
    InjectedFault,
    is_transient,
)
from ruleset_analysis_tpu.hostside import aclparse, pack, wire as wire_mod
from ruleset_analysis_tpu.hostside.listener import LineQueue, UdpSyslogListener
from ruleset_analysis_tpu.runtime import faults, obs, retrypolicy
from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS as VOLATILE
from ruleset_analysis_tpu.runtime.stream import run_stream_file, run_stream_wire

# same ruleset text + batch geometry as the chaos harness so every
# specialized step program here rides the jit cache the earlier suites
# already paid for (suite-budget discipline, tests/conftest.py)
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


def report_image(rep) -> dict:
    j = rep if isinstance(rep, dict) else json.loads(rep.to_json())
    j = json.loads(json.dumps(j))
    for k in VOLATILE:
        j["totals"].pop(k, None)
    return j


def _mixed_lines(n, seed=0):
    import random

    rng = random.Random(seed)
    out = []
    for i in range(n):
        acl = "A" if rng.random() < 0.8 else "B"
        if rng.random() < 0.3:
            src = f"2001:db8:2::{rng.randrange(1, 40):x}"
            dst = f"2001:db8:1:1::{rng.randrange(1, 99):x}"
            proto = rng.choice(["tcp", "udp"])
        else:
            src = f"10.1.{rng.randrange(256)}.{rng.randrange(1, 255)}"
            dst = "10.0.0.5" if rng.random() < 0.5 else "10.9.9.9"
            proto = "tcp"
        out.append(
            f"Jul 29 07:48:{i % 60:02d} fw1 : %ASA-6-106100: access-list {acl} "
            f"permitted {proto} inside/{src}({rng.randrange(1024, 60000)}) -> "
            f"outside/{dst}({rng.choice([443, 53, 8050])}) "
            f"hit-cnt 1 first hit [0x0, 0x0]"
        )
    return out


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("retry")
    rs = aclparse.parse_asa_config(CFG6, "fw1")
    packed = pack.pack_rulesets([rs])
    text = str(td / "mix.log")
    with open(text, "w", encoding="utf-8") as f:
        f.write("\n".join(_mixed_lines(1500, seed=21)) + "\n")
    wirep = str(td / "mix.rawire")
    wire_mod.convert_logs(packed, [text], wirep, batch_size=512, block_rows=512)
    return packed, text, wirep


def _cfg(depth, cadence, ckpt_dir, resume=False):
    return AnalysisConfig(
        batch_size=512,
        sketch=SketchConfig(cms_width=1 << 10, cms_depth=2, hll_p=6),
        prefetch_depth=depth,
        checkpoint_every_chunks=cadence,
        checkpoint_dir=ckpt_dir,
        resume=resume,
        stall_timeout_sec=3.0,
    )


@pytest.fixture(scope="module")
def baselines(corpus, tmp_path_factory):
    cache: dict = {}
    td = tmp_path_factory.mktemp("retry_base")

    def get(inp, depth, cadence):
        key = (inp, depth, cadence)
        if key not in cache:
            packed, text, wirep = corpus
            cfg = _cfg(depth, cadence, str(td / f"ck-{inp}-{depth}-{cadence}"))
            rep = (
                run_stream_wire(packed, wirep, cfg, topk=5)
                if inp == "wire"
                else run_stream_file(packed, text, cfg, topk=5)
            )
            cache[key] = report_image(rep)
        return cache[key]

    return get


# ---------------------------------------------------------------------------
# Units: grammar, classification, deterministic backoff, budgets.
# ---------------------------------------------------------------------------


def test_policy_spec_grammar():
    ov, seed = retrypolicy.parse_spec("device_put=7/0.5,seed=9")
    assert ov["device_put"].attempts == 7
    assert ov["device_put"].base_sec == 0.5
    assert seed == 9
    ov, _ = retrypolicy.parse_spec("checkpoint.save=3")
    assert ov["checkpoint.save"].attempts == 3
    assert (
        ov["checkpoint.save"].base_sec
        == retrypolicy.DEFAULT_POLICIES["checkpoint.save"].base_sec
    )
    off, _ = retrypolicy.parse_spec("off")
    assert all(p.attempts == 1 for p in off.values())
    assert set(off) == set(retrypolicy.RETRY_SITES)
    for bad in ("nosuch=3", "device_put", "device_put=x", "seed=x"):
        with pytest.raises(AnalysisError):
            retrypolicy.parse_spec(bad)


def test_every_site_has_policy_and_fault_mapping():
    assert set(retrypolicy.DEFAULT_POLICIES) == set(retrypolicy.RETRY_SITES)
    for site, meta in retrypolicy.RETRY_SITES.items():
        assert meta.fault_site in faults.SITES, site


def test_transient_classification_table():
    assert is_transient(InjectedFault("x"))
    assert is_transient(ConnectionResetError("x"))
    assert is_transient(TimeoutError("x"))
    assert is_transient(OSError(errno_of("EADDRINUSE"), "in use"))
    assert is_transient(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    # permanent: typed refusals, missing files, program bugs
    assert not is_transient(CheckpointCorrupt("x"))
    assert not is_transient(AnalysisError("x"))
    assert not is_transient(FileNotFoundError("x"))
    assert not is_transient(PermissionError("x"))
    assert not is_transient(ValueError("x"))
    assert not is_transient(RuntimeError("shape mismatch"))


def errno_of(name):
    import errno

    return getattr(errno, name)


def test_backoff_deterministic_same_seed_and_shape():
    retrypolicy.configure("")
    a = retrypolicy.backoff_schedule("device_put", 8, seed=7)
    b = retrypolicy.backoff_schedule("device_put", 8, seed=7)
    assert a == b
    assert retrypolicy.backoff_schedule("device_put", 8, seed=8) != a
    # exponential shape under the cap, jitter within +/-50%
    pol = retrypolicy.DEFAULT_POLICIES["device_put"]
    for i, d in enumerate(a):
        raw = min(pol.cap_sec, pol.base_sec * pol.mult**i)
        assert 0.5 * raw <= d < 1.5 * raw


def test_backoff_deterministic_across_processes():
    """Same seed -> same jitter sequence in a FRESH interpreter (no
    PYTHONHASHSEED dependence — the acceptance property)."""
    from cpuenv import REPO, cpu_env

    env = cpu_env(1)
    env["PYTHONHASHSEED"] = "random"
    out = subprocess.run(
        [sys.executable, "-c",
         "import json\n"
         "from ruleset_analysis_tpu.runtime import retrypolicy\n"
         "print(json.dumps(retrypolicy.backoff_schedule("
         "'checkpoint.save', 6, seed=42)))"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    theirs = json.loads(out.stdout.strip())
    assert theirs == retrypolicy.backoff_schedule("checkpoint.save", 6, seed=42)


def test_call_budget_and_permanent_escalation():
    retrypolicy.configure("device_put=3/0.001")
    # permanent errors escalate on attempt 1, no sleeps
    with pytest.raises(CheckpointCorrupt):
        retrypolicy.call("device_put", lambda: (_ for _ in ()).throw(
            CheckpointCorrupt("no")
        ))
    c = retrypolicy.counters()["device_put"]
    assert c == {"attempts": 0, "recoveries": 0, "giveups": 1}
    # transient exhaust: attempts-1 retries then the ORIGINAL error
    n = {"v": 0}

    def always():
        n["v"] += 1
        raise InjectedFault("t")

    with pytest.raises(InjectedFault):
        retrypolicy.call("device_put", always)
    assert n["v"] == 3
    c = retrypolicy.counters()["device_put"]
    assert c["attempts"] == 2 and c["giveups"] == 2
    g = retrypolicy.gauges()
    assert g["retry_attempts_total"] == 2
    assert g["retry_device_put_giveups"] == 2
    retrypolicy.configure("")  # restore defaults for later suites


def test_off_spec_disables_retries():
    retrypolicy.configure("off")
    n = {"v": 0}

    def once():
        n["v"] += 1
        raise InjectedFault("t")

    with pytest.raises(InjectedFault):
        retrypolicy.call("wire.read", once)
    assert n["v"] == 1
    retrypolicy.configure("")


# ---------------------------------------------------------------------------
# Transient chaos schedules (the strengthened invariant): LITERAL
# ``site@N:k`` plans with single-digit k — the retry engine must RECOVER,
# the report must be bit-identical to the fault-free baseline, and drop
# accounting must be untouched.  12 seeded schedules across the batch
# drivers; the serve-side transients live in test_serve/test_chaos.
# ---------------------------------------------------------------------------

TRANSIENT_SCHEDULES = [
    # (plan, input, prefetch depth, checkpoint cadence)
    ("stream.device_put.fail@1:2,seed=301", "text", 0, 0),
    ("stream.device_put.fail@2:3,seed=302", "text", 2, 0),
    ("stream.device_put.fail@1:4,seed=303", "wire", 0, 0),
    ("stream.device_put.fail@3:2,seed=304", "wire", 2, 0),
    ("stream.device_put.fail@2:2,seed=305", "text", 0, 2),
    ("checkpoint.torn_state@1:2,seed=306", "text", 0, 2),
    ("checkpoint.torn_state@2:3,seed=307", "wire", 0, 2),
    ("checkpoint.torn_state@1:1,seed=308", "wire", 2, 2),
    ("checkpoint.torn_manifest@1:2,seed=309", "text", 0, 2),
    ("checkpoint.torn_manifest@2:2,seed=310", "wire", 2, 2),
    ("stream.wire.read.fail@1:2,seed=311", "wire", 0, 0),
    ("stream.wire.read.fail@1:3,seed=312", "wire", 2, 2),
]


@pytest.mark.parametrize("plan,inp,depth,cadence", TRANSIENT_SCHEDULES)
def test_transient_schedule_recovers_bit_identical(
    corpus, baselines, tmp_path, plan, inp, depth, cadence
):
    packed, text, wirep = corpus
    base = baselines(inp, depth, cadence)
    cfg = _cfg(depth, cadence, str(tmp_path / "ck"))
    site = plan.split("@")[0]
    retry_site = next(
        s for s, m in retrypolicy.RETRY_SITES.items() if m.fault_site == site
    ) if site != "checkpoint.torn_manifest" else "checkpoint.save"
    with faults.armed(faults.FaultPlan.parse(plan)):
        rep = (
            run_stream_wire(packed, wirep, cfg, topk=5)
            if inp == "wire"
            else run_stream_file(packed, text, cfg, topk=5)
        )  # must NOT raise: the whole point of the survival plane
    # bit-identical INCLUDING line/skip totals: zero unaccounted drops
    assert report_image(rep) == base, f"{plan} diverged after recovery"
    c = retrypolicy.counters().get(retry_site, {})
    assert c.get("recoveries", 0) >= 1, (plan, retrypolicy.counters())
    assert c.get("giveups", 0) == 0, (plan, c)


def test_transient_schedules_meet_acceptance_floor():
    assert len(TRANSIENT_SCHEDULES) >= 12


# ---------------------------------------------------------------------------
# Budget exhaustion per retryable site: LITERAL ``@N:99`` plans (k far
# past every attempt bound) — escalation must stay TYPED, bounded in
# time, and leak-free (the conftest leak audit covers the latter).
# ---------------------------------------------------------------------------


def test_exhaustion_device_put_escalates_typed(corpus, baselines, tmp_path):
    packed, text, _ = corpus
    cfg = _cfg(0, 0, str(tmp_path / "ck"))
    t0 = time.monotonic()
    with faults.armed(faults.FaultPlan.parse("stream.device_put.fail@1:99")):
        with pytest.raises(InjectedFault):
            run_stream_file(packed, text, cfg, topk=5)
    assert time.monotonic() - t0 < 30
    assert retrypolicy.counters()["device_put"]["giveups"] >= 1
    # the process is healthy afterwards: a disarmed run matches baseline
    rep = run_stream_file(packed, text, _cfg(0, 0, str(tmp_path / "ck2")), topk=5)
    assert report_image(rep) == baselines("text", 0, 0)


def test_exhaustion_checkpoint_save_escalates_typed(corpus, tmp_path):
    packed, text, _ = corpus
    cfg = _cfg(0, 2, str(tmp_path / "ck"))
    with faults.armed(faults.FaultPlan.parse("checkpoint.torn_manifest@1:99")):
        with pytest.raises(InjectedFault):
            run_stream_file(packed, text, cfg, topk=5)
    # no litter from the retried attempts
    leftovers = [
        e for e in os.listdir(tmp_path / "ck") if e.startswith(".tmp-")
    ]
    assert not leftovers, leftovers


def test_exhaustion_wire_read_escalates_typed(corpus, tmp_path):
    packed, _, wirep = corpus
    cfg = _cfg(0, 0, str(tmp_path / "ck"))
    with faults.armed(faults.FaultPlan.parse("stream.wire.read.fail@1:99")):
        with pytest.raises(InjectedFault):
            run_stream_wire(packed, wirep, cfg, topk=5)
    assert retrypolicy.counters()["wire.read"]["giveups"] >= 1


def test_listener_bind_transient_recovers_and_exhaustion_typed():
    retrypolicy.configure("listener.bind=4/0.01")
    try:
        # transient: two consecutive bind failures, then the bind lands
        with faults.armed(faults.FaultPlan.parse("listener.bind.fail@1:2")):
            q = LineQueue(64)
            ln = UdpSyslogListener(q, "127.0.0.1", 0)
            ln.start()
            try:
                s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                s.sendto(b"hello\n", ln.address)
                s.close()
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline and not len(q):
                    time.sleep(0.02)
                assert q.pop(0.1) == "hello"
            finally:
                ln.close()
        assert retrypolicy.counters()["listener.bind"]["recoveries"] >= 1
        # exhaustion: the constructor escalates the typed error —
        # exactly the CLI's documented clean bind failure path
        with faults.armed(faults.FaultPlan.parse("listener.bind.fail@1:99")):
            with pytest.raises(InjectedFault):
                UdpSyslogListener(LineQueue(64), "127.0.0.1", 0)
    finally:
        retrypolicy.configure("")


def test_listener_accept_transient_recovers_and_exhaustion_dead():
    retrypolicy.configure("listener.accept=4/0.01")
    try:
        # transient: the receive loop faults twice mid-iteration; the
        # retry re-enters it and traffic still flows — with the line in
        # flight at each fault surfacing as a COUNTED drop, never a gap
        with faults.armed(faults.FaultPlan.parse("listener.accept.fail@3:2")):
            q = LineQueue(64)
            ln = UdpSyslogListener(q, "127.0.0.1", 0)
            ln.start()
            try:
                s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                for i in range(10):
                    s.sendto(f"m{i}\n".encode(), ln.address)
                    time.sleep(0.02)
                s.close()
                deadline = time.monotonic() + 15
                while time.monotonic() < deadline:
                    snap = q.snapshot()
                    if snap["received"] >= 10:
                        break
                    time.sleep(0.05)
                snap = q.snapshot()
                assert snap["received"] + snap["dropped"] >= 9
                assert ln.is_alive() and not ln.dead
            finally:
                ln.close()
        assert retrypolicy.counters()["listener.accept"]["recoveries"] >= 1
        # exhaustion: the listener dies with the error RECORDED (the
        # serve loop's existing dead-listener escalation takes over)
        with faults.armed(faults.FaultPlan.parse("listener.accept.fail@1:99")):
            q = LineQueue(64)
            ln = UdpSyslogListener(q, "127.0.0.1", 0)
            ln.start()
            try:
                deadline = time.monotonic() + 15
                while time.monotonic() < deadline and not ln.dead:
                    time.sleep(0.05)
                assert ln.dead
                assert isinstance(ln.error, InjectedFault)
            finally:
                ln.close()
    finally:
        retrypolicy.configure("")


def test_metrics_snapshot_failures_counted_thread_survives(tmp_path):
    """metrics.snapshot.fail: tick errors are counted, the ra-metrics
    thread never dies, and a clean tick resets consec_errors — the
    signal serve's degraded plane keys on."""
    mf = str(tmp_path / "m.jsonl")
    with faults.armed(faults.FaultPlan.parse("metrics.snapshot.fail@1:2")):
        obs.start_metrics(mf, every_sec=0.05)
        try:
            deadline = time.monotonic() + 15
            h = None
            while time.monotonic() < deadline:
                h = obs.metrics_health()
                if h["errors"] >= 2 and h["consec_errors"] == 0:
                    break
                time.sleep(0.05)
            assert h is not None and h["alive"], h
            assert h["errors"] >= 2 and h["consec_errors"] == 0, h
        finally:
            obs.shutdown(merge=False)
    # snapshots resumed after the burst: the file holds real records
    with open(mf, encoding="utf-8") as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    assert any(r.get("kind") == "snapshot" for r in recs)


# serve.publish schedules (transient @1:2 recovery and @1:99 degradation)
# live in tests/test_serve.py::test_publisher_degrades_and_recovers —
# they need the full driver; the audit greps the whole tests tree.
