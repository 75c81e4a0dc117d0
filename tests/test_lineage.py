"""Window provenance & lineage plane + SLO burn-rate alerting (ISSUE 19).

Pins the lineage-plane invariants (DESIGN §24):

- **Sealed records**: every published window carries a ``totals.lineage``
  record whose CRC covers only the deterministic core — term/path/
  published_unix stay outside it, so replay-identical windows carry
  identical CRCs even across supervisor terms.
- **Ledger durability**: the ``lineage.jsonl`` append is a CORE
  publication step (single-write O_APPEND); the ``lineage.append`` chaos
  site proves a failed append aborts typed BEFORE the window file exists
  — never a torn record, never a window without provenance.
- **Burn-rate hysteresis**: ``--slo`` breach/recovery fire only on
  multi-window burn-rate transitions (fast+slow pair), never per-window,
  and the JSON gauges agree with the labeled prom exposition.
- **Trend hysteresis**: ``rule_burst``/``rule_quiet`` fire once per
  label transition with a minimum-hits floor — steady load emits nothing.
"""

import json
import os
import socket
import threading
import time
import urllib.request

import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu.config import AnalysisConfig, ServeConfig
from ruleset_analysis_tpu.errors import AnalysisError, InjectedFault
from ruleset_analysis_tpu.hostside import aclparse, pack, synth
from ruleset_analysis_tpu.runtime import faults
from ruleset_analysis_tpu.runtime.metrics import (
    LatencyHistogram,
    SloBurnEngine,
    SloPolicy,
    build_info,
    render_build_info_prom,
    window_slo_stats,
)
from ruleset_analysis_tpu.runtime.report import (
    LINEAGE_VOLATILE,
    lineage_core,
    lineage_frontier,
    seal_lineage,
    trend_events,
)
from ruleset_analysis_tpu.runtime.serve import ServeDriver
from ruleset_analysis_tpu.runtime.wal import LineageLog


# ---------------------------------------------------------------------------
# Record sealing + frontier (pure host-side).
# ---------------------------------------------------------------------------

def _rec(window, *, term=1, path="live", incomplete=None, kind="window"):
    rec = {
        "window": window,
        "kind": kind,
        "hosts": [{
            "rank": 0, "wal_seq_lo": window * 10, "wal_seq_hi": window * 10 + 10,
            "drops": 0, "quarantine_hits": 0,
        }],
        "generation": 0,
        "term": term,
        "path": path,
        "published_unix": 123.0 + window,
    }
    if incomplete:
        rec["incomplete"] = incomplete
    return seal_lineage(rec)


def test_seal_lineage_crc_covers_only_the_core():
    a = _rec(3, term=1, path="live")
    b = _rec(3, term=7, path="replay")
    # the replay-identity law: volatile fields differ, cores agree, and
    # because the CRC covers only the core the two CRCs are EQUAL
    assert lineage_core(a) == lineage_core(b)
    assert a["crc"] == b["crc"]
    assert a["term"] != b["term"] and a["path"] != b["path"]
    for k in LINEAGE_VOLATILE:
        assert k not in lineage_core(a)
    # any core mutation moves the CRC
    c = _rec(3)
    c["hosts"][0]["drops"] = 1
    assert seal_lineage(c)["crc"] != a["crc"]
    # resealing an untouched record is a no-op (the audit idiom)
    assert seal_lineage(dict(a))["crc"] == a["crc"]


def test_lineage_frontier_complete_incomplete_gaps():
    assert lineage_frontier([]) == {
        "windows": 0, "last_complete": None, "first_incomplete": None,
        "gaps": [],
    }
    recs = [_rec(0), _rec(1), _rec(3, incomplete={"reasons": ["drops"]}),
            _rec(4)]
    fr = lineage_frontier(recs)
    assert fr["windows"] == 4
    assert fr["gaps"] == [2]
    assert fr["first_incomplete"] == 2  # the gap precedes the marked one
    assert fr["last_complete"] == 4
    # merged-K records never join the per-window frontier
    fr2 = lineage_frontier(recs + [{"window": 9, "kind": "merged", "k": 2}])
    assert fr2["windows"] == 4
    # last write wins: a replay republish of window 3 heals the marker
    fr3 = lineage_frontier(recs + [_rec(2), _rec(3, path="replay")])
    assert fr3["gaps"] == [] and fr3["first_incomplete"] is None
    assert fr3["last_complete"] == 4


# ---------------------------------------------------------------------------
# SLO policy grammar + burn-rate engine.
# ---------------------------------------------------------------------------

def test_slo_policy_parse_grammar_and_refusals():
    pol = SloPolicy.parse(" p99_publish_ms <= 500 , drop_rate<=0.001 ")
    assert pol.objectives == [("p99_publish_ms", 500.0), ("drop_rate", 0.001)]
    for bad in ("p99_publish_ms>=500", "p99_publish_ms<500", "nonsense",
                "no_such_metric<=1", "drop_rate<=0.1,drop_rate<=0.2", ""):
        with pytest.raises(ValueError):
            SloPolicy.parse(bad)
    # config validation surfaces the same error at construction time
    with pytest.raises(ValueError):
        ServeConfig(window_lines=10, slo="no_such_metric<=1")


def test_slo_burn_engine_breach_and_recovery_transitions():
    eng = SloBurnEngine(
        SloPolicy.parse("drop_rate<=0.001"), fast=3, slow=12, budget=0.01,
    )
    events = []
    # clean windows: no events, burn stays 0
    for w in range(4):
        events += eng.observe({"drop_rate": 0.0, "window": w})
    assert events == []
    assert eng.gauges()["slo_breached"] == 0
    # sustained violation: exactly ONE breach at the transition, then
    # hysteresis swallows the steady-bad windows
    for w in range(4, 10):
        events += eng.observe({"drop_rate": 0.5, "window": w})
    breaches = [e for e in events if e["event"] == "slo.breach"]
    assert len(breaches) == 1
    assert breaches[0]["objective"] == "drop_rate"
    assert breaches[0]["burn_fast"] >= eng.fast_burn
    assert eng.gauges()["slo_breached"] == 1
    assert eng.gauges()["slo_breaches_total"] == 1
    # recovery needs the whole fast window clean: exactly one event
    events = []
    for w in range(10, 16):
        events += eng.observe({"drop_rate": 0.0, "window": w})
    recs = [e for e in events if e["event"] == "slo.recovered"]
    assert len(recs) == 1 and len(events) == 1
    g = eng.gauges()
    assert g["slo_breached"] == 0 and g["slo_recoveries_total"] == 1
    # a window with no latency samples cannot violate a latency bound
    eng2 = SloBurnEngine(SloPolicy.parse("p99_publish_ms<=1"))
    for w in range(12):
        assert eng2.observe({"window": w}) == []
    # labeled gauges carry the per-objective view
    lab = eng.labeled_gauges()
    assert set(lab) == {"drop_rate"}
    assert lab["drop_rate"]["slo_bound"] == 0.001
    assert lab["drop_rate"]["slo_objective_breached"] == 0


def test_window_slo_stats_shape():
    hist = LatencyHistogram()
    for s in (0.001, 0.002, 0.004):
        hist.record(s)
    st = window_slo_stats(
        hist, lines=90, drops=10, incomplete=True, degraded=2, window=7,
    )
    assert st["drop_rate"] == 10 / 100  # drops over OFFERED lines
    assert st["incomplete_rate"] == 1.0
    assert st["degraded_subsystems"] == 2 and st["window"] == 7
    assert st["p99_publish_ms"] > st["p50_publish_ms"] > 0
    empty = window_slo_stats(None, lines=0, drops=0, incomplete=False,
                             degraded=0)
    assert empty["drop_rate"] == 0.0
    assert "p99_publish_ms" not in empty


def test_build_info_prom_is_one_value_1_gauge():
    info = build_info({"mesh": "hybrid/2"})
    assert info["mesh"] == "hybrid/2" and info["version"]
    prom = render_build_info_prom(info)
    assert prom.count("ra_build_info{") == 1
    assert prom.rstrip().endswith("} 1")
    for k, v in info.items():
        assert f'{k}="{v}"' in prom


# ---------------------------------------------------------------------------
# Per-rule trend hysteresis (pure host-side).
# ---------------------------------------------------------------------------

def _trend_rep(hits_by_idx, lines):
    return {
        "per_rule": [
            {"firewall": "fw1", "acl": "a", "index": i, "hits": h}
            for i, h in hits_by_idx.items()
        ],
        "totals": {"lines_total": lines},
    }


def test_trend_events_hysteresis_no_storm():
    state: dict = {}
    steady = _trend_rep({0: 100, 1: 50}, 1000)
    # steady load: nothing, ever
    for _ in range(5):
        assert trend_events(steady, steady, threshold=4.0, state=state) == []
    # a 10x burst: ONE event at the transition...
    burst = _trend_rep({0: 1000, 1: 50}, 1000)
    evs = trend_events(steady, burst, threshold=4.0, state=state)
    assert [e["event"] for e in evs] == ["rule_burst"]
    assert evs[0]["rule"] == "fw1 a 0"
    # ...and the sustained burst emits nothing (hysteresis, no storm)
    assert trend_events(burst, burst, threshold=4.0, state=state) == []
    # collapse back: the rate fell under old/threshold -> one quiet event
    evs = trend_events(burst, steady, threshold=4.0, state=state)
    assert [e["event"] for e in evs] == ["rule_quiet"]
    # back in band clears the state silently; a LATER burst re-fires
    trend_events(steady, steady, threshold=4.0, state=state)
    assert state == {}
    evs = trend_events(steady, burst, threshold=4.0, state=state)
    assert [e["event"] for e in evs] == ["rule_burst"]
    # the min-hits floor: a 2 -> 20 jump on a cold rule is noise
    cold_a = _trend_rep({0: 2}, 1000)
    cold_b = _trend_rep({0: 20}, 1000)
    assert trend_events(cold_a, cold_b, threshold=4.0, state={}) == []
    # an ingest lull is NOT every rule going quiet: rates normalise
    lull = _trend_rep({0: 10, 1: 5}, 100)
    assert trend_events(steady, lull, threshold=4.0, state={}) == []


# ---------------------------------------------------------------------------
# LineageLog durability + the lineage.append chaos site.
# ---------------------------------------------------------------------------

def test_lineage_log_roundtrip_and_torn_tail(tmp_path):
    path = str(tmp_path / "lineage.jsonl")
    log = LineageLog(path)
    a, b = _rec(0), _rec(1)
    log.append(a)
    log.append(b)
    log.sync()
    log.close()
    assert LineageLog.read(path) == [a, b]
    # a SIGKILL can tear at most the final line: read skips exactly it
    with open(path, "ab") as f:
        f.write(b'{"window": 2, "torn')
    assert LineageLog.read(path) == [a, b]
    assert LineageLog.read(str(tmp_path / "absent.jsonl")) == []


def test_lineage_append_fault_site_aborts_typed_never_torn(tmp_path):
    path = str(tmp_path / "lineage.jsonl")
    log = LineageLog(path)
    with faults.armed(faults.FaultPlan.parse("lineage.append@1")):
        with pytest.raises(InjectedFault):
            log.append(_rec(0))
    # the abort happened BEFORE the write: the ledger is empty and
    # readable, never torn — and the next append works
    assert LineageLog.read(path) == []
    log.append(_rec(0))
    log.close()
    assert [r["window"] for r in LineageLog.read(path)] == [0]


# ---------------------------------------------------------------------------
# Serve e2e: sealed records on every surface + chaos + SLO gauges.
# ---------------------------------------------------------------------------

RUN_CFG = dict(batch_size=128, prefetch_depth=0)
WL = 150


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Same geometry as the serve suite so the jit caches stay warm."""
    td = tmp_path_factory.mktemp("lineage")
    cfg_text = synth.synth_config(
        n_acls=2, rules_per_acl=8, seed=0, v6_fraction=0.25
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    prefix = str(td / "rules")
    pack.save_packed(packed, prefix)
    t = synth.synth_tuples(packed, 500, seed=1)
    lines = synth.render_syslog(packed, t, seed=1)
    return packed, prefix, lines, str(td)


def start_serve(prefix, cfg, scfg):
    drv = ServeDriver(prefix, cfg, scfg)
    out: dict = {}

    def runner():
        try:
            out["summary"] = drv.run()
        except BaseException as e:  # surfaced by finish()
            out["error"] = e

    th = threading.Thread(target=runner)
    th.start()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if out.get("error"):
            break
        if drv.listeners.listeners and drv.listeners.alive() and (
            scfg.http == "off" or drv.http_address
        ):
            break
        time.sleep(0.05)
    return drv, th, out


def finish(th, out, timeout=120):
    th.join(timeout=timeout)
    assert not th.is_alive(), "serve hung"
    if "error" in out:
        raise out["error"]
    return out["summary"]


def send_tcp(addr, lines):
    s = socket.create_connection(addr)
    s.sendall(("\n".join(lines) + "\n").encode())
    s.close()


def get_json(http, path):
    host, port = http
    with urllib.request.urlopen(
        f"http://{host}:{port}{path}", timeout=10
    ) as r:
        return json.load(r)


def wait_for(pred, timeout=60, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


def test_serve_lineage_e2e_ledger_routes_and_slo_gauges(corpus, tmp_path):
    packed, prefix, lines, td = corpus
    scfg = ServeConfig(
        listen=("tcp:127.0.0.1:0",), window_lines=WL, ring=4,
        serve_dir=str(tmp_path / "serve"), max_windows=0, stop_after_sec=90,
        reload_watch=False, checkpoint_every_windows=0, http="127.0.0.1:0",
        queue_lines=10_000, slo="p99_publish_ms<=60000,drop_rate<=0.5",
    )
    drv, th, out = start_serve(prefix, AnalysisConfig(**RUN_CFG), scfg)
    try:
        addr = tuple(drv.listeners.listeners[0].address)
        # the SAME lines twice: two windows of identical traffic, so the
        # per-rule trend plane must stay silent (the no-storm pin)
        send_tcp(addr, lines[:WL])
        wait_for(lambda: out.get("error") or drv.windows_published >= 1,
                 msg="window 0")
        send_tcp(addr, lines[:WL])
        wait_for(lambda: out.get("error") or drv.windows_published >= 2,
                 msg="window 1")
        # windows_published increments BEFORE the publish + SLO-observe
        # phase of the rotation — wait for both planes to catch up
        wait_for(lambda: out.get("error") or (
            drv.lineage_records_total >= 2
            and drv.slo.windows_observed >= 2
        ), msg="window 1 lineage + SLO observation")
        if "error" in out:
            raise out["error"]

        http = drv.http_address
        tail = get_json(http, "/lineage")
        assert tail["records_total"] == 2
        assert [r["window"] for r in tail["records"]] == [0, 1]
        for r in tail["records"]:
            assert r["kind"] == "window" and r["path"] == "live"
            assert r["term"] == 0 and r["generation"] == 0
            h = r["hosts"][0]
            assert h["wal_seq_hi"] >= h["wal_seq_lo"] >= 0
            assert h["drops"] == 0
            # the seal verifies: reseal of the served record is a no-op
            assert seal_lineage(dict(r))["crc"] == r["crc"]
        one = get_json(http, "/lineage/window/1")
        assert one == tail["records"][1]

        m = get_json(http, "/metrics")
        assert m["lineage_records_total"] == 2
        assert m["trend_events_total"] == 0
        assert m["slo_objectives"] == 2
        assert m["slo_windows_observed"] == 2
        assert m["slo_breached"] == 0 and m["slo_breaches_total"] == 0
        assert m["build_info"]["version"]
        host, port = http
        with urllib.request.urlopen(
            f"http://{host}:{port}/metrics?format=prom", timeout=10
        ) as r:
            prom = r.read().decode()
        assert "ra_build_info{" in prom
        assert f'version="{m["build_info"]["version"]}"' in prom
        assert "ra_serve_lineage_records_total 2" in prom
        assert 'ra_serve_slo_bound{objective="drop_rate"} 0.5' in prom
        assert 'ra_serve_slo_burn_fast{objective="p99_publish_ms"}' in prom
    finally:
        drv.stop()
    finish(th, out)

    # disk surfaces: window files, the ledger, and the frontier agree
    sd = scfg.serve_dir
    ledger = LineageLog.read(os.path.join(sd, LineageLog.NAME))
    assert len(ledger) == 2
    for w in range(2):
        with open(os.path.join(sd, f"window-{w:06d}.json")) as f:
            rep = json.load(f)
        lin = rep["totals"]["lineage"]
        assert lin == ledger[w]
        assert lin["window"] == w and "incomplete" not in lin
    fr = lineage_frontier(ledger)
    assert fr == {"windows": 2, "last_complete": 1,
                  "first_incomplete": None, "gaps": []}
    # identical traffic in both windows: zero trend events in the diff
    with open(os.path.join(sd, "diff-000001.json")) as f:
        diff = json.load(f)
    assert "trend_events" not in diff


def test_serve_slo_breach_names_drops_then_recovers(corpus, tmp_path):
    """A live window with 200 listener drops under ``drop_rate<=0.001``
    breaches once, its lineage record carries the 200 drops, and three
    clean windows bring exactly one recovery."""
    packed, prefix, lines, td = corpus
    scfg = ServeConfig(
        listen=("tcp:127.0.0.1:0",), window_lines=WL, ring=8,
        serve_dir=str(tmp_path / "serve"), max_windows=0, stop_after_sec=120,
        reload_watch=False, checkpoint_every_windows=0, http="off",
        queue_lines=10_000, slo="drop_rate<=0.001",
    )
    drv, th, out = start_serve(prefix, AnalysisConfig(**RUN_CFG), scfg)

    def observed(n):
        wait_for(lambda: out.get("error") or drv.slo.windows_observed >= n,
                 msg=f"{n} windows observed")
        if "error" in out:
            raise out["error"]

    try:
        addr = tuple(drv.listeners.listeners[0].address)
        send_tcp(addr, lines[:WL])
        observed(1)
        assert drv.slo.breaches_total == 0
        # 200 lines dropped, WL more accepted: window 1 closes lossy
        with faults.armed(faults.FaultPlan.parse("listener.drop@1:200")):
            send_tcp(addr, lines[WL:2 * WL + 200])
            observed(2)
        assert drv.slo.breaches_total == 1, drv.slo.gauges()
        assert drv.slo.gauges()["slo_breached"] == 1
        assert drv.lineage_record(1)["hosts"][0]["drops"] == 200
        for w in range(3):
            send_tcp(addr, lines[:WL])
            observed(3 + w)
        assert drv.slo.recoveries_total == 1, drv.slo.gauges()
        assert drv.slo.gauges()["slo_breached"] == 0
        assert drv.slo.breaches_total == 1
    finally:
        drv.stop()
    finish(th, out)


def test_serve_lineage_disarmed_has_no_plane(corpus, tmp_path):
    packed, prefix, lines, td = corpus
    scfg = ServeConfig(
        listen=("tcp:127.0.0.1:0",), window_lines=WL, ring=4,
        serve_dir=str(tmp_path / "serve"), max_windows=1, stop_after_sec=60,
        reload_watch=False, checkpoint_every_windows=0, http="off",
        queue_lines=10_000, lineage=False,
    )
    drv, th, out = start_serve(prefix, AnalysisConfig(**RUN_CFG), scfg)
    send_tcp(tuple(drv.listeners.listeners[0].address), lines[:WL])
    finish(th, out)
    sd = scfg.serve_dir
    assert not os.path.exists(os.path.join(sd, LineageLog.NAME))
    with open(os.path.join(sd, "window-000000.json")) as f:
        rep = json.load(f)
    assert "lineage" not in rep["totals"]
    assert "lineage_records_total" not in drv.metrics_gauges()


def test_tenant_lineage_shared_ledger_and_routes(tmp_path):
    """Two tenants through one process: the shared ledger carries
    tenant-keyed records (the WAL record-v2 idiom applied to
    provenance), and /lineage + /t/<name>/lineage serve them."""
    from ruleset_analysis_tpu.runtime.tenantserve import TenantServeDriver

    tenants = {}
    for i in range(2):
        cfg_text = synth.synth_config(
            n_acls=2, rules_per_acl=6 + i, seed=10 + i, v6_fraction=0.0
        )
        rs = aclparse.parse_asa_config(cfg_text, f"fw{i}")
        packed = pack.pack_rulesets([rs])
        prefix = os.path.join(str(tmp_path), f"rules{i}")
        pack.save_packed(packed, prefix)
        t = synth.synth_tuples(packed, 100, seed=20 + i)
        tenants[f"t{i}"] = (prefix, synth.render_syslog(packed, t, seed=30 + i))
    manifest = os.path.join(str(tmp_path), "manifest.json")
    with open(manifest, "w", encoding="utf-8") as f:
        json.dump({"tenants": [
            {"name": n, "ruleset": p, "listen": ["tcp:127.0.0.1:0"]}
            for n, (p, _) in sorted(tenants.items())
        ]}, f)
    scfg = ServeConfig(
        listen=(), window_lines=100, ring=8,
        serve_dir=os.path.join(str(tmp_path), "serve"),
        http="127.0.0.1:0", checkpoint_every_windows=0,
        slo="drop_rate<=0.5",
    )
    drv = TenantServeDriver(manifest, AnalysisConfig(**RUN_CFG), scfg)
    out: dict = {}

    def runner():
        try:
            out["summary"] = drv.run()
        except BaseException as e:
            out["error"] = e

    th = threading.Thread(target=runner)
    th.start()
    wait_for(
        lambda: out.get("error") or (
            drv.listeners.alive() == 2 and drv.http_address
        ),
        msg="tenant listeners",
    )
    try:
        if "error" in out:
            raise out["error"]
        by_tenant = {
            ln.q.tenant: tuple(ln.address) for ln in drv.listeners.listeners
        }
        for n, (_, tlines) in tenants.items():
            send_tcp(by_tenant[n], tlines)
        wait_for(
            lambda: out.get("error") or drv.windows_published >= 2,
            timeout=120, msg="one window per tenant",
        )
        # windows_published increments before the publish phase ends —
        # wait for both lanes' lineage appends to land
        wait_for(
            lambda: out.get("error") or drv.lineage_records_total >= 2,
            msg="tenant lineage records",
        )
        if "error" in out:
            raise out["error"]
        http = drv.http_address
        tail = get_json(http, "/lineage")
        assert tail["records_total"] == 2
        assert set(tail["tenants"]) == {"t0", "t1"}
        for n in ("t0", "t1"):
            recs = tail["tenants"][n]
            assert [r["window"] for r in recs] == [0]
            assert recs[0]["kind"] == "tenant" and recs[0]["tenant"] == n
            assert seal_lineage(dict(recs[0]))["crc"] == recs[0]["crc"]
            sub = get_json(http, f"/t/{n}/lineage")
            assert sub["records"] == recs
        m = get_json(http, "/metrics")
        assert m["lineage_records_total"] == 2
        assert m["slo_objectives"] == 1 and m["slo_breached"] == 0
        assert m["build_info"]["version"]
        host, port = http
        with urllib.request.urlopen(
            f"http://{host}:{port}/metrics?format=prom", timeout=10
        ) as r:
            prom = r.read().decode()
        assert "ra_build_info{" in prom
        assert 'ra_serve_slo_bound{objective="drop_rate"} 0.5' in prom
    finally:
        drv.stop()
    finish(th, out)
    # one shared ledger, tenant-keyed, beside the per-tenant report trees
    ledger = LineageLog.read(os.path.join(scfg.serve_dir, LineageLog.NAME))
    assert sorted(r["tenant"] for r in ledger) == ["t0", "t1"]
    for n in ("t0", "t1"):
        with open(os.path.join(
            scfg.serve_dir, "t", n, "window-000000.json"
        )) as f:
            rep = json.load(f)
        lin = rep["totals"]["lineage"]
        assert lin == next(r for r in ledger if r["tenant"] == n)


def test_serve_lineage_append_chaos_never_publishes_without_record(
    corpus, tmp_path
):
    packed, prefix, lines, td = corpus
    scfg = ServeConfig(
        listen=("tcp:127.0.0.1:0",), window_lines=WL, ring=4,
        serve_dir=str(tmp_path / "serve"), max_windows=2, stop_after_sec=60,
        reload_watch=False, checkpoint_every_windows=0, http="off",
        queue_lines=10_000,
    )
    with faults.armed(faults.FaultPlan.parse("lineage.append@1")):
        drv, th, out = start_serve(prefix, AnalysisConfig(**RUN_CFG), scfg)
        send_tcp(tuple(drv.listeners.listeners[0].address), lines[:WL])
        with pytest.raises(AnalysisError):
            finish(th, out)
    sd = scfg.serve_dir
    # typed abort BEFORE the window file: no window published without
    # its provenance record, and the ledger is readable (never torn)
    assert not os.path.exists(os.path.join(sd, "window-000000.json"))
    assert not os.path.exists(os.path.join(sd, "latest.json"))
    assert LineageLog.read(os.path.join(sd, LineageLog.NAME)) == []
