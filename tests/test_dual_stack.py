"""A unified (dual-stack) ACL end to end, against the oracle and the
benchmark's plain reference.

A 300-ACE ruleset from the benchmark's own generator
(``benchmark/gen/rules_dual.py``: v4 and v6 ACEs interleaved in one list)
and 12,000 ``106100`` lines from ``benchmark/gen/traffic_dual.py``, at a
v6 share of 0, 0.45 and 1, through the native and the Python text
sources.  Hits and the unused list must equal ``hostside/oracle.py``'s;
unique sources and talkers must read as ``benchmark/reference_dual.py``
says they must.  The v6 counters of ``totals.ingest`` count what the
oracle saw (the talker map's inserts, its distinct v6 sources), and read 0
on a pure-v4 run.
"""

import importlib
import json
import os
import sys

import numpy as np
import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.hostside import aclparse, oracle, pack
from ruleset_analysis_tpu.runtime import obs
from ruleset_analysis_tpu.runtime.stream import run_stream, run_stream_file

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
LINES = 12_000
SHARES = [0.0, 0.45, 1.0]


@pytest.fixture(scope="module")
def bench():
    """The benchmark's generator and reference, loaded from their files."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    return (importlib.import_module("gen.rules_dual"),
            importlib.import_module("gen.traffic_dual"),
            importlib.import_module("reference_dual"))


def _json(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module", params=SHARES, ids=lambda s: f"v6share{s}")
def corpus(request, bench, tmp_path_factory):
    rules_dual, traffic_dual, reference_dual = bench
    share = request.param
    cfg = _json("configs", "asa-dual-10k.json")
    cfg.update(filters=300, v6_fraction=share)
    traffic = _json("traffic", "text-run-dual.json")
    traffic.update(v6_fraction=share)
    rs = rules_dual.make_ruleset(cfg)
    rows4, (rows6, pos6) = rules_dual.expand4(rs), rules_dual.expand6(rs)
    flows, seq = traffic_dual.make_flows(rs, rows4, rows6, traffic, 2**40 + 26, LINES)
    text = traffic_dual.render_lines(rs, flows)
    lines = [text[i] for i in seq.tolist()]
    path = str(tmp_path_factory.mktemp("dual") / "dual.log")
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    parsed = aclparse.parse_asa_config(rs.text, rs.firewall)
    packed = pack.pack_rulesets([parsed])
    res = oracle.Oracle([parsed]).consume(lines)
    ace4, ace6 = reference_dual.first_match(rows4, rows6, pos6, flows, len(rs.acls))
    mult = np.bincount(seq, minlength=flows.is6.size)
    exp = reference_dual.Expected(rs, flows, ace4, ace6, mult[~flows.is6],
                                  mult[flows.is6], cfg["analysis"]["sketch"])
    return share, cfg, packed, parsed, path, lines, res, exp


def _analysis(cfg):
    a = cfg["analysis"]
    return AnalysisConfig(batch_size=2048, exact_counts=a["exact_counts"],
                          sketch=SketchConfig(**a["sketch"]))


def _v6_lines(res) -> int:
    return sum(c for t in res.talkers.values() for (fam, _), c in t.items() if fam == 6)


def _v6_sources(res) -> int:
    return len({src for t in res.talkers.values() for fam, src in t if fam == 6})


@pytest.mark.parametrize("source", ["native", "python"])
def test_dual_stack_run_matches_oracle_and_reference(bench, corpus, source):
    reference_dual = bench[2]
    share, cfg, packed, parsed, path, lines, res, exp = corpus
    acfg = _analysis(cfg)
    rep = (run_stream_file(packed, path, acfg) if source == "native"
           else run_stream(packed, iter(lines), acfg))
    got = json.loads(rep.to_json())
    # the oracle: exact hits and the unused list
    hits = {(e["firewall"], e["acl"], e["index"]): e["hits"]
            for e in got["per_rule"] if e["hits"]}
    assert hits == dict(res.hits)
    assert [tuple(u) for u in got["unused"]] == res.unused_rules([parsed])
    assert got["totals"]["lines_total"] == res.lines_total == LINES
    # unique sources: the stated HLL over the oracle's exact source sets
    # is what the reference recomputes; the estimate stays near the count
    for e in got["per_rule"]:
        if e["hits"]:
            exact = len(res.sources[(e["firewall"], e["acl"], e["index"])])
            assert abs(e["unique_sources"] - exact) <= max(2, 0.25 * exact), e
    # talkers: each a source the oracle saw in its ACL, never above its lines
    # by more than the sketch allows (the reference's bound)
    for acl, items in got["talkers"].items():
        seen = res.talkers[tuple(acl.split(" ", 1))]
        for s, c in items:
            fam_src = reference_dual._parse(s)
            assert fam_src in seen and c >= 1, (acl, s)
    # the reference: all five numbers 0
    assert reference_dual.compare(got, exp) == dict.fromkeys(
        ("hits_wrong", "unused_wrong", "unique_wrong", "talkers_wrong", "talkers_missed"), 0)
    # the v6 counters count what the oracle saw as IPv6
    ing = got["totals"]["ingest"]
    assert ing["v6_lines"] == _v6_lines(res)
    # the talker map gained one entry per distinct v6 source (none collide
    # at this size), and nothing on a pure-v4 run
    assert ing["v6_digest_inserts"] == _v6_sources(res)
    if share:
        assert ing["v6_chunks"] >= 1 and ing["v6_stage_sec"] > 0
        assert ing["v6_plane_sec"] > 0 if source == "native" else ing["v6_plane_sec"] == 0
    else:
        assert ing["v6_lines"] == ing["v6_chunks"] == 0
        assert ing["v6_plane_sec"] == ing["v6_stage_sec"] == 0


@pytest.mark.parametrize("corpus", [0.45], indirect=True, ids=["v6share0.45"])
def test_v6_spans_sit_on_their_threads(corpus, tmp_path):
    """A profiler session holds ``ingest.v6_plane`` on the producer's
    thread and ``ingest.stage6`` (with its batch id) on the dispatching
    thread, whose ``run.call`` it lies in."""
    import glob

    import jax
    from jax.profiler import ProfileData

    _share, cfg, packed, _parsed, path, _lines, _res, _exp = corpus
    obs._reset_for_tests()
    acfg = _analysis(cfg)
    run_stream_file(packed, path, acfg)  # compile outside the session
    with jax.profiler.trace(str(tmp_path)):
        rep = run_stream_file(packed, path, acfg)
    obs._reset_for_tests()
    pb = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True),
             key=os.path.getmtime)
    where: dict[str, set] = {}  # span name -> the host lines (threads) it is on
    batches = set()
    for plane in ProfileData.from_file(pb).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name in ("run.call", "ingest.produce", "ingest.v6_plane", "ingest.stage6"):
                    where.setdefault(name, set()).add(i)
                if name == "ingest.stage6":
                    batches.add(dict(ev.stats).get("batch"))
    assert where["ingest.v6_plane"] <= where["ingest.produce"]
    assert where["ingest.stage6"] == where["run.call"]
    assert where["ingest.v6_plane"].isdisjoint(where["run.call"])
    assert batches and None not in batches
    assert rep.totals["ingest"]["v6_chunks"] >= 1
