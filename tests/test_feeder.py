"""Multi-process parse feeder: shared-memory workers == sequential path.

Chunk boundaries in feeder mode follow raw-line counts (grouped batches
are 2x wide under egress bindings instead of closing early), so the
assertions here are the boundary-invariant ones: identical registers,
per-rule hits, unused set, and counters.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.hostside import aclparse, fastparse, oracle, pack, synth, wire
from ruleset_analysis_tpu.hostside.feeder import (
    ParallelFeeder,
    RingFeeder,
    ThreadedFeeder,
    _scan_batches,
)
from ruleset_analysis_tpu.runtime.stream import (
    _FileSource,
    _WireFileSource,
    run_stream_file,
    run_stream_wire,
)

from tests.test_v6_digests import per_row_fill

pytestmark = pytest.mark.skipif(
    not fastparse.available(), reason="native parser not buildable here"
)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    td = tmp_path_factory.mktemp("feed")
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=10, seed=61, egress_acls=True)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 3000, seed=62)
    lines = synth.render_syslog(packed, tuples, seed=63, variety=0.4)
    p1 = td / "a.log"
    p1.write_text("\n".join(lines[:1700]) + "\n", encoding="utf-8")
    p2 = td / "b.log"
    p2.write_text("\n".join(lines[1700:]) + "\n", encoding="utf-8")
    res = oracle.Oracle([rs]).consume(list(lines))
    return packed, rs, [str(p1), str(p2)], res


def test_scan_batches_covers_every_line(corpus):
    packed, rs, paths, res = corpus
    descs = list(_scan_batches(paths, 256, 0))
    assert sum(d[3] for d in descs) == 3000
    # descriptors tile each file contiguously
    by_file = {}
    for path_i, off, nbytes, n in descs:
        by_file.setdefault(path_i, []).append((off, nbytes, n))
    for segs in by_file.values():
        pos = 0
        for off, nbytes, n in segs:
            assert off == pos
            assert 1 <= n <= 256
            pos = off + nbytes


def test_scan_batches_skip_lines(corpus):
    packed, rs, paths, res = corpus
    descs = list(_scan_batches(paths, 256, 500))
    assert sum(d[3] for d in descs) == 2500


def test_feeder_source_matches_sequential_counters(corpus):
    packed, rs, paths, res = corpus
    feeder = ParallelFeeder(packed, paths, n_workers=3)
    total_lines = 0
    total_valid = 0
    for batch, n_raw in feeder.batches(0, 256):
        total_lines += n_raw
        total_valid += int(batch[pack.T_VALID].sum())
    assert total_lines == 3000
    assert feeder.packer.parsed == total_valid == res.lines_matched
    assert feeder.packer.skipped == res.lines_skipped


@pytest.mark.parametrize("workers", [2, 3])
def test_feeder_report_equals_sequential(corpus, workers):
    packed, rs, paths, res = corpus
    cfg = AnalysisConfig(
        batch_size=256,
        sketch=SketchConfig(cms_width=1 << 11, cms_depth=4, hll_p=6),
    )
    seq = run_stream_file(packed, paths, cfg)
    par = run_stream_file(packed, paths, cfg, feed_workers=workers)
    hs = {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in seq.per_rule}
    hp = {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in par.per_rule}
    assert hs == hp
    assert seq.unused == par.unused
    assert seq.totals["lines_total"] == par.totals["lines_total"] == 3000
    assert seq.totals["lines_matched"] == par.totals["lines_matched"]
    # per-rule unique-source estimates come straight from the HLL
    # registers, which are order-invariant -> must agree exactly
    us = {tuple(e["key"]) if "key" in e else (e["firewall"], e["acl"], e["index"]): e.get("unique_sources")
          for e in seq.per_rule}
    up = {tuple(e["key"]) if "key" in e else (e["firewall"], e["acl"], e["index"]): e.get("unique_sources")
          for e in par.per_rule}
    assert us == up


def test_feeder_resume_checkpoint(corpus, tmp_path):
    packed, rs, paths, res = corpus
    ck = str(tmp_path / "ck")
    cfg = AnalysisConfig(
        batch_size=256,
        sketch=SketchConfig(cms_width=1 << 11, cms_depth=4, hll_p=6),
        checkpoint_every_chunks=3,
        checkpoint_dir=ck,
    )
    # crash after 5 chunks (max_chunks), then resume with the feeder
    run_stream_file(packed, paths, cfg, feed_workers=2, max_chunks=5)
    rep = run_stream_file(packed, paths, cfg.replace(resume=True), feed_workers=2)
    full = run_stream_file(packed, paths, cfg.replace(checkpoint_every_chunks=0))
    hr = {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in rep.per_rule}
    hf = {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in full.per_rule}
    assert hr == hf
    assert rep.totals["lines_total"] == 3000


def test_killed_worker_detected_not_hung(corpus):
    """An OS-killed worker (OOM analog) must surface as RuntimeError via
    the liveness timeout, not hang the coordinator on done_q forever."""
    import os
    import signal

    packed, rs, paths, res = corpus
    feeder = ParallelFeeder(packed, paths, n_workers=1)
    gen = feeder.batches(0, 64)  # 3000 lines -> ~47 batches, plenty left
    next(gen)
    os.kill(feeder._workers[0].pid, signal.SIGKILL)
    with pytest.raises(RuntimeError, match="died without reporting"):
        for _ in gen:
            pass


# ---------------------------------------------------------------------------
# threaded tier: same descriptors, same in-order commit, no processes
# ---------------------------------------------------------------------------


def test_threaded_feeder_report_equals_process_tier(corpus):
    """Thread and process tiers chop identical descriptors, so the FULL
    report — including chunk-boundary-sensitive top-K candidates — must
    match between them (not just the order-invariant registers)."""
    import json

    packed, rs, paths, res = corpus
    cfg = AnalysisConfig(
        batch_size=256,
        sketch=SketchConfig(cms_width=1 << 11, cms_depth=4, hll_p=6),
    )
    thr = run_stream_file(packed, paths, cfg, feed_workers=3, feed_mode="thread")
    prc = run_stream_file(packed, paths, cfg, feed_workers=3, feed_mode="process")
    jt, jp = json.loads(thr.to_json()), json.loads(prc.to_json())
    from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS

    for k in VOLATILE_TOTALS:
        jt["totals"].pop(k, None)
        jp["totals"].pop(k, None)
    assert jt == jp
    assert thr.totals["lines_matched"] == res.lines_matched


def test_threaded_feeder_registers_equal_sequential(corpus):
    packed, rs, paths, res = corpus
    cfg = AnalysisConfig(
        batch_size=256,
        sketch=SketchConfig(cms_width=1 << 11, cms_depth=4, hll_p=6),
    )
    seq = run_stream_file(packed, paths, cfg)
    thr = run_stream_file(packed, paths, cfg, feed_workers=2, feed_mode="thread")
    hs = {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in seq.per_rule}
    ht = {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in thr.per_rule}
    assert hs == ht
    assert seq.unused == thr.unused
    assert thr.totals["lines_total"] == 3000
    assert thr.totals["lines_matched"] == res.lines_matched


# ---------------------------------------------------------------------------
# v6 plane: both feed tiers carry the SAME evaluation-row streams, byte
# for byte, as the sequential native parse (VERDICT Missing #4 closure)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def corpus6(tmp_path_factory):
    td = tmp_path_factory.mktemp("feed6")
    cfg_text = synth.synth_config(
        n_acls=2, rules_per_acl=8, seed=77, v6_fraction=0.5
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    n4, n6 = 1600, 1400
    lines = synth.render_syslog(
        packed, synth.synth_tuples(packed, n4, seed=78), seed=79
    )
    lines += synth.render_syslog6(
        packed, synth.synth_tuples6(packed, n6, seed=80), seed=81
    )
    import random

    random.Random(7).shuffle(lines)
    p = td / "mixed.log"
    p.write_text("\n".join(lines) + "\n", encoding="utf-8")
    assert packed.has_v6
    return packed, [str(p)]


def _row_streams(batches_it, take_v6):
    """(v4 valid-row stream, v6 row stream) concatenated over all batches."""
    v4, v6 = [], []
    for batch, _n in batches_it:
        v4.append(batch[:, batch[pack.T_VALID] == 1].copy())
        rows6 = take_v6()
        if len(rows6):
            v6.append(np.asarray(rows6, dtype=np.uint32))
    cat4 = np.concatenate(v4, axis=1) if v4 else np.zeros((pack.TUPLE_COLS, 0))
    cat6 = (
        np.concatenate(v6) if v6 else np.zeros((0, pack.TUPLE6_COLS))
    )
    return cat4, cat6


@pytest.mark.parametrize("tier", ["process", "thread", "ring"])
def test_feeder_v6_plane_byte_identical_to_sequential(corpus6, tier):
    packed, paths = corpus6
    packer = fastparse.NativePacker(packed)
    seq4, seq6 = _row_streams(
        fastparse.batches_from_files(paths, packer, 256), packer.take_v6
    )
    assert seq6.shape[0] > 0  # the corpus genuinely exercises the plane
    feeder_cls = {
        "process": ParallelFeeder, "thread": ThreadedFeeder,
        "ring": RingFeeder,
    }[tier]
    if tier == "ring":
        # 13-tuple v6 staging rides the per-chip rings (ISSUE 11): the
        # committed stream must still be the sequential parse's, byte
        # for byte, in line order across ring partitions
        feeder = feeder_cls(packed, paths, n_workers=2, n_rings=8)
    else:
        feeder = feeder_cls(packed, paths, n_workers=2)
    par4, par6 = _row_streams(feeder.batches(0, 256), feeder.take_v6)
    assert np.array_equal(seq4, par4)
    assert np.array_equal(seq6, par6)
    # capped digest->address map: same rows in the same stream order ->
    # identical first-seen winners, in the same order
    want: dict[int, int] = {}
    per_row_fill(want, seq6)
    assert list(feeder.v6_digests.items()) == list(want.items())


def test_v6_digest_map_same_on_native_and_wire_paths(corpus6, tmp_path):
    """The text-native source and the wire's v6 section fill the talker
    map as the feeders do (above): what the per-row loop gives over the
    sequential parse's v6 rows.  ``totals.ingest.v6_digest_inserts``
    counts its entries on each path: one per distinct v6 source."""
    packed, paths = corpus6
    packer = fastparse.NativePacker(packed)
    _seq4, seq6 = _row_streams(
        fastparse.batches_from_files(paths, packer, 256), packer.take_v6
    )
    want: dict[int, int] = {}
    per_row_fill(want, seq6)
    assert want and len(want) == len({tuple(r) for r in seq6[:, pack.T6_SRC:pack.T6_SRC + 4].tolist()})

    native = _FileSource(packed, paths)
    for _batch in native.batches(0, 256):
        native.take_v6()
    out = str(tmp_path / "mixed.rawire")
    wire.convert_logs(packed, paths, out)
    wired = _WireFileSource(packed, [out])
    for _batch in wired.batches(0, 256):
        pass
    for _batch in wired.batches6(0, 256):
        pass
    wired.close()
    for dig in (native.v6_digests, wired.v6_digests):
        assert list(dig.items()) == list(want.items())

    cfg = AnalysisConfig(
        batch_size=256, sketch=SketchConfig(cms_width=1 << 11, cms_depth=4, hll_p=6)
    )
    for rep in (run_stream_file(packed, paths, cfg),
                run_stream_file(packed, paths, cfg, feed_workers=2, feed_mode="thread"),
                run_stream_wire(packed, out, cfg)):
        assert rep.totals["ingest"]["v6_digest_inserts"] == len(want)


# ---------------------------------------------------------------------------
# per-chip feeder rings (ISSUE 11): one shared-memory ring per device,
# producer pool partitioned by chip, per-chip device_put fed straight
# from the rings — reports bit-identical to the global-queue tier
# ---------------------------------------------------------------------------


def _stripped(rep):
    import json

    from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS

    j = json.loads(rep.to_json())
    for k in VOLATILE_TOTALS:
        j["totals"].pop(k, None)
    return j


def test_ring_feeder_report_equals_queue_tier(corpus):
    """Full report — including the chunk-boundary-sensitive top-K
    talkers — must match the global-queue process tier, under BOTH the
    prefetched per-chip-device_put path and the sync assembled path
    (ring groups cover exactly the lines queue batches cover, and every
    register update is padding/order-invariant)."""
    packed, rs, paths, res = corpus
    cfg = AnalysisConfig(
        batch_size=256,
        sketch=SketchConfig(cms_width=1 << 11, cms_depth=4, hll_p=6),
    )
    queue_rep = _stripped(
        run_stream_file(packed, paths, cfg, feed_workers=2, feed_mode="process")
    )
    ring_prefetch = _stripped(
        run_stream_file(packed, paths, cfg, feed_workers=2, feed_mode="ring")
    )
    ring_sync = _stripped(
        run_stream_file(
            packed, paths, cfg.replace(prefetch_depth=0),
            feed_workers=2, feed_mode="ring",
        )
    )
    assert ring_prefetch == queue_rep
    assert ring_sync == queue_rep


def test_ring_feeder_resume_checkpoint(corpus, tmp_path):
    """Crash-at-K resume through the ring plane: registers, per-rule
    hits, HLL uniques, and the unused set must equal an uninterrupted
    queue-tier run (chunk counts may differ — a snapshot flushes partial
    v6 buffers — so the pin is register-level, exactly like the other
    feeder tiers' resume contract)."""
    packed, rs, paths, res = corpus
    ck = str(tmp_path / "ck")
    cfg = AnalysisConfig(
        batch_size=256,
        sketch=SketchConfig(cms_width=1 << 11, cms_depth=4, hll_p=6),
        checkpoint_every_chunks=3,
        checkpoint_dir=ck,
    )
    run_stream_file(packed, paths, cfg, feed_workers=2, feed_mode="ring",
                    max_chunks=5)
    rep = run_stream_file(
        packed, paths, cfg.replace(resume=True), feed_workers=2,
        feed_mode="ring",
    )
    full = run_stream_file(
        packed, paths, cfg.replace(checkpoint_every_chunks=0),
        feed_workers=2, feed_mode="process",
    )
    hr = {(e["firewall"], e["acl"], e["index"]): (e["hits"], e.get("unique_sources"))
          for e in rep.per_rule}
    hf = {(e["firewall"], e["acl"], e["index"]): (e["hits"], e.get("unique_sources"))
          for e in full.per_rule}
    assert hr == hf
    assert rep.unused == full.unused
    assert rep.totals["lines_total"] == 3000
    assert rep.totals["lines_matched"] == full.totals["lines_matched"]


def test_ring_feeder_killed_worker_detected_not_hung(corpus):
    """An OS-killed ring worker must surface as a typed FeedWorkerError
    via the liveness probe — one starved chip must never hang the run."""
    import os
    import signal

    packed, rs, paths, res = corpus
    feeder = RingFeeder(packed, paths, n_workers=2, n_rings=8)
    gen = feeder.batches(0, 256)
    next(gen)
    os.kill(feeder._workers[0].pid, signal.SIGKILL)
    with pytest.raises(RuntimeError, match="died without reporting"):
        for _ in gen:
            pass


def test_ring_feeder_rejects_runtime_coalesce(corpus):
    from ruleset_analysis_tpu.errors import AnalysisError

    packed, rs, paths, res = corpus
    cfg = AnalysisConfig(
        batch_size=256,
        sketch=SketchConfig(cms_width=1 << 11, cms_depth=4, hll_p=6),
        coalesce="on",
    )
    with pytest.raises(AnalysisError, match="convert --coalesce"):
        run_stream_file(packed, paths, cfg, feed_workers=2, feed_mode="ring")


def test_ring_feeder_gauges_cover_every_ring(corpus, tmp_path):
    """The metrics sampler must expose per-ring occupancy, partition
    imbalance, and starved-chip seconds — the gauges the trace_summary
    feed block renders."""
    packed, rs, paths, res = corpus
    from ruleset_analysis_tpu.runtime import obs

    obs.start_metrics(str(tmp_path / "m.jsonl"), every_sec=60.0)
    seen = {}
    try:
        feeder = RingFeeder(packed, paths, n_workers=2, n_rings=4)
        gen = feeder.batches(0, 256)
        try:
            for i, _ in enumerate(gen):
                if i == 2:
                    seen = dict(obs.metrics_snapshot().get("feeder", {}))
                    break
        finally:
            gen.close()
    finally:
        obs.shutdown()
    assert seen.get("mode") == "ring"
    assert seen.get("rings") == 4
    assert len(seen.get("ring_occupancy", [])) == 4
    assert len(seen.get("starved_sec", [])) == 4
    assert "partition_imbalance" in seen


def test_ring_feeder_summary_instant_feeds_trace_summary(corpus, tmp_path):
    """A ring run under --trace-out leaves one feeder.summary instant;
    tools/trace_summary.py renders it as the feed block."""
    import sys

    from ruleset_analysis_tpu.runtime import obs

    sys.path.insert(0, "tools")
    try:
        import trace_summary
    finally:
        sys.path.pop(0)
    packed, rs, paths, res = corpus
    td = str(tmp_path / "tr")
    obs.start_trace(td, role="main")
    try:
        feeder = RingFeeder(packed, paths, n_workers=2, n_rings=4)
        for _ in feeder.batches(0, 256):
            pass
    finally:
        merged = obs.merge_trace(td)
        obs.shutdown()
    s = trace_summary.summarize(merged)
    feed = s.get("feed")
    assert feed and feed["mode"] == "ring" and feed["rings"] == 4
    assert len(feed["ring_occupancy_pct"]) == 4
    assert len(feed["starved_sec"]) == 4
    assert "partition_imbalance_pct" in feed
    text = trace_summary.render(s)
    assert "feed: ring x4" in text and "ring 0:" in text


def test_ring_feeder_requires_workers_at_api_level(corpus):
    """feed_mode='ring' without feed_workers must be a typed refusal at
    the run_stream_file API, not a silent fall-through to the plain
    source (the requested topology would otherwise be dropped)."""
    from ruleset_analysis_tpu.errors import AnalysisError

    packed, rs, paths, res = corpus
    cfg = AnalysisConfig(
        batch_size=256,
        sketch=SketchConfig(cms_width=1 << 11, cms_depth=4, hll_p=6),
    )
    with pytest.raises(AnalysisError, match="feed_workers"):
        run_stream_file(packed, paths, cfg, feed_mode="ring")


def test_ring_feeder_slot_exhaustion_aborts_typed(corpus):
    """A consumer that hoards unreleased _RingBatch views past the ring
    depth must get a typed FeedWorkerError, never a silently truncated
    corpus (the generator cannot make progress while every slot of a
    ring is held)."""
    from ruleset_analysis_tpu.errors import FeedWorkerError

    packed, rs, paths, res = corpus
    feeder = RingFeeder(
        packed, paths, n_workers=2, n_rings=2, ring_depth=2
    )
    feeder.emit_views = True
    held = []
    gen = feeder.batches(0, 256)
    try:
        with pytest.raises(FeedWorkerError, match="ring slots exhausted"):
            for rb, _n in gen:
                held.append(rb)  # never released: slots run dry
    finally:
        for rb in held:
            rb.release()
        gen.close()
