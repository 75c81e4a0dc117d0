"""Pipelined ingest engine (runtime/ingest.py).

The prefetch driver must be BIT-IDENTICAL to the synchronous driver on
the full report — per-rule hits, unused set, talkers, totals — across
flat/stacked x text/wire x v4/v6, because batches commit in source order
and the side effects of producing a batch (counters, staged v6 rows,
elastic cursors) only land when the driver consumes it.  Failure modes
must be typed and prompt: a dead feed worker or a producer exception
surfaces at the consumer's next pull, never as a hang.
"""

import json
import random

import numpy as np
import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.errors import FeedWorkerError, ResumeInputMismatch
from ruleset_analysis_tpu.hostside import aclparse, fastparse, pack, synth
from ruleset_analysis_tpu.hostside import wire as wire_mod
from ruleset_analysis_tpu.runtime import stream
from ruleset_analysis_tpu.runtime.ingest import PrefetchingSource
from ruleset_analysis_tpu.runtime.stream import (
    _TextSource,
    run_stream_file,
    run_stream_packed,
    run_stream_wire,
)

#: totals keys that legitimately differ run to run (timings); everything
#: else in the report must match bit for bit
# ONE volatile-keys list (runtime/report.py): the registry auditor
# (verify/registry.py) flags any module keeping a private copy.
from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS as VOLATILE


def report_image(rep) -> dict:
    j = json.loads(rep.to_json())
    for k in VOLATILE:
        j["totals"].pop(k, None)
    return j


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


def _mixed_lines(n, seed=0, v6_share=0.35):
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
def corpus4(tmp_path_factory):
    """v4-only synth corpus, one text file."""
    td = tmp_path_factory.mktemp("ingest4")
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=8, seed=41)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 5000, seed=42)
    lines = synth.render_syslog(packed, tuples, seed=43)
    p = td / "v4.log"
    p.write_text("\n".join(lines) + "\n", encoding="utf-8")
    return packed, str(p)


@pytest.fixture(scope="module")
def corpus6(tmp_path_factory):
    """Mixed v4+v6 corpus against a unified ruleset."""
    td = tmp_path_factory.mktemp("ingest6")
    rs = aclparse.parse_asa_config(CFG6, "fw1")
    packed = pack.pack_rulesets([rs])
    p = td / "v6.log"
    p.write_text("\n".join(_mixed_lines(4000, seed=7)) + "\n", encoding="utf-8")
    return packed, str(p)


def _cfg(depth, layout="flat", **kw):
    return AnalysisConfig(
        batch_size=512,
        sketch=SketchConfig(cms_width=1 << 10, cms_depth=2, hll_p=6),
        prefetch_depth=depth,
        layout=layout,
        **kw,
    )


@pytest.mark.parametrize("layout", ["flat", "stacked"])
@pytest.mark.parametrize("family", ["v4", "v6"])
def test_text_prefetch_bit_identical(corpus4, corpus6, layout, family):
    packed, path = corpus4 if family == "v4" else corpus6
    sync = run_stream_file(packed, path, _cfg(0, layout), topk=5)
    pre = run_stream_file(packed, path, _cfg(3, layout), topk=5)
    assert report_image(sync) == report_image(pre)


@pytest.mark.parametrize("layout", ["flat", "stacked"])
@pytest.mark.parametrize("family", ["v4", "v6"])
def test_wire_prefetch_bit_identical(
    corpus4, corpus6, layout, family, tmp_path
):
    packed, path = corpus4 if family == "v4" else corpus6
    wp = str(tmp_path / "c.rawire")
    wire_mod.convert_logs(packed, [path], wp, batch_size=512, block_rows=512)
    sync = run_stream_wire(packed, wp, _cfg(0, layout), topk=5)
    pre = run_stream_wire(packed, wp, _cfg(2, layout), topk=5)
    assert report_image(sync) == report_image(pre)


@pytest.mark.parametrize("family", ["v4", "v6"])
def test_text_prefetch_coalesced_bit_identical(corpus4, corpus6, family):
    """Prefetch + flow coalescing still commits in source order: the
    coalesced pack stage runs on the producer thread, and the report is
    bit-identical to the synchronous UNcoalesced driver (ISSUE 5)."""
    packed, path = corpus4 if family == "v4" else corpus6
    sync = run_stream_file(packed, path, _cfg(0), topk=5)
    co = run_stream_file(packed, path, _cfg(3, coalesce="on"), topk=5)
    assert report_image(sync) == report_image(co)


def test_python_parser_prefetch_bit_identical(corpus4):
    packed, path = corpus4
    sync = run_stream_file(packed, path, _cfg(0), topk=5, native=False)
    pre = run_stream_file(packed, path, _cfg(2), topk=5, native=False)
    assert report_image(sync) == report_image(pre)


def test_packed_source_prefetch_bit_identical(corpus4):
    packed, _path = corpus4
    feeds = [
        np.ascontiguousarray(synth.synth_tuples(packed, 700, seed=i).T)
        for i in range(5)
    ]
    sync = run_stream_packed(packed, iter(feeds), _cfg(0), topk=5)
    pre = run_stream_packed(packed, iter(feeds), _cfg(3), topk=5)
    assert report_image(sync) == report_image(pre)


def test_ingest_stats_reported(corpus4):
    packed, path = corpus4
    rep = run_stream_file(packed, path, _cfg(3), topk=5)
    ing = rep.totals["ingest"]
    assert ing["prefetch_depth"] == 3
    assert ing["batches"] == rep.totals["chunks"]
    assert "compile" in rep.totals
    assert "sustained_lines_per_sec" in rep.totals
    # synchronous runs report no ingest section at all
    rep0 = run_stream_file(packed, path, _cfg(0), topk=5)
    assert "ingest" not in rep0.totals


def test_crash_at_chunk_k_resume_under_prefetch(corpus6, tmp_path):
    """Crash simulation + resume with prefetch == uninterrupted sync run.

    The snapshot taken at a chunk boundary must cover exactly the
    committed batches — lines the producer prefetched past the crash
    point must NOT be claimed — or the resumed registers would double- or
    skip-count them.
    """
    packed, path = corpus6
    # same checkpoint cadence as the crashed run: checkpoint flushes step
    # partial v6 chunks, so cadence is part of the chunk structure
    ref = run_stream_file(
        packed,
        path,
        _cfg(0).replace(
            checkpoint_every_chunks=2, checkpoint_dir=str(tmp_path / "ref")
        ),
        topk=5,
    )

    ck = str(tmp_path / "ck")
    cfg = _cfg(3).replace(checkpoint_every_chunks=2, checkpoint_dir=ck)
    crashed = run_stream_file(packed, path, cfg, topk=5, max_chunks=3)
    assert crashed.totals["lines_total"] < ref.totals["lines_total"]
    resumed = run_stream_file(packed, path, cfg.replace(resume=True), topk=5)
    assert report_image(resumed) == report_image(ref)


def test_producer_exception_typed_not_hung(corpus4):
    """A producer-side failure re-raises, typed, at the consumer."""
    packed, path = corpus4
    src = PrefetchingSource(
        _TextSource(packed, stream._iter_files([path])), depth=2
    )
    it = src.batches(10_000_000, 512)  # skip past EOF -> ResumeInputMismatch
    with pytest.raises(ResumeInputMismatch):
        next(it)
    src.close()


@pytest.mark.skipif(
    not fastparse.available(), reason="native parser not buildable here"
)
def test_killed_feed_worker_mid_prefetch_typed_error(corpus4):
    """Killing feeder workers under the prefetch wrapper: typed, no hang."""
    from ruleset_analysis_tpu.hostside.feeder import ParallelFeeder

    packed, path = corpus4
    feeder = ParallelFeeder(packed, [path], n_workers=2)
    src = PrefetchingSource(feeder, depth=1)
    it = src.batches(0, 256)
    assert next(it) is not None  # workers are up and parsing
    for w in feeder._workers:
        w.terminate()
    with pytest.raises(FeedWorkerError):
        # bounded by the feeder's 5s liveness timeout; drain what the
        # producer managed to queue before the kill landed
        for _ in range(64):
            next(it)
    src.close()


def test_prefetch_source_commits_in_order(corpus4):
    """Counters visible on the wrapper track committed batches only."""
    packed, path = corpus4
    from ruleset_analysis_tpu.runtime.stream import _FileSource

    inner_cls = _FileSource if fastparse.available() else _TextSource
    if inner_cls is _TextSource:
        inner = _TextSource(packed, stream._iter_files([path]))
    else:
        inner = _FileSource(packed, [path])
    src = PrefetchingSource(inner, depth=4)
    it = src.batches(0, 512)
    assert src.packer.parsed == 0
    seen = 0
    parsed_after = []
    for _batch, n_raw in it:
        seen += n_raw
        parsed_after.append(src.packer.parsed)
        # committed counters never exceed what a synchronous parse of the
        # consumed batches would have produced (2x bound: dual-eval rows)
        assert src.packer.parsed <= 2 * seen
    assert seen == 5000
    assert parsed_after == sorted(parsed_after)
    src.close()
