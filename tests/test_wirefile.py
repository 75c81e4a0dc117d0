"""Packed ingest tier: .rawire on-disk wire format (SURVEY.md §8.2).

The production pre-tokenized input path: `convert` writes evaluation rows
once, `run` feeds the device from the mmap'd file.  The contract under
test: a wire run is bit-identical to the text run that produced the file —
same per-rule counts, same unused set, same raw-line totals."""

import numpy as np
import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.hostside import aclparse, pack, synth, wire
from ruleset_analysis_tpu.runtime import checkpoint as ckpt
from ruleset_analysis_tpu.runtime.stream import (
    run_stream,
    run_stream_file,
    run_stream_wire,
)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("wire-corpus")
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=10, seed=77)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 2500, seed=77)
    lines = synth.render_syslog(packed, tuples, seed=77)
    log = tmp / "fw1.log"
    log.write_text("\n".join(lines) + "\n")
    return packed, rs, [str(log)], lines


def make_cfg(**kw):
    kw.setdefault("batch_size", 256)
    kw.setdefault("sketch", SketchConfig(cms_width=1 << 11, cms_depth=4, hll_p=6))
    return AnalysisConfig(backend="tpu", **kw)


def hits_of(rep):
    return {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in rep.per_rule}


@pytest.fixture(scope="module")
def wire_path(corpus, tmp_path_factory):
    packed, _rs, logs, _lines = corpus
    out = tmp_path_factory.mktemp("wire-out") / "fw1.rawire"
    stats = wire.convert_logs(packed, logs, str(out), block_rows=128)
    assert stats["rows"] == stats["evals"] > 0
    assert stats["raw_lines"] == len(corpus[3])
    return str(out)


def test_wire_file_sniff_and_header(corpus, wire_path):
    packed = corpus[0]
    assert wire.is_wire_file(wire_path)
    assert not wire.is_wire_file(corpus[2][0])  # the text log
    r = wire.WireReader([wire_path], packed)
    assert r.n_rows == r.n_evals
    assert r.raw_lines == len(corpus[3])
    assert r.raw_lines == r.n_skipped + (r.n_evals - _dual_evals(corpus))
    r.close()


def _dual_evals(corpus) -> int:
    # corpus has no out-direction bindings -> every eval is its own line
    packed = corpus[0]
    assert not packed.bindings_out
    return 0


def test_wire_run_bit_identical_to_text_run(corpus, wire_path):
    packed, _rs, logs, _lines = corpus
    ref = run_stream_file(packed, logs, make_cfg(), topk=5)
    rep = run_stream_wire(packed, wire_path, make_cfg(), topk=5)
    assert hits_of(rep) == hits_of(ref)
    assert rep.unused == ref.unused
    # raw-line accounting restored from the converter's header
    assert rep.totals["lines_total"] == ref.totals["lines_total"]
    assert rep.totals["lines_matched"] == ref.totals["lines_matched"]
    assert rep.totals["lines_skipped"] == ref.totals["lines_skipped"]
    # per-rule unique-source estimates ride the same registers
    us_ref = {e["index"]: e.get("unique_sources") for e in ref.per_rule}
    us_rep = {e["index"]: e.get("unique_sources") for e in rep.per_rule}
    assert us_ref == us_rep


@pytest.mark.parametrize("batch_size", [96, 128, 333])
def test_wire_rechunk_invariance(corpus, wire_path, batch_size):
    """Any run batch size over any block size yields identical registers."""
    packed = corpus[0]
    ref = run_stream_wire(packed, wire_path, make_cfg(), topk=5)
    rep = run_stream_wire(packed, wire_path, make_cfg(batch_size=batch_size), topk=5)
    assert hits_of(rep) == hits_of(ref)
    assert rep.unused == ref.unused
    assert rep.totals["lines_total"] == ref.totals["lines_total"]


def test_wire_multiple_files_concatenate(corpus, tmp_path):
    packed, _rs, logs, lines = corpus
    mid = len(lines) // 2
    a, b = tmp_path / "a.log", tmp_path / "b.log"
    a.write_text("\n".join(lines[:mid]) + "\n")
    b.write_text("\n".join(lines[mid:]) + "\n")
    wa, wb = str(tmp_path / "a.rawire"), str(tmp_path / "b.rawire")
    wire.convert_logs(packed, [str(a)], wa, block_rows=64)
    wire.convert_logs(packed, [str(b)], wb, block_rows=96)
    one = str(tmp_path / "all.rawire")
    wire.convert_logs(packed, logs, one, block_rows=128)
    rep_two = run_stream_wire(packed, [wa, wb], make_cfg(), topk=5)
    rep_one = run_stream_wire(packed, one, make_cfg(), topk=5)
    assert hits_of(rep_two) == hits_of(rep_one)
    assert rep_two.totals["lines_total"] == rep_one.totals["lines_total"]


def test_wire_fingerprint_mismatch_refused(corpus, wire_path):
    other_cfg = synth.synth_config(n_acls=2, rules_per_acl=6, seed=5)
    other = pack.pack_rulesets([aclparse.parse_asa_config(other_cfg, "fw1")])
    with pytest.raises(wire.WireFormatError, match="different ruleset"):
        wire.WireReader([wire_path], other)


def test_wire_truncated_refused(wire_path, tmp_path, corpus):
    data = open(wire_path, "rb").read()
    bad = tmp_path / "trunc.rawire"
    bad.write_bytes(data[: len(data) - 17])
    with pytest.raises(wire.WireFormatError, match="truncated"):
        wire.WireReader([str(bad)], corpus[0])


def test_wire_bad_magic_refused(tmp_path, corpus):
    p = tmp_path / "not.rawire"
    p.write_bytes(b"definitely not a wire file")
    with pytest.raises(wire.WireFormatError, match="magic"):
        wire.WireReader([str(p)], corpus[0])


def test_wire_checkpoint_crash_resume_bit_identical(corpus, wire_path, tmp_path):
    packed = corpus[0]
    ref = run_stream_wire(packed, wire_path, make_cfg(), topk=5)
    ck = dict(checkpoint_every_chunks=2, checkpoint_dir=str(tmp_path / "ck"))
    run_stream_wire(packed, wire_path, make_cfg(**ck), topk=5, max_chunks=3)
    snap = ckpt.load(str(tmp_path / "ck"))
    assert snap is not None and snap.fingerprint.endswith("-wire")
    rep = run_stream_wire(packed, wire_path, make_cfg(**ck, resume=True), topk=5)
    assert hits_of(rep) == hits_of(ref)
    assert rep.unused == ref.unused
    assert rep.totals["lines_matched"] == ref.totals["lines_matched"]
    assert rep.totals["lines_total"] == ref.totals["lines_total"]


def test_text_snapshot_cannot_resume_wire_input(corpus, wire_path, tmp_path):
    """Offsets count raw lines on the text path but rows on the wire path —
    cross-resume must be refused, not silently misaligned."""
    packed, _rs, logs, _lines = corpus
    ck = dict(checkpoint_every_chunks=2, checkpoint_dir=str(tmp_path / "ck"))
    run_stream_file(packed, logs, make_cfg(**ck), topk=5, max_chunks=3)
    with pytest.raises(ckpt.CheckpointMismatch):
        run_stream_wire(packed, wire_path, make_cfg(**ck, resume=True), topk=5)


def test_wire_stacked_layout_parity(corpus, wire_path):
    packed = corpus[0]
    ref = run_stream_wire(packed, wire_path, make_cfg(), topk=5)
    rep = run_stream_wire(
        packed, wire_path, make_cfg(layout="stacked", stacked_lane=64), topk=5
    )
    assert hits_of(rep) == hits_of(ref)
    assert rep.unused == ref.unused


def test_wire_dual_eval_corpus_counts_identical(tmp_path):
    """With out-direction bindings a line can emit two evaluation rows;
    registers and counts still match the text path exactly."""
    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=8, seed=9, egress_acls=True)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    assert packed.bindings_out, "corpus must exercise dual evaluation"
    tuples = synth.synth_tuples(packed, 1200, seed=9)
    lines = synth.render_syslog(packed, tuples, seed=9)
    log = tmp_path / "fw1.log"
    log.write_text("\n".join(lines) + "\n")
    out = str(tmp_path / "fw1.rawire")
    stats = wire.convert_logs(packed, [str(log)], out, block_rows=128)
    assert stats["raw_lines"] == len(lines)
    ref = run_stream_file(packed, [str(log)], make_cfg(), topk=5)
    rep = run_stream_wire(packed, out, make_cfg(), topk=5)
    assert hits_of(rep) == hits_of(ref)
    assert rep.unused == ref.unused
    assert rep.totals["lines_total"] == ref.totals["lines_total"]
    assert rep.totals["lines_matched"] == ref.totals["lines_matched"]
    assert rep.totals["lines_skipped"] == ref.totals["lines_skipped"]


def test_wire_reader_zero_copy_alignment(corpus, wire_path):
    """block_rows == batch_size batches come straight off the mmap."""
    packed = corpus[0]
    r = wire.WireReader([wire_path], packed)
    views = 0
    for batch, n in r.iter_batches(0, 128):  # file written with block_rows=128
        assert batch.shape == (pack.WIRE_COLS, 128)
        if not batch.flags.owndata and not batch.flags.writeable:
            views += 1
    # every full block except possibly the tail must be zero-copy
    assert views >= (r.n_rows // 128) - 1
    r.close()


def test_cli_convert_and_packed_run(corpus, tmp_path, capsys):
    from ruleset_analysis_tpu.cli import main

    packed, _rs, logs, _lines = corpus
    prefix = str(tmp_path / "rs")
    pack.save_packed(packed, prefix)
    out = str(tmp_path / "logs.rawire")
    rc = main(["convert", "--ruleset", prefix, "--logs", *logs, "--out", out])
    assert rc == 0
    rc = main(
        ["run", "--ruleset", prefix, "--logs", out, "--packed-input", "--json",
         "--batch-size", "256"]
    )
    assert rc == 0
    import json

    rep = json.loads(capsys.readouterr().out)
    ref = run_stream_file(packed, logs, make_cfg(batch_size=256,
        sketch=SketchConfig(cms_width=1 << 14, cms_depth=4, hll_p=8)), topk=10)
    got = {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in rep["per_rule"]}
    exp = {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in ref.per_rule}
    assert got == exp
    assert rep["totals"]["lines_total"] == ref.totals["lines_total"]


def test_cli_mixed_inputs_refused(corpus, tmp_path, capsys):
    from ruleset_analysis_tpu.cli import main

    packed, _rs, logs, _lines = corpus
    prefix = str(tmp_path / "rs")
    pack.save_packed(packed, prefix)
    out = str(tmp_path / "logs.rawire")
    assert main(["convert", "--ruleset", prefix, "--logs", *logs, "--out", out]) == 0
    rc = main(["run", "--ruleset", prefix, "--logs", out, *logs])
    assert rc == 2
    assert "mix" in capsys.readouterr().err


def test_aborted_convert_leaves_refused_file(corpus, tmp_path):
    """A crashed/aborted convert must leave a file readers refuse outright,
    not one that validates with part of the rows (code-review finding)."""
    packed = corpus[0]
    p = str(tmp_path / "partial.rawire")
    w = wire.WireWriter(p, wire.ruleset_fingerprint(packed), block_rows=4)
    w.add(np.ones((pack.WIRE_COLS, 10), dtype=np.uint32), 10, 0)
    w.abort()
    with pytest.raises(wire.WireFormatError, match="incomplete"):
        wire.WireReader([p], packed)
    # partial files still sniff as wire files so run/oracle ROUTING sends
    # them to WireReader's loud refusal instead of the text parser
    assert wire.is_wire_file(p)

    # the context manager aborts on exception automatically
    q = str(tmp_path / "crashed.rawire")
    with pytest.raises(RuntimeError):
        with wire.WireWriter(q, wire.ruleset_fingerprint(packed)) as w2:
            w2.add(np.ones((pack.WIRE_COLS, 3), dtype=np.uint32), 3, 0)
            raise RuntimeError("simulated crash mid-convert")
    with pytest.raises(wire.WireFormatError, match="incomplete"):
        wire.WireReader([q], packed)


def test_corrupt_block_rows_header_refused(corpus, wire_path, tmp_path):
    """block_rows == 0 must be a WireFormatError, not a ZeroDivisionError."""
    import struct as _struct

    data = bytearray(open(wire_path, "rb").read())
    data[8:12] = _struct.pack("<I", 0)
    bad = tmp_path / "zeroblock.rawire"
    bad.write_bytes(bytes(data))
    with pytest.raises(wire.WireFormatError, match="block_rows"):
        wire.WireReader([str(bad)], corpus[0])


def test_cli_wire_plus_stdin_refused(corpus, tmp_path, capsys):
    """a.rawire + '-' must be refused, not text-parsed as garbage."""
    from ruleset_analysis_tpu.cli import main

    packed, _rs, logs, _lines = corpus
    prefix = str(tmp_path / "rs")
    pack.save_packed(packed, prefix)
    out = str(tmp_path / "logs.rawire")
    assert main(["convert", "--ruleset", prefix, "--logs", *logs, "--out", out]) == 0
    rc = main(["run", "--ruleset", prefix, "--logs", out, "-"])
    assert rc == 2
    assert "mix" in capsys.readouterr().err


def test_cli_partial_wire_file_refused_loudly(corpus, tmp_path, capsys):
    """A crashed convert's partial file must produce a loud error through
    the run CLI, not an empty text-parse report (code-review finding)."""
    from ruleset_analysis_tpu.cli import main

    packed = corpus[0]
    prefix = str(tmp_path / "rs")
    pack.save_packed(packed, prefix)
    p = str(tmp_path / "partial.rawire")
    w = wire.WireWriter(p, wire.ruleset_fingerprint(packed), block_rows=4)
    w.add(np.ones((pack.WIRE_COLS, 10), dtype=np.uint32), 10, 0)
    w.abort()
    rc = main(["run", "--ruleset", prefix, "--logs", p, "--batch-size", "64"])
    assert rc == 1
    assert "incomplete" in capsys.readouterr().err


def test_cli_convert_block_rows_validation(corpus, tmp_path, capsys):
    from ruleset_analysis_tpu.cli import main

    packed, _rs, logs, _lines = corpus
    prefix = str(tmp_path / "rs")
    pack.save_packed(packed, prefix)
    rc = main(["convert", "--ruleset", prefix, "--logs", *logs,
               "--out", str(tmp_path / "x.rawire"), "--block-rows", "0"])
    assert rc == 2
    assert "block-rows" in capsys.readouterr().err


def test_cli_convert_refuses_wire_input(corpus, tmp_path, capsys):
    """convert over an existing .rawire must be refused, not laundered
    through the text parser into a valid empty file (code-review finding)."""
    from ruleset_analysis_tpu.cli import main

    packed, _rs, logs, _lines = corpus
    prefix = str(tmp_path / "rs")
    pack.save_packed(packed, prefix)
    out = str(tmp_path / "a.rawire")
    assert main(["convert", "--ruleset", prefix, "--logs", *logs, "--out", out]) == 0
    rc = main(["convert", "--ruleset", prefix, "--logs", out,
               "--out", str(tmp_path / "b.rawire")])
    assert rc == 2
    assert "already a wire file" in capsys.readouterr().err


def test_convert_feed_workers_byte_identical(corpus, tmp_path):
    """Multi-process conversion writes the byte-identical file: chunk
    boundaries differ between parse tiers but the row stream does not."""
    from ruleset_analysis_tpu.hostside import fastparse

    if not fastparse.available():
        pytest.skip("native parser not buildable here")
    packed, _rs, logs, _lines = corpus
    seq = str(tmp_path / "seq.rawire")
    par = str(tmp_path / "par.rawire")
    s1 = wire.convert_logs(packed, logs, seq, block_rows=128)
    s2 = wire.convert_logs(packed, logs, par, block_rows=128, feed_workers=2)
    assert s2["parser"] == "native-feeder-x2"
    assert s1["raw_lines"] == s2["raw_lines"]
    assert open(seq, "rb").read() == open(par, "rb").read()


def test_convert_feed_workers_native_false_refused(corpus, tmp_path):
    packed, _rs, logs, _lines = corpus
    with pytest.raises(ValueError, match="native"):
        wire.convert_logs(packed, logs, str(tmp_path / "x.rawire"),
                          native=False, feed_workers=2)


def test_cli_wire_info(corpus, tmp_path, capsys):
    from ruleset_analysis_tpu.cli import main

    packed, _rs, logs, lines = corpus
    prefix = str(tmp_path / "rs")
    pack.save_packed(packed, prefix)
    out = str(tmp_path / "a.rawire")
    assert main(["convert", "--ruleset", prefix, "--logs", *logs, "--out", out]) == 0
    capsys.readouterr()
    rc = main(["wire-info", out, "--ruleset", prefix, "--json"])
    assert rc == 0
    import json

    info = json.loads(capsys.readouterr().out)[0]
    assert info["ok"] and info["raw_lines"] == len(lines)

    # wrong ruleset -> invalid, rc 1
    other_cfg = synth.synth_config(n_acls=2, rules_per_acl=4, seed=3)
    other = pack.pack_rulesets([aclparse.parse_asa_config(other_cfg, "fw1")])
    pack.save_packed(other, str(tmp_path / "rs2"))
    rc = main(["wire-info", out, "--ruleset", str(tmp_path / "rs2")])
    assert rc == 1
    assert "INVALID" in capsys.readouterr().out


def test_cli_wire_info_missing_file_reported(tmp_path, capsys):
    from ruleset_analysis_tpu.cli import main

    rc = main(["wire-info", str(tmp_path / "nope.rawire")])
    assert rc == 1
    assert "INVALID" in capsys.readouterr().out


def test_wire_stacked_checkpoint_crash_resume(corpus, wire_path, tmp_path):
    """All three round-4 features compose: wire input, stacked layout,
    checkpointed crash/resume — counts bit-identical to uninterrupted."""
    packed = corpus[0]
    base = make_cfg(layout="stacked", stacked_lane=64)
    ref = run_stream_wire(packed, wire_path, base, topk=5)
    ck = dict(layout="stacked", stacked_lane=64,
              checkpoint_every_chunks=2, checkpoint_dir=str(tmp_path / "ck"))
    run_stream_wire(packed, wire_path, make_cfg(**ck), topk=5, max_chunks=3)
    snap = ckpt.load(str(tmp_path / "ck"))
    assert snap is not None and snap.fingerprint.endswith("-wire")
    rep = run_stream_wire(packed, wire_path, make_cfg(**ck, resume=True), topk=5)
    assert hits_of(rep) == hits_of(ref)
    assert rep.unused == ref.unused
    assert rep.totals["lines_matched"] == ref.totals["lines_matched"]


def test_cli_topk_sample_shift_plumbing(corpus, tmp_path, capsys):
    """--topk-sample-shift reaches the device step; exact counts and the
    unused set are sample-invariant (only candidate SELECTION samples)."""
    import json

    from ruleset_analysis_tpu.cli import main

    packed, _rs, logs, _lines = corpus
    prefix = str(tmp_path / "rs")
    pack.save_packed(packed, prefix)

    def run(shift):
        out = str(tmp_path / f"rep{shift}.json")
        rc = main(["run", "--ruleset", prefix, "--logs", *logs,
                   "--batch-size", "256", "--topk-sample-shift", str(shift),
                   "--json", "--out", out])
        assert rc == 0
        return json.load(open(out))

    a, b = run(0), run(3)
    ha = {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in a["per_rule"]}
    hb = {(e["firewall"], e["acl"], e["index"]): e["hits"] for e in b["per_rule"]}
    assert ha == hb
    assert a["unused"] == b["unused"]


def test_wire_run_closes_reader_deterministically(corpus, wire_path, monkeypatch):
    """run_stream_wire must release the reader's mmaps in a finally —
    long-lived drivers over many wire inputs cannot wait for GC."""
    closed = []
    orig = wire.WireReader.close

    def spy(self):
        closed.append(True)
        return orig(self)

    monkeypatch.setattr(wire.WireReader, "close", spy)
    run_stream_wire(corpus[0], wire_path, make_cfg(), topk=5)
    assert closed, "WireReader.close was never called by the run driver"


def test_wire_close_tolerates_live_zero_copy_views(corpus, tmp_path):
    """block_rows == batch_size serves zero-copy mmap views; the finally-
    block close() must not raise BufferError while a suspended generator
    (max_chunks abort) or loop frame still holds the last view."""
    packed, _rs, logs, _lines = corpus
    out = tmp_path / "aligned.rawire"
    wire.convert_logs(packed, logs, str(out), block_rows=512)
    rep = run_stream_wire(
        packed, str(out), make_cfg(batch_size=512), topk=5, max_chunks=1
    )
    assert rep.totals["chunks"] == 1  # aborted cleanly, no BufferError


def test_wire_midrun_error_not_replaced_by_buffererror(corpus, tmp_path, monkeypatch):
    """An exception raised mid-run (device failure analog) must propagate
    as itself: the close() in the finally runs while the traceback keeps
    the chunk-loop frame (and its mmap view) alive, and a BufferError
    there would mask the real error from callers catching specific types."""
    from ruleset_analysis_tpu.parallel import mesh as mesh_lib

    packed, _rs, logs, _lines = corpus
    out = tmp_path / "aligned2.rawire"
    wire.convert_logs(packed, logs, str(out), block_rows=512)
    calls = []
    orig = mesh_lib.shard_batch

    def boom(*a, **kw):
        calls.append(True)
        if len(calls) == 2:
            raise RuntimeError("injected device failure")
        return orig(*a, **kw)

    monkeypatch.setattr(mesh_lib, "shard_batch", boom)
    with pytest.raises(RuntimeError, match="injected device failure"):
        run_stream_wire(packed, str(out), make_cfg(batch_size=512), topk=5)


def test_wire_reader_corruption_fuzz_clean_refusals(corpus, wire_path, tmp_path):
    """Random byte flips / truncation / extension of a wire file: the
    reader must refuse with AnalysisError (or surface corrupt rows via
    the valid-bit accounting) — never crash with a raw struct/numpy/
    mmap error (r5 fuzz pass)."""
    import random

    from ruleset_analysis_tpu.errors import AnalysisError
    from ruleset_analysis_tpu.hostside.wire import sanity_check_valid_bits

    packed = corpus[0]
    blob = open(wire_path, "rb").read()
    mp = str(tmp_path / "m.rawire")
    for trial in range(300):
        rng = random.Random(trial)
        b = bytearray(blob)
        for _ in range(rng.randint(1, 8)):
            if not b:
                break
            op = rng.randrange(3)
            if op == 0:
                b[rng.randrange(len(b))] = rng.randrange(256)
            elif op == 1:
                b = bytearray(b[: rng.randrange(len(b))])
            else:
                b += bytes(rng.randrange(64))
        with open(mp, "wb") as f:
            f.write(bytes(b))
        try:
            r = wire.WireReader([mp], packed)
            for batch, _n in r.iter_batches(0, 128):
                sanity_check_valid_bits(batch)
            r.close()
        except AnalysisError:
            pass  # loud, typed refusal — the contract
