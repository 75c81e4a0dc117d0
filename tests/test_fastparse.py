"""Native C++ parser parity: fastparse must match the pure-Python path.

The native parser is a from-scratch reimplementation of
hostside/syslog.py + LinePacker (SURVEY.md §4.3 mapper parse semantics);
these tests hold the two paths bit-identical on batches, counters,
streaming chunking, and the full run_stream driver incl. resume.
"""

import numpy as np
import pytest

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.hostside import aclparse, fastparse, pack, synth

pytestmark = pytest.mark.skipif(
    not fastparse.available(), reason="native parser not buildable here"
)


EDGE_CFG = """
access-list OUT extended permit tcp any host 10.0.0.5 eq 443
access-list OUT extended deny ip any any
access-list IN extended permit udp 192.168.0.0 255.255.0.0 any range 5000 6000
access-group OUT in interface outside
access-group IN in interface inside
"""

EDGE_LINES = [
    "Jul 29 01:02:03 fw9 : %ASA-6-106100: access-list OUT permitted tcp "
    "outside/1.2.3.4(1234) -> inside/10.0.0.5(443) hit-cnt 1 first hit [0x0, 0x0]",
    "Jul 29 01:02:03 fw9 : %ASA-6-106100: access-list OUT est-allowed tcp "
    "outside/1.2.3.4(999) -> inside/10.0.0.5(443) hit-cnt 1",
    # icmp 106100: parenthesised values are type/code -> type lands in dport
    "Jul 29 01:02:03 fw9 : %ASA-6-106100: access-list OUT denied icmp "
    "outside/1.2.3.4(8) -> inside/10.0.0.5(0) hit-cnt 1",
    'Jul 29 01:02:03 fw9 : %ASA-4-106023: Deny tcp src outside:5.6.7.8/55 '
    'dst inside:10.0.0.5/443 by access-group "OUT" [0x0, 0x0]',
    'Jul 29 01:02:03 fw9 : %ASA-4-106023: Deny icmp src outside:5.6.7.8 '
    'dst inside:10.0.0.5 (type 3, code 1) by access-group "OUT"',
    "<166>Jul 29 01:02:03 fw9 : %ASA-6-302013: Built inbound TCP connection 123 "
    "for outside:9.9.9.9/1000 (9.9.9.9/1000) to inside:10.0.0.5/443 (10.0.0.5/443)",
    "<166>Jul 29 01:02:03 fw9 : %ASA-6-302013: Built outbound UDP connection 9 "
    "for outside:8.8.8.8/53 (8.8.8.8/53) to inside:192.168.1.7/5500 (192.168.1.7/5500)",
    "fw9: %ASA-6-302015: Built outbound UDP connection 9 for outside:8.8.8.8/53 "
    "to inside:192.168.1.7/5501",
    # unknown host / unknown acl / unhandled msgid / garbage / no host token
    "Jul 29 01:02:03 other : %ASA-6-106100: access-list OUT permitted tcp "
    "outside/1.2.3.4(1) -> inside/10.0.0.5(443) x",
    "Jul 29 01:02:03 fw9 : %ASA-6-106100: access-list NOPE permitted tcp "
    "outside/1.2.3.4(1) -> inside/10.0.0.5(443) x",
    "Jul 29 01:02:03 fw9 : %ASA-6-305011: Built dynamic TCP translation",
    "totally not a syslog line",
    "%ASA-6-106100: access-list OUT permitted tcp outside/1.2.3.4(1) -> "
    "inside/10.0.0.5(443) x",
    # hostname with attached colon, no space before the tag
    'fw9:%ASA-4-106023: Deny udp src inside:192.168.2.2/5000 '
    'dst outside:1.1.1.1/6000 by access-group "IN"',
    # identity-firewall user info after both endpoints (idfw)
    "Jul 30 01:02:03 fw9 : %ASA-6-106100: access-list OUT permitted tcp "
    "outside/1.2.3.4(1234)(DOMAIN\\user1) -> inside/10.0.0.5(443)(DOMAIN\\svc) "
    "hit-cnt 1 first hit [0x0, 0x0]",
    # 300-second-interval hit-count phrasing
    "Jul 30 01:02:03 fw9 : %ASA-6-106100: access-list OUT permitted tcp "
    "outside/1.2.3.4(5555) -> inside/10.0.0.5(443) hit-cnt 44 300-second interval [0x0, 0x0]",
    # syslog priority + ISO timestamp prefix
    "<166>2026-07-30T01:02:03Z fw9 : %ASA-6-106100: access-list OUT denied tcp "
    "outside/9.8.7.6(1) -> inside/10.0.0.5(80) hit-cnt 1",
    # interface names with dashes + flow-hash tail
    'Jul 30 01:02:03 fw9 : %ASA-4-106023: Deny tcp src outside:5.6.7.8/55 '
    'dst dmz-web:10.0.0.5/443 by access-group "OUT" [0x8a2b, 0x0]',
    # malformed bodies
    "Jul 29 fw9 : %ASA-6-106100: access-list OUT permitted tcp garbage",
    'Jul 29 fw9 : %ASA-4-106023: Deny tcp src outside:5.6.7.8 dst missing-group',
    "Jul 29 fw9 : %ASA-6-302013: Built sideways TCP connection 1 for a:1.2.3.4/5 to b:6.7.8.9/10",
    # 302013 hitting an interface with no access-group binding
    "Jul 29 fw9 : %ASA-6-302013: Built inbound TCP connection 5 for "
    "dmz:9.9.9.9/1000 to inside:10.0.0.5(443)",
    "",
]


def _edge_packed():
    rs = aclparse.parse_asa_config(EDGE_CFG, "fw9")
    return pack.pack_rulesets([rs])


def _synth_case(n=4000, seed=0):
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=24, seed=seed)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, n, seed=seed + 1)
    lines = synth.render_syslog(packed, tuples, seed=seed + 2)
    return packed, lines


def _egress_case(n, seed):
    """A ruleset with out-bindings: connection lines emit two rows."""
    cfg_text = synth.synth_config(
        n_acls=2, rules_per_acl=12, seed=seed, egress_acls=True
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, n, seed=seed + 1)
    lines = synth.render_syslog(packed, tuples, seed=seed + 2, variety=0.6)
    return packed, lines


def _both(packed, lines, batch_size):
    py = pack.LinePacker(packed)
    ref = py.pack_lines(lines, batch_size=batch_size)
    nat = fastparse.NativePacker(packed)
    got = nat.pack_lines(lines, batch_size=batch_size)
    return py, ref, nat, got


class TestParity:
    def test_edge_corpus(self):
        packed = _edge_packed()
        py, ref, nat, got = _both(packed, EDGE_LINES, 32)
        np.testing.assert_array_equal(ref, got)
        assert (py.parsed, py.skipped) == (nat.parsed, nat.skipped)
        assert py.parsed > 0 and py.skipped > 0  # corpus exercises both

    def test_synth_corpus(self):
        packed, lines = _synth_case()
        py, ref, nat, got = _both(packed, lines, len(lines))
        np.testing.assert_array_equal(ref, got)
        assert (py.parsed, py.skipped) == (nat.parsed, nat.skipped)

    def test_crlf_and_no_trailing_newline(self):
        packed = _edge_packed()
        data = ("\r\n".join(EDGE_LINES[:6])).encode()  # CRLF, unterminated tail
        nat = fastparse.NativePacker(packed)
        out, n_lines, used = nat.pack_chunk(data, 16, final=True)
        py = pack.LinePacker(packed)
        ref = py.pack_lines(EDGE_LINES[:6], batch_size=16).T
        assert n_lines == 6 and used == len(data)
        np.testing.assert_array_equal(np.ascontiguousarray(ref), out)

    def test_partial_tail_held_back(self):
        packed = _edge_packed()
        line = EDGE_LINES[0] + "\n"
        data = (line + "Jul 29 01:02:03 fw9 : %ASA-6-1061").encode()
        nat = fastparse.NativePacker(packed)
        out, n_lines, used = nat.pack_chunk(data, 8, final=False)
        assert n_lines == 1 and used == len(line.encode())
        assert out[6, 0] == 1 and out[6, 1] == 0

    @pytest.mark.parametrize(
        "shape", ["lf", "crlf", "fragment_final", "fragment_held", "dual_rows"]
    )
    @pytest.mark.parametrize("n_threads", [2, 3, 8])
    def test_multithread_bit_identical(self, n_threads, shape):
        """The parallel pass (per-worker newline scan, slabs, parallel
        compaction) must produce the same batch, counters, and consumed
        bytes as one thread — for any thread count, including more
        workers than lines per split, on CRLF lines, an unterminated
        last line (parsed when final, held back otherwise), and dual-row
        lines that close the batch early."""
        if shape == "dual_rows":
            packed, corpus = _egress_case(n=5000, seed=3)
            cap = len(corpus)  # two-row lines overflow it: an early close
        else:
            packed, lines = _synth_case(n=5000, seed=3)
            corpus = lines[:2500] + EDGE_LINES + lines[2500:]  # edge lines mid-stream
            cap = len(corpus)
        sep = "\r\n" if shape == "crlf" else "\n"
        tail = "" if shape.startswith("fragment") else sep
        data = (sep.join(corpus) + tail).encode()
        final = shape != "fragment_held"
        nat1 = fastparse.NativePacker(packed)
        out1, l1, u1 = nat1.pack_chunk(data, cap, final=final, n_threads=1)
        natn = fastparse.NativePacker(packed)
        outn, ln, un = natn.pack_chunk(data, cap, final=final, n_threads=n_threads)
        np.testing.assert_array_equal(out1, outn)
        assert (l1, u1) == (ln, un)
        assert (nat1.parsed, nat1.skipped) == (natn.parsed, natn.skipped)
        assert natn.parse_workers == min(n_threads, cap // 1024 + 1)
        assert nat1.parse_serial_batches == 1 and natn.parse_serial_batches == 0
        if shape == "dual_rows":
            assert ln < len(corpus)
        elif shape == "fragment_held":
            assert ln == len(corpus) - 1 and un < len(data)
        else:
            assert ln == len(corpus) and un == len(data)

    def test_parallel_pass_stress_more_workers_than_cores(self):
        """32 workers (more than the cores here) meet at the pass's two
        barriers, again and again on one packer's persistent slabs: every
        repeat finishes in bounded time with the one-thread loop's batch."""
        import threading

        packed, lines = _egress_case(n=40000, seed=12)
        data = ("\n".join(lines) + "\n").encode()
        cap = 40000
        ref, ref_lines, ref_used = fastparse.NativePacker(packed).pack_chunk(
            data, cap, final=True, n_threads=1
        )
        nat = fastparse.NativePacker(packed)
        got = []

        def repeat():
            for _ in range(20):
                got.append(nat.pack_chunk(data, cap, final=True, n_threads=32))

        t = threading.Thread(target=repeat, daemon=True)
        t.start()
        t.join(timeout=120)
        assert not t.is_alive() and len(got) == 20
        assert nat.parse_workers == 32
        for out, n_lines, used in got:
            assert (n_lines, used) == (ref_lines, ref_used)
            np.testing.assert_array_equal(out, ref)

    def test_full_batch_reports_short_buffer(self):
        """With full_batch and final=False, a buffer holding fewer than
        max_lines complete lines is left unparsed, on both paths: the call
        names the complete lines held and moves no counter."""
        packed, lines = _synth_case(n=3000, seed=5)
        data = ("\n".join(lines) + "\n").encode()
        for n_threads in (1, 3):
            nat = fastparse.NativePacker(packed)
            got = nat.pack_chunk(data, 4096, final=False, n_threads=n_threads,
                                 full_batch=True)
            assert got == (None, 3000, 0)
            assert (nat.parsed, nat.skipped) == (0, 0)
            out, n_lines, used = nat.pack_chunk(
                data, 4096, final=True, n_threads=n_threads, full_batch=True
            )
            assert (n_lines, used) == (3000, len(data))

    def test_multithread_respects_max_lines(self):
        packed, lines = _synth_case(n=3000, seed=4)
        data = ("\n".join(lines) + "\n").encode()
        nat = fastparse.NativePacker(packed)
        out, n_lines, used = nat.pack_chunk(
            data, 3000, final=True, max_lines=1500, n_threads=4
        )
        assert n_lines == 1500
        nat2 = fastparse.NativePacker(packed)
        out2, n2, used2 = nat2.pack_chunk(
            data, 3000, final=True, max_lines=1500, n_threads=1
        )
        np.testing.assert_array_equal(out, out2)
        assert (n_lines, used) == (n2, used2)


class TestFileStream:
    def _write(self, tmp_path, lines, name="a.log"):
        p = tmp_path / name
        p.write_text("\n".join(lines) + "\n", encoding="utf-8")
        return str(p)

    def test_batches_match_text_path(self, tmp_path):
        packed, lines = _synth_case(n=1000)
        path = self._write(tmp_path, lines)
        bs = 128  # 1000 lines -> 7 full batches + partial
        nat = fastparse.NativePacker(packed)
        got = list(fastparse.batches_from_file(path, nat, bs))
        py = pack.LinePacker(packed)
        from ruleset_analysis_tpu.runtime.stream import chunked

        ref = [
            (np.ascontiguousarray(py.pack_lines(c, batch_size=bs).T), len(c))
            for c in chunked(iter(lines), bs)
        ]
        assert len(got) == len(ref)
        for (gb, gn), (rb, rn) in zip(got, ref):
            assert gn == rn
            np.testing.assert_array_equal(gb, rb)
        assert (nat.parsed, nat.skipped) == (py.parsed, py.skipped)

    def test_skip_lines_resume(self, tmp_path):
        packed, lines = _synth_case(n=600)
        path = self._write(tmp_path, lines)
        nat = fastparse.NativePacker(packed)
        got = list(fastparse.batches_from_file(path, nat, 100, skip_lines=250))
        assert sum(n for _, n in got) == 350

    def test_skip_past_eof_raises(self, tmp_path):
        from ruleset_analysis_tpu.errors import ResumeInputMismatch

        packed, lines = _synth_case(n=50)
        path = self._write(tmp_path, lines)
        nat = fastparse.NativePacker(packed)
        with pytest.raises(ResumeInputMismatch):
            list(fastparse.batches_from_file(path, nat, 100, skip_lines=51))

    def test_multi_file_chain_and_skip(self, tmp_path):
        packed, lines = _synth_case(n=300)
        p1 = self._write(tmp_path, lines[:120], "a.log")
        p2 = self._write(tmp_path, lines[120:], "b.log")
        nat = fastparse.NativePacker(packed)
        got = list(fastparse.batches_from_files([p1, p2], nat, 64, skip_lines=150))
        assert sum(n for _, n in got) == 150

    def test_multi_file_batches_identical_to_text_path(self, tmp_path):
        # files chain into ONE stream: batches straddle the file boundary
        # exactly like the text path, incl. an unterminated last line
        packed, lines = _synth_case(n=500)
        p1 = tmp_path / "a.log"
        p1.write_text("\n".join(lines[:333]), encoding="utf-8")  # no trailing \n
        p2 = self._write(tmp_path, lines[333:], "b.log")
        nat = fastparse.NativePacker(packed)
        got = list(fastparse.batches_from_files([str(p1), p2], nat, 128))
        py = pack.LinePacker(packed)
        from ruleset_analysis_tpu.runtime.stream import chunked

        ref = [
            (np.ascontiguousarray(py.pack_lines(c, batch_size=128).T), len(c))
            for c in chunked(iter(lines), 128)
        ]
        assert [n for _, n in got] == [n for _, n in ref]
        for (gb, _), (rb, _) in zip(got, ref):
            np.testing.assert_array_equal(gb, rb)

    def test_skip_spanning_many_read_blocks(self, tmp_path):
        # regression: the resume fast-skip must refill mid-fragment when
        # the skip region spans multiple read blocks
        packed, lines = _synth_case(n=400)
        path = self._write(tmp_path, lines)
        nat = fastparse.NativePacker(packed)
        got = list(
            fastparse.batches_from_file(path, nat, 64, skip_lines=301, read_block=64)
        )
        assert sum(n for _, n in got) == 99

    def test_full_batches_with_tiny_read_block(self, tmp_path):
        # regression: when batch_size lines span many read blocks, the
        # reader must keep buffering until a FULL batch of raw lines is
        # available — mid-stream chunk boundaries must match the text path
        packed, lines = _synth_case(n=400)
        path = self._write(tmp_path, lines)
        nat = fastparse.NativePacker(packed)
        got = list(fastparse.batches_from_file(path, nat, 128, read_block=256))
        assert [n for _, n in got] == [128, 128, 128, 16]
        py = pack.LinePacker(packed)
        from ruleset_analysis_tpu.runtime.stream import chunked

        for (gb, _), c in zip(got, chunked(iter(lines), 128)):
            rb = np.ascontiguousarray(py.pack_lines(c, batch_size=128).T)
            np.testing.assert_array_equal(gb, rb)

    @pytest.mark.parametrize(
        "case",
        ["uneven", "chained", "crlf", "long_line", "resume", "dual_rows", "fragment"],
    )
    @pytest.mark.parametrize("n_threads", [2, 3, 8])
    def test_stream_parallel_matches_one_thread(
        self, tmp_path, monkeypatch, n_threads, case
    ):
        """batches_from_files through the parallel pass yields exactly the
        batches, line counts and counters of the one-thread loop: a batch
        size that does not divide the corpus, chained files (the first
        without a trailing newline), CRLF lines, a line longer than the
        read block, a mid-file resume, two-row lines that close batches
        early, and a final unterminated fragment."""
        n, bs = 20000, 7500
        if case == "dual_rows":
            packed, lines = _egress_case(n=n, seed=7)
        else:
            packed, lines = _synth_case(n=n, seed=7)
        if case == "long_line":
            lines.insert(9000, "x" * 100_000)
        sep = "\r\n" if case == "crlf" else "\n"
        if case == "chained":
            p1, p2 = tmp_path / "a.log", tmp_path / "b.log"
            p1.write_bytes(sep.join(lines[:8333]).encode())
            p2.write_bytes((sep.join(lines[8333:]) + sep).encode())
            paths = [str(p1), str(p2)]
        else:
            p = tmp_path / "c.log"
            p.write_bytes((sep.join(lines) + ("" if case == "fragment" else sep)).encode())
            paths = [str(p)]
        skip = 12345 if case == "resume" else 0
        runs = {}
        for t in (1, n_threads):
            monkeypatch.setenv("RA_PARSE_THREADS", str(t))
            nat = fastparse.NativePacker(packed)
            runs[t] = (
                list(fastparse.batches_from_files(
                    paths, nat, bs, skip_lines=skip, read_block=1 << 16
                )),
                nat,
            )
        (ref, nat1), (got, natn) = runs[1], runs[n_threads]
        counts = [k for _, k in ref]
        assert counts == [k for _, k in got]
        for (a, _), (b, _) in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        assert (nat1.parsed, nat1.skipped) == (natn.parsed, natn.skipped)
        assert (natn.parse_workers, natn.parse_serial_batches) == (n_threads, 0)
        assert (nat1.parse_workers, nat1.parse_serial_batches) == (0, len(ref))
        assert sum(counts) == len(lines) - skip
        if case == "dual_rows":
            assert min(counts[:-1]) < bs  # two-row lines closed a batch early
        else:
            assert counts[:-1] == [bs] * (len(counts) - 1)

    def test_persistent_slabs_leave_no_stale_rows(self, tmp_path, monkeypatch):
        """The workers' slabs persist across batches: a short batch after
        full ones has all-zero padding and no stale rows, and each yielded
        batch is an array of its own that later parses do not overwrite."""
        monkeypatch.setenv("RA_PARSE_THREADS", "4")
        packed, lines = _synth_case(n=10000, seed=8)
        path = self._write(tmp_path, lines)
        nat = fastparse.NativePacker(packed)
        held = [(b, b.copy(), k) for b, k in fastparse.batches_from_file(path, nat, 4096)]
        assert [k for *_, k in held] == [4096, 4096, 1808]
        assert nat.parse_workers == 4
        for (a, _, _), (b, _, _) in zip(held, held[1:]):
            assert not np.shares_memory(a, b)
        for batch, snap, _ in held:
            np.testing.assert_array_equal(batch, snap)
        last = held[-1][0]
        valid = int(last[pack.T_VALID].sum())
        assert 0 < valid <= 1808 and not last[:, valid:].any()
        ref, _, _ = fastparse.NativePacker(packed).pack_chunk(
            ("\n".join(lines[8192:]) + "\n").encode(), 4096, final=True, n_threads=1
        )
        np.testing.assert_array_equal(last, ref)

    def test_count_lines(self, tmp_path):
        p = tmp_path / "c.log"
        p.write_bytes(b"a\nb\nc")
        assert fastparse.count_lines_in_file(str(p)) == 3
        p.write_bytes(b"a\nb\n")
        assert fastparse.count_lines_in_file(str(p)) == 2


class TestDriver:
    def _cfg(self, **kw):
        return AnalysisConfig(
            batch_size=256,
            sketch=SketchConfig(cms_width=1 << 10, cms_depth=4, hll_p=6),
            **kw,
        )

    def test_run_stream_file_matches_text(self, tmp_path):
        from ruleset_analysis_tpu.runtime.stream import run_stream, run_stream_file

        packed, lines = _synth_case(n=900)
        path = tmp_path / "s.log"
        path.write_text("\n".join(lines) + "\n", encoding="utf-8")

        rep_text = run_stream(packed, (ln for ln in lines), self._cfg())
        rep_nat = run_stream_file(packed, str(path), self._cfg(), native=True)
        assert rep_nat.per_rule == rep_text.per_rule
        assert rep_nat.unused == rep_text.unused
        assert rep_nat.totals["lines_total"] == rep_text.totals["lines_total"]
        assert rep_nat.totals["lines_matched"] == rep_text.totals["lines_matched"]

    @pytest.mark.parametrize("threads", [1, 2])
    def test_report_counts_parse_workers(self, tmp_path, monkeypatch, threads):
        """totals.ingest says how the native parse ran: the workers of the
        last parallel batch, and the batches that took the one-thread
        loop (every batch under RA_PARSE_THREADS=1)."""
        from ruleset_analysis_tpu.runtime.stream import run_stream_file

        monkeypatch.setenv("RA_PARSE_THREADS", str(threads))
        packed, lines = _synth_case(n=5000)
        path = tmp_path / "s.log"
        path.write_text("\n".join(lines) + "\n", encoding="utf-8")
        cfg = AnalysisConfig(
            batch_size=2048,
            sketch=SketchConfig(cms_width=1 << 10, cms_depth=4, hll_p=6),
        )
        rep = run_stream_file(packed, str(path), cfg, native=True)
        ing = rep.totals["ingest"]
        assert rep.totals["chunks"] == 3
        if threads > 1:
            assert ing["parse_workers"] == 2 and ing["parse_serial_batches"] == 0
        else:
            assert ing["parse_workers"] == 0 and ing["parse_serial_batches"] == 3

    def test_checkpoint_resume_native(self, tmp_path):
        from ruleset_analysis_tpu.runtime.stream import run_stream_file

        packed, lines = _synth_case(n=1024)
        path = tmp_path / "s.log"
        path.write_text("\n".join(lines) + "\n", encoding="utf-8")
        ckdir = str(tmp_path / "ck")

        full = run_stream_file(packed, str(path), self._cfg(), native=True)
        # crash after 2 chunks (snapshot each chunk), then resume
        cfg1 = self._cfg(checkpoint_every_chunks=1, checkpoint_dir=ckdir)
        run_stream_file(packed, str(path), cfg1, native=True, max_chunks=2)
        cfg2 = self._cfg(checkpoint_every_chunks=1, checkpoint_dir=ckdir, resume=True)
        resumed = run_stream_file(packed, str(path), cfg2, native=True)
        assert resumed.per_rule == full.per_rule
        assert resumed.totals["lines_total"] == full.totals["lines_total"]


class TestFuzzParity:
    def test_mutated_corpus_bit_identical_and_crash_free(self):
        """Randomized corruption of valid syslog lines: both parsers must
        SKIP malformed lines (r5 fuzz: a corrupt address like
        '1.2.3.4.5.6' leaked an AclParseError out of the Python
        parse_line) and stay bit-identical to each other on the
        survivors."""
        import random

        packed, lines = _synth_case(n=300, seed=3)
        garbage = ["", "\x00\x01\x02", "%ASA-6-106100", "a" * 5000, "\u0661\u0660"]
        mutated = []
        for trial in range(1024):
            rng = random.Random(trial)
            line = rng.choice(lines)
            op = rng.randrange(4)
            if op == 0:
                line = line[: rng.randrange(len(line))]
            elif op == 1:
                i = rng.randrange(len(line))
                line = line[:i] + rng.choice("()/:->% \x00日\u0661") + line[i + 1:]
            elif op == 2:
                line = line + rng.choice(garbage)
            else:
                i, j = sorted(rng.randrange(len(line)) for _ in range(2))
                line = line[:j] + line[i:]
            mutated.append(line.replace("\n", " ").replace("\r", " "))
        py, ref, nat, got = _both(packed, mutated, 2048)
        np.testing.assert_array_equal(ref, got)
        assert (py.parsed, py.skipped) == (nat.parsed, nat.skipped)
        assert py.skipped > 0  # the corpus really contains corrupt lines


def test_pack_lines_stages_v6_rows_for_take_v6():
    """pack_lines on a unified corpus returns the v4 plane and stages the
    v6 evaluations for take_v6 — the same side-channel contract as the
    chunk API and the streaming drivers (the old loud v4-only refusal
    was deleted with ISSUE 11, which closed the last v6-refusing tier)."""
    cfg = synth.synth_config(n_acls=2, rules_per_acl=6, seed=3, v6_fraction=0.5)
    rs = aclparse.parse_asa_config(cfg, "fw6")
    packed = pack.pack_rulesets([rs])
    assert packed.has_v6
    v6_lines = synth.render_syslog6(
        packed, synth.synth_tuples6(packed, 4, seed=3), seed=4
    )
    v4_lines = synth.render_syslog(
        packed, synth.synth_tuples(packed, 4, seed=5), seed=6
    )
    nat = fastparse.NativePacker(packed)
    out = nat.pack_lines(v6_lines + v4_lines, batch_size=16)
    assert out.shape == (16, pack.TUPLE_COLS)
    assert int((out[:, pack.T_VALID] == 1).sum()) == len(v4_lines)
    rows6 = nat.take_v6()
    assert len(rows6) == len(v6_lines)  # staged, not lost, not raised
    assert len(nat.take_v6()) == 0  # drained exactly once
    # the dual-plane API remains the convenient route for unified corpora
    b4, b6 = nat.pack_lines2(v6_lines + v4_lines, batch_size=16)
    assert int((b6[:, pack.T6_VALID] == 1).sum()) == len(v6_lines)


# ---------------------------------------------------------------------------
# SIMD tokenizer byte-identity (ISSUE 11): the dispatched parse (AVX2 /
# NEON line parser + bulk newline scans) must produce byte-for-byte the
# output of the scalar reference on ANY input — well-formed lines of all
# seven message classes in both address families, 12k adversarial
# mutants, and truncated/oversize tails placed flush against the end of
# exactly-sized buffers (no primitive may read past the buffer).
# ---------------------------------------------------------------------------

simd_required = pytest.mark.skipif(
    not (fastparse.available() and fastparse.simd_active()),
    reason="CPU has neither AVX2 nor NEON (or RA_SIMD=off): nothing to A/B",
)


@pytest.fixture
def _simd_restore():
    was = fastparse.simd_active()
    yield
    fastparse.set_simd(was)


def _dual_family_case(seed=11):
    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=12, seed=seed, v6_fraction=0.4,
        egress_acls=True,
    )
    rs = aclparse.parse_asa_config(cfg_text, "fwS")
    packed = pack.pack_rulesets([rs])
    lines = synth.render_syslog(
        packed, synth.synth_tuples(packed, 500, seed=seed + 1),
        seed=seed + 2, variety=0.7,
    )
    lines += synth.render_syslog6(
        packed, synth.synth_tuples6(packed, 400, seed=seed + 3),
        seed=seed + 4, variety=0.7,
    )
    return packed, lines


def _parse_both_modes(packed, data: bytes, cap: int, *, final=True,
                      max_lines=None):
    """Parse the same bytes under SIMD and scalar dispatch; return both."""
    results = {}
    for mode in (True, False):
        fastparse.set_simd(mode)
        pk = fastparse.NativePacker(packed)
        out, lines, used = pk.pack_chunk(
            data, cap, final=final,
            max_lines=max_lines if max_lines is not None else cap,
            n_threads=1,
        )
        rows6 = pk.take_v6() if packed.has_v6 else []
        results[mode] = (out, lines, used, pk.parsed, pk.skipped,
                         np.asarray(rows6, dtype=np.uint32))
    return results[True], results[False]


def _assert_identical(simd_res, scal_res):
    s_out, s_lines, s_used, s_p, s_s, s_v6 = simd_res
    c_out, c_lines, c_used, c_p, c_s, c_v6 = scal_res
    np.testing.assert_array_equal(s_out, c_out)
    np.testing.assert_array_equal(s_v6, c_v6)
    assert (s_lines, s_used, s_p, s_s) == (c_lines, c_used, c_p, c_s)


@simd_required
def test_simd_identity_all_message_classes(_simd_restore):
    """Well-formed corpus, all 7 msg classes, both families, dual-row
    connection lines (egress bindings): SIMD == scalar byte-for-byte."""
    packed, lines = _dual_family_case()
    data = ("\n".join(lines) + "\n").encode()
    simd_res, scal_res = _parse_both_modes(
        packed, data, 2 * len(lines), max_lines=len(lines)
    )
    _assert_identical(simd_res, scal_res)
    assert simd_res[3] > 0 and len(simd_res[5]) > 0  # both planes exercised


@simd_required
def test_simd_identity_mutant_sweep_12k(_simd_restore):
    """12k adversarial mutants (truncations, substitutions, splices,
    garbage tails) of all-message-class dual-family lines: the SIMD and
    scalar parses must agree byte-for-byte, including counters and
    consumed bytes.  The mutation grammar mirrors the python-vs-native
    sweep above; this one A/Bs the two NATIVE dispatch states."""
    import random

    packed, lines = _dual_family_case(seed=21)
    garbage = ["", "\x00\x01\x02", "%ASA-6-106100", "a" * 5000, "١٠",
               ":" * 40, "." * 40, "1" * 40, "f" * 40]
    mutated = []
    for trial in range(12000):
        rng = random.Random(trial)
        line = rng.choice(lines)
        op = rng.randrange(5)
        if op == 0:
            line = line[: rng.randrange(len(line))]
        elif op == 1:
            i = rng.randrange(len(line))
            line = line[:i] + rng.choice("()/:->% .\x00日١") + line[i + 1:]
        elif op == 2:
            line = line + rng.choice(garbage)
        elif op == 3:
            i, j = sorted(rng.randrange(len(line)) for _ in range(2))
            line = line[:j] + line[i:]
        else:
            # digit-run stress: double a random digit run (overlong
            # octets/ports exercise the ipv4 fast path's defer branch)
            i = rng.randrange(len(line))
            line = line[:i] + line[i:i + rng.randrange(1, 8)] + line[i:]
        mutated.append(line.replace("\n", " ").replace("\r", " "))
    data = ("\n".join(mutated) + "\n").encode()
    simd_res, scal_res = _parse_both_modes(
        packed, data, 2 * len(mutated), max_lines=len(mutated)
    )
    _assert_identical(simd_res, scal_res)
    assert simd_res[4] > 0  # the corpus really contains corrupt lines


@simd_required
def test_simd_identity_buffer_edge_tails(_simd_restore):
    """Lines placed flush against the end of exactly-sized buffers, with
    truncated (final=False) and final unterminated tails: every
    dispatch state must consume identical bytes and emit identical
    rows.  Covers address runs of every length straddling the 16/32-byte
    SIMD windows at the buffer edge."""
    packed, lines = _dual_family_case(seed=31)
    base = "\n".join(lines[:50]).encode()
    edge_tails = [
        b"",  # clean newline-terminated end
        b"\nJul 29 01:02:03 fwS : %ASA-6-106100: access-list A denied tcp "
        b"a/1.2.3.4(1) -> b/5.6.7.8",          # truncated mid-address
        b"\nfwS : %ASA-6-106100: access-list A permitted tcp a/" +
        b"1" * 40,                              # oversize digit run at EOF
        b"\nfwS : %ASA-4-106023: Deny tcp src a:" + b"0001.2.3.4",
        b"\nfwS : %ASA-6-302013: Built inbound TCP connection 1 for "
        b"a:255.255.255.255/65535",             # max-width quad at EOF
        b"\nfwS : %ASA-6-106100: access-list A permitted tcp a/"
        b"2001:db8::1(80) -> b/2001:db8::2",    # v6 run truncated at EOF
    ]
    for tail in edge_tails:
        for final in (True, False):
            data = bytes(base + tail)  # exactly sized; no slack bytes
            simd_res, scal_res = _parse_both_modes(
                packed, data, 2 * 64, max_lines=64, final=final
            )
            _assert_identical(simd_res, scal_res)


@simd_required
def test_simd_identity_bulk_newline_primitives(_simd_restore):
    """count_nl / count_lines (nl_skip) primitives: scalar vs SIMD over
    newline layouts that stress block boundaries — runs of newlines,
    32/16-byte-aligned clusters, no trailing newline, k edge cases."""
    import ctypes
    import random

    lib = fastparse._load()
    rng = random.Random(5)
    cases = [
        b"",
        b"\n",
        b"x" * 31 + b"\n",
        b"\n" * 200,
        (b"y" * 15 + b"\n") * 40,
        b"tail-without-newline",
    ]
    for _ in range(40):
        n = rng.randrange(0, 400)
        cases.append(bytes(rng.choice(b"ab\n") for _ in range(n)))
    for data in cases:
        got = {}
        for mode in (True, False):
            lib.asa_simd_set(1 if mode else 0)
            cnt = int(lib.asa_count_nl(data, len(data)))
            per_k = []
            for k in (0, 1, 3, 10**6):
                for final in (0, 1):
                    used = ctypes.c_int64(0)
                    c = int(lib.asa_count_lines(
                        data, len(data), final, k, ctypes.byref(used)))
                    per_k.append((k, final, c, int(used.value)))
            got[mode] = (cnt, per_k)
        assert got[True] == got[False], f"bulk scan divergence on {data!r}"
