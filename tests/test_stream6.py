"""End-to-end IPv6: mixed-family stream runs vs the exact oracle.

The v6 side path (runtime/stream.py): text sources stage v6 evaluations
separately; the driver steps them through the v6 device program into the
SAME registers, flushing at checkpoints and end-of-stream.
"""

import random

import numpy as np
import pytest

pytest.importorskip("jax")

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.errors import AnalysisError
from ruleset_analysis_tpu.hostside import aclparse, oracle, pack
from ruleset_analysis_tpu.runtime.stream import run_stream, run_stream_file

CFG = """\
hostname fw1
access-list A extended permit tcp any host 10.0.0.5 eq 443
access-list A extended permit tcp any6 2001:db8:1::/48 eq 443
access-list A extended permit udp 2001:db8:2::/64 any6 eq 53
access-list A extended deny tcp any6 host 2001:db8::bad
access-list A extended permit ip any any
access-list B extended permit tcp any6 any6 range 8000 8100
access-group A in interface outside
"""


def mixed_lines(n, seed=0, v6_share=0.4):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        acl = "A" if rng.random() < 0.8 else "B"
        if rng.random() < v6_share:
            src = f"2001:db8:2::{rng.randrange(1, 40):x}" if rng.random() < 0.5 else \
                  f"2001:db8:{rng.randrange(0, 4):x}::{rng.randrange(1, 2000):x}"
            dst = "2001:db8::bad" if rng.random() < 0.1 else \
                  f"2001:db8:{rng.randrange(0, 4):x}:1::{rng.randrange(1, 99):x}"
            proto = rng.choice(["tcp", "udp"])
            sport = rng.randrange(1024, 60000)
            dport = rng.choice([443, 53, 8050, 9999])
        else:
            src = f"10.1.{rng.randrange(256)}.{rng.randrange(1, 255)}"
            dst = "10.0.0.5" if rng.random() < 0.5 else "10.9.9.9"
            proto = "tcp"
            sport = rng.randrange(1024, 60000)
            dport = rng.choice([443, 80])
        out.append(
            f"Jul 29 07:48:{i % 60:02d} fw1 : %ASA-6-106100: access-list {acl} "
            f"permitted {proto} inside/{src}({sport}) -> outside/{dst}({dport}) "
            f"hit-cnt 1 first hit [0x0, 0x0]"
        )
    return out


@pytest.fixture(scope="module")
def corpus():
    rs = aclparse.parse_asa_config(CFG, "fw1")
    packed = pack.pack_rulesets([rs])
    lines = mixed_lines(2500, seed=5)
    res = oracle.Oracle([rs]).consume(list(lines))
    return packed, rs, lines, res


def run_cfg(**kw):
    return AnalysisConfig(
        backend="tpu",
        batch_size=256,
        sketch=SketchConfig(cms_width=1 << 12, cms_depth=4, hll_p=8),
        **kw,
    )


def report_hits(rep):
    return {
        (e["firewall"], e["acl"], e["index"]): e["hits"]
        for e in rep.per_rule
        if e["hits"] > 0
    }


def test_mixed_stream_counts_match_oracle(corpus):
    packed, rs, lines, res = corpus
    rep = run_stream(packed, iter(lines), run_cfg(), topk=5)
    assert report_hits(rep) == dict(res.hits)
    assert rep.totals["lines_matched"] == res.lines_matched
    assert rep.totals["lines_skipped"] == res.lines_skipped == 0
    assert rep.unused == res.unused_rules([rs])


def test_v6_talkers_render_addresses(corpus):
    packed, rs, lines, res = corpus
    # one dominant source per family: both must surface in the SAME
    # merged per-ACL talker section, each rendered in its own notation
    heavy6 = [
        "Jul 29 07:49:00 fw1 : %ASA-6-106100: access-list A permitted tcp "
        "inside/2001:db8:1::7777(4321) -> outside/2001:db8:1::1(443) "
        "hit-cnt 1 first hit [0x0, 0x0]"
    ] * 400
    heavy4 = [
        "Jul 29 07:49:01 fw1 : %ASA-6-106100: access-list A permitted tcp "
        "inside/10.1.2.3(4321) -> outside/10.0.0.5(443) "
        "hit-cnt 1 first hit [0x0, 0x0]"
    ] * 300
    rep = run_stream(
        packed, iter(list(lines) + heavy6 + heavy4), run_cfg(), topk=5
    )
    talk = rep.talkers.get("fw1 A", [])
    assert any(ip == "2001:db8:1::7777" for ip, _ in talk), talk
    assert any(ip == "10.1.2.3" for ip, _ in talk), talk


def test_crash_resume_bit_identity_with_v6(corpus, tmp_path):
    packed, rs, lines, res = corpus
    base = run_cfg(checkpoint_every_chunks=2, checkpoint_dir=str(tmp_path / "ck"))
    uninterrupted = run_stream(packed, iter(lines), base, topk=5)
    # crash after 5 chunks, then resume over the same stream
    ck2 = str(tmp_path / "ck2")
    crash_cfg = run_cfg(checkpoint_every_chunks=2, checkpoint_dir=ck2)
    run_stream(packed, iter(lines), crash_cfg, topk=5, max_chunks=5)
    resume_cfg = run_cfg(
        checkpoint_every_chunks=2, checkpoint_dir=ck2, resume=True
    )
    resumed = run_stream(packed, iter(lines), resume_cfg, topk=5)
    assert report_hits(resumed) == report_hits(uninterrupted) == dict(res.hits)
    assert resumed.unused == uninterrupted.unused


def test_native_parser_analyzes_v6_corpora(corpus, tmp_path):
    """The native parse tier handles v6 via its dual-family entry
    (the feeder tier's v6 path is pinned by test_feeder_v6_*)."""
    packed, rs, lines, res = corpus
    p = tmp_path / "logs.txt"
    p.write_text("\n".join(lines) + "\n")
    from ruleset_analysis_tpu.hostside import fastparse

    if fastparse.available():
        rep_native = run_stream_file(
            packed, str(p), run_cfg(), native=True, topk=5
        )
        assert report_hits(rep_native) == dict(res.hits)
        assert rep_native.unused == res.unused_rules([rs])
    rep = run_stream_file(packed, str(p), run_cfg(), native=False, topk=5)
    assert report_hits(rep) == dict(res.hits)


def test_fold_host_device_agree():
    from ruleset_analysis_tpu.ops import match6 as match6_ops

    rng = random.Random(9)
    vals = [rng.getrandbits(128) for _ in range(512)]
    batch = np.zeros((len(vals), pack.TUPLE6_COLS), dtype=np.uint32)
    for i, v in enumerate(vals):
        batch[i, pack.T6_SRC:pack.T6_SRC + 4] = pack.u128_limbs(v)
    import jax.numpy as jnp

    b = jnp.asarray(np.ascontiguousarray(batch.T))
    cols = {f"src{i}": b[pack.T6_SRC + i] for i in range(4)}
    dev = np.asarray(match6_ops.fold_src32(cols))
    host = np.array([pack.fold_src32_host(v) for v in vals], dtype=np.uint32)
    np.testing.assert_array_equal(dev, host)


def test_synth_unified_corpus_end_to_end():
    """Randomized unified (v4+v6) synth config through the full stream."""
    from ruleset_analysis_tpu.hostside import synth

    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=14, seed=33, v6_fraction=0.35
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    assert packed.has_v6 and packed.rules.shape[0] > 0
    t4 = synth.synth_tuples(packed, 900, seed=33)
    t6 = synth.synth_tuples6(packed, 600, seed=33)
    lines = synth.render_syslog(packed, t4, seed=33) + synth.render_syslog6(
        packed, t6, seed=34
    )
    rng = random.Random(7)
    rng.shuffle(lines)
    res = oracle.Oracle([rs]).consume(list(lines))
    rep = run_stream(packed, iter(lines), run_cfg(), topk=5)
    assert report_hits(rep) == dict(res.hits)
    assert rep.unused == res.unused_rules([rs])
    assert rep.totals["lines_matched"] == res.lines_matched


def test_mixed_sketch_only_unused_recall():
    """Sketch-only registers (no exact counts) on an interleaved v4+v6
    stream: the CMS holds both families' hits, so the unused set keeps
    >= 99% of the exact one and never names a rule that was hit."""
    from ruleset_analysis_tpu.hostside import synth

    cfg_text = synth.synth_config(
        n_acls=4, rules_per_acl=64, seed=41, v6_fraction=0.4
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    t4 = synth.synth_tuples(packed, 3000, seed=41)
    t6 = synth.synth_tuples6(packed, 2000, seed=41)
    lines = synth.render_syslog(packed, t4, seed=41) + synth.render_syslog6(
        packed, t6, seed=42
    )
    random.Random(9).shuffle(lines)
    exact_unused = oracle.Oracle([rs]).consume(list(lines)).unused_rules([rs])
    cfg = AnalysisConfig(
        backend="tpu", batch_size=256, exact_counts=False,
        sketch=SketchConfig(cms_width=1 << 10, cms_depth=4, hll_p=8),
    )
    rep = run_stream(packed, iter(lines), cfg, topk=5)
    assert oracle.unused_rule_recall(exact_unused, rep.unused) >= 0.99
    assert set(rep.unused) <= set(exact_unused)


@pytest.mark.parametrize("seed", [2, 8])
def test_native_python_v6_differential(seed):
    """Python LinePacker vs native parser: bit-identical dual-family packs."""
    from ruleset_analysis_tpu.hostside import fastparse, synth

    if not fastparse.available():
        pytest.skip("no native toolchain")
    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=10, seed=seed, v6_fraction=0.4
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    t4 = synth.synth_tuples(packed, 400, seed=seed)
    t6 = synth.synth_tuples6(packed, 300, seed=seed)
    lines = synth.render_syslog(packed, t4, seed=seed) + synth.render_syslog6(
        packed, t6, seed=seed + 1
    )
    rng = random.Random(seed)
    rng.shuffle(lines)
    py = pack.LinePacker(packed)
    ref4, ref6 = py.pack_lines2(lines, batch_size=2 * len(lines))
    nat = fastparse.NativePacker(packed)
    got4, got6 = nat.pack_lines2(lines, batch_size=2 * len(lines))
    np.testing.assert_array_equal(ref4, got4)
    np.testing.assert_array_equal(ref6, got6)
    assert (py.parsed, py.skipped) == (nat.parsed, nat.skipped)


V6_EDGE_LINES = [
    # valid compressions and embedded v4 forms
    "Jul 29 0 fw1 : %ASA-6-106100: access-list A permitted tcp i/::1(1) -> o/::(2) hit",
    "Jul 29 0 fw1 : %ASA-6-106100: access-list A permitted tcp "
    "i/1:2:3:4:5:6:7:8(1) -> o/::ffff:10.0.0.5(2) hit",
    "Jul 29 0 fw1 : %ASA-6-106100: access-list A permitted tcp "
    "i/1:2:3:4:5:6:1.2.3.4(1) -> o/fe80::(2) hit",
    # invalid: too many groups / double '::' / bad embedded v4 / stray ':'
    "Jul 29 0 fw1 : %ASA-6-106100: access-list A permitted tcp "
    "i/1:2:3:4:5:6:7:8:9(1) -> o/::1(2) hit",
    "Jul 29 0 fw1 : %ASA-6-106100: access-list A permitted tcp "
    "i/1::2::3(1) -> o/::1(2) hit",
    "Jul 29 0 fw1 : %ASA-6-106100: access-list A permitted tcp "
    "i/::ffff:1.2.3.999(1) -> o/::1(2) hit",
    "Jul 29 0 fw1 : %ASA-6-106100: access-list A permitted tcp "
    "i/1:2:(1) -> o/::1(2) hit",
    "Jul 29 0 fw1 : %ASA-6-106100: access-list A permitted tcp "
    "i/12345::1(1) -> o/::1(2) hit",
    # mixed family: skipped by both parsers
    "Jul 29 0 fw1 : %ASA-6-106100: access-list A permitted tcp "
    "i/::1(1) -> o/10.0.0.5(2) hit",
    # 106023 / 302013 / 106001 v6 shapes
    'Jul 29 0 fw1 : %ASA-4-106023: Deny tcp src inside:2001:db8::9/100 '
    'dst outside:2001:db8:1::5/200 by access-group "A" [0x0]',
    "Jul 29 0 fw1 : %ASA-6-302013: Built inbound TCP connection 9 for "
    "outside:2001:db8::7/1000 (2001:db8::7/1000) to inside:2001:db8::8/80 "
    "(2001:db8::8/80)",
    "Jul 29 0 fw1 : %ASA-2-106001: Inbound TCP connection denied from "
    "2001:db8::9/5555 to 2001:db8:1::5/443 flags SYN on interface outside",
    # icmp6 with type/code parens
    "Jul 29 0 fw1 : %ASA-6-106100: access-list A permitted icmp6 "
    "i/2001:db8::9(128) -> o/2001:db8::5(0) hit-cnt 1",
]


def test_native_python_v6_edge_lines_bit_identical():
    from ruleset_analysis_tpu.hostside import fastparse

    if not fastparse.available():
        pytest.skip("no native toolchain")
    rs = aclparse.parse_asa_config(CFG, "fw1")
    packed = pack.pack_rulesets([rs])
    py = pack.LinePacker(packed)
    ref4, ref6 = py.pack_lines2(V6_EDGE_LINES, batch_size=32)
    nat = fastparse.NativePacker(packed)
    got4, got6 = nat.pack_lines2(V6_EDGE_LINES, batch_size=32)
    np.testing.assert_array_equal(ref4, got4)
    np.testing.assert_array_equal(ref6, got6)
    assert (py.parsed, py.skipped) == (nat.parsed, nat.skipped)


def test_feeder_v6_registers_match_text_run(tmp_path):
    """Multi-process feeder on a unified corpus: same hits as the text run."""
    from ruleset_analysis_tpu.hostside import fastparse, synth

    if not fastparse.available():
        pytest.skip("no native toolchain")
    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=10, seed=77, v6_fraction=0.4
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    t4 = synth.synth_tuples(packed, 700, seed=77)
    t6 = synth.synth_tuples6(packed, 400, seed=77)
    lines = synth.render_syslog(packed, t4, seed=77) + synth.render_syslog6(
        packed, t6, seed=78
    )
    random.Random(3).shuffle(lines)
    p = tmp_path / "logs.txt"
    p.write_text("\n".join(lines) + "\n")
    res = oracle.Oracle([rs]).consume(list(lines))
    rep_text = run_stream(packed, iter(lines), run_cfg(), topk=5)
    rep_feed = run_stream_file(
        packed, str(p), run_cfg(), feed_workers=2, topk=5
    )
    assert report_hits(rep_feed) == report_hits(rep_text) == dict(res.hits)
    assert rep_feed.unused == rep_text.unused == res.unused_rules([rs])
    assert rep_feed.totals["lines_matched"] == res.lines_matched


@pytest.mark.parametrize("seed", [1, 7])
def test_v6_mutation_fuzz_parity(seed):
    """Randomized mutations of v6/v4 syslog: both parsers bit-identical."""
    from ruleset_analysis_tpu.hostside import fastparse, synth

    if not fastparse.available():
        pytest.skip("no native toolchain")
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=10, seed=5, v6_fraction=0.5)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    base = synth.render_syslog6(packed, synth.synth_tuples6(packed, 60, seed=5), seed=6)
    base += synth.render_syslog(packed, synth.synth_tuples(packed, 60, seed=5), seed=6)
    rng = random.Random(seed)
    chars = ":abcdef0123456789./ ()->%ASA"
    mutants = []
    for _ in range(800):
        ln = rng.choice(base)
        pos = rng.randrange(len(ln))
        k = rng.randrange(5)
        if k == 0:
            ln = ln[:pos] + rng.choice(chars) + ln[pos + 1:]
        elif k == 1:
            ln = ln[:pos] + ln[pos + 1:]
        elif k == 2:
            ln = ln[:pos] + rng.choice(chars) + ln[pos:]
        elif k == 3:
            ln = ln[:pos]
        else:
            p2 = rng.randrange(len(ln))
            ls = list(ln)
            ls[pos], ls[p2] = ls[p2], ls[pos]
            ln = "".join(ls)
        mutants.append(ln)
    py = pack.LinePacker(packed)
    r4, r6 = py.pack_lines2(mutants, batch_size=4 * len(mutants))
    nat = fastparse.NativePacker(packed)
    g4, g6 = nat.pack_lines2(mutants, batch_size=4 * len(mutants))
    np.testing.assert_array_equal(r4, g4)
    np.testing.assert_array_equal(r6, g6)
    assert (py.parsed, py.skipped) == (nat.parsed, nat.skipped)


def test_v6_adversarial_endpoint_parity():
    """Hex-ish iface names x pathological v6 literals across all message
    classes: the iface:addr split and address validation must agree
    between the regex path and the native scanner."""
    from ruleset_analysis_tpu.hostside import fastparse

    if not fastparse.available():
        pytest.skip("no native toolchain")
    cfg = (
        "access-list A extended permit ip any any\n"
        "access-list A extended permit ip any6 any6\n"
        "access-group A in interface abc\n"
        "access-group A in interface fe80\n"
        "access-group A out interface def0\n"
    )
    rs = aclparse.parse_asa_config(cfg, "fw1")
    packed = pack.pack_rulesets([rs])
    rng = random.Random(7)
    ifaces = ["abc", "fe80", "def0", "a:b", "x", "0", "eth0"]
    addrs = ["1.2.3.4", "::1", "fe80::1", "1:2:3:4:5:6:7:8", "::ffff:1.2.3.4",
             "1:2:3:4:5:6:1.2.3.4", "2001:db8::9", "::", "abcd::", "a::b",
             "1:::2", "1::2::3", ":::", "1.2.3", "1.2.3.4.5", "12345::",
             "g::1", "0:0:0:0:0:0:0:0", "::1.2.3.999",
             "1:2:3:4:5:6:7:1.2.3.4",
             "ffff:ffff:ffff:ffff:ffff:ffff:ffff:ffff"]
    msgs = []
    for _ in range(1500):
        a, b = rng.choice(addrs), rng.choice(addrs)
        fi, fo = rng.choice(ifaces), rng.choice(ifaces)
        sp, dp = rng.randrange(1 << 17), rng.randrange(1 << 17)
        k = rng.randrange(5)
        if k == 0:
            msgs.append(f"J 1 0 fw1 : %ASA-6-106100: access-list A permitted tcp {fi}/{a}({sp}) -> {fo}/{b}({dp}) hit")
        elif k == 1:
            msgs.append(f'J 1 0 fw1 : %ASA-4-106023: Deny udp src {fi}:{a}/{sp} dst {fo}:{b}/{dp} by access-group "A"')
        elif k == 2:
            msgs.append(f'J 1 0 fw1 : %ASA-4-106023: Deny icmp6 src {fi}:{a} dst {fo}:{b} (type 128, code 0) by access-group "A"')
        elif k == 3:
            msgs.append(f"J 1 0 fw1 : %ASA-6-302013: Built inbound TCP connection 7 for {fi}:{a}/{sp} ({a}/{sp}) to {fo}:{b}/{dp} ({b}/{dp})")
        else:
            msgs.append(f"J 1 0 fw1 : %ASA-2-106001: Inbound TCP connection denied from {a}/{sp} to {b}/{dp} flags SYN on interface {fi}")
    py = pack.LinePacker(packed)
    r4, r6 = py.pack_lines2(msgs, batch_size=4 * len(msgs))
    nat = fastparse.NativePacker(packed)
    g4, g6 = nat.pack_lines2(msgs, batch_size=4 * len(msgs))
    np.testing.assert_array_equal(r4, g4)
    np.testing.assert_array_equal(r6, g6)
    assert (py.parsed, py.skipped) == (nat.parsed, nat.skipped)


def test_native_v6_mt_bit_identical_to_single_thread():
    """The v6 plane through the parallel pass (n_threads>1) must match
    the sequential loop bit-for-bit, including line-atomic batch closes."""
    from ruleset_analysis_tpu.hostside import fastparse, synth

    if not fastparse.available():
        pytest.skip("no native toolchain")
    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=10, seed=9, v6_fraction=0.5, egress_acls=True
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    t4 = synth.synth_tuples(packed, 1500, seed=9)
    t6 = synth.synth_tuples6(packed, 1200, seed=9)
    lines = synth.render_syslog(packed, t4, seed=9, variety=0.4)
    lines += synth.render_syslog6(packed, t6, seed=10)
    random.Random(9).shuffle(lines)
    data = ("\n".join(lines) + "\n").encode()

    for cap in (4096, 700):  # ample AND batch-closing capacities
        p1 = fastparse.NativePacker(packed)
        o1, l1, u1 = p1.pack_chunk(data, cap, final=True, max_lines=cap,
                                   n_threads=1)
        r61 = p1.take_v6()
        p4 = fastparse.NativePacker(packed)
        o4, l4, u4 = p4.pack_chunk(data, cap, final=True, max_lines=cap,
                                   n_threads=4)
        r64 = p4.take_v6()
        assert (l1, u1) == (l4, u4)
        np.testing.assert_array_equal(o1, o4)
        np.testing.assert_array_equal(np.asarray(r61), np.asarray(r64))
        assert (p1.parsed, p1.skipped) == (p4.parsed, p4.skipped)


def test_synth_v6_variety_corpus_end_to_end_and_native_parity():
    """v6 variety tier (all message classes, binding-resolved): oracle-
    exact through the stream AND bit-identical across both parsers."""
    from ruleset_analysis_tpu.hostside import fastparse, synth

    cfg_text = synth.synth_config(
        n_acls=3, rules_per_acl=10, seed=44, v6_fraction=0.5, egress_acls=True
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    t6 = synth.synth_tuples6(packed, 800, seed=44)
    lines = synth.render_syslog6(packed, t6, seed=45, variety=0.5)
    res = oracle.Oracle([rs]).consume(list(lines))
    rep = run_stream(packed, iter(lines), run_cfg(), topk=5)
    assert report_hits(rep) == dict(res.hits)
    assert rep.totals["lines_matched"] == res.lines_matched
    if fastparse.available():
        py = pack.LinePacker(packed)
        r4, r6 = py.pack_lines2(lines, batch_size=4 * len(lines))
        nat = fastparse.NativePacker(packed)
        g4, g6 = nat.pack_lines2(lines, batch_size=4 * len(lines))
        np.testing.assert_array_equal(r4, g4)
        np.testing.assert_array_equal(r6, g6)
        assert (py.parsed, py.skipped) == (nat.parsed, nat.skipped)


def test_stacked_text_v6_matches_flat(corpus):
    """Single-process stacked layout over the mixed text corpus."""
    packed, rs, lines, res = corpus
    rep = run_stream(packed, iter(lines), run_cfg(layout="stacked"), topk=5)
    assert report_hits(rep) == dict(res.hits)
    assert rep.unused == res.unused_rules([rs])


def test_106023_src_mid_token_colon_backtracks_like_regex():
    """The 106023 SRC endpoint must try LATER colon splits when an earlier
    one leaves token residue (regex \\s+dst backtracking); the DST endpoint
    commits to its first structural split (followed by .*?by).  Soak-found
    divergence pinned."""
    from ruleset_analysis_tpu.hostside import fastparse

    if not fastparse.available():
        pytest.skip("no native toolchain")
    cfg = (
        "access-list A extended permit ip any any\n"
        "access-group A in interface outside\n"
    )
    rs = aclparse.parse_asa_config(cfg, "fw1")
    packed = pack.pack_rulesets([rs])
    lines = [
        # src splits at the SECOND colon (first leaves "side:..." residue)
        'J 1 0 fw1 : %ASA-4-106023: Deny icmp src inside:1side:172.17.70.70 '
        'dst outside:198.51.0.225 (type 9, code 0) by access-group "A"',
        # dst with the same shape: first split commits, value "1" invalid,
        # line skips (both parsers)
        'J 1 0 fw1 : %ASA-4-106023: Deny tcp src inside:10.0.0.1/1 '
        'dst outside:1side:1.2.3.4/2 by access-group "A"',
        # port residue in the src token also backtracks/fails structurally
        'J 1 0 fw1 : %ASA-4-106023: Deny tcp src inside:2.3.4.5/19x '
        'dst outside:1.2.3.4/2 by access-group "A"',
    ]
    py = pack.LinePacker(packed)
    r4, _ = py.pack_lines2(lines, batch_size=8)
    nat = fastparse.NativePacker(packed)
    g4, _ = nat.pack_lines2(lines, batch_size=8)
    np.testing.assert_array_equal(r4, g4)
    assert (py.parsed, py.skipped) == (nat.parsed, nat.skipped)
    # and the first line really did parse (src = 172.17.70.70)
    assert py.parsed >= 1


def test_zero_valid_v4_batches_skip_device_step():
    """A mostly-IPv6 corpus must not step all-invalid v4 device chunks:
    _TextSource yields (None, n_raw) for zero-fill batches and the driver
    accounts the raw lines without a v4 step."""
    from ruleset_analysis_tpu.runtime.stream import _TextSource

    rs = aclparse.parse_asa_config(CFG, "fw1")
    packed = pack.pack_rulesets([rs])
    v6_only = mixed_lines(512, seed=11, v6_share=1.0)
    src = _TextSource(packed, iter(v6_only))
    got = list(src.batches(0, 128))
    # every batch is a zero-fill marker: raw lines accounted, no array
    assert [n for _b, n in got] == [128, 128, 128, 128]
    assert all(b is None for b, _n in got)
    assert src.packer.parsed > 0  # the v6 rows went to the side channel
    assert len(src.take_v6()) == src.packer.parsed

    # end-to-end: the driver steps ONLY v6 chunks for this corpus and the
    # report still matches the oracle exactly
    res = oracle.Oracle([rs]).consume(list(v6_only))
    cfg128 = run_cfg().replace(batch_size=128)
    rep = run_stream(packed, iter(v6_only), cfg128, topk=5)
    assert report_hits(rep) == dict(res.hits)
    assert rep.totals["lines_total"] == 512
    assert rep.totals["lines_matched"] == res.lines_matched
    # chunk count covers the v6 program only — the pre-fix driver stepped
    # one all-invalid v4 chunk per 128 v6 raw lines on top of these
    evals = res.lines_matched
    assert rep.totals["chunks"] == -(-evals // 128)  # ceil: v6 chunks alone


def test_convert_python_tier_handles_zero_valid_batches(tmp_path):
    """The python-tier converter must survive (None, n_raw) zero-v4
    batches from _TextSource: raw-line/skip accounting still lands in the
    wire header, and the wire run matches the oracle exactly."""
    from ruleset_analysis_tpu.hostside import wire
    from ruleset_analysis_tpu.runtime.stream import run_stream_wire

    rs = aclparse.parse_asa_config(CFG, "fw1")
    packed = pack.pack_rulesets([rs])
    v6_only = mixed_lines(300, seed=12, v6_share=1.0)
    p = tmp_path / "v6.log"
    p.write_text("\n".join(v6_only) + "\n")
    out = str(tmp_path / "v6.rawire")
    stats = wire.convert_logs(
        packed, [str(p)], out, native=False, batch_size=128, block_rows=128
    )
    assert stats["raw_lines"] == 300
    assert stats["rows"] == 0  # no v4 evaluation rows at all
    assert stats["rows6"] > 0
    res = oracle.Oracle([rs]).consume(list(v6_only))
    rep = run_stream_wire(packed, out, run_cfg(), topk=5)
    assert report_hits(rep) == dict(res.hits)
    assert rep.totals["lines_total"] == 300
