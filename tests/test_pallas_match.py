"""Lane-dense Pallas first-match kernel: bit-equality with the XLA scan.

Runs in pallas interpret mode on the CPU test mesh (the kernel compiles
natively on TPU: ``chip_smoke.py`` runs it there against the oracle,
``tests/test_tpu_compile.py`` compiles it for a described v5e).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ruleset_analysis_tpu.hostside import aclparse, pack, synth  # noqa: E402
from ruleset_analysis_tpu.models.pipeline import pad_rules  # noqa: E402
from ruleset_analysis_tpu.ops import pallas_match  # noqa: E402
from ruleset_analysis_tpu.ops.match import NO_MATCH, first_match_rows, match_keys  # noqa: E402

_FIELDS = ["acl", "proto", "src", "sport", "dst", "dport"]


def _cols(tuples):
    return {k: jnp.asarray(tuples[:, i]) for i, k in enumerate(_FIELDS)}


def _case(n_acls=3, rules_per_acl=24, n=2048, seed=0):
    cfg_text = synth.synth_config(n_acls=n_acls, rules_per_acl=rules_per_acl, seed=seed)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, n, seed=seed + 1)
    return packed, _cols(tuples), tuples


@pytest.mark.parametrize("seed", [0, 1])
def test_rows_match_xla_path(seed):
    packed, cols, _ = _case(seed=seed)
    rules = jnp.asarray(packed.rules)
    want = np.asarray(first_match_rows(cols, rules))
    got = np.asarray(pallas_match.first_match_rows_pallas(cols, rules, interpret=True))
    np.testing.assert_array_equal(got, want)


def test_keys_match_xla_path():
    packed, cols, _ = _case(n_acls=2, rules_per_acl=40, n=1024, seed=3)
    rules = jnp.asarray(packed.rules)
    deny = jnp.asarray(packed.deny_key.astype(np.uint32))
    want = np.asarray(match_keys(cols, rules, deny))
    got = np.asarray(
        pallas_match.match_keys_pallas(cols, rules, deny, interpret=True)
    )
    np.testing.assert_array_equal(got, want)


def test_no_match_lines_get_no_match():
    packed, cols, tuples = _case(n=512, seed=5)
    # lines pointing at a non-existent ACL gid never match any rule
    cols = dict(cols)
    cols["acl"] = jnp.full_like(cols["acl"], 0xFFFF)
    got = np.asarray(
        pallas_match.first_match_rows_pallas(
            cols, jnp.asarray(packed.rules), interpret=True
        )
    )
    assert (got == 0xFFFFFFFF).all()


def test_stream_report_identical_across_match_impls(tmp_path):
    """The full driver with match_impl=pallas must produce the exact
    report of the XLA path (pallas runs in its compiled form on TPU; on
    the CPU test backend pallas_call executes via the interpreter-backed
    lowering, exercising the same wiring)."""
    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.runtime.stream import run_stream

    packed, _, tuples = _case(n=600, seed=9)
    lines = synth.render_syslog(packed, tuples, seed=10)

    def run(impl):
        cfg = AnalysisConfig(
            batch_size=256,
            sketch=SketchConfig(cms_width=1 << 10, cms_depth=2, hll_p=6),
            match_impl=impl,
        )
        return run_stream(packed, iter(lines), cfg)

    a, b = run("xla"), run("pallas")
    assert a.per_rule == b.per_rule
    assert a.unused == b.unused
    assert a.talkers == b.talkers


def test_rule_padding_to_lane_tile():
    # 442-ish expanded rows, staged into whole rule blocks: the staging
    # rows past R must never match, not even all-zero lines
    packed, cols, _ = _case(n_acls=4, rules_per_acl=64, n=512, seed=7)
    rules = jnp.asarray(packed.rules)
    staged = pallas_match.stage_rules(rules)
    rows = staged.shape[0] // pallas_match.RULE_WORDS
    assert rows % pack.RULE_BLOCK == 0 and rows > rules.shape[0]
    zero_cols = {k: jnp.zeros(512, dtype=jnp.uint32) for k in cols}
    got = np.asarray(
        pallas_match.first_match_rows_pallas(zero_cols, rules, interpret=True)
    )
    want = np.asarray(first_match_rows(zero_cols, rules))
    np.testing.assert_array_equal(got, want)


def _hand_rules(n_rows: int, n_acls: int, rng) -> np.ndarray:
    """``n_rows`` rows over ``n_acls`` ACLs: random boxes, with full-range
    rows (every field 0..max) and single-point rows mixed in."""
    rules = np.zeros((n_rows, pack.RULE_COLS), np.uint32)
    rules[:, pack.R_ACL] = np.sort(rng.integers(0, n_acls, n_rows))
    spans = (
        (pack.R_PLO, pack.R_PHI, 0xFF),
        (pack.R_SLO, pack.R_SHI, 0xFFFFFFFF),
        (pack.R_SPLO, pack.R_SPHI, 0xFFFF),
        (pack.R_DLO, pack.R_DHI, 0xFFFFFFFF),
        (pack.R_DPLO, pack.R_DPHI, 0xFFFF),
    )
    kind = rng.integers(0, 4, n_rows)  # 0 full range, 1 point, else a box
    for lo_c, hi_c, top in spans:
        a = rng.integers(0, top + 1, n_rows, dtype=np.uint64)
        b = rng.integers(0, top + 1, n_rows, dtype=np.uint64)
        lo, hi = np.minimum(a, b), np.maximum(a, b)
        lo = np.where(kind == 0, 0, lo)
        hi = np.where(kind == 0, top, np.where(kind == 1, lo, hi))
        rules[:, lo_c] = lo.astype(np.uint32)
        rules[:, hi_c] = hi.astype(np.uint32)
    rules[:, pack.R_KEY] = np.arange(n_rows, dtype=np.uint32)
    return rules


def _hand_lines(rules: np.ndarray, n: int, n_acls: int, rng) -> np.ndarray:
    """Lines on rows' low corners, uniform lines, lines of an ACL with no
    rows, which match nothing, and lines that match only padding rows."""
    pick = rules[rng.integers(0, rules.shape[0], n)]
    t = np.stack(
        [pick[:, pack.R_ACL], pick[:, pack.R_PLO], pick[:, pack.R_SLO],
         pick[:, pack.R_SPLO], pick[:, pack.R_DLO], pick[:, pack.R_DPLO]],
        axis=1,
    ).astype(np.uint32)
    uniform = rng.random(n) < 0.3
    tops = (0xFF, 0xFFFFFFFF, 0xFFFF, 0xFFFFFFFF, 0xFFFF)
    for i, top in enumerate(tops, start=1):
        u = rng.integers(0, top + 1, n, dtype=np.uint64).astype(np.uint32)
        t[:, i] = np.where(uniform, u, t[:, i])
    t[rng.random(n) < 0.1, 0] = n_acls  # an ACL without rows
    # all-zero lines tagged NO_ACL: the padding rows' own tuple
    t[rng.random(n) < 0.05] = np.array([pack.NO_ACL, 0, 0, 0, 0, 0], np.uint32)
    return t


@pytest.mark.parametrize("n_rows", [300, 1300], ids=["one_rule_block", "three_rule_blocks"])
@pytest.mark.parametrize("n_lines", [1, 1000, 1024, 4097])
def test_rows_bit_identical_to_xla_scan(n_lines, n_rows):
    """Any B (padded to the line tile) and any R (staged into rule
    blocks): several ACLs, full-range and single-point rows, lines that
    match nothing, and pack's NO_ACL padding rows past a block."""
    rng = np.random.default_rng(n_lines * 7 + n_rows)
    n_acls = 4
    hand = _hand_rules(n_rows, n_acls, rng)
    # one rule block scans R rows as they are; several take pack's
    # padding (NO_ACL rows) to a whole number of blocks, as shipped
    rules_np = hand if n_rows <= pack.RULE_BLOCK else pad_rules(hand, pack.RULE_BLOCK)
    assert (rules_np[n_rows:, pack.R_ACL] == pack.NO_ACL).all()
    rules = jnp.asarray(rules_np)
    cols = _cols(_hand_lines(hand, n_lines, n_acls, rng))
    want = np.asarray(first_match_rows(cols, rules))
    got = np.asarray(pallas_match.first_match_rows_pallas(cols, rules, interpret=True))
    assert got.shape == (n_lines,)
    np.testing.assert_array_equal(got, want)
    if n_lines >= 1000:
        assert (want == NO_MATCH).any() and (want != NO_MATCH).any()
