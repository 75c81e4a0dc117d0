"""Fused pallas match+count kernel: bit-equality with the XLA path.

The fused kernel (ops/pallas_fused.py) replaces the batch-sized
exact-counts scatter with in-VMEM histograms + a row-sized fold.  These
tests pin its keys AND counts delta bit-identical to
``segment_counts(match_keys(...), valid)`` — including no-match lines
(implicit deny), invalid lines, lane/batch padding, and the full stream
driver.  Interpret mode on CPU.  The kernel runs in no benchmark cell.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ruleset_analysis_tpu.hostside import aclparse, pack, synth  # noqa: E402
from ruleset_analysis_tpu.ops import pallas_fused, pallas_match  # noqa: E402
from ruleset_analysis_tpu.ops.counts import segment_counts  # noqa: E402
from ruleset_analysis_tpu.ops.match import match_keys  # noqa: E402


def _case(n_acls=3, rules_per_acl=24, n=2048, seed=0, invalidate_every=0):
    cfg_text = synth.synth_config(n_acls=n_acls, rules_per_acl=rules_per_acl, seed=seed)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, n, seed=seed + 1)
    valid = np.ones(n, dtype=np.uint32)
    if invalidate_every:
        valid[::invalidate_every] = 0
    cols = {
        k: jnp.asarray(tuples[:, i])
        for k, i in zip(["acl", "proto", "src", "sport", "dst", "dport"], range(6))
    }
    return packed, cols, jnp.asarray(valid), tuples


def _want(packed, cols, valid):
    rules = jnp.asarray(packed.rules)
    deny = jnp.asarray(packed.deny_key.astype(np.uint32))
    keys = match_keys(cols, rules, deny)
    delta = segment_counts(keys, valid, packed.n_keys)
    return np.asarray(keys), np.asarray(delta)


def _got(packed, cols, valid, block_lines=512):
    rules = jnp.asarray(packed.rules)
    deny = jnp.asarray(packed.deny_key.astype(np.uint32))
    keys, delta = pallas_fused.match_keys_and_counts_pallas(
        cols, valid, rules, pallas_match.prep_rules(rules), deny,
        packed.n_keys, block_lines=block_lines, interpret=True,
    )
    return np.asarray(keys), np.asarray(delta)


@pytest.mark.parametrize("seed", [0, 1, 5])
def test_keys_and_counts_match_xla(seed):
    packed, cols, valid, _ = _case(seed=seed)
    wk, wd = _want(packed, cols, valid)
    gk, gd = _got(packed, cols, valid)
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gd, wd)
    assert int(gd.sum()) == int(np.asarray(valid).sum())  # every valid line counted once


def test_invalid_lines_excluded_from_counts():
    packed, cols, valid, _ = _case(n=1024, seed=2, invalidate_every=3)
    wk, wd = _want(packed, cols, valid)
    gk, gd = _got(packed, cols, valid)
    np.testing.assert_array_equal(gd, wd)
    assert int(gd.sum()) == int(np.asarray(valid).sum())


def test_unmatched_lines_land_on_deny_keys():
    # all-zero lines match nothing (proto 0 with port ranges etc. may
    # match permit-ip-any rules; use acl ids beyond range to force the
    # clamp + deny path on SOME lines instead): zero tuples on acl 0
    packed, cols, valid, _ = _case(n_acls=2, rules_per_acl=8, n=512, seed=4)
    zero_cols = {k: jnp.zeros(512, dtype=jnp.uint32) for k in cols}
    wk, wd = _want(packed, zero_cols, valid)
    gk, gd = _got(packed, zero_cols, valid)
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gd, wd)


def test_batch_not_multiple_of_block():
    packed, cols, valid, _ = _case(n=700, seed=6)  # 700 % 512 != 0
    wk, wd = _want(packed, cols, valid)
    gk, gd = _got(packed, cols, valid, block_lines=512)
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gd, wd)


def test_stream_report_identical_fused_vs_xla():
    """Full driver with match_impl=pallas_fused == XLA path, exactly."""
    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.runtime.stream import run_stream

    packed, _, _, tuples = _case(n=600, seed=9)
    lines = synth.render_syslog(packed, tuples, seed=10)

    def run(impl):
        cfg = AnalysisConfig(
            batch_size=256,
            sketch=SketchConfig(cms_width=1 << 10, cms_depth=2, hll_p=6),
            match_impl=impl,
        )
        return run_stream(packed, iter(lines), cfg)

    a, b = run("xla"), run("pallas_fused")
    assert a.per_rule == b.per_rule
    assert a.unused == b.unused
    assert a.talkers == b.talkers
    assert a.totals["lines_matched"] == b.totals["lines_matched"]


def test_stacked_layout_refuses_fused():
    from ruleset_analysis_tpu.config import AnalysisConfig

    with pytest.raises(ValueError, match="layout='flat' only"):
        AnalysisConfig(match_impl="pallas_fused", layout="stacked")


@pytest.mark.parametrize("impl", ["matmul", "reduce"])
def test_counts_impl_reports_identical_to_scatter(impl):
    """counts_impl formulations are bit-identical through the full driver
    (ops/counts.py SEGMENT_COUNTS_IMPLS; the TPU stage bench prices them)."""
    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.runtime.stream import run_stream

    packed, _, _, tuples = _case(n=600, seed=11)
    lines = synth.render_syslog(packed, tuples, seed=12)

    def run(ci):
        cfg = AnalysisConfig(
            batch_size=256,
            sketch=SketchConfig(cms_width=1 << 10, cms_depth=2, hll_p=6),
            counts_impl=ci,
        )
        return run_stream(packed, iter(lines), cfg)

    a, b = run("scatter"), run(impl)
    assert a.per_rule == b.per_rule
    assert a.unused == b.unused
    assert a.totals["lines_matched"] == b.totals["lines_matched"]


def test_counts_impl_validation():
    from ruleset_analysis_tpu.config import AnalysisConfig

    with pytest.raises(ValueError, match="counts_impl"):
        AnalysisConfig(counts_impl="bogus")


def test_out_of_range_acl_counts_match_xla():
    """A valid line with a corrupt acl gid (>= n_acls) must land on the
    LAST ACL's deny key in BOTH keys and counts — the kernel clamps
    exactly as rows_to_keys does (review r5 finding)."""
    packed, cols, valid, _ = _case(n_acls=2, rules_per_acl=8, n=512, seed=8)
    bad = dict(cols)
    bad["acl"] = jnp.full(512, 999, dtype=jnp.uint32)  # way out of range
    wk, wd = _want(packed, bad, valid)
    gk, gd = _got(packed, bad, valid)
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gd, wd)
    assert int(gd.sum()) == 512  # every valid line counted exactly once


def test_fused_rejects_nondefault_counts_impl():
    from ruleset_analysis_tpu.config import AnalysisConfig

    with pytest.raises(ValueError, match="computes counts in-kernel"):
        AnalysisConfig(match_impl="pallas_fused", counts_impl="matmul")
