"""Pallas first-match kernel: bit-equality with the XLA-fused path.

Runs in pallas interpret mode on the CPU test mesh (the kernel compiles
natively on TPU: ``chip_smoke.py`` runs it there against the oracle,
``tests/test_tpu_compile.py`` compiles it for a described v5e).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ruleset_analysis_tpu.hostside import aclparse, pack, synth  # noqa: E402
from ruleset_analysis_tpu.ops import pallas_match  # noqa: E402
from ruleset_analysis_tpu.ops.match import first_match_rows, match_keys  # noqa: E402


def _case(n_acls=3, rules_per_acl=24, n=2048, seed=0):
    cfg_text = synth.synth_config(n_acls=n_acls, rules_per_acl=rules_per_acl, seed=seed)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, n, seed=seed + 1)
    cols = {
        k: jnp.asarray(tuples[:, i])
        for k, i in zip(["acl", "proto", "src", "sport", "dst", "dport"], range(6))
    }
    return packed, cols, tuples


@pytest.mark.parametrize("seed", [0, 1])
def test_rows_match_xla_path(seed):
    packed, cols, _ = _case(seed=seed)
    rules = jnp.asarray(packed.rules)
    want = np.asarray(first_match_rows(cols, rules))
    got = np.asarray(
        pallas_match.first_match_rows_pallas(
            cols, pallas_match.prep_rules(rules), block_lines=512, interpret=True
        )
    )
    np.testing.assert_array_equal(got, want)


def test_keys_match_xla_path():
    packed, cols, _ = _case(n_acls=2, rules_per_acl=40, n=1024, seed=3)
    rules = jnp.asarray(packed.rules)
    deny = jnp.asarray(packed.deny_key.astype(np.uint32))
    want = np.asarray(match_keys(cols, rules, deny))
    got = np.asarray(
        pallas_match.match_keys_pallas(
            cols, rules, pallas_match.prep_rules(rules), deny,
            block_lines=256, interpret=True,
        )
    )
    np.testing.assert_array_equal(got, want)


def test_no_match_lines_get_no_match():
    packed, cols, tuples = _case(n=512, seed=5)
    # lines pointing at a non-existent ACL gid never match any rule
    cols = dict(cols)
    cols["acl"] = jnp.full_like(cols["acl"], 0xFFFF)
    got = np.asarray(
        pallas_match.first_match_rows_pallas(
            cols, pallas_match.prep_rules(jnp.asarray(packed.rules)),
            block_lines=512, interpret=True,
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
    # 442-ish expanded rows -> padded to a multiple of 128; padding columns
    # must never match even all-zero lines
    packed, cols, _ = _case(n_acls=4, rules_per_acl=64, n=512, seed=7)
    fm = pallas_match.prep_rules(jnp.asarray(packed.rules))
    assert fm.shape[1] % pallas_match.RULE_TILE == 0
    zero_cols = {k: jnp.zeros(512, dtype=jnp.uint32) for k in cols}
    got = np.asarray(
        pallas_match.first_match_rows_pallas(
            zero_cols, fm, block_lines=512, interpret=True
        )
    )
    want = np.asarray(
        first_match_rows(zero_cols, jnp.asarray(packed.rules))
    )
    np.testing.assert_array_equal(got, want)
