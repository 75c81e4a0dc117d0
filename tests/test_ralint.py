"""ralint — the static program-invariant lint plane (DESIGN §18).

Tier-1 coverage of the verify/ package:

- the representative program subset traces and lints clean (abstract
  eval only — no device data, no XLA compile, so this module is cheap);
- the DERIVED weighted-refusal verdicts equal the declarative table
  (config.WEIGHTED_INPUT_REFUSALS) the runtime refusal path reads — the
  no-drift acceptance criterion;
- a set of deliberately broken mini-programs (nonlinear weight use,
  ``indices_are_sorted`` without a sort, missing/unregistered ``ra.*``
  scopes, wrong merge dtype/law, weight-dependent scatter routing) is
  MUST-flag: this pins zero false negatives, not just zero false
  positives;
- the repo registry auditor (fault sites / CLI flags vs README+PARITY /
  VOLATILE totals keys) passes clean;
- the runtime weighted-input refusals are typed and driven by the table.

The FULL grid lint (~76 programs) runs under ``make lint`` and as a
``slow``-marked test here; the tier-1 subset covers every verdict class
and check dimension at least once.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from ruleset_analysis_tpu.config import (  # noqa: E402
    WEIGHTED_INPUT_REFUSALS,
    AnalysisConfig,
)
from ruleset_analysis_tpu.errors import AnalysisError  # noqa: E402
from ruleset_analysis_tpu.verify import (  # noqa: E402
    ProgramSpec,
    fast_grid,
    lint_program,
    shipping_grid,
    trace_program,
)
from ruleset_analysis_tpu.verify.grid import _sds, trace_fixture  # noqa: E402
from ruleset_analysis_tpu.verify.report import (  # noqa: E402
    check_table_drift,
    expected_weighted_refusal,
)


@pytest.fixture(scope="module")
def fast_lints():
    """Trace + lint the representative subset once per module."""
    return [lint_program(trace_program(s)) for s in fast_grid()]


# ---------------------------------------------------------------------------
# Shipping-grid verdicts
# ---------------------------------------------------------------------------


def test_fast_grid_zero_violations(fast_lints):
    """No shipping program violates scatter/scope/merge invariants."""
    for pl in fast_lints:
        viols = [f for f in pl.findings if f.severity == "violation"]
        assert not viols, (pl.spec.name, [f.kind for f in viols])


def test_fast_grid_weight_verdicts(fast_lints):
    """Derived weight-linearity per impl family, exactly as designed:
    xla/pallas matches and scatter/reduce counts and both update impls
    prove LINEAR; matmul counts derive float-bounded; the opaque
    pallas_fused kernel derives unprovable."""
    by_name = {pl.spec.name: pl for pl in fast_lints}
    for name, pl in by_name.items():
        if "pallas_fused" in name:
            assert pl.weight_verdict == "unprovable", name
        elif "matmul" in name:
            assert pl.weight_verdict == "float-bounded", name
        else:
            assert pl.weight_verdict == "linear", name


def test_fast_grid_scope_coverage(fast_lints):
    """Zero unattributed register-update primitives in shipping code."""
    for pl in fast_lints:
        scope = [f for f in pl.findings if f.check == "scope"]
        assert not scope, (pl.spec.name, [f.kind for f in scope])


def test_fast_grid_merge_seams(fast_lints):
    """Every register output crossed its law's collective with its
    law's dtype (counts exempt only under exact_counts=False)."""
    for pl in fast_lints:
        merge = [f for f in pl.findings if f.check == "merge"]
        assert not merge, (pl.spec.name, [f.kind for f in merge])
        if getattr(pl.spec, "exact_counts", True):
            assert "psum" in pl.outputs["counts_lo"]["prov"], pl.spec.name
        assert "pmax" in pl.outputs["hll"]["prov"], pl.spec.name
        assert "psum" not in pl.outputs["hll"]["prov"], pl.spec.name
        assert "all_gather" in pl.outputs["cand_est"]["prov"], pl.spec.name


def test_derived_refusals_match_table(fast_lints):
    """The no-drift criterion: derived weighted-refusal set == the ONE
    declarative table, in both directions."""
    assert check_table_drift(fast_lints) == []
    # and the table's members really are what ships in config.py
    fields = {(r.field, r.value) for r in WEIGHTED_INPUT_REFUSALS}
    assert ("match_impl", "pallas_fused") in fields
    assert ("counts_impl", "matmul") in fields
    assert len(fields) == 2  # today's exact refusal set, nothing more


def test_full_grid_enumerates_all_shipping_combos():
    """Grid membership is derived from AnalysisConfig validation: every
    spec is constructible, invalid combos are absent, and the grid
    covers the whole impl space (enumeration only — no tracing)."""
    grid = shipping_grid()
    names = {s.name for s in grid}
    assert len(names) == len(grid)  # no duplicates
    assert len(grid) >= 60
    for s in grid:
        assert s.is_shipping(), s.name
    # pallas_fused ships only with scatter counts + scatter updates
    fused = [s for s in grid if s.match_impl == "pallas_fused"]
    assert fused and all(
        s.counts_impl == "scatter" and s.update_impl == "scatter"
        for s in fused
    )
    # sorted x pallas_fused (config-refused) must NOT appear
    assert not any(
        s.match_impl == "pallas_fused" and s.update_impl == "sorted"
        for s in grid
    )
    # every kind and impl axis is represented
    assert {s.kind for s in grid} == {"flat", "stacked", "v6", "tenant"}
    assert {s.counts_impl for s in grid} == {"scatter", "matmul", "reduce"}
    assert {s.update_impl for s in grid} == {"scatter", "sorted"}


@pytest.mark.slow
def test_full_grid_lint_clean():
    """The whole shipping grid (what `make lint` traces): zero
    violations, zero table drift."""
    lints = [lint_program(trace_program(s)) for s in shipping_grid()]
    for pl in lints:
        assert pl.ok, (pl.spec.name, [f.kind for f in pl.findings])
    assert check_table_drift(lints) == []


# ---------------------------------------------------------------------------
# Negative fixtures — MUST flag (zero false negatives)
# ---------------------------------------------------------------------------

_K = 8


def _lint_fixture(fn, out_names=("out",), name="fixture"):
    keys = _sds((32,))
    w = _sds((32,))
    traced = trace_fixture(
        fn, (keys, w), weight_arg=1, output_names=out_names, name=name
    )
    return lint_program(traced)


def _kinds(pl):
    return {f.kind for f in pl.findings}


def test_fixture_nonlinear_weight_use():
    pl = _lint_fixture(
        lambda k, w: (jnp.zeros(_K, jnp.uint32).at[k].add(w * w, mode="drop"),)
    )
    assert "nonlinear-into-add" in _kinds(pl)
    assert pl.weight_verdict == "nonlinear"


def test_fixture_gated_into_add():
    """The pallas_fused bug class spelled in pure jax: counting one per
    valid row instead of the row's weight."""
    pl = _lint_fixture(
        lambda k, w: (
            jnp.zeros(_K, jnp.uint32)
            .at[k]
            .add((w > 0).astype(jnp.uint32), mode="drop"),
        )
    )
    assert "gated-into-add" in _kinds(pl)
    assert pl.weight_verdict == "gated"


def test_fixture_float_roundtrip():
    pl = _lint_fixture(
        lambda k, w: (
            jnp.zeros(_K, jnp.uint32)
            .at[k]
            .add(w.astype(jnp.float32).astype(jnp.uint32), mode="drop"),
        )
    )
    assert "float-into-add" in _kinds(pl)
    assert pl.weight_verdict == "float-bounded"


def test_fixture_sorted_claim_without_sort():
    pl = _lint_fixture(
        lambda k, w: (
            jnp.zeros(_K, jnp.uint32)
            .at[k]
            .add(w, mode="drop", indices_are_sorted=True),
        )
    )
    assert "sorted-claim-without-sort" in _kinds(pl)


def test_fixture_sorted_claim_with_sort_passes():
    """The positive twin: a genuine sort on the key chain is accepted."""

    def fn(k, w):
        ks, ws = lax.sort((k, w), num_keys=1)
        with jax.named_scope("ra.counts"):
            d = jnp.zeros(_K, jnp.uint32).at[ks].add(
                ws, mode="drop", indices_are_sorted=True
            )
        return (d,)

    pl = _lint_fixture(fn)
    assert "sorted-claim-without-sort" not in _kinds(pl)


def test_fixture_scatter_without_drop():
    pl = _lint_fixture(
        lambda k, w: (jnp.zeros(_K, jnp.uint32).at[k].add(w, mode="clip"),)
    )
    assert "scatter-not-drop" in _kinds(pl)


def test_fixture_missing_scope():
    pl = _lint_fixture(
        lambda k, w: (jnp.zeros(_K, jnp.uint32).at[k].add(w, mode="drop"),)
    )
    assert "unattributed-register-update" in _kinds(pl)


def test_fixture_unregistered_stage():
    def fn(k, w):
        with jax.named_scope("ra.bogus"):
            return (jnp.zeros(_K, jnp.uint32).at[k].add(w, mode="drop"),)

    pl = _lint_fixture(fn)
    assert "unregistered-stage" in _kinds(pl)


def test_fixture_linear_into_max():
    """Weight magnitude into a max-law register: not idempotent."""
    pl = _lint_fixture(
        lambda k, w: (jnp.zeros(_K, jnp.uint32).at[k].max(w, mode="drop"),)
    )
    assert "linear-into-max" in _kinds(pl)


def test_fixture_wrong_merge_law():
    """An hll output merged by psum: wrong law + missing pmax seam."""

    def fn(k, w):
        with jax.named_scope("ra.hll"):
            d = jnp.zeros(_K, jnp.uint32).at[k].max(
                (w > 0).astype(jnp.uint32), mode="drop"
            )
        with jax.named_scope("ra.merge"):
            return (lax.psum(d, "data"),)

    pl = _lint_fixture(fn, out_names=("hll",))
    assert "wrong-merge-law" in _kinds(pl)
    assert "missing-merge-seam" in _kinds(pl)


def test_fixture_missing_merge_seam():
    def fn(k, w):
        with jax.named_scope("ra.cms"):
            return (jnp.zeros(_K, jnp.uint32).at[k].add(w, mode="drop"),)

    pl = _lint_fixture(fn, out_names=("cms",))
    assert "missing-merge-seam" in _kinds(pl)


def test_fixture_bad_register_dtype():
    def fn(k, w):
        with jax.named_scope("ra.cms"):
            d = jnp.zeros(_K, jnp.float32).at[k].add(
                w.astype(jnp.float32), mode="drop"
            )
        with jax.named_scope("ra.merge"):
            return (lax.psum(d, "data"),)

    pl = _lint_fixture(fn, out_names=("cms",))
    assert "register-dtype" in _kinds(pl)


def test_fixture_weight_dependent_indices():
    pl = _lint_fixture(
        lambda k, w: (
            jnp.zeros(_K, jnp.uint32)
            .at[(k + w) % _K]
            .add(jnp.ones(32, jnp.uint32), mode="drop"),
        )
    )
    assert "tainted-scatter-indices" in _kinds(pl)
    assert pl.weight_verdict == "nonlinear"


# ---------------------------------------------------------------------------
# Registry auditor + runtime refusal path
# ---------------------------------------------------------------------------


def test_registry_audit_clean():
    """Fault sites <-> call sites <-> tests; CLI flags <-> README <->
    PARITY; VOLATILE keys <-> producers: all clean in this repo."""
    from ruleset_analysis_tpu.verify import audit_registry

    findings = audit_registry()
    assert findings == [], [
        (f.registry, f.kind, f.subject) for f in findings
    ]


def test_weighted_runtime_refusals_typed_and_table_driven():
    """The runtime refusal path consumes the SAME table the linter
    cross-checks: both table entries refuse typed, everything else
    passes (incl. sorted updates — weight-linear by construction)."""
    from ruleset_analysis_tpu.runtime.stream import (
        _check_weighted_input_config,
    )

    with pytest.raises(AnalysisError, match="pallas_fused"):
        _check_weighted_input_config(
            AnalysisConfig(match_impl="pallas_fused")
        )
    with pytest.raises(AnalysisError, match="matmul"):
        _check_weighted_input_config(AnalysisConfig(counts_impl="matmul"))
    _check_weighted_input_config(AnalysisConfig())
    _check_weighted_input_config(AnalysisConfig(update_impl="sorted"))
    _check_weighted_input_config(AnalysisConfig(counts_impl="reduce"))


def test_expected_refusal_helper_matches_config_fields():
    assert expected_weighted_refusal(
        ProgramSpec(kind="flat", match_impl="pallas_fused")
    ) == "unprovable"
    assert expected_weighted_refusal(
        ProgramSpec(kind="v6", counts_impl="matmul")
    ) == "float-bounded"
    assert expected_weighted_refusal(ProgramSpec(kind="flat")) is None


def test_stages_vocabulary_single_source():
    """devprof re-exports the stages.py tuple — identity, not a copy."""
    from ruleset_analysis_tpu import stages
    from ruleset_analysis_tpu.runtime import devprof

    assert devprof.STAGES is stages.STAGES
    assert stages.scope_of("jit/ra.talk/ra.cms/scatter") == "ra.talk"
    assert stages.scope_of("fusion.5") is None


def test_volatile_totals_imported_by_identity_suites():
    """The canonical list covers every key the per-module lists held."""
    from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS

    assert set(VOLATILE_TOTALS) >= {
        "elapsed_sec", "lines_per_sec", "compile_sec",
        "sustained_lines_per_sec", "ingest", "throughput", "coalesce",
        "autoscale", "recovery", "devprof",
    }
