"""Sorted segment-reduce register updates (ISSUE 9, DESIGN §15).

Assertion tiers:

- **op-level value identity** — the sorted formulations
  (ops/sorted_update.py) produce byte-equal register arrays to the
  scatter formulations on adversarial inputs: out-of-range keys (the
  ``mode="drop"`` contract), zero and >1 weights (the coalesce plane),
  slot collisions, plus the composite-overflow fallback;
- **driver bit-identity matrix** — ``update_impl=sorted`` reports are
  bit-identical to ``scatter`` across flat/stacked x text/wire x
  v4/v6 x sync/prefetch x weighted/unweighted, including crash-at-K
  resume ACROSS impls (the checkpoint fingerprint deliberately excludes
  update_impl) and seeded chaos schedules;
- **deferred selection** — ``topk_every > 1`` defers candidate
  selection identically in both impls (cross-impl identity at the same
  cadence), registers/hits/unused are cadence-invariant, and the
  cadence folds into the checkpoint fingerprint only when non-default;
- **typed refusals + weight safety** — sorted x pallas_fused is a
  config-time refusal (CLI exit 2), while weighted (RAWIREv3) inputs
  are ACCEPTED under sorted everywhere (weight-linear by construction);
- **attribution** — the sorts trace under the ``ra.sort`` named scope
  and the vocabulary knows the stage.

The corpus deliberately reuses test_obs/test_devprof's ruleset + sketch
geometry (synth seed 7, 3 ACLs x 8 rules, batch 512, cms 1<<10 x 2,
hll_p 6): the SCATTER-side specialized step jit is keyed on the ruleset
value, so the baseline runs here share one XLA compile with those
suites in a tier-1 process — only the sorted-side programs compile
fresh (the 870 s gate is a hard budget, ROADMAP).
"""

import json
import os

import pytest

pytest.importorskip("jax")

import jax
import jax.numpy as jnp
import numpy as np

from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
from ruleset_analysis_tpu.errors import InjectedFault
from ruleset_analysis_tpu.hostside import aclparse, pack, synth
from ruleset_analysis_tpu.hostside import wire as wire_mod
from ruleset_analysis_tpu.ops import cms as cms_ops
from ruleset_analysis_tpu.ops import counts as count_ops
from ruleset_analysis_tpu.ops import hll as hll_ops
from ruleset_analysis_tpu.ops import sorted_update as sorted_ops
from ruleset_analysis_tpu.ops import topk as topk_ops
from ruleset_analysis_tpu.runtime import checkpoint as ckpt_mod
from ruleset_analysis_tpu.runtime.stream import (
    run_stream_file,
    run_stream_wire,
)

# ONE volatile-keys list (runtime/report.py): the registry auditor
# (verify/registry.py) flags any module keeping a private copy.
from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS as VOLATILE


def report_image(rep) -> dict:
    j = json.loads(rep.to_json())
    for k in VOLATILE:
        j["totals"].pop(k, None)
    return j


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    # SAME ruleset + geometry as test_obs/test_devprof (see module doc)
    td = tmp_path_factory.mktemp("sorted")
    cfg_text = synth.synth_config(n_acls=3, rules_per_acl=8, seed=7)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    tuples = synth.synth_tuples(packed, 2600, seed=18)
    lines = synth.render_syslog(packed, tuples, seed=19)
    log = str(td / "s.log")
    with open(log, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    wirep = str(td / "s.rawire")
    wire_mod.convert_logs(packed, [log], wirep, block_rows=512)
    wirew = str(td / "sw.rawire")
    wire_mod.convert_logs(
        packed, [log], wirew, batch_size=512, block_rows=512, coalesce=True
    )
    prefix = str(td / "packed")
    pack.save_packed(packed, prefix)
    return packed, prefix, log, wirep, wirew


@pytest.fixture(scope="module")
def corpus6(tmp_path_factory):
    """Mixed v4+v6 corpus so the matrix covers the step.v6 program."""
    td = tmp_path_factory.mktemp("sorted6")
    cfg_text = synth.synth_config(
        n_acls=2, rules_per_acl=8, seed=27, v6_fraction=0.4
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    t4 = synth.synth_tuples(packed, 1400, seed=28)
    lines = synth.render_syslog(packed, t4, seed=29)
    t6 = synth.synth_tuples6(packed, 1000, seed=30)
    lines += synth.render_syslog6(packed, t6, seed=31)
    log = str(td / "s6.log")
    with open(log, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    return packed, log


def _cfg(depth=0, **kw):
    sk = dict(cms_width=1 << 10, cms_depth=2, hll_p=6)
    sk.update(kw.pop("sk", {}))
    return AnalysisConfig(
        batch_size=512,
        sketch=SketchConfig(**sk),
        prefetch_depth=depth,
        stall_timeout_sec=5.0,
        **kw,
    )


@pytest.fixture(scope="module")
def baselines(corpus):
    """Fault-free SCATTER reports (identity anchors), computed once.

    The scatter step at this geometry is the compile test_obs and
    test_devprof already paid for in a tier-1 process.
    """
    packed, _prefix, log, wirep, wirew = corpus
    return {
        "wire0": run_stream_wire(packed, [wirep], _cfg(depth=0)),
        "wire2": run_stream_wire(packed, [wirep], _cfg(depth=2)),
        "text0": run_stream_file(packed, [log], _cfg(depth=0), native=False),
        "wirew0": run_stream_wire(packed, [wirew], _cfg(depth=0)),
    }


# ---------------------------------------------------------------------------
# Op-level value identity.
# ---------------------------------------------------------------------------


def test_counts_hll_sorted_matches_scatter_ops():
    rng = np.random.default_rng(0)
    b, n_keys, p = 1500, 37, 4
    m = 1 << p
    keys = jnp.asarray(rng.integers(0, n_keys + 7, b), dtype=jnp.uint32)
    w = jnp.asarray(rng.integers(0, 5, b), dtype=jnp.uint32)  # weights incl 0
    src = jnp.asarray(rng.integers(0, 2**32, b, dtype=np.uint32))
    hll0 = jnp.asarray(rng.integers(0, 3, (n_keys, m)), dtype=jnp.uint32)

    ref_counts = count_ops.segment_counts(keys, w, n_keys)
    ref_hll = hll_ops.hll_update(hll0, keys, src, w)
    delta, new_hll = sorted_ops.counts_hll_sorted(
        hll0, keys, w, src, n_keys, need_counts=True
    )
    assert np.array_equal(np.asarray(delta), np.asarray(ref_counts))
    assert np.array_equal(np.asarray(new_hll), np.asarray(ref_hll))
    # counts skipped when another counts_impl owns the stage
    none_delta, only_hll = sorted_ops.counts_hll_sorted(
        hll0, keys, w, src, n_keys, need_counts=False
    )
    assert none_delta is None
    assert np.array_equal(np.asarray(only_hll), np.asarray(ref_hll))


def test_talker_tables_sorted_match_scatter_tables():
    rng = np.random.default_rng(1)
    b, width, depth, slots = 2000, 1 << 10, 2, topk_ops.CAND_SLOTS
    acl = jnp.asarray(rng.integers(0, 6, b), dtype=jnp.uint32)
    src = jnp.asarray(rng.integers(0, 50, b), dtype=jnp.uint32)  # collisions
    w = jnp.asarray(rng.integers(0, 4, b), dtype=jnp.uint32)
    salt = jnp.uint32(5)
    talk0 = jnp.asarray(rng.integers(0, 9, (depth, width)), dtype=jnp.uint32)

    pair = topk_ops.hash_pair(acl, src)
    ref_cms = cms_ops.cms_update(talk0, pair, w)
    slot = np.asarray(topk_ops.cand_slot(pair, salt, slots))
    v32 = np.asarray(w)
    ref_cnt = np.zeros(slots, np.uint32)
    np.add.at(ref_cnt, slot, v32)
    ref_rep = np.full(slots, -1, np.int64)
    for i in range(b):
        if v32[i] > 0:
            ref_rep[slot[i]] = max(ref_rep[slot[i]], i)

    cd, cnt, rep = sorted_ops.talker_tables_sorted(
        acl, src, w, salt, width=width, depth=depth, slots=slots
    )
    assert np.array_equal(np.asarray(talk0 + cd), np.asarray(ref_cms))
    assert np.array_equal(np.asarray(cnt), ref_cnt)
    assert np.array_equal(np.asarray(rep), ref_rep)
    # the deferred-chunk variant: same CMS values, empty tables
    cd2, cnt2, rep2 = sorted_ops.talker_tables_sorted(
        acl, src, w, salt, width=width, depth=depth, slots=slots,
        with_candidates=False,
    )
    assert np.array_equal(np.asarray(cd2), np.asarray(cd))
    assert int(np.asarray(cnt2).sum()) == 0
    assert np.all(np.asarray(rep2) == -1)


def test_composite_overflow_falls_back_value_identically(monkeypatch):
    """Geometries whose (key, register) composite would wrap uint32 take
    the scatter path inside the sorted entry point — same values."""
    assert sorted_ops.composite_fits(1 << 20, 256)
    assert not sorted_ops.composite_fits(1 << 24, 256)
    rng = np.random.default_rng(2)
    b, n_keys, p = 600, 19, 3
    keys = jnp.asarray(rng.integers(0, n_keys + 3, b), dtype=jnp.uint32)
    w = jnp.asarray(rng.integers(0, 3, b), dtype=jnp.uint32)
    src = jnp.asarray(rng.integers(0, 2**32, b, dtype=np.uint32))
    hll0 = jnp.zeros((n_keys, 1 << p), dtype=jnp.uint32)
    want_d, want_h = sorted_ops.counts_hll_sorted(
        hll0, keys, w, src, n_keys, need_counts=True
    )
    monkeypatch.setattr(sorted_ops, "COMPOSITE_LIMIT", 4)  # force fallback
    got_d, got_h = sorted_ops.counts_hll_sorted(
        hll0, keys, w, src, n_keys, need_counts=True
    )
    assert np.array_equal(np.asarray(want_d), np.asarray(got_d))
    assert np.array_equal(np.asarray(want_h), np.asarray(got_h))


def test_sorted_scopes_in_hlo():
    """The sorts trace under ra.sort; devprof's vocabulary knows the stage."""
    from ruleset_analysis_tpu.runtime import devprof

    assert "ra.sort" in devprof.STAGES
    assert devprof.scope_of("jit(f)/ra.sort/sort.1") == "ra.sort"
    b = 128
    keys = jnp.zeros(b, jnp.uint32)
    w = jnp.ones(b, jnp.uint32)
    src = jnp.arange(b, dtype=jnp.uint32)
    txt = (
        jax.jit(
            lambda k, v, s: sorted_ops.counts_hll_sorted(
                jnp.zeros((16, 16), jnp.uint32), k, v, s, 16, need_counts=True
            )
        )
        .lower(keys, w, src)
        .compile()
        .as_text()
    )
    assert "ra.sort" in txt and "ra.counts" in txt and "ra.hll" in txt
    txt2 = (
        jax.jit(
            lambda a, s, v: sorted_ops.talker_tables_sorted(
                a, s, v, jnp.uint32(0), width=256, depth=2, slots=1 << 10
            )
        )
        .lower(keys, src, w)
        .compile()
        .as_text()
    )
    assert "ra.sort" in txt2 and "ra.talk" in txt2 and "ra.topk" in txt2


# ---------------------------------------------------------------------------
# Driver bit-identity matrix.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("inp,depth", [
    ("wire", 0),
    ("wire", 2),   # prefetch: same sorted program, jit cache hit
    ("text", 0),
])
def test_sorted_flat_bit_identical(corpus, baselines, inp, depth):
    packed, _prefix, log, wirep, _wirew = corpus
    cfg = _cfg(depth=depth, update_impl="sorted")
    rep = (
        run_stream_wire(packed, [wirep], cfg)
        if inp == "wire"
        else run_stream_file(packed, [log], cfg, native=False)
    )
    assert report_image(rep) == report_image(baselines[f"{inp}{depth}"])


def test_sorted_weighted_wire_accepted_and_identical(corpus, baselines):
    """RAWIREv3 weighted input under sorted: accepted (weight-linear by
    construction) and bit-identical to the scatter path on the SAME file."""
    packed, _prefix, _log, _wirep, wirew = corpus
    rep = run_stream_wire(
        packed, [wirew], _cfg(depth=0, update_impl="sorted")
    )
    assert rep.totals["wire_weighted"] is True
    assert report_image(rep) == report_image(baselines["wirew0"])


def test_sorted_v6_coalesced_bit_identical(corpus6):
    """Mixed v4+v6 text under runtime coalescing: both family programs
    run the sorted tail over the weighted valid plane."""
    packed, log = corpus6
    base = run_stream_file(
        packed, [log], _cfg(depth=2, coalesce="on"), native=False
    )
    rep = run_stream_file(
        packed, [log], _cfg(depth=2, coalesce="on", update_impl="sorted"),
        native=False,
    )
    assert report_image(rep) == report_image(base)


def test_sorted_stacked_bit_identical(corpus):
    packed, _prefix, log, _wirep, _wirew = corpus
    kw = dict(layout="stacked", stacked_lane=8192)
    base = run_stream_file(packed, [log], _cfg(depth=0, **kw), native=False)
    rep = run_stream_file(
        packed, [log], _cfg(depth=0, update_impl="sorted", **kw), native=False
    )
    assert report_image(rep) == report_image(base)


def test_crash_resume_across_impls(corpus, baselines, tmp_path):
    """Crash under scatter at chunk K, resume under sorted — identical to
    the uninterrupted scatter run.  This is only sound because the two
    formulations produce bit-identical REGISTERS, and is why update_impl
    stays out of the checkpoint fingerprint."""
    packed, _prefix, log, _wirep, _wirew = corpus
    ref = run_stream_file(packed, [log], _cfg(depth=0), native=False)
    ck = str(tmp_path / "ck")
    cfg = _cfg(depth=0).replace(checkpoint_every_chunks=2, checkpoint_dir=ck)
    crashed = run_stream_file(packed, [log], cfg, native=False, max_chunks=3)
    assert crashed.totals["lines_total"] < ref.totals["lines_total"]
    resumed = run_stream_file(
        packed, [log], cfg.replace(resume=True, update_impl="sorted"),
        native=False,
    )
    assert report_image(resumed) == report_image(ref)


#: Seeded chaos schedules under update_impl=sorted: the sorted programs
#: must inherit the whole failure model — typed abort, no hang, process
#: healthy afterwards (the bit-identical next run).  Two in tier-1
#: (producer raise + coalesce fault x sorted), two more in the slow soak.
_CHAOS = [
    ("ingest.producer.raise@2,seed=201", 2, {}),
    ("ingest.coalesce.fail@1,seed=202", 2, {"coalesce": "on"}),
]
_CHAOS_SLOW = [
    ("ingest.queue.stall@2,seed=203", 2, {}),
    ("ingest.producer.raise@1,seed=204", 3, {"coalesce": "on"}),
]


@pytest.mark.parametrize("plan,depth,kw", _CHAOS)
def test_chaos_sorted_typed_abort_then_healthy(corpus, baselines, plan, depth, kw):
    from ruleset_analysis_tpu.errors import AnalysisError

    packed, _prefix, _log, wirep, _wirew = corpus
    with pytest.raises(AnalysisError):
        run_stream_wire(
            packed, [wirep],
            _cfg(depth=depth, update_impl="sorted", fault_plan=plan, **kw),
        )
    again = run_stream_wire(
        packed, [wirep], _cfg(depth=2, update_impl="sorted", **kw)
    )
    assert report_image(again) == report_image(baselines["wire2"])


@pytest.mark.slow
@pytest.mark.parametrize("plan,depth,kw", _CHAOS_SLOW)
def test_chaos_sorted_soak(corpus, baselines, plan, depth, kw):
    from ruleset_analysis_tpu.errors import AnalysisError

    packed, _prefix, _log, wirep, _wirew = corpus
    with pytest.raises(AnalysisError):
        run_stream_wire(
            packed, [wirep],
            _cfg(depth=depth, update_impl="sorted", fault_plan=plan, **kw),
        )
    again = run_stream_wire(
        packed, [wirep], _cfg(depth=2, update_impl="sorted", **kw)
    )
    assert report_image(again) == report_image(baselines["wire2"])


# ---------------------------------------------------------------------------
# Deferred selection (--topk-every).
# ---------------------------------------------------------------------------


def test_topk_every_cross_impl_identity_and_cadence_invariants(
    corpus, baselines
):
    packed, _prefix, _log, wirep, _wirew = corpus
    sc = run_stream_wire(
        packed, [wirep], _cfg(depth=0, sk={"topk_every": 3})
    )
    so = run_stream_wire(
        packed, [wirep],
        _cfg(depth=0, update_impl="sorted", sk={"topk_every": 3}),
    )
    # both impls defer identically: reports agree at the same cadence
    assert report_image(sc) == report_image(so)
    # registers are selection-independent: hits/unused match the
    # every-chunk baseline exactly; only the candidate stream may thin
    base = baselines["wire0"]
    ib, ic = report_image(base), report_image(sc)
    assert ic["per_rule"] == ib["per_rule"]
    assert ic["unused"] == ib["unused"]
    assert sc.talkers, "deferred selection must still surface talkers"


def test_topk_every_fingerprint_and_validation(corpus):
    packed, _prefix, _log, _wirep, _wirew = corpus
    f1 = ckpt_mod.fingerprint(packed, _cfg())
    f2 = ckpt_mod.fingerprint(packed, _cfg(sk={"topk_every": 2}))
    f3 = ckpt_mod.fingerprint(packed, _cfg(update_impl="sorted"))
    assert f1 != f2, "non-default cadence must change the snapshot identity"
    assert f1 == f3, "update_impl must NOT change the snapshot identity"
    with pytest.raises(ValueError):
        SketchConfig(topk_every=0)
    with pytest.raises(ValueError):
        SketchConfig(topk_every=1 << 13)


# ---------------------------------------------------------------------------
# Typed refusals + CLI surface.
# ---------------------------------------------------------------------------


def test_config_refuses_sorted_with_pallas_fused():
    with pytest.raises(ValueError, match="pallas_fused"):
        AnalysisConfig(update_impl="sorted", match_impl="pallas_fused")
    with pytest.raises(ValueError, match="update_impl"):
        AnalysisConfig(update_impl="bogus")
    # the weight-safe combinations all construct
    AnalysisConfig(update_impl="sorted", coalesce="on")
    AnalysisConfig(update_impl="sorted", counts_impl="reduce")


def test_cli_refusals(corpus, capsys):
    from ruleset_analysis_tpu import cli

    _packed, prefix, log, _wirep, _wirew = corpus
    rc = cli.main([
        "run", "--ruleset", prefix, "--logs", log,
        "--update-impl", "sorted",
        "--experimental-match-impl", "pallas_fused",
    ])
    assert rc == 2
    assert "pallas_fused" in capsys.readouterr().err
    # oracle backend: device-formulation knobs are tpu-only
    rc = cli.main([
        "run", "--ruleset", prefix, "--logs", log,
        "--backend", "oracle", "--update-impl", "sorted",
    ])
    assert rc == 2
    assert "--update-impl" in capsys.readouterr().err
    rc = cli.main([
        "run", "--ruleset", prefix, "--logs", log,
        "--backend", "oracle", "--topk-every", "4",
    ])
    assert rc == 2
    assert "--topk-every" in capsys.readouterr().err
