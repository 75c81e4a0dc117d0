"""Benchmark suite: one JSON line per BASELINE.json config.

The headline driver benchmark stays in bench.py (single JSON line); this
suite stands up the five configs BASELINE.json names so throughput AND
accuracy claims are reproducible on real hardware:

  1 exact     single-ruleset exact hit-count throughput + oracle equality
  2 cms       exact -> count-min sketch width x depth sweep (error, recall)
  3 hll       per-rule unique-source HLL relative error
  4 multifw   multi-firewall batched match: flat vs stacked (vmap) paths
  5 topk      streaming top-K talkers precision vs exact

Run all: ``python bench_suite.py``; one: ``python bench_suite.py cms``.
Each config prints exactly one JSON line on stdout.
"""

from __future__ import annotations

import functools
import json
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _setup(n_acls=4, rules_per_acl=64, seed=0, firewalls=1):
    from ruleset_analysis_tpu.hostside import aclparse, pack, synth

    rulesets = [
        aclparse.parse_asa_config(
            synth.synth_config(n_acls=n_acls, rules_per_acl=rules_per_acl, seed=seed + i),
            f"fw{i}",
        )
        for i in range(firewalls)
    ]
    return pack.pack_rulesets(rulesets)


def _tuples(packed, n, seed=0):
    from ruleset_analysis_tpu.hostside import synth

    return synth.synth_tuples(packed, n, seed=seed)


def _time_steps(step, state, rules, feeds, iters, valid_per_feed):
    """Counts-validated timed loop (shared sync discipline with bench.py)."""
    from ruleset_analysis_tpu.runtime.timing import timed_validated_steps

    state, _ = step(state, rules, feeds[0])  # warmup/compile
    state, dt, delta, expect = timed_validated_steps(
        step, state, rules, feeds, valid_per_feed, iters
    )
    if delta != expect:
        raise AssertionError(
            f"timed window did not execute: counts moved {delta}, expected {expect}"
        )
    return state, dt


# ---------------------------------------------------------------------------


def bench_exact() -> dict:
    """Config #1: single-ruleset exact hit-count; correctness vs oracle."""
    import jax
    import jax.numpy as jnp

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside import pack
    from ruleset_analysis_tpu.models import pipeline
    from ruleset_analysis_tpu.ops.match import match_keys

    packed = _setup()
    b = 1 << 20
    cfg = AnalysisConfig(batch_size=b, sketch=SketchConfig(cms_width=1 << 14, cms_depth=4))
    state = pipeline.init_state(packed.n_keys, cfg)
    rules = pipeline.ship_ruleset(packed)
    feeds_np = [np.ascontiguousarray(_tuples(packed, b, seed=i).T) for i in range(2)]
    valid_per_feed = [int(f[pack.T_VALID].sum()) for f in feeds_np]
    feeds = [jnp.asarray(f) for f in feeds_np]
    step = jax.jit(
        functools.partial(
            pipeline.analysis_step,
            n_keys=packed.n_keys,
            topk_k=cfg.sketch.topk_chunk_candidates,
        ),
        donate_argnums=(0,),
    )
    iters = 20
    state, dt = _time_steps(step, state, rules, feeds, iters, valid_per_feed)

    # correctness: a fresh state stepped over a small batch must hold
    # exactly the bincount of the device-matched keys (oracle equality of
    # the match itself is pinned by tests/)
    t = _tuples(packed, 4096, seed=99)
    cols = {
        "acl": jnp.asarray(t[:, pack.T_ACL]), "proto": jnp.asarray(t[:, pack.T_PROTO]),
        "src": jnp.asarray(t[:, pack.T_SRC]), "sport": jnp.asarray(t[:, pack.T_SPORT]),
        "dst": jnp.asarray(t[:, pack.T_DST]), "dport": jnp.asarray(t[:, pack.T_DPORT]),
    }
    keys = np.asarray(match_keys(cols, rules.rules, rules.deny_key))
    check_state = pipeline.init_state(packed.n_keys, cfg)
    check_state, _ = pipeline.analysis_step(
        check_state, rules, jnp.asarray(np.ascontiguousarray(t.T)),
        n_keys=packed.n_keys, topk_k=cfg.sketch.topk_chunk_candidates,
    )
    want = np.bincount(keys[t[:, pack.T_VALID] == 1], minlength=packed.n_keys)
    exact_ok = bool((np.asarray(check_state.counts_lo) == want.astype(np.uint32)).all())
    lines_per_sec = iters * b / dt
    return {
        "metric": "config1_exact_hitcount_lines_per_sec_per_chip",
        "value": round(lines_per_sec / len(jax.devices()), 1),
        "unit": "lines/sec/chip",
        "vs_baseline": round(lines_per_sec / len(jax.devices()) / (1e9 / 60 / 8), 4),
        "detail": {"batch": b, "iters": iters, "rules_rows": int(packed.rules.shape[0]),
                   "exact_path_ok": exact_ok},
    }


def bench_cms() -> dict:
    """Config #2: CMS width x depth sweep — one-sided error + unused recall."""
    import jax.numpy as jnp

    from ruleset_analysis_tpu.ops import cms as cms_ops

    rng = np.random.default_rng(0)
    n_keys = 4096
    # zipf-ish key stream: heavy head, long tail, plus keys that never occur
    raw = rng.zipf(1.3, size=1 << 20).astype(np.uint64)
    keys = (raw % (n_keys // 2)).astype(np.uint32)  # half the keyspace never hit
    exact = np.bincount(keys, minlength=n_keys).astype(np.uint64)
    valid = np.ones_like(keys)

    sweep = []
    for width in (1 << 10, 1 << 12, 1 << 14, 1 << 16):
        for depth in (2, 4, 6):
            cms = cms_ops.cms_init(width, depth)
            cms = cms_ops.cms_update(cms, jnp.asarray(keys), jnp.asarray(valid))
            est = cms_ops.cms_query_np(np.asarray(cms), np.arange(n_keys, dtype=np.uint32))
            over = est.astype(np.int64) - exact.astype(np.int64)
            assert (over >= 0).all(), "CMS one-sided error violated"
            # unused-rule recall: of truly-zero keys, fraction estimated zero
            zero = exact == 0
            recall = float((est[zero] == 0).mean())
            sweep.append({
                "width": width, "depth": depth,
                "recall_unused": round(recall, 4),
                "mean_overcount": round(float(over.mean()), 2),
                "p99_overcount": round(float(np.percentile(over, 99)), 1),
            })
            log(f"cms w={width} d={depth} recall={recall:.4f} mean_over={over.mean():.2f}")
    best = [s for s in sweep if s["recall_unused"] >= 0.99]
    return {
        "metric": "config2_cms_unused_recall_at_16k_x4",
        "value": next(s["recall_unused"] for s in sweep if s["width"] == 1 << 14 and s["depth"] == 4),
        "unit": "recall",
        "vs_baseline": round(
            next(s["recall_unused"] for s in sweep if s["width"] == 1 << 14 and s["depth"] == 4) / 0.99, 4
        ),
        "detail": {"stream": int(keys.size), "n_keys": n_keys, "sweep": sweep,
                   "configs_meeting_99pct": len(best)},
    }


def bench_hll() -> dict:
    """Config #3: per-rule unique-source HLL relative error."""
    import jax.numpy as jnp

    from ruleset_analysis_tpu.ops import hll as hll_ops

    rng = np.random.default_rng(1)
    n_keys = 256
    p = 8
    # per-key unique-source populations spanning 4 decades
    true_cards = np.unique(np.round(np.logspace(1, 5, n_keys)).astype(np.int64))
    n_keys = len(true_cards)
    regs = hll_ops.hll_init(n_keys, p)
    batch = 1 << 20
    keys_all, src_all = [], []
    for k, card in enumerate(true_cards):
        pool = rng.integers(0, 1 << 32, size=card, dtype=np.uint32)
        draws = pool[rng.integers(0, card, size=min(4 * card, 1 << 18))]
        keys_all.append(np.full(draws.size, k, dtype=np.uint32))
        src_all.append(draws)
    keys = np.concatenate(keys_all)
    srcs = np.concatenate(src_all)
    order = rng.permutation(keys.size)
    keys, srcs = keys[order], srcs[order]
    for i in range(0, keys.size, batch):
        regs = hll_ops.hll_update(
            regs, jnp.asarray(keys[i:i + batch]), jnp.asarray(srcs[i:i + batch]),
            jnp.ones(keys[i:i + batch].size, dtype=np.uint32),
        )
    est = hll_ops.hll_estimate_np(np.asarray(regs))
    # true uniques actually seen (sampling may miss some of the pool)
    true_seen = np.array([
        len(np.unique(srcs[keys == k])) for k in range(n_keys)
    ])
    rel = np.abs(est - true_seen) / np.maximum(true_seen, 1)
    theory = 1.04 / np.sqrt(1 << p)
    return {
        "metric": "config3_hll_median_rel_error",
        "value": round(float(np.median(rel)), 4),
        "unit": "relative_error",
        "vs_baseline": round(theory / max(float(np.median(rel)), 1e-9), 4),
        "detail": {"p": p, "m": 1 << p, "theory_rse": round(theory, 4),
                   "p90_rel_error": round(float(np.percentile(rel, 90)), 4),
                   "n_keys": int(n_keys), "stream": int(keys.size)},
    }


def bench_multifw() -> dict:
    """Config #4: multi-firewall batched match — flat vs stacked layouts,
    measured through the PRODUCTION stream driver (runtime/stream.py with
    ``layout=...``), not a hand-fed step loop: the number includes the
    GroupBuffer bucketing, host->device transfer, sharded steps, and
    candidate draining that a real run pays."""
    import jax

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside import pack
    from ruleset_analysis_tpu.runtime.stream import run_stream_packed

    # Large rulesets: the regime where per-line match cost dominates and
    # slab-grouping pays (small rulesets are sketch-bound, where grouping
    # only adds lane padding).
    firewalls = 8
    packed = _setup(n_acls=2, rules_per_acl=1024, firewalls=firewalls)
    g = packed.n_acls
    batch = 1 << 20
    n_batches = 6
    total = batch * n_batches
    feeds = [
        np.ascontiguousarray(_tuples(packed, batch, seed=i).T) for i in range(2)
    ]
    log(f"multifw: {firewalls} firewalls, {g} ACL groups, "
        f"{packed.rules.shape[0]} flat rows, {total} lines/run via stream driver")

    def arrays():
        for i in range(n_batches):
            yield feeds[i % len(feeds)]

    def run(layout: str) -> float:
        cfg = AnalysisConfig(
            batch_size=batch,
            sketch=SketchConfig(cms_width=1 << 14, cms_depth=4),
            layout=layout,
        )
        # each run_stream_packed call builds a fresh jit wrapper, so a
        # cold full run (same shapes) populates the persistent XLA
        # compilation cache (enable_persistent_cache in main) and only
        # the second, timed run reflects steady state
        run_stream_packed(packed, arrays(), cfg)
        t0 = time.perf_counter()
        rep = run_stream_packed(packed, arrays(), cfg)
        dt = time.perf_counter() - t0
        assert rep.totals["lines_total"] >= total
        return total / dt

    flat_lps = run("flat")
    stacked_lps = run("stacked")

    return {
        "metric": "config4_multifw_stacked_lines_per_sec_per_chip",
        "value": round(stacked_lps / len(jax.devices()), 1),
        "unit": "lines/sec/chip",
        "vs_baseline": round(stacked_lps / max(flat_lps, 1.0), 4),  # speedup vs flat
        "detail": {
            "firewalls": firewalls, "groups": g,
            "flat_rows": int(packed.rules.shape[0]),
            "slab_rows": pack.stacked_slab_rows(packed),
            "flat_stream_lines_per_sec": round(flat_lps, 1),
            "stacked_stream_lines_per_sec": round(stacked_lps, 1),
            "measured": "production stream driver (run_stream_packed)",
        },
    }


def bench_topk() -> dict:
    """Config #5: streaming top-K talkers precision vs exact.

    Also sweeps the scatter-bound FLIP variants (talk_cms_depth=1 halves
    fusion.7; sample_shift=3 kills 7/8 of fusions 8+9 — DESIGN.md §8):
    their ACCURACY halves are platform-independent, so the flip decision
    only needs the TPU timing half from bench.py's step_variants A/B.
    """
    import jax.numpy as jnp

    from ruleset_analysis_tpu.ops import cms as cms_ops
    from ruleset_analysis_tpu.ops import topk as topk_ops

    rng = np.random.default_rng(2)
    n_chunks, chunk = 32, 1 << 16
    k = 10
    acls = rng.integers(0, 4, size=n_chunks * chunk).astype(np.uint32)
    # zipf sources: the heavy hitters we must recover
    src = (rng.zipf(1.2, size=n_chunks * chunk) % 50000).astype(np.uint32)
    valid = np.ones(chunk, dtype=np.uint32)
    import collections

    exact_tops = {}
    for a in range(4):
        cnt = collections.Counter(src[acls == a].tolist())
        exact_tops[a] = {s for s, _ in cnt.most_common(k)}

    def precision(depth: int, shift: int) -> list[float]:
        talk = cms_ops.cms_init(1 << 14, depth)
        tracker = topk_ops.TopKTracker(capacity=4096)
        for c in range(n_chunks):
            sl = slice(c * chunk, (c + 1) * chunk)
            talk, ca, cs, ce = topk_ops.talker_chunk_update(
                talk, jnp.asarray(acls[sl]), jnp.asarray(src[sl]),
                jnp.asarray(valid), 64, salt=c, sample_shift=shift,
            )
            tracker.offer_chunk(np.asarray(ca), np.asarray(cs), np.asarray(ce))
        return [
            len(exact_tops[a] & {s for s, _ in tracker.top(a, k)}) / k
            for a in range(4)
        ]

    variants = {}
    for depth, shift in [(4, 0), (4, 3), (2, 0), (1, 0), (2, 3), (1, 3)]:
        ps = precision(depth, shift)
        variants[f"d{depth}_shift{shift}"] = {
            "talk_cms_depth": depth,
            "sample_shift": shift,
            "precision_at_10": round(float(np.mean(ps)), 4),
            "per_acl": [round(p, 3) for p in ps],
        }
        log(f"topk d={depth} shift={shift}: precision@{k}="
            f"{variants[f'd{depth}_shift{shift}']['precision_at_10']:.2f}")

    headline = variants["d4_shift0"]["precision_at_10"]
    return {
        "metric": "config5_topk_precision_at_10",
        "value": headline,
        "unit": "precision",
        "vs_baseline": round(headline / 0.9, 4),
        "detail": {"chunks": n_chunks, "chunk": chunk,
                   "per_acl": variants["d4_shift0"]["per_acl"],
                   # accuracy half of every pending scatter-lever flip
                   "flip_variants": variants},
    }


def bench_pallas() -> dict:
    """Match-kernel shootout: XLA-fused vs pallas vs pallas_fused.

    pallas_fused also does the exact-counts work (match + in-VMEM count
    histograms, ops/pallas_fused.py), so its fair comparison is against
    XLA match + segment_counts — the fused column measures match+counts
    for all three, deciding whether the batch-sized counts scatter
    (fusion.5 in the committed trace) is worth a kernel.
    """
    import jax
    import jax.numpy as jnp

    from ruleset_analysis_tpu.hostside import pack
    from ruleset_analysis_tpu.ops import pallas_fused, pallas_match
    from ruleset_analysis_tpu.ops.counts import segment_counts
    from ruleset_analysis_tpu.ops.match import first_match_rows, match_keys

    on_tpu = jax.devices()[0].platform == "tpu"
    # CPU runs execute pallas via the interpreter (parity smoke only);
    # full-size timing there would burn the whole config timeout
    b = 1 << 20 if on_tpu else 1 << 17
    n_timing_iters = 10 if on_tpu else 2
    results = {}
    from ruleset_analysis_tpu.models import pipeline

    for tag, rules_per_acl in (("small", 64), ("large", 1024)):
        packed = _setup(n_acls=4, rules_per_acl=rules_per_acl)
        t = _tuples(packed, b, seed=0)
        cols = {
            k: jnp.asarray(t[:, i])
            for k, i in zip(["acl", "proto", "src", "sport", "dst", "dport"], range(6))
        }
        # block-padded exactly as the pipeline ships it (the scan path of
        # first_match_rows asserts rule_block alignment)
        shipped = pipeline.ship_ruleset(packed, match_impl="pallas")
        rules, fm = shipped.rules, shipped.rules_fm

        def run(fn, *args):
            # sync via a 4-byte readback of the LAST output: device
            # programs execute FIFO, so its completion bounds the loop
            out = fn(*args)
            np.asarray(out[:1])
            t0 = time.perf_counter()
            n = n_timing_iters
            for _ in range(n):
                out = fn(*args)
            np.asarray(out[:1])
            return (time.perf_counter() - t0) / n

        xla_fn = jax.jit(lambda c: first_match_rows(c, rules))
        pl_fn = jax.jit(lambda c: pallas_match.first_match_rows_pallas(c, fm))
        got = np.asarray(pl_fn(cols))
        want = np.asarray(xla_fn(cols))
        assert (got == want).all(), f"pallas/xla mismatch ({tag})"
        dt_x, dt_p = run(xla_fn, cols), run(pl_fn, cols)

        # match+counts leg: XLA match_keys + segment_counts vs the fused
        # kernel (keys AND per-key count delta in one pallas_call)
        deny = shipped.deny_key
        valid = jnp.ones(b, dtype=jnp.uint32)
        n_keys = packed.n_keys

        def xla_mc(c):
            keys = match_keys(c, rules, deny)
            return segment_counts(keys, valid, n_keys)

        def fused_mc(c):
            _keys, delta = pallas_fused.match_keys_and_counts_pallas(
                c, valid, rules, fm, deny, n_keys
            )
            return delta

        xla_mc_fn, fused_mc_fn = jax.jit(xla_mc), jax.jit(fused_mc)
        d_want = np.asarray(xla_mc_fn(cols))
        d_got = np.asarray(fused_mc_fn(cols))
        assert (d_got == d_want).all(), f"fused counts mismatch ({tag})"
        dt_xc, dt_f = run(xla_mc_fn, cols), run(fused_mc_fn, cols)

        results[tag] = {
            "rows": int(rules.shape[0]),
            "xla_mlines_per_sec": round(b / dt_x / 1e6, 1),
            "pallas_mlines_per_sec": round(b / dt_p / 1e6, 1),
            "pallas_speedup": round(dt_x / dt_p, 3),
            "xla_match_counts_mlines_per_sec": round(b / dt_xc / 1e6, 1),
            "fused_match_counts_mlines_per_sec": round(b / dt_f / 1e6, 1),
            "fused_speedup": round(dt_xc / dt_f, 3),
        }
        log(
            f"pallas[{tag}]: xla {b/dt_x/1e6:.1f}M vs pallas {b/dt_p/1e6:.1f}M"
            f" | match+counts: xla {b/dt_xc/1e6:.1f}M vs fused {b/dt_f/1e6:.1f}M lines/s"
        )
    results["platform"] = "tpu" if on_tpu else "cpu"
    results["batch"] = b
    results["timing_iters"] = n_timing_iters
    # a CPU run exercises parity only (pallas via the interpreter at 1/8
    # batch) — its timings must never be read as TPU numbers
    return {
        "metric": "pallas_match_speedup_vs_xla_large_ruleset",
        "value": results["large"]["pallas_speedup"],
        "unit": "speedup",
        "vs_baseline": results["large"]["pallas_speedup"],
        "detail": results,
    }


def bench_stage() -> dict:
    """Device-step stage attribution: where do the milliseconds go?

    The round-4 headline runs at ~4% of the u32 VPU roofline, so the step
    is NOT bounded by the predicate math — this config times each piece of
    the fused step in isolation (match kernel, exact-counts scatter, a
    one-hot-matmul counts alternative, HLL scatter-max, talker update,
    full step) to show which register update to attack next.  Timing
    discipline matches runtime/timing.py: the warmup dispatch is closed
    by a host fetch before the clock starts, every iteration's carry
    depends on the previous one (no pipelined elision of the chain), the
    window closes with a host fetch of the carry, and every stage
    validates the fetched value against an independently computed
    expectation — a window whose work did not run fails loudly instead of
    reporting a plausible number (the round-2 9x-roofline lesson).
    """
    import jax
    import jax.numpy as jnp

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.models import pipeline
    from ruleset_analysis_tpu.ops import cms as cms_ops
    from ruleset_analysis_tpu.ops import counts as count_ops
    from ruleset_analysis_tpu.ops import hll as hll_ops
    from ruleset_analysis_tpu.ops import topk as topk_ops
    from ruleset_analysis_tpu.ops.match import match_keys
    from ruleset_analysis_tpu.runtime.timing import timed_validated_steps

    on_tpu = jax.devices()[0].platform == "tpu"
    b = 1 << 20 if on_tpu else 1 << 16
    iters = 20 if on_tpu else 5
    packed = _setup(n_acls=4, rules_per_acl=64)
    n_keys = packed.n_keys
    tup = _tuples(packed, b, seed=3)
    wire = jnp.asarray(pack_mod.compact_batch(np.ascontiguousarray(tup.T)))
    rules = pipeline.ship_ruleset(packed)
    cols, valid = pipeline.batch_cols(wire)
    keys0 = jax.block_until_ready(
        match_keys(cols, rules.rules, rules.deny_key)
    )
    src, acl = cols["src"], cols["acl"]
    n_valid = int(jax.device_get(valid.astype(jnp.uint32).sum()))
    # exact host-side expectations (device sums are u32 and wrap mod 2^32)
    keys_sum = int(np.asarray(jax.device_get(keys0), dtype=np.uint64).sum() % (1 << 32))

    u32 = jnp.uint32
    M = 1 << 32

    def timed(name, init_carry, one_iter, validate):
        """Chained-carry window: warmup closed by a fetch, then timed."""
        f = jax.jit(one_iter)
        jax.device_get(jax.tree_util.tree_leaves(f(init_carry))[0])
        carry = init_carry
        t0 = time.perf_counter()
        for _ in range(iters):
            carry = f(carry)
        final = jax.device_get(carry)  # closes the window
        dt = time.perf_counter() - t0
        validate(final)
        ms = dt / iters * 1e3
        log(f"stage {name}: {ms:.2f} ms/iter")
        return round(ms, 3)

    def expect_scalar(expected, what):
        def check(final):
            got = int(np.asarray(final).reshape(())) 
            if got != expected:
                raise AssertionError(
                    f"stage window invalid: {what} carry {got} != {expected}"
                )
        return check

    results = {}

    # match kernel only: each iteration folds the (recomputed) key sum
    # into the carry, so iteration i+1 cannot issue before i finished
    results["match_ms"] = timed(
        "match",
        u32(0),
        lambda c: c + match_keys(cols, rules.rules, rules.deny_key).sum(dtype=u32),
        expect_scalar(iters * keys_sum % M, "match key-sum"),
    )

    # exact-counts scatter-add ([B] -> [n_keys]); per-iter sum == n_valid
    results["counts_scatter_ms"] = timed(
        "counts-scatter",
        u32(0),
        lambda c: c + count_ops.segment_counts(keys0, valid, n_keys).sum(dtype=u32),
        expect_scalar(iters * n_valid % M, "counts total"),
    )

    # one-hot matmul alternative: the SHIPPED formulation
    # (ops/counts.segment_counts_matmul — what counts_impl="matmul"
    # actually runs), so a measured default flip prices production code
    def counts_matmul(keys):
        return count_ops.segment_counts_matmul(keys, valid, n_keys)

    results["counts_matmul_ms"] = timed(
        "counts-matmul",
        u32(0),
        lambda c: c + counts_matmul(keys0).sum(dtype=u32),
        expect_scalar(iters * n_valid % M, "matmul counts total"),
    )

    # compare-and-reduce alternative: the SHIPPED formulation
    # (ops/counts.segment_counts_reduce, counts_impl="reduce")
    def counts_reduce(keys):
        return count_ops.segment_counts_reduce(keys, valid, n_keys)

    results["counts_reduce_ms"] = timed(
        "counts-reduce",
        u32(0),
        lambda c: c + counts_reduce(keys0).sum(dtype=u32),
        expect_scalar(iters * n_valid % M, "reduce counts total"),
    )

    # parity: every counts formulation must produce the exact scatter counts
    c_sc = jax.device_get(count_ops.segment_counts(keys0, valid, n_keys))
    c_mm = jax.device_get(counts_matmul(keys0))
    c_rd = jax.device_get(counts_reduce(keys0))
    if not np.array_equal(c_sc, c_mm):
        raise AssertionError("one-hot matmul counts != scatter counts")
    if not np.array_equal(c_sc, c_rd):
        raise AssertionError("compare-reduce counts != scatter counts")

    # HLL scatter-max ([B] -> [n_keys, m]).  Max-updates are idempotent,
    # so iterations past the first change nothing; the carry chain still
    # forces each scatter to execute, and the fixed point is the check.
    hll0 = hll_ops.hll_init(n_keys, 8)
    hll1 = jax.device_get(hll_ops.hll_update(hll0, keys0, src, valid))

    def check_hll(final):
        if not np.array_equal(final, hll1):
            raise AssertionError("stage window invalid: hll != 1-step fixed point")

    results["hll_ms"] = timed(
        "hll", hll0, lambda h: hll_ops.hll_update(h, keys0, src, valid), check_hll
    )

    # talker update INCLUDING candidate extraction: the candidates must be
    # live outputs of the chain or XLA dead-code-eliminates the per-chunk
    # top-k selection that the real step pays for
    sk = SketchConfig()
    tcms = cms_ops.cms_init(sk.cms_width, sk.talk_cms_depth)
    d1 = int(np.asarray(jax.device_get(
        topk_ops.talker_chunk_update(tcms, acl, src, valid, 10, salt=0)[0]
    ), dtype=np.uint64).sum())

    def step_talk(carry):
        t, acc = carry
        new, _ca, _cs, ce = topk_ops.talker_chunk_update(t, acl, src, valid, 10, salt=0)
        return new, acc + ce.sum(dtype=u32)

    # candidate estimates evolve with the accumulating cms, so the acc
    # expectation comes from an untimed replay of the same chain; the cms
    # sum (additive: iters x one-step delta) is the independent anchor
    pre = jax.jit(step_talk)
    c = (tcms, u32(0))
    for _ in range(iters):
        c = pre(c)
    expected_acc = int(np.asarray(jax.device_get(c[1])).reshape(()))

    def check_talk(final):
        t_final, acc = final
        got = int(np.asarray(t_final, dtype=np.uint64).sum())
        if got != iters * d1:
            raise AssertionError(
                f"stage window invalid: talker sum {got} != {iters * d1}"
            )
        got_acc = int(np.asarray(acc).reshape(()))
        if got_acc != expected_acc:
            raise AssertionError(
                f"stage window invalid: candidate sum {got_acc} != {expected_acc}"
            )

    results["talker_ms"] = timed("talker", (tcms, u32(0)), step_talk, check_talk)

    # full fused step, via the SHARED counts-validated helper
    import functools

    full_step = jax.jit(
        functools.partial(pipeline.analysis_step, n_keys=n_keys, topk_k=10, salt=0),
        donate_argnums=(0,),  # same discipline as bench_exact: no state copy
    )
    state = pipeline.init_state(n_keys, AnalysisConfig(sketch=sk))
    state, _ = full_step(state, rules, wire)  # warmup
    pipeline.counts_total(state)  # close warmup with the counts fetch
    state, dt, delta, expect = timed_validated_steps(
        full_step, state, rules, [wire], [n_valid], iters
    )
    if delta != expect:
        raise AssertionError(f"full-step window invalid: {delta} != {expect}")
    results["full_step_ms"] = round(dt / iters * 1e3, 3)
    log(f"stage full: {results['full_step_ms']:.2f} ms/iter")

    results["unattributed_ms"] = round(
        results["full_step_ms"] - (
            results["match_ms"] + results["counts_scatter_ms"]
            + results["hll_ms"] + results["talker_ms"]
        ), 3,
    )
    results["batch"] = b
    results["iters"] = iters
    results["n_keys"] = n_keys
    results["platform"] = "tpu" if on_tpu else "cpu"
    results["counts_matmul_speedup"] = round(
        results["counts_scatter_ms"] / max(results["counts_matmul_ms"], 1e-9), 2
    )
    return {
        "metric": "stage_full_step_ms",
        "value": results["full_step_ms"],
        "unit": "ms",
        "vs_baseline": 0.0,
        "detail": results,
    }


def bench_recall() -> dict:
    """Sketch-only recall certification at 1e8 lines (VERDICT r3 #7).

    The BASELINE.md accuracy north star ("exact counts replaced by CMS,
    >=99% unused-ACL recall vs the exact run") demonstrated at the scale
    where CMS load factors actually stress: ~1e8 packed lines (1e6 on the
    CPU so the config still completes anywhere) over a 1k-key
    ruleset, swept across CMS widths.  One exact run is the ground truth;
    each geometry then runs sketch-only through the production stream
    driver, giving the committed recall CURVE plus the recommended
    geometry per ruleset size.
    """
    import jax

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside.oracle import unused_rule_recall
    from ruleset_analysis_tpu.models.pipeline import register_bytes
    from ruleset_analysis_tpu.runtime.stream import run_stream_packed

    import os

    on_tpu = jax.devices()[0].platform == "tpu"
    # RA_RECALL_KEYS ~ fleet size of the key universe (VERDICT r4 #6:
    # certify at 10k+ keys, not just the 1k of the r4 run); RA_RECALL_LAYOUT
    # runs the sweep through the stacked (per-ACL slab) path instead of flat.
    want_keys = int(os.environ.get("RA_RECALL_KEYS", "1024"))
    n_acls_ = 16 if want_keys >= 4096 else 8
    packed = _setup(n_acls=n_acls_, rules_per_acl=max(want_keys // n_acls_, 8))
    layout = os.environ.get("RA_RECALL_LAYOUT", "flat")
    chunk = 1 << 20
    # RA_RECALL_CHUNKS overrides the scale (e.g. a deliberate 1e8-line CPU
    # certification run: accuracy is platform-independent, only slower)
    n_chunks_ = int(
        os.environ.get("RA_RECALL_CHUNKS", "0")
    ) or (96 if on_tpu else 1)
    feeds = [np.ascontiguousarray(_tuples(packed, chunk, seed=100 + i).T)
             for i in range(2)]
    total = n_chunks_ * chunk
    log(f"recall: {packed.n_keys} keys, {total} lines, tpu={on_tpu}")

    def arrays():
        for i in range(n_chunks_):
            yield feeds[i % len(feeds)]

    def cfg_for(width: int, depth: int, exact: bool) -> AnalysisConfig:
        return AnalysisConfig(
            batch_size=chunk,
            sketch=SketchConfig(cms_width=width, cms_depth=depth, hll_p=8),
            exact_counts=exact,
            layout=layout,
        )

    t0 = time.perf_counter()
    rep_exact = run_stream_packed(packed, arrays(), cfg_for(1 << 14, 4, True))
    t_exact = time.perf_counter() - t0
    exact_unused = rep_exact.unused

    sweep = []
    # depth is part of the sweep (VERDICT r4 #6): depth 2 halves the
    # register traffic, depth 6 tests whether extra rows buy recall at
    # fleet key counts where width collisions concentrate
    for width, depth in [
        (1 << 12, 4), (1 << 14, 2), (1 << 14, 4), (1 << 14, 6), (1 << 16, 4),
    ]:
        cfg = cfg_for(width, depth, False)
        t0 = time.perf_counter()
        rep = run_stream_packed(packed, arrays(), cfg)
        dt = time.perf_counter() - t0
        recall = unused_rule_recall(exact_unused, rep.unused)
        # CMS error is one-sided: a rule with real hits can never estimate
        # zero, so false "unused" claims must be structurally absent
        false_unused = [k for k in rep.unused if k not in set(exact_unused)]
        rb = sum(register_bytes(packed.n_keys, cfg).values())
        sweep.append({
            "width": width, "depth": depth,
            "recall_unused": round(recall, 4),
            "false_unused": len(false_unused),
            "register_bytes": rb,
            "lines_per_sec": round(total / dt, 1),
        })
        log(f"recall w={width} d={depth}: {recall:.4f} "
            f"({total / dt:.0f} lines/s)")
    meets = [s for s in sweep if s["recall_unused"] >= 0.99]
    recommended = min(meets, key=lambda s: s["register_bytes"]) if meets else None
    # headline geometry pinned to (2^14, depth 4) — the same row every
    # round, so cross-round artifact comparisons track ONE config even as
    # the sweep grows more depths
    headline = next(
        s for s in sweep if s["width"] == 1 << 14 and s["depth"] == 4
    )
    return {
        "metric": f"recall_sketch_only_unused_vs_exact_{total // 1_000_000}M_lines",
        "value": headline["recall_unused"],
        "unit": "recall",
        "vs_baseline": round(headline["recall_unused"] / 0.99, 4),
        "detail": {
            "lines": total,
            "n_keys": packed.n_keys,
            "exact_unused": len(exact_unused),
            "exact_run_sec": round(t_exact, 1),
            "exact_lines_per_sec": round(total / t_exact, 1),
            "sweep": sweep,
            # smallest geometry meeting the >=99% north star for this
            # ruleset size — the documented recommendation
            "recommended_geometry": recommended,
            "layout": layout,
            "platform": "tpu" if on_tpu else "cpu",
        },
    }


def bench_e2e() -> dict:
    """Full system: raw syslog text file -> report (host parse + device).

    The north-star metric is END-TO-END lines/min, so this measures the
    whole path the CLI takes: native C++ parse of raw bytes, packing,
    device analysis, report assembly.  The host parse runs on one CPU
    core here; on multi-core v5e hosts it scales per-process/per-core.

    One-time jit/compile cost is measured SEPARATELY (VERDICT r5 Weak #1:
    committed artifacts ranged 113k-873k lines/s purely on how much of
    the run was compile): a tiny warmup run with the identical batch
    geometry fills the persistent XLA cache first, so the headline
    ``value`` is the sustained rate and ``compile_warmup_sec`` prices the
    one-time cost explicitly in the emitted JSON.
    """
    import os
    import tempfile

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside import fastparse, synth
    from ruleset_analysis_tpu.runtime.compcache import enable_persistent_cache
    from ruleset_analysis_tpu.runtime.stream import run_stream_file

    packed = _setup()
    n = 2_000_000
    n_warm = 10_000
    log(f"rendering {n} syslog lines...")
    tuples = _tuples(packed, n, seed=0)
    lines = synth.render_syslog(packed, tuples, seed=1)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "bench.log")
        with open(path, "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
        warm_path = os.path.join(d, "warm.log")
        with open(warm_path, "w", encoding="utf-8") as f:
            f.write("\n".join(lines[:n_warm]) + "\n")
        del lines
        size_mb = os.path.getsize(path) / 1e6
        cfg = AnalysisConfig(
            batch_size=1 << 19,
            sketch=SketchConfig(cms_width=1 << 14, cms_depth=4, hll_p=8),
        )
        # the warm run only de-compiles the measured run where compiled
        # programs persist across the two fresh jit wrappers — i.e. when
        # the persistent cache is active (always on TPU, the platform
        # whose committed e2e artifacts motivated this split; the CPU
        # cache is unsafe on some jaxlibs and stays off by default, and
        # there the emitted persistent_cache:false flags that the
        # sustained rate still includes a compile share)
        cache = enable_persistent_cache()
        t0 = time.perf_counter()
        run_stream_file(packed, warm_path, cfg, native=None)
        warm_sec = time.perf_counter() - t0
        rep = run_stream_file(packed, path, cfg, native=None)  # auto-select
    lps = rep.totals["lines_per_sec"]
    return {
        "metric": "e2e_text_to_report_lines_per_sec",
        "value": lps,
        "unit": "lines/sec",
        "vs_baseline": round(lps / (1e9 / 60 / 8), 4),  # vs north-star/chip
        "detail": {
            "lines": n,
            "file_mb": round(size_mb, 1),
            "native_parse": fastparse.available(),
            "host_cores": os.cpu_count(),
            "totals": rep.totals,
            # one-time cost, priced separately from the sustained rate
            # (dominated by jit trace + XLA compile; includes n_warm
            # lines of real work, negligible at the sustained rate)
            "compile_warmup_sec": round(warm_sec, 3),
            "warmup_lines": n_warm,
            "persistent_cache": bool(cache),
        },
    }


def bench_convert() -> dict:
    """One-time text->wire conversion throughput at w ∈ {1, 4, 8}.

    VERDICT r4 #7: the wire tier's "convert once" cost was only measured
    single-process.  This is pure host work (native parse + row packing,
    no device), so the numbers are valid on any host; the TPU host's
    core count is what matters at fleet scale.  Emits GB/min and the
    projected wall time for the north-star volume (1e9 lines).
    """
    import os
    import tempfile

    from ruleset_analysis_tpu.hostside import fastparse, synth
    from ruleset_analysis_tpu.hostside import wire as wire_mod

    packed = _setup()
    n = 2_000_000
    log(f"rendering {n} syslog lines...")
    tuples = _tuples(packed, n, seed=0)
    lines = synth.render_syslog(packed, tuples, seed=1)
    workers = [1, 4, 8]
    runs = {}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "bench.log")
        with open(path, "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
        del lines
        size_mb = os.path.getsize(path) / 1e6
        for w in workers:
            out = os.path.join(d, f"bench-w{w}.rawire")
            t0 = time.perf_counter()
            stats = wire_mod.convert_logs(
                packed, [path], out,
                batch_size=1 << 18, block_rows=1 << 18,
                feed_workers=0 if w == 1 else w,
            )
            dt = time.perf_counter() - t0
            runs[f"w{w}"] = {
                "lines_per_sec": round(n / dt, 1),
                "elapsed_sec": round(dt, 3),
                "gb_per_min": round(size_mb / 1e3 / dt * 60, 3),
                "parser": stats["parser"],
            }
            log(f"w={w}: {runs[f'w{w}']['lines_per_sec']:.0f} lines/s")
        # byte-identity across worker counts is pinned by
        # tests/test_wirefile.py::test_convert_feed_workers_byte_identical
    best = max(runs.values(), key=lambda r: r["lines_per_sec"])
    return {
        "metric": "wire_convert_lines_per_sec",
        "value": best["lines_per_sec"],
        "unit": "lines/sec",
        # convert is a ONE-TIME cost; vs_baseline rates it against the
        # north-star per-minute line volume (1e9/min): 1.0 means convert
        # keeps up with the analysis stream in real time on this host
        "vs_baseline": round(best["lines_per_sec"] / (1e9 / 60), 4),
        "detail": {
            "lines": n,
            "file_mb": round(size_mb, 1),
            "native_parse": fastparse.available(),
            "host_cores": os.cpu_count(),
            "runs": runs,
            "north_star_1e9_lines_convert_min": round(
                1e9 / best["lines_per_sec"] / 60, 1
            ),
        },
    }


def bench_v6() -> dict:
    """IPv6 step cost: the lexicographic limb predicate vs the v4 step.

    DESIGN.md's v6 extension predicts ~1.5x step cost (3x the
    address-compare FLOPs on a step whose match is ~22% of time); this
    config measures the actual per-line ratio on device, over a unified
    ruleset with comparable expanded row counts per family, plus a
    device-vs-host correctness check of the v6 counts path.
    """
    import jax
    import jax.numpy as jnp

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside import aclparse, pack, synth
    from ruleset_analysis_tpu.models import pipeline
    from ruleset_analysis_tpu.ops.match6 import match_keys6

    rs = aclparse.parse_asa_config(
        synth.synth_config(n_acls=4, rules_per_acl=64, seed=7, v6_fraction=0.5),
        "fw0",
    )
    packed = pack.pack_rulesets([rs])
    b = 1 << 19
    cfg = AnalysisConfig(batch_size=b, sketch=SketchConfig(cms_width=1 << 14, cms_depth=4))
    topk_k = cfg.sketch.topk_chunk_candidates

    # v4 leg
    state = pipeline.init_state(packed.n_keys, cfg)
    rules4 = pipeline.ship_ruleset(packed)
    feeds4_np = [np.ascontiguousarray(_tuples(packed, b, seed=i).T) for i in range(2)]
    valid4 = [int(f[pack.T_VALID].sum()) for f in feeds4_np]
    feeds4 = [jnp.asarray(f) for f in feeds4_np]
    step4 = jax.jit(
        functools.partial(
            pipeline.analysis_step, n_keys=packed.n_keys, topk_k=topk_k
        ),
        donate_argnums=(0,),
    )
    iters = 10
    state, dt4 = _time_steps(step4, state, rules4, feeds4, iters, valid4)

    # v6 leg (same state/key space — the production arrangement)
    rules6 = pipeline.ship_ruleset6(packed)
    feeds6_np = [
        np.ascontiguousarray(synth.synth_tuples6(packed, b, seed=i).T)
        for i in range(2)
    ]
    valid6 = [int(f[pack.T6_VALID].sum()) for f in feeds6_np]
    feeds6 = [jnp.asarray(f) for f in feeds6_np]
    step6 = jax.jit(
        functools.partial(
            pipeline.analysis_step6, n_keys=packed.n_keys, topk_k=topk_k
        ),
        donate_argnums=(0,),
    )
    state, dt6 = _time_steps(step6, state, rules6, feeds6, iters, valid6)

    # correctness: v6 counts == host bincount of device-matched keys
    t6 = synth.synth_tuples6(packed, 4096, seed=99)
    b6 = jnp.asarray(np.ascontiguousarray(t6.T))
    cols6, _ = pipeline.batch_cols6(b6)
    keys6 = np.asarray(match_keys6(cols6, rules6.rules6, rules6.deny_key))
    chk = pipeline.init_state(packed.n_keys, cfg)
    chk, _ = pipeline.analysis_step6(
        chk, rules6, b6, n_keys=packed.n_keys, topk_k=topk_k
    )
    want = np.bincount(
        keys6[t6[:, pack.T6_VALID] == 1], minlength=packed.n_keys
    )
    v6_ok = bool((np.asarray(chk.counts_lo) == want.astype(np.uint32)).all())

    n_dev = len(jax.devices())
    v4_rate = iters * b / dt4 / n_dev
    v6_rate = iters * b / dt6 / n_dev
    return {
        "metric": "config_v6_step_lines_per_sec_per_chip",
        "value": round(v6_rate, 1),
        "unit": "lines/sec/chip",
        "vs_baseline": round(v6_rate / (1e9 / 60 / 8), 4),
        "detail": {
            "batch": b,
            "iters": iters,
            "v4_rows": int(packed.rules.shape[0]),
            "v6_rows": int(packed.rules6.shape[0]),
            "v4_lines_per_sec_per_chip": round(v4_rate, 1),
            "v6_relative_cost": round(v4_rate / v6_rate, 3),
            "design_predicted_cost": 1.5,
            "v6_counts_ok": v6_ok,
        },
    }


def bench_v6recall() -> dict:
    """Sketch-only unused-rule recall on a MIXED v4+v6 stream.

    The north-star accuracy criterion certified with both families live
    in the SAME registers: one exact direct-step run (v4 and v6 chunks
    interleaved) is ground truth; each CMS geometry then re-runs
    sketch-only and the unused sets compare.  Direct step calls (not the
    stream driver) — driver-level mixed correctness is pinned
    oracle-exact by tests/test_stream6.py; this config isolates the
    register-geometry question at scale.
    """
    import os

    import jax
    import jax.numpy as jnp

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside import aclparse, pack, synth
    from ruleset_analysis_tpu.hostside.oracle import unused_rule_recall
    from ruleset_analysis_tpu.models import pipeline
    from ruleset_analysis_tpu.ops import cms as cms_ops

    on_tpu = jax.devices()[0].platform == "tpu"
    rs = aclparse.parse_asa_config(
        synth.synth_config(n_acls=12, rules_per_acl=48, seed=13, v6_fraction=0.35),
        "fw0",
    )
    packed = pack.pack_rulesets([rs])
    b4, b6 = 1 << 20, 1 << 18
    feeds4 = [
        jnp.asarray(np.ascontiguousarray(synth.synth_tuples(packed, b4, seed=200 + i).T))
        for i in range(2)
    ]
    feeds6 = [
        jnp.asarray(np.ascontiguousarray(synth.synth_tuples6(packed, b6, seed=200 + i).T))
        for i in range(2)
    ]
    epochs = int(os.environ.get("RA_V6RECALL_EPOCHS", "0")) or (24 if on_tpu else 3)
    total = epochs * (2 * b4 + 2 * b6)
    total6 = epochs * 2 * b6
    log(f"v6recall: {packed.n_keys} keys ({packed.rules6.shape[0]} v6 rows), "
        f"{total} lines ({total6} v6), tpu={on_tpu}")

    def run(width: int, depth: int, exact: bool):
        cfg = AnalysisConfig(
            batch_size=b4,
            sketch=SketchConfig(cms_width=width, cms_depth=depth, hll_p=8),
            exact_counts=exact,
        )
        topk_k = cfg.sketch.topk_chunk_candidates
        rules4 = pipeline.ship_ruleset(packed)
        rules6 = pipeline.ship_ruleset6(packed)
        state = pipeline.init_state(packed.n_keys, cfg)
        step4 = jax.jit(
            functools.partial(
                pipeline.analysis_step, n_keys=packed.n_keys, topk_k=topk_k,
                exact_counts=exact,
            ),
            donate_argnums=(0,),
        )
        step6 = jax.jit(
            functools.partial(
                pipeline.analysis_step6, n_keys=packed.n_keys, topk_k=topk_k,
                exact_counts=exact,
            ),
            donate_argnums=(0,),
        )
        for e in range(epochs):
            for f in feeds4:
                state, _ = step4(state, rules4, f)
            for f in feeds6:
                state, _ = step6(state, rules6, f)
        host = pipeline.state_to_host(state)
        if exact:
            import ruleset_analysis_tpu.ops.counts as count_ops

            per_key = count_ops.to_u64(host["counts_lo"], host["counts_hi"])
        else:
            per_key = cms_ops.cms_query_np(
                host["cms"], np.arange(packed.n_keys, dtype=np.uint32)
            )
        unused = [
            (m.firewall, m.acl, m.index)
            for k, m in enumerate(packed.key_meta)
            if not m.implicit_deny and per_key[k] == 0
        ]
        return unused

    t0 = time.perf_counter()
    exact_unused = run(1 << 14, 4, True)
    t_exact = time.perf_counter() - t0
    sweep = []
    for width, depth in [(1 << 12, 4), (1 << 14, 4), (1 << 16, 4)]:
        t0 = time.perf_counter()
        got = run(width, depth, False)
        dt = time.perf_counter() - t0
        recall = unused_rule_recall(exact_unused, got)
        false_unused = [k for k in got if k not in set(exact_unused)]
        sweep.append({
            "width": width, "depth": depth,
            "recall_unused": round(recall, 4),
            "false_unused": len(false_unused),
            "lines_per_sec": round(total / dt, 1),
        })
        log(f"v6recall w={width} d={depth}: {recall:.4f}")
    headline = next(s for s in sweep if s["width"] == 1 << 14)
    return {
        "metric": "v6_mixed_recall_sketch_only_unused_vs_exact",
        "value": headline["recall_unused"],
        "unit": "recall",
        "vs_baseline": round(headline["recall_unused"] / 0.99, 4),
        "detail": {
            "lines_total": total,
            "lines_v6": total6,
            "n_keys": packed.n_keys,
            "v6_rows": int(packed.rules6.shape[0]),
            "n_unused_exact": len(exact_unused),
            "exact_run_sec": round(t_exact, 1),
            "sweep": sweep,
        },
    }


def bench_sustained() -> dict:
    """Sustained end-to-end run through the PRODUCTION CLI path.

    Closes the "projection vs measurement" gap on the e2e arm (VERDICT
    r5 #1): ≥1e8 synthetic lines flow through exactly what an operator
    runs — ``ruleset-analyze run`` over a ``.rawire`` wire file (mmap →
    pipelined ingest → H2D → sharded step → report) — and the emitted
    NORTHSTAR-style JSON separates the one-time jit/compile cost from
    the sustained rate, so this artifact can never be compile-dominated
    the way the 2M-line e2e artifacts were (two committed runs once
    disagreed 7.7x on exactly that).

    ``RA_SUSTAINED_LINES`` overrides the volume (default 1e8; the
    acceptance floor).  A small warm run first fills the in-process jit
    cache; the driver's ``compile.sec`` then prices any residue.
    """
    import os
    import tempfile

    import jax

    from ruleset_analysis_tpu import cli
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.hostside import synth
    from ruleset_analysis_tpu.hostside import wire as wire_mod

    n = int(float(os.environ.get("RA_SUSTAINED_LINES", "1e8")))
    batch = 1 << 20
    chunks = max(1, (n + batch - 1) // batch)
    n = chunks * batch
    packed = _setup()
    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "rules")
        pack_mod.save_packed(packed, prefix)

        def write_wire(path: str, n_chunks: int, seed0: int) -> dict:
            w = wire_mod.WireWriter(
                path, wire_mod.ruleset_fingerprint(packed), block_rows=batch
            )
            with w:
                for i in range(n_chunks):
                    t = _tuples(packed, batch, seed=seed0 + i).T
                    t = np.ascontiguousarray(t)
                    dense = t[:, t[pack_mod.T_VALID] == 1]
                    w.add(
                        pack_mod.compact_batch(dense),
                        batch,
                        batch - dense.shape[1],
                    )
            return {"rows": w.n_rows, "bytes": os.path.getsize(path)}

        t0 = time.perf_counter()
        warm_path = os.path.join(d, "warm.rawire")
        write_wire(warm_path, 1, seed0=10_000)
        wire_path = os.path.join(d, "sustained.rawire")
        stats = write_wire(wire_path, chunks, seed0=0)
        t_synth = time.perf_counter() - t0
        log(f"sustained corpus: {n} lines -> {stats['bytes']/1e9:.2f} GB "
            f"wire in {t_synth:.0f}s")

        def run_cli(logs: str, out: str) -> dict:
            rc = cli.main([
                "run", "--ruleset", prefix, "--logs", logs,
                "--batch-size", str(batch), "--json", "--out", out,
            ])
            if rc != 0:
                raise RuntimeError(f"production CLI run failed rc={rc}")
            with open(out, "r", encoding="utf-8") as f:
                return json.load(f)

        # warm: fills the memoized step-builder + jit caches in-process,
        # so the measured run's compile.sec is the honest residue
        t0 = time.perf_counter()
        run_cli(warm_path, os.path.join(d, "warm.json"))
        warm_sec = time.perf_counter() - t0
        t0 = time.perf_counter()
        rep = run_cli(wire_path, os.path.join(d, "sustained.json"))
        elapsed = time.perf_counter() - t0
    totals = rep["totals"]
    sustained = totals["sustained_lines_per_sec"]
    return {
        "metric": "sustained_e2e_wire_lines_per_sec",
        "value": sustained,
        "unit": "lines/sec",
        # vs the north-star e2e volume (1e9 lines/min on the 8-chip part)
        "vs_baseline": round(sustained / (1e9 / 60), 4),
        "detail": {
            "platform": platform,
            "devices": n_dev,
            "lines": n,
            "rows": stats["rows"],
            "file_gb": round(stats["bytes"] / 1e9, 3),
            "elapsed_sec": round(elapsed, 1),
            "lines_per_sec_incl_compile": totals["lines_per_sec"],
            "sustained_lines_per_sec": sustained,
            "sustained_lines_per_min": round(sustained * 60, 1),
            "compile_sec": totals["compile"]["sec"],
            "warm_run_sec": round(warm_sec, 2),
            "corpus_synth_sec": round(t_synth, 1),
            "ingest": totals.get("ingest"),
            "chunks": totals["chunks"],
            "path": "production CLI: run --logs *.rawire (wire mmap -> "
                    "pipelined ingest -> H2D -> sharded step -> report)",
            "totals": totals,
        },
    }


def bench_obs() -> dict:
    """Observability-plane guard: disarmed overhead + stage attribution.

    Two measured runs over the same wire corpus through the production
    CLI at the sustained bench geometry (batch 1<<20, wire mmap ->
    pipelined ingest -> sharded step):

    - **disarmed** (no --trace-out/--metrics-out): every obs site is one
      None-check.  The sustained rate here is the <2%-regression guard
      against the PR 3 baseline (NORTHSTAR_SUSTAINED_1E8_r06_cpu.json),
      recorded as ``vs_r06_baseline``.
    - **armed** (--trace-out + --metrics-out): prices the observability
      tax when ON, and its merged trace feeds
      ``tools.trace_summary.summarize`` so the artifact records
      per-stage occupancy — the attribution substrate the ISSUE names.

    ``RA_OBS_LINES`` overrides the corpus size (default 4M lines —
    enough chunks for a stable sustained separation on CPU).
    """
    import os
    import tempfile

    import jax

    from ruleset_analysis_tpu import cli
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.hostside import wire as wire_mod

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
    import trace_summary

    n = int(float(os.environ.get("RA_OBS_LINES", "4e6")))
    batch = 1 << 20
    chunks = max(2, (n + batch - 1) // batch)
    n = chunks * batch
    packed = _setup()
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "rules")
        pack_mod.save_packed(packed, prefix)
        wire_path = os.path.join(d, "obs.rawire")
        w = wire_mod.WireWriter(
            wire_path, wire_mod.ruleset_fingerprint(packed), block_rows=batch
        )
        with w:
            for i in range(chunks):
                t = np.ascontiguousarray(_tuples(packed, batch, seed=i).T)
                dense = t[:, t[pack_mod.T_VALID] == 1]
                w.add(pack_mod.compact_batch(dense), batch, batch - dense.shape[1])

        def run_cli(extra: list[str], out: str) -> dict:
            rc = cli.main([
                "run", "--ruleset", prefix, "--logs", wire_path,
                "--batch-size", str(batch), "--json", "--out", out, *extra,
            ])
            if rc != 0:
                raise RuntimeError(f"obs bench CLI run failed rc={rc}")
            with open(out, "r", encoding="utf-8") as f:
                return json.load(f)

        # warm: fills the in-process jit caches so both measured runs
        # carry the same (near-zero) compile residue
        run_cli([], os.path.join(d, "warm.json"))
        rep_off = run_cli([], os.path.join(d, "off.json"))
        trace_dir = os.path.join(d, "trace")
        metrics_path = os.path.join(d, "metrics.jsonl")
        rep_on = run_cli(
            ["--trace-out", trace_dir, "--metrics-out", metrics_path,
             "--metrics-every", "1"],
            os.path.join(d, "on.json"),
        )
        attribution = trace_summary.summarize(
            os.path.join(trace_dir, "trace.json")
        )
        with open(metrics_path, "r", encoding="utf-8") as f:
            metrics_records = [json.loads(ln) for ln in f if ln.strip()]
    off = rep_off["totals"]["sustained_lines_per_sec"]
    on = rep_on["totals"]["sustained_lines_per_sec"]
    baseline_r06 = 439_000.0  # NORTHSTAR_SUSTAINED_1E8_r06_cpu.json, 8-dev CPU
    return {
        "metric": "obs_disarmed_sustained_lines_per_sec",
        "value": off,
        "unit": "lines/sec",
        "vs_baseline": round(off / baseline_r06, 4),
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "lines": n,
            "chunks": chunks,
            "disarmed_sustained_lines_per_sec": off,
            "armed_sustained_lines_per_sec": on,
            "armed_over_disarmed": round(on / off, 4) if off else 0.0,
            "vs_r06_baseline": round(off / baseline_r06, 4),
            "r06_baseline_lines_per_sec": baseline_r06,
            "metrics_records": len(metrics_records),
            "stage_attribution": {
                "wall_sec": attribution["wall_sec"],
                "processes": attribution["processes"],
                "stages": attribution["stages"],
                "top_stalls": attribution["top_stalls"],
            },
        },
    }


def bench_steptrace() -> dict:
    """Device attribution guard (ISSUE 8): capture window + trace diff.

    Three measured runs over one wire corpus through the production CLI
    at the PRODUCTION batch geometry (batch 1<<16 — attribution must
    cover the step that actually ships; the 1<<20 throughput geometry
    floods the CPU profiler's event buffer and collapses the stage
    table):

    - **disarmed** (no --devprof-out): every devprof seam is one
      None-check; the sustained rate is the baseline.
    - **armed** (--devprof-out, counts_impl=scatter): one bounded
      capture window inside the run.  The artifact records the
      armed/disarmed sustained ratio with the capture pause priced
      apart (``window_wall_sec`` — profiling a step on XLA:CPU emits an
      event per scatter-loop iteration, 10-50x the plain step; the same
      separation discipline as compile.sec, r6), budget >= 0.98
      OUTSIDE the window; the raw including-pause ratio is reported
      alongside, never hidden.  Plus the attributed fraction
      (acceptance >= 0.90, remainder explicit), the per-stage table —
      the named replacement for DESIGN §8's hand-derived fusion.N
      rows — and report bit-identity armed vs disarmed.
    - **armed, counts_impl=matmul**: the second capture
      ``tools/trace_diff.py`` consumes; the per-stage delta table +
      fusion-boundary verdict land in the artifact — the evidence
      format the scatter-wall work (ROADMAP item 2) and the two
      VERDICT inversions will be closed with.

    ``RA_STEPTRACE_LINES`` overrides the corpus size (default 2M).
    """
    import os
    import tempfile

    import jax

    from ruleset_analysis_tpu import cli
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.hostside import wire as wire_mod

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
    import trace_diff

    n = int(float(os.environ.get("RA_STEPTRACE_LINES", "2e6")))
    batch = 1 << 16
    chunks = max(8, (n + batch - 1) // batch)
    n = chunks * batch
    steps = min(4, chunks - 2)
    packed = _setup()
    from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS

    volatile = VOLATILE_TOTALS

    def image(rep: dict) -> dict:
        rep = json.loads(json.dumps(rep))
        for k in volatile:
            rep["totals"].pop(k, None)
        return rep

    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "rules")
        pack_mod.save_packed(packed, prefix)
        wire_path = os.path.join(d, "steptrace.rawire")
        w = wire_mod.WireWriter(
            wire_path, wire_mod.ruleset_fingerprint(packed), block_rows=batch
        )
        with w:
            for i in range(chunks):
                t = np.ascontiguousarray(_tuples(packed, batch, seed=i).T)
                dense = t[:, t[pack_mod.T_VALID] == 1]
                w.add(pack_mod.compact_batch(dense), batch, batch - dense.shape[1])

        def run_cli(extra: list[str], out: str) -> dict:
            rc = cli.main([
                "run", "--ruleset", prefix, "--logs", wire_path,
                "--batch-size", str(batch), "--json", "--out", out, *extra,
            ])
            if rc != 0:
                raise RuntimeError(f"steptrace bench CLI run failed rc={rc}")
            with open(out, "r", encoding="utf-8") as f:
                return json.load(f)

        # warm the jit caches so both measured runs carry the same
        # (near-zero) compile residue
        run_cli([], os.path.join(d, "warm.json"))
        rep_off = run_cli([], os.path.join(d, "off.json"))
        dp_scatter = os.path.join(d, "dp-scatter")
        rep_on = run_cli(
            ["--devprof-out", dp_scatter, "--devprof-steps", str(steps),
             "--devprof-warmup", "2"],
            os.path.join(d, "on.json"),
        )
        dp_matmul = os.path.join(d, "dp-matmul")
        run_cli(
            ["--counts-impl", "matmul", "--devprof-out", dp_matmul,
             "--devprof-steps", str(steps), "--devprof-warmup", "2"],
            os.path.join(d, "matmul.json"),
        )
        cap_a = trace_diff.load_capture(dp_scatter)
        cap_b = trace_diff.load_capture(dp_matmul)
        diff = trace_diff.diff_captures(cap_a, cap_b)
        for side in ("A", "B"):
            diff[side].pop("path", None)  # tempdir paths are noise
    off = rep_off["totals"]["sustained_lines_per_sec"]
    on = rep_on["totals"]["sustained_lines_per_sec"]
    cap = rep_on["totals"]["devprof"]
    # the capture pause (profiler live for the bounded window) priced
    # apart from the armed run's sustained rate, exactly as compile is:
    # lines_this_run / (elapsed - compile - window_wall)
    t_on = rep_on["totals"]
    pause = cap.get("window_wall_sec") or 0.0
    ex_window = t_on["elapsed_sec"] - t_on["compile"]["sec"] - pause
    on_ex = (
        round(t_on["lines_total"] / ex_window, 1)
        if ex_window > 0
        else 0.0
    )
    return {
        "metric": "devprof_attributed_frac",
        "value": cap["attributed_frac"],
        "unit": "fraction of device-step time attributed to named stages",
        "vs_baseline": round(on_ex / off, 4) if off else 0.0,
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "lines": n,
            "chunks": chunks,
            "capture_steps": cap["steps_profiled"],
            "warmup": cap["warmup"],
            "disarmed_sustained_lines_per_sec": off,
            "armed_sustained_lines_per_sec_ex_window": on_ex,
            "armed_sustained_lines_per_sec_incl_window": on,
            # the >= 0.98 budget applies OUTSIDE the bounded capture
            # pause (reported right below, never hidden)
            "armed_over_disarmed": round(on_ex / off, 4) if off else 0.0,
            "armed_over_disarmed_incl_window": (
                round(on / off, 4) if off else 0.0
            ),
            "capture_pause_sec": pause,
            "attributed_frac": cap["attributed_frac"],
            "unattributed": cap["unattributed"],
            "report_identical_armed_vs_disarmed": (
                image(rep_off) == image(rep_on)
            ),
            "stages": cap["stages"],
            "programs": {
                label: {
                    k: v for k, v in prog.items() if k != "fusions"
                }
                for label, prog in cap["programs"].items()
            },
            "cross_stage_fusions": len(cap["cross_stage_fusions"]),
            "trace_diff_scatter_vs_matmul": diff,
        },
    }


def bench_stepvariants() -> dict:
    """Scatter-vs-sorted A/B grid + the two §8 inversion capture pairs
    (ISSUE 9).

    **The grid** drives the production batch geometry (batch 1<<16, the
    default sketch) over one wire corpus through the stream driver for
    every update formulation variant: ``update_impl scatter/sorted`` x
    ``topk_every 1/4``.  Per variant: a warmed sustained e2e rate, a
    bounded devprof capture (per-stage µs/step), a ``trace_diff`` delta
    table vs the scatter baseline, and an explicit keep/reject verdict —
    a measured rejection with trace evidence is a valid outcome; a
    silent keep is not.  Reports are asserted bit-identical between
    impls at equal cadence (the tentpole's contract).

    **The inversion pairs** answer two open questions with trace diffs
    instead of smells:

    - flat vs stacked at the ~27k-row multifw geometry (the TPU 0.78x
      inversion; round-5 capture, not reproduced on current code): one
      capture pair + fusion-boundary verdict;
    - counts scatter vs matmul at the production geometry (stage win /
      step loss, BENCH_r05_local.json step_variants): one capture pair
      showing where the step time went instead.

    CPU caveat (DESIGN §14): per-stage ABSOLUTE times on XLA:CPU are
    profiling-amplified on loop-lowered scatters; shares are indicative
    and fusion-boundary detection is exact.  The TPU rows re-capture
    through the same plane on the chip (ROADMAP Queue 1).
    ``RA_SEGSUM_LINES`` overrides the grid corpus size (default 1M).
    """
    import os
    import tempfile

    import jax

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.hostside import wire as wire_mod
    from ruleset_analysis_tpu.runtime import devprof
    from ruleset_analysis_tpu.runtime.stream import (
        run_stream_packed,
        run_stream_wire,
    )

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
    import trace_diff

    n = int(float(os.environ.get("RA_SEGSUM_LINES", "1e6")))
    batch = 1 << 16
    chunks = max(6, (n + batch - 1) // batch)
    n = chunks * batch
    cap_steps, cap_warmup = 2, 2
    packed = _setup()
    from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS

    volatile = VOLATILE_TOTALS

    def image(rep) -> dict:
        j = json.loads(rep.to_json())
        for k in volatile:
            j["totals"].pop(k, None)
        return j

    def verdict(e2e_ratio: float) -> str:
        if e2e_ratio >= 1.02:
            return "keep (measured e2e win on this backend)"
        if e2e_ratio <= 0.98:
            return (
                "reject on cpu (measured e2e loss; stage table + boundary "
                "diff committed — re-evaluate on TPU, ROADMAP item 5)"
            )
        return "neutral on cpu (within noise; TPU decides)"

    with tempfile.TemporaryDirectory() as d:
        wire_path = os.path.join(d, "segsum.rawire")
        w = wire_mod.WireWriter(
            wire_path, wire_mod.ruleset_fingerprint(packed), block_rows=batch
        )
        with w:
            for i in range(chunks):
                t = np.ascontiguousarray(_tuples(packed, batch, seed=i).T)
                dense = t[:, t[pack_mod.T_VALID] == 1]
                w.add(pack_mod.compact_batch(dense), batch, batch - dense.shape[1])

        def cfg_for(update_impl="scatter", topk_every=1, counts_impl="scatter"):
            return AnalysisConfig(
                batch_size=batch,
                sketch=SketchConfig(topk_every=topk_every),
                update_impl=update_impl,
                counts_impl=counts_impl,
            )

        def sustained(cfg) -> tuple[float, object]:
            run_stream_wire(packed, [wire_path], cfg)  # warm the jit
            rep = run_stream_wire(packed, [wire_path], cfg)
            return rep.totals["sustained_lines_per_sec"], rep

        def capture(cfg, name: str) -> dict:
            devprof.shutdown()
            out = os.path.join(d, f"cap-{name}")
            devprof.arm(out, steps=cap_steps, warmup=cap_warmup, label=name)
            run_stream_wire(packed, [wire_path], cfg)
            devprof.finalize_if_armed()
            return trace_diff.load_capture(out)

        variants = [
            ("scatter", dict()),
            ("sorted", dict(update_impl="sorted")),
            ("scatter_topk4", dict(topk_every=4)),
            ("sorted_topk4", dict(update_impl="sorted", topk_every=4)),
        ]
        grid, caps, reps = {}, {}, {}
        for name, kw in variants:
            log(f"stepvariants: grid variant {name}")
            lps, rep = sustained(cfg_for(**kw))
            caps[name] = capture(cfg_for(**kw), name)
            reps[name] = rep
            grid[name] = {"sustained_lines_per_sec": round(lps, 1)}
        base_lps = grid["scatter"]["sustained_lines_per_sec"]
        for name, _kw in variants:
            g = grid[name]
            ratio = round(g["sustained_lines_per_sec"] / base_lps, 4)
            g["e2e_ratio_vs_scatter"] = ratio
            g["step_us_per_step"] = round(
                caps[name]["device_us_total"]
                / max(1, caps[name]["steps_profiled"]),
                1,
            )
            g["stages_pct"] = {
                s: st["pct"] for s, st in caps[name]["stages"].items()
            }
            if name != "scatter":
                diff = trace_diff.diff_captures(caps["scatter"], caps[name])
                for side in ("A", "B"):
                    diff[side].pop("path", None)
                g["trace_diff_vs_scatter"] = diff
                g["verdict"] = verdict(ratio)
            else:
                g["verdict"] = "baseline"
        # the tentpole's contract: bit-identical reports between impls at
        # equal selection cadence (full-matrix enforcement lives in
        # tests/test_sorted_update.py; this pins the bench geometry too)
        ident = {
            "sorted_vs_scatter": image(reps["sorted"]) == image(reps["scatter"]),
            "sorted_vs_scatter_topk4": image(reps["sorted_topk4"])
            == image(reps["scatter_topk4"]),
        }
        if not all(ident.values()):
            raise AssertionError(f"bit-identity violated: {ident}")

        # ---- inversion pair 1: flat vs stacked @ ~27k rows ----
        log("stepvariants: inversion pair flat vs stacked @27k rows")
        packed27 = _setup(n_acls=2, rules_per_acl=1024, firewalls=8)
        batch27, chunks27 = 1 << 13, 5
        feeds = [
            np.ascontiguousarray(_tuples(packed27, batch27, seed=i).T)
            for i in range(2)
        ]

        def arrays():
            for i in range(chunks27):
                yield feeds[i % len(feeds)]

        def run27(layout, cap_dir=None):
            cfg = AnalysisConfig(
                batch_size=batch27,
                sketch=SketchConfig(cms_width=1 << 14, cms_depth=4),
                layout=layout,
            )
            if cap_dir is None:
                run_stream_packed(packed27, arrays(), cfg)  # warm
                t0 = time.perf_counter()
                run_stream_packed(packed27, arrays(), cfg)
                return batch27 * chunks27 / (time.perf_counter() - t0)
            devprof.shutdown()
            devprof.arm(cap_dir, steps=2, warmup=1, label=f"{layout}-27k")
            run_stream_packed(packed27, arrays(), cfg)
            devprof.finalize_if_armed()
            return trace_diff.load_capture(cap_dir)

        flat_lps = run27("flat")
        stacked_lps = run27("stacked")
        cap_flat = run27("flat", os.path.join(d, "cap-flat27"))
        cap_stacked = run27("stacked", os.path.join(d, "cap-stacked27"))
        diff_stacked = trace_diff.diff_captures(cap_flat, cap_stacked)
        for side in ("A", "B"):
            diff_stacked[side].pop("path", None)

        # ---- inversion pair 2: counts scatter vs matmul, production ----
        log("stepvariants: inversion pair counts scatter vs matmul")
        mat_lps, _rep = sustained(cfg_for(counts_impl="matmul"))
        cap_matmul = capture(cfg_for(counts_impl="matmul"), "counts-matmul")
        diff_matmul = trace_diff.diff_captures(caps["scatter"], cap_matmul)
        for side in ("A", "B"):
            diff_matmul[side].pop("path", None)
        devprof.shutdown()

    sorted_ratio = grid["sorted"]["e2e_ratio_vs_scatter"]
    return {
        "metric": "segsum_sorted_over_scatter_e2e",
        "value": sorted_ratio,
        "unit": "sustained e2e ratio, update_impl=sorted vs scatter",
        "vs_baseline": sorted_ratio,
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "lines": n,
            "chunks": chunks,
            "batch": batch,
            "capture_steps": cap_steps,
            "grid": grid,
            "report_identity": ident,
            "inversions": {
                "stacked_27k": {
                    "flat_rows": int(packed27.rules.shape[0]),
                    "batch": batch27,
                    "cpu_flat_lines_per_sec": round(flat_lps, 1),
                    "cpu_stacked_lines_per_sec": round(stacked_lps, 1),
                    "cpu_stacked_over_flat": round(
                        stacked_lps / max(flat_lps, 1.0), 4
                    ),
                    "tpu_committed_ratio": 0.78,
                    "trace_diff_flat_vs_stacked": diff_stacked,
                    "fusion_boundaries_changed": diff_stacked[
                        "fusion_boundaries_changed"
                    ],
                },
                "counts_matmul": {
                    "cpu_matmul_lines_per_sec": round(mat_lps, 1),
                    "cpu_matmul_over_scatter": round(mat_lps / base_lps, 4),
                    "trace_diff_scatter_vs_matmul": diff_matmul,
                    "fusion_boundaries_changed": diff_matmul[
                        "fusion_boundaries_changed"
                    ],
                },
            },
            "cpu_caveat": (
                "XLA:CPU profiling amplifies loop-lowered scatters and "
                "sorts; shares indicative, boundary detection exact; TPU "
                "re-capture on the chip (ROADMAP Queue 1)"
            ),
        },
    }


def bench_coalesce() -> dict:
    """Flow-coalescing guard (ISSUE 5): skewed speedup + uniform overhead.

    Three wire corpora through the production CLI at the sustained
    geometry (batch 1<<20, wire mmap -> pipelined ingest -> sharded
    step), sweeping traffic skew:

    - **uniform** — independent lines (compaction ratio ~1).  Guards the
      overhead: ``--coalesce auto`` must sample, disable itself, and
      land within ~3% of the off baseline; ``on`` prices the always-on
      hash pass.
    - **zipf s=1.0 / s=1.2** — Zipf flow repetition from a bounded pool
      (synth.zipf_weights; the heavy-hitter regime of real firewall
      logs).  Guards the win: ``--coalesce on`` vs ``off`` sustained
      speedup, expected >= 1.3x (the step is scatter/device-bound, so
      shrinking device rows by the compaction ratio dominates).

    ``RA_COALESCE_LINES`` overrides the per-corpus size (default ~6M).
    """
    import os
    import tempfile

    import jax

    from ruleset_analysis_tpu import cli
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.hostside import synth as synth_mod
    from ruleset_analysis_tpu.hostside import wire as wire_mod

    n = int(float(os.environ.get("RA_COALESCE_LINES", "6e6")))
    batch = 1 << 20
    chunks = max(3, (n + batch - 1) // batch)
    n = chunks * batch
    pool_flows = 1 << 18
    packed = _setup()
    pool = synth_mod.flow_pool(packed, pool_flows, seed=7)
    sweeps = {}
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "rules")
        pack_mod.save_packed(packed, prefix)

        def write_corpus(path: str, skew: float | None) -> None:
            w = wire_mod.WireWriter(
                path, wire_mod.ruleset_fingerprint(packed), block_rows=batch
            )
            p = (
                synth_mod.zipf_weights(pool.shape[0], skew)
                if skew is not None
                else None
            )
            with w:
                for i in range(chunks):
                    if skew is None:
                        t = _tuples(packed, batch, seed=100 + i)
                    else:
                        rng = np.random.default_rng(1000 + i)
                        t = pool[rng.choice(pool.shape[0], size=batch, p=p)]
                    t = np.ascontiguousarray(t.T)
                    dense = t[:, t[pack_mod.T_VALID] == 1]
                    w.add(
                        pack_mod.compact_batch(dense), batch,
                        batch - dense.shape[1],
                    )

        def run_cli(wire_path: str, coalesce: str, out: str) -> dict:
            rc = cli.main([
                "run", "--ruleset", prefix, "--logs", wire_path,
                "--batch-size", str(batch), "--coalesce", coalesce,
                "--json", "--out", out,
            ])
            if rc != 0:
                raise RuntimeError(f"coalesce bench CLI run failed rc={rc}")
            with open(out, "r", encoding="utf-8") as f:
                return json.load(f)

        for name, skew in [("uniform", None), ("zipf_1.0", 1.0), ("zipf_1.2", 1.2)]:
            wp = os.path.join(d, f"{name}.rawire")
            write_corpus(wp, skew)
            # warm fills the jit caches (off-path shapes); the coalesced
            # bucket shapes compile inside their own measured run's
            # compile.sec, which the sustained rate already excludes
            run_cli(wp, "off", os.path.join(d, "warm.json"))
            rep_off = run_cli(wp, "off", os.path.join(d, f"{name}-off.json"))
            rep_on = run_cli(wp, "on", os.path.join(d, f"{name}-on.json"))
            off = rep_off["totals"]["sustained_lines_per_sec"]
            on = rep_on["totals"]["sustained_lines_per_sec"]
            entry = {
                "skew": skew if skew is not None else "uniform",
                "lines": n,
                "off_sustained_lines_per_sec": off,
                "on_sustained_lines_per_sec": on,
                "on_speedup": round(on / off, 4) if off else 0.0,
                "compaction_ratio": rep_on["totals"]["coalesce"][
                    "compaction_ratio"
                ],
            }
            if skew is None:
                # production setting for unknown traffic: auto samples a
                # few batches and turns itself off — the overhead guard
                rep_auto = run_cli(
                    wp, "auto", os.path.join(d, f"{name}-auto.json")
                )
                auto = rep_auto["totals"]["sustained_lines_per_sec"]
                entry["auto_sustained_lines_per_sec"] = auto
                entry["auto_over_off"] = round(auto / off, 4) if off else 0.0
                entry["auto_disabled"] = (
                    rep_auto["totals"]["coalesce"]["active"] is False
                )
            sweeps[name] = entry

    headline = sweeps["zipf_1.0"]["on_speedup"]
    return {
        "metric": "coalesce_sustained_speedup_zipf1",
        "value": headline,
        "unit": "x vs coalesce=off",
        "vs_baseline": headline,
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "batch": batch,
            "chunks": chunks,
            "pool_flows": pool_flows,
            "guards": {
                "skewed_speedup_min": 1.3,
                "uniform_auto_overhead_max": 0.03,
                "skewed_speedup_ok": sweeps["zipf_1.0"]["on_speedup"] >= 1.3,
                "uniform_overhead_ok": sweeps["uniform"].get(
                    "auto_over_off", 0.0
                ) >= 0.97,
            },
            "sweeps": sweeps,
        },
    }


def bench_servesoak() -> dict:
    """Serve-mode soak (ISSUE 6): live listener -> windowed reports.

    Drives the production ``serve`` CLI against a synthetic syslog
    stream replayed at a paced rate over a loopback TCP socket, across
    >= 3 deterministic window rotations and ONE mid-stream hot ruleset
    reload (a renumbering re-pack picked up by the file watcher).  The
    artifact records the sustained serve-loop rate, per-rotation
    latency and the reload pause (from the obs trace's serve spans via
    ``tools.trace_summary``), and the drop count — which must be 0 at
    the offered rate for the soak to count as sustained.

    ``RA_SOAK_LINES`` (default 60k; 3 windows) and ``RA_SOAK_RATE``
    (default 12k lines/s offered — within the serve loop's measured
    per-line steady-state capacity on the 8-dev CPU mesh, so the
    kept-up guard is judged against a rate the artifact claims) size
    the soak.
    """
    import os
    import socket
    import tempfile
    import threading

    import jax

    from ruleset_analysis_tpu import cli
    from ruleset_analysis_tpu.hostside import aclparse
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.hostside import synth

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
    import trace_summary

    total = int(float(os.environ.get("RA_SOAK_LINES", "60000")))
    rate = float(os.environ.get("RA_SOAK_RATE", "12000"))
    windows = 3
    w_lines = total // windows
    total = w_lines * windows
    # small batches carry a large fixed dispatch cost (collective setup
    # dominates below ~16k rows); a live service sizes its batch to its
    # window, not to a file
    BATCH = 16384

    # OLD ruleset + a NEW re-pack that deletes the first access-list
    # line: every later rule renumbers, so the mid-soak reload exercises
    # the full migration path (not the identity fast path)
    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=12, seed=0)
    old_packed = pack_mod.pack_rulesets([aclparse.parse_asa_config(cfg_text, "fw1")])
    keep = [ln for ln in cfg_text.splitlines() if ln.startswith("access-list")]
    new_text = cfg_text.replace(keep[0] + "\n", "", 1)
    new_packed = pack_mod.pack_rulesets([aclparse.parse_asa_config(new_text, "fw1")])
    t = _tuples(old_packed, total, seed=3)
    lines = synth.render_syslog(old_packed, t, seed=3)

    def wait_for(pred, timeout, what):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return
            time.sleep(0.05)
        raise RuntimeError(f"servesoak: timed out waiting for {what}")

    def read_json(path):
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)

    def http_get(addr, path):
        import urllib.error
        import urllib.request

        for attempt in range(3):
            try:
                with urllib.request.urlopen(
                    f"http://{addr[0]}:{addr[1]}{path}", timeout=10
                ) as r:
                    return json.load(r)
            except (urllib.error.URLError, OSError):
                if attempt == 2:
                    raise
                time.sleep(0.2)

    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "rules")
        pack_mod.save_packed(old_packed, prefix)
        serve_dir = os.path.join(d, "serve")
        trace_dir = os.path.join(d, "trace")

        # warm the memoized step builders + jit caches for BOTH rulesets
        # BEFORE the service starts (a production service compiles at
        # deploy, not mid-window), so the measured soak prices the serve
        # loop, not XLA compiles; geometry must MATCH the serve CLI
        # flags below exactly — the builders memoize on (mesh, sketch
        # geometry, n_keys) and jit specializes on the register shapes
        from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
        from ruleset_analysis_tpu.runtime.stream import run_stream

        warm_cfg = AnalysisConfig(
            backend="tpu", batch_size=BATCH, prefetch_depth=0,
            sketch=SketchConfig(cms_width=1 << 14, cms_depth=4, hll_p=8),
        )
        run_stream(old_packed, iter(lines[:64]), warm_cfg)
        run_stream(new_packed, iter(lines[:64]), warm_cfg)

        rc: dict = {}
        th = threading.Thread(target=lambda: rc.update(rc=cli.main([
            "serve", "--ruleset", prefix,
            "--listen", "tcp:127.0.0.1:0",
            "--window", f"lines:{w_lines}",
            "--serve-dir", serve_dir,
            "--max-windows", str(windows),
            "--stop-after", "600",
            "--batch-size", str(BATCH),
            "--http", "127.0.0.1:0",
            "--reload-poll", "0.2",
            "--queue-lines", str(1 << 18),
            # ring-checkpoint ONCE at the final rotation: resume safety
            # is exercised, but the paced-rate phase is not serialized
            # behind this filesystem's fsync latency (production windows
            # are minutes-to-hours; these are ~1 s)
            "--checkpoint-every-windows", str(windows),
            "--trace-out", trace_dir,
        ])))
        th.start()
        ep_path = os.path.join(serve_dir, "endpoint.json")
        wait_for(lambda: os.path.exists(ep_path), 60, "serve endpoint")
        ep = read_json(ep_path)
        http = tuple(ep["http"])
        (tcp_addr,) = [a for a in ep["listeners"].values()]

        def send(seg, sock):
            # paced replay: bursts of 500 lines against the wall clock
            t0 = time.perf_counter()
            sent = 0
            for i in range(0, len(seg), 500):
                burst = seg[i:i + 500]
                sock.sendall(("\n".join(burst) + "\n").encode())
                sent += len(burst)
                lag = sent / rate - (time.perf_counter() - t0)
                if lag > 0:
                    time.sleep(lag)

        wall_start = time.time()
        s = socket.create_connection(tuple(tcp_addr))
        cut = w_lines + w_lines // 2  # reload lands mid-window-1
        send(lines[:cut], s)
        t_gap = time.perf_counter()
        pack_mod.save_packed(new_packed, prefix)  # watcher fires the reload
        wait_for(
            lambda: http_get(http, "/health")["reloads"] == 1, 60, "hot reload"
        )
        reload_wait = time.perf_counter() - t_gap  # sender idle, not service
        send(lines[cut:], s)
        s.close()
        th.join(timeout=300)
        if th.is_alive() or rc.get("rc") != 0:
            raise RuntimeError(f"servesoak: serve CLI failed rc={rc.get('rc')}")
        # the sustained clock stops at the LAST window's publication
        # (its report file's mtime): the final ring checkpoint + HTTP
        # teardown after it are shutdown cost, not serve-loop rate
        t_last_pub = os.path.getmtime(
            os.path.join(serve_dir, f"window-{windows - 1:06d}.json")
        )
        elapsed = max(t_last_pub - wall_start - reload_wait, 1e-3)
        summary = read_json(os.path.join(serve_dir, "summary.json"))
        per_window = [
            read_json(os.path.join(serve_dir, f"window-{i:06d}.json"))["totals"]
            for i in range(windows)
        ]
        attribution = trace_summary.summarize(os.path.join(trace_dir, "trace.json"))
    serve_attr = attribution.get("serve", {})
    sustained = round(total / elapsed, 1)
    return {
        "metric": "servesoak_sustained_lines_per_sec",
        "value": sustained,
        "unit": "lines/sec",
        "vs_baseline": round(sustained / rate, 4),  # achieved / offered
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "lines": total,
            "offered_rate_lines_per_sec": rate,
            "window_lines": w_lines,
            "windows_published": summary["windows_published"],
            "rotations": serve_attr.get("rotations", 0),
            "rotation_mean_ms": serve_attr.get("rotation_mean_ms"),
            "rotation_max_ms": serve_attr.get("rotation_max_ms"),
            "reload_pause_ms": serve_attr.get("reload_pause_ms"),
            "reload_watch_wait_sec": round(reload_wait, 3),
            "drops": summary["drops"],
            "reloads": summary["reloads"],
            "reload_errors": summary["reload_errors"],
            "quarantine_hits": summary["quarantine_hits"],
            "per_window_lines_per_sec": [
                t_["lines_per_sec"] for t_ in per_window
            ],
            "guards": {
                "drop_count_zero": summary["drops"] == 0,
                "three_rotations": summary["windows_published"] >= 3,
                "one_live_reload": summary["reloads"] == 1
                and summary["reload_errors"] == 0,
                "kept_up_with_offered_rate": total / elapsed >= 0.9 * rate,
            },
        },
    }


def bench_autoscale() -> dict:
    """Metrics-driven autoscaling under a square-wave offered load (ISSUE 7).

    Drives the production ``serve --autoscale`` CLI with bursts of
    loopback traffic separated by idle gaps longer than the flap-damping
    window: each burst must scale the device mesh OUT (sustained queue
    pressure), each idle must scale it back IN (sustained starvation)
    after the cooldown, with ZERO flaps and ZERO drops across the whole
    soak.  The bench measures the load->decision response latency from
    the outside (polling the same ``/metrics`` gauges the policy reads)
    and folds in the trace plane's decision evidence + time-to-effect.

    ``RA_AS_BURSTS`` (default 3) and ``RA_AS_BURST_LINES`` (default 100k)
    size the square wave.
    """
    import os
    import socket
    import tempfile
    import threading
    import urllib.request

    import jax

    from ruleset_analysis_tpu import cli
    from ruleset_analysis_tpu.hostside import aclparse
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.hostside import synth

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
    import trace_summary

    bursts = int(os.environ.get("RA_AS_BURSTS", "3"))
    w_lines = int(float(os.environ.get("RA_AS_BURST_LINES", "100000")))
    total = bursts * w_lines
    BATCH = 2048
    QUEUE = 1 << 15
    # policy knobs of the soak: damping window = 2*(cooldown+sustain) = 3s;
    # the idle gaps below hold longer than that, so zero flaps is the
    # CORRECT outcome, not a lucky one
    SUSTAIN, COOLDOWN = 0.5, 1.0
    MIN_W, MAX_W = 2, 4

    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=12, seed=0)
    packed = pack_mod.pack_rulesets([aclparse.parse_asa_config(cfg_text, "fw1")])
    t = _tuples(packed, total, seed=5)
    lines = synth.render_syslog(packed, t, seed=5)

    def wait_for(pred, timeout, what):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return
            time.sleep(0.05)
        raise RuntimeError(f"autoscale soak: timed out waiting for {what}")

    def read_json(path):
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)

    def http_json(addr, path):
        import urllib.error

        for attempt in range(3):
            try:
                with urllib.request.urlopen(
                    f"http://{addr[0]}:{addr[1]}{path}", timeout=10
                ) as r:
                    return json.load(r)
            except (urllib.error.URLError, OSError):
                if attempt == 2:
                    raise
                time.sleep(0.2)

    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "rules")
        pack_mod.save_packed(packed, prefix)
        serve_dir = os.path.join(d, "serve")
        trace_dir = os.path.join(d, "trace")

        # pre-warm the jit caches for EVERY world rung the ladder can
        # visit (a production deploy compiles its geometries up front;
        # the step builders memoize on mesh identity and the serve
        # driver's fixed max-world batch padding makes the geometry
        # identical across rungs)
        from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
        from ruleset_analysis_tpu.parallel import mesh as mesh_lib
        from ruleset_analysis_tpu.runtime.stream import run_stream

        warm_cfg = AnalysisConfig(
            backend="tpu", batch_size=BATCH, prefetch_depth=0,
            sketch=SketchConfig(cms_width=1 << 14, cms_depth=4, hll_p=8),
        )
        devs = list(jax.devices())
        for k in (MIN_W, MAX_W):
            run_stream(
                packed, iter(lines[:64]), warm_cfg,
                mesh=mesh_lib.make_mesh(devs[:k], axis=warm_cfg.mesh_axis),
            )

        rc: dict = {}
        th = threading.Thread(target=lambda: rc.update(rc=cli.main([
            "serve", "--ruleset", prefix,
            "--listen", "tcp:127.0.0.1:0",
            "--window", f"lines:{w_lines}",
            "--serve-dir", serve_dir,
            "--max-windows", str(bursts),
            "--stop-after", "600",
            "--batch-size", str(BATCH),
            "--http", "127.0.0.1:0",
            "--no-reload-watch",
            "--queue-lines", str(QUEUE),
            "--autoscale",
            "--autoscale-min", str(MIN_W),
            "--autoscale-max", str(MAX_W),
            "--autoscale-initial", str(MIN_W),
            "--autoscale-out-threshold", "0.25",
            "--autoscale-in-threshold", "0.8",
            "--autoscale-sustain", str(SUSTAIN),
            "--autoscale-cooldown", str(COOLDOWN),
            "--autoscale-budget", str(2 * bursts + 2),
            "--autoscale-poll", "0.1",
            "--trace-out", trace_dir,
        ])))
        th.start()
        ep_path = os.path.join(serve_dir, "endpoint.json")
        wait_for(lambda: os.path.exists(ep_path), 60, "serve endpoint")
        ep = read_json(ep_path)
        http = tuple(ep["http"])
        (tcp_addr,) = [a for a in ep["listeners"].values()]

        def gauge(name):
            return http_json(http, "/metrics").get(name, 0)

        damping = 2 * (COOLDOWN + SUSTAIN)
        wall_start = time.time()
        response_out, response_in = [], []
        s = socket.create_connection(tuple(tcp_addr))
        for i in range(bursts):
            seen_out = gauge("autoscale_scale_out_total")
            t0 = time.perf_counter()
            # high phase: offer the window's whole line budget as fast
            # as the queue absorbs it, throttling just under the drop
            # line — a closed-loop overload, so the queue-occupancy
            # pressure signal sustains on ANY host regardless of its
            # absolute device rate, and drops stay zero by construction
            seg = lines[i * w_lines:(i + 1) * w_lines]
            fired = False
            # ONE gauge fetch per 4096-line chunk: the serve loop answers
            # HTTP between lines, so a chatty sender would throttle
            # itself below the service's drain rate and never build the
            # very pressure the bench exists to create
            for j in range(0, len(seg), 4096):
                s.sendall(("\n".join(seg[j:j + 4096]) + "\n").encode())
                try:
                    g = http_json(http, "/metrics")
                    if not fired and g.get("autoscale_scale_out_total", 0) > seen_out:
                        response_out.append(round(time.perf_counter() - t0, 3))
                        fired = True
                    throttle_deadline = time.monotonic() + 120
                    while g.get("queue_depth", 0) > 0.55 * QUEUE:
                        if time.monotonic() > throttle_deadline:
                            raise RuntimeError(
                                "autoscale soak: queue never drained "
                                "below the throttle line (service wedged?)"
                            )
                        time.sleep(0.05)  # hold below the drop line
                        g = http_json(http, "/metrics")
                except OSError:
                    if i != bursts - 1 or j + 4096 < len(seg):
                        raise  # only the final rotation may take it down
                    break
            if i == bursts - 1:
                # the service stops itself at the final rotation (its
                # /metrics endpoint goes down with it); the summary's
                # decision log verifies this burst's scale-out below
                if not fired:
                    deadline = time.monotonic() + 120
                    while time.monotonic() < deadline and th.is_alive():
                        try:
                            if gauge("autoscale_scale_out_total") > seen_out:
                                response_out.append(
                                    round(time.perf_counter() - t0, 3)
                                )
                                break
                        except OSError:
                            break  # endpoint gone: the service finished
                        time.sleep(0.1)
                break
            if not fired:
                wait_for(
                    lambda: gauge("autoscale_scale_out_total") > seen_out,
                    120, f"scale-out on burst {i}",
                )
                response_out.append(round(time.perf_counter() - t0, 3))
            # low phase: wait for the scale-in, then hold the idle past
            # the damping window so the NEXT burst's out is a load
            # response, not a flap
            seen_in = gauge("autoscale_scale_in_total")
            t1 = time.perf_counter()
            wait_for(
                lambda: gauge("autoscale_scale_in_total") > seen_in,
                180, f"scale-in after burst {i}",
            )
            response_in.append(round(time.perf_counter() - t1, 3))
            time.sleep(damping)
        s.close()
        th.join(timeout=300)
        if th.is_alive() or rc.get("rc") != 0:
            raise RuntimeError(f"autoscale soak: serve CLI failed rc={rc.get('rc')}")
        elapsed = max(time.time() - wall_start, 1e-3)
        summary = read_json(os.path.join(serve_dir, "summary.json"))
        attribution = trace_summary.summarize(os.path.join(trace_dir, "trace.json"))
    asum = summary["autoscale"]
    tr = attribution.get("autoscale", {})
    mean_out = round(sum(response_out) / max(len(response_out), 1), 3)
    return {
        "metric": "autoscale_scale_out_response_sec",
        "value": mean_out,
        "unit": "sec (burst start -> scale-out observed at /metrics)",
        "vs_baseline": round(mean_out / max(SUSTAIN, 1e-9), 3),  # x sustain floor
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "lines": total,
            "bursts": bursts,
            "burst_lines": w_lines,
            "square_wave_idle_sec": damping,
            "world_ladder": [MIN_W, MAX_W],
            "queue_lines": QUEUE,
            "policy": {
                "out_threshold": 0.25, "in_threshold": 0.8,
                "sustain_sec": SUSTAIN, "cooldown_sec": COOLDOWN,
                "damping_window_sec": damping,
            },
            "scale_out_events": asum["scale_out"],
            "scale_in_events": asum["scale_in"],
            "flaps": asum["flaps"],
            "budget_left": asum["budget_left"],
            "final_world": summary["world"],
            "decisions": asum["decisions"],
            "response_out_sec": response_out,
            "response_in_sec": response_in,
            "time_to_effect_mean_ms": tr.get("time_to_effect_mean_ms"),
            "time_to_effect_max_ms": tr.get("time_to_effect_max_ms"),
            "trace_flaps": tr.get("flaps"),
            "drops": summary["drops"],
            "windows_published": summary["windows_published"],
            "elapsed_sec": round(elapsed, 1),
            "guards": {
                "scale_out_on_every_burst": asum["scale_out"] >= bursts,
                "scale_in_after_every_idle": asum["scale_in"] >= bursts - 1,
                "zero_flaps": asum["flaps"] == 0 and tr.get("flaps", 0) == 0,
                "zero_drops": summary["drops"] == 0,
                "all_windows_published": summary["windows_published"] == bursts,
            },
        },
    }


def _feedscale_devices() -> int:
    import jax

    return len(jax.devices())


def bench_feedscale() -> dict:
    """Host-feed scale-out (ISSUE 11 / ROADMAP 3): the aggregate
    parse+feed curve an 8-chip mesh needs.

    Four sections, all on one corpus:

    - **simd**: scalar vs dispatched (AVX2/NEON) parse of the same bytes
      — full-parse lines/s A/B plus the bulk newline-scan GB/s A/B —
      with byte-identity asserted in-bench.
    - **parse_scaling**: feeder-consumption lines/s across worker
      counts (parse only, no device) — the per-core ceiling curve.
    - **convert_fleet**: `convert --workers N` wall rates, with w=1 vs
      w=N aggregate accounting asserted equal.
    - **e2e**: full device runs, global-queue vs per-chip-ring feed
      modes, sustained lines/s + the ring occupancy/starved gauges.

    The artifact states the HONEST aggregate: on a 1-core container the
    >=8M lines/s point cannot be demonstrated locally, so the JSON
    carries the measured per-core ceiling and the cores needed to clear
    8M lines/s at that ceiling (the v5e-8 host, with >100 usable cores,
    sits far above that bar).
    """
    import ctypes
    import os
    import tempfile

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside import aclparse, fastparse, synth
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.hostside.convertfleet import (
        convert_logs_fleet,
        read_manifest,
    )
    from ruleset_analysis_tpu.hostside.feeder import ParallelFeeder
    from ruleset_analysis_tpu.runtime import obs
    from ruleset_analysis_tpu.runtime.stream import run_stream_file

    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        cores = os.cpu_count() or 1

    cfg_text = synth.synth_config(
        n_acls=4, rules_per_acl=16, seed=1, egress_acls=True
    )
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack_mod.pack_rulesets([rs])
    n_lines = 400_000
    lines = synth.render_syslog(
        packed, synth.synth_tuples(packed, n_lines, seed=2), seed=3,
        variety=0.6,
    )
    data = ("\n".join(lines) + "\n").encode()
    log(f"feedscale: corpus {n_lines} lines / {len(data) / 1e6:.1f} MB, "
        f"{cores} usable core(s), simd={fastparse.simd_kind()}")

    # ---- simd A/B: full parse + bulk newline scan, identity asserted
    def parse_once():
        pk = fastparse.NativePacker(packed)
        out, nl, used = pk.pack_chunk(
            data, 2 * n_lines, final=True, max_lines=n_lines, n_threads=1
        )
        return out, nl, used, pk.parsed, pk.skipped

    lib = fastparse._load()
    simd = {"kind": fastparse.simd_kind()}
    outs = {}
    for mode, label in ((True, "simd"), (False, "scalar")):
        fastparse.set_simd(mode)
        outs[mode] = parse_once()  # warm + identity capture
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            parse_once()
            ts.append(time.perf_counter() - t0)
        simd[f"parse_{label}_lines_per_sec"] = round(n_lines / min(ts), 1)
        t0 = time.perf_counter()
        lib.asa_count_nl(data, len(data))
        simd[f"count_nl_{label}_gb_per_sec"] = round(
            len(data) / (time.perf_counter() - t0) / 1e9, 2
        )
    fastparse.set_simd(True)
    identical = (
        np.array_equal(outs[True][0], outs[False][0])
        and outs[True][1:] == outs[False][1:]
    )
    assert identical, "SIMD parse diverged from scalar"
    simd["byte_identical"] = identical
    simd["parse_speedup"] = round(
        simd["parse_simd_lines_per_sec"] / simd["parse_scalar_lines_per_sec"],
        3,
    )
    simd["count_nl_speedup"] = round(
        simd["count_nl_simd_gb_per_sec"]
        / max(simd["count_nl_scalar_gb_per_sec"], 1e-9),
        2,
    )
    log(f"  simd: parse {simd['parse_simd_lines_per_sec'] / 1e6:.2f}M vs "
        f"scalar {simd['parse_scalar_lines_per_sec'] / 1e6:.2f}M lines/s "
        f"({simd['parse_speedup']}x); count_nl "
        f"{simd['count_nl_simd_gb_per_sec']} vs "
        f"{simd['count_nl_scalar_gb_per_sec']} GB/s")

    td = tempfile.mkdtemp(prefix="feedscale-")
    corpus_path = os.path.join(td, "corpus.log")
    with open(corpus_path, "wb") as f:
        f.write(data)

    worker_counts = [1, 2, 4]

    # ---- parse-only feeder scaling (no device): the host-feed ceiling
    parse_scaling = []
    for w in worker_counts:
        feeder = ParallelFeeder(packed, [corpus_path], n_workers=w)
        t0 = time.perf_counter()
        consumed = 0
        for _batch, n_raw in feeder.batches(0, 1 << 16):
            consumed += n_raw
        dt = time.perf_counter() - t0
        assert consumed == n_lines
        parse_scaling.append({
            "workers": w,
            "lines_per_sec": round(n_lines / dt, 1),
        })
        log(f"  parse-only w={w}: {n_lines / dt / 1e6:.2f}M lines/s")

    # ---- convert fleet scaling (pre-coalesced RAWIREv3 shards)
    convert_fleet = []
    fleet_stats = {}
    for w in worker_counts:
        out_path = os.path.join(td, f"fleet-w{w}.rawire")
        t0 = time.perf_counter()
        stats = convert_logs_fleet(
            packed, [corpus_path], out_path, workers=w
        )
        dt = time.perf_counter() - t0
        fleet_stats[w] = stats
        convert_fleet.append({
            "workers": w,
            "lines_per_sec": round(n_lines / dt, 1),
            "stored_rows": stats["rows"] + stats["rows6"],
            "evals": stats["evals"],
            "bytes": stats["bytes"],
        })
        log(f"  convert fleet w={w}: {n_lines / dt / 1e6:.2f}M lines/s, "
            f"{stats['rows']} rows")
    for w in worker_counts[1:]:
        for k in ("rows", "rows6", "raw_lines", "evals", "skipped"):
            assert fleet_stats[w][k] == fleet_stats[1][k], (
                f"fleet w={w} {k} diverged from w=1"
            )

    # ---- e2e feeder->device: global queue vs per-chip rings
    cfg = AnalysisConfig(
        batch_size=1 << 14,
        sketch=SketchConfig(cms_width=1 << 12, cms_depth=4, hll_p=8),
    )
    e2e = []
    feed_ring_gauges = None
    for mode in ("process", "ring"):
        td_tr = os.path.join(td, f"tr-{mode}")
        obs.start_trace(td_tr, role="main")
        try:
            rep = run_stream_file(
                packed, [corpus_path], cfg, feed_workers=2, feed_mode=mode
            )
        finally:
            merged = obs.merge_trace(td_tr)
            obs.shutdown()
        row = {
            "feed_mode": mode,
            "workers": 2,
            "sustained_lines_per_sec": rep.totals["sustained_lines_per_sec"],
            "ingest": rep.totals.get("ingest"),
        }
        if mode == "ring":
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            try:
                import trace_summary
            finally:
                sys.path.pop(0)
            feed_ring_gauges = trace_summary.summarize(merged).get("feed")
            row["feed"] = feed_ring_gauges
        e2e.append(row)
        log(f"  e2e {mode}: {row['sustained_lines_per_sec'] / 1e6:.3f}M "
            "lines/s sustained")

    # ---- the aggregate statement, honestly bounded by this container
    per_core_ceiling = max(
        max(r["lines_per_sec"] for r in parse_scaling),
        simd["parse_simd_lines_per_sec"],
    )
    target = 8_000_000
    cores_needed = int(np.ceil(target / per_core_ceiling))
    achieved = max(r["lines_per_sec"] for r in parse_scaling)
    aggregate = {
        "target_lines_per_sec": target,
        "container_cores": cores,
        "achieved_aggregate_lines_per_sec": achieved,
        "per_core_ceiling_lines_per_sec": per_core_ceiling,
        "cores_needed_for_target_at_ceiling": cores_needed,
        "target_demonstrated_locally": achieved >= target,
        "extrapolation": (
            f"this container exposes {cores} usable core(s), so the >=8M "
            f"lines/s aggregate cannot be demonstrated locally; at the "
            f"measured per-core ceiling of {per_core_ceiling / 1e6:.2f}M "
            f"lines/s the feed fleet needs {cores_needed} cores — a v5e-8 "
            "host (>100 usable cores) clears the bar with >5x headroom, "
            "and the descriptor/ring planes scale by construction "
            "(disjoint byte ranges, per-chip rings, no shared parse state)"
        ),
    }
    log(f"  aggregate: ceiling {per_core_ceiling / 1e6:.2f}M lines/s/core, "
        f"{cores_needed} cores needed for 8M")

    return {
        "bench": "feedscale",
        "metric": "host_feed_aggregate_lines_per_sec",
        "value": achieved,
        "detail": {
            "devices": _feedscale_devices(),
            "corpus_lines": n_lines,
            "corpus_bytes": len(data),
            "simd": simd,
            "parse_scaling": parse_scaling,
            "convert_fleet": convert_fleet,
            "e2e": e2e,
            "aggregate": aggregate,
            "identity_guards": {
                "simd_vs_scalar_parse": identical,
                "fleet_w1_vs_wN_accounting": True,
            },
        },
    }


def bench_rulescale() -> dict:
    """ISSUE 12: static-analyzer wall time vs R (the O(R²) tiling model).

    Sweeps ruleset size for the two shapes that matter: ONE big ACL
    (the worst case — the pair grid is R², witness pass included) and
    the same total rows split across stacked ACL slabs (per-ACL O(Ra²)
    grids, the shardable case).  The honest model for this 1-core
    container: tile kernels are sequential XLA:CPU dispatches, so wall
    time is ~linear in tiles_run = Σ ceil(Ra/T)² plus the witness pass
    (which scales with overlap density, not R²); on a real mesh the
    tile grid round-robins across chips (pair_relations(devices=...))
    — embarrassingly parallel, since a tile reads only its two row
    blocks.  Verdict parity across shapes/tiles guards the sweep.
    """
    from ruleset_analysis_tpu.hostside import aclparse, pack, synth
    from ruleset_analysis_tpu.runtime import staticanalysis

    def build(n_acls, rules_per_acl, seed=0):
        return pack.pack_rulesets([
            aclparse.parse_asa_config(
                synth.synth_config(
                    n_acls=n_acls, rules_per_acl=rules_per_acl, seed=seed
                ),
                "fw0",
            )
        ])

    def run(packed, tile=None):
        t0 = time.perf_counter()
        res = staticanalysis.analyze_ruleset(packed, tile=tile or 512)
        dt = time.perf_counter() - t0
        return res, dt

    sweep = []
    for r in (128, 256, 512, 1024, 2048):
        flat = build(1, r, seed=r)
        stacked = build(4, r // 4, seed=r)
        res_f, dt_f = run(flat)
        res_s, dt_s = run(stacked)
        rows_f = int(res_f.meta["n_rows"])
        rows_s = int(res_s.meta["n_rows"])
        entry = {
            "rules": r,
            "flat_1acl": {
                "rows": rows_f,
                "pairs_m": round(rows_f ** 2 / 1e6, 3),
                "tiles": res_f.meta["tiles_run"],
                "witnesses": res_f.meta["witnesses_checked"],
                "dead": res_f.meta["dead"],
                "sec": round(dt_f, 3),
            },
            "stacked_4acl": {
                "rows": rows_s,
                "tiles": res_s.meta["tiles_run"],
                "witnesses": res_s.meta["witnesses_checked"],
                "dead": res_s.meta["dead"],
                "sec": round(dt_s, 3),
            },
        }
        sweep.append(entry)
        log(f"rulescale R={r}: flat {dt_f:.2f}s ({res_f.meta['tiles_run']} "
            f"tiles, {res_f.meta['dead']} dead), stacked {dt_s:.2f}s "
            f"({res_s.meta['tiles_run']} tiles)")

    # tile-grid parity at the largest R: a small tile must not change a
    # single verdict (the sharding-safety invariant)
    big = build(1, 512, seed=512)
    v_big, _ = run(big)
    v_small, _ = run(big, tile=128)
    parity = {
        k: (v.verdict, v.basis) for k, v in v_big.verdicts.items()
    } == {
        k: (v.verdict, v.basis) for k, v in v_small.verdicts.items()
    }

    last = sweep[-1]["flat_1acl"]
    sec_per_mpair = last["sec"] / max(last["pairs_m"], 1e-9)
    return {
        "bench": "rulescale",
        "metric": "analyzer_sec_per_million_pairs_flat",
        "value": round(sec_per_mpair, 4),
        "detail": {
            "sweep": sweep,
            "tile": 512,
            "tile_parity_512_vs_128": parity,
            "model": (
                "O(R^2) pairs per ACL, walked as the LOWER-TRIANGLE "
                "[T,T] tile grid only (row order is key-ascending, so "
                "upper tiles cannot survive the earlier-key mask — "
                "~half the pair work, bit-identical verdicts); on this "
                "1-core CPU container tiles dispatch sequentially so "
                "wall ~ tiles_run x per-tile cost + witness pass "
                "(overlap-density-bound, not R^2); stacked ACLs divide "
                "the exponent's base (4 ACLs = R^2/4 total pairs) and "
                "the tile grid itself is embarrassingly device-parallel "
                "(pair_relations devices=) — unmeasured here, 1 core"
            ),
        },
    }


def bench_retrysoak() -> dict:
    """ISSUE 14: transient-fault survival soak + disarmed-overhead guard.

    Two halves:

    1. **Disarmed overhead** — the retry plane is always armed (a table
       lookup + one wrapper frame per seam call); this half measures the
       production text path with the default policies vs
       ``retry_policy="off"`` (single attempts) and guards the ratio
       inside the <2% obs budget.  Best-of-3 interleaved runs, same
       process, compile excluded by a warmup run.

    2. **Degraded-mode soak** — a live ServeDriver with injected
       non-core failures (static analyzer at start, metrics snapshotter
       persistent, disk publisher persistent) PLUS a transient
       device_put burst: ingest must keep serving with /health naming
       the degraded set and the retry engine absorbing the burst; the
       faults then clear (disarm + reload) and every subsystem must
       re-arm.  Recovery counts land in the artifact.
    """
    import os
    import socket
    import tempfile
    import threading

    import jax

    from ruleset_analysis_tpu.config import (
        AnalysisConfig, ServeConfig, SketchConfig,
    )
    from ruleset_analysis_tpu.hostside import aclparse
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.hostside import synth
    from ruleset_analysis_tpu.runtime import faults, obs, retrypolicy
    from ruleset_analysis_tpu.runtime.serve import ServeDriver
    from ruleset_analysis_tpu.runtime.stream import run_stream

    n_lines = int(float(os.environ.get("RA_RETRY_SOAK_LINES", "120000")))
    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=10, seed=0)
    packed = pack_mod.pack_rulesets([aclparse.parse_asa_config(cfg_text, "fw1")])
    t = _tuples(packed, n_lines, seed=7)
    lines = synth.render_syslog(packed, t, seed=7)

    # -- half 1: disarmed-path overhead ---------------------------------
    def rate(retry_policy: str) -> float:
        cfg = AnalysisConfig(
            backend="tpu", batch_size=1 << 14, prefetch_depth=0,
            sketch=SketchConfig(cms_width=1 << 12, cms_depth=2, hll_p=6),
            retry_policy=retry_policy,
        )
        t0 = time.perf_counter()
        run_stream(packed, iter(lines), cfg)
        return n_lines / (time.perf_counter() - t0)

    rate("off")  # warmup: compile + caches
    on_rates, off_rates = [], []
    for _ in range(3):  # interleaved best-of-3 (1-core noise)
        on_rates.append(rate(""))
        off_rates.append(rate("off"))
    armed_over_off = max(on_rates) / max(off_rates)

    # -- half 2: degraded-mode soak --------------------------------------
    W = 2000
    soak_lines = lines[:3 * W]
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "rules")
        pack_mod.save_packed(packed, prefix)
        obs.start_metrics(os.path.join(d, "metrics.jsonl"), every_sec=0.1)
        cfg = AnalysisConfig(
            backend="tpu", batch_size=512, prefetch_depth=0,
            sketch=SketchConfig(cms_width=1 << 12, cms_depth=2, hll_p=6),
            fault_plan=(
                "stream.device_put.fail@2:2,analyze.tile@1,"
                "metrics.snapshot.fail@1:99,serve.publish.fail@1:99"
            ),
        )
        scfg = ServeConfig(
            listen=("tcp:127.0.0.1:0",), window_lines=W, ring=4,
            serve_dir=os.path.join(d, "serve"), stop_after_sec=300,
            reload_watch=False, checkpoint_every_windows=0, http="off",
            queue_lines=1 << 17, static_analysis=True,
        )
        out: dict = {}
        drv = ServeDriver(prefix, cfg, scfg, topk=10)

        def runner():
            try:
                out["summary"] = drv.run()
            except BaseException as e:
                out["error"] = e

        th = threading.Thread(target=runner)
        th.start()

        def wait_for(pred, timeout, what):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if pred():
                    return
                time.sleep(0.05)
            raise RuntimeError(f"retrysoak: timed out waiting for {what}")

        wait_for(lambda: drv.listeners.alive() or "error" in out, 60, "listeners")
        s = socket.create_connection(drv.listeners.listeners[0].address)
        s.sendall(("\n".join(soak_lines[:2 * W]) + "\n").encode())
        wait_for(lambda: drv.windows_published >= 2, 180, "2 windows under faults")
        wait_for(
            lambda: {"static_analysis", "metrics", "publisher"}
            <= set(drv.health()["degraded_subsystems"]),
            60, "degraded set",
        )
        degraded_mid = drv.health()["degraded_subsystems"]
        # the faults clear: publisher re-arms on its next write, metrics
        # on its next clean tick, static on the reload's re-analysis
        faults.disarm()
        drv.request_reload()
        s.sendall(("\n".join(soak_lines[2 * W:]) + "\n").encode())
        s.close()
        wait_for(lambda: drv.windows_published >= 3, 180, "window 3")
        wait_for(
            lambda: not drv.health()["degraded_subsystems"], 120, "recovery"
        )
        drv.stop()
        th.join(timeout=120)
        obs.shutdown(merge=False)
        if th.is_alive() or "error" in out:
            raise RuntimeError(f"retrysoak: serve failed: {out.get('error')!r}")
        summary = out["summary"]

    retry_counts = summary["retry"]
    guards = {
        "disarmed_overhead_within_2pct": armed_over_off >= 0.98,
        "ingest_survived_degraded": summary["windows_published"] >= 3
        and summary["drops"] == 0,
        "degraded_set_enumerated": sorted(degraded_mid)
        == ["metrics", "publisher", "static_analysis"],
        "all_recovered": summary["degraded"] == []
        and summary["recovered_events"] >= 3,
        "transient_burst_absorbed": retry_counts.get("device_put", {})
        .get("recoveries", 0) >= 1,
    }
    return {
        "bench": "retrysoak",
        "metric": "retry_armed_over_off_rate_ratio",
        "value": round(armed_over_off, 4),
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "overhead_lines": n_lines,
            "rates_armed": [round(r, 1) for r in on_rates],
            "rates_off": [round(r, 1) for r in off_rates],
            "soak": {
                "windows_published": summary["windows_published"],
                "drops": summary["drops"],
                "degraded_mid_soak": degraded_mid,
                "degraded_final": summary["degraded"],
                "degraded_events": summary["degraded_events"],
                "recovered_events": summary["recovered_events"],
                "retry_counters": retry_counts,
            },
            "guards": guards,
        },
    }


def bench_blackbox() -> dict:
    """ISSUE 15: flight-recorder overhead guard + postmortem acceptance.

    Three parts:

    1. **Recorder overhead** — the production text path with the
       always-on flight recorder armed (a blackbox dir, the default)
       vs ``--blackbox off``, 5 interleaved pairs through the REAL
       CLI, compile excluded by a warmup run.  Sustained ratio
       (median over median) must be >= 0.98 (the PR 4 <2%%
       observability budget) and the reports must be BIT-IDENTICAL
       (VOLATILE-stripped) — both asserted in-bench.

    2. **Histogram-arm overhead** — the latency histograms are
       unconditionally armed (one ``record`` per committed batch /
       consumed line), so their cost is priced directly: ns per record
       x the production batch cadence -> overhead fraction.

    3. **Postmortem acceptance** — a chaos-killed run with NO
       trace/metrics flags leaves a merged ``postmortem.json`` from
       which ``doctor`` names the failing stage and the fired fault
       site; a clean run leaves nothing.  Asserted in-bench (the same
       contract tests/test_flightrec.py pins in tier-1).
    """
    import os
    import tempfile

    import jax

    from ruleset_analysis_tpu import cli
    from ruleset_analysis_tpu.hostside import aclparse
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.hostside import synth
    from ruleset_analysis_tpu.runtime import flightrec
    from ruleset_analysis_tpu.runtime.metrics import LatencyHistogram
    from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS

    n_lines = int(float(os.environ.get("RA_BLACKBOX_LINES", "120000")))
    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=10, seed=0)
    packed = pack_mod.pack_rulesets([aclparse.parse_asa_config(cfg_text, "fw1")])
    t = _tuples(packed, n_lines, seed=11)
    lines = synth.render_syslog(packed, t, seed=11)

    def image(rep: dict) -> dict:
        rep = json.loads(json.dumps(rep))
        for k in VOLATILE_TOTALS:
            rep["totals"].pop(k, None)
        return rep

    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "rules")
        pack_mod.save_packed(packed, prefix)
        log = os.path.join(d, "fw1.log")
        with open(log, "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")

        def run_cli(extra: list[str], out: str) -> tuple[int, dict | None]:
            rc = cli.main([
                "run", "--ruleset", prefix, "--logs", log,
                "--batch-size", str(1 << 14), "--cms-width", str(1 << 12),
                "--cms-depth", "2", "--hll-p", "6",
                "--json", "--out", out, *extra,
            ])
            # a fresh recorder per run: cli arming is per-invocation
            flightrec._reset_for_tests()
            if rc == 0:
                with open(out, "r", encoding="utf-8") as f:
                    return rc, json.load(f)
            return rc, None

        bb = os.path.join(d, "bb")
        on_flags = ["--blackbox-dir", bb]
        off_flags = ["--blackbox", "off"]
        run_cli(off_flags, os.path.join(d, "warm.json"))  # compile warmup
        on_rates, off_rates = [], []
        rep_on = rep_off = None
        for i in range(5):  # interleaved pairs (1-core noise)
            t0 = time.perf_counter()
            _, rep_on = run_cli(on_flags, os.path.join(d, f"on{i}.json"))
            on_rates.append(n_lines / (time.perf_counter() - t0))
            t0 = time.perf_counter()
            _, rep_off = run_cli(off_flags, os.path.join(d, f"off{i}.json"))
            off_rates.append(n_lines / (time.perf_counter() - t0))
        # ratio of medians: per-sample jitter on this container is ~±8%,
        # so a best-of-N comparison flakes around the 2% budget; the
        # median of 5 interleaved samples per arm is stable
        med = lambda xs: sorted(xs)[len(xs) // 2]
        ratio = med(on_rates) / med(off_rates)
        assert ratio >= 0.98, f"recorder-on/off sustained ratio {ratio:.4f} < 0.98"
        identical = image(rep_on) == image(rep_off)
        assert identical, "blackbox-armed report diverged from disarmed"
        # clean exits left NO forensics behind
        leftovers = sorted(os.listdir(bb)) if os.path.isdir(bb) else []
        assert not leftovers, f"clean runs left forensics: {leftovers}"

        # -- histogram-arm overhead (the always-on record path) ----------
        h = LatencyHistogram()
        reps = 200_000
        t0 = time.perf_counter()
        for _ in range(reps):
            h.record(1.5e-3)
        ns_per_record = (time.perf_counter() - t0) / reps * 1e9
        # one record per committed batch on the ingest path: overhead
        # fraction at the measured sustained cadence
        batches_per_sec = max(off_rates) / (1 << 14)
        hist_overhead_frac = ns_per_record * 1e-9 * batches_per_sec

        # -- postmortem acceptance ---------------------------------------
        bb2 = os.path.join(d, "bb2")
        rc, _ = run_cli(
            ["--blackbox-dir", bb2, "--fault-plan", "ingest.producer.raise@3"],
            os.path.join(d, "crash.json"),
        )
        assert rc != 0, "chaos run must abort typed"
        bundle = flightrec.load_bundle(bb2)
        sites = bundle["analysis"]["fault_sites_fired"]
        assert sites.get("ingest.producer.raise"), sites
        diags = flightrec.diagnose(bundle, exit_code=rc)
        assert diags and "fault plan" in diags[0]["cause"]
        acceptance = {
            "exit_code": rc,
            "trigger": bundle["trigger"],
            "failing_stage": bundle["analysis"]["failing_stage"],
            "fault_sites_fired": sites,
            "doctor_top_cause": diags[0]["cause"],
            "shards": len(bundle["shards"]),
        }

    guards = {
        "recorder_on_over_off_ge_0p98": ratio >= 0.98,
        "report_bit_identical": identical,
        "clean_exit_leaves_none": not leftovers,
        "postmortem_names_fired_site": True,  # asserted above
    }
    return {
        "bench": "blackbox",
        "metric": "blackbox_on_over_off_rate_ratio",
        "value": round(ratio, 4),
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "lines": n_lines,
            "rates_on": [round(r, 1) for r in on_rates],
            "rates_off": [round(r, 1) for r in off_rates],
            "histogram_ns_per_record": round(ns_per_record, 1),
            "histogram_overhead_frac_at_batch_cadence": round(
                hist_overhead_frac, 8
            ),
            "acceptance": acceptance,
            "guards": guards,
        },
    }


def bench_tenant() -> dict:
    """ISSUE 16: one packed N-tenant serve process vs N sequential
    solo serves — the committed evidence is BENCH_TENANT_r18_cpu.json.

    N small tenants with DISTINCT geometries (each its own key
    universe) share one rule-rung bucket, so the packed process
    compiles ONE tenant step for all of them while every solo process
    compiles its own flat step, builds its own mesh, and stands up its
    own serve machinery.  Measures wall-clock for the same traffic
    (N x L lines, one window each) both ways; the ratio
    (sequential-solo total / packed) must be >= 2.0 at N=16 — asserted
    in-bench, like the blackbox budget.  Per-tenant window reports are
    spot-checked bit-identical to the solo runs (the full sweep lives
    in tests/test_tenancy.py).
    """
    import os
    import shutil
    import socket
    import tempfile
    import threading

    import jax

    from ruleset_analysis_tpu.config import AnalysisConfig, ServeConfig
    from ruleset_analysis_tpu.hostside import aclparse, synth
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS
    from ruleset_analysis_tpu.runtime.serve import ServeDriver
    from ruleset_analysis_tpu.runtime.tenantserve import TenantServeDriver

    n_tenants = int(os.environ.get("RA_TENANTS", "16"))
    n_lines = int(os.environ.get("RA_TENANT_LINES", "100"))
    run_cfg = dict(batch_size=128, prefetch_depth=0)

    def image(rep: dict) -> dict:
        rep = json.loads(json.dumps(rep))
        for k in VOLATILE_TOTALS:
            rep["totals"].pop(k, None)
        rep["totals"].pop("window", None)
        rep["totals"].pop("tenant", None)
        return rep

    def drive(drv, feed, n_listeners):
        out: dict = {}

        def runner():
            try:
                out["summary"] = drv.run()
            except BaseException as e:
                out["error"] = e

        th = threading.Thread(target=runner)
        th.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if out.get("error") is not None:
                break
            if drv.listeners.alive() == n_listeners:
                break
            time.sleep(0.02)
        if "error" not in out:
            feed(drv)
        th.join(timeout=600)
        if "error" in out:
            raise out["error"]
        return out["summary"]

    td = tempfile.mkdtemp(prefix="ra-bench-tenant-")
    try:
        tenants: dict[str, tuple[str, list[str]]] = {}
        rows = []
        for i in range(n_tenants):
            name = f"t{i:02d}"
            cfg_text = synth.synth_config(
                n_acls=2, rules_per_acl=6 + i, seed=10 + i, v6_fraction=0.0
            )
            packed = pack_mod.pack_rulesets(
                [aclparse.parse_asa_config(cfg_text, f"fw{i}")]
            )
            prefix = os.path.join(td, f"rules{i}")
            pack_mod.save_packed(packed, prefix)
            t = _tuples(packed, n_lines, seed=20 + i)
            lines = synth.render_syslog(packed, t, seed=30 + i)
            tenants[name] = (prefix, lines)
            rows.append({
                "name": name, "ruleset": prefix,
                "listen": ["tcp:127.0.0.1:0"],
            })
        manifest = os.path.join(td, "manifest.json")
        with open(manifest, "w", encoding="utf-8") as f:
            json.dump({"tenants": rows}, f)

        log(f"tenant: packed serve, {n_tenants} tenants x {n_lines} lines")
        t0 = time.perf_counter()
        pdir = os.path.join(td, "packed")
        scfg = ServeConfig(
            listen=(), window_lines=n_lines, ring=4, serve_dir=pdir,
            max_windows=n_tenants, http="off", checkpoint_every_windows=0,
        )
        drv = TenantServeDriver(manifest, AnalysisConfig(**run_cfg), scfg)

        def feed_all(d):
            by_tenant = {
                ln.q.tenant: ln.address for ln in d.listeners.listeners
            }
            for name, (_prefix, lines) in sorted(tenants.items()):
                s = socket.create_connection(tuple(by_tenant[name]))
                s.sendall(("\n".join(lines) + "\n").encode())
                s.close()

        summary = drive(drv, feed_all, n_tenants)
        packed_wall = time.perf_counter() - t0
        assert summary["windows_published"] == n_tenants, summary
        assert summary["lines_unrouted"] == 0, summary

        solo_walls = []
        for name, (prefix, lines) in sorted(tenants.items()):
            log(f"tenant: solo serve {name}")
            t0 = time.perf_counter()
            sdir = os.path.join(td, f"solo-{name}")
            sscfg = ServeConfig(
                listen=("tcp:127.0.0.1:0",), window_lines=n_lines, ring=4,
                serve_dir=sdir, max_windows=1, http="off",
                checkpoint_every_windows=0,
            )
            sdrv = ServeDriver(prefix, AnalysisConfig(**run_cfg), sscfg)

            def feed_one(d, _lines=lines):
                s = socket.create_connection(
                    tuple(d.listeners.listeners[0].address)
                )
                s.sendall(("\n".join(_lines) + "\n").encode())
                s.close()

            drive(sdrv, feed_one, 1)
            solo_walls.append(time.perf_counter() - t0)
        solo_total = sum(solo_walls)
        ratio = solo_total / packed_wall

        identical = 0
        for name in sorted(tenants):
            with open(os.path.join(
                pdir, "t", name, "window-000000.json"
            ), encoding="utf-8") as f:
                a = json.load(f)
            with open(os.path.join(
                td, f"solo-{name}", "window-000000.json"
            ), encoding="utf-8") as f:
                b = json.load(f)
            assert image(a) == image(b), f"{name} diverged from solo"
            identical += 1
        assert ratio >= 2.0, (
            f"packed {n_tenants}-tenant serve is only {ratio:.2f}x "
            f"sequential solo (packed {packed_wall:.1f}s vs "
            f"solo {solo_total:.1f}s); want >= 2.0x"
        )
    finally:
        shutil.rmtree(td, ignore_errors=True)
    return {
        "bench": "tenant",
        "metric": "solo_sequential_over_packed_wall_ratio",
        "value": round(ratio, 2),
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "tenants": n_tenants,
            "lines_per_tenant": n_lines,
            "packed_wall_sec": round(packed_wall, 2),
            "solo_total_wall_sec": round(solo_total, 2),
            "solo_wall_sec": [round(w, 2) for w in solo_walls],
            "reports_bit_identical": identical,
            "guards": {"ratio_ge_2": True, "bit_identical_all": True},
        },
    }


def bench_servescale() -> dict:
    """Multi-host distributed serve scale-out (ISSUE 17, DESIGN §22).

    Three legs, one corpus, process-mode workers throughout:

    1. **Solo reference** — a single ``ServeDriver`` replays the union
       corpus (per-window: host 0's slice then host 1's slice) at the
       aggregate offered rate.  Its window reports are the bit-identity
       baseline and its sustained rate the per-host ceiling.
    2. **2-host distributed** — ``DistServeDriver`` with two spawned
       worker processes, each fed its own slice at the per-host rate.
       Asserted in-bench: every merged window report AND the cumulative
       report are bit-identical (VOLATILE-stripped) to the solo run —
       registers, per-rule hits, unique-source counts, and the
       unused-rule deletion candidates — with the talkers section's
       heavy-hitter prefix pinned exactly (its deep tail is sampled-
       candidate CMS output, approximate by design; tier-1 pins FULL
       identity, talkers included, at complete candidate coverage);
       zero drops on either side; and the rank-0
       merge+publish stage — the only serialized cross-host work —
       costs <= 0.2 of the ingest wall, which is exactly the condition
       under which N dedicated host cores sustain >= 0.8*N x the
       single-host rate.
    3. **Whole-host chaos** — a fresh 2-host run SIGKILLs host 1
       mid-window: the service must keep publishing every window, name
       ``host_died:1`` in the incomplete markers of the affected
       windows, and lose none of host 0's delivered lines.

    The artifact states the HONEST aggregate: on this 1-core container
    both "hosts" timeshare one CPU, so the >= 0.8*N claim is the
    measured solo rate x N x (1 - measured merge overhead fraction) —
    the same per-core extrapolation discipline as FEEDSCALE_r14.

    ``RA_SERVESCALE_LINES`` (default 24k; 3 windows) and
    ``RA_SERVESCALE_RATE`` (default 4k lines/s offered PER HOST) size
    the soak.
    """
    import os
    import socket
    import tempfile
    import threading

    import jax

    from ruleset_analysis_tpu.config import (
        AnalysisConfig,
        DistServeConfig,
        ServeConfig,
    )
    from ruleset_analysis_tpu.hostside import aclparse, synth
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.runtime.distserve import DistServeDriver
    from ruleset_analysis_tpu.runtime.report import VOLATILE_TOTALS
    from ruleset_analysis_tpu.runtime.serve import ServeDriver
    from ruleset_analysis_tpu.runtime.stream import run_stream

    n_hosts = 2
    windows = 3
    rate = float(os.environ.get("RA_SERVESCALE_RATE", "4000"))
    wl = int(float(os.environ.get("RA_SERVESCALE_LINES", "24000"))) // (
        n_hosts * windows
    )
    total = wl * n_hosts * windows
    BATCH = 4096

    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=10, seed=0)
    packed = pack_mod.pack_rulesets([aclparse.parse_asa_config(cfg_text, "fw1")])
    t = _tuples(packed, total, seed=7)
    lines = synth.render_syslog(packed, t, seed=7)
    # union order IS the solo replay order; host r's stream is the
    # concatenation of its per-window slices, so merged window w and
    # solo window w cover the same lines
    host_stream = {
        r: [
            ln
            for w in range(windows)
            for ln in lines[(w * n_hosts + r) * wl:(w * n_hosts + r + 1) * wl]
        ]
        for r in range(n_hosts)
    }

    def image(rep: dict) -> dict:
        rep = json.loads(json.dumps(rep))
        for k in VOLATILE_TOTALS:
            rep["totals"].pop(k, None)
        # window/chunk metadata names hosts and batch segmentation —
        # layout, not analysis content.  talkers compared separately:
        # the section is a sampled-candidate CMS summary whose deep
        # tail is approximate BY DESIGN (per-chunk slot-limited
        # sampling differs with chunk boundaries), so the register-law
        # identity covers everything else bit-exactly while the talker
        # check pins the heavy-hitter prefix.  Full identity including
        # talkers is pinned at the tier-1 geometry (tests/
        # test_distserve.py), where candidate coverage is complete.
        rep["totals"].pop("window", None)
        rep["totals"].pop("chunks", None)
        rep.pop("talkers", None)
        return rep

    def talker_heads(rep: dict, k: int = 3) -> dict:
        return {
            acl: rows[:k] for acl, rows in (rep.get("talkers") or {}).items()
        }

    def read_json(path):
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)

    def paced_send(addr, seg, rate, *, swallow=False):
        try:
            s = socket.create_connection(tuple(addr))
            t0 = time.perf_counter()
            sent = 0
            for i in range(0, len(seg), 500):
                burst = seg[i:i + 500]
                s.sendall(("\n".join(burst) + "\n").encode())
                sent += len(burst)
                lag = sent / rate - (time.perf_counter() - t0)
                if lag > 0:
                    time.sleep(lag)
            s.close()
        except OSError:
            if not swallow:
                raise

    def wait_for(pred, timeout, what):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return
            time.sleep(0.05)
        raise RuntimeError(f"servescale: timed out waiting for {what}")

    def run_driver(drv):
        out: dict = {}

        def runner():
            try:
                out["summary"] = drv.run()
            except BaseException as e:  # surfaced by the caller
                out["error"] = e

        th = threading.Thread(target=runner)
        th.start()
        return th, out

    def host_tcp(drv, r):
        with drv._lock:
            h = drv.hosts.get(r)
            addrs = dict(h.addresses) if h else {}
        for lbl, ad in addrs.items():
            if lbl.startswith("tcp"):
                return tuple(ad)
        return None

    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "rules")
        pack_mod.save_packed(packed, prefix)

        # warm the jit caches at the exact serve geometry so the solo
        # leg prices the serve loop, not XLA compiles (spawned workers
        # compile in their own processes; the oversized listener queue
        # absorbs that stall without drops)
        warm_cfg = AnalysisConfig(batch_size=BATCH, prefetch_depth=0)
        run_stream(packed, iter(lines[:64]), warm_cfg)

        # ---- leg 1: solo reference over the union ----
        solo_dir = os.path.join(d, "solo")
        solo_drv = ServeDriver(
            prefix,
            AnalysisConfig(batch_size=BATCH, prefetch_depth=0),
            ServeConfig(
                listen=("tcp:127.0.0.1:0",), window_lines=n_hosts * wl,
                serve_dir=solo_dir, max_windows=windows, http="off",
                checkpoint_every_windows=0, reload_watch=False,
                queue_lines=1 << 18,
            ),
        )
        th, out = run_driver(solo_drv)
        ep_path = os.path.join(solo_dir, "endpoint.json")
        wait_for(lambda: os.path.exists(ep_path), 120, "solo endpoint")
        (solo_addr,) = read_json(ep_path)["listeners"].values()
        wall_start = time.time()
        paced_send(solo_addr, lines, n_hosts * rate)
        th.join(timeout=600)
        if th.is_alive() or "error" in out:
            raise RuntimeError(f"servescale: solo leg failed: {out.get('error')}")
        # the sustained clock stops at the LAST window's publication
        solo_elapsed = max(
            os.path.getmtime(
                os.path.join(solo_dir, f"window-{windows - 1:06d}.json")
            ) - wall_start,
            1e-3,
        )
        solo_sum = out["summary"]
        assert solo_sum["drops"] == 0, f"solo dropped {solo_sum['drops']}"
        solo_rate = total / solo_elapsed
        log(f"servescale: solo {solo_rate:,.0f} lines/s over {total} lines")

        # ---- leg 2: 2-host distributed at the same per-host rate ----
        dist_dir = os.path.join(d, "dist")
        dist_drv = DistServeDriver(
            prefix,
            AnalysisConfig(
                batch_size=BATCH, prefetch_depth=0, mesh_shape="hybrid"
            ),
            ServeConfig(
                listen=("tcp:127.0.0.1:0",), window_lines=wl,
                serve_dir=dist_dir, max_windows=windows, http="off",
                checkpoint_every_windows=0, reload_watch=False,
                queue_lines=1 << 18,
            ),
            DistServeConfig(hosts=n_hosts, workers="process"),
        )
        merge_times: list[float] = []
        orig_pub = dist_drv._publish_window

        def timed_pub(*a, **k):
            tp = time.perf_counter()
            r = orig_pub(*a, **k)
            merge_times.append(time.perf_counter() - tp)
            return r

        dist_drv._publish_window = timed_pub
        th, out = run_driver(dist_drv)
        wait_for(
            lambda: out.get("error")
            or all(host_tcp(dist_drv, r) for r in range(n_hosts)),
            300, "distributed host listeners",
        )
        if "error" in out:
            raise RuntimeError(f"servescale: dist leg failed: {out['error']}")
        t_ingest0 = time.perf_counter()
        senders = [
            threading.Thread(
                target=paced_send,
                args=(host_tcp(dist_drv, r), host_stream[r], rate),
            )
            for r in range(n_hosts)
        ]
        for s in senders:
            s.start()
        for s in senders:
            s.join()
        th.join(timeout=600)
        if th.is_alive() or "error" in out:
            raise RuntimeError(f"servescale: dist leg failed: {out.get('error')}")
        ingest_wall = max(time.perf_counter() - t_ingest0, 1e-3)
        dist_sum = out["summary"]
        assert dist_sum["drops"] == 0, f"dist dropped {dist_sum['drops']}"
        assert dist_sum["lines_total"] == total, (
            f"dist published {dist_sum['lines_total']} of {total} lines"
        )
        assert dist_sum["dead_hosts"] == [], dist_sum["dead_hosts"]

        identical = 0
        for w in range(windows):
            a = read_json(os.path.join(dist_dir, f"window-{w:06d}.json"))
            b = read_json(os.path.join(solo_dir, f"window-{w:06d}.json"))
            assert image(a) == image(b), (
                f"merged window {w} diverged from the solo replay"
            )
            assert talker_heads(a) == talker_heads(b), (
                f"merged window {w} heavy-hitter talkers diverged"
            )
            identical += 1
        cum_a = read_json(os.path.join(dist_dir, "cumulative.json"))
        cum_b = read_json(os.path.join(solo_dir, "cumulative.json"))
        cum_same = image(cum_a) == image(cum_b) and (
            talker_heads(cum_a) == talker_heads(cum_b)
        )
        assert cum_same, "cumulative report diverged from the solo replay"

        merge_wall = sum(merge_times)
        merge_frac = merge_wall / ingest_wall
        assert merge_frac <= 0.2, (
            f"rank-0 merge+publish is {merge_frac:.1%} of the ingest wall "
            "(> 20%); the 0.8*N scaling floor does not hold"
        )
        # N dedicated host cores ingest at ~solo_rate each; rank 0's
        # merge is the only serialized stage, so the honest aggregate
        # is N x solo x (1 - merge_frac) >= 0.8 * N * solo
        extrapolated = n_hosts * solo_rate * (1.0 - merge_frac)
        assert extrapolated >= 0.8 * n_hosts * solo_rate

        # ---- leg 3: whole-host SIGKILL chaos ----
        chaos_dir = os.path.join(d, "chaos")
        chaos_drv = DistServeDriver(
            prefix,
            AnalysisConfig(
                batch_size=BATCH, prefetch_depth=0, mesh_shape="hybrid"
            ),
            ServeConfig(
                listen=("tcp:127.0.0.1:0",), window_lines=wl,
                serve_dir=chaos_dir, max_windows=windows, http="off",
                checkpoint_every_windows=0, reload_watch=False,
                queue_lines=1 << 18,
            ),
            DistServeConfig(hosts=n_hosts, workers="process"),
        )
        th, out = run_driver(chaos_drv)
        wait_for(
            lambda: out.get("error")
            or all(host_tcp(chaos_drv, r) for r in range(n_hosts)),
            300, "chaos host listeners",
        )
        if "error" in out:
            raise RuntimeError(f"servescale: chaos leg failed: {out['error']}")
        h0 = threading.Thread(
            target=paced_send,
            args=(host_tcp(chaos_drv, 0), host_stream[0], rate),
        )
        h0.start()
        # host 1 gets 1.5 windows' worth, then dies mid-window — but
        # only after its window-0 epoch reached rank 0, so the kill
        # lands in window 1, not in a still-compiling first batch
        paced_send(
            host_tcp(chaos_drv, 1), host_stream[1][:wl + wl // 2], rate,
            swallow=True,
        )
        wait_for(
            lambda: out.get("error") or chaos_drv.hosts[1].last_wid >= 0,
            300, "host 1's first epoch",
        )
        chaos_drv.kill_host(1)
        h0.join()
        th.join(timeout=600)
        if th.is_alive() or "error" in out:
            raise RuntimeError(
                f"servescale: chaos leg failed: {out.get('error')}"
            )
        chaos_sum = out["summary"]
        assert chaos_sum["dead_hosts"] == [1], chaos_sum["dead_hosts"]
        assert chaos_sum["windows_published"] == windows, chaos_sum
        assert chaos_sum["drops"] == 0, f"chaos dropped {chaos_sum['drops']}"
        # every line host 0 delivered is published, plus host 1's
        # completed window 0 — a dead peer degrades the merge, it does
        # not silently shrink survivors
        assert chaos_sum["lines_total"] >= windows * wl + wl, (
            chaos_sum["lines_total"]
        )
        died_marks = 0
        for w in range(windows):
            meta = read_json(
                os.path.join(chaos_dir, f"window-{w:06d}.json")
            )["totals"]["window"]
            reasons = (meta.get("incomplete") or {}).get("reasons", [])
            if any(r.startswith("host_died:1") for r in reasons):
                died_marks += 1
        assert died_marks >= 1, "no window names the killed host"

    sustained_1core = round(total / ingest_wall, 1)
    return {
        "bench": "servescale",
        "metric": "servescale_extrapolated_aggregate_lines_per_sec",
        "value": round(extrapolated, 1),
        "unit": "lines/sec",
        "vs_baseline": round(extrapolated / solo_rate, 3),  # x single host
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "hosts": n_hosts,
            "workers": "process",
            "windows": windows,
            "lines_total": total,
            "offered_rate_per_host_lines_per_sec": rate,
            "solo_sustained_lines_per_sec": round(solo_rate, 1),
            "dist_1core_timeshared_lines_per_sec": sustained_1core,
            "merge_publish_wall_sec": round(merge_wall, 3),
            "merge_publish_per_window_ms": [
                round(x * 1e3, 1) for x in merge_times
            ],
            "merge_overhead_frac": round(merge_frac, 4),
            "windows_bit_identical": identical,
            "cumulative_bit_identical": cum_same,
            "chaos": {
                "killed_host": 1,
                "dead_hosts": chaos_sum["dead_hosts"],
                "windows_published": chaos_sum["windows_published"],
                "windows_naming_dead_host": died_marks,
                "lines_published": chaos_sum["lines_total"],
                "drops": chaos_sum["drops"],
            },
            "extrapolation": (
                "both hosts timeshare one CPU core here, so the "
                "aggregate is stated as solo_rate x hosts x (1 - "
                "merge_overhead_frac): per-host ingest is share-nothing "
                "(own listener, queue, feeder, registers) and the "
                "measured rank-0 merge+publish stage is the only "
                "serialized cross-host work"
            ),
            "guards": {
                "bit_identical_all_windows": True,
                "talker_heads_identical": True,
                "cumulative_bit_identical": True,
                "zero_drops_both_runs": True,
                "merge_overhead_le_0p2": True,
                "extrapolated_ge_0p8N": True,
                "chaos_names_dead_host": True,
                "chaos_zero_silent_drops": True,
            },
        },
    }


def bench_failover() -> dict:
    """Supervisor failover soak (DESIGN §23): kill the lease-holder
    mid-soak, elect a successor, replay the spools — and price the
    armed failover plane.

    Four legs, one corpus, thread-mode workers (one process, shared jit
    caches — the kill is the in-process supervisor-death seam, exactly
    what the slow CLI SIGKILL e2e pins end-to-end):

    1. **Bare reference** — lease + spool disabled
       (``lease_ttl_sec=0``, ``spool_budget_mb=0``): the pre-§23 serve
       plane's sustained rate on this corpus.
    2. **Armed control** — lease + spool on, no chaos: publishes every
       window under term 1.  Asserted in-bench: the spool/lease armed
       overhead — 1 - armed_rate/bare_rate — is **< 2%** (the r19
       SERVESCALE plane must not get slower by growing a failover
       plane; both legs are paced identically, so the rates differ
       only by per-window spool fsyncs and ttl/4 lease heartbeats).
    3. **Victim** — a full merge-plane partition
       (``dist.epoch.ship`` armed for the whole leg) parks every epoch
       in the durable spools, then the supervisor dies abruptly with
       ZERO windows published.
    4. **Successor** — wins term 2 off the on-disk lease and replays
       the spools.  Asserted in-bench: every window it publishes is
       **bit-identical** (VOLATILE-stripped, talkers included) to the
       unkilled control's, zero drops, zero skipped windows, every
       window stamped with exactly one fencing term (control windows
       term 1, successor windows term 2 — one publisher per term),
       every replayed window's **lineage record** is identical to the
       control's outside the volatile term/path stamps (DESIGN §24's
       replay-identity law) with a gapless successor ledger frontier,
       and **time-to-takeover** (successor start -> last replayed
       window on disk, election + replay inclusive) is **<= 2x the
       lease TTL**.

    ``RA_FAILOVER_LINES`` (default 12k; 2 hosts x 4 windows) and
    ``RA_FAILOVER_RATE`` (default 3k lines/s offered PER HOST) size
    the soak.
    """
    import os
    import socket
    import tempfile
    import threading

    import jax

    from ruleset_analysis_tpu.config import (
        AnalysisConfig,
        DistServeConfig,
        ServeConfig,
    )
    from ruleset_analysis_tpu.hostside import aclparse, synth
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.runtime import faults
    from ruleset_analysis_tpu.runtime.distserve import DistServeDriver
    from ruleset_analysis_tpu.errors import AnalysisError
    from ruleset_analysis_tpu.runtime.report import (
        LINEAGE_VOLATILE,
        VOLATILE_TOTALS,
        lineage_frontier,
    )
    from ruleset_analysis_tpu.runtime.stream import run_stream
    from ruleset_analysis_tpu.runtime.wal import LineageLog

    n_hosts = 2
    windows = 4
    ttl = 2.0
    rate = float(os.environ.get("RA_FAILOVER_RATE", "3000"))
    wl = int(float(os.environ.get("RA_FAILOVER_LINES", "12000"))) // (
        n_hosts * windows
    )
    total = wl * n_hosts * windows
    BATCH = 4096

    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=10, seed=0)
    packed = pack_mod.pack_rulesets([aclparse.parse_asa_config(cfg_text, "fw1")])
    t = _tuples(packed, total, seed=23)
    lines = synth.render_syslog(packed, t, seed=23)
    host_stream = {
        r: [
            ln
            for w in range(windows)
            for ln in lines[(w * n_hosts + r) * wl:(w * n_hosts + r + 1) * wl]
        ]
        for r in range(n_hosts)
    }

    def image(rep: dict) -> dict:
        rep = json.loads(json.dumps(rep))
        for k in VOLATILE_TOTALS:
            rep["totals"].pop(k, None)
        # window meta names hosts, chunks, and the fencing term —
        # provenance, not analysis content (the term is asserted
        # separately: that is the one-publisher-per-term pin)
        rep["totals"].pop("window", None)
        rep["totals"].pop("chunks", None)
        return rep

    def read_json(path):
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)

    def paced_send(addr, seg, rate):
        s = socket.create_connection(tuple(addr))
        t0 = time.perf_counter()
        sent = 0
        for i in range(0, len(seg), 500):
            burst = seg[i:i + 500]
            s.sendall(("\n".join(burst) + "\n").encode())
            sent += len(burst)
            lag = sent / rate - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
        s.close()

    def wait_for(pred, timeout, what):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return
            time.sleep(0.05)
        raise RuntimeError(f"failover: timed out waiting for {what}")

    def run_driver(drv):
        out: dict = {}

        def runner():
            try:
                out["summary"] = drv.run()
            except BaseException as e:  # surfaced by the caller
                out["error"] = e

        th = threading.Thread(target=runner)
        th.start()
        return th, out

    def host_tcp(drv, r):
        with drv._lock:
            h = drv.hosts.get(r)
            addrs = dict(h.addresses) if h else {}
        for lbl, ad in addrs.items():
            if lbl.startswith("tcp"):
                return tuple(ad)
        return None

    def serve_leg(d, name, dscfg, *, feed=True):
        """One paced 2-host run; returns (driver, summary, sustained)."""
        sd = os.path.join(d, name)
        drv = DistServeDriver(
            os.path.join(d, "rules"),
            AnalysisConfig(
                batch_size=BATCH, prefetch_depth=0, mesh_shape="hybrid"
            ),
            ServeConfig(
                listen=("tcp:127.0.0.1:0",), window_lines=wl,
                serve_dir=sd, max_windows=windows, http="off",
                checkpoint_every_windows=0, reload_watch=False,
                queue_lines=1 << 18,
            ),
            dscfg,
        )
        th, out = run_driver(drv)
        wait_for(
            lambda: out.get("error")
            or all(host_tcp(drv, r) for r in range(n_hosts)),
            300, f"{name} host listeners",
        )
        if "error" in out:
            raise RuntimeError(f"failover: {name} leg failed: {out['error']}")
        t0 = time.perf_counter()
        senders = [
            threading.Thread(
                target=paced_send,
                args=(host_tcp(drv, r), host_stream[r], rate),
            )
            for r in range(n_hosts)
        ]
        for s in senders:
            s.start()
        for s in senders:
            s.join()
        if not feed:
            return drv, th, out, t0
        th.join(timeout=600)
        if th.is_alive() or "error" in out:
            raise RuntimeError(
                f"failover: {name} leg failed: {out.get('error')}"
            )
        last = os.path.join(sd, f"window-{windows - 1:06d}.json")
        sustained = total / max(os.path.getmtime(last) - wall0[name], 1e-3)
        summary = out["summary"]
        assert summary["drops"] == 0, f"{name} dropped {summary['drops']}"
        assert summary["windows_published"] == windows, summary
        return drv, summary, sustained

    wall0: dict = {}

    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "rules")
        pack_mod.save_packed(packed, prefix)
        warm_cfg = AnalysisConfig(batch_size=BATCH, prefetch_depth=0)
        run_stream(packed, iter(lines[:64]), warm_cfg)

        # ---- leg 1: bare (no lease, no spool) ----
        wall0["bare"] = time.time()
        _, bare_sum, bare_rate = serve_leg(
            d, "bare",
            DistServeConfig(
                hosts=n_hosts, workers="thread",
                lease_ttl_sec=0.0, spool_budget_mb=0,
            ),
        )
        log(f"failover: bare {bare_rate:,.0f} lines/s over {total} lines")

        # ---- leg 2: armed control (lease + spool, no chaos) ----
        wall0["control"] = time.time()
        _, ctl_sum, armed_rate = serve_leg(
            d, "control",
            DistServeConfig(
                hosts=n_hosts, workers="thread", lease_ttl_sec=ttl,
            ),
        )
        assert ctl_sum["term"] == 1
        overhead = max(0.0, 1.0 - armed_rate / bare_rate)
        log(
            f"failover: armed {armed_rate:,.0f} lines/s "
            f"({overhead:.2%} overhead vs bare)"
        )
        assert overhead < 0.02, (
            f"spool/lease armed overhead {overhead:.2%} >= 2% "
            f"(bare {bare_rate:,.0f} vs armed {armed_rate:,.0f} lines/s)"
        )

        # ---- leg 3: victim (full partition, then supervisor death) ----
        fo_dir = os.path.join(d, "failover")
        with faults.armed(faults.FaultPlan.parse("dist.epoch.ship@1:99999")):
            drv, th, out, _ = serve_leg(
                d, "failover",
                DistServeConfig(
                    hosts=n_hosts, workers="thread",
                    merge_timeout_sec=600, lease_ttl_sec=ttl,
                ),
                feed=False,
            )
            wait_for(
                lambda: out.get("error") or all(
                    drv.host_gauges().get(str(r), {}).get("spool_seq", 0)
                    >= windows
                    for r in range(n_hosts)
                ),
                300, "every epoch durably spooled",
            )
            assert drv.windows_published == 0  # term 1 published NOTHING
            drv.kill_supervisor()
            th.join(timeout=600)
            assert not th.is_alive(), "killed supervisor failed to die"
            err = out.get("error")
            assert isinstance(err, AnalysisError), err

        # ---- leg 4: successor (election + replay) ----
        t_takeover = time.perf_counter()
        succ = DistServeDriver(
            prefix,
            AnalysisConfig(
                batch_size=BATCH, prefetch_depth=0, mesh_shape="hybrid",
                resume=True,
            ),
            ServeConfig(
                listen=("tcp:127.0.0.1:0",), window_lines=wl,
                serve_dir=fo_dir, max_windows=windows, http="off",
                checkpoint_every_windows=0, reload_watch=False,
                queue_lines=1 << 18,
            ),
            DistServeConfig(
                hosts=n_hosts, workers="thread",
                merge_timeout_sec=600, lease_ttl_sec=ttl,
            ),
        )
        th, out = run_driver(succ)
        last = os.path.join(fo_dir, f"window-{windows - 1:06d}.json")
        wait_for(lambda: out.get("error") or os.path.exists(last),
                 300, "successor replay")
        takeover = time.perf_counter() - t_takeover
        th.join(timeout=600)
        if th.is_alive() or "error" in out:
            raise RuntimeError(
                f"failover: successor leg failed: {out.get('error')}"
            )
        s2 = out["summary"]
        assert takeover <= 2 * ttl, (
            f"takeover {takeover:.2f}s > 2x lease TTL ({2 * ttl:.1f}s)"
        )
        assert s2["term"] == 2
        assert s2["windows_published"] == windows, s2
        assert s2["lines_total"] == total, s2
        assert s2["drops"] == 0 and s2["skipped_windows"] == [], s2
        assert s2["failover"]["replay_windows"] == windows, s2["failover"]
        assert s2["failover"]["replay_refused"] == 0, s2["failover"]

        def lineage_identity(rec: dict) -> dict:
            # the replay-identity law (DESIGN §24): strip the volatile
            # fields AND the per-host payload_crc — epoch payloads carry
            # run-local wall stamps, so the byte CRC is only comparable
            # within one serve dir, never across the control/failover pair
            core = {k: v for k, v in rec.items() if k not in LINEAGE_VOLATILE}
            core["hosts"] = [
                {k: v for k, v in h.items() if k != "payload_crc"}
                for h in core["hosts"]
            ]
            return core

        identical = 0
        for w in range(windows):
            a = read_json(os.path.join(fo_dir, f"window-{w:06d}.json"))
            b = read_json(os.path.join(d, "control", f"window-{w:06d}.json"))
            # exactly one publisher per fencing term: the control's
            # windows all carry term 1, the successor's all term 2
            assert a["totals"]["window"]["term"] == 2, a["totals"]["window"]
            assert b["totals"]["window"]["term"] == 1, b["totals"]["window"]
            assert image(a) == image(b), (
                f"replayed window {w} diverged from the unkilled control"
            )
            assert a.get("talkers") == b.get("talkers"), (
                f"replayed window {w} talkers diverged"
            )
            # lineage replay identity: the replayed record is the SAME
            # deterministic function of the delivered lines, term/path
            # volatiles aside ("dist", every host's WAL range + drop
            # counts), and the successor stamps (term 2, path "replay")
            # against the control's (term 1, "live")
            la = a["totals"]["lineage"]
            lb = b["totals"]["lineage"]
            assert lineage_identity(la) == lineage_identity(lb), (
                f"replayed window {w} lineage core diverged"
            )
            assert la["kind"] == "dist" and len(la["hosts"]) == n_hosts
            assert (la["term"], la["path"]) == (2, "replay"), la
            assert (lb["term"], lb["path"]) == (1, "live"), lb
            identical += 1
        # the successor's ledger frontier is gapless and complete
        fr = lineage_frontier(
            LineageLog.read(os.path.join(fo_dir, LineageLog.NAME))
        )
        assert fr["windows"] >= windows and fr["gaps"] == [], fr
        assert fr["last_complete"] == windows - 1, fr
        assert fr["first_incomplete"] is None, fr
        cum_same = image(
            read_json(os.path.join(fo_dir, "cumulative.json"))
        ) == image(read_json(os.path.join(d, "control", "cumulative.json")))
        assert cum_same, "replayed cumulative diverged from the control"

    return {
        "bench": "failover",
        "metric": "failover_time_to_takeover_sec",
        "value": round(takeover, 3),
        "unit": "sec",
        "vs_baseline": round(takeover / (2 * ttl), 3),  # x the 2xTTL budget
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "hosts": n_hosts,
            "workers": "thread",
            "windows": windows,
            "lines_total": total,
            "offered_rate_per_host_lines_per_sec": rate,
            "lease_ttl_sec": ttl,
            "bare_sustained_lines_per_sec": round(bare_rate, 1),
            "armed_sustained_lines_per_sec": round(armed_rate, 1),
            "armed_overhead_frac": round(overhead, 4),
            "takeover_budget_sec": 2 * ttl,
            "epochs_replayed": s2["failover"]["spool_replayed"],
            "windows_replayed": s2["failover"]["replay_windows"],
            "windows_bit_identical": identical,
            "cumulative_bit_identical": cum_same,
            "victim_windows_published": 0,
            "terms": {"control": 1, "successor": 2},
            "method": (
                "both rate legs are paced identically at the same "
                "offered rate, so the armed-overhead fraction isolates "
                "per-window spool fsyncs + ttl/4 lease heartbeats; the "
                "victim leg parks every epoch behind an armed "
                "dist.epoch.ship partition so term 1 provably publishes "
                "nothing before the in-process supervisor death, and "
                "time-to-takeover clocks the successor from run() start "
                "to the last replayed window on disk (election + spool "
                "replay inclusive)"
            ),
            "guards": {
                "armed_overhead_lt_2pct": True,
                "takeover_le_2x_ttl": True,
                "bit_identical_all_windows": True,
                "talkers_identical": True,
                "cumulative_bit_identical": True,
                "zero_drops_all_legs": True,
                "zero_skipped_windows": True,
                "one_publisher_per_term": True,
                "victim_published_nothing": True,
                "lineage_replay_identity": True,
                "lineage_frontier_complete": True,
            },
        },
    }


def bench_lineage() -> dict:
    """Lineage plane + SLO burn-rate overhead & acceptance (DESIGN §24).

    Three legs, one solo-serve corpus, one process (shared jit caches):

    1. **Overhead pairs** — ``RA_LINEAGE_PAIRS`` (default 3) interleaved
       disarmed/armed runs: disarmed = ``--lineage off``, no ``--slo``,
       trends off; armed = lineage ledger + sealed records + a 2-objective
       SLO policy + trend plane.  Both legs are paced identically at
       ``RA_LINEAGE_RATE`` (default 8k lines/s — under the serve loop's
       measured 1-core capacity, the servesoak discipline); sustained =
       lines / (send start -> last window published), so the ratio
       isolates the armed plane's per-window cost (one canonical-JSON
       CRC + one O_APPEND write + burn-rate arithmetic) from load noise.
       Asserted in-bench: **median armed/disarmed sustained ratio >=
       0.98** (the provenance plane must not tax the hot path).
    2. **Ledger audit** — after the last armed run: every window file's
       ``totals.lineage`` equals the ledger record, every seal CRC
       re-verifies, and the frontier is gapless and complete.
    3. **Breach + recovery e2e** — a fresh armed run with
       ``drop_rate<=0.001``: one window with chaos-injected listener
       drops breaches (fast+slow burn over budget within the rotation),
       three clean windows recover.  Asserted in-bench: exactly one
       ``slo.breach`` and one ``slo.recovered`` transition (gauge
       counters), the breached window's lineage record carries the drop
       count, and the JSON /metrics SLO + build-info gauges agree with
       the prom exposition (flat, labeled, and ``ra_build_info``).
    """
    import os
    import socket
    import tempfile
    import threading

    import jax

    from ruleset_analysis_tpu.config import AnalysisConfig, ServeConfig
    from ruleset_analysis_tpu.hostside import aclparse, synth
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.runtime import faults
    from ruleset_analysis_tpu.runtime.report import (
        lineage_frontier, seal_lineage,
    )
    from ruleset_analysis_tpu.runtime.serve import ServeDriver
    from ruleset_analysis_tpu.runtime.stream import run_stream
    from ruleset_analysis_tpu.runtime.wal import LineageLog

    windows = 3
    pairs = int(os.environ.get("RA_LINEAGE_PAIRS", "3"))
    rate = float(os.environ.get("RA_LINEAGE_RATE", "8000"))
    wl = int(float(os.environ.get("RA_LINEAGE_LINES", "9000"))) // windows
    total = wl * windows
    BATCH = 4096
    SLO = "p99_publish_ms<=60000,drop_rate<=0.5"

    cfg_text = synth.synth_config(n_acls=2, rules_per_acl=10, seed=0)
    packed = pack_mod.pack_rulesets([aclparse.parse_asa_config(cfg_text, "fw1")])
    t = _tuples(packed, total, seed=29)
    lines = synth.render_syslog(packed, t, seed=29)

    def read_json(path):
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)

    def wait_for(pred, timeout, what):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return
            time.sleep(0.02)
        raise RuntimeError(f"lineage: timed out waiting for {what}")

    def run_serve(d, name, *, armed, feed_lines, wl, http="off", slo=None):
        sd = os.path.join(d, name)
        drv = ServeDriver(
            os.path.join(d, "rules"),
            AnalysisConfig(batch_size=BATCH, prefetch_depth=0),
            ServeConfig(
                listen=("tcp:127.0.0.1:0",), window_lines=wl,
                serve_dir=sd, max_windows=0, http=http,
                checkpoint_every_windows=0, reload_watch=False,
                queue_lines=1 << 18,
                lineage=armed,
                slo=(SLO if slo is None else slo) if armed else "",
                trend_threshold=4.0 if armed else 0.0,
            ),
        )
        out: dict = {}

        def runner():
            try:
                out["summary"] = drv.run()
            except BaseException as e:
                out["error"] = e

        th = threading.Thread(target=runner)
        th.start()
        wait_for(
            lambda: out.get("error") or (
                drv.listeners.listeners and drv.listeners.alive()
                and (http == "off" or drv.http_address)
            ),
            60, f"{name} listener",
        )
        if "error" in out:
            raise RuntimeError(f"lineage: {name} failed: {out['error']}")
        addr = tuple(drv.listeners.listeners[0].address)
        t0 = time.perf_counter()
        s = socket.create_connection(addr)
        # paced replay (the servesoak discipline): bursts of 500 lines
        # against the wall clock, so both legs see the same offered rate
        sent = 0
        for i in range(0, len(feed_lines), 500):
            burst = feed_lines[i:i + 500]
            s.sendall(("\n".join(burst) + "\n").encode())
            sent += len(burst)
            lag = sent / rate - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
        s.close()
        want = len(feed_lines) // wl
        wait_for(
            lambda: out.get("error") or drv.windows_published >= want,
            300, f"{name} windows",
        )
        if "error" in out:
            raise RuntimeError(f"lineage: {name} failed: {out['error']}")
        sustained = len(feed_lines) / max(time.perf_counter() - t0, 1e-6)
        return drv, th, out, sustained

    def stop(drv, th, out):
        drv.stop()
        th.join(timeout=120)
        if th.is_alive():
            raise RuntimeError("lineage: serve failed to stop")
        if "error" in out:
            raise RuntimeError(f"lineage: {out['error']}")
        return out["summary"]

    with tempfile.TemporaryDirectory() as d:
        pack_mod.save_packed(packed, os.path.join(d, "rules"))
        run_stream(
            packed, iter(lines[:64]),
            AnalysisConfig(batch_size=BATCH, prefetch_depth=0),
        )

        # ---- leg 1: interleaved disarmed/armed overhead pairs ----
        ratios = []
        rates: dict = {"disarmed": [], "armed": []}
        last_armed_dir = None
        for i in range(pairs):
            _d, th, out, off_rate = run_serve(
                d, f"off-{i}", armed=False, feed_lines=lines, wl=wl,
            )
            soff = stop(_d, th, out)
            assert soff["drops"] == 0
            _a, th, out, on_rate = run_serve(
                d, f"on-{i}", armed=True, feed_lines=lines, wl=wl,
            )
            son = stop(_a, th, out)
            assert son["drops"] == 0
            last_armed_dir = os.path.join(d, f"on-{i}")
            rates["disarmed"].append(round(off_rate, 1))
            rates["armed"].append(round(on_rate, 1))
            ratios.append(on_rate / off_rate)
            log(
                f"lineage: pair {i}: disarmed {off_rate:,.0f} vs armed "
                f"{on_rate:,.0f} lines/s (ratio {ratios[-1]:.4f})"
            )
        med_ratio = sorted(ratios)[len(ratios) // 2]
        assert med_ratio >= 0.98, (
            f"lineage/SLO armed plane costs too much: median sustained "
            f"ratio {med_ratio:.4f} < 0.98 ({ratios})"
        )

        # ---- leg 2: ledger audit on the last armed run ----
        ledger = LineageLog.read(os.path.join(last_armed_dir, LineageLog.NAME))
        assert len(ledger) == windows, f"ledger holds {len(ledger)} records"
        for w in range(windows):
            rep = read_json(
                os.path.join(last_armed_dir, f"window-{w:06d}.json")
            )
            lin = rep["totals"]["lineage"]
            assert lin == ledger[w], f"window {w} record drifted"
            assert seal_lineage(dict(lin))["crc"] == lin["crc"]
            assert lin["path"] == "live" and "incomplete" not in lin
        fr = lineage_frontier(ledger)
        assert fr["last_complete"] == windows - 1
        assert fr["first_incomplete"] is None and fr["gaps"] == []

        # ---- leg 3: provoked breach + recovery, JSON<->prom parity ----
        import urllib.request

        drv, th, out, _rate = run_serve(
            d, "breach", armed=True, feed_lines=lines[:wl], wl=wl,
            http="127.0.0.1:0", slo="drop_rate<=0.001",
        )
        try:
            addr = tuple(drv.listeners.listeners[0].address)
            wait_for(
                lambda: out.get("error") or drv.slo.windows_observed >= 1,
                60, "clean window observed",
            )
            # one window with 200 chaos-dropped lines: drop_rate ~6%
            # >> 0.001 -> fast AND slow burn cross in one rotation
            with faults.armed(faults.FaultPlan.parse("listener.drop@1:200")):
                s = socket.create_connection(addr)
                s.sendall(
                    ("\n".join(lines[wl:2 * wl + 200]) + "\n").encode()
                )
                s.close()
                wait_for(
                    lambda: out.get("error")
                    or drv.slo.windows_observed >= 2,
                    300, "breach window",
                )
            if "error" in out:
                raise RuntimeError(f"lineage: {out['error']}")
            assert drv.slo.breaches_total == 1, drv.slo.gauges()
            assert drv.slo.gauges()["slo_breached"] == 1
            breach_rec = drv.lineage_record(1)
            assert breach_rec["hosts"][0]["drops"] == 200, breach_rec
            # three clean windows: burn_fast falls under 1 -> recovery
            for w in range(3):
                s = socket.create_connection(addr)
                s.sendall(("\n".join(lines[:wl]) + "\n").encode())
                s.close()
                wait_for(
                    lambda: out.get("error")
                    or drv.slo.windows_observed >= 3 + w,
                    300, f"recovery window {w}",
                )
            assert drv.slo.recoveries_total == 1, drv.slo.gauges()
            assert drv.slo.gauges()["slo_breached"] == 0

            host, port = drv.http_address
            with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10
            ) as r:
                mjson = json.load(r)
            with urllib.request.urlopen(
                f"http://{host}:{port}/metrics?format=prom", timeout=10
            ) as r:
                prom = r.read().decode()
            prom_vals = {}
            for line in prom.splitlines():
                if line and not line.startswith("#") and " " in line:
                    k, v = line.rsplit(" ", 1)
                    try:
                        prom_vals[k] = float(v)
                    except ValueError:
                        pass
            slo_keys = [k for k in mjson if k.startswith("slo_")]
            assert slo_keys, "no SLO gauges on /metrics"
            for k in slo_keys:
                assert prom_vals.get(f"ra_serve_{k}") == float(mjson[k]), (
                    f"JSON<->prom drift on {k}: "
                    f"{mjson[k]} vs {prom_vals.get(f'ra_serve_{k}')}"
                )
            assert prom_vals.get("ra_serve_lineage_records_total") == float(
                mjson["lineage_records_total"]
            )
            for lk, lv in drv.slo.labeled_gauges()["drop_rate"].items():
                assert (
                    prom_vals[f'ra_serve_{lk}{{objective="drop_rate"}}']
                    == float(lv)
                ), f"labeled drift on {lk}"
            bi = mjson["build_info"]
            assert "ra_build_info{" in prom
            for k, v in bi.items():
                assert f'{k}="{v}"' in prom, f"build_info label {k} missing"
        finally:
            stop(drv, th, out)

    return {
        "bench": "lineage",
        "metric": "lineage_armed_sustained_ratio",
        "value": round(med_ratio, 4),
        "unit": "ratio",
        "vs_baseline": round(med_ratio / 0.98, 4),  # x the floor
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "pairs": pairs,
            "windows_per_run": windows,
            "lines_per_run": total,
            "offered_rate_lines_per_sec": rate,
            "slo_policy": SLO,
            "disarmed_sustained_lines_per_sec": rates["disarmed"],
            "armed_sustained_lines_per_sec": rates["armed"],
            "sustained_ratios": [round(r, 4) for r in ratios],
            "ledger_records_audited": windows,
            "breach_drop_lines": 200,
            "breaches_total": 1,
            "recoveries_total": 1,
            "method": (
                "interleaved disarmed/armed pairs replay the same corpus "
                "through one solo serve process, paced identically at the "
                "offered rate (sustained = lines / send-start->last-"
                "window, so the ratio isolates the armed plane's "
                "per-window cost from load noise); armed adds the sealed "
                "lineage ledger, a 2-objective SLO burn-rate engine, and "
                "the trend plane.  The breach leg serves under a "
                "drop_rate<=0.001 policy and injects exactly 200 "
                "listener.drop chaos hits into one window (drop_rate "
                "~6% >> bound; fast+slow burn cross in one rotation), "
                "then feeds three clean windows for the recovery "
                "transition; /metrics JSON gauges are compared "
                "numerically against the prom exposition (flat, "
                "objective-labeled, and ra_build_info)"
            ),
            "guards": {
                "median_ratio_ge_0_98": True,
                "ledger_equals_window_files": True,
                "seal_crcs_verify": True,
                "frontier_gapless": True,
                "one_breach_one_recovery": True,
                "breach_window_lineage_names_drops": True,
                "json_prom_slo_parity": True,
                "json_prom_build_info_parity": True,
            },
        },
    }


def bench_epochstore() -> dict:
    """Durable epoch store (DESIGN §25): query speedup, spill tax, crash.

    Three legs, the ISSUE 20 acceptance artifact:

    1. **Range-query speedup** — ``RA_EPOCHSTORE_EPOCHS`` (default 512)
       synthetic epochs spilled through the production spill/compact
       path, then random ``[t0,t1]`` queries spanning >= 256 epochs
       answered twice: the segment-tree decomposition (<= 2 log n stored
       aggregates + one merge fold) vs the naive linear L0 fold.
       Asserted in-bench: **median speedup >= 10x** and every query pair
       **bit-identical** (registers, tracker tables, accounting).
       Compaction throughput (spills/s through the binary-counter
       promote) rides along in the detail.
    2. **Spill overhead pairs** — ``RA_EPOCHSTORE_PAIRS`` (default 3)
       interleaved disarmed/armed solo-serve runs over one corpus, paced
       identically at ``RA_EPOCHSTORE_RATE`` (default 8k lines/s, the
       servesoak discipline); armed = ``--epoch-store`` spilling every
       rotation.  Asserted in-bench: **median armed/disarmed sustained
       ratio >= 0.98**, and the last armed run's ``/report/range`` over
       the full span answers complete with the corpus line total.
    3. **Compaction crash** — a child process spills epochs under an
       armed ``epochstore.compact`` crash plan (os._exit at the worst
       instant: pair chosen, merged node unwritten).  Asserted in-bench:
       the reopened store is readable, holds **every epoch whose spill
       started** (zero lost), repair restores the level invariant, and
       the full-span tree fold still equals the linear fold bit for bit.
    """
    import os
    import socket
    import subprocess
    import tempfile
    import textwrap
    import threading
    import urllib.request

    import jax
    import numpy as np

    from ruleset_analysis_tpu.config import AnalysisConfig, ServeConfig
    from ruleset_analysis_tpu.hostside import aclparse, synth
    from ruleset_analysis_tpu.hostside import pack as pack_mod
    from ruleset_analysis_tpu.runtime import epochstore
    from ruleset_analysis_tpu.runtime.serve import ServeDriver
    from ruleset_analysis_tpu.runtime.stream import run_stream

    n_epochs = int(os.environ.get("RA_EPOCHSTORE_EPOCHS", "512"))
    assert n_epochs >= 512, "leg 1 needs >= 512 epochs for 256-wide spans"
    n_queries = 16
    pairs = int(os.environ.get("RA_EPOCHSTORE_PAIRS", "3"))
    rate = float(os.environ.get("RA_EPOCHSTORE_RATE", "8000"))
    windows = 3
    wl = int(float(os.environ.get("RA_EPOCHSTORE_LINES", "9000"))) // windows
    BATCH = 4096

    def synth_epoch(wid: int):
        rng = np.random.default_rng(wid)

        class _Ep:
            arrays = {
                "counts_lo": rng.integers(0, 2**32, 1024, dtype=np.uint32),
                "counts_hi": rng.integers(0, 3, 1024, dtype=np.uint32),
                "cms": rng.integers(0, 2**32, (4, 1024), dtype=np.uint32),
                "hll": rng.integers(0, 30, (256, 8), dtype=np.uint32),
                "talk_cms": rng.integers(
                    0, 2**32, (4, 1024), dtype=np.uint32
                ),
            }
            meta = {
                "id": wid, "lines": 1000 + wid, "parsed": 990,
                "skipped": 10, "chunks": 2, "drops": 0,
                "started_unix": 10.0 + wid, "ended_unix": 11.0 + wid,
            }
            tracker_tables = {
                int(a): {
                    int(s): int(e) for s, e in zip(
                        rng.integers(0, 2**32, 8),
                        rng.integers(1, 10_000, 8),
                    )
                } for a in range(2)
            }
            quarantine = {}

        return _Ep()

    def agg_equal(a, b):
        return (
            all(np.array_equal(a.arrays[k], b.arrays[k]) for k in a.arrays)
            and a.tables == b.tables and a.summary == b.summary
            and a.quarantine == b.quarantine
        )

    results: dict = {}
    with tempfile.TemporaryDirectory() as d:
        # ---- leg 1: tree vs naive fold over a 512-epoch store ----
        store = epochstore.EpochStore(
            os.path.join(d, "estore"), budget_bytes=256 << 20
        )
        store.bind_base(0)
        t0 = time.perf_counter()
        for wid in range(n_epochs):
            store.spill(synth_epoch(wid))
        build_sec = time.perf_counter() - t0
        rng = np.random.default_rng(7)
        speedups, q_tree_ms, q_naive_ms = [], [], []
        for _ in range(n_queries):
            span = int(rng.integers(256, n_epochs))
            lo = int(rng.integers(0, n_epochs - span))
            hi = lo + span - 1
            t1 = time.perf_counter()
            agg, marker = store.range_agg(lo, hi)
            t2 = time.perf_counter()
            ref, nmarker = store.naive_range_agg(lo, hi)
            t3 = time.perf_counter()
            assert marker is None and nmarker is None, (marker, nmarker)
            assert agg_equal(agg, ref), f"fold drift on [{lo},{hi}]"
            q_tree_ms.append((t2 - t1) * 1e3)
            q_naive_ms.append((t3 - t2) * 1e3)
            speedups.append((t3 - t2) / max(t2 - t1, 1e-9))
        med_speedup = sorted(speedups)[len(speedups) // 2]
        assert med_speedup >= 10.0, (
            f"segment-tree range query only {med_speedup:.1f}x over the "
            f"naive linear fold (need >= 10x): {speedups}"
        )
        depth = store.stats()["depth"]
        store.close()
        log(
            f"epochstore: {n_epochs} epochs, depth {depth}: median query "
            f"{sorted(q_tree_ms)[n_queries // 2]:.2f} ms vs naive "
            f"{sorted(q_naive_ms)[n_queries // 2]:.1f} ms "
            f"({med_speedup:.1f}x); build {n_epochs / build_sec:,.0f} "
            f"spills/s"
        )
        results["leg1"] = {
            "epochs": n_epochs,
            "tree_depth": depth,
            "queries": n_queries,
            "query_tree_ms": [round(x, 3) for x in sorted(q_tree_ms)],
            "query_naive_ms": [round(x, 2) for x in sorted(q_naive_ms)],
            "median_speedup": round(med_speedup, 1),
            "compaction_spills_per_sec": round(n_epochs / build_sec, 1),
        }

        # ---- leg 2: spill-armed vs disarmed serve pairs ----
        cfg_text = synth.synth_config(n_acls=2, rules_per_acl=10, seed=0)
        packed = pack_mod.pack_rulesets(
            [aclparse.parse_asa_config(cfg_text, "fw1")]
        )
        t = _tuples(packed, wl * windows, seed=31)
        lines = synth.render_syslog(packed, t, seed=31)
        pack_mod.save_packed(packed, os.path.join(d, "rules"))
        run_stream(
            packed, iter(lines[:64]),
            AnalysisConfig(batch_size=BATCH, prefetch_depth=0),
        )

        def wait_for(pred, timeout, what):
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if pred():
                    return
                time.sleep(0.02)
            raise RuntimeError(f"epochstore: timed out waiting for {what}")

        def run_serve(name, *, armed, http="off"):
            sd = os.path.join(d, name)
            drv = ServeDriver(
                os.path.join(d, "rules"),
                AnalysisConfig(batch_size=BATCH, prefetch_depth=0),
                ServeConfig(
                    listen=("tcp:127.0.0.1:0",), window_lines=wl,
                    serve_dir=sd, max_windows=0, http=http,
                    checkpoint_every_windows=0, reload_watch=False,
                    queue_lines=1 << 18,
                    epoch_store=(
                        os.path.join(sd, "estore") if armed else ""
                    ),
                ),
            )
            out: dict = {}

            def runner():
                try:
                    out["summary"] = drv.run()
                except BaseException as e:
                    out["error"] = e

            th = threading.Thread(target=runner)
            th.start()
            wait_for(
                lambda: out.get("error") or (
                    drv.listeners.listeners and drv.listeners.alive()
                    and (http == "off" or drv.http_address)
                ),
                60, f"{name} listener",
            )
            if "error" in out:
                raise RuntimeError(f"epochstore: {name}: {out['error']}")
            addr = tuple(drv.listeners.listeners[0].address)
            t0 = time.perf_counter()
            s = socket.create_connection(addr)
            sent = 0
            for i in range(0, len(lines), 500):
                burst = lines[i:i + 500]
                s.sendall(("\n".join(burst) + "\n").encode())
                sent += len(burst)
                lag = sent / rate - (time.perf_counter() - t0)
                if lag > 0:
                    time.sleep(lag)
            s.close()
            wait_for(
                lambda: out.get("error")
                or drv.windows_published >= windows,
                300, f"{name} windows",
            )
            if "error" in out:
                raise RuntimeError(f"epochstore: {name}: {out['error']}")
            sustained = len(lines) / max(time.perf_counter() - t0, 1e-6)
            return drv, th, out, sustained

        def stop(drv, th, out):
            drv.stop()
            th.join(timeout=120)
            if th.is_alive():
                raise RuntimeError("epochstore: serve failed to stop")
            if "error" in out:
                raise RuntimeError(f"epochstore: {out['error']}")
            return out["summary"]

        ratios = []
        rates: dict = {"disarmed": [], "armed": []}
        for i in range(pairs):
            last = i == pairs - 1
            drv, th, out, off_rate = run_serve(f"off-{i}", armed=False)
            soff = stop(drv, th, out)
            assert soff["drops"] == 0
            drv, th, out, on_rate = run_serve(
                f"on-{i}", armed=True,
                http="127.0.0.1:0" if last else "off",
            )
            if last:
                # the armed plane must ANSWER, not just keep up: the
                # full-span range report equals the corpus totals
                host, port = drv.http_address
                with urllib.request.urlopen(
                    f"http://{host}:{port}/report/range?from=0"
                    f"&to={windows - 1}", timeout=10,
                ) as r:
                    rng_rep = json.load(r)
                assert "range_incomplete" not in rng_rep, rng_rep
                tot = rng_rep["totals"]
                assert tot["lines_total"] == len(lines), tot
                assert tot["window"]["windows"] == windows, tot
            son = stop(drv, th, out)
            assert son["drops"] == 0
            assert son["epoch_store"]["epochs"] == windows, (
                son["epoch_store"]
            )
            rates["disarmed"].append(round(off_rate, 1))
            rates["armed"].append(round(on_rate, 1))
            ratios.append(on_rate / off_rate)
            log(
                f"epochstore: pair {i}: disarmed {off_rate:,.0f} vs "
                f"armed {on_rate:,.0f} lines/s (ratio {ratios[-1]:.4f})"
            )
        med_ratio = sorted(ratios)[len(ratios) // 2]
        assert med_ratio >= 0.98, (
            f"epoch-store spill taxes the hot path: median sustained "
            f"ratio {med_ratio:.4f} < 0.98 ({ratios})"
        )
        results["leg2"] = {
            "pairs": pairs,
            "windows_per_run": windows,
            "lines_per_run": len(lines),
            "offered_rate_lines_per_sec": rate,
            "disarmed_sustained_lines_per_sec": rates["disarmed"],
            "armed_sustained_lines_per_sec": rates["armed"],
            "sustained_ratios": [round(r, 4) for r in ratios],
            "median_ratio": round(med_ratio, 4),
        }

        # ---- leg 3: crash mid-compaction, reopen, zero lost epochs ----
        crash_dir = os.path.join(d, "crash-estore")
        child = textwrap.dedent("""
            import sys
            import numpy as np
            from ruleset_analysis_tpu.runtime import epochstore

            store = epochstore.EpochStore(sys.argv[1])
            store.bind_base(0)
            for wid in range(32):
                rng = np.random.default_rng(wid)

                class _Ep:
                    arrays = {
                        "counts_lo": rng.integers(
                            0, 2**32, 64, dtype=np.uint32),
                        "counts_hi": np.zeros(64, dtype=np.uint32),
                        "cms": rng.integers(
                            0, 2**32, (2, 64), dtype=np.uint32),
                        "hll": rng.integers(
                            0, 30, (32, 4), dtype=np.uint32),
                        "talk_cms": rng.integers(
                            0, 2**32, (2, 64), dtype=np.uint32),
                    }
                    meta = {
                        "id": wid, "lines": 100, "parsed": 100,
                        "skipped": 0, "chunks": 1, "drops": 0,
                        "started_unix": 1.0 + wid,
                        "ended_unix": 2.0 + wid,
                    }
                    tracker_tables = {0: {wid: wid + 1}}
                    quarantine = {}

                store.spill(_Ep())
                print(wid, flush=True)
        """)
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            RA_FAULT_PLAN="epochstore.compact@6",
        )
        proc = subprocess.run(
            [sys.executable, "-c", child, crash_dir],
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        )
        assert proc.returncode != 0, "crash plan never fired"
        done = [int(x) for x in proc.stdout.split()]
        assert done, f"child crashed before any spill: {proc.stderr[-500:]}"
        survivor = epochstore.EpochStore(crash_dir)
        st = survivor.stats()
        # the crash fires INSIDE a spill's promote, after its L0 append:
        # every started spill is on disk — completed prints + the victim
        assert st["epochs"] == len(done) + 1, (st, done)
        hi = st["epochs"] - 1
        agg, marker = survivor.range_agg(0, hi)
        ref, nmarker = survivor.naive_range_agg(0, hi)
        assert marker is None and nmarker is None, (marker, nmarker)
        assert agg_equal(agg, ref), "post-crash fold drift"
        assert agg.summary["windows"] == st["epochs"]
        survivor.close()
        log(
            f"epochstore: crash leg: {len(done)} spills acked, "
            f"{st['epochs']} epochs survive, repair ok, fold identical"
        )
        results["leg3"] = {
            "spills_acked_before_crash": len(done),
            "epochs_after_reopen": st["epochs"],
            "fault_plan": "epochstore.compact@6",
            "holes_after_repair": st["holes_total"],
        }

    return {
        "bench": "epochstore",
        "metric": "range_query_median_speedup",
        "value": results["leg1"]["median_speedup"],
        "unit": "x_vs_naive_fold",
        "vs_baseline": round(results["leg1"]["median_speedup"] / 10.0, 2),
        "detail": {
            "platform": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            **results["leg1"],
            "spill_overhead": results["leg2"],
            "crash": results["leg3"],
            "method": (
                "leg 1 spills synthetic epochs through the production "
                "spill/compact path and answers random >=256-wide "
                "ranges twice — the segment-tree decomposition vs the "
                "naive linear L0 fold — timing both and comparing "
                "registers, tracker tables, and accounting bit for bit; "
                "leg 2 interleaves disarmed/armed paced solo-serve "
                "runs over one corpus (sustained = lines / send-start->"
                "last-window) and reads /report/range over the full "
                "span from the last armed run; leg 3 crashes a child "
                "process mid-compaction (pair chosen, merged node "
                "unwritten) and reopens the store"
            ),
            "guards": {
                "median_speedup_ge_10x": True,
                "tree_fold_bit_identical_to_naive": True,
                "median_armed_ratio_ge_0_98": True,
                "range_report_complete_over_full_span": True,
                "zero_drops_both_legs": True,
                "crash_store_readable": True,
                "zero_lost_epochs_after_crash": True,
                "post_crash_fold_bit_identical": True,
            },
        },
    }


BENCHES = {
    "stage": bench_stage,
    "exact": bench_exact,
    "cms": bench_cms,
    "hll": bench_hll,
    "multifw": bench_multifw,
    "topk": bench_topk,
    "pallas": bench_pallas,
    "recall": bench_recall,
    "e2e": bench_e2e,
    "sustained": bench_sustained,
    "servesoak": bench_servesoak,
    "autoscale": bench_autoscale,
    "obs": bench_obs,
    "steptrace": bench_steptrace,
    "stepvariants": bench_stepvariants,
    "coalesce": bench_coalesce,
    "convert": bench_convert,
    "feedscale": bench_feedscale,
    "rulescale": bench_rulescale,
    "retrysoak": bench_retrysoak,
    "blackbox": bench_blackbox,
    "tenant": bench_tenant,
    "servescale": bench_servescale,
    "failover": bench_failover,
    "lineage": bench_lineage,
    "epochstore": bench_epochstore,
    "v6": bench_v6,
    "v6recall": bench_v6recall,
}


#: a bare `python bench_suite.py` runs these; `sustained` (≥1e8 lines —
#: minutes of wall time by design), `servesoak` and `autoscale` (paced
#: live-service soaks with sockets + threads), `feedscale` (worker
#: fleets of spawned processes), `tenant` (17 full serve drivers
#: with live sockets), `servescale` (three paced multi-process
#: distributed-serve soaks), `failover` (four paced supervisor
#: kill/election soaks), `lineage` (live-socket lineage/SLO
#: overhead + breach soaks) and `epochstore` (512-epoch store build +
#: paced serve pairs + a crash child) are explicit-only
DEFAULT_BENCHES = [
    n for n in BENCHES
    if n not in ("sustained", "servesoak", "autoscale", "feedscale",
                 "retrysoak", "blackbox", "tenant", "servescale",
                 "failover", "lineage", "epochstore")
]


def main(argv: list[str]) -> int:
    from ruleset_analysis_tpu.runtime.compcache import enable_persistent_cache

    log(f"compilation cache: {enable_persistent_cache()}")
    names = argv or DEFAULT_BENCHES
    for name in names:
        if name not in BENCHES:
            log(f"unknown bench {name!r}; choices: {list(BENCHES)}")
            return 2
        log(f"=== {name} ===")
        t0 = time.perf_counter()
        result = BENCHES[name]()
        result["detail"]["bench_wall_sec"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
