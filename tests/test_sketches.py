"""Sketch property tests (SURVEY.md §5): CMS one-sided error, HLL accuracy,
clz exactness, 64-bit carry, merge laws (sum/max) that scale-out relies on."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ruleset_analysis_tpu.ops import cms as cms_ops
from ruleset_analysis_tpu.ops import counts as count_ops
from ruleset_analysis_tpu.ops import hashing
from ruleset_analysis_tpu.ops import hll as hll_ops
from ruleset_analysis_tpu.ops import topk as topk_ops


def test_clz32_exact():
    xs = np.array([0, 1, 2, 3, 4, 255, 256, 2**16 - 1, 2**16, 2**31, 2**32 - 1], dtype=np.uint32)
    got = np.asarray(hashing.clz32(jnp.asarray(xs)))
    exp = np.array([32 - int(x).bit_length() for x in xs], dtype=np.uint32)
    np.testing.assert_array_equal(got, exp)


def test_fmix32_avalanche():
    """Flipping one input bit should flip ~half the output bits on average."""
    rng = np.random.default_rng(0)
    x = rng.integers(0, 2**32, size=2000, dtype=np.uint32)
    h0 = np.asarray(hashing.fmix32(jnp.asarray(x)))
    h1 = np.asarray(hashing.fmix32(jnp.asarray(x ^ np.uint32(1))))
    flips = np.unpackbits((h0 ^ h1).view(np.uint8)).mean() * 32
    assert 14 < flips < 18


def test_add64_carry():
    lo = jnp.asarray(np.array([0xFFFFFFFF, 0xFFFFFFFE, 5], dtype=np.uint32))
    hi = jnp.asarray(np.array([0, 7, 1], dtype=np.uint32))
    delta = jnp.asarray(np.array([1, 1, 0], dtype=np.uint32))
    nlo, nhi = count_ops.add64(lo, hi, delta)
    total = count_ops.to_u64(np.asarray(nlo), np.asarray(nhi))
    np.testing.assert_array_equal(
        total, np.array([1 << 32, (7 << 32) + 0xFFFFFFFF, (1 << 32) + 5], dtype=np.uint64)
    )


def _random_stream(n, n_keys, seed, zipf=1.3):
    rng = np.random.default_rng(seed)
    keys = rng.zipf(zipf, size=n).astype(np.uint32) % n_keys
    return keys


def test_cms_one_sided_and_bounded():
    n, n_keys, width, depth = 20000, 500, 1 << 10, 4
    keys = _random_stream(n, n_keys, seed=1)
    true = np.bincount(keys, minlength=n_keys)

    sk = cms_ops.cms_init(width, depth)
    sk = cms_ops.cms_update(sk, jnp.asarray(keys), jnp.ones(n, dtype=jnp.uint32))
    est = np.asarray(cms_ops.cms_query(sk, jnp.asarray(np.arange(n_keys, dtype=np.uint32))))

    assert (est >= true).all(), "CMS must never underestimate"
    # error <= e*N/width with prob 1-exp(-depth); allow 3x slack for a seeded test
    bound = 3 * np.e * n / width
    assert (est - true).max() <= bound
    # numpy query path agrees with device query path
    est_np = cms_ops.cms_query_np(np.asarray(sk), np.arange(n_keys, dtype=np.uint32))
    np.testing.assert_array_equal(est, est_np)


def test_cms_merge_is_sum():
    keys = _random_stream(5000, 200, seed=2)
    half = len(keys) // 2
    ones = jnp.ones(half, dtype=jnp.uint32)
    a = cms_ops.cms_update(cms_ops.cms_init(1 << 9, 3), jnp.asarray(keys[:half]), ones)
    b = cms_ops.cms_update(cms_ops.cms_init(1 << 9, 3), jnp.asarray(keys[half:]), ones)
    whole = cms_ops.cms_update(
        cms_ops.cms_init(1 << 9, 3), jnp.asarray(keys), jnp.ones(len(keys), dtype=jnp.uint32)
    )
    np.testing.assert_array_equal(np.asarray(a) + np.asarray(b), np.asarray(whole))


def test_cms_weights_respected():
    sk = cms_ops.cms_init(1 << 8, 2)
    keys = jnp.asarray(np.array([7, 7, 9], dtype=np.uint32))
    w = jnp.asarray(np.array([1, 0, 1], dtype=np.uint32))  # middle line invalid
    sk = cms_ops.cms_update(sk, keys, w)
    est = np.asarray(cms_ops.cms_query(sk, jnp.asarray(np.array([7, 9], dtype=np.uint32))))
    assert est[0] == 1 and est[1] == 1


def test_hll_accuracy():
    n_keys, p = 8, 10  # m=1024 -> ~3.2% typical error
    true_cards = [1, 10, 100, 1000, 5000, 20000, 0, 3]
    keys_list, vals_list = [], []
    rng = np.random.default_rng(3)
    for k, c in enumerate(true_cards):
        if c == 0:
            continue
        vals = rng.choice(2**32, size=c, replace=False).astype(np.uint32)
        # each unique value appears 1-3 times
        reps = rng.integers(1, 4, size=c)
        keys_list.append(np.full(int(reps.sum()), k, dtype=np.uint32))
        vals_list.append(np.repeat(vals, reps))
    keys = np.concatenate(keys_list)
    vals = np.concatenate(vals_list)
    perm = rng.permutation(len(keys))
    keys, vals = keys[perm], vals[perm]

    hll = hll_ops.hll_init(n_keys, p)
    hll = hll_ops.hll_update(
        hll, jnp.asarray(keys), jnp.asarray(vals), jnp.ones(len(keys), dtype=jnp.uint32)
    )
    est = hll_ops.hll_estimate_np(np.asarray(hll))
    for k, c in enumerate(true_cards):
        if c == 0:
            assert est[k] == 0
        else:
            assert abs(est[k] - c) / c < 0.15, (k, c, est[k])


def test_hll_merge_is_max_and_order_invariant():
    rng = np.random.default_rng(4)
    vals = rng.integers(0, 2**32, size=4000, dtype=np.uint32)
    keys = rng.integers(0, 4, size=4000).astype(np.uint32)
    ones = jnp.ones(2000, dtype=jnp.uint32)
    a = hll_ops.hll_update(hll_ops.hll_init(4, 6), jnp.asarray(keys[:2000]), jnp.asarray(vals[:2000]), ones)
    b = hll_ops.hll_update(hll_ops.hll_init(4, 6), jnp.asarray(keys[2000:]), jnp.asarray(vals[2000:]), ones)
    whole = hll_ops.hll_update(
        hll_ops.hll_init(4, 6), jnp.asarray(keys), jnp.asarray(vals), jnp.ones(4000, dtype=jnp.uint32)
    )
    np.testing.assert_array_equal(np.maximum(np.asarray(a), np.asarray(b)), np.asarray(whole))


def test_hll_invalid_lines_are_identity():
    hll0 = hll_ops.hll_init(2, 6)
    keys = jnp.asarray(np.array([0, 1], dtype=np.uint32))
    vals = jnp.asarray(np.array([123, 456], dtype=np.uint32))
    out = hll_ops.hll_update(hll0, keys, vals, jnp.zeros(2, dtype=jnp.uint32))
    assert (np.asarray(out) == 0).all()


def test_topk_tracker_finds_heavy_hitters():
    rng = np.random.default_rng(5)
    # skewed stream over one ACL: talker i has weight ~ 1/i
    n = 30000
    srcs = rng.zipf(1.5, size=n).astype(np.uint32) % 1000
    acls = np.zeros(n, dtype=np.uint32)
    true = np.bincount(srcs, minlength=1000)
    true_top = set(np.argsort(true)[-5:])

    sk = cms_ops.cms_init(1 << 12, 4)
    tracker = topk_ops.TopKTracker(capacity=64)
    for i in range(0, n, 4096):
        a = jnp.asarray(acls[i : i + 4096])
        s = jnp.asarray(srcs[i : i + 4096])
        v = jnp.ones(a.shape[0], dtype=jnp.uint32)
        sk, ca, cs, ce = topk_ops.talker_chunk_update(sk, a, s, v, 32)
        tracker.offer_chunk(np.asarray(ca), np.asarray(cs), np.asarray(ce))

    got_top = {src for src, _ in tracker.top(0, 5)}
    assert len(true_top & got_top) >= 4  # at least 4/5 of true heavy hitters


def test_topk_salt_breaks_persistent_slot_collisions():
    """Two (acl, src) pairs sharing a candidate slot under one salt must
    land in different slots under (almost always) another salt, so a
    talker can't be starved forever; same salt stays deterministic."""
    # find two pairs colliding under salt=0 on host
    srcs = np.arange(200000, dtype=np.uint32)
    pair = np.asarray(topk_ops.hash_pair(jnp.zeros_like(jnp.asarray(srcs)), jnp.asarray(srcs)))
    slot0 = np.asarray(
        topk_ops.fmix32(jnp.asarray(pair) ^ jnp.uint32(0))
    ) & (topk_ops.CAND_SLOTS - 1)
    order = np.argsort(slot0, kind="stable")
    dup = np.nonzero(slot0[order][1:] == slot0[order][:-1])[0]
    assert dup.size > 0, "no collision found in probe range"
    a, b = srcs[order][dup[0]], srcs[order][dup[0] + 1]

    # X (=b) and H (=a) share a slot; the slot representative is the max
    # line index, so ordering H's lines first guarantees X holds the slot
    # at salt=0 and H is suppressed
    batch_src = np.concatenate([
        np.full(100, a, np.uint32), np.full(1000, b, np.uint32),
        np.arange(1000, dtype=np.uint32) + 1_000_000,
    ])
    acl = np.zeros(batch_src.size, dtype=np.uint32)
    v = np.ones(batch_src.size, dtype=np.uint32)
    sk = cms_ops.cms_init(1 << 12, 2)

    def cands(salt):
        _, ca, cs, ce = topk_ops.talker_chunk_update(
            sk, jnp.asarray(acl), jnp.asarray(batch_src), jnp.asarray(v), 16,
            salt=salt,
        )
        return set(np.asarray(cs)[np.asarray(ce) > 0].tolist())

    assert a not in cands(0)  # suppressed by the hotter collider
    assert b in cands(0)
    # under varied salts, H surfaces in the vast majority of chunks
    seen = sum(int(a in cands(s)) for s in range(1, 9))
    assert seen >= 6
    # determinism: same salt, same candidates (checkpoint resume replay)
    assert cands(3) == cands(3)


# ---------------------------------------------------------------------------
# HLL large-range behavior (VERDICT r3 weak #5): prove the 1.04/sqrt(m)
# bound holds near and beyond 2^31 distinct sources WITHOUT the classic
# 32-bit collision correction — fmix32 is a bijection on uint32, so there
# are no hash collisions to correct for (see hll_estimate_np docstring).
# ---------------------------------------------------------------------------


def _simulate_hll_registers(n: int, p: int, seed: int) -> np.ndarray:
    """Registers after folding n DISTINCT uint32 values, by exact
    inverse-CDF sampling of each register's max rank.

    fmix32 is a bijection, so n distinct inputs contribute n distinct
    rank-hash values sampled without replacement from the 2^32 space,
    each landing in a uniform register.  Values with rank >= r number
    2^(33-r) (rank 33 is the single value 0), each present with
    probability q = n/2^32 and in register j with probability 1/m, so
    P(max_j <= r) = (1 - q/m)^(2^(32-r)); inverting at u ~ U(0,1) gives
    max_j = ceil(32 - log2(ln u / ln(1 - q/m))), clipped to [0, 33].
    """
    m = 1 << p
    rng = np.random.default_rng(seed)
    q = n / 2.0**32
    u = rng.random(m)
    with np.errstate(divide="ignore"):
        L = np.log(u) / np.log1p(-q / m)
        r = np.ceil(32.0 - np.log2(L))
    return np.clip(r, 0, 33).astype(np.uint32)[None, :]


@pytest.mark.parametrize("n", [2**31, 3_800_000_000])
def test_hll_estimator_holds_bound_near_2_31(n):
    p = 14
    m = 1 << p
    ests = [
        float(hll_ops.hll_estimate_np(_simulate_hll_registers(n, p, seed=s))[0])
        for s in range(8)
    ]
    rel_err = abs(np.mean(ests) - n) / n
    # mean of 8 runs: SE ~ (1.04/sqrt(m))/sqrt(8) ~ 0.29%; allow 3x the
    # single-run bound so the test is deterministic-seed robust
    assert rel_err < 3 * 1.04 / np.sqrt(m), (n, np.mean(ests), rel_err)


def test_hll_classic_collision_correction_would_be_wrong():
    """The classic -2^32 ln(1 - E/2^32) correction assumes colliding
    hashes; with bijective fmix32 it would inflate ~39% at n = 2^31."""
    n = 2**31
    est = float(np.mean([
        hll_ops.hll_estimate_np(_simulate_hll_registers(n, 14, seed=s))[0]
        for s in range(8)
    ]))
    corrected = -(2.0**32) * np.log1p(-est / 2.0**32)
    assert abs(est - n) / n < 0.03  # uncorrected: within bound
    assert corrected > 1.3 * n  # "corrected": badly inflated


def test_hll_estimate_capped_at_value_space():
    """Fully saturated registers (n -> 2^32) must not report > 2^32 —
    the folded values are uint32 IPv4 addresses."""
    regs = np.full((1, 1 << 14), 33, dtype=np.uint32)
    est = float(hll_ops.hll_estimate_np(regs)[0])
    assert est == 2.0**32


def test_talker_sampled_selection_still_finds_heavy_hitters():
    """sample_shift selects candidates from a stride sample; the sketch
    still covers every line, so estimates are unchanged and persistent
    heavy hitters still surface (they recur within any stride)."""
    rng = np.random.default_rng(7)
    b = 1 << 14
    # one dominant talker at 30% of lines, background uniform
    src = rng.integers(0, 1 << 32, size=b, dtype=np.uint32)
    hot = np.uint32(0x0A0A0A0A)
    src[rng.random(b) < 0.3] = hot
    acl = np.zeros(b, dtype=np.uint32)
    valid = np.ones(b, dtype=np.uint32)
    sk = cms_ops.cms_init(1 << 12, 2)

    full = topk_ops.talker_chunk_update(
        sk, jnp.asarray(acl), jnp.asarray(src), jnp.asarray(valid), 8, salt=1
    )
    samp = topk_ops.talker_chunk_update(
        sk, jnp.asarray(acl), jnp.asarray(src), jnp.asarray(valid), 8, salt=1,
        sample_shift=3,
    )
    # identical sketch state (every line absorbed either way)
    np.testing.assert_array_equal(np.asarray(full[0]), np.asarray(samp[0]))
    cand_full = set(np.asarray(full[2])[np.asarray(full[3]) > 0].tolist())
    cand_samp = set(np.asarray(samp[2])[np.asarray(samp[3]) > 0].tolist())
    assert int(hot) in cand_full and int(hot) in cand_samp
    # and the hot talker's estimate comes from the full sketch both ways
    est_full = dict(zip(np.asarray(full[2]).tolist(), np.asarray(full[3]).tolist()))
    est_samp = dict(zip(np.asarray(samp[2]).tolist(), np.asarray(samp[3]).tolist()))
    assert est_full[int(hot)] == est_samp[int(hot)]


def test_talker_sampled_phase_rotates_with_salt():
    """The sampled column rotates by salt, so grouped (group-major) lines
    cannot alias entire groups out of the sample across chunks."""
    b = 1 << 10
    stride = 8
    # put a unique talker at flat positions with index % 8 == 5 only
    src = np.arange(b, dtype=np.uint32) + 1
    acl = np.zeros(b, dtype=np.uint32)
    valid = np.zeros(b, dtype=np.uint32)
    valid[5::stride] = 1  # only phase-5 lines are valid
    sk = cms_ops.cms_init(1 << 12, 2)

    def cands(salt):
        _, ca, cs, ce = topk_ops.talker_chunk_update(
            sk, jnp.asarray(acl), jnp.asarray(src), jnp.asarray(valid), 8,
            salt=salt, sample_shift=3,
        )
        return set(np.asarray(cs)[np.asarray(ce) > 0].tolist())

    # across stride consecutive salts, at least one chunk samples phase 5
    seen = [len(cands(s)) > 0 for s in range(stride)]
    assert any(seen), "rotation never reached the valid phase"
    assert not all(seen), "with only phase-5 valid, other phases must be empty"


def test_talker_sampled_selection_small_batch_degrades_to_exact():
    """A per-shard batch smaller than 2**shift must fall back to exact
    full-batch selection — not silently select zero candidates every
    chunk."""
    b = 4  # < 2**3
    src = np.asarray([7, 7, 7, 9], dtype=np.uint32)
    acl = np.zeros(b, dtype=np.uint32)
    valid = np.ones(b, dtype=np.uint32)
    sk = cms_ops.cms_init(1 << 10, 2)
    _, ca, cs, ce = topk_ops.talker_chunk_update(
        sk, jnp.asarray(acl), jnp.asarray(src), jnp.asarray(valid), 4,
        salt=1, sample_shift=3,
    )
    winners = set(np.asarray(cs)[np.asarray(ce) > 0].tolist())
    assert 7 in winners
