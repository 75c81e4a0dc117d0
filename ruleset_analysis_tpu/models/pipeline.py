"""The analysis pipeline — the "flagship model" of this framework.

One jitted step fuses everything the reference's mapper+reducer pair did
per line (SURVEY.md §4.3/§4.4), over a whole batch:

  batch -> first-match keys -> { exact 64-bit counts, CMS, per-rule HLL,
                                 top-K talker candidates }

The state is a pytree of uint32 register files, every component of which
is mergeable (add for counts/CMS, max for HLL) — the property that makes
multi-chip scale-out a pair of XLA collectives (psum/pmax) instead of a
Hadoop shuffle, and makes checkpoint/resume idempotent.

Batches arrive column-major ``[TUPLE_COLS, B]`` so each field is a
contiguous lane-aligned vector on device.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import AnalysisConfig
from ..hostside.pack import (
    PackedRuleset,
    T_ACL, T_DPORT, T_DST, T_PROTO, T_SPORT, T_SRC, T_VALID,
    T6_ACL, T6_DPORT, T6_DST, T6_PROTO, T6_SPORT, T6_SRC, T6_VALID,
    TUPLE_COLS, TUPLE6_COLS, W_DST, W_META, W_PORTS, W_SRC, W_WEIGHT,
    WIRE_COLS, WIRE_MAX_ACLS, WIREW_COLS,
)
from ..ops import cms as cms_ops
from ..ops import counts as count_ops
from ..ops import hll as hll_ops
from ..ops import topk as topk_ops
from ..ops.match import RULE_BLOCK, match_keys, match_keys_stacked

_U32 = jnp.uint32


class DeviceRuleset(NamedTuple):
    """Device-resident rule tensor (the reference's shipped ACL pickle)."""

    rules: jax.Array  # [R, RULE_COLS] uint32, R % rule_block == 0
    deny_key: jax.Array  # [n_acls] uint32
    #: field-major lane-padded twin for the pallas kernel; None on the
    #: default XLA path (ship_ruleset(match_impl="pallas") fills it)
    rules_fm: jax.Array | None = None


class DeviceRuleset6(NamedTuple):
    """Device-resident IPv6 rule tensor (pack.rules6, limb layout).

    Shares the v4 key universe and deny_key; shipped only when the packed
    ruleset carries v6 rows, so pure-v4 runs never touch the v6 path.
    """

    rules6: jax.Array  # [R6, RULE6_COLS] uint32, R6 % rule_block == 0
    deny_key: jax.Array  # [n_acls] uint32


#: High bit tagged onto ACL gids of IPv6 talker candidates: v6 source
#: identities are 32-bit limb digests (ops.match6.fold_src32), and the tag
#: keeps them from ever merging with a numerically-equal v4 address in the
#: talker tracker.  gids are bounded by WIRE_MAX_ACLS (23 bits), so bit 31
#: is always free; reports strip the tag and render these as v6 digests.
V6_ACL_TAG = np.uint32(0x80000000)


class AnalysisState(NamedTuple):
    """All mergeable device registers for one analysis run."""

    counts_lo: jax.Array  # [K] u32   exact hit counts, low word
    counts_hi: jax.Array  # [K] u32   exact hit counts, high word
    cms: jax.Array  # [d, w] u32      approximate per-key counts
    hll: jax.Array  # [K, m] u32      per-key unique-source registers
    talk_cms: jax.Array  # [d, w] u32 (acl, src) pair counts for top-K


class ChunkOut(NamedTuple):
    """Per-chunk host-bound outputs (top-K candidates)."""

    cand_acl: jax.Array  # [k] u32
    cand_src: jax.Array  # [k] u32
    cand_est: jax.Array  # [k] u32


def batch_cols(batch: jax.Array) -> tuple[dict, jax.Array]:
    """Field columns + valid/weight plane from a batch in ANY layout.

    Accepts the working layout ``[TUPLE_COLS, B]`` (one uint32 lane per
    field), the wire layout ``[WIRE_COLS, B]`` (bit-packed, 16 B/line —
    what the stream driver ships over PCIe; see pack.compact_batch), or
    the WEIGHTED wire layout ``[WIREW_COLS, B]`` (a coalesced batch: the
    extra row carries each unique row's repetition count, which becomes
    the valid plane — every register update is weight-linear in it or
    idempotent, see DESIGN §11).  The layout is static shape information,
    so under jit this is a free Python branch; the wire unpack is three
    shifts and three ands on the VPU — noise next to the match itself.

    Traces under the ``ra.unpack`` named scope (incl. the coalesce
    weight plane): the unpack's HLO ops carry their stage label for the
    device attribution plane (runtime/devprof.py, DESIGN §14).
    """
    u32 = jnp.uint32
    with jax.named_scope("ra.unpack"):
        if batch.shape[-2] in (WIRE_COLS, WIREW_COLS):
            meta = batch[..., W_META, :]
            ports = batch[..., W_PORTS, :]
            cols = {
                "acl": meta & u32(WIRE_MAX_ACLS - 1),
                "proto": meta >> u32(24),
                "src": batch[..., W_SRC, :],
                "sport": ports >> u32(16),
                "dst": batch[..., W_DST, :],
                "dport": ports & u32(0xFFFF),
            }
            if batch.shape[-2] == WIREW_COLS:
                return cols, batch[..., W_WEIGHT, :]
            return cols, (meta >> u32(23)) & u32(1)
        if batch.shape[-2] == TUPLE_COLS:
            cols = {
                "acl": batch[..., T_ACL, :],
                "proto": batch[..., T_PROTO, :],
                "src": batch[..., T_SRC, :],
                "sport": batch[..., T_SPORT, :],
                "dst": batch[..., T_DST, :],
                "dport": batch[..., T_DPORT, :],
            }
            return cols, batch[..., T_VALID, :]
    raise ValueError(
        f"batch field axis must be TUPLE_COLS={TUPLE_COLS} or "
        f"WIRE_COLS={WIRE_COLS}, got shape {batch.shape}"
    )


def batch_cols6(batch: jax.Array) -> tuple[dict, jax.Array]:
    """Field columns + valid mask from a v6 batch in EITHER layout.

    Accepts the working ``[TUPLE6_COLS, B]`` layout or the wire-v2
    ``[WIRE6_COLS, B]`` layout (40 B/line; ports/meta bit-packed exactly
    like the v4 wire words, so the on-device unpack is the same three VPU
    shifts).  Address limbs surface as src0..src3 / dst0..dst3.
    """
    from ..hostside.pack import (
        W6_DST, W6_META, W6_PORTS, W6_SRC, W6_WEIGHT, WIRE6_COLS,
        WIRE6W_COLS,
    )

    u32 = jnp.uint32
    with jax.named_scope("ra.unpack"):
        if batch.shape[-2] in (WIRE6_COLS, WIRE6W_COLS):
            meta = batch[..., W6_META, :]
            ports = batch[..., W6_PORTS, :]
            cols = {
                "acl": meta & u32(WIRE_MAX_ACLS - 1),
                "proto": meta >> u32(24),
                "sport": ports >> u32(16),
                "dport": ports & u32(0xFFFF),
            }
            for i in range(4):
                cols[f"src{i}"] = batch[..., W6_SRC + i, :]
                cols[f"dst{i}"] = batch[..., W6_DST + i, :]
            if batch.shape[-2] == WIRE6W_COLS:
                return cols, batch[..., W6_WEIGHT, :]
            return cols, (meta >> u32(23)) & u32(1)
        if batch.shape[-2] != TUPLE6_COLS:
            raise ValueError(
                f"v6 batch field axis must be TUPLE6_COLS={TUPLE6_COLS} or "
                f"WIRE6_COLS={WIRE6_COLS}, got shape {batch.shape}"
            )
        cols = {
            "acl": batch[..., T6_ACL, :],
            "proto": batch[..., T6_PROTO, :],
            "sport": batch[..., T6_SPORT, :],
            "dport": batch[..., T6_DPORT, :],
        }
        for i in range(4):
            cols[f"src{i}"] = batch[..., T6_SRC + i, :]
            cols[f"dst{i}"] = batch[..., T6_DST + i, :]
        return cols, batch[..., T6_VALID, :]


def pad_rules6(rules6: np.ndarray, rule_block: int = RULE_BLOCK) -> np.ndarray:
    """Pad the v6 rule matrix to a block multiple (NO_ACL padding rows)."""
    from ..hostside.pack import NO_ACL, R6_ACL, RULE6_COLS

    r = rules6.shape[0]
    target = max(rule_block, ((r + rule_block - 1) // rule_block) * rule_block)
    if r == target:
        return rules6
    out = np.zeros((target, RULE6_COLS), dtype=np.uint32)
    out[:, R6_ACL] = NO_ACL
    out[:r] = rules6
    return out


def ship_ruleset6(packed: PackedRuleset, rule_block: int = RULE_BLOCK) -> DeviceRuleset6:
    return DeviceRuleset6(
        rules6=jnp.asarray(pad_rules6(packed.rules6, rule_block)),
        deny_key=jnp.asarray(packed.deny_key.astype(np.uint32)),
    )


def ship_ruleset6_host(packed: PackedRuleset, rule_block: int = RULE_BLOCK) -> DeviceRuleset6:
    """Numpy twin of :func:`ship_ruleset6` — no backend touched."""
    return DeviceRuleset6(
        rules6=pad_rules6(packed.rules6, rule_block),
        deny_key=packed.deny_key.astype(np.uint32),
    )


def pad_rules(rules: np.ndarray, rule_block: int = RULE_BLOCK) -> np.ndarray:
    """Pad the host rule matrix to a multiple of the scan block size."""
    from ..hostside.pack import NO_ACL, R_ACL, RULE_COLS

    r = rules.shape[0]
    target = max(rule_block, ((r + rule_block - 1) // rule_block) * rule_block)
    if r == target:
        return rules
    out = np.zeros((target, RULE_COLS), dtype=np.uint32)
    out[:, R_ACL] = NO_ACL
    out[:r] = rules
    return out


def ship_ruleset(
    packed: PackedRuleset,
    rule_block: int = RULE_BLOCK,
    match_impl: str = "xla",
) -> DeviceRuleset:
    rules = jnp.asarray(pad_rules(packed.rules, rule_block))
    rules_fm = None
    # pallas_fused is an explicit experimental surface (VERDICT r5 Weak
    # #4: 0.083x vs XLA); the loud warning lives in the step builder
    # (parallel/step.py), which every driver path crosses exactly once
    if match_impl in ("pallas", "pallas_fused"):
        from ..ops import pallas_match

        rules_fm = pallas_match.prep_rules(rules)
    return DeviceRuleset(
        rules=rules,
        deny_key=jnp.asarray(packed.deny_key.astype(np.uint32)),
        rules_fm=rules_fm,
    )


def register_bytes(n_keys: int, cfg: AnalysisConfig) -> dict[str, int]:
    """Per-register-file device memory for this geometry, in bytes."""
    s = cfg.sketch
    return {
        "counts": 2 * 4 * n_keys,
        "cms": 4 * s.cms_depth * s.cms_width,
        "hll": 4 * n_keys * s.hll_m,
        "talk_cms": 4 * s.talk_cms_depth * s.cms_width,
    }


def check_register_budget(n_keys: int, cfg: AnalysisConfig) -> None:
    """Refuse geometries whose registers exceed the configured budget.

    The per-key HLL file (``n_keys * 2**hll_p * 4`` bytes) scales with the
    ruleset: 1M expanded rule keys at the default hll_p=8 is already 1 GiB
    of HBM.  Failing here with a concrete suggestion beats an opaque
    device OOM mid-run.
    """
    sizes = register_bytes(n_keys, cfg)
    total = sum(sizes.values())
    budget = cfg.register_memory_budget_bytes
    if total <= budget:
        return
    non_hll = total - sizes["hll"]
    fit_p = -1
    for p in range(cfg.sketch.hll_p, 0, -1):
        if non_hll + 4 * n_keys * (1 << p) <= budget:
            fit_p = p
            break
    hint = (
        f"try --hll-p {fit_p}"
        if fit_p > 0
        else "even hll_p=1 does not fit; raise register_memory_budget_bytes "
        "or shrink the ruleset/cms geometry"
    )
    raise ValueError(
        f"sketch registers need {total / 2**20:.0f} MiB "
        f"(hll {sizes['hll'] / 2**20:.0f} MiB = {n_keys} keys x "
        f"{cfg.sketch.hll_m} registers x 4 B) but the budget is "
        f"{budget / 2**20:.0f} MiB; {hint}"
    )


def init_state(n_keys: int, cfg: AnalysisConfig) -> AnalysisState:
    check_register_budget(n_keys, cfg)
    s = cfg.sketch
    return AnalysisState(
        counts_lo=jnp.zeros(n_keys, dtype=_U32),
        counts_hi=jnp.zeros(n_keys, dtype=_U32),
        cms=cms_ops.cms_init(s.cms_width, s.cms_depth),
        hll=hll_ops.hll_init(n_keys, s.hll_p),
        talk_cms=cms_ops.cms_init(s.cms_width, s.talk_cms_depth),
    )


def init_state_host(n_keys: int, cfg: AnalysisConfig) -> AnalysisState:
    """Numpy twin of :func:`init_state` — same pytree, no JAX backend touched.

    Lets entry points build example arguments without initializing any
    backend (jax.jit accepts numpy leaves); the driver's own jit call is
    then the first and only backend contact.
    """
    check_register_budget(n_keys, cfg)
    s = cfg.sketch
    u32 = np.uint32
    return AnalysisState(
        counts_lo=np.zeros(n_keys, dtype=u32),
        counts_hi=np.zeros(n_keys, dtype=u32),
        cms=np.zeros((s.cms_depth, s.cms_width), dtype=u32),
        hll=np.zeros((n_keys, s.hll_m), dtype=u32),
        talk_cms=np.zeros((s.talk_cms_depth, s.cms_width), dtype=u32),
    )


def ship_ruleset_host(packed: PackedRuleset, rule_block: int = RULE_BLOCK) -> DeviceRuleset:
    """Numpy twin of :func:`ship_ruleset` (XLA match path only) — no backend."""
    return DeviceRuleset(
        rules=pad_rules(packed.rules, rule_block),
        deny_key=packed.deny_key.astype(np.uint32),
        rules_fm=None,
    )


def _update_registers(
    state: AnalysisState,
    keys: jax.Array,  # [B] u32 count keys (matched rule / implicit deny)
    valid: jax.Array,  # [B] u32 weight plane (0 = invalid, w = w raw lines)
    src: jax.Array,  # [B] u32 source IPs
    acl: jax.Array,  # [B] u32 ACL gids
    *,
    n_keys: int,
    topk_k: int,
    exact_counts: bool,
    salt: jax.Array | int = 0,
    topk_sample_shift: int = 0,
    counts_delta: jax.Array | None = None,
    counts_impl: str = "scatter",
    update_impl: str = "scatter",
    topk_every: int = 1,
) -> tuple[AnalysisState, ChunkOut]:
    """Shared register tail: the reducer's whole job, for any match layout."""
    # One bincount into the (small) key space feeds BOTH the exact counts
    # and the CMS: count-min updates are linear in per-key increments, so
    # updating from [n_keys] aggregated deltas instead of [B] raw lines is
    # bit-identical and turns the batch-sized CMS scatter into a
    # key-space-sized one (~free; the batch-sized scatter dominated the
    # whole step at 1M-line chunks).  counts_delta: the fused pallas
    # kernel already built the bincount in VMEM (mirrors parallel/step.py
    # _merge_tail — keep the two tails in lockstep).
    #
    # update_impl="sorted" (DESIGN §15): the batch-sized scatters become
    # segment reductions over sorted key runs (ops/sorted_update.py) —
    # bit-identical by add/max associativity.  counts_impl composes: the
    # matmul/reduce counts formulations are already scatter-free, so the
    # sorted path only takes over the counts stage at the default
    # "scatter" setting.
    if update_impl == "sorted":
        from ..ops import sorted_update as sorted_ops

        need = counts_delta is None and counts_impl == "scatter"
        sorted_delta, hll = sorted_ops.counts_hll_sorted(
            state.hll, keys, valid, src, n_keys, need_counts=need
        )
        if counts_delta is None:
            counts_delta = (
                sorted_delta
                if need
                else count_ops.SEGMENT_COUNTS_IMPLS[counts_impl](
                    keys, valid, n_keys
                )
            )
    else:
        if counts_delta is None:
            counts_delta = count_ops.SEGMENT_COUNTS_IMPLS[counts_impl](
                keys, valid, n_keys
            )
        hll = hll_ops.hll_update(state.hll, keys, src, valid)
    delta = counts_delta
    if exact_counts:
        lo, hi = count_ops.add64(state.counts_lo, state.counts_hi, delta)
    else:
        lo, hi = state.counts_lo, state.counts_hi
    cms = cms_ops.cms_update(state.cms, jnp.arange(n_keys, dtype=_U32), delta)
    if update_impl == "sorted":
        from ..ops import sorted_update as sorted_ops

        salt_u = jnp.asarray(salt, dtype=_U32)
        dt, wt = state.talk_cms.shape

        def _tables(sel):
            return sorted_ops.talker_tables_sorted(
                acl, src, valid, salt_u, width=wt, depth=dt,
                slots=topk_ops.CAND_SLOTS, sample_shift=topk_sample_shift,
                with_candidates=sel,
            )

        if topk_every > 1:
            cms_delta, cnt, rep = jax.lax.cond(
                salt_u % _U32(topk_every) == _U32(0),
                lambda _: _tables(True),
                lambda _: _tables(False),
                None,
            )
        else:
            cms_delta, cnt, rep = _tables(True)
        talk_cms = state.talk_cms + cms_delta
        s_acl, s_src, _sv = topk_ops.sample_cols(
            acl, src, valid, salt_u, topk_sample_shift
        )
        ca, cs, ce = topk_ops.select_from_tables(
            cnt, rep, s_acl, s_src, talk_cms,
            min(topk_k, s_acl.shape[0]),
        )
    else:
        talk_cms, ca, cs, ce = topk_ops.talker_chunk_update(
            state.talk_cms, acl, src, valid, topk_k, salt=salt,
            sample_shift=topk_sample_shift, topk_every=topk_every,
        )
    return (
        AnalysisState(counts_lo=lo, counts_hi=hi, cms=cms, hll=hll, talk_cms=talk_cms),
        ChunkOut(cand_acl=ca, cand_src=cs, cand_est=ce),
    )


def analysis_step(
    state: AnalysisState,
    ruleset: DeviceRuleset,
    batch: jax.Array,  # [TUPLE_COLS, B] uint32, column-major
    *,
    n_keys: int,
    topk_k: int,
    exact_counts: bool = True,
    rule_block: int = RULE_BLOCK,
    salt: jax.Array | int = 0,
    match_impl: str = "xla",
    topk_sample_shift: int = 0,
    counts_impl: str = "scatter",
    update_impl: str = "scatter",
    topk_every: int = 1,
) -> tuple[AnalysisState, ChunkOut]:
    """One fused device step over a batch of packed log lines.

    ``batch`` may be the working ``[TUPLE_COLS, B]`` layout or the wire
    ``[WIRE_COLS, B]`` layout (see :func:`batch_cols`).
    """
    cols, valid = batch_cols(batch)
    counts_delta = None
    if match_impl == "pallas_fused" and ruleset.rules_fm is not None:
        from ..ops import pallas_fused

        keys, counts_delta = pallas_fused.match_keys_and_counts_pallas(
            cols, valid, ruleset.rules, ruleset.rules_fm, ruleset.deny_key,
            n_keys,
        )
    elif match_impl == "pallas" and ruleset.rules_fm is not None:
        from ..ops import pallas_match

        keys = pallas_match.match_keys_pallas(
            cols, ruleset.rules, ruleset.rules_fm, ruleset.deny_key
        )
    else:
        keys = match_keys(cols, ruleset.rules, ruleset.deny_key, rule_block)
    return _update_registers(
        state, keys, valid, cols["src"], cols["acl"],
        n_keys=n_keys, topk_k=topk_k, exact_counts=exact_counts, salt=salt,
        topk_sample_shift=topk_sample_shift, counts_delta=counts_delta,
        counts_impl=counts_impl, update_impl=update_impl,
        topk_every=topk_every,
    )


def analysis_step6(
    state: AnalysisState,
    ruleset6: DeviceRuleset6,
    batch6: jax.Array,  # [TUPLE6_COLS, B6] uint32, column-major
    *,
    n_keys: int,
    topk_k: int,
    exact_counts: bool = True,
    rule_block: int = RULE_BLOCK,
    salt: jax.Array | int = 0,
    topk_sample_shift: int = 0,
    counts_impl: str = "scatter",
    update_impl: str = "scatter",
    topk_every: int = 1,
) -> tuple[AnalysisState, ChunkOut]:
    """One fused device step over a batch of v6 lines.

    Updates the SAME register state as the v4 step (shared key universe):
    exact counts and CMS key by rule key; HLL / talker source identity is
    the 32-bit limb digest (ops.match6.fold_src32), with the talker ACL
    gid tagged V6_ACL_TAG so v6 digests never merge with v4 addresses.
    """
    from ..ops.match6 import fold_src32, match_keys6

    cols, valid = batch_cols6(batch6)
    keys = match_keys6(cols, ruleset6.rules6, ruleset6.deny_key, rule_block)
    return _update_registers(
        state, keys, valid, fold_src32(cols), cols["acl"] | V6_ACL_TAG,
        n_keys=n_keys, topk_k=topk_k, exact_counts=exact_counts, salt=salt,
        topk_sample_shift=topk_sample_shift, counts_impl=counts_impl,
        update_impl=update_impl, topk_every=topk_every,
    )


class DeviceRulesetStacked(NamedTuple):
    """Device-resident stacked rule slabs (BASELINE.json config #4)."""

    rules3d: jax.Array  # [G, Rmax, RULE_COLS] uint32
    deny_key: jax.Array  # [n_acls] uint32


class DeviceRulesetTenant(NamedTuple):
    """Device-resident TENANT-stacked rule tensors (one packing bucket).

    Many tenants' independently-packed rulesets, each padded to the
    bucket's rule/ACL rungs (runtime/tenancy.py ladder) and stacked on a
    leading tenant axis.  Each tenant keeps its OWN key/gid universe —
    the step dynamically slices one tenant's plane out, runs the
    unchanged flat core, and writes the plane back, so per-tenant
    registers are bit-identical to a solo run of that tenant.
    """

    rules_t: jax.Array  # [T, R_pad, RULE_COLS] uint32, R_pad % rule_block == 0
    deny_key_t: jax.Array  # [T, A_pad] uint32


def ship_ruleset_stacked(packed: PackedRuleset, rule_block: int = RULE_BLOCK) -> DeviceRulesetStacked:
    from ..hostside.pack import stack_rules

    return DeviceRulesetStacked(
        rules3d=jnp.asarray(stack_rules(packed, rule_block)),
        deny_key=jnp.asarray(packed.deny_key.astype(np.uint32)),
    )


def analysis_step_stacked(
    state: AnalysisState,
    ruleset: DeviceRulesetStacked,
    batch: jax.Array,  # [G, TUPLE_COLS, Bg] uint32, grouped by ACL gid
    *,
    n_keys: int,
    topk_k: int,
    exact_counts: bool = True,
    rule_block: int = RULE_BLOCK,
    salt: jax.Array | int = 0,
    topk_sample_shift: int = 0,
    update_impl: str = "scatter",
    topk_every: int = 1,
) -> tuple[AnalysisState, ChunkOut]:
    """Grouped-batch variant of analysis_step (vmap over rule slabs).

    The match runs per-group against only that ACL's slab; the mergeable
    register updates are order-invariant, so the resulting state is
    identical to the flat step fed the same multiset of lines.
    """
    cols, valid = batch_cols(batch)
    keys = match_keys_stacked(cols, ruleset.rules3d, ruleset.deny_key, rule_block).reshape(-1)
    return _update_registers(
        state,
        keys,
        valid.reshape(-1),
        cols["src"].reshape(-1),
        cols["acl"].reshape(-1),
        n_keys=n_keys,
        topk_k=topk_k,
        exact_counts=exact_counts,
        salt=salt,
        topk_sample_shift=topk_sample_shift,
        update_impl=update_impl,
        topk_every=topk_every,
    )


def state_to_host(state: AnalysisState) -> dict[str, np.ndarray]:
    """Fetch every register file to host numpy (a hard sync point)."""
    return {
        k: np.asarray(jax.device_get(getattr(state, k)))
        for k in AnalysisState._fields
    }


def counts_total(state: AnalysisState) -> int:
    """Total hits across all keys, fetched to host — and therefore a hard
    synchronization point.

    No bytes of a register can arrive before every step that wrote them
    has executed, and the value is evidence that the work ran: each valid
    line contributes exactly one count (a rule key or its ACL's implicit
    deny).  Benchmarks close their timed sections with this and assert
    the delta equals the number of valid lines stepped.
    """
    lo = np.asarray(jax.device_get(state.counts_lo), dtype=np.uint64)
    hi = np.asarray(jax.device_get(state.counts_hi), dtype=np.uint64)
    return int((lo + (hi << np.uint64(32))).sum())


def sync_state(state: AnalysisState) -> None:
    """Force completion of every pending step writing into ``state``.

    A device_get of the count register (see :func:`counts_total`); the
    fetched register is small ([n_keys] uint32), so the transfer cost is
    negligible.
    """
    np.asarray(jax.device_get(state.counts_lo))


# ---------------------------------------------------------------------------
# Finalize: device registers -> report-shaped host results.
# ---------------------------------------------------------------------------


def finalize(
    state: AnalysisState,
    packed: PackedRuleset,
    cfg: AnalysisConfig,
    tracker: topk_ops.TopKTracker | None = None,
    *,
    topk: int = 10,
    totals: dict | None = None,
    v6_digests: dict[int, int] | None = None,
):
    """Pull registers to host and assemble the Report (SURVEY.md L5).

    ``v6_digests`` maps fold_src32 digests -> 128-bit source ints (built
    by the stream driver as it packs v6 lines, bounded) so v6 talkers
    render as real addresses; digests missing from the map (map capped,
    or resume discarded pre-crash entries) render as ``v6#<8 hex>``.
    """
    from ..hostside.aclparse import int_to_ip6
    from ..runtime.report import build_report

    lo = np.asarray(jax.device_get(state.counts_lo))
    hi = np.asarray(jax.device_get(state.counts_hi))
    hll_regs = np.asarray(jax.device_get(state.hll))
    cms_host = np.asarray(jax.device_get(state.cms))

    if cfg.exact_counts:
        per_key = count_ops.to_u64(lo, hi)
    else:
        per_key = cms_ops.cms_query_np(cms_host, np.arange(packed.n_keys, dtype=np.uint32))
    card = hll_ops.hll_estimate_np(hll_regs)

    hits = {}
    uniq = {}
    for key_id, meta in enumerate(packed.key_meta):
        k = (meta.firewall, meta.acl, meta.index)
        hits[k] = int(per_key[key_id])
        if per_key[key_id] > 0:
            uniq[k] = int(round(card[key_id]))

    # HLL error band (VERDICT Weak #6): a deletion report quoting unique
    # sources without its ±1.04/sqrt(m) p90 band invites over-trust.  The
    # band and (when the observed key space sits far below the sketch's
    # size) a concrete --hll-p memory hint ride totals so every renderer
    # — text, JSON, the serve endpoints — can surface them.
    totals = dict(totals or {})
    m = cfg.sketch.hll_m
    hll_info: dict = {
        "p": cfg.sketch.hll_p,
        "m": m,
        "rel_err_p90": round(1.04 / (m ** 0.5), 4),
    }
    u_max = max(uniq.values(), default=0)
    if u_max and u_max * 8 <= m and cfg.sketch.hll_p > 4:
        import math

        fit_p = max(4, math.ceil(math.log2(max(8 * u_max, 16))))
        if fit_p < cfg.sketch.hll_p:
            hll_info["hint"] = (
                f"observed per-rule cardinality tops out at ~{u_max}, far "
                f"below the hll_p={cfg.sketch.hll_p} sketch ({m} registers/"
                f"rule); --hll-p {fit_p} would cut HLL register memory "
                f"{2 ** (cfg.sketch.hll_p - fit_p)}x at ±"
                f"{100 * 1.04 / (2 ** fit_p) ** 0.5:.1f}% p90 error"
            )
    totals["hll"] = hll_info

    talkers = None
    if tracker is not None:
        gid_to_name = {gid: name for name, gid in packed.acl_gid.items()}
        talkers = {}
        tag = int(V6_ACL_TAG)
        for gid in tracker.acls():
            is6 = bool(int(gid) & tag)
            name = gid_to_name.get(int(gid) & ~tag)
            if name is None:
                continue
            items = tracker.top(gid, topk)
            if is6:
                dig = v6_digests or {}
                items = [
                    (
                        int_to_ip6(dig[int(s)])
                        if int(s) in dig
                        else f"v6#{int(s):08x}",
                        c,
                    )
                    for s, c in items
                ]
            talkers.setdefault(name, []).extend(items)
        # one merged per-ACL section across families, ranked by count
        talkers = {
            k: sorted(v, key=lambda kv: -kv[1])[:topk]
            for k, v in talkers.items()
        }

    return build_report(
        packed,
        hits,
        backend="tpu",
        totals=totals,
        unique_sources=uniq,
        talkers=talkers,
    )
