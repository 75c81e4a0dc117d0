"""The first-match kernel: the reference mapper's inner loop, TPU-native.

Reference semantics (SURVEY.md §4.3): for each log line, linearly scan the
named ACL's expanded ACEs *in configuration order*; the first row whose
five range predicates all hold wins; no row -> the ACL's implicit deny.

TPU realisation: the per-line × per-rule double loop becomes one batched
``[B, R]`` boolean predicate (pure uint32 compares on the VPU) reduced with
``min`` over masked row indices — first match == smallest matching row
index, because pack.py emits rows in global configuration order.  No
data-dependent control flow; XLA fuses the compare/reduce into a tiled
loop without materialising [B, R] in HBM.

For large rule tensors the rule axis is processed in fixed-size blocks via
``lax.scan`` (running-min carry), bounding VMEM pressure while keeping one
compiled program for any R.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..hostside.pack import (
    R_ACL,
    R_DHI,
    R_DLO,
    R_DPHI,
    R_DPLO,
    R_PHI,
    R_PLO,
    R_SHI,
    R_SLO,
    R_SPHI,
    R_SPLO,
    R_KEY,
    RULE_BLOCK,  # re-export: the kernel-facing name for the block size
)

_U32 = jnp.uint32


def _block_min_row(cols: dict, rules: jnp.ndarray, base: jnp.ndarray) -> jnp.ndarray:
    """Min matching global row index within one rule block; NO_MATCH if none."""
    r = rules.astype(_U32)
    # [B, 1] vs [1, Rb] broadcasts -> [B, Rb] predicate on the VPU
    def col(i):
        return r[:, i][None, :]

    def in_range(lo_col, hi_col, x):
        # unsigned wraparound range check: with lo <= hi (pack.py
        # guarantees it), x in [lo, hi]  <=>  x - lo <= hi - lo.  One
        # subtract + one compare instead of two compares + an AND — and
        # with the rule tensor compiled in as a constant (parallel/step
        # specialization), hi - lo folds away entirely.
        lo = col(lo_col)
        return (x - lo) <= (col(hi_col) - lo)

    acl = cols["acl"][:, None]
    ok = (
        (col(R_ACL) == acl)
        & in_range(R_PLO, R_PHI, cols["proto"][:, None])
        & in_range(R_SLO, R_SHI, cols["src"][:, None])
        & in_range(R_SPLO, R_SPHI, cols["sport"][:, None])
        & in_range(R_DLO, R_DHI, cols["dst"][:, None])
        & in_range(R_DPLO, R_DPHI, cols["dport"][:, None])
    )
    rb = rules.shape[0]
    idx = base + lax.broadcasted_iota(_U32, (1, rb), 1)
    return jnp.min(jnp.where(ok, idx, NO_MATCH), axis=1)


# numpy scalar, NOT jnp: a module-level jnp scalar would initialize the
# JAX backend at import time, taking the chip in every process that
# merely imports this module; np.uint32 participates in jnp expressions
# identically.
NO_MATCH = np.uint32(0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("rule_block",))
def first_match_rows(
    cols: dict,
    rules: jnp.ndarray,
    rule_block: int = RULE_BLOCK,
) -> jnp.ndarray:
    """Global row index of the first matching ACE per line; NO_MATCH if none.

    cols: dict of [B] uint32 arrays (acl, proto, src, sport, dst, dport).
    rules: [R, RULE_COLS] uint32, R padded to a multiple of rule_block
    (padding rows carry NO_ACL and never match).
    """
    # ra.match named scope: the kernel's HLO ops (and the scan's while
    # loop) carry the stage label for the device attribution plane
    # (runtime/devprof.py, DESIGN §14); trace-time only, zero run cost
    with jax.named_scope("ra.match"):
        r = rules.shape[0]
        if r <= rule_block:
            return _block_min_row(cols, rules, jnp.uint32(0))
        assert r % rule_block == 0, "pad the rule tensor to a multiple of rule_block"
        blocks = rules.reshape(r // rule_block, rule_block, rules.shape[1])

        def body(best, xs):
            block, base = xs
            m = _block_min_row(cols, block, base)
            return jnp.minimum(best, m), None

        bases = (jnp.arange(r // rule_block, dtype=_U32) * _U32(rule_block))
        init = jnp.full(cols["acl"].shape, NO_MATCH, dtype=_U32)
        best, _ = lax.scan(body, init, (blocks, bases))
        return best


def first_match_rows_stacked(
    cols: dict,
    rules3d: jnp.ndarray,
    rule_block: int = RULE_BLOCK,
) -> jnp.ndarray:
    """Grouped first-match: vmap of the kernel over stacked rule slabs.

    cols: dict of [G, Bg] uint32 arrays, lines pre-bucketed by ACL gid
    (pack.group_tuples / pack.GroupBuffer); rules3d: [G, Rmax, RULE_COLS]
    from pack.stack_rules.  Returns [G, Bg] LOCAL slab row indices
    (NO_MATCH where nothing matches).  Each line only scans its own ACL's
    slab — O(Rmax) per line instead of the flat path's O(total rows)
    (BASELINE.json config #4).
    """
    return jax.vmap(
        lambda c, r: first_match_rows(c, r, rule_block), in_axes=(0, 0)
    )(cols, rules3d)


def match_keys_stacked(
    cols: dict,
    rules3d: jnp.ndarray,
    deny_key: jnp.ndarray,
    rule_block: int = RULE_BLOCK,
) -> jnp.ndarray:
    """Count-key per line for the grouped layout ([G, Bg] in and out)."""
    row = first_match_rows_stacked(cols, rules3d, rule_block)
    with jax.named_scope("ra.match"):
        matched = row != NO_MATCH
        safe_row = jnp.where(matched, row, _U32(0))
        keys3 = rules3d[:, :, R_KEY].astype(_U32)  # [G, Rmax]
        rule_key = jnp.take_along_axis(keys3, safe_row, axis=1)
        acl = jnp.minimum(cols["acl"], _U32(deny_key.shape[0] - 1))
        deny = deny_key.astype(_U32)[acl]
        return jnp.where(matched, rule_key, deny)


def match_keys(
    cols: dict,
    rules: jnp.ndarray,
    deny_key: jnp.ndarray,
    rule_block: int = RULE_BLOCK,
) -> jnp.ndarray:
    """Count-key per line: first-match rule key, or the line's ACL's
    implicit-deny key when nothing matches.

    Invalid lines (valid=0) still produce a (meaningless) key; every
    consumer weights by ``cols["valid"]`` so they contribute nothing.
    """
    row = first_match_rows(cols, rules, rule_block)
    with jax.named_scope("ra.match"):
        return rows_to_keys(row, rules, deny_key, cols["acl"])


def rows_to_keys(
    row: jnp.ndarray,
    rules: jnp.ndarray,
    deny_key: jnp.ndarray,
    acl: jnp.ndarray,
) -> jnp.ndarray:
    """Global first-match row -> count key (shared by every match impl).

    NO_MATCH rows land on the line's ACL's implicit-deny key, with
    out-of-range ACL ids clamped to the last ACL — the single definition
    of that fold, so the xla/pallas/pallas_fused epilogues cannot drift.
    """
    matched = row != NO_MATCH
    safe_row = jnp.where(matched, row, _U32(0))
    rule_key = rules[:, R_KEY].astype(_U32)[safe_row]
    deny = deny_key.astype(_U32)[
        jnp.minimum(acl, _U32(deny_key.shape[0] - 1))
    ]
    return jnp.where(matched, rule_key, deny)
