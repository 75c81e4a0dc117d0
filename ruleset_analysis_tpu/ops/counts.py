"""Exact per-key hit counts — the reducer's sum, as a device scatter-add.

The reference reducer (SURVEY.md §4.4) sums sorted ``key\\t1`` pairs.  On
device this is one ``segment_sum`` of the valid mask over count keys.  To
stay exact past 2**32 lines without enabling x64 (which would slow every
uint32 op on TPU), totals are carried as a (lo, hi) uint32 pair with manual
carry propagation — per-chunk deltas are < 2**32 by construction, so
``carry = (new_lo < delta)`` detects wrap exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_U32 = jnp.uint32

# Every formulation traces under the ``ra.counts`` named scope, so HLO
# ops (and therefore profiler fusions) carry the stage label instead of
# an opaque ``fusion.N`` — the attribution substrate runtime/devprof.py
# classifies device time by (DESIGN §14).  Scopes are trace-time only:
# zero runtime cost, bit-identical outputs.


def segment_counts(keys: jnp.ndarray, weights: jnp.ndarray, n_keys: int) -> jnp.ndarray:
    """[B] keys + [B] uint32 weights -> [n_keys] uint32 per-key sums."""
    with jax.named_scope("ra.counts"):
        return jnp.zeros(n_keys, dtype=_U32).at[keys].add(
            weights.astype(_U32), mode="drop"
        )


def segment_counts_matmul(
    keys: jnp.ndarray, weights: jnp.ndarray, n_keys: int
) -> jnp.ndarray:
    """One-hot matmul formulation of :func:`segment_counts`.

    ``[B] f32 @ [B, n_keys] one-hot -> [n_keys]`` rides the MXU instead
    of issuing a batch-sized scatter — the committed TPU trace shows the
    scatter (fusion.5) at 9.2 ms/step while the MXU sits idle
    (DESIGN.md §8).  Exact because every product is 0/1 and per-key
    per-chunk sums are < 2^24 (f32 integer range): guarded at trace time,
    falling back to the scatter for pathological batch sizes.  Keys out
    of range contribute to no column (the one-hot row is all zero) —
    same semantics as the scatter's ``mode="drop"``.
    """
    if keys.shape[0] >= 1 << 24:
        return segment_counts(keys, weights, n_keys)
    with jax.named_scope("ra.counts"):
        iota = jnp.arange(n_keys, dtype=_U32)
        onehot = (keys[:, None] == iota[None, :]).astype(jnp.float32)
        return jnp.dot(weights.astype(jnp.float32), onehot).astype(_U32)


def segment_counts_reduce(
    keys: jnp.ndarray, weights: jnp.ndarray, n_keys: int
) -> jnp.ndarray:
    """Compare-and-reduce formulation: ``counts[k] = sum_b (keys==k)*w``.

    XLA fuses the compare into the reduction (reductions accept fused
    producers, dots do not), so nothing [B, K]-shaped materializes; all
    VPU, no scatter, no MXU.  ``AnalysisConfig.counts_impl`` selects the
    formulation; only the scatter runs in a benchmark cell.
    """
    with jax.named_scope("ra.counts"):
        iota = jnp.arange(n_keys, dtype=_U32)
        eq = keys[None, :] == iota[:, None]
        return jnp.sum(jnp.where(eq, weights.astype(_U32), 0), axis=1)


#: counts_impl name -> formulation (all bit-identical; see the stage bench)
SEGMENT_COUNTS_IMPLS = {
    "scatter": segment_counts,
    "matmul": segment_counts_matmul,
    "reduce": segment_counts_reduce,
}


def add64(lo: jnp.ndarray, hi: jnp.ndarray, delta: jnp.ndarray):
    """(lo, hi) uint32 pair += delta (uint32), exact 64-bit accumulation."""
    with jax.named_scope("ra.counts"):
        new_lo = lo + delta
        carry = (new_lo < delta).astype(_U32)
        return new_lo, hi + carry


def to_u64(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Host-side: recombine the pair into numpy uint64."""
    return np.asarray(hi, dtype=np.uint64) * np.uint64(1 << 32) + np.asarray(lo, dtype=np.uint64)
