"""Streaming top-K talkers per ACL (BASELINE.json config #5).

Space-Saving / Misra-Gries is inherently sequential (each update may evict
the current minimum), so a literal port would serialize the TPU.  The
TPU-native shape is the standard "sketch + candidate heap" decomposition:

- device: a dedicated count-min sketch over (acl, src) pair hashes absorbs
  every line (mergeable, psum-able like any CMS); per chunk, ``lax.top_k``
  over the chunk's own CMS estimates surfaces the strongest candidates —
  all batched, no data-dependent control flow;
- host: a small :class:`TopKTracker` folds each chunk's candidates into a
  bounded per-ACL summary (evict-min, keep-max-estimate), the cheap
  sequential part that touches only ``k`` items per chunk.

Heavy hitters by definition recur across chunks, so candidates they miss in
one chunk they get in the next; the tracker's estimates come from the
global CMS, not per-chunk counts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .cms import cms_query, cms_update
from .hashing import fmix32, hash_pair

_U32 = jnp.uint32


#: Chunk-local candidate table size.  Far larger than any realistic k, so
#: within one chunk a heavy hitter rarely loses its slot to a collision;
#: across chunks the slot hash is re-salted (see ``salt``), so no pair of
#: talkers can collide persistently.
CAND_SLOTS = 1 << 15


def cand_slot(pair: jnp.ndarray, salt: jnp.ndarray | int, slots: int) -> jnp.ndarray:
    """Candidate-table slot of each (acl, src) pair hash.

    ONE definition shared by the scatter path below and the sorted
    segment-reduce path (ops/sorted_update.py): the two formulations must
    place every pair in the SAME slot or their selected candidates — and
    therefore reports — could diverge.
    """
    return fmix32(pair ^ jnp.asarray(salt, dtype=_U32)) & _U32(slots - 1)


def sample_cols(acl, src, valid, salt: jnp.ndarray | int, sample_shift: int):
    """Salt-rotated strided sample of the batch (candidate SELECTION only).

    Extracted from :func:`select_candidates` so the sorted formulation
    samples identically; see there for why the phase rotates with the
    chunk salt.  Degrades to the full batch when a shard is smaller than
    the stride (shapes are static, so this resolves at trace time).
    """
    if sample_shift and acl.shape[0] >= (1 << sample_shift):
        stride = 1 << sample_shift
        bs = (acl.shape[0] // stride) * stride
        phase = jnp.asarray(salt, dtype=_U32) % _U32(stride)

        def col(x):
            return jnp.take(x[:bs].reshape(-1, stride), phase, axis=1)

        return col(acl), col(src), col(valid)
    return acl, src, valid


def cand_k(k: int, b: int, sample_shift: int) -> int:
    """Static candidate count after sampling: min(k, sampled length)."""
    if sample_shift and b >= (1 << sample_shift):
        return min(k, b >> sample_shift)
    return min(k, b)


def select_from_tables(cnt, rep, acl, src, talk_cms, k: int):
    """Top-k selection over an already-built candidate table.

    ``cnt``/``rep`` are the per-slot frequency and representative-line
    tables (however they were built — batch-sized scatters or the sorted
    segment reduce); ``acl``/``src`` are the arrays ``rep``'s line
    indices point into.  Estimates come from the (merged) global talker
    CMS, so the host tracker's values stay chunk-order invariant.
    """
    with jax.named_scope("ra.topk"):
        top_cnt, top_slot = lax.top_k(cnt.astype(jnp.int32), k)
        rep_idx = rep[top_slot]
        safe = jnp.maximum(rep_idx, 0)
        ca, cs = acl[safe], src[safe]
        est = cms_query(talk_cms, hash_pair(ca, cs))
        ok = ((rep_idx >= 0) & (top_cnt > 0)).astype(_U32)
        return ca * ok, cs * ok, est * ok


def maybe_select(fn, salt: jnp.ndarray | int, topk_every: int, k: int):
    """Run candidate-producing ``fn`` on selection chunks only.

    ``topk_every > 1`` defers top-K candidate selection to every Nth
    chunk (Space-Saving spirit: heavy hitters recur, so a stride sample
    of CHUNKS still surfaces them while the talker CMS keeps absorbing
    every line).  Deterministic in the chunk salt — resume replays the
    same selection schedule — and skipped chunks yield est=0 candidates,
    which the host tracker ignores.  ``topk_every == 1`` is a straight
    call: the pre-existing single-knob HLO is untouched.
    """
    if topk_every <= 1:
        return fn(None)
    with jax.named_scope("ra.topk"):
        z = jnp.zeros(k, dtype=_U32)
        sel = jnp.asarray(salt, dtype=_U32) % _U32(topk_every) == _U32(0)
        return lax.cond(sel, fn, lambda _: (z, z, z), None)


def talker_chunk_update(
    talk_cms: jnp.ndarray,
    acl: jnp.ndarray,
    src: jnp.ndarray,
    valid: jnp.ndarray,
    k: int,
    salt: jnp.ndarray | int = 0,
    sample_shift: int = 0,
    topk_every: int = 1,
):
    """Absorb one chunk; return (new_cms, cand_acl, cand_src, cand_est).

    The candidate estimates are post-update global CMS estimates, masked to
    0 for suppressed/empty slots so they can never displace real candidates.
    ``salt`` re-randomizes the candidate table's slot assignment; stream
    drivers pass the chunk counter so collisions cannot persist across
    chunks while staying deterministic for checkpoint resume.

    ``sample_shift > 0`` selects candidates from every 2**shift-th line
    only.  The CMS update — and therefore every reported estimate — still
    covers the full batch; the sample only shrinks the candidate-table
    scatters (the scatter-bound share of the TPU step).  Deterministic:
    the stride is fixed, so resume replays identically.

    ``topk_every > 1`` additionally defers selection to every Nth chunk
    (see :func:`maybe_select`); the CMS still absorbs every chunk.
    """
    with jax.named_scope("ra.talk"):
        pair = hash_pair(acl, src)
        new_cms = cms_update(talk_cms, pair, valid)
    k1 = min(k, acl.shape[0])

    def _select(_):
        return select_candidates(
            new_cms, acl, src, valid, k1, salt=salt, sample_shift=sample_shift
        )

    cand = maybe_select(
        _select, salt, topk_every, cand_k(k1, acl.shape[0], sample_shift)
    )
    return (new_cms, *cand)


def select_candidates(talk_cms, acl, src, valid, k, slots: int = CAND_SLOTS,
                      salt: jnp.ndarray | int = 0, sample_shift: int = 0):
    """Top-k distinct (acl, src) candidates of this chunk.

    ``sample_shift > 0`` selects from 1/2**shift of the lines: the batch
    reshapes to [b', stride] rows and ONE column — rotated by ``salt`` so
    the phase differs every chunk — feeds the candidate table.  The
    rotation matters for grouped (stacked) layouts, where lines are
    group-major and a FIXED stride phase could alias entire ACL groups
    out of the sample forever; with rotation every line position is
    sampled within ``stride`` chunks, restoring the heavy-hitters-recur
    argument.  Estimates are untouched (they come from ``talk_cms``,
    which absorbed every line).

    A naive "dedup then top_k over the batch" costs a full argsort of the
    batch (the old implementation dominated the whole analysis step).
    Instead, pairs hash into a ``slots``-sized chunk-local table with two
    scatters — per-slot frequency (add) and a representative line index
    (max) — and ``top_k`` runs over the small table, not the batch:

      batch-sized work: 2 scatters + 1 hash  (vs argsort + scatter + top_k)
      table-sized work: one top_k over ``slots``

    Selection ranks by in-chunk frequency (Misra-Gries flavored); the
    reported estimate is the global post-update CMS estimate of each
    winner, so the host tracker's values stay chunk-order invariant.
    Distinct pairs colliding in a slot: the pair whose LAST occurrence in
    the chunk is later holds the representative (the max-line-index
    scatter), the other is suppressed — and the slot's rank is inflated
    by both pairs' counts.  The same two pairs collide in every chunk
    with the same ``salt``, which is why streaming callers pass a
    per-chunk salt: the suppressed pair surfaces under the next salt.
    """
    # A per-shard batch smaller than the stride would leave bs == 0 and
    # feed ZERO candidates every chunk — an empty talker report with no
    # warning.  Degrade to exact full-batch selection instead;
    # shapes are static so this resolves at trace time.
    with jax.named_scope("ra.topk"):
        acl, src, valid = sample_cols(acl, src, valid, salt, sample_shift)
        k = min(k, acl.shape[0])
        b = acl.shape[0]
        pair = hash_pair(acl, src)
        slot = cand_slot(pair, salt, slots)
        v32 = valid.astype(_U32)
        cnt = jnp.zeros(slots, dtype=_U32).at[slot].add(v32, mode="drop")
        iota = lax.broadcasted_iota(jnp.int32, (b,), 0)
        rep = (
            jnp.full(slots, -1, dtype=jnp.int32)
            .at[slot]
            .max(jnp.where(v32 > 0, iota, -1), mode="drop")
        )
    return select_from_tables(cnt, rep, acl, src, talk_cms, k)


class TopKTracker:
    """Host-side bounded per-ACL talker summary fed by chunk candidates."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._tables: dict[int, dict[int, int]] = {}

    def offer(self, acl: int, src: int, est: int) -> None:
        if est <= 0:
            return
        t = self._tables.setdefault(acl, {})
        if src in t:
            t[src] = max(t[src], est)
            return
        if len(t) < self.capacity:
            t[src] = est
            return
        victim = min(t, key=t.get)
        if est > t[victim]:
            del t[victim]
            t[src] = est

    def offer_chunk(self, cand_acl, cand_src, cand_est) -> None:
        for a, s, e in zip(cand_acl.tolist(), cand_src.tolist(), cand_est.tolist()):
            self.offer(int(a), int(s), int(e))

    def top(self, acl: int, k: int) -> list[tuple[int, int]]:
        t = self._tables.get(acl, {})
        # canonical tie order (estimate desc, then source asc): candidate
        # ARRIVAL order varies with the mesh world size (per-device top-k
        # slices), and reports must render identically across scale
        # events for the autoscale bit-identity law
        return sorted(t.items(), key=lambda kv: (-kv[1], kv[0]))[:k]

    def acls(self) -> list[int]:
        return list(self._tables)

    def tables(self) -> dict[int, dict[int, int]]:
        """Snapshot-serializable view of the per-ACL summaries."""
        return {acl: dict(t) for acl, t in self._tables.items()}
