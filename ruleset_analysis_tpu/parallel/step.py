"""The data-parallel analysis step: shard_map + explicit ICI collectives.

Per chunk, each device runs the single-device hot path on its batch shard
and produces *delta* registers from zero; the deltas then merge with one
collective each — ``psum`` for counts/CMS (addition is the merge law),
``pmax`` for HLL (max is the merge law) — and fold into the replicated
state.  This is the exact seam BASELINE.json's north star names: the
Hadoop shuffle/sort/merge replaced by two XLA collectives over ICI.

Integer adds are associative and commutative, so the merged state is
bit-identical to a single-device run over the concatenated batch — the
property tests/test_parallel.py asserts (SURVEY.md §5 "multi-node without
a cluster"), and what makes resume-by-re-merge idempotent.

shard_map (not GSPMD auto-sharding) because the collective placement here
is the design: scatter locally into small replicated registers, reduce the
registers — never all-gather the (huge) batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..config import AnalysisConfig
from ..models.pipeline import (
    AnalysisState, ChunkOut, DeviceRuleset, DeviceRuleset6,
    DeviceRulesetStacked, DeviceRulesetTenant, V6_ACL_TAG,
    batch_cols, batch_cols6,
)
from ..ops import cms as cms_ops
from ..ops import counts as count_ops
from ..ops import hll as hll_ops
from ..ops import topk as topk_ops
from ..ops.match import RULE_BLOCK, match_keys, match_keys_stacked
from ..runtime import devprof

_U32 = jnp.uint32


#: shard_map with replication checking off: the collectives are written
#: explicitly, so the checker adds nothing here
_shard_map = functools.partial(jax.shard_map, check_vma=False)


def _merge_tail(
    state: AnalysisState,
    keys: jax.Array,  # [b] u32 count keys, local shard
    valid: jax.Array,  # [b] u32 WEIGHT plane (0 = invalid; a coalesced
    #                    row's w counts as w raw lines — every update
    #                    below is weight-linear or idempotent, DESIGN §11)
    src: jax.Array,  # [b] u32
    acl: jax.Array,  # [b] u32
    salt: jax.Array,
    *,
    axis: str,
    n_keys: int,
    topk_k: int,
    exact_counts: bool,
    topk_sample_shift: int = 0,
    counts_delta: jax.Array | None = None,
    counts_impl: str = "scatter",
    update_impl: str = "scatter",
    topk_every: int = 1,
) -> tuple[AnalysisState, ChunkOut]:
    # The register-update tail shared by the flat and stacked shard steps:
    # mirrors pipeline._update_registers with the collective merges
    # interleaved at the law-of-merge seams (psum for adds, pmax for max);
    # tests/test_parallel.py pins it bit-identical to the single-device
    # step over the concatenated batch.

    # one globally-merged bincount feeds exact counts AND the per-rule CMS
    # (linear in per-key increments — see pipeline._update_registers);
    # the batch-sized CMS scatter this replaces dominated the shard step.
    # counts_delta: the fused pallas kernel already built the local
    # bincount in VMEM (ops/pallas_fused.py) — skip the batch-sized
    # scatter and merge its row-sized result instead.
    # Stage boundaries carry jax.named_scope labels (ra.counts/ra.cms/
    # ra.hll inside the ops; ra.talk/ra.merge here) so profiler fusions
    # attribute to semantic stages instead of fusion.N — the substrate
    # runtime/devprof.py classifies (DESIGN §14).  Trace-time only.
    #
    # update_impl="sorted": local deltas come from the sorted
    # segment-reduce formulations (ops/sorted_update.py, DESIGN §15);
    # the collective merge seams are IDENTICAL — only how each shard
    # builds its delta changes, so bit-identity to the scatter path
    # follows from per-shard value identity plus the same merges.
    if update_impl == "sorted":
        from ..ops import sorted_update as sorted_ops

        need = counts_delta is None and counts_impl == "scatter"
        sorted_delta, delta_hll = sorted_ops.counts_hll_sorted(
            jnp.zeros_like(state.hll), keys, valid, src, n_keys,
            need_counts=need,
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
        delta_hll = hll_ops.hll_update(
            jnp.zeros_like(state.hll), keys, src, valid
        )
    with jax.named_scope("ra.merge"):
        delta = lax.psum(counts_delta, axis)
    if exact_counts:
        lo, hi = count_ops.add64(state.counts_lo, state.counts_hi, delta)
    else:
        lo, hi = state.counts_lo, state.counts_hi
    cms = cms_ops.cms_update(state.cms, jnp.arange(n_keys, dtype=_U32), delta)

    with jax.named_scope("ra.merge"):
        hll = jnp.maximum(state.hll, lax.pmax(delta_hll, axis))

    dt, wt = state.talk_cms.shape
    if update_impl == "sorted":
        from ..ops import sorted_update as sorted_ops

        def _tables(sel):
            return sorted_ops.talker_tables_sorted(
                acl, src, valid, salt, width=wt, depth=dt,
                slots=topk_ops.CAND_SLOTS, sample_shift=topk_sample_shift,
                with_candidates=sel,
            )

        if topk_every > 1:
            delta_talk, cnt, rep = lax.cond(
                salt % _U32(topk_every) == _U32(0),
                lambda _: _tables(True),
                lambda _: _tables(False),
                None,
            )
        else:
            delta_talk, cnt, rep = _tables(True)
        with jax.named_scope("ra.merge"):
            talk_cms = state.talk_cms + lax.psum(delta_talk, axis)
        s_acl, s_src, _sv = topk_ops.sample_cols(
            acl, src, valid, salt, topk_sample_shift
        )
        ca, cs, ce = topk_ops.select_from_tables(
            cnt, rep, s_acl, s_src, talk_cms,
            min(topk_k, s_acl.shape[0]),
        )
    else:
        with jax.named_scope("ra.talk"):
            delta_talk = cms_ops.cms_update(
                jnp.zeros((dt, wt), _U32), topk_ops.hash_pair(acl, src), valid
            )
        with jax.named_scope("ra.merge"):
            talk_cms = state.talk_cms + lax.psum(delta_talk, axis)
        # candidate selection against the *merged* global talker sketch,
        # then gather every device's candidates so the host sees them all,
        # replicated (sample_shift: salt-rotated sampled selection — the
        # sketch covered every line above; see ops.topk.select_candidates;
        # topk_every: deferred selection, ops.topk.maybe_select)
        k1 = min(topk_k, valid.shape[0])
        ca, cs, ce = topk_ops.maybe_select(
            lambda _: topk_ops.select_candidates(
                talk_cms, acl, src, valid, k1,
                salt=salt, sample_shift=topk_sample_shift,
            ),
            salt, topk_every,
            topk_ops.cand_k(k1, valid.shape[0], topk_sample_shift),
        )
    with jax.named_scope("ra.merge"):
        cand_acl = lax.all_gather(ca, axis, tiled=True)
        cand_src = lax.all_gather(cs, axis, tiled=True)
        cand_est = lax.all_gather(ce, axis, tiled=True)

    return (
        AnalysisState(counts_lo=lo, counts_hi=hi, cms=cms, hll=hll, talk_cms=talk_cms),
        ChunkOut(cand_acl=cand_acl, cand_src=cand_src, cand_est=cand_est),
    )


def _core_flat(
    state: AnalysisState,
    ruleset: DeviceRuleset,
    cols: dict,  # unpacked field columns (batch_cols)
    valid: jax.Array,  # [b] u32 weight plane
    salt: jax.Array,  # u32 scalar (chunk counter), replicated
    *,
    axis: str,
    n_keys: int,
    topk_k: int,
    exact_counts: bool,
    rule_block: int,
    match_impl: str = "xla",
    topk_sample_shift: int = 0,
    counts_impl: str = "scatter",
    update_impl: str = "scatter",
    topk_every: int = 1,
) -> tuple[AnalysisState, ChunkOut]:
    # The post-unpack body of the flat shard step.  Split from the
    # batch unpack so the static lint plane (verify/, DESIGN §18) can
    # trace the SHIPPING program with the weight plane as an explicit
    # jaxpr input — the taint source of the weight-linearity proof —
    # instead of a slice of the packed batch.  One definition: the real
    # step and the linter trace this exact function.
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
    return _merge_tail(
        state, keys, valid, cols["src"], cols["acl"], salt,
        axis=axis, n_keys=n_keys, topk_k=topk_k, exact_counts=exact_counts,
        topk_sample_shift=topk_sample_shift, counts_delta=counts_delta,
        counts_impl=counts_impl, update_impl=update_impl,
        topk_every=topk_every,
    )


def _local_shard_step(
    state: AnalysisState,
    ruleset: DeviceRuleset,
    batch: jax.Array,  # [TUPLE_COLS or WIRE_COLS, B/n] local shard
    salt: jax.Array,  # u32 scalar (chunk counter), replicated
    **kw,
) -> tuple[AnalysisState, ChunkOut]:
    cols, valid = batch_cols(batch)
    return _core_flat(state, ruleset, cols, valid, salt, **kw)


def _core_stacked(
    state: AnalysisState,
    ruleset: DeviceRulesetStacked,
    cols: dict,  # grouped field columns [G, lane/n]
    valid: jax.Array,  # [G, lane/n] u32 weight plane
    salt: jax.Array,
    *,
    axis: str,
    n_keys: int,
    topk_k: int,
    exact_counts: bool,
    rule_block: int,
    topk_sample_shift: int = 0,
    counts_impl: str = "scatter",
    update_impl: str = "scatter",
    topk_every: int = 1,
) -> tuple[AnalysisState, ChunkOut]:
    # Grouped twin of _core_flat: each line scans only its own ACL's
    # slab (vmapped match over the group axis); the mergeable register
    # tail — and therefore the final report — is identical.
    keys = match_keys_stacked(cols, ruleset.rules3d, ruleset.deny_key, rule_block).reshape(-1)
    return _merge_tail(
        state,
        keys,
        valid.reshape(-1),
        cols["src"].reshape(-1),
        cols["acl"].reshape(-1),
        salt,
        axis=axis,
        n_keys=n_keys,
        topk_k=topk_k,
        exact_counts=exact_counts,
        topk_sample_shift=topk_sample_shift,
        counts_impl=counts_impl,
        update_impl=update_impl,
        topk_every=topk_every,
    )


def _local_shard_step_stacked(
    state: AnalysisState,
    ruleset: DeviceRulesetStacked,
    batch: jax.Array,  # [G, TUPLE_COLS or WIRE_COLS, lane/n] local shard
    salt: jax.Array,
    **kw,
) -> tuple[AnalysisState, ChunkOut]:
    cols, valid = batch_cols(batch)
    return _core_stacked(state, ruleset, cols, valid, salt, **kw)


def _core6(
    state: AnalysisState,
    ruleset6: DeviceRuleset6,
    cols: dict,  # unpacked v6 field columns (batch_cols6)
    valid: jax.Array,  # [b] u32 weight plane
    salt: jax.Array,
    *,
    axis: str,
    n_keys: int,
    topk_k: int,
    exact_counts: bool,
    rule_block: int,
    topk_sample_shift: int = 0,
    counts_impl: str = "scatter",
    update_impl: str = "scatter",
    topk_every: int = 1,
) -> tuple[AnalysisState, ChunkOut]:
    # IPv6 twin of _core_flat: lexicographic limb match, then the SAME
    # mergeable register tail into the shared key universe.  Source
    # identity for HLL/talkers is the 32-bit limb digest; the talker ACL
    # gid carries V6_ACL_TAG so digests never merge with v4 addresses.
    from ..ops.match6 import fold_src32, match_keys6

    keys = match_keys6(cols, ruleset6.rules6, ruleset6.deny_key, rule_block)
    return _merge_tail(
        state, keys, valid, fold_src32(cols),
        cols["acl"] | jnp.uint32(V6_ACL_TAG), salt,
        axis=axis, n_keys=n_keys, topk_k=topk_k, exact_counts=exact_counts,
        topk_sample_shift=topk_sample_shift, counts_impl=counts_impl,
        update_impl=update_impl, topk_every=topk_every,
    )


def _local_shard_step6(
    state: AnalysisState,
    ruleset6: DeviceRuleset6,
    batch: jax.Array,  # [TUPLE6_COLS, B6/n] local shard
    salt: jax.Array,
    **kw,
) -> tuple[AnalysisState, ChunkOut]:
    cols, valid = batch_cols6(batch)
    return _core6(state, ruleset6, cols, valid, salt, **kw)


def _core_tenant(
    state: AnalysisState,  # leaves carry a leading [T] tenant axis
    ruleset: DeviceRulesetTenant,
    cols: dict,  # unpacked field columns (batch_cols) — ONE tenant's lines
    valid: jax.Array,  # [b] u32 weight plane
    tid: jax.Array,  # i32 scalar tenant index into the bucket stack
    salt: jax.Array,  # u32 scalar (per-tenant chunk counter), replicated
    *,
    axis: str,
    n_keys: int,  # the BUCKET's padded key universe (R_pad + A_pad)
    topk_k: int,
    exact_counts: bool,
    rule_block: int,
    topk_sample_shift: int = 0,
    counts_impl: str = "scatter",
    update_impl: str = "scatter",
    topk_every: int = 1,
) -> tuple[AnalysisState, ChunkOut]:
    # Tenant-sliced twin of _core_flat (ISSUE 16): every register plane
    # carries a leading tenant axis; the step dynamically slices tenant
    # `tid`'s plane + rule tensor out of the bucket stack, runs the
    # UNCHANGED flat core on it, and scatters the plane back.  The merge
    # laws are untouched (the collectives act on the sliced plane), so a
    # tenant's slice evolves bit-identically to a solo run with the same
    # chunk boundaries and salts — the tenancy property test pins it.
    # dynamic_slice is not a scope-required primitive in the jaxpr lint
    # plane, and the weight plane threads through _core_flat verbatim,
    # so the tenant programs prove weight-linear exactly like flat ones.
    with jax.named_scope("ra.tenant_slice"):
        rules = lax.dynamic_index_in_dim(ruleset.rules_t, tid, 0, keepdims=False)
        deny = lax.dynamic_index_in_dim(ruleset.deny_key_t, tid, 0, keepdims=False)
        plane = AnalysisState(*(
            lax.dynamic_index_in_dim(x, tid, 0, keepdims=False) for x in state
        ))
    plane, out = _core_flat(
        plane, DeviceRuleset(rules=rules, deny_key=deny, rules_fm=None),
        cols, valid, salt,
        axis=axis, n_keys=n_keys, topk_k=topk_k, exact_counts=exact_counts,
        rule_block=rule_block, match_impl="xla",
        topk_sample_shift=topk_sample_shift, counts_impl=counts_impl,
        update_impl=update_impl, topk_every=topk_every,
    )
    with jax.named_scope("ra.tenant_unslice"):
        new_state = AnalysisState(*(
            lax.dynamic_update_index_in_dim(big, small, tid, 0)
            for big, small in zip(state, plane)
        ))
    return new_state, out


def _local_shard_step_tenant(
    state: AnalysisState,
    ruleset: DeviceRulesetTenant,
    batch: jax.Array,  # [TUPLE_COLS or WIRE_COLS, B/n] local shard
    tid: jax.Array,  # i32 scalar, replicated
    salt: jax.Array,  # u32 scalar, replicated
    **kw,
) -> tuple[AnalysisState, ChunkOut]:
    cols, valid = batch_cols(batch)
    return _core_tenant(state, ruleset, cols, valid, tid, salt, **kw)


#: Post-unpack shard-step bodies by program kind — what the static lint
#: plane traces (verify/grid.py).  The shipping steps above are thin
#: unpack wrappers around exactly these functions, so a lint verdict on
#: a core IS a verdict on the shipping program.
CORES = {
    "flat": _core_flat,
    "stacked": _core_stacked,
    "v6": _core6,
    "tenant": _core_tenant,
}


#: Bake the rule tensor into the compiled step as an XLA constant when it
#: is at most this many bytes.  The ruleset is fixed for a whole stream,
#: and constant rules let XLA specialize the [B, R] predicate evaluation.
#: In the step on the TPU in round 5 the two paths measured 1.00x (DESIGN
#: §8); every benchmark cell runs this one, and the generic argument path
#: none.  Above the threshold the generic argument path
#: keeps compile time and HLO size bounded for pathological rulesets.
RULES_CONST_MAX_BYTES = 8 << 20


def _rules_nbytes(ruleset) -> int:
    return sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(ruleset)
    )


#: Distinct specialized executables kept per step builder.  Real drivers
#: use one ruleset per stream; the bound only guards against a caller that
#: cycles many DIFFERENT rulesets through one step (each executable pins
#: its baked-in rules, so an unbounded cache would leak).
_SPECIALIZED_CACHE_MAX = 4


def _make_step(mesh: Mesh, local, batch_spec, label: str = "step"):
    """Shared builder: ruleset-specialized jits with a generic fallback.

    Returns ``step(state, ruleset, batch, salt)``.  For each distinct
    (small) ruleset VALUE, a jit closing over the ruleset is built once
    and cached — the rule tensor compiles as an XLA constant.  The cache
    is two-level: object identity first (zero-cost for the normal
    one-ruleset stream), then a content fingerprint — so a caller that
    re-ships an equal-valued ruleset per call pays one hash, never a
    recompile.  Oversized rulesets fall back to one generic jit with the
    ruleset as a traced argument (the pre-round-4 behavior).  Results are
    bit-identical either way; only specialization differs.

    Every dispatch passes through the device attribution plane's seam
    (``devprof.active_capture()``): disarmed cost is one module-global
    None-check; armed, the capture window counts dispatches, brackets
    the ``jax.profiler`` trace, and remembers each program's jit +
    abstract arguments so its optimized HLO can be re-derived for
    semantic attribution (runtime/devprof.py, DESIGN §14).  ``label``
    names the program (``step.flat`` / ``step.v6`` / ``step.stacked``)
    in the capture summary.
    """
    generic = None
    by_id: dict[tuple, tuple] = {}  # id-key -> (fingerprint, pinned leaves)
    by_value: dict[str, object] = {}

    def _fingerprint(ruleset) -> str:
        import hashlib

        h = hashlib.sha1()
        for x in jax.tree_util.tree_leaves(ruleset):
            h.update(str(x.shape).encode())
            h.update(np.asarray(x).tobytes())
        return h.hexdigest()

    def step(state, ruleset, batch, salt: int | jax.Array = 0):
        nonlocal generic
        salt = jnp.asarray(salt, dtype=_U32)
        if _rules_nbytes(ruleset) <= RULES_CONST_MAX_BYTES:
            leaves = jax.tree_util.tree_leaves(ruleset)
            id_key = tuple(id(x) for x in leaves)
            hit = by_id.get(id_key)
            if hit is not None:
                fp = hit[0]
            else:
                fp = _fingerprint(ruleset)
                if len(by_id) >= 4 * _SPECIALIZED_CACHE_MAX:
                    by_id.clear()
                # keep the leaves alive alongside the entry: a freed array's
                # id can be recycled by a NEW array, and a stale id->fp hit
                # would silently run the wrong baked-in rules
                by_id[id_key] = (fp, leaves)
            fn = by_value.get(fp)
            if fn is None:
                sharded = _shard_map(
                    lambda st, b, s: local(st, ruleset, b, s),
                    mesh=mesh,
                    in_specs=(P(), batch_spec, P()),
                    out_specs=(P(), P()),
                )
                fn = jax.jit(sharded, donate_argnums=(0,))
                if len(by_value) >= _SPECIALIZED_CACHE_MAX:
                    by_value.pop(next(iter(by_value)))  # evict oldest
                by_value[fp] = fn
            cap = devprof.active_capture()
            if cap is not None:
                return cap.dispatch(label, fn, (state, batch, salt))
            return fn(state, batch, salt)
        if generic is None:
            sharded = _shard_map(
                local,
                mesh=mesh,
                in_specs=(P(), P(), batch_spec, P()),
                out_specs=(P(), P()),
            )
            generic = jax.jit(sharded, donate_argnums=(0,))
        cap = devprof.active_capture()
        if cap is not None:
            return cap.dispatch(label, generic, (state, ruleset, batch, salt))
        return generic(state, ruleset, batch, salt)

    return step


_LOCALS = {
    "flat": _local_shard_step,
    "v6": _local_shard_step6,
    "stacked": _local_shard_step_stacked,
}


def _mesh_axes(mesh: Mesh):
    """Collective/batch axes of ``mesh`` (mesh.data_axes, deferred import:
    parallel/mesh.py imports the runtime fault registry at module load).

    The flat topology contributes its single data axis; the hybrid
    DCN x ICI topology contributes the ``("dcn", data)`` tuple —
    ``lax.psum``/``pmax``/``all_gather`` and ``PartitionSpec`` all
    accept the tuple form, and reducing over both axes is
    associative-identical to the flat reduction over the same devices
    (the bit-identity tests pin it).
    """
    from .mesh import data_axes

    return data_axes(mesh)


@functools.lru_cache(maxsize=16)
def _cached_step(
    kind: str,
    mesh: Mesh,
    axis: str,
    n_keys: int,
    topk_k: int,
    exact_counts: bool,
    rule_block: int,
    match_impl: str | None,
    topk_sample_shift: int,
    counts_impl: str,
    update_impl: str,
    topk_every: int,
):
    """Step builders memoized on their full geometry.

    Every driver run builds its step through here, so a second run with
    the same (mesh, config geometry) in the same process gets the SAME
    step closure back — and therefore hits the jit executable cache
    instead of re-tracing and re-compiling.  This is what makes the
    warm-up passes of ``benchmark/`` and ``chip_smoke.py`` actually
    measure steady state: a fresh closure per run would recompile even
    with identical shapes.  Keyed values are all hashable scalars plus
    the Mesh (hashable by devices + axis names); maxsize bounds the
    specialized-jit pyramids kept alive.
    """
    kwargs = dict(
        axis=axis,
        n_keys=n_keys,
        topk_k=topk_k,
        exact_counts=exact_counts,
        rule_block=rule_block,
        topk_sample_shift=topk_sample_shift,
        counts_impl=counts_impl,
        update_impl=update_impl,
        topk_every=topk_every,
    )
    if match_impl is not None:
        kwargs["match_impl"] = match_impl
    local = functools.partial(_LOCALS[kind], **kwargs)
    spec = P(None, None, axis) if kind == "stacked" else P(None, axis)
    return _make_step(mesh, local, spec, label=f"step.{kind}")


def _warn_experimental_match(match_impl: str) -> None:
    if match_impl == "pallas_fused":
        import sys

        print(
            "WARNING: EXPERIMENTAL match_impl='pallas_fused' enabled — "
            "measured 0.083x vs the default XLA step on TPU (VERDICT r5); "
            "this is a bench/research kernel, not a production path.",
            file=sys.stderr,
            flush=True,
        )


def make_parallel_step(
    mesh: Mesh,
    cfg: AnalysisConfig,
    n_keys: int,
    rule_block: int = RULE_BLOCK,
):
    """Build the jitted data-parallel step for `mesh`.

    state/ruleset replicated, batch sharded on the data axis; the returned
    state and candidates are replicated (identical on every device).
    """
    _warn_experimental_match(cfg.match_impl)
    return _cached_step(
        "flat",
        mesh,
        _mesh_axes(mesh),
        n_keys,
        cfg.sketch.topk_chunk_candidates,
        cfg.exact_counts,
        rule_block,
        cfg.match_impl,
        cfg.sketch.topk_sample_shift,
        cfg.counts_impl,
        cfg.update_impl,
        cfg.sketch.topk_every,
    )


def make_parallel_step6(
    mesh: Mesh,
    cfg: AnalysisConfig,
    n_keys: int,
    rule_block: int = RULE_BLOCK,
):
    """Build the jitted data-parallel IPv6 step for `mesh`.

    Same sharding contract as :func:`make_parallel_step`: state/ruleset
    replicated, v6 batch sharded on the data axis, merged registers and
    candidates replicated.  The v6 and v4 steps update ONE shared state,
    so the driver may interleave them freely (mergeable registers).
    """
    return _cached_step(
        "v6",
        mesh,
        _mesh_axes(mesh),
        n_keys,
        cfg.sketch.topk_chunk_candidates,
        cfg.exact_counts,
        rule_block,
        None,
        cfg.sketch.topk_sample_shift,
        cfg.counts_impl,
        cfg.update_impl,
        cfg.sketch.topk_every,
    )


@functools.lru_cache(maxsize=16)
def _cached_tenant_step(
    mesh: Mesh,
    axis,
    n_keys: int,
    topk_k: int,
    exact_counts: bool,
    rule_block: int,
    topk_sample_shift: int,
    counts_impl: str,
    update_impl: str,
    topk_every: int,
):
    kwargs = dict(
        axis=axis,
        n_keys=n_keys,
        topk_k=topk_k,
        exact_counts=exact_counts,
        rule_block=rule_block,
        topk_sample_shift=topk_sample_shift,
        counts_impl=counts_impl,
        update_impl=update_impl,
        topk_every=topk_every,
    )
    local = functools.partial(_local_shard_step_tenant, **kwargs)
    sharded = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(None, axis), P(), P()),
        out_specs=(P(), P()),
    )
    jfn = jax.jit(sharded, donate_argnums=(0,))

    def step(state, ruleset, batch, tid: int | jax.Array, salt: int | jax.Array = 0):
        tid = jnp.asarray(tid, dtype=jnp.int32)
        salt = jnp.asarray(salt, dtype=_U32)
        cap = devprof.active_capture()
        if cap is not None:
            return cap.dispatch(
                "step.tenant", jfn, (state, ruleset, batch, tid, salt)
            )
        return jfn(state, ruleset, batch, tid, salt)

    return step


def make_tenant_step(
    mesh: Mesh,
    cfg: AnalysisConfig,
    n_keys: int,
    rule_block: int = RULE_BLOCK,
):
    """Build the jitted multi-tenant step for `mesh` (one packing bucket).

    ``step(state, ruleset, batch, tid, salt)``: tenant-stacked state and
    rule tensors replicated, ONE tenant's batch sharded on the data axis,
    the tenant index ``tid`` a traced scalar.  Deliberately NEVER
    ruleset-specialized (unlike :func:`_make_step`): the rule stack is a
    traced argument, so hot-reloading one tenant — a value change in one
    slice of the stack — reuses the same executable.  Constant-baking
    would force a full recompile of the shared program on every
    single-tenant reload, stalling every other tenant in the bucket,
    which is exactly the isolation guarantee the tenancy plane makes.
    Results are bit-identical either way (see _make_step docstring).
    """
    return _cached_tenant_step(
        mesh,
        _mesh_axes(mesh),
        n_keys,
        cfg.sketch.topk_chunk_candidates,
        cfg.exact_counts,
        rule_block,
        cfg.sketch.topk_sample_shift,
        cfg.counts_impl,
        cfg.update_impl,
        cfg.sketch.topk_every,
    )


def make_parallel_step_stacked(
    mesh: Mesh,
    cfg: AnalysisConfig,
    n_keys: int,
    rule_block: int = RULE_BLOCK,
):
    """Build the jitted data-parallel STACKED step for `mesh`.

    The grouped batch ``[G, TUPLE_COLS, lane]`` shards along the lane
    (per-group line) axis — every device holds a slice of every ACL's
    bucket plus the full (replicated) slab tensor, so the match needs no
    rule-side communication and the register merges are the same two
    collectives as the flat path.  ``lane`` must divide by the mesh size.
    """
    return _cached_step(
        "stacked",
        mesh,
        _mesh_axes(mesh),
        n_keys,
        cfg.sketch.topk_chunk_candidates,
        cfg.exact_counts,
        rule_block,
        None,
        cfg.sketch.topk_sample_shift,
        cfg.counts_impl,
        cfg.update_impl,
        cfg.sketch.topk_every,
    )
