"""Shipping step-program grid + abstract tracing (no device, no compile).

Every lintable program is one of the post-unpack shard-step cores
(``parallel/step.py::CORES`` — the SAME functions the shipping steps
call after ``batch_cols``), wrapped in a one-device ``shard_map`` so the
collective merge seams (``psum``/``pmax``/``all_gather``) trace as
explicit primitives, and traced with ``jax.make_jaxpr`` over
``ShapeDtypeStruct`` arguments: abstract eval only — no device buffer
is created and no XLA compile runs, which is what keeps the whole grid
under the ``make lint`` budget on a 1-core host.

The weight plane enters the wrapper as its OWN argument (the cores were
split from the unpack for exactly this), so the taint walk in
:mod:`.jaxpr_lint` can seed taint at a top-level jaxpr invar instead of
chasing a slice of the packed batch.

Grid membership is derived from :class:`~..config.AnalysisConfig`
validation itself: a combination the config refuses at construction
time is not a shipping program and is skipped — so when a future PR
adds or retires an impl axis, the grid follows automatically.
"""

from __future__ import annotations

import dataclasses
import functools

#: Small abstract geometry for lint traces.  Verdicts are structural
#: (which primitives, which operands, which scopes), not shape-
#: dependent, so a small geometry proves the same program shape the
#: production sizes run — while keeping ~100 traces cheap.
LINT_GEOMETRY = dict(
    batch=256,  # lines per shard
    rules=128,  # v4 ACE rows (== one RULE_BLOCK / RULE_TILE multiple)
    rules6=128,  # v6 ACE rows
    n_keys=16,  # count-key universe
    n_acls=4,
    cms_depth=2,
    cms_width=256,
    hll_m=16,
    topk_k=8,
    groups=2,  # stacked: ACL groups
    lane=128,  # stacked: per-group lane width
    tenants=4,  # tenant: bucket stack depth (leading register axis)
)


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One shipping step-program coordinate in the impl grid."""

    kind: str  # {"flat", "stacked", "v6", "tenant"}
    match_impl: str = "xla"
    counts_impl: str = "scatter"
    update_impl: str = "scatter"
    topk_every: int = 1
    topk_sample_shift: int = 0
    exact_counts: bool = True

    @property
    def name(self) -> str:
        parts = [self.kind, self.match_impl, self.counts_impl, self.update_impl]
        if self.topk_every != 1:
            parts.append(f"te{self.topk_every}")
        if self.topk_sample_shift:
            parts.append(f"ss{self.topk_sample_shift}")
        if not self.exact_counts:
            parts.append("noexact")
        return "step." + ",".join(parts)

    def config_kwargs(self) -> dict:
        """AnalysisConfig kwargs naming this combination (validation)."""
        from ..config import SketchConfig

        return dict(
            match_impl=self.match_impl if self.kind == "flat" else "xla",
            counts_impl=self.counts_impl,
            update_impl=self.update_impl,
            layout="stacked" if self.kind == "stacked" else "flat",
            sketch=SketchConfig(
                topk_every=self.topk_every,
                topk_sample_shift=self.topk_sample_shift,
                cms_depth=LINT_GEOMETRY["cms_depth"],
                cms_width=LINT_GEOMETRY["cms_width"],
                talk_cms_depth=LINT_GEOMETRY["cms_depth"],
            ),
            exact_counts=self.exact_counts,
        )

    def is_shipping(self) -> bool:
        """True iff AnalysisConfig accepts this combination."""
        from ..config import AnalysisConfig

        try:
            AnalysisConfig(**self.config_kwargs())
        except ValueError:
            return False
        return True


#: (topk_every, topk_sample_shift) variants traced per impl combination:
#: the plain path, the deferred-selection cond path, and the sampled-
#: selection path — each changes which candidate-table program traces.
_TOPK_VARIANTS = ((1, 0), (4, 0), (1, 2))


def shipping_grid() -> list[ProgramSpec]:
    """Every shipping step program: the full impl grid, all kinds."""
    specs: list[ProgramSpec] = []
    for kind in ("flat", "stacked", "v6", "tenant"):
        match_impls = (
            ("xla", "pallas", "pallas_fused") if kind == "flat" else ("xla",)
        )
        for mi in match_impls:
            for ci in ("scatter", "matmul", "reduce"):
                for ui in ("scatter", "sorted"):
                    for te, ss in _TOPK_VARIANTS:
                        s = ProgramSpec(
                            kind=kind, match_impl=mi, counts_impl=ci,
                            update_impl=ui, topk_every=te,
                            topk_sample_shift=ss,
                        )
                        if s.is_shipping():
                            specs.append(s)
    # the no-exact-counts mode drops the counts registers' merge seam by
    # design — one representative program pins the linter's exemption
    specs.append(ProgramSpec(kind="flat", exact_counts=False))
    return specs


def fast_grid() -> list[ProgramSpec]:
    """Tier-1 subset: every verdict class and every check dimension at
    least once (one program per distinct structure family)."""
    return [
        ProgramSpec(kind="flat"),
        ProgramSpec(kind="flat", update_impl="sorted", topk_every=4),
        ProgramSpec(kind="flat", counts_impl="matmul"),
        ProgramSpec(kind="flat", counts_impl="reduce", update_impl="sorted"),
        ProgramSpec(kind="flat", match_impl="pallas"),
        ProgramSpec(kind="flat", match_impl="pallas_fused"),
        ProgramSpec(kind="stacked", topk_sample_shift=2),
        ProgramSpec(kind="v6", update_impl="sorted"),
        ProgramSpec(kind="flat", exact_counts=False),
        # tenant-sliced register planes: dynamic slice/update around the
        # flat core — one program pins the wrapper's lint verdict
        ProgramSpec(kind="tenant"),
    ]


@dataclasses.dataclass(frozen=True)
class TracedProgram:
    """One traced program: the closed jaxpr + lint bookkeeping."""

    spec: ProgramSpec
    closed_jaxpr: object  # jax.core.ClosedJaxpr
    weight_invar_index: int  # flat index of the weight plane input
    output_names: tuple[str, ...]  # flatten order of (state, chunk_out)


#: Flatten order of the step outputs (AnalysisState, ChunkOut) — the
#: NamedTuple field order, pinned here so the merge-law table in
#: jaxpr_lint addresses outputs by name.
OUTPUT_NAMES = (
    "counts_lo", "counts_hi", "cms", "hll", "talk_cms",
    "cand_acl", "cand_src", "cand_est",
)

_V4_FIELDS = ("acl", "proto", "src", "sport", "dst", "dport")


def _sds(shape, dtype=None):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(tuple(shape), dtype or jnp.uint32)


def _abstract_args(spec: ProgramSpec):
    """(state, ruleset, cols, valid[, tid], salt) ShapeDtypeStructs for
    `spec` — the weight plane (``valid``) is ALWAYS args[3], which is
    what trace_program's marker flatten relies on."""
    from ..hostside.pack import RULE6_COLS, RULE_COLS
    from ..models.pipeline import (
        AnalysisState, DeviceRuleset, DeviceRuleset6, DeviceRulesetStacked,
        DeviceRulesetTenant,
    )

    g = LINT_GEOMETRY
    state = AnalysisState(
        counts_lo=_sds((g["n_keys"],)),
        counts_hi=_sds((g["n_keys"],)),
        cms=_sds((g["cms_depth"], g["cms_width"])),
        hll=_sds((g["n_keys"], g["hll_m"])),
        talk_cms=_sds((g["cms_depth"], g["cms_width"])),
    )
    salt = _sds(())
    if spec.kind == "tenant":
        import jax.numpy as jnp

        t = g["tenants"]
        state = AnalysisState(
            counts_lo=_sds((t, g["n_keys"])),
            counts_hi=_sds((t, g["n_keys"])),
            cms=_sds((t, g["cms_depth"], g["cms_width"])),
            hll=_sds((t, g["n_keys"], g["hll_m"])),
            talk_cms=_sds((t, g["cms_depth"], g["cms_width"])),
        )
        ruleset = DeviceRulesetTenant(
            rules_t=_sds((t, g["rules"], RULE_COLS)),
            deny_key_t=_sds((t, g["n_acls"])),
        )
        cols = {k: _sds((g["batch"],)) for k in _V4_FIELDS}
        valid = _sds((g["batch"],))
        tid = _sds((), jnp.int32)
        return state, ruleset, cols, valid, tid, salt
    if spec.kind == "flat":
        rules_fm = (
            _sds((RULE_COLS, g["rules"]))
            if spec.match_impl in ("pallas", "pallas_fused")
            else None
        )
        ruleset = DeviceRuleset(
            rules=_sds((g["rules"], RULE_COLS)),
            deny_key=_sds((g["n_acls"],)),
            rules_fm=rules_fm,
        )
        cols = {k: _sds((g["batch"],)) for k in _V4_FIELDS}
        valid = _sds((g["batch"],))
    elif spec.kind == "stacked":
        ruleset = DeviceRulesetStacked(
            rules3d=_sds((g["groups"], g["rules"], RULE_COLS)),
            deny_key=_sds((g["n_acls"],)),
        )
        cols = {k: _sds((g["groups"], g["lane"])) for k in _V4_FIELDS}
        valid = _sds((g["groups"], g["lane"]))
    elif spec.kind == "v6":
        ruleset = DeviceRuleset6(
            rules6=_sds((g["rules6"], RULE6_COLS)),
            deny_key=_sds((g["n_acls"],)),
        )
        cols = {k: _sds((g["batch"],)) for k in ("acl", "proto", "sport", "dport")}
        for i in range(4):
            cols[f"src{i}"] = _sds((g["batch"],))
            cols[f"dst{i}"] = _sds((g["batch"],))
        valid = _sds((g["batch"],))
    else:
        raise ValueError(f"unknown program kind {spec.kind!r}")
    return state, ruleset, cols, valid, salt


def _core_kwargs(spec: ProgramSpec) -> dict:
    g = LINT_GEOMETRY
    kw = dict(
        axis="data",
        n_keys=g["n_keys"],
        topk_k=g["topk_k"],
        exact_counts=spec.exact_counts,
        rule_block=g["rules"],
        topk_sample_shift=spec.topk_sample_shift,
        counts_impl=spec.counts_impl,
        update_impl=spec.update_impl,
        topk_every=spec.topk_every,
    )
    if spec.kind == "flat":
        kw["match_impl"] = spec.match_impl
    # the tenant core runs _core_flat on the sliced plane with the
    # backend's scan (models.pipeline.flat_match; make_tenant_step never
    # specializes); no extra kwarg
    return kw


@dataclasses.dataclass(frozen=True)
class FixtureSpec:
    """Spec stand-in for hand-built mini-programs (negative fixtures)."""

    name: str
    exact_counts: bool = True


def trace_fixture(
    fn,
    args,
    weight_arg: int,
    output_names: tuple[str, ...],
    name: str = "fixture",
) -> TracedProgram:
    """Trace an arbitrary mini-program through the SAME one-device
    shard_map wrapper the shipping grid uses.

    The negative-fixture harness (tests/test_ralint.py): deliberately
    broken programs — nonlinear weight use, ``indices_are_sorted``
    without a sort, a missing ``ra.*`` scope, a wrong merge law — go
    through this exact door, so a fixture the linter misses is a real
    false negative, not a harness artifact.  ``args[weight_arg]`` must
    be a single array (the taint seed).
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..parallel.step import _shard_map

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    wrapped = _shard_map(
        fn, mesh=mesh, in_specs=(P(),) * len(args), out_specs=P(),
    )
    closed = jax.make_jaxpr(wrapped)(*args)
    markers = list(jax.tree_util.tree_map(lambda _: False, tuple(args)))
    markers[weight_arg] = True
    flat, _ = jax.tree_util.tree_flatten(tuple(markers))
    widx = flat.index(True)
    return TracedProgram(
        spec=FixtureSpec(name=name),
        closed_jaxpr=closed,
        weight_invar_index=widx,
        output_names=tuple(output_names),
    )


def trace_program(spec: ProgramSpec) -> TracedProgram:
    """Trace one shipping program to a closed jaxpr by abstract eval.

    The wrapper is ``shard_map(core)`` over a one-device mesh: real
    enough that the collectives trace as primitives bound to the data
    axis, abstract enough that nothing compiles or touches device
    memory.  Works identically under ``JAX_PLATFORMS=cpu``.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..parallel.step import CORES, _shard_map

    core = functools.partial(CORES[spec.kind], **_core_kwargs(spec))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    args = _abstract_args(spec)
    fn = _shard_map(
        core, mesh=mesh, in_specs=(P(),) * len(args), out_specs=(P(), P())
    )
    closed = jax.make_jaxpr(fn)(*args)
    # the weight plane's flat invar index: flatten a marker pytree with
    # the arguments' exact structure (valid is args[3])
    markers = jax.tree_util.tree_map(lambda _: False, args)
    markers = (*markers[:3], True, *markers[4:])
    flat, _ = jax.tree_util.tree_flatten(markers)
    widx = flat.index(True)
    assert sum(1 for f in flat if f is True) == 1
    return TracedProgram(
        spec=spec,
        closed_jaxpr=closed,
        weight_invar_index=widx,
        output_names=OUTPUT_NAMES,
    )
