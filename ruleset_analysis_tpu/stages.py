"""The ``ra.*`` named-scope stage vocabulary (DESIGN §14) — ONE source.

Every register-update stage in ``ops/`` and the dispatch seams in
``parallel/step.py`` trace under ``jax.named_scope`` labels from this
vocabulary.  Scopes ride HLO op *metadata* (``op_name``) through XLA's
optimizer, so profiler fusions — even renumbered ones — carry the
stages they fused; they also land on every jaxpr equation's
``source_info.name_stack``, which is how the static lint plane
(``verify/``) proves scope coverage without a device.

Three consumers import this module so the vocabulary can never drift
between them:

- ``runtime/devprof.py`` — in-process capture windows classify profiled
  events by these stages;
- ``tools/trace_attrib.py`` — offline trace attribution flags ``ra.*``
  tokens that are NOT in the vocabulary (a scope someone added without
  registering it here);
- ``ruleset_analysis_tpu/verify`` — the jaxpr linter requires every
  register-update primitive to attribute to exactly one member stage
  (DESIGN §18).

Classification accepts any ``ra.<word>`` token syntactically — but an
unregistered token is a lint finding, so adding a stage means adding it
HERE (with its one-line meaning) and nowhere else.

The stages the step programs emit today:

   ra.unpack  wire bit-unpack + the coalesce weight plane (batch_cols)
   ra.match   v4 first-match kernel (flat + stacked + pallas epilogues)
   ra.match6  v6 lexicographic limb match + source fold
   ra.counts  exact per-key counts (scatter/matmul/reduce impls + add64)
   ra.cms     per-rule count-min scatter
   ra.hll     per-key HLL scatter-max
   ra.talk    talker (acl, src) sketch update
   ra.topk    chunk-local candidate table + top_k selection
   ra.sort    register-key sorts feeding the segment-reduce updates
              (update_impl=sorted, ops/sorted_update.py — DESIGN §15)
   ra.overlap static-analysis pairwise rule-relation tiles (ISSUE 12)
   ra.merge   cross-device psum/pmax/all_gather merges
"""

from __future__ import annotations

import re

STAGES = (
    "ra.unpack",
    "ra.match",
    "ra.match6",
    "ra.counts",
    "ra.cms",
    "ra.hll",
    "ra.talk",
    "ra.topk",
    "ra.sort",
    "ra.merge",
    "ra.overlap",
)

#: Syntactic shape of a stage token inside an HLO op_name path or a
#: jaxpr name stack.  Matching is deliberately broader than
#: :data:`STAGES` membership: classifiers accept any token (so captures
#: from newer code still attribute), while the lint plane additionally
#: enforces membership (so new tokens must be registered above).
SCOPE_RE = re.compile(r"ra\.[a-z0-9_]+")


def scope_of(op_name: str | None) -> str | None:
    """Outermost ``ra.*`` scope token of an HLO ``op_name`` path or a
    jaxpr ``name_stack`` string.

    Outermost wins so a wrapping stage owns its helpers: the talker
    plane's ``ra.talk/ra.cms/...`` classifies as ``ra.talk`` even though
    the inner scatter is the shared CMS kernel.
    """
    m = SCOPE_RE.search(op_name or "")
    return m.group(0) if m else None
