"""Fused Pallas kernel: first-match scan + in-VMEM count histograms.

The committed TPU trace (DESIGN.md §8) shows the analysis step is
SCATTER-BOUND: the exact-counts segment-sum (fusion.5, 9.2 ms) is a
batch-sized scatter into a ~260-key register, while the match itself is
only 22% of the step.  This kernel attacks that scatter by never doing
it: while the match block is resident in VMEM it also builds

- ``hist_rows`` ``[1, Rp]`` — how many (valid) lines first-matched each
  rule ROW, and
- ``hist_deny`` ``[1, Ap]`` — how many (valid) lines of each ACL matched
  nothing (implicit deny),

both via lane-tile compare-reduce (``best == iota`` summed over the
sublane axis): O(B * Rp/128) VPU ops instead of a serialized batch-sized
scatter.  The remaining scatter is ROW-sized (Rp ~ 512) not BATCH-sized
(64k): :func:`counts_from_hists` folds the histograms into per-KEY count
deltas with two tiny scatters (rows share keys via R_KEY — multiple ACEs
per rule — and unmatched lines land on their ACL's deny key).

Accumulation across the batch grid uses the standard Pallas revisiting
pattern: the histogram output block maps every grid step to block 0, is
zero-initialized at ``program_id == 0``, and accumulates in VMEM.

Parity: ``tests/test_pallas_fused.py`` pins the counts delta and the
report bit-identical to the XLA path (interpret mode on CPU, compiled on
TPU).  Select with ``AnalysisConfig(match_impl="pallas_fused")`` /
``--match-impl pallas_fused``.  The r5 TPU A/B DECIDED the default:
compiled, this kernel measures 0.19-0.70x the XLA path (and 0.08x
in-step) — "xla" stays the default on measurement; the kernel remains a
selectable alternative and a Mosaic regression probe (DESIGN.md §8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..hostside.pack import (
    R_ACL,
    R_DHI,
    R_DLO,
    R_DPHI,
    R_DPLO,
    R_KEY,
    R_PHI,
    R_PLO,
    R_SHI,
    R_SLO,
    R_SPHI,
    R_SPLO,
    RULE_COLS,
)
from .match import NO_MATCH, rows_to_keys

_U32 = jnp.uint32
_I32 = jnp.int32
_NO_MATCH = 0xFFFFFFFF
#: int32 in-kernel sentinel: Mosaic TPU has no lowering for reductions
#: over UNSIGNED integers (r5 TPU window), so the running min over rule
#: tiles is carried in int32 — row indices are far below 2^31 — and
#: mapped back to the uint32 NO_MATCH at the kernel boundary.
_NO_MATCH_I32 = 0x7FFFFFFF

#: Lines per grid step (sublane-major).  A [BLOCK_LINES, 1] u32 block is
#: physically tiled (8, 128), so it occupies BLOCK_LINES x 128 lanes of
#: VMEM — 512 KB at 1024 lines; 4096 OOM'd at 28 MB on the first real-TPU
#: compile (r5 window), 1024 leaves ~2x headroom.
BLOCK_LINES = 1024

#: Rules per lane tile — the VPU lane width.
RULE_TILE = 128


def tile_first_match(fields: tuple, rules, n_tiles: int):
    """Min matching global row per line, over rule tiles on lanes.

    ``fields`` = (acl, proto, src, sport, dst, dport) as [BLOCK_LINES, 1]
    u32 VALUES (lines on sublanes); ``rules`` is the [RULE_COLS, Rp]
    field-major ref.  Returns the [BLOCK_LINES, 1] running-min rows.
    """
    a, p, s, sp, d, dp = fields

    def body(t, best):
        sl = pl.ds(t * RULE_TILE, RULE_TILE)

        def row(c):
            return rules[c, sl][None, :]  # [1, RULE_TILE]

        def in_range(lo_c, hi_c, x):
            # unsigned wraparound range check, as ops.match
            lo = row(lo_c)
            return (x - lo) <= (row(hi_c) - lo)

        ok = (
            (row(R_ACL) == a)
            & in_range(R_PLO, R_PHI, p)
            & in_range(R_SLO, R_SHI, s)
            & in_range(R_SPLO, R_SPHI, sp)
            & in_range(R_DLO, R_DHI, d)
            & in_range(R_DPLO, R_DPHI, dp)
        )
        idx = (
            lax.broadcasted_iota(_I32, (1, RULE_TILE), 1)
            + (t * RULE_TILE).astype(_I32)
        )
        cand = jnp.where(ok, jnp.broadcast_to(idx, ok.shape), _I32(_NO_MATCH_I32))
        return jnp.minimum(best, jnp.min(cand, axis=1, keepdims=True))

    init = jnp.full((a.shape[0], 1), _NO_MATCH_I32, dtype=_I32)
    best = lax.fori_loop(0, n_tiles, body, init)
    return jnp.where(best == _I32(_NO_MATCH_I32), _U32(_NO_MATCH), best.astype(_U32))


def prep_rules(rules: jnp.ndarray) -> jnp.ndarray:
    """[R, RULE_COLS] row-major -> [RULE_COLS, Rp] field-major, lane-padded.

    Padding columns carry NO_MATCH in the ACL field so they never match
    (mirrors pack.py's NO_ACL padding rows).
    """
    r = rules.shape[0]
    rp = _ceil_to(r, RULE_TILE)
    t = jnp.transpose(rules.astype(_U32))  # [RULE_COLS, R]
    if rp != r:
        pad = jnp.zeros((RULE_COLS, rp - r), dtype=_U32).at[R_ACL].set(NO_MATCH)
        t = jnp.concatenate([t, pad], axis=1)
    return t


def _ceil_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _kernel(
    acl, proto, src, sport, dst, dport, valid, rules,
    out_row, hist_rows, hist_deny,
    *, n_tiles: int, n_acl_tiles: int, n_acls: int,
):
    """One batch block: first-match rows + histogram accumulation.

    Refs: seven [BLOCK_LINES, 1] u32 line fields (incl. valid); rules
    [RULE_COLS, Rp] u32 field-major.  out_row [BLOCK_LINES, 1];
    hist_rows [1, Rp] and hist_deny [1, Ap] revisit block 0 every grid
    step and accumulate in VMEM.
    """
    a = acl[:]
    v = valid[:]
    best = tile_first_match(
        (a, proto[:], src[:], sport[:], dst[:], dport[:]), rules, n_tiles
    )
    out_row[:] = best

    # Histogram pass: compare-reduce per lane tile.  Invalid lines are
    # excluded here (the XLA path weights them 0 in segment_counts).
    bv = jnp.where(v > 0, best, _U32(_NO_MATCH - 1))  # valid-masked copy
    # _NO_MATCH-1 can never equal a row index (< Rp << 2^32-2) nor the
    # NO_MATCH sentinel, so invalid lines fall out of BOTH histograms.

    # Clamp out-of-range ACL ids exactly as the keys epilogue does
    # (jnp.minimum(acl, n_acls-1)): a valid line with a corrupt acl gid
    # must land on the LAST ACL's deny key in BOTH the keys and the
    # counts, or delta would diverge from segment_counts(keys, valid).
    # Spelled compare+select: Mosaic has no arith.minui legalization
    # (r5 TPU window), while unsigned compares lower fine.
    a_max = _U32(n_acls - 1)
    a_cl = jnp.where(a > a_max, a_max, a)
    unmatched = jnp.where(bv == _U32(_NO_MATCH), a_cl, _U32(_NO_MATCH - 1))

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_rows[:] = jnp.zeros_like(hist_rows[:])
        hist_deny[:] = jnp.zeros_like(hist_deny[:])

    # The tile loops are STATIC Python unrolls accumulating straight into
    # the revisited output refs with static slices: the first compiled
    # run (r5 TPU window) showed Mosaic implements neither unsigned
    # reductions (hence the int32 sums; block counts <= BLOCK_LINES
    # cannot overflow) nor dynamic_update_slice on values (hence no
    # fori_loop-carried accumulator).  Unrolling is n_tiles = Rp/128
    # bodies — trivial at bench/production slab sizes; a 100k-row flat
    # ruleset would pay compile time and should prefer match_impl=xla
    # or plain pallas there.
    def tile_hist(t, masked, ref):
        idx = (
            lax.broadcasted_iota(_U32, (1, RULE_TILE), 1)
            + _U32(t * RULE_TILE)
        )
        eq = (masked == idx).astype(jnp.int32)  # [BLOCK, RULE_TILE]
        part = jnp.sum(eq, axis=0, keepdims=True)  # [1, RULE_TILE]
        sl = slice(t * RULE_TILE, (t + 1) * RULE_TILE)
        ref[:, sl] += part

    for t in range(n_tiles):
        tile_hist(t, bv, hist_rows)
    for t in range(n_acl_tiles):
        tile_hist(t, unmatched, hist_deny)


@functools.partial(
    jax.jit, static_argnames=("n_acls", "block_lines", "interpret")
)
def match_rows_and_hists_pallas(
    cols: dict,
    valid: jnp.ndarray,  # [B] u32
    rules_fm: jnp.ndarray,  # [RULE_COLS, Rp] from prep_rules
    n_acls: int | None = None,
    block_lines: int = BLOCK_LINES,
    interpret: bool | None = None,
):
    """Fused first-match + histograms over the whole batch.

    Returns ``(row [B] u32, hist_rows [Rp] u32, hist_deny [Ap] u32)``
    where ``Ap = ceil(n_acls/128)*128``.  ``interpret=None`` auto-selects
    like :func:`pallas_match.first_match_rows_pallas`.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b = cols["acl"].shape[0]
    rp = rules_fm.shape[1]
    assert rp % RULE_TILE == 0
    ap = _ceil_to(max(n_acls or 1, 1), RULE_TILE)
    block_lines = min(block_lines, _ceil_to(b, 8))
    bp = _ceil_to(b, block_lines)

    def field(v):
        if bp != b:
            # padding lines carry valid=0 via the valid field below, so
            # they fall out of both histograms; their out rows are sliced
            v = jnp.concatenate([v, jnp.zeros(bp - b, dtype=_U32)])
        return v.reshape(bp, 1)

    line_spec = pl.BlockSpec((block_lines, 1), lambda i: (i, 0))
    hist_rows_spec = pl.BlockSpec((1, rp), lambda i: (0, 0))
    hist_deny_spec = pl.BlockSpec((1, ap), lambda i: (0, 0))
    row, hist_rows, hist_deny = pl.pallas_call(
        functools.partial(
            _kernel, n_tiles=rp // RULE_TILE, n_acl_tiles=ap // RULE_TILE,
            n_acls=max(n_acls or 1, 1),
        ),
        grid=(bp // block_lines,),
        in_specs=[line_spec] * 7
        + [pl.BlockSpec((RULE_COLS, rp), lambda i: (0, 0))],
        out_specs=(line_spec, hist_rows_spec, hist_deny_spec),
        out_shape=(
            jax.ShapeDtypeStruct((bp, 1), _U32),
            # int32 histograms (Mosaic unsigned-reduction constraint);
            # per-chunk totals are bounded by the batch size << 2^31.
            jax.ShapeDtypeStruct((1, rp), jnp.int32),
            jax.ShapeDtypeStruct((1, ap), jnp.int32),
        ),
        interpret=interpret,
    )(
        field(cols["acl"]),
        field(cols["proto"]),
        field(cols["src"]),
        field(cols["sport"]),
        field(cols["dst"]),
        field(cols["dport"]),
        field(valid.astype(_U32)),
        rules_fm,
    )
    return (
        row.reshape(bp)[:b],
        hist_rows.reshape(rp).astype(_U32),
        hist_deny.reshape(ap).astype(_U32),
    )


def counts_from_hists(
    hist_rows: jnp.ndarray,  # [Rp] u32
    hist_deny: jnp.ndarray,  # [Ap] u32
    rules: jnp.ndarray,  # [R, RULE_COLS] row-major
    deny_key: jnp.ndarray,  # [n_acls] u32
    n_keys: int,
) -> jnp.ndarray:
    """Fold row/deny histograms into per-KEY count deltas.

    Two ROW-sized scatters (R ~ 512, n_acls ~ tens) replace the
    batch-sized segment-sum scatter — this is the whole point of the
    fusion.  Bit-identical to ``segment_counts(match_keys(...), valid)``:
    rows -> keys via R_KEY (several ACE rows share one rule key), deny
    counts land on each ACL's deny key.  Padding rows never match (their
    hist entries are 0), so their R_KEY=0 contributions add zero.
    """
    # ra.counts: these two row-sized scatters ARE the fused path's
    # counts stage — scoped so devprof attribution (DESIGN §14) and the
    # static scope-coverage lint (DESIGN §18) see them like every other
    # counts formulation.
    with jax.named_scope("ra.counts"):
        r = rules.shape[0]
        delta = jnp.zeros(n_keys, dtype=_U32)
        delta = delta.at[rules[:, R_KEY].astype(_U32)].add(
            hist_rows[:r], mode="drop"
        )
        a = deny_key.shape[0]
        delta = delta.at[deny_key.astype(_U32)].add(hist_deny[:a], mode="drop")
        return delta


def match_keys_and_counts_pallas(
    cols: dict,
    valid: jnp.ndarray,
    rules: jnp.ndarray,
    rules_fm: jnp.ndarray,
    deny_key: jnp.ndarray,
    n_keys: int,
    block_lines: int = BLOCK_LINES,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Count-key per line + per-key count delta, fused (step integration).

    The keys feed the downstream HLL/talker updates exactly as
    ``match_keys`` would; the counts delta replaces ``segment_counts``.
    """
    row, hist_rows, hist_deny = match_rows_and_hists_pallas(
        cols, valid, rules_fm, deny_key.shape[0], block_lines, interpret
    )
    # ra.match: the shared row->key epilogue (xla's match_keys wraps the
    # same call in the same scope)
    with jax.named_scope("ra.match"):
        keys = rows_to_keys(row, rules, deny_key, cols["acl"])
    delta = counts_from_hists(hist_rows, hist_deny, rules, deny_key, n_keys)
    return keys, delta
