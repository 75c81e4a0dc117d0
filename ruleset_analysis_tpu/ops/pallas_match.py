"""Pallas TPU kernel for the first-match scan (alternative to ops.match).

The XLA-fused predicate (ops/match.py) keeps the VPU reasonably busy,
but it re-decides tiling per shape and materializes block temporaries at
the compiler's discretion.  This kernel pins the layout explicitly:

- line fields live along SUBLANES ([BLOCK_LINES, 1] per field), rule
  fields along LANES ([1, 128] per rule tile), so one VPU op evaluates
  128 rules for 8 lines;
- the whole (transposed, lane-padded) rule tensor stays resident in
  VMEM across the batch grid; the running min over rule tiles is a
  register carry in a ``fori_loop`` — nothing [B, R]-shaped ever exists;
- first-match == min matching global rule index, as in ops.match
  (pack.py emits rows in config order — the parity-critical invariant).

Use :func:`first_match_rows_pallas` as a drop-in for
``ops.match.first_match_rows``; ``tests/test_pallas_match.py`` pins
equality (interpret mode on CPU, compiled on TPU).  It runs in no
benchmark cell.  The round-5 compiled A/B on the TPU measured this kernel
at 0.15-0.26x the XLA-fused predicate (the [N,1] field layout wastes
VMEM 128x, forcing small grid blocks, while XLA tiles the same
compare-reduce freely) — "xla" stays the default BY MEASUREMENT; select
with ``AnalysisConfig(match_impl="pallas")`` where a different balance
holds.
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
    R_PHI,
    R_PLO,
    R_SHI,
    R_SLO,
    R_SPHI,
    R_SPLO,
    RULE_COLS,
)
from .match import NO_MATCH

_U32 = jnp.uint32
_I32 = jnp.int32
#: Python-int twin of ops.match.NO_MATCH — pallas kernels cannot capture
#: module-level jax arrays, only literals.
_NO_MATCH = 0xFFFFFFFF
#: int32 in-kernel sentinel: Mosaic TPU has no lowering for reductions
#: over UNSIGNED integers (first compiled run, r5 TPU window:
#: "NotImplementedError: Reductions over unsigned integers"), so the
#: running-min over rule tiles is carried in int32 — row indices are far
#: below 2^31 — and mapped back to the uint32 NO_MATCH at the kernel
#: boundary, keeping callers bit-compatible with ops.match.
_NO_MATCH_I32 = 0x7FFFFFFF

#: Lines per grid step (sublane-major).  A [BLOCK_LINES, 1] u32 block is
#: physically tiled (8, 128), so it occupies BLOCK_LINES x 128 lanes of
#: VMEM — 512 KB at 1024 lines.  Seven such blocks (6 in + 1 out), double-
#: buffered across the grid, plus the [BLOCK_LINES, RULE_TILE] compare
#: temporary must fit the 16 MB scoped-vmem limit; 4096 OOM'd at 28 MB on
#: the first real-TPU compile (r5 window), 1024 leaves ~2x headroom.
BLOCK_LINES = 1024

#: Rules per lane tile — the VPU lane width.
RULE_TILE = 128


def tile_first_match(fields: tuple, rules, n_tiles: int):
    """Shared kernel body: min matching global row per line, over rule tiles.

    ``fields`` = (acl, proto, src, sport, dst, dport) as [BLOCK_LINES, 1]
    u32 VALUES; ``rules`` is the [RULE_COLS, Rp] field-major ref.  The one
    definition of the tile predicate — ops/pallas_fused.py reuses it, so
    a predicate change (e.g. a new tuple field) lands in every pallas
    kernel at once.  Returns the [BLOCK_LINES, 1] running-min rows.
    """
    a, p, s, sp, d, dp = fields

    def body(t, best):
        sl = pl.ds(t * RULE_TILE, RULE_TILE)

        def row(c):
            return rules[c, sl][None, :]  # [1, RULE_TILE]

        def in_range(lo_c, hi_c, x):
            # unsigned wraparound range check (see ops.match._block_min_row):
            # one subtract + one compare per range instead of two compares
            # + an AND; pack/aclparse + load_packed validation guarantee
            # lo <= hi
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


def _kernel(acl, proto, src, sport, dst, dport, rules, out, *, n_tiles: int):
    """One batch block vs every rule tile; running-min carry over tiles.

    Refs: six [BLOCK_LINES, 1] u32 line fields; rules [RULE_COLS, R]
    u32 (field-major, lane-padded); out [BLOCK_LINES, 1] u32.
    """
    out[:] = tile_first_match(
        (acl[:], proto[:], src[:], sport[:], dst[:], dport[:]),
        rules, n_tiles,
    )


def prep_rules(rules: jnp.ndarray) -> jnp.ndarray:
    """[R, RULE_COLS] row-major -> [RULE_COLS, Rp] field-major, lane-padded.

    Padding columns carry NO_MATCH in the ACL field so they never match
    (mirrors pack.py's NO_ACL padding rows).
    """
    r = rules.shape[0]
    rp = ((r + RULE_TILE - 1) // RULE_TILE) * RULE_TILE
    t = jnp.transpose(rules.astype(_U32))  # [RULE_COLS, R]
    if rp != r:
        pad = jnp.zeros((RULE_COLS, rp - r), dtype=_U32).at[R_ACL].set(NO_MATCH)
        t = jnp.concatenate([t, pad], axis=1)
    return t


@functools.partial(
    jax.jit, static_argnames=("block_lines", "interpret")
)
def first_match_rows_pallas(
    cols: dict,
    rules_fm: jnp.ndarray,  # [RULE_COLS, Rp] from prep_rules
    block_lines: int = BLOCK_LINES,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Global row index of the first matching ACE per line (pallas path).

    cols: dict of [B] uint32 arrays (acl/proto/src/sport/dst/dport).
    Returns [B] u32, NO_MATCH where no rule matches — bit-compatible
    with ops.match.first_match_rows.  ``interpret=None`` auto-selects:
    compiled on TPU, the pallas interpreter on the CPU test backend
    (pallas_call has no compiled CPU lowering).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b = cols["acl"].shape[0]
    rp = rules_fm.shape[1]
    assert rp % RULE_TILE == 0
    block_lines = min(block_lines, _ceil_to(b, 8))
    bp = _ceil_to(b, block_lines)

    def field(name):
        v = cols[name]
        if bp != b:  # padded lines produce garbage rows, sliced off below
            v = jnp.concatenate([v, jnp.zeros(bp - b, dtype=_U32)])
        return v.reshape(bp, 1)

    line_spec = pl.BlockSpec((block_lines, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, n_tiles=rp // RULE_TILE),
        grid=(bp // block_lines,),
        in_specs=[line_spec] * 6
        + [pl.BlockSpec((RULE_COLS, rp), lambda i: (0, 0))],
        out_specs=line_spec,
        out_shape=jax.ShapeDtypeStruct((bp, 1), _U32),
        interpret=interpret,
    )(
        field("acl"),
        field("proto"),
        field("src"),
        field("sport"),
        field("dst"),
        field("dport"),
        rules_fm,
    )
    return out.reshape(bp)[:b]


def _ceil_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def match_keys_pallas(
    cols: dict,
    rules: jnp.ndarray,  # [R, RULE_COLS] row-major (DeviceRuleset.rules)
    rules_fm: jnp.ndarray,  # prep_rules(rules)
    deny_key: jnp.ndarray,
    block_lines: int = BLOCK_LINES,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Count-key per line via the pallas kernel (ops.match.match_keys twin)."""
    from .match import rows_to_keys

    row = first_match_rows_pallas(cols, rules_fm, block_lines, interpret)
    return rows_to_keys(row, rules, deny_key, cols["acl"])
