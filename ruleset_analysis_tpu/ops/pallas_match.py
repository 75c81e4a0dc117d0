"""Pallas TPU kernel for the v4 first-match scan, with lines on lanes.

The XLA predicate (ops/match.py) evaluates a ``[B, Rb]`` block with lines
on sublanes and rules on lanes: every line value is broadcast across the
128 lanes, and each line's first match is a cross-lane min, both relayout
work on the cross-lane unit for every rule block.  This kernel takes the
transpose:

- each field of a block of lines is ``[rows, 128]`` u32 with lines along
  LANES, so one vreg holds 1,024 lines of one field, and a grid block is
  lane-dense (no 128x VMEM padding of ``[N, 1]`` columns);
- each rule's ``acl``, ``lo`` and ``hi - lo`` (five ranges) are SCALARS,
  read from a rule block staged into SMEM (rule-major, one 16-word record
  a rule) on the grid's inner, sequential axis, and applied to whole line
  vregs: no line value is ever broadcast;
- rows are scanned in DESCENDING order and each line keeps
  ``best = select(ok, row, best)`` in the revisited output block, so the
  last writer is the smallest matching row: the first match, with no
  cross-lane reduction and no (unsigned) min at all.

Every (line, row) pair is still tested, as in ops.match: pack.py emits
rows in configuration order, and padding rows carry ``NO_ACL``.  The
result is bit-identical to ``ops.match.first_match_rows``
(``tests/test_pallas_match.py``, interpret mode on the CPU).

The flat v4 step runs this kernel on the TPU backend
(``models.pipeline.flat_match``); other backends keep the XLA predicate,
since ``pallas_call`` has no compiled CPU lowering.
``AnalysisConfig(match_impl="pallas")`` forces the kernel on any backend
(interpreted off the TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
    RULE_BLOCK,
)
from . import match as _match
from .match import rows_to_keys

_U32 = jnp.uint32
#: Python-int twin of ops.match.NO_MATCH (kernels capture literals only)
_NO_MATCH = 0xFFFFFFFF

#: Lines per vreg row: the lane width.
LANES = 128
#: Sublane rows of one sub-tile: 4 vregs of each field, so one rule's 11
#: scalar reads are spread over 4 line vregs per field and the six fields
#: plus the running best stay in registers.
TILE_ROWS = 32
#: Rows of a grid block along the lines: 16,384 lines, 64 KB a field.
BLOCK_ROWS = 128
#: Rules unrolled per loop iteration of the scan.
UNROLL = 8

#: Ranges as (lo column, hi column), in the order of the line fields
#: after ``acl``.
_RANGES = (
    (R_PLO, R_PHI),
    (R_SLO, R_SHI),
    (R_SPLO, R_SPHI),
    (R_DLO, R_DHI),
    (R_DPLO, R_DPHI),
)
_FIELDS = ("acl", "proto", "src", "sport", "dst", "dport")
#: Scalars staged per rule: acl, then lo and hi - lo of each range.
N_SCALARS = 1 + 2 * len(_RANGES)
#: Words of a rule's SMEM record: N_SCALARS padded so that a rule block
#: is a whole number of SMEM's 1,024-word tiles.
RULE_WORDS = 16


def stage_rules(rules: jnp.ndarray) -> jnp.ndarray:
    """``[R, RULE_COLS]`` -> ``[ceil(R / RULE_BLOCK) * RULE_BLOCK * RULE_WORDS]``.

    One record per rule, rule-major: ``acl``, then ``lo`` and ``hi - lo``
    of each range (the wraparound test ``x - lo <= hi - lo``, as
    ops.match), then padding.  Rows past R are zeros;
    :func:`first_match_rows_pallas` discards any match on them.  With the
    rule tensor baked into the step as a constant, XLA folds this.
    """
    r = rules.shape[0]
    rp = -(-r // RULE_BLOCK) * RULE_BLOCK
    rules = rules.astype(_U32)
    cols = [rules[:, R_ACL]]
    for lo, hi in _RANGES:
        cols += [rules[:, lo], rules[:, hi] - rules[:, lo]]
    t = jnp.stack(cols, axis=1)  # [R, N_SCALARS]
    t = jnp.pad(t, ((0, rp - r), (0, RULE_WORDS - N_SCALARS)))
    return t.reshape(rp * RULE_WORDS)


def _kernel(rules, acl, proto, src, sport, dst, dport, out, *, n_blocks: int):
    """One block of lines against one rule block (grid axis 1, descending).

    Refs: ``rules`` [RULE_BLOCK * RULE_WORDS] u32 in SMEM (rule block
    ``n_blocks - 1 - j``); six ``[BLOCK_ROWS, 128]`` u32 line fields; out
    ``[BLOCK_ROWS, 128]`` u32, revisited across the rule axis and holding
    each line's smallest matching row so far.
    """
    rb = RULE_BLOCK
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out[...] = jnp.full(out.shape, _NO_MATCH, _U32)

    base = ((n_blocks - 1 - j) * rb).astype(_U32)

    def tile(t, carry):
        sl = pl.ds(pl.multiple_of(t * TILE_ROWS, TILE_ROWS), TILE_ROWS)
        a, *xs = (ref[sl, :] for ref in (acl, proto, src, sport, dst, dport))

        def rules_step(i, best):
            # rows off + UNROLL - 1 down to off: the dynamic part of each
            # SMEM address is shared by the UNROLL rules of the step
            off = pl.multiple_of((rb // UNROLL - 1 - i) * UNROLL, UNROLL)
            for u in reversed(range(UNROLL)):
                r = off + u
                w = r * RULE_WORDS
                ok = a == rules[w]
                for k, x in enumerate(xs):
                    ok = ok & ((x - rules[w + 1 + 2 * k]) <= rules[w + 2 + 2 * k])
                best = jnp.where(ok, base + r.astype(_U32), best)
            return best

        out[sl, :] = lax.fori_loop(0, rb // UNROLL, rules_step, out[sl, :])
        return carry

    lax.fori_loop(0, out.shape[0] // TILE_ROWS, tile, 0)


def first_match_rows_pallas(
    cols: dict,
    rules: jnp.ndarray,  # [R, RULE_COLS] row-major (DeviceRuleset.rules)
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Global row index of the first matching ACE per line (lane-dense).

    cols: dict of [B] uint32 arrays (acl/proto/src/sport/dst/dport), any
    B; rules: any R.  Returns [B] u32, NO_MATCH where no rule matches —
    bit-identical to ops.match.first_match_rows.  ``interpret=None``
    compiles on the TPU and interprets elsewhere; it is resolved here,
    outside the jit, so the jit's cache is keyed on the decision.
    """
    if interpret is None:
        interpret = not _match.tpu_backend()
    return _first_match_rows(cols, rules, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _first_match_rows(cols: dict, rules: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    # ra.match: the kernel's custom call (and the staging around it)
    # carries the stage label the device attribution plane and
    # match_roofline read (runtime/devprof.py, DESIGN §14)
    with jax.named_scope("ra.match"):
        b = cols["acl"].shape[0]
        r = rules.shape[0]
        staged = stage_rules(rules)
        n_blocks = staged.shape[0] // (RULE_BLOCK * RULE_WORDS)
        rows = -(-b // LANES)
        rows = -(-rows // TILE_ROWS) * TILE_ROWS
        block_rows = min(BLOCK_ROWS, rows)
        rows = -(-rows // block_rows) * block_rows

        def field(name):
            # padding lines are zeros; their rows are sliced off below
            v = jnp.pad(cols[name].astype(_U32), (0, rows * LANES - b))
            return v.reshape(rows, LANES)

        line_spec = pl.BlockSpec((block_rows, LANES), lambda i, j: (i, 0))
        out = pl.pallas_call(
            functools.partial(_kernel, n_blocks=n_blocks),
            grid=(rows // block_rows, n_blocks),
            in_specs=[
                pl.BlockSpec(
                    (RULE_BLOCK * RULE_WORDS,),
                    lambda i, j: (n_blocks - 1 - j,),
                    memory_space=pltpu.SMEM,
                )
            ]
            + [line_spec] * len(_FIELDS),
            out_specs=line_spec,
            out_shape=jax.ShapeDtypeStruct((rows, LANES), _U32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=interpret,
        )(staged, *(field(n) for n in _FIELDS))
        row = out.reshape(rows * LANES)[:b]
        if n_blocks * RULE_BLOCK != r:
            # a match on a staging row past R is no match
            row = jnp.where(row >= _U32(r), _U32(_NO_MATCH), row)
        return row


def match_keys_pallas(
    cols: dict,
    rules: jnp.ndarray,  # [R, RULE_COLS] row-major (DeviceRuleset.rules)
    deny_key: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Count-key per line via the kernel (ops.match.match_keys twin)."""
    row = first_match_rows_pallas(cols, rules, interpret)
    with jax.named_scope("ra.match"):
        return rows_to_keys(row, rules, deny_key, cols["acl"])
