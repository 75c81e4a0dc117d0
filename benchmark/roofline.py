"""Operations and bytes a kernel needs, computed from its shapes.

``match``: the first-match scan (``ops/match.py``) tests every line against
every row of the flat rule tensor.  Per (line, row) pair it needs at least
one ACL compare, five range tests of one subtract and one compare each
(``x - lo <= hi - lo``, with ``hi - lo`` folded into the rule tensor) and
one running min: 12.  The ANDs joining the tests and the select of the row
index are left out, since a compiler may fold them into mask registers, so
the count errs low and the share with it.  Rows are the real expanded rows,
not the padding the program scans too.
Bytes: each line's 16-byte wire row read once and its 4-byte key written,
plus the rule tensor (12 uint32 a row) read once per step.
"""

from __future__ import annotations

MATCH_OPS_PER_PAIR = 12
WIRE_BYTES_PER_LINE = 16
KEY_BYTES_PER_LINE = 4
RULE_BYTES_PER_ROW = 48


def match_ops(lines: int, rows: int) -> float:
    return float(lines) * float(rows) * MATCH_OPS_PER_PAIR


def match_bytes(lines: int, rows: int, steps: int) -> float:
    return float(lines) * (WIRE_BYTES_PER_LINE + KEY_BYTES_PER_LINE) \
        + float(steps) * rows * RULE_BYTES_PER_ROW


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The larger of ops over the VPU's u32 peak and bytes over HBM's, and
    which of the two bounds it."""
    t_ops = ops / peak["vpu_u32_ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "vpu") if t_ops >= t_mem else (t_mem, "hbm")
