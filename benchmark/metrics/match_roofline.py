"""The first-match kernel's share of its roofline.

Least time: the larger of the operations the scan needs over the VPU's
u32 peak and the bytes it needs over HBM's (roofline.py, peaks.json), for
the lines of the pass against the flat rule tensor's rows.  Over the
device time of the ops scoped ``ra.match`` in the trace.  On several
chips each chip scans its share of the lines, so the least time and the
match time are both per chip.
"""

import math

import roofline


def read(ctx):
    t = ctx["trace"]
    tm = t["stage_s"].get("match", 0.0)
    if tm <= 0 or ctx["lines"] <= 0:
        return None
    chips = ctx["chips"]
    lines = ctx["lines"] / chips
    steps = math.ceil(ctx["lines"] / ctx["cell"].config["analysis"]["batch_size"])
    least, _bound = roofline.least_time(
        roofline.match_ops(lines, ctx["rows_real"]),
        roofline.match_bytes(lines, ctx["rows_real"], steps),
        ctx["cell"].peaks[ctx["cell"].device["kind"]],
    )
    return 100.0 * least / tm
