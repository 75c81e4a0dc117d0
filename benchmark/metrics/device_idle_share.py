"""Share of the traced pass in which no operation ran on the device.

Source: the profiler trace; busy is the union of op intervals per chip,
averaged over the chips (trace_reduce.py), over the pass's wall time.
"""


def read(ctx):
    t = ctx["trace"]
    if t["chips_seen"] == 0 or ctx["wall_s"] <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - t["busy_s"] / ctx["wall_s"])
