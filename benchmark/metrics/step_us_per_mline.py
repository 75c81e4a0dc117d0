"""Device time of the analysis step per million lines, in microseconds.

Source: the profiler trace's ``XLA Modules`` line: the summed device time
of the programs whose instructions carry the program's ``ra.*`` scopes
(its jitted step, trace_reduce.py), averaged over chips, per 10**6 lines
of the pass.
"""


def read(ctx):
    step = ctx["trace"]["step_s"]
    if step <= 0 or ctx["lines"] <= 0:
        return None
    return step * 1e6 / (ctx["lines"] / 1e6)
