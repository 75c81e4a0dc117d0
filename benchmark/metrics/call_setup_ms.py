"""Milliseconds from the ``run`` call's entry to its first step dispatch.

Source: the program's own counter ``totals.phases.setup_sec``, measured
by the code that opens and closes its ``run.setup`` span: source and
producer start, ruleset ship, step lookup, state init, the first batch.
"""


def read(ctx):
    ph = (ctx["report"].get("totals") or {}).get("phases") or {}
    if "setup_sec" not in ph:
        return None
    return 1e3 * float(ph["setup_sec"])
