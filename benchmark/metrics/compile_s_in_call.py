"""Seconds of compile inside the traced ``run`` call: 0 once warmed up.

Source: the program's own counter ``totals.compile.sec``: the trace,
lowering and backend-compile durations JAX reports through
``jax.monitoring`` while the call runs.  ``totals.compile.fns`` names
what compiled.
"""


def read(ctx):
    comp = (ctx["report"].get("totals") or {}).get("compile")
    if not isinstance(comp, dict) or "sec" not in comp:
        return None
    return float(comp["sec"])
