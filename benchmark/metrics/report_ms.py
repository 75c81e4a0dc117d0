"""Milliseconds from the ``run`` call's last step dispatch to its report.

Source: the program's own counter ``totals.phases.finalize_sec``,
measured by the code that opens and closes its ``run.finalize`` span:
the state sync, the last candidate drains and the report's assembly.
"""


def read(ctx):
    ph = (ctx["report"].get("totals") or {}).get("phases") or {}
    if "finalize_sec" not in ph:
        return None
    return 1e3 * float(ph["finalize_sec"])
