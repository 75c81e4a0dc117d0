"""Share of the pass in which the device step waited for parsed batches.

Source: the program's own counter ``totals.ingest.starved_sec`` (consumer
time blocked on an empty prefetch queue), over the pass's wall time.
"""


def read(ctx):
    ing = (ctx["report"].get("totals") or {}).get("ingest") or {}
    if "starved_sec" not in ing or ctx["wall_s"] <= 0:
        return None
    return 100.0 * float(ing["starved_sec"]) / ctx["wall_s"]
