"""Milliseconds of parse per million lines of the pass.

Source: the program's own counter ``totals.ingest.parse_sec``: producer
time inside the source iterator (the native parse, or the wire reader's
block reads), which its ``ingest.produce`` spans cover.  Per 10**6 lines
of the pass.
"""


def read(ctx):
    ing = (ctx["report"].get("totals") or {}).get("ingest") or {}
    if "parse_sec" not in ing or ctx["lines"] <= 0:
        return None
    return 1e3 * float(ing["parse_sec"]) / (ctx["lines"] / 1e6)
