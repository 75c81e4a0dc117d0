"""Milliseconds of pack and H2D issue per million lines of the pass.

Source: the program's own counter ``totals.ingest.pack_sec``: producer
time in the bit-pack and the sharded ``device_put`` issue, which its
``ingest.pack`` spans cover.  Per 10**6 lines of the pass.
"""


def read(ctx):
    ing = (ctx["report"].get("totals") or {}).get("ingest") or {}
    if "pack_sec" not in ing or ctx["lines"] <= 0:
        return None
    return 1e3 * float(ing["pack_sec"]) / (ctx["lines"] / 1e6)
