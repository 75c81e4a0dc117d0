#!/usr/bin/env python
"""Attribute device-step time from a jax.profiler Chrome trace.

Usage:
    python tools/trace_attrib.py [trace.json[.gz] ...]

Defaults to every ``*.trace.json.gz`` under ``profiles/``.  For each
process track, prints total duration by **semantic stage** where the
events carry ``jax.named_scope`` labels (the ``ra.*`` vocabulary every
register-update stage traces under since PR 8 — DESIGN §14), falling
back to the raw event name where they don't (pre-scope captures, host
runtime events).  The classifier is IMPORTED from
``ruleset_analysis_tpu.runtime.devprof`` — the same function the
in-process capture windows use — so offline and in-process attribution
can never disagree about what stage an op belongs to.

This is the offline half of the attribution plane: good for committed
TPU captures taken through ``--profile-dir`` or TensorBoard.  For
repeatable in-process capture (bounded window, optimized-HLO mapping
for backends whose event names are bare instruction names, per-stage
static FLOPs/bytes, diffable summaries) use ``run --devprof-out`` and
``tools/trace_diff.py`` instead.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ruleset_analysis_tpu.runtime.devprof import classify_event_name  # noqa: E402
from ruleset_analysis_tpu.stages import STAGES  # noqa: E402  (the ONE vocabulary)


def load_events(path: str) -> list[dict]:
    """Chrome trace events from ``.json`` or ``.json.gz`` (either form)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, dict):
        return data.get("traceEvents", [])
    return data  # bare event-array form is also valid Chrome JSON


def attribute(path: str, top: int = 20) -> dict:
    """Per-(process, label) totals; label = ra.* stage or raw event name."""
    ev = load_events(path)
    names = {
        e["pid"]: e["args"].get("name", "")
        for e in ev
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and isinstance(e.get("args"), dict)
    }
    tot: dict = collections.defaultdict(float)
    cnt: collections.Counter = collections.Counter()
    scoped_us = 0.0
    total_us = 0.0
    unregistered: set = set()
    for e in ev:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        stage = classify_event_name(e.get("name", ""), e.get("args"))
        label = stage if stage is not None else e.get("name", "?")[:90]
        if stage is not None and stage not in STAGES:
            # syntactically an ra.* scope, but absent from the registered
            # vocabulary (stages.py) — someone added a scope without
            # registering it; the static linter flags the same drift
            unregistered.add(stage)
        key = (names.get(e["pid"], str(e["pid"])), label)
        tot[key] += e["dur"]
        cnt[key] += 1
        total_us += e["dur"]
        if stage is not None:
            scoped_us += e["dur"]
    return {
        "path": path,
        "events": len(ev),
        "total_us": total_us,
        "scoped_us": scoped_us,
        "unregistered_stages": sorted(unregistered),
        "rows": [
            {"process": proc, "label": name, "us": d, "count": cnt[(proc, name)]}
            for (proc, name), d in sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        ],
    }


def render(a: dict) -> str:
    out = [f"== {a['path']} ({a['events']} events) =="]
    if a["total_us"]:
        out.append(
            f"  {100.0 * a['scoped_us'] / a['total_us']:.1f}% of span time "
            "carries a named ra.* stage label"
            if a["scoped_us"]
            else "  no named-scope labels found (pre-scope capture or CPU "
            "thunk names); showing raw event names — use `run "
            "--devprof-out` for semantic attribution on this backend"
        )
    if a.get("unregistered_stages"):
        out.append(
            "  WARNING: ra.* scopes not in the registered vocabulary "
            f"(stages.py): {', '.join(a['unregistered_stages'])}"
        )
    for r in a["rows"]:
        out.append(
            f"{r['us'] / 1e3:10.1f} ms  x{r['count']:>6}  "
            f"[{r['process']}] {r['label']}"
        )
    return "\n".join(out)


def main(argv: list[str]) -> int:
    paths = argv or sorted(
        glob.glob("profiles/**/*.trace.json.gz", recursive=True)
        + glob.glob("profiles/**/*.trace.json", recursive=True)
    )
    if not paths:
        print("no traces found under profiles/", file=sys.stderr)
        return 1
    rc = 0
    for p in paths:
        try:
            print(render(attribute(p)))
            print()
        except (OSError, ValueError) as e:
            print(f"error: unreadable trace {p!r}: {e}", file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
