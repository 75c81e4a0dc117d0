#!/usr/bin/env python
"""Per-stage occupancy + top stalls from a merged pipeline trace.

Usage:
    python tools/trace_summary.py TRACE.json [--json] [--top N]

Reads the merged Chrome trace the observability plane writes
(``run --trace-out DIR`` -> ``DIR/trace.json``; runtime/obs.py) and
answers the attribution question directly from the timeline:

- **occupancy** — for each span name (``ingest.produce``, ``ingest.pack``,
  ``step.dispatch``, ``feeder.parse``, ``checkpoint.save``, ...), total
  busy time as a percentage of the trace wall window, with event counts
  and mean durations.  Parallel tracks (producer thread, feeder worker
  processes) each contribute their own busy time, so totals over 100%
  mean real overlap — exactly what the pipelined ingest engine exists
  to produce.
- **top stalls** — the longest ``ingest.starved`` (parse-bound) and
  ``ingest.backpressure`` (device-bound) intervals, with their offsets
  into the run, so "where did the pipeline wait" has a concrete answer.
- **instants** — fault-site firings, checkpoint commits, elastic
  detections, counted by name.
- **serve** — for traces from the always-on ``serve`` mode: window
  rotation count + latency (``serve.rotate``), reload pauses
  (``serve.reload``), and ``listener.drop`` instants.
- **feed** — for runs on the per-chip ring feeder (``--feed-mode
  ring``): per-ring occupancy %, producer-partition imbalance, and
  starved-chip seconds, from the ``feeder.summary`` instant the ring
  coordinator emits at teardown.
- **devprof** — when a device attribution capture ran in-process
  (``run/serve --devprof-out``, runtime/devprof.py): per-stage device
  occupancy %, the top stage by time, and the unattributed fraction,
  read from the ``devprof.summary`` instant the capture emits onto the
  obs timeline (the full table lives in the capture's devprof.json).
- **lineage** — when a ``lineage.jsonl`` window-provenance ledger sits
  beside the trace (serve runs with ``--lineage on``, DESIGN §24): the
  record/kind counts, the last fully-published window, the first
  missing/incomplete one, and any contiguity gaps.
- **retries** — the transient-fault survival plane (DESIGN §19):
  per-site retry attempts with their summed backoff, recoveries, and
  giveups, from the ``retry.attempt``/``retry.recovered``/
  ``retry.giveup`` instants the policy engine emits.

Tests assert the merged traces of chaos runs stay summarizable.
"""

from __future__ import annotations

import argparse
import collections
import gzip
import json
import os
import sys

#: span names whose duration IS waiting, reported as stalls not work
STALL_NAMES = ("ingest.starved", "ingest.backpressure")


def _load_events(path: str) -> tuple[list[dict], dict | None]:
    """Events + the postmortem bundle when ``path`` is one.

    A flight-recorder ``postmortem.json`` (runtime/flightrec.py, DESIGN
    §20) holds per-PID ring shards of Chrome-trace-shaped events, so the
    SAME occupancy/stall/instant machinery below reads a crash bundle —
    the ``blackbox`` block carries the bundle-only facts (dump trigger,
    cursors, failing stage).
    """
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, dict) and data.get("kind") == "ra-postmortem":
        events = [
            e
            for shard in data.get("shards", [])
            for e in shard.get("ring_events", [])
        ]
        return events, data
    if isinstance(data, dict):
        return data.get("traceEvents", []), None
    return data, None  # bare event-array form is also valid Chrome JSON


def _blackbox_block(bundle: dict) -> dict:
    """The postmortem-only facts: trigger, cursors, final-window view."""
    a = bundle.get("analysis", {})
    return {
        "trigger": bundle.get("trigger"),
        "exit_code": bundle.get("exit_code"),
        "error": bundle.get("error"),
        "error_type": bundle.get("error_type"),
        "failing_stage": a.get("failing_stage"),
        "fault_sites_fired": a.get("fault_sites_fired") or {},
        "shards": [
            {
                "role": s.get("role"),
                "pid": s.get("pid"),
                "trigger": s.get("trigger"),
                "ring_events": len(s.get("ring_events", [])),
                "ring_total": s.get("ring_total"),
                # the final ring window's per-stage busy % — what the
                # process was doing in its last recorded seconds
                "stage_occupancy_pct": next(
                    (
                        p.get("stage_occupancy_pct")
                        for p in a.get("per_shard", [])
                        if p.get("pid") == s.get("pid")
                    ),
                    {},
                ),
                "cursors": s.get("cursors", {}),
            }
            for s in bundle.get("shards", [])
        ],
        "queue_depths": a.get("queue_depths") or {},
        "retries": a.get("retries") or {},
        "degraded": a.get("degraded") or [],
    }


def _lineage_block(path: str) -> dict | None:
    """Window-provenance summary from a lineage.jsonl beside the trace.

    Serve runs with ``--lineage on`` (the default) append one sealed
    record per published window to ``serve_dir/lineage.jsonl``; traces
    and postmortem bundles usually land in (or under) that same dir.
    Stdlib-only twin of runtime/report.py::lineage_frontier so this
    tool stays runnable on a box with nothing installed.
    """
    d = os.path.dirname(os.path.abspath(path))
    lpath = None
    for cand in (d, os.path.dirname(d)):
        c = os.path.join(cand, "lineage.jsonl")
        if os.path.isfile(c):
            lpath = c
            break
    if lpath is None:
        return None
    by_id: dict[int, dict] = {}
    kinds: collections.Counter = collections.Counter()
    paths: collections.Counter = collections.Counter()
    try:
        with open(lpath, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    r = json.loads(line)
                except ValueError:
                    continue  # torn final line after a crash is legal
                kinds[str(r.get("kind"))] += 1
                paths[str(r.get("path"))] += 1
                if r.get("kind") != "merged" and r.get("window") is not None:
                    by_id[int(r["window"])] = r  # last write wins
    except OSError:
        return None
    ids = sorted(by_id)
    gaps = (
        [w for w in range(ids[0], ids[-1] + 1) if w not in by_id]
        if ids else []
    )
    last_complete = None
    first_incomplete = gaps[0] if gaps else None
    for wid in ids:
        if by_id[wid].get("incomplete"):
            if first_incomplete is None or wid < first_incomplete:
                first_incomplete = wid
        else:
            last_complete = wid
    return {
        "path": lpath,
        "records": sum(kinds.values()),
        "kinds": dict(kinds),
        "paths": dict(paths),
        "last_complete": last_complete,
        "first_incomplete": first_incomplete,
        "gaps": gaps[:8],
    }


def summarize(path: str, top: int = 5) -> dict:
    """Machine-readable attribution for one merged trace file."""
    events, bundle = _load_events(path)
    spans = [e for e in events if e.get("ph") == "X" and "ts" in e]
    instants = collections.Counter(
        e.get("name", "?") for e in events if e.get("ph") == "i"
    )
    pids = {e.get("pid") for e in events if "pid" in e}
    tracks = {(e.get("pid"), e.get("tid")) for e in spans}
    if not spans:
        return {
            "path": path,
            "events": len(events),
            "processes": len(pids),
            "tracks": 0,
            "wall_sec": 0.0,
            "stages": {},
            "top_stalls": [],
            "instants": dict(instants),
            **({"blackbox": _blackbox_block(bundle)} if bundle else {}),
            **({"lineage": lb} if (lb := _lineage_block(path)) else {}),
        }
    t_min = min(e["ts"] for e in spans)
    t_max = max(e["ts"] + e.get("dur", 0) for e in spans)
    wall_us = max(1, t_max - t_min)
    by_stage: dict[str, dict] = {}
    for e in spans:
        s = by_stage.setdefault(e["name"], {"busy_us": 0, "count": 0})
        s["busy_us"] += e.get("dur", 0)
        s["count"] += 1
    stages = {
        name: {
            "occupancy_pct": round(100.0 * s["busy_us"] / wall_us, 2),
            "busy_sec": round(s["busy_us"] / 1e6, 4),
            "count": s["count"],
            "mean_ms": round(s["busy_us"] / s["count"] / 1e3, 3),
        }
        for name, s in sorted(
            by_stage.items(), key=lambda kv: -kv[1]["busy_us"]
        )
    }
    stalls = sorted(
        (e for e in spans if e["name"] in STALL_NAMES),
        key=lambda e: -e.get("dur", 0),
    )[:top]
    # flow-coalescing attribution: each ingest.coalesce span carries the
    # batch's raw/unique row counts in its args, so the trace alone
    # answers "what compaction ratio did this run actually see"
    coalesce = None
    raw = unique = 0
    for e in spans:
        if e["name"] == "ingest.coalesce":
            a = e.get("args") or {}
            raw += int(a.get("raw", 0))
            unique += int(a.get("unique", 0))
    if raw or unique:
        coalesce = {
            "raw_rows": raw,
            "unique_rows": unique,
            "compaction_ratio": round(raw / max(unique, 1), 4),
        }
    # serve-mode attribution: rotation latency (serve.rotate covers the
    # flush + ring push + publish of one window) and the reload pause
    # (serve.reload = how long live analysis stood still for the swap);
    # listener.drop instants are the trace's copy of the drop counter
    serve = None
    rotations = [e for e in spans if e["name"] == "serve.rotate"]
    reloads = [e for e in spans if e["name"] == "serve.reload"]
    if rotations or reloads:
        durs = [e.get("dur", 0) for e in rotations]
        serve = {
            "rotations": len(rotations),
            **(
                {
                    "rotation_mean_ms": round(sum(durs) / len(durs) / 1e3, 3),
                    "rotation_max_ms": round(max(durs) / 1e3, 3),
                }
                if durs
                else {}
            ),
            "reloads": len(reloads),
            **(
                {
                    "reload_pause_ms": [
                        round(e.get("dur", 0) / 1e3, 3) for e in reloads
                    ]
                }
                if reloads
                else {}
            ),
            "listener_drops": instants.get("listener.drop", 0),
        }
    # autoscale attribution: every policy decision is an instant with
    # its full evidence attached, every serve-side actuation a span —
    # so "what did the autoscaler do, on what grounds, and how fast did
    # it take effect" is answerable from the trace alone
    autoscale = None
    decides = sorted(
        (e for e in events
         if e.get("ph") == "i" and e.get("name") == "autoscale.decide"
         and "ts" in e),
        key=lambda e: e["ts"],
    )
    applies = [e for e in spans if e["name"] == "autoscale.apply"]
    if decides or applies:
        flaps = 0
        prev = None
        for e in decides:
            a = e.get("args") or {}
            if prev is not None:
                pa = prev.get("args") or {}
                window_us = float(a.get("damping_window_sec", 0)) * 1e6
                if (
                    a.get("direction") != pa.get("direction")
                    and e["ts"] - prev["ts"] < window_us
                ):
                    flaps += 1
            prev = e
        durs = [e.get("dur", 0) for e in applies]
        autoscale = {
            "decisions": [
                {
                    "at_sec": round((e["ts"] - t_min) / 1e6, 3),
                    **{
                        k: (e.get("args") or {}).get(k)
                        for k in ("seq", "direction", "from_world",
                                  "to_world", "reason", "actuate")
                    },
                    "evidence": (e.get("args") or {}).get("evidence"),
                }
                for e in decides
            ],
            "scale_out": sum(
                1 for e in decides
                if (e.get("args") or {}).get("direction") == "out"
            ),
            "scale_in": sum(
                1 for e in decides
                if (e.get("args") or {}).get("direction") == "in"
            ),
            "flaps": flaps,
            **(
                {
                    # serve-side: the apply span IS the time-to-effect
                    "applies": len(applies),
                    "time_to_effect_mean_ms": round(
                        sum(durs) / len(durs) / 1e3, 3
                    ),
                    "time_to_effect_max_ms": round(max(durs) / 1e3, 3),
                }
                if durs
                else {}
            ),
            # elastic-side actuation markers (planned retirements and
            # parked standbys; time-to-effect lands in the report's
            # totals.autoscale.applied records)
            "retirements": instants.get("autoscale.retire", 0),
            "standby_parks": instants.get("autoscale.standby", 0),
        }
    # feed-fleet attribution (ISSUE 11): the ring feeder pushes one
    # feeder.summary instant at teardown — per-ring occupancy %, the
    # producer-partition imbalance, and how long each chip's ring sat
    # dry while the coordinator waited on it (starved-chip seconds)
    feed = None
    feed_instants = [
        e for e in events
        if e.get("ph") == "i" and e.get("name") == "feeder.summary"
        and isinstance(e.get("args"), dict)
    ]
    if feed_instants:
        a = feed_instants[-1]["args"]  # latest feed run wins
        feed = {
            "mode": a.get("mode"),
            "rings": a.get("rings"),
            "ring_depth": a.get("ring_depth"),
            "workers": a.get("workers"),
            "groups": a.get("groups"),
            "ring_occupancy_pct": a.get("ring_occupancy_pct"),
            "partition_imbalance_pct": a.get("partition_imbalance_pct"),
            "starved_sec": a.get("starved_sec"),
            "starved_total_sec": a.get("starved_total_sec"),
        }
    # device attribution capture (run/serve --devprof-out): the capture
    # pushes one devprof.summary instant whose args are the flat gauges
    # — per-stage device occupancy, top stage, attributed fraction
    devprof = None
    dp_instants = [
        e for e in events
        if e.get("ph") == "i" and e.get("name") == "devprof.summary"
        and isinstance(e.get("args"), dict)
    ]
    if dp_instants:
        a = dp_instants[-1]["args"]  # latest capture wins
        stage_pcts = {
            k[len("devprof_pct_"):].replace("_", ".", 1): v
            for k, v in a.items()
            if k.startswith("devprof_pct_")
        }
        devprof = {
            "steps_profiled": a.get("devprof_steps_profiled"),
            "attributed_frac": a.get("devprof_attributed_frac"),
            "top_stage": a.get("devprof_top_stage"),
            "top_stage_pct": a.get("devprof_top_stage_pct"),
            "stage_pct": dict(
                sorted(stage_pcts.items(), key=lambda kv: -(kv[1] or 0))
            ),
            "unattributed_pct": (
                round(100.0 * (1.0 - a["devprof_attributed_frac"]), 2)
                if isinstance(a.get("devprof_attributed_frac"), (int, float))
                else None
            ),
        }
    # retry attribution (DESIGN §19): every retry decision is an instant
    # with its site/attempt/delay, recoveries and giveups likewise — so
    # "what did the survival plane absorb, and what escalated" is
    # answerable from the trace alone
    retries = None
    retry_by_site: dict[str, dict] = {}
    for e in events:
        if e.get("ph") != "i" or not isinstance(e.get("args"), dict):
            continue
        name = e.get("name", "")
        if name not in ("retry.attempt", "retry.recovered", "retry.giveup"):
            continue
        site = e["args"].get("site", "?")
        s = retry_by_site.setdefault(
            site, {"attempts": 0, "recoveries": 0, "giveups": 0,
                   "backoff_sec": 0.0}
        )
        if name == "retry.attempt":
            s["attempts"] += 1
            s["backoff_sec"] = round(
                s["backoff_sec"] + float(e["args"].get("delay_sec", 0.0)), 4
            )
        elif name == "retry.recovered":
            s["recoveries"] += 1
        else:
            s["giveups"] += 1
    if retry_by_site:
        retries = {
            "sites": dict(sorted(retry_by_site.items())),
            "attempts": sum(s["attempts"] for s in retry_by_site.values()),
            "recoveries": sum(s["recoveries"] for s in retry_by_site.values()),
            "giveups": sum(s["giveups"] for s in retry_by_site.values()),
        }

    # failover attribution (DESIGN §23): lease.acquired carries the won
    # term + election wait, distserve.failover.replay the spool-replay
    # accounting, lease.fenced every fencing event — time-to-takeover
    # (election wait + replay) and "who fenced whom" read off the trace
    failover = None
    acquired = [
        e for e in events
        if e.get("ph") == "i" and e.get("name") == "lease.acquired"
        and isinstance(e.get("args"), dict)
    ]
    fenced_ev = [
        e for e in events
        if e.get("ph") == "i" and e.get("name") == "lease.fenced"
        and isinstance(e.get("args"), dict)
    ]
    replays = [
        e for e in events
        if e.get("ph") == "i" and e.get("name") == "distserve.failover.replay"
        and isinstance(e.get("args"), dict)
    ]
    if acquired or fenced_ev or replays:
        wait_sec = sum(
            float(e["args"].get("wait_sec", 0.0)) for e in acquired
        )
        replay_sec = sum(
            float(e["args"].get("takeover_sec", 0.0)) for e in replays
        )
        failover = {
            "terms_won": [int(e["args"].get("term", 0)) for e in acquired],
            "time_to_takeover_sec": round(wait_sec + replay_sec, 3),
            "election_wait_sec": round(wait_sec, 3),
            "epochs_replayed": sum(
                int(e["args"].get("epochs", 0)) for e in replays
            ),
            "windows_replayed": sum(
                int(e["args"].get("windows", 0)) for e in replays
            ),
            "replay_refused": instants.get("distserve.replay.refused", 0),
            "fencing_events": [
                {
                    "fenced_term": int(e["args"].get("term", 0)),
                    "winner_term": int(e["args"].get("winner_term", 0)),
                    "winner": e["args"].get("winner", "?"),
                }
                for e in fenced_ev
            ],
            "partitions": instants.get("serve.host.partition", 0),
            "partition_heals": instants.get("serve.host.partition_heal", 0),
        }
    return {
        "path": path,
        "events": len(events),
        "processes": len(pids),
        "tracks": len(tracks),
        "wall_sec": round(wall_us / 1e6, 4),
        "stages": stages,
        "top_stalls": [
            {
                "kind": e["name"],
                "at_sec": round((e["ts"] - t_min) / 1e6, 4),
                "dur_ms": round(e.get("dur", 0) / 1e3, 3),
                "pid": e.get("pid"),
            }
            for e in stalls
        ],
        "instants": dict(instants),
        **({"coalesce": coalesce} if coalesce else {}),
        **({"serve": serve} if serve else {}),
        **({"autoscale": autoscale} if autoscale else {}),
        **({"feed": feed} if feed else {}),
        **({"devprof": devprof} if devprof else {}),
        **({"retries": retries} if retries else {}),
        **({"failover": failover} if failover else {}),
        **({"blackbox": _blackbox_block(bundle)} if bundle else {}),
        **({"lineage": lb} if (lb := _lineage_block(path)) else {}),
    }


def render(s: dict) -> str:
    out = [
        f"== {s['path']} ==",
        f"  {s['events']} events, {s['processes']} process(es), "
        f"{s['tracks']} span track(s), wall {s['wall_sec']:.3f}s",
        "  stage occupancy (busy / wall; >100% total = overlap):",
    ]
    for name, st in s["stages"].items():
        out.append(
            f"    {st['occupancy_pct']:6.2f}%  {st['busy_sec']:9.3f}s  "
            f"x{st['count']:<6} mean {st['mean_ms']:8.3f} ms  {name}"
        )
    if s["top_stalls"]:
        out.append("  top stall intervals:")
        for st in s["top_stalls"]:
            out.append(
                f"    +{st['at_sec']:9.3f}s  {st['dur_ms']:9.3f} ms  "
                f"[pid {st['pid']}] {st['kind']}"
            )
    if s.get("coalesce"):
        c = s["coalesce"]
        out.append(
            f"  coalesce: {c['raw_rows']} raw -> {c['unique_rows']} unique "
            f"rows ({c['compaction_ratio']:.2f}x compaction)"
        )
    if s.get("serve"):
        sv = s["serve"]
        line = f"  serve: {sv['rotations']} rotation(s)"
        if "rotation_mean_ms" in sv:
            line += (
                f" (mean {sv['rotation_mean_ms']:.1f} ms, "
                f"max {sv['rotation_max_ms']:.1f} ms)"
            )
        line += f", {sv['reloads']} reload(s)"
        if sv.get("reload_pause_ms"):
            line += f" (pause {', '.join(f'{p:.1f}' for p in sv['reload_pause_ms'])} ms)"
        line += f", {sv['listener_drops']} listener drop(s)"
        out.append(line)
    if s.get("autoscale"):
        a = s["autoscale"]
        line = (
            f"  autoscale: {a['scale_out']} out / {a['scale_in']} in, "
            f"{a['flaps']} flap(s)"
        )
        if "time_to_effect_mean_ms" in a:
            line += (
                f", time-to-effect mean {a['time_to_effect_mean_ms']:.1f} ms"
                f" max {a['time_to_effect_max_ms']:.1f} ms"
            )
        if a.get("retirements"):
            line += f", {a['retirements']} planned retirement(s)"
        out.append(line)
        for d in a["decisions"]:
            ev = d.get("evidence") or {}
            grounds = ""
            sig = ev.get("pressure" if d.get("reason") == "backpressure"
                         else "starvation")
            if isinstance(sig, dict):
                grounds = (
                    f"  [min {sig.get('min')} >= thr {sig.get('threshold')}"
                    f" over {ev.get('window_sec')}s]"
                )
            out.append(
                f"    +{d['at_sec']:9.3f}s  #{d.get('seq')} "
                f"{d.get('direction')} {d.get('from_world')}->"
                f"{d.get('to_world')} ({d.get('reason')}){grounds}"
            )
    if s.get("feed"):
        fd = s["feed"]
        out.append(
            f"  feed: {fd.get('mode')} x{fd.get('rings')} ring(s) depth "
            f"{fd.get('ring_depth')}, {fd.get('workers')} worker(s), "
            f"{fd.get('groups')} group(s); partition imbalance "
            f"{fd.get('partition_imbalance_pct')}%, starved "
            f"{fd.get('starved_total_sec')}s total"
        )
        occ = fd.get("ring_occupancy_pct") or []
        sts = fd.get("starved_sec") or []
        for j, pct in enumerate(occ):
            starved = sts[j] if j < len(sts) else 0.0
            out.append(
                f"    ring {j}: occupancy {pct:6.2f}%  starved {starved:.3f}s"
            )
    if s.get("devprof"):
        dp = s["devprof"]
        af = dp.get("attributed_frac")
        line = f"  devprof: {dp.get('steps_profiled')} step(s) captured"
        if af is not None:
            line += (
                f", {100 * af:.1f}% attributed "
                f"({dp.get('unattributed_pct')}% unattributed)"
            )
        if dp.get("top_stage"):
            line += f", top stage {dp['top_stage']} ({dp.get('top_stage_pct')}%)"
        out.append(line)
        for name, pct in dp.get("stage_pct", {}).items():
            out.append(f"    {pct:6.2f}%  {name}")
    if s.get("retries"):
        r = s["retries"]
        out.append(
            f"  retries: {r['attempts']} attempt(s), {r['recoveries']} "
            f"recovery(ies), {r['giveups']} giveup(s)"
        )
        for site, st in r["sites"].items():
            out.append(
                f"    {site}: {st['attempts']} retry(ies) "
                f"({st['backoff_sec']:.3f}s backoff), "
                f"{st['recoveries']} recovered, {st['giveups']} gave up"
            )
    if s.get("failover"):
        fo = s["failover"]
        terms = ", ".join(str(t) for t in fo["terms_won"]) or "-"
        out.append(
            f"  failover: term(s) {terms} won in "
            f"{fo['time_to_takeover_sec']:.3f}s "
            f"({fo['election_wait_sec']:.3f}s election), "
            f"{fo['epochs_replayed']} epoch(s) -> "
            f"{fo['windows_replayed']} window(s) replayed, "
            f"{fo['replay_refused']} refused"
        )
        for fe in fo["fencing_events"]:
            out.append(
                f"    fenced: term {fe['fenced_term']} lost to term "
                f"{fe['winner_term']} ({fe['winner']})"
            )
        if fo["partitions"] or fo["partition_heals"]:
            out.append(
                f"    partitions: {fo['partitions']} parked, "
                f"{fo['partition_heals']} healed"
            )
    if s.get("blackbox"):
        bb = s["blackbox"]
        out.append(
            f"  blackbox: trigger={bb['trigger']} exit_code={bb['exit_code']}"
            f" error={bb.get('error_type')}: {bb.get('error')}"
        )
        out.append(f"    failing stage: {bb.get('failing_stage')}")
        if bb.get("fault_sites_fired"):
            fired = ", ".join(
                f"{k} x{v}" for k, v in sorted(bb["fault_sites_fired"].items())
            )
            out.append(f"    fault sites fired: {fired}")
        for sh in bb.get("shards", []):
            out.append(
                f"    shard [{sh.get('role')} pid {sh.get('pid')}] "
                f"trigger={sh.get('trigger')} "
                f"({sh.get('ring_events')} of {sh.get('ring_total')} ring "
                f"events retained)"
            )
            occ = sh.get("stage_occupancy_pct") or {}
            for name, pct in list(occ.items())[:4]:
                out.append(f"      {pct:6.2f}%  {name}")
            if sh.get("cursors"):
                cur = ", ".join(
                    f"{k}={v}" for k, v in sorted(sh["cursors"].items())
                )
                out.append(f"      cursors: {cur}")
        if bb.get("degraded"):
            out.append(f"    degraded: {'; '.join(bb['degraded'])}")
    if s.get("lineage"):
        ln = s["lineage"]
        kinds = ", ".join(f"{k} x{v}" for k, v in sorted(ln["kinds"].items()))
        out.append(
            f"  lineage: {ln['records']} record(s) in {ln['path']} ({kinds})"
        )
        out.append(
            f"    last complete window: {ln['last_complete']}   "
            f"first missing/incomplete: {ln['first_incomplete']}"
        )
        if ln["gaps"]:
            out.append(f"    gap window id(s): {ln['gaps']}")
        off_live = {
            k: v for k, v in ln["paths"].items() if k not in ("live", "None")
        }
        if off_live:
            alt = ", ".join(f"{k} x{v}" for k, v in sorted(off_live.items()))
            out.append(f"    non-live publication paths: {alt}")
    if s["instants"]:
        marks = ", ".join(f"{k} x{v}" for k, v in sorted(s["instants"].items()))
        out.append(f"  instants: {marks}")
    return "\n".join(out)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="per-stage occupancy + top stalls from a merged "
        "--trace-out trace"
    )
    ap.add_argument("traces", nargs="+", help="merged trace.json file(s)")
    ap.add_argument("--json", action="store_true", help="machine output")
    ap.add_argument("--top", type=int, default=5, help="stall intervals to list")
    args = ap.parse_args(argv)
    rc = 0
    results = []
    for path in args.traces:
        try:
            results.append(summarize(path, top=args.top))
        except (OSError, ValueError) as e:
            print(f"error: unreadable trace {path!r}: {e}", file=sys.stderr)
            rc = 1
    if not results:
        return rc or 1
    if args.json:
        print(json.dumps(results if len(results) > 1 else results[0], indent=2))
    else:
        for s in results:
            print(render(s))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
