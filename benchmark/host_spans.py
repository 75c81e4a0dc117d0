"""The program's host spans on the profiler's clock, against the device's idle.

While a profiler session is open, each span of the program (its
``runtime/obs.py``) is also a ``TraceAnnotation``: an event on the
``.xplane.pb``'s ``/host:CPU`` plane, one line per thread, on the clock
of the device's ops.  :func:`host_events` cuts those events down to plain
data; :func:`reduce_host` works on plain data only, so ``tests/`` checks
it on a small fixture without a chip.

- head and tail: the device's idle from the start of ``run.call`` to its
  first op, and from its last op to the end of ``run.call``;
- names: a gap is ``idle in <span>`` after the innermost span of the
  dispatching thread (the thread of ``run.call``) that covers most of
  it; when that span is a wait, the producer's span that overlaps the
  gap most follows after `` / ``.  A gap no span covers keeps the name
  ``idle after <op>`` (trace_reduce.py);
- ``idle_by_host``: seconds of device idle per name, averaged over chips;
- :func:`dispatch_lead`: the k-th step module on ``XLA Modules`` against
  the k-th ``step.dispatch`` span, the check that the two share a clock.
"""

from __future__ import annotations

import glob
import os

import trace_reduce

HOST_PLANE = "/host:CPU"
CALL = "run.call"
DISPATCH = "step.dispatch"
#: the program's span names (a copy of the names its call sites give, as
#: hlo_scopes.py keeps its own patterns): the call's, the step's, the feed's
SPANS = (CALL, "run.setup", "run.finalize", DISPATCH, "step.drain", "step.fetch",
         "ingest.produce", "ingest.pack", "ingest.starved", "ingest.backpressure")
WAITS = ("ingest.starved", "ingest.backpressure")


def span_name(event_name: str) -> str:
    """An event's name without TraceMe metadata (``name#key=value#``)."""
    return event_name.split("#", 1)[0]


def host_events(trace_dir: str) -> list[dict]:
    """The program's spans on the newest ``.xplane.pb``'s host plane."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                name = span_name(ev.name)
                if name in SPANS:
                    stats = {k: v for k, v in ev.stats}
                    out.append({"name": name, "line": i, "start_ns": ev.start_ns,
                                "dur_ns": ev.duration_ns, "batch": stats.get("batch")})
    return out


def _overlap(ev: dict, s: float, e: float) -> float:
    return max(0.0, min(ev["start_ns"] + ev["dur_ns"], e) - max(ev["start_ns"], s))


def gap_name(s: float, e: float, mine: list[dict], others: list[dict]) -> str | None:
    """``idle in <span>[ / <producer span>]`` for the gap [s, e), or None."""
    half = (e - s) / 2.0
    cover = [ev for ev in mine if _overlap(ev, s, e) > half]
    if not cover:
        return None
    inner = min(cover, key=lambda ev: ev["dur_ns"])
    name = f"idle in {inner['name']}"
    if inner["name"] in WAITS:
        best = max(others, key=lambda ev: _overlap(ev, s, e), default=None)
        if best is not None and _overlap(best, s, e) > 0:
            name += f" / {best['name']}"
    return name


def _device_ops(planes: list[dict], n_chips: int) -> list[list[dict]]:
    devs = []
    for p in planes:
        m = trace_reduce.DEVICE_RE.match(p["name"])
        ops = [ev for ln in p["lines"] if ln["name"] == trace_reduce.OPS_LINE
               for ev in ln["events"]]
        if m and ops:
            devs.append((int(m.group(1)), ops))
    return [ops for _, ops in sorted(devs, key=lambda d: d[0])[:n_chips]]


def reduce_host(planes: list[dict], host: list[dict], n_chips: int) -> dict:
    """The device's idle gaps, the head and tail of ``run.call`` among them,
    named by the host's spans and averaged over the ``n_chips``
    lowest-numbered device planes."""
    out = {"head_s": 0.0, "tail_s": 0.0, "idle_s": 0.0, "named_s": 0.0,
           "idle_by_host": {}, "gaps": []}
    calls = [ev for ev in host if ev["name"] == CALL]
    devs = _device_ops(planes, n_chips)
    if not calls or not devs:
        return out
    call = max(calls, key=lambda ev: ev["dur_ns"])
    c0, c1 = call["start_ns"], call["start_ns"] + call["dur_ns"]
    mine = [ev for ev in host if ev["line"] == call["line"]]
    others = [ev for ev in host if ev["line"] != call["line"]]
    k = float(len(devs))
    for ops in devs:
        ivs = [(ev["start_ns"], ev["start_ns"] + ev["dur_ns"]) for ev in ops]
        _, gaps = trace_reduce.union_ns(ivs)
        first, last = min(s for s, _ in ivs), max(e for _, e in ivs)
        ends_at = {ev["start_ns"] + ev["dur_ns"]: ev["name"] for ev in ops}
        idle = [(f"idle after {trace_reduce.instr_of(ends_at.get(s, '?'))}", s, e)
                for s, e in gaps]
        if c0 < first:
            out["head_s"] += (first - c0) * 1e-9 / k
            idle.append(("idle before the first op", c0, first))
        if last < c1:
            out["tail_s"] += (c1 - last) * 1e-9 / k
            idle.append(("idle after the last op", last, c1))
        for fallback, s, e in idle:
            name = gap_name(s, e, mine, others) or fallback
            sec = (e - s) * 1e-9
            out["idle_s"] += sec / k
            if name.startswith("idle in "):
                out["named_s"] += sec / k
            out["idle_by_host"][name] = out["idle_by_host"].get(name, 0.0) + sec / k
            out["gaps"].append((name, sec))
    out["gaps"].sort(key=lambda g: -g[1])
    out["gaps"] = out["gaps"][:10]
    return out


def dispatch_lead(planes: list[dict], host: list[dict], scopes: dict) -> dict:
    """Pair the k-th step module on chip 0's ``XLA Modules`` line with the
    k-th ``step.dispatch`` span: a module that starts before the span that
    issued it means the two are not on one clock."""
    mods = []
    for p in planes:
        if p["name"] == "/device:TPU:0":
            mods = sorted((ev for ln in p["lines"] if ln["name"] == trace_reduce.MODULES_LINE
                           for ev in ln["events"]
                           if scopes.get(trace_reduce.module_of(ev["name"]))),
                          key=lambda ev: ev["start_ns"])
    spans = sorted((ev for ev in host if ev["name"] == DISPATCH), key=lambda ev: ev["start_ns"])
    leads = [m["start_ns"] - d["start_ns"] for m, d in zip(mods, spans)]
    early = [(k, x) for k, x in enumerate(leads) if x < 0]
    return {"modules": len(mods), "dispatches": len(spans),
            "before_their_dispatch": len(early),
            # the first few: their step, lead, and start after the first dispatch
            "early": [{"k": k, "lead_us": x * 1e-3,
                       "at_s": (spans[k]["start_ns"] - spans[0]["start_ns"]) * 1e-9}
                      for k, x in early[:5]],
            "min_lead_us": min(leads) * 1e-3 if leads else None,
            "median_lead_us": sorted(leads)[len(leads) // 2] * 1e-3 if leads else None}
