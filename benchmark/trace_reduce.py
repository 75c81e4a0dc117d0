"""From a profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` and first cut down to plain data
(:func:`planes_of`): each device plane's ``XLA Ops`` and ``XLA Modules``
lines, each event with its name, start and duration.  The reduction
(:func:`reduce`) works on that plain data only, so ``tests/`` checks it on
a small recorded trace without a chip.

- busy: the union of the op intervals on a chip, averaged over chips;
- self time: an op's duration less the ops nested inside it (a loop and
  the fusions of its body are all on the ``XLA Ops`` line);
- stage: an op's ``ra.*`` stage, looked up by its instruction name in the
  table of its module (hlo_scopes.py), the module being the ``XLA
  Modules`` event that holds the op;
- step: the summed time of the modules whose instructions carry a stage.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR_RE = re.compile(r"^%?([\w.\-]+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def planes_of(trace_dir: str) -> list[dict]:
    """The newest ``.xplane.pb`` under ``trace_dir``, as plain data."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return []
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        if not DEVICE_RE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines.append({"name": line.name, "events": [
                    {"name": ev.name, "start_ns": ev.start_ns, "dur_ns": ev.duration_ns}
                    for ev in line.events]})
        out.append({"name": plane.name, "lines": lines})
    return out


def instr_of(event_name: str) -> str:
    m = _INSTR_RE.match(event_name)
    return m.group(1) if m else event_name


def module_of(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def union_ns(intervals: list) -> tuple[float, list]:
    """Total covered length of [start, end) intervals, and the gaps between."""
    total = 0.0
    gaps = []
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                gaps.append((end, s))
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total, gaps


def self_times(ops: list[dict]) -> list[float]:
    """Each op's duration less the durations of the ops nested in it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["start_ns"], -ops[i]["dur_ns"]))
    own = [float(ev["dur_ns"]) for ev in ops]
    stack: list[int] = []
    for i in order:
        s = ops[i]["start_ns"]
        while stack and ops[stack[-1]]["start_ns"] + ops[stack[-1]]["dur_ns"] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            if s + ops[i]["dur_ns"] <= ops[parent]["start_ns"] + ops[parent]["dur_ns"]:
                own[parent] -= ops[i]["dur_ns"]
        stack.append(i)
    return own


def reduce(planes: list[dict], n_chips: int, scopes: dict | None = None) -> dict:
    """Busy seconds, per-stage, per-op and per-module seconds, averaged over
    the ``n_chips`` lowest-numbered device planes that ran ops."""
    scopes = scopes or {}
    devs = []
    for p in planes:
        m = DEVICE_RE.match(p["name"])
        ops = [ev for ln in p["lines"] if ln["name"] == OPS_LINE for ev in ln["events"]]
        if m and ops:
            mods = [ev for ln in p["lines"] if ln["name"] == MODULES_LINE for ev in ln["events"]]
            devs.append((int(m.group(1)), ops, mods))
    devs = sorted(devs, key=lambda d: d[0])[:n_chips]
    out = {"chips_seen": len(devs), "busy_s": 0.0, "stage_s": {}, "op_s": {},
           "module_s": {}, "step_s": 0.0, "gaps": [], "ops": 0}
    if not devs:
        return out
    k = float(len(devs))

    def add(d: dict, key: str, ns: float) -> None:
        d[key] = d.get(key, 0.0) + ns * 1e-9 / k

    for _, ops, mods in devs:
        busy, gaps = union_ns([(ev["start_ns"], ev["start_ns"] + ev["dur_ns"]) for ev in ops])
        out["busy_s"] += busy * 1e-9 / k
        out["ops"] += len(ops)
        mods = sorted(mods, key=lambda ev: ev["start_ns"])
        starts = [ev["start_ns"] for ev in mods]
        ends_at = {}
        for ev, own in zip(ops, self_times(ops)):
            ends_at[ev["start_ns"] + ev["dur_ns"]] = ev["name"]
            j = _holder(starts, mods, ev["start_ns"])
            table = scopes.get(module_of(mods[j]["name"]), {}) if j is not None else {}
            add(out["stage_s"], table.get(instr_of(ev["name"]), "unscoped"), own)
            add(out["op_s"], instr_of(ev["name"]), own)
        for ev in mods:
            add(out["module_s"], module_of(ev["name"]), ev["dur_ns"])
            if scopes.get(module_of(ev["name"])):
                out["step_s"] += ev["dur_ns"] * 1e-9 / k
        for s, e in gaps:
            out["gaps"].append((f"idle after {instr_of(ends_at.get(s, '?'))}", (e - s) * 1e-9))
    out["gaps"].sort(key=lambda g: -g[1])
    out["gaps"] = out["gaps"][:10]
    return out


def _holder(starts: list, mods: list, t: float):
    """Index of the module event that holds time ``t``, if any."""
    j = bisect.bisect_right(starts, t) - 1
    if j >= 0 and t <= mods[j]["start_ns"] + mods[j]["dur_ns"]:
        return j
    return None


def reduce_dir(trace_dir: str, n_chips: int, scopes: dict | None = None) -> dict:
    return reduce(planes_of(trace_dir), n_chips, scopes)


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: top device ops and longest idle gaps."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in red["gaps"][:10]]}
