"""Run cells on the chip and line the program's host spans up with the device.

    python benchmark/tests/host_trace.py --workload NAME --seeds 1,2,3 \
        [--trace 0,1,2] [--seconds 51] [--out FILE]

For each seed, one run per ``--trace`` value, all in this one process
(cellrun.py); ``2`` is a traced run with the profiler's Python tracer off
(``jax.profiler.start_trace`` records every Python call by default).  Each
run prints one JSON line: the result line's metrics and ``correct``, and
the window's rate (lines over the call's wall time, traced or not), and
that window on the host clock: before the call, the call, the report's
``to_json`` and the rest (window_pieces).  A traced run adds, from its
``.xplane.pb`` before the cell's work directory goes: the device idle named by the host spans
(host_spans.reduce_host: head and tail of ``run.call``, ``idle_by_host``,
the share of idle in ``idle in ...`` gaps) and the clock check
(host_spans.dispatch_lead: every step module starts after the start of
the ``step.dispatch`` span that issued it, k-th with k-th), with the
spread of each span's durations and where its longest one fell.  Every run
also lists the interpreter's garbage-collector pauses of 5 ms or more in
its window, which the spans cannot see.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import statistics
import time

import cellrun  # noqa: F401  (puts benchmark/ and the checkout on sys.path)
import jax
import harness
import host_spans
import trace_reduce
from cellrun import run_cell
from ruleset_analysis_tpu.runtime import report as report_mod, stream as stream_mod

#: collector pauses shorter than this are not listed
GC_MIN_S = 0.005


def span_stats(host: list[dict]) -> dict:
    """Per span name: count, median, p90 and longest duration (ms), and
    the longest one's batch and start after the start of ``run.call``."""
    calls = [ev for ev in host if ev["name"] == host_spans.CALL]
    c0 = calls[0]["start_ns"] if calls else 0
    out = {}
    for name in host_spans.SPANS:
        evs = [ev for ev in host if ev["name"] == name]
        if not evs:
            continue
        durs = sorted(ev["dur_ns"] * 1e-6 for ev in evs)
        top = max(evs, key=lambda ev: ev["dur_ns"])
        out[name] = {"n": len(evs), "median_ms": statistics.median(durs),
                     "p90_ms": durs[int(0.9 * (len(durs) - 1))], "max_ms": durs[-1],
                     "max_batch": top["batch"], "max_at_s": (top["start_ns"] - c0) * 1e-9}
    return out


def window_pieces(marks: list) -> dict:
    """The last ``run`` call's window on the host clock: from the driver's
    clock read before the call to the one after its report's JSON round
    trip, as before the call, the call, ``Report.to_json`` and the rest."""
    enter = max(i for i, (w, _) in enumerate(marks) if w == "call.enter")
    t0 = next(t for w, t in reversed(marks[:enter]) if w == "now")
    out = next(i for i in range(enter, len(marks)) if marks[i][0] == "call.exit")
    end = next(i for i in range(out, len(marks)) if marks[i][0] == "now")
    tj = [t for w, t in marks[out:end] if w.startswith("to_json.")]
    json_s = tj[-1] - tj[0] if len(tj) >= 2 else 0.0
    t_in, t_out, t_end = marks[enter][1], marks[out][1], marks[end][1]
    return {"window_s": t_end - t0, "before_call_s": t_in - t0, "call_s": t_out - t_in,
            "to_json_s": json_s, "after_s": t_end - t_out - json_s, "t0": t0, "t_end": t_end}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace", default="1")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default="")
    a = ap.parse_args()

    seen: dict = {}
    pauses: list = []  # (perf_counter at start, seconds, generation)
    gc_t0: list = []

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[:] = [time.perf_counter()]
        elif gc_t0 and time.perf_counter() - gc_t0[0] >= GC_MIN_S:
            pauses.append((gc_t0[0], time.perf_counter() - gc_t0[0], info["generation"]))

    marks: list = []  # (what, perf_counter): the window's pieces on the host clock

    def marked(what, fn):
        def wrapper(*args, **kw):
            marks.append((f"{what}.enter", time.perf_counter()))
            try:
                return fn(*args, **kw)
            finally:
                marks.append((f"{what}.exit", time.perf_counter()))
        return wrapper

    def now():
        t = time.perf_counter()
        marks.append(("now", t))
        return t

    reduce_dir, read_layer = trace_reduce.reduce_dir, harness.read_layer_metrics
    harness_now = harness.now
    start_trace = jax.profiler.start_trace
    no_python = jax.profiler.ProfileOptions()
    no_python.python_tracer_level = 0
    calls = {n: getattr(stream_mod, n) for n in ("run_stream_wire", "run_stream_file")}
    to_json = report_mod.Report.to_json

    def traced_reduce(tdir, chips, scopes=None):
        planes = trace_reduce.planes_of(tdir)
        host = host_spans.host_events(tdir)
        seen["host"] = host_spans.reduce_host(planes, host, chips)
        seen["clock"] = host_spans.dispatch_lead(planes, host, scopes or {})
        seen["spans"] = span_stats(host)
        return trace_reduce.reduce(planes, chips, scopes)

    def layer(cell, lctx):
        seen["totals"] = {k: lctx["report"]["totals"].get(k)
                          for k in ("ingest", "phases", "compile", "elapsed_sec")}
        return read_layer(cell, lctx)

    trace_reduce.reduce_dir, harness.read_layer_metrics = traced_reduce, layer
    harness.now = now
    for n, fn in calls.items():
        setattr(stream_mod, n, marked("call", fn))
    report_mod.Report.to_json = marked("to_json", to_json)
    gc.callbacks.append(on_gc)
    out = open(a.out, "a", encoding="utf-8") if a.out else None
    try:
        for seed in (int(s) for s in a.seeds.split(",")):
            for trace in (int(t) for t in a.trace.split(",")):
                seen.clear()
                pauses.clear()
                marks.clear()
                rec = {"workload": a.workload, "seed": seed, "trace": trace}
                jax.profiler.start_trace = (
                    functools.partial(start_trace, profiler_options=no_python)
                    if trace == 2 else start_trace)
                try:
                    res = run_cell(a.workload, seed, a.seconds, trace=min(trace, 1))
                except Exception as e:  # one failed run must not lose the others
                    rec["error"] = f"{type(e).__name__}: {e}"
                else:
                    rec.update(correct=res["correct"], device=res["device"],
                               metrics={k: m["value"] for k, m in res["metrics"].items()},
                               breakdown=res.get("breakdown"))
                    win = window_pieces(marks)
                    t0, t_end = win.pop("t0"), win.pop("t_end")
                    rec.update(rate=(res["attempted"] - res["failed"]) / win["window_s"],
                               window=win)
                    if trace:
                        host = seen["host"]
                        if host["idle_s"] > 0:
                            host["named_share"] = host["named_s"] / host["idle_s"]
                        host["idle_by_host"] = dict(sorted(
                            host["idle_by_host"].items(), key=lambda kv: -kv[1])[:12])
                        rec.update(host=host, clock=seen["clock"], spans=seen["spans"],
                                   totals=seen["totals"])
                    rec["gc_pauses"] = [{"at_s": t - t0, "s": d, "gen": g}
                                        for t, d, g in pauses if t0 <= t <= t_end]
                line = json.dumps(rec)
                print(line, flush=True)
                if out is not None:
                    out.write(line + "\n")
                    out.flush()
    finally:
        trace_reduce.reduce_dir, harness.read_layer_metrics = reduce_dir, read_layer
        harness.now, jax.profiler.start_trace = harness_now, start_trace
        for n, fn in calls.items():
            setattr(stream_mod, n, fn)
        report_mod.Report.to_json = to_json
        gc.callbacks.remove(on_gc)
        if out is not None:
            out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
