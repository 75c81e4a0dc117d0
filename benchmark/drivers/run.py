"""Mode ``run``: one batch ``run`` over an archived corpus, as an audit does.

Set-up draws the ruleset and the flows from the seed, has the program
parse the rendered configuration (``parse-acls``), writes one corpus file
(text lines, or ``.rawire`` rows for ``"input": "wire"``), and runs
warm-up passes over it, which compile and warm every shape.  The window
is ONE ``run`` call over the corpus repeated ``k`` times, ``k`` sized from
the last two warm-up passes so the call lasts at least ``--seconds`` and,
whatever the passes read, at most about ``3.6 * --seconds``.  The rate is
all lines over all the time of that call, from the call to its report.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

import harness
import hlo_scopes
import reference
import trace_reduce
import drivers_common
from gen import traffic as gen_traffic

#: every number compared, and its limit (exact statements of the
#: configuration's guarantees: see PERF.md, "How correct is decided")
LIMITS = {"hits_wrong": 0, "unused_wrong": 0, "unique_wrong": 0, "talkers_wrong": 0,
          "talkers_missed": 0}
#: a calibration pass lasts at least this long (s)
CALIBRATE_S = 3.0


def write_text(path: str, lines: list[str], seq: np.ndarray) -> None:
    with open(path, "w", encoding="utf-8") as f:
        step = 1 << 16
        for i in range(0, seq.size, step):
            f.write("\n".join(lines[j] for j in seq[i:i + step].tolist()))
            f.write("\n")


def write_wire(path: str, packed, rs, heads: dict, seq: np.ndarray) -> None:
    """``.rawire`` rows straight from the headers, with the program's writer."""
    from ruleset_analysis_tpu.hostside import pack, wire

    gid = np.array([packed.acl_gid[(rs.firewall, n)] for n in rs.acls], dtype=np.uint32)
    t = np.zeros((pack.TUPLE_COLS, seq.size), dtype=np.uint32)
    for i, k in enumerate(gen_traffic.FIELDS):
        col = heads[k][seq]
        t[i] = gid[col] if k == "acl" else col
    t[pack.T_VALID] = 1
    with wire.WireWriter(path, wire.ruleset_fingerprint(packed)) as w:
        w.add(pack.compact_batch(t), raw_lines=seq.size, skipped=0)


def one_pass(cell, packed, paths, cfg):
    from ruleset_analysis_tpu.runtime.stream import run_stream_file, run_stream_wire

    t0 = harness.now()
    if cell.traffic["input"] == "wire":
        rep = run_stream_wire(packed, paths, cfg)
    else:
        rep = run_stream_file(packed, paths, cfg)
    return json.loads(rep.to_json()), harness.now() - t0


def run(cell: harness.Cell, t_start: float) -> dict:
    import jax

    tr = cell.traffic
    rs, rows, packed = drivers_common.prepare_ruleset(cell)
    n = tr["corpus_lines"]
    heads, seq = gen_traffic.make_flows(rs, rows, tr, cell.seed, n)
    corpus = os.path.join(cell.work, "corpus" + (".rawire" if tr["input"] == "wire" else ".log"))
    if tr["input"] == "wire":
        write_wire(corpus, packed, rs, heads, seq)
    else:
        write_text(corpus, gen_traffic.render_lines(rs, heads), seq)
    harness.say(f"ruleset: {rs.n_aces} ACEs, {rs.n_rows} rows; corpus "
                f"{n} lines over {heads['acl'].size} headers, "
                f"{os.path.getsize(corpus)} bytes; set-up so far "
                f"{harness.now() - t_start:.3f}s")
    cfg = drivers_common.analysis_config(cell)
    os.chdir(cell.work)  # anything the run writes beside itself stays here
    # the first pass compiles: two copies, so it steps a fresh state and a
    # donated one, which the program compiles apart (PERF.md, Findings)
    rep, dt = one_pass(cell, packed, [corpus] * 2, cfg)
    harness.say(f"warm-up pass over 2 copies: {dt:.4f}s (compile_sec "
                f"{rep['totals'].get('compile_sec')})")
    passes = []  # (copies, wall), doubling until a pass lasts CALIBRATE_S
    copies = 1
    while True:
        rep, dt = one_pass(cell, packed, [corpus] * copies, cfg)
        passes.append((copies, dt))
        harness.say(f"warm-up pass over {copies} copies: {rep['totals']['lines_total']} "
                    f"lines in {dt:.4f}s, {rep['totals']['lines_total'] / dt:.1f} lines/s")
        if dt >= CALIBRATE_S and len(passes) >= 2:
            break
        copies *= 2
    (c1, w1), (c2, w2) = passes[-2:]
    # a copy's time without the call's own, held within [1/3, 1] of the last
    # pass's mean: a noisy pass can at most triple the window
    per_copy = min(max((w2 - w1) / (c2 - c1), w2 / (3 * c2)), w2 / c2)
    fixed = max(w2 - c2 * per_copy, 0.0)
    k = max(1, math.ceil(max(cell.seconds - fixed, 0.0) * tr["window_margin"] / per_copy))
    setup_s = harness.now() - t_start

    if cell.trace:
        tdir = os.path.join(cell.work, "trace")
        rec = hlo_scopes.ProgramRecorder()
        with rec.installed():
            jax.profiler.start_trace(tdir)
            rep, wall = one_pass(cell, packed, [corpus] * k, cfg)
            jax.profiler.stop_trace()
    else:
        rep, wall = one_pass(cell, packed, [corpus] * k, cfg)
    mem = harness.memory_peak(jax.devices())
    lines = rep["totals"]["lines_total"]
    harness.say(f"window: one run over {k} copies = {lines} lines in {wall:.4f}s "
                f"(compile_sec {rep['totals'].get('compile_sec')}, ingest "
                f"{rep['totals'].get('ingest')})")

    metrics = {}
    result = {"device": {**cell.device, "memory_peak_bytes": mem}}
    if cell.trace:
        tred = trace_reduce.reduce_dir(tdir, cell.workload["chips"], rec.scopes())
        result["device"].update(busy_s=tred["busy_s"], window_s=wall)
        result["breakdown"] = trace_reduce.breakdown(tred)
        metrics = harness.read_layer_metrics(cell, {
            "cell": cell, "report": rep, "wall_s": wall, "lines": lines,
            "trace": tred, "rows_real": int(rs.n_rows), "chips": cell.workload["chips"],
        })
    else:
        values = {f"run_lines_per_s.{tr['input']}": lines / wall, "setup_s": setup_s}
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    # the reference, after the window and with the program's state freed
    t0 = harness.now()
    ace_of = reference.first_match(rows, heads, len(rs.acls))
    mult = np.bincount(seq, minlength=heads["acl"].size) * k
    exp = reference.Expected(rs, heads, ace_of, mult, cell.config["analysis"]["sketch"])
    numbers = reference.compare(rep, exp)
    harness.say(f"reference: {harness.now() - t0:.3f}s")
    ok, checks = harness.judge(numbers, LIMITS)
    result.update(correct=ok, attempted=n * k, failed=n * k - lines,
                  metrics=metrics, checks=checks)
    return result
