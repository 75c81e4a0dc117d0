"""Chip smoke: drive `run` and `serve` once on a TPU, checked against the oracle.

Everything that needs the chip runs in this one process, through
``ruleset_analysis_tpu.cli.main`` or the stream driver it calls.  The only
children are the oracle workers: pure Python, pinned to the CPU.  All data
comes from ``--seed`` through ``synth``; nothing outside the repo is read.

    python chip_smoke.py               # one chip: run, run variants, serve
    python chip_smoke.py --four-chips  # a 4-chip flat mesh against 1 chip

The corpus is an enterprise-scale ASA: 8 ACLs x 512 rules (4,096 rules,
~7.5k expanded v4 rows plus a v6 section) and 2^22 mixed v4/v6 lines,
stepped in 2^20-line batches.  Earlier lines print smoke timings, which
are not benchmark numbers; on success the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or away from the repo, it prints no result and exits
non-zero.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import multiprocessing
import os
import shutil
import sys
import threading
import time
import traceback
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".smoke_work")


@dataclasses.dataclass(frozen=True)
class Size:
    """Corpus and geometry of one smoke run."""

    acls: int = 8
    rules: int = 512  # per ACL
    lines: int = 1 << 22
    v6_fraction: float = 0.1
    batch: int = 1 << 20  # 4 device steps over the corpus
    window: int = 1 << 18  # serve window, in lines
    windows: int = 3


class SmokeFailure(RuntimeError):
    """A phase disagreed with the oracle or failed a check."""


def say(*parts) -> None:
    print("[smoke]", *parts, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def cli(argv: list[str]) -> None:
    from ruleset_analysis_tpu import cli as cli_mod

    rc = cli_mod.main(argv)
    check(rc == 0, f"`{' '.join(argv[:1])}` exited {rc}: {argv}")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


# ---------------------------------------------------------------------------
# Corpus and the oracle reference
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Corpus:
    work: str
    config: str  # the ASA config text file
    prefix: str  # packed ruleset path prefix
    log: str  # the syslog corpus


def make_corpus(work: str, size: Size, seed: int) -> Corpus:
    cli([
        "synth", "--out-dir", work, "--acls", str(size.acls),
        "--rules", str(size.rules), "--lines", str(size.lines),
        "--v6-fraction", str(size.v6_fraction), "--seed", str(seed),
    ])
    corpus = Corpus(
        work, os.path.join(work, "fw1.cfg"), os.path.join(work, "packed"),
        os.path.join(work, "fw1.log"),
    )
    cli(["parse-acls", corpus.config, "--out", corpus.prefix])
    return corpus


def _pin_to_cpu() -> None:
    # oracle workers never need the chip, which this process holds
    os.environ["JAX_PLATFORMS"] = "cpu"


def _oracle_worker(argv: list[str]) -> int:
    from ruleset_analysis_tpu import cli as cli_mod

    return cli_mod.main(argv)


def oracle_reports(corpus: Corpus, logs: list[str], workers: int) -> list[dict]:
    """`run --backend oracle` on each file, in a pool of CPU-only workers."""
    outs = [f"{p}.oracle.json" for p in logs]
    argvs = [
        ["run", "--ruleset", corpus.prefix, "--logs", p, "--backend",
         "oracle", "--acl-configs", corpus.config, "--json", "--out", o]
        for p, o in zip(logs, outs)
    ]
    ctx = multiprocessing.get_context("spawn")
    pool = ctx.Pool(min(workers, len(argvs)), initializer=_pin_to_cpu)
    try:
        rcs = pool.map(_oracle_worker, argvs)
    finally:
        pool.close()
        pool.join()
    check(rcs == [0] * len(rcs), f"oracle workers exited {rcs}")
    reps = []
    for o in outs:
        with open(o, encoding="utf-8") as f:
            reps.append(json.load(f))
    return reps


def exact_view(rep: dict) -> dict:
    """What must agree exactly: per-rule hits, unused rules, line total."""
    return {
        "hits": {
            (e["firewall"], e["acl"], e["index"]): e["hits"]
            for e in rep["per_rule"]
        },
        "unused": [tuple(u) for u in rep["unused"]],
        "lines_total": rep["totals"]["lines_total"],
    }


def merge_views(views: list[dict]) -> dict:
    """The exact view of the concatenation of the views' inputs."""
    hits: collections.Counter = collections.Counter()
    for v in views:
        hits.update(v["hits"])
    unused_everywhere = set.intersection(*(set(v["unused"]) for v in views))
    return {
        "hits": dict(hits),
        "unused": [u for u in views[0]["unused"] if u in unused_everywhere],
        "lines_total": sum(v["lines_total"] for v in views),
    }


def compare(got: dict, ref: dict, what: str) -> None:
    bad = [k for k in ref["hits"] if got["hits"].get(k) != ref["hits"][k]]
    check(
        not bad and got["hits"].keys() == ref["hits"].keys(),
        f"{what}: {len(bad)} rules' hits differ from the oracle, e.g. "
        + ", ".join(f"{k}: {got['hits'].get(k)} != {ref['hits'][k]}" for k in bad[:3]),
    )
    check(
        got["unused"] == ref["unused"],
        f"{what}: unused list differs from the oracle "
        f"({len(got['unused'])} vs {len(ref['unused'])} rules)",
    )
    check(
        got["lines_total"] == ref["lines_total"],
        f"{what}: lines_total {got['lines_total']} != oracle {ref['lines_total']}",
    )


def split_lines(path: str, n: int) -> list[str]:
    """Cut ``path`` into ``n`` files at line boundaries (about equal bytes)."""
    total = os.path.getsize(path)
    outs = []
    with open(path, "rb") as f:
        for i in range(n):
            out = f"{path}.part{i}"
            end = total if i == n - 1 else (i + 1) * total // n
            with open(out, "wb") as g:
                while f.tell() < end:
                    g.write(f.readline())
            outs.append(out)
    return outs


def oracle_reference(corpus: Corpus, workers: int) -> dict:
    t0 = time.perf_counter()
    parts = split_lines(corpus.log, workers)
    ref = merge_views([exact_view(r) for r in oracle_reports(corpus, parts, workers)])
    say(
        f"oracle: {ref['lines_total']} lines, {len(ref['unused'])} unused "
        f"rules, {workers} workers, {time.perf_counter() - t0:.1f}s"
    )
    return ref


# ---------------------------------------------------------------------------
# run
# ---------------------------------------------------------------------------


def run_tpu(corpus: Corpus, size: Size, name: str, logs: list[str],
            extra: list[str], ref: dict) -> dict:
    out = os.path.join(corpus.work, f"{name}.json")
    t0 = time.perf_counter()
    cli([
        "run", "--ruleset", corpus.prefix, "--logs", *logs, "--backend",
        "tpu", "--batch-size", str(size.batch), "--json", "--out", out,
        *extra,
    ])
    wall = time.perf_counter() - t0
    with open(out, encoding="utf-8") as f:
        rep = json.load(f)
    compare(exact_view(rep), ref, name)
    t = rep["totals"]
    say(
        f"{name}: matches the oracle exactly | smoke timings, not benchmark "
        f"numbers: wall {wall:.2f}s, compile {t.get('compile')}, "
        f"lines_per_sec {t.get('lines_per_sec')}, sustained_lines_per_sec "
        f"{t.get('sustained_lines_per_sec')}, chunks {t.get('chunks')}"
    )
    return rep


class DispatchRecorder:
    """Stands at devprof's dispatch seam (``devprof.active_capture()``).

    Keeps the jit and the argument shapes of the first dispatch of each
    step program, so the program that actually ran can be lowered again
    and inspected; runs the dispatch unchanged, or (``run=False``, for a
    compile against a described chip) not at all.
    """

    def __init__(self, run: bool = True):
        self.run = run
        self.programs: dict[str, tuple] = {}

    def dispatch(self, label, fn, args):
        import jax

        def shape_of(x):
            # mesh-placed arguments keep their placement; anything else
            # (a fresh state on one device, the salt) is left to jit
            named = isinstance(x.sharding, jax.sharding.NamedSharding)
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding if named else None
            )

        if label not in self.programs:
            self.programs[label] = (fn, jax.tree.map(shape_of, args))
        return fn(*args) if self.run else (None, None)

    # the rest of the capture interface `run` calls: nothing to do
    def finalize(self):
        return None

    def abort(self):
        pass

    @contextlib.contextmanager
    def installed(self):
        from ruleset_analysis_tpu.runtime import devprof

        devprof._capture = self
        try:
            yield self
        finally:
            devprof._capture = None

    def lower(self, label: str):
        fn, args = self.programs[label]
        return fn.lower(*args)


def run_pallas(corpus: Corpus, size: Size, ref: dict) -> bool:
    """`run --match-impl pallas`; True when its step holds the compiled kernel."""
    with DispatchRecorder().installed() as rec:
        run_tpu(corpus, size, "run-pallas", [corpus.log],
                ["--match-impl", "pallas"], ref)
    compiled = "tpu_custom_call" in rec.lower("step.flat").as_text()
    say(
        "run-pallas: the step that ran holds the "
        + ("compiled Pallas kernel (tpu_custom_call)" if compiled
           else "interpreted Pallas kernel (no tpu_custom_call)")
    )
    return compiled


def run_phases(corpus: Corpus, size: Size, ref: dict) -> bool:
    """Text, wire, process-feeder and Pallas runs, each equal to the oracle.

    Returns whether the Pallas run's step held the compiled kernel.
    """
    from ruleset_analysis_tpu.hostside import fastparse

    say(f"parser: {'native' if fastparse.available() else 'python'}")
    run_tpu(corpus, size, "run", [corpus.log], [], ref)
    wire = os.path.join(corpus.work, "fw1.rawire")
    t0 = time.perf_counter()
    cli(["convert", "--ruleset", corpus.prefix, "--logs", corpus.log, "--out", wire])
    say(f"convert: {time.perf_counter() - t0:.2f}s")
    run_tpu(corpus, size, "run-wire", [wire], [], ref)
    run_tpu(corpus, size, "run-feeder", [corpus.log],
            ["--feed-workers", "2", "--feed-mode", "process"], ref)
    return run_pallas(corpus, size, ref)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def _get(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def _wait_for(path: str, thread: threading.Thread, timeout: float,
              health: str | None = None) -> None:
    """Wait for serve to write ``path``; fail fast once it drops a line."""
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        check(thread.is_alive(), f"serve exited before {os.path.basename(path)}")
        check(time.monotonic() < deadline, f"no {os.path.basename(path)} in {timeout}s")
        if health is not None:
            dropped = _get(health)["queue"]["dropped"]
            check(dropped == 0, f"serve dropped {dropped} lines (queue full)")
        time.sleep(0.2)


def serve_phase(corpus: Corpus, size: Size, workers: int, timeout: float) -> None:
    """`serve` over a tailed file; every window equals the oracle on its lines."""
    n, w = size.windows, size.window
    with open(corpus.log, "rb") as f:
        lines = [f.readline() for _ in range(n * w)]
    check(len(lines[-1]) > 0, f"corpus holds fewer than {n * w} lines")
    win_logs = []
    for i in range(n):
        p = os.path.join(corpus.work, f"window-{i}.log")
        with open(p, "wb") as g:
            g.writelines(lines[i * w:(i + 1) * w])
        win_logs.append(p)
    refs = [exact_view(r) for r in oracle_reports(corpus, win_logs, workers)]

    sd = os.path.join(corpus.work, "serve")
    tail = os.path.join(corpus.work, "serve-input.log")
    open(tail, "wb").close()  # tail listeners start at the end of a file
    rc: dict = {}
    from ruleset_analysis_tpu import cli as cli_mod

    # the spool grows by whole windows at once; listeners drop (and
    # count) what a full queue cannot take, so the queue holds a burst
    argv = [
        "serve", "--ruleset", corpus.prefix, "--listen", f"tail:{tail}",
        "--window", f"lines:{w}", "--max-windows", str(n),
        "--http", "127.0.0.1:0", "--serve-dir", sd,
        "--queue-lines", str(n * w),
    ]
    th = threading.Thread(
        target=lambda: rc.update(rc=cli_mod.main(argv)),
        name="smoke-serve", daemon=True,
    )
    t0 = time.perf_counter()
    th.start()
    _wait_for(os.path.join(sd, "endpoint.json"), th, timeout)
    with open(os.path.join(sd, "endpoint.json"), encoding="utf-8") as f:
        host, port = json.load(f)["http"]
    base = f"http://{host}:{port}"
    time.sleep(1.0)  # let the tailer open the file
    with open(tail, "ab") as f:
        for i in range(n - 1):
            f.writelines(lines[i * w:(i + 1) * w])
    _wait_for(os.path.join(sd, f"window-{n - 2:06d}.json"), th, timeout,
              health=base + "/health")
    report, metrics, health = (
        _get(base + "/report"), _get(base + "/metrics"), _get(base + "/health")
    )
    check(
        report["totals"]["window"]["id"] == n - 2,
        f"/report serves window {report['totals']['window']['id']}, not {n - 2}",
    )
    check("build_info" in metrics, "/metrics has no build_info")
    check(
        health["degraded_subsystems"] == [] and health["queue"]["dropped"] == 0,
        f"degraded subsystems {health['degraded_subsystems']}, "
        f"{health['queue']['dropped']} lines dropped",
    )
    check(th.is_alive(), "serve exited before the last window's lines arrived")
    with open(tail, "ab") as f:
        f.writelines(lines[(n - 1) * w:])
    th.join(timeout)
    check(not th.is_alive(), f"serve did not stop within {timeout}s")
    check(rc.get("rc") == 0, f"serve exited {rc.get('rc')}")
    wall = time.perf_counter() - t0
    for i in range(n):
        with open(os.path.join(sd, f"window-{i:06d}.json"), encoding="utf-8") as f:
            compare(exact_view(json.load(f)), refs[i], f"serve window {i}")
    say(
        f"serve: {n} windows of {w} lines match the oracle exactly; /report, "
        f"/metrics and /health answered before the last window, no degraded "
        f"subsystems | smoke timing, not a benchmark number: wall {wall:.2f}s"
    )


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def mesh_phase(corpus: Corpus, size: Size, ref: dict, devices) -> None:
    """The same corpus on a flat mesh over ``devices`` and on 1 chip.

    Exact hits equal the oracle on both, and every register (exact
    counts, CMS, HLL, the top-K talker CMS) is bit-identical.
    """
    import numpy as np

    from ruleset_analysis_tpu.config import AnalysisConfig
    from ruleset_analysis_tpu.hostside import pack
    from ruleset_analysis_tpu.parallel import mesh as mesh_lib
    from ruleset_analysis_tpu.runtime import checkpoint as ckpt
    from ruleset_analysis_tpu.runtime.stream import run_stream_file

    packed = pack.load_packed(corpus.prefix)
    regs = {}
    for n in (len(devices), 1):
        cdir = os.path.join(corpus.work, f"ckpt-{n}")
        cfg = AnalysisConfig(
            batch_size=size.batch,
            checkpoint_every_chunks=1 << 30,  # one snapshot, at the end
            checkpoint_dir=cdir,
        )
        t0 = time.perf_counter()
        rep = run_stream_file(
            packed, [corpus.log], cfg, mesh=mesh_lib.make_mesh(devices[:n])
        )
        compare(exact_view(json.loads(rep.to_json())), ref, f"{n}-chip run")
        regs[n] = ckpt.load(cdir).arrays
        t = rep.totals
        say(
            f"{n}-chip run: matches the oracle exactly | smoke timings, not "
            f"benchmark numbers: wall {time.perf_counter() - t0:.2f}s, "
            f"compile {t.get('compile')}, sustained_lines_per_sec "
            f"{t.get('sustained_lines_per_sec')}"
        )
    many = len(devices)
    for name in sorted(regs[1]):
        check(
            np.array_equal(regs[many][name], regs[1][name]),
            f"register {name} differs between {many} chips and 1 chip",
        )
    say(f"registers {sorted(regs[1])}: bit-identical on {many} chips and 1 chip")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def one_chip(work: str, size: Size, seed: int, workers: int,
             serve_timeout: float) -> bool:
    """Every one-chip phase; returns whether Pallas ran compiled."""
    t0 = time.perf_counter()
    corpus = make_corpus(work, size, seed)
    say(f"corpus: {size.lines} lines, {time.perf_counter() - t0:.1f}s")
    ref = oracle_reference(corpus, workers)
    compiled = run_phases(corpus, size, ref)
    serve_phase(corpus, size, workers, serve_timeout)
    return compiled


def four_chips(work: str, size: Size, seed: int, workers: int) -> None:
    import jax

    corpus = make_corpus(work, size, seed)
    ref = oracle_reference(corpus, workers)
    mesh_phase(corpus, size, ref, jax.devices()[:4])


def _entries(cache: str | None) -> int:
    return len(os.listdir(cache)) if cache and os.path.isdir(cache) else 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip flat mesh against 1 chip")
    ap.add_argument("--seed", type=int, default=0, help="corpus seed")
    args = ap.parse_args(argv)
    try:
        import jax  # noqa: F401

        import ruleset_analysis_tpu
    except ImportError as e:
        print(f"chip_smoke: cannot import what it drives: {e}", file=sys.stderr)
        return 2
    if os.path.dirname(os.path.dirname(ruleset_analysis_tpu.__file__)) != HERE:
        print(f"chip_smoke: {ruleset_analysis_tpu.__file__} is not this "
              "checkout's package", file=sys.stderr)
        return 2
    dev = device_info()
    say(f"device: platform {dev['platform']}, kind {dev['kind']}, count {dev['count']}")
    want = 4 if args.four_chips else 1
    if dev["platform"] != "tpu" or dev["count"] < want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX found {dev}",
              file=sys.stderr)
        return 2
    from ruleset_analysis_tpu.runtime.compcache import enable_persistent_cache

    cache = enable_persistent_cache()
    say(f"compile cache: {cache} ({_entries(cache)} entries before this run)")
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    workers = max(1, min(16, (os.cpu_count() or 2) - 1))
    cwd = os.getcwd()
    os.chdir(WORK)  # relative run outputs (flight recorder) stay in here
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(WORK, Size(), args.seed, workers)
        else:
            check(one_chip(WORK, Size(), args.seed, workers, 300.0),
                  "the Pallas run did not step the compiled kernel")
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    finally:
        os.chdir(cwd)
    say(f"compile cache: {cache} ({_entries(cache)} entries after this run)")
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
