"""Headline benchmark: ASA syslog lines/sec/chip through the device pipeline.

Runs in one process on the TPU and prints ONE JSON line on stdout, naming
the device it ran on (``platform``, ``device_kind``, count).  Without a
TPU it prints no result and exits non-zero, naming what JAX found: a CPU
timing is never a device number.

The headline `value` is the device-pipeline steady-state rate per chip
(batches resident in HBM, state donated) — the per-chip capability number.
`detail` also carries the measured end-to-end rate through the full file
path (text -> native parse -> device_put -> step), plus a roofline-style
utilization estimate, so "is it actually fast" is answerable from the JSON.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))

NORTH_STAR_TOTAL = 1e9 / 60.0  # lines/sec, v5e-8, end-to-end (BASELINE.md)
NORTH_STAR_PER_CHIP = NORTH_STAR_TOTAL / 8.0

#: Per-chip roofline constants keyed by ``device_kind``: a device not in
#: the table gets no utilization share (null), never a borrowed peak.
#: v5e ("TPU v5 lite"), from public TPU v5e specs / the scaling-book
#: numbers: VPU is an (8, 128) vector unit with 4 independent ALUs at
#: ~0.94 GHz -> ~3.85e12 u32 ops/s; HBM bandwidth 819 GB/s (Google Cloud
#: documentation, "TPU v5e").
PEAKS = {
    "TPU v5 lite": {"vpu_u32_ops": 8 * 128 * 4 * 0.94e9, "hbm_bytes": 819e9},
}
# u32 VPU ops per (line, rule-row) predicate cell: 11 compares + 10 ands
# + ~2 for the masked min-index reduction (ops/match.py _block_min_row).
OPS_PER_CELL = 23.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# Child: the actual measurement (runs under a known-healthy backend).
# ---------------------------------------------------------------------------


class BenchInvalid(RuntimeError):
    """A self-validation check failed; the measurement cannot be trusted."""


def device_info() -> dict:
    """The device this process runs on, as JAX reports it."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def run_bench() -> dict:
    import jax
    import numpy as np

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside import aclparse, pack, synth
    from ruleset_analysis_tpu.models import pipeline
    from ruleset_analysis_tpu.parallel import mesh as mesh_lib
    from ruleset_analysis_tpu.parallel.step import make_parallel_step
    from ruleset_analysis_tpu.runtime.compcache import enable_persistent_cache
    from ruleset_analysis_tpu.runtime.timing import timed_validated_steps

    cache_dir = enable_persistent_cache()
    log(f"compilation cache: {cache_dir}")

    devices = jax.devices()
    n_dev = len(devices)
    peaks = PEAKS.get(devices[0].device_kind)
    log(f"devices: {devices}")

    # BASELINE.json config #1 geometry: one realistic ruleset
    cfg_text = synth.synth_config(n_acls=4, rules_per_acl=64, seed=0)
    rs = aclparse.parse_asa_config(cfg_text, "fw1")
    packed = pack.pack_rulesets([rs])
    log(f"ruleset: {packed.n_rules} rules, {packed.rules.shape[0]} expanded rows")

    per_chip_batch = 1 << 20
    batch_size = per_chip_batch * n_dev
    cfg = AnalysisConfig(
        batch_size=batch_size,
        sketch=SketchConfig(cms_width=1 << 14, cms_depth=4, hll_p=8),
    )

    mesh = mesh_lib.make_mesh(devices)
    step = make_parallel_step(mesh, cfg, packed.n_keys)
    rules = pipeline.ship_ruleset(packed)
    state = pipeline.init_state(packed.n_keys, cfg)

    n_feed = 4
    feeds = []
    valid_per_feed = []
    for i in range(n_feed):
        b = np.ascontiguousarray(synth.synth_tuples(packed, batch_size, seed=i).T)
        valid_per_feed.append(int(b[pack.T_VALID].sum()))
        # production wire layout (stream.py ships the same): the step's
        # measured cost includes the on-device bit-unpack
        feeds.append(mesh_lib.shard_batch(mesh, pack.compact_batch(b)))
    log(f"batch: {batch_size} lines x {n_feed} resident feed buffers")

    t0 = time.perf_counter()
    for i in range(2):
        state, _out = step(state, rules, feeds[i % n_feed])
    pipeline.sync_state(state)
    log(f"warmup+compile: {time.perf_counter() - t0:.1f}s")

    # --- self-validating measurement: two runs at 1x and 3x iterations.
    # The count assertion proves the steps executed inside each timed
    # window; the 1x-vs-3x comparison catches a timed window dominated by
    # fixed overhead rather than per-step execution.
    iters = 10
    state, dt1, delta1, expect1 = timed_validated_steps(
        step, state, rules, feeds, valid_per_feed, iters
    )
    if delta1 != expect1:
        raise BenchInvalid(
            f"timed window did not execute: counts moved {delta1}, "
            f"expected {expect1} ({iters} steps x {batch_size} lines)"
        )
    state, dt3, delta3, expect3 = timed_validated_steps(
        step, state, rules, feeds, valid_per_feed, 3 * iters
    )
    if delta3 != expect3:
        raise BenchInvalid(
            f"3x timed window did not execute: counts moved {delta3}, expected {expect3}"
        )
    linearity = (dt3 / 3.0) / dt1  # ~1.0 when per-step time dominates
    if not dt3 > dt1:
        raise BenchInvalid(
            f"3x the steps took no longer ({dt3:.4f}s vs {dt1:.4f}s): "
            "the timed window is not observing execution"
        )
    log(f"timed: {iters} iters {dt1:.3f}s, {3*iters} iters {dt3:.3f}s "
        f"(linearity {linearity:.2f})")

    # headline from the longer run (overheads amortized 3x further)
    lines_per_sec = expect3 / dt3
    per_chip = lines_per_sec / n_dev
    step_ms = dt3 / (3 * iters) * 1e3

    # roofline-style utilization, only against this device kind's peaks
    rows = int(packed.rules.shape[0])
    cells_per_sec_chip = per_chip * rows
    vpu_util = hbm_util = None
    if peaks is not None:
        vpu_util = round(cells_per_sec_chip * OPS_PER_CELL / peaks["vpu_u32_ops"], 4)
        # 16 B/line: the wire-format batch read; rules/registers are
        # VMEM-resident across the batch and contribute ~nothing per line
        hbm_util = round(per_chip * 16.0 / peaks["hbm_bytes"], 6)
        if vpu_util > 1.0:
            raise BenchInvalid(
                f"vpu_util_estimate {vpu_util} > 1.0: measured rate exceeds "
                f"the VPU roofline ({peaks['vpu_u32_ops']:.3g} u32 ops/s); "
                "the timed window cannot be observing real execution"
            )

    # --- step-variant A/Bs: every scatter-bound flip lever from the
    # committed trace attribution (DESIGN.md §8), priced in THE SAME
    # window as the headline so one chip run decides them all.
    # Auxiliary: any failure logs and never sinks the headline.
    def time_variant(name, cfg_v, rules_v=None):
        step_v = make_parallel_step(mesh, cfg_v, packed.n_keys)
        state_v = pipeline.init_state(packed.n_keys, cfg_v)
        r_v = rules if rules_v is None else rules_v
        state_v, _ = step_v(state_v, r_v, feeds[0])  # warmup/compile
        pipeline.sync_state(state_v)
        state_v, dt_v, delta_v, expect_v = timed_validated_steps(
            step_v, state_v, r_v, feeds, valid_per_feed, iters
        )
        if delta_v != expect_v:
            raise BenchInvalid(f"{name} window did not execute")
        out = {
            "step_ms": round(dt_v / iters * 1e3, 3),
            "speedup_vs_default": round((dt1 / iters) / (dt_v / iters), 3),
        }
        log(f"{name}: {out['step_ms']} ms/step ({out['speedup_vs_default']}x)")
        return out

    variants = {}
    try:
        variants["topk_sampled"] = {
            "topk_sample_shift": 3,
            **time_variant(
                "topk sample shift=3",
                cfg.replace(
                    sketch=dataclasses.replace(cfg.sketch, topk_sample_shift=3)
                ),
            ),
        }
    except Exception as e:
        log(f"sampled-selection bench failed: {e!r}")
    try:
        variants["pallas_fused"] = time_variant(
            "pallas_fused step",
            cfg.replace(match_impl="pallas_fused"),
            pipeline.ship_ruleset(packed, match_impl="pallas_fused"),
        )
    except Exception as e:
        log(f"pallas_fused bench failed: {e!r}")
    try:
        variants["counts_matmul"] = time_variant(
            "counts_impl=matmul step", cfg.replace(counts_impl="matmul")
        )
    except Exception as e:
        log(f"counts_matmul bench failed: {e!r}")
    try:
        variants["talk_cms_depth1"] = time_variant(
            "talk_cms_depth=1 step",
            cfg.replace(
                sketch=dataclasses.replace(cfg.sketch, talk_cms_depth=1)
            ),
        )
    except Exception as e:
        log(f"talk_cms_depth1 bench failed: {e!r}")

    e2e = _bench_e2e(packed, mesh, per_chip * n_dev)

    # Profile capture runs LAST: tracing slows the host, so it must never
    # precede a timed section.
    profile = _capture_profile(step, state, rules, feeds)

    detail = {
        "total_lines_per_sec": round(lines_per_sec, 1),
        "batch_size": batch_size,
        "iters": 3 * iters,
        "rules": int(packed.n_rules),
        "expanded_rows": rows,
        "elapsed_sec": round(dt3, 3),
        "step_ms": round(step_ms, 3),
        # self-validation evidence: the timed window is closed by a host
        # fetch whose count delta must equal steps x valid lines, and the
        # per-step time must scale with iteration count
        "checks": {
            "counts_delta_ok": True,
            "counts_delta": int(delta3),
            "linearity_1x_vs_3x": round(linearity, 3),
            "sync": "device_get(counts)",
        },
        # all step-variant A/Bs from this window: sampled candidate
        # selection, pallas_fused, counts_matmul, talk_cms_depth1
        "step_variants": variants,
        # device-step roofline: predicate cells (line x rule-row) per sec
        # per chip, and the share of this device kind's VPU u32-op peak
        "rule_cells_per_sec_per_chip": round(cells_per_sec_chip, 1),
        "vpu_util_estimate": vpu_util,
        "hbm_util_estimate": hbm_util,
        "profile": profile,
        # honest end-to-end decomposition (text -> parse -> transfer ->
        # device); the headline value above is the device-resident rate
        "e2e": e2e,
        "vs_north_star_e2e": (
            round(e2e["lines_per_sec"] / n_dev / NORTH_STAR_PER_CHIP, 4)
            if e2e and "lines_per_sec" in e2e
            else None
        ),
        # wire-tier e2e vs the north star, on the PROJECTED host link
        "vs_north_star_e2e_wire_projected": (
            round(
                e2e["wire_ingest"]["projection_real_host"][
                    "projected_lines_per_sec"
                ]
                / n_dev
                / NORTH_STAR_PER_CHIP,
                4,
            )
            if e2e and "wire_ingest" in e2e
            else None
        ),
    }
    return {
        "metric": "asa_syslog_lines_per_sec_per_chip",
        "device": device_info(),
        "value": round(per_chip, 1),
        "unit": "lines/sec/chip",
        "vs_baseline": round(per_chip / NORTH_STAR_PER_CHIP, 4),
        "detail": detail,
    }


def _capture_profile(step, state, rules, feeds) -> dict | None:
    """Trace a few steps with jax.profiler into profiles/ (best effort).

    The trace answers "is the step match-bound or scatter-bound" on real
    hardware; some PJRT plugins can't profile, so failure only reports
    itself — it never sinks the bench.
    """
    import glob

    import jax

    out_dir = os.path.join(_REPO, "profiles", "bench")
    try:
        from ruleset_analysis_tpu.models import pipeline

        os.makedirs(out_dir, exist_ok=True)
        with jax.profiler.trace(out_dir):
            for i in range(3):
                state, _ = step(state, rules, feeds[i % len(feeds)])
            pipeline.sync_state(state)
        traces = glob.glob(
            os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True
        )
        return {
            "dir": out_dir,
            "captured": bool(traces),
            "trace_files": [os.path.relpath(t, _REPO) for t in traces[:4]],
        }
    except Exception as e:
        log(f"profiler capture failed: {e!r}")
        return {"dir": out_dir, "captured": False, "error": repr(e)[:300]}


def _bench_e2e(packed, mesh, device_lines_per_sec: float) -> dict | None:
    """Decomposed full-path rate: parse-only, transfer-only, overlapped.

    The overlapped run is the honest end-to-end number; the stage rates
    say WHICH stage bounds it.
    """
    import tempfile

    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig
    from ruleset_analysis_tpu.hostside import synth
    from ruleset_analysis_tpu.runtime import stream

    # batch matches the headline device measurement (per-chip batch x
    # devices) so the per-stage rates and the overlapped run price the
    # same chunk geometry; enough chunks that the pipelined ingest
    # actually overlaps (a single-chunk corpus cannot pipeline).
    n_lines = 1 << 22
    batch_size = 1 << 20
    try:
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "bench.log")
            t0 = time.perf_counter()
            synth.synth_syslog_file(packed, path, n_lines, seed=7)
            log(f"e2e corpus: {n_lines} lines in {time.perf_counter()-t0:.1f}s")

            # --- stage 1: host parse only (no device traffic at all)
            parse = _bench_parse_only(packed, path, batch_size)
            parse["feeder_scaling"] = _bench_feeder_scaling(packed, path, batch_size)

            # --- stage 2: host->device transfer only (pre-packed batches)
            h2d = _bench_h2d_only(packed, batch_size, mesh)

            # --- overlapped: the production stream driver
            cfg = AnalysisConfig(
                batch_size=batch_size,
                sketch=SketchConfig(cms_width=1 << 14, cms_depth=4, hll_p=8),
            )
            # warm the jit cache so the timed run measures steady state,
            # not compilation (the step builders are memoized per
            # geometry, so the timed run reuses this run's executables);
            # the driver additionally prices any residual first-dispatch
            # compile separately in totals.compile_sec
            stream.run_stream_file(packed, path, cfg, mesh=mesh, max_chunks=1)
            t0 = time.perf_counter()
            rep = stream.run_stream_file(packed, path, cfg, mesh=mesh)
            dt = time.perf_counter() - t0
            overlapped = n_lines / dt
            # the honest pipelined number: rate with the one-time compile
            # priced out (reported separately), as the driver measures it
            sustained = rep.totals.get("sustained_lines_per_sec") or overlapped
            compile_sec = rep.totals.get("compile_sec", 0.0)
            ingest = rep.totals.get("ingest")

            # --- packed ingest tier (SURVEY §8.2 / VERDICT r3 #2): convert
            # once, then the production wire run — repeated analysis pays
            # no host parse, so its bottleneck should be the device step
            # (or the link).
            from ruleset_analysis_tpu.hostside import wire as wire_mod

            wire_path = os.path.join(td, "bench.rawire")
            t0 = time.perf_counter()
            wstats = wire_mod.convert_logs(
                packed, [path], wire_path,
                batch_size=batch_size, block_rows=batch_size,
            )
            t_convert = time.perf_counter() - t0
            stream.run_stream_wire(packed, wire_path, cfg, mesh=mesh, max_chunks=1)
            t0 = time.perf_counter()
            rep_w = stream.run_stream_wire(packed, wire_path, cfg, mesh=mesh)
            dt_wire = time.perf_counter() - t0
            wire_lps = n_lines / dt_wire
            wire_sustained = (
                rep_w.totals.get("sustained_lines_per_sec") or wire_lps
            )

            rates = {
                "parse_lines_per_sec": parse["lines_per_sec"],
                "h2d_lines_per_sec": h2d["lines_per_sec"],
                "device_lines_per_sec": round(device_lines_per_sec, 1),
                "overlapped_lines_per_sec": round(overlapped, 1),
                "overlapped_sustained_lines_per_sec": round(sustained, 1),
                "wire_ingest_lines_per_sec": round(wire_lps, 1),
            }
            # Real-host H2D projection input: a v5e host moves ≥8 GB/s
            # over PCIe; ROW_BYTES is the wire format's single source of
            # truth for bytes/line
            pcie_lps = 8e9 / wire_mod.ROW_BYTES
            stage_min = min(
                parse["lines_per_sec"], h2d["lines_per_sec"], device_lines_per_sec
            )
            bottleneck = min(
                ("parse", parse["lines_per_sec"]),
                ("h2d_transfer", h2d["lines_per_sec"]),
                ("device_step", device_lines_per_sec),
                key=lambda kv: kv[1],
            )[0]
            return {
                "lines": n_lines,
                "elapsed_sec": round(dt, 3),
                "lines_per_sec": round(overlapped, 1),
                # the pipelined-driver sustained rate (one-time compile
                # priced out below, never silently folded in)
                "sustained_lines_per_sec": round(sustained, 1),
                "compile_sec": round(compile_sec, 4),
                "ingest": ingest,
                "parser": "native" if _native_available() else "python",
                "stages": rates,
                "parse_detail": parse,
                "h2d_detail": h2d,
                "wire_ingest": {
                    "lines_per_sec": round(wire_lps, 1),
                    "sustained_lines_per_sec": round(wire_sustained, 1),
                    "elapsed_sec": round(dt_wire, 3),
                    "convert_sec": round(t_convert, 3),
                    "convert_lines_per_sec": round(n_lines / t_convert, 1),
                    "rows": wstats["rows"],
                    "file_mb": round(wstats["bytes"] / 1e6, 1),
                    "speedup_vs_text_e2e": round(wire_sustained / sustained, 2),
                    # without parse, the wire path is bounded by link+device
                    "bottleneck": min(
                        ("h2d_transfer", h2d["lines_per_sec"]),
                        ("device_step", device_lines_per_sec),
                        key=lambda kv: kv[1],
                    )[0],
                    # Projection, not a measurement: a v5e host is assumed
                    # to move ≥8 GB/s over PCIe; at 16 B/line the projected
                    # wire e2e is min(pcie, device_step).  Both the measured
                    # and projected numbers are reported so neither can
                    # masquerade as the other.
                    "projection_real_host": {
                        "assumed_pcie_bytes_per_sec": 8e9,
                        "pcie_lines_per_sec": round(pcie_lps, 1),
                        "projected_lines_per_sec": round(
                            min(pcie_lps, device_lines_per_sec), 1
                        ),
                        "projected_bottleneck": (
                            "device_step"
                            if device_lines_per_sec < pcie_lps
                            else "pcie_h2d"
                        ),
                    },
                },
                "bottleneck": bottleneck,
                # overlap quality: 1.0 = perfect pipelining to the slowest
                # stage; the serial bound is what zero overlap would give.
                # Measured on the SUSTAINED rate — the one-time compile is
                # priced separately above, not laundered into overlap.
                "pipeline_efficiency": round(sustained / stage_min, 4),
                "pipeline_efficiency_incl_compile": round(
                    overlapped / stage_min, 4
                ),
                "serial_bound_lines_per_sec": round(
                    1.0
                    / (
                        1.0 / parse["lines_per_sec"]
                        + 1.0 / h2d["lines_per_sec"]
                        + 1.0 / device_lines_per_sec
                    ),
                    1,
                ),
            }
    except Exception as e:  # e2e is auxiliary — never sink the headline
        log(f"e2e bench failed: {e!r}")
        return {"error": repr(e)[:500]}


def _bench_parse_only(packed, path: str, batch_size: int) -> dict:
    """Native (or Python) parse of the corpus with no device in the loop."""
    from ruleset_analysis_tpu.hostside import fastparse

    t0 = time.perf_counter()
    total = 0
    if _native_available():
        packer = fastparse.NativePacker(packed)
        for _batch, n in fastparse.batches_from_files([path], packer, batch_size):
            total += n
        parser = "native"
        threads = fastparse.default_parse_threads()
    else:
        from ruleset_analysis_tpu.hostside.pack import LinePacker

        packer = LinePacker(packed)
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            from ruleset_analysis_tpu.runtime.stream import chunked

            for chunk in chunked(f, batch_size):
                packer.pack_lines(chunk, batch_size=batch_size)
                total += len(chunk)
        parser = "python"
        threads = 1
    dt = time.perf_counter() - t0
    log(f"parse-only: {total} lines in {dt:.2f}s = {total/dt:.0f} lines/s")
    return {
        "lines_per_sec": round(total / dt, 1),
        "parser": parser,
        "threads": threads,
        "elapsed_sec": round(dt, 3),
    }


def _bench_feeder_scaling(packed, path: str, batch_size: int) -> dict | None:
    """Parse rate of the multi-process feeder at 1/2/4 workers.

    The input-split tier (SURVEY.md §2 L2): on a multi-core host the rate
    should scale ~linearly with workers; on a single-core host (this dev
    harness) it honestly reports flat numbers and the core count.
    """
    import os

    try:
        from ruleset_analysis_tpu.hostside.feeder import ParallelFeeder

        cores = len(os.sched_getaffinity(0))
        out = {"host_cores": cores}
        for w in (1, 2, 4):
            feeder = ParallelFeeder(packed, [path], n_workers=w)
            it = feeder.batches(0, batch_size)
            # steady state only: the first batch absorbs process spawn
            # (the 'spawn' context re-imports numpy per worker) and the
            # coordinator's scan start — on small inputs that startup
            # would otherwise read as anti-scaling
            first = next(it, None)
            if first is None:
                continue
            t0 = time.perf_counter()
            total = 0
            for _batch, n in it:
                total += n
            dt = time.perf_counter() - t0
            if total:
                out[f"workers_{w}_lines_per_sec"] = round(total / dt, 1)
                log(f"feeder w={w}: {total/dt:.0f} lines/s steady-state")
        return out
    except Exception as e:  # auxiliary measurement — never sink the bench
        log(f"feeder scaling bench failed: {e!r}")
        return {"error": repr(e)[:300]}


def _bench_h2d_only(packed, batch_size: int, mesh) -> dict:
    """Host->device batch transfer rate, synced by a cross-shard readback.

    Measures BOTH layouts — the 16 B/line wire format the stream ships
    and the 28 B/line wide layout it replaced — so the JSON quantifies
    what the bit-packing buys on this link.
    """
    import jax
    import numpy as np

    from ruleset_analysis_tpu.hostside import pack, synth
    from ruleset_analysis_tpu.parallel import mesh as mesh_lib

    wide = np.ascontiguousarray(synth.synth_tuples(packed, batch_size, seed=3).T)
    wire = pack.compact_batch(wide)
    # full reduction, NOT a slice: the batch shards over the mesh's data
    # axis, and a one-shard readback would only prove device 0's transfer
    # finished — the sum's result depends on every shard's bytes
    allsum = jax.jit(lambda x: x.sum(dtype=jax.numpy.uint32))

    def measure(batch) -> tuple[float, float]:
        d = mesh_lib.shard_batch(mesh, batch)  # warmup (allocator)
        np.asarray(allsum(d))
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            d = mesh_lib.shard_batch(mesh, batch)
            np.asarray(allsum(d))  # 4-byte fetch bounding every transfer
        dt = time.perf_counter() - t0
        return reps * batch_size / dt, reps * batch.nbytes / dt / 1e6

    rate, mbps = measure(wire)
    wide_rate, wide_mbps = measure(wide)
    log(f"h2d-only: wire {mbps:.1f} MB/s = {rate:.0f} lines/s; "
        f"wide {wide_mbps:.1f} MB/s = {wide_rate:.0f} lines/s "
        f"(wire speedup {rate/max(wide_rate,1):.2f}x)")
    return {
        "lines_per_sec": round(rate, 1),
        "mb_per_sec": round(mbps, 2),
        "batch_mb": round(wire.nbytes / 1e6, 1),
        "bytes_per_line": round(wire.nbytes / batch_size, 1),
        "wide_lines_per_sec": round(wide_rate, 1),
        "wire_speedup_vs_wide": round(rate / max(wide_rate, 1.0), 3),
    }


def _native_available() -> bool:
    try:
        from ruleset_analysis_tpu.hostside import fastparse

        return fastparse.available()
    except Exception:
        return False


def main() -> int:
    dev = device_info()
    if dev["platform"] != "tpu":
        log(
            f"bench.py measures the TPU; JAX found {dev['count']} "
            f"{dev['platform']} device(s) ({dev['kind']}): no result"
        )
        return 2
    try:
        emit(run_bench())
    except BenchInvalid as e:
        emit(
            {
                "metric": "asa_syslog_lines_per_sec_per_chip",
                "device": dev,
                "value": None,
                "unit": "lines/sec/chip",
                "error": f"self-validation failed: {e}"[:600],
            }
        )
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
