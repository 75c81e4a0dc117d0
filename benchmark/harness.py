"""What every driver shares: the cell, the chip check, the result line."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import time


class BenchError(RuntimeError):
    """The cell cannot run here, or a run did not finish."""


def say(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    root: str  # the checkout
    here: str  # benchmark/
    name: str
    seed: int
    seconds: float
    trace: bool
    bench: dict
    workload: dict
    config: dict
    traffic: dict
    peaks: dict
    device: dict
    work: str

    @classmethod
    def load(cls, root: str, here: str, args) -> "Cell":
        path = os.path.join(root, "BENCHMARK.json")
        try:
            with open(path, encoding="utf-8") as f:
                bench = json.load(f)
        except OSError as e:
            raise BenchError(f"cannot read {path}: {e}") from None
        return cls.of(bench, root, here, args)

    @classmethod
    def of(cls, bench: dict, root: str, here: str, args) -> "Cell":
        wl = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
        if wl is None:
            raise BenchError(f"no workload {args.workload!r} in BENCHMARK.json")
        cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
        with open(os.path.join(root, cfg_entry["file"]), encoding="utf-8") as f:
            config = json.load(f)
        with open(os.path.join(here, "traffic", f"{wl['traffic']}.json"), encoding="utf-8") as f:
            traffic = json.load(f)
        with open(os.path.join(here, "peaks.json"), encoding="utf-8") as f:
            peaks = json.load(f)
        device = check_chips(wl["chips"], peaks)
        enable_cache(root)
        work = os.path.join(root, ".bench_work", wl["name"])
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        return cls(root, here, wl["name"], args.seed, args.seconds, bool(args.trace),
                   bench, wl, config, traffic, peaks, device, work)

    def cleanup(self) -> None:
        shutil.rmtree(self.work, ignore_errors=True)

    def per_layer(self) -> list[dict]:
        """The per-layer metrics this cell reports (BENCHMARK.json's rule)."""
        mine = {m["name"] for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])}
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in mine:
                out.append(m)
        return out

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]


def check_chips(chips: int, peaks: dict) -> dict:
    """The TPU this run holds; refuses anything else (no CPU fallback)."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        raise BenchError(f"needs {chips} TPU chip(s); JAX found {info}")
    if info["kind"] not in peaks:
        raise BenchError(f"device kind {info['kind']!r} is not in peaks.json")
    return info


def enable_cache(root: str) -> str:
    """JAX's persistent compile cache, at a fixed path inside the checkout
    unless the environment names one (the program then takes that)."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_cache"))
    try:
        from ruleset_analysis_tpu.runtime.compcache import enable_persistent_cache
    except ImportError as e:
        raise BenchError(f"the system under test is not in this checkout: {e}") from None

    return enable_persistent_cache()


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def reader_path(here: str, metric: str) -> str:
    """``metrics/<metric>.py``, else the reader of the name before its last
    dot: ``device_idle_share.text`` and ``.wire`` share ``device_idle_share.py``."""
    path = os.path.join(here, "metrics", f"{metric}.py")
    if not os.path.exists(path) and "." in metric:
        path = os.path.join(here, "metrics", f"{metric.rsplit('.', 1)[0]}.py")
    return path


def read_layer_metrics(cell: Cell, lctx: dict) -> dict:
    """Run each per-layer metric's reader.  A reader that finds nothing
    returns None: a metric whose ``workloads`` lists this cell then fails
    the run, since its source is gone; one without the key is left out."""
    import importlib.util

    out = {}
    for m in cell.per_layer():
        spec = importlib.util.spec_from_file_location(
            f"metric_{len(out)}", reader_path(cell.here, m["name"]))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(lctx)
        if v is None and "workloads" in m:
            raise BenchError(f"per-layer metric {m['name']} found nothing to read")
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def emit(result: dict) -> None:
    """Checks on stderr as its last lines, then the result as stdout's last."""
    checks = result["checks"]
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    line = {k: result[k] for k in ("correct", "attempted", "failed", "metrics", "device")}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(line), flush=True)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def now() -> float:
    return time.perf_counter()
