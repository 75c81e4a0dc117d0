"""Run one benchmark cell once: ``--workload NAME --seed N --seconds S --trace 0|1``.

Everything is found by name: the cell in ``BENCHMARK.json`` names its
configuration (``configs/<name>.json``) and its traffic mix
(``traffic/<mix>.json``); the mix names the driver that offers it
(``drivers/<mode>.py``); each per-layer metric is read by
``metrics/<metric>.py``, or by the reader of its name before the last dot
(``metrics/device_idle_share.py`` reads ``device_idle_share.text``).  A new
cell, mix or metric is new files only.

The last line of standard output is the result's JSON object.  Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from process start

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(1, ROOT)
    import harness

    try:
        ctx = harness.Cell.load(ROOT, HERE, args)
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    driver = load_module(os.path.join(HERE, "drivers", f"{ctx.traffic['mode']}.py"),
                         f"driver_{ctx.traffic['mode']}")
    try:
        result = driver.run(ctx, T_START)
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    finally:
        ctx.cleanup()
    harness.emit(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
