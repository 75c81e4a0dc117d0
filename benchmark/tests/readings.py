"""Readings for the limits, at a cell's own size, on the chip.

    python benchmark/tests/readings.py --workload NAME --seeds 1,2,3 [--control]

Runs the cell once per seed in this one process, with a short window
(``--seconds``), and prints each run's compared numbers: the program's
readings set the lower end of each limit, the control's (``--control``:
cellrun.control_config) the upper.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json

from cellrun import run_cell


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    a = ap.parse_args()
    for seed in (int(s) for s in a.seeds.split(",")):
        res = run_cell(a.workload, seed, a.seconds, control=a.control)
        print(json.dumps({"workload": a.workload, "seed": seed, "control": a.control,
                          "correct": res["correct"],
                          "numbers": {k: c["value"] for k, c in res["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
