"""Drive benchmark cells in this process, for the tests and the control.

``run_cell`` runs ``run.py``'s main for one cell and returns its result
line.  ``small`` shrinks a cell for a CPU test run (``off_chip=True`` also
skips the harness's look for a chip); ``control`` runs the program with the
configuration's sketches one step below what it states: CMS-estimated hits
in place of exact counters, HyperLogLog at ``hll_p - 1``, a talker sketch
one row shallower.  ``breaks`` lists program functions to break for the
run (see test_faults.py).
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import drivers_common  # noqa: E402
import harness  # noqa: E402
import run as run_mod  # noqa: E402

_LOAD = harness.Cell.load.__func__
_CHECK = harness.check_chips
_ACFG = drivers_common.analysis_config


def control_config(cell, **over):
    from ruleset_analysis_tpu.config import SketchConfig

    sk = dict(cell.config["analysis"]["sketch"])
    sk["hll_p"] -= 1
    sk["talk_cms_depth"] = max(1, sk["talk_cms_depth"] - 1)
    return _ACFG(cell, exact_counts=False, sketch=SketchConfig(**sk), **over)


@contextlib.contextmanager
def patched(small: dict | None, off_chip: bool, control: bool):
    def load(cls, root, here, args):
        c = _LOAD(cls, root, here, args)
        if small:
            c.traffic.update(small.get("traffic", {}))
            c.config["analysis"].update(small.get("analysis", {}))
            c.config["filters"] = small.get("filters", c.config["filters"])
        return c

    def fake_chips(chips, peaks):
        import jax

        return {"platform": jax.devices()[0].platform, "kind": "TPU v5 lite",
                "count": len(jax.devices())}

    harness.Cell.load = classmethod(load)
    if off_chip:
        harness.check_chips = fake_chips
    if control:
        drivers_common.analysis_config = control_config
    try:
        yield
    finally:
        harness.Cell.load = classmethod(_LOAD)
        harness.check_chips = _CHECK
        drivers_common.analysis_config = _ACFG


def run_cell(workload: str, seed: int, seconds: float = 1.0, trace: int = 0,
             small: dict | None = None, off_chip: bool = False,
             control: bool = False) -> dict:
    cwd = os.getcwd()
    out = io.StringIO()
    try:
        with patched(small, off_chip, control), contextlib.redirect_stdout(out):
            rc = run_mod.main(["--workload", workload, "--seed", str(seed),
                               "--seconds", str(seconds), "--trace", str(trace)])
    finally:
        os.chdir(cwd)
    if rc != 0:
        raise RuntimeError(f"{workload} seed {seed} exited {rc}")
    return json.loads(out.getvalue().strip().splitlines()[-1])
