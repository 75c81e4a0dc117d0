"""`correct` is decided by the reference: sound runs pass, the control and
each fault a cell can have fail.  CPU, at a size a test run holds."""

from __future__ import annotations

import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from cellrun import run_cell  # noqa: E402

# a corpus that is no multiple of the batch, as in the cells (PERF.md, Findings)
SMALL = {"traffic": {"corpus_lines": 16000}, "analysis": {"batch_size": 4096},
         "filters": 400}
CELLS = [("asa-1k.text-run", SMALL), ("asa-10k.wire-run", SMALL)]
SEED = 2**33 + 17


def wrong(res: dict) -> dict:
    return {k: c["value"] for k, c in res["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("workload,small", CELLS)
def test_sound_run_is_correct(workload, small):
    res = run_cell(workload, SEED, 0.5, small=small, off_chip=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


@pytest.mark.parametrize("workload,small", CELLS)
def test_control_is_not_correct(workload, small):
    res = run_cell(workload, SEED, 0.5, small=small, off_chip=True, control=True)
    assert not res["correct"]
    # the lower-precision sketches read wrong on both sketch numbers
    assert {"unique_wrong", "talkers_wrong"} <= set(wrong(res)), res["checks"]
