"""Each fault a cell can have, planted under the timed path, turns `correct`
false: a step that returns its state unchanged, a step that counts half
its batch, an answer altered where the report is assembled, a talker table
emptied or scrambled.  (The cells
here run on one chip: none has an exchange between chips to leave out.)"""

from __future__ import annotations

import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from cellrun import run_cell  # noqa: E402
from test_correct import CELLS, SEED  # noqa: E402


def _wrap_step(monkeypatch, change):
    from ruleset_analysis_tpu.parallel import step as step_mod

    orig = step_mod.make_parallel_step

    def make(mesh, cfg, n_keys):
        step = orig(mesh, cfg, n_keys)

        def broken(state, ruleset, batch, salt=0):
            return change(step, state, ruleset, batch, salt)

        return broken

    monkeypatch.setattr(step_mod, "make_parallel_step", make)


def state_unchanged(monkeypatch):
    def change(step, state, ruleset, batch, salt):
        import jax
        import jax.numpy as jnp

        kept = jax.tree.map(jnp.copy, state)  # the step donates its input
        _, out = step(state, ruleset, batch, salt)
        return kept, out

    _wrap_step(monkeypatch, change)


def half_batch(monkeypatch):
    def change(step, state, ruleset, batch, salt):
        b = batch.shape[-1]
        return step(state, ruleset, batch.at[..., b // 2:].set(0), salt)

    _wrap_step(monkeypatch, change)


def answer_altered(monkeypatch):
    from ruleset_analysis_tpu.runtime import report as report_mod

    orig = report_mod.build_report

    def build(packed, hits, *a, **k):
        hits = dict(hits)
        key = next(k for k, v in hits.items() if v > 0)
        hits[key] += 1
        return orig(packed, hits, *a, **k)

    monkeypatch.setattr(report_mod, "build_report", build)


def talkers_emptied(monkeypatch):
    from ruleset_analysis_tpu.ops import topk

    monkeypatch.setattr(topk.TopKTracker, "top", lambda self, acl, k: [])


def talkers_scrambled(monkeypatch):
    from ruleset_analysis_tpu.ops import topk

    orig = topk.TopKTracker.top

    def top(self, acl, k):
        return [((s * 0x9E3779B1 + 1) & 0xFFFFFFFF, c) for s, c in orig(self, acl, k)]

    monkeypatch.setattr(topk.TopKTracker, "top", top)


FAULTS = [state_unchanged, half_batch, answer_altered, talkers_emptied, talkers_scrambled]


@pytest.mark.parametrize("fault", FAULTS,
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload,small", CELLS, ids=[c[0] for c in CELLS])
def test_fault_is_not_correct(monkeypatch, fault, workload, small):
    fault(monkeypatch)
    res = run_cell(workload, SEED, 0.5, small=small, off_chip=True)
    assert not res["correct"], res["checks"]
