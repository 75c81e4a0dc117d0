"""Self-validating benchmark timing: counts-closed step windows.

Every timed step window in this repo closes with a host fetch of the
count registers, which (a) cannot return before every step in the window
has executed, and (b) yields independent evidence the work happened:
each valid line adds exactly one count.

bench.py and bench_suite.py both use this helper so the sync discipline
cannot drift between them.
"""

from __future__ import annotations

import time


def timed_validated_steps(step, state, rules, feeds, valid_per_feed, iters):
    """Run ``iters`` steps over cycling resident feeds, timed and validated.

    Returns ``(state, dt, delta, expect)``: the new state, the wall time of
    the window (closed by a counts fetch), the measured count delta, and
    the expected delta (``sum of valid lines stepped``).  Callers must
    treat ``delta != expect`` as a measurement-integrity failure.
    """
    from ..models import pipeline

    base = pipeline.counts_total(state)
    t0 = time.perf_counter()
    for i in range(iters):
        state, _out = step(state, rules, feeds[i % len(feeds)])
    total = pipeline.counts_total(state)  # sync + evidence, inside the window
    dt = time.perf_counter() - t0
    expect = sum(valid_per_feed[i % len(valid_per_feed)] for i in range(iters))
    return state, dt, total - base, expect
