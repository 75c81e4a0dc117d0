"""Test harness setup.

Must run before any jax import: force the CPU backend with 8 fake devices so
multi-chip sharding tests (SURVEY.md §5 "multi-node without a cluster") run
anywhere, exactly as they would on a real v5e-8 mesh.  No test module
imports jax before this file runs.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# Disable the flight recorder's DEFAULT-on CLI arming (DESIGN §20)
# so incidental cli.main() invocations across the suite don't write
# out/blackbox forensics into the working tree; tests that exercise
# the recorder pass an explicit --blackbox-dir, which overrides this.
os.environ.setdefault("RA_BLACKBOX", "off")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# NOTE: the persistent compilation cache stays off for the suite
# (runtime/compcache.py skips it under JAX_PLATFORMS=cpu): on this jaxlib
# the XLA:CPU persistent cache reloads executables that compute WRONG
# register values (observed: corrupted HLL registers in the distributed
# wire test).

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time as _time  # noqa: E402

import pytest  # noqa: E402  (after the environment set-up above)

# ---------------------------------------------------------------------------
# Test-calibration note (carried forward from PR 10): the 2 in-suite
# distributed flakes occasionally seen on this 1-core container are LOAD
# artifacts — xla:cpu collective/heartbeat timeouts when the host is
# oversubscribed — not product bugs.  Do not chase them, and NEVER run
# anything concurrently with the tier-1 gate run (a parallel build or
# bench steals the core and manufactures exactly these failures).
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Tier-1 wall-budget guard (ROADMAP: the `-m 'not slow'` suite must stay
# under the 870 s gate, with headroom).  Suite-budget discipline is part
# of the test contract — new variant tests share compiles and mark
# redundant matrix cells `slow` — and this hook makes an overrun a FAILED
# run instead of a silent drift toward the external timeout.  Active only
# for full-suite sessions (small selections tell nothing about the gate).
# ---------------------------------------------------------------------------

_T1_GATE_SEC = float(os.environ.get("RA_T1_GATE_SEC", "870"))
_T1_WARN_FRAC = 0.92  # loudly flag runs inside the last 8% of the gate
_T1_MIN_TESTS = 400  # below this the session is a hand-picked subset
_t1_start = _time.monotonic()


def pytest_sessionfinish(session, exitstatus):
    collected = getattr(session, "testscollected", 0)
    if collected < _T1_MIN_TESTS:
        return
    # only the `-m 'not slow'` tier is governed by the gate: a full run
    # INCLUDING the slow soak legitimately exceeds it and must not be
    # turned into a spurious failure
    markexpr = getattr(session.config.option, "markexpr", "") or ""
    if "not slow" not in markexpr:
        return
    dur = _time.monotonic() - _t1_start
    frac = dur / _T1_GATE_SEC
    line = (
        f"[t1-budget] {dur:.1f}s of the {_T1_GATE_SEC:.0f}s tier-1 gate "
        f"({100 * frac:.1f}%, {collected} tests)"
    )
    if dur > _T1_GATE_SEC:
        print(f"{line} — EXCEEDED: mark redundant cells `slow` or share "
              "compiles (see ROADMAP tier-1 verify)", file=sys.stderr)
        if exitstatus == 0:
            session.exitstatus = 1
    elif frac > _T1_WARN_FRAC:
        print(f"{line} — WARNING: inside the gate's last "
              f"{100 * (1 - _T1_WARN_FRAC):.0f}%", file=sys.stderr)
    else:
        print(line, file=sys.stderr)


@pytest.fixture(autouse=True)
def _no_leaked_workers():
    """Fail any test that leaks our worker threads or child processes.

    Every thread the pipeline spawns carries an ``ra-`` name prefix
    (ingest producers, feed workers, heartbeats, watchdogs) and every
    worker process is a ``multiprocessing`` child, so a cheap enumerate
    catches a shutdown path that stranded one — the audit the chaos
    harness relies on ("zero leaked threads/processes").  A short grace
    window absorbs teardown still in flight; the zero-leak case costs
    one enumerate and no sleep.
    """
    yield
    import multiprocessing
    import threading
    import time

    def leaked():
        ts = [
            t
            for t in threading.enumerate()
            if t.is_alive() and t.name.startswith("ra-")
        ]
        return ts, multiprocessing.active_children()

    deadline = time.monotonic() + 5.0
    ts, procs = leaked()
    while (ts or procs) and time.monotonic() < deadline:
        for p in procs:
            p.join(timeout=0.1)
        time.sleep(0.05)
        ts, procs = leaked()
    assert not ts and not procs, (
        f"leaked workers after test: threads={[t.name for t in ts]} "
        f"processes={[p.pid for p in procs]}"
    )
