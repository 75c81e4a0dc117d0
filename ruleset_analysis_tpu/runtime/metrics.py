"""Observability meters: throughput, dispatch timing, recovery, profiling.

The reference's only visibility is Hadoop's job counters and stdout
(SURVEY.md §6).  Here: a periodic throughput line (lines/sec,
instantaneous and cumulative), the compile-vs-sustained dispatch timer,
recovery-event accounting, and an opt-in ``jax.profiler`` trace whose
output loads in TensorBoard's profile plugin for per-op device timing.
Every meter also feeds the unified tracing + metrics plane
(``runtime/obs.py``) when it is armed — spans for device dispatches and
elastic re-formations, line counters and throughput events for the
metrics JSONL — at a disarmed cost of one None-check per site.
"""

from __future__ import annotations

import math
import sys
import threading
import time

from . import obs

# ---------------------------------------------------------------------------
# Fixed-bucket latency histograms (DESIGN §20).  Log2 bucket bounds with
# u64 counts: mergeable across processes/windows by plain addition (the
# same merge-law discipline as the device registers — associative,
# commutative, order-free), so a fleet's histograms sum into one without
# any resampling.  One schema everywhere: report ``totals.latency``,
# metrics JSONL snapshots, and serve ``/metrics`` in BOTH the JSON gauge
# form (p50/p90/p99) and the Prometheus histogram exposition
# (``_bucket``/``_sum``/``_count`` with cumulative ``le`` labels).
# ---------------------------------------------------------------------------

#: Upper bucket bounds in seconds: 1 µs * 2^i for i in 0..33 (~2.4 h),
#: plus an implicit +Inf overflow bucket.  Fixed for every histogram so
#: counts merge positionally.
LATENCY_BUCKET_BOUNDS: tuple[float, ...] = tuple(
    (1 << i) * 1e-6 for i in range(34)
)


class LatencyHistogram:
    """Log2-bucket latency histogram with u64 counts.

    ``record`` is O(1) (a bit_length + one increment under a short
    lock); quantiles are conservative — they report the UPPER bound of
    the bucket containing the target rank, so a published p99 is always
    >= the true p99 (never a flattering under-estimate).  Samples
    landing past the last finite bound count in the overflow bucket and
    clamp quantiles to the largest finite bound.
    """

    N = len(LATENCY_BUCKET_BOUNDS)

    def __init__(self):
        self.counts: list[int] = [0] * (self.N + 1)  # +1 = +Inf overflow
        self.sum_sec = 0.0
        self.count = 0
        self._lock = threading.Lock()

    @staticmethod
    def bucket_index(sec: float) -> int:
        """Smallest i with bounds[i] >= sec (N = the +Inf overflow)."""
        if sec <= 1e-6:
            return 0
        us = int(math.ceil(sec * 1e6))
        i = (us - 1).bit_length()
        return min(i, LatencyHistogram.N)

    def record(self, sec: float, n: int = 1) -> None:
        """Add ``n`` samples of ``sec`` (n > 1 = decimated sampling)."""
        if sec < 0:
            sec = 0.0  # monotonic sources cannot go negative; belt+braces
        i = self.bucket_index(sec)
        with self._lock:
            self.counts[i] += n
            self.sum_sec += sec * n
            self.count += n

    def merge(self, other: "LatencyHistogram") -> None:
        """Positional count addition — the histogram merge law."""
        with other._lock:
            counts = list(other.counts)
            s, c = other.sum_sec, other.count
        with self._lock:
            for i, v in enumerate(counts):
                self.counts[i] += v
            self.sum_sec += s
            self.count += c

    def _quantile_locked(self, p: float) -> float:
        if self.count == 0:
            return 0.0
        rank = max(1, math.ceil(p * self.count))
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= rank:
                return LATENCY_BUCKET_BOUNDS[min(i, self.N - 1)]
        return LATENCY_BUCKET_BOUNDS[-1]

    def quantile(self, p: float) -> float:
        with self._lock:
            return self._quantile_locked(p)

    def summary(self) -> dict:
        """Report/totals image: counts + the SLO percentiles."""
        with self._lock:
            return {
                "count": self.count,
                "sum_sec": round(self.sum_sec, 6),
                "p50_sec": self._quantile_locked(0.50),
                "p90_sec": self._quantile_locked(0.90),
                "p99_sec": self._quantile_locked(0.99),
            }

    def gauges(self, prefix: str) -> dict:
        """Flat numeric gauges (serve /metrics JSON + prom gauge render)."""
        s = self.summary()
        return {f"{prefix}{k}": v for k, v in s.items()}

    def render_prom(self, name: str, labels: dict | None = None) -> str:
        """Prometheus histogram exposition (text format 0.0.4).

        Cumulative ``le`` buckets ending at ``+Inf``, plus ``_sum`` and
        ``_count`` — derived from the SAME counts as :meth:`summary`,
        so a scraper's bucket-derived p99 equals the JSON gauge exactly.
        ``labels`` (e.g. ``{"tenant": "acme"}``) prefix the ``le`` label
        on every bucket and brace the ``_sum``/``_count`` series — the
        multi-tenant serve /metrics renders one labeled histogram per
        tenant this way, and the labeled parity audit replays them
        through :func:`quantile_from_prom` with the same labels.
        """
        with self._lock:
            counts = list(self.counts)
            total = self.count
            sum_sec = self.sum_sec
        lab = _prom_labels(labels)
        pre = f"{lab}," if lab else ""
        suf = f"{{{lab}}}" if lab else ""
        lines = [f"# TYPE {name} histogram"]
        cum = 0
        for i, bound in enumerate(LATENCY_BUCKET_BOUNDS):
            cum += counts[i]
            # repr round-trips exactly: a scraper re-parsing the le label
            # recovers the identical float bound the JSON quantiles use
            lines.append(f'{name}_bucket{{{pre}le="{bound!r}"}} {cum}')
        lines.append(f'{name}_bucket{{{pre}le="+Inf"}} {total}')
        lines.append(f"{name}_sum{suf} {sum_sec:.9g}")
        lines.append(f"{name}_count{suf} {total}")
        return "\n".join(lines) + "\n"


def _prom_labels(labels: dict | None) -> str:
    """``k="v"`` label-pair body (no braces), sorted for determinism."""
    if not labels:
        return ""
    return ",".join(f'{k}="{labels[k]}"' for k in sorted(labels))


def quantile_from_prom(
    text: str, name: str, p: float, labels: dict | None = None
) -> float | None:
    """p-quantile from a Prometheus histogram exposition (tests/audit).

    Same conservative bucket-upper-bound rule as
    :meth:`LatencyHistogram.quantile`, so the prom and JSON renderings
    of one histogram must agree exactly — the drift check
    ``verify/registry.py::audit_observability`` enforces.  ``labels``
    selects one labeled series out of a multi-tenant exposition (must
    match the ``render_prom(labels=...)`` that produced it).
    """
    lab = _prom_labels(labels)
    bucket_pre = f'{name}_bucket{{{lab},le="' if lab else f'{name}_bucket{{le="'
    count_pre = f"{name}_count{{{lab}}} " if lab else f"{name}_count "
    buckets: list[tuple[float, int]] = []
    count = None
    for line in text.splitlines():
        if line.startswith(bucket_pre):
            le, _, cum = line[len(bucket_pre):].partition('"} ')
            buckets.append(
                (math.inf if le == "+Inf" else float(le), int(cum))
            )
        elif line.startswith(count_pre):
            count = int(line.rsplit(" ", 1)[1])
    if count is None or not buckets:
        return None
    if count == 0:
        return 0.0
    rank = max(1, math.ceil(p * count))
    finite = [b for b, _ in buckets if b != math.inf]
    for bound, cum in buckets:
        if cum >= rank:
            return min(bound, finite[-1]) if finite else bound
    return finite[-1] if finite else None


class ThroughputMeter:
    """Periodic lines/sec reporting without per-chunk host/device syncs.

    Every tick also feeds the metrics plane's cumulative line counter
    (one None-check when ``--metrics-out`` is unset), and the periodic
    report line lands in the metrics JSONL as a ``throughput`` event in
    addition to stderr — a sustained run is watchable by tailing the
    metrics file instead of scraping stderr.  :meth:`summary` folds the
    final cumulative numbers into the report totals so downstream
    artifacts stop re-deriving them.
    """

    def __init__(self, report_every_chunks: int = 0, out=sys.stderr):
        self.every = report_every_chunks
        self.out = out
        self.t0 = time.perf_counter()
        self.t_last = self.t0
        self.lines = 0
        self.lines_last = 0
        self.chunks = 0

    def tick(self, n_lines: int) -> None:
        self.lines += n_lines
        self.chunks += 1
        obs.add_lines(n_lines)
        if self.every and self.chunks % self.every == 0:
            now = time.perf_counter()
            inst = (self.lines - self.lines_last) / max(now - self.t_last, 1e-9)
            cum = self.lines / max(now - self.t0, 1e-9)
            print(
                f"[chunk {self.chunks}] {self.lines} lines, "
                f"{inst:,.0f} lines/s (inst), {cum:,.0f} lines/s (cum)",
                file=self.out,
                flush=True,
            )
            obs.metric_event(
                "throughput",
                chunk=self.chunks,
                lines=self.lines,
                lines_per_sec_inst=round(inst, 1),
                lines_per_sec_cum=round(cum, 1),
            )
            self.t_last, self.lines_last = now, self.lines

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def summary(self) -> dict:
        """Final cumulative numbers for the report totals (``throughput``)."""
        elapsed = self.elapsed()
        return {
            "chunks_ticked": self.chunks,
            "lines": self.lines,
            "elapsed_sec": round(elapsed, 4),
            "lines_per_sec_cum": (
                round(self.lines / elapsed, 1) if elapsed > 0 else 0.0
            ),
        }


class DispatchTimer:
    """Prices one-time jit/XLA-compile apart from the sustained rate.

    The first dispatch of each device program blocks on trace + compile;
    its excess over the SECOND dispatch of the same program is the
    one-time cost.  (On a backend with synchronous dispatch — XLA:CPU —
    every dispatch also carries the chunk's execution, so
    first-minus-second isolates compile where raw first-dispatch time
    would launder one chunk's work into "compile".)  A program that
    dispatched only ONCE contributes ZERO: its lone timing conflates
    compile with a full chunk's execution, and subtracting it whole
    from the sustained denominator would inflate the sustained rate by
    10x+ on single-chunk runs — under-attributing compile there is the
    conservative error.  Shared by the single-process and distributed
    stream drivers so their ``totals.compile_sec`` mean the same thing.
    """

    def __init__(self):
        self._t: dict[str, list[float]] = {}

    def first(self, kind: str, fn, *args):
        """Run ``fn(*args)``, timing the first two dispatches of ``kind``.

        Every dispatch also records a ``step.dispatch`` trace span when
        the observability plane is armed — this method already wraps
        every device dispatch of both stream drivers, so one hook here
        covers the whole step vocabulary.  Disarmed cost past the first
        two dispatches: one None-check.
        """
        lst = self._t.setdefault(kind, [])
        rec = obs.recording()  # tracer shard OR flight-recorder ring
        if len(lst) >= 2 and not rec:
            return fn(*args)
        t0 = time.perf_counter()
        out = fn(*args)
        t1 = time.perf_counter()
        if len(lst) < 2:
            lst.append(t1 - t0)
        if rec:
            obs.complete(
                "step.dispatch", t0, t1, cat="step", args={"kind": kind}
            )
        return out

    def compile_sec(self) -> float:
        return sum(
            max(0.0, t[0] - t[1]) for t in self._t.values() if len(t) > 1
        )


class RecoveryMeter:
    """Recovery-event counters for the elastic supervisor (runtime/elastic.py).

    One event per cluster re-formation: ``detect()`` marks the moment a
    peer death (or any generation failure) is observed, ``recovered()``
    the moment the replacement generation's workers are running again.
    ``summary()`` feeds the end-of-run report totals, so an operator sees
    how often the job healed itself and how long each heal took — the
    observability half of the SURVEY §3b elastic/retry analog.
    """

    def __init__(self):
        self.events: list[dict] = []
        self._t_detect: float | None = None
        #: detection reason for the OPEN event; initialized here so an
        #: out-of-order recovered() (no prior detect()) reads a defined
        #: value instead of depending on attribute-existence luck
        self._reason: str = ""
        #: chaos-harness outcomes (record_run): one bool per seeded fault
        #: schedule — True when the run ended inside the invariant (bit-
        #: identical report or typed abort), False on any breach
        self.runs: list[bool] = []

    @property
    def detecting(self) -> bool:
        """True while a detected failure awaits its recovered() close.

        The elastic supervisor uses this to record a recovery event only
        for FAILURE re-formations — a planned autoscale re-formation has
        no detection window, and a zero-length recovery event would
        pollute the mean-time-to-recover statistics.
        """
        return self._t_detect is not None

    def detect(self, reason: str = "") -> None:
        if self._t_detect is None:  # first detection wins per event
            self._t_detect = time.perf_counter()
            self._reason = reason
            obs.instant("elastic.detect", args={"reason": reason})

    def recovered(self, *, world: int) -> None:
        t = time.perf_counter()
        t0 = self._t_detect if self._t_detect is not None else t
        event = {
            "time_to_recover_sec": round(t - t0, 3),
            "world": world,
            "reason": self._reason if self._t_detect is not None else "",
        }
        self.events.append(event)
        # the detect..recovered window IS the re-formation span; pushed
        # to both planes so a 10s recovery is visible on the timeline
        # and in the metrics JSONL without waiting for the final report
        obs.complete("elastic.reform", t0, t, cat="elastic", args=event)
        obs.metric_event("recovery", **event)
        self._t_detect = None

    def abandon(self) -> None:
        """Forget an open detection (budget exhausted: no recovery happened)."""
        self._t_detect = None

    def record_run(self, ok: bool) -> None:
        """One chaos schedule's verdict (pass-rate feeds BENCH artifacts)."""
        self.runs.append(bool(ok))

    def summary(self) -> dict:
        """Totals patch: {} when nothing was recorded (zero-noise)."""
        out: dict = {}
        if self.events:
            out.update(
                {
                    "recovery_events": len(self.events),
                    "recovery_total_sec": round(
                        sum(e["time_to_recover_sec"] for e in self.events), 3
                    ),
                    "mean_time_to_recover_sec": round(
                        sum(e["time_to_recover_sec"] for e in self.events)
                        / len(self.events),
                        3,
                    ),
                    "recoveries": self.events,
                }
            )
        if self.runs:
            # robustness the BENCH artifacts can track alongside speed:
            # how many seeded fault schedules ended inside the
            # bit-identical-or-typed-abort invariant
            out.update(
                {
                    "chaos_runs": len(self.runs),
                    "chaos_pass_rate": round(
                        sum(self.runs) / len(self.runs), 4
                    ),
                }
            )
        return out


class Profiler:
    """Context manager around jax.profiler tracing (no-op when dir is None).

    Hardened: entering twice is a typed error (jax's second start_trace
    would otherwise fail deep inside the profiler with an opaque
    message), the trace ALWAYS stops when the body raises (a stop_trace
    failure during exception unwind is swallowed so it cannot mask the
    run's real error), and a successful exit prints the trace path with
    the TensorBoard hint so operators do not have to know the plugin
    incantation.
    """

    def __init__(self, trace_dir: str | None, out=sys.stderr):
        self.trace_dir = trace_dir
        self.out = out
        self._active = False

    def __enter__(self):
        if self._active:
            from ..errors import AnalysisError

            raise AnalysisError(
                "Profiler already started; nest runs, not profiler scopes"
            )
        if self.trace_dir:
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self._active = True
        return self

    def __exit__(self, exc_type, exc, tb):
        if not self._active:
            return False
        self._active = False
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception:
            # unwinding with the body's exception: the profiler's own
            # teardown failure must not mask it.  A clean-exit failure
            # is real and propagates.
            if exc_type is None:
                raise
        else:
            if exc_type is None:
                print(
                    f"profiler trace: {self.trace_dir} (open with "
                    "`tensorboard --logdir` -> Profile tab)",
                    file=self.out,
                    flush=True,
                )
        return False


# ---------------------------------------------------------------------------
# SLO burn-rate engine (DESIGN §24).  Policy objectives are evaluated per
# published window against the same log2 latency histograms and
# drop/incomplete/degraded counters the serve drivers already keep —
# no second measurement path, so the alert and the evidence can never
# disagree.  Fast/slow window pairs in the Google SRE style: the fast
# deque catches a sharp regression within a few rotations, the slow
# deque confirms sustained budget burn, and breach/recover fire only on
# state TRANSITIONS (hysteresis), never per-window, so a steady bad or
# steady good service emits nothing.
# ---------------------------------------------------------------------------

#: Window-stat keys an ``--slo`` objective may bound.  Latency quantiles
#: come from the per-window ingest->publish histogram (milliseconds);
#: the rates are per-window fractions in [0, 1]; ``degraded_subsystems``
#: is the live degraded-set size at rotation.
SLO_METRICS: tuple[str, ...] = (
    "p50_publish_ms",
    "p90_publish_ms",
    "p99_publish_ms",
    "drop_rate",
    "incomplete_rate",
    "degraded_subsystems",
)

_SLO_OBJ_RE = None  # compiled lazily; objective grammar: metric<=number


class SloPolicy:
    """Parsed ``--slo`` policy: a list of ``(metric, bound)`` objectives.

    Grammar (one comma-separated spec, whitespace-tolerant)::

        p99_publish_ms<=500,drop_rate<=0.001

    Only ``<=`` bounds: every supported metric is a "smaller is better"
    quantity, so one comparator keeps the spec unambiguous.  Unknown
    metric names are a hard :class:`ValueError` at parse time (config
    validation), never a silently-ignored objective at runtime.
    """

    def __init__(self, objectives: list[tuple[str, float]]):
        self.objectives = list(objectives)

    @classmethod
    def parse(cls, spec: str) -> "SloPolicy":
        import re

        global _SLO_OBJ_RE
        if _SLO_OBJ_RE is None:
            _SLO_OBJ_RE = re.compile(
                r"^\s*([a-z0-9_]+)\s*<=\s*([0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?)\s*$"
            )
        objectives: list[tuple[str, float]] = []
        seen: set[str] = set()
        for part in str(spec).split(","):
            if not part.strip():
                continue
            m = _SLO_OBJ_RE.match(part)
            if m is None:
                raise ValueError(
                    f"bad --slo objective {part.strip()!r} "
                    "(want metric<=number, e.g. p99_publish_ms<=500)"
                )
            metric, bound = m.group(1), float(m.group(2))
            if metric not in SLO_METRICS:
                raise ValueError(
                    f"unknown --slo metric {metric!r} "
                    f"(supported: {', '.join(SLO_METRICS)})"
                )
            if metric in seen:
                raise ValueError(f"duplicate --slo metric {metric!r}")
            seen.add(metric)
            objectives.append((metric, bound))
        if not objectives:
            raise ValueError("empty --slo spec")
        return cls(objectives)


class SloBurnEngine:
    """Multi-window burn-rate evaluator over per-window SLO stats.

    Each objective keeps two sliding windows of per-rotation compliance
    bits: ``fast`` (default 3 rotations) and ``slow`` (default 12).
    Burn rate = violating fraction / error budget; an objective BREACHES
    when the fast burn crosses ``fast_burn`` AND the slow burn crosses
    1.0 (budget fully consumed at the slow horizon), and RECOVERS once
    the fast burn falls back under 1.0 — i.e. the whole fast window is
    clean again.  The asymmetric pair is the hysteresis: one bad window
    alerts within ``fast`` rotations, and recovery needs ``fast``
    consecutive clean rotations, so the state cannot flap per-window.
    ``observe`` returns transition events only; gauges stay flat numeric
    so :func:`autoscale.render_prom` exports them with JSON<->prom
    parity for free.
    """

    def __init__(
        self,
        policy: SloPolicy,
        *,
        fast: int = 3,
        slow: int = 12,
        budget: float = 0.01,
        fast_burn: float = 2.0,
    ):
        if fast < 1 or slow < fast:
            raise ValueError("want 1 <= fast <= slow")
        self.policy = policy
        self.fast = int(fast)
        self.slow = int(slow)
        self.budget = float(budget)
        self.fast_burn = float(fast_burn)
        # per-objective: compliance-bit deque (1 = violated), breached flag
        self._bits: dict[str, list[int]] = {m: [] for m, _ in policy.objectives}
        self._breached: dict[str, bool] = {m: False for m, _ in policy.objectives}
        self._burn: dict[str, tuple[float, float]] = {
            m: (0.0, 0.0) for m, _ in policy.objectives
        }
        self.windows_observed = 0
        self.breaches_total = 0
        self.recoveries_total = 0

    def _burn_of(self, bits: list[int], horizon: int) -> float:
        tail = bits[-horizon:]
        if not tail:
            return 0.0
        return (sum(tail) / len(tail)) / self.budget

    def observe(self, stats: dict) -> list[dict]:
        """Feed one published window's stats; return transition events.

        Missing stat keys count as compliant (a window with no latency
        samples cannot violate a latency objective).  Events carry the
        objective, bound, observed value, and both burn rates — enough
        for the obs instant / flight-recorder record to stand alone.
        """
        self.windows_observed += 1
        events: list[dict] = []
        for metric, bound in self.policy.objectives:
            val = stats.get(metric)
            violated = 1 if (val is not None and float(val) > bound) else 0
            bits = self._bits[metric]
            bits.append(violated)
            del bits[:-self.slow]
            bf = self._burn_of(bits, self.fast)
            bs = self._burn_of(bits, self.slow)
            self._burn[metric] = (bf, bs)
            was = self._breached[metric]
            ev = None
            if not was and bf >= self.fast_burn and bs >= 1.0:
                self._breached[metric] = True
                self.breaches_total += 1
                ev = "slo.breach"
            elif was and bf < 1.0:
                self._breached[metric] = False
                self.recoveries_total += 1
                ev = "slo.recovered"
            if ev is not None:
                events.append({
                    "event": ev,
                    "objective": metric,
                    "bound": bound,
                    "value": None if val is None else float(val),
                    "burn_fast": round(bf, 4),
                    "burn_slow": round(bs, 4),
                    "window": stats.get("window"),
                })
        return events

    def gauges(self) -> dict:
        """Flat numeric gauges for the driver ``metrics_gauges`` merge."""
        g = {
            "slo_objectives": len(self.policy.objectives),
            "slo_windows_observed": self.windows_observed,
            "slo_breached": sum(1 for b in self._breached.values() if b),
            "slo_breaches_total": self.breaches_total,
            "slo_recoveries_total": self.recoveries_total,
        }
        return g

    def labeled_gauges(self) -> dict[str, dict]:
        """Per-objective gauge dicts for the labeled prom exposition."""
        out: dict[str, dict] = {}
        for metric, bound in self.policy.objectives:
            bf, bs = self._burn[metric]
            out[metric] = {
                "slo_bound": float(bound),
                "slo_burn_fast": round(bf, 4),
                "slo_burn_slow": round(bs, 4),
                "slo_objective_breached": 1 if self._breached[metric] else 0,
            }
        return out


def window_slo_stats(
    hist: "LatencyHistogram | None",
    *,
    lines: int,
    drops: int,
    incomplete: bool,
    degraded: int,
    window: int | None = None,
) -> dict:
    """One published window's stats in the shape ``SloBurnEngine.observe``
    and the lineage plane share.  Centralised so solo, tenant, and
    distributed serve cannot diverge on what "drop rate" means: drops
    over (delivered lines + drops), i.e. the fraction of offered lines
    the window lost."""
    stats: dict = {
        "drop_rate": (drops / (lines + drops)) if (lines + drops) > 0 else 0.0,
        "incomplete_rate": 1.0 if incomplete else 0.0,
        "degraded_subsystems": int(degraded),
        "window": window,
    }
    if hist is not None and hist.count > 0:
        for p, key in ((0.5, "p50_publish_ms"), (0.9, "p90_publish_ms"),
                       (0.99, "p99_publish_ms")):
            q = hist.quantile(p)
            if q == q and q != float("inf"):  # not NaN / overflow bucket
                stats[key] = q * 1e3
    return stats


# ---------------------------------------------------------------------------
# Build-info gauge (ra_build_info): the scrape-side answer to "what
# binary produced these numbers".  Constant-per-process labels (version,
# jax version, SIMD kind, mesh topology) with a value of 1, the standard
# Prometheus build-info idiom; the JSON /metrics variant carries the
# same dict verbatim and verify/registry.py::audit_observability holds
# the two renderings to each other.
# ---------------------------------------------------------------------------


def build_info(extra: dict | None = None) -> dict:
    """Assemble the build-info label dict (all values coerced to str)."""
    from .. import __version__

    try:
        import jax

        jax_version = str(jax.__version__)
    except Exception:  # pragma: no cover - jax is baked into the image
        jax_version = "unknown"
    try:
        from ..hostside import fastparse

        simd = str(fastparse.simd_kind())
    except Exception:  # pragma: no cover - fastparse probe never raises
        simd = "unknown"
    info = {"version": str(__version__), "jax": jax_version, "simd": simd}
    for k, v in (extra or {}).items():
        info[str(k)] = str(v)
    return info


def render_build_info_prom(info: dict, *, name: str = "ra_build_info") -> str:
    """One ``ra_build_info{...} 1`` line from :func:`build_info`'s dict."""
    body = _prom_labels({k: str(info[k]) for k in info})
    return f"# TYPE {name} gauge\n{name}{{{body}}} 1\n"
