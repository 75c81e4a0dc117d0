"""Metrics-driven elastic autoscaling: the load-reactive policy engine.

PR 1's elastic tier reacts to *death* (a peer drops, the cluster
re-forms smaller); production traffic is bursty, so this module makes
the same machinery react to *load* (ROADMAP item 4).  The metrics plane
(runtime/obs.py) already exports exactly the signals a scaling policy
needs — producer backpressure seconds (the device tier cannot keep up),
consumer starvation seconds (capacity sits idle), queue depth, lines/s —
and the epoch-tagged world-size-independent checkpoints were designed so
ANY world size can resume them.  Autoscaling is therefore a *policy*
problem, not a new mechanism: decide when the signals justify a
different world size, then drive the existing re-formation machinery as
a planned scale event.

Three pieces:

- :class:`PolicyEngine` — the pure decision core, unit-testable with
  synthetic samples.  Two canonical signals in [0, 1] per sample:
  **pressure** (device-bound fraction of recent wall time: sustained ⇒
  scale OUT) and **starvation** (input-bound idle fraction: sustained ⇒
  scale IN).  A decision needs the signal's *minimum* over a full
  ``sustain_sec`` window above threshold, at least ``cooldown_sec``
  since the previous decision, and budget left — the flap-damping math
  DESIGN §13 spells out.  Every decision carries its evidence (the
  window statistics + the raw gauges) and is an obs instant + metrics
  event; the ``autoscale.decide`` fault site fires right before a
  decision is returned so chaos schedules can land failures exactly at
  the decide→actuate seam.

- :class:`MetricsTail` + :func:`ingest_signals` — adapters from the live
  metrics JSONL stream (the one ``--metrics-out`` writes and external
  scrapers read: one source of truth) to the canonical signals.

- :class:`AutoscaleController` — the distributed actuation half: a
  thread the elastic *leader* supervisor runs per generation, tailing
  its rank-0 worker's metrics shard and publishing one scale request
  into the rendezvous directory when the engine decides (the supervisors
  then retire the generation at a checkpoint-bounded cost and re-form at
  the target world; runtime/elastic.py).  The serve driver embeds the
  engine directly (runtime/serve.py) and resizes its own device mesh.

Scale events are *planned*: they consume the autoscaler's own
``reform_budget``, never ``--max-reforms`` (which stays the failure
budget), and a budget of 0 runs the whole policy in observe-only mode —
decisions with evidence, no actuation — for drills and rollout.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
from collections import deque

from ..config import AutoscaleConfig
from ..errors import AnalysisError
from . import faults, obs


def parse_plan(plan: str) -> list[tuple[str, float]]:
    """``"out@T,in@T"`` -> ordered [(direction, seconds-offset)] entries.

    Validated by ``AutoscaleConfig.__post_init__``; this is the single
    decoder the engine uses (scripted drills/tests — production decides
    from the live signals).
    """
    out: list[tuple[str, float]] = []
    for part in filter(None, (p.strip() for p in plan.split(","))):
        d, _, t = part.partition("@")
        if d not in ("out", "in"):
            raise AnalysisError(
                f"autoscale plan entry {part!r}: direction must be 'out' or 'in'"
            )
        try:
            out.append((d, float(t)))
        except ValueError as e:
            raise AnalysisError(
                f"autoscale plan entry {part!r}: want DIRECTION@SECONDS"
            ) from e
    return out


def world_ladder(min_world: int, max_world: int, *, divisors_of: int = 0) -> list[int]:
    """Allowed world sizes, smallest first.

    ``divisors_of`` restricts the ladder to divisors of that extent —
    the serve driver's constraint: its padded batch geometry is fixed at
    the maximum world, and a world that divides it keeps every chunk
    boundary (and therefore the full report, candidates included)
    bit-identical across scale events.  0 = every integer in range (the
    elastic tier: the collective step is shape-correct at any world).
    """
    if divisors_of:
        rungs = [
            k for k in range(1, divisors_of + 1)
            if divisors_of % k == 0 and min_world <= k <= max_world
        ]
    else:
        rungs = list(range(min_world, max_world + 1))
    if not rungs:
        raise AnalysisError(
            f"autoscale world ladder is empty (min {min_world}, max "
            f"{max_world}" + (f", divisors of {divisors_of}" if divisors_of else "")
            + ")"
        )
    return rungs


def host_ladder(min_hosts: int, max_hosts: int) -> list[int]:
    """Allowed HOST counts of the distributed serve tier (DESIGN §22).

    The device-tier ladder restricts worlds to divisors of the padded
    batch geometry; the host tier has no such constraint — every host
    runs its own full (flat) mesh and the cross-host register merge is
    world-size-independent (the ``_merge_tail`` laws are associative),
    so any contiguous rung count is reachable.  The checkpoint
    fingerprint pins ``max_hosts`` (the ladder maximum), which is what
    lets a merged-ring checkpoint taken at any host count resume at any
    other on the same ladder.
    """
    return world_ladder(min_hosts, max_hosts)


@dataclasses.dataclass
class ScaleDecision:
    """One policy decision, evidence attached (obs + report facing)."""

    seq: int
    direction: str  # "out" | "in"
    from_world: int
    to_world: int
    reason: str  # "backpressure" | "starvation" | "plan"
    t: float  # engine clock (caller's ``now``) at decision time
    actuate: bool  # False in observe-only mode (reform_budget 0)
    evidence: dict

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class PolicyEngine:
    """Sustained-signal decision core (pure; feed it samples, get events).

    Decision table, evaluated at every :meth:`observe` (DESIGN §13):

    1. budget gone (``reform_budget`` actuations used) -> hold forever;
    2. within ``cooldown_sec`` of the previous decision -> hold;
    3. the sample window does not yet span ``sustain_sec`` -> hold
       (the window keeps ``1.5 * sustain_sec`` of samples, and never
       fewer than the last two; it resets after every decision:
       post-reform signals describe a different capacity);
    4. min(pressure) over the window >= ``out_threshold`` and a higher
       rung exists -> scale OUT one rung;
    5. else min(starvation) >= ``in_threshold`` and a lower rung
       exists -> scale IN one rung.

    A reversal (out after in, or in after out) within
    ``2 * (cooldown_sec + sustain_sec)`` of the previous decision counts
    as a **flap** — the damping knobs exist to keep that number at zero,
    and the bench artifact asserts it.
    """

    def __init__(self, acfg: AutoscaleConfig, *, world: int, ladder: list[int]):
        if world not in ladder:
            raise AnalysisError(
                f"current world {world} is not on the autoscale ladder {ladder}"
            )
        self.acfg = acfg
        self.ladder = list(ladder)
        self.world = world
        self.budget_left = acfg.reform_budget
        self.observe_only = acfg.reform_budget == 0
        self.decisions: list[ScaleDecision] = []
        self.flaps = 0
        self.suppressed_budget = 0  # would-be decisions after budget ran out
        self._window: deque[tuple[float, float, float]] = deque()
        self._t0: float | None = None
        self._last: ScaleDecision | None = None
        self._plan = parse_plan(acfg.plan)
        self._plan_fired = 0
        self._budget_noted = False
        self._seq = 0

    # -- internals --------------------------------------------------------
    def _rung(self, direction: str) -> int | None:
        i = self.ladder.index(self.world)
        if direction == "out":
            return self.ladder[i + 1] if i + 1 < len(self.ladder) else None
        return self.ladder[i - 1] if i > 0 else None

    def _decide(
        self, direction: str, reason: str, now: float, evidence: dict
    ) -> ScaleDecision | None:
        target = self._rung(direction)
        if target is None:
            return None  # already at the edge of the ladder
        if not self.observe_only and self.budget_left <= 0:
            self.suppressed_budget += 1
            if not self._budget_noted:
                self._budget_noted = True
                obs.instant(
                    "autoscale.budget_exhausted",
                    args={"reform_budget": self.acfg.reform_budget},
                )
            return None
        prev = self._last
        if (
            prev is not None
            and prev.direction != direction
            and now - prev.t < 2 * (self.acfg.cooldown_sec + self.acfg.sustain_sec)
        ):
            self.flaps += 1
        self._seq += 1
        dec = ScaleDecision(
            seq=self._seq,
            direction=direction,
            from_world=self.world,
            to_world=target,
            reason=reason,
            t=now,
            actuate=not self.observe_only,
            evidence=evidence,
        )
        # chaos seam: a decision that fails to LEAVE the policy engine
        # must be a typed abort, never a half-issued scale event
        faults.fire("autoscale.decide")
        self.decisions.append(dec)
        self._last = dec
        self._window.clear()
        if dec.actuate:
            self.budget_left -= 1
            self.world = target
        # the damping window rides the instant so the trace alone can
        # count flaps (tools/trace_summary.py autoscale block)
        obs.instant(
            "autoscale.decide",
            args={
                **dec.to_dict(),
                "damping_window_sec": 2 * (self.acfg.cooldown_sec + self.acfg.sustain_sec),
            },
        )
        obs.metric_event("autoscale", **dec.to_dict())
        return dec

    # -- the sampling surface ---------------------------------------------
    def observe(
        self,
        *,
        now: float,
        pressure: float,
        starvation: float,
        gauges: dict | None = None,
    ) -> ScaleDecision | None:
        """Feed one sample; returns a decision when the table fires."""
        a = self.acfg
        if self._t0 is None:
            self._t0 = now
        pressure = min(max(float(pressure), 0.0), 1.0)
        starvation = min(max(float(starvation), 0.0), 1.0)
        self._window.append((now, pressure, starvation))
        # the previous sample always stays: a loop blocked longer than the
        # window (a compile, a slow step) still compares the readings on
        # both sides of the gap instead of going blind while it is behind
        while (
            len(self._window) > 2
            and now - self._window[0][0] > a.sustain_sec * 1.5
        ):
            self._window.popleft()

        if self._plan:
            # scripted drill: entries fire in order at their offsets,
            # bypassing thresholds and cooldown (the script IS the policy)
            if self._plan_fired < len(self._plan):
                d, t_off = self._plan[self._plan_fired]
                if now - self._t0 >= t_off:
                    self._plan_fired += 1
                    return self._decide(
                        d, "plan", now,
                        {
                            "plan_entry": f"{d}@{t_off:g}",
                            "pressure_last": pressure,
                            "starvation_last": starvation,
                            **({"gauges": gauges} if gauges else {}),
                        },
                    )
            return None

        if self._last is not None and now - self._last.t < a.cooldown_sec:
            return None
        if not self._window or now - self._window[0][0] < a.sustain_sec:
            return None  # window does not span the sustain bound yet
        ps = [p for _, p, _ in self._window]
        ss = [s for _, _, s in self._window]
        evidence = {
            "window_sec": round(now - self._window[0][0], 3),
            "samples": len(self._window),
            "pressure": {
                "min": round(min(ps), 4),
                "mean": round(sum(ps) / len(ps), 4),
                "last": round(pressure, 4),
                "threshold": a.out_threshold,
            },
            "starvation": {
                "min": round(min(ss), 4),
                "mean": round(sum(ss) / len(ss), 4),
                "last": round(starvation, 4),
                "threshold": a.in_threshold,
            },
            **({"gauges": gauges} if gauges else {}),
        }
        if min(ps) >= a.out_threshold:
            return self._decide("out", "backpressure", now, evidence)
        if min(ss) >= a.in_threshold:
            return self._decide("in", "starvation", now, evidence)
        return None

    def applied(self, dec: ScaleDecision, *, now: float) -> None:
        """Note actuation completed (time-to-effect lands in the summary)."""
        dec.evidence["time_to_effect_sec"] = round(now - dec.t, 3)

    def summary(self) -> dict:
        """Report/summary totals block ({} when nothing ever happened)."""
        if not self.decisions and not self.suppressed_budget:
            return {}
        return {
            "world": self.world,
            "decisions": [d.to_dict() for d in self.decisions],
            "scale_out": sum(1 for d in self.decisions if d.direction == "out"),
            "scale_in": sum(1 for d in self.decisions if d.direction == "in"),
            "flaps": self.flaps,
            "budget_left": self.budget_left,
            "observe_only": self.observe_only,
            **(
                {"suppressed_by_budget": self.suppressed_budget}
                if self.suppressed_budget
                else {}
            ),
        }


# ---------------------------------------------------------------------------
# Metrics-stream adapters: the JSONL the metrics plane writes (and
# external scrapers read) is the policy's one source of truth.
# ---------------------------------------------------------------------------


class MetricsTail:
    """Incremental reader of a metrics JSONL file another process writes.

    Tolerates the file not existing yet (the worker has not armed its
    metrics plane) and a torn final line (killed mid-write): bytes past
    the last newline stay unconsumed until completed.
    """

    def __init__(self, path: str):
        self.path = path
        self._pos = 0
        self._buf = b""

    def poll(self) -> list[dict]:
        try:
            with open(self.path, "rb") as f:
                f.seek(self._pos)
                chunk = f.read()
        except OSError:
            return []
        self._pos += len(chunk)
        self._buf += chunk
        recs: list[dict] = []
        while True:
            nl = self._buf.find(b"\n")
            if nl < 0:
                break
            line, self._buf = self._buf[:nl], self._buf[nl + 1:]
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn or foreign line: skip, keep tailing
            if isinstance(rec, dict):
                recs.append(rec)
        return recs


def ingest_signals(prev: dict | None, rec: dict) -> tuple[float, float] | None:
    """(pressure, starvation) from two consecutive metrics snapshots.

    The ingest sampler (runtime/ingest.py) exports *cumulative*
    backpressure/starvation seconds; the canonical signals are their
    derivative over the snapshot interval — the fraction of recent wall
    time the pipeline spent device-bound vs input-bound.  None when the
    pair cannot be differentiated yet (first snapshot, no ingest gauge,
    clock went backwards).
    """
    if prev is None:
        return None
    ing, ping = rec.get("ingest"), prev.get("ingest")
    if not isinstance(ing, dict) or not isinstance(ping, dict):
        return None
    try:
        dt = float(rec["t"]) - float(prev["t"])
        if dt <= 0:
            return None
        dp = float(ing["backpressure_sec"]) - float(ping["backpressure_sec"])
        ds = float(ing["starved_sec"]) - float(ping["starved_sec"])
    except (KeyError, TypeError, ValueError):
        return None
    clamp = lambda v: min(max(v / dt, 0.0), 1.0)  # noqa: E731
    return clamp(dp), clamp(ds)


def render_prom(gauges: dict, *, prefix: str = "ra_") -> str:
    """Prometheus text exposition of a flat numeric gauge dict.

    The serve ``/metrics?format=prom`` variant: the SAME gauges the
    policy engine consumes, so an external scraper and the autoscaler
    can never disagree about what the service saw.  Non-numeric values
    are skipped (the JSON variant keeps them); booleans export as 0/1.
    """
    lines: list[str] = []
    for key in sorted(gauges):
        v = gauges[key]
        if isinstance(v, bool):
            v = int(v)
        if not isinstance(v, (int, float)):
            continue
        name = prefix + "".join(c if c.isalnum() else "_" for c in str(key))
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {v:g}" if isinstance(v, float) else f"{name} {v}")
    return "\n".join(lines) + "\n"


def render_prom_labeled(
    per_label: dict[str, dict],
    *,
    prefix: str = "ra_",
    label: str = "tenant",
) -> str:
    """Labeled twin of :func:`render_prom` for per-tenant gauge families.

    ``per_label`` maps one label value (tenant name) to that tenant's
    flat numeric gauge dict; every gauge key becomes ONE metric family
    with one ``{label="value"}`` series per tenant — so a scraper sums
    or compares tenants without string-parsing metric names.  Same
    skip-non-numeric / bool-as-int rules as the flat rendering; the
    labeled drift audit (verify/registry.py) holds both renderings to
    the same JSON source.
    """
    families: dict[str, list[str]] = {}
    for value in sorted(per_label):
        for key in sorted(per_label[value]):
            v = per_label[value][key]
            if isinstance(v, bool):
                v = int(v)
            if not isinstance(v, (int, float)):
                continue
            name = prefix + "".join(c if c.isalnum() else "_" for c in str(key))
            body = f"{v:g}" if isinstance(v, float) else f"{v}"
            families.setdefault(name, []).append(
                f'{name}{{{label}="{value}"}} {body}'
            )
    lines: list[str] = []
    for name in sorted(families):
        lines.append(f"# TYPE {name} gauge")
        lines.extend(families[name])
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Elastic actuation: the leader supervisor's per-generation controller.
# ---------------------------------------------------------------------------


class AutoscaleController(threading.Thread):
    """Tail the rank-0 worker's metrics shard; publish ONE scale request.

    Runs on the elastic *leader* supervisor for the lifetime of one
    generation (runtime/elastic.py starts it after spawning the worker
    and stops it when the generation ends).  When the policy engine
    decides, the controller appends the decision to ``scale-log.jsonl``
    (the run's full decision history, report-facing) and atomically
    publishes ``scale.json`` (seq + target world) — every supervisor
    polls that file and retires its worker, which is the planned scale
    event.  One request per controller: the re-formation it causes
    replaces this generation (and this controller) anyway.
    """

    def __init__(
        self,
        acfg: AutoscaleConfig,
        *,
        world: int,
        ladder: list[int],
        metrics_path: str,
        publish,  # callable(ScaleDecision) -> None, actuated decisions
        budget_left: int,
        cooldown_anchor: float | None = None,
        log=None,  # callable(ScaleDecision) -> None, EVERY decision
    ):
        super().__init__(daemon=True, name="ra-autoscale")
        self.engine = PolicyEngine(acfg, world=world, ladder=ladder)
        # budget/cooldown survive across generations (each gets a fresh
        # controller): the supervisor passes what previous requests used
        self.engine.budget_left = max(
            0, min(self.engine.budget_left, budget_left)
        ) if not self.engine.observe_only else 0
        self._cooldown_anchor = cooldown_anchor
        self.acfg = acfg
        self._tail = MetricsTail(metrics_path)
        self._publish = publish
        self._log = log
        # NOT named _stop: threading.Thread.join() calls its internal
        # self._stop() after the thread exits, and an Event attribute of
        # that name shadows it
        self._stop_ev = threading.Event()
        self.decision: ScaleDecision | None = None
        self.error: BaseException | None = None

    def stop(self) -> None:
        self._stop_ev.set()

    def run(self) -> None:
        try:
            self._run()
        except BaseException as e:  # surfaced by the supervisor's join
            self.error = e

    def _run(self) -> None:
        a = self.acfg
        prev: dict | None = None
        # Differentiate over at least this stride: the ingest counters
        # advance in per-batch steps (a blocked put books its whole
        # blocked interval at once), so consecutive fine-grained
        # snapshots alternate between 0 and >1 fractions and the
        # engine's min-over-window would never cross a threshold.  A
        # ~1s stride averages over the batch cadence while staying well
        # inside any realistic sustain window.
        smooth = max(a.poll_sec, 1.0)
        if self._cooldown_anchor is not None:
            # seed the cooldown: a request published by the PREVIOUS
            # generation's controller still paces this one
            self.engine._last = ScaleDecision(
                seq=0, direction="", from_world=self.engine.world,
                to_world=self.engine.world, reason="carryover",
                t=self._cooldown_anchor, actuate=False, evidence={},
            )
        while not self._stop_ev.wait(min(a.poll_sec, 0.2)):
            now = time.monotonic()
            dec = None
            if self.engine._plan:
                # scripted drills pace on the controller clock even when
                # no snapshot has landed yet
                dec = self.engine.observe(now=now, pressure=0.0, starvation=0.0)
            for rec in self._tail.poll():
                if dec is not None:
                    break
                if rec.get("kind") not in ("snapshot", "final"):
                    continue
                if prev is not None and (
                    float(rec.get("t", 0)) - float(prev.get("t", 0)) < smooth
                ):
                    continue  # hold the anchor until a full stride passed
                sig = ingest_signals(prev, rec)
                prev = rec
                if sig is None:
                    continue
                pressure, starvation = sig
                dec = self.engine.observe(
                    now=now,
                    pressure=pressure,
                    starvation=starvation,
                    gauges={
                        "lines": rec.get("lines"),
                        "lines_per_sec_inst": rec.get("lines_per_sec_inst"),
                        "queue_depth": (rec.get("ingest") or {}).get("queue_depth"),
                    },
                )
            if dec is not None:
                if self._log is not None:
                    # EVERY decision lands in the run's decision log —
                    # observe-only mode (budget 0) exists precisely to
                    # produce this evidence without actuating
                    self._log(dec)
                if dec.actuate:
                    self.decision = dec
                    self._publish(dec)
                    return  # the generation is about to be retired


def flap_count(
    decisions: list[dict], *, cooldown_sec: float, sustain_sec: float
) -> int:
    """Flaps in a decision log: direction reversals inside the damping
    window ``2 * (cooldown_sec + sustain_sec)`` (DESIGN §13).

    Works on wall-clock ``t_wall`` stamps so it composes across
    generations and processes (the engine's own per-generation counter
    cannot see a reversal that spans a re-formation)."""
    window = 2 * (cooldown_sec + sustain_sec)
    flaps = 0
    prev: dict | None = None
    for d in decisions:
        if prev is not None and d.get("direction") != prev.get("direction"):
            t0, t1 = prev.get("t_wall"), d.get("t_wall")
            if (
                isinstance(t0, (int, float))
                and isinstance(t1, (int, float))
                and t1 - t0 < window
            ):
                flaps += 1
        prev = d
    return flaps


def append_decision_log(path: str, dec: ScaleDecision, **extra) -> None:
    """Append one decision to the run's scale-log.jsonl (crash-tolerant)."""
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps({**dec.to_dict(), **extra}, separators=(",", ":")) + "\n")


def read_decision_log(path: str) -> list[dict]:
    out: list[dict] = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        return out
    return out
