"""Elastic recovery: automatic cluster re-formation after peer death.

The reference's Hadoop runtime re-executes failed tasks *automatically*
(YARN restarts a mapper whose node died and the job completes); our
distributed runtime could only detect a dead peer and abort cleanly,
leaving a human to restart.  This module closes that gap — the SURVEY §3b
"elastic/retry analog" promoted from manual to automatic:

- every worker process runs under an :class:`ElasticSupervisor` that
  registers a filesystem heartbeat in a shared rendezvous directory and
  spawns the actual analysis worker as a child process per *generation*;
- the distributed chunk loop snapshots an **epoch-tagged, world-size-
  independent checkpoint** (replicated registers + a per-shard cursor
  manifest) into the shared ``epoch/`` directory at the configured
  cadence (stream.py ``save_epoch_snapshot``);
- when a peer dies, the survivors' collectives abort (jax heartbeat
  where supported; the supervisor's own watchdog — stale member
  heartbeats for whole-node death, per-generation failure markers for
  worker-only death — kills a wedged child as the version-proof
  backstop), the supervisors detect the loss, **re-elect** a coordinator
  (lowest surviving member tag), re-form ``jax.distributed`` at the
  surviving world size on a fresh port, and spawn the next generation;
- the new generation loads the epoch checkpoint, **re-splits the unread
  input shards** across the survivors (deterministic round-robin over the
  cursor manifest), and resumes.

Teardown of the failed ``jax.distributed`` cluster is by child-process
exit — the one teardown that can never wedge on a half-dead coordinator.

Because the registers are mergeable and order-invariant, the final
per-rule hit counts and the unused-rule report are **bit-identical** to an
uninterrupted run over the same shards, at any surviving world size (the
top-K talker candidate pool is chunk-boundary-sensitive by design and may
differ — the same caveat the feeder tier documents).

Rendezvous directory layout (shared filesystem)::

    elastic_dir/
      members/<tag>.hb        heartbeat file (mtime refreshed ~2x/sec)
      members/<tag>.job.json  this member's job spec for its workers
      epoch/                  epoch checkpoints (runtime/checkpoint.py)
      gen-<g>/join/<tag>      generation-g membership markers
      gen-<g>/plan.json       leader-written formation plan
      gen-<g>/worker-<t>.log  per-worker stdio capture

Liveness notes: every wait has a timeout, exhausting ``max_reforms``
aborts with the existing clean-abort behavior, and a member that misses a
formation (slow heartbeat) aborts rather than wedging the others.
"""

from __future__ import annotations

import dataclasses
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

from ..errors import AnalysisError, EXIT_REFORM_BUDGET, StallError, exit_code_for
from . import faults, obs
from .metrics import RecoveryMeter

#: internal rc sentinel for a worker retired by a PLANNED scale event
#: (outside the kernel's exit-status range, so it can never collide with
#: a real worker rc or -signal)
SCALE_RC = -1001

#: seconds between heartbeat-file touches
HB_INTERVAL = 0.5
#: a member whose heartbeat is older than this is presumed dead (15 missed
#: beats: wide enough that host load spikes — a fleet of workers jitting
#: at once — don't read as death)
STALE_SEC = 7.5
#: after a peer is presumed dead, how long a still-running worker gets to
#: abort on its own (jax's heartbeat surface) before the supervisor kills it
KILL_GRACE_SEC = 10.0
#: generation-formation waits (join barrier, plan publication)
FORM_TIMEOUT_SEC = 180.0
#: dead-peer detection bound passed to jax.distributed (where supported)
JAX_HEARTBEAT_SEC = 10
#: cluster-formation bound: a planned member that died before joining must
#: not hold everyone in initialize() for jax's 300 s default
JAX_INIT_TIMEOUT_SEC = 60

#: child exit code that simulates abrupt node death (test fault injection:
#: the supervisor re-raises it with os._exit, taking the heartbeat with it)
DIE_RC = 77


class FormationTimeout(StallError):
    """A generation could not form within the rendezvous timeout.

    A StallError subclass: formation hanging past its bound is the
    distributed face of the same watchdog tier (CLI exit code 6)."""


class _PrevGenDone(Exception):
    """Internal: the previous generation finished while we headed into
    the next formation (a scale/death signal raced the final worker
    exits).  The run is complete; this member exits 0."""


# ---------------------------------------------------------------------------
# Cursor manifest + shard re-splitting
# ---------------------------------------------------------------------------


def manifest_of(snap) -> tuple[list[str] | None, dict[int, int], set[int]]:
    """(shards, cursors, done) from an epoch Snapshot (None -> empty)."""
    if snap is None or not snap.extra or "elastic" not in snap.extra:
        return None, {}, set()
    man = snap.extra["elastic"]
    return (
        list(man["shards"]),
        {int(k): int(v) for k, v in man["cursors"].items()},
        {int(i) for i in man["done"]},
    )


def assign_shards(
    shards: list[str],
    cursors: dict[int, int],
    done: set[int],
    world_size: int,
) -> list[list[tuple[int, str, int]]]:
    """Deterministic re-split of unread shard work across ``world_size`` ranks.

    Whole shards are the assignment unit (the HDFS-input-split analog); a
    partially-consumed shard travels with its cursor so the new owner
    resumes mid-file.  Round-robin over the remaining shards in index
    order — every worker computes the identical split from the shared
    manifest, so no coordination message is needed.
    """
    remaining = [i for i in range(len(shards)) if i not in done]
    out: list[list[tuple[int, str, int]]] = [[] for _ in range(world_size)]
    for pos, idx in enumerate(remaining):
        out[pos % world_size].append((idx, shards[idx], cursors.get(idx, 0)))
    return out


@dataclasses.dataclass
class ElasticRunSpec:
    """Everything stream.run_stream_file_distributed needs for one generation."""

    epoch_dir: str
    shards: list[str]  # the GLOBAL ordered shard list (identical everywhere)
    assignments: list[tuple[int, str, int]]  # this rank's (idx, path, start)
    snapshot: object | None  # checkpoint.Snapshot of the epoch, or None
    base_cursors: dict[int, int]  # manifest cursors at epoch load
    base_done: set[int]  # shards fully consumed before this generation
    epoch: int  # generation tag stamped into new snapshots
    die_after_batches: int | None = None  # TEST-ONLY crash injection
    pace_sec: float = 0.0  # TEST-ONLY offered-load throttle (autoscale drills)


# ---------------------------------------------------------------------------
# Rendezvous helpers
# ---------------------------------------------------------------------------


def _atomic_write_json(path: str, obj) -> None:
    """fsync'd write-then-rename; ``obj`` may be a pre-serialized string."""
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    with os.fdopen(fd, "w", encoding="utf-8") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


class _Heartbeat(threading.Thread):
    """Touches ``members/<tag>.hb`` until stopped (daemon: dies with us)."""

    def __init__(self, path: str):
        super().__init__(daemon=True, name="ra-heartbeat")
        self._path = path
        self._stop = threading.Event()

    def run(self) -> None:
        from ..errors import InjectedFault

        while not self._stop.is_set():
            try:
                # chaos site: this member's heartbeat silently stops
                # (network partition / node freeze) — the PEERS' staleness
                # watchdog must re-form without it, and this member must
                # abort when it finds itself outside the next formation
                faults.fire("elastic.heartbeat.drop", stop=self._stop)
            except InjectedFault:
                return  # stop touching forever: the partition persists
            try:
                with open(self._path, "a"):
                    os.utime(self._path, None)
            except OSError:
                pass
            self._stop.wait(HB_INTERVAL)

    def stop(self) -> None:
        self._stop.set()


# ---------------------------------------------------------------------------
# Supervisor
# ---------------------------------------------------------------------------


class ElasticSupervisor:
    """Per-process recovery supervisor: heartbeat, re-election, respawn.

    One supervisor runs in each of the job's N launcher processes (the
    ``run --distributed --elastic`` CLI path).  The analysis itself runs
    in a child process per generation, so tearing down a failed
    ``jax.distributed`` cluster is a child exit — never an in-process
    re-initialize that can wedge on a half-dead coordinator.
    """

    def __init__(
        self,
        elastic_dir: str,
        tag: int,
        n_procs: int,
        ruleset_prefix: str,
        shards: list[str],
        cfg,
        *,
        max_reforms: int = 2,
        topk: int = 10,
        native: bool | None = None,
        out_prefix: str | None = None,
        fault: dict | None = None,
        heartbeat_timeout: int = JAX_HEARTBEAT_SEC,
        coordinator_host: str | None = None,
        autoscale=None,  # config.AutoscaleConfig | None
    ):
        from ..hostside.wire import is_wire_file

        if not 0 <= tag < n_procs:
            raise AnalysisError(f"tag {tag} outside 0..{n_procs - 1}")
        wired = [p for p in shards if is_wire_file(p)]
        if wired:
            raise AnalysisError(
                f"--elastic re-splits text shards; {wired[0]!r} is a "
                ".rawire wire file (convert-tier elastic is not built yet)"
            )
        if cfg.checkpoint_every_chunks < 1:
            raise AnalysisError(
                "--elastic needs an epoch-checkpoint cadence; set "
                "--checkpoint-every N (recovery replays at most N chunks)"
            )
        self.dir = os.path.abspath(elastic_dir)
        self.tag = int(tag)
        self.n_procs = int(n_procs)
        self.max_reforms = int(max_reforms)
        # -- metrics-driven autoscaling (runtime/autoscale.py) ------------
        # the launcher pool is the PROVISIONED maximum: members outside
        # the active world park as warm standbys and join the next
        # formation when a scale-out (or a death) needs them
        self.autoscale = autoscale
        self._ladder: list[int] = []
        self._initial_world = int(n_procs)
        if autoscale is not None:
            from .autoscale import world_ladder

            max_w = autoscale.max_world or self.n_procs
            if max_w > self.n_procs:
                raise AnalysisError(
                    f"--autoscale-max {max_w} exceeds the provisioned "
                    f"launcher pool ({self.n_procs} members)"
                )
            self._ladder = world_ladder(autoscale.min_world, max_w)
            self._initial_world = autoscale.initial_world or autoscale.min_world
        self._scale_pending: dict | None = None
        self._scale_anchor: float | None = None
        # children always start fresh from the shared epoch dir; the
        # per-process --resume machinery must not engage
        self.cfg = cfg.replace(resume=False)
        self.job = {
            "ruleset": os.path.abspath(ruleset_prefix),
            "shards": [os.path.abspath(p) for p in shards],
            "cfg": self.cfg.to_dict(),
            "topk": int(topk),
            "native": native,
            "out": os.path.abspath(out_prefix) if out_prefix else None,
            "heartbeat_timeout": int(heartbeat_timeout),
            "init_timeout": JAX_INIT_TIMEOUT_SEC,
            "fault": fault,
            "autoscale": autoscale.to_dict() if autoscale is not None else None,
        }
        self.coordinator_host = coordinator_host or os.environ.get(
            "RA_ELASTIC_HOST", "127.0.0.1"
        )
        from ..parallel.distributed import (
            check_one_chip_per_process, is_loopback,
        )

        if is_loopback(self.coordinator_host):
            # every launcher's generation worker runs on this host
            check_one_chip_per_process(
                self.n_procs, f"--elastic launchers behind a loopback "
                f"coordinator ({self.coordinator_host})",
            )
        self.meter = RecoveryMeter()
        self.reforms_used = 0
        self.final_world: list[int] | None = None
        self._hb: _Heartbeat | None = None

    # -- paths ------------------------------------------------------------
    def _members_dir(self) -> str:
        return os.path.join(self.dir, "members")

    def _hb_path(self, tag: int) -> str:
        return os.path.join(self._members_dir(), f"{tag}.hb")

    def _gen_dir(self, gen: int) -> str:
        return os.path.join(self.dir, f"gen-{gen}")

    def _plan_path(self, gen: int) -> str:
        return os.path.join(self._gen_dir(gen), "plan.json")

    @property
    def epoch_dir(self) -> str:
        return os.path.join(self.dir, "epoch")

    def _scale_path(self) -> str:
        return os.path.join(self.dir, "scale.json")

    def _scale_log_path(self) -> str:
        return os.path.join(self.dir, "scale-log.jsonl")

    def _metrics_path(self, gen: int, tag: int) -> str:
        return os.path.join(self._gen_dir(gen), f"metrics-{tag}.jsonl")

    # -- membership -------------------------------------------------------
    def _fresh_members(self) -> set[int]:
        now = time.time()
        fresh = set()
        try:
            entries = os.listdir(self._members_dir())
        except OSError:
            return fresh
        for e in entries:
            if not e.endswith(".hb"):
                continue
            try:
                t = int(e[:-3])
                if now - os.path.getmtime(os.path.join(self._members_dir(), e)) < STALE_SEC:
                    fresh.add(t)
            except (ValueError, OSError):
                continue
        return fresh

    def _join(self, gen: int) -> None:
        d = os.path.join(self._gen_dir(gen), "join")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, str(self.tag)), "w") as f:
            f.write(str(os.getpid()))

    def _joined(self, gen: int) -> set[int]:
        d = os.path.join(self._gen_dir(gen), "join")
        try:
            return {int(e) for e in os.listdir(d) if e.isdigit()}
        except OSError:
            return set()

    def _mark_done(self, gen: int) -> None:
        """Success marker: parked standbys (and racing peers heading into
        the next formation) learn the run completed and exit 0."""
        d = os.path.join(self._gen_dir(gen), "done")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, str(self.tag)), "w") as f:
            f.write("")

    def _done(self, gen: int) -> bool:
        try:
            return bool(os.listdir(os.path.join(self._gen_dir(gen), "done")))
        except OSError:
            return False

    def _read_scale(self) -> dict | None:
        """The current scale request (atomic-written by the leader)."""
        try:
            with open(self._scale_path(), "r", encoding="utf-8") as f:
                req = json.load(f)
        except (OSError, ValueError):
            return None
        return req if isinstance(req, dict) else None

    def _mark_failed(self, gen: int) -> None:
        d = os.path.join(self._gen_dir(gen), "failed")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, str(self.tag)), "w") as f:
            f.write("")

    def _peer_failed(self, gen: int) -> bool:
        d = os.path.join(self._gen_dir(gen), "failed")
        try:
            return any(e.isdigit() and int(e) != self.tag for e in os.listdir(d))
        except OSError:
            return False

    # -- formation --------------------------------------------------------
    def _target_world(self, gen: int, avail: list[int]) -> tuple[int, int]:
        """Leader-side sizing: (active world size, consumed scale seq).

        The active size carries forward from the previous generation's
        plan, updated by a pending scale request (``scale.json`` with a
        seq the previous plan has not consumed) and clamped to the
        members actually available — a death below the requested world
        runs with what is left, and a parked standby is promoted to
        backfill a dead active member (warm-standby replacement).
        """
        if gen == 0:
            prev_size, seen = self._initial_world, 0
        else:
            try:
                with open(self._plan_path(gen - 1), "r", encoding="utf-8") as f:
                    prev = json.load(f)
                prev_size = len(prev["world"])
                seen = int(prev.get("scale_seq", 0))
            except (OSError, ValueError, KeyError):
                prev_size, seen = self._initial_world, 0
        req = self._read_scale()
        if req is not None and int(req.get("seq", 0)) > seen:
            seen = int(req["seq"])
            prev_size = int(req["to_world"])
        hi = self._ladder[-1] if self._ladder else len(avail)
        return max(1, min(prev_size, len(avail), hi)), seen

    def _form(self, gen: int) -> dict:
        """Join the generation-``gen`` barrier; return the agreed plan.

        Membership rule: wait until every member with a FRESH heartbeat
        has joined this generation — a slow-failing survivor keeps its
        heartbeat fresh, so the barrier waits for it; a dead member's
        heartbeat goes stale and it simply drops out of the set.  Gen 0
        additionally waits for the full launch-time membership (processes
        may still be starting, heartbeat-less).  The member with the
        lowest surviving tag is the leader: it allocates the coordinator
        port and publishes the plan; everyone else polls for it.

        Under ``--autoscale`` the plan splits the pool into an ACTIVE
        world (``world``, sized by :meth:`_target_world`) and parked
        ``standby`` members; without it the plan keeps its historical
        shape (world = everyone, no standby).
        """
        t_form0 = time.perf_counter()
        self._join(gen)
        deadline = time.monotonic() + FORM_TIMEOUT_SEC
        plan_path = self._plan_path(gen)
        while True:
            if os.path.exists(plan_path):
                break  # someone already published the plan
            if gen > 0 and self._done(gen - 1):
                # the previous generation completed while a scale/death
                # signal sent us here; nobody will ever form this one
                raise _PrevGenDone()
            fresh = self._fresh_members()
            fresh.add(self.tag)  # our own hb file may lag a beat
            joined = self._joined(gen)
            ready = (
                joined >= set(range(self.n_procs))
                if gen == 0
                else fresh <= joined
            )
            if ready:
                avail = sorted(joined & fresh | {self.tag})
                if avail and avail[0] == self.tag:
                    # re-elected coordinator: publish the formation plan
                    plan = {
                        "gen": gen,
                        "world": avail,
                        "coordinator": f"{self.coordinator_host}:{_free_port()}",
                    }
                    if self.autoscale is not None:
                        target, seen = self._target_world(gen, avail)
                        plan["world"] = avail[:target]
                        plan["standby"] = avail[target:]
                        plan["scale_seq"] = seen
                    _atomic_write_json(plan_path, plan)
                    break
                # not the leader: fall through and poll for the plan (if
                # the presumed leader died before writing, its heartbeat
                # goes stale and a later iteration elects the next tag)
            if time.monotonic() > deadline:
                raise FormationTimeout(
                    f"generation {gen} did not form within "
                    f"{FORM_TIMEOUT_SEC:.0f}s (joined={sorted(joined)}, "
                    f"fresh={sorted(fresh)})"
                )
            time.sleep(0.1)
        with open(plan_path, "r", encoding="utf-8") as f:
            plan = json.load(f)
        # the join-to-plan window of THIS member, on the merged timeline
        obs.complete(
            "elastic.form", t_form0, time.perf_counter(), cat="elastic",
            args={"gen": gen, "world": list(plan["world"])},
        )
        if (
            self.tag not in plan["world"]
            and self.tag not in plan.get("standby", [])
        ):
            # our heartbeat was stale when the plan was cut; aborting THIS
            # member is the safe outcome (the formed world runs without us)
            raise AnalysisError(
                f"member {self.tag} missed generation {gen} formation "
                f"(world={plan['world']}); aborting this launcher"
            )
        return plan

    # -- autoscale actuation ----------------------------------------------
    def _standby_wait(self, gen: int, plan: dict) -> str:
        """Park as a warm standby while generation ``gen`` runs without us.

        Returns ``"done"`` when the run completed (this member exits 0)
        or ``"next"`` when the generation ended another way — a scale
        request, a peer-marked failure, or an active member's heartbeat
        going stale — and the next formation needs us at the barrier.
        """
        obs.instant(
            "autoscale.standby", args={"gen": gen, "tag": self.tag}
        )
        scale_seq = int(plan.get("scale_seq", 0))
        active = set(plan["world"])
        while True:
            if self._done(gen):
                return "done"
            req = self._read_scale()
            if req is not None and int(req.get("seq", 0)) > scale_seq:
                return "next"
            if self._peer_failed(gen):
                return "next"
            if active - self._fresh_members():
                # an active member died outright; the survivors are
                # about to re-form and the barrier will want us fresh
                return "next"
            time.sleep(0.2)

    def _start_controller(self, gen: int, world: list[int], scale_seq: int):
        """Leader-only: per-generation policy controller (autoscale.py).

        Tails this member's own worker metrics shard — the leader IS
        rank 0, so that shard carries the ingest gauges of the rank that
        paces the collective step — and publishes at most one scale
        request into the rendezvous directory.  Returns None when the
        surviving world fell off the ladder (deaths below
        ``--autoscale-min``): scaling pauses until a formation puts the
        world back on a rung.
        """
        from .autoscale import AutoscaleController, append_decision_log

        a = self.autoscale
        if len(world) not in self._ladder:
            return None
        seq = scale_seq + 1

        def log(dec) -> None:
            # EVERY decision — actuated or observe-only (budget 0, the
            # rollout drill) — lands in the shared decision log, which
            # is what _patch_result folds into totals.autoscale
            append_decision_log(
                self._scale_log_path(), dec,
                gen=gen, seq_global=seq, t_wall=round(time.time(), 3),
            )

        def publish(dec) -> None:
            _atomic_write_json(self._scale_path(), {
                "seq": seq,
                "from_world": dec.from_world,
                "to_world": dec.to_world,
                "direction": dec.direction,
                "reason": dec.reason,
                "gen": gen,
                "t_wall": round(time.time(), 3),
            })

        ctrl = AutoscaleController(
            a,
            world=len(world),
            ladder=self._ladder,
            metrics_path=self._metrics_path(gen, self.tag),
            publish=publish,
            log=log,
            budget_left=max(0, a.reform_budget - scale_seq),
            cooldown_anchor=self._scale_anchor,
        )
        # scripted drills: entries already actuated by previous
        # generations' controllers must not re-fire
        ctrl.engine._plan_fired = min(scale_seq, len(ctrl.engine._plan))
        ctrl.start()
        return ctrl

    # -- child lifecycle --------------------------------------------------
    def _spawn_worker(self, gen: int) -> tuple[subprocess.Popen, object]:
        env = dict(os.environ)
        pkg_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        env["PYTHONPATH"] = os.pathsep.join(
            [pkg_root, *filter(None, [env.get("PYTHONPATH", "")])]
        )
        log = open(
            os.path.join(self._gen_dir(gen), f"worker-{self.tag}.log"), "ab"
        )
        proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "ruleset_analysis_tpu.runtime.elastic",
                "worker",
                self.dir,
                str(self.tag),
                str(gen),
            ],
            env=env,
            stdout=log,
            stderr=subprocess.STDOUT,
        )
        return proc, log

    def _watch_worker(
        self,
        proc: subprocess.Popen,
        world: list[int],
        gen: int,
        *,
        scale_seq: int = 0,
        ctrl=None,
    ) -> int:
        """Wait for the worker; kill it when a peer is known lost.

        Two loss signals feed the same grace-then-kill path, making
        detection bounded on EVERY supported jax (where the installed jax
        has collective heartbeats those usually abort the survivors
        first; this watchdog is the version-proof bound):

        - a peer's rendezvous heartbeat went stale (whole-node death);
        - a peer marked this generation failed (worker-only death — its
          supervisor is alive and heartbeating, but our worker may be
          wedged in a collective that will never complete).

        A worker still running KILL_GRACE_SEC after either signal is
        presumed wedged and killed, which counts as an ordinary
        generation failure and feeds re-formation.
        """
        lost_since: float | None = None
        while True:
            rc = proc.poll()
            if rc is not None:
                return rc
            if ctrl is not None and ctrl.error is not None:
                # the policy controller died (e.g. an injected
                # autoscale.decide fault): no scale request was ever
                # published, so the safe outcomes are continue-at-old-
                # world or typed abort — we abort typed, matching the
                # serve driver's semantics for the same seam
                proc.kill()
                proc.wait()
                err = ctrl.error
                if isinstance(err, AnalysisError):
                    raise err
                raise AnalysisError(f"autoscale controller failed: {err}") from err
            if self.autoscale is not None:
                req = self._read_scale()
                if (
                    req is not None
                    and int(req.get("seq", 0)) > scale_seq
                    and not self._done(gen)
                ):
                    # PLANNED retirement: kill the worker exactly like the
                    # certified death path — the next generation resumes
                    # from the epoch checkpoint (replaying at most
                    # checkpoint_every_chunks), report bit-identical.
                    # A generation already marked done is finishing: the
                    # request raced the final exits, and killing rank 0
                    # mid-report-write would lose the run — let the
                    # worker exit instead.
                    obs.instant(
                        "autoscale.retire",
                        args={"gen": gen, "tag": self.tag, **req},
                    )
                    proc.kill()
                    proc.wait()
                    self._scale_pending = {"t": time.monotonic(), **req}
                    return SCALE_RC
            peers = set(world) - {self.tag}
            stale = bool(peers - self._fresh_members())
            failed = self._peer_failed(gen)
            if stale or failed:
                if lost_since is None:
                    lost_since = time.monotonic()
                    self.meter.detect(
                        "peer heartbeat lost" if stale else "peer worker failed"
                    )
                elif time.monotonic() - lost_since > KILL_GRACE_SEC:
                    proc.kill()
                    proc.wait()
                    return -9
            else:
                # the lagging peer came back (load spike, not death): a
                # one-off stale reading must not arm a later kill
                lost_since = None
            time.sleep(0.2)

    # -- the supervised driver loop ---------------------------------------
    def run(self) -> tuple[int, str | None]:
        """Supervise until success or budget exhaustion.

        Returns ``(rc, result_json_path)``: rc 0 on success; the path is
        set only on the member whose worker held rank 0 of the final
        generation (the one that wrote the report).
        """
        os.makedirs(self._members_dir(), exist_ok=True)
        os.makedirs(self.epoch_dir, exist_ok=True)
        _atomic_write_json(
            os.path.join(self._members_dir(), f"{self.tag}.job.json"), self.job
        )
        self._hb = _Heartbeat(self._hb_path(self.tag))
        self._hb.start()
        # recovery totals ride every metrics snapshot while supervising
        # (satellite of the RecoveryMeter summary — an operator tailing
        # --metrics-out sees reforms_used move without waiting for the
        # final report)
        obs.register_sampler(
            "recovery",
            lambda: {"reforms_used": self.reforms_used, **self.meter.summary()},
        )
        try:
            gen = 0
            world: list[int] = []
            while True:
                try:
                    plan = self._form(gen)
                except _PrevGenDone:
                    # the run completed while a scale/death signal sent
                    # us to the next barrier.  If WE held rank 0 of the
                    # generation that completed, the report is ours to
                    # return — and it must exist intact: a planned
                    # retirement that raced the final report write must
                    # surface as a typed abort, never a silent exit 0
                    # with the report lost (the standing invariant)
                    out = self.job["out"]
                    if not (world and world[0] == self.tag and out):
                        return 0, None  # completed without us
                    path = out + ".json"
                    try:
                        with open(path, "r", encoding="utf-8") as f:
                            json.load(f)
                    except (OSError, ValueError) as e:
                        raise AnalysisError(
                            "elastic: run completed but rank 0's report "
                            f"at {path!r} is missing or torn (a scale/"
                            "death signal raced the final write); "
                            "re-run to regenerate it"
                        ) from e
                    return 0, self._patch_result(path)
                except FormationTimeout as e:
                    print(f"elastic: {e}", file=sys.stderr)
                    return exit_code_for(e), None  # stall class (6)
                world = list(plan["world"])
                scale_seq = int(plan.get("scale_seq", 0))
                if self.tag not in world:
                    # parked warm standby: heartbeat on, no worker — we
                    # join the next formation when a scale-out (or a
                    # death backfill) needs us
                    self._scale_pending = None
                    if self._standby_wait(gen, plan) == "done":
                        return 0, None
                    gen += 1
                    continue
                if self._scale_pending is not None:
                    # the planned scale event is applied: the new world
                    # formed and its worker is about to run
                    rec = {
                        "applied_seq": int(self._scale_pending.get("seq", scale_seq)),
                        "gen": gen,
                        "world": len(world),
                        "time_to_effect_sec": round(
                            time.monotonic() - self._scale_pending["t"], 3
                        ),
                    }
                    self._scale_anchor = time.monotonic()
                    if world[0] == self.tag:
                        with open(
                            self._scale_log_path(), "a", encoding="utf-8"
                        ) as f:
                            f.write(json.dumps(
                                {"kind": "applied", **rec},
                                separators=(",", ":"),
                            ) + "\n")
                    obs.metric_event("autoscale.applied", **rec)
                    self._scale_pending = None
                if gen > 0 and self.meter.detecting:
                    # the moment the replacement cluster is formed and its
                    # worker is about to run — the recovery is complete
                    # (planned scale re-formations have no detect window
                    # and must not pollute the MTTR statistics)
                    self.meter.recovered(world=len(world))
                proc, log = self._spawn_worker(gen)
                ctrl = None
                if self.autoscale is not None and world[0] == self.tag:
                    ctrl = self._start_controller(gen, world, scale_seq)
                try:
                    rc = self._watch_worker(
                        proc, world, gen, scale_seq=scale_seq, ctrl=ctrl
                    )
                finally:
                    log.close()
                    if ctrl is not None:
                        ctrl.stop()
                        ctrl.join(timeout=5.0)
                if rc == 0:
                    self.final_world = world
                    self._mark_done(gen)
                    out = self.job["out"]
                    if world[0] == self.tag and out:
                        return 0, self._patch_result(out + ".json")
                    return 0, None
                if rc == SCALE_RC:
                    req = self._scale_pending or {}
                    seq_seen = int(req.get("seq", scale_seq + 1))
                    print(
                        f"elastic: planned scale event #{seq_seen}: "
                        f"world {req.get('from_world')}->{req.get('to_world')} "
                        f"({req.get('reason', '?')}); re-forming",
                        file=sys.stderr,
                    )
                    # chaos seam: actuation failing between retiring the
                    # old world and forming the new one must be a typed
                    # abort over an intact epoch checkpoint, never a hang
                    faults.fire("autoscale.spawn")
                    gen += 1
                    continue
                if rc == DIE_RC:
                    # fault injection: this NODE is simulated dead — take
                    # the heartbeat down with us, abruptly
                    os._exit(DIE_RC)
                # tell the peers this generation is dead even though WE
                # are alive — their workers may be wedged in a collective
                # and their supervisors see our heartbeat as healthy (the
                # worker-only-death signal; see _watch_worker)
                self._mark_failed(gen)
                self.meter.detect(f"worker exited rc={rc}")
                self.reforms_used += 1
                if self.reforms_used > self.max_reforms:
                    self.meter.abandon()
                    print(
                        f"elastic: re-formation budget exhausted "
                        f"({self.reforms_used - 1} re-forms used, "
                        f"--max-reforms {self.max_reforms}); aborting "
                        f"(last worker rc={rc}, log: "
                        f"{self._gen_dir(gen)}/worker-{self.tag}.log)",
                        file=sys.stderr,
                    )
                    # documented failure-class exit code (errors.py):
                    # supervisors branch on 7 = ReformBudgetExhausted
                    return EXIT_REFORM_BUDGET, None
                print(
                    f"elastic: generation {gen} failed (worker rc={rc}); "
                    f"re-forming ({self.reforms_used}/{self.max_reforms})",
                    file=sys.stderr,
                )
                gen += 1
        finally:
            obs.unregister_sampler("recovery")
            if self._hb is not None:
                self._hb.stop()

    def _patch_result(self, result_path: str) -> str:
        """Fold the supervisor's recovery + autoscale totals into the report."""
        try:
            with open(result_path, "r", encoding="utf-8") as f:
                rep = json.load(f)
        except (OSError, ValueError):
            return result_path  # report stands as written
        rec = {"reforms_used": self.reforms_used, **self.meter.summary()}
        rep.setdefault("totals", {})["recovery"] = rec
        if self.autoscale is not None:
            from .autoscale import flap_count, read_decision_log

            log = read_decision_log(self._scale_log_path())
            decisions = [r for r in log if r.get("kind") != "applied"]
            applied = [r for r in log if r.get("kind") == "applied"]
            rep["totals"]["autoscale"] = {
                "scale_events": len(applied),
                "scale_out": sum(
                    1 for r in decisions if r.get("direction") == "out"
                ),
                "scale_in": sum(
                    1 for r in decisions if r.get("direction") == "in"
                ),
                "flaps": flap_count(
                    decisions,
                    cooldown_sec=self.autoscale.cooldown_sec,
                    sustain_sec=self.autoscale.sustain_sec,
                ),
                "final_world": len(self.final_world or []),
                "decisions": decisions,
                "applied": applied,
            }
        _atomic_write_json(result_path, rep)
        return result_path


# ---------------------------------------------------------------------------
# Worker (child) entry — one generation of actual analysis
# ---------------------------------------------------------------------------


def _start_supervisor_watchdog() -> None:
    """Abort this worker if its supervisor dies (per-generation liveness).

    The supervisor owns the heartbeat; if it dies, the peers re-form
    WITHOUT this member while its orphaned worker would keep computing
    and — worst case — keep writing epoch snapshots over the new
    generation's.  Reparenting (getppid change) is the cheap, version-
    proof orphan signal; exit is abrupt on purpose (the collectives this
    worker holds open must abort, not drain)."""
    ppid = os.getppid()

    def watch() -> None:
        while True:
            if os.getppid() != ppid:
                print(
                    "elastic worker: supervisor died (orphaned); aborting",
                    file=sys.stderr,
                    flush=True,
                )
                os._exit(1)
            time.sleep(1.0)

    threading.Thread(
        target=watch, daemon=True, name="ra-supervisor-watchdog"
    ).start()


def _worker_main(elastic_dir: str, tag: int, gen: int) -> int:
    # trace shard arming is inherited via RA_TRACE_DIR (supervisor env);
    # the label names this generation worker's track in the merged view.
    # The flight recorder arms the same way (RA_BLACKBOX_DIR, inside
    # note_role's lazy env check): a generation worker that dies typed
    # dumps its ring via the excepthook, and a clean generation seals at
    # exit so a later supervisor abort can still merge its telemetry.
    obs.note_role(f"elastic-worker-{tag}-gen{gen}")
    from . import flightrec

    flightrec.cursor(elastic_gen=gen, elastic_tag=tag)
    _start_supervisor_watchdog()
    with open(
        os.path.join(elastic_dir, "members", f"{tag}.job.json"),
        "r",
        encoding="utf-8",
    ) as f:
        job = json.load(f)
    with open(
        os.path.join(elastic_dir, f"gen-{gen}", "plan.json"),
        "r",
        encoding="utf-8",
    ) as f:
        plan = json.load(f)
    world = list(plan["world"])
    if tag not in world:
        print(f"worker {tag}: not in generation {gen} world {world}", file=sys.stderr)
        return 4
    rank, nproc = world.index(tag), len(world)

    from ..parallel.distributed import init_distributed
    from .compcache import enable_persistent_cache

    # every generation is a fresh process: without the on-disk cache each
    # re-formation would re-pay the full step compile, inflating
    # time-to-recover by the compile time
    enable_persistent_cache()
    init_distributed(
        plan["coordinator"],
        nproc,
        rank,
        heartbeat_timeout_seconds=job["heartbeat_timeout"],
        initialization_timeout=job["init_timeout"],
    )

    import numpy as np

    from ..config import AnalysisConfig
    from ..hostside import pack
    from . import checkpoint as ckpt
    from .stream import run_stream_file_distributed

    packed = pack.load_packed(job["ruleset"])
    cfg = AnalysisConfig.from_dict(job["cfg"])
    epoch_dir = os.path.join(elastic_dir, "epoch")
    snap = ckpt.load(epoch_dir)
    shards = list(job["shards"])
    man_shards, cursors, done = manifest_of(snap)
    if man_shards is not None and man_shards != shards:
        raise ckpt.CheckpointMismatch(
            f"epoch snapshot in {epoch_dir!r} covers different shards; "
            "refusing to merge"
        )
    acfg = job.get("autoscale")
    if acfg:
        # arm the metrics snapshotter on this worker's per-generation
        # shard: the leader supervisor's policy controller tails rank
        # 0's shard for the canonical backpressure/starvation signals
        # (autoscale.ingest_signals) — the SAME JSONL an operator's
        # --metrics-out would carry, one source of truth
        obs.start_metrics(
            os.path.join(elastic_dir, f"gen-{gen}", f"metrics-{tag}.jsonl"),
            every_sec=float(acfg.get("poll_sec", 0.5)),
        )
    try:
        pace = float(os.environ.get("RA_ELASTIC_PACE", "") or 0.0)
    except ValueError:
        pace = 0.0
    fault = job.get("fault")
    die = None
    if (
        fault is not None
        and int(fault["tag"]) == tag
        and (fault.get("gen") is None or gen == int(fault["gen"]))
    ):
        # no gen filter: the fault arms at this tag's FIRST opportunity
        # (its supervisor dies with it, so it never fires twice)
        die = int(fault["after_batches"])
    spec = ElasticRunSpec(
        epoch_dir=epoch_dir,
        shards=shards,
        assignments=assign_shards(shards, cursors, done, nproc)[rank],
        snapshot=snap,
        base_cursors=cursors,
        base_done=done,
        epoch=gen,
        die_after_batches=die,
        pace_sec=pace,
    )
    try:
        report, regs = run_stream_file_distributed(
            packed,
            [],
            cfg,
            native=job["native"],
            topk=job["topk"],
            return_state=True,
            elastic=spec,
        )
    finally:
        flightrec.seal()
    if rank == 0 and job["out"]:
        np.savez(job["out"] + ".npz", **regs)
        _atomic_write_json(job["out"] + ".json", report.to_json())
    print(f"worker {tag} (rank {rank}/{nproc}, gen {gen}) done", file=sys.stderr)
    return 0



if __name__ == "__main__":
    if len(sys.argv) == 5 and sys.argv[1] == "worker":
        raise SystemExit(
            _worker_main(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
        )
    print(
        "usage: python -m ruleset_analysis_tpu.runtime.elastic worker "
        "ELASTIC_DIR TAG GEN  (spawned by ElasticSupervisor)",
        file=sys.stderr,
    )
    raise SystemExit(2)
