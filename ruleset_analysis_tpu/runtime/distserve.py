"""Multi-host distributed serve: per-host ingest tiers + cross-host
register merge over the hybrid mesh's host (dcn) axis (DESIGN §22).

``serve --distributed`` splits the always-on service into two planes:

- **Host ingest workers** (:class:`HostServeDriver`, one per host): a
  full single-host serve loop — listeners, LineQueue, feeder, WAL
  spool, flight-recorder shard, device mesh — ingesting its own traffic
  slice into host-local register planes.  Publication is stripped to
  nothing: at every window rotation the closed epoch (register arrays +
  tracker tables + accounting meta + WAL cursor) ships to rank 0 as one
  CRC-framed payload (parallel/distributed.py::pack_epoch_payload).

- **Rank-0 merge + publication** (:class:`DistServeDriver`): collects
  each window's per-host epochs and merges them under the ``_merge_tail``
  laws (add64 for exact counts, add mod 2^32 for CMS planes, max for
  HLL) — the same associative laws the in-mesh ``("dcn", data)``
  collective reduces over, realized host-side so a dead host degrades
  the service instead of poisoning a pending collective.  The merged
  window is bit-identical to a single-host replay of the union of all
  hosts' delivered lines (registers AND report body, candidates
  included — pinned by tests/test_distserve.py), and rank 0 owns every
  publication surface: window/cumulative/diff JSON, merged views, the
  HTTP endpoint, and the merged-ring checkpoint.

Ordering + liveness: merged windows publish strictly in window-id
order.  Window ``w`` publishes when every host expected at ``w`` has
submitted it; a host marked dead completes the window immediately
(named in the typed ``WindowIncomplete`` marker — never a hang, never
a silent zero-hit), and a live-but-silent host is waited on for
``merge_timeout_sec`` past the window's first arrival, then named as
missing.  A host's late epoch for an already-published window is
dropped with explicit accounting (``late_epochs`` in /health), never
silently merged or silently discarded.

Elasticity: the checkpoint fingerprint pins the host-tier ladder
MAXIMUM (``DistServeConfig.ladder_max``), not the live host count —
the merged registers are world-size-independent, so a checkpoint taken
at 2 hosts resumes at 3 (and vice versa).  With ``--autoscale`` the
policy engine is promoted to a host-tier actuator: scale-out spawns a
fresh host joining at the merge frontier; scale-in retires the
highest-rank host, which stops ingress, drains its queue into one
final window marked ``retired``, and leaves cleanly — never a silent
drop.  An unexpectedly dead host (SIGKILL, OOM) is respawned when
``--dist-respawn`` is set; the replacement replays its predecessor's
WAL tail past the last merged seq.
"""

from __future__ import annotations

import dataclasses
import json
import os
import socket
import struct
import threading
import time
import zlib

import numpy as np

from ..config import (
    AnalysisConfig, AutoscaleConfig, DistServeConfig, ServeConfig,
)
from ..errors import AnalysisError, SupervisorFenced, WalQuarantine
from ..hostside import pack as pack_mod
from ..hostside.listener import offset_listen_spec
from ..models import pipeline
from ..ops.topk import TopKTracker
from ..parallel.distributed import (
    check_one_chip_per_process, pack_epoch_payload, unpack_epoch_payload,
)
from . import checkpoint as ckpt
from . import epochstore, faults, flightrec, obs, retrypolicy
from .lease import EpochSpool, SupervisorLease
from .autoscale import PolicyEngine, host_ladder, render_prom_labeled
from .metrics import LatencyHistogram, build_info, render_build_info_prom
from .serve import (
    ServeDriver, WindowEpoch, WindowRing, _make_http_server,
    _merge_quarantine, merge_register_arrays, zero_arrays,
)
from .wal import LineageLog
from .report import seal_lineage

# ---------------------------------------------------------------------------
# Host-tier control frames: one length-prefixed frame = u32 LE body
# length + 1 kind byte + body.  Worker -> rank 0: H(ello, JSON),
# E(poch, pack_epoch_payload bytes), F (an epoch draining out of the
# partition backlog at heal — same body as E, lineage path stamp
# differs), G(auges, JSON), B(ye, JSON).
# Rank 0 -> worker: R(etire), S(top).  Thread-mode workers skip the
# socket but run the SAME frames through the same dispatch, so the wire
# discipline is exercised in-tier, not only in the slow process tests.
# ---------------------------------------------------------------------------

#: frame size ceiling: a register epoch is MBs, never GBs — anything
#: larger is a corrupt length prefix, refused before allocation
_FRAME_MAX = 1 << 31


def _send_frame(sock: socket.socket, kind: bytes, body: bytes) -> None:
    sock.sendall(struct.pack("<I", len(body) + 1) + kind + body)


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> tuple[bytes, bytes] | None:
    """One frame, or None on clean EOF; typed error on a torn frame."""
    hdr = _recv_exact(sock, 4)
    if hdr is None:
        return None
    (n,) = struct.unpack("<I", hdr)
    if not 1 <= n <= _FRAME_MAX:
        raise AnalysisError(f"host-tier frame length {n} out of range")
    body = _recv_exact(sock, n)
    if body is None:
        raise AnalysisError("host-tier connection died mid-frame")
    return body[:1], body[1:]


def _ser_tracker(tables: dict[int, dict[int, int]]) -> list:
    return [
        [int(acl), [[int(s), int(e)] for s, e in t.items()]]
        for acl, t in tables.items()
    ]


def _ser_quarantine(q: dict[tuple, int]) -> list:
    return [
        [fw, acl, int(idx), text, int(h)]
        for (fw, acl, idx, text), h in sorted(q.items())
    ]


def _de_quarantine(rows: list) -> dict[tuple, int]:
    return {
        (fw, acl, int(idx), text): int(h) for fw, acl, idx, text, h in rows
    }


# ---------------------------------------------------------------------------
# The per-host ingest worker.
# ---------------------------------------------------------------------------

#: Thread-mode hosts share ONE process and therefore ONE xla:cpu client;
#: concurrent shard_map executes from different host threads can cross
#: their collective rendezvous and wedge until the collective timeout
#: (the oversubscribed-host load artifact the tests/conftest.py
#: calibration note describes).  Thread-mode hosts therefore take this
#: gate around device execution — blocking until the step's outputs are
#: ready before releasing — so at most one collective program is in
#: flight per client.  Process workers (the production mode) never take
#: it: each owns its own client and keeps the full async pipeline.
_THREAD_STEP_GATE = threading.Lock()


class HostServeDriver(ServeDriver):
    """One host's ingest tier of ``serve --distributed``.

    A full :class:`ServeDriver` with publication handed to rank 0: the
    ``_emit_epoch`` hook ships every closed window to the merge plane
    and ``_publish`` keeps only the in-memory report (debug surface; no
    disk, no diffs, no cumulative render — rank 0 owns all of that).
    The worker NEVER checkpoints its ring (``checkpoint_every_windows``
    is forced to 0 by the supervisor): durability is the per-host WAL +
    rank 0's merged-ring checkpoint, and a rejoining worker replays its
    WAL tail past ``wal_resume_seq`` (the last seq rank 0 merged).
    """

    #: this tier's lineage records (host-local ledger under host-<r>/);
    #: the supervisor assembles the authoritative "dist" records from
    #: the shipped per-epoch extras
    _lineage_kind = "host"

    def __init__(
        self,
        rank: int,
        emit,
        ruleset_prefix: str,
        cfg: AnalysisConfig,
        scfg: ServeConfig,
        *,
        topk: int = 10,
        start_window: int = 0,
        wal_resume_seq: int = 0,
        serialize_dispatch: bool = False,
        spool_dir: str = "",
        spool_budget_mb: int = 0,
        spool_resume: bool = False,
    ):
        super().__init__(ruleset_prefix, cfg, scfg, topk=topk)
        self.rank = rank
        self._emit = emit  # callable(kind: bytes, body: bytes)
        self._dispatch_gate = (
            _THREAD_STEP_GATE if serialize_dispatch else None
        )
        self._start_window = start_window
        self._given_wal_seq = wal_resume_seq
        self._seeded = False
        self._gauge_next = 0.0
        self._retire_req = False
        self._retiring = False
        self._kill_req = False  # chaos seam: abrupt in-process host death
        # external stop (supervisor S-frame / signal), as opposed to the
        # local max_windows finish: only the former aborts the final
        # backlog drain (_teardown sets _stop_req on EVERY exit path, so
        # it cannot distinguish the two)
        self._ext_stop = threading.Event()
        # durable epoch spool (DESIGN §23): every closed window's packed
        # epoch is appended here BEFORE it ships, so it survives this
        # host AND any supervisor; a failover successor replays it
        self._ship_backlog: list[bytes] = []  # parked by partition mode
        self._spool: EpochSpool | None = None
        if spool_dir and spool_budget_mb > 0:
            try:
                self._spool = EpochSpool(
                    spool_dir, budget_bytes=spool_budget_mb << 20
                )
                if not spool_resume:
                    # a fresh (non-rejoin) start must not leave a stale
                    # spool for a later failover to replay into new ids
                    self._spool.reset()
            except (WalQuarantine, OSError) as e:
                self._spool = None
                self._degrade("spool", e)

    # -- control surface (reader thread / supervisor) ---------------------
    def request_retire(self) -> None:
        """Planned retirement: stop ingress, drain the queue into one
        final window marked ``retired`` — never a silent drop."""
        self._retire_req = True

    def kill(self) -> None:
        """Abrupt death for the in-process chaos tests: the serve loop
        raises at its next tick, losing the open window exactly like a
        SIGKILL would (minus what the WAL already spooled)."""
        self._kill_req = True

    def stop(self) -> None:
        self._ext_stop.set()
        super().stop()

    # -- overridden device dispatch ---------------------------------------
    def _run_chunk(self, batch_np: np.ndarray) -> None:
        gate = self._dispatch_gate
        if gate is None:
            return super()._run_chunk(batch_np)
        import jax

        with gate:
            super()._run_chunk(batch_np)
            jax.block_until_ready(self.state)

    def _run_chunk6(self, batch6_np: np.ndarray) -> None:
        gate = self._dispatch_gate
        if gate is None:
            return super()._run_chunk6(batch6_np)
        import jax

        with gate:
            super()._run_chunk6(batch6_np)
            jax.block_until_ready(self.state)

    # -- overridden window lifecycle --------------------------------------
    def _begin_window(self) -> None:
        if not self._seeded:
            self._seeded = True
            # joining at the merge frontier (scale-out, rejoin): the
            # first local window takes the supervisor-assigned id so
            # merged window ids stay globally consistent
            if self.win_id < self._start_window:
                self.win_id = self._start_window
        super()._begin_window()

    def _restore_ring(self) -> None:
        # a host worker has no on-disk ring (rank 0 owns the merged-ring
        # checkpoint); "resume" here means REJOIN — replay the local WAL
        # tail past the last seq the merge plane already published
        self._wal_resume_seq = self._given_wal_seq

    def _window_meta(self, *, partial: bool) -> dict:
        meta = super()._window_meta(partial=partial)
        meta["host"] = self.rank
        if self._retiring:
            # the retirement drain closed the listeners on purpose: that
            # is not lost traffic, so the listener-death reasons come
            # off; genuine drops (queue overflow before the drain) stay
            meta["retired"] = True
            inc = meta.get("incomplete")
            if inc:
                inc["reasons"] = [
                    r for r in inc["reasons"]
                    if r not in ("listener_died", "listener_down")
                ]
                if not inc["reasons"]:
                    del meta["incomplete"]
        return meta

    def _emit_epoch(self, ep: WindowEpoch) -> None:
        extra = {
            "rank": self.rank,
            "meta": ep.meta,
            "tracker": _ser_tracker(ep.tracker_tables),
            "quarantine": _ser_quarantine(ep.quarantine),
            # label map only (digest -> full src128 for report
            # rendering): union-merged at rank 0 via setdefault, which
            # cannot affect register counts
            "v6_digests": [
                [int(d), int(s)] for d, s in self._v6_digests.items()
            ],
            "wal_next": int(self._wal_next),
            # the closed window's inclusive WAL low bound (the next
            # window is already open here, so _win_wal_lo has advanced):
            # rank 0 stamps [wal_lo, wal_next) into the dist lineage
            "wal_lo": int(getattr(self, "_prev_win_wal_lo", 0)),
            "degraded": self.degraded_set(),
        }
        payload = pack_epoch_payload(ep.arrays, extra)
        if self._spool is not None:
            try:
                self._spool.append_epoch(payload)
            except (AnalysisError, OSError) as e:
                # full/readonly spool volume: the epoch still SHIPS (the
                # live merge is unaffected) — only failover durability
                # degrades, and /health says so
                self._degrade("spool", e)
                obs.instant("serve.host.spool_fail", args={
                    "host": self.rank, "window": ep.meta.get("id"),
                })
        if self._ship_backlog:
            # partition mode: epochs must reach the supervisor in window
            # order, so nothing ships until the backlog drains at heal
            self._ship_backlog.append(payload)
            return
        self._ship_or_park(payload)

    def _ship_attempt(self, payload: bytes, kind: bytes = b"E") -> None:
        # chaos site: the ship connection fails (severed merge-plane
        # link / partition analog); the retry seam absorbs a transient
        # burst, exhaustion parks the epoch in the partition backlog.
        # b"F" marks an epoch arriving via the backlog-heal drain so
        # rank 0 can stamp path="backlog_heal" on the window's lineage
        faults.fire("dist.epoch.ship")
        self._emit(kind, payload)

    def _ship_or_park(self, payload: bytes) -> None:
        try:
            retrypolicy.call("dist.epoch.ship", lambda: self._ship_attempt(payload))
        except (AnalysisError, OSError) as e:
            self._ship_backlog.append(payload)
            self._degrade(f"partition:{self.rank}", e)
            obs.instant("serve.host.partition", args={
                "host": self.rank, "backlog": len(self._ship_backlog),
            })

    def _heal_partition(self) -> None:
        """Drain the parked epochs in order (one probe per gauge tick);
        the spool already holds them, so a persistent partition costs
        latency, never data — zero silent drops on heal."""
        while self._ship_backlog:
            try:
                self._ship_attempt(self._ship_backlog[0], kind=b"F")
            except (AnalysisError, OSError):
                return  # still partitioned; next tick probes again
            self._ship_backlog.pop(0)
        self._recover(f"partition:{self.rank}")
        obs.instant("serve.host.partition_heal", args={"host": self.rank})

    def _publish(self, rep_obj: dict, prev: dict | None, meta: dict) -> None:
        # rank 0 owns publication; the worker keeps only the in-memory
        # window map (bounded by the ring) as a debug surface.  The
        # host-tier lineage record still ledgers locally (kind "host",
        # host-<r>/lineage.jsonl): the doctor joins it against rank 0's
        # "dist" records when diagnosing which tier lost a window
        lin = rep_obj.get("totals", {}).get("lineage")
        if lin is not None:
            self._lineage_append(lin)
        with self._pub_lock:
            self._published["report"] = rep_obj
            self._window_reports[meta["id"]] = rep_obj
            live = set(self.ring.window_ids())
            for wid in [w for w in self._window_reports if w not in live]:
                del self._window_reports[wid]

    def _maybe_autoscale(self) -> None:
        super()._maybe_autoscale()  # canonical-signal sampling (no engine)
        if self._kill_req:
            raise AnalysisError(
                f"serve host {self.rank} killed (injected host death)"
            )
        if self._retire_req and not self._retiring:
            self._retiring = True
            obs.instant("serve.host.retire", args={"host": self.rank})
            # stop ingress; the serve loop then drains the queue and
            # exits through its clean all-ingress-closed path, rotating
            # the remainder into one final marked window
            self.listeners.close()
        now = time.monotonic()
        if now >= self._gauge_next:
            self._gauge_next = now + 0.5
            self._emit_gauges()

    def _emit_gauges(self) -> None:
        if self._ship_backlog:
            self._heal_partition()
        gauges = self.metrics_gauges()
        gauges["spool_depth"] = len(self._ship_backlog)
        gauges["spool_seq"] = (
            int(self._spool.next_seq) if self._spool is not None else 0
        )
        try:
            self._emit(b"G", json.dumps({
                "rank": self.rank,
                "gauges": gauges,
                "degraded": self.degraded_set(),
                "addresses": self.listeners.addresses(),
            }).encode("utf-8"))
        except OSError:
            pass  # gauge frames are advisory; epochs have the
            # retry/backlog plane, and the supervisor's monitor
            # owns death detection

    def run(self) -> dict:
        try:
            summary = super().run()
            self._drain_backlog_final()
            summary["degraded"] = self.degraded_set()
            return summary
        finally:
            if self._spool is not None:
                self._spool.close()  # fsync: the tail survives a crash

    def _drain_backlog_final(self) -> None:
        """Clean-finish barrier: a parked epoch must not die with its
        producer when the partition is healable — keep probing until
        the backlog drains or a stop tears the host down.  A stop
        during a persistent partition is NOT a drop: the spool holds
        every parked epoch durably for the elected successor's replay.
        """
        while self._ship_backlog and not self._ext_stop.is_set():
            # the gauge frame keeps the drain observable (spool_depth,
            # partition marker) AND probes the heal path each tick
            self._emit_gauges()
            if self._ship_backlog:
                self._ext_stop.wait(0.5)  # still partitioned; re-probe


# ---------------------------------------------------------------------------
# Process-mode worker entry (multiprocessing spawn target).
# ---------------------------------------------------------------------------


def _worker_entry(spec_json: str) -> None:
    """Spawn target: rebuild configs, connect to rank 0, run the host.

    Flight-recorder inheritance mirrors the RA_TRACE_DIR discipline:
    the supervisor arms with ``export_env=True`` (publishing
    RA_BLACKBOX_DIR), and the worker arms FROM the environment with
    ``export_env=False`` — its shard lands in the same directory for
    the doctor's cross-host postmortem merge without stealing run
    ownership or pruning live sibling shards.
    """
    spec = json.loads(spec_json)
    rank = int(spec["rank"])
    bb = os.environ.get(flightrec.ENV_VAR, "")
    if bb:
        flightrec.arm(bb, role=f"serve-host{rank}", export_env=False)
    cfg = AnalysisConfig.from_dict(spec["cfg"])
    sdict = dict(spec["scfg"])
    sdict["listen"] = tuple(sdict.get("listen", ()))
    sdict["views"] = tuple(sdict.get("views", ()))
    scfg = ServeConfig(**sdict)
    host, _, port = spec["merge_addr"].rpartition(":")
    conn = socket.create_connection((host, int(port)), timeout=30.0)
    conn.settimeout(None)
    send_lock = threading.Lock()

    def emit(kind: bytes, body: bytes) -> None:
        with send_lock:
            _send_frame(conn, kind, body)

    drv = HostServeDriver(
        rank, emit, spec["prefix"], cfg, scfg,
        topk=int(spec["topk"]),
        start_window=int(spec["start_window"]),
        wal_resume_seq=int(spec["wal_resume_seq"]),
        spool_dir=spec.get("spool_dir", ""),
        spool_budget_mb=int(spec.get("spool_budget_mb", 0)),
        spool_resume=bool(spec.get("spool_resume", False)),
    )

    def control_reader() -> None:
        try:
            while True:
                fr = _recv_frame(conn)
                if fr is None:
                    break
                kind, _body = fr
                if kind == b"R":
                    drv.request_retire()
                elif kind == b"S":
                    drv.stop()
        except (OSError, AnalysisError):
            pass  # supervisor died: the worker stops on its own terms
        drv.stop()

    emit(b"H", json.dumps({"rank": rank, "pid": os.getpid()}).encode())
    threading.Thread(
        target=control_reader, name=f"ra-host{rank}-ctl", daemon=True
    ).start()
    code = 0
    try:
        summary = drv.run()
        emit(b"B", json.dumps({
            "rank": rank, "summary": summary,
            "wal_next": int(drv._wal_next),
        }).encode())
    except BaseException as e:
        try:
            emit(b"B", json.dumps({
                "rank": rank, "error": f"{type(e).__name__}: {e}"[:500],
                "wal_next": int(getattr(drv, "_wal_next", 0)),
            }).encode())
        except OSError:
            pass
        code = 1
    finally:
        try:
            conn.close()
        except OSError:
            pass
    raise SystemExit(code)


# ---------------------------------------------------------------------------
# Rank 0: merge + publication supervisor.
# ---------------------------------------------------------------------------


class _Host:
    """Supervisor-side state of one ingest host (any worker mode)."""

    def __init__(self, rank: int, start_window: int):
        self.rank = rank
        self.start_window = start_window
        self.generation = 0
        self.finished = False  # clean BYE received
        self.dead = False  # unexpected death (SIGKILL, typed abort)
        self.dead_reason = ""
        self.dead_from: int | None = None  # first window id lost to death
        self.dead_until: int | None = None  # respawn rejoin window
        self.retiring = False
        self.stop_sent = False  # stop control delivered to THIS generation
        self.last_wid = -1  # highest window id submitted
        self.final_wid: int | None = None  # last wid at clean finish
        self.wal_recv = 0  # wal_next of the last RECEIVED epoch
        self.wal_ckpt = 0  # wal_next covered by PUBLISHED windows
        self.gauges: dict = {}
        self.degraded: list[str] = []
        self.addresses: dict = {}
        self.proc = None  # multiprocessing handle (process mode)
        self.thread: threading.Thread | None = None  # thread mode
        self.driver: HostServeDriver | None = None  # thread mode
        self.conn: socket.socket | None = None  # process mode
        self.send_lock = threading.Lock()
        self.summary: dict | None = None

    @property
    def live(self) -> bool:
        return not (self.finished or self.dead)


class _DropsQueue:
    """Queue shim so the borrowed cumulative renderer reads the merged
    drop total where the single-host driver reads its listener queue."""

    def __init__(self, drv: "DistServeDriver"):
        self._drv = drv

    def snapshot(self) -> dict:
        return {"dropped": int(self._drv.live_drops)}


class DistServeDriver:
    """Rank 0 of ``serve --distributed``: spawn the per-host ingest
    workers, merge their window epochs in id order under the
    ``_merge_tail`` laws, and own every publication surface.

    Renders through the SAME code paths as the single-host driver —
    ``_publish``, ``_render_merged``, ``_render_cumulative``,
    ``merged_report_obj`` and the HTTP server are borrowed from
    :class:`ServeDriver` unbound — so the published report of a merged
    window is bit-identical to a single-host replay of the union of
    the hosts' delivered lines by construction, not by re-implementation.
    """

    def __init__(
        self,
        ruleset_prefix: str,
        cfg: AnalysisConfig,
        scfg: ServeConfig,
        dscfg: DistServeConfig,
        *,
        topk: int = 10,
        ascfg: AutoscaleConfig | None = None,
    ):
        if cfg.mesh_shape != "hybrid":
            raise AnalysisError(
                "serve --distributed realizes the hybrid DCN x ICI "
                "topology (the host tier IS the dcn axis); pass --mesh "
                "hybrid"
            )
        if scfg.static_analysis:
            raise AnalysisError(
                "serve --distributed does not run the static analyzer "
                "yet (rank 0 holds no device mesh); run `analyze` "
                "offline or serve single-host with --static-analysis"
            )
        if not scfg.listen:
            raise AnalysisError(
                "serve needs at least one --listen spec "
                "(udp:HOST:PORT, tcp:HOST:PORT, or tail:PATH)"
            )
        if dscfg.workers == "process":
            # every spawned host process steps its own registers on the
            # device, and all of them run on this machine
            check_one_chip_per_process(
                dscfg.ladder_max, "serve --dist-workers process"
            )
        self.prefix = ruleset_prefix
        self.cfg = cfg
        self.scfg = scfg
        self.dscfg = dscfg
        self.topk = topk
        self.ascfg = ascfg
        try:
            self.packed = pack_mod.load_packed(ruleset_prefix)
        except OSError as e:
            raise AnalysisError(
                f"cannot read packed ruleset {ruleset_prefix!r}: {e}"
            ) from e
        # the worker cfg is derived ONCE: each host runs a flat local
        # mesh (the hybrid topology's inner ICI axis); the outer dcn
        # axis is realized by the host-tier merge below
        self._worker_cfg = cfg.replace(
            mesh_shape="flat", mesh_dcn=0, resume=False, blackbox_dir=""
        )
        self._fp = (
            ckpt.fingerprint(self.packed, cfg, dscfg.ladder_max, 0)
            + "-distserve"
        )
        # supervisor lease + fencing term (DESIGN §23): 0 until a lease
        # is won; every published artifact, gauge, and checkpoint
        # fingerprint carries it, and losing the lease turns every
        # publication path into a typed SupervisorFenced abort
        self.term = 0
        self._lease: SupervisorLease | None = None
        self._fenced_seen: tuple[int, str] | None = None
        self._sup_kill = False  # chaos seam: abrupt supervisor death
        self.spool_replayed_total = 0  # epochs replayed at takeover
        self.replay_windows_total = 0  # windows published from replay
        self.replay_lag_windows = 0  # frontier lag measured at takeover
        self.replay_refused_total = 0  # corrupt spooled epochs refused
        # merged publication state (mirrors ServeDriver so its unbound
        # render/publish methods run here unchanged)
        self.ring = WindowRing(scfg.ring)
        self.cum_arrays = zero_arrays(self.packed.n_keys, cfg)
        self.cum_tracker = TopKTracker(cfg.sketch.topk_capacity)
        self.cum_quarantine: dict[tuple, int] = {}
        self.cum_incomplete_reasons: list[str] = []
        self.cum_incomplete_windows: list[int] = []
        self._v6_digests: dict[int, int] = {}
        self._static_obj = None  # distributed serve: no static plane
        self.windows_published = 0
        self.total_lines = 0
        self.total_parsed = 0
        self.total_skipped = 0
        self.total_chunks = 0
        self.live_drops = 0  # merged drops published this process
        self.drops_restored = 0  # from the restored checkpoint
        self.reloads = 0  # no hot reload in distributed v1 (DESIGN §22)
        self.lat_cum = LatencyHistogram()  # per-host SLO histograms stay
        self.queue = _DropsQueue(self)     # per-host; shims for borrows
        self._pub_lock = threading.Lock()
        self._published: dict[str, dict] = {}
        self._window_reports: dict[int, dict] = {}
        self._deg_lock = threading.Lock()
        self.degraded: dict[str, str] = {}
        self.degraded_events = 0
        self.recovered_events = 0
        # merge plane
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self.hosts: dict[int, _Host] = {}
        self.next_wid = 0
        self._pending: dict[int, dict[int, tuple[dict, dict]]] = {}
        self._arrival: dict[int, float] = {}
        self._host_wal_restored: dict[int, int] = {}
        self.late_epochs = 0
        self.late_epoch_lines = 0
        self.skipped_windows: list[int] = []
        self.hosts_spawned = 0
        self.hosts_dead_total = 0
        self.hosts_retired_total = 0
        self._stop_req = threading.Event()
        self._old_signals: dict = {}
        self._engine: PolicyEngine | None = None
        self._ladder: list[int] = []
        self._as_next = 0.0
        self._msock: socket.socket | None = None
        self._accept_thread: threading.Thread | None = None
        self._accept_stop = False
        self._t0 = time.time()
        # lineage + SLO + trend state (DESIGN §24): the same shared
        # initializer ServeDriver's ctor calls, so the borrowed
        # _publish finds identical attributes here.  term resets to 0
        # (matching the line above); run() overwrites it at lease win
        self._init_lineage_plane()
        # bind the endpoints HERE, like ServeDriver: a bad --http or
        # --dist-merge-bind port must be the documented clean bind
        # error (exit 2), never a mid-run failure with traffic flowing
        self._http = None
        self._http_thread = None
        if scfg.http != "off":
            host, _, port = scfg.http.rpartition(":")
            self._http = _make_http_server((host, int(port)), self)
        if dscfg.workers == "process":
            host, _, port = dscfg.merge_bind.rpartition(":")
            try:
                self._msock = socket.create_server(
                    (host, int(port)), backlog=16
                )
                self._msock.settimeout(0.5)
            except BaseException:
                if self._http is not None:
                    self._http.server_close()
                raise

    # borrowed single-host surfaces: identical rendering/publication by
    # construction (the bit-identity tentpole), one implementation to
    # audit.  Each reads only attributes this class also maintains.
    published = ServeDriver.published
    window_report = ServeDriver.window_report
    merged_report_obj = ServeDriver.merged_report_obj
    _render_merged = ServeDriver._render_merged
    _render_window_obj = ServeDriver._render_window_obj
    _window_totals = ServeDriver._window_totals
    _attach_static = ServeDriver._attach_static
    _render_cumulative = ServeDriver._render_cumulative
    _publish = ServeDriver._publish
    _write_json = ServeDriver._write_json
    _degrade = ServeDriver._degrade
    _recover = ServeDriver._recover
    degraded_set = ServeDriver.degraded_set
    render_latency_prom = ServeDriver.render_latency_prom
    _init_lineage_plane = ServeDriver._init_lineage_plane
    _lineage_append = ServeDriver._lineage_append
    lineage_record = ServeDriver.lineage_record
    _observe_slo = ServeDriver._observe_slo
    _rule_labels = ServeDriver._rule_labels
    _spill_epoch = ServeDriver._spill_epoch
    range_report_obj = ServeDriver.range_report_obj

    def lineage_tail(self) -> dict:
        """The ``/lineage`` view plus the live leadership snapshot: who
        holds the publication right the records' term stamps refer to."""
        out = ServeDriver.lineage_tail(self)
        if self._lease is not None:
            out["lease"] = self._lease.describe()
        return out

    # -- public control ---------------------------------------------------
    def stop(self) -> None:
        self._stop_req.set()
        with self._cond:
            self._cond.notify_all()

    @property
    def http_address(self) -> tuple[str, int] | None:
        srv = self._http
        return tuple(srv.server_address[:2]) if srv is not None else None

    @property
    def merge_address(self) -> tuple[str, int] | None:
        s = self._msock
        return tuple(s.getsockname()[:2]) if s is not None else None

    def live_hosts(self) -> list[int]:
        with self._lock:
            return sorted(r for r, h in self.hosts.items() if h.live)

    def kill_host(self, rank: int) -> None:
        """Chaos surface: abrupt whole-host death (tests + drills).

        Thread mode injects a crash into the worker loop; process mode
        SIGKILLs the worker process — either way the supervisor's death
        path (typed incomplete windows naming the host, degraded
        service, optional respawn) is what's being exercised.
        """
        with self._lock:
            h = self.hosts.get(rank)
        if h is None:
            raise AnalysisError(f"no such serve host: {rank}")
        if h.driver is not None:
            h.driver.kill()
        elif h.proc is not None:
            h.proc.kill()

    def kill_supervisor(self) -> None:
        """Chaos surface: abrupt merge/publication-supervisor death.

        The merge loop raises at its next tick, dying with whatever
        epochs were pending unpublished — exactly what a SIGKILL costs
        (the per-host spools keep them; an elected successor replays).
        """
        self._sup_kill = True
        with self._cond:
            self._cond.notify_all()

    # -- lease / failover --------------------------------------------------
    def _spool_root(self) -> str:
        return self.dscfg.spool_dir or self.scfg.serve_dir

    def _lease_dir(self) -> str:
        return os.path.join(self._spool_root(), "lease")

    def _host_spool_dir(self, rank: int) -> str:
        root = self.dscfg.spool_dir
        if root:
            return os.path.join(root, f"host-{rank}")
        return os.path.join(self.scfg.serve_dir, f"host-{rank}", "spool")

    def _on_lease_fenced(self) -> None:
        """Heartbeat-thread callback: a HIGHER term was observed."""
        if self._lease is not None:
            self._fenced_seen = self._lease.observed()
            obs.instant("lease.fenced", args={
                "term": self.term,
                "winner_term": self._fenced_seen[0],
                "winner": self._fenced_seen[1],
            })
            flightrec.cursor(fenced_by_term=self._fenced_seen[0])
        self.stop()

    def _check_fenced(self) -> None:
        """Raise typed BEFORE any externally visible effect once this
        supervisor may no longer publish (observed a higher term, or its
        own renewals aged past the TTL).  Called on every publication,
        checkpoint, and merge-loop pass — the split-brain half of the
        DESIGN §23 argument (the other half is the lease's 1.5x steal
        margin)."""
        L = self._lease
        if L is None or not L.fenced:
            return
        t, h = self._fenced_seen or L.observed()
        raise SupervisorFenced(
            f"stale supervisor fenced: this process held term {self.term} "
            f"but term {t} is now held by {h!r} (or renewals aged past the "
            f"{L.ttl:.1f}s TTL); publishing would risk two publications "
            "for one window id — the successor replays the epoch spools "
            "and publishes bit-identically instead"
        )

    def failover_gauges(self) -> dict:
        """Leader/lease/replay gauges — merged into ``metrics_gauges``
        so the JSON /metrics block and the prom families carry the SAME
        values (audit_distserve parity)."""
        L = self._lease
        return {
            "leader_term": self.term,
            "lease_age_sec": round(L.age(), 3) if L is not None else 0.0,
            "lease_fenced": int(L.fenced) if L is not None else 0,
            "spool_replayed_total": self.spool_replayed_total,
            "replay_windows_total": self.replay_windows_total,
            "replay_lag_windows": self.replay_lag_windows,
        }

    # -- health / metrics -------------------------------------------------
    def health(self) -> dict:
        with self._lock:
            hosts = {
                str(r): {
                    "live": h.live,
                    "finished": h.finished,
                    "dead": h.dead,
                    **({"dead_reason": h.dead_reason} if h.dead else {}),
                    "retiring": h.retiring,
                    "generation": h.generation,
                    "start_window": h.start_window,
                    "last_window": h.last_wid,
                    "degraded": list(h.degraded),
                    "addresses": h.addresses,
                }
                for r, h in sorted(self.hosts.items())
            }
            dead = sorted(r for r, h in self.hosts.items() if h.dead)
            live = sum(1 for h in self.hosts.values() if h.live)
            pending = len(self._pending)
        with self._pub_lock:
            ring_windows = self.ring.window_ids()
            quarantine_hits = int(sum(self.cum_quarantine.values()))
        deg = self.degraded_set()
        host_deg = sorted({
            f"host{r}:{s}"
            for r, h in self.hosts.items() for s in h.degraded
        })
        degraded = bool(dead or deg or host_deg or self.live_drops)
        return {
            "status": "degraded" if degraded else "ok",
            "distributed": True,
            "term": self.term,
            "degraded_subsystems": deg + host_deg,
            "degraded_events": self.degraded_events,
            "recovered_events": self.recovered_events,
            "uptime_sec": round(time.time() - self._t0, 3),
            "windows_published": self.windows_published,
            "next_window": self.next_wid,
            "merge_pending_windows": pending,
            "lines_total": self.total_lines,
            "drops_total": self.live_drops + self.drops_restored,
            "late_epochs": self.late_epochs,
            "skipped_windows": list(self.skipped_windows),
            "hosts": hosts,
            "hosts_live": live,
            "dead_hosts": dead,
            "world": live,
            "ruleset": {
                "n_rules": self.packed.n_rules,
                "n_acls": self.packed.n_acls,
                "n_keys": self.packed.n_keys,
            },
            "window": {
                "mode": "lines" if self.scfg.window_lines else "sec",
                "length": self.scfg.window_lines or self.scfg.window_sec,
                "ring": self.scfg.ring,
                "ring_windows": ring_windows,
            },
            "quarantine_hits": quarantine_hits,
            **(
                {"autoscale": self._engine.summary()}
                if self._engine is not None
                else {}
            ),
        }

    def host_gauges(self) -> dict[str, dict]:
        """Per-host flat gauge blocks, host rank as the label value.

        ONE source of truth for the JSON ``/metrics`` ``hosts`` block
        AND the labeled Prometheus families on ``format=prom`` — the
        parity verify/registry.py::audit_distserve pins.
        """
        with self._lock:
            out = {}
            for r, h in sorted(self.hosts.items()):
                out[str(r)] = {
                    **h.gauges,
                    "live": int(h.live),
                    "dead": int(h.dead),
                    "degraded_subsystems": len(h.degraded),
                    "generation": h.generation,
                    "last_window": h.last_wid,
                }
            return out

    def metrics_gauges(self) -> dict:
        with self._lock:
            live = sum(1 for h in self.hosts.values() if h.live)
            pending = len(self._pending)
            rate = sum(
                h.gauges.get("lines_per_sec", 0.0)
                for h in self.hosts.values() if h.live
            )
            qdepth = max(
                (h.gauges.get("queue_depth", 0) for h in self.hosts.values()),
                default=0,
            )
        g = {
            "hosts_live": live,
            "hosts_spawned_total": self.hosts_spawned,
            "hosts_dead_total": self.hosts_dead_total,
            "hosts_retired_total": self.hosts_retired_total,
            "windows_published": self.windows_published,
            "next_window": self.next_wid,
            "merge_pending_windows": pending,
            "lines_windowed_total": self.total_lines,
            "drops_total": self.live_drops + self.drops_restored,
            "late_epochs_total": self.late_epochs,
            "late_epoch_lines_total": self.late_epoch_lines,
            "skipped_windows_total": len(self.skipped_windows),
            "lines_per_sec": round(rate, 1),
            "queue_depth_max": qdepth,
            "world": live,
            "degraded_subsystems": len(self.degraded_set()),
            "degraded_events_total": self.degraded_events,
            "recovered_events_total": self.recovered_events,
        }
        if self.scfg.lineage:
            g["lineage_records_total"] = self.lineage_records_total
            g["trend_events_total"] = self.trend_events_total
        if self.epoch_store is not None:
            g.update(self.epoch_store.gauges())
            g.update(self.lat_range.gauges("latency_range_query_"))
        if self._suffix is not None:
            g.update({
                "merged_suffix_hits_total": self._suffix.hits,
                "merged_suffix_misses_total": self._suffix.misses,
            })
        if self.slo is not None:
            g.update(self.slo.gauges())
        g.update(self.failover_gauges())
        g.update(retrypolicy.gauges())
        eng = self._engine
        if eng is not None:
            g.update({
                "autoscale_decisions_total": len(eng.decisions),
                "autoscale_scale_out_total": sum(
                    1 for d in eng.decisions if d.direction == "out"
                ),
                "autoscale_scale_in_total": sum(
                    1 for d in eng.decisions if d.direction == "in"
                ),
                "autoscale_flaps_total": eng.flaps,
                "autoscale_budget_left": eng.budget_left,
            })
        return g

    def _sample_metrics(self) -> dict:
        return {"hosts": self.host_gauges()}

    def build_info_dict(self) -> dict:
        """Static build identity for ``ra_build_info`` (no ``world``
        attribute here: the mesh label carries the host-tier width)."""
        return build_info({
            "mesh": f"{self.cfg.mesh_shape}/{self.dscfg.hosts}",
        })

    def render_labeled_prom(self) -> str:
        """Host-labeled Prometheus families from the SAME per-host gauge
        blocks the JSON ``/metrics`` serves (audit_distserve parity),
        plus the build-info and objective-labeled SLO families every
        serve tier exports."""
        out = render_build_info_prom(self.build_info_dict())
        if self.slo is not None:
            out += render_prom_labeled(
                self.slo.labeled_gauges(),
                prefix="ra_serve_", label="objective",
            )
        return out + render_prom_labeled(
            self.host_gauges(), prefix="ra_serve_host_", label="host"
        )

    # -- run --------------------------------------------------------------
    def run(self) -> dict:
        scfg = self.scfg
        os.makedirs(scfg.serve_dir, exist_ok=True)
        armed_here = faults.arm_spec(self.cfg.fault_plan)
        retrypolicy.configure(self.cfg.retry_policy)
        if self.cfg.blackbox_dir:
            # run OWNER: export RA_BLACKBOX_DIR so spawned host workers
            # shard into the same directory (the doctor merges them)
            flightrec.arm(self.cfg.blackbox_dir, role="serve-sup")
        aborted: BaseException | None = None
        try:
            if self.ascfg is not None:
                self._ladder = host_ladder(
                    self.dscfg.min_hosts, self.dscfg.ladder_max
                )
                if self.dscfg.hosts not in self._ladder:
                    raise AnalysisError(
                        f"--dist-hosts {self.dscfg.hosts} is not on the "
                        f"host ladder {self._ladder}"
                    )
                self._engine = PolicyEngine(
                    self.ascfg, world=self.dscfg.hosts, ladder=self._ladder
                )
            if self.dscfg.lease_ttl_sec > 0:
                ttl = self.dscfg.lease_ttl_sec
                self._lease = SupervisorLease(
                    self._lease_dir(),
                    holder=f"{socket.gethostname()}:pid{os.getpid()}",
                    ttl_sec=ttl,
                )
                t_wait = time.monotonic()
                # blocks until this process wins a term: behind a live
                # incumbent it waits out the 1.5x-TTL staleness window,
                # so the previous holder has provably self-fenced first
                self.term = self._lease.acquire(
                    stop=self._stop_req, timeout=max(30.0, 10 * ttl)
                )
                obs.instant("lease.acquired", args={
                    "term": self.term,
                    "holder": self._lease.holder,
                    "wait_sec": round(time.monotonic() - t_wait, 3),
                })
                flightrec.cursor(term=self.term)
                self._lease.start_heartbeat(on_fenced=self._on_lease_fenced)
            if self.cfg.resume:
                self._restore()
            if scfg.epoch_store:
                # rank 0 spills MERGED windows only (DESIGN §25) —
                # host tiers keep no history; opened before the spool
                # replay so replayed windows land like live ones (the
                # store dedupes ids below its frontier)
                self.epoch_store = epochstore.EpochStore(
                    scfg.epoch_store,
                    budget_bytes=scfg.epoch_store_budget_bytes,
                    trend_threshold=scfg.trend_threshold,
                )
                if not self.cfg.resume:
                    self.epoch_store.reset()
                self.epoch_store.bind_base(self.next_wid)
                self.epoch_store.set_labels(
                    self._rule_labels(self.packed)
                )
            if scfg.lineage:
                # rank 0's provenance ledger (DESIGN §24), opened BEFORE
                # the takeover replay so the successor's replayed
                # windows ledger here like any live publication
                lpath = os.path.join(scfg.serve_dir, LineageLog.NAME)
                if self.cfg.resume:
                    live = set(self.ring.window_ids())
                    for r in LineageLog.read(lpath):
                        if (
                            r.get("kind") != "merged"
                            and r.get("window") in live
                        ):
                            self._lineage_recent[r["window"]] = r
                            self.lineage_records_total += 1
                else:
                    try:
                        os.remove(lpath)
                    except OSError:
                        pass
                self._lineage_log = LineageLog(lpath)
            if self.cfg.resume:
                self._replay_spools()
            obs.register_sampler("distserve", self.metrics_gauges)
            if self._msock is not None:
                self._accept_thread = threading.Thread(
                    target=self._accept_loop, name="ra-distserve-accept",
                    daemon=True,
                )
                self._accept_thread.start()
            self._start_http()
            self._install_signals()
            for r in range(self.dscfg.hosts):
                self._spawn_host(r, rejoin=self.cfg.resume)
            self._write_json("endpoint.json", {
                "pid": os.getpid(),
                "distributed": True,
                "term": self.term,
                "hosts": self.dscfg.hosts,
                "http": list(self.http_address) if self.http_address else None,
                "merge": (
                    list(self.merge_address) if self.merge_address else None
                ),
                "serve_dir": os.path.abspath(scfg.serve_dir),
                "host_dirs": {
                    str(r): os.path.abspath(
                        os.path.join(scfg.serve_dir, f"host-{r}")
                    )
                    for r in range(self.dscfg.hosts)
                },
            })
            self._merge_loop()
        except BaseException as e:
            aborted = e
            raise
        finally:
            try:
                self._teardown(aborted)
            finally:
                if armed_here:
                    faults.disarm()
        with self._lock:
            host_summaries = {
                str(r): {
                    "generation": h.generation,
                    "dead": h.dead,
                    **({"dead_reason": h.dead_reason} if h.dead else {}),
                    "retired": h.retiring,
                    "last_window": h.last_wid,
                    **({"summary": h.summary} if h.summary else {}),
                }
                for r, h in sorted(self.hosts.items())
            }
            dead = sorted(r for r, h in self.hosts.items() if h.dead)
        summary = {
            "distributed": True,
            "term": self.term,
            "failover": {
                "spool_replayed": self.spool_replayed_total,
                "replay_windows": self.replay_windows_total,
                "replay_refused": self.replay_refused_total,
                "lease_renews": (
                    self._lease.renews if self._lease is not None else 0
                ),
            },
            "hosts": host_summaries,
            "hosts_spawned": self.hosts_spawned,
            "dead_hosts": dead,
            "hosts_retired": self.hosts_retired_total,
            "windows_published": self.windows_published,
            "lines_total": self.total_lines,
            "drops": self.live_drops + self.drops_restored,
            "late_epochs": self.late_epochs,
            "skipped_windows": list(self.skipped_windows),
            "quarantine_hits": int(sum(self.cum_quarantine.values())),
            "serve_dir": os.path.abspath(scfg.serve_dir),
            "world": self.dscfg.hosts,
            "degraded": self.degraded_set(),
            "retry": retrypolicy.counters(),
            **(
                {"epoch_store": self.epoch_store.stats()}
                if self.epoch_store is not None
                else {}
            ),
            **(
                {"autoscale": self._engine.summary()}
                if self._engine is not None
                else {}
            ),
        }
        self._write_json("summary.json", summary)
        return summary

    # -- worker lifecycle -------------------------------------------------
    def _spawn_host(self, rank: int, *, rejoin: bool) -> None:
        scfg = self.scfg
        host_dir = os.path.join(scfg.serve_dir, f"host-{rank}")
        wscfg = dataclasses.replace(
            scfg,
            listen=tuple(
                offset_listen_spec(s, rank) for s in scfg.listen
            ),
            http="off",
            serve_dir=host_dir,
            checkpoint_every_windows=0,
            checkpoint_dir="",
            reload_watch=False,
            views=(),
            wal_dir=os.path.join(host_dir, "wal") if scfg.wal else "",
            # burn-rate alerting runs at rank 0 over the MERGED windows;
            # per-host engines would double-fire every breach event
            slo="",
        )
        with self._lock:
            h = self.hosts.get(rank)
            if h is None:
                h = self.hosts[rank] = _Host(rank, self.next_wid)
            else:
                # respawn/rejoin: same rank, fresh generation, joining
                # at the merge frontier past its predecessor's last
                # submitted window
                h.generation += 1
                h.start_window = max(self.next_wid, h.last_wid + 1)
                h.finished = False
                h.dead = False
                h.dead_until = h.start_window
                h.retiring = False
                h.stop_sent = False
                # the replacement binds its own (ephemeral) ports; the
                # predecessor's addresses must not be served meanwhile
                h.addresses = {}
                h.gauges = {}
                h.conn = None
                h.driver = None
                h.proc = None
            start_window = h.start_window
            wal_seq = (
                max(h.wal_recv, self._host_wal_restored.get(rank, 0))
                if rejoin else 0
            )
            self.hosts_spawned += 1
        wcfg = self._worker_cfg.replace(resume=bool(rejoin and scfg.wal))
        spool_dir = (
            self._host_spool_dir(rank)
            if self.dscfg.spool_budget_mb > 0 else ""
        )
        obs.instant("serve.host.spawn", args={
            "host": rank, "rejoin": bool(rejoin),
            "start_window": start_window, "wal_seq": wal_seq,
        })
        if self.dscfg.workers == "thread":
            drv = HostServeDriver(
                rank,
                lambda kind, body, _r=rank: self._on_frame(_r, kind, body),
                self.prefix, wcfg, wscfg,
                topk=self.topk, start_window=start_window,
                wal_resume_seq=wal_seq, serialize_dispatch=True,
                spool_dir=spool_dir,
                spool_budget_mb=self.dscfg.spool_budget_mb,
                spool_resume=rejoin,
            )

            def runner(_r=rank, _drv=drv):
                try:
                    s = _drv.run()
                    self._on_frame(_r, b"B", json.dumps({
                        "rank": _r, "summary": s,
                        "wal_next": int(_drv._wal_next),
                    }).encode())
                except BaseException as e:
                    self._on_frame(_r, b"B", json.dumps({
                        "rank": _r,
                        "error": f"{type(e).__name__}: {e}"[:500],
                        "wal_next": int(getattr(_drv, "_wal_next", 0)),
                    }).encode())

            th = threading.Thread(
                target=runner, name=f"ra-serve-host{rank}", daemon=True
            )
            with self._lock:
                h.driver = drv
                h.thread = th
            th.start()
            return
        import multiprocessing as mp

        addr = self.merge_address
        spec = json.dumps({
            "rank": rank,
            "prefix": self.prefix,
            "cfg": wcfg.to_dict(),
            "scfg": dataclasses.asdict(wscfg),
            "topk": self.topk,
            "merge_addr": f"{addr[0]}:{addr[1]}",
            "start_window": start_window,
            "wal_resume_seq": wal_seq,
            "spool_dir": spool_dir,
            "spool_budget_mb": self.dscfg.spool_budget_mb,
            "spool_resume": bool(rejoin),
        })
        p = mp.get_context("spawn").Process(
            target=_worker_entry, args=(spec,),
            name=f"ra-serve-host{rank}", daemon=True,
        )
        p.start()
        with self._lock:
            h.proc = p

    def _send_control(self, h: _Host, kind: bytes) -> None:
        if h.driver is not None:
            if kind == b"R":
                h.driver.request_retire()
            elif kind == b"S":
                h.driver.stop()
            return
        if h.conn is not None:
            try:
                with h.send_lock:
                    _send_frame(h.conn, kind, b"")
            except OSError:
                pass  # death handled by the monitor/reader paths

    # -- frame dispatch (worker threads / conn readers) --------------------
    def _on_frame(self, rank: int, kind: bytes, body: bytes) -> None:
        if kind in (b"E", b"F"):
            arrays, extra = unpack_epoch_payload(body)
            # provenance stamps (DESIGN §24): the CRC is over the exact
            # shipped payload bytes — the spool holds those same bytes,
            # so a failover successor's replayed record carries the
            # identical crc (the replay-identity law, pinned in tests).
            # b"F" marks arrival via the partition backlog-heal drain
            extra["payload_crc"] = zlib.crc32(body) & 0xFFFFFFFF
            extra["healed"] = kind == b"F"
            wid = int(extra["meta"]["id"])
            with self._cond:
                h = self.hosts[rank]
                h.last_wid = max(h.last_wid, wid)
                h.wal_recv = max(h.wal_recv, int(extra.get("wal_next", 0)))
                if wid < self.next_wid:
                    # the window already published without this host
                    # (death/timeout marking named it): merging now
                    # would double-publish — drop with explicit
                    # accounting, never silently
                    self.late_epochs += 1
                    self.late_epoch_lines += int(extra["meta"].get("lines", 0))
                    obs.instant("serve.host.late_epoch", args={
                        "host": rank, "window": wid,
                        "lines": int(extra["meta"].get("lines", 0)),
                    })
                else:
                    self._pending.setdefault(wid, {})[rank] = (arrays, extra)
                    self._arrival.setdefault(wid, time.monotonic())
                self._cond.notify_all()
        elif kind == b"G":
            j = json.loads(body)
            with self._lock:
                h = self.hosts[rank]
                h.gauges = j.get("gauges", {})
                h.degraded = list(j.get("degraded", []))
                h.addresses = j.get("addresses", h.addresses)
        elif kind == b"B":
            j = json.loads(body)
            with self._cond:
                h = self.hosts[rank]
                h.wal_recv = max(h.wal_recv, int(j.get("wal_next", 0)))
                if "error" in j:
                    self._mark_dead_locked(h, j["error"])
                else:
                    h.finished = True
                    h.final_wid = h.last_wid
                    h.summary = j.get("summary")
                    if h.retiring:
                        self.hosts_retired_total += 1
                self._cond.notify_all()
        elif kind == b"H":
            pass  # liveness signal; conn binding happens in _conn_reader

    # -- death plane ------------------------------------------------------
    def _mark_dead_locked(self, h: _Host, reason: str) -> None:
        if h.dead or h.finished:
            return
        h.dead = True
        h.dead_reason = reason[:300]
        h.dead_from = max(self.next_wid, h.last_wid + 1)
        h.dead_until = None
        self.hosts_dead_total += 1
        obs.instant("serve.host.died", args={
            "host": h.rank, "reason": h.dead_reason,
        })
        flightrec.cursor(dead_hosts=sorted(
            r for r, hh in self.hosts.items() if hh.dead
        ))
        obs.metric_event(
            "distserve.host.died", host=h.rank, reason=h.dead_reason
        )
        self._degrade(f"host{h.rank}", reason)

    def mark_host_dead(self, rank: int, reason: str) -> None:
        with self._cond:
            self._mark_dead_locked(self.hosts[rank], reason)
            self._cond.notify_all()

    def _check_workers(self) -> None:
        respawn: list[int] = []
        with self._cond:
            for r, h in self.hosts.items():
                if h.live:
                    if h.proc is not None and not h.proc.is_alive():
                        self._mark_dead_locked(
                            h, "process exited (code "
                               f"{h.proc.exitcode}) without bye"
                        )
                    elif h.thread is not None and not h.thread.is_alive():
                        self._mark_dead_locked(h, "worker thread died")
                if (
                    h.dead
                    and h.dead_until is None
                    and self.dscfg.respawn
                    and not self._stop_req.is_set()
                ):
                    respawn.append(r)
            self._cond.notify_all()
        for r in respawn:
            self._spawn_host(r, rejoin=True)

    # -- merge + publication ----------------------------------------------
    def _expected(self, w: int) -> list[int]:
        """Hosts whose epoch for window ``w`` is still owed (lock held)."""
        out = []
        for r, h in self.hosts.items():
            if h.start_window > w or h.last_wid >= w:
                continue
            if h.finished or h.dead:
                continue
            out.append(r)
        return out

    def _dead_at(self, w: int) -> list[int]:
        """Hosts whose death swallowed window ``w`` (lock held)."""
        out = []
        for r, h in self.hosts.items():
            if h.dead_from is None or h.dead_from > w:
                continue
            if h.dead_until is not None and w >= h.dead_until:
                continue
            out.append(r)
        return out

    def _drain_publishable(self) -> None:
        while True:
            with self._lock:
                w = self.next_wid
                # a window no surviving host ever reached cannot publish:
                # skip it explicitly (accounted in /health + summary),
                # never hang the frontier behind it
                while (
                    self._pending
                    and w < min(self._pending)
                    and not self._expected(w)
                ):
                    self.skipped_windows.append(w)
                    obs.instant("serve.window.skipped", args={"window": w})
                    self.next_wid = w = w + 1
                recs = self._pending.get(w)
                if not recs:
                    break
                waiting = self._expected(w)
                timed_out = (
                    waiting
                    and time.monotonic() - self._arrival.get(w, 0.0)
                    > self.dscfg.merge_timeout_sec
                )
                alldone = all(
                    not h.live for h in self.hosts.values()
                )
                if waiting and not timed_out and not alldone:
                    break
                recs = self._pending.pop(w)
                self._arrival.pop(w, None)
                dead = [r for r in self._dead_at(w) if r not in recs]
                missing = [
                    r for r in waiting if r not in recs and r not in dead
                ]
                self.next_wid = w + 1
            self._publish_window(w, recs, dead, missing)

    def _publish_window(
        self,
        w: int,
        recs: dict[int, tuple[dict, dict]],
        dead: list[int],
        missing: list[int],
        *,
        path: str = "live",
    ) -> None:
        self._check_fenced()  # a stale supervisor must never publish
        ranks = sorted(recs)
        with obs.span("distserve.merge", window=w, hosts=len(ranks)):
            arrays = merge_register_arrays([recs[r][0] for r in ranks])
            # candidate-table merge law: the hosts saw DISJOINT slices
            # of the same window, so a source's per-host estimates ADD
            # (the CMS add law lifted to the candidate tables) — unlike
            # cross-WINDOW merges (cum_tracker, merged views), where
            # re-offering the same window's table must stay max/idempotent.
            # Summing is what keeps the merged talkers section
            # bit-identical to a single-host replay of the union.
            cand: dict[int, dict[int, int]] = {}
            quarantine: dict[tuple, int] = {}
            per_host: dict[str, dict] = {}
            reasons: list[str] = []
            partial = False
            lines = parsed = skipped = chunks = drops = 0
            started = ended = None
            elapsed = 0.0
            for r in ranks:
                _arr, extra = recs[r]
                meta = extra["meta"]
                per_host[str(r)] = meta
                lines += int(meta.get("lines", 0))
                parsed += int(meta.get("parsed", 0))
                skipped += int(meta.get("skipped", 0))
                chunks += int(meta.get("chunks", 0))
                drops += int(meta.get("drops", 0))
                partial = partial or bool(meta.get("partial"))
                elapsed = max(elapsed, float(meta.get("elapsed_sec", 0.0)))
                su, eu = meta.get("started_unix"), meta.get("ended_unix")
                started = su if started is None else min(started, su)
                ended = eu if ended is None else max(ended, eu)
                for reason in (meta.get("incomplete") or {}).get(
                    "reasons", []
                ):
                    if reason not in reasons:
                        reasons.append(reason)
                for acl, table in extra.get("tracker", []):
                    t = cand.setdefault(int(acl), {})
                    for src, est in table:
                        t[int(src)] = t.get(int(src), 0) + int(est)
                _merge_quarantine(
                    quarantine, _de_quarantine(extra.get("quarantine", []))
                )
                for d, s in extra.get("v6_digests", []):
                    self._v6_digests.setdefault(int(d), int(s))
            tracker = TopKTracker(self.cfg.sketch.topk_capacity)
            for acl in sorted(cand):
                # canonical offer order (estimate desc, source asc):
                # capacity eviction keeps the heaviest merged talkers
                # regardless of which host shipped its table first
                for src, est in sorted(
                    cand[acl].items(), key=lambda kv: (-kv[1], kv[0])
                ):
                    tracker.offer(acl, src, est)
            for r in sorted(dead):
                reasons.append(f"host_died:{r}")
            for r in sorted(missing):
                reasons.append(f"host_missing:{r}")
            meta = {
                "id": w,
                "term": self.term,  # which leadership published this
                "mode": "lines" if self.scfg.window_lines else "sec",
                "length": self.scfg.window_lines or self.scfg.window_sec,
                "lines": lines,
                "parsed": parsed,
                "skipped": skipped,
                "chunks": chunks,
                "drops": drops,
                "reloads": 0,
                "started_unix": started if started is not None else 0.0,
                "ended_unix": ended if ended is not None else 0.0,
                "elapsed_sec": round(elapsed, 4),
                "hosts": per_host,
                "merged_hosts": ranks,
            }
            if partial:
                meta["partial"] = True
            if reasons:
                meta["incomplete"] = {
                    "drops": drops,
                    "reasons": reasons,
                    **({"dead_hosts": sorted(dead)} if dead else {}),
                    **({"missing_hosts": sorted(missing)} if missing else {}),
                }
            ep = WindowEpoch(
                arrays=arrays,
                meta=meta,
                tracker_tables=tracker.tables(),
                quarantine=quarantine,
            )
            rep = pipeline.finalize(
                pipeline.AnalysisState(**arrays), self.packed, self.cfg,
                tracker, topk=self.topk,
                totals=self._window_totals(meta, quarantine),
                v6_digests=self._v6_digests,
            )
            rep_obj = json.loads(rep.to_json())
            if self.scfg.lineage:
                # the merged window's provenance (DESIGN §24): one entry
                # per contributing host with its delivered WAL range and
                # the crc of the exact epoch payload it shipped.  All of
                # it is a deterministic function of the delivered epochs
                # — only term/path/published_unix/crc (LINEAGE_VOLATILE)
                # may differ between a live publish and a failover
                # successor's replay of the same spooled bytes
                eff_path = path
                if eff_path == "live" and any(
                    recs[r][1].get("healed") for r in ranks
                ):
                    eff_path = "backlog_heal"
                lrec: dict = {
                    "window": w,
                    "kind": "dist",
                    "hosts": [{
                        "rank": int(r),
                        "wal_seq_lo": int(recs[r][1].get("wal_lo", 0)),
                        "wal_seq_hi": int(recs[r][1].get("wal_next", 0)),
                        "drops": int(
                            recs[r][1]["meta"].get("drops", 0)
                        ),
                        "quarantine_hits": int(sum(
                            int(row[-1])
                            for row in recs[r][1].get("quarantine", [])
                        )),
                        "payload_crc": int(
                            recs[r][1].get("payload_crc", 0)
                        ),
                    } for r in ranks],
                    "generation": int(self.reloads),
                    "term": int(self.term),
                    "path": eff_path,
                    "published_unix": round(time.time(), 3),
                }
                if dead:
                    lrec["dead_hosts"] = sorted(dead)
                if missing:
                    lrec["missing_hosts"] = sorted(missing)
                if meta.get("incomplete"):
                    lrec["incomplete"] = meta["incomplete"]
                rep_obj["totals"]["lineage"] = seal_lineage(lrec)
                # merged-K records sealed inside the borrowed _publish
                # carry the same path stamp
                self._path = eff_path
            if meta.get("incomplete"):
                self.cum_incomplete_windows.append(w)
                for r in meta["incomplete"]["reasons"]:
                    if r not in self.cum_incomplete_reasons:
                        self.cum_incomplete_reasons.append(r)
            with self._pub_lock:
                self.ring.push(ep)
                prev = self._published.get("report")
                _merge_quarantine(self.cum_quarantine, quarantine)
            self.cum_arrays = merge_register_arrays(
                [self.cum_arrays, arrays]
            )
            for acl, table in ep.tracker_tables.items():
                for src, est in table.items():
                    self.cum_tracker.offer(int(acl), int(src), int(est))
            self.total_lines += lines
            self.total_parsed += parsed
            self.total_skipped += skipped
            self.total_chunks += chunks
            self.live_drops += drops
            self.windows_published += 1
            with self._lock:
                for r in ranks:
                    h = self.hosts.get(r)
                    if h is not None:
                        h.wal_ckpt = max(
                            h.wal_ckpt,
                            int(recs[r][1].get("wal_next", 0)),
                        )
            # durable history spills the MERGED epoch (post cross-host
            # register merge) so range queries see exactly what /report
            # published, not any single host's shard
            self._spill_epoch(ep)
            if self._suffix is not None:
                self._suffix.push(w, arrays)
            flightrec.cursor(
                windows_published=self.windows_published,
                next_window=self.next_wid,
            )
            obs.metric_event(
                "distserve.window", id=w, hosts=len(ranks), lines=lines,
                drops=drops, dead=len(dead), missing=len(missing),
            )
            self._publish(rep_obj, prev, meta)
            self._path = "live"
            # burn-rate engine over the MERGED windows (rank 0 has no
            # per-window ingest->publish histogram, so latency
            # objectives are host-tier concerns; drop/incomplete/
            # degraded objectives burn here)
            self._observe_slo(meta)
            if (
                self.scfg.checkpoint_every_windows
                and self.windows_published
                % self.scfg.checkpoint_every_windows == 0
            ):
                self._save_ckpt()

    # -- the supervisor loop ----------------------------------------------
    def _merge_loop(self) -> None:
        while True:
            with self._cond:
                self._cond.wait(timeout=0.2)
            if self._sup_kill:
                raise AnalysisError(
                    "distserve supervisor killed (injected supervisor "
                    "death); pending epochs stay in the host spools for "
                    "the elected successor to replay"
                )
            self._check_fenced()
            self._check_workers()
            self._maybe_autoscale()
            if self._stop_req.is_set():
                # per-host (and per-generation) delivery, retried every
                # pass: a worker that comes up AFTER the stop request —
                # a respawn racing max_windows, an autoscale spawn — has
                # no channel yet when the request lands, and a one-shot
                # broadcast would leave it running forever (alldone
                # never true = supervisor hang)
                with self._lock:
                    pend = [
                        h for h in self.hosts.values()
                        if h.live and not h.stop_sent
                        and (h.driver is not None or h.conn is not None)
                    ]
                    for h in pend:
                        h.stop_sent = True
                for h in pend:
                    self._send_control(h, b"S")
            self._drain_publishable()
            if (
                self.scfg.max_windows
                and self.windows_published >= self.scfg.max_windows
                and not self._stop_req.is_set()
            ):
                # --max-windows is a SERVICE budget: it counts merged
                # published windows, exactly like the single-host
                # driver counts its own.  Workers inherit the budget as
                # a local backstop, but a host that joined at the merge
                # frontier (respawn, scale-out) publishes fewer LOCAL
                # windows than the service total and would never
                # self-stop — rank 0 must stop the world, or alldone
                # never comes
                self._stop_req.set()
            with self._lock:
                alldone = all(not h.live for h in self.hosts.values())
                empty = not self._pending
            if alldone:
                if not empty:
                    continue  # next pass publishes the tail
                break

    def _maybe_autoscale(self) -> None:
        eng = self._engine
        if eng is None:
            return
        now = time.monotonic()
        if now < self._as_next:
            return
        self._as_next = now + self.ascfg.poll_sec
        with self._lock:
            live = [h for h in self.hosts.values() if h.live]
            if not live or any(not h.gauges for h in live):
                return  # no full signal yet
            pressure = max(
                h.gauges.get("queue_depth", 0)
                / max(h.gauges.get("queue_capacity", 1), 1)
                for h in live
            )
            starvation = min(
                float(h.gauges.get("starved_frac", 0.0)) for h in live
            )
            world = len(live)
        if world in eng.ladder:
            # resync the rung to reality (a death can shrink the live
            # set under the engine); below the ladder floor the engine
            # keeps its last rung — respawn, not policy, owns recovery
            eng.world = world
        dec = eng.observe(
            now=now, pressure=pressure, starvation=starvation,
            gauges={"hosts_live": world, "pressure": round(pressure, 4)},
        )
        if dec is None or not dec.actuate:
            return
        with obs.span(
            "distserve.autoscale.apply", seq=dec.seq,
            direction=dec.direction, from_world=dec.from_world,
            to_world=dec.to_world,
        ):
            faults.fire("autoscale.spawn")
            if dec.direction == "out":
                with self._lock:
                    rank = max(self.hosts) + 1 if self.hosts else 0
                self._spawn_host(rank, rejoin=False)
            else:
                with self._lock:
                    live = sorted(
                        (r for r, h in self.hosts.items()
                         if h.live and not h.retiring),
                        reverse=True,
                    )
                    target = self.hosts[live[0]] if live else None
                    if target is not None:
                        target.retiring = True
                if target is not None:
                    self._send_control(target, b"R")
        eng.applied(dec, now=time.monotonic())
        obs.metric_event(
            "distserve.autoscale.applied", seq=dec.seq,
            direction=dec.direction, world=dec.to_world,
        )

    # -- process-mode merge server ----------------------------------------
    def _accept_loop(self) -> None:
        while not self._accept_stop:
            try:
                conn, _ = self._msock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(
                target=self._conn_reader, args=(conn,),
                name="ra-distserve-conn", daemon=True,
            ).start()

    def _conn_reader(self, conn: socket.socket) -> None:
        rank: int | None = None
        try:
            while True:
                fr = _recv_frame(conn)
                if fr is None:
                    break
                kind, body = fr
                if kind == b"H":
                    j = json.loads(body)
                    rank = int(j["rank"])
                    with self._lock:
                        h = self.hosts.get(rank)
                        if h is not None:
                            h.conn = conn
                    continue
                if rank is None:
                    raise AnalysisError(
                        "host-tier frame before hello; dropping connection"
                    )
                self._on_frame(rank, kind, body)
        except (OSError, AnalysisError, ValueError, KeyError) as e:
            if rank is not None:
                self.mark_host_dead(rank, f"merge connection error: {e}")
        finally:
            # EOF without a bye is a death signal in its own right (the
            # process monitor confirms with the exit code)
            if rank is not None:
                with self._cond:
                    h = self.hosts.get(rank)
                    if h is not None and h.live and h.conn is conn:
                        self._mark_dead_locked(
                            h, "merge connection closed without bye"
                        )
                        self._cond.notify_all()
            try:
                conn.close()
            except OSError:
                pass

    # -- checkpoint (rank-0 merged ring; ladder-max fingerprint) -----------
    def _save_ckpt(self) -> None:
        self._check_fenced()  # a fenced snapshot could roll back the
        # successor's frontier — refuse it like any other publication
        arrays: dict[str, np.ndarray] = {}
        wmeta = []
        for ep in self.ring.epochs:
            pfx = f"w{ep.meta['id']:06d}__"
            for k, v in ep.arrays.items():
                arrays[pfx + k] = v
            wmeta.append({
                "meta": ep.meta,
                "tracker": _ser_tracker(ep.tracker_tables),
                "quarantine": _ser_quarantine(ep.quarantine),
            })
        for k, v in self.cum_arrays.items():
            arrays["cum__" + k] = v
        with self._lock:
            host_wal = {
                str(r): int(h.wal_ckpt) for r, h in self.hosts.items()
            }
        snap = ckpt.Snapshot(
            arrays=arrays,
            lines_consumed=self.total_lines,
            n_chunks=self.total_chunks,
            parsed=self.total_parsed,
            skipped=self.total_skipped,
            tracker_tables=self.cum_tracker.tables(),
            # the fencing term rides the fingerprint as a -t<term>
            # suffix (ckpt.split_fence peels it): a restore that finds
            # a HIGHER term than its own lease proves a successor
            # already ran — SupervisorFenced, not a resume
            fingerprint=ckpt.fence_fingerprint(self._fp, self.term),
            extra={
                "serve": {
                    "next_window": self.next_wid,
                    "windows_published": self.windows_published,
                    "windows": wmeta,
                    "reloads": self.reloads,
                    "quarantine": _ser_quarantine(self.cum_quarantine),
                    "v6_digests": [
                        [int(d), int(s)]
                        for d, s in self._v6_digests.items()
                    ],
                    "incomplete_reasons": list(
                        self.cum_incomplete_reasons
                    ),
                    "incomplete_windows": list(
                        self.cum_incomplete_windows
                    ),
                    "drops": self.drops_restored + self.live_drops,
                    "wal_seq": 0,
                    "wal_lost": 0,
                },
                # per-host WAL cursors COVERED BY PUBLISHED WINDOWS
                # (not merely received: a pending-but-unpublished epoch
                # dies with this process, and its lines must replay)
                "distserve": {
                    "host_wal": host_wal,
                    "skipped_windows": list(self.skipped_windows),
                    "late_epochs": self.late_epochs,
                },
            },
        )
        try:
            ckpt.save(
                self.scfg.checkpoint_dir or os.path.join(
                    self.scfg.serve_dir, "ckpt"
                ),
                snap,
            )
        except (OSError, AnalysisError) as e:
            self._degrade("checkpoint", e)
            return
        self._recover("checkpoint")

    def _restore(self) -> None:
        snap = ckpt.load(
            self.scfg.checkpoint_dir
            or os.path.join(self.scfg.serve_dir, "ckpt")
        )
        if snap is None:
            return
        base_fp, snap_term = ckpt.split_fence(snap.fingerprint)
        if snap_term > self.term and self._lease is not None:
            t, h = self._lease.observed()
            raise SupervisorFenced(
                f"checkpoint was written by fencing term {snap_term} but "
                f"this supervisor holds term {self.term} (newest observed "
                f"leadership: term {t} by {h!r}); a successor already ran "
                "— refusing to roll its frontier back"
            )
        if base_fp != self._fp:
            raise ckpt.CheckpointMismatch(
                "distributed serve checkpoint was taken with a different "
                "ruleset, sketch geometry, or host-tier ladder maximum; "
                "refusing to resume the merged ring (delete the serve "
                "checkpoint dir, or keep --dist-max-hosts stable across "
                "restarts — the ladder max, not the live host count, is "
                "the resume identity)"
            )
        sv = (snap.extra or {}).get("serve")
        if not sv:
            raise ckpt.CheckpointCorrupt(
                "distributed serve checkpoint manifest lacks the serve "
                "extra block"
            )
        self.total_lines = snap.lines_consumed
        self.total_chunks = snap.n_chunks
        self.total_parsed = snap.parsed
        self.total_skipped = snap.skipped
        self.cum_tracker = ckpt.restore_tracker(
            snap, self.cfg.sketch.topk_capacity
        )
        self.cum_arrays = {
            k[len("cum__"):]: v
            for k, v in snap.arrays.items()
            if k.startswith("cum__")
        }
        self.next_wid = int(sv["next_window"])
        self.windows_published = int(sv.get("windows_published", 0))
        self.cum_quarantine = _de_quarantine(sv.get("quarantine", []))
        self._v6_digests.update(
            {int(d): int(s) for d, s in sv.get("v6_digests", [])}
        )
        self.cum_incomplete_reasons = list(sv.get("incomplete_reasons", []))
        self.cum_incomplete_windows = [
            int(w) for w in sv.get("incomplete_windows", [])
        ]
        self.drops_restored = int(sv.get("drops", 0))
        ds = (snap.extra or {}).get("distserve", {})
        self._host_wal_restored = {
            int(r): int(s) for r, s in ds.get("host_wal", {}).items()
        }
        self.skipped_windows = [
            int(w) for w in ds.get("skipped_windows", [])
        ]
        for wrec in sv.get("windows", []):
            meta = wrec["meta"]
            pfx = f"w{meta['id']:06d}__"
            self.ring.push(WindowEpoch(
                arrays={
                    k[len(pfx):]: v
                    for k, v in snap.arrays.items()
                    if k.startswith(pfx)
                },
                meta=meta,
                tracker_tables={
                    int(acl): {int(s): int(e) for s, e in t}
                    for acl, t in wrec.get("tracker", [])
                },
                quarantine=_de_quarantine(wrec.get("quarantine", [])),
            ))
        for ep in self.ring.epochs:
            self._window_reports[ep.meta["id"]] = self._render_window_obj(ep)
        if self.ring.epochs:
            self._published["report"] = self._window_reports[
                self.ring.epochs[-1].meta["id"]
            ]
            self._published["cumulative"] = json.loads(
                self._render_cumulative().to_json()
            )

    # -- failover replay (DESIGN §23) --------------------------------------
    def _scan_spool_ranks(self) -> list[int]:
        root = self._spool_root()
        ranks = []
        try:
            names = os.listdir(root)
        except OSError:
            return ranks
        for n in names:
            if n.startswith("host-"):
                try:
                    r = int(n[5:])
                except ValueError:
                    continue
                if os.path.isdir(self._host_spool_dir(r)):
                    ranks.append(r)
        return sorted(ranks)

    def _replay_spools(self) -> None:
        """Elected-successor takeover: replay every host's durable epoch
        spool past the restored merge frontier and publish those windows
        exactly as the dead supervisor would have — the merge laws are
        associative, so replay order is free and the output is
        bit-identical to the union (the tentpole invariant the failover
        chaos tests pin).

        Loss discipline mirrors the merge loop's: a window some host
        spooled later epochs past but not this one gets a typed
        ``host_missing:<rank>`` marker; a window NO host's spool reached
        is skipped with explicit accounting; a corrupt spooled epoch is
        refused typed by ``unpack_epoch_payload`` and counted — never a
        crash, never a silently wrong merge.
        """
        if self.dscfg.spool_budget_mb <= 0:
            return
        t0 = time.monotonic()
        frontier = self.next_wid
        pending: dict[int, dict[int, tuple[dict, dict]]] = {}
        top_by_host: dict[int, int] = {}
        epochs = 0
        for rank in self._scan_spool_ranks():
            try:
                spool = EpochSpool(
                    self._host_spool_dir(rank),
                    budget_bytes=self.dscfg.spool_budget_mb << 20,
                )
            except (WalQuarantine, OSError) as e:
                self._degrade(f"spool{rank}", e)
                continue
            try:
                for seq, payload in spool.replay(0):
                    try:
                        arrays, extra = unpack_epoch_payload(payload)
                        wid = int(extra["meta"]["id"])
                    except (AnalysisError, KeyError, TypeError, ValueError) as e:
                        self.replay_refused_total += 1
                        obs.instant("distserve.replay.refused", args={
                            "host": rank, "seq": seq,
                            "error": f"{type(e).__name__}: {e}"[:160],
                        })
                        continue
                    epochs += 1
                    # the spool holds the exact bytes the host shipped
                    # (or would have shipped), so this crc matches what
                    # the dead supervisor stamped at live arrival —
                    # lineage cores come out identical (replay-identity)
                    extra["payload_crc"] = zlib.crc32(payload) & 0xFFFFFFFF
                    top_by_host[rank] = max(top_by_host.get(rank, -1), wid)
                    # the replayed epoch's WAL cursor supersedes the
                    # checkpointed one: a rejoining host must not replay
                    # WAL lines a replayed window already covers (that
                    # would double-count them)
                    self._host_wal_restored[rank] = max(
                        self._host_wal_restored.get(rank, 0),
                        int(extra.get("wal_next", 0)),
                    )
                    if wid >= frontier:
                        pending.setdefault(wid, {})[rank] = (arrays, extra)
            finally:
                spool.close()
        self.spool_replayed_total = epochs
        self.replay_lag_windows = len(pending)
        for w in sorted(pending):
            while self.next_wid < w:
                # a window below every surviving spool record: all its
                # epochs are gone (evicted/quarantined) — skip loudly
                self.skipped_windows.append(self.next_wid)
                obs.instant("serve.window.skipped", args={
                    "window": self.next_wid, "replay": True,
                })
                self.next_wid += 1
            recs = pending[w]
            missing = sorted(
                r for r, top in top_by_host.items()
                if r not in recs and top > w
            )
            self.next_wid = w + 1
            self._publish_window(w, recs, [], missing, path="replay")
            self.replay_windows_total += 1
        obs.instant("distserve.failover.replay", args={
            "frontier": frontier,
            "epochs": epochs,
            "windows": self.replay_windows_total,
            "refused": self.replay_refused_total,
            "takeover_sec": round(time.monotonic() - t0, 3),
        })
        flightrec.cursor(
            replay_windows=self.replay_windows_total,
            next_window=self.next_wid,
        )

    # -- plumbing ----------------------------------------------------------
    def _start_http(self) -> None:
        if self._http is None:
            return
        self._http_thread = threading.Thread(
            target=self._http.serve_forever, name="ra-distserve-http",
            daemon=True,
        )
        self._http_thread.start()

    def _install_signals(self) -> None:
        import signal

        if threading.current_thread() is not threading.main_thread():
            return
        # SIGINT/SIGTERM stop gracefully: workers drain their final
        # partial windows, the merge frontier publishes them, then
        # summary.json lands.  No SIGHUP reload in distributed v1
        # (restart the deployment to re-pack; DESIGN §22 scope bound).
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                self._old_signals[sig] = signal.signal(
                    sig, lambda *_: self.stop()
                )
            except (ValueError, OSError):
                pass

    def _teardown(self, aborted: BaseException | None) -> None:
        import signal

        self._stop_req.set()
        for sig, old in self._old_signals.items():
            try:
                signal.signal(sig, old)
            except (ValueError, OSError):
                pass
        self._old_signals = {}
        with self._lock:
            live = [h for h in self.hosts.values() if h.live]
        for h in live:
            self._send_control(h, b"S")
        deadline = time.monotonic() + 30.0
        for h in list(self.hosts.values()):
            budget = max(deadline - time.monotonic(), 0.1)
            if h.thread is not None:
                h.thread.join(timeout=budget)
            if h.proc is not None:
                h.proc.join(timeout=budget)
                if h.proc.is_alive():
                    h.proc.terminate()
                    h.proc.join(timeout=5.0)
        self._accept_stop = True
        if self._msock is not None:
            try:
                self._msock.close()
            except OSError:
                pass
            if self._accept_thread is not None:
                self._accept_thread.join(timeout=5.0)
        if self._http is not None:
            if self._http_thread is not None:
                self._http.shutdown()
                self._http.server_close()
                self._http_thread.join(timeout=5.0)
            else:
                self._http.server_close()
        if self._lease is not None:
            # planned exit releases (clears the stamp so a successor
            # wins immediately); a fenced holder leaves lease.json to
            # the winner — release() knows the difference
            self._lease.release()
        if self._lineage_log is not None:
            self._lineage_log.sync()
            self._lineage_log.close()
            self._lineage_log = None
        if self.epoch_store is not None:
            self.epoch_store.sync()
            self.epoch_store.close()
        obs.unregister_sampler("distserve")
