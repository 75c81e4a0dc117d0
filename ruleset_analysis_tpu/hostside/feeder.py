"""Multi-process host feed: parallel parse workers over file shards.

The reborn Hadoop input split (SURVEY.md §2 L2), one level above the
native parser's in-process threads: N worker PROCESSES each run a
:class:`fastparse.NativePacker` over byte ranges of the input files and
pack straight into shared-memory slots; the coordinator hands the device
driver batches in input order.  On a multi-core host this scales the
parse stage — the e2e bottleneck once transfers are fast — nearly
linearly with workers, without the GIL or per-batch pickling.

Layout decisions:

- The coordinator pre-chops files into batch descriptors of exactly
  ``batch_size`` raw lines using the native newline scanner — byte
  ranges only, no parsing.  Workers read their range straight from the
  file (page cache makes this nearly free) so no input bytes cross a
  queue; only tiny descriptors and completions do.
- Output slots hold ``rows_cap = 2 x batch_size`` rows when any
  out-direction binding exists (a connection line can emit two
  evaluations), else ``batch_size``.  Since a descriptor never holds
  more than ``batch_size`` lines, every line always fits and batches
  stay aligned to the precomputed raw-line boundaries.
- parsed/skipped counters ride each completion and fold into the
  feeder's totals when its batch is YIELDED, so checkpoint snapshots
  (taken at chunk boundaries) stay coherent with consumed input.

Requires the native parser; the pure-Python path has no multi-process
tier (it is not the deployment path).
"""

from __future__ import annotations

import multiprocessing
import pickle
import time
from multiprocessing import shared_memory

import numpy as np

from . import fastparse
from ..errors import FeedWorkerError, StallError
from ..runtime import faults, obs
from .pack import PackedRuleset, TUPLE_COLS, TUPLE6_COLS, V6DigestFill

#: Coordinator read granularity while scanning for batch boundaries.
SCAN_BLOCK = 8 << 20


def default_feed_workers() -> int:
    return fastparse.host_workers("RA_FEED_WORKERS", 16)


def _scan_batches(paths: list[str], batch_size: int, skip_lines: int):
    """Yield (path_idx, offset, nbytes, n_lines) descriptors.

    Each descriptor covers exactly ``batch_size`` raw lines (the final
    one per file may be short; descriptors never span files).  The first
    ``skip_lines`` lines are consumed without emitting (resume).
    """
    lib = fastparse._load()
    if lib is None:
        from ..errors import NativeParserUnavailable

        raise NativeParserUnavailable("feeder requires the native parser")
    import ctypes

    to_skip = skip_lines
    for path_i, path in enumerate(paths):
        with open(path, "rb") as f:
            buf = b""
            base = 0  # file offset of buf[0]
            pos = 0  # consumed bytes within buf
            eof = False
            pend_lines = 0  # lines in the current (incomplete) descriptor
            pend_start = 0  # absolute file offset where it starts

            def refill():
                nonlocal buf, base, pos, eof
                block = f.read(SCAN_BLOCK)
                if not block:
                    eof = True
                    return
                buf = buf[pos:] + block
                base += pos
                pos = 0

            while True:
                avail = len(buf) - pos
                if avail == 0:
                    if eof:
                        break
                    refill()
                    continue
                want = to_skip if to_skip > 0 else batch_size - pend_lines
                # zero-copy pointer into buf at pos (buf outlives the call)
                arr = np.frombuffer(buf, dtype=np.uint8)
                used = ctypes.c_int64(0)
                got = int(
                    lib.asa_count_lines(
                        ctypes.c_void_p(arr.ctypes.data + pos), avail,
                        1 if eof else 0, want, ctypes.byref(used),
                    )
                )
                if got == 0:
                    if eof:
                        break
                    refill()  # a line longer than the buffered bytes
                    continue
                if to_skip > 0:
                    to_skip -= got
                    pos += int(used.value)
                    continue
                if pend_lines == 0:
                    pend_start = base + pos
                pend_lines += got
                pos += int(used.value)
                if pend_lines == batch_size:
                    yield (path_i, pend_start, base + pos - pend_start, pend_lines)
                    pend_lines = 0
            if pend_lines:
                yield (path_i, pend_start, base + pos - pend_start, pend_lines)
    if to_skip > 0:
        from ..errors import ResumeInputMismatch

        raise ResumeInputMismatch(
            f"snapshot consumed {skip_lines} lines but the input ran short "
            f"by {to_skip}"
        )


def _worker(packed_blob, paths, rows_cap, rows6_cap, shm_name, task_q, done_q):
    # span shards arm lazily from the inherited RA_TRACE_DIR (the same
    # env channel the fault plan rides); the label makes this process's
    # track readable in the merged timeline
    obs.note_role("feeder-worker")
    packed = pickle.loads(packed_blob)
    packer = fastparse.NativePacker(packed)
    shm = shared_memory.SharedMemory(name=shm_name)
    slot_words = TUPLE_COLS * rows_cap + TUPLE6_COLS * rows6_cap
    files = {}
    try:
        while True:
            task = task_q.get()
            if task is None:
                return
            t0_span = time.perf_counter()
            # fault sites (plan arrives via the inherited RA_FAULT_PLAN
            # env): abrupt death — the OOM-kill the coordinator's
            # liveness probe must catch — and a wedge the coordinator's
            # stall watchdog must bound
            faults.fire("feeder.worker.crash")
            faults.fire("feeder.worker.stall")
            idx, slot, path_i, offset, nbytes, n_lines = task
            try:
                f = files.get(path_i)
                if f is None:
                    f = files[path_i] = open(paths[path_i], "rb")
                f.seek(offset)
                data = f.read(nbytes)
                out = np.ndarray(
                    (TUPLE_COLS, rows_cap), dtype=np.uint32, buffer=shm.buf,
                    offset=4 * slot * slot_words,
                )
                p0, s0 = packer.parsed, packer.skipped
                _, lines, _used = packer.pack_chunk(
                    data, rows_cap, final=True, max_lines=n_lines, n_threads=1,
                    out=out,
                )
                n6 = 0
                if rows6_cap:
                    # v6 rows the dual-family parse staged for this range
                    # ride the slot's second plane (input order preserved:
                    # the coordinator attributes them when idx yields)
                    rows6 = packer.take_v6()
                    n6 = len(rows6)
                    if n6:
                        plane6 = np.ndarray(
                            (TUPLE6_COLS, rows6_cap), dtype=np.uint32,
                            buffer=shm.buf,
                            offset=4 * (slot * slot_words + TUPLE_COLS * rows_cap),
                        )
                        plane6[:, :n6] = np.asarray(rows6, dtype=np.uint32).T
            except Exception as e:  # forward instead of dying silently
                done_q.put(("error", idx, f"{type(e).__name__}: {e}"))
                return
            obs.complete(
                "feeder.parse", t0_span, time.perf_counter(), cat="feeder",
                args={"batch": idx, "lines": lines},
            )
            done_q.put(
                (idx, slot, lines, packer.parsed - p0, packer.skipped - s0, n6)
            )
    finally:
        # seal this worker's flight ring (no-op disarmed): if the RUN
        # aborts — e.g. a sibling was SIGKILL'd — the supervising merge
        # reads the survivors' telemetry; a clean run prunes every seal
        from ..runtime import flightrec

        flightrec.seal()
        for f in files.values():
            f.close()
        shm.close()


class _FeedCounters:
    def __init__(self):
        self.parsed = 0
        self.skipped = 0


class _FeederBase:
    """Shared source-protocol state of the multi-worker feed tiers.

    Both tiers commit worker completions in input order: parsed/skipped
    deltas fold into ``.packer`` and v6 rows stage for ``take_v6`` only
    when their batch is YIELDED, so checkpoint snapshots stay coherent
    with consumed input no matter how far workers ran ahead.
    """

    def __init__(
        self,
        packed: PackedRuleset,
        paths: list[str],
        n_workers: int | None = None,
        stall_timeout: float | None = None,
    ):
        if not fastparse.available():
            from ..errors import NativeParserUnavailable

            raise NativeParserUnavailable("feeder requires the native parser")
        self.packed = packed
        self.paths = list(paths)
        self.n_workers = n_workers or default_feed_workers()
        #: watchdog bound: workers alive but completing nothing for this
        #: long is a wedge, escalated to a typed StallError abort
        self.stall_timeout = (
            stall_timeout if stall_timeout and stall_timeout > 0
            else faults.default_stall_timeout()
        )
        self.packer = _FeedCounters()
        self._resume_counts = (0, 0)
        self._v6chunks: list[np.ndarray] = []  # [n,13] arrays, input order
        #: digest -> 128-bit source for talker rendering (same contract
        #: as the other sources)
        self.v6_digests: dict[int, int] = {}
        self._v6_fill = V6DigestFill(self.v6_digests)

    def set_counts(self, parsed: int, skipped: int) -> None:
        self._resume_counts = (parsed, skipped)

    def take_v6(self):
        """Staged v6 rows as one [n, 13] array (or [] when none)."""
        chunks = self._v6chunks
        self._v6chunks = []
        if not chunks:
            return []
        if len(chunks) == 1:
            return chunks[0]
        return np.concatenate(chunks)

    def _stage_v6(self, rows6: np.ndarray) -> None:
        """Commit one batch's v6 rows + talker digests, in input order."""
        self._v6_fill.add_rows(rows6)
        self._v6chunks.append(rows6)


class ParallelFeeder(_FeederBase):
    """Stream-source over files backed by N parse worker processes.

    Drop-in for the stream driver's source protocol: ``.packer`` exposes
    parsed/skipped counters and ``.batches(skip_lines, batch_size)``
    yields ``([TUPLE_COLS, rows_cap] uint32, raw_line_count)`` in input
    order.  ``rows_cap`` is fixed per run (2x batch_size with
    out-bindings), so one compiled device program serves every chunk.
    """

    def batches(self, skip_lines: int, batch_size: int):
        self.packer.parsed, self.packer.skipped = self._resume_counts
        rows_cap = (2 if self.packed.bindings_out else 1) * batch_size
        # v6 plane: any line of a batch can be a dual-evaluation v6 line
        rows6_cap = 2 * batch_size if self.packed.has_v6 else 0
        n_slots = 2 * self.n_workers + 2
        slot_bytes = 4 * (TUPLE_COLS * rows_cap + TUPLE6_COLS * rows6_cap)
        shm = shared_memory.SharedMemory(create=True, size=n_slots * slot_bytes)
        # spawn, not fork: the driver process runs JAX's thread pools, and
        # forking a multi-threaded process can deadlock the child.  The
        # workers import only numpy + the native parser, so spawn is cheap.
        ctx = multiprocessing.get_context("spawn")
        task_q = ctx.Queue()
        done_q = ctx.Queue()
        blob = pickle.dumps(self.packed)
        workers = [
            ctx.Process(
                target=_worker,
                args=(blob, self.paths, rows_cap, rows6_cap, shm.name,
                      task_q, done_q),
                daemon=True,
            )
            for _ in range(self.n_workers)
        ]
        for w in workers:
            w.start()
        self._workers = workers  # exposed for fault-injection tests
        try:
            free_slots = list(range(n_slots))
            ready: dict[int, tuple] = {}  # idx -> completion
            next_submit = 0
            next_yield = 0
            desc_it = _scan_batches(self.paths, batch_size, skip_lines)
            descs_done = False

            def submit_until_full():
                nonlocal next_submit, descs_done
                while free_slots and not descs_done:
                    d = next(desc_it, None)
                    if d is None:
                        descs_done = True
                        break
                    slot = free_slots.pop()
                    task_q.put((next_submit, slot, *d))
                    next_submit += 1

            import queue as _queue

            def _occupancy() -> dict:
                # pool gauges for the metrics snapshotter: how many
                # descriptors are in flight vs workers still alive
                return {
                    "mode": "process",
                    "workers": len(workers),
                    "alive": sum(1 for w in workers if w.is_alive()),
                    "inflight": next_submit - next_yield,
                    "ready": len(ready),
                    "free_slots": len(free_slots),
                }

            obs.register_sampler("feeder", _occupancy)
            submit_until_full()
            stall_deadline = time.monotonic() + self.stall_timeout
            while next_yield < next_submit:
                while next_yield not in ready:
                    # timeout + liveness: a worker killed by the OS (OOM)
                    # can't forward its error, and waiting forever on its
                    # completion would hang the whole analysis silently
                    try:
                        msg = done_q.get(timeout=5.0)
                    except _queue.Empty:
                        dead = [w.pid for w in workers if not w.is_alive()]
                        if dead:
                            raise FeedWorkerError(
                                f"feeder worker(s) {dead} died without "
                                "reporting (killed by the OS?)"
                            )
                        if time.monotonic() > stall_deadline:
                            # alive but completing nothing: a wedged
                            # worker (stuck I/O, injected stall) must be
                            # a bounded typed abort, not a silent hang
                            raise StallError(
                                f"feeder workers made no progress in "
                                f"{self.stall_timeout:.0f}s "
                                f"({len(workers)} alive); raise "
                                "--stall-timeout if the input is "
                                "legitimately this slow"
                            )
                        continue
                    # progress: any completion resets the stall window
                    stall_deadline = time.monotonic() + self.stall_timeout
                    if msg[0] == "error":
                        raise FeedWorkerError(
                            f"feeder worker failed on batch {msg[1]}: {msg[2]}"
                        )
                    idx, slot, lines, dp, ds, n6 = msg
                    ready[idx] = (slot, lines, dp, ds, n6)
                slot, lines, dp, ds, n6 = ready.pop(next_yield)
                slot_words = TUPLE_COLS * rows_cap + TUPLE6_COLS * rows6_cap
                out = np.ndarray(
                    (TUPLE_COLS, rows_cap), dtype=np.uint32, buffer=shm.buf,
                    offset=4 * slot * slot_words,
                ).copy()  # the slot is reused; the driver may hold the batch
                if n6:
                    plane6 = np.ndarray(
                        (TUPLE6_COLS, rows6_cap), dtype=np.uint32,
                        buffer=shm.buf,
                        offset=4 * (slot * slot_words + TUPLE_COLS * rows_cap),
                    )
                    self._stage_v6(np.ascontiguousarray(plane6[:, :n6].T))
                free_slots.append(slot)
                next_yield += 1
                self.packer.parsed += dp
                self.packer.skipped += ds
                submit_until_full()
                yield out, lines
        finally:
            obs.unregister_sampler("feeder")
            # Bounded teardown, also on a consumer-side exception: poison
            # pills, ONE shared join budget (a wedged worker must not
            # serialize N x 10s), terminate + reap stragglers, and close
            # the queues so their feeder threads don't outlive the run.
            for _ in workers:
                task_q.put(None)
            deadline = time.monotonic() + 10.0
            for w in workers:
                w.join(timeout=max(0.0, deadline - time.monotonic()))
            for w in workers:
                if w.is_alive():
                    w.terminate()
            for w in workers:
                w.join(timeout=5)
            for q in (task_q, done_q):
                q.cancel_join_thread()
                q.close()
            shm.close()
            shm.unlink()


def _ring_worker(packed_blob, paths, rows_cap_shard, rows6_cap_shard,
                 ring_depth, shm_name, task_q, done_q):
    """Ring-partition parse worker: fine descriptors -> per-chip slots.

    Each task names the chip (ring) and slot its output belongs to; the
    worker parses the descriptor's byte range straight into that slot's
    shared-memory planes.  One worker may own several rings (W < D) or
    share a ring with siblings (W > D); the coordinator's routing keeps
    every ring's slots written in group order either way.
    """
    obs.note_role("ring-worker")
    packed = pickle.loads(packed_blob)
    packer = fastparse.NativePacker(packed)
    shm = shared_memory.SharedMemory(name=shm_name)
    slot_words = (
        TUPLE_COLS * rows_cap_shard + TUPLE6_COLS * rows6_cap_shard
    )
    files = {}
    try:
        while True:
            task = task_q.get()
            if task is None:
                return
            t0_span = time.perf_counter()
            # the ring twin of the queue tier's fault sites, plus the
            # ring-specific stall: a wedged partition producer starves
            # exactly one chip — the coordinator must bound it
            faults.fire("feeder.worker.crash")
            faults.fire("feeder.ring.stall")
            g, j, slot, path_i, offset, nbytes, n_lines = task
            try:
                f = files.get(path_i)
                if f is None:
                    f = files[path_i] = open(paths[path_i], "rb")
                f.seek(offset)
                data = f.read(nbytes)
                slot_off = 4 * (j * ring_depth + slot) * slot_words
                out = np.ndarray(
                    (TUPLE_COLS, rows_cap_shard), dtype=np.uint32,
                    buffer=shm.buf, offset=slot_off,
                )
                p0, s0 = packer.parsed, packer.skipped
                _, lines, _used = packer.pack_chunk(
                    data, rows_cap_shard, final=True, max_lines=n_lines,
                    n_threads=1, out=out,
                )
                n6 = 0
                if rows6_cap_shard:
                    rows6 = packer.take_v6()
                    n6 = len(rows6)
                    if n6:
                        plane6 = np.ndarray(
                            (TUPLE6_COLS, rows6_cap_shard), dtype=np.uint32,
                            buffer=shm.buf,
                            offset=slot_off + 4 * TUPLE_COLS * rows_cap_shard,
                        )
                        plane6[:, :n6] = np.asarray(rows6, dtype=np.uint32).T
            except Exception as e:  # forward instead of dying silently
                done_q.put(("error", g, f"{type(e).__name__}: {e}"))
                return
            obs.complete(
                "feeder.parse", t0_span, time.perf_counter(), cat="feeder",
                args={"group": g, "ring": j, "lines": lines},
            )
            done_q.put(
                (g, j, slot, lines, packer.parsed - p0, packer.skipped - s0,
                 n6)
            )
    finally:
        # worker-exit seal, exactly like the queue-tier worker above
        from ..runtime import flightrec

        flightrec.seal()
        for f in files.values():
            f.close()
        shm.close()


class _RingBatch:
    """One committed group: per-chip zero-copy views of ring slots.

    ``views[d]`` is chip d's ``[TUPLE_COLS, shard_rows]`` plane, a view
    STRAIGHT INTO that chip's shared-memory ring slot.  The consumer
    must call :meth:`release` once it has copied the data out (the wire
    bit-pack copies, so the per-chip ``device_put`` path releases right
    after compacting); :meth:`assemble` is the copy-and-release
    convenience for consumers that want one plain batch.
    """

    __slots__ = ("views", "n_raw", "_release_cb", "released")

    def __init__(self, views, n_raw, release_cb):
        self.views = views
        self.n_raw = n_raw
        self._release_cb = release_cb
        self.released = False

    def release(self) -> None:
        if not self.released:
            self.released = True
            self._release_cb()

    def assemble(self) -> np.ndarray:
        """Concatenate to one ``[TUPLE_COLS, D*shard_rows]`` batch
        (copies, then releases the ring slots)."""
        out = np.concatenate(self.views, axis=1)
        self.release()
        return out


class RingFeeder(_FeederBase):
    """Per-chip feeder rings: one shared-memory ring per device.

    The global task/completion queue of :class:`ParallelFeeder` funnels
    every batch through one coordinator copy and one whole-batch
    ``device_put`` — a host-side serialization point an 8-chip mesh
    outgrows.  This tier partitions the producer pool BY CHIP instead
    (ISSUE 11; the per-host data-tier idiom of the hybrid DCN x ICI
    mesh): each device d owns a ring of ``ring_depth`` shared-memory
    slots, descriptors chop ``batch_size/D`` lines fine (so a group of D
    consecutive descriptors covers exactly the lines a queue-tier batch
    would), and the worker partition serving ring d parses its line
    sub-ranges straight into d's slots.  The driver's pack stage then
    bit-packs each chip's view and issues that chip's ``device_put``
    directly from the ring — no global assembly, no coordinator copy.

    Equivalence with the queue tier: a group covers the same raw lines
    as the queue batch with the same index (groups reset at file
    boundaries exactly like batches), every register update is
    order/padding-invariant, and v6 rows commit in line order through
    the same rings — reports are bit-identical (pinned in
    tests/test_feeder.py).  Within a group, chip d's shard holds the
    rows of line sub-range d with its own valid prefix; padding between
    shards is masked on device like any other padding.

    ``emit_views`` (set by the driver): True yields :class:`_RingBatch`
    per-chip views for the direct ``device_put`` path (flat layout +
    prefetch); False yields plain assembled ``[TUPLE_COLS, rows_cap]``
    arrays so the sync driver and the stacked layout consume this tier
    unchanged.
    """

    yields_ring = True

    def __init__(
        self,
        packed: PackedRuleset,
        paths: list[str],
        n_workers: int | None = None,
        stall_timeout: float | None = None,
        n_rings: int | None = None,
        ring_depth: int = 4,
    ):
        super().__init__(packed, paths, n_workers, stall_timeout)
        #: one ring per device; the driver resolves None to the mesh's
        #: data extent before pulling batches
        self.n_rings = n_rings
        self.ring_depth = max(2, ring_depth)
        self.emit_views = False
        #: per-ring starved seconds (coordinator waited on this chip's
        #: shard) — the trace_summary feed block's starved-chip gauge
        self._starved_sec: list[float] = []
        self._occupancy: list[int] = []

    def batches(self, skip_lines: int, batch_size: int):
        self.packer.parsed, self.packer.skipped = self._resume_counts
        D = int(self.n_rings or 1)
        if batch_size % D:
            from ..errors import AnalysisError

            raise AnalysisError(
                f"ring feeder needs batch_size divisible by the ring count "
                f"({batch_size} % {D} != 0); pad the batch size"
            )
        sub = batch_size // D
        rows_cap_shard = (2 if self.packed.bindings_out else 1) * sub
        rows6_cap_shard = 2 * sub if self.packed.has_v6 else 0
        R = self.ring_depth
        W = self.n_workers
        slot_words = TUPLE_COLS * rows_cap_shard + TUPLE6_COLS * rows6_cap_shard
        shm = shared_memory.SharedMemory(
            create=True, size=4 * D * R * slot_words
        )
        ctx = multiprocessing.get_context("spawn")
        # one producer pool partition per chip: ring d is served by a
        # fixed worker set — contiguous ring blocks when W < D, the
        # w ≡ d (mod D) residue class when W >= D — so chip d's feed
        # never contends with another chip's parse backlog
        if W >= D:
            ring_workers = [[w for w in range(W) if w % D == d]
                            for d in range(D)]
        else:
            ring_workers = [[d * W // D] for d in range(D)]
        used_workers = sorted({w for ws in ring_workers for w in ws})
        task_qs = {w: ctx.Queue() for w in used_workers}
        done_q = ctx.Queue()
        blob = pickle.dumps(self.packed)
        workers = {
            w: ctx.Process(
                target=_ring_worker,
                args=(blob, self.paths, rows_cap_shard, rows6_cap_shard, R,
                      shm.name, task_qs[w], done_q),
                daemon=True,
            )
            for w in used_workers
        }
        for w in workers.values():
            w.start()
        self._workers = list(workers.values())  # fault-injection tests
        self._starved_sec = [0.0] * D
        self._occupancy = [0] * D
        import queue as _queue

        next_submit = 0  # defined before try: the finally reads them
        next_yield = 0
        t_feed0 = None
        occ_integral = [0.0] * D
        try:
            free_slots = [list(range(R)) for _ in range(D)]
            # group bookkeeping: meta[g] = (n_shards, n_raw); done[g] =
            # {j: (slot, lines, dp, ds, n6)}
            meta: dict[int, tuple[int, int]] = {}
            done: dict[int, dict[int, tuple]] = {}

            def group_it():
                """Yield [descriptors] groups of <= D fine descriptors,
                resetting at file boundaries (exactly the line spans the
                queue tier's batch_size-line batches cover)."""
                cur: list[tuple] = []
                for d in _scan_batches(self.paths, sub, skip_lines):
                    if cur and (d[0] != cur[0][0] or len(cur) == D):
                        yield cur
                        cur = []
                    cur.append(d)
                    if d[3] < sub:  # short descriptor: file ends here
                        yield cur
                        cur = []
                if cur:
                    yield cur

            groups = group_it()
            groups_done = False

            def submit_until_full():
                # a group submits only when EVERY ring it touches has a
                # free slot, so submission order per ring == group order
                nonlocal next_submit, groups_done
                while not groups_done:
                    if any(not free_slots[j] for j in range(D)):
                        return
                    grp = next(groups, None)
                    if grp is None:
                        groups_done = True
                        return
                    g = next_submit
                    next_submit += 1
                    meta[g] = (len(grp), sum(d[3] for d in grp))
                    done.setdefault(g, {})
                    for j, desc in enumerate(grp):
                        slot = free_slots[j].pop()
                        self._occupancy[j] += 1
                        ws = ring_workers[j]
                        task_qs[ws[g % len(ws)]].put((g, j, slot, *desc))

            def _gauges() -> dict:
                occ = list(self._occupancy)
                return {
                    "mode": "ring",
                    "rings": D,
                    "ring_depth": R,
                    "workers": len(workers),
                    "alive": sum(1 for w in workers.values() if w.is_alive()),
                    "inflight": next_submit - next_yield,
                    "ring_occupancy": occ,
                    "partition_imbalance": max(occ) - min(occ) if occ else 0,
                    "starved_sec": [round(s, 3) for s in self._starved_sec],
                }

            obs.register_sampler("feeder", _gauges)
            submit_until_full()
            t_feed0 = time.monotonic()  # occupancy integral starts here
            t_occ = t_feed0
            stall_deadline = time.monotonic() + self.stall_timeout
            while True:
                if next_yield == next_submit:
                    if groups_done:
                        break
                    # input remains but nothing could submit: the consumer
                    # still holds every slot of some ring, and releases can
                    # only happen on the consumer's own thread between
                    # pulls — progress is impossible from inside this
                    # generator, so abort loudly rather than silently
                    # truncating the corpus at this point
                    raise FeedWorkerError(
                        "ring slots exhausted with unparsed input left: "
                        "the consumer holds batches for every slot of a "
                        "ring; release each batch before pulling the next "
                        "(or raise ring_depth)"
                    )
                n_shards, n_raw = meta[next_yield]
                while len(done[next_yield]) < n_shards:
                    pending = [
                        j for j in range(n_shards)
                        if j not in done[next_yield]
                    ]
                    t0 = time.monotonic()
                    try:
                        msg = done_q.get(timeout=5.0)
                    except _queue.Empty:
                        dt = time.monotonic() - t0
                        for j in pending:
                            self._starved_sec[j] += dt
                        dead = [
                            w.pid for w in workers.values()
                            if not w.is_alive()
                        ]
                        if dead:
                            raise FeedWorkerError(
                                f"ring feed worker(s) {dead} died without "
                                "reporting (killed by the OS?)"
                            )
                        if time.monotonic() > stall_deadline:
                            starving = ", ".join(
                                f"chip{j}" for j in pending[:4]
                            )
                            raise StallError(
                                f"ring feed made no progress in "
                                f"{self.stall_timeout:.0f}s (rings dry: "
                                f"{starving}); raise --stall-timeout if "
                                "the input is legitimately this slow"
                            )
                        continue
                    now = time.monotonic()
                    dt = now - t0
                    for j in pending:
                        self._starved_sec[j] += dt
                    for j in range(D):  # occupancy integral (slot-seconds)
                        occ_integral[j] += self._occupancy[j] * (now - t_occ)
                    t_occ = now
                    stall_deadline = time.monotonic() + self.stall_timeout
                    if msg[0] == "error":
                        raise FeedWorkerError(
                            f"ring feed worker failed on group {msg[1]}: "
                            f"{msg[2]}"
                        )
                    g, j, slot, lines, dp, ds, n6 = msg
                    done[g][j] = (slot, lines, dp, ds, n6)
                shards = done.pop(next_yield)
                meta.pop(next_yield)
                views = []
                taken: list[tuple[int, int]] = []  # (ring, slot) to free
                for j in range(n_shards):
                    slot, lines, dp, ds, n6 = shards[j]
                    slot_off = 4 * (j * R + slot) * slot_words
                    views.append(np.ndarray(
                        (TUPLE_COLS, rows_cap_shard), dtype=np.uint32,
                        buffer=shm.buf, offset=slot_off,
                    ))
                    if n6:
                        plane6 = np.ndarray(
                            (TUPLE6_COLS, rows6_cap_shard), dtype=np.uint32,
                            buffer=shm.buf,
                            offset=slot_off + 4 * TUPLE_COLS * rows_cap_shard,
                        )
                        # committed in shard (= line) order, same stream
                        # as the queue tier stages
                        self._stage_v6(
                            np.ascontiguousarray(plane6[:, :n6].T)
                        )
                    self.packer.parsed += dp
                    self.packer.skipped += ds
                    taken.append((j, slot))
                for j in range(n_shards, D):
                    # short group (file end): missing chips feed zeros —
                    # valid=0 padding, masked on device like any other
                    views.append(np.zeros(
                        (TUPLE_COLS, rows_cap_shard), dtype=np.uint32
                    ))

                def release(taken=taken):
                    for j, slot in taken:
                        free_slots[j].append(slot)
                        self._occupancy[j] -= 1

                rb = _RingBatch(views, n_raw, release)
                next_yield += 1
                if not self.emit_views:
                    out = rb.assemble()  # copies + releases before yield
                    submit_until_full()
                    yield out, n_raw
                else:
                    yield rb, n_raw
                    # the consumer released during pack (same thread);
                    # anything still held just waits another round
                    submit_until_full()
        finally:
            obs.unregister_sampler("feeder")
            # one summary instant on the obs timeline (the devprof.summary
            # pattern): the trace_summary feed block renders these without
            # needing the metrics JSONL
            if next_submit and t_feed0 is not None:
                elapsed = max(1e-9, time.monotonic() - t_feed0)
                occ_pct = [
                    round(100.0 * occ_integral[j] / (R * elapsed), 2)
                    for j in range(D)
                ]
                obs.instant(
                    "feeder.summary",
                    args={
                        "mode": "ring",
                        "rings": D,
                        "ring_depth": R,
                        "workers": len(workers),
                        "groups": next_yield,
                        "ring_occupancy_pct": occ_pct,
                        "partition_imbalance_pct": round(
                            max(occ_pct) - min(occ_pct), 2
                        ) if occ_pct else 0.0,
                        "starved_sec": [
                            round(s, 3) for s in self._starved_sec
                        ],
                        "starved_total_sec": round(
                            sum(self._starved_sec), 3
                        ),
                    },
                )
            for w_id, q in task_qs.items():
                q.put(None)
            deadline = time.monotonic() + 10.0
            for w in workers.values():
                w.join(timeout=max(0.0, deadline - time.monotonic()))
            for w in workers.values():
                if w.is_alive():
                    w.terminate()
            for w in workers.values():
                w.join(timeout=5)
            for q in (*task_qs.values(), done_q):
                q.cancel_join_thread()
                q.close()
            try:
                shm.close()
            except BufferError:
                # a consumer still holds a zero-copy slot view (e.g. an
                # exception unwound mid-pack); dropping our reference
                # lets GC finalize the mapping once the view dies — and
                # teardown must not mask the consumer's real error
                pass
            shm.unlink()


class ThreadedFeeder(_FeederBase):
    """In-process threaded twin of :class:`ParallelFeeder`.

    Worker THREADS parse the same exact-raw-line byte-range descriptors
    the coordinator scans; the native parser releases the GIL for the
    parse itself, so threads scale across cores with no spawn cost, no
    pickling, and no shared-memory plumbing — the tier of choice when
    the driver process can spare cores (the prefetching ingest engine
    stacks on top, overlapping whichever tier feeds it with the device
    step).  Each thread builds ONE NativePacker lazily (the gid tables
    are per-thread, reused across its descriptors); completions commit
    strictly in input order with their parsed/skipped deltas and staged
    v6 rows, so batch boundaries — and the top-K caveat — are identical
    to the process tier over the same input.
    """

    def batches(self, skip_lines: int, batch_size: int):
        import concurrent.futures as cf
        import threading

        self.packer.parsed, self.packer.skipped = self._resume_counts
        rows_cap = (2 if self.packed.bindings_out else 1) * batch_size
        has_v6 = self.packed.has_v6
        tl = threading.local()
        # every handle any worker thread opens, for deterministic release
        # in the finally below (thread-local GC alone would hold fds open
        # past an early consumer exit — the same discipline _run_core's
        # close() applies to wire mmaps)
        files_lock = threading.Lock()
        opened: list = []

        stop_ev = threading.Event()  # releases injected stalls at teardown

        def work(desc):
            t0_span = time.perf_counter()
            # thread-tier twin of the process worker's fault sites (no
            # crash site: os._exit here would take the driver down)
            faults.fire("feeder.worker.stall", stop=stop_ev)
            path_i, offset, nbytes, n_lines = desc
            pk = getattr(tl, "packer", None)
            if pk is None:
                pk = tl.packer = fastparse.NativePacker(self.packed)
                tl.files = {}
            f = tl.files.get(path_i)
            if f is None:
                f = tl.files[path_i] = open(self.paths[path_i], "rb")
                with files_lock:
                    opened.append(f)
            f.seek(offset)
            data = f.read(nbytes)
            p0, s0 = pk.parsed, pk.skipped
            batch, lines, _used = pk.pack_chunk(
                data, rows_cap, final=True, max_lines=n_lines, n_threads=1
            )
            rows6 = pk.take_v6() if has_v6 else []
            obs.complete(
                "feeder.parse", t0_span, time.perf_counter(), cat="feeder",
                args={"lines": lines},
            )
            return batch, lines, pk.parsed - p0, pk.skipped - s0, rows6

        from collections import deque

        desc_it = _scan_batches(self.paths, batch_size, skip_lines)
        ex = cf.ThreadPoolExecutor(
            max_workers=self.n_workers, thread_name_prefix="ra-feed"
        )
        inflight: deque = deque()
        max_inflight = 2 * self.n_workers + 2
        stalled = False
        try:
            obs.register_sampler(
                "feeder",
                lambda: {
                    "mode": "thread",
                    "workers": self.n_workers,
                    "inflight": len(inflight),
                },
            )

            def fill() -> None:
                while len(inflight) < max_inflight:
                    d = next(desc_it, None)
                    if d is None:
                        return
                    inflight.append(ex.submit(work, d))

            fill()
            while inflight:
                fut = inflight.popleft()
                try:
                    # stall watchdog: a worker thread that wedges (stuck
                    # I/O, injected stall) bounds to a typed abort — the
                    # batches commit in submission order, so waiting on
                    # THIS future is exactly producer-to-consumer progress
                    batch, lines, dp, ds, rows6 = fut.result(
                        timeout=self.stall_timeout
                    )
                except cf.TimeoutError:
                    stalled = True
                    raise StallError(
                        f"feed worker made no progress in "
                        f"{self.stall_timeout:.0f}s; raise --stall-timeout "
                        "if the input is legitimately this slow"
                    ) from None
                except Exception as e:
                    raise FeedWorkerError(
                        f"feed worker failed: {type(e).__name__}: {e}"
                    ) from e
                self.packer.parsed += dp
                self.packer.skipped += ds
                if len(rows6):
                    self._stage_v6(np.asarray(rows6, dtype=np.uint32))
                fill()
                yield batch, lines
        finally:
            obs.unregister_sampler("feeder")
            # release injected stalls FIRST so the bounded shutdown below
            # cannot wedge on a thread parked in a fault site
            stop_ev.set()
            # wait: a worker mid-descriptor must finish before its file
            # handles close under it (each task is one bounded parse).
            # EXCEPT after a stall verdict: a thread wedged in an OS call
            # cannot be cancelled, and waiting on it would turn the typed
            # StallError into the very hang the watchdog exists to
            # prevent — abandon it (the process tier, which CAN terminate
            # its workers, is the tier of choice for hostile inputs)
            ex.shutdown(wait=not stalled, cancel_futures=True)
            with files_lock:
                for f in opened:
                    f.close()
