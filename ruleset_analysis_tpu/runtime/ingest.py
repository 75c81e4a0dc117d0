"""Pipelined ingest: bounded prefetch of device-ready batches.

The synchronous drivers interleave three stages on one thread — host
parse, host->device transfer, device step — and rely only on JAX's async
dispatch for overlap, so the end-to-end rate trends toward the SUM of the
stage times instead of their max (BENCH r5 measured pipeline_efficiency
0.35).  This module decouples the stages, MapReduce-input-split style
(SURVEY.md §2 L2): a background producer thread runs the source's batch
iterator (the parse stage — the native parser releases the GIL and fans
one batch across cores itself), optionally applies a ``pack`` transform
(flow coalescing when ``--coalesce`` is armed — the O(B) unique-row
hash pass, runtime/coalesce.py — then wire bit-packing and the async
sharded ``device_put``, so the queue holds device-ready batches and H2D
of chunk N+k overlaps the step of chunk N), and feeds a bounded queue
the driver's chunk loop consumes.

Correctness contract — COMMIT AT CONSUME, not at produce:

- Every queue item carries its batch plus the side effects its
  production implied: the source's cumulative parsed/skipped counters,
  the v6 rows staged while parsing it, and (elastic sources) the
  per-shard cursor snapshot.  The wrapper's public ``packer`` counters,
  ``take_v6`` buffer, and ``cursor_rows()`` only advance when the
  driver actually receives the batch — so a checkpoint taken at a chunk
  boundary covers exactly the committed lines, never lines the producer
  merely ran ahead on (the epoch-snapshot manifest records the last
  COMMITTED batch, not the last prefetched one).
- Batches flow through in source order (single producer, FIFO queue);
  with the inner iterator unchanged, every batch boundary — and
  therefore the full report, including per-chunk top-K candidates — is
  bit-identical to the synchronous driver.
- A producer exception is re-raised, typed, at the consumer's next
  pull; a consumer that stops early (crash simulation, ``close()``)
  signals the producer to stop so no thread is left blocked on a full
  queue.

The sources themselves already guarantee the donation/in-flight-mutation
constraint (every yielded array is freshly allocated — see
``_PackedSource._emit``), so producing ahead never mutates a buffer
under an in-flight async ``device_put``.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from ..errors import AnalysisError, IngestError, StallError
from . import faults, flightrec, obs
from .metrics import LatencyHistogram

_END = ("end", None)


class _Counters:
    """parsed/skipped counters advanced only as batches are committed."""

    def __init__(self):
        self.parsed = 0
        self.skipped = 0


class IngestStats:
    """Per-stage overlap accounting for one prefetched stream.

    ``parse_sec`` is time the producer spent inside the inner iterator
    (the parse, or the wire reader's block reads) — what the
    ``ingest.produce`` spans cover; ``pack_sec`` is the pack transform
    (coalescing, bit-pack and the async sharded ``device_put`` issue) —
    what ``ingest.pack`` covers; ``produce_sec`` is their sum.
    ``parse_workers`` and ``parse_serial_batches`` (native text sources
    only) are the workers the last batch parsed in parallel used and the
    batches that took the one-thread loop.  ``backpressure_sec`` is
    producer time blocked on a full queue (the device is the
    bottleneck); ``starved_sec`` is consumer time blocked on an empty
    queue (the parse is the bottleneck).  The report totals
    carry these so "parse-starved vs device-bound" is answerable from
    any run's JSON.
    """

    def __init__(self):
        self.parse_sec = 0.0
        self.pack_sec = 0.0
        self.backpressure_sec = 0.0
        self.starved_sec = 0.0
        self.batches = 0
        self.parse_workers: int | None = None
        self.parse_serial_batches: int | None = None

    @property
    def produce_sec(self) -> float:
        return self.parse_sec + self.pack_sec

    def to_dict(self) -> dict:
        parse, pack = round(self.parse_sec, 4), round(self.pack_sec, 4)
        workers = {} if self.parse_serial_batches is None else {
            "parse_workers": self.parse_workers,
            "parse_serial_batches": self.parse_serial_batches,
        }
        return {
            "batches": self.batches,
            "produce_sec": round(parse + pack, 4),
            "parse_sec": parse,
            **workers,
            "pack_sec": pack,
            "backpressure_sec": round(self.backpressure_sec, 4),
            "starved_sec": round(self.starved_sec, 4),
        }


class _Pump:
    """One producer thread filling one bounded queue from one iterator."""

    def __init__(self, owner: "PrefetchingSource", it, *, with_v6: bool, pack):
        self.owner = owner
        self.q: queue.Queue = queue.Queue(maxsize=owner.depth)
        self.stop = threading.Event()
        self._it = it
        self._with_v6 = with_v6
        self._pack = pack
        self.thread = threading.Thread(
            target=self._produce, name="ra-ingest-producer", daemon=True
        )

    def _put(self, item, batch: int) -> bool:
        """Enqueue with stop-responsiveness; False if the consumer left."""
        # producer blocked on a full queue: the device is the bottleneck
        # for this interval
        with obs.span("ingest.backpressure", obs.STALL_SPAN_MIN_SEC, batch=batch):
            t0 = time.perf_counter()
            while not self.stop.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    self.owner.stats.backpressure_sec += time.perf_counter() - t0
                    return True
                except queue.Full:
                    continue
        return False

    def _produce(self) -> None:
        owner = self.owner
        inner = owner._inner
        take_v6 = getattr(inner, "take_v6", None) if self._with_v6 else None
        cursor_rows = getattr(inner, "cursor_rows", None)
        pack = self._pack
        seq = owner.stats.batches  # batch ids run on across the pumps
        try:
            while True:
                with obs.span("ingest.produce", batch=seq):
                    t0 = time.perf_counter()
                    # fault sites: a producer bug (typed at the consumer)
                    # and a wedged producer (the consumer's stall
                    # watchdog fires)
                    faults.fire("ingest.producer.raise")
                    faults.fire("ingest.queue.stall", stop=self.stop)
                    nxt = next(self._it, None)
                    if nxt is not None:
                        batch, n_raw = nxt
                        # side effects of producing THIS batch, captured
                        # now and committed only when the consumer
                        # receives it
                        v6 = take_v6() if take_v6 is not None else None
                        parsed = inner.packer.parsed
                        skipped = inner.packer.skipped
                        cur = cursor_rows() if cursor_rows is not None else None
                    t_parsed = time.perf_counter()
                owner.stats.parse_sec += t_parsed - t0
                if nxt is None:
                    break
                if pack is not None and batch is not None:
                    # bit-pack + async sharded device_put (H2D issue)
                    with obs.span("ingest.pack", batch=seq):
                        batch = pack(batch)
                    owner.stats.pack_sec += time.perf_counter() - t_parsed
                # t0 rides the item: the consumer records produce->commit
                # latency into the batch-e2e histogram at receipt
                item = ("item", (batch, n_raw, parsed, skipped, v6, cur, t0))
                if not self._put(item, seq):
                    return
                seq += 1
        except BaseException as e:  # re-raised typed at the consumer
            self._put(("error", e), seq)
            return
        self._put(_END, seq)

    def _get_bounded(self):
        """Next queue item, bounded by the stall watchdog.

        Every received item resets the window, so a slow-but-advancing
        producer never trips it; a producer that is alive yet makes NO
        progress for ``stall_timeout`` seconds (hung I/O, deadlock, an
        injected ``ingest.queue.stall``) escalates to a typed StallError
        instead of wedging the driver forever.  A producer that died
        without its error/end sentinel (should be impossible — the
        sentinel put is unconditional) surfaces as IngestError.
        """
        timeout = self.owner.stall_timeout
        deadline = time.monotonic() + timeout
        while True:
            try:
                return self.q.get(timeout=0.2)
            except queue.Empty:
                if not self.thread.is_alive():
                    raise IngestError(
                        "ingest producer thread died without reporting"
                    ) from None
                if time.monotonic() > deadline:
                    raise StallError(
                        f"ingest producer made no progress in {timeout:.0f}s "
                        "(queue empty, producer alive); raise "
                        "--stall-timeout if the input is legitimately "
                        "this slow"
                    ) from None

    def consume(self):
        owner = self.owner
        self.thread.start()
        try:
            while True:
                # consumer blocked on an empty queue: the parse is the
                # bottleneck for this interval.  FIFO from one producer,
                # so the batch waited for is the next uncommitted one.
                with obs.span(
                    "ingest.starved", obs.STALL_SPAN_MIN_SEC,
                    batch=owner.stats.batches,
                ):
                    t0 = time.perf_counter()
                    tag, payload = self._get_bounded()
                    t1 = time.perf_counter()
                owner.stats.starved_sec += t1 - t0
                if tag == "end":
                    return
                if tag == "error":
                    if isinstance(payload, AnalysisError) or not isinstance(
                        payload, Exception
                    ):
                        raise payload
                    # untyped producer failure: wrap so every failed run
                    # still exits with a typed AnalysisError (the chaos
                    # invariant); the original rides __cause__
                    raise IngestError(
                        f"ingest producer failed: "
                        f"{type(payload).__name__}: {payload}"
                    ) from payload
                batch, n_raw, parsed, skipped, v6, cur, t_prod = payload
                owner.packer.parsed = parsed
                owner.packer.skipped = skipped
                if v6 is not None and len(v6):
                    owner._staged6.append(v6)
                if cur is not None:
                    owner._cursor_rows = cur
                owner.stats.batches += 1
                # batch end-to-end latency, produce-start -> commit (the
                # moment the driver receives it): the ingest half of the
                # latency SLO plane (DESIGN §20)
                owner.latency.record(t1 - t_prod)
                # flight-recorder cursors: a crash dump names the last
                # COMMITTED batch (one dict update when armed)
                flightrec.cursor(
                    committed_batches=owner.stats.batches,
                    committed_parsed=parsed,
                )
                yield batch, n_raw
        finally:
            self.shutdown()

    def shutdown(self) -> None:
        self.stop.set()
        deadline = time.monotonic() + 10.0
        # drain-and-join LOOP, not drain-then-join: a producer that was
        # mid-_put when we drained can enqueue one more item and block
        # again on a full depth-1 queue, so keep draining until it exits
        while self.thread.is_alive() and time.monotonic() < deadline:
            try:
                while True:
                    self.q.get_nowait()
            except queue.Empty:
                pass
            self.thread.join(timeout=0.1)
        if not self.thread.is_alive():
            # release the inner iterator's resources (feeder worker
            # pools, file handles) deterministically — an abandoned
            # generator would only run its finally at GC time, leaking
            # threads/processes past the consumer's exception
            close_it = getattr(self._it, "close", None)
            if close_it is not None:
                try:
                    close_it()
                except Exception:
                    pass  # teardown must not mask the consumer's error


class PrefetchingSource:
    """Wrap any stream source with a bounded background prefetch.

    Presents the same source protocol the drivers consume
    (``packer``/``set_counts``/``batches``/optional ``take_v6`` /
    ``batches6`` / ``cursor_rows`` / ``totals_patch`` / ``close``), so it
    drops in front of every tier: the native text parser (threads inside
    the GIL-releasing parse), the multi-worker feeders, the packed-array
    source, and the mmap'd wire reader (chunked reads happen in the
    producer thread).

    ``pack`` runs in the producer thread on every non-``None`` batch —
    the drivers pass the wire bit-pack + async sharded ``device_put``
    here, so queue items are device-ready and the H2D transfer of later
    chunks overlaps the current device step (double/triple buffering,
    sized by ``depth``; the default — 2 — lives in
    ``AnalysisConfig.prefetch_depth``, the single user surface).
    """

    def __init__(self, inner, depth: int, pack=None, stall_timeout: float | None = None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._inner = inner
        self.depth = depth
        self._pack = pack
        #: watchdog bound on producer-to-consumer progress (see _get_bounded)
        self.stall_timeout = (
            stall_timeout if stall_timeout and stall_timeout > 0
            else faults.default_stall_timeout()
        )
        self.packer = _Counters()
        self.stats = IngestStats()
        #: produce->commit batch latency (log2 buckets, u64 counts —
        #: mergeable by addition); summarized into report totals.latency
        #: and every metrics snapshot
        self.latency = LatencyHistogram()
        self._staged6: list = []
        self._pumps: list[_Pump] = []
        self.yields_wire = getattr(inner, "yields_wire", False)
        #: weighted (coalesced) wire input: drivers key the fingerprint
        #: unit, padding shapes, grouped compaction, and the
        #: non-weight-linear-impl refusals off this — it must survive
        #: the wrap exactly like yields_wire
        self.yields_wire_weighted = getattr(
            inner, "yields_wire_weighted", False
        )
        self._cursor_rows = None
        # expose optional protocol members only when the inner source has
        # them: the drivers feature-detect with hasattr (e.g. a v6 step
        # is only built for sources exposing take_v6/batches6)
        if hasattr(inner, "take_v6"):
            self.take_v6 = self._take_v6
        if hasattr(inner, "batches6"):
            self.batches6 = self._batches6
        if hasattr(inner, "cursor_rows"):
            self._cursor_rows = inner.cursor_rows()
            self.cursor_rows = self._committed_cursor_rows
        if hasattr(inner, "totals_patch"):
            self.totals_patch = inner.totals_patch
        # live queue gauges for the metrics snapshotter (one None-check
        # when --metrics-out is unset); unregistered on close
        obs.register_sampler("ingest", self._sample_metrics)

    # -- delegated attributes -------------------------------------------
    @property
    def v6_digests(self):
        return self._inner.v6_digests

    @property
    def n4_rows(self):
        return self._inner.n4_rows

    def set_counts(self, parsed: int, skipped: int) -> None:
        self._inner.set_counts(parsed, skipped)
        self.packer.parsed, self.packer.skipped = parsed, skipped

    # -- committed side channels ----------------------------------------
    def _take_v6(self):
        staged = self._staged6
        self._staged6 = []
        if not staged:
            return []
        if len(staged) == 1:
            return staged[0]
        if isinstance(staged[0], np.ndarray):
            return np.concatenate(staged)
        out: list = []
        for rows in staged:
            out.extend(rows)
        return out

    def _committed_cursor_rows(self) -> np.ndarray:
        return self._cursor_rows

    # -- batch streams --------------------------------------------------
    def _pump_iter(self, it, with_v6: bool, pack):
        pump = _Pump(self, it, with_v6=with_v6, pack=pack)
        self._pumps.append(pump)
        return pump.consume()

    def batches(self, skip_lines: int, batch_size: int):
        return self._pump_iter(
            iter(self._inner.batches(skip_lines, batch_size)),
            with_v6=True,
            pack=self._pack,
        )

    def _batches6(self, skip_rows6: int, batch_size: int):
        # wire phase 2: v6 rows arrive as the batch itself, no side pull;
        # NO pack either — the drivers' run_chunk6 shards v6 batches
        # themselves (the v4 pack would double-shard them)
        return self._pump_iter(
            iter(self._inner.batches6(skip_rows6, batch_size)),
            with_v6=False,
            pack=None,
        )

    # -- lifecycle ------------------------------------------------------
    def ingest_stats(self) -> dict:
        pk = getattr(self._inner, "packer", None)
        if hasattr(pk, "parse_serial_batches"):  # the native text parser
            self.stats.parse_workers = pk.parse_workers
            self.stats.parse_serial_batches = pk.parse_serial_batches
        return {"prefetch_depth": self.depth, **self.stats.to_dict()}

    def latency_summary(self) -> dict:
        """Report-facing ``totals.latency`` patch ({} before any batch)."""
        if self.latency.count == 0:
            return {}
        return {"batch_e2e": self.latency.summary()}

    def _sample_metrics(self) -> dict:
        """Live snapshot of the bounded queue + overlap accounting."""
        out = {
            "prefetch_depth": self.depth,
            "queue_depth": sum(p.q.qsize() for p in self._pumps),
            "batches": self.stats.batches,
            "produce_sec": round(self.stats.produce_sec, 3),
            "backpressure_sec": round(self.stats.backpressure_sec, 3),
            "starved_sec": round(self.stats.starved_sec, 3),
        }
        if self.latency.count:
            out.update(self.latency.gauges("latency_batch_e2e_"))
        return out

    def close(self) -> None:
        obs.unregister_sampler("ingest")
        for pump in self._pumps:
            pump.shutdown()
        inner_close = getattr(self._inner, "close", None)
        if inner_close is not None:
            inner_close()
