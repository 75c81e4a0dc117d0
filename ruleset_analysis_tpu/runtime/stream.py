"""Streaming driver: host text -> packed batches -> fused device steps.

The rebuild of the reference's job loop (SURVEY.md §4.2): where Hadoop
splits HDFS input across mapper processes, this driver cuts the unbounded
log stream into fixed-size batches (constant device memory, one compiled
program — SURVEY.md §6 "long-context" note), packs them on host, and feeds
the jitted analysis step.

Overlap comes from JAX's async dispatch: ``step`` returns immediately with
futures, so host parsing of chunk N+1 runs while the device crunches chunk
N.  Top-K candidates drain through a short lag queue so fetching them
never synchronises the host with the in-flight chunk.
"""

from __future__ import annotations

import contextlib
import os
import time
from collections import deque
from collections.abc import Iterable, Iterator

import jax
import numpy as np

from ..config import AnalysisConfig
from ..hostside import pack as pack_mod
from ..hostside.pack import T_VALID, TUPLE_COLS, LinePacker, PackedRuleset
from ..hostside.syslog import parse_line
from ..models import pipeline
from ..ops.topk import TopKTracker
from . import devprof, faults, obs
from .metrics import CompileMeter


_SENTINEL = object()


def _arm_retry(cfg: AnalysisConfig) -> None:
    """Arm the retry/backoff table for one driver run (DESIGN §19).

    Called at the PUBLIC driver entries, before any source construction
    — the wire reader's open IO is itself a retry seam, and its attempts
    must land in this run's freshly-reset counters.  The flight
    recorder (DESIGN §20) arms here too when the config names a
    blackbox directory, so library callers get the same always-on
    forensics the CLI wires up.
    """
    from . import flightrec, retrypolicy

    retrypolicy.configure(cfg.retry_policy)
    if cfg.blackbox_dir:
        flightrec.arm(cfg.blackbox_dir, role="main")


def chunked(it: Iterable[str], size: int) -> Iterator[list[str]]:
    buf: list[str] = []
    for x in it:
        buf.append(x)
        if len(buf) == size:
            yield buf
            buf = []
    if buf:
        yield buf


class LineBatcher:
    """Push-based core of the text batching rules.

    Extracted from :class:`_TextSource` so the always-on serve loop
    (runtime/serve.py) forms batches under EXACTLY the batch drivers'
    boundary rules — the same early close when a dual-evaluation line
    would overflow, the same ``(None, n_raw)`` zero-valid batches, the
    same v6 side channel and capped digest map.  Identical boundaries
    are what make a per-window serve report bit-identical to an offline
    ``run_stream`` over the same window's lines (talker candidates are
    the one chunk-boundary-sensitive statistic; registers never are).

    ``push`` returns the ``(batch, n_raw)`` events the line completed
    (possibly empty); ``flush`` closes the partial batch at a window
    rotation or end of stream.
    """

    def __init__(
        self,
        packer: LinePacker,
        has_v6: bool,
        v6rows: list,
        v6_digests: dict[int, int],
        batch_size: int,
    ):
        self.packer = packer
        self._has_v6 = has_v6
        self._v6rows = v6rows
        self._digests = v6_digests
        self._batch = batch_size
        self._out = np.zeros((TUPLE_COLS, batch_size), dtype=np.uint32)
        self._fill = 0
        self.raw = 0  # raw lines assigned to the open batch

    def _emit(self) -> tuple[np.ndarray | None, int]:
        ev = ((self._out if self._fill else None), self.raw)
        self._out = np.zeros((TUPLE_COLS, self._batch), dtype=np.uint32)
        self._fill = 0
        self.raw = 0
        return ev

    def push(self, line: str) -> list[tuple[np.ndarray | None, int]]:
        events: list[tuple[np.ndarray | None, int]] = []
        packer = self.packer
        p = parse_line(line)
        gids = [] if p is None else packer.resolve_gids(p)
        if gids and p.family == 6:
            if not self._has_v6:
                # v6 traffic vs a pure-v4 ruleset: counted skip
                gids = []
            else:
                s = pack_mod.u128_limbs(p.src)
                d = pack_mod.u128_limbs(p.dst)
                for gid in gids:
                    self._v6rows.append(
                        (gid, p.proto, *s, p.sport, *d, p.dport, 1)
                    )
                dig = self._digests
                if len(dig) < pack_mod.V6_DIGEST_CAP:
                    dig.setdefault(pack_mod.fold_src32_host(p.src), p.src)
                packer.parsed += len(gids)
                self.raw += 1
                if self.raw == self._batch:
                    events.append(self._emit())
                return events
        if gids and self._fill + len(gids) > self._batch:
            events.append(self._emit())
        for gid in gids:
            self._out[:, self._fill] = (
                gid, p.proto, p.src, p.sport, p.dst, p.dport, 1
            )
            self._fill += 1
        packer.parsed += len(gids)
        if not gids:
            packer.skipped += 1
        self.raw += 1
        if self.raw == self._batch:
            events.append(self._emit())
        return events

    def flush(self) -> tuple[np.ndarray | None, int] | None:
        """Close the open partial batch (rotation / end of stream)."""
        if self.raw:
            return self._emit()
        return None


class _TextSource:
    """Batch source over an iterable of decoded lines (pure-Python parse).

    Batches are line-atomic: each holds a whole number of raw lines and at
    most ``batch_size`` tuple rows.  A batch normally covers exactly
    ``batch_size`` raw lines, but closes early when the next line's
    evaluations would not fit — a connection line evaluated against both
    an ``in`` and an ``out`` ACL emits two rows.  Counters update as lines
    are assigned to batches, so checkpoint snapshots (taken at batch
    boundaries) always agree with the batches actually emitted.

    A batch whose raw lines produced NO v4 tuple rows (a mostly-IPv6 or
    mostly-unparseable stretch of the corpus) is yielded as ``(None,
    n_raw)``: the driver accounts the raw lines (and drains any staged v6
    rows) without stepping an all-invalid device chunk.
    """

    def __init__(self, packed: PackedRuleset, lines: Iterable[str]):
        self.packer = LinePacker(packed)
        self._lines = lines
        self._has_v6 = packed.has_v6
        self._v6rows: list[tuple] = []
        #: fold_src32 digest -> 128-bit source int (report rendering)
        self.v6_digests: dict[int, int] = {}

    def set_counts(self, parsed: int, skipped: int) -> None:
        self.packer.parsed, self.packer.skipped = parsed, skipped

    def take_v6(self) -> list[tuple]:
        """Drain v6 tuple rows staged since the last call (driver-pulled).

        Drains IN PLACE: the LineBatcher holds a reference to this list,
        so rebinding the attribute would orphan its staging target and
        silently lose every later v6 row.
        """
        out = self._v6rows[:]
        del self._v6rows[:]
        return out

    def batches(self, skip_lines: int, batch_size: int) -> Iterator[tuple[np.ndarray, int]]:
        it = iter(self._lines)
        skipped_ok = 0
        for _ in range(skip_lines):
            if next(it, _SENTINEL) is _SENTINEL:
                break
            skipped_ok += 1
        if skipped_ok < skip_lines:
            from ..errors import ResumeInputMismatch

            raise ResumeInputMismatch(
                f"snapshot consumed {skip_lines} lines but the input "
                f"stream has only {skipped_ok}; wrong or truncated log input"
            )
        # v6 evaluations ride a side channel the driver pulls via take_v6
        # and steps through the v6 device program; they never consume v4
        # batch capacity (LineBatcher stages them into self._v6rows)
        b = LineBatcher(
            self.packer, self._has_v6, self._v6rows, self.v6_digests,
            batch_size,
        )
        for line in it:
            yield from b.push(line)
        tail = b.flush()
        if tail is not None:
            yield tail


class _PackedCounters:
    """parsed/skipped counters for sources that skip the text parse."""

    def __init__(self):
        self.parsed = 0
        self.skipped = 0


class _PackedSource:
    """Batch source over pre-packed ``[TUPLE_COLS, n]`` tuple arrays.

    The packed tier (SURVEY.md synth §"two tiers"): feeds the device
    pipeline at rates the text renderer can't reach — used by the scale
    benchmarks and the sketch-accuracy-at-scale validation.  Incoming
    arrays are re-chunked to exactly ``batch_size`` columns so chunk
    boundaries are identical to a text-path run over the same tuples.
    """

    def __init__(self, arrays: Iterable[np.ndarray]):
        self._arrays = arrays
        self.packer = _PackedCounters()

    def set_counts(self, parsed: int, skipped: int) -> None:
        self.packer.parsed, self.packer.skipped = parsed, skipped

    def batches(self, skip_lines: int, batch_size: int) -> Iterator[tuple[np.ndarray, int]]:
        buf = np.empty((TUPLE_COLS, batch_size), dtype=np.uint32)
        fill = 0
        to_skip = skip_lines
        for arr in self._arrays:
            pos = 0
            n = arr.shape[1]
            if to_skip:
                take = min(to_skip, n)
                pos += take
                to_skip -= take
            while pos < n:
                m = min(batch_size - fill, n - pos)
                buf[:, fill : fill + m] = arr[:, pos : pos + m]
                fill += m
                pos += m
                if fill == batch_size:
                    yield self._emit(buf, fill, batch_size)
                    fill = 0
        if to_skip:
            from ..errors import ResumeInputMismatch

            raise ResumeInputMismatch(
                f"snapshot consumed {skip_lines} lines but the packed input "
                f"ran short by {to_skip}"
            )
        if fill:
            yield self._emit(buf, fill, batch_size)

    def _emit(self, buf, fill, batch_size):
        # always a fresh array: the reusable fill buffer must not be
        # mutated under an in-flight async device_put of a prior chunk
        if fill == batch_size:
            out = buf.copy()
        else:
            out = np.zeros_like(buf)
            out[:, :fill] = buf[:, :fill]
        valid = int(out[T_VALID].sum())
        self.packer.parsed += valid
        self.packer.skipped += fill - valid
        return out, fill


def run_stream_packed(
    packed: PackedRuleset,
    arrays: Iterable[np.ndarray],
    cfg: AnalysisConfig,
    *,
    topk: int = 10,
    mesh=None,
    profile_dir: str | None = None,
    max_chunks: int | None = None,
):
    """Analyze pre-packed ``[TUPLE_COLS, n]`` tuple arrays (packed tier)."""
    with _CallPhases() as phases:
        _arm_retry(cfg)
        return _run_core(
            packed,
            _PackedSource(arrays),
            cfg,
            topk=topk,
            mesh=mesh,
            profile_dir=profile_dir,
            max_chunks=max_chunks,
            phases=phases,
        )


class _WireFileSource:
    """Batch source over on-disk ``.rawire`` files (hostside.wire).

    Yields wire-format ``[WIRE_COLS, batch]`` arrays directly —
    ``yields_wire`` tells the chunk loop to skip the host-side
    ``compact_batch`` (rows already crossed the converter in wire layout)
    and feed ``device_put`` straight from the mmap.  Counters come from
    the stored valid bits, and a stored row whose valid bit is clear —
    impossible from the converter, so necessarily block damage — is a
    typed ``WireCorrupt`` refusal rather than a silent skip-count.
    """

    yields_wire = True

    def __init__(self, packed: PackedRuleset, paths: list[str]):
        from ..hostside.wire import WireReader

        self.reader = WireReader(paths, packed)
        #: weighted (RAWIREv3) input: stored rows are coalesced unique
        #: tuples with a weights plane; parsed counters then count summed
        #: weights (true evaluations) while resume offsets stay in the
        #: stored-row unit this file defines
        self.weighted = self.reader.weighted
        self.yields_wire_weighted = self.weighted
        self.packer = _PackedCounters()
        #: fold digest -> 128-bit source (populated by batches6; report
        #: rendering of v6 talkers, same contract as _TextSource)
        self.v6_digests: dict[int, int] = {}
        self._v6_fill = pack_mod.V6DigestFill(self.v6_digests)

    def set_counts(self, parsed: int, skipped: int) -> None:
        self.packer.parsed, self.packer.skipped = parsed, skipped

    @property
    def n4_rows(self) -> int:
        return self.reader.n_rows

    @staticmethod
    def _check_chunk_weight(ws: int) -> None:
        """Refuse weighted chunks whose summed weights reach 2^32.

        The exact-counts accumulator's carry detection (counts.add64)
        assumes per-chunk deltas < 2^32; a plain chunk satisfies it by
        shape, but a weighted chunk's delta is the ORIGINAL line count
        behind its rows — an extraordinarily repetitive corpus could
        overflow the uint32 scatter undetected.  Loud refusal with a
        concrete fix beats a silently wrapped register.
        """
        from ..config import WEIGHTED_CHUNK_WEIGHT_LIMIT

        if ws >= WEIGHTED_CHUNK_WEIGHT_LIMIT:
            from ..errors import AnalysisError

            raise AnalysisError(
                f"weighted wire chunk carries {ws} original lines, which "
                "overflows the per-chunk uint32 count delta; re-convert "
                "with a smaller --block-rows (or run with a smaller "
                "--batch-size) so each chunk stays under 2^32 lines"
            )

    @staticmethod
    def _corrupt_wire(wire: np.ndarray, rng) -> np.ndarray:
        """Seeded storage-damage model for the ``stream.wire.corrupt`` site.

        Scrambles whole stored rows including their valid/meta word — the
        detectable corruption class the strict reader check below exists
        for.  (Damage confined to the address words of a still-valid row
        is indistinguishable from legitimate data without payload
        checksums; DESIGN §9 records that as the format's open item.)
        """
        from ..hostside.pack import W_META

        wire = wire.copy()  # never write through the read-only mmap view
        for _ in range(1 + rng.randrange(3)):
            j = rng.randrange(wire.shape[1])
            for w in range(wire.shape[0]):
                wire[w, j] ^= np.uint32(rng.getrandbits(32))
            wire[W_META, j] &= np.uint32(~(1 << 23) & 0xFFFFFFFF)
        return wire

    def batches(self, skip_lines: int, batch_size: int) -> Iterator[tuple[np.ndarray, int]]:
        from ..hostside.wire import sanity_check_valid_bits

        # resume offsets count the CONCATENATED v4-then-v6 row stream; an
        # offset past the v4 section means phase 1 is already complete.
        # The truncation/mismatch guard must live HERE against the total:
        # clamping alone would let a wrong or truncated wire input resume
        # "successfully" (iter_batches6's own guard never runs for
        # pure-v4 rulesets, where phase 2 is skipped entirely).
        total = self.reader.n_rows + self.reader.n6_rows
        if skip_lines > total:
            from ..errors import ResumeInputMismatch

            raise ResumeInputMismatch(
                f"snapshot consumed {skip_lines} rows but the wire input "
                f"has only {total}; wrong or truncated input"
            )
        skip4 = min(skip_lines, self.reader.n_rows)
        for wire, n in self.reader.iter_batches(skip4, batch_size):
            wire = faults.fire(
                "stream.wire.corrupt", payload=wire, corrupt=self._corrupt_wire
            )
            v, inv = sanity_check_valid_bits(wire)
            # padding columns of a short final batch are not stored rows
            pad = wire.shape[1] - n
            if inv > pad:
                # the converter stores ONLY valid evaluation rows, so a
                # stored row with the valid bit clear is block damage —
                # refuse loudly rather than silently skip-counting rows
                # of a corrupted production input (bit-identical-or-
                # typed-abort invariant, DESIGN §9)
                from ..errors import WireCorrupt

                raise WireCorrupt(
                    f"wire batch holds {inv - pad} stored row(s) with the "
                    "valid bit clear — the block was damaged after "
                    "conversion; re-run `ruleset-analyze convert` (or "
                    "repair storage) to proceed"
                )
            if self.weighted:
                # each stored row stands for `weight` original evaluations
                from ..hostside.pack import W_WEIGHT

                ws = int(wire[W_WEIGHT].sum())
                self._check_chunk_weight(ws)
                self.packer.parsed += ws
            else:
                self.packer.parsed += v
            self.packer.skipped += inv - pad
            yield wire, n

    def batches6(self, skip_rows6: int, batch_size: int) -> Iterator[tuple[np.ndarray, int]]:
        """Wire-v2 v6 section (consumed after the v4 stream — phase 2)."""
        import numpy as _np

        from ..hostside.pack import W6_META, W6_SRC

        for w6, n in self.reader.iter_batches6(skip_rows6, batch_size):
            v = int(_np.count_nonzero(w6[W6_META] & _np.uint32(1 << 23)))
            if self.weighted:
                from ..hostside.pack import W6_WEIGHT

                ws6 = int(w6[W6_WEIGHT].sum())
                self._check_chunk_weight(ws6)
                self.packer.parsed += ws6
            else:
                self.packer.parsed += v
            self.packer.skipped += (w6.shape[1] - v) - (w6.shape[1] - n)
            self._v6_fill.add(w6[W6_SRC:W6_SRC + 4, :n])
            yield w6, n

    def close(self) -> None:
        """Release the reader's mmaps/fds (called from _run_core's finally)."""
        self.reader.close()

    def totals_patch(self, complete: bool) -> dict:
        """True raw-line accounting once the whole input was consumed.

        Mid-stream, "lines" counts evaluation rows (the unit resume
        offsets use); after a complete pass the report states the
        original text totals recorded by the converter.
        """
        if not complete:
            return {"wire_rows_only": True}
        out = {
            "lines_total": self.reader.raw_lines,
            "lines_skipped": self.reader.n_skipped + self.packer.skipped,
            "wire_rows": self.reader.n_rows + self.reader.n6_rows,
        }
        if self.weighted:
            # stored rows are coalesced: state the true evaluation count
            # and the file's compaction ratio alongside
            out["wire_evals"] = self.reader.n_evals
            out["wire_weighted"] = True
        return out


def run_stream_wire(
    packed: PackedRuleset,
    paths: str | list[str],
    cfg: AnalysisConfig,
    *,
    topk: int = 10,
    mesh=None,
    profile_dir: str | None = None,
    max_chunks: int | None = None,
):
    """Analyze pre-tokenized ``.rawire`` file(s) (the packed ingest tier).

    The production path for repeated/at-scale analysis (SURVEY.md §8.2):
    text parse happens once in ``ruleset-analyze convert``; this run feeds
    the device from the mmap'd wire file, so the bottleneck is the device
    step, not host regex.  Registers and per-rule counts are bit-identical
    to a text run over the same logs.
    """
    if isinstance(paths, str):
        paths = [paths]
    with _CallPhases() as phases:
        # arm BEFORE the source: the wire reader's open/header IO is
        # itself a retry seam, and its attempts must land in THIS run's
        # counters
        _arm_retry(cfg)
        return _run_core(
            packed,
            _WireFileSource(packed, paths),
            cfg,
            topk=topk,
            mesh=mesh,
            profile_dir=profile_dir,
            max_chunks=max_chunks,
            phases=phases,
        )


def _needed_v6_digests(tracker, dig: dict[int, int]) -> dict[int, int]:
    """digest -> address for the sources the tracker tables reference.

    The single definition of "which digests must persist/travel": the
    per-process snapshots, the elastic epoch snapshot, and the final
    distributed report gather all need exactly this set — bounded by
    the top-K capacity, not V6_DIGEST_CAP.
    """
    tag = int(pipeline.V6_ACL_TAG)
    needed = {
        int(s)
        for gid, table in tracker.tables().items()
        if int(gid) & tag
        for s in table
    }
    return {d: dig[d] for d in sorted(needed) if d in dig}


def _v6_digest_extra(source, tracker) -> dict | None:
    """Snapshot payload for the digest->address talker render map.

    The map is collected at PARSE time, so a resumed run only re-sees
    sources appearing after the crash point — pre-crash talkers would
    render as opaque ``v6#xxxx`` digests (a silent report divergence the
    chaos harness caught).
    """
    dig = getattr(source, "v6_digests", None)
    if not dig:
        return None
    rows = [[int(d), int(s)] for d, s in _needed_v6_digests(tracker, dig).items()]
    return {"v6_digests": rows} if rows else None


def _restore_v6_digests(source, snap) -> None:
    """Inverse of :func:`_v6_digest_extra` on resume (pre-PR snapshots
    carry no entry and restore nothing)."""
    dig = getattr(source, "v6_digests", None)
    if dig is None or not snap.extra:
        return
    for d, s in snap.extra.get("v6_digests", []):
        dig.setdefault(int(d), int(s))


class _FileSource:
    """Batch source over syslog file(s) via the native C++ parser."""

    def __init__(self, packed: PackedRuleset, paths: list[str]):
        from ..hostside import fastparse

        self.packer = fastparse.NativePacker(packed)
        self._paths = paths
        self._has_v6 = packed.has_v6
        self.v6_digests: dict[int, int] = {}
        self._v6_fill = pack_mod.V6DigestFill(self.v6_digests)

    def set_counts(self, parsed: int, skipped: int) -> None:
        self.packer.set_counts(parsed, skipped)

    def take_v6(self):
        """v6 rows the native parser staged (driver side channel)."""
        if not self._has_v6:  # a pure-v4 ruleset stages none
            return []
        with self.packer.v6_plane():
            rows = self.packer.take_v6()
            self._v6_fill.add_rows(rows)
        return rows

    def batches(self, skip_lines: int, batch_size: int) -> Iterator[tuple[np.ndarray, int]]:
        from ..hostside import fastparse

        return fastparse.batches_from_files(
            self._paths, self.packer, batch_size, skip_lines=skip_lines
        )


class _ShardCursorSource:
    """Sequential multi-shard source with per-shard resume cursors.

    The elastic tier's input view (runtime/elastic.py): a worker owns a
    LIST of ``(shard_index, path, start_line)`` assignments instead of one
    opaque split, consumes them in order, and tracks how many raw lines of
    each shard have been assigned to emitted batches.  Cursors snapshot at
    batch boundaries, in world-size-independent per-shard units — exactly
    what lets a re-formed cluster of ANY surviving size re-split the
    remaining work and resume with registers covering every consumed line
    exactly once.

    ``die_after_batches`` is TEST-ONLY fault injection (the elastic analog
    of ``max_chunks`` crash simulation): the process exits abruptly —
    ``os._exit``, no teardown — after that many emitted batches, exactly
    as a failing node would mid-collective.
    """

    yields_wire = False

    def __init__(
        self,
        packed: PackedRuleset,
        assignments: list[tuple[int, str, int]],
        native: bool,
        die_after_batches: int | None = None,
        pace_sec: float = 0.0,
    ):
        self._packed = packed
        self._assignments = list(assignments)
        self._native = native
        self._has_v6 = packed.has_v6
        self.v6_digests: dict[int, int] = {}
        self._v6_fill = pack_mod.V6DigestFill(self.v6_digests)
        #: shard_index -> raw lines of that shard assigned to emitted batches
        self.cursors = {int(i): int(start) for i, _p, start in self._assignments}
        self.done: set[int] = set()
        self._die_after = die_after_batches
        #: TEST-ONLY offered-load throttle (RA_ELASTIC_PACE): sleep this
        #: long per emitted batch so autoscale drills observe a stream
        #: that lasts long enough to measure and react to
        self._pace = float(pace_sec or 0.0)
        self._yielded = 0
        self._subs: list[_TextSource] = []
        if native:
            from ..hostside import fastparse

            self.packer = fastparse.NativePacker(packed)
        else:
            self.packer = LinePacker(packed)

    def set_counts(self, parsed: int, skipped: int) -> None:
        if self._native:
            self.packer.set_counts(parsed, skipped)
        else:
            self.packer.parsed, self.packer.skipped = parsed, skipped

    def take_v6(self):
        if self._native:
            rows = self.packer.take_v6()
            self._v6_fill.add_rows(rows)
            return rows
        out: list[tuple] = []
        for sub in self._subs:
            out.extend(sub.take_v6())
        return out

    def cursor_rows(self) -> np.ndarray:
        """``[n, 4]`` uint32 (idx, cursor_lo, cursor_hi, done) rows.

        The shape the per-epoch manifest gather uses
        (parallel.distributed.allgather_rows is uint32-only; cursors split
        into 32-bit limbs so shards past 2^32 lines stay representable).
        """
        rows = [
            (idx, cur & 0xFFFFFFFF, cur >> 32, 1 if idx in self.done else 0)
            for idx, cur in sorted(self.cursors.items())
        ]
        return np.asarray(rows, dtype=np.uint32).reshape(-1, 4)

    def batches(self, skip_lines: int, batch_size: int) -> Iterator[tuple[np.ndarray, int]]:
        if skip_lines:
            from ..errors import AnalysisError

            raise AnalysisError(
                "elastic sources resume via per-shard cursors, not a "
                "global skip offset"
            )
        # deferred: elastic imports this module's driver at call time
        from .elastic import DIE_RC

        for idx, path, start in self._assignments:
            if self._native:
                from ..hostside import fastparse

                it = fastparse.batches_from_files(
                    [path], self.packer, batch_size, skip_lines=start
                )
            else:
                sub = _TextSource(self._packed, _iter_files([path]))
                sub.packer = self.packer  # shared cumulative counters
                sub.v6_digests = self.v6_digests  # shared capped digest map
                self._subs.append(sub)
                it = sub.batches(start, batch_size)
            for batch, n_raw in it:
                # cursor moves as lines are ASSIGNED to a batch, so a
                # snapshot taken after this batch steps (the driver always
                # flushes in-flight work first) covers exactly the lines
                # the cursors claim
                self.cursors[idx] += n_raw
                if self._pace:
                    time.sleep(self._pace)
                yield batch, n_raw
                self._yielded += 1
                # plan-driven twin of die_after_batches: abrupt node
                # death mid-collective (DIE_RC tells the supervisor to
                # propagate it as whole-node death)
                faults.fire("elastic.worker.die", crash_rc=DIE_RC)
                if self._die_after is not None and self._yielded >= self._die_after:
                    # crash injection: abrupt, mid-collective (the exit
                    # code is elastic.DIE_RC — the supervisor propagates
                    # it to simulate whole-node death)
                    os._exit(DIE_RC)
            self.done.add(idx)


def run_stream(
    packed: PackedRuleset,
    lines: Iterable[str],
    cfg: AnalysisConfig,
    *,
    topk: int = 10,
    mesh=None,
    profile_dir: str | None = None,
    max_chunks: int | None = None,
):
    """Run the full analysis over a stream of raw syslog lines; return Report.

    With a multi-device mesh (or by default when several devices are
    visible), the batch shards over the data axis and registers merge via
    ICI collectives; on one device this degenerates to the single-chip
    step.  Results are bit-identical either way (mergeable registers).

    With ``cfg.checkpoint_every_chunks`` set, an atomic (offset, registers)
    snapshot lands in ``cfg.checkpoint_dir`` every N chunks; with
    ``cfg.resume``, an existing snapshot is loaded and that many raw input
    lines are skipped before streaming continues — final registers are
    bit-identical to an uninterrupted run (mergeable state).

    ``max_chunks`` stops after N chunks (fault-injection in tests; also a
    cheap "analyze a prefix" knob).
    """
    with _CallPhases() as phases:
        _arm_retry(cfg)
        return _run_core(
            packed,
            _TextSource(packed, lines),
            cfg,
            topk=topk,
            mesh=mesh,
            profile_dir=profile_dir,
            max_chunks=max_chunks,
            phases=phases,
        )


def run_stream_file(
    packed: PackedRuleset,
    paths: str | list[str],
    cfg: AnalysisConfig,
    *,
    native: bool | None = None,
    topk: int = 10,
    mesh=None,
    profile_dir: str | None = None,
    max_chunks: int | None = None,
    feed_workers: int = 0,
    feed_mode: str = "process",
):
    """Analyze syslog file(s), using the native C++ parser when available.

    ``native=None`` auto-selects: the C++ fast path if its library loads
    (building it on first use), else the pure-Python line path.  Results
    are identical either way; only host-side parse throughput differs.

    With ``cfg.prefetch_depth > 0`` (the default) the parse runs on a
    background producer that keeps a bounded queue of packed,
    device-ready batches ahead of the device step (runtime/ingest.py) —
    host parse, H2D transfer, and device compute overlap instead of
    serializing, with the report bit-identical to the synchronous
    driver.

    ``feed_workers > 1`` parses with that many workers over file shards
    — worker PROCESSES packing into shared memory (``feed_mode=
    "process"``, hostside.feeder.ParallelFeeder) or in-process worker
    THREADS around the GIL-releasing native parser (``feed_mode=
    "thread"``, hostside.feeder.ThreadedFeeder) — the multi-core
    input-split tier.  Chunk boundaries then follow raw-line counts only
    (a dual-evaluation line never closes a batch early; the grouped
    batch is 2x wide instead), so per-chunk candidates may differ from
    the sequential path.  Registers, per-rule counts, and the unused set
    are identical either way (order-invariant mergeable state); the
    top-K talker section is the one approximation whose candidate pool
    is chunk-boundary-sensitive, so borderline talkers can differ
    between feeder and sequential runs.
    """
    from ..hostside import fastparse

    _arm_retry(cfg)
    if isinstance(paths, str):
        paths = [paths]
    use_native = native if native is not None else fastparse.available()
    if feed_mode not in ("process", "thread", "ring"):
        from ..errors import AnalysisError

        raise AnalysisError(
            f"feed_mode must be 'process', 'thread' or 'ring', got {feed_mode!r}"
        )
    if feed_mode == "ring" and not (feed_workers and feed_workers >= 1):
        from ..errors import AnalysisError

        # an explicitly requested topology must never be silently dropped
        raise AnalysisError(
            "feed_mode='ring' needs feed_workers >= 1 (the per-chip "
            "producer pool size)"
        )
    with _CallPhases() as phases:
        if feed_workers and (feed_workers > 1 or feed_mode == "ring"):
            if native is False:
                from ..errors import AnalysisError

                raise AnalysisError(
                    "feed_workers requires the native parser; drop native=False"
                )
            from ..hostside.feeder import ParallelFeeder, RingFeeder, ThreadedFeeder

            feeder_cls = {
                "thread": ThreadedFeeder,
                "process": ParallelFeeder,
                "ring": RingFeeder,
            }[feed_mode]
            source = feeder_cls(
                packed, paths, n_workers=feed_workers,
                stall_timeout=cfg.stall_timeout_sec,
            )
        elif use_native:
            source = _FileSource(packed, paths)
        else:
            source = _TextSource(packed, _iter_files(paths))
        return _run_core(
            packed,
            source,
            cfg,
            topk=topk,
            mesh=mesh,
            profile_dir=profile_dir,
            max_chunks=max_chunks,
            phases=phases,
        )


def run_stream_file_distributed(
    packed: PackedRuleset,
    local_paths: str | list[str],
    cfg: AnalysisConfig,
    *,
    native: bool | None = None,
    topk: int = 10,
    return_state: bool = False,
    max_chunks: int | None = None,
    elastic=None,
):
    """Multi-process analysis: each process feeds ITS OWN input split.

    The reborn Hadoop job (SURVEY.md §3c): ``jax.distributed`` must already
    be initialized (parallel.distributed.init_distributed); the mesh spans
    every device of every process, each process parses only its own files
    (the input-split analog), and the per-chunk global batch is assembled
    with ``jax.make_array_from_process_local_data``.  The SAME shard_map
    step then merges registers with psum/pmax — over ICI within a host,
    DCN between hosts.  Every process returns the identical Report.

    Checkpointing: every process snapshots under its own
    ``checkpoint_dir/proc-<i>-of-<n>`` subdirectory — registers are
    replicated (identical everywhere) but each process must remember its
    OWN offset into its OWN split.  The chunk loop is collective, so all
    processes snapshot at the same chunk count; resume verifies that in
    lockstep and refuses a changed process count.

    ``elastic`` (a ``runtime.elastic.ElasticRunSpec``) switches the run
    into the supervised elastic tier: the source becomes a per-shard
    cursor source over the spec's assignments (``local_paths`` is
    ignored), per-process snapshots are replaced by ONE epoch-tagged,
    world-size-independent checkpoint in ``spec.epoch_dir`` (registers +
    merged cursor manifest, written by the generation's rank 0), and the
    fingerprint deliberately excludes mesh width and process layout so a
    re-formed cluster of any surviving size can resume it.  Driven by
    ``runtime.elastic.ElasticSupervisor``, never called this way directly
    by operators.
    """
    from ..hostside import fastparse
    from ..parallel import distributed as dist
    from ..parallel import mesh as mesh_lib
    from ..parallel.step import make_parallel_step, make_parallel_step_stacked
    from jax.sharding import PartitionSpec as P

    from ..errors import AnalysisError

    stacked = cfg.layout == "stacked"
    if cfg.coalesce != "off":
        # per-process unique-row counts diverge, and to_global assembles
        # ONE global array per round — every process would need the same
        # post-compaction shape.  Weighted .rawire inputs (converted with
        # `convert --coalesce`) are the distributed way to the same win.
        raise AnalysisError(
            "coalesce applies to the single-process stream drivers only; "
            "for distributed runs convert the input with "
            "`ruleset-analyze convert --coalesce` instead"
        )
    if isinstance(local_paths, str):
        local_paths = [local_paths]
    from ..hostside.wire import is_wire_file

    _arm_retry(cfg)  # before the source: wire open IO is a retry seam
    n_wire = sum(1 for p in local_paths if is_wire_file(p))
    if n_wire and n_wire < len(local_paths):
        raise AnalysisError(
            "cannot mix .rawire and text inputs in one --logs list"
        )
    if elastic is not None:
        if native is None:
            native = fastparse.available()
        source = _ShardCursorSource(
            packed,
            elastic.assignments,
            native,
            die_after_batches=elastic.die_after_batches,
            pace_sec=getattr(elastic, "pace_sec", 0.0),
        )
    elif n_wire:
        source = _WireFileSource(packed, local_paths)
    else:
        if native is None:
            native = fastparse.available()
        source = _FileSource(packed, local_paths) if native else _TextSource(
            packed, _iter_files(local_paths)
        )
    # Pipelined ingest, collective edition: the producer thread overlaps
    # THIS process's parse (and, flat text path, the wire bit-pack) with
    # the collective step rounds.  device_put stays on the consumer side
    # here — to_global assembles a multi-process global array and is not
    # produced ahead.  Counters / v6 rows / elastic cursors commit only
    # as batches are consumed, so epoch snapshots record the last
    # COMMITTED batch, never one the producer merely prefetched.
    armed_here = faults.arm_spec(cfg.fault_plan)
    prepacked = False
    if cfg.prefetch_depth > 0:
        from .ingest import PrefetchingSource

        _pack = None
        if not stacked and not n_wire:
            _pack = pack_mod.compact_batch
            prepacked = True
        source = PrefetchingSource(
            source, cfg.prefetch_depth, pack=_pack,
            stall_timeout=cfg.stall_timeout_sec,
        )
    try:
        wire_src = getattr(source, "yields_wire", False)
        wire_weighted = getattr(source, "yields_wire_weighted", False)
        if wire_weighted:
            _check_weighted_input_config(cfg)

        mesh = dist.make_global_mesh(
            cfg.mesh_axis, topology=cfg.mesh_shape, dcn=cfg.mesh_dcn
        )
        # batch axes of the mesh: the flat data axis, or the ("dcn",
        # data) pair of the hybrid topology — one value for every
        # PartitionSpec below
        data_ax = mesh_lib.data_axes(mesh, cfg.mesh_axis)
        pid, nproc = jax.process_index(), jax.process_count()
        global_batch = mesh_lib.pad_batch_size(
            max(cfg.batch_size, 2 if packed.bindings_out else 1) * nproc,
            mesh, cfg.mesh_axis,
        )
        local_batch = global_batch // nproc

        if stacked:
            from ..hostside.pack import GroupBuffer, stack_rules

            # per-GLOBAL-batch lane, sharded over every device; each process
            # contributes its local lane slice from its own group buffer
            lane = cfg.stacked_lane or max(1, cfg.batch_size // max(1, packed.n_acls))
            lane = mesh_lib.pad_batch_size(lane * nproc, mesh, cfg.mesh_axis)
            local_lane = lane // nproc
            rules = pipeline.DeviceRulesetStacked(
                rules3d=dist.to_global(mesh, stack_rules(packed), P()),
                deny_key=dist.to_global(
                    mesh, packed.deny_key.astype(np.uint32), P()
                ),
            )
            step = make_parallel_step_stacked(mesh, cfg, packed.n_keys)
            gbuf = GroupBuffer(max(packed.n_acls, 1), local_lane)
        else:
            rules_host = pipeline.ship_ruleset_host(packed)
            rules = pipeline.DeviceRuleset(
                rules=dist.to_global(mesh, rules_host.rules, P()),
                deny_key=dist.to_global(mesh, rules_host.deny_key, P()),
                rules_fm=None,
            )
            step = make_parallel_step(mesh, cfg, packed.n_keys)
            gbuf = None
        # IPv6 side path (collective twin of _run_core's): v6 rows stage
        # per process at a data-dependent rate, so full chunks drain
        # through the same lockstep ready-round protocol as the stacked
        # layout — every process steps the v6 program the same number of
        # times, padding with all-invalid batches when its queue is dry.
        step6 = None
        rules6_g = None
        if packed.has_v6 and (
            hasattr(source, "take_v6") or hasattr(source, "batches6")
        ):
            from ..parallel.step import make_parallel_step6

            r6h = pipeline.ship_ruleset6_host(packed)
            rules6_g = pipeline.DeviceRuleset6(
                rules6=dist.to_global(mesh, r6h.rules6, P()),
                deny_key=dist.to_global(mesh, r6h.deny_key, P()),
            )
            step6 = make_parallel_step6(mesh, cfg, packed.n_keys)
        ready6: deque[np.ndarray] = deque()  # full [TUPLE6_COLS, local_batch]
        buf6 = None
        fill6 = 0
        packer = source.packer
        pending: deque[pipeline.ChunkOut] = deque()

        # compile work inside this call, priced apart from the sustained
        # rate (the same meter as the single-process driver's)
        from .metrics import dispatch

        compile_meter = CompileMeter()

        from . import checkpoint as ckpt

        # per-process snapshot dir: registers are identical everywhere, but
        # the offset is into THIS process's own input split
        my_ckpt_dir = os.path.join(cfg.checkpoint_dir, f"proc-{pid}-of-{nproc}")
        if elastic is not None:
            # Elastic epoch checkpoints are WORLD-SIZE-INDEPENDENT: the
            # fingerprint pins ruleset + sketch geometry + layout but NOT
            # mesh width or process layout, because re-formation resumes
            # on a smaller world by design.  (Candidate-table chunk
            # boundaries shift across world sizes; the order-invariant
            # registers — exact counts, CMS, HLL — and therefore the
            # unused-rule report cannot.)
            fp = ckpt.fingerprint(packed, cfg, 1, 0) + "-elastic"
        else:
            fp = (
                ckpt.fingerprint(
                    packed, cfg, mesh_lib.data_extent(mesh), local_lane if stacked else 0
                )
                + f"-dist{pid}of{nproc}"
                + (("-wirew" if wire_weighted else "-wire") if wire_src else "")
            )
        lines_consumed = 0
        n_chunks = 0
        snap = None
        if elastic is not None:
            snap = elastic.snapshot
            if snap is not None and snap.fingerprint != fp:
                raise ckpt.CheckpointMismatch(
                    f"elastic epoch snapshot in {elastic.epoch_dir!r} was "
                    "taken with a different ruleset, sketch geometry, or "
                    "layout; refusing to merge"
                )
            # every process read the same epoch file; one tiny allgather
            # catches a stale-storage torn view before any work happens
            chunks_all = dist.value_across_processes(
                snap.n_chunks if snap is not None else -1
            )
            if not (chunks_all == chunks_all[0]).all():
                raise ckpt.CheckpointMismatch(
                    "processes loaded different elastic epoch snapshots "
                    f"({chunks_all.tolist()}); shared storage is inconsistent"
                )
        elif cfg.resume:
            # Every process must reach every allgather: evaluate ALL local
            # conditions first, gather once, and raise the SAME verdict
            # everywhere — a lone early raise would leave the other processes
            # blocked in the next collective instead of surfacing the error.
            layout_err = _dist_ckpt_layout_error(cfg.checkpoint_dir, nproc)
            corrupt_err = None
            if layout_err is None:
                try:
                    snap = ckpt.load(my_ckpt_dir)
                except (ckpt.CheckpointCorrupt, OSError) as e:
                    # a LOCAL raise here would strand the other processes
                    # in the allgather below — classify and gather instead.
                    # OSError too: an unreadable pointer (PermissionError,
                    # IsADirectoryError) is as stranding as a corrupt one.
                    corrupt_err = e
            local_state = 0  # 0 = no snapshot
            if layout_err is not None:
                local_state = 3  # foreign process layout
            elif corrupt_err is not None:
                local_state = 4  # undecodable snapshot on this process
            elif snap is not None:
                local_state = 1 if snap.fingerprint == fp else 2
            states = dist.value_across_processes(local_state)
            chunks_all = dist.value_across_processes(
                snap.n_chunks if snap is not None else -1
            )
            if (states == 4).any():
                raise ckpt.CheckpointCorrupt(
                    str(corrupt_err)
                    if corrupt_err is not None
                    else f"another process found an undecodable snapshot in "
                    f"{cfg.checkpoint_dir!r}"
                )
            if (states == 3).any():
                raise ckpt.CheckpointMismatch(
                    layout_err
                    or f"another process found a foreign process layout in "
                    f"{cfg.checkpoint_dir!r}"
                )
            if (states == 2).any():
                raise ckpt.CheckpointMismatch(
                    f"snapshot under {cfg.checkpoint_dir!r} was taken with a "
                    "different ruleset, geometry, or process layout; refusing "
                    "to merge"
                )
            n_have = int((states == 1).sum())
            if 0 < n_have < nproc:
                raise ckpt.CheckpointMismatch(
                    f"only {n_have}/{nproc} processes found a snapshot in "
                    f"{cfg.checkpoint_dir!r}; all or none must resume"
                )
            if n_have and not (chunks_all == chunks_all[0]).all():
                raise ckpt.CheckpointMismatch(
                    "processes hold snapshots from different chunk counts "
                    f"({chunks_all.tolist()}); the checkpoint is inconsistent"
                )
        if snap is not None:
            state = ckpt.state_of(snap, lambda v: dist.to_global(mesh, v, P()))
            tracker = ckpt.restore_tracker(snap, cfg.sketch.topk_capacity)
            if elastic is not None:
                # the epoch snapshot stores GLOBAL cumulative counters;
                # seed them on rank 0 only — the final totals re-aggregate
                # with sum_across_processes, so base + every rank's new
                # contributions add exactly once
                if pid == 0:
                    source.set_counts(snap.parsed, snap.skipped)
                    lines_consumed = snap.lines_consumed
            else:
                source.set_counts(snap.parsed, snap.skipped)
                lines_consumed = snap.lines_consumed
            # every rank re-seeds the talker render map (merged at save
            # for elastic, per-split otherwise): pre-crash talkers must
            # not render as opaque digests after a resume
            _restore_v6_digests(source, snap)
            n_chunks = snap.n_chunks
        else:
            state_host = pipeline.init_state_host(packed.n_keys, cfg)
            state = pipeline.AnalysisState(
                **{
                    k: dist.to_global(mesh, getattr(state_host, k), P())
                    for k in pipeline.AnalysisState._fields
                }
            )
            tracker = TopKTracker(cfg.sketch.topk_capacity)
        lines_at_start = lines_consumed  # throughput covers this run only

        def drain(out: pipeline.ChunkOut) -> None:
            tracker.offer_chunk(
                np.asarray(out.cand_acl), np.asarray(out.cand_src), np.asarray(out.cand_est)
            )

        def collective_flush() -> None:
            # Snapshot barrier for the stacked layout (VERDICT r3 #4): flush
            # emissions are data-dependent per process, so every process
            # drains its group buffer through the SAME lockstep ready-queue
            # protocol the end-of-stream path uses — processes whose queue ran
            # dry keep stepping padded batches until everyone is empty, so all
            # processes reach the snapshot at the same chunk count with no
            # lines in limbo.
            ready.extend(gbuf.flush())
            while True:
                has = bool(ready)
                if not dist.all_processes_have_data(has):
                    break
                step_grouped_round(has)

        def pull_v6() -> None:
            # stage source-parsed v6 rows; enqueue each full local chunk
            # (text sources; wire v6 rows arrive via the phase-2 loop)
            nonlocal buf6, fill6
            if not hasattr(source, "take_v6"):
                return
            rows = source.take_v6()
            i = 0
            while i < len(rows):
                if buf6 is None:
                    buf6 = np.zeros(
                        (pack_mod.TUPLE6_COLS, local_batch), dtype=np.uint32
                    )
                take = min(local_batch - fill6, len(rows) - i)
                buf6[:, fill6:fill6 + take] = np.asarray(
                    rows[i:i + take], dtype=np.uint32
                ).T
                fill6 += take
                i += take
                if fill6 == local_batch:
                    ready6.append(buf6)
                    buf6 = None
                    fill6 = 0

        def step_v6_round(has: bool) -> None:
            nonlocal state, n_chunks
            b = (
                ready6.popleft()
                if has
                else np.zeros(
                    (pack_mod.TUPLE6_COLS, local_batch), dtype=np.uint32
                )
            )
            gb = dist.to_global(mesh, b, P(None, data_ax))
            state, out = dispatch("v6", step6, state, rules6_g, gb, n_chunks)
            pending.append(out)
            if len(pending) > 2:
                drain(pending.popleft())
            n_chunks += 1

        def drain_v6_rounds() -> None:
            # step full v6 chunks in lockstep; one tiny allgather per round
            # plus a terminating one (skipped entirely for pure-v4 rulesets)
            if step6 is None:
                return
            while True:
                has = bool(ready6)
                if not dist.all_processes_have_data(has):
                    break
                step_v6_round(has)

        def collective_flush_v6() -> None:
            # snapshot/EOF barrier: drain EVERYTHING including the partial
            # chunk, so no consumed line is in limbo across a snapshot
            nonlocal buf6, fill6
            if step6 is None:
                return
            pull_v6()
            if fill6:
                ready6.append(buf6)  # padding columns carry valid=0
                buf6 = None
                fill6 = 0
            drain_v6_rounds()

        def save_epoch_snapshot() -> None:
            # Elastic epoch checkpoint: replicated registers + the merged
            # world-size-independent cursor manifest.  EVERY rank takes
            # part in the gathers (they are collective); only the
            # generation's rank 0 writes, atomically, so survivors of a
            # later failure all load one consistent epoch.
            merged = dist.allgather_rows(source.cursor_rows())
            cursors = dict(elastic.base_cursors)
            done = set(elastic.base_done)
            for r in merged:
                cursors[int(r[0])] = int(r[1]) | (int(r[2]) << 32)
                if int(r[3]):
                    done.add(int(r[0]))
            agg = dist.sum_across_processes(
                {
                    "lines": lines_consumed,
                    "parsed": packer.parsed,
                    "skipped": packer.skipped,
                }
            )
            # each rank only holds digests for ITS split's sources; the
            # epoch snapshot needs the union so ANY surviving world can
            # render every persisted talker candidate (collective: every
            # rank gathers, rank 0 writes)
            dig = getattr(source, "v6_digests", None) or {}
            drows = np.array(
                [
                    (d, *pack_mod.u128_limbs(s))
                    for d, s in _needed_v6_digests(tracker, dig).items()
                ],
                dtype=np.uint32,
            ).reshape(-1, 5)
            dmerged = dist.allgather_rows(drows)
            if pid != 0:
                return
            v6_digest_rows = [
                [int(r[0]), int(pack_mod.limbs_u128(*r[1:5]))] for r in dmerged
            ]
            ckpt.save(
                elastic.epoch_dir,
                ckpt.snapshot_of(
                    state,
                    lines_consumed=agg["lines"],
                    n_chunks=n_chunks,
                    parsed=agg["parsed"],
                    skipped=agg["skipped"],
                    tracker=tracker,
                    fingerprint=fp,
                    extra={
                        **(
                            {"v6_digests": v6_digest_rows}
                            if v6_digest_rows
                            else {}
                        ),
                        "elastic": {
                            "epoch": elastic.epoch,
                            "world": nproc,
                            "shards": list(elastic.shards),
                            "cursors": {
                                str(k): v for k, v in sorted(cursors.items())
                            },
                            "done": sorted(done),
                        }
                    },
                ),
            )

        def save_snapshot() -> None:
            if stacked:
                collective_flush()
            collective_flush_v6()
            while pending:
                drain(pending.popleft())
            pipeline.sync_state(state)
            if elastic is not None:
                save_epoch_snapshot()
                return
            ckpt.save(
                my_ckpt_dir,
                ckpt.snapshot_of(
                    state,
                    lines_consumed=lines_consumed,
                    n_chunks=n_chunks,
                    parsed=packer.parsed,
                    skipped=packer.skipped,
                    tracker=tracker,
                    fingerprint=fp,
                    extra=_v6_digest_extra(source, tracker),
                ),
            )

        from .metrics import ThroughputMeter

        meter = ThroughputMeter(cfg.report_every_chunks)
        # elastic sources resume via their per-shard cursors; the global
        # offset (rank 0's cumulative base) must not be re-skipped
        it = source.batches(
            0 if elastic is not None else lines_consumed, local_batch
        )
        if stacked:
            empty = None
        elif prepacked:
            # padding rounds must match the producer's output layout
            empty = pack_mod.compact_batch(
                np.zeros((TUPLE_COLS, local_batch), dtype=np.uint32)
            )
        else:
            if wire_src:
                empty_cols = (
                    pack_mod.WIREW_COLS if wire_weighted else pack_mod.WIRE_COLS
                )
            else:
                empty_cols = TUPLE_COLS
            empty = np.zeros((empty_cols, local_batch), dtype=np.uint32)
        last_snap_chunks = n_chunks
        chunks_this_run = 0
        aborted = False
        # Stacked: grouped batches emit from the group buffer at a
        # data-dependent cadence, so a ready-queue decouples source pulls from
        # the collective loop — each round steps at most ONE grouped batch per
        # process, and processes whose queue ran dry pad with an all-invalid
        # batch until every queue is empty.
        ready: deque[np.ndarray] = deque()
        src_done = False

        def refill_ready() -> None:
            nonlocal src_done, lines_consumed
            while not ready and not src_done:
                nxt = next(it, None)
                if nxt is None:
                    src_done = True
                    ready.extend(gbuf.flush())
                    return
                batch_np, n_raw = nxt
                lines_consumed += n_raw
                meter.tick(n_raw)
                if batch_np is None:  # zero-valid text batch: lines only
                    continue
                cols = pack_mod.expand_batch(batch_np) if wire_src else batch_np
                ready.extend(gbuf.add(np.ascontiguousarray(cols.T)))

        def next_real():
            # pull the next steppable batch, absorbing zero-valid (None)
            # text batches as pure raw-line accounting — the collective
            # round protocol only ever sees batches that need a step
            nonlocal lines_consumed
            while True:
                nxt = next(it, None)
                if nxt is None or nxt[0] is not None:
                    return nxt
                lines_consumed += nxt[1]
                meter.tick(nxt[1])
                if step6 is not None:
                    pull_v6()

        def step_grouped_round(has: bool) -> None:
            nonlocal state, n_chunks
            grouped = (
                ready.popleft()
                if has
                else np.zeros(
                    (max(packed.n_acls, 1), TUPLE_COLS, local_lane), dtype=np.uint32
                )
            )
            with obs.span("ingest.pack"):
                # a weighted wire input's rows carry weights in T_VALID
                # (expand_batch); the 1-bit compactor would crush them
                wire = (
                    pack_mod.compact_grouped_w(grouped)
                    if wire_weighted
                    else pack_mod.compact_grouped(grouped)
                )
                gbatch = dist.to_global(mesh, wire, P(None, None, data_ax))
            state, out = dispatch("v4", step, state, rules, gbatch, n_chunks)
            pending.append(out)
            if len(pending) > 2:
                drain(pending.popleft())
            n_chunks += 1

        while True:
            if stacked:
                refill_ready()
                has = bool(ready)
            else:
                nxt = next_real()
                has = nxt is not None
            # collective agreement: everyone steps while anyone has data
            if not dist.all_processes_have_data(has):
                break
            if stacked:
                step_grouped_round(has)
            else:
                batch_np, n_raw = nxt if has else (empty, 0)
                lines_consumed += n_raw
                meter.tick(n_raw)
                with obs.span("ingest.pack"):
                    wire = (
                        batch_np
                        if wire_src or prepacked
                        else pack_mod.compact_batch(batch_np)
                    )
                    gbatch = dist.to_global(mesh, wire, P(None, data_ax))
                state, out = dispatch("v4", step, state, rules, gbatch, n_chunks)
                pending.append(out)
                if len(pending) > 2:
                    drain(pending.popleft())
                n_chunks += 1
            if step6 is not None:
                pull_v6()
                drain_v6_rounds()
            chunks_this_run += 1
            # the loop is collective, so every process reaches the cadence at
            # the same n_chunks and snapshots the same register state
            if (
                cfg.checkpoint_every_chunks
                and n_chunks - last_snap_chunks >= cfg.checkpoint_every_chunks
            ):
                save_snapshot()
                last_snap_chunks = n_chunks
            if max_chunks is not None and chunks_this_run >= max_chunks:
                aborted = True  # crash simulation: skip the final snapshot
                break

        if stacked and aborted:
            # drain buffered lines after a max_chunks abort: they are already
            # counted in lines_consumed / the packer counters, and a report
            # claiming lines the registers never saw would be a lie (the same
            # invariant _run_core's post-abort gbuf flush preserves).  The
            # drain stays collective: everyone keeps stepping until every
            # process's queue is dry.
            src_done = True
            ready.extend(gbuf.flush())
            while True:
                has = bool(ready)
                if not dist.all_processes_have_data(has):
                    break
                step_grouped_round(has)
        # Phase 2 — wire-v2 v6 sections, in collective rounds: every
        # process steps while ANY still has v6 rows, padding when dry,
        # so the jitted v6 program's collectives stay aligned.
        b6fn = getattr(source, "batches6", None)
        if b6fn is not None and step6 is not None and not aborted:
            it6 = b6fn(max(0, lines_at_start - source.n4_rows), local_batch)
            while True:
                nxt6 = next(it6, None)
                has6 = nxt6 is not None
                if not dist.all_processes_have_data(has6):
                    break
                if has6:
                    b6, n_rows6 = nxt6
                    lines_consumed += n_rows6
                    meter.tick(n_rows6)
                else:
                    b6 = np.zeros(
                        (
                            pack_mod.WIRE6W_COLS
                            if wire_weighted
                            else pack_mod.WIRE6_COLS,
                            local_batch,
                        ),
                        dtype=np.uint32,
                    )
                gb6 = dist.to_global(mesh, b6, P(None, data_ax))
                state, out = dispatch("v6", step6, state, rules6_g, gb6, n_chunks)
                pending.append(out)
                if len(pending) > 2:
                    drain(pending.popleft())
                n_chunks += 1
                chunks_this_run += 1
                if (
                    cfg.checkpoint_every_chunks
                    and n_chunks - last_snap_chunks >= cfg.checkpoint_every_chunks
                ):
                    save_snapshot()
                    last_snap_chunks = n_chunks
                if max_chunks is not None and chunks_this_run >= max_chunks:
                    aborted = True
                    break

        # v6 rows from consumed lines drain collectively on BOTH the
        # normal and aborted exits (same invariant as the stacked drain)
        collective_flush_v6()

        pipeline.sync_state(state)
        elapsed = meter.elapsed()  # before the final snapshot write (as _run_core)
        if cfg.checkpoint_every_chunks and not aborted:
            save_snapshot()
        while pending:
            drain(pending.popleft())
        local_total, local_skipped = lines_consumed, packer.skipped
        if wire_src and not aborted:
            # restore the converter's raw-line accounting for this process's
            # fully-consumed wire split (rows != raw text lines)
            p = source.totals_patch(True)
            local_total, local_skipped = p["lines_total"], p["lines_skipped"]
        agg = dist.sum_across_processes(
            {
                "lines_total": local_total,
                "lines_matched": packer.parsed,
                "lines_skipped": local_skipped,
                # throughput covers THIS run's lines only (totals above are
                # cumulative across resumes)
                "lines_this_run": lines_consumed - lines_at_start,
            }
        )
        lines_this_run = agg.pop("lines_this_run")
        compiled = compile_meter.summary()
        sustained = elapsed - compiled["sec"]
        totals = {
            **agg,
            "chunks": n_chunks,
            "processes": nproc,
            "elapsed_sec": round(elapsed, 4),
            "lines_per_sec": round(lines_this_run / elapsed, 1) if elapsed > 0 else 0.0,
            # this process's compile work in the call, separated from
            # the sustained rate
            "compile": compiled,
            "sustained_lines_per_sec": (
                round(lines_this_run / sustained, 1) if sustained > 0 else 0.0
            ),
        }
        stats_fn = getattr(source, "ingest_stats", None)
        if stats_fn is not None:
            totals["ingest"] = stats_fn()
        lat_fn = getattr(source, "latency_summary", None)
        if lat_fn is not None:
            lat = lat_fn()
            if lat:
                # produce->commit batch-latency percentiles (DESIGN §20)
                totals["latency"] = lat
        if elastic is not None:
            # which generation of the elastic cluster produced the report
            totals["elastic_epoch"] = elastic.epoch
        v6_digests = getattr(source, "v6_digests", None)
        if step6 is not None:
            # The tracker is replicated but each process's digest map only
            # covers ITS split's sources; gather just the rows the final
            # candidates need (tiny) so every process renders the SAME
            # report — the driver's identical-everywhere contract.
            tag = int(pipeline.V6_ACL_TAG)
            needed = {
                int(s)
                for gid, table in tracker.tables().items()
                if int(gid) & tag
                for s in table
            }
            local = v6_digests or {}
            rows = np.array(
                [
                    (d, *pack_mod.u128_limbs(local[d]))
                    for d in sorted(needed)
                    if d in local
                ],
                dtype=np.uint32,
            ).reshape(-1, 5)
            merged = dist.allgather_rows(rows)
            v6_digests = {
                int(r[0]): pack_mod.limbs_u128(*r[1:5]) for r in merged
            }
        report = pipeline.finalize(
            state, packed, cfg, tracker, topk=topk, totals=totals,
            v6_digests=v6_digests,
        )
        if return_state:
            return report, pipeline.state_to_host(state)
        return report
    finally:
        # release the wire mmaps deterministically: a
        # long-lived driver iterating many wire inputs must not wait
        # for GC to drop file mappings
        close = getattr(source, "close", None)
        if close is not None:
            close()
        if armed_here:
            # a plan this run armed must not leak (env export included)
            # into a later run in the same process
            faults.disarm()


def _check_weighted_input_config(cfg: AnalysisConfig) -> None:
    """Refuse device formulations that are not weight-linear/exact.

    A weighted (RAWIREv3) input reaches the step with weights the config
    validator never saw, so every entry of the ONE declarative
    compatibility table (``config.WEIGHTED_INPUT_REFUSALS`` — shared
    with the config-time ``coalesce`` checks and the static linter,
    which *derives* the same set from the traced jaxprs) is also
    refused here, unconditionally: wire weights are unbounded by the
    stored batch size, so the table's config-time batch bounds do not
    apply.

    ``update_impl='sorted'`` needs NO entry there: every sorted segment
    reduce is weight-linear (sums of the uint32 weight plane) or
    idempotent by construction (DESIGN §15), so weighted inputs are
    accepted everywhere the default scatter path accepts them —
    tests/test_sorted_update.py pins the combination, and the linter
    proves it (tests/test_ralint.py).
    """
    from ..config import WEIGHTED_INPUT_REFUSALS
    from ..errors import AnalysisError

    for r in WEIGHTED_INPUT_REFUSALS:
        if getattr(cfg, r.field) == r.value:
            raise AnalysisError(
                "weighted (coalesced) wire inputs are incompatible with "
                f"{r.field}={r.value!r}: {r.reason}"
            )


def _iter_files(paths: list[str]):
    for path in paths:
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            yield from f


def _dist_ckpt_layout_error(ckpt_dir: str, nproc: int) -> str | None:
    """Error message if resuming this layout would silently restart.

    Snapshot subdirs are named ``proc-<i>-of-<n>``.  Foreign-``n`` dirs
    are only fatal when NO matching-``n`` dirs exist: then a resume would
    find nothing and silently start from scratch even though an (older,
    differently-laid-out) checkpoint is clearly present.  When a complete
    current-layout set coexists with stale dirs, the stale ones are
    ignored.
    """
    import re

    try:
        entries = os.listdir(ckpt_dir)
    except OSError:
        return None
    foreign = set()
    have_matching = False
    for e in entries:
        m = re.fullmatch(r"proc-\d+-of-(\d+)", e)
        if not m:
            continue
        if int(m.group(1)) == nproc:
            have_matching = True
        else:
            foreign.add(int(m.group(1)))
    if foreign and not have_matching:
        return (
            f"{ckpt_dir!r} holds snapshots from a "
            f"{sorted(foreign)[0]}-process run; this job has {nproc} "
            "processes"
        )
    return None


class _CallPhases:
    """The call's own spans, and their seconds for ``totals.phases``.

    ``run.call`` covers the whole driver call.  ``run.setup`` runs from
    its entry to the first step dispatch: source construction, ruleset
    ship, step lookup, state init, the producer's start and the wait for
    the first batch.  ``run.finalize`` runs from after the last dispatch
    to the built report: the state sync, the last candidate drains,
    ``pipeline.finalize``.  All three are on the dispatching thread.
    """

    def __enter__(self):
        self._t0 = time.perf_counter()
        self.compile = CompileMeter()
        self._call = obs.span("run.call")
        self._call.__enter__()
        self._setup = obs.span("run.setup")
        self._setup.__enter__()
        self._fin = None
        self.setup_sec = 0.0
        return self

    def dispatching(self) -> None:
        """Before each step dispatch: the first one ends set-up."""
        if self._setup is not None:
            self.setup_sec = time.perf_counter() - self._t0
            self._setup.__exit__(None, None, None)
            self._setup = None

    def finalizing(self) -> None:
        """After the last dispatch (a call with none ends set-up here)."""
        self.dispatching()
        self._t_fin = time.perf_counter()
        self._fin = obs.span("run.finalize")
        self._fin.__enter__()

    def finalized(self) -> dict:
        """The report is built: close ``run.finalize``; ``totals.phases``."""
        sec = time.perf_counter() - self._t_fin
        self._fin.__exit__(None, None, None)
        self._fin = None
        return {"setup_sec": round(self.setup_sec, 4), "finalize_sec": round(sec, 4)}

    def __exit__(self, *exc):
        for sp in (self._setup, self._fin, self._call):
            if sp is not None:
                sp.__exit__(*exc)
        return False


def _run_core(
    packed: PackedRuleset,
    source,
    cfg: AnalysisConfig,
    *,
    topk: int,
    mesh,
    profile_dir: str | None,
    max_chunks: int | None,
    phases: _CallPhases,
):
    """Run the chunk loop, deterministically closing the source after.

    Sources holding OS resources (the wire reader's mmaps, the ingest
    pipeline's producer threads) expose ``close()``; releasing them here
    instead of at GC time keeps repeated wire runs in one process from
    accumulating file mappings and never strands a prefetch
    producer on a full queue.

    Pipelined ingest (``cfg.prefetch_depth > 0``, runtime/ingest.py)
    wraps the source HERE, so the ``finally`` below closes the wrapper:
    a background producer runs the source iterator (parse / feeder /
    mmap reads) and — for flat layouts — also bit-packs and issues the
    async sharded ``device_put``, so the queue holds device-ready
    batches and H2D of chunk N+k overlaps the step of chunk N.  Reports
    are bit-identical to the synchronous path (batches commit in source
    order).
    """
    from ..parallel import mesh as mesh_lib
    from . import coalesce as coalesce_mod

    armed_here = faults.arm_spec(cfg.fault_plan)
    coal = None
    try:
        if mesh is None:
            mesh = mesh_lib.make_mesh(
                axis=cfg.mesh_axis,
                topology=cfg.mesh_shape,
                dcn=cfg.mesh_dcn,
            )
        # Flow coalescing (ISSUE 5): compact duplicate evaluation tuples
        # into (unique row, weight) pairs before the device step.  The
        # compactor runs inside the pack stage, so under pipelined ingest
        # the O(B) host hash pass runs on the producer thread and
        # overlaps device compute exactly like the wire bit-pack does.
        coal = coalesce_mod.make_coalescer(
            cfg,
            mesh_lib.pad_batch_size(cfg.batch_size, mesh, cfg.mesh_axis),
            mesh_lib.data_extent(mesh),
        )
        if coal is not None:
            obs.register_sampler("coalesce", coal.sample_metrics)
        # per-chip ring feeder (ISSUE 11): resolve the ring count to the
        # mesh's data extent, and pick the consumption mode — per-chip
        # views for the direct device_put path (flat + prefetch), or
        # assembled plain batches everywhere else (sync, stacked)
        ring_src = getattr(source, "yields_ring", False)
        if ring_src:
            if coal is not None:
                from ..errors import AnalysisError

                raise AnalysisError(
                    "runtime coalescing is not available with the ring "
                    "feeder (per-chip shards compact independently, which "
                    "would change batch grouping); pre-coalesce with "
                    "`convert --coalesce` or the convert fleet instead"
                )
            if not getattr(source, "n_rings", None):
                source.n_rings = mesh_lib.data_extent(mesh)
            source.emit_views = (
                cfg.prefetch_depth > 0 and cfg.layout != "stacked"
            )
        device_ready = False
        if cfg.prefetch_depth > 0:
            from ..hostside import pack as _pm
            from .ingest import PrefetchingSource

            pack = None
            if cfg.layout != "stacked":
                axis = cfg.mesh_axis
                wire_src = getattr(source, "yields_wire", False)
                if ring_src:
                    # per-chip compact + device_put straight from each
                    # chip's ring view; no global host-side assembly
                    def pack(rb):
                        return mesh_lib.shard_ring_batch(mesh, rb, axis)
                elif wire_src:
                    def pack(b):
                        if coal is not None and coal.enabled():
                            b = coal.wire4(b)
                        return mesh_lib.shard_batch(mesh, b, axis)
                else:
                    def pack(b):
                        if coal is not None and coal.enabled():
                            wire = _pm.compact_batch_w(coal.tuple4(b))
                        else:
                            wire = _pm.compact_batch(b)
                        return mesh_lib.shard_batch(mesh, wire, axis)
                device_ready = True
            source = PrefetchingSource(
                source, cfg.prefetch_depth, pack=pack,
                stall_timeout=cfg.stall_timeout_sec,
            )
        return _run_core_impl(
            packed,
            source,
            cfg,
            topk=topk,
            mesh=mesh,
            profile_dir=profile_dir,
            max_chunks=max_chunks,
            phases=phases,
            device_ready=device_ready,
            coal=coal,
        )
    finally:
        if coal is not None:
            obs.unregister_sampler("coalesce")
        close = getattr(source, "close", None)
        if close is not None:
            close()
        if armed_here:
            # a plan this run armed must not leak (env export included)
            # into a later run in the same process
            faults.disarm()


def _run_core_impl(
    packed: PackedRuleset,
    source,
    cfg: AnalysisConfig,
    *,
    topk: int,
    mesh,
    profile_dir: str | None,
    max_chunks: int | None,
    phases: _CallPhases,
    device_ready: bool = False,
    coal=None,
):
    from ..parallel import mesh as mesh_lib
    from ..parallel.step import make_parallel_step
    from . import checkpoint as ckpt
    from .metrics import Profiler, ThroughputMeter, dispatch

    # mesh is always resolved by _run_core (it needs it for the prefetch
    # pack closures) before this is called
    batch_size = mesh_lib.pad_batch_size(cfg.batch_size, mesh, cfg.mesh_axis)
    if packed.bindings_out and batch_size < 2:
        from ..errors import AnalysisError

        raise AnalysisError(
            "batch_size must be >= 2 when out-direction access-groups are "
            "bound: one connection line can emit two ACL evaluations"
        )

    stacked = cfg.layout == "stacked"
    lane = 0
    if stacked:
        from ..hostside.pack import GroupBuffer
        from ..parallel.step import make_parallel_step_stacked

        lane = cfg.stacked_lane or max(1, cfg.batch_size // max(1, packed.n_acls))
        lane = mesh_lib.pad_batch_size(lane, mesh, cfg.mesh_axis)
        dev_rules = pipeline.ship_ruleset_stacked(packed)
        step = make_parallel_step_stacked(mesh, cfg, packed.n_keys)
        gbuf = GroupBuffer(max(packed.n_acls, 1), lane)
    else:
        dev_rules = pipeline.ship_ruleset(packed, match_impl=cfg.match_impl)
        step = make_parallel_step(mesh, cfg, packed.n_keys)
        gbuf = None
    # IPv6 side path: sources that parse text stage v6 evaluations in a
    # separate buffer (take_v6); full [TUPLE6_COLS, batch] chunks step
    # through the v6 device program into the SAME registers.  Partial
    # buffers flush at checkpoints and end-of-stream, so snapshots never
    # leave consumed lines unstepped.
    step6 = None
    dev_rules6 = None
    if packed.has_v6 and (
        hasattr(source, "take_v6") or hasattr(source, "batches6")
    ):
        from ..parallel.step import make_parallel_step6

        dev_rules6 = pipeline.ship_ruleset6(packed)
        step6 = make_parallel_step6(mesh, cfg, packed.n_keys)
    buf6 = None
    fill6 = 0
    packer = source.packer
    wire_src = getattr(source, "yields_wire", False)
    #: input rows already carry weights (a coalesced .rawire file): the
    #: grouped compactor must preserve them, and resume offsets count
    #: STORED (unique) rows — a distinct unit from a plain wire file's.
    wire_weighted = getattr(source, "yields_wire_weighted", False)
    #: rows fed to the group buffer may carry weights > 1 (the coalescer
    #: was created — even auto-disabled runs buffered weighted rows
    #: during the sampling window — or the input file is weighted)
    weighted_rows = coal is not None or wire_weighted
    if wire_weighted:
        _check_weighted_input_config(cfg)
    # wire offsets count evaluation rows, text offsets count raw lines —
    # the same snapshot must not resume across input kinds (nor may a
    # weighted wire file's stored-row offsets resume a plain file's)
    fp = ckpt.fingerprint(packed, cfg, mesh_lib.data_extent(mesh), lane) + (
        ("-wirew" if wire_weighted else "-wire") if wire_src else ""
    )
    lines_consumed = 0
    n_chunks = 0

    snap = ckpt.load(cfg.checkpoint_dir) if cfg.resume else None
    if snap is not None:
        if snap.fingerprint != fp:
            raise ckpt.CheckpointMismatch(
                f"snapshot in {cfg.checkpoint_dir!r} was taken with a different "
                "ruleset, sketch geometry, batch size, or device count; "
                "refusing to merge"
            )
        state = ckpt.state_of(
            snap, lambda v: jax.device_put(v, mesh_lib.replicated(mesh))
        )
        tracker = ckpt.restore_tracker(snap, cfg.sketch.topk_capacity)
        source.set_counts(snap.parsed, snap.skipped)
        _restore_v6_digests(source, snap)
        lines_consumed = snap.lines_consumed
        n_chunks = snap.n_chunks
    else:
        state = pipeline.init_state(packed.n_keys, cfg)
        tracker = TopKTracker(cfg.sketch.topk_capacity)

    def drain(item: tuple[int, pipeline.ChunkOut]) -> None:
        # the candidates of the chunk two dispatches back and their top-K
        # offer; where the device sets the pace, the host waits for that
        # chunk here (``step.fetch``)
        bid, out = item
        with obs.span("step.drain", batch=bid):
            with obs.span("step.fetch", batch=bid):
                cand = (np.asarray(out.cand_acl), np.asarray(out.cand_src),
                        np.asarray(out.cand_est))
            tracker.offer_chunk(*cand)

    def save_snapshot() -> None:
        nonlocal last_snap_chunks
        # Stacked layout: step any buffered lines out first so the
        # registers cover exactly lines_consumed (the buffer holds lines
        # back until an ACL's lane fills; a snapshot with lines in limbo
        # would silently drop them on resume).
        if gbuf is not None:
            for grouped in gbuf.flush():
                run_grouped(grouped)
        flush_v6()
        last_snap_chunks = n_chunks
        while pending:
            drain(pending.popleft())
        pipeline.sync_state(state)
        ckpt.save(
            cfg.checkpoint_dir,
            ckpt.snapshot_of(
                state,
                lines_consumed=lines_consumed,
                n_chunks=n_chunks,
                parsed=packer.parsed,
                skipped=packer.skipped,
                tracker=tracker,
                fingerprint=fp,
                extra=_v6_digest_extra(source, tracker),
            ),
        )

    def run_chunk(batch_dev) -> None:
        # salt = chunk index: re-randomizes candidate-table slots per
        # chunk (no persistent talker collisions) yet replays exactly on
        # resume since n_chunks is restored from the snapshot
        nonlocal state, n_chunks
        phases.dispatching()
        state, out = dispatch(
            "v4", step, state, dev_rules, batch_dev, n_chunks, batch=chunks_this_run
        )
        pending.append((chunks_this_run, out))
        if len(pending) > 2:
            drain(pending.popleft())
        n_chunks += 1

    def run_grouped(grouped_np: np.ndarray) -> None:
        # grouped batches also cross the wire bit-packed (16 B/line; the
        # weighted variant adds the 4-byte weights row — rows that may
        # carry weights MUST take it, or compact_grouped's 1-bit valid
        # would silently crush a weight-w row down to one line)
        with obs.span("ingest.pack"):
            wire = (
                pack_mod.compact_grouped_w(grouped_np)
                if weighted_rows
                else pack_mod.compact_grouped(grouped_np)
            )
            batch_dev = mesh_lib.shard_grouped(mesh, wire, cfg.mesh_axis)
        run_chunk(batch_dev)

    # the v6 side path's share of ``totals.ingest``: seconds in its
    # staging on this thread, v6 evaluations taken, v6 step dispatches
    v6_acct = {"v6_stage_sec": 0.0, "v6_lines": 0, "v6_chunks": 0}
    # the talker map after any resume's restore: the digests this call adds
    # are ``v6_digest_inserts`` (where pack.V6DigestFill fills the map, the
    # only v6 rows that reach a Python loop)
    digests_at_start = len(getattr(source, "v6_digests", None) or ())

    @contextlib.contextmanager
    def staging6():
        # the take, the copy into the chunk, coalescing and the
        # shard_batch issue of v6 rows; never the step dispatch
        with obs.span("ingest.stage6", batch=chunks_this_run):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                v6_acct["v6_stage_sec"] += time.perf_counter() - t0

    def run_chunk6(batch6_np: np.ndarray) -> None:
        nonlocal state, n_chunks
        with staging6():
            if coal is not None and coal.enabled():
                # v6 chunks coalesce at step time: tuple batches carry the
                # weights in T6_VALID (no layout change), wire-v2 sections
                # grow the weights row (WIRE6W_COLS)
                if batch6_np.shape[0] == pack_mod.TUPLE6_COLS:
                    batch6_np = coal.tuple6(batch6_np)
                else:
                    batch6_np = coal.wire6(batch6_np)
            batch_dev = mesh_lib.shard_batch(mesh, batch6_np, cfg.mesh_axis)
        phases.dispatching()
        state, out = dispatch(
            "v6", step6, state, dev_rules6, batch_dev, n_chunks, batch=chunks_this_run
        )
        v6_acct["v6_chunks"] += 1
        pending.append((chunks_this_run, out))
        if len(pending) > 2:
            drain(pending.popleft())
        n_chunks += 1

    def stage_v6() -> None:
        # pull staged v6 rows from the source; step full chunks (text
        # sources only — wire v6 rows arrive via the phase-2 batches6)
        nonlocal buf6, fill6
        if not hasattr(source, "take_v6"):
            return
        with staging6():
            rows = source.take_v6()
            v6_acct["v6_lines"] += len(rows)
        i = 0
        while i < len(rows):
            with staging6():
                if buf6 is None:
                    buf6 = np.zeros(
                        (pack_mod.TUPLE6_COLS, batch_size), dtype=np.uint32
                    )
                take = min(batch_size - fill6, len(rows) - i)
                buf6[:, fill6:fill6 + take] = np.asarray(
                    rows[i:i + take], dtype=np.uint32
                ).T
                fill6 += take
                i += take
            if fill6 == batch_size:
                run_chunk6(buf6)  # fresh array allocated next fill
                buf6 = None
                fill6 = 0

    def flush_v6() -> None:
        # partial v6 chunk (padding columns carry valid=0) — called at
        # checkpoints and end-of-stream so consumed lines are never in
        # limbo across a snapshot
        nonlocal buf6, fill6
        if step6 is None:
            return
        stage_v6()
        if fill6:
            run_chunk6(buf6)
            buf6 = None
            fill6 = 0

    # Candidates drain with a 2-chunk lag: by the time chunk N-2's arrays
    # are fetched, their compute is long done, so the host never stalls on
    # the device — and memory stays O(1) chunks instead of O(n_chunks).
    pending: deque[tuple[int, pipeline.ChunkOut]] = deque()  # (batch id, out)
    lines_at_start = lines_consumed  # nonzero after resume
    meter = ThroughputMeter(cfg.report_every_chunks)
    chunks_this_run = 0
    last_snap_chunks = n_chunks  # snapshot cadence is device chunks SINCE
    with Profiler(profile_dir):  # the last save (stacked emits unevenly)
        for batch_np, n_raw_lines in source.batches(lines_consumed, batch_size):
            if batch_np is None:
                # zero-valid text batch (mostly-v6/unparseable stretch):
                # account the raw lines and drain staged v6 rows, but skip
                # the all-invalid v4 device step entirely.  Still ticks
                # chunks_this_run so max_chunks crash simulation aborts at
                # the same source-batch boundary it always did.
                lines_consumed += n_raw_lines
                meter.tick(n_raw_lines)
                if step6 is not None:
                    stage_v6()
                chunks_this_run += 1
                if max_chunks is not None and chunks_this_run >= max_chunks:
                    aborted = True
                    break
                continue
            if gbuf is not None:
                # bucket by ACL; grouped batches emit when a lane fills.
                # Coalescing compacts the batch BEFORE bucketing, so
                # lanes fill at the unique-row rate — more raw lines per
                # grouped device chunk.  (Emission cadence therefore
                # shifts vs the uncoalesced run; registers are cadence-
                # invariant, and the single-emission regime — lane >=
                # per-ACL rows — keeps even candidates identical,
                # DESIGN §11.)
                cols = (
                    pack_mod.expand_batch(batch_np) if wire_src else batch_np
                )
                if coal is not None and coal.enabled():
                    cols = coal.tuple4(cols, pad=False)
                for grouped in gbuf.add(np.ascontiguousarray(cols.T)):
                    run_grouped(grouped)
            elif device_ready:
                # the ingest pipeline already bit-packed the batch and
                # issued its async sharded device_put in the producer
                # thread; the H2D transfer has been overlapping earlier
                # steps since then
                run_chunk(batch_np)
            else:
                # ship the bit-packed wire layout: host->device transfer
                # is the narrowest stage on PCIe-starved links, and the
                # device unpack is three VPU shifts (pipeline.batch_cols)
                with obs.span("ingest.pack"):
                    if coal is not None and coal.enabled():
                        wire = (
                            coal.wire4(batch_np)
                            if wire_src
                            else pack_mod.compact_batch_w(coal.tuple4(batch_np))
                        )
                    else:
                        wire = (
                            batch_np if wire_src
                            else pack_mod.compact_batch(batch_np)
                        )
                    batch_dev = mesh_lib.shard_batch(mesh, wire, cfg.mesh_axis)
                run_chunk(batch_dev)
            if step6 is not None:
                stage_v6()
            lines_consumed += n_raw_lines
            chunks_this_run += 1
            meter.tick(n_raw_lines)
            if (
                cfg.checkpoint_every_chunks
                and n_chunks - last_snap_chunks >= cfg.checkpoint_every_chunks
            ):
                save_snapshot()
            if max_chunks is not None and chunks_this_run >= max_chunks:
                aborted = True
                break
        else:
            aborted = False
    if gbuf is not None:
        # Drain buffered lines (padded grouped batches) — also on a
        # max_chunks abort: those lines are already in lines_consumed and
        # the packer counters, so leaving them unstepped would return a
        # report whose totals claim lines the registers never saw.  (The
        # crash simulation lives in the SKIPPED final snapshot below, not
        # in losing buffered work from the returned report.)
        for grouped in gbuf.flush():
            run_grouped(grouped)
    # v6 rows buffered from consumed lines must step for the same reason
    # the grouped buffer drains above (totals already claim those lines)
    flush_v6()

    # Phase 2 — wire-v2 v6 section: the v6 rows of a .rawire input are
    # stored after every v4 block and consume here, with resume offsets
    # continuing over the concatenated row stream.
    b6fn = getattr(source, "batches6", None)
    if b6fn is not None and step6 is not None and not aborted:
        skip6 = max(0, lines_at_start - source.n4_rows)
        for b6, n_rows6 in b6fn(skip6, batch_size):
            # raw numpy in: run_chunk6 does the single shard_batch itself
            run_chunk6(b6)
            v6_acct["v6_lines"] += n_rows6
            lines_consumed += n_rows6
            chunks_this_run += 1
            meter.tick(n_rows6)
            if (
                cfg.checkpoint_every_chunks
                and n_chunks - last_snap_chunks >= cfg.checkpoint_every_chunks
            ):
                save_snapshot()
            if max_chunks is not None and chunks_this_run >= max_chunks:
                aborted = True
                break

    # every chunk has executed before elapsed() is read: the count
    # register's bytes cannot arrive earlier
    phases.finalizing()
    pipeline.sync_state(state)
    elapsed = meter.elapsed()
    while pending:
        drain(pending.popleft())
    # a max_chunks stop simulates a crash: only periodic snapshots survive
    if cfg.checkpoint_every_chunks and not aborted:
        save_snapshot()

    # lines_total/matched/skipped/chunks are cumulative across resumes;
    # throughput is this run's lines over this run's wall time only.
    # lines_matched counts ACL evaluations (a connection line bound to
    # both an in and an out ACL contributes two); lines_skipped counts
    # raw lines that produced no evaluation.
    lines_this_run = lines_consumed - lines_at_start
    # trace + lower + XLA compile that JAX reported inside this call,
    # priced separately: two committed e2e artifacts once disagreed 7.7x
    # purely on how much of the run was compile
    compiled = phases.compile.summary()
    sustained = elapsed - compiled["sec"]
    totals = {
        "lines_total": lines_consumed,
        "lines_matched": packer.parsed,
        "lines_skipped": packer.skipped,
        "chunks": n_chunks,
        "elapsed_sec": round(elapsed, 4),
        "lines_per_sec": round(lines_this_run / elapsed, 1) if elapsed > 0 else 0.0,
        "compile": compiled,
        "sustained_lines_per_sec": (
            round(lines_this_run / sustained, 1) if sustained > 0 else 0.0
        ),
    }
    stats_fn = getattr(source, "ingest_stats", None)
    if stats_fn is not None:
        # per-stage overlap accounting: parse-starved vs device-bound
        totals["ingest"] = {
            **stats_fn(),
            **v6_acct,
            "v6_stage_sec": round(v6_acct["v6_stage_sec"], 4),
            "v6_digest_inserts": (
                len(getattr(source, "v6_digests", None) or ()) - digests_at_start
            ),
        }
    lat_fn = getattr(source, "latency_summary", None)
    if lat_fn is not None:
        lat = lat_fn()
        if lat:
            # produce->commit batch-latency percentiles (DESIGN §20)
            totals["latency"] = lat
    if coal is not None:
        # raw-vs-unique accounting + the auto decision, in the report so
        # artifacts can state the compaction ratio a run actually saw
        totals["coalesce"] = coal.summary()
    dp = devprof.finalize_if_armed()
    if dp is not None:
        # per-stage device attribution of the capture window (DESIGN
        # §14); VOLATILE in the identity tests — armed vs disarmed
        # reports stay bit-identical outside this block
        totals["devprof"] = dp
    patch = getattr(source, "totals_patch", None)
    if patch is not None:
        # wire input: restore the converter's raw-line accounting once the
        # whole file is consumed (rows != raw text lines)
        totals.update(patch(not aborted))
    rep = pipeline.finalize(
        state, packed, cfg, tracker, topk=topk, totals=totals,
        v6_digests=getattr(source, "v6_digests", None),
    )
    rep.totals["phases"] = phases.finalized()
    return rep
