"""Native fast path for the host parse: ctypes binding to _asaparse.so.

The reference's mapper spends its host CPU in regex parsing (SURVEY.md
§4.3); at TPU-scale feed rates that parse is the end-to-end bottleneck
(SURVEY.md §8.2).  This module loads the C++ parser/packer from
``ruleset_analysis_tpu/native/`` (building it with make/g++ on first use)
and exposes:

- :class:`NativePacker` — drop-in producer of the same column-major
  ``[TUPLE_COLS, B]`` uint32 batches as ``LinePacker.pack_lines(...).T``,
  but straight from raw bytes;
- :func:`batches_from_file` — stream a syslog file (or byte stream) as
  device-ready batches of ``batch_size`` raw lines each.

If no C++ toolchain is available the import still succeeds and
``available()`` returns False; callers fall back to the pure-Python path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from collections.abc import Iterator

import numpy as np

from .pack import PackedRuleset, TUPLE_COLS, TUPLE6_COLS

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "_asaparse.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

#: Bytes per read when streaming a file through the native parser.
READ_BLOCK = 8 << 20


def _as_buffer(data: bytes | bytearray | memoryview):
    """ctypes argument for a readable buffer, without copying.

    bytes pass through (immutable, ctypes pins them); a numpy array passes
    its data pointer (the caller keeps it alive); bytearray/memoryview
    get a zero-copy ``from_buffer`` view — the caller must drop the
    returned object before resizing the underlying buffer.
    """
    if isinstance(data, bytes):
        return data
    if isinstance(data, np.ndarray):
        return data.ctypes.data
    return (ctypes.c_char * len(data)).from_buffer(data)


def host_workers(env_var: str, cap: int) -> int:
    """Worker-count heuristic: ``env_var`` override, else usable cores."""
    env = os.environ.get(env_var)
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    try:
        n = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        n = os.cpu_count() or 1
    return max(1, min(n, cap))


def default_parse_threads() -> int:
    """Parse threads for the native path: RA_PARSE_THREADS or CPU count.

    On a one-core host this degenerates to the single-threaded parse; on a
    real accelerator host (a v5e-8 host has dozens of cores) the batch
    splits across workers (SURVEY.md §2 L2 — the input-split analog).
    """
    return host_workers("RA_PARSE_THREADS", 32)


def _build() -> bool:
    try:
        r = subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            capture_output=True,
            text=True,
            timeout=120,
        )
        return r.returncode == 0 and os.path.exists(_LIB_PATH)
    except (OSError, subprocess.TimeoutExpired):
        return False


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        # always invoke make: it is a fast no-op when the .so is current,
        # and rebuilds it when asaparse.cpp changed (a stale library would
        # silently miss newer ABI symbols)
        if not _build() and not os.path.exists(_LIB_PATH):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            _bind(lib)
        except (OSError, AttributeError):
            # AttributeError: a stale .so predating the current ABI with
            # no toolchain to rebuild — fall back to the Python parser
            return None
        _lib = lib
        return _lib


def _bind(lib: ctypes.CDLL) -> None:
        lib.asa_packer_new.restype = ctypes.c_void_p
        lib.asa_packer_free.argtypes = [ctypes.c_void_p]
        lib.asa_packer_add_acl.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint32,
        ]
        lib.asa_packer_add_binding.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint32,
        ]
        lib.asa_packer_add_binding_out.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint32,
        ]
        lib.asa_packer_parsed.argtypes = [ctypes.c_void_p]
        lib.asa_packer_parsed.restype = ctypes.c_int64
        lib.asa_packer_skipped.argtypes = [ctypes.c_void_p]
        lib.asa_packer_skipped.restype = ctypes.c_int64
        lib.asa_packer_set_counts.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]
        # buf params are c_void_p (not c_char_p) so immutable bytes and
        # zero-copy views of a reusable buffer can both be passed
        i64p = ctypes.POINTER(ctypes.c_int64)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.asa_pack_batch.argtypes = [
            ctypes.c_void_p,  # packer
            ctypes.c_void_p,  # buf
            ctypes.c_int64,  # len
            ctypes.c_int,  # final
            ctypes.c_int64,  # max_lines
            u32p,  # out [TUPLE_COLS, cap]
            ctypes.c_int64,  # cap
            u32p,  # out6 [TUPLE6_COLS, cap6], NULL for a v4-only ruleset
            ctypes.c_int64,  # cap6
            i64p,  # n_lines_out
            i64p,  # n_valid_out
            i64p,  # n_valid6_out
            ctypes.c_int,  # n_threads
            ctypes.c_int,  # full_batch
            ctypes.POINTER(ctypes.c_int),  # workers_out
        ]
        lib.asa_pack_batch.restype = ctypes.c_int64
        lib.asa_count_lines.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.asa_count_lines.restype = ctypes.c_int64
        lib.asa_count_nl.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.asa_count_nl.restype = ctypes.c_int64
        # flow coalescing (ISSUE 5): open-addressing batch compaction
        lib.asa_coalesce.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.asa_coalesce.restype = ctypes.c_int64
        # SIMD tokenizer dispatch (ISSUE 11): runtime probe + A/B switch
        lib.asa_simd_kind.argtypes = []
        lib.asa_simd_kind.restype = ctypes.c_int
        lib.asa_simd_set.argtypes = [ctypes.c_int]


def available() -> bool:
    """True if the native parser library is loadable (building if needed)."""
    return _load() is not None


#: asa_simd_kind() codes -> human-readable ISA names.
_SIMD_KINDS = {0: "scalar", 1: "avx2", 2: "neon"}


def simd_kind() -> str:
    """Active tokenizer dispatch: ``"avx2"``/``"neon"``/``"scalar"``.

    ``"scalar"`` means the CPU lacks both ISAs, the library is not
    loadable, or ``RA_SIMD=off`` (the A/B override) disabled dispatch.
    """
    lib = _load()
    if lib is None:
        return "scalar"
    return _SIMD_KINDS.get(int(lib.asa_simd_kind()), "scalar")


def simd_active() -> bool:
    """True when a vectorized scan implementation is dispatched."""
    return simd_kind() != "scalar"


def set_simd(on: bool) -> str:
    """Force the tokenizer dispatch on/off at runtime; returns the
    resulting :func:`simd_kind`.

    The in-process twin of the ``RA_SIMD=off`` env override: the
    identity sweep and the feedscale bench flip this to compare scalar
    and SIMD parses of the same bytes in one process.  ``set_simd(True)``
    on a CPU without AVX2/NEON is a no-op (stays ``"scalar"``).
    """
    lib = _load()
    if lib is not None:
        lib.asa_simd_set(1 if on else 0)
    return simd_kind()


def native_coalesce(
    mat: np.ndarray, want_first: bool = False
) -> tuple[np.ndarray, np.ndarray | None] | None:
    """Native batch compaction, or None when the library is unavailable.

    ``mat`` is a C-contiguous ``[rows, B]`` uint32 plane whose LAST row
    is the weight/valid plane (see ``pack.coalesce_cols``, which owns the
    numpy fallback and the output contract — first-occurrence order,
    summed weights).  The hash pass releases the GIL (ctypes), so under
    the pipelined ingest producer it overlaps the device step.
    """
    lib = _load()
    if lib is None:
        return None
    rows, b = mat.shape
    if not mat.flags.c_contiguous:
        mat = np.ascontiguousarray(mat)
    scratch = np.empty((rows, b), dtype=np.uint32)
    first = np.empty(b, dtype=np.int64) if want_first else None
    u = int(
        lib.asa_coalesce(
            mat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            rows,
            b,
            scratch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            first.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
            if first is not None
            else None,
        )
    )
    out = np.ascontiguousarray(scratch[:, :u])
    return out, (first[:u].copy() if first is not None else None)


class NativePacker:
    """Raw syslog bytes -> column-major [TUPLE_COLS, B] uint32 batches.

    Mirrors ``LinePacker`` exactly: the (firewall, acl)->gid and
    (firewall, iface)->gid resolution tables (both in- and out-direction)
    come from the same PackedRuleset, unresolvable or unparseable lines
    count as skipped, and valid tuples are packed densely from row 0.
    A connection line whose ingress interface has an ``in`` ACL and whose
    egress interface has an ``out`` ACL emits two rows; ``parsed`` counts
    evaluations, ``skipped`` counts lines that produced none.
    """

    def __init__(self, packed: PackedRuleset):
        from ..errors import NativeParserUnavailable

        lib = _load()
        if lib is None:
            raise NativeParserUnavailable(
                "native parser unavailable (no C++ toolchain to build "
                "ruleset_analysis_tpu/native/_asaparse.so?)"
            )
        self._lib = lib
        self._h = ctypes.c_void_p(lib.asa_packer_new())
        for (fw, acl), gid in packed.acl_gid.items():
            lib.asa_packer_add_acl(self._h, fw.encode(), acl.encode(), gid)
        for (fw, iface), gid in packed.bindings.items():
            lib.asa_packer_add_binding(self._h, fw.encode(), iface.encode(), gid)
        for (fw, iface), gid in packed.bindings_out.items():
            lib.asa_packer_add_binding_out(self._h, fw.encode(), iface.encode(), gid)
        #: with out-bindings a connection line can emit two rows; sizes
        #: the default pack_lines capacity like LinePacker.pack_parsed
        self._rows_per_line = 2 if packed.bindings_out else 1
        #: v6-capable ruleset: parse through the dual-family native entry
        #: and stage v6 rows for the driver's take_v6 side channel
        self._has_v6 = packed.has_v6
        self._staged6: list[np.ndarray] = []
        #: workers the last batch parsed in parallel used (0: none yet),
        #: and batches that took the one-thread loop
        self.parse_workers = 0
        self.parse_serial_batches = 0

    def take_v6(self):
        """Drain staged v6 rows as ONE ``[n, TUPLE6_COLS]`` uint32 array.

        Only meaningful for v6-capable rulesets; the stream driver pulls
        this after every batch, exactly as with the Python text source.
        Returned whole (not per-row objects) so consumers slice/transpose
        vectorized — per-row Python views would negate the native parse
        speed on v6-heavy corpora.  Empty list when nothing staged.
        """
        staged = self._staged6
        self._staged6 = []
        if not staged:
            return []
        if len(staged) == 1:
            return staged[0]
        return np.concatenate(staged)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.asa_packer_free(h)
            self._h = None

    @property
    def parsed(self) -> int:
        return int(self._lib.asa_packer_parsed(self._h))

    @property
    def skipped(self) -> int:
        return int(self._lib.asa_packer_skipped(self._h))

    def set_counts(self, parsed: int, skipped: int) -> None:
        """Restore cumulative counters (checkpoint resume)."""
        self._lib.asa_packer_set_counts(self._h, parsed, skipped)

    def pack_chunk(
        self,
        data: bytes | bytearray | memoryview,
        batch_size: int,
        *,
        final: bool,
        max_lines: int | None = None,
        n_threads: int | None = None,
        length: int | None = None,
        out: np.ndarray | None = None,
        full_batch: bool = False,
    ) -> tuple[np.ndarray | None, int, int]:
        """Parse up to ``max_lines`` (default batch_size) lines from data.

        Returns (batch [TUPLE_COLS, batch_size] uint32, lines_consumed,
        bytes_consumed).  With ``final=False`` a trailing fragment without
        a newline is left unconsumed — feed it back with the next block.
        ``n_threads`` (default :func:`default_parse_threads`) splits the
        parse across native workers; output is bit-identical for any
        thread count.  ``length`` limits the parse to ``data[:length]``
        (zero-copy prefix of a reusable buffer).  ``out`` supplies a
        preallocated ``[TUPLE_COLS, batch_size]`` uint32 C-contiguous
        destination (e.g. a shared-memory view) instead of a fresh array.
        With ``full_batch`` and ``final=False``, data holding fewer than
        ``max_lines`` complete lines is left unparsed: the call returns
        ``(None, complete_lines_held, 0)`` so the caller can read more.
        """
        n = len(data) if length is None else length
        arg = _as_buffer(data)
        if out is None:
            out = np.empty((TUPLE_COLS, batch_size), dtype=np.uint32)
        else:
            if out.shape != (TUPLE_COLS, batch_size) or out.dtype != np.uint32:
                raise ValueError(
                    f"out must be [TUPLE_COLS, {batch_size}] uint32, got "
                    f"{out.shape} {out.dtype}"
                )
            if not out.flags.c_contiguous:
                raise ValueError("out must be C-contiguous")
        n_lines = ctypes.c_int64(0)
        n_valid = ctypes.c_int64(0)
        n_valid6 = ctypes.c_int64(0)
        workers = ctypes.c_int(0)
        ml = max_lines if max_lines is not None else batch_size
        u32p = ctypes.POINTER(ctypes.c_uint32)
        # v6-capable ruleset: the v6 plane is sized 2*max_lines so v6 rows
        # never close a batch, mirroring the Python text source's side
        # buffer
        cap6 = 2 * ml if self._has_v6 else 0
        out6 = np.empty((TUPLE6_COLS, cap6), dtype=np.uint32) if cap6 else None
        used = int(
            self._lib.asa_pack_batch(
                self._h,
                arg,
                n,
                1 if final else 0,
                ml,
                out.ctypes.data_as(u32p),
                batch_size,
                out6.ctypes.data_as(u32p) if out6 is not None else None,
                cap6,
                ctypes.byref(n_lines),
                ctypes.byref(n_valid),
                ctypes.byref(n_valid6),
                n_threads if n_threads is not None else default_parse_threads(),
                1 if full_batch else 0,
                ctypes.byref(workers),
            )
        )
        del arg  # release any buffer export before the caller resizes
        if used < 0:
            return None, int(n_lines.value), 0
        if workers.value > 1:
            self.parse_workers = workers.value
        else:
            self.parse_serial_batches += 1
        if out6 is not None and n_valid6.value:
            self._staged6.append(np.ascontiguousarray(out6[:, : n_valid6.value].T))
        return out, int(n_lines.value), used

    def _pack_lines_v4(self, lines: list[str], batch_size: int | None) -> np.ndarray:
        """The shared v4-plane pack (v6 rows, if any, land in _staged6)."""
        data = "".join(ln if ln.endswith("\n") else ln + "\n" for ln in lines).encode()
        b = batch_size if batch_size is not None else self._rows_per_line * len(lines)
        out, _, _ = self.pack_chunk(data, b, final=True, max_lines=len(lines))
        return np.ascontiguousarray(out.T)

    def pack_lines(self, lines: list[str], batch_size: int | None = None) -> np.ndarray:
        """LinePacker-compatible helper (row-major [B, TUPLE_COLS]).

        Returns the v4 plane only; v6 evaluations the parse produced stay
        staged for :meth:`take_v6`, exactly like the chunk API and the
        streaming drivers (ISSUE 11 closed the last v6-refusing tier, so
        this call follows the same side-channel contract instead of the
        old loud v4-only refusal).  Callers that never drain
        :meth:`take_v6` on a unified corpus would accumulate staged rows
        — the historical reason for the refusal (ADVICE r5 #2) — so
        prefer :meth:`pack_lines2` when v6 traffic is possible.
        """
        return self._pack_lines_v4(lines, batch_size)

    def pack_lines2(
        self, lines: list[str], batch_size: int | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """LinePacker.pack_lines2-compatible helper (padded row-major pair)."""
        b4 = self._pack_lines_v4(lines, batch_size)
        b = b4.shape[0]
        rows6 = self.take_v6()
        out6 = np.zeros((b if self._has_v6 else 0, TUPLE6_COLS), dtype=np.uint32)
        for i, r in enumerate(rows6):
            out6[i] = r
        return b4, out6


class _ChainedReader:
    """Several files as one byte stream, with line-boundary parity.

    A file whose last line is unterminated still contributes that line as
    a line of its own on the text path (``yield from f``); to keep the
    byte stream identical, a ``\\n`` is synthesized at any file boundary
    where the previous file did not end with one.
    """

    def __init__(self, paths: list[str]):
        self._paths = list(paths)
        self._i = 0
        self._f = None
        self._last = b"\n"

    def read(self, n: int) -> bytes:
        while True:
            if self._f is None:
                if self._i >= len(self._paths):
                    return b""
                self._f = open(self._paths[self._i], "rb")
                self._i += 1
            block = self._f.read(n)
            if block:
                self._last = block[-1:]
                return block
            self._f.close()
            self._f = None
            if self._last != b"\n":
                self._last = b"\n"
                return b"\n"

    def readinto(self, view: memoryview) -> int:
        """Fill ``view`` from the stream; 0 only at end of all files."""
        while True:
            if self._f is None:
                if self._i >= len(self._paths):
                    return 0
                self._f = open(self._paths[self._i], "rb")
                self._i += 1
            n = self._f.readinto(view)
            if n:
                self._last = bytes(view[n - 1 : n])
                return n
            self._f.close()
            self._f = None
            if self._last != b"\n":
                self._last = b"\n"
                view[0:1] = b"\n"
                return 1

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


def batches_from_files(
    paths: list[str],
    packer: NativePacker,
    batch_size: int,
    *,
    skip_lines: int = 0,
    read_block: int = READ_BLOCK,
) -> Iterator[tuple[np.ndarray, int]]:
    """Yield (batch [TUPLE_COLS, batch_size], raw_line_count) over files.

    The files are chained into one stream, so batch boundaries fall
    exactly where the pure-Python text path puts them — per-chunk outputs
    (top-K candidates) match, not just the merged registers.
    ``skip_lines`` raw lines are skipped first without parsing
    (checkpoint resume); raises if the input has fewer lines than that.
    """
    lib = packer._lib
    reader = _ChainedReader(paths)
    try:
        # Buffer management: one reusable buffer filled with readinto — no
        # per-block copies, no join.  (A naive ``rem += block`` chain
        # re-copies the accumulated buffer per read — ~1.7 GB of memcpy
        # per 1M-line batch — and was measured to cost 4x end-to-end
        # throughput.)  It is an uninitialised numpy array, grown only
        # when a batch needs more bytes than it holds (once, after the
        # first block shows the bytes per line), so no zero-fill runs on
        # this thread.  After each batch the unconsumed tail (about
        # read_block bytes) moves to the front.
        buf = np.empty(2 * read_block, dtype=np.uint8)
        filled = 0  # bytes of buf holding live data
        eof = False

        def fill(target: int) -> None:
            """Read blocks until ``target`` bytes are held or input ends."""
            nonlocal buf, filled, eof
            while not eof and filled < target:
                if len(buf) < filled + read_block:
                    grown = np.empty(max(target, filled) + read_block, dtype=np.uint8)
                    grown[:filled] = buf[:filled]
                    buf = grown
                n = reader.readinto(memoryview(buf)[filled : filled + read_block])
                if n == 0:
                    eof = True
                else:
                    filled += n

        def consume(used: int) -> None:
            """Drop buf[:used]; move the tail to the front."""
            nonlocal filled
            if used == 0:
                return
            tail = filled - used
            ctypes.memmove(buf.ctypes.data, buf.ctypes.data + used, tail)
            filled = tail

        # ---- resume fast-skip
        to_skip = skip_lines
        while to_skip > 0:
            if filled == 0 and not eof:
                fill(1)
            if filled == 0 and eof:
                from ..errors import ResumeInputMismatch

                raise ResumeInputMismatch(
                    f"snapshot consumed {skip_lines} lines but the input has "
                    f"only {skip_lines - to_skip}; wrong or truncated log input"
                )
            bytes_used = ctypes.c_int64(0)
            arg = _as_buffer(buf)
            skipped = lib.asa_count_lines(
                arg, filled, 1 if eof else 0, to_skip, ctypes.byref(bytes_used)
            )
            del arg
            to_skip -= int(skipped)
            consume(int(bytes_used.value))
            if to_skip > 0 and int(skipped) == 0:
                # newline-free fragment: grow the buffer to make progress
                fill(filled + 1)
        # ---- stream batches
        # Each batch holds batch_size COMPLETE lines (fewer only at the end
        # of the input) and closes line-atomically: at most batch_size raw
        # lines AND at most batch_size tuple rows — with out-direction
        # bindings a dual-evaluation line can close a batch early, exactly
        # as _TextSource does, so chunk boundaries — and therefore
        # per-chunk top-K candidates and resume offsets — land exactly
        # where the pure-Python text path puts them.  The native call
        # counts the lines itself, in its workers, and says when the
        # buffer holds too few; the reads are sized from the bytes per
        # line seen so far, plus one block, so that is rare.
        target = read_block
        while True:
            fill(target)
            if filled == 0 and eof:
                return
            batch, n_lines, used = packer.pack_chunk(
                buf, batch_size, final=eof, length=filled, full_batch=True
            )
            if batch is None:
                # fewer than batch_size complete lines held (a line may be
                # longer than the buffered bytes): read one block more than
                # the missing lines take at the bytes per line held, and at
                # most 32 times what is held, so a few long lines cannot
                # size the buffer for a whole batch of them
                missing = batch_size - n_lines
                more = missing * filled // n_lines if n_lines else 0
                target = filled + read_block + min(more, 32 * filled)
                continue
            consume(used)
            if n_lines == 0:  # nothing left at the end of the input
                return
            target = used * batch_size // n_lines + read_block
            yield batch, n_lines
    finally:
        reader.close()


def batches_from_file(
    path: str,
    packer: NativePacker,
    batch_size: int,
    *,
    skip_lines: int = 0,
    read_block: int = READ_BLOCK,
) -> Iterator[tuple[np.ndarray, int]]:
    """Single-file convenience wrapper over :func:`batches_from_files`."""
    return batches_from_files(
        [path], packer, batch_size, skip_lines=skip_lines, read_block=read_block
    )


def count_lines_in_file(path: str, read_block: int = READ_BLOCK) -> int:
    """Raw line count (trailing unterminated fragment counts as a line)."""
    n = 0
    tail_fragment = False
    with open(path, "rb") as f:
        while True:
            block = f.read(read_block)
            if not block:
                break
            n += block.count(b"\n")
            tail_fragment = not block.endswith(b"\n")
    return n + (1 if tail_fragment else 0)
