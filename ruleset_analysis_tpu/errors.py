"""Framework error types (jax-free so the CLI can import them cheaply)."""


class AnalysisError(RuntimeError):
    """Base class for user-facing runtime errors."""


class CheckpointMismatch(AnalysisError):
    """Snapshot belongs to a different ruleset or sketch geometry."""


class CheckpointCorrupt(AnalysisError):
    """The pointed-to snapshot exists but cannot be decoded.

    Raised LOUDLY instead of silently starting the analysis from scratch:
    a truncated/bit-flipped snapshot usually means storage trouble, and a
    fresh-start would discard the operator's resume intent without a
    trace.  Recovery: delete the snapshot directory (or fix the storage)
    and rerun."""


class ResumeInputMismatch(AnalysisError):
    """Input stream is shorter than the snapshot's consumed-line offset."""


class NativeParserUnavailable(AnalysisError):
    """The C++ parser was requested but its library cannot be built/loaded."""


class FeedWorkerError(AnalysisError):
    """A parse feed worker (process or thread) died or reported failure.

    Raised by the multi-worker feed tiers instead of hanging on a
    completion that will never arrive — a worker killed by the OS (OOM),
    a crashed parse, or a poisoned descriptor all surface as this typed
    error within the liveness timeout."""


class IngestError(AnalysisError):
    """The prefetch producer failed with an untyped exception.

    The pipelined ingest engine re-raises producer-side failures at the
    consumer's next pull; failures that are not already AnalysisError
    subclasses are wrapped in this so the chaos invariant — every failed
    run exits with a TYPED error — holds for arbitrary producer bugs
    (the original exception rides ``__cause__``)."""


class StallError(AnalysisError):
    """A bounded-progress watchdog fired: a pipeline stage stopped
    advancing without dying.

    Raised instead of wedging forever when a producer/worker is alive
    but makes no progress within the stall timeout
    (``AnalysisConfig.stall_timeout_sec`` / ``RA_STALL_TIMEOUT``) — a
    hung NFS read, a deadlocked worker, or an injected
    ``ingest.queue.stall`` fault all surface as this typed abort."""


class WireCorrupt(AnalysisError):
    """A stored wire-format row failed its integrity invariant.

    The converter only ever stores valid evaluation rows, so a stored
    (non-padding) row with the valid bit clear means the block was
    damaged after conversion; refusing loudly beats silently skipping
    rows of a corrupted production input."""


class ChipBindingError(AnalysisError):
    """Several processes on one host would each need a chip of their own.

    Raised before any of them starts, by the multi-process modes that
    cannot bind one distinct chip per process on a TPU host (see
    parallel/distributed.py ``check_one_chip_per_process``).
    """


class ReformBudgetExhausted(AnalysisError):
    """The elastic supervisor used up ``--max-reforms`` re-formations."""


class AnalyzerContradiction(AnalysisError):
    """Live hit evidence contradicts a static "provably dead" verdict.

    A rule the analyzer certified as unreachable (shadowed / redundant /
    conflict) recorded hits under the SAME ruleset — one of the two
    planes is wrong (analyzer bug, corrupted rule tensor, or damaged
    counters), and a deletion report built from either would be
    untrustworthy.  Raised loudly instead of publishing the
    contradiction as if both facts could hold (ISSUE 12: "hit +
    shadow-verdict -> typed error, never silent")."""


class WalQuarantine(AnalysisError):
    """The serve ingest write-ahead log refused an unusable segment.

    Raised only when the WAL directory itself cannot be opened or
    created; a CRC-corrupt record inside a segment never raises — the
    segment is quarantined (renamed aside), the lost records are counted
    exactly where the seq arithmetic allows, and replay continues with
    the next segment (DESIGN §19)."""


class SupervisorFenced(AnalysisError):
    """A distributed-serve supervisor lost its leadership lease.

    Raised by the merge/publication plane the moment a stale supervisor
    would otherwise publish: either its own lease renewals have been
    failing longer than the lease TTL (it must assume a successor may
    already hold the lease), or it has OBSERVED a higher fencing term on
    disk (a successor definitely won).  Publishing anyway could produce
    two different publications for one window id — the split-brain
    failure mode the fencing term exists to make impossible — so the
    stale supervisor aborts typed (exit 8) instead.  The successor's
    replay of the durable epoch spools re-publishes anything this
    supervisor had pending, bit-identically (runtime/lease.py,
    DESIGN §23)."""


class InjectedFault(AnalysisError):
    """A deterministic fault fired by an armed plan (runtime/faults.py).

    Typed as AnalysisError on purpose: chaos schedules assert every
    faulted run ends in a typed abort or a bit-identical report, and an
    injected failure crossing an un-wrapping propagation path must not
    break that invariant by surfacing raw."""


# ---------------------------------------------------------------------------
# Transient-vs-permanent classification (DESIGN §19).  The retry engine
# (runtime/retrypolicy.py) consults this at every wrapped seam: a
# TRANSIENT failure is worth re-attempting with backoff (the fault is in
# the environment and may clear — a flaky transfer, EINTR, a socket in
# TIME_WAIT, a saturated disk queue); a PERMANENT one never clears by
# waiting (a typed refusal, a missing file, a permission wall, a
# programming error) and must escalate immediately.  One table, one
# function — so the drivers, the listeners, and the checkpoint plane can
# never disagree about what is worth retrying.
# ---------------------------------------------------------------------------

import errno as _errno

#: OSError errnos that describe environmental, possibly-clearing faults.
TRANSIENT_ERRNOS = frozenset(
    getattr(_errno, name)
    for name in (
        "EAGAIN", "EINTR", "EIO", "EBUSY", "ENOBUFS", "ENOMEM",
        "EADDRINUSE", "ECONNRESET", "ECONNREFUSED", "ECONNABORTED",
        "ENETDOWN", "ENETUNREACH", "ENETRESET", "EHOSTUNREACH",
        "ETIMEDOUT", "EPIPE", "ESTALE", "EDQUOT", "ENOSPC",
    )
    if hasattr(_errno, name)
)

#: Substrings of jax/XLA RuntimeError messages that mark environmental
#: device/runtime faults (gRPC status tokens) rather than program bugs.
TRANSIENT_XLA_TOKENS = ("RESOURCE_EXHAUSTED", "UNAVAILABLE", "DEADLINE_EXCEEDED")


def is_transient(exc: BaseException) -> bool:
    """True when ``exc`` describes a fault a bounded retry may clear.

    Order matters: InjectedFault (the chaos tier's stand-in for exactly
    these environmental faults) is transient by definition, every OTHER
    typed AnalysisError is a deliberate refusal and therefore permanent,
    and the os-level classes split by errno.  Anything unrecognized is
    permanent — retrying an unknown failure can only mask a bug.
    """
    if isinstance(exc, InjectedFault):
        return True
    if isinstance(exc, AnalysisError):
        return False  # typed refusals (corrupt ckpt, mismatch...) never retry
    if isinstance(exc, (FileNotFoundError, PermissionError, IsADirectoryError,
                        NotADirectoryError)):
        return False
    if isinstance(exc, (ConnectionError, InterruptedError, BlockingIOError,
                        TimeoutError)):
        return True  # includes socket.timeout and ECONNRESET et al.
    if isinstance(exc, OSError):
        return exc.errno in TRANSIENT_ERRNOS
    if isinstance(exc, RuntimeError):
        # XlaRuntimeError subclasses RuntimeError; only the gRPC-status
        # environmental classes qualify (a shape error must escalate)
        msg = str(exc)
        return any(tok in msg for tok in TRANSIENT_XLA_TOKENS)
    return False


# ---------------------------------------------------------------------------
# CLI exit codes: supervisors and operators branch on the failure class.
# Documented in README "Exit codes"; keep the two tables in sync.
# ---------------------------------------------------------------------------

EXIT_OK = 0
#: generic analysis error (parse failure, missing input, uncategorized)
EXIT_ANALYSIS = 1
#: bad usage / invalid configuration (argparse-level and ValueError)
EXIT_USAGE = 2
#: a checkpoint exists but cannot be trusted (torn write, bit rot, CRC)
EXIT_CHECKPOINT_CORRUPT = 3
#: checkpoint/resume identity mismatch (foreign ruleset/geometry/input)
EXIT_CHECKPOINT_MISMATCH = 4
#: the feed tier failed (dead worker, corrupt wire block, producer bug)
EXIT_FEED = 5
#: a watchdog bounded a hang (stall, formation timeout)
EXIT_STALL = 6
#: elastic re-formation budget exhausted (--max-reforms)
EXIT_REFORM_BUDGET = 7
#: a distributed-serve supervisor was fenced by a newer leadership term
EXIT_FENCED = 8

#: Human names for the documented codes — the ``doctor`` tool's first
#: lookup (exit codes 3-8 each map to a runbook entry in its diagnosis;
#: see tools/doctor.py and README "Exit codes").
EXIT_CODE_NAMES = {
    EXIT_OK: "ok",
    EXIT_ANALYSIS: "analysis-error",
    EXIT_USAGE: "usage",
    EXIT_CHECKPOINT_CORRUPT: "checkpoint-corrupt",
    EXIT_CHECKPOINT_MISMATCH: "checkpoint-mismatch",
    EXIT_FEED: "feed-failure",
    EXIT_STALL: "stall",
    EXIT_REFORM_BUDGET: "reform-budget-exhausted",
    EXIT_FENCED: "supervisor-fenced",
}


def exit_code_for(exc: BaseException) -> int:
    """Map a typed runtime error to its documented CLI exit code.

    Ordered most-specific-first; anything unrecognized (including plain
    AnalysisError) keeps the historical catch-all code 1.
    """
    if isinstance(exc, CheckpointCorrupt):
        return EXIT_CHECKPOINT_CORRUPT
    if isinstance(exc, (CheckpointMismatch, ResumeInputMismatch)):
        return EXIT_CHECKPOINT_MISMATCH
    if isinstance(exc, StallError):
        return EXIT_STALL
    if isinstance(exc, ReformBudgetExhausted):
        return EXIT_REFORM_BUDGET
    if isinstance(exc, SupervisorFenced):
        return EXIT_FENCED
    if isinstance(
        exc, (FeedWorkerError, IngestError, WireCorrupt, NativeParserUnavailable)
    ):
        return EXIT_FEED
    return EXIT_ANALYSIS
