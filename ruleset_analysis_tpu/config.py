"""Central configuration (the reference's ``config.py`` analog, SURVEY.md L0).

The reference keeps module-level constants naming the firewall inventory and
job paths; scripts ``import config`` and read them.  We keep that shape for
compatibility (module constants below) and add a typed, immutable
:class:`AnalysisConfig` used by the CLI and runtime, since the TPU path has
real tunables (batch size, sketch geometry, mesh shape) that the Hadoop path
never needed.
"""

from __future__ import annotations

import dataclasses
import os

# ---------------------------------------------------------------------------
# Reference-style module constants (SURVEY.md §3: "module-level constants:
# firewall list, credentials/paths, HDFS/job paths").  Paths are local rather
# than HDFS; the firewall inventory maps a firewall name to the path of its
# saved configuration.
# ---------------------------------------------------------------------------

#: Firewall inventory: name -> path of the saved ASA configuration file.
FIREWALLS: dict[str, str] = {}

#: Directory where `parse-acls` (the getaccesslists analog) writes parsed,
#: serialized rulesets.
RULESET_DIR = os.environ.get("RA_RULESET_DIR", "rulesets")

#: Directory for analysis outputs (reports, checkpoints).
OUTPUT_DIR = os.environ.get("RA_OUTPUT_DIR", "out")


# ---------------------------------------------------------------------------
# Typed runtime configuration.
# ---------------------------------------------------------------------------


#: Maximum CMS depth — ops/hashing.py guarantees this many independent
#: multiply-shift constants (asserted there against MS_CONSTANTS).
MAX_CMS_DEPTH = 8


# ---------------------------------------------------------------------------
# Weighted-input compatibility — ONE declarative table (DESIGN §11/§18).
#
# A weighted batch (coalesced on the fly, or a RAWIREv3 wire file whose
# rows carry original-line weights) is only correct through device
# formulations that are weight-linear (adds scale with the weight plane)
# or idempotent (max gates on weight>0).  Three consumers read this
# table so the refusal set can never drift between them:
#
# - AnalysisConfig.__post_init__ — config-time refusal of `coalesce`
#   with an incompatible impl choice;
# - runtime/stream.py::_check_weighted_input_config — run-time refusal
#   when a weighted WIRE input reaches a driver whose config the
#   validator accepted (it never saw the input's weights);
# - ruleset_analysis_tpu/verify — the static linter DERIVES each impl
#   combination's weight-linearity verdict from its traced jaxpr and
#   cross-checks the derived refusal set against exactly this table
#   (tests/test_ralint.py pins the equality).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WeightedRefusal:
    """One impl choice that cannot accept weighted (coalesced) inputs."""

    #: AnalysisConfig field and value naming the incompatible choice.
    field: str
    value: str
    #: Human reason, embedded in both refusal messages.
    reason: str
    #: The linearity verdict the static linter must derive for programs
    #: built with this choice ("unprovable" = opaque kernel the taint
    #: walk cannot enter; "float-bounded" = linear but through an f32
    #: formulation whose exactness is range-bounded).
    lint_verdict: str
    #: Config-time `coalesce` refusal bound: None = refuse always;
    #: an int N = refuse only when batch_size >= N (below it the
    #: formulation's own guards keep the combination exact).
    coalesce_min_batch: int | None = None


WEIGHTED_INPUT_REFUSALS: tuple[WeightedRefusal, ...] = (
    WeightedRefusal(
        field="match_impl",
        value="pallas_fused",
        reason=(
            "the experimental pallas_fused kernel's in-VMEM count "
            "histogram is not weight-linear (it adds ONE per valid "
            "line, so a weight-w row would silently count as one "
            "line); use the default match_impl"
        ),
        lint_verdict="unprovable",
    ),
    WeightedRefusal(
        field="counts_impl",
        value="matmul",
        reason=(
            "the matmul counts formulation is exact only while per-key "
            "per-chunk sums stay < 2^24 (f32 integer range), and a "
            "weighted chunk's summed weights are bounded by the "
            "ORIGINAL corpus lines behind it, not the stored batch "
            "size its shape guard sees; use 'scatter' or 'reduce'"
        ),
        lint_verdict="float-bounded",
        coalesce_min_batch=1 << 24,
    ),
)

#: Per-chunk summed-weight ceiling for weighted wire inputs: the exact-
#: counts accumulator's carry detection (ops/counts.py add64) assumes
#: per-chunk deltas < 2^32.  A plain chunk satisfies it by shape; a
#: weighted chunk's delta is the original line count behind its rows, so
#: the stream drivers refuse chunks at or past this bound
#: (runtime/stream.py::_WireFileSource._check_chunk_weight) — the
#: run-time member of the weighted-input refusal set, which no static
#: check can prove away (it depends on the data, not the program).
WEIGHTED_CHUNK_WEIGHT_LIMIT = 1 << 32


@dataclasses.dataclass(frozen=True)
class SketchConfig:
    """Geometry of the mergeable sketches kept on device.

    Defaults follow the usual error bounds: a count-min sketch of width ``w``
    and depth ``d`` over-estimates by at most ``e*N/w`` with probability
    ``1 - exp(-d)``; a HyperLogLog with ``m = 2**hll_p`` registers has
    relative error ``~1.04/sqrt(m)``.

    Validation lives here (not in the CLI) so every entry point — CLI,
    library callers, tests — gets the same clean errors.
    """

    cms_width: int = 1 << 14
    cms_depth: int = 4
    hll_p: int = 8  # 256 registers/rule -> ~6.5% per-rule cardinality error
    topk_capacity: int = 256  # host-side talker-summary size per ACL
    topk_chunk_candidates: int = 64  # device top_k candidates fed per chunk
    #: Depth of the (acl, src) talker CMS.  Unlike the per-rule CMS, its
    #: estimates only rank talkers (the tracker keeps the max estimate
    #: across chunks), so a shallow sketch suffices — and its scatter cost
    #: scales with depth x batch, a large share of the whole device step.
    talk_cms_depth: int = 2
    #: Candidate-SELECTION subsampling: pick per-chunk talker candidates
    #: from every 2**shift-th line instead of the whole batch.  The talker
    #: CMS still absorbs EVERY line (estimates are exact-as-before); only
    #: the two candidate-table scatters shrink — the TPU trace shows the
    #: step is scatter-bound, and heavy hitters by definition recur, so a
    #: stride sample still surfaces them (a chunk where one is missed
    #: feeds it next chunk).  0 = select from the full batch (bit-exact
    #: pre-round-4 candidates).
    topk_sample_shift: int = 0
    #: Deferred candidate SELECTION cadence: run the candidate table +
    #: top_k on every Nth chunk only (Space-Saving spirit — heavy
    #: hitters recur across chunks, so a chunk-stride sample still
    #: surfaces them).  The talker CMS absorbs EVERY line regardless, so
    #: reported estimates are untouched; skipped chunks feed est=0
    #: candidates the host tracker ignores.  Deterministic in the chunk
    #: salt: resume replays the same selection schedule.  1 = select
    #: every chunk (the historical behavior, byte-identical HLO).
    topk_every: int = 1

    def __post_init__(self) -> None:
        if self.cms_width < 2 or self.cms_width & (self.cms_width - 1):
            raise ValueError(f"cms_width must be a power of two >= 2, got {self.cms_width}")
        if not 1 <= self.cms_depth <= MAX_CMS_DEPTH:
            raise ValueError(f"cms_depth must be in 1..{MAX_CMS_DEPTH}, got {self.cms_depth}")
        if not 1 <= self.talk_cms_depth <= MAX_CMS_DEPTH:
            raise ValueError(
                f"talk_cms_depth must be in 1..{MAX_CMS_DEPTH}, got {self.talk_cms_depth}"
            )
        if not 1 <= self.hll_p <= 16:
            raise ValueError(f"hll_p must be in 1..16, got {self.hll_p}")
        if self.topk_capacity < 1 or self.topk_chunk_candidates < 1:
            raise ValueError("topk_capacity and topk_chunk_candidates must be >= 1")
        if not 0 <= self.topk_sample_shift <= 8:
            raise ValueError(
                f"topk_sample_shift must be in 0..8, got {self.topk_sample_shift}"
            )
        if not 1 <= self.topk_every <= 4096:
            raise ValueError(
                f"topk_every must be in 1..4096, got {self.topk_every}"
            )

    @property
    def hll_m(self) -> int:
        return 1 << self.hll_p


@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    """Policy knobs of the metrics-driven elastic autoscaler
    (runtime/autoscale.py).

    The policy reads two canonical signals sampled from the live metrics
    plane — **pressure** (fraction of recent wall time the pipeline was
    producer-backpressured / queue-saturated: the device tier cannot keep
    up, scale OUT) and **starvation** (fraction of recent wall time the
    device tier sat idle waiting for input: capacity is excess, scale
    IN) — and turns them into planned scale events only when a signal
    holds over a full ``sustain_sec`` window.  Flap damping is threefold:
    the sustain window itself, a ``cooldown_sec`` dead time after every
    decision, and the hysteresis gap between the two thresholds (both
    signals cannot be sustained simultaneously).  ``reform_budget``
    bounds the scale re-formations of one run the way ``--max-reforms``
    bounds failure re-formations; 0 = observe-only (decisions are
    logged with evidence but never actuated).
    """

    min_world: int = 1
    max_world: int = 0  # 0 = everything provisioned (devices / launcher pool)
    initial_world: int = 0  # 0 = the smallest allowed world
    out_threshold: float = 0.5  # sustained pressure >= this => scale out
    in_threshold: float = 0.8  # sustained starvation >= this => scale in
    sustain_sec: float = 3.0  # a signal must hold this long to count
    cooldown_sec: float = 10.0  # dead time after every decision
    reform_budget: int = 4  # scale re-formations allowed (0 = observe-only)
    poll_sec: float = 0.5  # metrics sampling cadence
    #: scripted decision schedule for drills/tests ("out@T,in@T": fire
    #: each entry T seconds after the policy engine starts observing,
    #: in order); empty = decide from the live signals
    plan: str = ""

    def __post_init__(self) -> None:
        if self.min_world < 1:
            raise ValueError(f"min_world must be >= 1, got {self.min_world}")
        if self.max_world < 0 or (
            self.max_world and self.max_world < self.min_world
        ):
            raise ValueError(
                f"max_world must be 0 (= provisioned) or >= min_world, got "
                f"{self.max_world} (min_world {self.min_world})"
            )
        if self.initial_world < 0 or (
            self.initial_world
            and not (
                self.min_world
                <= self.initial_world
                <= (self.max_world or self.initial_world)
            )
        ):
            raise ValueError(
                f"initial_world must be 0 or within "
                f"[{self.min_world}, {self.max_world or 'max'}], got "
                f"{self.initial_world}"
            )
        for name in ("out_threshold", "in_threshold"):
            v = getattr(self, name)
            if not 0.0 < v <= 1.0:
                raise ValueError(f"{name} must be in (0, 1], got {v}")
        if self.sustain_sec <= 0 or self.poll_sec <= 0:
            raise ValueError("sustain_sec and poll_sec must be > 0")
        if self.cooldown_sec < 0:
            raise ValueError("cooldown_sec must be >= 0")
        if self.reform_budget < 0:
            raise ValueError("reform_budget must be >= 0")
        # validate the scripted plan eagerly (bad specs fail at config
        # time like every other knob), without importing the engine
        for part in filter(None, (p.strip() for p in self.plan.split(","))):
            d, _, t = part.partition("@")
            if d not in ("out", "in"):
                raise ValueError(
                    f"autoscale plan entry {part!r}: direction must be "
                    "'out' or 'in'"
                )
            try:
                if float(t) < 0:
                    raise ValueError
            except ValueError:
                raise ValueError(
                    f"autoscale plan entry {part!r}: want DIRECTION@SECONDS"
                ) from None

    def to_dict(self) -> dict:
        """JSON-serializable image (elastic supervisor -> worker handoff)."""
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "AutoscaleConfig":
        return AutoscaleConfig(**d)


@dataclasses.dataclass(frozen=True)
class DevprofConfig:
    """Device attribution capture window (runtime/devprof.py, DESIGN §14).

    ``run/serve --devprof-out DIR`` arms one bounded ``jax.profiler``
    window: dispatches ``1..warmup`` run unprofiled (compile + cache
    warm), the next ``steps`` dispatches are captured, parsed in-process
    against the step programs' optimized HLO, and classified by
    ``jax.named_scope`` stage — the result lands in ``DIR/devprof.json``,
    ``totals.devprof``, the metrics JSONL, and the ``/metrics`` gauges.
    Single-controller capture only (the CLI refuses ``--distributed``).
    """

    out_dir: str
    steps: int = 16
    warmup: int = 3

    def __post_init__(self) -> None:
        if not self.out_dir:
            raise ValueError("devprof out_dir must be non-empty")
        if not 1 <= self.steps <= 4096:
            raise ValueError(
                f"devprof steps must be in 1..4096, got {self.steps}"
            )
        if not 0 <= self.warmup <= 4096:
            raise ValueError(
                f"devprof warmup must be in 0..4096, got {self.warmup}"
            )


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Configuration of the always-on ``serve`` mode (runtime/serve.py).

    Exactly one of ``window_lines`` / ``window_sec`` must be positive:
    line-count windows are deterministic and replayable (tests, soak
    benches — the same traffic always cuts at the same boundary),
    wall-clock windows are the production cadence ("unused in the last
    24h" = merge the last ``86400/window_sec`` ring epochs).
    """

    #: listener specs: ``udp:HOST:PORT``, ``tcp:HOST:PORT``, ``tail:PATH``
    listen: tuple[str, ...] = ()
    window_lines: int = 0  # rotate after N received lines (deterministic)
    window_sec: float = 0.0  # rotate on a wall-clock cadence (production)
    ring: int = 8  # window epochs retained for merged views
    #: merged views (in windows) re-published at every rotation, e.g.
    #: (24, 168) for 24h/7d at a 1h window
    views: tuple[int, ...] = ()
    queue_lines: int = 1 << 16  # listener queue capacity (drops counted past it)
    http: str = "127.0.0.1:0"  # JSON endpoint bind; "off" disables
    serve_dir: str = os.path.join(OUTPUT_DIR, "serve")
    #: ring checkpoint cadence in windows (0 = never); dir defaults to
    #: ``serve_dir/ckpt`` when empty
    checkpoint_every_windows: int = 1
    checkpoint_dir: str = ""
    reload_watch: bool = True  # poll the ruleset files; SIGHUP always works
    reload_poll_sec: float = 2.0
    max_windows: int = 0  # stop after N rotations (0 = run forever)
    stop_after_sec: float = 0.0  # soft wall deadline (0 = none); bounds tests
    #: run the static ruleset analyzer (runtime/staticanalysis.py) at
    #: start and on every hot reload (unchanged ACLs reuse their
    #: verdicts); publishes /report/static and joins evidence classes
    #: into every window report.  Off by default: reports stay
    #: bit-identical to the analysis-free service.
    static_analysis: bool = False
    #: per-rule witness-grid enumeration cap for the serve analyzer
    static_witness_budget: int = 4096
    #: durable ingest write-ahead log (runtime/wal.py, DESIGN §19):
    #: every consumed line appends to a segmented, CRC'd on-disk spool
    #: BEFORE window accounting, so ``serve --resume`` after a hard kill
    #: replays the interrupted window bit-identical over its delivered
    #: lines.  Off by default (the pre-WAL behavior: a hard kill loses
    #: lines buffered past the last checkpoint).
    wal: bool = False
    #: WAL directory (empty = ``serve_dir/wal``)
    wal_dir: str = ""
    #: bytes per WAL segment before rolling to a fresh one
    wal_segment_bytes: int = 1 << 20
    #: total on-disk WAL budget; exceeding it evicts the OLDEST segment,
    #: and evicted-but-unreplayed records surface as explicit, exactly-
    #: counted drops at the next resume (never a silent gap)
    wal_budget_bytes: int = 64 << 20
    #: window provenance plane (DESIGN §24): every published window
    #: carries a sealed ``totals.lineage`` record, appends it to
    #: ``serve_dir/lineage.jsonl``, and serves it on ``/lineage``.  On
    #: by default — provenance is the audit trail the reports exist for;
    #: ``--lineage off`` is the disarm knob the overhead bench compares
    #: against.
    lineage: bool = True
    #: SLO policy spec (runtime/metrics.py::SloPolicy), e.g.
    #: ``"p99_publish_ms<=500,drop_rate<=0.001"``; empty = no SLO
    #: engine.  Breach/recovery fire on multi-window burn-rate
    #: transitions, never per-window.
    slo: str = ""
    #: per-rule trend hysteresis ratio: a rule's window-over-window hit
    #: RATE rising past ``threshold``x (or collapsing below 1/x)
    #: publishes one typed ``rule_burst``/``rule_quiet`` event into
    #: diff.json + the flight recorder.  Must be > 1; 0 disables.
    trend_threshold: float = 4.0
    #: durable epoch store directory (runtime/epochstore.py, DESIGN
    #: §25): every rotated window spills here and background compaction
    #: keeps power-of-two summary nodes, so ``/report/range?from=&to=``
    #: answers any ``[t0,t1]`` report from O(log n) stored aggregates —
    #: replay-free — and ``/report/last-hit`` cites each rule's quiet
    #: horizon.  Empty = off (the ring stays the only history).
    epoch_store: str = ""
    #: total on-disk budget for the epoch store; exceeding it evicts
    #: the OLDEST raw-epoch segment first (coarse summaries still
    #: answer aligned queries over the evicted span), and an evicted
    #: range answers a typed ``range_incomplete`` — never silent zeros
    epoch_store_budget_bytes: int = 512 << 20

    def __post_init__(self) -> None:
        if (self.window_lines > 0) == (self.window_sec > 0):
            raise ValueError(
                "exactly one of window_lines/window_sec must be positive "
                f"(got lines={self.window_lines}, sec={self.window_sec})"
            )
        if self.window_lines < 0 or self.window_sec < 0:
            raise ValueError("window length must be positive")
        if self.ring < 1:
            raise ValueError(f"ring must be >= 1, got {self.ring}")
        if self.queue_lines < 1:
            raise ValueError(f"queue_lines must be >= 1, got {self.queue_lines}")
        if any(v < 1 for v in self.views):
            raise ValueError("views must be >= 1 window each")
        if any(v > self.ring for v in self.views):
            # a merged-24 view over an 8-epoch ring would claim 24
            # windows of evidence while holding 8 — refuse, don't shrink
            raise ValueError(
                f"views {tuple(v for v in self.views if v > self.ring)} "
                f"exceed the ring ({self.ring} windows retained); raise "
                "--ring or lower --view"
            )
        if self.checkpoint_every_windows < 0:
            raise ValueError("checkpoint_every_windows must be >= 0")
        if self.reload_poll_sec <= 0:
            raise ValueError("reload_poll_sec must be > 0")
        if self.max_windows < 0 or self.stop_after_sec < 0:
            raise ValueError("max_windows/stop_after_sec must be >= 0")
        if self.static_witness_budget < 1:
            raise ValueError(
                f"static_witness_budget must be >= 1, got "
                f"{self.static_witness_budget}"
            )
        if self.wal_segment_bytes < 4096:
            raise ValueError(
                f"wal_segment_bytes must be >= 4096, got "
                f"{self.wal_segment_bytes}"
            )
        if self.wal_budget_bytes < 2 * self.wal_segment_bytes:
            # the budget must hold at least the rolling segment plus one
            # sealed predecessor, or every roll would immediately evict
            raise ValueError(
                "wal_budget_bytes must be >= 2 * wal_segment_bytes "
                f"(got {self.wal_budget_bytes} vs segment "
                f"{self.wal_segment_bytes})"
            )
        if (self.wal_dir or self.wal_segment_bytes != 1 << 20
                or self.wal_budget_bytes != 64 << 20) and not self.wal:
            raise ValueError(
                "wal_dir/wal_segment_bytes/wal_budget_bytes require wal=True "
                "(serve --wal)"
            )
        if self.epoch_store_budget_bytes < 1 << 20:
            raise ValueError(
                "epoch_store_budget_bytes must be >= 1 MiB, got "
                f"{self.epoch_store_budget_bytes}"
            )
        if self.epoch_store_budget_bytes != 512 << 20 and not self.epoch_store:
            raise ValueError(
                "epoch_store_budget_bytes requires epoch_store "
                "(serve --epoch-store DIR)"
            )
        if self.trend_threshold != 0 and self.trend_threshold <= 1.0:
            raise ValueError(
                "trend_threshold must be > 1 (a multiplicative rate "
                f"band) or 0 to disable, got {self.trend_threshold}"
            )
        if self.slo:
            # parse errors surface at config time as the documented
            # ValueError class, not mid-serve
            from .runtime.metrics import SloPolicy

            SloPolicy.parse(self.slo)
        if self.http != "off":
            host, _, port = self.http.rpartition(":")
            if not host or not port.isdigit():
                raise ValueError(
                    f"http must be HOST:PORT or 'off', got {self.http!r}"
                )


@dataclasses.dataclass(frozen=True)
class DistServeConfig:
    """Configuration of the multi-host distributed serve tier
    (runtime/distserve.py, DESIGN §22).

    ``serve --distributed`` runs one ingest worker per *host*: each host
    owns its own listeners, LineQueue, feeder tier, WAL spool, and
    flight-recorder ring, and accumulates windows into host-local
    register planes.  At every window rotation each host ships its
    epoch to rank 0 over the host-tier control plane (the ``("dcn",
    data)`` axis realized host-side: loopback TCP between processes on
    one machine, DCN between machines), where the epochs merge under
    the ``_merge_tail`` laws (add64/add32/max) — bit-identical to a
    single-host replay of the union of all hosts' delivered lines.
    Rank 0 owns publication (window/cumulative/diff JSON + HTTP).

    The host ladder runs ``min_hosts..max_hosts``; the ring-checkpoint
    fingerprint pins ``max_hosts`` (the ladder maximum, PR 7's divisor
    discipline lifted to the host tier) so a checkpoint taken at any
    world size resumes at any other.
    """

    #: number of ingest hosts to start with
    hosts: int = 2
    #: host-tier autoscale ladder bounds (actuated only when the serve
    #: run also passes --autoscale; max_hosts always pins the
    #: checkpoint fingerprint)
    min_hosts: int = 1
    max_hosts: int = 0  # 0 = hosts (no headroom to scale out into)
    #: worker isolation: "process" (true multi-core scaling, the
    #: production mode) or "thread" (in-process workers sharing one
    #: device pool — the deterministic test mode)
    workers: str = "process"
    #: rank-0 merge-plane bind (port 0 = ephemeral, recorded in
    #: serve_dir/endpoint.json)
    merge_bind: str = "127.0.0.1:0"
    #: how long rank 0 waits for a LIVE host's epoch before publishing
    #: the window without it (the window is then marked incomplete
    #: naming the missing host — never a hang, never a silent zero-hit)
    merge_timeout_sec: float = 120.0
    #: respawn a host that died unexpectedly (SIGKILL, OOM): the new
    #: process replays its predecessor's WAL tail past the last merged
    #: seq, so the rejoined host loses nothing that was spooled
    respawn: bool = False
    #: supervisor-lease TTL (DESIGN §23): the holder self-fences when it
    #: cannot renew within this long; a successor steals only after
    #: 1.5x, so takeover completes within ~2x TTL and the stale
    #: supervisor provably stops publishing first.  0 disables the
    #: whole lease/failover plane (single-supervisor PR 17 behaviour —
    #: the bench A/B leg and an operational escape hatch).
    lease_ttl_sec: float = 2.0
    #: where the durable per-host epoch spools + the lease live; ""
    #: places them under serve_dir (host-<rank>/spool and lease/).  Set
    #: this to shared storage so a successor on another machine can
    #: replay every host's spooled epochs.
    spool_dir: str = ""
    #: per-host epoch-spool disk budget (oldest segments evicted first,
    #: eviction counted — never silent); 0 disables spooling (epochs
    #: then survive only inside the supervisor's pending map)
    spool_budget_mb: int = 64

    def __post_init__(self) -> None:
        if self.hosts < 1:
            raise ValueError(f"hosts must be >= 1, got {self.hosts}")
        if self.min_hosts < 1:
            raise ValueError(f"min_hosts must be >= 1, got {self.min_hosts}")
        if self.max_hosts < 0:
            raise ValueError(f"max_hosts must be >= 0, got {self.max_hosts}")
        eff_max = self.max_hosts or self.hosts
        if not self.min_hosts <= self.hosts <= eff_max:
            raise ValueError(
                f"hosts {self.hosts} must lie within "
                f"[min_hosts {self.min_hosts}, max_hosts {eff_max}]"
            )
        if self.workers not in ("process", "thread"):
            raise ValueError(
                f"workers must be 'process' or 'thread', got {self.workers!r}"
            )
        host, _, port = self.merge_bind.rpartition(":")
        if not host or not port.isdigit():
            raise ValueError(
                f"merge_bind must be HOST:PORT, got {self.merge_bind!r}"
            )
        if self.merge_timeout_sec <= 0:
            raise ValueError(
                f"merge_timeout_sec must be > 0, got {self.merge_timeout_sec}"
            )
        if self.lease_ttl_sec < 0:
            raise ValueError(
                f"lease_ttl_sec must be >= 0 (0 disables the lease plane), "
                f"got {self.lease_ttl_sec}"
            )
        if self.spool_budget_mb < 0:
            raise ValueError(
                f"spool_budget_mb must be >= 0 (0 disables spooling), "
                f"got {self.spool_budget_mb}"
            )

    @property
    def ladder_max(self) -> int:
        """The host-tier ladder maximum the checkpoint fingerprint pins."""
        return self.max_hosts or self.hosts

    def to_dict(self) -> dict:
        """JSON-serializable image (supervisor -> spawned worker handoff)."""
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "DistServeConfig":
        return DistServeConfig(**d)


@dataclasses.dataclass(frozen=True)
class AnalysisConfig:
    """Everything the runtime needs to run one analysis job."""

    backend: str = "tpu"  # {"oracle", "tpu"}
    batch_size: int = 1 << 16  # log lines per device step (per global batch)
    sketch: SketchConfig = dataclasses.field(default_factory=SketchConfig)
    exact_counts: bool = True  # keep the exact per-rule bincount alongside sketches
    #: Ceiling on total device register memory (counts + CMS + per-key HLL
    #: + talker CMS).  The per-key HLL file is the dangerous term —
    #: ``n_keys * 2**hll_p * 4`` bytes grows with the ruleset — so
    #: init_state refuses geometries that exceed this, with a suggested
    #: smaller ``hll_p``, instead of silently OOMing the chip.
    register_memory_budget_bytes: int = 4 << 30
    mesh_axis: str = "data"
    #: Mesh topology: "flat" = one data axis over every device (the
    #: historical shape); "hybrid" = the two-level DCN x ICI idiom
    #: (SNIPPETS.md [2] ``create_hybrid_device_mesh``): an outer "dcn"
    #: axis of ``mesh_dcn`` groups (hosts, once world size grows past
    #: one) times an inner ICI axis.  Batches shard over BOTH axes and
    #: every register merge reduces over both, so reports are
    #: bit-identical to the flat mesh — pinned on CPU as 2x4 vs flat 8.
    mesh_shape: str = "flat"
    #: Outer (DCN) extent of the hybrid mesh; 0 = auto (the process
    #: count when multi-process, else 2 — the CPU exercise geometry).
    mesh_dcn: int = 0
    checkpoint_every_chunks: int = 0  # 0 = no checkpointing
    checkpoint_dir: str = os.path.join(OUTPUT_DIR, "ckpt")
    resume: bool = False  # resume from checkpoint_dir if a snapshot exists
    report_every_chunks: int = 0  # 0 = no periodic throughput lines on stderr
    seed: int = 0
    #: First-match kernel implementation: "xla" (default: the backend's
    #: scan — on the TPU the lane-dense Pallas kernel, ops/pallas_match.py,
    #: elsewhere the XLA predicate, ops/match.py; DESIGN §3), "pallas"
    #: (the lane-dense kernel on any backend, interpreted off the TPU), or
    #: "pallas_fused" (match + in-VMEM count histograms in one kernel,
    #: ops/pallas_fused.py — replaces the batch-sized exact-counts scatter
    #: with a row-sized one; 0.08x of the XLA step on the TPU in round 5,
    #: DESIGN §8).  Every benchmark cell runs the default.
    match_impl: str = "xla"
    #: Exact-counts formulation: "scatter" (segment-sum scatter-add,
    #: default), "matmul" (one-hot matmul on the MXU), or "reduce"
    #: (compare-and-reduce on the VPU).  All bit-identical
    #: (ops/counts.py).  Only "scatter" runs in a benchmark cell;
    #: "matmul" ran at 0.75x of it in the step on the TPU in round 5
    #: (DESIGN §8).
    counts_impl: str = "scatter"
    #: Register-update formulation (DESIGN §15): "scatter" (five
    #: batch-sized scatter-add/scatter-max updates per step — the
    #: historical path) or "sorted" (sort the batch's register keys once
    #: with lax.sort, then segment-sum / segment-max over the sorted
    #: runs — the MapReduce-combiner sort half, ops/sorted_update.py).
    #: Bit-identical reports either way (uint32 add/max associativity);
    #: only "scatter" runs in a benchmark cell.  Composes with
    #: counts_impl (matmul/reduce counts are already scatter-free and
    #: keep their formulation) and with
    #: coalesced/weighted inputs (the sorted updates are weight-linear
    #: by construction).
    update_impl: str = "scatter"
    #: Batch layout: "flat" scans every line against the whole rule
    #: tensor; "stacked" buckets lines by ACL host-side (pack.GroupBuffer)
    #: and vmaps the match over per-ACL rule slabs — O(max slab rows)
    #: per line instead of O(total rows) (BASELINE.json config #4).
    #: Registers are mergeable, so reports agree between layouts.
    layout: str = "flat"
    #: Per-ACL lane width of a stacked grouped batch; 0 = auto
    #: (~batch_size / n_acls, padded to the mesh).
    stacked_lane: int = 0
    #: Bounded prefetch depth of the pipelined ingest engine
    #: (runtime/ingest.py): a background producer parses / packs / issues
    #: the async device_put for up to this many batches ahead of the
    #: device step, so host parse and H2D overlap compute.  Reports stay
    #: bit-identical to the synchronous driver (batches commit in order).
    #: 0 = synchronous (the pre-pipelined driver); 2 = triple buffering.
    prefetch_depth: int = 2
    #: Watchdog bound (seconds) on a pipeline stage making NO progress:
    #: the prefetch consumer waiting on an empty queue and the feed
    #: coordinators waiting on worker completions escalate to a typed
    #: StallError after this long instead of wedging forever.  Progress
    #: resets the window, so legitimately slow inputs only need to
    #: advance once per window (CLI --stall-timeout; env
    #: RA_STALL_TIMEOUT overrides the default for bare library calls).
    stall_timeout_sec: float = 300.0
    #: Flow coalescing (runtime/coalesce.py): pre-aggregate each batch's
    #: duplicate evaluation tuples into (unique row, weight) pairs before
    #: the device step — the MapReduce-combiner move applied to a
    #: scatter-bound step.  Registers update weight-linearly (or
    #: idempotently, HLL), so reports are bit-identical to the
    #: uncoalesced path while device rows, scatters, and H2D bytes
    #: shrink by the traffic's compaction ratio.  "off" = never (the
    #: historical path, zero added work), "on" = always, "auto" =
    #: sample the first batches and disable below the break-even ratio.
    #: Applies to the single-process stream drivers; the distributed
    #: driver rejects it (per-process unique counts diverge, and the
    #: collective batch assembly needs one global shape).
    coalesce: str = "off"
    #: Serialized fault-injection schedule (runtime/faults.py;
    #: ``"site@N,site@N:k,seed=S"`` — the ``:k`` transient form fires k
    #: consecutive times then clears).  Empty = every site disarmed (the
    #: production state: one None-check per site).  Armed by the drivers
    #: at run start and exported to RA_FAULT_PLAN so spawned workers
    #: (feeder processes, elastic generations) inherit the schedule.
    fault_plan: str = ""
    #: Flight-recorder crash-forensics directory (runtime/flightrec.py,
    #: DESIGN §20).  Non-empty = the always-on in-memory telemetry ring
    #: is armed for this run and a typed abort / stall / unhandled crash
    #: dumps per-PID shards here, merged into ``postmortem.json``
    #: (exported to RA_BLACKBOX_DIR so spawned workers participate).
    #: Empty = disarmed (the bare-library default; the CLI defaults it
    #: to a ``blackbox`` dir beside the checkpoint/serve dir).
    blackbox_dir: str = ""
    #: Retry-policy overrides (runtime/retrypolicy.py, DESIGN §19;
    #: ``"site=attempts[/base_sec],...,seed=S"`` or ``"off"``).  Empty =
    #: the built-in per-site defaults (retries are always armed; this
    #: only tunes them).  Validated at configure time like fault_plan,
    #: so bad specs fail loudly at run start rather than silently at the
    #: first transient fault.
    retry_policy: str = ""

    def __post_init__(self) -> None:
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")
        if self.checkpoint_every_chunks < 0:
            raise ValueError("checkpoint_every_chunks must be >= 0")
        if self.match_impl not in ("xla", "pallas", "pallas_fused"):
            raise ValueError(
                "match_impl must be 'xla', 'pallas', or 'pallas_fused', "
                f"got {self.match_impl!r}"
            )
        if self.counts_impl not in ("scatter", "matmul", "reduce"):
            raise ValueError(
                "counts_impl must be 'scatter', 'matmul', or 'reduce', "
                f"got {self.counts_impl!r}"
            )
        if self.update_impl not in ("scatter", "sorted"):
            raise ValueError(
                "update_impl must be 'scatter' or 'sorted', "
                f"got {self.update_impl!r}"
            )
        if self.update_impl == "sorted" and self.match_impl == "pallas_fused":
            # the fused kernel computes its count histogram in-kernel
            # (its own scatter tail), so the sorted counts formulation
            # would silently never run — and the kernel is not
            # weight-linear, so the combination is unsafe for the
            # weighted inputs the sorted path exists to serve
            raise ValueError(
                "update_impl='sorted' is incompatible with the "
                "experimental match_impl='pallas_fused' (the fused kernel "
                "builds counts in-VMEM with its own scatter tail); use "
                "the default match_impl"
            )
        if self.match_impl == "pallas_fused" and self.counts_impl != "scatter":
            # the fused kernel produces the counts delta itself (in-VMEM
            # histograms), so a non-default counts_impl would silently
            # never run — reject the combination instead of mis-measuring
            raise ValueError(
                "match_impl='pallas_fused' computes counts in-kernel; "
                f"counts_impl={self.counts_impl!r} would be ignored — "
                "leave it 'scatter' (the default)"
            )
        if self.layout not in ("flat", "stacked"):
            raise ValueError(f"layout must be 'flat' or 'stacked', got {self.layout!r}")
        if self.mesh_shape not in ("flat", "hybrid"):
            raise ValueError(
                f"mesh_shape must be 'flat' or 'hybrid', got {self.mesh_shape!r}"
            )
        if self.mesh_dcn < 0:
            raise ValueError(f"mesh_dcn must be >= 0, got {self.mesh_dcn}")
        if self.mesh_dcn and self.mesh_shape != "hybrid":
            raise ValueError(
                "mesh_dcn only applies to mesh_shape='hybrid'"
            )
        if self.stacked_lane < 0:
            raise ValueError("stacked_lane must be >= 0")
        if not 0 <= self.prefetch_depth <= 1024:
            raise ValueError(
                f"prefetch_depth must be in 0..1024, got {self.prefetch_depth}"
            )
        if self.register_memory_budget_bytes < 1:
            raise ValueError("register_memory_budget_bytes must be >= 1")
        if self.stall_timeout_sec <= 0:
            raise ValueError(
                f"stall_timeout_sec must be > 0, got {self.stall_timeout_sec}"
            )
        if self.layout == "stacked" and self.match_impl != "xla":
            raise ValueError(
                f"match_impl={self.match_impl!r} supports layout='flat' only; "
                "the stacked path always uses the XLA vmapped kernel"
            )
        if self.coalesce not in ("off", "on", "auto"):
            raise ValueError(
                f"coalesce must be 'off', 'on', or 'auto', got {self.coalesce!r}"
            )
        if self.coalesce != "off":
            # the ONE weighted-input compatibility table (module top):
            # coalesced batches reach the step weighted, so every
            # registered incompatibility refuses here at config time.
            # (The stream drivers apply the same table to weighted
            # .rawire inputs, which this config-time check cannot see.)
            for r in WEIGHTED_INPUT_REFUSALS:
                if getattr(self, r.field) != r.value:
                    continue
                if (
                    r.coalesce_min_batch is not None
                    and self.batch_size < r.coalesce_min_batch
                ):
                    continue
                raise ValueError(
                    f"coalesce is incompatible with "
                    f"{r.field}={r.value!r}: {r.reason}"
                )

    def replace(self, **kw) -> "AnalysisConfig":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        """JSON-serializable image (elastic supervisor -> worker handoff)."""
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "AnalysisConfig":
        """Inverse of :meth:`to_dict`; validation re-runs in __post_init__."""
        d = dict(d)
        d["sketch"] = SketchConfig(**d["sketch"])
        return AnalysisConfig(**d)
