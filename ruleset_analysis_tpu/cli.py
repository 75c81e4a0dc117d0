"""Command-line interface — the job-submission layer (SURVEY.md §4.2).

The reference drives everything through three entry points: the parser
script (``getaccesslists.py``), a Hadoop Streaming submission wrapper
(``runAnalysis.sh``), and the report step.  This CLI is the single
replacement for all three:

  ruleset-analyze parse-acls CONFIG [CONFIG...] --out PREFIX
  ruleset-analyze run --ruleset PREFIX --logs FILE --backend {oracle,tpu}
  ruleset-analyze synth --out-dir DIR [...]

``--backend=oracle`` is the exact pure-Python path (the Hadoop-semantics
stand-in); ``--backend=tpu`` dispatches the hot loop to JAX (the reference
north star's ``--backend=tpu``).
"""

from __future__ import annotations

import argparse
import sys

from . import errors
from .config import AnalysisConfig, DevprofConfig, SketchConfig
from .hostside import aclparse, oracle, pack, synth
from .runtime import report as report_mod


def _report_ruleset(label: str, rs) -> None:
    """One parsed ruleset's summary + lenient-mode skips, to stderr."""
    skipped = f" skipped={len(rs.skipped)}" if rs.skipped else ""
    print(
        f"{label}: firewall={rs.firewall} acls={len(rs.acls)} "
        f"rules={rs.rule_count()} expanded_aces={rs.ace_count()}{skipped}",
        file=sys.stderr,
    )
    for lineno, reason, line in rs.skipped:
        print(f"{label}:{lineno}: skipped: {reason}: {line}", file=sys.stderr)


def _pack_and_save(rulesets, out_prefix: str, origin: str = "") -> int:
    packed = pack.pack_rulesets(rulesets)
    pack.save_packed(packed, out_prefix)
    print(
        f"packed {packed.rules.shape[0]} ACE rows, {packed.n_rules} rule keys, "
        f"{packed.n_acls} ACLs{origin} -> {out_prefix}.npz/.json",
        file=sys.stderr,
    )
    return 0


def _cmd_parse_acls(args: argparse.Namespace) -> int:
    rulesets = []
    for path in args.configs:
        rs = aclparse.parse_config_file(path, strict=not args.lenient)
        _report_ruleset(path, rs)
        rulesets.append(rs)
    return _pack_and_save(rulesets, args.out)


def _cmd_fetch_acls(args: argparse.Namespace) -> int:
    """getaccesslists.py analog: inventory -> fetch -> parse -> pack."""
    from .hostside import acquire

    inventory = acquire.load_inventory(args.inventory)
    if not inventory:
        print(
            "error: empty inventory (populate config.FIREWALLS or pass "
            "--inventory FILE with 'name = source' lines)",
            file=sys.stderr,
        )
        return 2
    rulesets = []
    for name, source, rs in acquire.iter_rulesets(
        inventory, strict=not args.lenient
    ):
        _report_ruleset(f"{name} <- {source}", rs)
        rulesets.append(rs)
    return _pack_and_save(
        rulesets, args.out, origin=f" from {len(rulesets)} firewalls"
    )


def _resolve_fault_plan(spec: str | None) -> str:
    """``--fault-plan`` value: a spec string, or ``@FILE`` naming a file
    holding one (chaos schedules checked into a repo).  Validated by
    parsing; the canonical form travels in the config."""
    if not spec:
        return ""
    from .runtime import faults

    if spec.startswith("@"):
        try:
            with open(spec[1:], "r", encoding="utf-8") as f:
                spec = f.read().strip()
        except OSError as e:
            # a bad plan FILE is a usage mistake like a bad plan string:
            # typed so the caller's handler exits 2, never a traceback
            raise errors.AnalysisError(
                f"cannot read fault plan file {spec[1:]!r}: {e}"
            ) from e
    return faults.FaultPlan.parse(spec).to_str()


#: --autoscale-X flag name -> AutoscaleConfig field.  The dataclass
#: field defaults are the ONE source of truth for flag defaults (both
#: the argparse defaults and the requires---autoscale check read them).
_AUTOSCALE_FIELDS = {
    "autoscale_min": "min_world",
    "autoscale_max": "max_world",
    "autoscale_initial": "initial_world",
    "autoscale_out_threshold": "out_threshold",
    "autoscale_in_threshold": "in_threshold",
    "autoscale_sustain": "sustain_sec",
    "autoscale_cooldown": "cooldown_sec",
    "autoscale_budget": "reform_budget",
    "autoscale_poll": "poll_sec",
    "autoscale_plan": "plan",
}


def _autoscale_defaults() -> dict:
    import dataclasses

    from .config import AutoscaleConfig

    by_field = {f.name: f.default for f in dataclasses.fields(AutoscaleConfig)}
    return {flag: by_field[field] for flag, field in _AUTOSCALE_FIELDS.items()}


def _autoscale_config(args):
    """``--autoscale`` flag family -> AutoscaleConfig (None when off)."""
    if not args.autoscale:
        for flag, dflt in _autoscale_defaults().items():
            if getattr(args, flag) != dflt:
                raise errors.AnalysisError(
                    f"--{flag.replace('_', '-')} requires --autoscale"
                )
        return None
    from .config import AutoscaleConfig

    return AutoscaleConfig(
        **{
            field: getattr(args, flag)
            for flag, field in _AUTOSCALE_FIELDS.items()
        }
    )


def _add_autoscale_flags(p) -> None:
    d = _autoscale_defaults()
    p.add_argument("--autoscale", action="store_true",
                   help="arm the metrics-driven elastic autoscaler "
                        "(DESIGN §13): sustained producer-backpressure "
                        "scales device workers OUT, sustained starvation "
                        "scales IN, via planned re-formations from the "
                        "epoch checkpoints — decisions carry their "
                        "evidence in the trace/metrics planes")
    p.add_argument("--autoscale-min", type=int, default=d["autoscale_min"], metavar="W",
                   help="smallest world the policy may scale in to")
    p.add_argument("--autoscale-max", type=int, default=d["autoscale_max"], metavar="W",
                   help="largest world (0 = everything provisioned: all "
                        "devices for serve, the launcher pool for "
                        "--elastic)")
    p.add_argument("--autoscale-initial", type=int, default=d["autoscale_initial"], metavar="W",
                   help="starting world (0 = the smallest allowed)")
    p.add_argument("--autoscale-out-threshold", type=float,
                   default=d["autoscale_out_threshold"],
                   metavar="F",
                   help="scale OUT when the pressure signal holds >= F "
                        "over the sustain window (fraction of wall time "
                        "producer-backpressured / queue-saturated)")
    p.add_argument("--autoscale-in-threshold", type=float,
                   default=d["autoscale_in_threshold"],
                   metavar="F",
                   help="scale IN when the starvation signal holds >= F "
                        "over the sustain window")
    p.add_argument("--autoscale-sustain", type=float, default=d["autoscale_sustain"],
                   metavar="SEC",
                   help="a signal must hold this long before a decision")
    p.add_argument("--autoscale-cooldown", type=float, default=d["autoscale_cooldown"],
                   metavar="SEC",
                   help="dead time after every decision (flap damping)")
    p.add_argument("--autoscale-budget", type=int, default=d["autoscale_budget"], metavar="N",
                   help="scale re-formations allowed per run (0 = "
                        "observe-only: decisions with evidence, no "
                        "actuation); separate from --max-reforms, which "
                        "stays the FAILURE budget")
    p.add_argument("--autoscale-poll", type=float, default=d["autoscale_poll"], metavar="SEC",
                   help="metrics sampling cadence of the policy engine")
    p.add_argument("--autoscale-plan", default=d["autoscale_plan"], metavar="SPEC",
                   help="scripted decisions for drills/tests "
                        "('out@T,in@T': fire at T seconds, in order), "
                        "bypassing the signal thresholds")


def _arm_devprof(args) -> int | None:
    """Validate + arm the device attribution capture (``--devprof-out``).

    Returns an exit code on a usage error, None on success (including
    the disarmed default).  Shared by ``run`` and ``serve``.
    """
    if not args.devprof_out:
        if (
            args.devprof_steps != DevprofConfig.steps
            or args.devprof_warmup != DevprofConfig.warmup
        ):
            print(
                "--devprof-steps/--devprof-warmup require --devprof-out",
                file=sys.stderr,
            )
            return 2
        return None
    if getattr(args, "distributed", False) or getattr(args, "elastic", False):
        # single-controller capture only: the profiler window, the
        # HLO re-derivation, and the trace parse all cover ONE process;
        # a multi-process job would publish a summary silently missing
        # every other rank's device time (DESIGN §14)
        print(
            "--devprof-out is a single-controller capture and is "
            "incompatible with --distributed/--elastic; capture on a "
            "single-process run of the same geometry instead",
            file=sys.stderr,
        )
        return 2
    if getattr(args, "profile_dir", None):
        print(
            "--devprof-out and --profile-dir both drive jax.profiler "
            "(one trace session per process); pick one — devprof is the "
            "bounded window with semantic attribution, profile-dir the "
            "whole-run TensorBoard trace",
            file=sys.stderr,
        )
        return 2
    from .runtime import devprof

    try:
        dcfg = DevprofConfig(
            out_dir=args.devprof_out,
            steps=args.devprof_steps,
            warmup=args.devprof_warmup,
        )
        devprof.arm(dcfg.out_dir, steps=dcfg.steps, warmup=dcfg.warmup)
    except (ValueError, errors.AnalysisError, OSError) as e:
        print(f"error: cannot arm --devprof-out: {e}", file=sys.stderr)
        return 2
    return None


def _add_devprof_flags(p) -> None:
    p.add_argument("--devprof-out", default=None, metavar="DIR",
                   help="device attribution capture (DESIGN §14): arm "
                        "jax.profiler for a bounded window of device "
                        "steps after warmup, classify device time by "
                        "named semantic stage (ra.match/ra.counts/"
                        "ra.hll/...), and write DIR/devprof.json — also "
                        "folded into totals.devprof, the metrics JSONL "
                        "and the /metrics gauges; diff two captures "
                        "with tools/trace_diff.py (single-controller "
                        "runs only)")
    p.add_argument("--devprof-steps", type=int,
                   default=DevprofConfig.steps, metavar="N",
                   help="device dispatches to capture (default "
                        f"{DevprofConfig.steps})")
    p.add_argument("--devprof-warmup", type=int,
                   default=DevprofConfig.warmup, metavar="K",
                   help="dispatches to skip before the window opens, so "
                        "compile/cache warmup never pollutes the "
                        f"attribution (default {DevprofConfig.warmup})")


def _resolve_blackbox(args, default_dir: str) -> str:
    """``--blackbox``/``--blackbox-dir`` -> the armed directory ('' = off).

    Always-on by default (DESIGN §20): a production run needs no flag to
    get crash forensics.  ``--blackbox off`` disarms; ``RA_BLACKBOX=off``
    disarms only the DEFAULT (an explicit ``--blackbox-dir`` still arms
    — test harnesses set the env so incidental CLI invocations don't
    write forensics into the working tree).  Raises AnalysisError on the
    contradictory ``--blackbox off --blackbox-dir D``.
    """
    import os

    from .runtime import flightrec

    if args.blackbox == "off":
        if args.blackbox_dir:
            raise errors.AnalysisError(
                "--blackbox-dir contradicts --blackbox off (drop one)"
            )
        return ""
    if not args.blackbox_dir and os.environ.get(
        flightrec.KILL_SWITCH, ""
    ).strip().lower() in ("off", "0"):
        return ""
    return args.blackbox_dir or default_dir


def _iter_log_lines(paths: list[str]):
    for path in paths:
        if path == "-":
            yield from sys.stdin
        else:
            with open(path, "r", encoding="utf-8", errors="replace") as f:
                yield from f


def _cmd_run(args: argparse.Namespace) -> int:
    try:
        import os as _os

        # the flight recorder's default home is BESIDE the checkpoint
        # dir ("out/ckpt" -> "out/blackbox"): forensics live where the
        # run's other durable state already lives
        ckpt_dir = args.checkpoint_dir or AnalysisConfig.checkpoint_dir
        blackbox_dir = _resolve_blackbox(
            args,
            _os.path.join(_os.path.dirname(ckpt_dir) or ".", "blackbox"),
        ) if args.backend == "tpu" else ""
        cfg = AnalysisConfig(
            backend=args.backend,
            batch_size=args.batch_size,
            sketch=SketchConfig(
                cms_width=args.cms_width,
                cms_depth=args.cms_depth,
                hll_p=args.hll_p,
                topk_sample_shift=args.topk_sample_shift,
                topk_every=args.topk_every,
            ),
            exact_counts=args.exact_counts,
            register_memory_budget_bytes=args.register_budget_mb << 20,
            checkpoint_every_chunks=args.checkpoint_every,
            resume=args.resume,
            report_every_chunks=args.report_every,
            match_impl=args.experimental_match_impl or args.match_impl,
            counts_impl=args.counts_impl,
            update_impl=args.update_impl,
            layout=args.layout,
            stacked_lane=args.stacked_lane,
            prefetch_depth=args.prefetch_depth,
            stall_timeout_sec=args.stall_timeout,
            coalesce=args.coalesce,
            mesh_shape=args.mesh,
            mesh_dcn=args.mesh_dcn,
            fault_plan=_resolve_fault_plan(args.fault_plan),
            retry_policy=args.retry_policy,
            blackbox_dir=blackbox_dir,
            **({"checkpoint_dir": args.checkpoint_dir} if args.checkpoint_dir else {}),
        )
        if args.retry_policy:
            # validate eagerly: a malformed --retry-policy must be the
            # usage error here, not a failure at the first transient
            from .runtime import retrypolicy

            retrypolicy.parse_spec(args.retry_policy)
        autoscale = _autoscale_config(args)
    except (ValueError, errors.AnalysisError) as e:
        # AnalysisError here is a malformed --fault-plan/--retry-policy:
        # a config mistake, so the usage exit code — not a runtime
        # failure class
        print(f"error: {e}", file=sys.stderr)
        return 2
    if not args.static_analysis and args.static_witness_budget != 4096:
        # the devprof dependent-flag convention: a budget without the
        # analysis would be silently ignored, not a smaller analysis
        print("error: --static-witness-budget requires --static-analysis",
              file=sys.stderr)
        return 2
    if args.static_analysis and args.static_witness_budget < 1:
        # fail BEFORE the (possibly hours-long) traffic run, not at the
        # post-run analysis step where the computed report would be lost
        print("error: --static-witness-budget must be >= 1", file=sys.stderr)
        return 2
    packed = pack.load_packed(args.ruleset)
    lines = _iter_log_lines(args.logs)

    if args.backend == "oracle":
        from .hostside.wire import is_wire_file

        if any(p != "-" and is_wire_file(p) for p in args.logs):
            print(
                "--backend=oracle reads text syslog; .rawire files only "
                "apply to --backend=tpu", file=sys.stderr,
            )
            return 2
        # These only plumb into the device stream driver; accepting them
        # silently would let a user believe an oracle run is checkpointed.
        tpu_only = {
            "--checkpoint-every": args.checkpoint_every,
            "--resume": args.resume,
            "--report-every": args.report_every,
            "--profile-dir": args.profile_dir,
            "--trace-out": args.trace_out,
            "--metrics-out": args.metrics_out,
            "--native-parse": args.native_parse,
            "--checkpoint-dir": args.checkpoint_dir,
            "--layout=stacked": args.layout != "flat",
            "--packed-input": args.packed_input,
            "--no-exact-counts": not args.exact_counts,
            "--feed-workers": args.feed_workers > 1,
            "--feed-mode=thread": args.feed_workers > 1 and args.feed_mode == "thread",
            "--feed-mode=ring": args.feed_mode == "ring",
            "--experimental-match-impl": bool(args.experimental_match_impl),
            "--elastic": args.elastic,
            "--fault-plan": bool(args.fault_plan),
            "--retry-policy": bool(args.retry_policy),
            "--coalesce": args.coalesce != "off",
            "--mesh=hybrid": args.mesh != "flat",
            "--autoscale": args.autoscale,
            "--devprof-out": bool(args.devprof_out),
            "--update-impl=sorted": args.update_impl != "scatter",
            "--topk-every": args.topk_every != 1,
            "--blackbox-dir": bool(args.blackbox_dir),
            "--blackbox=off": args.blackbox == "off",
        }
        # --prefetch-depth is deliberately NOT rejected: like
        # --batch-size it is a tpu-path tuning knob the oracle ignores,
        # and rejecting its off value (0) would be nonsense
        bad = [k for k, v in tpu_only.items() if v]
        if bad:
            print(
                f"{', '.join(bad)} only apply to --backend=tpu", file=sys.stderr
            )
            return 2
        # Exact path: rebuild Ruleset objects is not possible from packed form
        # alone; the oracle needs the original configs.
        if not args.acl_configs:
            print("--backend=oracle requires --acl-configs (original config files)", file=sys.stderr)
            return 2
        rulesets = [
            aclparse.parse_config_file(p, strict=not args.lenient)
            for p in args.acl_configs
        ]
        orc = oracle.Oracle(rulesets)
        res = orc.consume(lines)
        # render per family: oracle talker identities are (family, addr)
        # so a v6 source prints as a v6 literal, never a garbled quad
        talkers = {
            k: [
                (
                    aclparse.int_to_ip6(s) if f == 6 else aclparse.u32_to_ip(s),
                    c,
                )
                for (f, s), c in cnt.most_common(args.topk)
            ]
            for k, cnt in res.talkers.items()
        }
        rep = report_mod.build_report(
            packed,
            dict(res.hits),
            backend="oracle",
            totals={
                "lines_total": res.lines_total,
                "lines_matched": res.lines_matched,
                "lines_skipped": res.lines_skipped,
            },
            unique_sources={k: len(v) for k, v in res.sources.items()},
            talkers=talkers,
        )
    elif args.backend == "tpu":
        try:
            from .runtime.compcache import enable_persistent_cache
            from .runtime.stream import (  # deferred: imports JAX
                run_stream,
                run_stream_file,
                run_stream_wire,
            )
        except ImportError as e:
            print(f"error: tpu backend unavailable ({e})", file=sys.stderr)
            return 1
        enable_persistent_cache()  # skip the step recompile on repeat runs
        # convert-fleet manifests expand to their shard lists first: the
        # multi-file WireReader concatenates shard payloads and counts
        # resume offsets in stored-row units, so a fleet output is one
        # corpus from here on
        from .hostside.convertfleet import expand_wire_inputs

        args.logs = expand_wire_inputs(args.logs)
        file_input = all(p != "-" for p in args.logs)
        from .hostside.wire import is_wire_file

        # '-' (stdin) is never a wire file but still poisons a mix: binary
        # wire data must not fall through to the text-parse path
        n_wire = sum(1 for p in args.logs if p != "-" and is_wire_file(p))
        if args.packed_input and n_wire < len(args.logs):
            print(
                "--packed-input: not every --logs file is a .rawire wire "
                "file (run `ruleset-analyze convert` first)", file=sys.stderr,
            )
            return 2
        if 0 < n_wire < len(args.logs):
            print("cannot mix .rawire and text inputs in one --logs list", file=sys.stderr)
            return 2
        wire_input = n_wire == len(args.logs) and n_wire > 0
        if wire_input and (args.native_parse or args.feed_workers > 1):
            print(
                "--native-parse/--feed-workers do not apply to packed "
                ".rawire inputs (there is no text parse)", file=sys.stderr,
            )
            return 2
        if args.native_parse and not file_input:
            print("--native-parse requires file inputs (not '-')", file=sys.stderr)
            return 2
        if args.feed_workers > 1 and (
            not file_input or args.distributed or args.native_parse is False
        ):
            print(
                "--feed-workers requires file inputs and the native parser, "
                "and is not available with --distributed", file=sys.stderr,
            )
            return 2
        if args.feed_mode == "ring" and args.feed_workers < 1:
            print(
                "--feed-mode ring needs --feed-workers N (the per-chip "
                "producer pool size)", file=sys.stderr,
            )
            return 2
        if args.feed_mode == "ring" and (
            not file_input or args.distributed or args.native_parse is False
            or wire_input
        ):
            print(
                "--feed-mode ring requires text file inputs and the native "
                "parser, and is not available with --distributed",
                file=sys.stderr,
            )
            return 2
        if args.trace_out or args.metrics_out:
            # Arm the observability plane (runtime/obs.py) for the whole
            # run: span shards land in --trace-out (exported via
            # RA_TRACE_DIR so spawned feeder/elastic workers write
            # sibling shards) and the metrics snapshotter appends JSONL
            # to --metrics-out.  main()'s finally merges/stops them even
            # when the run ends in a typed abort — that trace is exactly
            # the one worth keeping.
            from .runtime import obs

            try:
                if args.trace_out:
                    obs.start_trace(args.trace_out, role="main")
                if args.metrics_out:
                    obs.start_metrics(args.metrics_out, args.metrics_every)
                    # live device-memory headroom in every snapshot
                    # (HBM stats where supported, explicit nulls on CPU)
                    from .runtime.devprof import device_memory_gauges

                    obs.register_sampler("device_mem", device_memory_gauges)
            except OSError as e:
                # an unwritable trace dir / metrics file is a usage
                # mistake, reported like every other bad-path flag —
                # not a raw traceback
                print(
                    f"error: cannot open --trace-out/--metrics-out "
                    f"target: {e}", file=sys.stderr,
                )
                return 2
        if args.autoscale and not args.elastic:
            print(
                "--autoscale applies to `serve` and to `run --elastic` "
                "(the supervised tier that can re-form the world); a "
                "fixed-membership run has nothing to scale", file=sys.stderr,
            )
            return 2
        rc = _arm_devprof(args)
        if rc is not None:
            return rc
        if args.elastic:
            # Elastic tier: this process becomes a recovery SUPERVISOR
            # (runtime/elastic.py) — --logs is the FULL shard list, the
            # same on every launcher; the supervisor rendezvous elects a
            # coordinator, spawns the analysis workers, and re-forms the
            # cluster automatically when a peer dies.  Only the final
            # generation's reporting member prints/writes the report.
            if not args.distributed:
                print("--elastic requires --distributed", file=sys.stderr)
                return 2
            if not file_input or wire_input:
                print(
                    "--elastic requires text file shards (not '-' or "
                    ".rawire)", file=sys.stderr,
                )
                return 2
            if args.num_processes is None or args.process_id is None:
                print(
                    "--elastic requires --num-processes and --process-id "
                    "(the launcher membership)", file=sys.stderr,
                )
                return 2
            if args.coordinator:
                print(
                    "--elastic elects its own coordinator; drop "
                    "--coordinator", file=sys.stderr,
                )
                return 2
            if not args.elastic_dir:
                print(
                    "--elastic requires --elastic-dir (shared rendezvous "
                    "+ epoch-checkpoint directory)", file=sys.stderr,
                )
                return 2
            if not args.json:
                print(
                    "--elastic reports via the JSON result the workers "
                    "write; add --json", file=sys.stderr,
                )
                return 2
            if args.static_analysis:
                print(
                    "--static-analysis does not ride the --elastic "
                    "result relay; run the `analyze` subcommand against "
                    "the same --ruleset instead", file=sys.stderr,
                )
                return 2
            import json as json_mod
            import os as os_mod

            from .errors import AnalysisError as _AErr
            from .runtime import faults
            from .runtime.elastic import ElasticSupervisor

            fault = None
            fault_env = os_mod.environ.get("RA_ELASTIC_FAULT")
            if fault_env:
                # test-only crash injection: "tag=K,after_batches=M[,gen=G]"
                fault = dict(
                    kv.split("=", 1) for kv in fault_env.split(",")
                )
            try:
                sup = ElasticSupervisor(
                    args.elastic_dir,
                    args.process_id,
                    args.num_processes,
                    args.ruleset,
                    args.logs,
                    cfg,
                    max_reforms=args.max_reforms,
                    topk=args.topk,
                    native=args.native_parse,
                    out_prefix=os_mod.path.join(
                        args.elastic_dir, "result"
                    ),
                    fault=fault,
                    autoscale=autoscale,
                )
            except _AErr as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
            # the supervisor process hosts fault sites of its own (the
            # autoscale decide/actuate seam); workers re-arm the same
            # spec idempotently from the job config.  The supervisor
            # also OWNS the blackbox dir: it arms first (pruning stale
            # shards), and the spawned generation workers join via the
            # exported RA_BLACKBOX_DIR without pruning.
            if cfg.blackbox_dir:
                from .runtime import flightrec as _flightrec

                _flightrec.arm(cfg.blackbox_dir, role="elastic-supervisor")
            armed_here = faults.arm_spec(cfg.fault_plan)
            try:
                rc, result_path = sup.run()
            except _AErr as e:
                # a typed runtime abort (e.g. an injected autoscale
                # fault at the decide/actuate seam) exits with its
                # documented failure-class code, never a traceback.
                # Note the abort so the finalize in main()'s finally
                # merges the generation workers' shards instead of
                # treating the return as a clean exit and pruning them.
                from .runtime import flightrec as _flightrec

                _flightrec.note_abort(e, errors.exit_code_for(e))
                print(f"error: {e}", file=sys.stderr)
                return errors.exit_code_for(e)
            finally:
                if armed_here:
                    faults.disarm()
            if rc != 0 or result_path is None:
                if rc != 0:
                    # a failure the supervisor reported by exit code
                    # alone (no exception reached us): the finalize in
                    # main()'s finally still merges the postmortem
                    from .runtime import flightrec as _flightrec

                    _flightrec.note_failure(rc)
                return rc
            with open(result_path, "r", encoding="utf-8") as f:
                rep_obj = json_mod.load(f)
            payload = json_mod.dumps(rep_obj, indent=2)
            if args.out:
                with open(args.out, "w", encoding="utf-8") as f:
                    f.write(payload + "\n")
            else:
                print(payload)
            return 0
        if args.distributed:
            # multi-process job: this process joins the cluster and feeds
            # only ITS OWN --logs (the input-split analog); every process
            # computes the identical report, only rank 0 prints it
            if not file_input:
                print("--distributed requires file inputs (not '-')", file=sys.stderr)
                return 2
            if args.coalesce != "off":
                print(
                    "--coalesce applies to single-process runs only; for "
                    "distributed jobs pre-coalesce the input with "
                    "`ruleset-analyze convert --coalesce`", file=sys.stderr,
                )
                return 2
            import jax

            from .parallel.distributed import init_distributed
            from .runtime.stream import run_stream_file_distributed

            init_distributed(
                coordinator_address=args.coordinator,
                num_processes=args.num_processes,
                process_id=args.process_id,
            )
            rep = run_stream_file_distributed(
                packed, args.logs, cfg, native=args.native_parse, topk=args.topk
            )
            if jax.process_index() != 0:
                return 0
        elif wire_input:
            rep = run_stream_wire(
                packed,
                args.logs,
                cfg,
                topk=args.topk,
                profile_dir=args.profile_dir,
            )
        elif file_input:
            # forced --native-parse with no C++ toolchain raises
            # NativeParserUnavailable, handled as AnalysisError in main()
            rep = run_stream_file(
                packed,
                args.logs,
                cfg,
                native=args.native_parse,  # None = auto
                topk=args.topk,
                profile_dir=args.profile_dir,
                feed_workers=args.feed_workers,
                feed_mode=args.feed_mode,
            )
        else:
            rep = run_stream(packed, lines, cfg, topk=args.topk, profile_dir=args.profile_dir)
    else:
        print(f"unknown backend {args.backend!r}", file=sys.stderr)
        return 2

    if args.static_analysis:
        # join the static verdicts into the live-evidence report: the
        # whole run counted under this one ruleset, so a hit on a
        # provably-dead rule is a hard contradiction (strict=True ->
        # typed AnalyzerContradiction, handled by main()).  Strict only
        # with EXACT counters: under --no-exact-counts the per-rule
        # "hits" are CMS estimates, and a sketch collision can inflate a
        # dead rule's estimate above zero — annotate, don't abort.
        from .runtime import staticanalysis

        sa = staticanalysis.analyze_ruleset(
            packed, witness_budget=args.static_witness_budget
        )
        # (oracle runs always count exactly; --no-exact-counts is
        # rejected for that backend above)
        staticanalysis.attach_static(rep, packed, sa, strict=args.exact_counts)

    payload = rep.to_json() if args.json else rep.to_text()
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(payload + "\n")
    else:
        print(payload)
    return 0


def _cmd_serve(args: argparse.Namespace) -> int:
    """Always-on service mode: live listeners -> windowed reports.

    Runs until --max-windows/--stop-after (or SIGINT); the window ring,
    report publication, reload semantics, and endpoint paths live in
    runtime/serve.py (DESIGN §12).
    """
    from .config import ServeConfig

    if not args.static_analysis and args.static_witness_budget != 4096:
        print("error: --static-witness-budget requires --static-analysis",
              file=sys.stderr)
        return 2
    if bool(args.ruleset) == bool(args.tenants):
        print("error: serve needs exactly one of --ruleset or "
              "--tenants MANIFEST", file=sys.stderr)
        return 2
    if not args.distributed:
        for flag, dflt in (
            ("dist_hosts", 2), ("dist_min_hosts", 1),
            ("dist_max_hosts", 0), ("dist_workers", "process"),
            ("dist_merge_bind", "127.0.0.1:0"),
            ("dist_merge_timeout", 120.0), ("dist_respawn", False),
            ("dist_lease_ttl", 2.0), ("dist_spool_dir", ""),
            ("dist_spool_budget_mb", 64),
        ):
            if getattr(args, flag) != dflt:
                print(f"error: --{flag.replace('_', '-')} requires "
                      "--distributed", file=sys.stderr)
                return 2
    try:
        import os as _os

        cfg = AnalysisConfig(
            backend="tpu",
            mesh_shape=args.mesh,
            batch_size=args.batch_size,
            sketch=SketchConfig(
                cms_width=args.cms_width,
                cms_depth=args.cms_depth,
                hll_p=args.hll_p,
                topk_every=args.topk_every,
            ),
            register_memory_budget_bytes=args.register_budget_mb << 20,
            resume=args.resume,
            stall_timeout_sec=args.stall_timeout,
            update_impl=args.update_impl,
            fault_plan=_resolve_fault_plan(args.fault_plan),
            retry_policy=args.retry_policy,
            # beside the serve dir, like the ring checkpoint (DESIGN §20)
            blackbox_dir=_resolve_blackbox(
                args, _os.path.join(args.serve_dir, "blackbox")
            ),
        )
        if args.retry_policy:
            from .runtime import retrypolicy

            retrypolicy.parse_spec(args.retry_policy)
        ascfg = _autoscale_config(args)
        mode, length = report_mod.parse_window_spec(args.window)
        scfg = ServeConfig(
            listen=tuple(args.listen),
            window_lines=int(length) if mode == "lines" else 0,
            window_sec=length if mode == "sec" else 0.0,
            ring=args.ring,
            views=tuple(args.view),
            queue_lines=args.queue_lines,
            http=args.http,
            serve_dir=args.serve_dir,
            checkpoint_every_windows=args.checkpoint_every_windows,
            checkpoint_dir=args.checkpoint_dir or "",
            reload_watch=args.reload_watch,
            reload_poll_sec=args.reload_poll,
            max_windows=args.max_windows,
            stop_after_sec=args.stop_after,
            static_analysis=args.static_analysis,
            static_witness_budget=args.static_witness_budget,
            wal=args.wal,
            wal_dir=args.wal_dir,
            wal_segment_bytes=args.wal_segment_kb << 10,
            wal_budget_bytes=args.wal_budget_mb << 20,
            lineage=args.lineage != "off",
            slo=args.slo,
            trend_threshold=args.trend_threshold,
            epoch_store=args.epoch_store,
            epoch_store_budget_bytes=args.epoch_store_budget_mb << 20,
        )
        dscfg = None
        if args.distributed:
            from .config import DistServeConfig

            dscfg = DistServeConfig(
                hosts=args.dist_hosts,
                min_hosts=args.dist_min_hosts,
                max_hosts=args.dist_max_hosts,
                workers=args.dist_workers,
                merge_bind=args.dist_merge_bind,
                merge_timeout_sec=args.dist_merge_timeout,
                respawn=args.dist_respawn,
                lease_ttl_sec=args.dist_lease_ttl,
                spool_dir=args.dist_spool_dir,
                spool_budget_mb=args.dist_spool_budget_mb,
            )
    except (ValueError, errors.AnalysisError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        from .runtime.compcache import enable_persistent_cache
        from .runtime.serve import ServeDriver  # deferred: imports JAX
    except ImportError as e:
        print(f"error: tpu backend unavailable ({e})", file=sys.stderr)
        return 1
    enable_persistent_cache()
    if args.trace_out or args.metrics_out:
        from .runtime import obs

        try:
            if args.trace_out:
                obs.start_trace(args.trace_out, role="serve")
            if args.metrics_out:
                obs.start_metrics(args.metrics_out, args.metrics_every)
                from .runtime.devprof import device_memory_gauges

                obs.register_sampler("device_mem", device_memory_gauges)
        except OSError as e:
            print(
                f"error: cannot open --trace-out/--metrics-out target: {e}",
                file=sys.stderr,
            )
            return 2
    rc = _arm_devprof(args)
    if rc is not None:
        return rc
    try:
        # construction binds the listener sockets: a privileged port or
        # an address in use must be the documented clean error, not a
        # traceback
        if args.tenants:
            if ascfg is not None:
                print("error: --autoscale does not combine with --tenants "
                      "(the tenancy plane packs many rulesets onto one "
                      "fixed mesh)", file=sys.stderr)
                return 2
            from .runtime.tenantserve import TenantServeDriver

            try:
                driver = TenantServeDriver(
                    args.tenants, cfg, scfg, topk=args.topk,
                    distributed=dscfg,
                )
            except errors.AnalysisError as e:
                # bad manifest / unsupported combination (e.g. --resume
                # with --tenants): typed refusal, exit 2.  A bad
                # --ruleset stays on main()'s typed-load path (exit 1).
                print(f"error: {e}", file=sys.stderr)
                return 2
        elif args.distributed:
            from .runtime.distserve import DistServeDriver

            try:
                driver = DistServeDriver(
                    args.ruleset, cfg, scfg, dscfg,
                    topk=args.topk, ascfg=ascfg,
                )
            except errors.AnalysisError as e:
                # unsupported combination (--mesh flat, --static-analysis)
                # or an unreadable ruleset: typed refusal, exit 2
                print(f"error: {e}", file=sys.stderr)
                return 2
        else:
            driver = ServeDriver(
                args.ruleset, cfg, scfg, topk=args.topk, ascfg=ascfg
            )
    except OSError as e:
        print(f"error: cannot bind --listen/--http: {e}", file=sys.stderr)
        return 2
    try:
        summary = driver.run()
    except OSError as e:
        print(f"error: serve I/O failure: {e}", file=sys.stderr)
        return 1
    import json as json_mod

    print(json_mod.dumps(summary, indent=2))
    return 0


def _cmd_convert(args: argparse.Namespace) -> int:
    """Text syslog -> pre-tokenized .rawire wire file (SURVEY.md §8.2).

    Parses once (native C++ parser when available) and writes the 16 B/line
    bit-packed evaluation rows; `run` then feeds the device straight from
    the mmap'd file, skipping the host parse that bottlenecks e2e.
    """
    from .hostside import wire

    if args.block_rows < 1:
        print("error: --block-rows must be >= 1", file=sys.stderr)
        return 2
    from .hostside.convertfleet import is_manifest_file

    already = [
        p for p in args.logs if wire.is_wire_file(p) or is_manifest_file(p)
    ]
    if already:
        # a shell glob catching *.rawire must not "convert" binary data
        # through the text parser into a valid-but-empty wire file
        print(
            f"error: {already[0]!r} is already a wire file; convert takes "
            "text syslog inputs",
            file=sys.stderr,
        )
        return 2
    packed = pack.load_packed(args.ruleset)
    if args.workers and args.workers >= 1:
        # convert fleet (ISSUE 11): N processes, N pre-coalesced weighted
        # shards, one manifest at --out; byte-identical for any N
        from .hostside.convertfleet import convert_logs_fleet

        if args.native_parse is False:
            print("error: --workers requires the native parser", file=sys.stderr)
            return 2
        stats = convert_logs_fleet(
            packed,
            args.logs,
            args.out,
            workers=args.workers,
            # --block-rows doubles as the descriptor granularity: shards
            # split (and batches coalesce) at exact multiples of it, so
            # the stored stream is a pure function of (corpus, block-rows)
            batch_size=args.block_rows,
            block_rows=args.block_rows,
            coalesce=True,  # the fleet always writes the weighted format
        )
    else:
        stats = wire.convert_logs(
            packed,
            args.logs,
            args.out,
            native=args.native_parse,
            block_rows=args.block_rows,
            feed_workers=args.feed_workers,
            coalesce=args.coalesce,
        )
    mb = stats["bytes"] / 1e6
    if stats.get("weighted"):
        stored = stats["rows"] + stats["rows6"]
        ratio = stats["evals"] / max(stored, 1)
        shape = (
            f"{stored} weighted rows for {stats['evals']} evaluations "
            f"(compaction {ratio:.2f}x)"
        )
    else:
        shape = f"{stats['evals']} evaluation rows"
    print(
        f"wrote {args.out}: {shape}"
        f"{' (' + str(stats['rows6']) + ' v6)' if stats.get('rows6') else ''} from "
        f"{stats['raw_lines']} lines ({stats['skipped']} skipped), "
        f"{mb:.1f} MB, parser={stats['parser']}",
        file=sys.stderr,
    )
    return 0


def _cmd_wire_info(args: argparse.Namespace) -> int:
    """Inspect .rawire headers; optionally validate against a ruleset."""
    import json as json_mod

    from .hostside import wire
    from .hostside.convertfleet import expand_wire_inputs

    args.files = expand_wire_inputs(args.files)
    # hash the ruleset once, not once per file
    fp = (
        wire.ruleset_fingerprint(pack.load_packed(args.ruleset))
        if args.ruleset
        else None
    )
    rc = 0
    rows = []
    for path in args.files:
        try:
            r = wire.WireReader([path], fingerprint=fp)
        except (wire.WireFormatError, OSError) as e:
            rows.append({"file": path, "ok": False, "error": str(e)})
            rc = 1
            continue
        rows.append({
            "file": path,
            "ok": True,
            "rows": r.n_rows,
            "rows6": r.n6_rows,
            "raw_lines": r.raw_lines,
            "skipped_lines": r.n_skipped,
            "block_rows": r.block_rows,
            "bytes_per_row": wire.ROWW_BYTES if r.weighted else wire.ROW_BYTES,
            "weighted": r.weighted,
            # weighted (coalesced) files: true evaluation count behind
            # the stored unique rows
            **({"evals": r.n_evals} if r.weighted else {}),
            # null = no ruleset given, nothing was checked; a real
            # mismatch surfaces as ok=false with the fingerprint error
            "ruleset_match": True if fp is not None else None,
        })
        r.close()
    if args.json:
        print(json_mod.dumps(rows, indent=2))
    else:
        for e in rows:
            if e["ok"]:
                w = (
                    f" weighted rows ({e['evals']} evaluations)"
                    if e.get("weighted")
                    else " rows"
                )
                print(
                    f"{e['file']}: {e['rows']}{w}"
                    f"{' + ' + str(e['rows6']) + ' v6 rows' if e.get('rows6') else ''}"
                    f" from {e['raw_lines']} lines "
                    f"({e['skipped_lines']} skipped), block={e['block_rows']}"
                    + (", ruleset OK" if args.ruleset else "")
                )
            else:
                print(f"{e['file']}: INVALID — {e['error']}")
    return rc


def _cmd_analyze(args: argparse.Namespace) -> int:
    """Static ruleset analysis: which rules can NEVER get a hit.

    The dual of ``run``: no traffic at all — per-rule reachability
    verdicts from the packed rule tensor alone (runtime/staticanalysis),
    with every dead verdict carrying an exact single-rule cover or a
    complete witness-exhaustion record.
    """
    import json as json_mod

    from .runtime import faults, staticanalysis

    if args.witness_budget < 1:
        print("error: --witness-budget must be >= 1", file=sys.stderr)
        return 2
    if args.tile is not None and args.tile < 1:
        print("error: --tile must be >= 1", file=sys.stderr)
        return 2
    packed = pack.load_packed(args.ruleset)
    armed_here = faults.arm_spec(_resolve_fault_plan(args.fault_plan))
    try:
        sa = staticanalysis.analyze_ruleset(
            packed, tile=args.tile, witness_budget=args.witness_budget
        )
    finally:
        if armed_here:
            faults.disarm()
    obj = sa.to_obj(packed)
    payload = (
        json_mod.dumps(obj, indent=2)
        if args.json
        else staticanalysis.render_text(packed, obj)
    )
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(payload + "\n")
    else:
        print(payload)
    return 0


def _cmd_lint(args: argparse.Namespace) -> int:
    """ralint: static program-invariant verification (DESIGN §18).

    Traces every shipping step program to a closed jaxpr by abstract
    eval (no device data, no XLA compile) and verifies weight-linearity,
    scatter safety, ra.* scope coverage, and merge-law conformance;
    cross-checks the derived weighted-refusal set against the ONE
    declarative table in config.py; audits the repo registries (fault
    sites / CLI flags vs docs / volatile totals keys).  Exit 0 = every
    invariant proven (or typed-refused), 1 = findings.
    """
    import json as json_mod

    from .verify import render_text, run_lint

    rep = run_lint(
        full=not args.fast,
        registry=not args.skip_registry,
        repo_root=args.repo_root,
    )
    if args.json:
        payload = json_mod.dumps(rep.to_dict(), indent=2)
    else:
        payload = render_text(rep)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(payload + "\n")
    else:
        print(payload)
    return 0 if rep.ok else 1


def _cmd_diff_reports(args: argparse.Namespace) -> int:
    """Compare two JSON run reports: the operator's delete-decision view.

    The reference's end goal is "which rules can we safely delete"; one
    run can't answer that (a rule may simply be quiet this week).  This
    diff shows stability across runs: rules unused in BOTH reports are
    the deletion candidates, newly-unused / newly-used rules are the
    churn to investigate.
    """
    import json as json_mod

    if args.top < 0:
        print("error: --top must be >= 0", file=sys.stderr)
        return 2

    def load(path):
        with open(path, "r", encoding="utf-8") as f:
            return json_mod.load(f)

    try:
        rep_a, rep_b = load(args.old), load(args.new)
        if args.expect_window:
            # typed refusal: a 24h window diffed against a 7d window is a
            # misleading answer, not a smaller one (main() maps the code)
            report_mod.check_window_compat(rep_a, rep_b, args.expect_window)
        out = report_mod.diff_report_objs(rep_a, rep_b, top=args.top)
    except errors.AnalysisError:
        raise
    except (OSError, ValueError, KeyError, TypeError) as e:
        print(f"error: unreadable report: {e}", file=sys.stderr)
        return 2

    if args.json:
        print(json_mod.dumps(out, indent=2))
        return 0
    print(f"# stable unused (deletion candidates): {len(out['stable_unused'])}")
    for k in out["stable_unused"]:
        print(f"  {k}")
    print(f"# newly unused (quiet this run): {len(out['newly_unused'])}")
    for k in out["newly_unused"]:
        print(f"  {k}")
    print(f"# newly used (were unused before): {len(out['newly_used'])}")
    for k in out["newly_used"]:
        print(f"  {k}")
    if out["rules_added"] or out["rules_removed"]:
        print(
            f"# ruleset churn: {len(out['rules_added'])} added, "
            f"{len(out['rules_removed'])} removed between reports"
        )
    if out["top_hit_movers"]:
        print("# top hit movers:")
        for m in out["top_hit_movers"]:
            print(f"  {m['rule']}: {m['old']} -> {m['new']}")
    if out.get("verdict_transitions"):
        print(
            f"# static verdict transitions: {len(out['verdict_transitions'])}"
            " (a rule changing reachability class across a ruleset change)"
        )
        for m in out["verdict_transitions"]:
            print(f"  {m['rule']}: {m['old']} -> {m['new']}")
    if out.get("window_incomplete"):
        print(
            f"# WARNING: incomplete window(s): {', '.join(out['window_incomplete'])}"
            " — churn there may be drop artifacts, not traffic"
        )
    return 0


def _cmd_synth(args: argparse.Namespace) -> int:
    import os

    os.makedirs(args.out_dir, exist_ok=True)
    cfg_text = synth.synth_config(
        n_acls=args.acls, rules_per_acl=args.rules, seed=args.seed,
        hostname=args.hostname, v6_fraction=args.v6_fraction,
    )
    cfg_path = f"{args.out_dir}/{args.hostname}.cfg"
    with open(cfg_path, "w", encoding="utf-8") as f:
        f.write(cfg_text)
    rs = aclparse.parse_asa_config(cfg_text, args.hostname)
    packed = pack.pack_rulesets([rs])
    n6 = int(args.lines * args.v6_fraction) if packed.has_v6 else 0
    if args.flows > 0:
        # flow-repetition tier: Zipf(--skew) draws from a bounded flow
        # pool, the feedstock the coalescing ingest tier compacts
        tuples = synth.synth_flow_tuples(
            packed, args.lines - n6, args.flows, skew=args.skew,
            seed=args.seed,
        )
    else:
        tuples = synth.synth_tuples(packed, args.lines - n6, seed=args.seed)
    log_lines = synth.render_syslog(packed, tuples, seed=args.seed)
    if n6:
        import random as _random

        t6 = synth.synth_tuples6(packed, n6, seed=args.seed)
        log_lines = log_lines + synth.render_syslog6(packed, t6, seed=args.seed + 1)
        _random.Random(args.seed).shuffle(log_lines)
    log_path = f"{args.out_dir}/{args.hostname}.log"
    with open(log_path, "w", encoding="utf-8") as f:
        f.write("\n".join(log_lines) + "\n")
    pack.save_packed(packed, f"{args.out_dir}/{args.hostname}")
    print(f"wrote {cfg_path}, {log_path}, {args.out_dir}/{args.hostname}.npz", file=sys.stderr)
    return 0


def _add_blackbox_flags(p) -> None:
    p.add_argument("--blackbox", choices=["on", "off"], default="on",
                   help="always-on flight recorder (DESIGN §20): every "
                        "process keeps a bounded in-memory ring of recent "
                        "telemetry (spans, fault/retry/degraded instants, "
                        "metrics snapshots, commit cursors); a typed "
                        "abort, watchdog stall, unhandled crash, or "
                        "SIGQUIT dumps per-PID shards merged into "
                        "postmortem.json — a clean exit leaves nothing. "
                        "Default on (no per-event file I/O; <2%% budget, "
                        "BENCH_BLACKBOX artifact)")
    p.add_argument("--blackbox-dir", default=None, metavar="DIR",
                   help="crash-forensics directory (default: a 'blackbox' "
                        "dir beside the checkpoint/serve dir); exported "
                        "as RA_BLACKBOX_DIR so spawned feeder/elastic "
                        "workers dump sibling shards; diagnose a bundle "
                        "with `ruleset-analyze doctor`")


def _cmd_doctor(args: argparse.Namespace) -> int:
    """Postmortem bundle + exit code -> ranked human-readable diagnosis.

    The first-response runbook for exit codes 3-8: reads the
    ``postmortem.json`` a crashed run's flight recorder merged and names
    the failing stage, the fired fault sites, and the next action.
    """
    import json as json_mod

    from .runtime import flightrec

    try:
        bundle = flightrec.load_bundle(args.bundle)
    except (OSError, ValueError) as e:
        print(f"error: unreadable postmortem bundle: {e}", file=sys.stderr)
        return 1
    lpath = getattr(args, "lineage", None) or flightrec.find_lineage(args.bundle)
    lineage = flightrec.load_lineage(lpath) if lpath else []
    diags = flightrec.diagnose(
        bundle, exit_code=args.exit_code, lineage=lineage
    )
    if args.json:
        from .runtime.report import lineage_frontier

        payload = json_mod.dumps(
            {
                "trigger": bundle.get("trigger"),
                "exit_code": (
                    args.exit_code if args.exit_code is not None
                    else bundle.get("exit_code")
                ),
                "error": bundle.get("error"),
                "error_type": bundle.get("error_type"),
                "failing_stage": bundle.get("analysis", {}).get("failing_stage"),
                "lineage_path": lpath,
                "lineage_frontier": (
                    lineage_frontier(lineage) if lineage else None
                ),
                "diagnosis": diags,
            },
            indent=2,
        )
    else:
        payload = flightrec.render_diagnosis(bundle, diags)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(payload + "\n")
    else:
        print(payload)
    return 0


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="ruleset-analyze")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("parse-acls", help="parse ASA configs into a packed ruleset")
    p.add_argument("configs", nargs="+")
    p.add_argument("--out", required=True, help="output path prefix")
    p.add_argument("--lenient", action="store_true",
                   help="skip (and count) unsupported access-list entries — "
                        "IPv6, exotic object members — instead of aborting; "
                        "skipped entries keep their rule positions")
    p.set_defaults(fn=_cmd_parse_acls)

    p = sub.add_parser(
        "fetch-acls",
        help="acquire + parse configs from a firewall inventory "
             "(config.FIREWALLS or --inventory)",
    )
    p.add_argument("--inventory", default=None, metavar="FILE",
                   help="'name = source' lines; source is a config file path "
                        "or cmd:<shell command> whose stdout is the config "
                        "(default: config.FIREWALLS). cmd: sources run "
                        "through the shell — the inventory file must be "
                        "trusted like a shell script")
    p.add_argument("--out", required=True, help="output path prefix")
    p.add_argument("--lenient", action="store_true",
                   help="skip-and-count unsupported entries (see parse-acls)")
    p.set_defaults(fn=_cmd_fetch_acls)

    p = sub.add_parser("run", help="run the analysis over syslog")
    p.add_argument("--ruleset", required=True, help="packed ruleset path prefix")
    p.add_argument("--logs", nargs="+", required=True, help="syslog file(s), '-' for stdin")
    p.add_argument("--backend", choices=["oracle", "tpu"], default="tpu")
    p.add_argument("--acl-configs", nargs="*", default=[], help="original configs (oracle backend)")
    p.add_argument("--lenient", action="store_true",
                   help="parse --acl-configs leniently (see parse-acls --lenient)")
    p.add_argument("--batch-size", type=int, default=1 << 16)
    p.add_argument("--cms-width", type=int, default=1 << 14)
    p.add_argument("--cms-depth", type=int, default=4)
    p.add_argument("--hll-p", type=int, default=8)
    p.add_argument("--exact-counts", action=argparse.BooleanOptionalAction, default=True,
                   help="--no-exact-counts drops the exact per-rule bincount and "
                        "reports CMS estimates instead (the BASELINE.json "
                        "north-star configuration: sketches only)")
    p.add_argument("--register-budget-mb", type=int, default=4096, metavar="MB",
                   help="ceiling on device register memory (counts+CMS+HLL); "
                        "oversized geometries fail fast with a suggested --hll-p")
    p.add_argument("--topk", type=int, default=10)
    p.add_argument("--topk-sample-shift", type=int, default=0, metavar="S",
                   help="select per-chunk talker candidates from every "
                        "2^S-th line (the talker sketch still covers every "
                        "line; trims the scatter-bound share of the device "
                        "step; 0 = full batch)")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="CHUNKS",
                   help="snapshot (offset, registers) every N chunks")
    p.add_argument("--checkpoint-dir", default=None,
                   help="default: $RA_OUTPUT_DIR/ckpt (see config.py)")
    p.add_argument("--resume", action="store_true",
                   help="resume from --checkpoint-dir if a snapshot exists")
    p.add_argument("--report-every", type=int, default=0, metavar="CHUNKS",
                   help="print throughput to stderr every N chunks")
    p.add_argument("--native-parse", action=argparse.BooleanOptionalAction, default=None,
                   help="use the C++ host parser (default: auto when logs are files)")
    p.add_argument("--packed-input", action="store_true",
                   help="require --logs to be .rawire wire files (see "
                        "`convert`; wire inputs are also auto-detected)")
    p.add_argument("--feed-workers", type=int, default=0, metavar="N",
                   help="parse with N workers over file shards "
                        "(multi-core hosts; implies the native parser; 0/1 = off)")
    p.add_argument("--feed-mode", choices=["process", "thread", "ring"],
                   default="process",
                   help="worker kind for --feed-workers: separate processes "
                        "packing into shared memory, in-process threads "
                        "around the GIL-releasing native parser, or 'ring' — "
                        "one pinned shared-memory ring PER CHIP with a "
                        "partitioned producer pool, each chip's device_put "
                        "fed straight from its own ring (bit-identical "
                        "reports across all three modes)")
    p.add_argument("--coalesce", choices=["off", "on", "auto"], default="off",
                   help="pre-aggregate each batch's duplicate flow tuples "
                        "into (unique row, weight) pairs before the device "
                        "step — shrinks the scatter-bound step, H2D bytes "
                        "and device rows by the traffic's repetition ratio "
                        "with a bit-identical report; 'auto' samples the "
                        "first batches and turns itself off below the "
                        "break-even ratio (single-process runs; for "
                        "--distributed use `convert --coalesce`)")
    p.add_argument("--prefetch-depth", type=int,
                   default=AnalysisConfig.prefetch_depth, metavar="K",
                   help="pipelined ingest: parse/pack/device_put up to K "
                        "batches ahead of the device step on a background "
                        "producer (bit-identical reports; 0 = synchronous "
                        "driver)")
    p.add_argument("--stall-timeout", type=float,
                   default=AnalysisConfig.stall_timeout_sec, metavar="SEC",
                   help="watchdog bound on a pipeline stage making no "
                        "progress before the run aborts with a typed "
                        "StallError (exit code 6) instead of hanging; "
                        "progress resets the window")
    p.add_argument("--fault-plan", default=None, metavar="SPEC",
                   help="ARM deterministic fault injection (testing/chaos "
                        "drills only): 'site@N[,site@N][,seed=S]' fires "
                        "each named site on its Nth hit — the transient "
                        "form site@N:k fires k consecutive hits then "
                        "clears (retry-recovery drills) — or @FILE holding "
                        "the spec; see runtime/faults.py SITES and DESIGN "
                        "§9/§19 for the registered sites")
    p.add_argument("--retry-policy", default="", metavar="SPEC",
                   help="override the typed retry/backoff engine (DESIGN "
                        "§19): 'site=attempts[/base_sec],...,seed=S' "
                        "tunes per-site bounds, 'off' collapses every "
                        "site to a single attempt (A/B measurement); "
                        "empty = the built-in per-site defaults, which "
                        "are always armed")
    p.add_argument("--mesh", choices=["flat", "hybrid"], default="flat",
                   help="device mesh topology: flat = one data axis over "
                        "every device; hybrid = the two-level DCN x ICI "
                        "mesh (an outer between-host axis times an inner "
                        "ICI axis, the create_hybrid_device_mesh idiom) — "
                        "batches shard and registers merge over BOTH "
                        "axes, reports bit-identical to flat (DESIGN §13)")
    p.add_argument("--mesh-dcn", type=int, default=0, metavar="N",
                   help="outer (DCN) extent of --mesh hybrid; 0 = auto "
                        "(process count when multi-host, else 2)")
    p.add_argument("--layout", choices=["flat", "stacked"], default="flat",
                   help="rule-match layout: flat scans all rules per line; stacked "
                        "buckets lines by ACL and vmaps over per-ACL rule slabs "
                        "(faster for many firewalls/ACLs)")
    p.add_argument("--stacked-lane", type=int, default=0, metavar="N",
                   help="per-ACL lane width for --layout=stacked (0 = auto)")
    p.add_argument("--match-impl", choices=["xla", "pallas"],
                   default="xla",
                   help="first-match kernel (pallas runs in no benchmark cell; it "
                        "lost to xla on the TPU in round 5, DESIGN §3)")
    p.add_argument("--experimental-match-impl", choices=["pallas_fused"],
                   default=None, metavar="IMPL",
                   help="enable an EXPERIMENTAL kernel, overriding "
                        "--match-impl (pallas_fused: match + in-VMEM counts "
                        "in one kernel, 0.08x of xla in the step on the TPU "
                        "in round 5 (DESIGN §8); runs in no benchmark cell; "
                        "logged loudly at run time)")
    p.add_argument("--counts-impl", choices=["scatter", "matmul", "reduce"],
                   default="scatter",
                   help="exact-counts formulation (all bit-identical; "
                        "matmul and reduce run in no benchmark cell, matmul "
                        "lost in the step on the TPU in round 5, DESIGN §8)")
    p.add_argument("--update-impl", choices=["scatter", "sorted"],
                   default="scatter",
                   help="register-update formulation (DESIGN §15): scatter "
                        "= batch-sized scatter updates; sorted = sort the "
                        "batch's register keys once and segment-reduce "
                        "over the sorted runs (the MapReduce-combiner "
                        "sort half; weight-linear, composes with "
                        "--coalesce).  Reports are bit-identical; "
                        "sorted runs in no benchmark cell")
    p.add_argument("--topk-every", type=int, default=1, metavar="N",
                   help="run talker candidate SELECTION every Nth chunk "
                        "only (the talker sketch still absorbs every "
                        "line; heavy hitters recur, so deferred selection "
                        "still surfaces them — trims the candidate-table "
                        "share of the device step; 1 = every chunk)")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace here (TensorBoard profile)")
    _add_devprof_flags(p)
    p.add_argument("--trace-out", default=None, metavar="DIR",
                   help="record pipeline spans (parse/pack/H2D/step/"
                        "checkpoint/elastic) + fault-site instants to "
                        "per-process shards in DIR, merged into DIR/"
                        "trace.json at exit — loads in Perfetto / "
                        "chrome://tracing; spawned feeder/elastic workers "
                        "inherit the directory via RA_TRACE_DIR (disarmed "
                        "cost: one None-check per site)")
    p.add_argument("--metrics-out", default=None, metavar="FILE",
                   help="append machine-readable run telemetry (JSON "
                        "lines: lines/s, prefetch queue depth + wait "
                        "times, feeder occupancy, checkpoint bytes/"
                        "latency, recovery events, RSS) to FILE")
    p.add_argument("--metrics-every", type=float, default=10.0, metavar="SEC",
                   help="snapshot cadence of --metrics-out (default 10s)")
    p.add_argument("--distributed", action="store_true",
                   help="join a jax.distributed multi-process job; --logs are "
                        "THIS process's input split (rank 0 prints the report)")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="jax.distributed coordinator (default: environment)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--elastic", action="store_true",
                   help="supervise the distributed job elastically: when a "
                        "peer dies the survivors re-form automatically at "
                        "the surviving world size and resume from the "
                        "shared epoch checkpoint.  --logs becomes the FULL "
                        "shard list (identical on every launcher); needs "
                        "--elastic-dir, --checkpoint-every and --json")
    p.add_argument("--elastic-dir", default=None, metavar="DIR",
                   help="shared rendezvous + epoch-checkpoint directory "
                        "for --elastic (must be visible to every launcher)")
    p.add_argument("--max-reforms", type=int, default=2, metavar="N",
                   help="abort after N automatic cluster re-formations "
                        "(the Hadoop max-task-retries analog; default 2)")
    _add_autoscale_flags(p)
    p.add_argument("--static-analysis", action="store_true",
                   help="join static reachability verdicts into the "
                        "report: unused rules split into provably-dead "
                        "(safe to delete) vs traffic-dependent classes, "
                        "and a rule with hits but a dead verdict is a "
                        "typed error (see the `analyze` subcommand; off "
                        "by default — the report is bit-identical without "
                        "it)")
    p.add_argument("--static-witness-budget", type=int, default=4096,
                   metavar="N",
                   help="per-rule witness-grid cap for --static-analysis")
    _add_blackbox_flags(p)
    p.add_argument("--json", action="store_true")
    p.add_argument("--out", default=None)
    p.set_defaults(fn=_cmd_run)

    p = sub.add_parser(
        "doctor",
        help="diagnose a crashed run: postmortem.json (the flight "
             "recorder's merged crash bundle) + exit code -> ranked "
             "causes with next actions — the first-response runbook for "
             "exit codes 3-8",
    )
    p.add_argument("bundle",
                   help="postmortem.json path, or the blackbox directory "
                        "holding one")
    p.add_argument("--exit-code", type=int, default=None, metavar="RC",
                   help="the run's CLI exit code (default: the code "
                        "recorded in the bundle)")
    p.add_argument("--lineage", default=None, metavar="PATH",
                   help="serve dir's lineage.jsonl to join with the "
                        "bundle (default: auto-detected beside the "
                        "bundle); the joined diagnosis names the last "
                        "fully-published window and the first "
                        "missing/incomplete one")
    p.add_argument("--json", action="store_true")
    p.add_argument("--out", default=None)
    p.set_defaults(fn=_cmd_doctor)

    p = sub.add_parser(
        "analyze",
        help="static ruleset analysis (no traffic): per-rule first-match "
             "reachability verdicts — shadowed/redundant/conflict rules "
             "are PROVABLY dead (device-tiled pair relations; union "
             "coverage certified by corner-point witness packets run "
             "through the production match kernel)",
    )
    p.add_argument("--ruleset", required=True,
                   help="packed ruleset path prefix (parse-acls output)")
    p.add_argument("--tile", type=int, default=None, metavar="T",
                   help="pair-tile edge (default 512); the O(R^2)-per-ACL "
                        "grid is walked in [T, T] device tiles")
    p.add_argument("--witness-budget", type=int, default=4096, metavar="N",
                   help="per-rule cap on witness-grid enumeration; a rule "
                        "whose corner grid exceeds it stays "
                        "partially-masked/uncertified instead of dead "
                        "(dead verdicts always carry a complete proof)")
    p.add_argument("--fault-plan", default=None, metavar="SPEC",
                   help="chaos drills (adds the analyze.tile site); see "
                        "`run --fault-plan`")
    p.add_argument("--json", action="store_true")
    p.add_argument("--out", default=None)
    p.set_defaults(fn=_cmd_analyze)

    p = sub.add_parser(
        "lint",
        help="ralint: static program-invariant verification — traces "
             "every shipping step program to a closed jaxpr (abstract "
             "eval; no device, no compile) and proves weight-linearity, "
             "scatter safety, ra.* scope coverage, and merge-law "
             "conformance; audits repo registries (fault sites, CLI "
             "flags vs docs, volatile totals keys)",
    )
    p.add_argument("--fast", action="store_true",
                   help="lint the representative program subset instead "
                        "of the full impl grid (the tier-1 test budget)")
    p.add_argument("--skip-registry", action="store_true",
                   help="skip the repo registry auditor (jaxpr checks only)")
    p.add_argument("--repo-root", default=None, metavar="DIR",
                   help="repo root for the registry auditor (default: "
                        "the installed package's parent)")
    p.add_argument("--json", action="store_true")
    p.add_argument("--out", default=None)
    p.set_defaults(fn=_cmd_lint)

    p = sub.add_parser(
        "serve",
        help="always-on service mode: live syslog listeners feed "
             "time-windowed registers; windowed/cumulative reports "
             "publish on every rotation to --serve-dir and a loopback "
             "JSON endpoint; SIGHUP (or a watched ruleset-file change) "
             "hot-reloads the rule tensor with counter migration",
    )
    p.add_argument("--ruleset", default=None, help="packed ruleset path prefix "
                   "(re-read on reload); exactly one of --ruleset/--tenants")
    p.add_argument("--tenants", default=None, metavar="MANIFEST",
                   help="multi-tenant mode (runtime/tenantserve.py): a JSON "
                        "manifest of tenants ({'tenants': [{'name', "
                        "'ruleset', 'listen': [...], 'hosts': [...], "
                        "'default': bool}]}) hosts MANY rulesets on one "
                        "mesh — per-tenant windows/reports under "
                        "SERVE_DIR/t/<name>/, per-tenant HTTP routes "
                        "(/tenants, /t/<name>/report...), tenant-labeled "
                        "/metrics, and per-tenant hot reload that never "
                        "pauses other tenants; lines route by @tenant "
                        "tag > per-tenant listener > syslog hostname > "
                        "manifest default")
    p.add_argument("--listen", action="append", default=[], metavar="SPEC",
                   help="ingress (repeatable): udp:HOST:PORT, "
                        "tcp:HOST:PORT (newline-framed), or tail:PATH "
                        "(rotating-file tailer)")
    p.add_argument("--window", required=True, metavar="W",
                   help="rotation cadence: a duration (900s, 15m, 24h) or "
                        "lines:N (deterministic line-count windows)")
    p.add_argument("--ring", type=int, default=8, metavar="N",
                   help="window epochs retained for merged views (default 8)")
    p.add_argument("--view", action="append", type=int, default=[],
                   metavar="K",
                   help="also publish a merged view of the last K windows "
                        "at every rotation (repeatable; e.g. --view 24 "
                        "--view 168 for 24h/7d at a 1h window)")
    p.add_argument("--serve-dir", required=True,
                   help="reports/endpoint/checkpoint directory")
    p.add_argument("--http", default="127.0.0.1:0", metavar="HOST:PORT",
                   help="JSON endpoint bind (port 0 = ephemeral, recorded "
                        "in serve-dir/endpoint.json; 'off' disables). "
                        "Paths: /report /report/cumulative "
                        "/report/window/<id> /report/merged/<k> /diff "
                        "/health /metrics")
    p.add_argument("--queue-lines", type=int, default=1 << 16, metavar="N",
                   help="listener queue capacity; lines past it DROP with "
                        "an explicit count and the window is published "
                        "with a WindowIncomplete marker (default 65536)")
    p.add_argument("--checkpoint-every-windows", type=int, default=1,
                   metavar="N",
                   help="checkpoint the window ring every N rotations "
                        "(0 = never; a restarted serve --resume keeps its "
                        "history)")
    p.add_argument("--checkpoint-dir", default=None,
                   help="default: SERVE_DIR/ckpt")
    p.add_argument("--resume", action="store_true",
                   help="restore the window ring from --checkpoint-dir")
    p.add_argument("--reload-watch", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="poll the ruleset files and hot-reload on change "
                        "(SIGHUP reloads regardless)")
    p.add_argument("--reload-poll", type=float, default=2.0, metavar="SEC")
    p.add_argument("--max-windows", type=int, default=0, metavar="N",
                   help="stop after N rotations (0 = run forever)")
    p.add_argument("--stop-after", type=float, default=0.0, metavar="SEC",
                   help="soft wall-clock deadline (0 = none)")
    p.add_argument("--batch-size", type=int, default=1 << 16)
    p.add_argument("--cms-width", type=int, default=1 << 14)
    p.add_argument("--cms-depth", type=int, default=4)
    p.add_argument("--hll-p", type=int, default=8)
    p.add_argument("--register-budget-mb", type=int, default=4096, metavar="MB")
    p.add_argument("--topk", type=int, default=10)
    p.add_argument("--stall-timeout", type=float,
                   default=AnalysisConfig.stall_timeout_sec, metavar="SEC")
    p.add_argument("--update-impl", choices=["scatter", "sorted"],
                   default="scatter",
                   help="register-update formulation (see `run "
                        "--update-impl`; bit-identical windows)")
    p.add_argument("--topk-every", type=int, default=1, metavar="N",
                   help="defer talker candidate selection to every Nth "
                        "chunk (see `run --topk-every`)")
    p.add_argument("--static-analysis", action="store_true",
                   help="run the static ruleset analyzer at start and on "
                        "every hot reload (unchanged ACLs reuse their "
                        "verdicts): /report/static publishes the verdict "
                        "table, every window report's unused rules carry "
                        "evidence classes (provably-dead vs "
                        "traffic-dependent), and /metrics gains "
                        "static_analysis_age_sec / "
                        "static_analysis_duration_sec")
    p.add_argument("--static-witness-budget", type=int, default=4096,
                   metavar="N",
                   help="per-rule witness-grid cap for the serve analyzer "
                        "(see `analyze --witness-budget`)")
    p.add_argument("--wal", action="store_true",
                   help="durable ingest write-ahead log (DESIGN §19): "
                        "every consumed line spools to segmented, CRC'd "
                        "on-disk records BEFORE window accounting, so "
                        "serve --resume after a hard kill replays the "
                        "interrupted window bit-identical over its "
                        "delivered lines; eviction/corruption losses are "
                        "exactly counted, never silent")
    p.add_argument("--wal-dir", default="",
                   help="WAL directory (default: SERVE_DIR/wal)")
    p.add_argument("--wal-segment-kb", type=int, default=1024, metavar="KB",
                   help="bytes per WAL segment before rolling (default "
                        "1024 KiB)")
    p.add_argument("--wal-budget-mb", type=int, default=64, metavar="MB",
                   help="total on-disk WAL budget; past it the oldest "
                        "segment evicts with its records counted as "
                        "explicit drops at the next resume (default 64)")
    p.add_argument("--lineage", choices=["on", "off"], default="on",
                   help="window provenance plane (DESIGN §24, default "
                        "on): every published window carries a sealed "
                        "totals.lineage record — contributing hosts with "
                        "their delivered WAL ranges, drop/quarantine "
                        "counts, supervisor term, publication path "
                        "(live/replay/backlog_heal), reload generation, "
                        "CRC — appended durably to SERVE_DIR/"
                        "lineage.jsonl and served at /lineage; 'off' "
                        "drops the plane for benchmarking the overhead")
    p.add_argument("--slo", default="", metavar="SPEC",
                   help="SLO burn-rate alerting over published windows "
                        "(Google SRE fast/slow pairs), e.g. "
                        "'p99_publish_ms<=500,drop_rate<=0.001': each "
                        "objective tracks fast(3)/slow(12)-window burn "
                        "rates; crossing 2x fast AND 1x slow emits a "
                        "typed slo.breach event (obs instant + metrics "
                        "JSONL + flight recorder) and slo.recovered "
                        "after 3 clean windows.  Metrics: "
                        "p50/p90/p99_publish_ms, drop_rate, "
                        "incomplete_rate, degraded_subsystems")
    p.add_argument("--epoch-store", default="", metavar="DIR",
                   help="durable epoch store + segment-tree summaries "
                        "(DESIGN §25): every rotated window spills to "
                        "CRC'd segment chains under DIR and compaction "
                        "maintains power-of-two merged nodes, so "
                        "/report/range?from=&to= renders any [t0,t1] "
                        "report from <= 2*log2(n) stored aggregates — "
                        "bit-identical to folding the raw epochs, no "
                        "replay — and /report/last-hit serves each "
                        "rule's last-hit window + wall time (the quiet "
                        "horizon safe_to_delete verdicts cite).  Bounds "
                        "range by id or unix seconds; a range the store "
                        "cannot fully cover answers a typed "
                        "range_incomplete, never silent zeros")
    p.add_argument("--epoch-store-budget-mb", type=int, default=512,
                   metavar="MB",
                   help="total on-disk epoch-store budget; past it the "
                        "oldest RAW-epoch segment evicts first (coarse "
                        "summary nodes still answer aligned queries "
                        "over the evicted span) (default 512)")
    p.add_argument("--trend-threshold", type=float, default=4.0,
                   metavar="X",
                   help="per-rule traffic trend events in diff.json: a "
                        "rule whose per-line hit rate grows by more "
                        "than Xx between consecutive windows emits "
                        "rule_burst, shrinking by Xx emits rule_quiet, "
                        "with sqrt(X) hysteresis so steady load near "
                        "the boundary never storms (0 disables; "
                        "default 4.0)")
    p.add_argument("--mesh", choices=["flat", "hybrid"], default="flat",
                   help="device mesh topology (parallel/mesh.py); "
                        "--distributed requires 'hybrid' (the host tier "
                        "IS the outer dcn axis, DESIGN §22)")
    p.add_argument("--distributed", action="store_true",
                   help="multi-host serve (runtime/distserve.py, DESIGN "
                        "§22): each host runs its own listener tier + "
                        "WAL + local mesh ingesting into host-local "
                        "registers; window epochs merge across hosts at "
                        "rank 0 under the register merge laws, so the "
                        "published report is bit-identical to a single-"
                        "host replay of the union of all hosts' "
                        "delivered lines.  Rank 0 owns publication, "
                        "HTTP, and the merged-ring checkpoint; listener "
                        "ports offset by host rank")
    p.add_argument("--dist-hosts", type=int, default=2, metavar="N",
                   help="ingest hosts to launch (default 2)")
    p.add_argument("--dist-min-hosts", type=int, default=1, metavar="N",
                   help="host-tier autoscale ladder floor (default 1)")
    p.add_argument("--dist-max-hosts", type=int, default=0, metavar="N",
                   help="host-tier ladder ceiling (0 = --dist-hosts). "
                        "Part of the checkpoint resume identity: any "
                        "live host count resumes any other under the "
                        "SAME ceiling")
    p.add_argument("--dist-workers", choices=["process", "thread"],
                   default="process",
                   help="host worker isolation (process = one OS process "
                        "per host, the production mode; thread = "
                        "in-process, the deterministic test mode)")
    p.add_argument("--dist-merge-bind", default="127.0.0.1:0",
                   metavar="HOST:PORT",
                   help="rank-0 merge-plane bind for process workers "
                        "(port 0 = ephemeral, recorded in endpoint.json)")
    p.add_argument("--dist-merge-timeout", type=float, default=120.0,
                   metavar="SEC",
                   help="max wait for a live host's epoch past a "
                        "window's first arrival before publishing "
                        "without it (named host_missing; default 120)")
    p.add_argument("--dist-respawn", action="store_true",
                   help="respawn a dead host at the merge frontier; its "
                        "WAL replays the lost tail on rejoin")
    p.add_argument("--dist-lease-ttl", type=float, default=2.0,
                   metavar="SEC",
                   help="supervisor-lease TTL (DESIGN §23): a holder "
                        "that cannot renew this long self-fences (stops "
                        "publishing, exits typed code 8); a successor "
                        "steals only after 1.5x, so takeover completes "
                        "within ~2x TTL with no split brain.  0 "
                        "disables the lease/failover plane (default 2)")
    p.add_argument("--dist-spool-dir", default="", metavar="DIR",
                   help="durable per-host epoch spool + lease root "
                        "(default: under --serve-dir).  Point at shared "
                        "storage so a successor elsewhere can replay "
                        "every host's spooled window epochs")
    p.add_argument("--dist-spool-budget-mb", type=int, default=64,
                   metavar="MB",
                   help="per-host epoch-spool disk budget; oldest "
                        "segments evict first, counted never silent "
                        "(0 disables spooling; default 64)")
    _add_autoscale_flags(p)
    _add_blackbox_flags(p)
    p.add_argument("--fault-plan", default=None, metavar="SPEC",
                   help="chaos drills: see `run --fault-plan` (adds the "
                        "listener.drop/listener.stall/reload.midbatch, "
                        "listener.bind.fail/listener.accept.fail/"
                        "serve.publish.fail/metrics.snapshot.fail, and "
                        "autoscale.decide/autoscale.spawn sites)")
    p.add_argument("--retry-policy", default="", metavar="SPEC",
                   help="retry/backoff overrides: see `run --retry-policy`")
    _add_devprof_flags(p)
    p.add_argument("--trace-out", default=None, metavar="DIR",
                   help="record listener/rotation/reload spans (see "
                        "`run --trace-out`)")
    p.add_argument("--metrics-out", default=None, metavar="FILE",
                   help="append queue/drop gauges + window events as JSON "
                        "lines")
    p.add_argument("--metrics-every", type=float, default=10.0, metavar="SEC")
    p.set_defaults(fn=_cmd_serve)

    p = sub.add_parser(
        "convert",
        help="pre-tokenize text syslog into a .rawire wire file "
             "(16 B/line; `run` feeds it to the device with no host parse)",
    )
    p.add_argument("--ruleset", required=True, help="packed ruleset path prefix")
    p.add_argument("--logs", nargs="+", required=True, help="text syslog file(s)")
    p.add_argument("--out", required=True, help="output .rawire path")
    p.add_argument("--native-parse", action=argparse.BooleanOptionalAction, default=None,
                   help="use the C++ parser for the one-time conversion (default: auto)")
    p.add_argument("--block-rows", type=int, default=1 << 16, metavar="N",
                   help="rows per payload block; match the run --batch-size "
                        "for the zero-copy mmap read path (default 65536)")
    p.add_argument("--feed-workers", type=int, default=0, metavar="N",
                   help="parse with N worker processes (multi-core one-time "
                        "conversion; output is byte-identical; 0/1 = off)")
    p.add_argument("--coalesce", action="store_true",
                   help="write the weighted v3 format: per-batch duplicate "
                        "flow tuples store once with a repetition count "
                        "(20 B/row + weights; bit-identical reports, file "
                        "and every later device step shrink by the "
                        "corpus's compaction ratio)")
    p.add_argument("--workers", type=int, default=0, metavar="N",
                   help="convert FLEET: shard the corpus by exact-raw-line "
                        "descriptors across N worker processes, each "
                        "writing one pre-coalesced RAWIREv3 shard; --out "
                        "becomes a merge manifest `run` consumes as one "
                        "corpus (bit-identical for any N; implies the "
                        "weighted format; 0 = classic single-file convert)")
    p.set_defaults(fn=_cmd_convert)

    p = sub.add_parser(
        "wire-info",
        help="inspect .rawire wire-file headers (row/line counts, "
             "integrity; --ruleset validates the fingerprint)",
    )
    p.add_argument("files", nargs="+", help=".rawire file(s)")
    p.add_argument("--ruleset", default=None,
                   help="packed ruleset prefix to validate the fingerprint against")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=_cmd_wire_info)

    p = sub.add_parser(
        "diff-reports",
        help="compare two `run --json` reports: stable-unused deletion "
             "candidates, newly used/unused rules, top hit movers",
    )
    p.add_argument("old", help="earlier report (run --json output)")
    p.add_argument("new", help="later report")
    p.add_argument("--top", type=int, default=10, help="hit movers to show")
    p.add_argument("--expect-window", default=None, metavar="W",
                   help="require BOTH reports to be serve-mode window "
                        "reports of exactly this window (lines:N or a "
                        "duration like 24h); a mismatch is a typed "
                        "refusal, not a misleading diff")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=_cmd_diff_reports)

    p = sub.add_parser("synth", help="generate synthetic config + syslog")
    p.add_argument("--out-dir", required=True)
    p.add_argument("--acls", type=int, default=4)
    p.add_argument("--rules", type=int, default=32)
    p.add_argument("--lines", type=int, default=10000)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--hostname", default="fw1")
    p.add_argument("--v6-fraction", type=float, default=0.0,
                   help="fraction of ACEs (and log lines) spelled IPv6 — "
                        "generates a unified v4+v6 config and mixed corpus")
    p.add_argument("--flows", type=int, default=0, metavar="M",
                   help="draw lines from a pool of M distinct flows with "
                        "Zipf(--skew) repetition (the coalescing tier's "
                        "feedstock; 0 = independent lines as before)")
    p.add_argument("--skew", type=float, default=1.0, metavar="S",
                   help="Zipf exponent for --flows (0 = uniform; larger "
                        "concentrates traffic on head flows; default 1.0)")
    p.set_defaults(fn=_cmd_synth)
    return ap


def _finalize_obs() -> None:
    """Stop the metrics thread + merge trace shards, typed aborts included.

    Runs from ``main``'s finally so a run that dies with an
    AnalysisError still leaves ONE merged timeline — a disarmed run
    exits through two None-checks.
    """
    from .runtime import devprof, obs

    try:
        cap = devprof.active_capture()
        if cap is not None and getattr(cap, "json_path", None):
            print(
                f"devprof: {cap.json_path} (per-stage attribution; diff "
                "two captures with tools/trace_diff.py)",
                file=sys.stderr,
            )
    except Exception as e:
        print(f"warning: devprof summary hint failed: {e}", file=sys.stderr)
    try:
        merged = obs.shutdown()
    except Exception as e:  # a broken merge must not mask the run's rc
        print(f"warning: trace merge failed: {e}", file=sys.stderr)
        merged = None
    finally:
        # AFTER obs.shutdown: the metrics plane's final snapshot must
        # still see the devprof/device_mem samplers; this stops any
        # dangling profiler window (typed-abort path) without parsing —
        # never a hang or a half-written devprof.json
        try:
            devprof.shutdown()
        except Exception as e:
            print(f"warning: devprof shutdown failed: {e}", file=sys.stderr)
    if merged:
        print(
            f"trace: {merged} (open in Perfetto or chrome://tracing; "
            "summarize with tools/trace_summary.py)",
            file=sys.stderr,
        )


def _finalize_blackbox() -> None:
    """Dump + merge the flight recorder on abort; prune on a clean exit.

    Runs from ``main``'s finally: by now the error handlers have noted
    any typed abort (and an unhandled exception is still in flight on
    ``sys.exc_info``), so an aborted run leaves ONE ``postmortem.json``
    and a clean run leaves nothing (DESIGN §20).
    """
    from .runtime import flightrec

    try:
        pm = flightrec.finalize()
    except Exception as e:  # forensics must never mask the run's rc
        print(f"warning: postmortem merge failed: {e}", file=sys.stderr)
        return
    if pm:
        print(
            f"postmortem: {pm} (diagnose with `ruleset-analyze doctor "
            f"{pm}`)",
            file=sys.stderr,
        )


def main(argv: list[str] | None = None) -> int:
    args = make_parser().parse_args(argv)
    from .runtime import flightrec

    try:
        return args.fn(args)
    except aclparse.AclParseError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    except errors.AnalysisError as e:
        # failure-class exit codes (errors.exit_code_for, README "Exit
        # codes"): supervisors/operators branch on corrupt checkpoint vs
        # resume mismatch vs feed failure vs stall vs reform budget
        print(f"error: {e}", file=sys.stderr)
        rc = errors.exit_code_for(e)
        flightrec.note_abort(e, rc)
        return rc
    except ValueError as e:
        # User-reachable library validation (corrupt packed-ruleset files,
        # bad distributed divisibility, malformed wire arrays) surfaces as
        # ValueError; a CLI should report it cleanly, not traceback.  The
        # trade-off (a genuine bug raising ValueError also loses its
        # traceback) is accepted for the operator-facing tool; run with
        # RA_DEBUG=1 to re-raise.
        import os

        if os.environ.get("RA_DEBUG"):
            raise
        print(f"error: {e}", file=sys.stderr)
        return 2
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    except BrokenPipeError:
        # stdout consumer (head, less) closed early — normal, not an error
        import os

        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0
    finally:
        _finalize_obs()
        # AFTER obs: a dump's sampler snapshot may read gauges the
        # metrics close would otherwise race; an unhandled exception is
        # still on sys.exc_info here, so finalize sees it
        _finalize_blackbox()


if __name__ == "__main__":
    raise SystemExit(main())


def main_entry() -> None:
    """console_scripts entry point (pyproject.toml: ``ruleset-analyze``)."""
    raise SystemExit(main())
