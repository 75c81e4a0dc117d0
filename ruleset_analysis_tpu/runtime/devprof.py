"""Device-step attribution plane: named-scope profiling + capture windows.

DESIGN §8's scatter-wall numbers were derived BY HAND from a one-off
``jax.profiler`` capture keyed on opaque XLA fusion names (``fusion.5``,
``fusion.7``) that silently remap on any compiler or code change.  This
module makes device attribution repeatable, semantic, and diffable —
the "attribute before you optimize" discipline the scatter-wall attack
(ROADMAP item 2) and the two stage-vs-step inversions (VERDICT Weak
#2/#3) are blocked on.  Three legs (DESIGN §14):

- **Semantic naming.**  Every register-update stage in ``ops/`` and the
  dispatch seams in ``parallel/step.py`` trace under ``jax.named_scope``
  labels (the ``ra.*`` vocabulary: :data:`STAGES`).  Scopes ride HLO op
  *metadata* (``op_name``) through XLA's optimizer, so fusions — even
  renumbered ones — carry the stages they fused.  Trace-time only:
  zero runtime cost, bit-identical outputs.

- **In-process capture windows.**  :class:`DevprofCapture` arms
  ``jax.profiler`` programmatically for a bounded N-dispatch window
  after a warmup (``run/serve --devprof-out DIR [--devprof-steps N]``),
  then parses the trace IN-PROCESS: each profiled event maps through
  the program's *optimized* HLO (re-derived via ``jit.lower(...).
  compile()`` with sharding-preserving abstract args — deterministic
  compilation reproduces the executed module, names included) to the
  outermost ``ra.*`` scope of its instruction's metadata.  The summary
  adds static ``compiled.cost_analysis()`` FLOPs/bytes per program and
  a per-stage instruction/output-byte footprint from the HLO itself,
  lands in ``OUT/devprof.json``, the report's ``totals.devprof`` block,
  the metrics JSONL, and the serve ``/metrics`` gauges.  The arming
  discipline is ``obs.py``'s: disarmed cost is one module-global
  None-check per dispatch.

- **Shared classifier.**  :func:`scope_of` / :func:`classify_event_name`
  are the ONE definition of "which stage does this op belong to" —
  ``tools/trace_attrib.py`` (offline captures) and this module
  (in-process) import the same functions, so offline and in-process
  attribution can never disagree.  ``tools/trace_diff.py`` consumes two
  ``devprof.json`` captures and emits the per-stage delta table with
  fusion-boundary change detection.

Failure model: the ``devprof.capture`` fault site fires at profiler
start AND stop — an injected (or real) profiler failure is a typed
abort or a clean no-trace run (the error is recorded in the summary),
never a hang or a corrupted report.  Single-controller only: the
capture window and trace parse run in one process, so the CLI refuses
``--devprof-out`` under ``--distributed`` multi-process.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import threading
import time

from . import faults, obs

# The stage vocabulary (DESIGN §14) is single-sourced in
# ruleset_analysis_tpu/stages.py — this module, tools/trace_attrib.py,
# and the static lint plane (verify/) all import the SAME tuple, so the
# three consumers can never drift.  Re-exported here because this module
# historically owned it and callers import devprof.STAGES.
from ..stages import SCOPE_RE as _SCOPE_RE  # noqa: F401
from ..stages import STAGES, scope_of  # noqa: F401

#: HLO dtype -> bytes per element (static footprint accounting).
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_SHAPE_RE = re.compile(r"^([a-z]\w*)\[([0-9,]*)\]")


def classify_event_name(name: str, args: dict | None = None) -> str | None:
    """Stage of one raw trace event, from its name or its args.

    The offline half of the shared classifier (tools/trace_attrib.py):
    TPU device tracks carry the full scope path in the event name or in
    metadata-ish args (``long_name``/``tf_op``/``name``); CPU thunk
    events don't — those need the HLO op index an in-process capture
    builds (:func:`parse_hlo_module`).  Returns None when no ``ra.*``
    token is present anywhere (callers fall back to the raw name).
    """
    s = scope_of(name)
    if s is not None:
        return s
    for k in ("long_name", "tf_op", "name", "op_name", "hlo_op"):
        v = (args or {}).get(k)
        if isinstance(v, str):
            s = scope_of(v)
            if s is not None:
                return s
    return None


def _shape_bytes(shape_text: str) -> int:
    """Byte size of one HLO array shape literal (``u32[34,16]{1,0}``).

    Tuple shapes (while/call results) and unknown dtypes report 0 —
    wrappers' footprints are their bodies', already counted.
    """
    m = _SHAPE_RE.match(shape_text.strip())
    if not m:
        return 0
    nbytes = _DTYPE_BYTES.get(m.group(1))
    if nbytes is None:
        return 0
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return n * nbytes


_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"((?:\([^)]*\))|(?:[a-z]\w*\[[0-9,]*\]\S*))\s+([\w\-]+)\("
)
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")


def parse_hlo_module(text: str) -> dict:
    """Index one optimized HLO module for attribution.

    Returns::

        {
          "entry": {instr_name: {"scope", "op", "bytes"}},   # entry computation
          "nested": {instr_name, ...},                        # body instr names
          "fusions": [{"name", "op", "stages": [...]}, ...],  # per fusion instr
        }

    ``entry`` drives event classification: profiled events are counted
    for ENTRY-computation instructions only (their durations contain
    any nested body work, so counting bodies too would double-count).
    ``fusions`` records, for every fusion instruction in ANY
    computation, the set of distinct stages of the instructions inside
    its fused computation — the fusion-boundary signature trace_diff's
    change detection compares.
    """
    entry: dict[str, dict] = {}
    nested: set[str] = set()
    comp_instrs: dict[str, list[tuple[str, str]]] = {}  # comp -> [(instr scope, op)]
    fusion_instrs: list[tuple[str, str, str]] = []  # (name, op_name, called comp)
    cur = None
    in_entry = False
    for line in text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _COMP_RE.match(line)
            if m:
                cur = m.group(2)
                in_entry = bool(m.group(1))
            continue
        m = _INSTR_RE.match(line)
        if m is None or cur is None:
            continue
        name, shape, op = m.group(1), m.group(2), m.group(3)
        om = _OPNAME_RE.search(line)
        op_name = om.group(1) if om else ""
        comp_instrs.setdefault(cur, []).append((op_name, op))
        if op == "fusion":
            cm = _CALLS_RE.search(line)
            if cm:
                fusion_instrs.append((name, op_name, cm.group(1)))
        if in_entry:
            entry[name] = {
                "scope": scope_of(op_name),
                "op": op,
                "bytes": _shape_bytes(shape),
            }
        else:
            nested.add(name)
    fusions = []
    for name, op_name, called in fusion_instrs:
        stages = sorted(
            {
                s
                for inner_op_name, _op in comp_instrs.get(called, [])
                for s in [scope_of(inner_op_name)]
                if s is not None
            }
        )
        outer = scope_of(op_name)
        if outer is not None and outer not in stages:
            stages = sorted(set(stages) | {outer})
        fusions.append({"name": name, "stages": stages})
    return {"entry": entry, "nested": nested, "fusions": fusions}


def _sds_of(x):
    """Sharding-preserving ShapeDtypeStruct of one dispatch argument.

    Single-device (uncommitted) shardings normalize to None — mixing a
    lone SingleDeviceSharding (the salt scalar) with the mesh-committed
    registers would make ``lower`` reject the signature the real
    dispatch accepted.
    """
    import jax

    s = getattr(x, "sharding", None)
    try:
        if s is not None and len(s.device_set) <= 1:
            s = None
    except Exception:
        s = None
    if s is not None:
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
    import numpy as _np

    arr = _np.asarray(x) if not hasattr(x, "dtype") else x
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype)


#: (jit id, abstract-args repr) -> {"text", "cost"}.  A capture's
#: attribution re-derives the dispatched program's optimized HLO via
#: lower().compile(); for one program that's one XLA compile per
#: PROCESS, not per capture — a serve daemon capturing every few hours
#: (or a test suite capturing repeatedly) pays it once.  Keyed on the
#: jit object's identity (kept alive by the entry) + the abstract args,
#: bounded like step.py's specialized-jit cache.
_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_MAX = 8


def _compiled_info(fn, args_sds) -> dict:
    key = (id(fn), str(jax_tree_repr(args_sds)))
    hit = _PROGRAM_CACHE.get(key)
    if hit is not None:
        return hit
    compiled = fn.lower(*args_sds).compile()
    info = {
        "text": compiled.as_text(),
        "cost": _norm_cost(compiled.cost_analysis()),
        "_fn": fn,  # keeps the id() key valid for the entry's lifetime
    }
    if len(_PROGRAM_CACHE) >= _PROGRAM_CACHE_MAX:
        _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
    _PROGRAM_CACHE[key] = info
    return info


def jax_tree_repr(tree) -> str:
    import jax

    # shardings participate: same shapes committed differently compile
    # to different modules, and the cache must never alias them
    return str(
        jax.tree_util.tree_map(
            lambda s: (s.shape, str(s.dtype), str(getattr(s, "sharding", None))),
            tree,
        )
    )


def _norm_cost(ca) -> dict:
    """``compiled.cost_analysis()`` -> {flops, bytes_accessed} (or {})."""
    try:
        d = ca[0] if isinstance(ca, (list, tuple)) else ca
        if not isinstance(d, dict):
            return {}
        out = {}
        if "flops" in d:
            out["flops"] = float(d["flops"])
        if "bytes accessed" in d:
            out["bytes_accessed"] = float(d["bytes accessed"])
        return out
    except Exception:
        return {}


def device_memory_gauges() -> dict:
    """Live device memory stats; graceful nulls where unsupported.

    ``jax.local_devices()[0].memory_stats()`` reports HBM occupancy on
    TPU/GPU; XLA:CPU returns nothing — the gauges then carry explicit
    ``None`` (JSON ``null``) so a dashboard shows "unsupported", never a
    fake zero.  The scatter-wall work (ROADMAP item 2) reads
    register-footprint headroom from exactly these gauges.
    """
    stats = None
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        stats = None
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    if not stats:
        return {f"device_mem_{k}": None for k in keys}
    return {f"device_mem_{k}": stats.get(k) for k in keys}


class DevprofCapture:
    """One bounded in-process profiler window over the step dispatches.

    Dispatches 1..warmup run unprofiled (compile + cache warm); the
    profiler arms before dispatch warmup+1 and disarms after dispatch
    warmup+steps completes (output synced first — async backends must
    not close the window with work in flight).  Everything after is a
    plain pass-through, so a long run pays the capture cost once and
    the sustained rate barely moves (bench_suite ``steptrace`` pins the
    armed/disarmed ratio >= 0.98).
    """

    def __init__(self, out_dir: str, steps: int = 16, warmup: int = 3,
                 label: str = ""):
        os.makedirs(out_dir, exist_ok=True)
        self.out_dir = os.path.abspath(out_dir)
        self.trace_dir = os.path.join(self.out_dir, "jax-trace")
        self.steps = int(steps)
        self.warmup = int(warmup)
        self.label = label
        self._lock = threading.Lock()
        self._count = 0
        self._profiling = False
        self._done = False
        self._pending_parse = False
        self._error: str | None = None
        self._summary: dict | None = None
        #: wall time the profiler was live (the bounded capture pause).
        #: Profiling a step is NOT free — on XLA:CPU every scatter-loop
        #: iteration emits a thunk event, so a profiled step can run
        #: 10-50x slower than a plain one.  The pause is priced apart
        #: from the run's sustained rate the same way compile is
        #: (bench_suite steptrace; DESIGN §14).
        self._window_wall: float | None = None
        self._t_window0: float | None = None
        #: label -> {"fn", "args_sds", "dispatches"} (programs seen in-window)
        self._programs: dict[str, dict] = {}

    # -- dispatch seam ---------------------------------------------------

    def dispatch(self, label: str, fn, args):
        """Run one device dispatch, advancing the capture window."""
        if self._done:
            return fn(*args)
        start = stop = False
        with self._lock:
            if self._done:
                return fn(*args)
            self._count += 1
            if not self._profiling and self._count == self.warmup + 1:
                start = True
            if self._profiling or start:
                prog = self._programs.get(label)
                if prog is None:
                    import jax

                    prog = self._programs[label] = {
                        "fn": fn,
                        "args_sds": jax.tree_util.tree_map(_sds_of, args),
                        "dispatches": 0,
                    }
                prog["dispatches"] += 1
                if self._count >= self.warmup + self.steps:
                    stop = True
        if start:
            import jax

            # quiesce before opening the window: async backends (and
            # XLA:CPU's thread-pool executor) may still be running the
            # warmup dispatches, whose tail would otherwise execute —
            # and be taxed — inside the profiled window.  The state
            # argument IS the previous dispatch's output, so blocking
            # on the args drains everything in flight.
            jax.block_until_ready(args)
            self._start()
            if self._done:  # start failed: clean no-trace run
                return fn(*args)
        out = fn(*args)
        if stop and self._profiling:
            import jax

            jax.block_until_ready(out)
            self._close_window()
        return out

    # -- window control --------------------------------------------------

    def _start(self) -> None:
        # the fault site fires OUTSIDE the try: an injected failure is a
        # typed abort (InjectedFault), while a REAL profiler failure
        # degrades to a clean no-trace run with the error recorded
        faults.fire("devprof.capture")
        import jax

        # the pause clock starts BEFORE start_trace: profiler backend
        # init is part of the capture's cost, not the run's
        t0 = time.perf_counter()
        try:
            jax.profiler.start_trace(self.trace_dir)
        except Exception as e:
            self._error = f"profiler start failed: {e}"
            self._done = True
            return
        self._t_window0 = t0
        self._profiling = True

    def _close_window(self) -> None:
        """Stop the profiler at the window boundary (cheap, mid-run).

        The expensive half — re-deriving the optimized HLO and parsing
        the trace — is DEFERRED to :meth:`finalize` / :meth:`poll`, so
        it can never pollute the run's measured elapsed/sustained rate
        (the drivers capture ``elapsed`` before finalizing).
        """
        self._done = True
        try:
            # typed-abort seam: an injected stop failure propagates, and
            # abort() below still stops the live profiler on the way out
            faults.fire("devprof.capture")
        except BaseException:
            self.abort()
            raise
        self._profiling = False
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:
            self._error = f"profiler stop failed: {e}"
            return
        if self._t_window0 is not None:
            self._window_wall = time.perf_counter() - self._t_window0
        self._pending_parse = True

    def _ensure_parsed(self) -> None:
        if not self._pending_parse:
            return
        self._pending_parse = False
        try:
            self._summary = self._parse()
        except Exception as e:  # attribution must never kill the run
            self._error = f"trace parse failed: {e}"
            return
        self._emit(self._summary)

    def poll(self) -> None:
        """Parse a CLOSED window if one is waiting (serve's rotation seam
        — never closes an open window early)."""
        self._ensure_parsed()

    def abort(self) -> None:
        """Stop a dangling profiler without parsing (typed-abort path)."""
        if self._profiling:
            self._profiling = False
            self._done = True
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass

    def finalize(self) -> dict:
        """Close the window (stream may end early) and return the summary.

        Idempotent; always returns a dict — a window that never opened
        (stream shorter than the warmup) or failed reports itself
        explicitly instead of pretending a capture happened.
        """
        if self._profiling:
            self._close_window()
        self._done = True
        self._ensure_parsed()
        if self._summary is not None:
            return self._summary
        out = {
            "steps_profiled": 0,
            "requested_steps": self.steps,
            "warmup": self.warmup,
        }
        if self.label:
            out["label"] = self.label
        if self._error is not None:
            out["error"] = self._error
        else:
            out["note"] = (
                "stream ended before the capture window opened "
                f"(saw {self._count} dispatches, warmup {self.warmup})"
            )
        return out

    # -- attribution -----------------------------------------------------

    def _newest_trace(self) -> str | None:
        pats = ("*.trace.json.gz", "*.trace.json")
        hits: list[str] = []
        for p in pats:
            hits += glob.glob(
                os.path.join(self.trace_dir, "plugins", "profile", "*", p)
            )
        return max(hits, key=os.path.getmtime) if hits else None

    def _program_info(self) -> tuple[dict, dict]:
        """(merged entry op index, per-program static info).

        Re-lowers each in-window program with its recorded abstract
        args (shardings preserved) and compiles it — XLA compilation is
        deterministic for an identical module, so instruction names
        match the executed program's trace events.  With the persistent
        compilation cache armed (runtime/compcache.py) this is a cache
        read, not a second compile.
        """
        index: dict[str, dict] = {}
        programs: dict[str, dict] = {}
        for label, prog in sorted(self._programs.items()):
            info = _compiled_info(prog["fn"], prog["args_sds"])
            cost = info["cost"]
            mod = parse_hlo_module(info["text"])
            static: dict[str, dict] = {}
            for name, instr in mod["entry"].items():
                stage = instr["scope"] or "unattributed"
                st = static.setdefault(
                    stage, {"instructions": 0, "out_bytes": 0}
                )
                st["instructions"] += 1
                st["out_bytes"] += instr["bytes"]
                prev = index.get(name)
                if prev is not None and prev.get("scope") != instr["scope"]:
                    # same instruction name, different stage in another
                    # program: ambiguous — classify as unattributed
                    # rather than guess (distinct programs rarely share
                    # hot-op names; conflicts are counted)
                    index[name] = {"scope": None, "op": instr["op"], "ambiguous": True}
                else:
                    index[name] = {"scope": instr["scope"], "op": instr["op"]}
            programs[label] = {
                "dispatches": prog["dispatches"],
                "hlo_instructions": len(mod["entry"]),
                "stages_static": dict(sorted(static.items())),
                "fusions": mod["fusions"],
                **cost,
            }
        return index, programs

    def _parse(self) -> dict:
        trace_path = self._newest_trace()
        index, programs = self._program_info()
        stages_us: dict[str, float] = {}
        stage_events: dict[str, int] = {}
        unattributed_us = 0.0
        n_events = 0
        if trace_path is not None:
            opener = gzip.open if trace_path.endswith(".gz") else open
            with opener(trace_path, "rt", encoding="utf-8") as f:
                data = json.load(f)
            for e in data.get("traceEvents", []):
                if e.get("ph") != "X" or "dur" not in e:
                    continue
                info = index.get(e.get("name", ""))
                if info is None:
                    continue  # nested-body or host-runtime event
                n_events += 1
                scope = info.get("scope")
                if scope is None:
                    unattributed_us += e["dur"]
                else:
                    stages_us[scope] = stages_us.get(scope, 0.0) + e["dur"]
                    stage_events[scope] = stage_events.get(scope, 0) + 1
        total_us = sum(stages_us.values()) + unattributed_us
        stages = {
            s: {
                "device_us": round(us, 1),
                "pct": round(100.0 * us / total_us, 2) if total_us else 0.0,
                "events": stage_events.get(s, 0),
            }
            for s, us in sorted(stages_us.items(), key=lambda kv: -kv[1])
        }
        cross = [
            {"program": label, "name": f["name"], "stages": f["stages"]}
            for label, prog in programs.items()
            for f in prog["fusions"]
            if len(f["stages"]) > 1
        ]
        steps_profiled = sum(p["dispatches"] for p in self._programs.values())
        import jax

        out = {
            "requested_steps": self.steps,
            "warmup": self.warmup,
            "steps_profiled": steps_profiled,
            #: the bounded pause the live profiler cost this run — price
            #: it apart from the sustained rate, like compile_sec
            "window_wall_sec": (
                round(self._window_wall, 3)
                if self._window_wall is not None
                else None
            ),
            "backend": jax.devices()[0].platform,
            "devices": len(jax.devices()),
            "device_us_total": round(total_us, 1),
            "attributed_frac": (
                round(1.0 - unattributed_us / total_us, 4) if total_us else 0.0
            ),
            "unattributed": {
                "device_us": round(unattributed_us, 1),
                "pct": (
                    round(100.0 * unattributed_us / total_us, 2)
                    if total_us
                    else 0.0
                ),
            },
            "stages": stages,
            "programs": programs,
            "cross_stage_fusions": cross,
            "trace_path": trace_path,
            "memory": device_memory_gauges(),
        }
        if self.label:
            out["label"] = self.label
        if self._error:
            out["error"] = self._error
        return out

    def _emit(self, summary: dict) -> None:
        path = os.path.join(self.out_dir, "devprof.json")
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(summary, f, indent=2)
        os.replace(tmp, path)
        self.json_path = path
        # the obs planes carry the capture (trace instant for
        # trace_summary's devprof block; metrics event for the JSONL)
        brief = self.gauges()
        obs.instant("devprof.summary", args=brief)
        obs.metric_event("devprof", **brief)

    def gauges(self) -> dict:
        """Flat numeric gauges for /metrics (JSON + prom) and the JSONL."""
        s = self._summary
        if s is None:
            return {"devprof_steps_profiled": 0}
        g = {
            "devprof_steps_profiled": s["steps_profiled"],
            "devprof_attributed_frac": s["attributed_frac"],
            "devprof_device_us_total": s["device_us_total"],
        }
        top = next(iter(s["stages"]), None)
        if top is not None:
            g["devprof_top_stage"] = top
            g["devprof_top_stage_pct"] = s["stages"][top]["pct"]
        for name, st in s["stages"].items():
            g[f"devprof_pct_{name.replace('.', '_')}"] = st["pct"]
        return g


# ---------------------------------------------------------------------------
# Module arming state — the faults.py discipline: ``_capture is None`` is
# the production fast path (one None-check per device dispatch).
# ---------------------------------------------------------------------------

_capture: DevprofCapture | None = None


def arm(out_dir: str, steps: int = 16, warmup: int = 3, label: str = "") -> DevprofCapture:
    """Arm a capture window process-wide (``--devprof-out``).

    Single-controller only: the window brackets THIS process's
    dispatches and the parse reads this process's trace.  Also registers
    the devprof + device-memory gauges with the metrics plane (no-ops
    when ``--metrics-out`` is not armed).
    """
    global _capture
    from ..config import DevprofConfig
    from ..errors import AnalysisError

    try:
        # ONE definition of the limits: the config dataclass validates
        # for the CLI and for programmatic callers alike
        DevprofConfig(out_dir=out_dir, steps=steps, warmup=warmup)
    except ValueError as e:
        raise AnalysisError(str(e)) from e
    cap = DevprofCapture(out_dir, steps=steps, warmup=warmup, label=label)
    _capture = cap
    obs.register_sampler("devprof", cap.gauges)
    obs.register_sampler("device_mem", device_memory_gauges)
    return cap


def active_capture() -> DevprofCapture | None:
    """The armed capture (the hot-path accessor: one None-check)."""
    return _capture


def gauges() -> dict:
    """Armed capture's flat gauges, or {} — serve /metrics folds these."""
    cap = _capture
    return cap.gauges() if cap is not None else {}


def finalize_if_armed() -> dict | None:
    """Driver seam: close the window and return the ``totals.devprof``
    block (None when disarmed).  The capture stays armed so gauges keep
    answering until :func:`shutdown`."""
    cap = _capture
    if cap is None:
        return None
    return cap.finalize()


def shutdown() -> None:
    """Disarm; stop any dangling profiler (abort path) without parsing."""
    global _capture
    cap = _capture
    _capture = None
    if cap is not None:
        cap.abort()
        obs.unregister_sampler("devprof")
        obs.unregister_sampler("device_mem")
