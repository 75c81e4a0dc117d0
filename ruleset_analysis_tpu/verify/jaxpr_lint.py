"""Jaxpr-level invariant lint: taint walk + scatter/scope/merge checks.

The walker abstract-interprets a traced step program (``grid.py``) over
a five-point taint lattice seeded at the weight-plane input::

    U  untainted        independent of the weights
    G  gated            depends on weights only through predicates
                        (``weight > 0`` zero-tests) — idempotent-safe
    L  linear           a linear function of the weight plane (w itself,
                        sums/permutations of w, w times untainted data)
    N  nonlinear        anything else (w*w, weight-dependent routing,
                        linear+gated mixtures)
    O  opaque           passed through a primitive the walker cannot
                        enter (a pallas kernel) — UNPROVABLE, which is
                        a typed refusal, never a silent pass

plus a ``float_risk`` flag (the value passed through a float conversion
on a tainted path: linear but only range-exact — the matmul-counts
class) and a provenance tag set (which structural primitives — sort,
psum, pmax, all_gather, scatters — the value passed through; this is
what the sorted-scatter and merge-law checks read).

Verdicts are enforced at the **register sinks**, not at every value:

- add-law sinks (``scatter-add`` updates, ``psum`` operands): must be
  U or L without float risk.  G into an add register is exactly the
  count-one-per-row bug class (a weight-w row counts as one line);
  float risk is the f32-exactness class; N/O are nonlinear/unprovable.
- max-law sinks (``scatter-max`` updates, ``pmax`` operands): must be
  U or G.  L into a max register would make the merged value depend on
  weight magnitude — max is only correct for idempotent gates.
- scatter **indices** must be U at every sink: weight-dependent routing
  is never linear (and opaque-derived keys are unprovable).

This sink discipline is what lets the exact-counts ``add64`` carry
chain pass: the carry (``new_lo < delta``) is a predicate of two linear
values — G — but it feeds a plain ``add`` into the high word, not a
sink; the (lo, hi) pair is weight-linear at the 64-bit level, which is
the add64 law the merge-law table records (DESIGN §18).
"""

from __future__ import annotations

import dataclasses

from ..stages import STAGES, scope_of

# taint classes
U, G, L, N, O = 0, 1, 2, 3, 4
_CLS_NAME = {U: "untainted", G: "gated", L: "linear", N: "nonlinear", O: "opaque"}


@dataclasses.dataclass(frozen=True)
class Info:
    """Per-value taint state."""

    cls: int = U
    float_risk: bool = False
    prov: frozenset = frozenset()


_UINFO = Info()


def _join_cls(a: int, b: int) -> int:
    if a == U:
        return b
    if b == U:
        return a
    if O in (a, b):
        return O
    if a == b:
        return a
    return N  # {G, L} mixtures (and anything involving N)


def _merge(infos, cls: int | None = None, tag: str | None = None) -> Info:
    """Combine operand infos into one output info."""
    c = U
    fl = False
    prov = set()
    for i in infos:
        c = _join_cls(c, i.cls)
        prov |= i.prov
        fl = fl or i.float_risk
    if cls is not None:
        c = cls
    if tag is not None:
        prov.add(tag)
    return Info(cls=c, float_risk=fl and c != U, prov=frozenset(prov))


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint finding (a violated or unprovable invariant)."""

    check: str  # {"linearity", "scatter", "scope", "merge"}
    kind: str  # e.g. "gated-into-add", "sorted-claim-without-sort"
    prim: str  # primitive (or output) name
    stage: str | None  # ra.* stage of the offending equation, if any
    #: "violation": wrong for every input; "weighted": wrong only for
    #: weighted inputs (the derived weighted-refusal set)
    severity: str
    detail: str = ""


@dataclasses.dataclass
class ProgramLint:
    """Verdicts for one traced program."""

    spec: object  # grid.ProgramSpec
    findings: list
    #: derived weight-linearity verdict: "linear" | "gated" |
    #: "float-bounded" | "unprovable" | "nonlinear"
    weight_verdict: str
    outputs: dict  # name -> {"class", "float_risk", "prov", "dtype"}
    eqns_walked: int = 0
    sinks_checked: int = 0

    @property
    def ok(self) -> bool:
        return not any(f.severity == "violation" for f in self.findings)

    @property
    def weight_safe(self) -> bool:
        return self.weight_verdict == "linear"

    def to_dict(self) -> dict:
        return {
            "program": getattr(self.spec, "name", str(self.spec)),
            "ok": self.ok,
            "weight_verdict": self.weight_verdict,
            "eqns_walked": self.eqns_walked,
            "sinks_checked": self.sinks_checked,
            "findings": [dataclasses.asdict(f) for f in self.findings],
            "outputs": self.outputs,
        }


# -- primitive classification ------------------------------------------------

#: call-like primitives: param key holding the inner jaxpr; invars map
#: positionally onto the inner invars (after the ClosedJaxpr's consts).
_CALL_PRIMS = {
    "jit": "jaxpr",
    "closed_call": "call_jaxpr",
    "remat2": "jaxpr",
    "custom_jvp_call": "call_jaxpr",
    "custom_vjp_call": "call_jaxpr",
    "shard_map": "jaxpr",
}

_COMPARES = {"eq", "ne", "ge", "gt", "le", "lt"}

#: multiplicative ops: two tainted operands compose nonlinearly
_MUL_LIKE = {"mul", "div", "rem", "pow", "integer_pow", "atan2", "nextafter"}

#: structural primitives whose equations must attribute to a registered
#: ra.* stage (DESIGN §14 coverage-by-construction)
_SCOPE_REQUIRED = {
    "scatter-add", "scatter-max", "scatter", "sort",
    "psum", "pmax", "all_gather", "top_k", "dot_general",
}

#: GatherScatterMode.FILL_OR_DROP — compared by name to stay independent
#: of the enum's import path across jax versions
_DROP_MODES = ("FILL_OR_DROP",)


def _stage_of(eqn) -> str | None:
    try:
        return scope_of(str(eqn.source_info.name_stack))
    except Exception:
        return None


class _Walker:
    def __init__(self):
        self.findings: list[Finding] = []
        self.eqns = 0
        self.sinks = 0

    # -- findings helpers ---------------------------------------------

    def _find(self, check, kind, eqn, severity, detail=""):
        self.findings.append(
            Finding(
                check=check, kind=kind,
                prim=eqn.primitive.name if hasattr(eqn, "primitive") else str(eqn),
                stage=_stage_of(eqn) if hasattr(eqn, "source_info") else None,
                severity=severity, detail=detail,
            )
        )

    def _check_scope(self, eqn):
        stack = str(eqn.source_info.name_stack)
        stage = scope_of(stack)
        if stage is None:
            self._find(
                "scope", "unattributed-register-update", eqn, "violation",
                f"no ra.* scope on name stack {stack!r}",
            )
        elif stage not in STAGES:
            self._find(
                "scope", "unregistered-stage", eqn, "violation",
                f"scope {stage!r} is not in the stages.py vocabulary",
            )

    def _check_add_sink(self, eqn, info: Info, what: str):
        self.sinks += 1
        if info.cls == G:
            self._find(
                "linearity", "gated-into-add", eqn, "weighted",
                f"{what} is a weight-gated value (counts one per row, "
                "not the row's weight)",
            )
        elif info.cls == N:
            self._find(
                "linearity", "nonlinear-into-add", eqn, "violation",
                f"{what} is a nonlinear function of the weight plane",
            )
        elif info.cls == O:
            self._find(
                "linearity", "opaque-into-add", eqn, "weighted",
                f"{what} passed through an opaque kernel — unprovable",
            )
        elif info.float_risk:
            self._find(
                "linearity", "float-into-add", eqn, "weighted",
                f"{what} is linear but crossed a float conversion — "
                "exact only within the float integer range",
            )

    def _check_max_sink(self, eqn, info: Info, what: str):
        self.sinks += 1
        if info.cls == L:
            self._find(
                "linearity", "linear-into-max", eqn, "weighted",
                f"{what} carries weight magnitude into a max-law "
                "register (max is only correct for idempotent gates)",
            )
        elif info.cls == N:
            self._find(
                "linearity", "nonlinear-into-max", eqn, "violation",
                f"{what} is a nonlinear function of the weight plane",
            )
        elif info.cls == O:
            self._find(
                "linearity", "opaque-into-max", eqn, "weighted",
                f"{what} passed through an opaque kernel — unprovable",
            )

    def _check_indices(self, eqn, info: Info):
        if info.cls == U:
            return
        sev = "weighted" if info.cls in (G, O) else "violation"
        kind = (
            "opaque-scatter-indices" if info.cls == O
            else "tainted-scatter-indices"
        )
        self._find(
            "linearity", kind, eqn, sev,
            f"scatter routing depends on the weight plane "
            f"({_CLS_NAME[info.cls]})",
        )

    # -- evaluation ---------------------------------------------------

    def eval_jaxpr(self, jaxpr, in_infos, const_infos=None):
        """Walk one (open) jaxpr; returns out infos."""
        env: dict = {}

        def read(v) -> Info:
            if not hasattr(v, "aval") or type(v).__name__ == "Literal":
                return _UINFO
            return env.get(v, _UINFO)

        def write(v, info):
            if type(v).__name__ != "DropVar":
                env[v] = info

        for v, i in zip(jaxpr.invars, in_infos):
            write(v, i)
        for v, i in zip(jaxpr.constvars, const_infos or []):
            write(v, i)
        for eqn in jaxpr.eqns:
            self.eqns += 1
            infos = [read(v) for v in eqn.invars]
            outs = self.eval_eqn(eqn, infos)
            for v, i in zip(eqn.outvars, outs):
                write(v, i)
        return [read(v) for v in jaxpr.outvars]

    def _eval_closed(self, closed, in_infos):
        jaxpr = getattr(closed, "jaxpr", closed)
        consts = [_UINFO] * len(jaxpr.constvars)
        return self.eval_jaxpr(jaxpr, in_infos, consts)

    def eval_eqn(self, eqn, infos) -> list:
        name = eqn.primitive.name
        n_out = len(eqn.outvars)

        # -- call-like: recurse positionally --------------------------
        if name in _CALL_PRIMS:
            inner = eqn.params.get(_CALL_PRIMS[name])
            if inner is not None:
                return self._eval_closed(inner, infos)
            return [_merge(infos)] * n_out

        if name == "cond":
            branches = eqn.params["branches"]
            pred, ops = infos[0], infos[1:]
            per_branch = [self._eval_closed(b, ops) for b in branches]
            outs = []
            for outs_i in zip(*per_branch):
                m = _merge(outs_i)
                if pred.cls != U:
                    # branch selection by a weight-derived predicate:
                    # same composition rule as select_n
                    m = _merge([m], cls=G if m.cls == U else N)
                    m = Info(m.cls, m.float_risk, m.prov | pred.prov)
                outs.append(m)
            return outs

        if name == "while":
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            body = eqn.params["body_jaxpr"]
            carry = list(infos[cn + bn:])
            bconsts = infos[cn:cn + bn]
            for _ in range(len(carry) + 2):  # monotone fixpoint
                outs = self._eval_closed(body, bconsts + carry)
                new = [_merge([a, b]) for a, b in zip(carry, outs)]
                if all(n == c for n, c in zip(new, carry)):
                    break
                carry = new
            return carry

        if name == "scan":
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            body = eqn.params["jaxpr"]
            consts = infos[:nc]
            carry = list(infos[nc:nc + ncar])
            xs = infos[nc + ncar:]
            ys = None
            for _ in range(len(carry) + 2):
                outs = self._eval_closed(body, consts + carry + xs)
                new = [_merge([a, b]) for a, b in zip(carry, outs[:ncar])]
                ys = outs[ncar:]
                if all(n == c for n, c in zip(new, carry)):
                    break
                carry = new
            return carry + list(ys or [])

        # -- register sinks -------------------------------------------
        if name in ("scatter-add", "scatter-max", "scatter"):
            operand, indices, updates = infos[0], infos[1], infos[2]
            self._check_scope(eqn)
            self._check_indices(eqn, indices)
            mode = eqn.params.get("mode")
            if getattr(mode, "name", str(mode)) not in _DROP_MODES:
                self._find(
                    "scatter", "scatter-not-drop", eqn, "violation",
                    f"scatter mode is {mode!r}, not FILL_OR_DROP "
                    "(mode='drop'): out-of-bounds keys would clip or be "
                    "undefined instead of dropping",
                )
            if eqn.params.get("indices_are_sorted") and "sort" not in indices.prov:
                self._find(
                    "scatter", "sorted-claim-without-sort", eqn, "violation",
                    "indices_are_sorted=True but the index chain contains "
                    "no lax.sort",
                )
            if name == "scatter-add":
                self._check_add_sink(eqn, updates, "scatter-add updates")
            elif name == "scatter-max":
                self._check_max_sink(eqn, updates, "scatter-max updates")
            elif updates.cls != U:
                self._find(
                    "linearity", "tainted-into-set", eqn, "violation",
                    "weight-derived value scattered with overwrite "
                    "semantics (neither add- nor max-law)",
                )
            out = _merge([operand, updates, indices], tag=name)
            if indices.cls != U:
                out = _merge([out], cls=_join_cls(out.cls, O if indices.cls == O else N))
            return [out] * n_out

        if name == "psum":
            self._check_scope(eqn)
            outs = []
            for i in infos:
                self._check_add_sink(eqn, i, "psum operand")
                outs.append(_merge([i], tag="psum"))
            return outs

        if name == "pmax":
            self._check_scope(eqn)
            outs = []
            for i in infos:
                self._check_max_sink(eqn, i, "pmax operand")
                outs.append(_merge([i], tag="pmax"))
            return outs

        if name == "all_gather":
            self._check_scope(eqn)
            return [_merge([i], tag="all_gather") for i in infos]

        if name == "sort":
            self._check_scope(eqn)
            num_keys = eqn.params.get("num_keys", 1)
            keys_tainted = any(i.cls != U for i in infos[:num_keys])
            outs = []
            for i in infos:
                if keys_tainted:
                    outs.append(_merge(infos, cls=N, tag="sort"))
                else:
                    outs.append(_merge([i], tag="sort"))
            return outs

        if name == "dot_general":
            self._check_scope(eqn)
            a, b = infos[0], infos[1]
            if a.cls == U and b.cls == U:
                return [_merge(infos)] * n_out
            if O in (a.cls, b.cls):
                return [_merge(infos, cls=O)] * n_out
            if a.cls != U and b.cls != U:
                return [_merge(infos, cls=N)] * n_out
            t = a if a.cls != U else b
            # contraction sums gated values -> counts rows, not weights
            cls = L if t.cls == L else N
            return [_merge(infos, cls=cls)] * n_out

        if name == "top_k":
            self._check_scope(eqn)
            cls = U if all(i.cls == U for i in infos) else N
            return [_merge(infos, cls=cls)] * n_out

        # -- everything else: dataflow rules --------------------------
        if name in _COMPARES:
            if any(i.cls == O for i in infos):
                return [_merge(infos, cls=O)] * n_out
            cls = G if any(i.cls != U for i in infos) else U
            return [_merge(infos, cls=cls)] * n_out

        if name in _MUL_LIKE:
            a, b = infos[0], infos[1] if len(infos) > 1 else _UINFO
            if a.cls == L and b.cls == L:
                return [_merge(infos, cls=N)] * n_out
            return [_merge(infos)] * n_out

        if name == "select_n":
            pred, cases = infos[0], infos[1:]
            m = _merge(cases)
            if pred.cls == U:
                return [m] * n_out
            cls = O if O in (pred.cls, m.cls) else (G if m.cls == U else N)
            return [_merge(infos, cls=cls)] * n_out

        if name in ("reduce_sum", "cumsum"):
            i = _merge(infos)
            if i.cls == G:
                i = _merge(infos, cls=N)  # sum of gates counts rows
            if self._tainted_reduce_needs_scope(infos):
                self._check_scope(eqn)
            return [i] * n_out

        if name in ("reduce_max", "reduce_min", "cummax", "cummin"):
            i = _merge(infos)
            if i.cls == L:
                i = _merge(infos, cls=N)  # magnitude extremum of weights
            if self._tainted_reduce_needs_scope(infos):
                self._check_scope(eqn)
            return [i] * n_out

        if name in ("argmax", "argmin", "reduce_precision"):
            cls = U if all(i.cls == U for i in infos) else N
            return [_merge(infos, cls=cls)] * n_out

        if name in ("gather", "dynamic_slice", "dynamic_update_slice", "take"):
            operand, rest = infos[0], infos[1:]
            routing = _merge(rest)
            if routing.cls != U:
                cls = O if O in (routing.cls, operand.cls) else N
                return [_merge(infos, cls=cls)] * n_out
            return [_merge(infos)] * n_out

        if name == "convert_element_type":
            i = _merge(infos)
            if i.cls != U:
                import numpy as np

                try:
                    kind = np.dtype(eqn.params["new_dtype"]).kind
                except TypeError:
                    kind = "?"
                if kind in "fc":
                    # a tainted value crossing into float: linear maybe,
                    # but exact only within the float integer range —
                    # the matmul-counts refusal class
                    i = Info(i.cls, True, i.prov)
            return [i] * n_out

        if eqn.params and any(
            hasattr(v, "eqns") or hasattr(getattr(v, "jaxpr", None), "eqns")
            for k, v in eqn.params.items()
            if k != "update_jaxpr"
        ):
            # an unrecognized primitive CARRYING a program (pallas_call,
            # a future custom call): opaque — unprovable, never entered
            if any(i.cls != U for i in infos):
                return [_merge(infos, cls=O, tag=f"opaque:{name}")] * n_out
            return [_merge(infos, tag=f"opaque:{name}")] * n_out

        # default: transparent elementwise/structural op
        return [_merge(infos)] * n_out

    @staticmethod
    def _tainted_reduce_needs_scope(infos) -> bool:
        return any(i.cls in (G, L, N, O) for i in infos)


#: merge-law table: output register -> (dtype, required collective,
#: forbidden collective, law name).  counts_lo/hi form the add64 pair
#: (uint32 lo/hi with carry — exact past 2^32 while per-chunk deltas
#: stay below config.WEIGHTED_CHUNK_WEIGHT_LIMIT); cms/talk_cms are
#: add32 mod-2^32 sketch planes; hll merges by idempotent max.
OUTPUT_LAWS = {
    "counts_lo": ("uint32", "psum", "pmax", "add64"),
    "counts_hi": ("uint32", "psum", "pmax", "add64"),
    "cms": ("uint32", "psum", "pmax", "add32"),
    "talk_cms": ("uint32", "psum", "pmax", "add32"),
    "hll": ("uint32", "pmax", "psum", "max"),
    "cand_acl": ("uint32", "all_gather", None, "gather"),
    "cand_src": ("uint32", "all_gather", None, "gather"),
    "cand_est": ("uint32", "all_gather", None, "gather"),
}


def lint_program(traced) -> ProgramLint:
    """Run every jaxpr-level check over one traced program."""
    closed = traced.closed_jaxpr
    jaxpr = closed.jaxpr
    walker = _Walker()
    in_infos = [
        Info(cls=L) if i == traced.weight_invar_index else _UINFO
        for i in range(len(jaxpr.invars))
    ]
    out_infos = walker.eval_jaxpr(
        jaxpr, in_infos, [_UINFO] * len(jaxpr.constvars)
    )

    spec = traced.spec
    outputs = {}
    for name, var, info in zip(traced.output_names, jaxpr.outvars, out_infos):
        dtype = str(getattr(getattr(var, "aval", None), "dtype", "?"))
        outputs[name] = {
            "class": _CLS_NAME[info.cls],
            "float_risk": info.float_risk,
            "prov": sorted(info.prov),
            "dtype": dtype,
        }
        law = OUTPUT_LAWS.get(name)
        if law is None:
            continue
        want_dtype, required, forbidden, law_name = law
        exempt = (
            name in ("counts_lo", "counts_hi")
            and not getattr(spec, "exact_counts", True)
        )
        if exempt:
            continue
        if dtype != want_dtype:
            walker.findings.append(Finding(
                "merge", "register-dtype", f"output:{name}", None,
                "violation",
                f"{name} is {dtype}, law {law_name} requires {want_dtype}",
            ))
        if required not in info.prov:
            walker.findings.append(Finding(
                "merge", "missing-merge-seam", f"output:{name}", None,
                "violation",
                f"{name} never crossed its {required} merge seam "
                f"(law {law_name})",
            ))
        if forbidden is not None and forbidden in info.prov:
            walker.findings.append(Finding(
                "merge", "wrong-merge-law", f"output:{name}", None,
                "violation",
                f"{name} crossed {forbidden}, which is not its law "
                f"({law_name})",
            ))

    verdict = "linear"
    kinds = {f.kind for f in walker.findings if f.check == "linearity"}
    if kinds & {"nonlinear-into-add", "nonlinear-into-max",
                "tainted-scatter-indices", "tainted-into-set"}:
        verdict = "nonlinear"
    elif kinds & {"opaque-into-add", "opaque-into-max",
                  "opaque-scatter-indices"}:
        verdict = "unprovable"
    elif "gated-into-add" in kinds or "linear-into-max" in kinds:
        verdict = "gated"
    elif "float-into-add" in kinds:
        verdict = "float-bounded"

    return ProgramLint(
        spec=spec,
        findings=walker.findings,
        weight_verdict=verdict,
        outputs=outputs,
        eqns_walked=walker.eqns,
        sinks_checked=walker.sinks,
    )
