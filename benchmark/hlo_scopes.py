"""Which ``ra.*`` stage each instruction of the device step belongs to.

A TPU trace names its ops by their HLO instruction (``%fusion.7 = ...``)
and carries no scope.  The program's named scopes live in the compiled
module's op metadata (``op_name="jit(..)/ra.match/..."``).  So a traced
run records, at the program's dispatch seam, each step program it runs
with the shapes of its arguments (:class:`ProgramRecorder`); after the
window each is compiled again, which XLA does deterministically, names
included, and its text is read here with this file's own patterns.
"""

from __future__ import annotations

import contextlib
import re

STAGE_RE = re.compile(r"(?:^|/)ra\.([a-z0-9_]+)")
_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)=\{?%?([\w.\-]+)")


def scopes_of_module(text: str) -> tuple[str, dict]:
    """(module name, {instruction: stage}) of one compiled module's text.

    An instruction's stage is the first ``ra.<stage>`` in its own
    ``op_name``, else the first found in a computation it calls (a fusion
    or a loop body), depth first in text order.
    """
    name = ""
    own: dict[str, str | None] = {}
    calls: dict[str, list[str]] = {}
    comp_instrs: dict[str, list[str]] = {}
    comp = None
    for line in text.splitlines():
        m = _MODULE_RE.match(line)
        if m:
            name = m.group(1)
            continue
        m = _COMP_RE.match(line)
        if m and "=" not in line.split("(")[0]:
            comp = m.group(1)
            comp_instrs[comp] = []
            continue
        m = _INSTR_RE.match(line)
        if not m or comp is None:
            continue
        instr = m.group(1)
        comp_instrs[comp].append(instr)
        op = _OPNAME_RE.search(line)
        s = STAGE_RE.search(op.group(1)) if op else None
        own[instr] = s.group(1) if s else None
        calls[instr] = _CALLS_RE.findall(line)

    memo: dict[str, str | None] = {}

    def comp_stage(c: str, seen: set) -> str | None:
        if c in memo:
            return memo[c]
        if c in seen:
            return None
        seen.add(c)
        out = None
        for i in comp_instrs.get(c, []):
            out = stage(i, seen)
            if out:
                break
        memo[c] = out
        return out

    def stage(i: str, seen: set) -> str | None:
        if own.get(i):
            return own[i]
        for c in calls.get(i, []):
            s = comp_stage(c, seen)
            if s:
                return s
        return None

    return name, {i: s for i in own if (s := stage(i, set()))}


class ProgramRecorder:
    """Stands at the program's dispatch seam (``devprof.active_capture()``).

    Runs every dispatch unchanged; keeps the jit and the argument shapes
    of the first dispatch of each program, to compile it again later.
    """

    def __init__(self):
        self.programs: dict[str, tuple] = {}

    def dispatch(self, label, fn, args):
        if label not in self.programs:
            import jax

            def shape_of(x):
                named = isinstance(x.sharding, jax.sharding.NamedSharding)
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=x.sharding if named else None)

            self.programs[label] = (fn, jax.tree.map(shape_of, args))
        return fn(*args)

    # the rest of the capture interface the program calls: nothing to do
    def finalize(self):
        return None

    def abort(self):
        pass

    def poll(self):
        pass

    def gauges(self) -> dict:
        return {}

    @contextlib.contextmanager
    def installed(self):
        from ruleset_analysis_tpu.runtime import devprof

        if not hasattr(devprof, "_capture"):
            raise RuntimeError("devprof has no _capture seam: the stage table would be empty")
        devprof._capture = self
        try:
            yield self
        finally:
            devprof._capture = None

    def scopes(self) -> dict:
        """{module name: {instruction: stage}} over the recorded programs."""
        out: dict[str, dict] = {}
        for fn, args in self.programs.values():
            name, table = scopes_of_module(fn.lower(*args).compile().as_text())
            out.setdefault(name, {}).update(table)
        return out
