"""Device time by the program's named scopes, and the program's counters.

The program names its device work with ``jax.named_scope`` (``locate``,
``upsert.insert``, ``sweep.combine`` ...).  A scope is HLO metadata: each
instruction of a compiled program carries the path of scopes it was traced
under in its ``op_name``.  A traced run's device operations
(:class:`bench.tracing.Trace`) are those instructions, named
``<program>/<instruction>``; this module finds each one's ``op_name`` in the
compiled programs the process still holds after the window, and sums device
time by scope:

* only leaf operations count: one that another operation of the same
  device runs inside (a ``while`` and its body's operations, which the
  trace lists beside it) is left out, so nothing counts twice;
* a scope matches a whole component of the ``op_name`` path, so the ops of
  ``locate.search`` count under ``locate.search`` and, since it nests
  inside it, under ``locate``; a fusion counts under its root's scope;
* an instruction whose ``op_name`` differs between two compiled programs of
  one name (the same function at other shapes) counts under no scope.

Counters are the ``repro.obs`` registry's, which holds the window's counts
after a traced run: the run resets it as the window opens and turns obs off
as it closes.
"""
from __future__ import annotations

import gc
import re
import weakref
from typing import Dict, List, Optional, Tuple

AMBIGUOUS = ""
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"(body|condition|calls|to_apply)=%?([\w.\-]+)")

_INDEX: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def hlo_op_names(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` of one HLO module's text.  An
    instruction without an ``op_name`` of its own takes, if it is a
    fusion, its fused root's, and else that of the instruction whose
    computation holds it (the ``while`` of a loop body, say)."""
    names, comp_of, fused, caller, roots = {}, {}, {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(1)
        comp_of[name] = comp
        op = _OP_NAME.search(line)
        if op is not None:
            names[name] = op.group(1)
            if line.lstrip().startswith("ROOT "):
                roots[comp] = op.group(1)
        for kind, callee in _CALLEE.findall(line):
            caller.setdefault(callee, name)
            if kind == "calls":
                fused[name] = callee
    for name, callee in fused.items():
        if name not in names and callee in roots:
            names[name] = roots[callee]

    def inherited(name, depth=0):
        if name in names or depth > 64:
            return names.get(name)
        up = caller.get(comp_of.get(name))
        return None if up is None else inherited(up, depth + 1)

    for name in comp_of:
        op = inherited(name)
        if op is not None:
            names[name] = op
    return names


def loaded_programs() -> List[Tuple[str, str]]:
    """``(module name, HLO text)`` of every compiled program the process
    holds (JAX's executables of ``jax.jit``)."""
    out = []
    for obj in gc.get_objects():
        if type(obj).__name__ != "MeshExecutable":
            continue
        try:
            modules = obj.xla_executable.hlo_modules()
        except (AttributeError, RuntimeError):   # no HLO to show
            continue
        out += [(m.name, m.to_string()) for m in modules]
    return out


def program_scopes(programs=None) -> Dict[str, Dict[str, str]]:
    """``{module: {instruction: op_name}}`` merged over ``programs``
    (:func:`loaded_programs` by default); an instruction whose ``op_name``
    differs between programs of one name maps to :data:`AMBIGUOUS`."""
    out: Dict[str, Dict[str, str]] = {}
    for module, text in (loaded_programs() if programs is None
                         else programs):
        seen = out.setdefault(module, {})
        for name, op in hlo_op_names(text).items():
            if seen.get(name, op) != op:
                op = AMBIGUOUS
            seen[name] = op
    return out


def leaf_ops(device: list) -> List[list]:
    """The operations of ``device`` (``[plane, name, start_ns, dur_ns]``)
    that no other operation of their plane runs inside."""
    inner = set()
    by_plane: Dict[str, list] = {}
    for i, (plane, _, s, d) in enumerate(device):
        by_plane.setdefault(plane, []).append((s, -d, i))
    for evs in by_plane.values():
        stack: List[Tuple[int, int]] = []          # (end, index), open ops
        for s, neg_d, i in sorted(evs):
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack and s - neg_d <= stack[-1][0]:
                inner.add(stack[-1][1])
            stack.append((s - neg_d, i))
    return [e for i, e in enumerate(device) if i not in inner]


def _index(trace):
    """Each leaf operation of ``trace`` with its scope path (components),
    computed once per trace."""
    rows = _INDEX.get(trace)
    if rows is None:
        table = program_scopes()
        rows = []
        for _, name, s, d in leaf_ops(trace.device):
            module, _, op = name.rpartition("/")
            path = table.get(module, {}).get(op.lstrip("%"), AMBIGUOUS)
            rows.append((module, tuple(path.split("/")) if path else (), s,
                         d))
        _INDEX[trace] = rows
    return rows


def scope_s(trace, program: str, scope: str, lo: int,
            hi: int) -> Optional[float]:
    """Device seconds in [lo, hi) of the leaf operations of the programs
    whose name holds ``program`` that carry ``scope``, averaged over the
    devices; None when no such operation ran."""
    tot, hit = 0, False
    for module, path, s, d in _index(trace):
        if program in module and scope in path and s < hi and s + d > lo:
            tot += min(s + d, hi) - max(s, lo)
            hit = True
    return tot / 1e9 / max(len(trace.planes), 1) if hit else None


def counter_total(name: str, **labels) -> Optional[float]:
    """Sum of the ``repro.obs`` counters ``name`` whose labels hold
    ``labels``; None when there is none."""
    import repro.obs as obs
    hits = [m.snapshot() for lab, m in obs.registry().collect(name)
            if all(lab.get(k) == str(v) for k, v in labels.items())]
    return float(sum(hits)) if hits else None


def per_flush_ms(ctx, scope: str, *programs: str) -> Optional[float]:
    """Device milliseconds per flush of the window under ``scope``, summed
    over the programs whose names hold one of ``programs``."""
    if ctx.trace is None or not ctx.flushes:
        return None
    lo, hi = ctx.win
    parts = [scope_s(ctx.trace, p, scope, lo, hi) for p in programs]
    parts = [p for p in parts if p is not None]
    return 1e3 * sum(parts) / ctx.flushes if parts else None


def per_sweep_ms(ctx, program: str, scope: str) -> Optional[float]:
    """Device milliseconds per sweep under ``scope`` in the vertex program
    ``program``'s request: its ``_run_program`` inside the request's host
    span, over the sweeps its fixpoint ran (``program.sweeps``)."""
    if ctx.trace is None:
        return None
    sweeps = counter_total("program.sweeps", program=program)
    spans = ctx.trace.spans(f"bench.analytics.{program}")
    if not sweeps or not spans:
        return None
    lo, hi = spans[0]
    secs = scope_s(ctx.trace, "_run_program", scope, lo, hi)
    return None if secs is None else 1e3 * secs / sweeps
