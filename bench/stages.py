"""Device time of the EM stages, by the program's own names.

The program names each EM stage with a ``jax.named_scope``
(``ivec_preselect``, ``ivec_rescore``, ``ivec_moments``, ``ivec_estep``,
``ivec_mstep`` and, inside the M-step, ``ivec_sigma_floor`` and
``ivec_min_divergence``; see ``PERF.md`` section 3). A TPU trace's op
events carry no scope: an op is known by its HLO instruction name, and
its scope is the ``op_name`` metadata of that instruction in the
compiled program that ran (``hlo_scopes``). That program is found among
the process's live executables: of those whose HLO holds an ``ivec_``
scope, the one whose instruction names cover most of the window's
device time.

An op is joined to a scope only where its program is known. Instruction
names are unique within a module only: a name that another live program
gives another scope (an op of the UBM refresh, or of the alignment of
the statistics at rest, may share a name with the step) is taken as the
step's only where it ran between two ops that no other program names,
since a chip runs one program at a time. Any other op is left unscoped
and counts in ``unstaged_seconds``.

This module is the reduction behind the per-layer readers
``bench/metrics/<stage>_ms.train.py`` and ``unstaged_ms.train.py``.
"""
from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as TR

PREFIX = "ivec_"
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = ")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.-]+)")
_APPLIES = re.compile(r"to_apply=%?([\w.-]+)")
_REF = re.compile(r"%([\w.-]+)")


@dataclass(frozen=True)
class ScopedEvent(TR.Event):
    scope: str = ""         # the op's op_name path, "" when unknown


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """{op: op_name path} of the instructions of a compiled module's HLO
    text that can run as device ops (those of computations that no
    fusion calls and no reduction applies), "" where none is found. An
    instruction is keyed as a trace names its op (``bench/trace
    .op_name``: a custom call's target appended unless it is a Pallas
    kernel).

    An op_name is the program's when it is a path (``jit(f)/...``); the
    compiler names some instructions it makes after themselves
    (``ragged-dot-none``) and leaves others without one (a ``sort`` it
    adds). An instruction without a path takes that of the computation
    it calls (the computation's root's, else the one most of its
    instructions carry); else the one most of its users carry, the work
    it feeds (the second moments' sort and grouped matmul feed the
    moments); else the one most of its operands carry. Operands come
    last: the compiler shares an ``iota`` or index computation between
    stages and keeps one stage's name on it."""
    key: Dict[str, str] = {}
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    args: Dict[str, List[str]] = {}
    comps: Dict[str, List[str]] = {}
    roots: Dict[str, str] = {}
    inner = set()               # computations run inside another op
    comp, defined = None, set()
    for line in hlo_text.splitlines():
        h = _COMP.match(line)
        if h:
            comp, defined = h.group(1), set()
            comps[comp] = []
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name, rest = m.group(1), line.split(" = ", 1)[1]
        key[name] = TR.op_name(f"{name} = {rest}")
        args[name] = [a for a in _REF.findall(rest) if a in defined]
        comps[comp].append(name)
        defined.add(name)
        if line.lstrip().startswith("ROOT "):
            roots[comp] = name
        n, c = _OP_NAME.search(line), _CALLS.search(line)
        if n and "/" in n.group(1):
            own[name] = n.group(1)
        if c:
            calls[name] = c.group(1)
            inner.add(c.group(1))
        inner.update(_APPLIES.findall(line))

    def common(found: List[str]) -> str:
        found = [x for x in found if x]
        return Counter(found).most_common(1)[0][0] if found else ""

    users: Dict[str, List[str]] = {}
    for name, used in args.items():
        for a in used:
            users.setdefault(a, []).append(name)

    def of_comp(comp: str, seen: frozenset) -> str:
        if comp in seen or comp not in comps:
            return ""
        seen = seen | {comp}
        return of(roots.get(comp, ""), seen) or common(
            [of(i, seen) for i in comps[comp]])

    def named(name: str, seen: frozenset) -> str:
        if name in own:
            return own[name]
        return of_comp(calls[name], seen) if name in calls else ""

    def along(name: str, edges: Dict[str, List[str]], memo: Dict[str, str],
              seen: frozenset) -> str:
        """The path of ``name``, else the most common one along
        ``edges`` (users, or operands), followed while none is found."""
        if name in memo or name in seen:
            return memo.get(name, "")
        seen = seen | {name}
        s = named(name, seen) or common(
            [along(n, edges, memo, seen) for n in edges.get(name, ())])
        memo[name] = s
        return s

    down: Dict[str, str] = {}
    up: Dict[str, str] = {}

    def of(name: str, seen: frozenset = frozenset()) -> str:
        return (along(name, users, down, seen)
                or along(name, args, up, seen))

    return {key[name]: of(name) for comp, names in comps.items()
            if comp not in inner for name in names}


def _staged(scope: str) -> bool:
    return any(c.startswith(PREFIX) for c in scope.split("/"))


def live_hlo_texts() -> List[str]:
    """HLO text of every module of the process's live executables (an
    executable that gives none is passed over)."""
    import jax
    out = []
    for ex in jax.devices()[0].client.live_executables():
        try:
            out += [m.to_string() for m in ex.hlo_modules()]
        except Exception:  # an executable built without its HLO
            continue
    return out


def program_scopes(trace: TR.Trace, texts: Optional[Sequence[str]] = None
                   ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """(sure, unsure): the scopes (``hlo_scopes``) of the program that
    ran the traced window, split by whether another of the modules gives
    the same op name another scope. The program is, among the modules of
    ``texts`` (default: the live executables') whose HLO holds an
    ``ivec_`` scope, the one whose op names cover most device time inside
    the window, more staged ops breaking a tie; ({}, {}) when none holds
    a scope."""
    lo, hi = trace.window
    by_name: Dict[str, float] = {}
    for ops in trace.devices:
        for e in ops:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                by_name[e.name] = by_name.get(e.name, 0.0) + d
    texts = live_hlo_texts() if texts is None else texts
    mods = [hlo_scopes(t) if PREFIX in t else None for t in texts]
    best, rank = None, (0.0, 0)
    for i, scopes in enumerate(mods):
        if scopes is None:
            continue
        staged = sum(map(_staged, scopes.values()))
        r = (sum(t for n, t in by_name.items() if n in scopes), staged)
        if staged and r > rank:
            best, rank = i, r
    if best is None:
        return {}, {}
    sure, unsure = dict(mods[best]), {}
    for i, text in enumerate(texts):
        if i == best:
            continue
        other = mods[i] if mods[i] is not None else hlo_scopes(text)
        for n in sure.keys() & other.keys():
            if other[n] != sure[n]:
                unsure[n] = sure.pop(n)
    return sure, unsure


def with_scopes(trace: TR.Trace, sure: Dict[str, str],
                unsure: Optional[Dict[str, str]] = None) -> TR.Trace:
    """The trace with each device op's scope: ``sure[name]``, or
    ``unsure[name]`` where the nearest ops before and after it in time
    that are not in ``unsure`` are both in ``sure`` (the same program ran
    them, and so it), else ""."""
    unsure = unsure or {}
    devices = []
    for ops in trace.devices:
        mine = [True if e.name in sure else None if e.name in unsure
                else False for e in ops]
        order = sorted(range(len(ops)), key=lambda i: ops[i].start)
        before, after = [False] * len(ops), [False] * len(ops)
        for seq, near in ((order, before), (order[::-1], after)):
            last = False
            for i in seq:
                if mine[i] is None:
                    near[i] = last
                else:
                    last = mine[i]
        between = [b and a for b, a in zip(before, after)]
        devices.append([ScopedEvent(
            e.name, e.start, e.end,
            sure[e.name] if k else unsure[e.name] if k is None and b
            else "") for e, k, b in zip(ops, mine, between)])
    return TR.Trace(devices, trace.spans, trace.window)


def _seconds(trace: TR.Trace, keep) -> float:
    if not trace.devices:
        return 0.0
    lo, hi = trace.window
    return sum(e - s for ops in trace.devices for s, e in TR._clip(
        [o for o in ops if keep(getattr(o, "scope", ""))], lo, hi)) / len(
            trace.devices)


def scope_seconds(trace: TR.Trace, scope: str) -> float:
    """Device seconds inside the window of the ops whose scope path has
    ``scope`` as a component, averaged over devices."""
    return _seconds(trace, lambda s: scope in s.split("/"))


def unstaged_seconds(trace: TR.Trace) -> float:
    """Device seconds inside the window of the ops under no ``ivec_``
    scope, averaged over devices."""
    return _seconds(trace, lambda s: not _staged(s))


def scoped(r) -> Optional[TR.Trace]:
    """The reading's trace with its ops' scopes; None when nothing was
    traced or the program that ran holds no ``ivec_`` scope (a checkout
    from before the scopes)."""
    if r.trace is None or not r.trace.devices:
        return None
    sure, unsure = program_scopes(r.trace)
    if not any(map(_staged, [*sure.values(), *unsure.values()])):
        return None
    return with_scopes(r.trace, sure, unsure)


def stage_ms(r, scope: str):
    """A reader's number: device milliseconds per traced iteration under
    ``scope``; None where ``scoped`` gives nothing."""
    its, tr = r.counters.get("iterations"), scoped(r)
    if not its or tr is None:
        return None
    return 1e3 * scope_seconds(tr, scope) / its


def unstaged_ms(r):
    """Device milliseconds per traced iteration under no stage; None
    where ``scoped`` gives nothing."""
    its, tr = r.counters.get("iterations"), scoped(r)
    if not its or tr is None:
        return None
    return 1e3 * unstaged_seconds(tr) / its
