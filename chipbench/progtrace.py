"""The program's own spans, counters and named scopes, read beside the
traced slice (``devtrace.Reduced``).

``ServingEngine`` keeps a tracer (``repro/serving/trace.py``).  Its counters
are always on and reach a run through ``run.stats["counters"]``.  While a
profiler session runs, as in the slice of a ``--trace 1`` run, it records
spans on ``time.perf_counter`` (``run.stats["trace"]["spans"]``), and
``run.stats["trace"]["hlo"]`` holds the compiled HLO text of each runner
program called meanwhile.  The TPU profile names a device operation by its
HLO instruction alone; :func:`op_scopes` reads each instruction's named
scope from that text.  A run of a program without the tracer (the parent of
the change that added it) has none of these, and every reader here returns
None for it.

The program's spans are put on the profiler's clock through the runner
calls: each ``chipbench.<kind>`` call span of the slice encloses one
``runner.prepare`` span of the program, and one offset maps the first onto
the second.
"""
from __future__ import annotations

import bisect
import collections
import functools
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

RUNNER_PROGRAMS = ("jit__decode_fn", "jit__chunk_fn", "jit__prefill_fn")
KV_SCOPES = ("kv_gather", "kv_commit")
DISPATCH_SPANS = ("runner.prepare", "runner.dispatch")
NO_SCOPE = "(no scope)"
# the program's jax.named_scope names on the device step, innermost wins:
# K/V pool gather and commit (serving/kv_cache.py), a block's attention and
# MLP and the layer scan around them (models/transformer.py), the head
# (models/layers.lm_logits) and on-device sampling (serving/engine.py)
SCOPES = ("kv_gather", "kv_commit", "attention", "mlp", "lm_head",
          "sampling", "layers")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=\s*")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([^\s,(){}=]+)")
_CALLS = re.compile(r"calls=%([^\s,(){}=]+)")
# instructions that only move or regroup data: an unnamed one takes the
# scope of the data it moves
_MOVES = {"copy", "copy-start", "copy-done", "bitcast", "broadcast",
          "get-tuple-element", "tuple", "transpose", "reshape"}


def counters(run) -> Optional[Dict[str, int]]:
    return (run.stats or {}).get("counters")


def spans(run) -> Optional[List[Dict]]:
    tr = (run.stats or {}).get("trace")
    return tr["spans"] if tr and tr.get("spans") else None


def decode_span_ms(run) -> Optional[float]:
    """Mean length (ms) of the complete ``runner.decode`` spans the run
    kept: those with children.  The tracer records while the profiler runs,
    so these are the slice's rounds.  The harness stops the profiler inside
    a runner call whose scheduler span opened before the stop and whose
    children opened after it: that span, without children, holds the stop's
    stall and is left out; the call the profiler started in opened its span
    before the tracer was on, so it is not in the ring."""
    sp = spans(run)
    if sp is None:
        return None
    parents = {s["parent"] for s in sp}
    done = [s["end"] - s["start"] for s in sp
            if s["name"] == "runner.decode" and s["id"] in parents]
    return 1e3 * float(np.mean(done)) if done else None


def _label(text: str) -> Tuple[str, str]:
    """``%fusion.7 = bf16[32,128]{1,0} fusion(...)`` -> ``("fusion.7",
    "bf16[32,128]")``; a tuple result reads ``"()"``."""
    name, _, rest = text.lstrip("%").partition("=")
    rtype = rest.strip().split(" ")[0]
    return name.strip(), "()" if rtype.startswith("(") else rtype.split("{")[0]


def _self_ns(ops: List[Tuple[float, float, str]]) -> List[float]:
    """Per operation (sorted by start), its time not covered by operations
    nested in it."""
    out = [b - a for a, b, _ in ops]
    stack: List[int] = []
    for i, (a, b, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            j = stack[-1]
            out[j] -= min(b, ops[j][1]) - a
        stack.append(i)
    return out


def scope_seconds(run) -> Optional[Dict[str, float]]:
    """Device self time of the runner programs' operations in the slice by
    named scope (``NO_SCOPE`` for the rest), and under ``"programs"`` the
    runner programs' whole device time.  An operation is looked up in the
    program whose instructions match most of the operations inside its
    module's event."""
    tr, st = run.trace, (run.stats or {}).get("trace") or {}
    if tr is None or not st.get("hlo"):
        return None
    by_module: Dict[str, List[Dict]] = {}
    for text in st["hlo"].values():
        prog = op_scopes(text)
        by_module.setdefault(prog["module"], []).append(prog["ops"])
    ops = tr.ops
    starts = [e[0] for e in ops]
    selfs = _self_ns(ops)
    out: Dict[str, float] = {"programs": 0.0}
    for a, b, name in tr.modules:
        cands = by_module.get(name.split("(")[0])
        if not name.startswith(RUNNER_PROGRAMS) or not cands:
            continue
        out["programs"] += (b - a) * 1e-9
        lo, hi = bisect.bisect_left(starts, a), bisect.bisect_right(starts, b)
        inside = [i for i in range(lo, hi) if ops[i][1] <= b]
        labels = [_label(ops[i][2]) for i in inside]
        best = max(cands, key=lambda m: sum(m.get(n, [None])[0] == t
                                            for n, t in labels))
        for i, (n, t) in zip(inside, labels):
            entry = best.get(n)
            scope = entry[1] if entry and entry[0] == t and entry[1] else NO_SCOPE
            out[scope] = out.get(scope, 0.0) + selfs[i] * 1e-9
    return out if out["programs"] else None


def _offset_ns(calls: List[Tuple[float, float, str]], sp: List[Dict]
               ) -> Optional[float]:
    """The offset (ns) from the program's clock (perf_counter seconds) to
    the profiler's.  Each runner call's work runs from a ``runner.prepare``
    start to the end of the next ``runner.wait``, inside the call's span.
    First the offset, among those that centre some work in the slice's first
    call, that puts the most calls around a work; then the median of the
    offsets that centre each work in its call.  None when no work fits."""
    prep = sorted(s["start"] * 1e9 for s in sp if s["name"] == "runner.prepare")
    waits = sorted((s["start"] * 1e9, s["end"] * 1e9) for s in sp
                   if s["name"] == "runner.wait")
    starts = [w[0] for w in waits]
    work = [(p, waits[k][1]) for p in prep
            if (k := bisect.bisect_left(starts, p)) < len(waits)]
    if not calls or not work:
        return None
    a = np.asarray([c[0] for c in calls])
    b = np.asarray([c[1] for c in calls])
    p0 = np.asarray([w[0] for w in work])
    p1 = np.asarray([w[1] for w in work])

    def held(off: float) -> np.ndarray:
        """Per work, the call that holds it whole (-1 for none)."""
        i = np.searchsorted(a, p0 + off, side="right") - 1
        return np.where((i >= 0) & (p1 + off <= b[np.maximum(i, 0)]), i, -1)

    mid0 = (a[0] + b[0]) / 2
    coarse = max((mid0 - (x + y) / 2 for x, y in work),
                 key=lambda o: len(set(held(o).tolist()) - {-1}))
    i = held(coarse)
    ok = i >= 0
    if not ok.any():
        return None
    return float(np.median((a[i[ok]] + b[i[ok]]) / 2 - (p0[ok] + p1[ok]) / 2))


def on_profiler_clock(run) -> Optional[List[Tuple[float, float, str]]]:
    """The program's spans that overlap the slice, on the profiler's clock:
    ``(start_ns, end_ns, name)``."""
    sp, tr = spans(run), run.trace
    if sp is None or tr is None:
        return None
    off = _offset_ns(tr.spans, sp)
    if off is None:
        return None
    out = [(s["start"] * 1e9 + off, s["end"] * 1e9 + off, s["name"])
           for s in sp if not s["name"].startswith("request.")]
    return sorted(s for s in out if s[1] > tr.lo and s[0] < tr.hi)


def _idle(tr) -> List[Tuple[float, float]]:
    edges = [tr.lo] + [x for ab in tr.busy for x in ab] + [tr.hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _innermost(spans_: List[Tuple[float, float, str]]
               ) -> List[Tuple[float, float, str]]:
    """Disjoint pieces of the union of ``spans_``, each named by the
    latest-opened span that covers it (the list is sorted by start)."""
    cuts = sorted({x for a, b, _ in spans_ for x in (a, b)})
    out, open_, k = [], [], 0
    for x, y in zip(cuts, cuts[1:]):
        while k < len(spans_) and spans_[k][0] <= x:
            open_.append(spans_[k])
            k += 1
        open_ = [s for s in open_ if s[1] > x]
        if open_:
            out.append((x, y, open_[-1][2]))
    return out


def _split(gaps: List[Tuple[float, float]],
           pieces: List[Tuple[float, float, str]]
           ) -> List[Tuple[float, float, Optional[str]]]:
    """``gaps`` cut along the disjoint ``pieces`` (both sorted), each part
    named by the piece over it (None where no piece is)."""
    out: List[Tuple[float, float, Optional[str]]] = []
    j = 0
    for ga, gb in gaps:
        while j < len(pieces) and pieces[j][1] <= ga:
            j += 1
        x, k = ga, j
        while k < len(pieces) and pieces[k][0] < gb:
            a, b, name = pieces[k]
            if a > x:
                out.append((x, a, None))
            out.append((max(a, x), min(b, gb), name))
            x = min(b, gb)
            k += 1
        if x < gb:
            out.append((x, gb, None))
    return out


def _totals(parts: List[Tuple[float, float, Optional[str]]]
            ) -> Dict[Optional[str], float]:
    out: Dict[Optional[str], float] = {}
    for a, b, name in parts:
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def dispatch_idle_ns(run) -> Optional[Tuple[float, float]]:
    """(device idle ns inside ``runner.prepare`` or ``runner.dispatch``
    spans, the slice's ns)."""
    sp = on_profiler_clock(run)
    if not sp:
        return None
    tr = run.trace
    marked = [(a, b, "dispatch") for a, b, name in sp if name in DISPATCH_SPANS]
    ns = _totals(_split(_idle(tr), _innermost(marked))).get("dispatch", 0.0)
    return ns, tr.hi - tr.lo


def idle_by_span(run) -> Optional[Dict[str, float]]:
    """Device idle seconds of the slice, inside the slice's runner calls
    (``"in call: <span>"``) and outside them (``"outside: <span>"``), by
    the innermost program span over each moment (``"(none)"`` where no
    span is)."""
    sp = on_profiler_clock(run)
    if not sp:
        return None
    tr = run.trace
    calls = _innermost([(a, b, "in call") for a, b, _ in tr.spans])
    where = _split(_idle(tr), calls)
    inner = _innermost(sp)
    out: Dict[str, float] = {}
    for side in ("in call", None):
        parts = [(a, b) for a, b, w in where if w == side]
        for name, ns in _totals(_split(parts, inner)).items():
            key = f"{side or 'outside'}: {name or '(none)'}"
            out[key] = out.get(key, 0.0) + ns * 1e-9
    return out


# --------------------------------------------------------------------------
# named scopes from the compiled HLO text
# --------------------------------------------------------------------------

def scope_of(op_name: str) -> Optional[str]:
    """The innermost of :data:`SCOPES` on an op's name-stack path."""
    return next((p for p in reversed(op_name.split("/")) if p in SCOPES),
                None)


def _opcode(rest: str) -> Tuple[str, str]:
    """``"bf16[4]{0} add(%a, %b), ..."`` -> ``("bf16[4]{0}", "add")``; a
    tuple type (spaces inside its parentheses) is read to its close."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        rtype, tail = rest[:i + 1], rest[i + 1:]
    else:
        rtype, _, tail = rest.partition(" ")
    return rtype, tail.strip().split("(")[0]


@functools.lru_cache(maxsize=32)
def op_scopes(hlo_text: str) -> Dict[str, Any]:
    """``{"module": name, "ops": {instruction: [result type, scope]}}`` of
    a compiled program's HLO text.  The result type drops its layout (a
    tuple reads ``"()"``).  The scope is :func:`scope_of` the instruction's
    ``op_name`` metadata; an instruction the compiler made without one
    takes, in this order: the scope most of its fused computation's
    instructions carry; for a copy, bitcast, broadcast, tuple or the like,
    the scope of the nearest producer, else consumer, through such moves;
    the scope of the instruction whose loop body or called computation it
    sits in; else None."""
    lines = hlo_text.splitlines()
    comps = {m.group(1) for ln in lines if (m := _COMPUTATION.match(ln))}
    module, cur = "", None
    own: Dict[str, Optional[str]] = {}
    rtype: Dict[str, str] = {}
    opcode: Dict[str, str] = {}
    comp_of: Dict[str, str] = {}
    args: Dict[str, List[str]] = {}
    fused: Dict[str, List[str]] = {}
    caller: Dict[str, str] = {}
    members: Dict[str, List[str]] = collections.defaultdict(list)
    for line in lines:
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        c = _COMPUTATION.match(line)
        if c:
            cur = c.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or cur is None:
            continue
        name = m.group(1)
        rtype[name], opcode[name] = _opcode(line[m.end():].lstrip())
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else None
        comp_of[name] = cur
        members[cur].append(name)
        refs = _REF.findall(line[m.end():])
        args[name] = [r for r in refs if r not in comps and r != name]
        for r in refs:
            if r in comps:
                caller.setdefault(r, name)
        fused[name] = _CALLS.findall(line)
    users: Dict[str, List[str]] = collections.defaultdict(list)
    for n, a in args.items():
        for x in a:
            users[x].append(n)

    def inner(n: str) -> Optional[str]:
        votes = collections.Counter(own[x] for comp in fused.get(n, ())
                                    for x in members.get(comp, ())
                                    if own.get(x))
        return votes.most_common(1)[0][0] if votes else None

    def walk(n: str, edges: Dict[str, List[str]]) -> Optional[str]:
        seen, frontier = {n}, [n]
        while frontier:
            nxt = []
            for x in frontier:
                for y in edges.get(x, ()):
                    if y in seen or y not in own:
                        continue
                    seen.add(y)
                    got = own[y] or inner(y)
                    if got:
                        return got
                    if opcode[y] in _MOVES:
                        nxt.append(y)
            frontier = nxt
        return None

    memo: Dict[str, Optional[str]] = {}

    def resolve(n: str) -> Optional[str]:
        if n not in memo:
            memo[n] = None              # a loop back to n reads None
            got = own[n] or inner(n)
            if not got and opcode[n] in _MOVES:
                got = walk(n, args) or walk(n, users)
            if not got and comp_of[n] in caller:
                got = resolve(caller[comp_of[n]])
            memo[n] = got
        return memo[n]

    ops = {n: ["()" if rtype[n].startswith("(") else rtype[n].split("{")[0],
               resolve(n)] for n in own}
    return {"module": module, "ops": ops}
