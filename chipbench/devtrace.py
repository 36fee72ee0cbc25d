"""A profiler trace of a slice of the window, reduced to what the per-layer
metrics read.

The slice starts ``start`` seconds into the window and lasts ``seconds``;
while it runs, every call into the runner is a host span
(``chipbench.decode`` / ``chipbench.prefill``) on the profiler's clock.  The
reduction keeps only calls that began and ended inside the slice, and per
call the device events of the TPU that fall in it:

* ``XLA Modules`` events name the program (``jit__decode_fn``,
  ``jit__chunk_fn``, ``jit__prefill_fn``);
* ``XLA Ops`` events are the operations, nested (a while loop contains its
  body); the polarized kernel's are the custom calls named
  ``polarized_matmul``.

Busy time is the union of operation intervals; idle gaps are the holes in
that union inside the slice, each named by the host span it falls in.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import time
from typing import Dict, List, Tuple

import numpy as np

SPAN = "chipbench."
KERNEL = re.compile(r"^%?polarized_matmul(\.\d+)?\s*=")
OP_NAME = re.compile(r"^%?([^\s=]+)\s*=\s*(\S*)")
HOST_LABEL = {"decode": "decode dispatch", "prefill": "prefill dispatch",
              None: "scheduler (admission, bookkeeping, waiting)"}


class Slice:
    def __init__(self, directory: str, start: float, seconds: float):
        self.dir = directory
        self.start_at = start
        self.seconds = seconds
        self.state = "waiting"
        self.t_start = 0.0
        self.stalled_s = 0.0

    def tick(self, t: float) -> None:
        """Called before each runner call with the time into the window."""
        import jax
        if self.state == "waiting" and t >= self.start_at:
            t0 = time.perf_counter()
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.state, self.t_start = "tracing", t
            self.stalled_s += time.perf_counter() - t0
        elif self.state == "tracing" and t - self.t_start >= self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.state == "tracing":
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.state = "done"
            self.stalled_s += time.perf_counter() - t0

    @contextlib.contextmanager
    def span(self, kind: str):
        if self.state != "tracing":
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(SPAN + kind):
            yield

    def reduce(self) -> "Reduced":
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"no profiler trace under {self.dir}: the "
                               f"window ended before the traced slice began")
        from jax.profiler import ProfileData
        red = reduce_planes(ProfileData.from_file(files[0]).planes)
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


def _events(line) -> List[Tuple[float, float, str]]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def reduce_planes(planes) -> "Reduced":
    """Reduce profiler planes (``ProfileData.planes`` or look-alikes with
    ``name``, ``lines[].name``, ``lines[].events[]`` of ``name``,
    ``start_ns``, ``duration_ns``)."""
    spans, ops, modules = [], [], []
    for plane in planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = _events(line)
                elif line.name == "XLA Modules":
                    modules = _events(line)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans += [(a, b, n[len(SPAN):]) for a, b, n in _events(line)
                          if n.startswith(SPAN)]
    return Reduced(sorted(spans), sorted(ops), sorted(modules))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _self_times(ops: List[Tuple[float, float, str]]) -> List[Tuple[str, float]]:
    """Each operation's time not covered by operations nested in it."""
    out, stack = [], []           # stack of [end, name, self_ns]
    for a, b, name in ops:
        while stack and stack[-1][0] <= a:
            end, n, self_ns = stack.pop()
            out.append((n, self_ns))
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    out += [(n, s) for _, n, s in stack]
    return out


def op_label(name: str) -> str:
    """``%fusion.12 = bf16[32,128]{...} fusion(...)`` -> ``fusion.12
    bf16[32,128]``; an op with a tuple result keeps its name alone."""
    m = OP_NAME.match(name)
    if not m:
        return name[:80]
    shape = m.group(2).split("{")[0]
    return m.group(1) if shape.startswith("(") else f"{m.group(1)} {shape}"


class Reduced:
    """The traced slice, cut to the runner calls wholly inside it."""

    def __init__(self, spans, ops, modules):
        if not spans:
            raise RuntimeError("the trace holds no chipbench host span")
        self.spans = spans                       # (start, end, kind)
        self.lo, self.hi = spans[0][0], spans[-1][1]
        inside = lambda ev: [e for e in ev if e[0] >= self.lo and e[1] <= self.hi]
        self.ops = inside(ops)
        self.modules = inside(modules)
        self.busy = _union([(a, b) for a, b, _ in self.ops])

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy) * 1e-9

    def calls(self, kind: str) -> List[Tuple[float, float, List[int]]]:
        """Runner calls of one kind: (start, end, the numbers of the span's
        label), e.g. ``decode/32x4/1234`` -> ``[32, 4, 1234]``."""
        out = []
        for a, b, label in self.spans:
            head, *rest = label.split("/")
            if head == kind:
                nums = [int(x) for part in rest for x in part.split("x")]
                out.append((a, b, nums))
        return out

    def seconds_in(self, events, calls) -> List[float]:
        """Per call, device seconds of ``events`` that lie inside it."""
        starts = np.asarray([c[0] for c in calls])
        out = [0.0] * len(calls)
        for a, b, _ in events:
            i = int(np.searchsorted(starts, a, side="right")) - 1
            if i >= 0 and b <= calls[i][1]:
                out[i] += (b - a) * 1e-9
        return out

    def module_events(self, prefix: str):
        return [e for e in self.modules if e[2].startswith(prefix)]

    def kernel_events(self):
        return [e for e in self.ops if KERNEL.match(e[2])]

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Holes in device busy time inside the slice, each named by the
        host span (or its absence) at the hole's midpoint."""
        edges = [self.lo] + [x for ab in self.busy for x in ab] + [self.hi]
        starts = np.asarray([s[0] for s in self.spans])
        out = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            i = np.searchsorted(starts, mid, side="right") - 1
            kind = (self.spans[i][2].split("/")[0]
                    if i >= 0 and mid <= self.spans[i][1] else None)
            out.append((HOST_LABEL.get(kind, kind), (b - a) * 1e-9))
        return out

    def breakdown(self) -> Dict[str, List]:
        by_op: Dict[str, float] = {}
        for name, self_ns in _self_times(self.ops):
            key = op_label(name)
            by_op[key] = by_op.get(key, 0.0) + self_ns * 1e-9
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
        gaps = self.idle_gaps()
        totals: Dict[str, float] = {}
        for label, s in gaps:
            totals[label] = totals.get(label, 0.0) + s
        idle = [["all gaps: " + k, v] for k, v in
                sorted(totals.items(), key=lambda kv: -kv[1])]
        idle += [[k, v] for k, v in sorted(gaps, key=lambda g: -g[1])]
        return {"device_ops": [[k, v] for k, v in top], "idle_gaps": idle[:10]}
