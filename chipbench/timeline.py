"""What the benchmark records around the calls the scheduler makes into the
runner, and the per-request timeline it rebuilds from them.

The recorder wraps three public entry points of the engine's
``ModelRunner``: ``prefill_slot`` (whole-prompt admission),
``prefill_chunk`` (chunked prefill) and ``decode_round``.  Their arguments
say which slot holds which prompt and at which position, and their results
are the tokens served, so the benchmark can time every request from its due
time to each of its tokens without a span inside the program.  A request is
recognised by the first tokens of its prompt, gathered over as many chunks
as it takes: a chunk can be shorter than that when the round's token budget
runs low.

A fixed window closes ``seconds`` after the first due time: the first call
into the runner after that raises ``WindowClosed``, so that no chip time
goes to requests the window does not count.  The run then holds what the
runner returned up to the close; requests that finished by then are the
ones checked.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

import numpy as np

KEY = 8   # prompt tokens that identify a request


class WindowClosed(Exception):
    """Raised at the first runner call after a fixed window has closed."""


def _key(tokens) -> tuple:
    return tuple(int(t) for t in np.asarray(tokens).reshape(-1)[:KEY])


@dataclasses.dataclass
class Track:
    """The served timeline of one request (host clock, seconds)."""

    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    first_t: Optional[float] = None
    done_t: Optional[float] = None
    last_t: float = 0.0
    gaps: List[float] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None    # the batch row that served it


class Recorder:
    """Wraps a runner's entry points and records every call of the window."""

    def __init__(self, runner: Any, planned: List[Any]):
        self.runner = runner
        self.planned = {p.uid: p for p in planned}
        if any(len(p.prompt) < KEY for p in planned):
            raise ValueError(f"every prompt needs {KEY} tokens to be told apart")
        self.by_key = {_key(p.prompt): p.uid for p in planned}
        self.slot_uid: Dict[int, int] = {}
        self.slot_head: Dict[int, List[int]] = {}   # a prompt's first tokens
        self.tracks: Dict[int, Track] = {u: Track() for u in self.planned}
        self.rounds: List[Dict] = []
        self.prefills: List[Dict] = []
        self.call_starts: List[float] = []
        self.trace = None            # a devtrace.Slice, for --trace 1
        self.t0 = 0.0
        self.close_at: Optional[float] = None   # a fixed window's end
        self.on = False
        self._orig = {n: getattr(runner, n)
                      for n in ("decode_round", "prefill_chunk",
                                "prefill_slot")}
        self.bucket_for = runner.bucket_for
        self.decode_block = runner.decode_block
        runner.decode_round = self._decode_round
        runner.prefill_chunk = self._prefill_chunk
        runner.prefill_slot = self._prefill_slot

    def start(self, t0: float, close_at: Optional[float] = None) -> None:
        self.t0 = t0
        self.close_at = close_at
        self.on = True

    def stop(self) -> None:
        self.on = False
        if self.trace is not None:
            self.trace.stop()
        for name, fn in self._orig.items():
            setattr(self.runner, name, fn)
        # hold nothing of the program once the window has closed, so that
        # its device memory is freed before the reference runs
        self.runner = self.bucket_for = None
        self._orig = {}

    # -- the wrapped calls ------------------------------------------------

    def _call(self, label: str, name: str, *args, **kw):
        """Run one wrapped call; ``label`` names its host span in a trace:
        ``<kind>/<rows>x<width>``, for a decode round followed by
        ``/<K/V positions read>/<active rows>``."""
        if not self.on:
            return self._orig[name](*args, **kw), 0.0, 0.0
        if self.close_at is not None and time.perf_counter() >= self.close_at:
            raise WindowClosed
        if self.trace is not None:
            # starting or stopping the profiler stalls the loop here, before
            # the call is timed; Run subtracts the stall from its window
            self.trace.tick(time.perf_counter() - self.t0)
        t = time.perf_counter()
        self.call_starts.append(t)
        if self.trace is not None:
            with self.trace.span(label):
                out = self._orig[name](*args, **kw)
        else:
            out = self._orig[name](*args, **kw)
        return out, t, time.perf_counter()

    def _emit(self, uid: int, toks: List[int], t: float) -> None:
        tr = self.tracks[uid]
        if not toks:
            return
        if tr.first_t is None:
            tr.first_t = t
        else:
            tr.gaps.extend([(t - tr.last_t) / len(toks)] * len(toks))
        tr.tokens.extend(toks)
        tr.times.extend([t] * len(toks))
        tr.last_t = t
        if len(tr.tokens) >= self.planned[uid].max_new_tokens:
            tr.done_t = t

    def _assign(self, slot: int, uid: int) -> None:
        self.slot_uid[slot] = uid
        if self.tracks[uid].slot is None:
            self.tracks[uid].slot = slot

    def _prefill_slot(self, slot, prompt, temperature=0.0, pages=None):
        n = len(np.asarray(prompt).reshape(-1))
        width = self.bucket_for(n)
        tok, t0, t1 = self._call(f"prefill/1x{width}", "prefill_slot", slot,
                                 prompt, temperature, pages=pages)
        if self.on:
            uid = self.by_key[_key(prompt)]
            self._assign(int(slot), uid)
            self.prefills.append(dict(t0=t0, t1=t1, tokens=n, rows=1,
                                      width=width, spans=[(0, n)]))
            self._emit(uid, [int(tok)], t1)
        return tok

    def _prefill_chunk(self, tokens, positions, block_tables, cols, temps):
        rows, width = np.asarray(tokens).shape
        tok, t0, t1 = self._call(f"prefill/{rows}x{width}", "prefill_chunk",
                                 tokens, positions, block_tables, cols, temps)
        if self.on:
            granted = np.flatnonzero(np.asarray(block_tables).any(axis=1))
            n, spans = 0, []
            for s in granted:
                s, take, pos = int(s), int(cols[s]) + 1, int(positions[s])
                n += take
                spans.append((pos, take))
                if pos == 0:
                    self.slot_uid.pop(s, None)
                    self.slot_head[s] = []
                if s not in self.slot_uid:
                    head = self.slot_head[s]
                    head.extend(int(t) for t in tokens[s, :take])
                    if len(head) < KEY:
                        continue        # not yet told apart, not yet done
                    self._assign(s, self.by_key[_key(head)])
                uid = self.slot_uid[s]
                if pos + take >= len(self.planned[uid].prompt):
                    self._emit(uid, [int(tok[s])], t1)
            self.prefills.append(dict(t0=t0, t1=t1, tokens=n, rows=int(rows),
                                      width=int(width), spans=spans))
        return tok

    def _decode_round(self, tokens, positions, temps, block_tables=None,
                      active=None):
        act = np.asarray(active, bool)
        k = self.decode_block
        pos = np.asarray(positions, np.int64)[act]
        kv = int(k * pos.sum() + len(pos) * k * (k + 1) // 2)
        (out, counts), t0, t1 = self._call(
            f"decode/{len(act)}x{k}/{kv}/{len(pos)}", "decode_round", tokens,
            positions, temps, block_tables=block_tables, active=active)
        if self.on:
            delivered, live, takes = 0, [], []
            for s in np.flatnonzero(act):
                s = int(s)
                uid = self.slot_uid[s]
                left = (self.planned[uid].max_new_tokens
                        - len(self.tracks[uid].tokens))
                take = max(0, min(int(counts[s]), left))
                self._emit(uid, [int(x) for x in out[:take, s]], t1)
                delivered += take
                live.append(int(positions[s]))
                takes.append(take)
            self.rounds.append(dict(t0=t0, t1=t1, delivered=delivered,
                                    steps=int(out.shape[0]),
                                    slots=int(out.shape[1]),
                                    positions=np.asarray(live, np.int64),
                                    takes=np.asarray(takes, np.int64)))
        return out, counts


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run.

    ``served`` holds, per request, the tokens the program returned when
    ``ServingEngine.run`` returned (``returned``); when a fixed window
    closed first, the tokens the runner returned for each request that
    finished by then."""

    cell: Dict
    seed: int
    seconds: float
    planned: Dict[int, Any]
    served: Dict[int, List[int]]
    returned: bool
    recorder: Recorder
    stats: Dict
    setup_s: float
    t0: float
    peak: Dict
    trace: Any = None

    @property
    def model(self) -> Dict:
        return self.cell["config_file"]["model"]

    @property
    def forms(self) -> Dict:
        return self.cell["config_file"]["forms"]

    @property
    def work(self) -> Any:
        """The configuration's family module: its operation and byte counts
        (``work.py``'s conventions)."""
        return self.cell["family"]

    @property
    def tracks(self) -> Dict[int, Track]:
        return self.recorder.tracks

    @property
    def output_tokens(self) -> int:
        return sum(len(t.tokens) for t in self.tracks.values())

    @property
    def window_end(self) -> float:
        """A ``fixed`` window closes ``seconds`` after the first due time; a
        ``drain`` window at the last completion."""
        if self.cell["mix"]["window"] == "fixed":
            return self.t0 + self.seconds
        done = [t.done_t for t in self.tracks.values() if t.done_t is not None]
        return max(done) if done else time.perf_counter()

    @property
    def stalled_s(self) -> float:
        """Seconds the profiler's start and stop held the loop (traced runs)."""
        tr = self.recorder.trace
        return tr.stalled_s if tr is not None else 0.0

    @property
    def window_s(self) -> float:
        return self.window_end - self.t0 - self.stalled_s

    @property
    def window_tokens(self) -> int:
        return sum(sum(1 for x in t.times if x <= self.window_end)
                   for t in self.tracks.values())

    def window_gaps(self) -> List[float]:
        """Gaps before every token delivered in the window but a request's
        first."""
        return [g for t in self.tracks.values()
                for g, x in zip(t.gaps, t.times[1:]) if x <= self.window_end]

    def backlog_left(self) -> int:
        """Requests not yet started when a fixed window closed."""
        return sum(t.first_t is None or t.first_t > self.window_end
                   for t in self.tracks.values())

    def due(self, uid: int) -> float:
        return self.t0 + self.planned[uid].arrival_s

    def ttft_s(self) -> Dict[int, float]:
        """Per request, seconds from its due time to its first token; inf
        for a miss: a request that failed or got no first token."""
        failed = set(self.failed_uids())
        return {u: math.inf if u in failed or t.first_t is None
                else t.first_t - self.due(u) for u, t in self.tracks.items()}

    def quarter_ttft_ms(self) -> List[float]:
        """Median time to first token of each quarter of the arrivals, ms."""
        first = self.ttft_s()
        order = sorted(self.planned.values(), key=lambda p: (p.arrival_s, p.uid))
        return [float(np.median([first[p.uid] for p in q])) * 1e3
                for q in np.array_split(order, 4) if len(q)]

    def failed_uids(self) -> List[int]:
        """Requests served short, long or with a token outside the
        vocabulary: of every request when the engine returned, else of those
        that finished before the window closed."""
        vocab = self.model["vocab_size"]
        bad = []
        for uid in (self.planned if self.returned else self.served):
            toks = self.served.get(uid)
            if (toks is None or len(toks) != self.planned[uid].max_new_tokens
                    or any(not 0 <= t < vocab for t in toks)):
                bad.append(uid)
        return bad

    def timeline_mismatches(self) -> Optional[int]:
        """Requests whose tokens, as read from the runner calls, differ from
        what the program returned (None when the window closed before the
        program returned anything)."""
        if not self.returned:
            return None
        return sum(self.tracks[u].tokens != toks
                   for u, toks in self.served.items())

    def generator_lag_ms(self) -> np.ndarray:
        """Per request, from its due time to the start of the first call into
        the runner after it: how late the serving loop could look at it."""
        starts = np.asarray(sorted(self.recorder.call_starts))
        due = np.asarray([self.due(u) for u in self.planned])
        i = np.minimum(np.searchsorted(starts, due), len(starts) - 1)
        return np.maximum(starts[i] - due, 0.0) * 1e3
