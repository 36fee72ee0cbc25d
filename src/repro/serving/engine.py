"""Batched serving engine, split into a host-side :class:`Scheduler` driving
a jitted :class:`ModelRunner`, over either a dense slot cache or a paged
KV-cache pool.

The engine keeps every hot-path property of the earlier designs — a
steady-state decode step does no avoidable HBM copies and no host
round-trips:

* **Bulk prefill** — admitting an L-token prompt costs ONE jitted
  ``model.prefill`` call (chunked full-sequence attention + a one-shot cache
  write), not L decode steps.  Attention families (MoE included: its serving
  routing drops no token, so pad rows take nothing from real ones) pad
  prompts to power-of-two buckets to bound recompilation; recurrent
  families (``Model.padded_prefill == False``) compile per exact length.
* **Donated caches** — the KV/state cache is donated into both jitted entry
  points (``donate_argnums``, matching launch/train.py), so cache updates
  alias in place instead of copying the full cache every token.
* **On-device sampling** — greedy and temperature sampling run inside the
  jitted step (``jax.random.categorical``, per-slot temperature vector); the
  host never sees logits on the hot path.
* **Chunked decode** — an inner ``lax.scan`` decodes ``decode_block`` tokens
  per dispatch, so the host syncs once every k tokens instead of every token.
* **Per-slot positions** — every slot owns its cache timeline end to end
  (vector ``pos`` through every decode step).
* **Mesh sharding** — ``mesh=...`` runs the whole engine SPMD on a device
  mesh (weights follow the logical-axis rules, caches shard slots — or page
  pools — over the data axes and heads over the model axis, both jitted
  entry points trace under the engine's ``ParallelContext``).

**Paged serving** (``page_size=...``, DESIGN.md §6d): instead of one
monolithic ``(layers, slots, max_len, ...)`` allocation, the cache is a
shared page pool (serving/kv_cache.py) and each slot holds an int32 block
table.  The :class:`Scheduler` admits by **free-page budget** instead of
slot count — a request reserves only the pages its prompt + token budget
actually needs, so the same HBM serves strictly more concurrent requests —
and shares page-aligned prompt prefixes across requests through a
:class:`~repro.serving.kv_cache.PrefixCache` (copy-on-write: shared pages
are never written after registration).  Greedy decode is token-identical to
the dense engine; recurrent families (xlstm/zamba — O(1) SSD/LSTM state)
fall back to the dense slot-addressed cache.

With ``forms=True``/``spec=...`` the engine compresses the weights once
(``repro.forms.compress_tree``) and decodes directly on the compressed
pytree: uint8 magnitudes + int8 fragment signs through the polarized-matmul
kernel, no float fake-quant copy.  A stacked block group may come as a
layer stream (a list of callables, one per layer; ``repro.forms.tree``):
each layer is made and compressed in turn, so set-up holds one layer's
float weights at a time.

With ``speculate=True`` (paged families) the scheduler's decode round is
self-speculative (serving/speculate.py, DESIGN.md §6e): a low-bit draft
derived from the target's own weights drafts up to ``draft_k`` tokens and
the target verifies them all in ONE bounded multi-token forward, so a round
yields a VARIABLE 1..draft_k+1 tokens per slot — the per-slot timelines
advance by the runner-reported counts, never by an assumed fixed block.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import (ParallelContext, cache_shardings,
                                        parallel_context, params_shardings,
                                        reshard_state)
from repro.forms import (CompressReport, FormsSpec, compress_tree,
                         default_spec, sparsity_stats, stack_streams)
from repro.kernels.sparsity import SparsityMeter
from repro.models.registry import Model
from repro.serving import kv_cache as KV
from repro.serving import trace as T


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    # SLO fields, consumed by the fleet scheduler (serving/sched.py) and
    # ignored by the plain Scheduler: priority class ("interactive"/"batch";
    # "" = the fleet's default), a completion deadline relative to arrival,
    # and an open-loop arrival offset relative to run() start (the load
    # generator stamps these; 0.0 = available immediately).
    priority: str = ""
    deadline_ms: Optional[float] = None
    arrival_s: float = 0.0


@dataclasses.dataclass
class Result:
    uid: int
    tokens: List[int]
    prefill_ms: float = 0.0
    decode_ms: float = 0.0


_MIN_BUCKET = 8

# rotating-window cap on the scheduler's admission log (satellite of the
# fleet-scheduler PR: a sustained-load run() admits tens of thousands of
# requests; the log exists for tests/debugging, not as an unbounded history)
ADMISSION_LOG_WINDOW = 1024


class ModelRunner:
    """The jitted side of the engine: params + compiled prefill/decode.

    Owns nothing about admission or page bookkeeping — it executes one
    bulk prefill or one decode ROUND (here a ``decode_block``-token chunk;
    on the speculative subclass a draft+verify round with variable yield)
    on whatever cache (dense slot cache or
    :class:`~repro.serving.kv_cache.PagedKVCache`) it was built with,
    keeping donation, on-device sampling, the inner decode scan and the
    mesh path.
    """

    # host-side activation-sparsity accumulator; installed by the engine
    # (``ServingEngine(zero_skip_stats=True)``) *before the first trace* —
    # forms.apply stages one debug callback per matmul when it is set
    meter: Optional[SparsityMeter] = None

    def __init__(self, model: Model, params: Any, cache: Any, *,
                 max_len: int,
                 spec: Optional[FormsSpec] = None,
                 ctx: Optional[ParallelContext] = None,
                 decode_block: int = 4, donate: bool = True,
                 rng_seed: int = 0,
                 cache_shardings: Any = None,
                 tracer: Optional[T.Tracer] = None):
        self.model = model
        # spans (runner.prepare / dispatch / wait) and compile counts
        self.tracer = tracer if tracer is not None else T.Tracer()
        self.params = params
        self.cache = cache
        self.paged = isinstance(cache, KV.PagedKVCache)
        self.spec = spec
        self.ctx = ctx
        self.decode_block = max(1, int(decode_block))
        self.donate = donate
        self.cache_shardings = cache_shardings
        self.max_len = int(max_len)
        self._key = jax.random.PRNGKey(rng_seed)

        # the spec's backend/tiling hints bake into the traced hot-path fns
        # (repro.forms.default_spec is read at trace time by forms.apply);
        # the cache (argument 1) is DONATED — updates alias in place and the
        # caller must always rebind ``self.cache`` to the returned tree.
        # The paged signature only threads the extra block-table argument
        # into the model call — scan/sampling logic is shared (_decode_impl).
        if self.paged:
            def _decode_fn(p, c, toks, pos, tables, temps, key):
                return self._decode_impl(
                    p, c, toks, pos, temps, key,
                    lambda p_, t_, c_, pos_: model.decode_paged(
                        p_, t_, c_, pos_, tables))
        else:
            def _decode_fn(p, c, toks, pos, temps, key):
                return self._decode_impl(p, c, toks, pos, temps, key,
                                         model.decode_step)

        self._decode = jax.jit(_decode_fn,
                               donate_argnums=(1,) if donate else (),
                               **self._out_shardings_kw())
        self._prefill_fns: Dict[int, Any] = {}
        self._chunk_fns: Dict[int, Any] = {}
        self._called: set = set()           # (program, width) called so far
        # (program, width) -> its lowering, kept from its first call while
        # the tracer is on, and its compiled HLO text once asked for
        self._lowered: Dict[Tuple[str, int], Any] = {}
        self._hlo: Dict[Tuple[str, int], str] = {}

    def _decode_impl(self, p, c, toks, pos, temps, key, step):
        """The shared decode-block scan: ``decode_block`` model steps with
        on-device sampling; ``step(p, toks, cache, pos)`` is the dense or
        block-table-bound paged decode call."""
        with default_spec(self.spec), sparsity_stats(self.meter):
            def body(carry, _):
                tok, cache, pos, key = carry
                with T.device_counts() as counts:
                    logits, cache = step(p, tok[:, None], cache, pos)
                lg = logits[:, 0].astype(jnp.float32)
                key, sub = jax.random.split(key)
                nxt = _sample_on_device(lg, temps, sub)
                return (nxt, cache, pos + 1, key), (nxt, dict(counts))

            (_, c, _, _), (toks_out, counts) = jax.lax.scan(
                body, (toks, c, pos, key), None, length=self.decode_block)
        return toks_out, {n: v.sum() for n, v in counts.items()}, c

    @property
    def page_size(self) -> int:
        return self.cache.page_size

    def _out_shardings_kw(self) -> Dict[str, Any]:
        """Pin the jitted outputs' shardings on a mesh: the returned cache
        keeps the engine's NamedSharding layout (exact donation aliasing, and
        ``.sharding`` stays assertable across steps); sampled tokens and
        device counters come back replicated — the host reads them every
        block anyway."""
        if self.ctx is None:
            return {}
        from jax.sharding import NamedSharding, PartitionSpec
        replicated = NamedSharding(self.ctx.mesh, PartitionSpec())
        return {"out_shardings": (replicated, replicated,
                                  self.cache_shardings)}

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Padded-prefill bucket (power of two) to bound recompilation; the
        exact length for recurrent families, whose state consumes every
        token."""
        if not self.model.padded_prefill:
            return n
        b = _MIN_BUCKET
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _prefill_impl(self, p, toks, c, slot, length, temp, key, call):
        """Shared prefill tail: one bulk model call + on-device sampling of
        the first token; ``call`` is the dense or destination-page-bound
        paged prefill."""
        with default_spec(self.spec), T.device_counts() as counts:
            logits, c = call(p, toks, c, slot, length)
        lg = logits.reshape(1, -1).astype(jnp.float32)
        tok = _sample_on_device(lg, temp[None], key)
        return tok[0], dict(counts), c

    def _get_prefill(self, bucket: int):
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            if self.paged:
                def _prefill_fn(p, toks, c, pages, slot, length, temp, key):
                    return self._prefill_impl(
                        p, toks, c, slot, length, temp, key,
                        lambda p_, t_, c_, s_, n_: self.model.prefill_paged(
                            p_, t_, c_, pages, s_, n_))
            else:
                def _prefill_fn(p, toks, c, slot, length, temp, key):
                    return self._prefill_impl(p, toks, c, slot, length, temp,
                                              key, self.model.prefill)

            fn = jax.jit(_prefill_fn,
                         donate_argnums=(2,) if self.donate else (),
                         **self._out_shardings_kw())
            self._prefill_fns[bucket] = fn
        return fn

    def padded_prompt(self, prompt: np.ndarray) -> Tuple[np.ndarray, int]:
        """Normalize + bucket-pad a prompt to its (1, bucket) token buffer;
        returns ``(toks, n)``.  The ONE prompt-shaping rule — the
        speculative runner reuses it so the draft prefill always sees
        exactly the buffer the target prefill consumed."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = int(prompt.shape[0])
        if not 1 <= n < self.max_len:
            raise ValueError(
                f"prompt length {n} must be in [1, max_len={self.max_len})")
        toks = np.zeros((1, self.bucket_for(n)), np.int32)
        toks[0, :n] = prompt
        return toks, n

    def prefill_slot(self, slot: int, prompt: np.ndarray,
                     temperature: float = 0.0,
                     pages: Optional[np.ndarray] = None) -> int:
        """Admit a prompt into ``slot`` with one bulk-prefill call; returns
        the first sampled token.  The slot's timeline restarts at 0 and the
        next decode write position is ``len(prompt)``.  On a paged cache,
        ``pages`` is the int32 destination-page vector covering the bucket
        (scratch-0 entries skip prefix-shared pages)."""
        toks, n = self.padded_prompt(prompt)
        if self.paged and pages is None:
            raise ValueError("paged prefill needs a destination-page "
                             "vector (pages=...)")
        bucket = toks.shape[1]
        fn = self._get_prefill(bucket)
        with self.tracer.span("runner.prepare"):
            self._key, sub = jax.random.split(self._key)
            args = [self.params, jnp.asarray(toks), self.cache]
            if self.paged:
                args.append(jnp.asarray(pages, jnp.int32))
            args += [jnp.asarray(slot, jnp.int32), jnp.asarray(n, jnp.int32),
                     jnp.asarray(temperature, jnp.float32), sub]
        tok, counts, self.cache = self._dispatch("prefill", bucket, fn, args)
        with self.tracer.span("runner.wait"):
            tok = int(tok)
            self._count_device(counts)
            return tok

    def _dispatch(self, program: str, width: int, fn: Any, args: Any) -> Any:
        """Call one jitted program (the ``runner.dispatch`` span), and
        count a compilation of ``program`` at ``width`` in the counter
        ``runner.compiles.<program>.<width>`` when this is the program's
        first call or XLA compiled (or loaded from its cache) during it.

        The first call of a program while the tracer is on keeps its
        lowering (a lookup in JAX's caches once the program has run), from
        which :meth:`hlo_texts` reads the compiled program."""
        key = (program, width)
        first = key not in self._called
        self._called.add(key)
        seen = T.backend_compiles()
        # parallel_context makes the models' logical-axis ``constrain``
        # annotations live while a new program traces (no-op when ctx is
        # None)
        with parallel_context(self.ctx):
            if self.tracer.on and key not in self._lowered:
                self._lowered[key] = fn.lower(*args)
            with self.tracer.span("runner.dispatch", program=program,
                                  width=width):
                out = fn(*args)
        if first or T.backend_compiles() != seen:
            self.tracer.count(f"runner.compiles.{program}.{width}")
        return out

    def _count_device(self, counts: Dict[str, jax.Array]) -> None:
        """Add the device counters a program returned (``T.device_counts``)
        to the tracer's; called once its tokens are on the host."""
        for name, n in jax.device_get(counts).items():
            self.tracer.count(name, int(n))

    def _count_kv_steps(self, steps: int, t: int,
                        model: Optional[Model] = None) -> None:
        """Count ``steps`` paged model steps of ``t`` tokens by how they
        read K/V: ``decode.kv_in_place_steps`` (straight from the page
        pool, ``KV.reads_in_place``) or ``decode.kv_gathered_steps``
        (block-table gathers of per-slot views)."""
        model = model if model is not None else self.model
        in_place = model.paged_in_place and KV.reads_in_place(t, self.ctx)
        self.tracer.count("decode.kv_in_place_steps" if in_place
                          else "decode.kv_gathered_steps", steps)

    def hlo_texts(self) -> Dict[str, str]:
        """The compiled HLO text of each program called while the tracer
        was on, by ``"<program>.<width>"``.  A TPU profile names a device
        operation by its HLO instruction alone; the instructions'
        ``op_name`` metadata carries the named scopes."""
        for key, low in self._lowered.items():
            if key not in self._hlo:
                self._hlo[key] = low.compile().as_text()
        return {f"{p}.{w}": text for (p, w), text in self._hlo.items()}

    # ------------------------------------------------------------------
    # chunked (incremental) prefill — the fleet scheduler's admission path
    # ------------------------------------------------------------------

    def chunk_width(self, n: int) -> int:
        """Power-of-two chunk bucket (min ``_MIN_BUCKET``) so the fleet
        scheduler compiles one chunk variant per width, like prefill
        buckets.  Chunks never exceed ``max_len``."""
        b = _MIN_BUCKET
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _get_chunk(self, width: int):
        """The jitted chunked-prefill step at ``width`` padded columns.

        One bounded multi-token ``decode_paged`` call advances every
        prefilling slot by its granted chunk: token ``(b, t)`` lands at
        cache position ``pos[b] + t`` through the slot's block table
        (in-chunk causality falls out of decode attention's
        ``kpos <= pos`` mask — the same path the speculative verify
        already proves exact), and the sampled token at per-slot column
        ``cols[b]`` is the request's first generated token when the chunk
        reaches the prompt end (discarded otherwise).  Padded columns and
        non-prefilling slots commit into scratch-redirected/garbage rows
        that the padded-bucket invariant makes dead: every row is
        rewritten before any mask can admit its position.
        """
        fn = self._chunk_fns.get(width)
        if fn is None:
            def _chunk_fn(p, c, toks, pos, tables, cols, temps, key):
                with default_spec(self.spec), sparsity_stats(self.meter):
                    with T.device_counts() as counts:
                        logits, c = self.model.decode_paged(p, toks, c, pos,
                                                            tables)
                    lg = jnp.take_along_axis(
                        logits, cols[:, None, None],
                        axis=1)[:, 0].astype(jnp.float32)
                    tok = _sample_on_device(lg, temps, key)
                return tok, dict(counts), c

            fn = jax.jit(_chunk_fn,
                         donate_argnums=(1,) if self.donate else (),
                         **self._out_shardings_kw())
            self._chunk_fns[width] = fn
        return fn

    def prefill_chunk(self, tokens: np.ndarray, positions: np.ndarray,
                      block_tables: np.ndarray, cols: np.ndarray,
                      temps: np.ndarray) -> np.ndarray:
        """Advance chunked prefill for a batch of slots; returns the (B,)
        sampled tokens (valid only for slots whose chunk covers the last
        prompt position).  ``tokens``: (B, width) chunk rows starting at
        per-slot cache position ``positions[b]``; ``block_tables`` must
        zero the rows of slots not prefilling this call (their commits are
        then scratch-redirected).  Requires the paged cache — the fleet
        scheduler falls back to whole-prompt admission otherwise."""
        if not self.paged:
            raise ValueError("chunked prefill needs the paged cache "
                             "(page_size=...)")
        width = tokens.shape[1]
        fn = self._get_chunk(width)
        with self.tracer.span("runner.prepare"):
            self._key, sub = jax.random.split(self._key)
            args = (self.params, self.cache,
                    jnp.array(tokens, jnp.int32, copy=True),
                    jnp.array(positions, jnp.int32, copy=True),
                    jnp.array(block_tables, jnp.int32, copy=True),
                    jnp.array(cols, jnp.int32, copy=True),
                    jnp.array(temps, jnp.float32, copy=True), sub)
        tok, counts, self.cache = self._dispatch("chunk", width, fn, args)
        self._count_kv_steps(1, width)
        with self.tracer.span("runner.wait"):
            tok = np.asarray(tok)
            self._count_device(counts)
            return tok

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def decode_chunk(self, tokens: np.ndarray, positions: np.ndarray,
                     temps: np.ndarray,
                     block_tables: Optional[np.ndarray] = None) -> np.ndarray:
        """One donated, jitted dispatch of ``decode_block`` steps for all
        slots; returns the (decode_block, slots) sampled-token grid.  The
        single host sync of the steady-state loop.

        The host buffers are COPIED at the boundary (``jnp.array``, not
        ``asarray``): CPU transfers are zero-copy and dispatch is async, so
        handing the device a view of a numpy buffer the serving loop mutates
        right after is a read race (observed: decode steps seeing
        next-iteration positions).
        """
        if self.paged and block_tables is None:
            raise ValueError("paged decode needs block_tables")
        with self.tracer.span("runner.prepare"):
            self._key, sub = jax.random.split(self._key)
            args = [self.params, self.cache,
                    jnp.array(tokens, jnp.int32, copy=True),
                    jnp.array(positions, jnp.int32, copy=True)]
            if self.paged:
                args.append(jnp.array(block_tables, jnp.int32, copy=True))
            args += [jnp.array(temps, jnp.float32, copy=True), sub]
        toks_out, counts, self.cache = self._dispatch(
            "decode", self.decode_block, self._decode, args)
        if self.paged:
            self._count_kv_steps(self.decode_block, 1)
        with self.tracer.span("runner.wait"):
            toks_out = np.asarray(toks_out)
            self._count_device(counts)
            return toks_out

    def decode_round(self, tokens: np.ndarray, positions: np.ndarray,
                     temps: np.ndarray,
                     block_tables: Optional[np.ndarray] = None,
                     active: Optional[List[bool]] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """One scheduler round: ``(grid, counts)`` where ``grid`` is a
        (tokens_per_round, slots) token grid and ``counts[s]`` how many of
        slot ``s``'s rows are valid this round.

        The scheduler accounts per-slot timelines from ``counts`` — a round
        produces a FIXED ``decode_block`` tokens per slot here, but a
        variable 1..K+1 on the speculative runner (accepted drafts + the
        correction/bonus token), so nothing downstream may assume one token
        per step or a constant tokens-per-round.
        """
        del active   # every slot decodes the full block on the plain runner
        out = self.decode_chunk(tokens, positions, temps,
                                block_tables=block_tables)
        return out, np.full(out.shape[1], out.shape[0], np.int32)

    def reset_slot(self, slot: int) -> None:
        """Per-slot runner state reset on (re)admission — a no-op here; the
        speculative runner clears its adaptive-K state."""


class Scheduler:
    """The host side of the engine: admission, slot/page bookkeeping, and
    the continuous-batching loop driving a :class:`ModelRunner`.

    Dense mode (``allocator is None``) admits by free slot, exactly the
    monolithic-cache engine.  Paged mode admits by **free-page budget**: a
    request is admitted when a free decode slot exists AND the allocator can
    reserve ``ceil(min(max(bucket, prompt + max_new), max_len) / page_size)``
    pages (minus any prefix-shared ones) — pages are reserved up front, so a
    running request can never be preempted by pool exhaustion.  On finish
    the pages are released (refcount-aware for shared ones) and the freed
    budget immediately re-admits from the queue.
    """

    def __init__(self, runner: ModelRunner, *, slots: int, max_len: int,
                 allocator: Optional[KV.PageAllocator] = None,
                 prefix: Optional[KV.PrefixCache] = None,
                 health: Optional[Any] = None,
                 log_every: int = 0):
        self.runner = runner
        self.tracer = runner.tracer      # sched.* and request.* spans, counters
        self.slots = slots
        self.max_len = max_len
        self.allocator = allocator
        self.prefix = prefix
        self.health = health    # reliability.health.HealthMonitor (or None)
        self.paged = allocator is not None
        self.log_every = int(log_every)  # decode rounds between stat lines
        self.rounds = 0
        self.max_concurrent = 0          # peak simultaneously-active slots
        # rotating admission log: (uid, pages) of the most recent
        # ADMISSION_LOG_WINDOW admissions; older entries roll off and are
        # counted in ``admissions_dropped`` (stats()) instead of growing
        # without bound across a sustained-load run
        self.admissions: "collections.deque[Tuple[int, Tuple[int, ...]]]" = \
            collections.deque(maxlen=ADMISSION_LOG_WINDOW)
        self.admissions_dropped = 0
        self.last_shared = 0             # prefix pages of the last reservation
        if self.paged:
            ps = runner.page_size
            self.n_tables = KV.pages_for(max_len, ps)
            if allocator.capacity < self.n_tables:
                raise ValueError(
                    f"page pool too small: a max_len={max_len} request needs "
                    f"{self.n_tables} pages, pool holds {allocator.capacity} "
                    f"(+1 scratch)")
            self.block_tables = np.zeros((slots, self.n_tables), np.int32)
            self.slot_pages: List[List[int]] = [[] for _ in range(slots)]

    # ------------------------------------------------------------------
    # paged admission
    # ------------------------------------------------------------------

    def _reserve_pages(self, uid: int, slot: int, prompt: np.ndarray,
                       max_new: int, *, shared_cap: Optional[int] = None,
                       rows: Optional[int] = None) -> Optional[np.ndarray]:
        """Reserve every page the request can touch (prefill bucket +
        decode budget, capped at max_len); returns the prefill
        destination-page vector, or None if the free-page budget blocks.
        Prefix-shared pages are refcounted instead of allocated, and their
        prefill destinations are redirected to scratch so the shared
        contents are never rewritten.

        ``shared_cap`` bounds how many prefix pages may be shared (the
        fleet scheduler's chunked admission SKIPS shared positions instead
        of recomputing into scratch, so it must keep the last prompt token
        on an owned page); ``rows`` overrides the reserved-row count (the
        chunked path never writes a whole prefill bucket, so it reserves
        exactly ``prompt + max_new`` rows).  ``self.last_shared`` reports
        the shared-page count of this reservation."""
        ps = self.runner.page_size
        n = len(prompt)
        bucket = self.runner.bucket_for(n)
        if rows is None:
            rows = min(max(bucket, n + max_new), self.max_len)
        need = KV.pages_for(rows, ps)
        shared = self.prefix.match(prompt) if self.prefix is not None else []
        if shared_cap is not None:
            shared = shared[:shared_cap]
        own = self.allocator.alloc(need - len(shared))
        if own is None:
            return None
        self.allocator.share(shared)
        pages = shared + own
        self.last_shared = len(shared)
        self.slot_pages[slot] = pages
        self.block_tables[slot] = 0
        self.block_tables[slot, :need] = pages
        if len(self.admissions) == self.admissions.maxlen:
            self.admissions_dropped += 1
        self.admissions.append((uid, tuple(pages)))
        n_bucket_pages = min(KV.pages_for(bucket, ps), need)
        return np.asarray(
            [KV.SCRATCH_PAGE if j < len(shared) else pages[j]
             for j in range(n_bucket_pages)], np.int32)

    def _probe_health(self) -> None:
        with self.tracer.span("health.probe", round=self.rounds):
            self.health.tick(self.runner, self.rounds)

    def _release_slot(self, slot: int) -> None:
        if not self.paged:
            return
        freed = self.allocator.release(self.slot_pages[slot])
        if self.prefix is not None:
            self.prefix.evict(freed)
        self.slot_pages[slot] = []
        self.block_tables[slot] = 0   # idle slots read/write scratch only

    # ------------------------------------------------------------------
    # serving loop
    # ------------------------------------------------------------------

    def run(self, requests: List[Request]) -> List[Result]:
        """Serve a list of requests with continuous batching over slots."""
        tr = self.tracer
        queue = list(requests)
        active: List[Optional[Tuple[Request, Result]]] = [None] * self.slots
        done: List[Result] = []
        cur = np.zeros(self.slots, np.int32)        # current token per slot
        slot_pos = np.zeros(self.slots, np.int32)   # next cache write position
        temps = np.zeros(self.slots, np.float32)
        since = [0.0] * self.slots       # start of each slot's decode span
        t_start = time.perf_counter()

        def admit(slot: int) -> None:
            """Admit queued requests into ``slot`` until one survives its
            prefill (a request whose budget is exhausted by the prefill
            token completes immediately and the loop drains the next one —
            iteratively, so a long queue of 1-token requests can't blow the
            stack).  In paged mode a request that doesn't fit the free-page
            budget stays at the head of the queue (admission blocks until a
            finishing request frees pages; up-front reservation guarantees
            it eventually fits)."""
            while queue:
                req = queue[0]
                # oversized prompts keep their most recent context-window
                # worth of tokens (leaving room to generate) instead of
                # aborting the whole run
                prompt = np.asarray(req.prompt, np.int32).reshape(-1)
                if prompt.shape[0] >= self.max_len:
                    prompt = prompt[-(self.max_len - 1):]
                pages = None
                if self.paged:
                    pages = self._reserve_pages(req.uid, slot, prompt,
                                                req.max_new_tokens)
                    if pages is None:
                        if not any(a is not None for a in active):
                            raise RuntimeError(
                                "page pool exhausted with no request in "
                                "flight — pool sizing bug")
                        return
                queue.pop(0)
                res = Result(uid=req.uid, tokens=[])
                n_prompt = int(prompt.shape[0])
                first, sp = self._bulk_prefill(slot, prompt, req.temperature,
                                               pages)
                res.prefill_ms = sp.seconds * 1e3
                tr.record("request.queue", t_start, sp.start, uid=req.uid)
                tr.record("request.prefill", sp.start, sp.end, uid=req.uid)
                res.tokens.append(first)
                if (len(res.tokens) >= req.max_new_tokens
                        or n_prompt >= self.max_len - 1):
                    self._release_slot(slot)
                    done.append(res)
                    tr.record("request.decode", sp.end, sp.end, uid=req.uid,
                              tokens=len(res.tokens), preemptions=0)
                    continue
                since[slot] = sp.end
                if self.paged and self.prefix is not None:
                    self.prefix.register(prompt, self.slot_pages[slot])
                cur[slot] = first
                slot_pos[slot] = n_prompt
                temps[slot] = req.temperature
                active[slot] = (req, res)
                self.runner.reset_slot(slot)
                self.max_concurrent = max(
                    self.max_concurrent,
                    sum(a is not None for a in active))
                return

        def finish(slot: int) -> None:
            res = active[slot][1]
            done.append(res)
            tr.record("request.decode", since[slot], time.perf_counter(),
                      uid=res.uid, tokens=len(res.tokens), preemptions=0)
            active[slot] = None
            temps[slot] = 0.0
            self._release_slot(slot)
            admit(slot)

        def admit_idle() -> None:
            """Retry admission into every idle slot (a finish elsewhere may
            have freed the pages a blocked head-of-queue request needed).
            Stops at the first slot that leaves the queue head in place —
            the head is page-blocked, and further idle slots face the same
            allocator state."""
            for s in range(self.slots):
                if not queue:
                    return
                if active[s] is None:
                    head = queue[0]
                    admit(s)
                    if queue and queue[0] is head and active[s] is None:
                        return

        # health pass BEFORE any prefill: faults injected while the engine
        # sat idle are repaired before they can poison KV pages, so a
        # repaired run is greedy-identical to a clean one end to end
        if self.health is not None:
            self._probe_health()
        with tr.span("sched.admit"):
            admit_idle()

        while any(a is not None for a in active):
            # snapshot the attribution denominator BEFORE the loop body
            # mutates ``active`` (finished slots must still pay their share
            # of the round they took part in)
            n_active = sum(a is not None for a in active)
            with tr.span("sched.round", round=self.rounds,
                         decoding=n_active, prefilling=0,
                         queued=len(queue)):
                self._plain_round(active, cur, slot_pos, temps, n_active,
                                  finish)
                # periodic health pass between rounds: in-flight requests
                # keep their slots, pages and positions across a repair —
                # only the runner's params binding changes (same
                # shapes/shardings, no retrace), so nothing is dropped
                if (self.health is not None
                        and self.health.config.probe_every
                        and self.rounds % self.health.config.probe_every
                        == 0):
                    self._probe_health()
                self._log_round(sum(a is not None for a in active))
                with tr.span("sched.admit"):
                    admit_idle()
        return done

    def _plain_round(self, active, cur, slot_pos, temps, n_active: int,
                     finish) -> None:
        """One decode round of :meth:`run` and its token bookkeeping."""
        tr = self.tracer
        act = [a is not None for a in active]
        # a round yields a VARIABLE number of tokens per slot: a fixed
        # decode_block on the plain runner, 1 + accepted drafts on the
        # speculative runner — counts[s] is the only source of truth
        with tr.timed("runner.decode", rows=n_active,
                      steps=self.runner.decode_block) as sp:
            out, counts = self.runner.decode_round(
                cur, slot_pos, temps,
                block_tables=self.block_tables if self.paged else None,
                active=act)
        dt = sp.seconds * 1e3
        self.rounds += 1
        kept = 0
        with tr.span("sched.emit"):
            for s in range(self.slots):
                a = active[s]
                if a is None:
                    continue
                req, res = a
                res.decode_ms += dt / max(1, n_active)
                # tokens this slot can still accept: request budget and the
                # slot's remaining cache length
                budget = min(req.max_new_tokens - len(res.tokens),
                             self.max_len - 1 - int(slot_pos[s]))
                take = min(int(counts[s]), budget)
                res.tokens.extend(int(t) for t in out[:take, s])
                kept += take
                if take >= budget:
                    finish(s)      # may re-admit into this slot
                else:
                    # the write cursor advances by the tokens actually kept
                    # (a speculative round already rolled back past
                    # counts[s]; rows beyond it are dead by the masks)
                    cur[s] = out[counts[s] - 1, s]
                    slot_pos[s] += int(counts[s])
        self._count_decode(out.size, kept)

    def _count_decode(self, rows: int, kept: int) -> None:
        """Decode counters: a round, its (steps x slots) rows dispatched
        and the tokens the requests kept."""
        tr = self.tracer
        tr.count("decode.rounds")
        tr.count("decode.rows", rows)
        tr.count("decode.tokens", kept)

    def _bulk_prefill(self, slot: int, prompt: np.ndarray,
                      temperature: float, pages: Optional[np.ndarray]
                      ) -> Tuple[int, T.Span]:
        """One whole-prompt prefill call, timed by its ``runner.prefill``
        span and counted; returns the first token and the span."""
        n = int(prompt.shape[0])
        width = self.runner.bucket_for(n)
        with self.tracer.timed("runner.prefill", rows=1, width=width) as sp:
            first = self.runner.prefill_slot(slot, prompt, temperature,
                                             pages=pages)
        self._count_prefill(n, width)
        return first, sp

    def _count_prefill(self, tokens: int, rows: int) -> None:
        """Prefill counters: a call, the prompt tokens it computed and the
        (rows x width) positions it dispatched."""
        tr = self.tracer
        tr.count("prefill.calls")
        tr.count("prefill.tokens", tokens)
        tr.count("prefill.rows", rows)

    def _log_round(self, n_active: int) -> None:
        """The serve CLI's periodic stat line (``log_every`` rounds)."""
        if not self.log_every or self.rounds % self.log_every:
            return
        parts = [f"round {self.rounds}", f"active {n_active}/{self.slots}"]
        if self.allocator is not None:
            st = self.allocator.stats()
            parts.append(f"pages {st['used']}/{st['capacity']} "
                         f"(hw {st['high_water']}, shared {st['shared']})")
        if self.prefix is not None:
            parts.append(f"prefix_hits {self.prefix.hits}")
        if hasattr(self.runner, "spec_stats"):
            sp = self.runner.spec_stats()
            parts.append(f"accept {sp['acceptance']:.2f} "
                         f"tok/round {sp['tokens_per_round']:.2f}")
        if self.health is not None:
            parts.append(f"drift {self.health.last_drift:.2e} "
                         f"repairs {self.health.repairs}")
        print("[serve] " + ", ".join(parts), flush=True)


class ServingEngine:
    """Continuous-batching engine facade: compression + sharding setup, a
    :class:`ModelRunner` for the jitted hot path, and a :class:`Scheduler`
    for admission.  ``page_size=...`` turns on the paged KV cache for the
    attention families (recurrent families fall back to the dense slot
    cache); ``prefix_cache=True`` additionally shares page-aligned prompt
    prefixes across concurrent requests; ``speculate=True`` serves with
    self-speculative decoding — a low-bit draft derived from the target's
    own weights drafts ``draft_k`` tokens per round and the target verifies
    them in one bounded multi-token forward (paged families only;
    DESIGN.md §6e).  Greedy speculative output is token-identical to plain
    decoding (MoE included: serving routes without drops, so a token's
    experts do not depend on how many tokens a step carries).

    ``plan={path: FormsSpec}`` serves a *heterogeneous* compressed tree:
    per-leaf spec overrides (bit-widths, fragment geometry) resolved by
    ``forms.spec_for_path`` on top of the engine spec —
    ``forms.autobits.plan_auto_bits`` derives one from a sensitivity sweep
    (``serve --auto-bits``).  ``draft_plan`` does the same for the
    speculative draft's quantization (``plan_draft_bits``).

    ``health=HealthConfig(...)`` (compressed trees only) arms the
    reliability loop of DESIGN.md §6f: golden-probe drift detection every
    ``probe_every`` rounds plus automatic re-encoding of corrupted leaves
    from the build-time reference copy — fault-tolerant serving that never
    drops in-flight requests.  ``engine.inject_faults(FaultModel(...))``
    corrupts the live params for experiments; ``stats()["health"]`` is the
    scoreboard.

    ``engine.tracer`` (serving/trace.py) records the serving loop's spans
    while an in-process profiler session runs, and always after
    ``engine.tracer.enable()``.  Its counters are always
    on; ``stats()["counters"]`` and ``stats()["trace"]`` report them."""

    def __init__(self, model: Model, params: Any, *, max_len: int = 512,
                 batch_slots: int = 8, forms: bool = False,
                 spec: Optional[FormsSpec] = None,
                 plan: Optional[Dict[str, FormsSpec]] = None,
                 fragment: int = 8, bits: int = 8, rng_seed: int = 0,
                 decode_block: int = 4, donate: bool = True,
                 mesh: Optional[Any] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefix_cache: bool = False,
                 speculate: bool = False,
                 draft_k: int = 4, draft_bits: int = 4,
                 draft_mode: str = "forms",
                 draft_plan: Optional[Dict[str, FormsSpec]] = None,
                 draft_fragment: Optional[int] = None,
                 draft_layer_step: int = 1,
                 adaptive_k: bool = True,
                 health: Optional[Any] = None,
                 stats_every: int = 0,
                 zero_skip: Optional[str] = None,
                 zero_skip_keep: float = 0.5,
                 zero_skip_stats: bool = False,
                 slo: Optional[Any] = None):
        self.model = model
        self.cfg = model.config
        self.ctx: Optional[ParallelContext] = (
            ParallelContext.for_mesh(mesh) if mesh is not None else None)
        self.spec: Optional[FormsSpec] = None
        self.compression_report: Optional[CompressReport] = None
        self.compression_errors: Dict[str, float] = {}
        if ((zero_skip not in (None, "off")) or zero_skip_stats) \
                and not (forms or spec is not None):
            raise ValueError(
                "zero_skip / zero_skip_stats act on the FORMS matmul path — "
                "enable compression too (forms=True, spec=..., or serve "
                "--forms)")
        if plan is not None and not (forms or spec is not None):
            raise ValueError(
                "plan= is a per-leaf override map over the engine's FORMS "
                "spec — enable compression too (forms=True, spec=..., or "
                "serve --forms)")
        if forms or spec is not None:
            self.spec = spec if spec is not None else FormsSpec(m=fragment,
                                                                bits=bits)
            if zero_skip is not None:
                # folded into the spec BEFORE compression/tracing so every
                # forms matmul in the jitted hot path picks the skip route
                self.spec = dataclasses.replace(
                    self.spec, zero_skip=zero_skip,
                    zero_skip_keep=zero_skip_keep)
            params, self.compression_report = compress_tree(
                params, self.spec, ctx=self.ctx, plan=plan)
            self.compression_errors = self.compression_report.errors
        else:
            params = stack_streams(params)
        self.max_len = max_len
        self.slots = batch_slots
        self.donate = donate

        self.paged = bool(page_size) and model.supports_paged
        self.page_size = int(page_size) if self.paged else None
        if slo is not None and not self.paged:
            raise ValueError(
                "slo= (the SLO-aware fleet scheduler) schedules pages: "
                "chunked prefill and preemption-by-page-eviction need the "
                "paged KV cache — pass page_size=... and an attention "
                "family (recurrent families have no paged path)")
        # speculation needs the bounded multi-token paged verify; recurrent
        # families (and page_size=0) fall back to the plain engine, like the
        # paged-cache fallback itself
        self.speculative = bool(speculate) and self.paged
        tracer = T.Tracer()
        allocator = prefix = None
        if self.paged:
            per_slot = KV.pages_for(max_len, self.page_size)
            if num_pages is None:
                # default budget: every slot can still hold a full max_len
                # request (+1 scratch page) — no admission regression, the
                # win comes from shorter requests leaving pages free.  On a
                # mesh, round up to the data-axis size so the page dim
                # shards instead of hitting the divisibility fallback.
                num_pages = batch_slots * per_slot + 1
                if self.ctx is not None:
                    d = max(1, self.ctx.axis_size("batch"))
                    num_pages = -(-num_pages // d) * d
            allocator = KV.PageAllocator(num_pages)
            prefix = (KV.PrefixCache(self.page_size) if prefix_cache
                      else None)
            cache = model.init_paged_cache(num_pages, self.page_size,
                                           batch_slots, max_len)
        else:
            cache = model.init_cache(batch_slots, max_len)

        self.param_shardings = None
        self.cache_shardings = None
        if self.ctx is not None:
            # weights: tensor-parallel over the model axis, replicated over
            # data (fsdp=False — a ZeRO all-gather per decode step would sit
            # on the latency path); caches: slots/pages over data, heads
            # over model.  The checkpoint path can restore straight into
            # this layout via checkpoint.restore(...,
            # shardings=engine.param_shardings).
            self.param_shardings = params_shardings(params, self.ctx,
                                                    fsdp=False)
            params = reshard_state(params, self.param_shardings)
            self.cache_shardings = cache_shardings(cache, self.ctx)
            cache = reshard_state(cache, self.cache_shardings)

        self.draft_report: Optional[CompressReport] = None
        self.draft_cache_shardings = None
        if self.speculative:
            from repro.serving import speculate as SP
            spec_cfg = SP.SpeculateConfig(
                k=draft_k, bits=draft_bits, mode=draft_mode,
                fragment=(draft_fragment if draft_fragment is not None
                          else (self.spec.m if self.spec is not None
                                else None)),
                layer_step=draft_layer_step, adaptive=adaptive_k)
            # the draft derives from what the target actually serves (the
            # float projection of the compressed tree when forms is on)
            draft_model, draft_params, self.draft_report = SP.make_draft(
                model, params, spec_cfg,
                ctx=self.ctx if draft_mode == "forms" else None,
                plan=draft_plan)
            draft_cache = draft_model.init_paged_cache(
                num_pages, self.page_size, batch_slots, max_len)
            if self.ctx is not None:
                dsh = params_shardings(draft_params, self.ctx, fsdp=False)
                draft_params = reshard_state(draft_params, dsh)
                self.draft_cache_shardings = cache_shardings(draft_cache,
                                                             self.ctx)
                draft_cache = reshard_state(draft_cache,
                                            self.draft_cache_shardings)
            self.runner: ModelRunner = SP.SpeculativeRunner(
                model, params, cache,
                draft_model=draft_model, draft_params=draft_params,
                draft_cache=draft_cache, spec_cfg=spec_cfg,
                draft_cache_shardings=self.draft_cache_shardings,
                max_len=max_len, spec=self.spec, ctx=self.ctx,
                decode_block=decode_block, donate=donate, rng_seed=rng_seed,
                cache_shardings=self.cache_shardings, tracer=tracer)
        else:
            self.runner = ModelRunner(model, params, cache, max_len=max_len,
                                      spec=self.spec,
                                      ctx=self.ctx, decode_block=decode_block,
                                      donate=donate, rng_seed=rng_seed,
                                      cache_shardings=self.cache_shardings,
                                      tracer=tracer)
        # install the sparsity meter before the first decode trace (the
        # debug callbacks bake into the traced fn); off by default because
        # each forms matmul then costs one host round-trip per decode step
        self.sparsity_meter: Optional[SparsityMeter] = None
        if zero_skip_stats:
            self.sparsity_meter = SparsityMeter()
            self.runner.meter = self.sparsity_meter
        # the health monitor is built LAST, over the exact tree the runner
        # serves (post-compression, post-mesh-placement) — its golden
        # logits and reference planes describe the real serving artifact
        self.health = None
        if health is not None:
            from repro.reliability.health import HealthMonitor
            self.health = HealthMonitor(model, self.runner.params, health,
                                        spec=self.spec, ctx=self.ctx)
        if slo is not None:
            from repro.serving.sched import FleetScheduler, SLOConfig
            if isinstance(slo, dict):
                slo = SLOConfig(**slo)
            self.scheduler: Scheduler = FleetScheduler(
                self.runner, slots=batch_slots, max_len=max_len,
                allocator=allocator, prefix=prefix, health=self.health,
                log_every=stats_every, cfg=slo)
        else:
            self.scheduler = Scheduler(self.runner, slots=batch_slots,
                                       max_len=max_len, allocator=allocator,
                                       prefix=prefix, health=self.health,
                                       log_every=stats_every)

    # --- delegation (the engine surface tests/benches/launchers consume) ---

    @property
    def params(self) -> Any:
        return self.runner.params

    @property
    def cache(self) -> Any:
        return self.runner.cache

    @cache.setter
    def cache(self, value: Any) -> None:
        self.runner.cache = value

    @property
    def decode_block(self) -> int:
        return self.runner.decode_block

    @property
    def tracer(self) -> T.Tracer:
        return self.runner.tracer

    @property
    def page_allocator(self) -> Optional[KV.PageAllocator]:
        return self.scheduler.allocator

    @property
    def prefix_cache(self) -> Optional[KV.PrefixCache]:
        return self.scheduler.prefix

    def cache_bytes(self) -> int:
        """Persistent HBM footprint of the serving cache(s) — the draft
        pool included when speculation is on (it is real HBM)."""
        leaves = jax.tree_util.tree_leaves(self.runner.cache)
        if self.speculative:
            leaves += jax.tree_util.tree_leaves(self.runner.draft_cache)
        return sum(leaf.nbytes for leaf in leaves)

    def stats(self) -> Dict[str, Any]:
        """Serving counters: scheduler occupancy, page-pool occupancy
        (free/used/shared/high-water), prefix-cache hits, with speculation
        on acceptance-rate/tokens-per-round, and with the fleet scheduler
        the ``"slo"`` block (TTFT/inter-token percentiles, preemption and
        deadline-miss counts, queue depths per class); always the tracer's
        ``"counters"``, and its ``"trace"`` (spans, dropped, and the
        runner's ``hlo_texts()``) once it is on or has recorded a span.

        The returned dict is a DEEP-COPIED snapshot: the health/sparsity/
        SLO sub-dicts are mutated by the serving loop, and a caller polling
        mid-run (the load generator does) must never observe partial
        mutation or have its snapshot change under it."""
        out: Dict[str, Any] = {
            "max_concurrent": self.scheduler.max_concurrent,
            "rounds": self.scheduler.rounds,
            "admissions_dropped": self.scheduler.admissions_dropped,
        }
        if self.page_allocator is not None:
            out["pages"] = self.page_allocator.stats()
        if self.prefix_cache is not None:
            out["prefix_hits"] = self.prefix_cache.hits
        if hasattr(self.runner, "spec_stats"):
            out["speculate"] = self.runner.spec_stats()
        if self.health is not None:
            out["health"] = self.health.stats()
        if self.sparsity_meter is not None:
            out["sparsity"] = self.sparsity_meter.summary()
        if hasattr(self.scheduler, "slo_stats"):
            out["slo"] = self.scheduler.slo_stats()
        out = copy.deepcopy(out)
        out.update(self.tracer.stats())     # fresh objects, no copy needed
        if "trace" in out:
            out["trace"]["hlo"] = self.runner.hlo_texts()
        return out

    def inject_faults(self, fault: Any, paths: Optional[List[str]] = None
                      ) -> Any:
        """Corrupt the LIVE serving params with ``fault`` (a
        ``reliability.faults.FaultModel``); returns the ``FaultReport``.

        The health monitor's golden/reference copies were captured at
        build, before any injection — so a subsequent probe sees exactly
        the drift this corruption causes, and repair restores the clean
        tree.  Rebinding ``runner.params`` never retraces (same shapes,
        dtypes and shardings; params are not donated).
        """
        from repro.reliability.faults import inject_tree
        self.runner.params, report = inject_tree(
            self.runner.params, fault, spec=self.spec, paths=paths)
        return report

    def prefill_slot(self, slot: int, prompt: np.ndarray,
                     temperature: float = 0.0,
                     pages: Optional[np.ndarray] = None) -> int:
        return self.runner.prefill_slot(slot, prompt, temperature,
                                        pages=pages)

    def decode_chunk(self, tokens: np.ndarray, positions: np.ndarray,
                     temps: np.ndarray,
                     block_tables: Optional[np.ndarray] = None) -> np.ndarray:
        if self.paged and block_tables is None:
            block_tables = self.scheduler.block_tables
        return self.runner.decode_chunk(tokens, positions, temps,
                                        block_tables=block_tables)

    def run(self, requests: List[Request]) -> List[Result]:
        return self.scheduler.run(requests)


def _sample_on_device(logits: jax.Array, temps: jax.Array,
                      key: jax.Array) -> jax.Array:
    """Greedy/temperature sampling inside the jitted step.

    logits: (B, V) f32; temps: (B,) — rows with temp <= 0 take the argmax,
    others sample from softmax(logits / temp) via ``jax.random.categorical``.
    """
    with jax.named_scope("sampling"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
        return jnp.where(temps > 0.0, sampled, greedy)
