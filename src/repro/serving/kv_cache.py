"""Paged KV cache: a fine-grained page pool + host-side page bookkeeping.

The dense serving cache allocates ``(layers, slots, max_len, ...)`` — every
slot pays ``max_len`` HBM rows regardless of how many tokens it actually
holds, so the cache (not compute) caps concurrency.  This module rebuilds the
cache the way FORMS rebuilds the crossbar (PAPER.md §IV, DESIGN.md §6d):
instead of one monolithic allocation per slot, the sequence dim is cut into
fixed-size **pages** drawn from a shared pool, and each slot owns an int32
**block table** mapping its logical page index to a physical page id.

Device side (jit-safe, donated):

* :class:`PagedKVCache` — a registered-dataclass pytree holding the page
  pools (``(layers, num_pages, page_size, ...)`` per cache leaf) plus any
  leaves that stay slot-addressed (e.g. whisper's encoder output).
* :func:`gather_views` — block-table gather producing the per-slot
  contiguous ``(layers, slots, cap, ...)`` views multi-token decode
  attention consumes; masks then derive from per-slot lengths exactly as
  on the dense cache.  Single-token decode of the dense family reads the
  pool in place instead (:func:`reads_in_place`,
  ``kernels/paged_attention.py``).
* :func:`commit_token` / :func:`commit_pages` — the decode-step scatter of
  one token row into its page, and the bulk-prefill one-shot write of whole
  pages.

Host side (plain Python, drives the scheduler):

* :class:`PageAllocator` — free list + refcounts over the pool.  Page 0 is
  the reserved **scratch page**: writes that must go nowhere (idle slots,
  positions past a slot's budget, shared prefix pages that must not be
  overwritten) are redirected to it and its contents are never read.
* :class:`PrefixCache` — maps page-aligned prompt prefixes to live page
  ids so requests sharing a prompt prefix share physical pages
  (copy-on-write is implicit: a sharer's first write lands at a position
  past the shared prefix, i.e. always on a page it owns).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SCRATCH_PAGE = 0


@dataclasses.dataclass
class PagedKVCache:
    """Page-pool serving cache (a jax pytree; ``page_size`` is static).

    ``pool`` leaves are ``(layers, num_pages, page_size, ...)`` — the paged
    counterparts of the dense cache's ``(layers, slots, max_len, ...)``
    leaves.  ``dense`` holds the leaves that stay slot-addressed (whisper's
    ``enc_out``; empty for the other attention families).  Block tables and
    lengths live on the host (the scheduler) and enter jitted functions as
    ordinary int32 arguments, so page allocation never retraces.
    """

    pool: Dict[str, jax.Array]
    dense: Dict[str, jax.Array]
    page_size: int

    @property
    def num_pages(self) -> int:
        return next(iter(self.pool.values())).shape[1]


jax.tree_util.register_dataclass(PagedKVCache,
                                 data_fields=("pool", "dense"),
                                 meta_fields=("page_size",))


def pages_for(rows: int, page_size: int) -> int:
    """Number of pages covering ``rows`` cache rows."""
    return -(-rows // page_size)


def gather_views(cache: PagedKVCache, block_tables: jax.Array
                 ) -> Dict[str, jax.Array]:
    """Per-slot contiguous views of the pool via the block tables.

    ``block_tables``: (slots, n_tables) int32 physical page ids (scratch-0
    for unallocated entries).  Returns ``(layers, slots, n_tables *
    page_size, ...)`` views — logically identical to the dense cache's
    ``(L, B, max_len, ...)`` leaves, so decode attention (and its
    ``kpos <= pos`` per-slot length masks) runs unchanged on them.
    Unallocated entries alias the scratch page; their logical positions are
    always past the slot's length, so the masks never admit them.
    """
    b, n = block_tables.shape
    out = {}
    with jax.named_scope("kv_gather"):
        for name, pool in cache.pool.items():
            v = pool[:, block_tables]               # (L, B, n, ps, ...)
            out[name] = v.reshape(v.shape[0], b, n * cache.page_size,
                                  *v.shape[4:])
    return out


def reads_in_place(t: int, ctx=None) -> bool:
    """Whether a T-token paged decode step reads K/V from the pool in place
    (``kernels/paged_attention.py``) instead of :func:`gather_views`: a
    single-token step on a pool held by one device.  ``ctx`` is the active
    ``distributed.sharding.ParallelContext``; on a mesh the pool is sharded
    and the kernel is not partitioned, so those steps gather, as do
    multi-token steps (chunked prefill, speculative verify)."""
    return t == 1 and (ctx is None or ctx.mesh.size == 1)


def resolve_pages(block_tables: jax.Array, grid: jax.Array, page_size: int,
                  select: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Resolve a (slots, T) position grid to (page, offset) scatter grids.

    The ONE place the page-addressing rule lives: positions past the block
    table — a slot that exhausted its budget mid decode-block, rejected
    speculative drafts at the edge of a slot's reservation — are redirected
    to the scratch page instead of being clamped onto a live page.
    ``select`` (bool, same shape as ``grid``) additionally scratch-redirects
    de-selected positions (the rollback scrub's "touch only rejected rows").
    """
    n_tables = block_tables.shape[1]
    bidx = jnp.arange(grid.shape[0], dtype=jnp.int32)[:, None]
    pidx = grid // page_size
    live = pidx < n_tables
    if select is not None:
        live = jnp.logical_and(live, select)
    page = jnp.where(live,
                     block_tables[bidx, jnp.minimum(pidx, n_tables - 1)],
                     SCRATCH_PAGE)
    return page, grid % page_size


def commit_tokens(cache: PagedKVCache, toks: Dict[str, jax.Array],
                  block_tables: jax.Array, pos: jax.Array) -> PagedKVCache:
    """Scatter each slot's T new-token rows into their pages (one scatter
    per leaf).

    ``toks``: per-leaf ``(layers, slots, T, ...)`` new-token rows; ``pos``:
    (slots,) start positions (row t lands at ``pos + t``) or an explicit
    (slots, T) position grid.  Out-of-table positions land in scratch
    (:func:`resolve_pages`).
    """
    t = next(iter(toks.values())).shape[2]
    with jax.named_scope("kv_commit"):
        pos = jnp.asarray(pos, jnp.int32)
        grid = (pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
                if pos.ndim == 1 else pos)
        page, off = resolve_pages(block_tables, grid, cache.page_size)
        pool = {name: cache.pool[name].at[:, page, off].set(
            tok.astype(cache.pool[name].dtype))
            for name, tok in toks.items()}
    return dataclasses.replace(cache, pool=pool)


def commit_token(cache: PagedKVCache, toks: Dict[str, jax.Array],
                 block_tables: jax.Array, pos: jax.Array) -> PagedKVCache:
    """Scatter each slot's single new-token row into its current page.

    ``toks``: per-leaf ``(layers, slots, ...)`` new-token rows; ``pos``:
    (slots,) write positions.  The T=1 view of :func:`commit_tokens`.
    """
    return commit_tokens(cache, {n: v[:, :, None] for n, v in toks.items()},
                         block_tables, jnp.asarray(pos, jnp.int32)[:, None])


def rollback_tokens(cache: PagedKVCache, block_tables: jax.Array,
                    pos: jax.Array, keep: jax.Array, t: int) -> PagedKVCache:
    """Scrub a tentative multi-token commit back to ``keep`` rows per slot.

    After a speculative round commits ``t`` rows at ``pos .. pos+t-1``
    (commit_tokens) and verification accepts only ``keep[b]`` of them, the
    rejected rows ``pos+keep .. pos+t-1`` release their page slots: they
    are zeroed here so the page rows hold no stale draft K/V.  This is the
    belt-and-braces form of the rollback protocol — the positional
    rollback alone (the scheduler rewinding its write cursor to
    ``pos + keep``) is already sound, because every decode mask admits only
    ``kpos <= pos`` rows and every row is rewritten before its position can
    enter a mask (DESIGN.md §6e).  Kept rows (and, via the scratch
    redirect, rows of other slots) are untouched: the zero-write for a
    kept position is redirected to the scratch page.
    """
    pool = {}
    with jax.named_scope("kv_commit"):
        pos = jnp.asarray(pos, jnp.int32)
        keep = jnp.asarray(keep, jnp.int32)
        offs = jnp.arange(t, dtype=jnp.int32)[None, :]
        page, off = resolve_pages(block_tables, pos[:, None] + offs,
                                  cache.page_size,
                                  select=offs >= keep[:, None])
        for name, arr in cache.pool.items():
            zeros = jnp.zeros(arr.shape[:1] + page.shape + arr.shape[3:],
                              arr.dtype)
            pool[name] = arr.at[:, page, off].set(zeros)
    return dataclasses.replace(cache, pool=pool)


def commit_pages(cache: PagedKVCache, leaves: Dict[str, jax.Array],
                 pages: jax.Array) -> PagedKVCache:
    """Bulk-prefill one-shot page write of a whole prompt.

    ``leaves``: per-leaf ``(layers, 1, S, ...)`` full-prompt rows (the
    prefill's collected K/V or MLA latents); ``pages``: ``(ceil(S /
    page_size),)`` int32 destination page ids.  Rows are padded to whole
    pages (padded rows sit past the slot's length, masked exactly like the
    dense engine's padded-bucket rows) and written with ONE scatter per
    leaf.  Prefix-shared pages are protected by passing scratch-0 in their
    table slot — the recomputed prefix K/V lands in scratch and the shared
    page keeps its (identical) contents.
    """
    ps = cache.page_size
    pool = dict(cache.pool)
    with jax.named_scope("kv_commit"):
        for name, arr in leaves.items():
            l, _, s = arr.shape[:3]
            pad = (-s) % ps
            if pad:
                arr = jnp.pad(arr, [(0, 0), (0, 0), (0, pad)]
                              + [(0, 0)] * (arr.ndim - 3))
            n = (s + pad) // ps
            tiles = arr.reshape(l, n, ps, *arr.shape[3:])
            pool[name] = pool[name].at[:, pages].set(
                tiles.astype(pool[name].dtype))
    return dataclasses.replace(cache, pool=pool)


# ---------------------------------------------------------------------------
# host-side bookkeeping (scheduler state — plain Python, no jax)
# ---------------------------------------------------------------------------


class PageAllocator:
    """Free list + refcounts over the page pool (host side).

    Page 0 (:data:`SCRATCH_PAGE`) is reserved and pinned; usable capacity is
    ``num_pages - 1``.  Shared (prefix-cache) pages are refcounted — a page
    returns to the free list only when its last holder releases it.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"num_pages={num_pages} must be >= 2 "
                             "(page 0 is the reserved scratch page)")
        self.num_pages = num_pages
        self._refs = np.zeros(num_pages, np.int32)
        self._refs[SCRATCH_PAGE] = 1
        # pop() hands out low page ids first (stable tests/debugging)
        self._free: List[int] = list(range(num_pages - 1, SCRATCH_PAGE, -1))
        self.high_water = 0          # peak pages simultaneously in use
        # lifetime accounting (eviction/restore churn shows up here: a
        # preempted-then-resumed request allocates its pages twice)
        self.total_allocated = 0     # pages handed out over the lifetime
        self.total_freed = 0         # pages returned to the free list
        self.failed_allocs = 0       # alloc() calls refused for lack of pages

    @property
    def capacity(self) -> int:
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.capacity - len(self._free)

    def stats(self) -> Dict[str, int]:
        """Pool occupancy snapshot: capacity, free/used pages, pages held by
        more than one request (prefix sharing), and the high-water mark of
        simultaneous use (surfaced through ``ServingEngine.stats()`` and the
        serve CLI's periodic log line)."""
        return {
            "capacity": self.capacity,
            "free": self.free_pages,
            "used": self.used_pages,
            "shared": int((self._refs[SCRATCH_PAGE + 1:] > 1).sum()),
            "high_water": self.high_water,
            "total_allocated": self.total_allocated,
            "total_freed": self.total_freed,
            "failed_allocs": self.failed_allocs,
        }

    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages (refcount 1 each), or None if short."""
        if n > len(self._free):
            self.failed_allocs += 1
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self.total_allocated += n
        self.high_water = max(self.high_water, self.used_pages)
        return pages

    def share(self, pages: Iterable[int]) -> None:
        """Take an additional reference on already-live pages."""
        for p in pages:
            if self._refs[p] <= 0:
                raise ValueError(f"page {p} is not live")
            self._refs[p] += 1

    def release(self, pages: Iterable[int]) -> List[int]:
        """Drop one reference per page; returns the pages actually freed."""
        freed = []
        for p in pages:
            if p == SCRATCH_PAGE:
                continue
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
                freed.append(p)
            elif self._refs[p] < 0:
                raise ValueError(f"page {p} released more times than held")
        self.total_freed += len(freed)
        return freed


class PrefixCache:
    """Page-aligned prompt-prefix registry: token prefix -> live page ids.

    Only FULL pages are shared — the divergent tail of a prompt always gets
    fresh pages, so a shared page is never written after registration (the
    sharer's first write position is ``>= len(prompt) >= shared_pages *
    page_size``).  Entries are dropped as soon as any of their pages is
    freed, so the registry never resurrects recycled pages; sharing
    therefore requires an overlapping live request (no eviction policy to
    tune).  Exact reuse relies on deterministic prefill: identical prefix
    tokens produce identical K/V rows.
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        self._entries: Dict[bytes, List[int]] = {}
        self.hits = 0
        self.evictions = 0           # entries dropped because a page freed

    @staticmethod
    def _key(tokens: np.ndarray) -> bytes:
        return np.ascontiguousarray(tokens, np.int32).tobytes()

    def match(self, prompt: np.ndarray) -> List[int]:
        """Page ids of the longest registered full-page prefix of ``prompt``."""
        n_full = len(prompt) // self.page_size
        for i in range(n_full, 0, -1):
            pages = self._entries.get(self._key(prompt[: i * self.page_size]))
            if pages is not None:
                self.hits += 1
                return list(pages)
        return []

    def register(self, prompt: np.ndarray, pages: List[int]) -> None:
        """Register every full-page prefix of ``prompt`` (pages[:i] covers
        tokens[:i * page_size])."""
        for i in range(1, len(prompt) // self.page_size + 1):
            self._entries[self._key(prompt[: i * self.page_size])] = \
                list(pages[:i])

    def evict(self, freed: Iterable[int]) -> None:
        """Drop every entry that references a freed page."""
        freed = set(freed)
        if freed:
            before = len(self._entries)
            self._entries = {k: v for k, v in self._entries.items()
                             if not freed.intersection(v)}
            self.evictions += before - len(self._entries)

    def stats(self) -> Dict[str, int]:
        """Registry snapshot: live entries, lifetime hits and evictions."""
        return {"entries": len(self._entries), "hits": self.hits,
                "evictions": self.evictions}
