"""Pallas TPU kernel: single-token decode attention read from a KV page pool.

The paged serving cache (serving/kv_cache.py) keeps K and V as page pools of
shape ``(layers, num_pages, page_size, kv, hd)``; each slot's int32 block
table maps its logical page index to a physical page.  This kernel attends
one new query token per slot against that pool *in place*: it walks each
slot's block table, DMAs the slot's live pages of one layer from HBM into
VMEM and runs an online (flash) softmax over them.  No per-slot copy of the
pool is ever made, and a slot costs reads and compute for the blocks of
pages that hold its live rows only.

* Scalar prefetch carries the layer index, each slot's position ``pos`` and
  the flattened block tables, so page addresses are known before the body
  runs.
* Pages are DMA'd whole, ``(page_size, kv, hd)`` with every KV head, several
  pages per compute block, double-buffered: while one block is scored the
  next one is in flight — the next block of the same slot or, on a slot's
  last block, the first block of the next slot (grid steps run in order).
* The pool holds rows ``[0, pos)`` of a slot.  Blocks past
  ``ceil(pos / page_size)`` issue no DMA and no compute; with a sliding
  ``window`` blocks wholly before ``pos - window + 1`` are skipped too.
  Inside a live block, rows outside ``[pos - window + 1, pos)`` are masked.
* The new token's own K/V row is not in the pool yet (the caller commits it
  after the layer scan), so it comes in as ``k_new``/``v_new`` and seeds
  the online softmax.  A slot at position 0 attends to itself alone.

Scores: a block's pages land in VMEM as ``(pages * page_size * kv, hd)``
rows (row ``t * kv + h`` is position ``t``, KV head ``h``), so one matmul
scores every query head against every row, and the mask keeps query head
``c`` on rows of its group ``c // (H / kv)``.  bf16 operands, f32 scores,
softmax and accumulation — the precision of ``models.layers.decode_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions scored per compute block (16 pages of 16 rows, with every KV
#: head).  A block has a fixed cost beside its bytes (the waits, the loop
#: step, the MXU passes over its rows), so larger blocks win until a slot's
#: partly used last block wastes more than they save.  On a TPU v5e, one
#: layer of 32 slots of 130-640 positions read in 0.107 ms at 256 positions
#: a block for both qwen2-1.5b and h2o-danube-1.8b, against 0.123 / 0.113
#: ms at 512 / 128 positions (1024 rows); 8 slots of 8k-16k positions read
#: 6% slower than at 512 positions.
BLOCK_POSITIONS = 256

MASKED = -1e30


def pages_per_block(page_size: int, n_tables: int, num_pages: int) -> int:
    """Pages DMA'd and scored together (see :data:`BLOCK_POSITIONS`), at
    most a block table's and a pool's worth."""
    return max(1, min(n_tables, num_pages, BLOCK_POSITIONS // page_size))


def _live_span(pos, *, page_size: int, n_tables: int, window: Optional[int]):
    """A slot's live pool pages ``[first, end)``: rows ``[lo, pos)`` with
    ``lo`` the window's first row (0 without a window)."""
    end = jnp.minimum((pos + page_size - 1) // page_size, n_tables)
    if window is None:
        return jnp.int32(0), end
    lo = jnp.maximum(pos - window + 1, 0)
    return jnp.minimum(lo // page_size, end), end


def _kernel(layer_ref, pos_ref, tables_ref,            # scalar prefetch
            q_ref, kn_ref, vn_ref, kp_hbm, vp_hbm,     # inputs
            o_ref,                                     # output
            kbuf, vbuf, sems, state,                   # scratch
            *, page_size: int, n_tables: int, nb: int, kv: int,
            window: Optional[int], scale: float):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    span = functools.partial(_live_span, page_size=page_size,
                             n_tables=n_tables, window=window)

    def start(slot, blk, buf):
        """DMA the ``nb`` pages of block ``blk`` of ``slot`` into buffer
        ``buf``.  A page index outside the slot's live span (the tail of
        its last block, the head of its first under a window) is clamped
        onto the span, so every block moves exactly ``nb`` pages and one
        wait per pool covers them; the clamped rows are masked."""
        first, end = span(pos_ref[slot])
        for j in range(nb):
            page = tables_ref[slot * n_tables
                              + jnp.clip(blk * nb + j, first, end - 1)]
            pltpu.make_async_copy(kp_hbm.at[layer, page], kbuf.at[buf, j],
                                  sems.at[0, buf]).start()
            pltpu.make_async_copy(vp_hbm.at[layer, page], vbuf.at[buf, j],
                                  sems.at[1, buf]).start()

    def wait(buf):
        """Wait for a whole block in ``buf``: the semaphores count bytes,
        so one block-sized descriptor per pool waits for its ``nb`` page
        copies."""
        pltpu.make_async_copy(kp_hbm.at[0, pl.ds(0, nb)], kbuf.at[buf],
                              sems.at[0, buf]).wait()
        pltpu.make_async_copy(vp_hbm.at[0, pl.ds(0, nb)], vbuf.at[buf],
                              sems.at[1, buf]).wait()

    @pl.when(b == 0)
    def _():
        state[0] = 0          # buffer holding the next block to score
        state[1] = 0          # whether that block was prefetched already

    pos = pos_ref[b]
    first, end = span(pos)
    first_blk, end_blk = first // nb, (end + nb - 1) // nb
    prefetched = state[1]
    state[1] = 0

    @pl.when(jnp.logical_and(prefetched == 0, first_blk < end_blk))
    def _():
        start(b, first_blk, state[0])

    h, hd = q_ref.shape[1], q_ref.shape[2]
    g = h // kv
    rows = nb * page_size * kv
    dtype = kbuf.dtype
    q = q_ref[0].astype(dtype)                               # (H, hd)
    head = jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0) // g   # (H, 1)

    # the new token seeds the online softmax: its score per query head is
    # against its own group's K row
    kn = kn_ref[0].astype(jnp.float32)                       # (kv, hd)
    vn = vn_ref[0].astype(jnp.float32)
    qf = q.astype(jnp.float32)
    s_new = jnp.zeros((h, 1), jnp.float32)
    v_new = jnp.zeros((h, hd), jnp.float32)
    for k in range(kv):
        mine = head == k
        s_new = jnp.where(mine, jnp.sum(qf * kn[k][None, :], axis=-1,
                                        keepdims=True), s_new)
        v_new = jnp.where(mine, vn[k][None, :], v_new)
    m0 = s_new * scale
    l0 = jnp.ones((h, 1), jnp.float32)
    acc0 = v_new

    lo = (jnp.maximum(pos - window + 1, 0) if window is not None
          else jnp.int32(0))
    r_col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)

    def body(blk, carry):
        m, l, acc = carry
        buf = state[0]
        nxt = 1 - buf

        @pl.when(blk + 1 < end_blk)
        def _():
            start(b, blk + 1, nxt)

        @pl.when(jnp.logical_and(blk + 1 == end_blk, b + 1 < n_slots))
        def _():
            # a slot's last block: prefetch the next slot's first block
            nb_ = jnp.minimum(b + 1, n_slots - 1)
            f1, e1 = span(pos_ref[nb_])
            f1_blk, e1_blk = f1 // nb, (e1 + nb - 1) // nb

            @pl.when(f1_blk < e1_blk)
            def _():
                start(nb_, f1_blk, nxt)
                state[1] = 1

        wait(buf)
        base = blk * nb * page_size
        k = kbuf[buf].reshape(rows, hd)
        v = vbuf[buf].reshape(rows, hd)
        kpos_col = base + r_col // kv                       # (1, R)
        live_col = jnp.logical_and(kpos_col >= lo, kpos_col < pos)
        mask = jnp.logical_and(live_col, (r_col % kv) == head)   # (H, R)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, MASKED)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        # masked rows (stale rows of the slot's last page, clamped
        # duplicates) weigh exactly 0, as in the gather path's softmax
        pv = jnp.dot(p.astype(dtype), v, preferred_element_type=jnp.float32)
        state[0] = nxt
        return m_new, l_new, alpha * acc + pv

    m, l, acc = jax.lax.fori_loop(first_blk, end_blk, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def paged_decode_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                           k_pool: jax.Array, v_pool: jax.Array,
                           layer: jax.Array, pos: jax.Array,
                           block_tables: jax.Array, *, scale: float,
                           window: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """Single-token GQA decode attention against a page pool.

    q: (B, H, hd); k_new/v_new: (B, kv, hd), the new token's rows (position
    ``pos``); k_pool/v_pool: (L, num_pages, page_size, kv, hd), read at
    ``layer``; pos: (B,) int32; block_tables: (B, n_tables) int32.  Query
    ``b`` attends to pool rows ``[max(0, pos - window + 1), pos)`` of its
    pages and to its own new row; scores are scaled by ``scale``.  Returns
    (B, H, hd) in the pool's dtype.
    """
    bsz, h, hd = q.shape
    _, num_pages, page_size, kv, _ = k_pool.shape
    n_tables = block_tables.shape[1]
    nb = pages_per_block(page_size, n_tables, num_pages)
    kernel = functools.partial(
        _kernel, page_size=page_size, n_tables=n_tables, nb=nb, kv=kv,
        window=window, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, kv, hd), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, kv, hd), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, nb, page_size, kv, hd), k_pool.dtype),
            pltpu.VMEM((2, nb, page_size, kv, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h, hd), k_pool.dtype),
        # grid steps hand the next slot's first block over in scratch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32),
      q, k_new, v_new, k_pool, v_pool)
