"""Dense decoder-only transformer family (yi, h2o-danube, qwen2, qwen1.5,
phi-3-vision backbone).

Covers: GQA with arbitrary kv heads, optional QKV bias (qwen), sliding-window
attention (danube), tied embeddings, and the VLM variant whose image positions
take precomputed patch embeddings (phi-3-vision; frontend stubbed per the
assignment).

Layer stacking uses ``lax.scan`` over a leading L axis on block params — this
bounds HLO size/compile time at 61-layer scale and is what makes the 80-cell
dry-run tractable (DESIGN.md §5).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import current_context
from repro.kernels import ops
from repro.models import layers as L
from repro.serving import kv_cache as KV

Params = Dict[str, Any]


def _block_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": L.rmsnorm_init(cfg.d_model),
        "attn": L.attn_init(k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                            cfg.hd(), bias=cfg.qkv_bias),
        "norm2": L.rmsnorm_init(cfg.d_model),
        "mlp": L.swiglu_init(k2, cfg.d_model, cfg.d_ff),
    }


def init(cfg: ModelConfig, key) -> Params:
    ke, kb, kh = jax.random.split(key, 3)
    block_keys = jax.random.split(kb, cfg.num_layers)
    blocks = jax.vmap(lambda k: _block_init(k, cfg))(block_keys)
    params: Params = {
        "embed": L.embed_init(ke, cfg.vocab_size, cfg.d_model),
        "blocks": blocks,
        "final_norm": L.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.dense_init(kh, cfg.d_model, cfg.vocab_size, scale=0.02)
    return params


def _block_apply(cfg: ModelConfig, bp: Params, x: jax.Array,
                 positions: jax.Array, cache, cache_pos, dtype, q_chunk: int,
                 collect_kv: bool = False, attend=None):
    with jax.named_scope("attention"):
        h, new_cache = L.attention_block(
            bp["attn"], L.rmsnorm(x, bp["norm1"], cfg.norm_eps),
            n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads, hd=cfg.hd(),
            rope_theta=cfg.rope_theta, positions=positions,
            window=cfg.sliding_window, q_chunk=q_chunk,
            cache=cache, cache_pos=cache_pos, return_kv=collect_kv,
            dtype=dtype, attend=attend)
        x = x + h
    with jax.named_scope("mlp"):
        mlp_in = L.rmsnorm(x, bp["norm2"], cfg.norm_eps)
        if cfg.act_sparsity > 0.0:
            # fragment-structured sparsification of the MLP input: gives the
            # zero-skipping matmul path (FormsSpec(zero_skip=...)) dead
            # whole fragments to skip in the gate/up projections, aligned
            # with act_fragment (DESIGN.md §6g)
            mlp_in = L.sparsify_fragments(mlp_in, cfg.act_fragment,
                                          cfg.act_sparsity)
        x = x + L.swiglu(bp["mlp"], mlp_in, dtype, act=cfg.mlp_act,
                         frag_drop=cfg.act_sparsity, frag_m=cfg.act_fragment)
    return x, new_cache


def _embed_inputs(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
                  dtype) -> jax.Array:
    x = L.embed_lookup(params["embed"], batch["tokens"], dtype)
    if cfg.num_image_tokens and "patch_embeds" in batch:
        # VLM: precomputed patch embeddings prefix the text tokens (stub frontend)
        x = jnp.concatenate([batch["patch_embeds"].astype(dtype), x], axis=1)
    return x


def head_matrix(cfg: ModelConfig, params: Params) -> jax.Array:
    head = params.get("head", None)
    return head if head is not None else params["embed"].T


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array], *,
            remat: bool = False, q_chunk: int = L.DEFAULT_Q_CHUNK,
            return_hidden: bool = False
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence forward (train / prefill).  Returns (logits, aux);
    ``return_hidden=True`` returns the final hidden states instead of logits
    (the chunked-CE training path never materializes full logits)."""
    dtype = jnp.dtype(cfg.dtype)
    x = _embed_inputs(cfg, params, batch, dtype)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)

    def body(x, bp):
        out, _ = _block_apply(cfg, bp, x, positions, None, None, dtype, q_chunk)
        return out, None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x, {}
    logits = L.lm_logits(x, head_matrix(cfg, params), dtype)
    return logits, {}


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    kv, hd = cfg.num_kv_heads, cfg.hd()
    shape = (cfg.num_layers, batch, max_len, kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     slots: int, max_len: int, dtype=jnp.bfloat16
                     ) -> KV.PagedKVCache:
    """Page-pool cache: ``(L, num_pages, page_size, kv, hd)`` pools replace
    the dense ``(L, slots, max_len, kv, hd)`` leaves (DESIGN.md §6d); on a
    TPU the last dim is ``hd`` zero-padded to whole 128-lane tiles
    (``kernels.ops.pool_lanes``), so decode reads pages in place."""
    del slots, max_len
    kv, hd = cfg.num_kv_heads, cfg.hd()
    shape = (cfg.num_layers, num_pages, page_size, kv, ops.pool_lanes(hd))
    return KV.PagedKVCache(pool={"k": jnp.zeros(shape, dtype),
                                 "v": jnp.zeros(shape, dtype)},
                           dense={}, page_size=page_size)


def _prefill_core(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  length: jax.Array):
    """Shared bulk-prefill compute: chunked full-seq attention over the
    prompt.  Returns (last-real-token logits (1, V), per-leaf full-prompt
    rows (L, 1, S, ...)); the dense/paged entry points differ only in how
    they commit those rows."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_lookup(params["embed"], tokens, dtype)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)

    def body(x, bp):
        out, kv = _block_apply(cfg, bp, x, positions, None, None, dtype,
                               L.DEFAULT_Q_CHUNK, collect_kv=True)
        return out, kv

    # the scan's own per-layer slices and stacked rows read as "layers"
    with jax.named_scope("layers"):
        x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    x_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    logits = L.lm_logits(x_last, head_matrix(cfg, params), dtype)
    return logits[:, 0], {"k": ks, "v": vs}


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            cache: Dict[str, jax.Array], slot: jax.Array, length: jax.Array
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Bulk prefill of one serving slot: chunked full-seq attention + a
    one-shot cache write.  tokens: (1, S) int32 (padded past ``length``);
    returns (last-real-token logits (1, vocab), cache).  Padded positions
    land in the cache but are never attended: decode masks each slot at
    kpos <= pos, and every position is re-written before it enters a mask.
    """
    logits, rows = _prefill_core(cfg, params, tokens, length)
    zero = jnp.zeros((), jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    starts = (zero, slot, zero, zero, zero)
    k_new = jax.lax.dynamic_update_slice(
        cache["k"], rows["k"].astype(cache["k"].dtype), starts)
    v_new = jax.lax.dynamic_update_slice(
        cache["v"], rows["v"].astype(cache["v"].dtype), starts)
    return logits, {"k": k_new, "v": v_new}


def prefill_paged(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  cache: KV.PagedKVCache, pages: jax.Array, slot: jax.Array,
                  length: jax.Array) -> Tuple[jax.Array, KV.PagedKVCache]:
    """Paged bulk prefill: same compute as :func:`prefill`, committed as a
    one-shot whole-page scatter at ``pages`` (scratch-0 entries protect
    prefix-shared pages)."""
    del slot
    logits, rows = _prefill_core(cfg, params, tokens, length)
    return logits, KV.commit_pages(cache, _to_pool(cache, rows), pages)


def _to_pool(cache: KV.PagedKVCache, rows: Dict[str, jax.Array]
             ) -> Dict[str, jax.Array]:
    """New K/V rows zero-padded to the pool's lane width."""
    lanes = cache.pool["k"].shape[-1]
    return {n: jnp.pad(r, [(0, 0)] * (r.ndim - 1)
                       + [(0, lanes - r.shape[-1])])
            if r.shape[-1] != lanes else r for n, r in rows.items()}


def _decode_core(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 k_cache: Optional[jax.Array], v_cache: Optional[jax.Array],
                 pos: jax.Array, attend=None):
    """Shared decode compute against ``(L, B, S, kv, hd)`` cache views
    (persistent dense leaves or block-table gathers — the per-slot
    ``kpos <= pos`` masks are identical), or, with ``attend(layer, q, k,
    v)`` and no views, attention that reads the cache itself.  tokens:
    (B, T) with token t of row b living at position ``pos[b] + t`` (T = 1
    steady state, K+1 for a speculative verify).  Returns (logits (B, T,
    V), new-token K/V of shape (L, B, T, kv, hd)); committing them is the
    caller's job."""
    dtype = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    x = L.embed_lookup(params["embed"], tokens, dtype)
    positions = L.position_span(pos, t)

    if attend is None:
        def body(x, xs):
            bp, kc, vc = xs
            out, new_cache = _block_apply(cfg, bp, x, positions, (kc, vc),
                                          positions, dtype, L.DEFAULT_Q_CHUNK)
            return out, new_cache

        xs = (params["blocks"], k_cache, v_cache)
    else:
        def body(x, xs):
            bp, layer = xs
            return _block_apply(cfg, bp, x, positions, None, None, dtype,
                                L.DEFAULT_Q_CHUNK,
                                attend=functools.partial(attend, layer))

        xs = (params["blocks"], jnp.arange(cfg.num_layers, dtype=jnp.int32))

    # the scan's own per-layer slices (weights, K/V views) and stacked
    # new-token rows read as "layers"
    with jax.named_scope("layers"):
        x, (k_tok, v_tok) = jax.lax.scan(body, x, xs)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_logits(x, head_matrix(cfg, params), dtype)
    return logits, k_tok, v_tok


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, jax.Array], pos: jax.Array,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step.  tokens: (B, T) int32 (T = 1 on the steady-state
    path); pos: scalar int32 or (B,) per-slot positions of the FIRST token
    (each batch row lives on its own cache timeline; token t commits at
    ``pos + t``, rows past max_len are dropped, not clamped)."""
    b, t = tokens.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    logits, k_tok, v_tok = _decode_core(cfg, params, tokens, cache["k"],
                                        cache["v"], pos)
    # per-row token-column write into the persistent caches (in-place when
    # the cache is donated into the jitted step)
    posgrid = L.position_span(pos, t)
    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
    k_new = cache["k"].at[:, bidx, posgrid].set(k_tok, mode="drop")
    v_new = cache["v"].at[:, bidx, posgrid].set(v_tok, mode="drop")
    return logits, {"k": k_new, "v": v_new}


def decode_paged(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 cache: KV.PagedKVCache, pos: jax.Array,
                 block_tables: jax.Array
                 ) -> Tuple[jax.Array, KV.PagedKVCache]:
    """Paged decode step: attend exactly like :func:`decode_step`, then
    commit the new tokens into their pages (positions past the block table
    land in scratch).

    A single-token step on an unsharded pool reads K/V in place: the layer
    scan closes over the pools and each layer's attention is the paged
    kernel (``kernels.ops.paged_attention``), walking the block tables.
    Otherwise (T > 1: chunked prefill, speculative verify; or a pool sharded
    over a mesh) per-slot K/V views are gathered through the block tables
    first (``KV.reads_in_place``)."""
    b, t = tokens.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    if KV.reads_in_place(t, current_context()):
        pools = cache.pool

        def attend(layer, q, k, v):
            return ops.paged_attention(q, k, v, pools["k"], pools["v"],
                                       layer, pos, block_tables,
                                       window=cfg.sliding_window)

        logits, k_tok, v_tok = _decode_core(cfg, params, tokens, None, None,
                                            pos, attend=attend)
    else:
        views = KV.gather_views(cache, block_tables)
        hd = cfg.hd()
        if views["k"].shape[-1] != hd:
            views = {n: v[..., :hd] for n, v in views.items()}
        logits, k_tok, v_tok = _decode_core(cfg, params, tokens, views["k"],
                                            views["v"], pos)
    cache = KV.commit_tokens(cache, _to_pool(cache, {"k": k_tok, "v": v_tok}),
                             block_tables, pos)
    return logits, cache
