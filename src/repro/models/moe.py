"""MoE transformer family: olmoe (64 experts, top-8) and deepseek-v3 (MLA,
3 leading dense layers, 1 shared + 256 routed top-8 experts under sigmoid
group-limited routing, MTP).

Routing (:func:`route`, float32): softmax top-k (olmoe), or DeepSeek-V3's
``noaux_tc`` — sigmoid scores, a selection-only correction bias, the top
``topk_group`` of ``n_group`` expert groups (a group scores the sum of its
top two), the top-k experts among those, weighted by their un-biased
scores normalized over the k and times ``routed_scaling_factor``.

Two expert paths share the router:

* serving (bulk and chunked prefill, decode, speculative verify) —
  :func:`held_moe_ffn`, dropless.  A layer holds experts ``expert_first``
  .. ``expert_first + experts_held - 1`` of the router's ``num_experts``
  (one chip's share under expert parallelism; all of them by default).
  Each held expert runs over every row through the polarized kernel on its
  compressed codes, weighted by its gate (zero where the row did not route
  to it), and the shared expert is added.  No row's output depends on the
  others in its batch, so prefill pads to buckets like the dense family.
* training (``forward``) — :func:`moe_ffn`, capacity-based dispatch
  (position-in-expert via a sort, scatter into an ``(E*C, d)`` buffer,
  batched expert matmuls, gather-combine), over a mesh the shard_map
  all_to_all of distributed/moe_dispatch.py; tokens past
  ``capacity_factor`` drop (the aux loss keeps the router balanced).

Leading dense layers (``first_k_dense_replace``) are a second stacked
group, ``dense_blocks`` (attention + a SwiGLU MLP of width ``d_ff``),
scanned before the MoE ``blocks``; one cache covers all ``num_layers``
layers, the dense ones first.

MLA (deepseek): train/prefill use the expanded form; decode uses the
*absorbed* form (q absorbed through kv_up so attention runs in the latent
space) with a cache of compressed latents ``c_kv`` + shared rope key — the
memory-efficient decode that makes 128-batch 32k-decode fit.  With
``rope_scaling`` the rope part follows YaRN and the softmax scale carries
its ``mscale_all_dim`` factor squared.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models import layers as L
from repro.serving import kv_cache as KV
from repro.serving import trace as T

Params = Dict[str, Any]
EXPERTS = ("w_gate", "w_up", "w_down")


# ---------------------------------------------------------------------------
# routing and the two expert paths
# ---------------------------------------------------------------------------

def moe_ffn_init(key, cfg: ModelConfig) -> Params:
    e, d, f = cfg.held_experts(), cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 7)
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": L.dense_init(ks[0], d, cfg.num_experts, scale=0.02),
        "w_gate": jax.random.normal(ks[1], (e, d, f)) * scale,
        "w_up": jax.random.normal(ks[2], (e, d, f)) * scale,
        "w_down": jax.random.normal(ks[3], (e, f, d)) * (1.0 / jnp.sqrt(f)),
    }
    if cfg.router_bias:
        p["router_bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
    if cfg.num_shared_experts:
        fs = cfg.moe_d_ff * cfg.num_shared_experts
        p["shared_gate"] = L.dense_init(ks[4], d, fs)
        p["shared_up"] = L.dense_init(ks[5], d, fs)
        p["shared_down"] = L.dense_init(ks[6], fs, d)
    return p


def route(p: Params, xt: jax.Array, cfg: ModelConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router of ``(T, d)`` rows over all ``num_experts``, in float32:
    ``(weights (T, k), expert ids (T, k), scores (T, E))``."""
    with jax.named_scope("router"):
        logits = jnp.matmul(xt.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if cfg.router_scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        choice = scores + p["router_bias"] if "router_bias" in p else scores
        if cfg.n_group > 1:
            t, e = choice.shape
            size = e // cfg.n_group
            group = jax.lax.top_k(choice.reshape(t, cfg.n_group, size),
                                  2)[0].sum(-1)
            _, kept = jax.lax.top_k(group, cfg.topk_group)
            keep = jnp.any(kept[:, :, None] == jnp.arange(cfg.n_group),
                           axis=1)
            choice = jnp.where(jnp.repeat(keep, size, axis=-1), choice,
                               -jnp.inf)
        _, idx = jax.lax.top_k(choice, cfg.experts_per_token)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.norm_topk_prob:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return w * cfg.routed_scaling_factor, idx, scores


def _shared_expert(p: Params, xt: jax.Array, dtype) -> jax.Array:
    with jax.named_scope("shared_expert"):
        h = jax.nn.silu(L.linear(p, "shared_gate", xt, dtype))
        h = h * L.linear(p, "shared_up", xt, dtype)
        return L.linear(p, "shared_down", h, dtype)


def held_moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig, dtype
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The serving expert layer, dropless: x (B, S, d) -> (out, counts).

    Only the held experts are computed, each over every row, weighted by
    its gate (zero for rows that did not route to it).  ``counts``: the
    (row, held expert) pairs routed here (``moe.routed``), the rows the held
    experts computed (``moe.rows``) and rows x top-k (``moe.routes``)."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d).astype(dtype)
    w, idx, _ = route(p, xt, cfg)
    n = cfg.held_experts()
    with jax.named_scope("experts"):
        hit = idx[:, :, None] == cfg.expert_first + jnp.arange(n)
        gates = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)  # (T, n)

        def expert(acc, inp):
            ep, gate = inp
            h = jax.nn.silu(L.linear(ep, "w_gate", xt, dtype))
            h = h * L.linear(ep, "w_up", xt, dtype)
            y = L.linear(ep, "w_down", h, dtype).astype(jnp.float32)
            return acc + gate[:, None] * y, None

        y, _ = jax.lax.scan(expert, jnp.zeros((t, d), jnp.float32),
                            ({k: p[k] for k in EXPERTS}, gates.T))
    y = y.astype(dtype)
    if cfg.num_shared_experts:
        y = y + _shared_expert(p, xt, dtype)
    counts = {"moe.routed": jnp.sum(hit), "moe.rows": jnp.int32(n * t),
              "moe.routes": jnp.int32(t * cfg.experts_per_token)}
    return y.reshape(b, s, d), counts


def _expert_ffn(p: Params, bufe: jax.Array, dtype) -> jax.Array:
    """Batched per-expert SwiGLU on the dispatched buffer (E, C, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", bufe, L.wload(p, "w_gate", dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", bufe, L.wload(p, "w_up", dtype))
    h = constrain(h, "model", "batch", None)
    out = jnp.einsum("ecf,efd->ecd", h, L.wload(p, "w_down", dtype))
    return constrain(out, "model", "batch", None)


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig, dtype
            ) -> Tuple[jax.Array, jax.Array]:
    """The training expert layer, capacity-based: x (B, S, d) -> (out,
    aux_loss).

    Two dispatch paths: the shard_map all_to_all expert-parallel path (large
    token counts on a mesh — production) and a small pjit scatter path
    (single-device tests).  Dispatch needs every expert's weights.
    """
    from repro.distributed import moe_dispatch
    from repro.distributed.sharding import current_context

    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    if cfg.held_experts() != e:
        raise ValueError(f"capacity dispatch needs all {e} experts; this "
                         f"layer holds {cfg.held_experts()} (a serving "
                         f"share: held_moe_ffn serves it)")
    t = b * s

    xt = x.reshape(t, d)
    xt = constrain(xt, "tokens", None)
    gates, idx, scores = route(p, xt, cfg)

    # aux load-balance loss (switch-style)
    probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * prob_mean)
    ctx = current_context()
    if moe_dispatch.can_use(ctx, t, e):
        n_dev = ctx.axis_size("tokens")
        c2 = max(1, int(cfg.capacity_factor * (t // n_dev) * k / e))
        bufe, slots = moe_dispatch.dispatch(xt.astype(dtype), idx, e, c2, ctx,
                                            quantized=cfg.moe_dispatch_int8)
        out_buf = _expert_ffn(p, bufe, dtype)
        y = moe_dispatch.combine(out_buf, idx, slots, gates, e, c2, ctx,
                                 quantized=cfg.moe_dispatch_int8)
    else:
        cap = max(4, int(cfg.capacity_factor * t * k / e))
        # sort-based positions: O(T*K) memory
        e_flat = idx.reshape(t * k)
        order = jnp.argsort(e_flat)
        sorted_e = e_flat[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(e))
        pos_sorted = jnp.arange(t * k) - seg_start[sorted_e]
        pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
        valid = pos < cap
        dest = jnp.where(valid, e_flat * cap + pos, 0)
        x_rep = jnp.repeat(xt, k, axis=0).astype(dtype)
        upd = jnp.where(valid[:, None], x_rep, jnp.zeros_like(x_rep))
        bufe = jnp.zeros((e * cap, d), dtype).at[dest].add(upd).reshape(e, cap, d)
        out_buf = _expert_ffn(p, bufe, dtype).reshape(e * cap, d)
        y_tk = jnp.take(out_buf, dest, axis=0)
        y_tk = jnp.where(valid[:, None], y_tk, jnp.zeros_like(y_tk))
        y_tk = y_tk * gates.reshape(t * k, 1).astype(dtype)
        y = y_tk.reshape(t, k, d).sum(axis=1)

    if cfg.num_shared_experts:
        y = y + _shared_expert(p, xt.astype(dtype), dtype)

    y = constrain(y, "tokens", None)
    return constrain(y.reshape(b, s, d), "batch", "model", None), aux


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v3)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig) -> Params:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 5)
    return {
        "q_down": L.dense_init(ks[0], d, m.q_lora_rank),
        "q_up": L.dense_init(ks[1], m.q_lora_rank, h * qk),
        "kv_down": L.dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim),
        "kv_up": L.dense_init(ks[3], m.kv_lora_rank,
                              h * (m.qk_nope_head_dim + m.v_head_dim)),
        "wo": L.dense_init(ks[4], h * m.v_head_dim, d),
        "q_norm": L.rmsnorm_init(m.q_lora_rank),
        "kv_norm": L.rmsnorm_init(m.kv_lora_rank),
    }


def _mla_rope(cfg: ModelConfig) -> Tuple[Any, float, float]:
    """(rope frequencies or None for theta's, factor on cos/sin, softmax
    scale) of MLA's rope part: YaRN under ``rope_scaling``, where the
    softmax scale ``(qk_nope + qk_rope) ** -0.5`` also takes
    ``yarn_mscale(factor, mscale_all_dim) ** 2``."""
    m: MLAConfig = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    yarn = cfg.rope_scaling
    if yarn is None:
        return None, 1.0, scale
    freqs, mscale = L.yarn_freqs(m.qk_rope_head_dim, cfg.rope_theta, yarn)
    if yarn.mscale_all_dim:
        scale *= L.yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return freqs, mscale, scale


def _mla_qkv_full(p: Params, x, cfg: ModelConfig, positions, dtype):
    """Expanded-form q, k, v for full-sequence attention."""
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    qk_rope, qk_nope, dv = m.qk_rope_head_dim, m.qk_nope_head_dim, m.v_head_dim
    freqs, mscale, _ = _mla_rope(cfg)

    x = constrain(x, "batch", None, None)   # Megatron-SP gather
    cq = L.rmsnorm(L.linear(p, "q_down", x, dtype), p["q_norm"], cfg.norm_eps)
    q = L.linear(p, "q_up", cq, dtype).reshape(b, s, h, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta, freqs, mscale)

    kv = L.linear(p, "kv_down", x, dtype)
    c_kv = L.rmsnorm(kv[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = L.apply_rope(kv[..., m.kv_lora_rank:], positions, cfg.rope_theta,
                          freqs, mscale)

    kvu = L.linear(p, "kv_up", c_kv, dtype).reshape(b, s, h, qk_nope + dv)
    k_nope, v = kvu[..., :qk_nope], kvu[..., qk_nope:]
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, qk_rope))],
        axis=-1)
    return q_full, k_full, v, c_kv, k_rope


def mla_full(p: Params, x, cfg: ModelConfig, positions, dtype,
             q_chunk: int) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Expanded-form MLA.  Returns (out, latents) where latents are the
    per-position decode-cache entries ({"c_kv", "k_rope"}) so bulk prefill
    can commit them in one write."""
    q, k, v, c_kv, k_rope = _mla_qkv_full(p, x, cfg, positions, dtype)
    out = L.causal_attention(q, k, v, q_chunk=q_chunk, positions=positions,
                             scale=_mla_rope(cfg)[2])
    b, s = x.shape[:2]
    out = constrain(L.linear(p, "wo", out.reshape(b, s, -1), dtype),
                    "batch", "model", None)
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def mla_decode(p: Params, x, cfg: ModelConfig, cache: Dict[str, jax.Array],
               pos, dtype) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Absorbed-form decode: attention in the compressed latent space.

    cache: {"c_kv": (B, Smax, r), "k_rope": (B, Smax, qk_rope)}; x is
    (B, T, d) (T = 1 steady state, K+1 for a speculative verify); pos is a
    (B,) vector of per-row first-token positions or an explicit (B, T)
    position grid (scalar callers are normalized by ``decode_step``).
    """
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    qk_rope, qk_nope, dv, r = (m.qk_rope_head_dim, m.qk_nope_head_dim,
                               m.v_head_dim, m.kv_lora_rank)
    positions = L.position_grid(pos, b, s)                # (B, T)
    freqs, mscale, scale = _mla_rope(cfg)

    cq = L.rmsnorm(L.linear(p, "q_down", x, dtype), p["q_norm"], cfg.norm_eps)
    q = L.linear(p, "q_up", cq, dtype).reshape(b, s, h, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta, freqs, mscale)
    kv = L.linear(p, "kv_down", x, dtype)
    c_new = L.rmsnorm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope_new = L.apply_rope(kv[..., r:], positions, cfg.rope_theta, freqs,
                              mscale)

    # transient updated views for attention; only the new-token latents are
    # returned (the caller commits the token columns after the layer scan)
    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
    c_cache = cache["c_kv"].at[bidx, positions].set(
        c_new.astype(cache["c_kv"].dtype))
    r_cache = cache["k_rope"].at[bidx, positions].set(
        k_rope_new.astype(cache["k_rope"].dtype))

    # absorb: q_lat[b,t,h,r] = q_nope @ W_uk(h)^T
    kv_up = L.wload(p, "kv_up", dtype)
    w_uk = kv_up.reshape(r, h, qk_nope + dv)[..., :qk_nope]
    w_uv = kv_up.reshape(r, h, qk_nope + dv)[..., qk_nope:]
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w_uk)
    # scores and the latent sum take operands in the cache's dtype and
    # accumulate in float32; the CPU's dot has no bf16 x bf16 -> f32 form,
    # so there the operands are widened after rounding (the same products
    # and sums)
    wide = c_cache.dtype if ops.on_tpu() else jnp.float32

    def operand(a):
        return a.astype(c_cache.dtype).astype(wide)

    c_op = operand(c_cache)
    scores = (jnp.einsum("bthr,bsr->bhts", operand(q_lat), c_op,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthp,bsp->bhts", operand(q_rope),
                           operand(r_cache),
                           preferred_element_type=jnp.float32)) * scale
    kpos = jnp.arange(c_cache.shape[1], dtype=jnp.int32)
    mask = kpos[None, None, :] <= positions[:, :, None]    # (B, T, S)
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhts,bsr->bthr", operand(probs), c_op,
                       preferred_element_type=jnp.float32).astype(dtype)
    o = jnp.einsum("bthr,rhv->bthv", o_lat, w_uv)
    out = L.linear(p, "wo", o.reshape(b, s, h * dv), dtype)
    return out, {"c_kv": c_new.astype(cache["c_kv"].dtype),
                 "k_rope": k_rope_new.astype(cache["k_rope"].dtype)}


# ---------------------------------------------------------------------------
# blocks / model
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, dense: bool = False) -> Params:
    """One block: attention (MLA or GQA) and either the expert layer or,
    for a leading dense layer, a SwiGLU MLP of width ``d_ff``."""
    k1, k2 = jax.random.split(key)
    p: Params = {
        "norm1": L.rmsnorm_init(cfg.d_model),
        "norm2": L.rmsnorm_init(cfg.d_model),
    }
    if dense:
        p["mlp"] = L.swiglu_init(k2, cfg.d_model, cfg.d_ff)
    else:
        p["moe"] = moe_ffn_init(k2, cfg)
    if cfg.mla is not None:
        p["mla"] = mla_init(k1, cfg)
    else:
        p["attn"] = L.attn_init(k1, cfg.d_model, cfg.num_heads,
                                cfg.num_kv_heads, cfg.hd())
    return p


def init(cfg: ModelConfig, key) -> Params:
    ke, kb, kh, km = jax.random.split(key, 4)
    k = cfg.first_k_dense_replace
    block_keys = jax.random.split(kb, cfg.num_layers - k)
    blocks = jax.vmap(lambda kk: _block_init(kk, cfg))(block_keys)
    params: Params = {
        "embed": L.embed_init(ke, cfg.vocab_size, cfg.d_model),
        "blocks": blocks,
        "final_norm": L.rmsnorm_init(cfg.d_model),
        "head": L.dense_init(kh, cfg.d_model, cfg.vocab_size, scale=0.02),
    }
    if k:
        dense_keys = jax.random.split(jax.random.fold_in(kb, 1), k)
        params["dense_blocks"] = jax.vmap(
            lambda kk: _block_init(kk, cfg, dense=True))(dense_keys)
    if cfg.mtp:
        params["mtp"] = {"proj": L.dense_init(km, 2 * cfg.d_model, cfg.d_model),
                         "block": _block_init(km, cfg),
                         "norm": L.rmsnorm_init(cfg.d_model)}
    return params


def _block_apply(cfg: ModelConfig, bp: Params, x, positions, cache, pos,
                 dtype, q_chunk: int, collect_kv: bool = False,
                 serve: bool = False):
    """One block -> (x, aux loss, new cache rows or None, device counts).
    ``serve`` picks the dropless held-expert layer over the training
    dispatch."""
    new_cache = None
    with jax.named_scope("attention"):
        xa = L.rmsnorm(x, bp["norm1"], cfg.norm_eps)
        if cfg.mla is not None:
            if cache is None:
                h, latents = mla_full(bp["mla"], xa, cfg, positions, dtype,
                                      q_chunk)
                if collect_kv:
                    new_cache = latents
            else:
                h, new_cache = mla_decode(bp["mla"], xa, cfg, cache, pos,
                                          dtype)
        else:
            kv_cache = (cache["k"], cache["v"]) if cache is not None else None
            h, new_cache = L.attention_block(
                bp["attn"], xa, n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
                hd=cfg.hd(), rope_theta=cfg.rope_theta, positions=positions,
                q_chunk=q_chunk, cache=kv_cache, cache_pos=pos,
                return_kv=collect_kv, dtype=dtype)
            if new_cache is not None:
                new_cache = {"k": new_cache[0], "v": new_cache[1]}
        x = x + h
    aux, counts = jnp.zeros((), jnp.float32), {}
    with jax.named_scope("mlp"):
        xm = L.rmsnorm(x, bp["norm2"], cfg.norm_eps)
        if "mlp" in bp:
            y = L.swiglu(bp["mlp"], xm, dtype)
        elif serve:
            y, counts = held_moe_ffn(bp["moe"], xm, cfg, dtype)
        else:
            y, aux = moe_ffn(bp["moe"], xm, cfg, dtype)
    return x + y, aux, new_cache, counts


def _groups(cfg: ModelConfig, params: Params) -> List[Tuple[Params, int, int]]:
    """The stacked layer groups in order, each with its first layer and the
    layer past its last: the leading dense layers, then the MoE layers."""
    k = cfg.first_k_dense_replace
    lead = [(params["dense_blocks"], 0, k)] if k else []
    return lead + [(params["blocks"], k, cfg.num_layers)]


def _scan_layers(cfg: ModelConfig, params: Params, x, layer, xs=None):
    """Scan ``layer(x, (block params, per-layer input)) -> (x, (rows,
    counts))`` over every group in order, ``xs`` (a dict of ``(L, ...)``
    leaves, or None) split by layer; returns (x, rows concatenated over the
    layers).  The groups' device counts are added up and reported."""
    rows, counts = [], {}
    with jax.named_scope("layers"):
        for group, lo, hi in _groups(cfg, params):
            part = (None if xs is None
                    else {n: v[lo:hi] for n, v in xs.items()})
            x, (r, c) = jax.lax.scan(layer, x, (group, part))
            rows.append(r)
            for name, v in c.items():
                counts[name] = counts.get(name, 0) + jnp.sum(v)
    for name, v in counts.items():
        T.add_device_count(name, v)
    return x, {n: jnp.concatenate([r[n] for r in rows], axis=0)
               for n in rows[0]}


def head_matrix(cfg: ModelConfig, params: Params) -> jax.Array:
    return params["head"]


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array], *,
            remat: bool = False, q_chunk: int = L.DEFAULT_Q_CHUNK,
            return_hidden: bool = False
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_lookup(params["embed"], batch["tokens"], dtype)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)

    def body(x, bp):
        out, aux, _, _ = _block_apply(cfg, bp, x, positions, None, None, dtype,
                                      q_chunk)
        return out, aux

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    auxs = []
    for group, _, _ in _groups(cfg, params):
        x, a = jax.lax.scan(body, x, group)
        auxs.append(a)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    # the balance loss of the expert layers (the dense ones have none)
    aux: Dict[str, jax.Array] = {"moe_aux_loss": jnp.mean(auxs[-1])}

    if cfg.mtp and "mtp" in params:
        # multi-token prediction: combine h_t with emb(token_{t+1}) -> predict t+2
        emb_next = jnp.roll(L.embed_lookup(params["embed"], batch["tokens"], dtype),
                            -1, axis=1)
        hm = L.linear(params["mtp"], "proj", jnp.concatenate([x, emb_next], axis=-1), dtype)
        hm, mtp_aux, _, _ = _block_apply(cfg, params["mtp"]["block"], hm,
                                         positions, None, None, dtype, q_chunk)
        hm = L.rmsnorm(hm, params["mtp"]["norm"], cfg.norm_eps)
        aux["moe_aux_loss"] = aux["moe_aux_loss"] + mtp_aux / max(cfg.num_layers, 1)
        if return_hidden:
            aux["mtp_hidden"] = hm
        else:
            aux["mtp_logits"] = L.lm_logits(hm, params["head"], dtype)
    if return_hidden:
        return x, aux
    logits = L.lm_logits(x, params["head"], dtype)
    return logits, aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c_kv": jnp.zeros((cfg.num_layers, batch, max_len, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((cfg.num_layers, batch, max_len, m.qk_rope_head_dim), dtype),
        }
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.hd())
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     slots: int, max_len: int, dtype=jnp.bfloat16
                     ) -> KV.PagedKVCache:
    """Page-pool cache: GQA K/V — or MLA latents — paged along the sequence
    dim (DESIGN.md §6d)."""
    del slots, max_len
    if cfg.mla is not None:
        m = cfg.mla
        pool = {
            "c_kv": jnp.zeros((cfg.num_layers, num_pages, page_size,
                               m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((cfg.num_layers, num_pages, page_size,
                                 m.qk_rope_head_dim), dtype),
        }
    else:
        shape = (cfg.num_layers, num_pages, page_size, cfg.num_kv_heads,
                 cfg.hd())
        pool = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    return KV.PagedKVCache(pool=pool, dense={}, page_size=page_size)


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            cache: Dict[str, jax.Array], slot: jax.Array, length: jax.Array
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Bulk prefill of one serving slot (tokens: (1, S), right-padded past
    ``length``): expanded-form attention, then one cache write per leaf —
    MLA latents or GQA K/V, whichever this config caches.  Pad rows route
    too, but the serving expert layer drops nothing, so they change no real
    row (``registry.Model.padded_prefill``)."""
    logits, rows = _prefill_core(cfg, params, tokens, length)
    zero = jnp.zeros((), jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    new_cache = {}
    for name, full in cache.items():
        tok = rows[name].astype(full.dtype)     # (L, 1, S, ...)
        starts = (zero, slot, zero) + (zero,) * (full.ndim - 3)
        new_cache[name] = jax.lax.dynamic_update_slice(full, tok, starts)
    return logits, new_cache


def _prefill_core(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  length: jax.Array):
    """Shared bulk-prefill compute.  Returns (last-real-token logits (1, V),
    per-leaf full-prompt rows (L, 1, S, ...) — MLA latents or GQA K/V)."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_lookup(params["embed"], tokens, dtype)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)

    def body(x, xs):
        bp, _ = xs
        out, _aux, kv, counts = _block_apply(
            cfg, bp, x, positions, None, None, dtype, L.DEFAULT_Q_CHUNK,
            collect_kv=True, serve=True)
        return out, (kv, counts)

    x, kvs = _scan_layers(cfg, params, x, body)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    x_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    logits = L.lm_logits(x_last, params["head"], dtype)
    return logits[:, 0], kvs


def prefill_paged(cfg: ModelConfig, params: Params, tokens: jax.Array,
                  cache: KV.PagedKVCache, pages: jax.Array, slot: jax.Array,
                  length: jax.Array) -> Tuple[jax.Array, KV.PagedKVCache]:
    """Paged bulk prefill: same compute as :func:`prefill`, committed as
    whole-page scatters at ``pages``."""
    del slot
    logits, rows = _prefill_core(cfg, params, tokens, length)
    return logits, KV.commit_pages(cache, rows, pages)


def _decode_core(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 views: Dict[str, jax.Array], pos: jax.Array):
    """Shared decode compute against (L, B, S, ...) cache views (persistent
    dense leaves or block-table gathers).  tokens: (B, T) with token t of
    row b at position ``pos[b] + t``.  Returns (logits (B, T, V), per-leaf
    new-token rows (L, B, T, ...)).  The expert layer is the dropless
    serving one, so a multi-token verify (T > 1) routes each token as a
    single-token step would."""
    dtype = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    x = L.embed_lookup(params["embed"], tokens, dtype)
    positions = L.position_span(pos, t)

    def body(x, xs):
        bp, layer_cache = xs
        out, _aux, new_cache, counts = _block_apply(
            cfg, bp, x, positions, layer_cache, positions, dtype,
            L.DEFAULT_Q_CHUNK, serve=True)
        return out, (new_cache, counts)

    x, tok_cache = _scan_layers(cfg, params, x, body, views)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_logits(x, params["head"], dtype)
    return logits, tok_cache


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, jax.Array], pos: jax.Array
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens: (B, T) (T = 1 steady state); pos: scalar int32 or (B,)
    per-slot positions of the first token."""
    b, t = tokens.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    logits, tok_cache = _decode_core(cfg, params, tokens, cache, pos)
    # commit the new-token columns into every cache leaf: one per-row scatter
    # each (in-place when the cache is donated into the jitted step; rows
    # past max_len are dropped, not clamped)
    posgrid = L.position_span(pos, t)
    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
    new_cache = {}
    for name, full in cache.items():
        tok = tok_cache[name]                   # (L, B, T, ...)
        new_cache[name] = full.at[:, bidx, posgrid].set(tok, mode="drop")
    return logits, new_cache


def decode_paged(cfg: ModelConfig, params: Params, tokens: jax.Array,
                 cache: KV.PagedKVCache, pos: jax.Array,
                 block_tables: jax.Array
                 ) -> Tuple[jax.Array, KV.PagedKVCache]:
    """Paged decode step: block-table gathers feed the same attention (MLA
    absorbed or GQA), then the new-token rows scatter into their pages."""
    b = tokens.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    views = KV.gather_views(cache, block_tables)
    logits, tok_cache = _decode_core(cfg, params, tokens, views, pos)
    cache = KV.commit_tokens(cache, tok_cache, block_tables, pos)
    return logits, cache
