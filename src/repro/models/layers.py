"""Shared model building blocks (pure-pytree, no framework dependency).

Conventions
-----------
* Params are nested dicts of float32 arrays; compute casts to the config
  dtype (bf16 by default) — mixed precision in the MaxText style.
* Parameter names follow the sharding rules in distributed/sharding.py
  (``attn/wq``, ``mlp/gate``, ...).
* Activation sharding is annotated via :func:`sharding.constrain` with
  logical axes; a no-op in single-device tests.
* Attention is chunked over query blocks (lax.scan) so the score tensor peak
  is ``B*H*q_chunk*S`` — required for the 32k-prefill cells to fit HBM.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain, grad_boundary
from repro.forms import FormsLinearParams
from repro.forms import apply as forms_apply
from repro.forms import to_dense as forms_to_dense

Params = Dict[str, jax.Array]

DEFAULT_Q_CHUNK = 1024


def wload(p: Params, name: str, dtype) -> jax.Array:
    """Weight read with transparent decompression.

    Serving-quantized trees store {"q": int8, "s": f32} per weight
    (serving/quant_weights.py); the dequant multiply fuses into the consuming
    matmul on TPU, so HBM reads stay int8.  FORMS-compressed trees store
    ``FormsLinearParams`` leaves (repro.forms); those are reconstructed
    in-graph — prefer :func:`linear` on matmul hot paths so the polarized
    kernel consumes the (mags, signs) factorization directly.
    """
    v = p[name]
    if isinstance(v, dict) and "q" in v:
        return v["q"].astype(dtype) * v["s"].astype(dtype)
    if isinstance(v, FormsLinearParams):
        return forms_to_dense(v).astype(dtype)
    return v.astype(dtype)


def linear(p: Params, name: str, x: jax.Array, dtype) -> jax.Array:
    """``x @ W`` where ``W = p[name]`` may be dense, int8-quantized or
    FORMS-compressed.

    Compressed 2-D weights (including scan-sliced stacked leaves) route
    through the polarized-matmul kernel so serving consumes the compressed
    pytree directly; anything else falls back to a dense matmul via
    :func:`wload`.

    On a mesh the compressed leaves arrive sharded (co-sharded
    mags/signs/scale, ``distributed/sharding.forms_param_spec``), and the
    sign-folded MVM runs on the per-device shards under GSPMD: N
    (output-column) shards compute their columns locally, K shards sum
    partials across devices — the sign-combine stays device-local because
    K shards always hold whole fragments.
    """
    v = p[name]
    if isinstance(v, FormsLinearParams) and v.mags.ndim == 2:
        return forms_apply(v, x, tag=name).astype(dtype)
    return x @ wload(p, name, dtype)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale)


def embed_init(key, vocab: int, d: int) -> jax.Array:
    return jax.random.normal(key, (vocab, d), jnp.float32) * 0.02


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int) -> jax.Array:
    return jnp.ones((d,), jnp.float32)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * w).astype(x.dtype)


def layernorm_init(d: int) -> Dict[str, jax.Array]:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(x: jax.Array, p: Dict[str, jax.Array], eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(hd: int, theta: float, yarn) -> Tuple[jax.Array, float]:
    """YaRN rotary frequencies of a ``hd``-wide rope (``yarn``: a
    ``configs.base.YarnConfig``) and the factor on cos/sin, as DeepSeek-V3
    computes them: frequencies whose wavelength is short against the
    original context keep theta's, long ones are divided by ``factor``, and
    those between follow a linear ramp over the dims from the ``beta_fast``
    to the ``beta_slow`` rotation count."""
    base = rope_freqs(hd, theta)
    orig = yarn.original_max_position_embeddings

    def dim_at(rotations):
        return (hd * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_at(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_at(yarn.beta_slow)), hd - 1)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    freqs = base / yarn.factor * ramp + base * (1.0 - ramp)
    scale = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    return freqs, scale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None,
               mscale: float = 1.0) -> jax.Array:
    """x: (..., S, H, hd) or (..., S, hd); positions: (S,) or (B, S) int.

    A (B, S) position grid gives every batch row its own timeline — the
    decode path uses (B, 1) so each serving slot rotates by its own position.
    ``freqs`` (default theta's) and ``mscale`` (on cos and sin) carry a
    scaled rope such as :func:`yarn_freqs`.
    """
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    if positions.ndim == 1:
        if x.ndim == 4:   # (B, S, H, hd)
            cos, sin = cos[None, :, None, :], sin[None, :, None, :]
        else:             # (B, S, hd)
            cos, sin = cos[None, :, :], sin[None, :, :]
    else:                 # per-batch positions (B, S)
        if x.ndim == 4:
            cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    xr1 = x1 * cos - x2 * sin
    xr2 = x2 * cos + x1 * sin
    out = jnp.stack([xr1, xr2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attn_init(key, d: int, n_heads: int, n_kv: int, hd: int,
              bias: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, n_heads * hd),
        "wk": dense_init(ks[1], d, n_kv * hd),
        "wv": dense_init(ks[2], d, n_kv * hd),
        "wo": dense_init(ks[3], n_heads * hd, d),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads * hd,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv * hd,), jnp.float32)
    return p


def gqa_scores_softmax_out(qr, k, v, qpos, kpos, window, scale, causal=True):
    """One chunk of grouped-query attention.

    qr: (B, qc, KV, G, hd); k/v: (B, S, KV, hd); positions int32 (qc,), (S,).
    Returns (B, qc, KV, G, hd).

    K/V are expanded to full query heads before the einsums so the score
    tensor shards cleanly on the (divisible) head dim — the grouped (KV, G)
    form breaks GSPMD head sharding whenever KV doesn't divide the model axis
    and forces full f32 score all-gathers (measured: 8 GiB x 96 per step on
    danube).  Operands stay bf16 with f32 accumulation.
    """
    b, qc, kv, g, hd = qr.shape
    s = k.shape[1]
    hdv = v.shape[-1]
    q_full = constrain(qr.reshape(b, qc, kv * g, hd), "batch", None, "model",
                       None)
    k_full = constrain(jnp.repeat(k, g, axis=2), "batch", None, "model", None)
    v_full = constrain(jnp.repeat(v, g, axis=2), "batch", None, "model", None)
    scores = jnp.einsum("bqhd,bshd->bhqs", q_full, k_full,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask = jnp.logical_and(mask, qpos[:, None] - kpos[None, :] < window)
        scores = jnp.where(mask[None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v_full,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype).reshape(b, qc, kv, g, hdv)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     window: Optional[int] = None,
                     q_chunk: int = DEFAULT_Q_CHUNK,
                     positions: Optional[jax.Array] = None,
                     causal: bool = True,
                     scale: Optional[float] = None) -> jax.Array:
    """Chunked (optionally causal) GQA for train/prefill.

    q: (B, S, H, hd); k/v: (B, S, KV, hd).  Scans over ceil(S/q_chunk) query
    chunks with full keys resident — peak scores are (B, H, q_chunk, S).
    ``scale`` multiplies the scores (default ``hd ** -0.5``).
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    hdv = v.shape[-1]   # may differ from hd (MLA: qk dims != v dims)
    g = h // kv
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    qr = q.reshape(b, s, kv, g, hd)
    qc = min(q_chunk, s)
    if s % qc != 0:
        qc = s  # fall back to single chunk for odd smoke-test lengths
    nc = s // qc
    if nc == 1:
        out = gqa_scores_softmax_out(qr, k, v, positions, positions, window,
                                     scale, causal)
        return out.reshape(b, s, h, hdv)

    qs = qr.reshape(b, nc, qc, kv, g, hd).transpose(1, 0, 2, 3, 4, 5)
    ps = positions.reshape(nc, qc)

    def chunk_fn(_, inp):
        qc_blk, qpos = inp
        out = gqa_scores_softmax_out(qc_blk, k, v, qpos, positions, window,
                                     scale, causal)
        return None, out

    _, outs = jax.lax.scan(chunk_fn, None, (qs, ps))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, kv, g, hdv)
    return out.reshape(b, s, h, hdv)


def position_grid(pos: jax.Array, b: int, t: int) -> jax.Array:
    """Normalize decode positions to a (B, T) int32 grid.

    Accepts a scalar, a (B,) per-row vector (every query in a row shares it —
    the single-token decode case), or an explicit (B, T) grid (the bounded
    multi-token decode of speculative verification, where query ``t`` of row
    ``b`` lives at ``pos[b] + t``).
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim <= 1:
        pos = jnp.reshape(pos, (-1, 1))
    return jnp.broadcast_to(pos, (b, t))


def position_span(pos: jax.Array, t: int) -> jax.Array:
    """(B,) first-token positions -> the (B, T) contiguous decode grid
    (token t of row b at ``pos[b] + t``) — the grid every family's
    multi-token decode and cache commit share."""
    pos = jnp.asarray(pos, jnp.int32)
    return pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, window: Optional[int] = None) -> jax.Array:
    """Bounded-token GQA against a cache.

    q: (B, T, H, hd) — T is 1 on the steady-state decode path and K+1 when a
    speculative verify scores a whole draft in one call; caches:
    (B, Smax, KV, hd); pos: scalar, (B,) per-row positions, or a (B, T)
    position grid (see :func:`position_grid`).  Query ``(b, t)`` attends to
    its own cache positions <= pos[b, t] — independent slot timelines, and
    causality between the T new tokens falls out of the same mask (token t
    sits at position pos[b, t] in the transient view written below).

    The cache operands may be persistent dense leaves OR the per-slot
    block-table gathers of a paged pool (serving/kv_cache.gather_views):
    both present the same logically-contiguous (B, Smax, KV, hd) layout,
    and the ``kpos <= pos`` per-slot length mask is what keeps stale rows
    (dense), scratch-page rows (paged) and rejected-draft rows (speculative
    rollback) out of the softmax.
    """
    b, t, h, hd = q.shape
    smax, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qr = q.reshape(b, t, kv, g, hd)
    pos2 = position_grid(pos, b, t)
    # keep the cache operands in their storage dtype and accumulate in f32:
    # .astype(f32) on the cache materializes a full-cache f32 copy inside the
    # decode loop (2x HBM traffic + 2x transient memory)
    scores = jnp.einsum("btkgh,bskh->bkgts", qr.astype(k_cache.dtype), k_cache,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(smax, dtype=jnp.int32)
    mask = kpos[None, None, :] <= pos2[:, :, None]          # (B, T, S)
    if window is not None:
        mask = jnp.logical_and(mask,
                               kpos[None, None, :] > pos2[:, :, None] - window)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, h, hd).astype(v_cache.dtype)


def attention_block(p: Params, x: jax.Array, *, n_heads: int, n_kv: int,
                    hd: int, rope_theta: float,
                    positions: jax.Array,
                    window: Optional[int] = None,
                    q_chunk: int = DEFAULT_Q_CHUNK,
                    cache: Optional[Tuple[jax.Array, jax.Array]] = None,
                    cache_pos: Optional[jax.Array] = None,
                    use_rope: bool = True, causal: bool = True,
                    return_kv: bool = False, dtype=jnp.bfloat16,
                    attend: Optional[Callable[..., jax.Array]] = None):
    """Full attention sub-layer.  Returns (out, new_cache_kv_or_None).

    Train/prefill: ``cache=None`` -> causal self-attention over x;
    ``return_kv=True`` additionally returns the post-rope (k, v) of shape
    (B, S, KV, hd) so bulk prefill can commit them to a cache in one write.
    Decode: ``cache=(k, v)`` of shape (B, Smax, KV, hd) — dense cache
    leaves or paged block-table gathers, see :func:`decode_attention` —
    x is (B, T, d) (T = 1 steady state, K+1 for a speculative verify),
    ``cache_pos`` scalar, (B,) per-row positions, or a (B, T) position
    grid — writes the T new K/V rows at their positions and attends.  The
    write targets a local TRANSIENT view either way; the caller commits
    the returned new-token K/V to the persistent cache (slot scatter or
    page scatter) after the layer scan.
    Paged single-token decode: ``attend(q, k, v)`` reads the cache itself
    (the page pool, in place) given the new token's post-rope q/k/v and
    returns the (B, T, H, hd) attention output; the new-token K/V are
    returned for the caller to commit, as above.
    """
    b, s, d = x.shape
    # Megatron-SP: gather the seq-sharded residual before the projections;
    # grad_boundary keeps the backward cotangent bf16 + seq-sharded
    x = grad_boundary(x, ("batch", "model", None))
    x = constrain(x, "batch", None, None)
    q = linear(p, "wq", x, dtype)
    k = linear(p, "wk", x, dtype)
    v = linear(p, "wv", x, dtype)
    if "bq" in p:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    q = q.reshape(b, s, n_heads, hd)
    k = k.reshape(b, s, n_kv, hd)
    v = v.reshape(b, s, n_kv, hd)
    q = constrain(q, "batch", None, "model", None)
    k = constrain(k, "batch", None, "model", None)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    if attend is not None:
        out = attend(q, k, v)
        new_cache = (k, v)
    elif cache is None:
        out = causal_attention(q, k, v, window=window, q_chunk=q_chunk,
                               positions=positions, causal=causal)
        new_cache = (k, v) if return_kv else None
    else:
        # write the tokens into a local (transient) view for attention, but
        # return only the new-token K/V — the caller commits them with ONE
        # token-column write after the layer scan, keeping the persistent
        # cache update in-place instead of restacking full caches (scan ys).
        k_cache, v_cache = cache
        k_t, v_t = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
        posgrid = position_grid(cache_pos, b, s)
        bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
        k_cache = k_cache.at[bidx, posgrid].set(k_t)
        v_cache = v_cache.at[bidx, posgrid].set(v_t)
        out = decode_attention(q, k_cache, v_cache, posgrid, window=window)
        new_cache = (k_t, v_t)
    out = out.reshape(b, s, n_heads * hd)
    out = linear(p, "wo", out, dtype)
    return constrain(out, "batch", "model", None), new_cache


def cross_attention_block(p: Params, x: jax.Array, enc: jax.Array, *,
                          n_heads: int, hd: int, dtype=jnp.bfloat16):
    """Encoder-decoder cross attention (whisper decoder). MHA, no mask."""
    b, s, d = x.shape
    se = enc.shape[1]
    q = linear(p, "wq", x, dtype).reshape(b, s, n_heads, hd)
    k = linear(p, "wk", enc, dtype).reshape(b, se, n_heads, hd)
    v = linear(p, "wv", enc, dtype).reshape(b, se, n_heads, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, v.astype(jnp.float32)).astype(dtype)
    return linear(p, "wo", out.reshape(b, s, n_heads * hd), dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_init(key, d: int, f: int) -> Params:
    ks = jax.random.split(key, 3)
    return {"gate": dense_init(ks[0], d, f), "up": dense_init(ks[1], d, f),
            "down": dense_init(ks[2], f, d)}


_MLP_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def sparsify_fragments(x: jax.Array, m: int, drop_frac: float) -> jax.Array:
    """Zero all but the top-``(1 - drop_frac)`` fragments of each row.

    Fragment-structured activation sparsification (the paper's zero-skip
    granularity, §IV-B): rank whole m-wide input groups by max |x| and zero
    the weakest ``drop_frac`` of them, so the sparsity the zero-skipping
    kernels see is aligned with the fragment layout they can actually skip.
    Unstructured (per-element) sparsity collapses at fragment granularity —
    a fragment survives if *any* of its m elements is nonzero — which is why
    this drops whole fragments.  Ties at the threshold may keep more than
    the budget (exact zeros never count as kept work).
    """
    if drop_frac <= 0.0:
        return x
    if not 0.0 < drop_frac < 1.0:
        raise ValueError(f"drop_frac must be in [0, 1), got {drop_frac}")
    K = x.shape[-1]
    if K % m:
        raise ValueError(
            f"feature dim {K} does not tile into fragments of m={m}; "
            f"align act_fragment with the layer width (or pad the model)")
    F = K // m
    keep = max(1, int(round(F * (1.0 - drop_frac))))
    xf = x.reshape(*x.shape[:-1], F, m)
    strength = jnp.max(jnp.abs(xf.astype(jnp.float32)), axis=-1)  # (..., F)
    kth = -jnp.sort(-strength, axis=-1)[..., keep - 1:keep]       # threshold
    mask = strength >= kth
    return (xf * mask[..., None].astype(xf.dtype)).reshape(x.shape)


def swiglu(p: Params, x: jax.Array, dtype=jnp.bfloat16, act: str = "silu",
           frag_drop: float = 0.0, frag_m: int = 8) -> jax.Array:
    """Gated MLP; ``act`` picks the gate nonlinearity (silu/gelu/relu).

    ``frag_drop > 0`` sparsifies the hidden activations at whole-fragment
    granularity before the down projection, so the zero-skipping matmul
    path (``FormsSpec(zero_skip=...)``) has dead fragments to skip.
    """
    x = grad_boundary(x, ("batch", "model", None))
    x = constrain(x, "batch", None, None)   # Megatron-SP gather
    h = _MLP_ACTS[act](linear(p, "gate", x, dtype)) * linear(p, "up", x, dtype)
    if frag_drop > 0.0:
        h = sparsify_fragments(h, frag_m, frag_drop)
    h = constrain(h, "batch", None, "model")
    return constrain(linear(p, "down", h, dtype), "batch", "model", None)


def gelu_mlp_init(key, d: int, f: int) -> Params:
    ks = jax.random.split(key, 2)
    return {"up": dense_init(ks[0], d, f), "down": dense_init(ks[1], f, d),
            "b_up": jnp.zeros((f,), jnp.float32), "b_down": jnp.zeros((d,), jnp.float32)}


def gelu_mlp(p: Params, x: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    x = grad_boundary(x, ("batch", "model", None))
    x = constrain(x, "batch", None, None)   # Megatron-SP gather
    h = jax.nn.gelu(linear(p, "up", x, dtype) + wload(p, "b_up", dtype))
    h = constrain(h, "batch", None, "model")
    return constrain(linear(p, "down", h, dtype) + wload(p, "b_down", dtype),
                     "batch", "model", None)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed_lookup(embed: jax.Array, tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    out = jnp.take(embed.astype(dtype), tokens, axis=0)
    # sequence-parallel residual stream (Megatron-SP): the seq dim shards over
    # the model axis between blocks; GSPMD inserts AG/RS at attention/MLP edges
    return constrain(out, "batch", "model", None)


def lm_logits(x: jax.Array, head: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    with jax.named_scope("lm_head"):
        if isinstance(head, FormsLinearParams) and head.mags.ndim == 2:
            logits = forms_apply(head, x, tag="head").astype(dtype)
        else:
            logits = x @ head.astype(dtype)
        return constrain(logits, "batch", None, "model")
