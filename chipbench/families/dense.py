"""The dense family: decoder-only transformers with GQA and SwiGLU.

A family module (``chipbench/families/<family>.py``) is found by the
``"family"`` of a configuration file, so a configuration of another family
comes as one new file here.  Nothing in a family imports the program.  It
holds

* ``engine_params(mc, forms, seed)``: the weights handed to
  ``ServingEngine``, in a form the engine accepts, made from the seed;
* ``gaps(mc, forms, seed, prompts, served, pad_len, pad_served, control)``:
  per served token of the requests given, the float32 reference's best
  logit minus its logit of the served token, or with ``control`` of the
  token the control ranks first.  The family builds its reference weights
  from the seed itself, projected as FORMS;
* the work counts that metric readers take as ``run.work``, under the
  conventions of ``work.py``: ``projected_matmuls``, ``matmul_params``,
  ``token_flops``, ``decode_flops``, ``prefill_flops``,
  ``kv_bytes_per_position``, ``decode_weight_bytes``,
  ``kernel_least_seconds``.

Memory: a new family's generator must not need the whole float32 tree at
once.  Derive each layer's key from the seed and the layer index (for
example ``jax.random.fold_in(weight_key(seed), layer)``), so that the
reference can rebuild, project and run one layer at a time.  This family
makes its whole tree in one call (6 GB for qwen2-1.5b) and keeps that
generator, so that its cells keep their weights.

This family holds

* its weight generator: random float32 weights of a dense decoder-only
  transformer, made from the seed in one jitted call, in the parameter
  layout the program's dense family reads (``embed``, stacked ``blocks``,
  ``final_norm``, an untied ``head``);
* a plain FORMS projection of those weights: per m-row fragment a sign
  (the one that keeps more squared magnitude), entries of the other sign
  zeroed, then per-column max-abs scaling onto 2**bits - 1 magnitude
  levels, round to nearest.  Every matmul weight of the blocks and an
  untied head are projected; the embedding, norms and biases stay float32;
* a straightforward float32 forward pass at ``highest`` matmul precision
  (RMSNorm, GQA with interleaved-pair RoPE and an optional sliding window,
  SwiGLU), computed layer by layer and in query blocks so that it fits
  next to nothing else on one chip;
* the control: the same forward with every matmul operand rounded to 8-bit
  floating point (e4m3, per-tensor scale), the precision step below the
  configuration's bfloat16;
* its operation and byte counts.

``request_gaps`` compares one request's served tokens with the reference:
at each position where a token was served, how far the reference's logit of
that token lies below the reference's best logit.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import work

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
HEAD_BLOCK = 256
# the projected matmul weights of a block (the program's crossbar weights)
PROJECTED = ("wq", "wk", "wv", "wo", "gate", "up", "down")


def weight_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (also beyond 32 bits)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                    impl="threefry2x32")


def _shapes(mc: Dict) -> Tuple[int, ...]:
    return (mc["num_layers"], mc["d_model"], mc["num_heads"],
            mc["num_kv_heads"], mc["head_dim"], mc["d_ff"], mc["vocab_size"])


def _init(key: jax.Array, mc: Dict) -> Dict:
    L, d, h, kv, hd, f, v = _shapes(mc)
    ks = iter(jax.random.split(key, 16))

    def dense(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) / np.sqrt(fan_in)

    def small(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    attn = {"wq": dense((L, d, h * hd), d), "wk": dense((L, d, kv * hd), d),
            "wv": dense((L, d, kv * hd), d), "wo": dense((L, h * hd, d), h * hd)}
    if mc["qkv_bias"]:
        attn.update(bq=small((L, h * hd), 0.02), bk=small((L, kv * hd), 0.02),
                    bv=small((L, kv * hd), 0.02))
    params = {
        "embed": small((v, d), 0.02),
        "blocks": {
            "norm1": 1.0 + small((L, d), 0.1), "attn": attn,
            "norm2": 1.0 + small((L, d), 0.1),
            "mlp": {"gate": dense((L, d, f), d), "up": dense((L, d, f), d),
                    "down": dense((L, f, d), f)}},
        "final_norm": 1.0 + small((d,), 0.1),
    }
    if not mc["tie_embeddings"]:
        params["head"] = small((d, v), 0.02)
    return params


@functools.lru_cache(maxsize=None)
def _init_fn(frozen: Tuple) -> callable:
    mc = dict(frozen)
    return jax.jit(lambda key: _init(key, mc))


def make_params(mc: Dict, seed: int) -> Dict:
    """The float32 weights of one run, made on the device in one call."""
    return _init_fn(tuple(sorted(mc.items())))(weight_key(seed))


def project(w: jax.Array, m: int, bits: int) -> jax.Array:
    """FORMS projection of ``(..., K, N)`` weights (K a multiple of m)."""
    *lead, k, n = w.shape
    frs = w.reshape(*lead, k // m, m, n)
    pos = jnp.sum(jnp.square(jnp.maximum(frs, 0.0)), axis=-2, keepdims=True)
    neg = jnp.sum(jnp.square(jnp.minimum(frs, 0.0)), axis=-2, keepdims=True)
    sign = jnp.where(pos >= neg, 1.0, -1.0)
    kept = jnp.where(frs * sign >= 0, frs, 0.0).reshape(w.shape)
    levels = 2 ** bits - 1
    scale = jnp.maximum(jnp.max(jnp.abs(kept), axis=-2, keepdims=True),
                        1e-12) / levels
    codes = jnp.clip(jnp.round(kept / scale), -levels, levels)
    return codes * scale


def project_params(params: Dict, m: int, bits: int) -> Dict:
    """The weights the compressed model serves, as float32 (jitted, donated:
    the dense tree is consumed)."""
    def fn(p):
        out = dict(p)
        blocks = dict(p["blocks"])
        blocks["attn"] = {k: project(w, m, bits) if k in PROJECTED else w
                          for k, w in p["blocks"]["attn"].items()}
        blocks["mlp"] = {k: project(w, m, bits)
                         for k, w in p["blocks"]["mlp"].items()}
        out["blocks"] = blocks
        if "head" in p:
            out["head"] = project(p["head"], m, bits)
        return out
    return jax.jit(fn, donate_argnums=0)(params)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fp8(x: jax.Array) -> jax.Array:
    """Round to 8-bit floating point (4 exponent, 3 mantissa bits) under a
    per-tensor scale that maps the largest magnitude near the top of the
    range."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 224.0
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def _mm(a, b, low):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Interleaved-pair rotary embedding of ``(S, heads, hd)``."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, window, low):
    """Causal GQA over ``(S, H, hd)`` queries in blocks of Q_BLOCK rows."""
    s, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    scale = 1.0 / np.sqrt(hd)
    kpos = jnp.arange(s)
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, kvh, g, hd)

    def block(_, inp):
        qblk, i = inp
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        a = qblk.transpose(1, 2, 0, 3)                     # (kv, g, qb, hd)
        kt = k.transpose(1, 2, 0)                          # (kv, hd, S)
        if low:
            a, kt = _fp8(a), _fp8(kt)
        sc = jnp.einsum("kgqd,kds->kgqs", a, kt, precision=HIGHEST) * scale
        mask = qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
        vv = v.transpose(1, 0, 2)                          # (kv, S, hd)
        if low:
            p, vv = _fp8(p), _fp8(vv)
        o = jnp.einsum("kgqs,ksd->qkgd", p, vv, precision=HIGHEST)
        return None, o.reshape(Q_BLOCK, h, hd)

    _, out = jax.lax.scan(block, None, (qb, jnp.arange(s // Q_BLOCK)))
    return out.reshape(s, h, hd)


def _hidden(params, tokens, mc, low):
    """Final-norm hidden states of one sequence ``(S,)`` -> ``(S, d)``."""
    h, kvh, hd = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"]
    eps, theta = mc["norm_eps"], mc["rope_theta"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = params["embed"][tokens]

    def layer(x, bp):
        a = bp["attn"]
        y = _rmsnorm(x, bp["norm1"], eps)
        q, k, v = (_mm(y, a[w], low) for w in ("wq", "wk", "wv"))
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(s, h, hd), pos, theta)
        k = _rope(k.reshape(s, kvh, hd), pos, theta)
        o = _attention(q, k, v.reshape(s, kvh, hd), mc["sliding_window"], low)
        x = x + _mm(o.reshape(s, h * hd), a["wo"], low)
        y = _rmsnorm(x, bp["norm2"], eps)
        mlp = bp["mlp"]
        z = jax.nn.silu(_mm(y, mlp["gate"], low)) * _mm(y, mlp["up"], low)
        return x + _mm(z, mlp["down"], low), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return _rmsnorm(x, params["final_norm"], eps)


def _head(params):
    return params["head"] if "head" in params else params["embed"].T


def _logits(params, x, low):
    """Logits of ``(P, d)`` hidden rows in blocks of HEAD_BLOCK rows."""
    head = _head(params)
    xb = x.reshape(-1, HEAD_BLOCK, x.shape[-1])
    return jax.lax.map(lambda r: _mm(r, head, low), xb).reshape(
        x.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _gaps_program(params, tokens, idx, served, frozen):
    mc = dict(frozen)
    lg = _logits(params, _hidden(params, tokens, mc, False)[idx], False)
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
    return best - got


@functools.partial(jax.jit, static_argnames=("frozen",))
def _gaps_control(params, tokens, idx, frozen):
    mc = dict(frozen)
    lg = _logits(params, _hidden(params, tokens, mc, False)[idx], False)
    low = _logits(params, _hidden(params, tokens, mc, True)[idx], True)
    pick = jnp.argmax(low, axis=-1)
    got = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - got


def _pad(n: int, block: int) -> int:
    return -(-n // block) * block


def request_gaps(params: Dict, mc: Dict, prompt: np.ndarray,
                 served: np.ndarray, pad_len: int, pad_served: int,
                 control: bool = False) -> np.ndarray:
    """Per served token, the reference's best logit minus its logit of the
    served token (``control=True``: of the token the 8-bit forward ranks
    first at that position).

    The sequence ``prompt + served[:-1]`` is padded to ``pad_len`` and the
    served positions to ``pad_served`` so that one compiled program serves
    every request of a cell; padding sits after the real positions and the
    causal mask keeps it out of them.
    """
    n, t = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    pad_len = _pad(max(pad_len, len(seq)), Q_BLOCK)
    pad_served = _pad(max(pad_served, t), HEAD_BLOCK)
    tokens = np.zeros(pad_len, np.int32)
    tokens[:len(seq)] = seq
    idx = np.zeros(pad_served, np.int32)
    idx[:t] = n - 1 + np.arange(t)
    tok = np.zeros(pad_served, np.int32)
    tok[:t] = served
    frozen = tuple(sorted(mc.items()))
    if control:
        out = _gaps_control(params, tokens, idx, frozen)
    else:
        out = _gaps_program(params, tokens, idx, tok, frozen)
    return np.asarray(out)[:t]


def gaps(mc: Dict, forms: Dict, seed: int, prompts: Sequence[np.ndarray],
         served: Sequence[np.ndarray], pad_len: int, pad_served: int,
         control: bool = False) -> np.ndarray:
    """``request_gaps`` of each request in turn, concatenated, against the
    seed's weights projected as ``forms`` (freed before returning)."""
    params = project_params(make_params(mc, seed), forms["m"], forms["bits"])
    out = [request_gaps(params, mc, p, np.asarray(s, np.int32), pad_len,
                        pad_served, control=control)
           for p, s in zip(prompts, served)]
    del params
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def engine_params(mc: Dict, forms: Dict, seed: int) -> Dict:
    """The float32 tree of the seed; the engine compresses it to ``forms``."""
    return make_params(mc, seed)


# --------------------------------------------------------------------------
# operations and bytes (work.py's conventions)
# --------------------------------------------------------------------------

def layer_matmuls(mc: Dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of the matmuls of one block."""
    _, d, h, kv, hd, f, _ = _shapes(mc)
    return [("wq", d, h * hd), ("wk", d, kv * hd), ("wv", d, kv * hd),
            ("wo", h * hd, d), ("gate", d, f), ("up", d, f), ("down", f, d)]


def projected_matmuls(mc: Dict) -> List[Tuple[str, int, int, int]]:
    """(name, K, N, calls per token step) of every matmul the polarized
    kernel serves: the block matmuls once per layer, an untied head once."""
    L, d, *_, v = _shapes(mc)
    out = [(n, k, nn, L) for n, k, nn in layer_matmuls(mc)]
    if not mc["tie_embeddings"]:
        out.append(("head", d, v, 1))
    return out


def matmul_params(mc: Dict) -> int:
    """Matmul parameters touched per token, the head included."""
    L, d, *_, v = _shapes(mc)
    return L * sum(k * n for _, k, n in layer_matmuls(mc)) + d * v


def attended(mc: Dict, ctx: int) -> int:
    """Positions one query attends to at context length ``ctx``."""
    w = mc.get("sliding_window")
    return min(ctx, w) if w else ctx


def token_flops(mc: Dict, ctx: int) -> float:
    """Model operations of one token whose query sees ``ctx`` positions."""
    L, _, h, _, hd, *_ = _shapes(mc)
    return 2.0 * matmul_params(mc) + 4.0 * L * h * hd * attended(mc, ctx)


def decode_flops(mc: Dict, tokens: int, positions: int) -> float:
    """Operations of ``tokens`` decoded tokens whose queries read
    ``positions`` K/V positions in all (per layer)."""
    L, _, h, _, hd, *_ = _shapes(mc)
    return 2.0 * matmul_params(mc) * tokens + 4.0 * L * h * hd * positions


def prefill_flops(mc: Dict, start: int, n: int) -> float:
    """Operations of prompt tokens at positions ``start .. start + n - 1``."""
    L, _, h, _, hd, *_ = _shapes(mc)
    w = mc.get("sliding_window")
    if w:
        ctx = sum(attended(mc, p + 1) for p in range(start, start + n))
    else:
        ctx = n * start + n * (n + 1) // 2
    return 2.0 * matmul_params(mc) * n + 4.0 * L * h * hd * ctx


def kv_bytes_per_position(mc: Dict) -> int:
    L, _, _, kv, hd, *_ = _shapes(mc)
    return L * 2 * kv * hd * work.BF16


def decode_weight_bytes(mc: Dict, forms: Dict, rows: int) -> float:
    """Weight bytes one decode step over ``rows`` token rows must read."""
    L, d, h, kv, hd, f, v = _shapes(mc)
    total = sum(calls * work.projected_bytes(k, n, forms)
                for _, k, n, calls in projected_matmuls(mc))
    if mc["tie_embeddings"]:
        total += v * d * work.BF16                # the embedding as head
    total += rows * d * work.BF16                 # embedding rows looked up
    total += (2 * L + 1) * d * work.BF16          # norms
    if mc["qkv_bias"]:
        total += L * (h + 2 * kv) * hd * work.BF16
    return total


def kernel_least_seconds(mc: Dict, forms: Dict, rows: int, steps: int,
                         peak: Dict) -> Tuple[float, float]:
    """``work.least_seconds`` of this configuration's projected matmuls."""
    return work.least_seconds(projected_matmuls(mc), forms, rows, steps, peak)
