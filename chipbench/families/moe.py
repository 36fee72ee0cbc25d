"""The moe family: DeepSeek-V3-style decoders served as one chip's share of
an expert-parallel deployment.

The family contract (``engine_params``, ``gaps``, the work counts) is set
out in ``chipbench/families/dense.py``; nothing here imports the program.

A layer is MLA attention (expanded form, YaRN rope) followed either, for
the ``first_k_dense_replace`` leading layers, by a SwiGLU MLP of width
``d_ff``, or by an expert layer: a float32 router over all
``num_experts`` (sigmoid scores, a selection-only correction bias, the top
``topk_group`` of ``n_group`` groups scored by the sum of their top two,
the top ``experts_per_token`` among those, weights the un-biased scores
normalized over them times ``routed_scaling_factor``), of which this chip
holds experts ``expert_first`` .. ``expert_first + experts_held - 1``, and
a shared expert.  A layer's output is its held experts' part for the rows
routed to them plus the shared expert's: what the absent experts would add
lies on other chips and is left out, in the program and here alike.

Weights as the engine takes them (``engine_params``): plain arrays and
callables.  The top-level leaves (``embed``, ``final_norm``, ``head``) are
float32 arrays; each stacked layer group (``dense_blocks``, ``blocks``) is
a list of zero-argument callables, the i-th making layer i's float32 tree
(the group's leaves without their leading layer axis).  The engine makes,
compresses and frees one layer at a time and stacks the compressed leaves
(a "layer stream", ``repro/forms/tree.py``), so set-up holds at most one
layer's float32 weights.  Layer ``l`` (counting every layer, the dense
ones first) is made from ``jax.random.fold_in(weight_key(seed), l)``; the
embedding, final norm and head from ``fold_in(weight_key(seed),
num_layers)``.  The configuration's ``model`` holds the program's
configuration fields, the MLA sizes under ``mla`` and YaRN under
``rope_scaling``.

This family holds

* its weight generator, one layer at a time, in the parameter layout the
  program's moe family reads;
* a plain FORMS projection, as ``dense.py``'s (per m-row fragment the sign
  that keeps more squared magnitude, per-column max-abs scales onto
  2**bits - 1 levels, round to nearest) of every weight the program
  compresses: MLA's five projections, the dense MLP, the held experts (per
  expert), the shared expert and the head; the embedding, norms, router
  and bias stay float32;
* a straightforward float32 forward at ``highest`` matmul precision, made
  and run one layer at a time over every request checked, attention in
  query blocks;
* the control: the same forward with every matmul operand rounded to 8-bit
  floating point (e4m3, per-tensor scale), the precision step below the
  configuration's bfloat16;
* ``logits`` and ``held_routes``: the reference's logits and held
  experts at every position of one sequence, for comparisons with the
  program beyond the served tokens;
* its operation and byte counts (``work.py``'s conventions).

Departures from the published model, each in the program too: the rope
pairs interleaved dims (as DeepSeek's own inference code does; the Hugging
Face port permutes them to halves), and the multi-token-prediction module
is not served.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import work

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
HEAD_BLOCK = 256
# matmul weights the program compresses, by the dict that holds them
PROJECTED = {"mla": ("q_down", "q_up", "kv_down", "kv_up", "wo"),
             "mlp": ("gate", "up", "down"),
             "moe": ("w_gate", "w_up", "w_down", "shared_gate", "shared_up",
                     "shared_down")}


def weight_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (also beyond 32 bits)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                    impl="threefry2x32")


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
    return jnp.clip(jnp.round(kept / scale), -levels, levels) * scale


def _fp8(x: jax.Array) -> jax.Array:
    """Round to 8-bit floating point (e4m3) under a per-tensor scale that
    maps the largest magnitude near the top of the range."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 224.0
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def _pad(n: int, block: int) -> int:
    return -(-n // block) * block


def _frozen(mc: Dict) -> Tuple:
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in mc.items()))


def _thaw(frozen: Tuple) -> Dict:
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in frozen}


def held(mc: Dict) -> int:
    return mc.get("experts_held") or mc["num_experts"]


def _dense_layers(mc: Dict) -> int:
    return mc["first_k_dense_replace"]


# --------------------------------------------------------------------------
# weights, one layer at a time
# --------------------------------------------------------------------------

def _init_layer(key: jax.Array, mc: Dict, dense: bool) -> Dict:
    d, h, m = mc["d_model"], mc["num_heads"], mc["mla"]
    ql, r, rope = m["q_lora_rank"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    nope, dv = m["qk_nope_head_dim"], m["v_head_dim"]
    ks = iter(jax.random.split(key, 24))

    def mat(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) / np.sqrt(fan_in)

    def norm(n):
        return 1.0 + 0.1 * jax.random.normal(next(ks), (n,), jnp.float32)

    layer = {
        "norm1": norm(d), "norm2": norm(d),
        "mla": {"q_down": mat((d, ql), d), "q_norm": norm(ql),
                "q_up": mat((ql, h * (nope + rope)), ql),
                "kv_down": mat((d, r + rope), d), "kv_norm": norm(r),
                "kv_up": mat((r, h * (nope + dv)), r),
                "wo": mat((h * dv, d), h * dv)}}
    if dense:
        f = mc["d_ff"]
        layer["mlp"] = {"gate": mat((d, f), d), "up": mat((d, f), d),
                        "down": mat((f, d), f)}
        return layer
    n, f, e = held(mc), mc["moe_d_ff"], mc["num_experts"]
    fs = f * mc["num_shared_experts"]
    layer["moe"] = {
        "router": mat((d, e), d),
        "w_gate": mat((n, d, f), d), "w_up": mat((n, d, f), d),
        "w_down": mat((n, f, d), f),
        "shared_gate": mat((d, fs), d), "shared_up": mat((d, fs), d),
        "shared_down": mat((fs, d), fs)}
    if mc["router_bias"]:
        layer["moe"]["router_bias"] = 0.01 * jax.random.normal(
            next(ks), (e,), jnp.float32)
    return layer


def _init_top(key: jax.Array, mc: Dict) -> Dict:
    d, v = mc["d_model"], mc["vocab_size"]
    k1, k2, k3 = jax.random.split(key, 3)
    return {"embed": 0.02 * jax.random.normal(k1, (v, d), jnp.float32),
            "final_norm": 1.0 + 0.1 * jax.random.normal(k2, (d,), jnp.float32),
            "head": 0.02 * jax.random.normal(k3, (d, v), jnp.float32)}


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen: Tuple, dense: bool):
    mc = _thaw(frozen)
    return jax.jit(lambda key: _init_layer(key, mc, dense))


@functools.lru_cache(maxsize=None)
def _top_fn(frozen: Tuple):
    mc = _thaw(frozen)
    return jax.jit(lambda key: _init_top(key, mc))


def make_layer(mc: Dict, seed: int, layer: int) -> Dict:
    """Layer ``layer``'s float32 weights (the dense ones come first)."""
    key = jax.random.fold_in(weight_key(seed), layer)
    return _layer_fn(_frozen(mc), layer < _dense_layers(mc))(key)


def make_top(mc: Dict, seed: int) -> Dict:
    """The embedding, final norm and head, float32."""
    key = jax.random.fold_in(weight_key(seed), mc["num_layers"])
    return _top_fn(_frozen(mc))(key)


def engine_params(mc: Dict, forms: Dict, seed: int) -> Dict:
    """The top-level float32 leaves and a layer stream per stacked group:
    the engine makes and compresses one layer at a time."""
    k, n = _dense_layers(mc), mc["num_layers"]
    stream = lambda lo, hi: [functools.partial(make_layer, mc, seed, i)
                             for i in range(lo, hi)]
    params = dict(make_top(mc, seed), blocks=stream(k, n))
    if k:
        params["dense_blocks"] = stream(0, k)
    return params


def _project_tree(tree: Dict, m: int, bits: int) -> Dict:
    out = dict(tree)
    for group, names in PROJECTED.items():
        if group in tree:
            out[group] = {k: project(w, m, bits) if k in names else w
                          for k, w in tree[group].items()}
    if "head" in tree:
        out["head"] = project(tree["head"], m, bits)
    return out


@functools.partial(jax.jit, static_argnames=("m", "bits"), donate_argnums=0)
def project_tree(tree: Dict, m: int, bits: int) -> Dict:
    """The weights the compressed model serves, as float32 (the float tree
    is consumed)."""
    return _project_tree(tree, m, bits)


# --------------------------------------------------------------------------
# forward, one layer at a time
# --------------------------------------------------------------------------

def _mm(a, b, low):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rope_table(mc: Dict) -> Tuple[np.ndarray, float, float]:
    """(rope frequencies, factor on cos and sin, softmax scale) of MLA's
    rope part, YaRN as DeepSeek-V3 defines it (``yarn_find_correction_range``
    and ``yarn_linear_ramp_mask`` of its modeling code)."""
    m, theta = mc["mla"], mc["rope_theta"]
    dim = m["qk_rope_head_dim"]
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    y = mc.get("rope_scaling")
    if not y:
        return base.astype(np.float32), 1.0, scale
    orig = y["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq_mask = 1.0 - ramp
    freqs = base / y["factor"] * (1 - inv_freq_mask) + base * inv_freq_mask
    mscale = (_yarn_mscale(y["factor"], y["mscale"])
              / _yarn_mscale(y["factor"], y["mscale_all_dim"]))
    if y["mscale_all_dim"]:
        scale *= _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return freqs.astype(np.float32), mscale, scale


def _rope(x, pos, freqs, mscale):
    """Interleaved-pair rotary embedding of ``(S, heads, dim)``."""
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, scale, low):
    """Causal attention over ``(S, H, .)`` queries in blocks of Q_BLOCK."""
    s, h, _ = q.shape
    kpos = jnp.arange(s)
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, h, -1)
    kt, vv = k.transpose(1, 2, 0), v.transpose(1, 0, 2)     # (h,dk,S), (h,S,dv)
    if low:
        kt, vv = _fp8(kt), _fp8(vv)

    def block(_, inp):
        qblk, i = inp
        a = qblk.transpose(1, 0, 2)                          # (h, qb, dk)
        if low:
            a = _fp8(a)
        sc = jnp.einsum("hqd,hds->hqs", a, kt, precision=HIGHEST) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        p = jax.nn.softmax(jnp.where(qpos[:, None] >= kpos[None, :], sc,
                                     -1e30), axis=-1)
        if low:
            p = _fp8(p)
        return None, jnp.einsum("hqs,hsd->qhd", p, vv, precision=HIGHEST)

    _, out = jax.lax.scan(block, None, (qb, jnp.arange(s // Q_BLOCK)))
    return out.reshape(s, h, -1)


def _mla(a, x, mc, low):
    m, h, eps = mc["mla"], mc["num_heads"], mc["norm_eps"]
    r, nope = m["kv_lora_rank"], m["qk_nope_head_dim"]
    freqs, mscale, scale = rope_table(mc)
    s = x.shape[0]
    pos = jnp.arange(s)
    q = _mm(_rmsnorm(_mm(x, a["q_down"], low), a["q_norm"], eps), a["q_up"],
            low).reshape(s, h, -1)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], pos, freqs, mscale)], axis=-1)
    kv = _mm(x, a["kv_down"], low)
    c = _rmsnorm(kv[:, :r], a["kv_norm"], eps)
    k_rope = _rope(kv[:, None, r:], pos, freqs, mscale)
    kvu = _mm(c, a["kv_up"], low).reshape(s, h, -1)
    k = jnp.concatenate([kvu[..., :nope],
                         jnp.broadcast_to(k_rope, (s, h, k_rope.shape[-1]))],
                        axis=-1)
    o = _attention(q, k, kvu[..., nope:], scale, low)
    return _mm(o.reshape(s, -1), a["wo"], low)


def route(p, x, mc, low):
    """(gates of the held experts (S, held), their routed mask) of rows
    ``x`` (S, d)."""
    scores = jax.nn.sigmoid(_mm(x, p["router"], low))
    choice = scores + p["router_bias"] if "router_bias" in p else scores
    s, e = choice.shape
    g = mc["n_group"]
    if g > 1:
        top2 = jax.lax.top_k(choice.reshape(s, g, e // g), 2)[0].sum(-1)
        _, kept = jax.lax.top_k(top2, mc["topk_group"])
        keep = jnp.zeros((s, g), bool).at[jnp.arange(s)[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(keep, e // g, axis=-1), choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, mc["experts_per_token"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if mc["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * mc["routed_scaling_factor"]
    ids = mc.get("expert_first", 0) + jnp.arange(held(mc))
    hit = idx[:, :, None] == ids                             # (S, k, held)
    return jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1), hit.any(1)


def _swiglu(x, gate, up, down, low):
    return _mm(jax.nn.silu(_mm(x, gate, low)) * _mm(x, up, low), down, low)


def _experts(p, x, mc, low):
    gates, _ = route(p, x, mc, low)
    y = _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"], low)
    for e in range(held(mc)):
        y = y + gates[:, e:e + 1] * _swiglu(x, p["w_gate"][e], p["w_up"][e],
                                            p["w_down"][e], low)
    return y


def _layer(lp, x, mc, low):
    eps = mc["norm_eps"]
    x = x + _mla(lp["mla"], _rmsnorm(x, lp["norm1"], eps), mc, low)
    y = _rmsnorm(x, lp["norm2"], eps)
    if "mlp" in lp:
        f = lp["mlp"]
        return x + _swiglu(y, f["gate"], f["up"], f["down"], low)
    return x + _experts(lp["moe"], y, mc, low)


@functools.partial(jax.jit, static_argnames=("frozen", "low"))
def _layer_program(lp, x, frozen, low):
    return _layer(lp, x, _thaw(frozen), low)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _routes_program(lp, x, frozen):
    mc = _thaw(frozen)
    eps = mc["norm_eps"]
    x = x + _mla(lp["mla"], _rmsnorm(x, lp["norm1"], eps), mc, False)
    return route(lp["moe"], _rmsnorm(x, lp["norm2"], eps), mc, False)[1]


def _logits(top, x, low):
    xb = x.reshape(-1, HEAD_BLOCK, x.shape[-1])
    return jax.lax.map(lambda r: _mm(r, top["head"], low), xb).reshape(
        x.shape[0], -1)


def _hidden_at(top, x, idx, eps):
    return _rmsnorm(x[idx], top["final_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _gaps_program(top, x, idx, served, eps):
    lg = _logits(top, _hidden_at(top, x, idx, eps), False)
    got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - got


@functools.partial(jax.jit, static_argnames=("eps",))
def _gaps_control(top, x, x_low, idx, eps):
    lg = _logits(top, _hidden_at(top, x, idx, eps), False)
    low = _logits(top, _hidden_at(top, x_low, idx, eps), True)
    got = jnp.take_along_axis(lg, jnp.argmax(low, axis=-1)[:, None],
                              axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - got


def _sequences(prompts, served, pad_len, pad_served):
    """Per request the padded sequence ``prompt + served[:-1]``, the
    positions whose next token was served and those tokens."""
    longest = max(len(p) + len(s) - 1 for p, s in zip(prompts, served))
    pad_len = _pad(max(pad_len, longest), Q_BLOCK)
    pad_served = _pad(max(pad_served, max(len(s) for s in served)),
                      HEAD_BLOCK)
    out = []
    for p, s in zip(prompts, served):
        s = np.asarray(s, np.int32)
        tokens = np.zeros(pad_len, np.int32)
        seq = np.concatenate([p, s[:-1]])
        tokens[:len(seq)] = seq
        idx = np.zeros(pad_served, np.int32)
        idx[:len(s)] = len(p) - 1 + np.arange(len(s))
        tok = np.zeros(pad_served, np.int32)
        tok[:len(s)] = s
        out.append((tokens, idx, tok, len(s)))
    return out


def _run_layers(mc, forms, seed, xs, lows, visit=None):
    """Every layer over every hidden state in ``xs`` (one list per
    precision in ``lows``), one layer's weights made, projected and freed
    at a time; ``visit(layer, weights, states)`` sees each layer first."""
    frozen = _frozen(mc)
    for layer in range(mc["num_layers"]):
        lp = project_tree(make_layer(mc, seed, layer), forms["m"],
                          forms["bits"])
        if visit is not None:
            visit(layer, lp, xs[0])
        xs = [[_layer_program(lp, x, frozen, low) for x in group]
              for group, low in zip(xs, lows)]
        del lp
    return xs


def gaps(mc: Dict, forms: Dict, seed: int, prompts: Sequence[np.ndarray],
         served: Sequence[np.ndarray], pad_len: int, pad_served: int,
         control: bool = False) -> np.ndarray:
    """Per served token of each request, the reference's best logit minus
    its logit of the served token (``control``: of the token the 8-bit
    forward ranks first), concatenated over the requests."""
    if not prompts:
        return np.zeros(0, np.float32)
    seqs = _sequences(prompts, served, pad_len, pad_served)
    top = project_tree(make_top(mc, seed), forms["m"], forms["bits"])
    x0 = [top["embed"][jnp.asarray(t)] for t, *_ in seqs]
    lows = (False, True) if control else (False,)
    xs = _run_layers(mc, forms, seed, [list(x0) for _ in lows], lows)
    eps, out = mc["norm_eps"], []
    for i, (_, idx, tok, n) in enumerate(seqs):
        if control:
            g = _gaps_control(top, xs[0][i], xs[1][i], idx, eps)
        else:
            g = _gaps_program(top, xs[0][i], idx, tok, eps)
        out.append(np.asarray(g)[:n])
    return np.concatenate(out)


def logits(mc: Dict, forms: Dict, seed: int, tokens: np.ndarray
           ) -> np.ndarray:
    """The reference's logits ``(S, vocab)`` of one sequence (S a multiple
    of Q_BLOCK and of HEAD_BLOCK) at every position."""
    top = project_tree(make_top(mc, seed), forms["m"], forms["bits"])
    x = _run_layers(mc, forms, seed, [[top["embed"][jnp.asarray(tokens)]]],
                    (False,))[0][0]
    idx = jnp.arange(x.shape[0])
    return np.asarray(_logits(top, _hidden_at(top, x, idx, mc["norm_eps"]),
                              False))


def held_routes(mc: Dict, forms: Dict, seed: int, tokens: np.ndarray
                ) -> np.ndarray:
    """The reference's routing of one sequence: ``(MoE layers, S, held)``,
    True where a row routes to a held expert."""
    top = make_top(mc, seed)
    x = top["embed"][jnp.asarray(tokens)]
    out, frozen = [], _frozen(mc)

    def visit(layer, lp, states):
        if "moe" in lp:
            out.append(np.asarray(_routes_program(lp, states[0], frozen)))

    _run_layers(mc, forms, seed, [[x]], (False,), visit)
    return np.stack(out)


# --------------------------------------------------------------------------
# operations and bytes (work.py's conventions)
# --------------------------------------------------------------------------

def _mla_matmuls(mc: Dict) -> List[Tuple[str, int, int]]:
    d, h, m = mc["d_model"], mc["num_heads"], mc["mla"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    r = m["kv_lora_rank"]
    return [("q_down", d, m["q_lora_rank"]),
            ("q_up", m["q_lora_rank"], h * qk),
            ("kv_down", d, r + m["qk_rope_head_dim"]),
            ("kv_up", r, h * (m["qk_nope_head_dim"] + m["v_head_dim"])),
            ("wo", h * m["v_head_dim"], d)]


def _swiglu_matmuls(prefix: str, d: int, f: int) -> List[Tuple[str, int, int]]:
    return [(prefix + "gate", d, f), (prefix + "up", d, f),
            (prefix + "down", f, d)]


def projected_matmuls(mc: Dict) -> List[Tuple[str, int, int, int]]:
    """(name, K, N, calls per decode step) of every matmul the polarized
    kernel serves, as the program calls it: MLA's projections but
    ``kv_up`` (the absorbed decode reads it decompressed) once a layer, the
    dense MLP once a dense layer, each held expert once a MoE layer (over
    every row), the shared expert once a MoE layer, and the head."""
    k, n = _dense_layers(mc), mc["num_layers"]
    d = mc["d_model"]
    fs = mc["moe_d_ff"] * mc["num_shared_experts"]
    out = [(nm, kk, nn, n) for nm, kk, nn in _mla_matmuls(mc)
           if nm != "kv_up"]
    out += [(nm, kk, nn, k) for nm, kk, nn in
            _swiglu_matmuls("", d, mc["d_ff"])]
    out += [(nm, kk, nn, (n - k) * held(mc)) for nm, kk, nn in
            _swiglu_matmuls("w_", d, mc["moe_d_ff"])]
    out += [(nm, kk, nn, n - k) for nm, kk, nn in
            _swiglu_matmuls("shared_", d, fs)]
    out.append(("head", d, mc["vocab_size"], 1))
    return out


def routed_share(mc: Dict) -> float:
    """Held experts a token routes to on average, if routing is even:
    ``experts_per_token * held / num_experts``."""
    return mc["experts_per_token"] * held(mc) / mc["num_experts"]


def matmul_params(mc: Dict) -> float:
    """Matmul parameters the model's share touches per token: MLA (the
    expanded form), the dense MLPs, the router, the shared expert, the
    routed experts a token reaches here on average (``routed_share``) and
    the head."""
    k, n = _dense_layers(mc), mc["num_layers"]
    d, f, fe = mc["d_model"], mc["d_ff"], mc["moe_d_ff"]
    mla = sum(kk * nn for _, kk, nn in _mla_matmuls(mc))
    moe = (3 * d * fe * (mc["num_shared_experts"] + routed_share(mc))
           + d * mc["num_experts"])
    return n * mla + k * 3 * d * f + (n - k) * moe + d * mc["vocab_size"]


def _attn_per_position(mc: Dict) -> int:
    """Operations of one query over one attended position in one layer:
    scores over qk_nope + qk_rope and the sum over v_head_dim, per head
    (the expanded form)."""
    m = mc["mla"]
    return 2 * mc["num_heads"] * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                                  + m["v_head_dim"])


def token_flops(mc: Dict, ctx: int) -> float:
    return (2.0 * matmul_params(mc)
            + mc["num_layers"] * _attn_per_position(mc) * ctx)


def decode_flops(mc: Dict, tokens: int, positions: int) -> float:
    return (2.0 * matmul_params(mc) * tokens
            + mc["num_layers"] * _attn_per_position(mc) * positions)


def prefill_flops(mc: Dict, start: int, n: int) -> float:
    ctx = n * start + n * (n + 1) // 2
    return (2.0 * matmul_params(mc) * n
            + mc["num_layers"] * _attn_per_position(mc) * ctx)


def kv_bytes_per_position(mc: Dict) -> int:
    """Every layer caches the latent and the shared rope key, bfloat16."""
    m = mc["mla"]
    return mc["num_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) \
        * work.BF16


def decode_weight_bytes(mc: Dict, forms: Dict, rows: int) -> float:
    """Weight bytes one decode step over ``rows`` token rows must read: the
    stored codes of every projected matmul (``kv_up`` too), the router and
    its bias, the norms, and the embedding rows looked up."""
    k, n = _dense_layers(mc), mc["num_layers"]
    d, e, m = mc["d_model"], mc["num_experts"], mc["mla"]
    total = sum(calls * work.projected_bytes(kk, nn, forms)
                for _, kk, nn, calls in projected_matmuls(mc))
    _, kk, nn = next(x for x in _mla_matmuls(mc) if x[0] == "kv_up")
    total += n * work.projected_bytes(kk, nn, forms)
    total += (n - k) * (d + 1) * e * work.BF16        # router and bias
    total += (n * (2 * d + m["q_lora_rank"] + m["kv_lora_rank"]) + d) \
        * work.BF16                                     # norms
    total += rows * d * work.BF16                       # embedding rows
    return total


def kernel_least_seconds(mc: Dict, forms: Dict, rows: int, steps: int,
                         peak: Dict) -> Tuple[float, float]:
    """``work.least_seconds`` of this configuration's projected matmuls."""
    return work.least_seconds(projected_matmuls(mc), forms, rows, steps, peak)
