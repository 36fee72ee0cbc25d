"""Operations and bytes that a configuration's layers need, from shapes and
the served format alone, whatever implements them.

Conventions (kept here so that every PR counts the same work):

* A matmul of M rows, K inputs and N outputs costs 2*M*K*N operations.
* Model operations per token are 2 x (every matmul parameter, the output
  head included) plus attention over the live context: 4 x heads x
  head_dim per attended position per layer (scores and the weighted sum).
* Served weight bytes: a projected (FORMS) matrix stores bits/8 bytes per
  magnitude code, one int8 sign per m rows of a column and a float32 scale
  per column; every other leaf is counted at the configuration's bfloat16
  (2 bytes), as a tight implementation would read it.
* K/V bytes per cached position: layers x 2 x kv_heads x head_dim x 2
  (bfloat16 pages).
* Kernel bytes of one polarized-matmul call: codes + signs + scales +
  activations in (M x K) + output (M x N), activations at 2 bytes.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

BF16 = 2


def _dims(mc: Dict) -> Tuple[int, ...]:
    return (mc["num_layers"], mc["d_model"], mc["num_heads"],
            mc["num_kv_heads"], mc["head_dim"], mc["d_ff"], mc["vocab_size"])


def layer_matmuls(mc: Dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of the matmuls of one block."""
    _, d, h, kv, hd, f, _ = _dims(mc)
    return [("wq", d, h * hd), ("wk", d, kv * hd), ("wv", d, kv * hd),
            ("wo", h * hd, d), ("gate", d, f), ("up", d, f), ("down", f, d)]


def projected_matmuls(mc: Dict) -> List[Tuple[str, int, int, int]]:
    """(name, K, N, calls per token step) of every matmul the polarized
    kernel serves: the block matmuls once per layer, an untied head once."""
    L, d, *_, v = _dims(mc)
    out = [(n, k, nn, L) for n, k, nn in layer_matmuls(mc)]
    if not mc["tie_embeddings"]:
        out.append(("head", d, v, 1))
    return out


def matmul_params(mc: Dict) -> int:
    """Matmul parameters touched per token, the head included."""
    L, d, *_, v = _dims(mc)
    return L * sum(k * n for _, k, n in layer_matmuls(mc)) + d * v


def attended(mc: Dict, ctx: int) -> int:
    """Positions one query attends to at context length ``ctx``."""
    w = mc.get("sliding_window")
    return min(ctx, w) if w else ctx


def token_flops(mc: Dict, ctx: int) -> float:
    """Model operations of one token whose query sees ``ctx`` positions."""
    L, _, h, _, hd, *_ = _dims(mc)
    return 2.0 * matmul_params(mc) + 4.0 * L * h * hd * attended(mc, ctx)


def decode_flops(mc: Dict, tokens: int, positions: int) -> float:
    """Operations of ``tokens`` decoded tokens whose queries read
    ``positions`` K/V positions in all (per layer)."""
    L, _, h, _, hd, *_ = _dims(mc)
    return 2.0 * matmul_params(mc) * tokens + 4.0 * L * h * hd * positions


def prefill_flops(mc: Dict, start: int, n: int) -> float:
    """Operations of prompt tokens at positions ``start .. start + n - 1``."""
    L, _, h, _, hd, *_ = _dims(mc)
    w = mc.get("sliding_window")
    if w:
        ctx = sum(attended(mc, p + 1) for p in range(start, start + n))
    else:
        ctx = n * start + n * (n + 1) // 2
    return 2.0 * matmul_params(mc) * n + 4.0 * L * h * hd * ctx


def kv_bytes_per_position(mc: Dict) -> int:
    L, _, _, kv, hd, *_ = _dims(mc)
    return L * 2 * kv * hd * BF16


def projected_bytes(k: int, n: int, forms: Dict) -> float:
    """Stored bytes of one projected K x N matrix."""
    return k * n * forms["bits"] / 8 + (k // forms["m"]) * n + 4 * n


def decode_weight_bytes(mc: Dict, forms: Dict, rows: int) -> float:
    """Weight bytes one decode step over ``rows`` token rows must read."""
    L, d, h, kv, hd, f, v = _dims(mc)
    total = sum(calls * projected_bytes(k, n, forms)
                for _, k, n, calls in projected_matmuls(mc))
    if mc["tie_embeddings"]:
        total += v * d * BF16                     # the embedding as head
    total += rows * d * BF16                      # embedding rows looked up
    total += (2 * L + 1) * d * BF16               # norms
    if mc["qkv_bias"]:
        total += L * (h + 2 * kv) * hd * BF16
    return total


def kernel_call(k: int, n: int, rows: int, forms: Dict) -> Tuple[float, float]:
    """(operations, bytes) of one polarized-matmul call."""
    return (2.0 * rows * k * n,
            projected_bytes(k, n, forms) + rows * k * BF16 + rows * n * BF16)


def kernel_least_seconds(mc: Dict, forms: Dict, rows: int, steps: int,
                         peak: Dict) -> Tuple[float, float]:
    """Least time of the kernel calls of ``steps`` model steps over ``rows``
    token rows: (seconds bound by operations, seconds bound by bytes), each
    summed over the calls where that bound is the larger one."""
    by_ops = by_bytes = 0.0
    for _, k, n, calls in projected_matmuls(mc):
        ops, nbytes = kernel_call(k, n, rows, forms)
        t_ops = ops / peak["bf16_flops"]
        t_bytes = nbytes / peak["hbm_bytes_per_s"]
        if t_ops >= t_bytes:
            by_ops += calls * steps * t_ops
        else:
            by_bytes += calls * steps * t_bytes
    return by_ops, by_bytes
