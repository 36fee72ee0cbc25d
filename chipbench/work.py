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

Each family module (``chipbench/families/<family>.py``) counts its own
layers under these conventions; metric readers take those counts as
``run.work``.  This module holds what does not depend on the family: the
bytes of the served format and the kernel's least time.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

BF16 = 2


def projected_bytes(k: int, n: int, forms: Dict) -> float:
    """Stored bytes of one projected K x N matrix."""
    return k * n * forms["bits"] / 8 + (k // forms["m"]) * n + 4 * n


def kernel_call(k: int, n: int, rows: int, forms: Dict) -> Tuple[float, float]:
    """(operations, bytes) of one polarized-matmul call."""
    return (2.0 * rows * k * n,
            projected_bytes(k, n, forms) + rows * k * BF16 + rows * n * BF16)


def least_seconds(matmuls: List[Tuple[str, int, int, int]], forms: Dict,
                  rows: int, steps: int, peak: Dict) -> Tuple[float, float]:
    """Least time of the kernel calls of ``steps`` model steps over ``rows``
    token rows, given a family's projected matmuls (name, K, N, calls per
    step): (seconds bound by operations, seconds bound by bytes), each
    summed over the calls where that bound is the larger one."""
    by_ops = by_bytes = 0.0
    for _, k, n, calls in matmuls:
        ops, nbytes = kernel_call(k, n, rows, forms)
        t_ops = ops / peak["bf16_flops"]
        t_bytes = nbytes / peak["hbm_bytes_per_s"]
        if t_ops >= t_bytes:
            by_ops += calls * steps * t_ops
        else:
            by_bytes += calls * steps * t_bytes
    return by_ops, by_bytes
