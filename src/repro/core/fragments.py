"""Fragment geometry for FORMS polarized crossbar mapping.

A *fragment* is the set of ``m`` consecutive weights that map onto one column
of a logical crossbar sub-array (paper §III-B, Fig 3).  All FORMS constraints
(polarization sign, sign-indicator storage, EIC zero-skipping) are defined at
fragment granularity, so every core module shares this geometry.

Conventions
-----------
A weight tensor destined for a crossbar is viewed as a 2-D matrix ``H`` of
shape ``(K, N)`` where ``K`` is the *input* (crossbar row) dimension and ``N``
the *output* (filter / crossbar column) dimension:

* dense / linear layers ``(in_features, out_features)`` are already ``(K, N)``;
* conv layers ``(H, W, C_in, C_out)`` reshape to ``(H*W*C_in, C_out)`` with the
  row ordering chosen by the *polarization policy* (W-major, H-major, C-major,
  paper Fig 3) — the policy is a pure permutation of the K axis.

Fragments partition the K axis into ``ceil(K / m)`` groups of ``m`` rows; the
fragment grid of the matrix is ``(num_fragments, N)``.  When ``K % m != 0``
the matrix is conceptually zero-padded — the pad rows are permanently zero and
never counted against polarization (zeros are sign-neutral, paper §III-B).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Policy = str  # "W" | "H" | "C"

VALID_POLICIES = ("W", "H", "C")


@dataclasses.dataclass(frozen=True)
class FragmentSpec:
    """Static description of how a weight tensor is fragmented.

    Attributes:
      m: fragment size == rows per logical sub-array column (paper: 4/8/16).
      policy: row-ordering policy for conv weights ("W", "H" or "C" major).
      n_sub_cols: columns per logical sub-array (``n`` in the paper's
        ``m x n`` sub-array); only used by the crossbar mapping / perf model,
        not by the math.
    """

    m: int = 8
    policy: Policy = "W"
    n_sub_cols: int = 128

    def __post_init__(self):
        if self.m < 1:
            raise ValueError(f"fragment size must be >= 1, got {self.m}")
        if self.policy not in VALID_POLICIES:
            raise ValueError(f"policy must be one of {VALID_POLICIES}, got {self.policy!r}")

    def num_fragments(self, k: int) -> int:
        return -(-k // self.m)

    def padded_k(self, k: int) -> int:
        return self.num_fragments(k) * self.m


def conv_to_matrix(w: jax.Array, policy: Policy = "W") -> jax.Array:
    """Reshape a conv kernel ``(H, W, C_in, C_out)`` to the 2-D crossbar matrix.

    The policy chooses which axis varies fastest along crossbar rows (paper
    Fig 3).  W-major: row order (h, c, w) with w fastest; H-major: (w, c, h)
    with h fastest; C-major: (h, w, c) with c fastest.
    """
    if w.ndim == 2:
        return w
    if w.ndim != 4:
        raise ValueError(f"expected 2-D or 4-D weight, got shape {w.shape}")
    h, ww, cin, cout = w.shape
    if policy == "W":
        # rows ordered (h, c, w): transpose to (H, C, W, O)
        m = jnp.transpose(w, (0, 2, 1, 3))
    elif policy == "H":
        m = jnp.transpose(w, (1, 2, 0, 3))
    elif policy == "C":
        m = jnp.transpose(w, (0, 1, 2, 3))
    else:
        raise ValueError(policy)
    return m.reshape(h * ww * cin, cout)


def matrix_to_conv(mat: jax.Array, shape: Tuple[int, int, int, int], policy: Policy = "W") -> jax.Array:
    """Inverse of :func:`conv_to_matrix`."""
    h, ww, cin, cout = shape
    if policy == "W":
        return jnp.transpose(mat.reshape(h, cin, ww, cout), (0, 2, 1, 3))
    if policy == "H":
        return jnp.transpose(mat.reshape(ww, cin, h, cout), (2, 0, 1, 3))
    if policy == "C":
        return mat.reshape(h, ww, cin, cout)
    raise ValueError(policy)


def pad_rows(mat: jax.Array, m: int) -> jax.Array:
    """Zero-pad the K axis of ``(K, N)`` to a multiple of the fragment size."""
    k = mat.shape[0]
    pad = (-k) % m
    if pad == 0:
        return mat
    return jnp.pad(mat, ((0, pad), (0, 0)))


def to_fragments(mat: jax.Array, m: int) -> jax.Array:
    """View ``(K, N)`` as ``(F, m, N)`` fragments (zero-padding K as needed)."""
    mat = pad_rows(mat, m)
    k, n = mat.shape
    return mat.reshape(k // m, m, n)


def from_fragments(frags: jax.Array, k: int) -> jax.Array:
    """Inverse of :func:`to_fragments`; drops K padding."""
    f, m, n = frags.shape
    return frags.reshape(f * m, n)[:k]


def fragment_sums(mat: jax.Array, m: int) -> jax.Array:
    """Per-fragment sums, shape ``(F, N)`` — used by the paper's sign rule."""
    return to_fragments(mat, m).sum(axis=1)


def fragment_count(shape: Tuple[int, ...], spec: FragmentSpec) -> int:
    """Number of fragments a weight tensor occupies (after policy reshape)."""
    if len(shape) == 4:
        h, w, cin, cout = shape
        k, n = h * w * cin, cout
    elif len(shape) == 2:
        k, n = shape
    else:
        raise ValueError(f"unsupported weight rank {len(shape)}")
    return spec.num_fragments(k) * n


def expand_fragment_values(values: jax.Array, m: int, k: int) -> jax.Array:
    """Broadcast per-fragment values ``(F, N)`` to per-weight ``(K, N)``.

    Used to expand fragment signs onto the weight grid (and to fold signs into
    magnitudes in the kernels).
    """
    f, n = values.shape
    out = jnp.broadcast_to(values[:, None, :], (f, m, n)).reshape(f * m, n)
    return out[:k]


def is_crossbar_weight(path: str, shape: Tuple[int, ...]) -> bool:
    """Heuristic: does this parameter map onto crossbar cells?

    Matmul weights (rank 2 with both dims > 1), scan-stacked matmul weights
    (rank 3: (L, in, out)) and conv kernels (rank 4) are crossbar-mapped.
    Biases, norms, per-channel recurrence params (rank 0/1) are digital-domain
    and excluded (paper stores only magnitude bits of MVM weights on ReRAM);
    the SSM depthwise conv and decay/step params are not MVMs; embedding
    tables are lookups, not MVMs — excluded by name, as is an MoE router,
    whose scores pick the experts and stay in float.
    """
    lname = path.lower()
    if any(t in lname for t in ("embed", "router", "bias", "scale", "norm",
                                "a_log",
                                "dt_", "conv_w", "conv_b", "conv1d", "lambda",
                                "d_skip", "/bf", "/ro", "/rz", "/ri", "/rf",
                                # QKV / MLP bias vectors (scan-stacked they are
                                # rank 2 but are digital-domain, not MVMs)
                                "/bq", "/bk", "/bv", "b_up", "b_down")):
        return False
    if len(shape) in (3, 4):
        return True
    if len(shape) == 2 and shape[0] > 1 and shape[1] > 1:
        return True
    return False
