"""Pallas TPU kernel: polarized-magnitude matmul (the FORMS MVM on the MXU).

Computes ``y = x @ (expand(signs) * mags) * scale`` where

* ``mags``  (K, N) are unsigned magnitude codes (the crossbar cells),
* ``signs`` (K/m, N) are per-fragment signs (the 1R sign indicator),
* ``scale`` (1, N) is the dequantization scale.

TPU adaptation (DESIGN.md §2): the accelerator applies signs *after* the
per-fragment analog partial sums; because the sign is constant within a
fragment, folding it into the magnitudes *before* one big MXU matmul is
bit-identical and keeps the MXU fully dense.  The fold happens in VMEM on the
VPU (a broadcast-multiply over the (bk, bn) weight tile) so HBM only ever
stores magnitudes + the 1/(8m)-sized sign plane — the paper's storage win —
while the MXU sees an ordinary dense tile.

Grid: (M/bm, N/bn, K/bk), K innermost for accumulation.  Blocks live in VMEM;
accumulation in float32; the dequant scale is applied on the final K step.

Zero-skipping (DESIGN.md §6g): pass ``block_mask`` — the (M/bm, K/bk) int32
tile-occupancy mask from ``kernels.sparsity.block_mask`` — and the kernel
predicates the sign-fold + MXU dot on the mask entry for the current
(i, k) tile, scalar-prefetched into SMEM.  An all-zero input tile contributes exactly 0
to the accumulator, so the skip is bit-identical to the dense kernel with
the same tiling: accumulator init and the final scale step are unchanged,
only the ``+= x @ w`` of dead tiles is elided.  This is the TPU analogue of
the paper's per-fragment NOR skip gate (fig 9) lifted to tile granularity.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _kernel(*refs, m: int, n_k_blocks: int, skip: bool):
    if skip:
        mask_ref, *refs = refs
    x_ref, mags_ref, signs_ref, scale_ref, out_ref, acc_ref = refs
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _mac():
        x = x_ref[...].astype(jnp.float32)                # (bm, bk)
        # Mosaic has no uint8 -> float cast; widening through int32 is exact
        mags = mags_ref[...].astype(jnp.int32).astype(jnp.float32)  # (bk, bn)
        signs = signs_ref[...].astype(jnp.float32)        # (bk//m, bn)
        bk, bn = mags.shape
        # fold the fragment signs into the magnitudes (VPU broadcast-multiply)
        sgrid = jnp.broadcast_to(signs[:, None, :],
                                 (bk // m, m, bn)).reshape(bk, bn)
        acc_ref[...] += jnp.dot(x, mags * sgrid,
                                preferred_element_type=jnp.float32)

    if skip:
        # the MAC is predicated on the tile occupancy bit (scalar-prefetched
        # into SMEM, flattened row-major over the (M/bm, K/bk) tile grid),
        # so dead input tiles never touch the MXU
        pl.when(mask_ref[pl.program_id(0) * n_k_blocks + k_idx] != 0)(_mac)
    else:
        _mac()

    @pl.when(k_idx == n_k_blocks - 1)
    def _finish():
        scale = scale_ref[...].astype(jnp.float32)        # (1, bn)
        out_ref[...] = (acc_ref[...] * scale).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("m", "bm", "bn", "bk", "interpret", "out_dtype"))
def polarized_matmul(
    x: jax.Array,            # (M, K)
    mags: jax.Array,         # (K, N) unsigned magnitude codes
    signs: jax.Array,        # (K/m, N) fragment signs in {+1, -1}
    scale: jax.Array,        # (1, N) dequant scale
    block_mask: Optional[jax.Array] = None,  # (M/bm, K/bk) int32 occupancy
    *,
    m: int = 8,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    M, K = x.shape
    K2, N = mags.shape
    if K != K2:
        raise ValueError(
            f"x and mags disagree on K: x is {x.shape}, mags is "
            f"{mags.shape}; pad activations to the magnitude rows "
            f"(ops.polarized_matmul / forms.apply do this automatically)")
    if K % m != 0:
        raise ValueError(
            f"K={K} is not a multiple of the fragment size m={m}: the sign "
            f"plane stores one sign per {m} rows, so K must tile into whole "
            f"fragments.  Pad K to {-(-K // m) * m} rows "
            f"(core.fragments.pad_rows) or change m.")
    if signs.shape != (K // m, N):
        raise ValueError(
            f"signs must be one row per fragment: expected shape "
            f"{(K // m, N)} for mags {mags.shape} with m={m}, got "
            f"{tuple(signs.shape)}")

    if block_mask is not None and bk % m != 0:
        raise ValueError(
            f"zero-skip block mask needs bk to be a whole number of "
            f"fragments: bk={bk} is not a multiple of m={m}, so the mask "
            f"tiling the caller computed would silently disagree with the "
            f"kernel grid after clamping.  Pick bk a multiple of {m} (e.g. "
            f"{max(m, (bk // m) * m)}) or use zero_skip='compact' instead.")
    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    # bk must be a multiple of m so sign blocks tile cleanly
    bk = max(m, (bk // m) * m)
    if M % bm != 0 or N % bn != 0 or K % bk != 0:
        raise ValueError(
            f"shapes (M={M}, N={N}, K={K}) must tile by (bm={bm}, bn={bn}, "
            f"bk={bk}); use ops.polarized_matmul for automatic padding")

    grid = (M // bm, N // bn, K // bk)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j)),
        pl.BlockSpec((bk // m, bn), lambda i, j, k, *_: (k, j)),
        pl.BlockSpec((1, bn), lambda i, j, k, *_: (0, j)),
    ]
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j))
    out_shape = jax.ShapeDtypeStruct((M, N), out_dtype)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if block_mask is None:
        return pl.pallas_call(
            functools.partial(_kernel, m=m, n_k_blocks=grid[2], skip=False),
            grid=grid, in_specs=in_specs, out_specs=out_spec,
            out_shape=out_shape, scratch_shapes=scratch,
            interpret=interpret,
        )(x, mags, signs, scale)

    if block_mask.shape != grid[:1] + grid[2:]:
        raise ValueError(
            f"block_mask shape {tuple(block_mask.shape)} does not match the "
            f"kernel grid: expected (M//bm, K//bk) = "
            f"{(M // bm, K // bk)} (kernels.sparsity.block_mask(x, "
            f"bm={bm}, bk={bk}))")
    # the occupancy mask rides in as a scalar-prefetch operand: one int32
    # per (i, k) tile in SMEM, readable as a scalar predicate
    return pl.pallas_call(
        functools.partial(_kernel, m=m, n_k_blocks=grid[2], skip=True),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=out_shape,
        interpret=interpret,
    )(block_mask.astype(jnp.int32).reshape(-1), x, mags, signs, scale)
