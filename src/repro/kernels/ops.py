"""Public kernel entry points: padding, backend dispatch, dequant plumbing.

Each op pads inputs to kernel tile multiples, calls the Pallas kernel
(``interpret=True`` automatically off-TPU so the same code path is exercised
everywhere), and unpads.  ``prefer_ref=True`` (default on CPU for large
shapes) routes to the jnp oracle, which XLA compiles to the same math — the
kernels remain the TPU target, the oracle the portable fast path.

Every op takes an optional ``spec`` (a :class:`repro.forms.FormsSpec`) that
supplies fragment size, bit widths, backend preference and tile sizes in one
place — the loose per-call kwargs remain for low-level and test use but new
call sites should thread a spec.  (Duck-typed on purpose: kernels sit below
``repro.forms`` in the import graph.)
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import ref, sparsity
from repro.kernels.admm_polarize import admm_polarize as _admm_polarize_kernel
from repro.kernels.bitserial_crossbar import bitserial_crossbar as _bitserial_kernel
from repro.kernels.paged_attention import \
    paged_decode_attention as _paged_attention_kernel
from repro.kernels.polarized_matmul import polarized_matmul as _polarized_kernel

#: zero-skip modes for :func:`polarized_matmul` (DESIGN.md §6g):
#: ``off`` is the dense path; ``block`` predicates the MXU dot on a
#: per-(bm, bk)-tile occupancy mask (bit-identical to dense); ``compact``
#: gathers live whole fragments into a smaller dense matmul when the live
#: count fits the ``zero_skip_keep`` budget, falling back to dense when not.
VALID_ZERO_SKIP = ("off", "block", "compact")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# polarized matmul
# ---------------------------------------------------------------------------

def _k_shard_count(arr: jax.Array, k_dim: int) -> int:
    """How many ways ``arr`` is sharded along its K dimension (1 for tracers,
    uncommitted arrays, and non-named shardings)."""
    sh = getattr(arr, "sharding", None)
    spec = getattr(sh, "spec", None)
    if spec is None:
        return 1
    entries = tuple(spec) + (None,) * (arr.ndim - len(tuple(spec)))
    entry = entries[k_dim]
    if entry is None:
        return 1
    names = entry if isinstance(entry, (tuple, list)) else (entry,)
    shape = dict(sh.mesh.shape)
    count = 1
    for a in names:
        count *= shape[a]
    return count


def _validate_polarized_geometry(x: jax.Array, mags: jax.Array,
                                 signs: jax.Array, m: int,
                                 spec: Optional[Any] = None) -> None:
    """Fragment-geometry validation with actionable messages.

    Two ways a caller can split a sign fragment across a boundary, both
    rejected here rather than by a bare assert deep in the kernel: a K
    dimension that doesn't tile into fragments, and a mesh-sharded K
    dimension whose per-device shard isn't a whole number of fragments.
    (The kernel's K *tile* is clamped to a fragment multiple internally, so
    any ``bk`` hint is safe.)
    """
    K, N = mags.shape
    if m < 1:
        raise ValueError(f"fragment size m must be >= 1, got {m}")
    if K % m != 0:
        raise ValueError(
            f"K={K} magnitude rows do not tile into fragments of m={m} "
            f"rows; pad K to {-(-K // m) * m} (core.fragments.pad_rows / "
            f"forms.from_dense do this) or choose an m dividing K")
    if signs.shape != (K // m, N):
        raise ValueError(
            f"signs must hold one row per fragment: expected "
            f"{(K // m, N)} for mags {tuple(mags.shape)} with m={m}, got "
            f"{tuple(signs.shape)}")
    for name, arr, k_dim in (("x", x, 1), ("mags", mags, 0)):
        shards = _k_shard_count(arr, k_dim)
        if shards <= 1:
            continue
        if spec is not None and hasattr(spec, "validate_k_shard"):
            spec.validate_k_shard(K, shards)
        elif K % shards != 0 or (K // shards) % m != 0:
            raise ValueError(
                f"{name} is sharded {shards}-way along K={K}, giving "
                f"{K / shards:g}-row shards — not a whole number of m={m} "
                f"fragments, so per-fragment signs would straddle devices. "
                f"Shard K only at multiples of shards*m "
                f"(distributed.sharding.forms_param_spec enforces this for "
                f"parameter trees), or replicate K.")


def _compact_matmul(x: jax.Array, mags: jax.Array, signs: jax.Array,
                    scale: jax.Array, m: int, keep_frac: float,
                    dense_fn) -> jax.Array:
    """Fragment-compaction wrapper: smaller dense matmul when sparsity fits.

    Gathers the live whole fragments (input columns + magnitude rows + the
    shared sign row move together, which is what makes the gather
    sign-consistent) into a static ``keep``-fragment budget and runs
    ``dense_fn`` on the compacted operands; when more fragments are live
    than the budget, falls back to the full dense call via ``lax.cond``.
    Exact because gathered-away fragments have all-zero input columns.
    """
    M, K = x.shape
    N = mags.shape[1]
    F = K // m
    keep = max(1, min(F, int(round(F * keep_frac))))
    if keep >= F:
        return dense_fn(x, mags, signs, scale)
    live = sparsity.fragment_occupancy(x, m)
    n_live = jnp.sum(live.astype(jnp.int32))
    idx = sparsity.compact_order(live)[:keep]

    def _compact(operands):
        x_, mg, sg, sc = operands
        xg = x_.reshape(M, F, m)[:, idx].reshape(M, keep * m)
        mg_g = mg.reshape(F, m, N)[idx].reshape(keep * m, N)
        sg_g = sg[idx]
        return dense_fn(xg, mg_g, sg_g, sc)

    def _dense(operands):
        return dense_fn(*operands)

    return jax.lax.cond(n_live <= keep, _compact, _dense,
                        (x, mags, signs, scale))


def _pallas_polarized(x: jax.Array, mags: jax.Array, signs: jax.Array,
                      scale: jax.Array, *, m: int, bm: int, bn: int, bk: int,
                      skip: bool = False) -> jax.Array:
    """Pad to tile multiples, run the Pallas kernel, unpad.  ``skip``
    predicates each tile on the occupancy mask of the padded input."""
    M, K = x.shape
    N = mags.shape[1]
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    bk_ = max(m, (bk_ // m) * m)
    xp = _pad_to(x, 0, bm_)
    xp = _pad_to(xp, 1, bk_)
    magsp = _pad_to(_pad_to(mags, 0, bk_), 1, bn_)
    signsp = _pad_to(_pad_to(signs, 0, bk_ // m), 1, bn_)
    scalep = _pad_to(scale.reshape(1, -1), 1, bn_)
    block_mask = sparsity.block_mask(xp, bm_, bk_) if skip else None
    out = _polarized_kernel(xp, magsp, signsp, scalep, block_mask, m=m,
                            bm=bm_, bn=bn_, bk=bk_, interpret=not on_tpu())
    return out[:M, :N]


def _mesh_polarized(x: jax.Array, mags: jax.Array, signs: jax.Array,
                    scale: jax.Array, **kernel_kw) -> jax.Array:
    """:func:`_pallas_polarized`, per device when a mesh is active.

    A Mosaic kernel is opaque to the SPMD partitioner, so under an active
    ``distributed.sharding.parallel_context`` it runs inside ``shard_map``:
    rows split over the batch axes and output columns over the model axes
    (each where the dim divides), K whole on every device.  Weights placed
    with a K shard (row-parallel layers) are re-laid out to column shards
    by XLA before the call.
    """
    kernel = functools.partial(_pallas_polarized, **kernel_kw)
    # imported here: distributed.sharding sits above kernels in the
    # import graph (it imports repro.forms, which imports this module)
    from repro.distributed.sharding import _checked_spec, current_context
    ctx = current_context()
    if ctx is None or ctx.mesh.size == 1:
        return kernel(x, mags, signs, scale)
    out = _checked_spec(("batch", "model"), (x.shape[0], mags.shape[1]), ctx)
    b, n = (tuple(out) + (None, None))[:2]
    return jax.shard_map(
        kernel, mesh=ctx.mesh,
        in_specs=(P(b, None), P(None, n), P(None, n), P(None, n)),
        out_specs=out, check_vma=False)(x, mags, signs, scale)


def polarized_matmul(
    x: jax.Array, mags: jax.Array, signs: jax.Array, scale: jax.Array,
    *, m: int = 8, prefer_ref: Optional[bool] = None,
    bm: int = 128, bn: int = 128, bk: int = 512,
    zero_skip: str = "off", zero_skip_keep: float = 0.5,
    spec: Optional[Any] = None,
) -> jax.Array:
    """y[M,N] = x[M,K] @ (signs*mags)[K,N] * scale[1,N].

    ``signs`` may be int8 (the FORMS storage dtype) or float — both backends
    cast per tile, so HBM only ever stores the 1/m-sized int8 sign plane.
    ``spec`` (a FormsSpec) overrides ``m``/``prefer_ref``/``bm``/``bn``/``bk``
    and the zero-skip knobs.

    ``zero_skip`` (see :data:`VALID_ZERO_SKIP`) exploits activation sparsity:
    on the Pallas path ``block`` skips whole (bm, bk) input tiles via an SMEM
    occupancy mask (bit-identical to dense) and ``compact`` gathers live
    fragments into a smaller kernel launch; on the oracle path both modes
    lower to the same cond-gated fragment compaction — genuinely fewer FLOPs
    when at most ``zero_skip_keep`` of the fragments are live, exact always.
    """
    if spec is not None:
        m, prefer_ref = spec.m, spec.prefer_ref
        bm, bn, bk = spec.bm, spec.bn, spec.bk
        zero_skip = getattr(spec, "zero_skip", zero_skip)
        zero_skip_keep = getattr(spec, "zero_skip_keep", zero_skip_keep)
    if zero_skip not in VALID_ZERO_SKIP:
        raise ValueError(
            f"zero_skip must be one of {VALID_ZERO_SKIP}, got "
            f"{zero_skip!r} (FormsSpec(zero_skip=...) / --zero-skip)")
    M, K = x.shape
    _, N = mags.shape
    _validate_polarized_geometry(x, mags, signs, m, spec=spec)
    if prefer_ref is None:
        prefer_ref = not on_tpu()
    if prefer_ref:
        if zero_skip == "off":
            return ref.ref_polarized_matmul_fast(x, mags, signs, scale, m)
        # off-TPU there is no tile predication to win from, so both modes
        # lower to fragment compaction: a strictly smaller oracle matmul
        return _compact_matmul(
            x, mags, signs, scale, m, zero_skip_keep,
            lambda x_, mg, sg, sc: ref.ref_polarized_matmul_fast(
                x_, mg, sg, sc, m))

    if zero_skip == "compact":
        return _compact_matmul(
            x, mags, signs, scale, m, zero_skip_keep,
            lambda x_, mg, sg, sc: _mesh_polarized(
                x_, mg, sg, sc, m=m, bm=bm, bn=bn, bk=bk))
    return _mesh_polarized(x, mags, signs, scale, m=m, bm=bm, bn=bn, bk=bk,
                           skip=zero_skip == "block")


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

def pool_lanes(hd: int) -> int:
    """Lane width a KV page pool stores for head size ``hd``.

    The paged-attention kernel DMAs whole pages out of the pool, and Mosaic
    slices an HBM buffer only at whole 128-lane tiles, so on a TPU a pool
    keeps ``hd`` padded with zeros to a multiple of 128 (the bytes a
    row-major TPU layout of the unpadded pool would occupy anyway).  Off
    the chip the interpreter slices anything, and the pool is ``hd`` wide.
    """
    return -(-hd // 128) * 128 if on_tpu() else hd


def paged_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    k_pool: jax.Array, v_pool: jax.Array, layer: jax.Array,
                    pos: jax.Array, block_tables: jax.Array, *,
                    window: Optional[int] = None) -> jax.Array:
    """Single-token decode attention read in place from a KV page pool.

    q: (B, 1, H, hd); k_new/v_new: (B, 1, kv, hd), the new token's rows at
    position ``pos`` (B,); pools: (L, num_pages, page_size, kv, lanes)
    (``lanes`` from :func:`pool_lanes`), read at ``layer`` through
    ``block_tables`` (B, n_tables).  Returns (B, 1, H, hd) in the pool's
    dtype — what ``models.layers.decode_attention`` computes on the
    block-table gather of the pool with the new rows written at ``pos``.
    """
    hd = q.shape[-1]
    lanes = k_pool.shape[-1]
    pad = lambda x: _pad_to(x[:, 0], 2, lanes)
    # 1 / sqrt(hd) in float32, as decode_attention scales its scores
    scale = float(np.float32(1.0) / np.sqrt(np.float32(hd)))
    out = _paged_attention_kernel(
        pad(q), pad(k_new), pad(v_new), k_pool, v_pool, layer, pos,
        block_tables, scale=scale, window=window, interpret=not on_tpu())
    return out[:, None, :, :hd]


# ---------------------------------------------------------------------------
# bit-serial crossbar simulation
# ---------------------------------------------------------------------------

def bitserial_crossbar(
    x_codes: jax.Array, cell_planes: jax.Array, signs: jax.Array,
    *, m: int = 8, input_bits: int = 16, cell_bits: int = 2,
    adc_bits: Optional[int] = None, prefer_ref: Optional[bool] = None,
    bm: int = 32, bn: int = 128,
    spec: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (acc[M,N] int32, eic[M,F] int32).

    ``spec`` (a FormsSpec) overrides ``m``/``input_bits``/``cell_bits``/
    ``adc_bits``/``prefer_ref`` and the sim tile sizes.
    """
    if spec is not None:
        m, input_bits, cell_bits = spec.m, spec.input_bits, spec.cell_bits
        adc_bits, prefer_ref = spec.adc_bits, spec.prefer_ref
        bm, bn = spec.sim_bm, spec.sim_bn
    M, K = x_codes.shape
    C, _, N = cell_planes.shape
    F = K // m
    if prefer_ref is None:
        prefer_ref = not on_tpu()
    if prefer_ref:
        acc, _cycles = ref.ref_bitserial_crossbar(
            x_codes, cell_planes, signs, m, input_bits, cell_bits,
            adc_bits=adc_bits, zero_skip=True)
        from repro.core.zeroskip import fragment_eic
        eic = fragment_eic(x_codes, m, input_bits)
        return acc, eic

    bm_, bn_ = min(bm, M), min(bn, N)
    xp = _pad_to(x_codes, 0, bm_)
    cellsp = _pad_to(cell_planes, 2, bn_)
    signsp = _pad_to(signs, 1, bn_)
    acc, eic = _bitserial_kernel(
        xp, cellsp, signsp, m=m, input_bits=input_bits, cell_bits=cell_bits,
        adc_bits=adc_bits, bm=bm_, bn=bn_, interpret=not on_tpu())
    return acc[:M, :N], eic[:M]


# ---------------------------------------------------------------------------
# polarization projection
# ---------------------------------------------------------------------------

def admm_polarize(
    v: jax.Array, *, m: int = 8, rule: str = "sum",
    prefer_ref: Optional[bool] = None, bk: int = 512, bn: int = 256,
    spec: Optional[Any] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (projected[K,N], signs[F,N]); K is padded internally.

    ``spec`` (a FormsSpec) overrides ``m``/``rule``/``prefer_ref``.
    """
    if spec is not None:
        m, rule, prefer_ref = spec.m, spec.rule, spec.prefer_ref
    K, N = v.shape
    F = -(-K // m)
    if prefer_ref is None:
        prefer_ref = not on_tpu()
    vp = _pad_to(v, 0, m)
    if prefer_ref:
        out, signs = ref.ref_admm_polarize(vp, m, rule)
        return out[:K], signs

    Kp = vp.shape[0]
    bk_ = max(m, (min(bk, Kp) // m) * m)
    bn_ = min(bn, N)
    vpp = _pad_to(_pad_to(vp, 0, bk_), 1, bn_)
    out, signs = _admm_polarize_kernel(vpp, m=m, rule=rule, bk=bk_, bn=bn_,
                                       interpret=not on_tpu())
    return out[:K, :N], signs[:F, :N]
