"""Zero-skipping kernel paths: block-skip bit-identity, compaction
exactness, the shared sparsity helpers, and the geometry error paths.

Bit-identity contract (DESIGN.md §6g): the block-skip kernel must be
*bitwise* equal to the dense Pallas kernel with the SAME tiling — a
skipped tile contributes exactly the 0.0 the dense kernel would have
added, and accumulation order is unchanged.  (Comparing against a single
``jnp`` matmul instead would fail spuriously: one big dot reassociates
the K accumulation differently from per-``bk``-block partial sums.)

The compaction path is exact (gathered-away fragments have all-zero input
columns; the dense fallback is the dense path) but not bitwise vs the
dense kernel — a smaller matmul reassociates — so it is checked with a
zero-tolerance allclose on well-scaled inputs and, end to end, by greedy
token identity in test_zeroskip_serving.py.

All Pallas calls run in interpret mode on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import sparsity as S
from repro.kernels.polarized_matmul import polarized_matmul as kernel_matmul


def _operands(seed, M, K, N, m):
    key = jax.random.PRNGKey(seed)
    kx, km, ks, kc = jax.random.split(key, 4)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    mags = jax.random.randint(km, (K, N), 0, 256).astype(jnp.uint8)
    signs = jnp.where(jax.random.normal(ks, (K // m, N)) > 0, 1, -1
                      ).astype(jnp.int8)
    scale = (jax.random.uniform(kc, (1, N)) * 0.01).astype(jnp.float32)
    return x, mags, signs, scale


def _sparsify(x, m, frac, seed):
    """Zero a random ``frac`` of the whole m-fragments of each row."""
    M, K = x.shape
    F = K // m
    rng = np.random.RandomState(seed)
    mask = (rng.rand(M, F) >= frac).astype(np.float32)
    return x * jnp.asarray(np.repeat(mask, m, axis=1))


# ---------------------------------------------------------------------------
# block-skip kernel: bit-identical to the dense kernel, same tiling
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("row_tiles", [1, 3])
@pytest.mark.parametrize("frac", [0.0, 0.5, 1.0])
def test_block_skip_bitwise_identical(frac, row_tiles):
    M, K, N, m = 8 * row_tiles, 64, 16, 4
    bm, bn, bk = 8, 16, 16
    x, mags, signs, scale = _operands(0, M, K, N, m)
    x = _sparsify(x, m, frac, seed=1)
    if row_tiles > 1:
        # a different dead-tile pattern in every row of tiles: the skip
        # kernel reads the flattened mask at i * (K // bk) + k, and an entry
        # read for the wrong (i, k) would skip a live tile
        live = (np.arange(row_tiles)[:, None] + np.arange(K // bk)) % 3 != 0
        x = x * jnp.asarray(np.repeat(np.repeat(live, bm, 0), bk, 1))
    dense = kernel_matmul(x, mags, signs, scale, m=m, bm=bm, bn=bn, bk=bk,
                          interpret=True)
    mask = S.block_mask(x, bm, bk)
    skip = kernel_matmul(x, mags, signs, scale, mask, m=m, bm=bm, bn=bn,
                         bk=bk, interpret=True)
    assert bool(jnp.all(dense == skip))


def test_block_skip_randomized_sweep():
    """Deterministic randomized sweep over sparsity patterns and tilings —
    the always-on counterpart of the hypothesis property test below,
    covering all-zero rows, all-zero inputs, and fragments straddling
    K-tile boundaries."""
    rng = np.random.RandomState(0)
    for trial in range(12):
        m = int(rng.choice([2, 4, 8]))
        n_k_tiles = int(rng.randint(1, 4))
        bk = m * int(rng.randint(1, 4))
        K = bk * n_k_tiles
        M, N = 4 * int(rng.randint(1, 3)), 8
        bm, bn = 4, 8
        x, mags, signs, scale = _operands(trial, M, K, N, m)
        x = _sparsify(x, m, float(rng.rand()), seed=trial)
        if trial % 3 == 0:
            x = x.at[0].set(0.0)          # an all-zero row
        if trial % 5 == 0:
            x = jnp.zeros_like(x)         # fully zero input
        dense = kernel_matmul(x, mags, signs, scale, m=m, bm=bm, bn=bn,
                              bk=bk, interpret=True)
        mask = S.block_mask(x, bm, bk)
        skip = kernel_matmul(x, mags, signs, scale, mask, m=m, bm=bm,
                             bn=bn, bk=bk, interpret=True)
        assert bool(jnp.all(dense == skip)), (
            f"trial {trial}: m={m} bk={bk} K={K} not bit-identical")


def test_block_mask_requires_fragment_aligned_bk():
    M, K, N, m = 8, 64, 16, 4
    x, mags, signs, scale = _operands(0, M, K, N, m)
    mask = S.block_mask(x, 8, 16)
    with pytest.raises(ValueError, match="whole number of\\s+fragments"):
        kernel_matmul(x, mags, signs, scale, mask, m=m, bm=8, bn=16, bk=18,
                      interpret=True)


def test_block_mask_shape_checked():
    M, K, N, m = 8, 64, 16, 4
    x, mags, signs, scale = _operands(0, M, K, N, m)
    bad = jnp.ones((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="does not match the\\s+kernel grid"):
        kernel_matmul(x, mags, signs, scale, bad, m=m, bm=8, bn=16, bk=16,
                      interpret=True)


# ---------------------------------------------------------------------------
# ops routing: oracle + Pallas, block + compact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["block", "compact"])
@pytest.mark.parametrize("prefer_ref", [True, False])
def test_ops_zero_skip_matches_dense(mode, prefer_ref):
    M, K, N, m = 8, 64, 16, 4
    x, mags, signs, scale = _operands(2, M, K, N, m)
    x = _sparsify(x, m, 0.7, seed=3)
    kw = dict(m=m, prefer_ref=prefer_ref, bm=8, bn=16, bk=16)
    dense = ops.polarized_matmul(x, mags, signs, scale, **kw)
    y = ops.polarized_matmul(x, mags, signs, scale, zero_skip=mode,
                             zero_skip_keep=0.6, **kw)
    if mode == "block" and not prefer_ref:
        # same kernel, same tiling, skipped tiles contribute exact zeros
        assert bool(jnp.all(dense == y))
    else:
        np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                                   rtol=1e-6, atol=1e-6)


def test_ops_compact_falls_back_when_dense():
    # fully dense input exceeds any keep budget -> the cond picks the dense
    # branch and the result is exactly the dense path's
    M, K, N, m = 4, 32, 8, 4
    x, mags, signs, scale = _operands(4, M, K, N, m)
    dense = ops.polarized_matmul(x, mags, signs, scale, m=m, prefer_ref=True)
    y = ops.polarized_matmul(x, mags, signs, scale, m=m, prefer_ref=True,
                             zero_skip="compact", zero_skip_keep=0.25)
    assert bool(jnp.all(dense == y))


def test_ops_rejects_unknown_mode():
    M, K, N, m = 4, 16, 8, 4
    x, mags, signs, scale = _operands(5, M, K, N, m)
    with pytest.raises(ValueError, match="zero_skip must be one of"):
        ops.polarized_matmul(x, mags, signs, scale, m=m, zero_skip="always")


def test_spec_routes_zero_skip():
    from repro.forms.spec import FormsSpec
    M, K, N, m = 4, 32, 8, 4
    x, mags, signs, scale = _operands(6, M, K, N, m)
    x = _sparsify(x, m, 0.8, seed=7)
    dense = ops.polarized_matmul(x, mags, signs, scale,
                                 spec=FormsSpec(m=m, prefer_ref=True))
    spec = FormsSpec(m=m, prefer_ref=True, zero_skip="compact",
                     zero_skip_keep=0.5)
    np.testing.assert_allclose(
        np.asarray(ops.polarized_matmul(x, mags, signs, scale, spec=spec)),
        np.asarray(dense), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def test_block_mask_marks_live_tiles():
    x = jnp.zeros((8, 32))
    x = x.at[5, 17].set(1.0)
    mask = np.asarray(S.block_mask(x, 4, 8))
    expect = np.zeros((2, 4), np.int32)
    expect[1, 2] = 1
    np.testing.assert_array_equal(mask, expect)
    with pytest.raises(ValueError, match="tiled input"):
        S.block_mask(x, 3, 8)


def test_fragment_live_shared_with_bitserial():
    # the bitserial kernel's per-bit-plane liveness is this helper
    xf = jnp.array([[[0, 0], [1, 0]], [[0, 3], [0, 0]]])
    np.testing.assert_array_equal(np.asarray(S.fragment_live(xf)),
                                  [[False, True], [True, False]])


def test_fragment_occupancy_unions_rows():
    x = jnp.array([[0.0, 0.0, 1.0, 0.0],
                   [0.0, 0.0, 0.0, 0.0]])
    np.testing.assert_array_equal(np.asarray(S.fragment_occupancy(x, 2)),
                                  [False, True])
    with pytest.raises(ValueError, match="not divisible"):
        S.fragment_occupancy(x, 3)


def test_compact_order_live_first_stable():
    live = jnp.array([False, True, False, True])
    np.testing.assert_array_equal(np.asarray(S.compact_order(live)),
                                  [1, 3, 0, 2])


def test_sparsity_meter_accumulates():
    meter = S.SparsityMeter()
    x = jnp.array([[0.0, 0.0, 1.0, 2.0]])
    meter.record("mlp", S.sparsity_counts(x, 2))
    meter.record("mlp", S.sparsity_counts(x, 2))
    out = meter.summary()
    assert out["layers"]["mlp"]["calls"] == 2
    assert out["layers"]["mlp"]["elem_sparsity"] == 0.5
    assert out["layers"]["mlp"]["fragment_sparsity"] == 0.5
    assert out["overall"]["elem_sparsity"] == 0.5
    meter.reset()
    assert meter.summary()["layers"] == {}


def test_sparsify_fragments_structure():
    from repro.models.layers import sparsify_fragments
    x = jnp.asarray(np.random.RandomState(0).randn(4, 32), jnp.float32)
    y = sparsify_fragments(x, 4, 0.75)
    live = np.asarray(S.fragment_occupancy(y, 4).reshape(-1))
    # per-row live fragments at most the keep budget (no batch union here:
    # check row-wise)
    yv = np.asarray(y).reshape(4, 8, 4)
    per_row_live = (np.abs(yv) > 0).any(-1).sum(-1)
    assert (per_row_live <= 2).all() and (per_row_live >= 1).all()
    # kept values are untouched
    xv = np.asarray(x).reshape(4, 8, 4)
    kept = (np.abs(yv) > 0)
    np.testing.assert_array_equal(yv[kept], xv[kept])
    with pytest.raises(ValueError, match="does not tile"):
        sparsify_fragments(x, 5, 0.5)


# ---------------------------------------------------------------------------
# hypothesis property test (only this test skips when hypothesis is absent —
# a module-level importorskip would take the always-on sweeps above with it)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 2 ** 16), m=st.sampled_from([2, 4, 8]),
           k_tiles=st.integers(1, 3), frag_per_tile=st.integers(1, 3),
           frac=st.floats(0.0, 1.0))
    @settings(max_examples=20, deadline=None)
    def test_block_skip_bit_identity_property(seed, m, k_tiles,
                                              frag_per_tile, frac):
        """For ARBITRARY fragment-sparsity patterns — including zero
        fragments straddling K-tile boundaries — the block-skip kernel is
        bit-identical to the dense kernel with the same tiling."""
        bk = m * frag_per_tile
        K = bk * k_tiles
        M, N, bm, bn = 4, 8, 4, 8
        x, mags, signs, scale = _operands(seed, M, K, N, m)
        x = _sparsify(x, m, frac, seed=seed)
        dense = kernel_matmul(x, mags, signs, scale, m=m, bm=bm, bn=bn,
                              bk=bk, interpret=True)
        mask = S.block_mask(x, bm, bk)
        skip = kernel_matmul(x, mags, signs, scale, mask, m=m, bm=bm,
                             bn=bn, bk=bk, interpret=True)
        assert bool(jnp.all(dense == skip))
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_block_skip_bit_identity_property():
        pass
