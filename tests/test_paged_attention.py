"""The paged decode-attention kernel (kernels/paged_attention.py), in
interpret mode, against what the gather path computes on the same pool:
``kv_cache.gather_views`` -> the new token's K/V written at ``pos`` ->
``layers.decode_attention``.

Tolerance: K/V, q and the output are bf16, scores and accumulation f32 in
both.  The two differ in summation order (an online softmax over page
blocks against one softmax over the whole view) and in where probabilities
round to bf16 (unnormalised in the kernel), so an output element may differ
by a couple of bf16 rounding steps: 2^-7 relative, 0.0156 absolute for
values in [2, 4).  Outputs are convex mixtures of unit-normal V rows, so
``atol = rtol = 2e-2`` holds them to about two such steps.
"""
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import layers as L
from repro.serving import kv_cache as KV

TOL = dict(atol=2e-2, rtol=2e-2)


@dataclasses.dataclass(frozen=True)
class Geometry:
    heads: int
    kv: int
    hd: int
    page_size: int
    n_tables: int
    window: Optional[int] = None
    layers: int = 2
    slots: int = 4

    @property
    def cap(self) -> int:
        return self.n_tables * self.page_size

    @property
    def num_pages(self) -> int:
        return 1 + self.slots * self.n_tables


GEOMETRIES = {
    "qwen2": Geometry(heads=12, kv=2, hd=128, page_size=16, n_tables=8),
    "danube": Geometry(heads=32, kv=8, hd=80, page_size=16, n_tables=8,
                       window=40),
    "tiny": Geometry(heads=4, kv=2, hd=16, page_size=8, n_tables=6),
    # 70-page tables: a slot spans up to 3 blocks of the kernel, and the
    # window can skip whole blocks
    "blocks": Geometry(heads=4, kv=2, hd=16, page_size=8, n_tables=70,
                       window=200),
}


def _positions(geo: Geometry, kind: str) -> list:
    ps, cap = geo.page_size, geo.cap
    return {
        "zero": [0, 0, 1, 0],
        "page_edge": [ps - 1, ps, 2 * ps - 1, 2 * ps],
        "mid_page": [ps // 2, ps + 3, 3 * ps + ps // 2, 5],
        "table_end": [cap - 1, cap - 2, cap - ps, cap - ps - 1],
        "past_window": [cap - 1, (geo.window or 0) + ps + 5,
                        (geo.window or 0) + 1, 2 * ps + 1],
        "idle_slot": [ps + 1, 0, cap - 1, 7],
        "shared_pages": [cap - 1, cap - 5, ps * 3, 2],
    }[kind]


def _case(geo: Geometry, kind: str, seed: int = 0):
    rng = np.random.default_rng(seed)
    shape = (geo.layers, geo.num_pages, geo.page_size, geo.kv, geo.hd)
    k_pool = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v_pool = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    tables = (1 + rng.permutation(geo.num_pages - 1)).reshape(
        geo.slots, geo.n_tables).astype(np.int32)
    if kind == "idle_slot":
        tables[1] = KV.SCRATCH_PAGE             # no pages: only scratch-0
    if kind == "shared_pages":
        tables[1, :geo.n_tables // 2] = tables[0, :geo.n_tables // 2]
    q = jnp.asarray(rng.standard_normal((geo.slots, 1, geo.heads, geo.hd)),
                    jnp.bfloat16)
    k_new, v_new = (jnp.asarray(rng.standard_normal(
        (geo.slots, 1, geo.kv, geo.hd)), jnp.bfloat16) for _ in range(2))
    pos = jnp.asarray(_positions(geo, kind), jnp.int32)
    return q, k_new, v_new, k_pool, v_pool, pos, jnp.asarray(tables)


def _gathered(q, k_new, v_new, k_pool, v_pool, layer, pos, tables, *,
              page_size, window):
    """The gather path: views of the pool, the new rows written at pos."""
    cache = KV.PagedKVCache(pool={"k": k_pool, "v": v_pool}, dense={},
                            page_size=page_size)
    views = KV.gather_views(cache, tables)
    bidx = jnp.arange(q.shape[0])[:, None]
    grid = pos[:, None]
    kc = views["k"][layer].at[bidx, grid].set(k_new)
    vc = views["v"][layer].at[bidx, grid].set(v_new)
    return L.decode_attention(q, kc, vc, grid, window=window)


KINDS = ["zero", "page_edge", "mid_page", "table_end", "past_window",
         "idle_slot", "shared_pages"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("geo", list(GEOMETRIES), ids=list(GEOMETRIES))
def test_kernel_matches_gathered_decode_attention(geo, kind):
    g = GEOMETRIES[geo]
    q, k_new, v_new, k_pool, v_pool, pos, tables = _case(g, kind)
    layer = 1
    scale = float(np.float32(1.0) / np.sqrt(np.float32(g.hd)))
    got = paged_decode_attention(
        q[:, 0], k_new[:, 0], v_new[:, 0], k_pool, v_pool, jnp.int32(layer),
        pos, tables, scale=scale, window=g.window, interpret=True)
    want = _gathered(q, k_new, v_new, k_pool, v_pool, layer, pos, tables,
                     page_size=g.page_size, window=g.window)[:, 0]
    assert got.shape == want.shape and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL)


@pytest.mark.parametrize("kind", ["page_edge", "past_window"])
def test_op_reads_a_lane_padded_pool(kind):
    """``ops.paged_attention`` on a pool whose lanes are ``hd`` zero-padded
    to 128 (the TPU pool layout, ``ops.pool_lanes``) gives the unpadded
    gather path's answer at ``hd``."""
    g = GEOMETRIES["danube"]
    q, k_new, v_new, k_pool, v_pool, pos, tables = _case(g, kind, seed=1)
    lanes = [(0, 0)] * 4 + [(0, 128 - g.hd)]
    got = ops.paged_attention(q, k_new, v_new, jnp.pad(k_pool, lanes),
                              jnp.pad(v_pool, lanes), jnp.int32(0), pos,
                              tables, window=g.window)
    want = _gathered(q, k_new, v_new, k_pool, v_pool, 0, pos, tables,
                     page_size=g.page_size, window=g.window)
    assert got.shape == want.shape == (g.slots, 1, g.heads, g.hd)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL)


def test_rows_past_the_live_span_weigh_nothing():
    """Rows a slot does not hold yet may hold stale values (large ones
    here): past ``pos`` on its own last page, and every page after it.
    The answer is the same to the bit."""
    g = GEOMETRIES["tiny"]
    q, k_new, v_new, k_pool, v_pool, pos, tables = _case(g, "mid_page")
    want = paged_decode_attention(
        q[:, 0], k_new[:, 0], v_new[:, 0], k_pool, v_pool, jnp.int32(0), pos,
        tables, scale=0.25, interpret=True)
    t = np.asarray(tables)
    k_np, v_np = np.array(k_pool, np.float32), np.array(v_pool, np.float32)
    for b, p in enumerate(np.asarray(pos)):
        for i in range(g.n_tables):
            lo = max(0, int(p) - i * g.page_size)   # live rows on page i
            k_np[0, t[b, i], lo:] = 1e4
            v_np[0, t[b, i], lo:] = -1e4
    got = paged_decode_attention(
        q[:, 0], k_new[:, 0], v_new[:, 0], jnp.asarray(k_np, jnp.bfloat16),
        jnp.asarray(v_np, jnp.bfloat16), jnp.int32(0), pos, tables,
        scale=0.25, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
