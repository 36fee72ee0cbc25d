"""Compile-only checks for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts (unsupported casts,
block shapes off the (8, 128) tiling, programs that do not fit HBM), so the
served kernel and one full-width compressed decode step are compiled here
for a v5e and checked to contain the Pallas kernel (``tpu_custom_call``).
Nothing runs: these say nothing about results or times.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.fragments import is_crossbar_weight
from repro.core.paths import path_str
from repro.forms import FormsSpec, default_spec
from repro.forms.tree import _compress_leaf
from repro.kernels import ops
from repro.models.registry import build

KERNEL = 'custom_call_target="tpu_custom_call"'
# the paged-attention kernel's custom call is named after the kernel
PAGED_KERNEL = r"%paged_attention[.\d]* = [^\n]*tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_routing(monkeypatch):
    """Route the kernel ops as on a TPU: compiled Pallas, no interpreter,
    no jnp oracle."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("m", [8, 4])
@pytest.mark.parametrize("K,N", [(1536, 8960), (8960, 1536)])
@pytest.mark.parametrize("M", [8, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("zero_skip", ["off", "block"])
def test_polarized_matmul_compiles_for_v5e(one_chip, tpu_routing, zero_skip,
                                           M, K, N, m):
    args = _on(one_chip, (jax.ShapeDtypeStruct((M, K), jnp.float32),
                          jax.ShapeDtypeStruct((K, N), jnp.uint8),
                          jax.ShapeDtypeStruct((K // m, N), jnp.int8),
                          jax.ShapeDtypeStruct((1, N), jnp.float32)))
    fn = functools.partial(ops.polarized_matmul, m=m, zero_skip=zero_skip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert KERNEL in text


@pytest.mark.parametrize("k_sharded", [False, True],
                         ids=["column-parallel", "row-parallel"])
def test_polarized_matmul_compiles_on_2x2_mesh(topo, tpu_routing, k_sharded):
    """A Mosaic kernel cannot be partitioned automatically: on a mesh the
    op runs it per device, whichever way the weights were placed."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import ParallelContext, parallel_context

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    w = P("model", None) if k_sharded else P(None, "model")
    shapes = (((8, 1536), jnp.float32, P("data", None)),
              ((1536, 8960), jnp.uint8, w), ((192, 8960), jnp.int8, w),
              ((1, 8960), jnp.float32, P(None, w[1])))
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, p))
            for s, d, p in shapes]
    with parallel_context(ParallelContext.for_mesh(mesh)):
        text = jax.jit(functools.partial(ops.polarized_matmul, m=8)).lower(
            *args).compile().as_text()
    assert KERNEL in text


def _compressed_shapes(params, spec):
    """Shapes of ``compress_tree(params, spec)[0]`` without computing it."""
    def leaf(path, x):
        p = path_str(path)
        if not is_crossbar_weight(p, x.shape):
            return x
        fn = functools.partial(_compress_leaf, name=p.rsplit("/", 1)[-1],
                               spec=spec)
        return jax.eval_shape(fn, x)[0]
    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.mark.parametrize("step", ["decode_step", "decode_paged"])
def test_qwen2_full_width_compressed_decode_compiles_for_v5e(
        one_chip, tpu_routing, step):
    """qwen2-1.5b at its published widths, FORMS m=8 / 8-bit, 8 slots of
    1024 positions (16-row pages for the paged step): the compiled step
    holds one kernel per compressed projection and fits one chip."""
    slots, max_len, page = 8, 1024, 16
    model = build(get_config("qwen2-1.5b"))
    spec = FormsSpec(m=8, bits=8)
    params = _compressed_shapes(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), spec)
    toks = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32)
    if step == "decode_step":
        cache = jax.eval_shape(
            functools.partial(model.init_cache, slots, max_len))
        extra = ()
    else:
        n_pages = slots * max_len // page + 1
        cache = jax.eval_shape(functools.partial(
            model.init_paged_cache, n_pages, page, slots, max_len))
        extra = (jax.ShapeDtypeStruct((slots, max_len // page), jnp.int32),)
    call = getattr(model, step)

    def fn(*a):
        with default_spec(spec):
            return call(*a)

    compiled = jax.jit(fn).lower(
        *_on(one_chip, (params, toks, cache, pos) + extra)).compile()
    text = compiled.as_text()
    # q, k, v, o, gate, up, down: the layer scan's body holds each once;
    # the paged step adds the paged-attention kernel, which reads the pool
    # in place: no op of the program gathers per-slot views
    paged = step == "decode_paged"
    assert text.count(KERNEL) == 7 + paged, text.count(KERNEL)
    assert bool(re.search(PAGED_KERNEL, text)) == paged
    assert "/kv_gather/" not in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 10**9), mem


def test_deepseek_ep32_share_compressed_decode_compiles_for_v5e(
        one_chip, tpu_routing):
    """DeepSeek-V3 at its published widths as one chip's share of 32-way
    expert parallelism (3 dense + 4 MoE layers, 8 held experts, a 16160-row
    vocabulary slice), FORMS m=8 / 8-bit, 128 slots of 1024 in 16-row
    pages: the paged decode step holds one kernel per compressed matmul a
    scan body calls and fits one chip."""
    import dataclasses
    slots, max_len, page = 128, 1024, 16
    cfg = dataclasses.replace(get_config("deepseek-v3-671b"), num_layers=7,
                              experts_held=8, vocab_size=16160, mtp=False)
    model = build(cfg)
    spec = FormsSpec(m=8, bits=8)
    params = _compressed_shapes(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), spec)
    cache = jax.eval_shape(functools.partial(
        model.init_paged_cache, slots * max_len // page + 1, page, slots,
        max_len))
    args = (params, jax.ShapeDtypeStruct((slots, 1), jnp.int32), cache,
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots, max_len // page), jnp.int32))

    def fn(*a):
        with default_spec(spec):
            return model.decode_paged(*a)

    compiled = jax.jit(fn, donate_argnums=(2,)).lower(
        *_on(one_chip, args)).compile()
    # dense layers: q_down, q_up, kv_down, wo, gate, up, down; MoE layers:
    # the same MLA four, the held experts' gate, up, down (one scan over
    # the experts) and the shared expert's three; the head
    assert compiled.as_text().count(KERNEL) == 7 + 10 + 1
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 10**9), mem


@pytest.mark.parametrize("arch,window", [("qwen2", None), ("danube", 4096)])
def test_paged_attention_compiles_for_v5e(one_chip, tpu_routing, arch,
                                          window):
    """The paged-attention kernel at the decode_heavy cells' widths (32
    slots x 64 pages of 16 rows): qwen2-1.5b (12/2 heads of 128, 28
    layers) and h2o-danube-1.8b (32/8 heads of 80 in a pool padded to 128
    lanes, 24 layers, window 4096).  It reads the pools where they are:
    the program makes no copy of them."""
    layers, heads, kv, hd = {"qwen2": (28, 12, 2, 128),
                             "danube": (24, 32, 8, 80)}[arch]
    slots, n_tables, page = 32, 64, 16
    pool = (layers, slots * n_tables + 1, page, kv, ops.pool_lanes(hd))
    bf = jnp.bfloat16
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((slots, 1, heads, hd), bf),
        jax.ShapeDtypeStruct((slots, 1, kv, hd), bf),
        jax.ShapeDtypeStruct((slots, 1, kv, hd), bf),
        jax.ShapeDtypeStruct(pool, bf), jax.ShapeDtypeStruct(pool, bf),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots, n_tables), jnp.int32)))
    compiled = jax.jit(functools.partial(ops.paged_attention,
                                         window=window)).lower(
        *args).compile()
    assert re.search(PAGED_KERNEL, compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
