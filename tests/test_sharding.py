"""Sharding rules: specs by path, divisibility fallback, FSDP extension."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import MeshConfig
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh, single_device_mesh


def test_param_spec_rules():
    assert tuple(shd.param_spec("blocks/attn/wq", (64, 64))) == (None, "model")
    assert tuple(shd.param_spec("blocks/attn/wo", (64, 64))) == ("model",)
    assert tuple(shd.param_spec("blocks/mlp/down", (64, 64))) == ("model",)
    assert tuple(shd.param_spec("embed", (1000, 64))) == ("model",)
    # scanned MoE expert weights are rank 4: (L, E, d, f) -> experts on model
    assert tuple(shd.param_spec("blocks/moe/w_gate", (4, 8, 64, 64),
                                scanned=True)) == (None, "model")
    assert tuple(shd.param_spec("moe/w_gate", (8, 64, 64))) == ("model",)
    assert tuple(shd.param_spec("blocks/norm1", (64,))) == ()
    # scanned: leading L axis skipped
    assert tuple(shd.param_spec("blocks/attn/wq", (4, 64, 64),
                                scanned=True)) == (None, None, "model")


def test_checked_spec_divisibility_fallback():
    mesh = single_device_mesh()
    ctx = shd.ParallelContext.for_mesh(mesh)
    # axis size 1 -> always replicate
    spec = shd._checked_spec(("batch", "model"), (8, 8), ctx)
    assert tuple(spec) == ()


def test_constrain_noop_without_context():
    x = jnp.ones((4, 4))
    y = shd.constrain(x, "batch", "model")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_params_shardings_tree():
    mesh = single_device_mesh()
    ctx = shd.ParallelContext.for_mesh(mesh)
    params = {"embed": jnp.zeros((100, 16)),
              "blocks": {"attn": {"wq": jnp.zeros((2, 16, 16))}}}
    sh = shd.params_shardings(params, ctx)
    leaves = jax.tree_util.tree_leaves(
        sh, is_leaf=lambda x: hasattr(x, "spec"))
    assert all(hasattr(l, "spec") for l in leaves)


def test_fsdp_extend_picks_largest_divisible_dim():
    class FakeCtx:
        batch_axes = ("data",)
        mesh = type("M", (), {"shape": {"data": 4}})()

    entries = shd._fsdp_extend([None, "model"], (64, 128), FakeCtx(),
                               threshold=1)
    assert entries[0] == "data"
    # too small: untouched
    entries = shd._fsdp_extend([None, None], (4, 4), FakeCtx(),
                               threshold=1 << 22)
    assert entries == [None, None]
    # non-divisible dims skipped
    entries = shd._fsdp_extend([None, None], (7, 13), FakeCtx(), threshold=1)
    assert entries == [None, None]


def test_reshard_state_roundtrip():
    mesh = single_device_mesh()
    ctx = shd.ParallelContext.for_mesh(mesh)
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    sh = shd.params_shardings(tree, ctx)
    out = shd.reshard_state(tree, sh)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


def test_parallel_context_resolution():
    mesh = single_device_mesh()
    ctx = shd.ParallelContext.for_mesh(mesh)
    assert ctx.resolve("batch") == "data"
    assert ctx.resolve("model") == "model"
    assert ctx.resolve("tokens") == ("data", "model")
    assert ctx.resolve(None) is None
    with pytest.raises(ValueError):
        ctx.resolve("bogus")


def test_forms_leaves_get_cosharded_trio():
    """params_shardings on a compressed tree: the FormsLinearParams leaf
    flattens to a sharding trio with one shared N entry (single-device mesh;
    the multi-device behaviour is covered by test_serving_sharded.py)."""
    from repro.forms import FormsSpec, compress_tree

    mesh = single_device_mesh()
    ctx = shd.ParallelContext.for_mesh(mesh)
    params = {"blocks": {"attn": {"wq": jnp.ones((2, 16, 16))}},
              "norm": jnp.ones((16,))}
    comp, _ = compress_tree(params, FormsSpec(m=8))
    sh = shd.params_shardings(comp, ctx)
    trio = sh["blocks"]["attn"]["wq"]
    assert hasattr(trio.mags, "spec") and hasattr(trio.signs, "spec")
    placed = shd.reshard_state(comp, sh)
    np.testing.assert_array_equal(
        np.asarray(placed["blocks"]["attn"]["wq"].mags),
        np.asarray(comp["blocks"]["attn"]["wq"].mags))


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 devices (the XLA_FLAGS CI job)")
def test_compressed_tree_shards_on_8_devices():
    """On a real 2x4 mesh: N co-shards over the model axis on all three
    planes, the cache slot dim shards over data, and the co-sharding
    validator passes."""
    from repro.forms import FormsSpec, compress_tree, validate_tree_sharding

    mesh = make_mesh(MeshConfig(data=2, model=4))
    ctx = shd.ParallelContext.for_mesh(mesh)
    params = {"blocks": {"attn": {"wq": jnp.ones((2, 64, 128)),
                                  "wo": jnp.ones((2, 128, 64))}}}
    comp, rep = compress_tree(params, FormsSpec(m=8), ctx=ctx)
    assert rep.shardings["blocks/attn/wq"] == str(
        comp["blocks"]["attn"]["wq"].mags.sharding.spec)
    checked = validate_tree_sharding(comp)
    wq_spec = tuple(checked["blocks/attn/wq"])
    assert wq_spec[-1] == "model"
    cache = {"k": jnp.zeros((2, 8, 32, 4, 16))}
    csh = shd.cache_shardings(cache, ctx)
    assert tuple(csh["k"].spec)[1] == "data"
