"""Per-arch smoke tests: reduced configs, one forward/train step on CPU,
output shapes + no NaNs (assignment requirement), decode == forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config, get_reduced, shapes_for
from repro.configs.base import TrainConfig
from repro.models.registry import build
from repro.training import train_loop


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_shapes_no_nans(name):
    cfg = get_reduced(name)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = m.make_inputs(jax.random.PRNGKey(1), 2, 16)
    logits, aux = m.forward(params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_one_train_step_no_nans(name):
    cfg = get_reduced(name)
    m = build(cfg)
    tcfg = TrainConfig(total_steps=10, warmup_steps=2, remat=False)
    state, _ = train_loop.init_train_state(m, tcfg, jax.random.PRNGKey(0))
    step = jax.jit(train_loop.make_train_step(m, tcfg))
    batch = m.make_inputs(jax.random.PRNGKey(1), 2, 16)
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(state.step) == 1
    # params actually moved
    moved = jax.tree_util.tree_reduce(
        lambda acc, x: acc + float(jnp.sum(jnp.abs(x))), state.params, 0.0)
    assert moved > 0.0


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_decode_matches_forward(name):
    """Teacher-forced sequential decode reproduces the full forward pass."""
    cfg = dataclasses.replace(get_reduced(name), dtype="float32",
                              num_image_tokens=0, capacity_factor=64.0)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    T = 8
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, T), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.family == "whisper":
        batch["frames"] = jax.random.normal(jax.random.PRNGKey(8),
                                            (2, T, cfg.d_model))
    full_logits, _ = m.forward(params, batch)
    cache = m.init_cache(2, T, dtype=jnp.float32)
    if cfg.family == "whisper":
        from repro.models import whisper as W
        cache["enc_out"] = W.encode(cfg, params, batch["frames"]).astype(
            cache["enc_out"].dtype)
    errs = []
    for t in range(T):
        lg, cache = m.decode_step(params, tokens[:, t:t + 1], cache,
                                  jnp.array(t, jnp.int32))
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - full_logits[:, t]))))
    assert max(errs) < 5e-4, f"decode diverges from forward: {max(errs)}"


def test_long_500k_only_for_subquadratic():
    names = {n: [s.name for s in shapes_for(get_config(n))] for n in ARCH_NAMES}
    assert "long_500k" in names["xlstm-350m"]
    assert "long_500k" in names["zamba2-2.7b"]
    for n in ARCH_NAMES:
        if n not in ("xlstm-350m", "zamba2-2.7b"):
            assert "long_500k" not in names[n]


def test_full_configs_match_assignment():
    """The exact assigned architecture numbers."""
    c = get_config("yi-9b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (48, 4096, 32, 4, 11008, 64000)
    c = get_config("deepseek-v3-671b")
    assert (c.num_layers, c.d_model, c.num_heads, c.vocab_size,
            c.num_experts, c.experts_per_token) == (61, 7168, 128, 129280, 256, 8)
    assert c.mla is not None and c.mtp
    assert (c.d_ff, c.moe_d_ff, c.first_k_dense_replace,
            c.num_shared_experts, c.norm_eps) == (18432, 2048, 3, 1, 1e-6)
    assert (c.router_scoring, c.router_bias, c.n_group, c.topk_group,
            c.norm_topk_prob, c.routed_scaling_factor) == (
                "sigmoid", True, 8, 4, True, 2.5)
    assert c.held_experts() == 256
    assert (c.rope_scaling.factor, c.rope_scaling.mscale_all_dim,
            c.rope_scaling.original_max_position_embeddings) == (40, 1, 4096)
    c = get_config("qwen2-1.5b")
    assert c.qkv_bias and c.vocab_size == 151936 and c.num_kv_heads == 2
    c = get_config("olmoe-1b-7b")
    assert c.num_experts == 64 and c.experts_per_token == 8
    assert c.router_scoring == "softmax" and c.first_k_dense_replace == 0
    c = get_config("h2o-danube-1.8b")
    assert c.sliding_window is not None
    c = get_config("zamba2-2.7b")
    assert c.ssm_state == 64 and c.num_layers == 54
    c = get_config("whisper-small")
    assert c.encoder_layers == 12 and c.num_layers == 12
    c = get_config("xlstm-350m")
    assert c.num_layers == 24 and c.d_model == 1024 and c.num_heads == 4
    c = get_config("phi-3-vision-4.2b")
    assert c.num_layers == 32 and c.d_model == 3072
    c = get_config("qwen1.5-4b")
    assert c.num_layers == 40 and c.num_kv_heads == 20


def test_vlm_consumes_patch_embeds():
    cfg = get_reduced("phi-3-vision-4.2b")
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = m.make_inputs(jax.random.PRNGKey(1), 2, 16)
    assert "patch_embeds" in batch
    assert batch["tokens"].shape[1] == 16 - cfg.num_image_tokens
    logits, _ = m.forward(params, batch)
    assert logits.shape[1] == 16  # image + text positions
