"""DeepSeek-V3 served as one expert-parallel chip's share, at a small size
on the CPU, against the plain float32 reference of the benchmark's moe
family (``chipbench/families/moe.py``, loaded by path).

The model: 1 leading dense layer and 2 MoE layers, MLA with YaRN rope, 16
routed experts in 4 groups (top-2 groups, top-4 experts) under sigmoid
scores with a correction bias, 1 shared expert; this share holds 4 of the
16 experts.
"""
import dataclasses
import hashlib
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_reduced
from repro.forms import FormsSpec, compress_tree, compressed_paths
from repro.models import moe
from repro.models.registry import build
from repro.serving.engine import Request, ServingEngine

CHIPBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")


@pytest.fixture(scope="module")
def family():
    if CHIPBENCH not in sys.path:
        sys.path.insert(0, CHIPBENCH)      # the family imports work.py
    spec = importlib.util.spec_from_file_location(
        "family_moe", os.path.join(CHIPBENCH, "families", "moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MC = dict(
    num_layers=3, first_k_dense_replace=1, d_model=64, num_heads=4,
    num_kv_heads=4, head_dim=16, d_ff=96, vocab_size=128, num_experts=16,
    experts_per_token=4, moe_d_ff=32, num_shared_experts=1, n_group=4,
    topk_group=2, router_scoring="sigmoid", router_bias=True,
    norm_topk_prob=True, routed_scaling_factor=2.5, experts_held=4,
    expert_first=0,
    mla=dict(q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16),
    rope_scaling=dict(factor=4.0, original_max_position_embeddings=16,
                      beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                      mscale_all_dim=1.0, type="yarn"),
    rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=False,
    dtype="float32")
FORMS = dict(m=8, bits=8)
SEED = 2 ** 33 + 5          # a seed beyond 32 bits, as the benchmark draws


def _cfg(**over):
    return dataclasses.replace(get_config("deepseek-v3-671b"),
                               **dict(MC, **over))


def _engine(family, **kw):
    return ServingEngine(build(_cfg()), family.engine_params(MC, FORMS, SEED),
                         max_len=32, batch_slots=2, spec=FormsSpec(**FORMS),
                         page_size=8, **kw)


def test_prefill_then_paged_decode_matches_reference_logits(family):
    """Bulk prefill of a prompt, then paged decode of the following tokens
    one at a time, on the engine's compressed tree: every position's
    logits against the reference's full forward.  The program computes in
    float32 here, over a float32 page pool, with the compressed weights
    the reference projects: 1e-5 is float32 rounding (the differences read
    4e-7 on logits up to 0.49), where the bfloat16 pool the engine keeps
    moves them by 3e-3 and 4-bit codes of the same weights by 0.05 to
    0.15."""
    eng = _engine(family)
    m, params = eng.model, eng.params
    seq = np.array([3, 17, 99, 5, 64, 1, 2, 40, 77, 8, 31, 120, 6, 90, 15,
                    55], np.int32)
    n = 7
    cache = m.init_paged_cache(5, 8, 2, 32, dtype=jnp.float32)
    tables = np.zeros((2, 4), np.int32)
    tables[0, :2] = [1, 2]
    toks = np.zeros((1, 8), np.int32)
    toks[0, :n] = seq[:n]
    lg, cache = jax.jit(m.prefill_paged)(
        params, jnp.asarray(toks), cache, jnp.asarray([1], jnp.int32),
        jnp.int32(0), jnp.int32(n))
    got = [np.asarray(lg)[0]]
    step = jax.jit(m.decode_paged)
    for t in range(n, len(seq)):
        tok = np.zeros((2, 1), np.int32)
        tok[0, 0] = seq[t]
        lg, cache = step(params, jnp.asarray(tok), cache,
                         jnp.asarray([t, 0], jnp.int32), jnp.asarray(tables))
        got.append(np.asarray(lg)[0, 0])
    padded = np.zeros(256, np.int32)
    padded[:len(seq)] = seq
    want = family.logits(MC, FORMS, SEED, padded)[n - 1:len(seq)]
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=1e-5)


def test_engine_serves_the_references_tokens(family):
    """Greedy tokens the engine serves (bulk prefill, paged decode, the
    fleet scheduler) are the reference's best at each position: the
    reference's best logit lies within 6e-3 of its logit of the served
    token (the engine's bfloat16 page pool moves a logit by up to 3e-3,
    the test above)."""
    eng = _engine(family, slo=dict(prefill_chunk=0, step_token_budget=0))
    prompts = [np.array([3, 17, 99, 5, 64, 1, 2], np.int32),
               np.array([9, 4, 33], np.int32)]
    res = sorted(eng.run([Request(uid=i, prompt=p, max_new_tokens=8)
                          for i, p in enumerate(prompts)]),
                 key=lambda r: r.uid)
    gaps = family.gaps(MC, FORMS, SEED, prompts,
                       [np.array(r.tokens) for r in res], 32, 16)
    assert gaps.size == 16 and gaps.max() < 6e-3
    c = eng.stats()["counters"]
    # 2 MoE layers x 4 held experts over every prefill and decode row
    rows = c["prefill.rows"] + c["decode.rows"]
    assert c["moe.rows"] == 2 * 4 * rows
    assert c["moe.routes"] == 2 * 4 * rows
    assert 0 < c["moe.routed"] < c["moe.rows"]


def test_shares_add_up_to_the_uncut_layer(family):
    """Four shares of four experts each: their routed parts, with the
    shared expert counted once, add up to the uncut reference layer over
    all 16 experts (float32; 1e-5 is rounding)."""
    full = dict(MC, experts_held=16)
    lp = family.make_layer(full, SEED, 1)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, MC["d_model"]))
    want = family._experts(lp, x[0], full, False)
    shared = np.asarray(moe._shared_expert(lp, x[0], jnp.float32))
    total = shared.copy()
    for first in range(0, 16, 4):
        share = dict(lp, **{k: lp[k][first:first + 4] for k in moe.EXPERTS})
        y, counts = moe.held_moe_ffn(share, x, _cfg(expert_first=first),
                                     jnp.float32)
        total += np.asarray(y[0]) - shared
    np.testing.assert_allclose(total, np.asarray(want), rtol=0, atol=1e-5)


def test_group_limited_routing_by_hand():
    """8 experts in 4 groups of 2, top-2 groups, top-2 experts, router =
    identity so the logits are the row itself.  Biased choice scores:
    groups {0,1} 1.150, {2,3} 1.221, {4,5} 1.620, {6,7} 1.972 (expert 6's
    +0.9 bias), so groups {4,5} and {6,7} survive and expert 0 (score
    0.881) is out though it beats 4 and 5; the top two are 6 (biased
    1.019) and 7 (0.953), weighted by their un-biased scores 0.119 and
    0.953, normalized, times 2.5."""
    cfg = dataclasses.replace(get_reduced("deepseek-v3-671b"), d_model=8,
                              num_experts=8, experts_per_token=2, n_group=4,
                              topk_group=2, routed_scaling_factor=2.5)
    logits = np.array([[2.0, -1.0, 0.5, 0.4, 1.5, 1.4, -2.0, 3.0]],
                      np.float32)
    bias = np.zeros(8, np.float32)
    bias[6] = 0.9
    w, idx, _ = moe.route({"router": jnp.eye(8), "router_bias": bias},
                          jnp.asarray(logits), cfg)
    assert sorted(np.asarray(idx)[0].tolist()) == [6, 7]
    s6, s7 = (1 / (1 + math.exp(-v)) for v in (-2.0, 3.0))
    want = {6: 2.5 * s6 / (s6 + s7), 7: 2.5 * s7 / (s6 + s7)}
    for e, v in zip(np.asarray(idx)[0], np.asarray(w)[0]):
        assert v == pytest.approx(want[int(e)], rel=1e-6)


def test_a_row_is_the_same_alone_and_in_a_batch(family):
    """Dropless: a row's output does not depend on the rows beside it,
    even when most of the batch routes to one expert (the training
    dispatch at its capacity would drop some of them)."""
    lp = family.make_layer(MC, SEED, 1)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 1, MC["d_model"]))
    batch = jnp.concatenate([jnp.repeat(x, 15, axis=1),
                             jax.random.normal(jax.random.PRNGKey(3),
                                               (1, 1, MC["d_model"]))], 1)
    cfg = _cfg()
    alone, _ = moe.held_moe_ffn(lp, x, cfg, jnp.float32)
    together, _ = moe.held_moe_ffn(lp, batch, cfg, jnp.float32)
    np.testing.assert_allclose(np.asarray(together[0, :15]),
                               np.repeat(np.asarray(alone[0]), 15, 0),
                               rtol=0, atol=1e-6)


def test_padded_and_exact_length_prefill_agree(family):
    """A prompt prefilled in a 16-wide bucket and at its exact length gives
    the same last-token logits and the same latents at its positions
    (pad rows route too, and take nothing from the real ones)."""
    eng = _engine(family)
    m, params = eng.model, eng.params
    prompt = np.array([3, 17, 99, 5, 64], np.int32)
    out = []
    for width in (5, 16):
        toks = np.zeros((1, width), np.int32)
        toks[0, :5] = prompt
        cache = m.init_paged_cache(5, 8, 2, 32)
        pages = jnp.asarray([1, 2][:-(-width // 8)], jnp.int32)
        lg, cache = m.prefill_paged(params, jnp.asarray(toks), cache, pages,
                                    jnp.int32(0), jnp.int32(5))
        out.append((np.asarray(lg), np.asarray(cache.pool["c_kv"][:, 1, :5],
                                               np.float32)))
    np.testing.assert_allclose(out[0][0], out[1][0], rtol=0, atol=1e-5)
    np.testing.assert_allclose(out[0][1], out[1][1], rtol=0, atol=1e-2)


def test_layer_at_a_time_compression_is_bit_identical(family):
    """Compressing a layer stream one layer at a time gives exactly the
    codes, signs and scales of compressing the stacked tree."""
    streamed, _ = compress_tree(family.engine_params(MC, FORMS, SEED),
                                FormsSpec(**FORMS))
    stacked = family.engine_params(MC, FORMS, SEED)
    for group in ("dense_blocks", "blocks"):
        layers = [make() for make in stacked[group]]
        stacked[group] = jax.tree_util.tree_map(lambda *x: jnp.stack(x),
                                                *layers)
    whole, report = compress_tree(stacked, FormsSpec(**FORMS))
    a, b = compressed_paths(streamed), compressed_paths(whole)
    assert sorted(a) == sorted(b) and "blocks/moe/w_gate" in a
    assert "blocks/moe/router" not in a          # the router stays float
    for path in a:
        for x, y in zip((a[path].mags, a[path].signs, a[path].scale),
                        (b[path].mags, b[path].signs, b[path].scale)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# sha256 of the dense family's compressed codes (paths, magnitudes, signs,
# scales) of the reduced configurations at PRNGKey(3), read before layer
# streams came in: the whole-tree path compresses exactly as it did
DENSE_CODES = {
    "qwen2-1.5b":
        "d264b795a0ff5a4f238d47c8dfb92c56a1fed790de06c328604a763598d85df6",
    "h2o-danube-1.8b":
        "dca7708371112fc8060a050887d68e206c9b202d45d16ab90fb016066b254406",
}


@pytest.mark.parametrize("arch", sorted(DENSE_CODES))
def test_dense_whole_tree_codes_unchanged(arch):
    m = build(get_reduced(arch))
    tree, _ = compress_tree(m.init(jax.random.PRNGKey(3)),
                            FormsSpec(m=8, bits=8))
    h = hashlib.sha256()
    for path, fp in sorted(compressed_paths(tree).items()):
        h.update(path.encode())
        for x in (fp.mags, fp.signs, fp.scale):
            h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == DENSE_CODES[arch]


def test_serving_step_reads_expert_codes(family, monkeypatch):
    """The decode step and bulk prefill reconstruct no expert leaf: the
    held experts go through the polarized kernel on their codes (only
    MLA's absorbed ``kv_up`` is decompressed, in decode)."""
    from repro.models import layers
    eng = _engine(family)
    m, params = eng.model, eng.params
    seen = []
    real = layers.forms_to_dense
    monkeypatch.setattr(layers, "forms_to_dense",
                        lambda p: seen.append(p.mags.shape) or real(p))
    cache = m.init_paged_cache(5, 8, 2, 32)
    jax.eval_shape(m.prefill_paged, params, jnp.zeros((1, 8), jnp.int32),
                   cache, jnp.asarray([1], jnp.int32), jnp.int32(0),
                   jnp.int32(5))
    jax.eval_shape(m.decode_paged, params, jnp.zeros((2, 1), jnp.int32),
                   cache, jnp.zeros(2, jnp.int32), jnp.zeros((2, 4), jnp.int32))
    kv_up = params["blocks"]["mla"]["kv_up"].mags.shape[1:]
    assert seen and all(s == kv_up for s in seen), seen
