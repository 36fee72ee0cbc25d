"""The unified FORMS compression API (repro.forms).

Covers the acceptance surface of the redesign: FormsSpec validation,
compress_tree -> decompress_tree exactness on mixed pytrees (2D/3D/4D +
non-weight leaves), kernel-path parity of apply() vs dense matmul, serving
decode directly on a compressed pytree, checkpointing with uint8 magnitudes
on disk, and the removal of the PR-1 legacy shims.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import forms
from repro.core import polarization as polmod
from repro.core import quantization as quantmod
from repro.core.fragments import conv_to_matrix, pad_rows
from repro.forms import (FormsLinearParams, FormsSpec, compress_tree,
                         compressed_paths, decompress_tree)


def _mixed_tree():
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    return {
        "blocks": {"attn": {"wq": jax.random.normal(ks[0], (3, 24, 16))}},
        "conv0": jax.random.normal(ks[1], (3, 3, 4, 8)),
        "fc1": jax.random.normal(ks[2], (37, 10)),
        "fc1_b": jnp.zeros((10,)),
        "embed": jax.random.normal(ks[3], (32, 8)),
        "final_norm": jnp.ones((16,)),
    }


@functools.partial(jax.jit, static_argnames="spec")
def _reference_projection(w2d, spec):
    """The polarize->quantize projection compress_tree must invert exactly.

    Compiled like compress_tree's per-leaf conversion: XLA turns the
    division by the level count into a multiply by its reciprocal, so an
    op-by-op run of the same projection differs in the last bit of the
    scale."""
    mat = pad_rows(w2d.astype(jnp.float32), spec.m)
    pol, _ = polmod.project_polarize(mat, spec.m, rule=spec.rule)
    return quantmod.project_quantize(pol, spec.quant)[: w2d.shape[0]]


# ---------------------------------------------------------------------------
# FormsSpec
# ---------------------------------------------------------------------------

def test_spec_validation_errors():
    with pytest.raises(ValueError):
        FormsSpec(m=0)
    with pytest.raises(ValueError):
        FormsSpec(policy="X")
    with pytest.raises(ValueError):
        FormsSpec(rule="frozen")  # internal-only rule, not a spec value
    with pytest.raises(ValueError):
        FormsSpec(bits=7, cell_bits=2)
    with pytest.raises(ValueError):
        FormsSpec(input_bits=0)
    with pytest.raises(ValueError):
        FormsSpec(adc_bits=0)
    with pytest.raises(ValueError):
        FormsSpec(bk=0)


def test_spec_views_and_derived():
    spec = FormsSpec(m=4, bits=8, cell_bits=2, policy="H", n_sub_cols=64)
    assert spec.fragment.m == 4 and spec.fragment.policy == "H"
    assert spec.quant.bits == 8 and spec.quant.cells_per_weight == 4
    assert spec.levels == 255 and spec.cells_per_weight == 4
    assert spec.num_fragments(10) == 3 and spec.padded_k(10) == 12
    legacy = FormsSpec.from_legacy(spec.fragment, spec.quant)
    assert legacy.m == spec.m and legacy.bits == spec.bits


# ---------------------------------------------------------------------------
# compress_tree / decompress_tree
# ---------------------------------------------------------------------------

def test_compress_tree_mixed_pytree_leaves():
    tree = _mixed_tree()
    spec = FormsSpec(m=8, bits=8)
    comp, rep = compress_tree(tree, spec)
    by_path = compressed_paths(comp)
    assert set(by_path) == {"blocks/attn/wq", "conv0", "fc1"}
    assert rep.num_compressed == 3 and set(rep.errors) == set(by_path)
    assert rep.bytes_compressed < rep.bytes_dense

    wq = comp["blocks"]["attn"]["wq"]
    assert isinstance(wq, FormsLinearParams)
    assert wq.mags.dtype == jnp.uint8 and wq.signs.dtype == jnp.int8
    assert wq.mags.shape == (3, 24, 16)       # scan-stacked, K already /8
    assert wq.signs.shape == (3, 3, 16)
    assert comp["conv0"].orig_shape == (3, 3, 4, 8)
    # non-weight leaves pass through untouched (same objects)
    assert comp["fc1_b"] is tree["fc1_b"]
    assert comp["embed"] is tree["embed"]
    assert comp["final_norm"] is tree["final_norm"]


def test_decompress_is_exact_inverse_of_projection():
    tree = _mixed_tree()
    spec = FormsSpec(m=8, bits=8)
    dec = decompress_tree(compress_tree(tree, spec)[0])
    # 2D leaf: exactly the polarize->quantize projection
    np.testing.assert_array_equal(
        np.asarray(dec["fc1"]), np.asarray(_reference_projection(tree["fc1"], spec)))
    # 3D leaf: per-layer projection
    ref3 = jax.vmap(lambda w: _reference_projection(w, spec))(
        tree["blocks"]["attn"]["wq"])
    np.testing.assert_array_equal(np.asarray(dec["blocks"]["attn"]["wq"]),
                                  np.asarray(ref3))
    # 4D leaf: policy reshape round-trips to the original conv view
    assert dec["conv0"].shape == tree["conv0"].shape
    ref4 = _reference_projection(conv_to_matrix(tree["conv0"], spec.policy), spec)
    np.testing.assert_array_equal(
        np.asarray(conv_to_matrix(dec["conv0"], spec.policy)), np.asarray(ref4))
    # shapes and dtypes preserved everywhere
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(dec)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_compress_tree_idempotent_and_roundtrip_stable():
    tree = _mixed_tree()
    spec = FormsSpec(m=4, bits=8)
    comp, rep = compress_tree(tree, spec)
    comp2, rep2 = compress_tree(comp, spec)
    assert rep2.num_compressed == 0
    # a projected tree re-compresses with ~zero error (fixed point)
    dec = decompress_tree(comp)
    _, rep3 = compress_tree(dec, spec)
    assert rep3.max_error < 1e-5, rep3.errors


def test_apply_parity_with_dense_matmul():
    spec = FormsSpec(m=8, bits=8)
    w = jax.random.normal(jax.random.PRNGKey(1), (37, 12))
    fp, err = forms.from_dense(w, spec)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 37))
    y = forms.apply(fp, x, spec)
    assert y.shape == (2, 3, 12)
    # exact vs the decompressed weights (same math through the kernel)...
    y_proj = x @ forms.to_dense(fp)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_proj),
                               rtol=1e-4, atol=1e-4)
    # ...and within the conversion error vs the original dense weights
    y_dense = x @ w
    rel = float(jnp.linalg.norm(y - y_dense) / jnp.linalg.norm(y_dense))
    assert rel <= float(err) + 0.05


def test_default_spec_context_supplies_backend_hints():
    """The engine-style ambient spec reaches apply() without explicit args."""
    from repro.forms import linear as forms_linear
    w = jax.random.normal(jax.random.PRNGKey(6), (16, 8))
    fp, _ = forms.from_dense(w, FormsSpec(m=8))
    ambient = FormsSpec(m=4, prefer_ref=True, bm=64)  # m adapts to the leaf
    with forms_linear.default_spec(ambient):
        assert forms_linear._resolve_spec(fp, None) == dataclasses.replace(
            ambient, m=8)
        y = forms.apply(fp, jnp.ones((2, 16)))
    assert forms_linear._resolve_spec(fp, None) == FormsSpec(m=8)  # restored
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.ones((2, 16)) @ forms.to_dense(fp)),
                               rtol=1e-4, atol=1e-4)


def test_apply_rejects_stacked_and_mismatched_spec():
    spec = FormsSpec(m=8)
    tree = {"wq": jax.random.normal(jax.random.PRNGKey(3), (2, 16, 8))}
    comp, _ = compress_tree(tree, spec)
    with pytest.raises(ValueError):
        forms.apply(comp["wq"], jnp.ones((4, 16)))
    fp, _ = forms.from_dense(jnp.ones((16, 8)), spec)
    with pytest.raises(ValueError):
        forms.apply(fp, jnp.ones((4, 16)), FormsSpec(m=4))


# ---------------------------------------------------------------------------
# acceptance configs: paper_cnns + qwen2_1_5b
# ---------------------------------------------------------------------------

def test_paper_cnns_compress_and_forward():
    from repro.configs.paper_cnns import tiny_cnn
    from repro.models import cnn as cnn_mod
    cfg = tiny_cnn()
    params = cnn_mod.init(cfg, jax.random.PRNGKey(0))
    spec = FormsSpec(m=4, bits=8)
    comp, rep = compress_tree(params, spec)
    for name, leaf in comp.items():
        if name.endswith("_b"):
            assert not isinstance(leaf, FormsLinearParams)
        else:
            assert isinstance(leaf, FormsLinearParams), name
    # exact round-trip
    dec = decompress_tree(comp)
    _, rep2 = compress_tree(dec, spec)
    assert rep2.max_error < 1e-5
    # the model consumes the compressed tree directly
    x = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.image_size,
                                                  cfg.image_size,
                                                  cfg.in_channels))
    logits_dec, _ = cnn_mod.forward(cfg, dec, x)
    logits_comp, _ = cnn_mod.forward(cfg, comp, x)
    np.testing.assert_allclose(np.asarray(logits_comp),
                               np.asarray(logits_dec), rtol=1e-3, atol=1e-3)


def test_qwen2_compress_and_decode_smoke():
    """Acceptance: decode runs directly on the compressed qwen2 pytree."""
    from repro.configs import get_reduced
    from repro.models.registry import build
    from repro.serving.engine import Request, ServingEngine
    model = build(get_reduced("qwen2-1.5b"))
    params = model.init(jax.random.PRNGKey(0))
    spec = FormsSpec(m=8, bits=8)
    eng = ServingEngine(model, params, max_len=32, batch_slots=2, spec=spec)
    # the engine holds the compressed pytree — no float fake-quant copy
    by_path = compressed_paths(eng.params)
    assert "blocks/attn/wq" in by_path and "blocks/mlp/gate" in by_path
    assert by_path["blocks/attn/wq"].mags.dtype == jnp.uint8
    assert eng.compression_report is not None
    assert eng.compression_report.ratio > 1.5
    res = eng.run([Request(uid=0, prompt=np.array([3, 4, 5]),
                           max_new_tokens=4)])
    assert len(res[0].tokens) == 4
    assert all(0 <= t < model.config.vocab_size for t in res[0].tokens)


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "xlstm-350m", "zamba2-2.7b",
                                  "whisper-small"])
def test_all_families_decode_on_compressed_tree(arch):
    """Every model family consumes the compressed pytree in decode_step."""
    from repro.configs import get_reduced
    from repro.models.registry import build
    model = build(get_reduced(arch))
    params = model.init(jax.random.PRNGKey(0))
    comp, rep = compress_tree(params, FormsSpec(m=4, bits=8))
    assert rep.num_compressed > 0, arch
    cache = model.init_cache(2, 16)
    toks = jnp.array([[1], [2]], jnp.int32)
    logits, _ = model.decode_step(comp, toks, cache, jnp.array(0, jnp.int32))
    assert logits.shape[0] == 2
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_compressed_decode_matches_fakequant_decode():
    """Compressed-pytree decode == decode on the decompressed projection."""
    from repro.configs import get_reduced
    from repro.models.registry import build
    model = build(get_reduced("qwen2-1.5b"))
    params = model.init(jax.random.PRNGKey(0))
    comp, _ = compress_tree(params, FormsSpec(m=8, bits=8))
    cache = model.init_cache(2, 16)
    toks = jnp.array([[5], [7]], jnp.int32)
    pos = jnp.array(0, jnp.int32)
    logits_c, _ = model.decode_step(comp, toks, cache, pos)
    logits_d, _ = model.decode_step(decompress_tree(comp), toks, cache, pos)
    np.testing.assert_allclose(np.asarray(logits_c, dtype=np.float32),
                               np.asarray(logits_d, dtype=np.float32),
                               rtol=0.1, atol=0.1)


# ---------------------------------------------------------------------------
# checkpointing the compressed tree
# ---------------------------------------------------------------------------

def test_checkpoint_compressed_tree_uint8_on_disk(tmp_path):
    from repro.checkpoint import manager as ckpt
    tree = _mixed_tree()
    spec = FormsSpec(m=8, bits=8)
    comp, _ = compress_tree(tree, spec)
    d = ckpt.save(str(tmp_path), comp, step=1,
                  extra_meta=dataclasses.asdict(spec))
    # magnitudes are stored as uint8 (the serving artifact, not f32 fake-quant)
    data = np.load(os.path.join(d, "arrays.npz"))
    kinds = sorted(str(data[f].dtype) for f in data.files)
    assert "uint8" in kinds and "int8" in kinds
    # restore into a template compressed with the same spec: bit-exact
    template, _ = compress_tree(_mixed_tree(), spec)
    restored, step = ckpt.restore(str(tmp_path), template)
    assert step == 1
    for a, b in zip(jax.tree_util.tree_leaves(comp),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype
    # the spec rides along in the checkpoint metadata
    meta = ckpt.read_meta(str(tmp_path))
    assert meta["extra"]["m"] == 8 and meta["extra"]["bits"] == 8
    assert FormsSpec(**meta["extra"]) == spec


# ---------------------------------------------------------------------------
# removed legacy entry points
# ---------------------------------------------------------------------------

def test_legacy_shims_are_removed():
    """The PR-1 deprecation shims are gone: ``repro.core.forms_layer`` no
    longer imports and the engine exports no ``forms_compress_params`` —
    ``repro.forms`` is the only compression surface (DESIGN.md §9)."""
    with pytest.raises(ImportError):
        from repro.core import forms_layer  # noqa: F401
    import repro.serving.engine as engine_mod
    assert not hasattr(engine_mod, "forms_compress_params")


def test_legacy_spec_pair_converts_via_from_legacy():
    """``FormsSpec.from_legacy`` remains the documented migration path for
    code still holding a (FragmentSpec, QuantSpec) pair — it must produce
    bit-identical compression to the natively-constructed spec."""
    from repro.core.fragments import FragmentSpec
    from repro.core.quantization import QuantSpec
    spec = FormsSpec.from_legacy(FragmentSpec(m=8), QuantSpec(bits=8))
    assert spec == FormsSpec(m=8, bits=8)
    w = jax.random.normal(jax.random.PRNGKey(4), (24, 6))
    fp_legacy, err_legacy = forms.from_dense(w, spec)
    fp_native, err_native = forms.from_dense(w, FormsSpec(m=8, bits=8))
    np.testing.assert_array_equal(np.asarray(fp_legacy.mags),
                                  np.asarray(fp_native.mags))
    np.testing.assert_array_equal(np.asarray(fp_legacy.signs),
                                  np.asarray(fp_native.signs))
    assert float(err_legacy) == float(err_native)


def test_fragment_size_not_dividing_default_bk():
    """An m that doesn't divide the default bk=512 stays usable: the kernel
    clamps its K tile to a fragment multiple (regression guard — spec-level
    bk % m validation once rejected m=12 at construction)."""
    spec = FormsSpec(m=12)
    assert spec.k_shard_unit == 12
    w = jax.random.normal(jax.random.PRNGKey(0), (24, 8))
    p, _ = forms.from_dense(w, spec)
    y = forms.apply(p, jnp.ones((2, 24)), spec)
    assert y.shape == (2, 8)
    with pytest.raises(ValueError, match="whole number of fragments"):
        spec.validate_k_shard(24, 4)
