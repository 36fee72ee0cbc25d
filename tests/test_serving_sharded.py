"""Mesh-sharded serving: parity, donation, fallback, sharded restore.

Each test forces 8 (or 16) fake host devices — in a subprocess
(tests/_sharded_child.py), because XLA_FLAGS must be set before jax
initializes and this pytest session must keep seeing 1 device
(conftest.py).  The child asserts and exits non-zero on failure.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
CHILD = os.path.join(ROOT, "tests", "_sharded_child.py")


def _run_child(check: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, CHILD, check, str(devices)],
        capture_output=True, text=True, timeout=560, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


def test_sharded_decode_token_parity():
    """data=2,model=4 decode on a compressed pytree is token-identical to the
    single-device engine; params and caches are verifiably sharded."""
    out = _run_child("parity")
    assert "parity ok" in out


def test_sharded_cache_donation():
    """Donation stays legal when the KV cache is sharded over the mesh."""
    out = _run_child("donation")
    assert "donation ok" in out


def test_twelve_heads_on_sixteen_way_replication_fallback():
    """12 heads on a 16-way model axis: non-dividing dims (including the
    fragment-granularity K rule) replicate, output still matches."""
    out = _run_child("fallback", devices=16)
    assert "fallback ok" in out


def test_paged_serving_on_mesh_parity_and_2x_concurrency():
    """Paged pool sharded over the data axis: token-identical to the
    single-device dense engine, pool donation intact, and >= 2x concurrent
    admissions at the same cache-HBM budget."""
    out = _run_child("paged")
    assert "paged ok" in out


def test_speculative_decode_on_mesh_parity():
    """Speculative decoding under data=2,model=4: token-identical to the
    single-device non-speculative paged engine, draft weights/pool sharded
    by the same rules as the target, pools donated."""
    out = _run_child("speculative")
    assert "speculative ok" in out


def test_pallas_kernel_on_mesh_parity():
    """The Pallas polarized kernel (not the jnp oracle) under data=2,model=2:
    token-identical to the single-device engine."""
    out = _run_child("kernel_mesh", devices=4)
    assert "kernel_mesh ok" in out


def test_fleet_chunked_prefill_on_mesh_parity():
    """The SLO fleet scheduler's chunked prefill under data=2,model=4:
    token-identical to the single-device plain paged engine, page pool
    sharding preserved across chunked rounds."""
    out = _run_child("fleet")
    assert "fleet ok" in out


def test_restore_straight_into_sharded_layout():
    """checkpoint.restore(shardings=...) places compressed leaves onto the
    mesh without a replicated intermediate, and the engine serves from it."""
    out = _run_child("restore")
    assert "restore ok" in out


def test_mixed_precision_plan_on_mesh_parity_and_restore():
    """A heterogeneous per-leaf plan (mixed bits + an m=16 geometry
    override) serves token-identically to the single-device engine, shards
    each leaf by its own geometry, and restores onto the mesh from
    plan_from_meta checkpoint metadata."""
    out = _run_child("mixed_precision")
    assert "mixed_precision ok" in out


def test_forms_param_spec_granularity_unit():
    """In-process unit check of the co-sharding rule (no devices needed):
    K shards must hold whole fragments, scale never shards its row axis."""
    from repro.forms import FormsLinearParams
    import numpy as np_

    from repro.distributed.sharding import forms_param_spec

    class FakeMesh:
        shape = {"data": 2, "model": 4}

    class FakeCtx:
        mesh = FakeMesh()
        batch_axes = ("data",)
        model_axes = ("model",)

        def axis_size(self, logical):
            return {"batch": 2, "model": 4}[logical]

        def resolve(self, logical):
            return {"batch": "data", "model": "model"}[logical]

    def leaf(kp, n, m):
        return FormsLinearParams(
            mags=np_.zeros((kp, n), np_.uint8),
            signs=np_.zeros((kp // m, n), np_.int8),
            scale=np_.zeros((1, n), np_.float32), k=kp, m=m)

    # wq: N sharded on all three planes, scale K row stays None
    mags, signs, scale = forms_param_spec("blocks/attn/wq", leaf(64, 128, 8),
                                          FakeCtx(), fsdp=False)
    assert tuple(mags) == (None, "model")
    assert tuple(signs) == (None, "model")
    assert tuple(scale) == (None, "model")
    # wo: K = 96 over 4-way model axis -> 24-row shards = 3 fragments: legal
    mags, signs, _ = forms_param_spec("blocks/attn/wo", leaf(96, 64, 8),
                                      FakeCtx(), fsdp=False)
    assert tuple(mags)[0] == "model"
    assert tuple(signs)[0] == "model"
    # wo: K = 104 over 4-way -> 26-row shards split fragments: replicate
    mags, signs, _ = forms_param_spec("blocks/attn/wo", leaf(104, 64, 8),
                                      FakeCtx(), fsdp=False)
    assert tuple(mags)[0] is None
    assert tuple(signs)[0] is None


def test_validate_tree_sharding_skips_uncommitted():
    """Validation is a no-op for trees that never touched a mesh."""
    import jax.numpy as jnp

    from repro.forms import FormsSpec, compress_tree, validate_tree_sharding

    params = {"blocks": {"attn": {"wq": jnp.ones((64, 128))}}}
    comp, _ = compress_tree(params, FormsSpec(m=8))
    assert validate_tree_sharding(comp) == {}
