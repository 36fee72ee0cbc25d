"""Child process for tests/test_serving_sharded.py.

Forces N fake host-platform devices BEFORE importing jax (the parent pytest
session must keep seeing 1 device — see conftest.py), then runs one named
check: ``python tests/_sharded_child.py <check> [num_devices]``.  Exits
non-zero (assertion/exception) on failure.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import MeshConfig  # noqa: E402
from repro.launch.mesh import force_host_device_count, make_mesh  # noqa: E402

# replace (not append) any inherited count flag; the jax backend has not
# initialized yet, so this still takes effect
force_host_device_count(int(sys.argv[2]) if len(sys.argv) > 2 else 8)

import dataclasses  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_reduced  # noqa: E402
from repro.models.registry import build  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402


def _tiny_model(heads: int = 2, kv: int = 2, hd: int = 16, d_ff: int = 64):
    cfg = dataclasses.replace(get_reduced("yi-9b"), num_layers=2,
                              d_model=heads * hd, num_heads=heads,
                              num_kv_heads=kv, head_dim=hd, d_ff=d_ff,
                              vocab_size=64, dtype="float32")
    return build(cfg)


def _requests(n: int = 4, new: int = 6):
    return [Request(uid=i, prompt=np.array([1 + i, 2, 3]), max_new_tokens=new)
            for i in range(n)]


def _spec_entries(arr):
    spec = tuple(arr.sharding.spec)
    return spec + (None,) * (arr.ndim - len(spec))


def check_parity():
    """Sharded decode is token-identical to the single-device engine for
    greedy decoding on a compressed pytree, with params and caches
    verifiably sharded (asserted via .sharding)."""
    from repro.forms import validate_tree_sharding

    m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    ref = ServingEngine(m, params, max_len=32, batch_slots=4, forms=True)
    want = {r.uid: r.tokens for r in ref.run(_requests())}

    mesh = make_mesh(MeshConfig(data=2, model=4))
    eng = ServingEngine(m, params, max_len=32, batch_slots=4, forms=True,
                        mesh=mesh)
    # compressed leaves co-shard along N over the model axis
    wq = eng.params["blocks"]["attn"]["wq"]
    assert _spec_entries(wq.mags)[-1] == "model", wq.mags.sharding
    assert _spec_entries(wq.signs)[-1] == "model", wq.signs.sharding
    assert _spec_entries(wq.scale)[-1] == "model", wq.scale.sharding
    checked = validate_tree_sharding(eng.params)
    assert "blocks/attn/wq" in checked and "blocks/mlp/gate" in checked
    # KV cache slots shard over the data axis
    assert _spec_entries(eng.cache["k"])[1] == "data", eng.cache["k"].sharding
    got = {r.uid: r.tokens for r in eng.run(_requests())}
    assert got == want, (got, want)
    # the steady-state cache kept its mesh layout across donated steps
    assert _spec_entries(eng.cache["k"])[1] == "data"
    print("parity ok:", want)


def check_donation():
    """Cache donation stays legal with mesh-sharded caches: the jitted decode
    consumes the old shards in place (no full-cache copy per block)."""
    m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    mesh = make_mesh(MeshConfig(data=2, model=4))
    eng = ServingEngine(m, params, max_len=32, batch_slots=4, forms=True,
                        mesh=mesh)
    eng.prefill_slot(0, np.array([5, 6], np.int32))
    old = jax.tree_util.tree_leaves(eng.cache)
    out1 = eng.decode_chunk(np.zeros(4, np.int32),
                            np.array([2, 0, 0, 0], np.int32),
                            np.zeros(4, np.float32))
    assert all(leaf.is_deleted() for leaf in old), \
        "sharded decode copied the cache instead of donating it"
    out2 = eng.decode_chunk(out1[-1], np.array([6, 4, 4, 4], np.int32),
                            np.zeros(4, np.float32))
    assert out1.shape == out2.shape == (eng.decode_block, 4)
    print("donation ok")


def check_fallback():
    """12 heads on a 16-way model axis: head-grid dims that don't divide the
    axis replicate instead of erroring, the fragment-granularity rule
    replicates a K=192 plane (192 % (16*8) != 0 even though 192 % 16 == 0),
    and decoding still matches the single-device engine."""
    assert jax.device_count() == 16, jax.device_count()
    m = _tiny_model(heads=12, kv=12, hd=16, d_ff=384)
    params = m.init(jax.random.PRNGKey(0))
    ref = ServingEngine(m, params, max_len=32, batch_slots=2, forms=True)
    want = {r.uid: r.tokens for r in ref.run(_requests(2))}

    mesh = make_mesh(MeshConfig(data=1, model=16))
    eng = ServingEngine(m, params, max_len=32, batch_slots=2, forms=True,
                        mesh=mesh)
    wq = eng.params["blocks"]["attn"]["wq"]   # (L, 192, 192) compressed
    wo = eng.params["blocks"]["attn"]["wo"]
    down = eng.params["blocks"]["mlp"]["down"]  # (L, 384, 192) compressed
    # N = 192 divides 16 -> wq shards its columns
    assert _spec_entries(wq.mags)[-1] == "model", wq.mags.sharding
    # wo K = 192: 16-way shards would hold 12 rows — not a whole number of
    # m=8 fragments — so K must fall back to replication...
    assert _spec_entries(wo.mags)[-2] is None, wo.mags.sharding
    assert _spec_entries(wo.signs)[-2] is None, wo.signs.sharding
    # ...while K = 384 (24-row shards, 3 fragments each) may shard
    assert _spec_entries(down.mags)[-2] == "model", down.mags.sharding
    assert _spec_entries(down.signs)[-2] == "model", down.signs.sharding
    got = {r.uid: r.tokens for r in eng.run(_requests(2))}
    assert got == want, (got, want)
    print("fallback ok:", want)


def check_paged():
    """Paged serving on the mesh: the page pool shards its page dim over the
    data axis, greedy decode is token-identical to the single-device DENSE
    engine, the pool stays donated, and at the same cache-HBM budget the
    paged engine admits >= 2x the dense engine's concurrent requests."""
    m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    # 3-token prompts + 5 new tokens fit one 8-row page per request
    dense = ServingEngine(m, params, max_len=32, batch_slots=2, forms=True)
    want = {r.uid: r.tokens for r in dense.run(_requests(4, new=5))}
    assert dense.scheduler.max_concurrent == 2

    mesh = make_mesh(MeshConfig(data=2, model=4))
    # same budget: dense = 2 slots x 32 rows; pool = 8 pages x 8 rows
    eng = ServingEngine(m, params, max_len=32, batch_slots=4, forms=True,
                        mesh=mesh, page_size=8, num_pages=8)
    assert eng.cache_bytes() <= dense.cache_bytes()
    assert _spec_entries(eng.cache.pool["k"])[1] == "data", \
        eng.cache.pool["k"].sharding
    got = {r.uid: r.tokens for r in eng.run(_requests(4, new=5))}
    assert got == want, (got, want)
    assert eng.scheduler.max_concurrent >= 2 * dense.scheduler.max_concurrent
    # the pool kept its mesh layout across donated steps
    assert _spec_entries(eng.cache.pool["k"])[1] == "data"
    old = jax.tree_util.tree_leaves(eng.cache)
    eng.decode_chunk(np.zeros(4, np.int32), np.zeros(4, np.int32),
                     np.zeros(4, np.float32))
    assert all(leaf.is_deleted() for leaf in old), \
        "sharded paged decode copied the pool instead of donating it"
    print("paged ok:", eng.scheduler.max_concurrent, "concurrent")


def check_speculative():
    """Speculative decoding on the mesh: greedy tokens identical to the
    single-device NON-speculative paged engine, draft params co-sharded by
    the PR-3 rules, the draft page pool sharded over the data axis, and
    both pools donated across rounds."""
    m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    ref = ServingEngine(m, params, max_len=32, batch_slots=4, page_size=8,
                        forms=True)
    want = {r.uid: r.tokens for r in ref.run(_requests())}

    mesh = make_mesh(MeshConfig(data=2, model=4))
    eng = ServingEngine(m, params, max_len=32, batch_slots=4, page_size=8,
                        forms=True, mesh=mesh, speculate=True, draft_k=4,
                        draft_bits=4)
    # draft compressed leaves follow the same co-sharding rules as the target
    dwq = eng.runner.draft_params["blocks"]["attn"]["wq"]
    assert _spec_entries(dwq.mags)[-1] == "model", dwq.mags.sharding
    assert _spec_entries(dwq.signs)[-1] == "model", dwq.signs.sharding
    # the draft page pool shards its page dim over the data axis
    assert _spec_entries(eng.runner.draft_cache.pool["k"])[1] == "data", \
        eng.runner.draft_cache.pool["k"].sharding
    got = {r.uid: r.tokens for r in eng.run(_requests())}
    assert got == want, (got, want)
    st = eng.stats()["speculate"]
    assert st["rounds"] > 0 and st["acceptance"] > 0.0, st
    # both pools stay donated across speculative rounds
    eng.scheduler.block_tables[:] = 0
    eng.scheduler.block_tables[0, 0] = eng.page_allocator.alloc(1)[0]
    old = (jax.tree_util.tree_leaves(eng.cache)
           + jax.tree_util.tree_leaves(eng.runner.draft_cache))
    eng.runner.decode_round(np.zeros(4, np.int32), np.zeros(4, np.int32),
                            np.zeros(4, np.float32),
                            block_tables=eng.scheduler.block_tables)
    assert all(leaf.is_deleted() for leaf in old), \
        "sharded speculative round copied a pool instead of donating"
    print("speculative ok:", f"acceptance={st['acceptance']:.2f}")


def check_restore():
    """checkpoint.restore(shardings=...) loads a compressed tree straight
    into the mesh layout the engine serves from."""
    import tempfile

    from repro.checkpoint import manager as ckpt
    from repro.distributed import sharding as shd
    from repro.forms import FormsSpec, compress_tree

    m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    comp, _ = compress_tree(params, FormsSpec(m=8))
    mesh = make_mesh(MeshConfig(data=2, model=4))
    ctx = shd.ParallelContext.for_mesh(mesh)
    sh = shd.params_shardings(comp, ctx, fsdp=False)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, comp, step=1)
        out, step = ckpt.restore(d, comp, shardings=sh)
    wq = out["blocks"]["attn"]["wq"]
    assert _spec_entries(wq.mags)[-1] == "model", wq.mags.sharding
    np.testing.assert_array_equal(
        np.asarray(wq.mags), np.asarray(comp["blocks"]["attn"]["wq"].mags))
    # the restored tree serves as-is: weights already placed, engine reuses
    eng = ServingEngine(m, out, max_len=32, batch_slots=2, mesh=mesh)
    res = eng.run([Request(uid=0, prompt=np.array([3, 4]), max_new_tokens=4)])
    assert len(res[0].tokens) == 4
    print("restore ok")


def check_mixed_precision():
    """Heterogeneous mixed-precision serving on the mesh: a per-leaf plan
    (varying bits AND fragment geometry) shards every leaf by its OWN
    geometry — the m=16 override forces its K axis to replicate (8-row
    shards would split fragments) while m=8 neighbours shard N — greedy
    decode is token-identical to the single-device engine, and a sharded
    checkpoint restore rebuilds the mixed template from plan_from_meta
    metadata and places it straight onto the mesh."""
    import tempfile

    from repro.checkpoint import manager as ckpt
    from repro.distributed import sharding as shd
    from repro.forms import FormsSpec, compress_tree
    from repro.forms.autobits import plan_from_meta, plan_to_meta

    m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    spec = FormsSpec(m=8)
    plan = {"attn/wq": spec.with_bits(4),
            "mlp/gate": spec.with_bits(2),
            "attn/wo": dataclasses.replace(spec, m=16, bits=6)}

    ref = ServingEngine(m, params, max_len=32, batch_slots=4, spec=spec,
                        plan=plan)
    assert ref.compression_report.bits["blocks/attn/wq"] == 4
    want = {r.uid: r.tokens for r in ref.run(_requests())}

    mesh = make_mesh(MeshConfig(data=2, model=4))
    eng = ServingEngine(m, params, max_len=32, batch_slots=4, spec=spec,
                        plan=plan, mesh=mesh)
    wq = eng.params["blocks"]["attn"]["wq"]
    assert wq.bits == 4 and _spec_entries(wq.mags)[-1] == "model", \
        (wq.bits, wq.mags.sharding)
    assert eng.params["blocks"]["mlp"]["gate"].bits == 2
    # wo carries its own geometry: K=32 over the 4-way model axis gives
    # 8-row shards — whole fragments at m=8, but NOT at this leaf's m=16,
    # so the per-leaf granularity rule must replicate K here
    wo = eng.params["blocks"]["attn"]["wo"]
    assert (wo.m, wo.bits) == (16, 6)
    assert _spec_entries(wo.mags)[-2] is None, wo.mags.sharding
    assert _spec_entries(wo.signs)[-2] is None, wo.signs.sharding
    got = {r.uid: r.tokens for r in eng.run(_requests())}
    assert got == want, (got, want)

    # sharded restore of the mixed tree, template rebuilt from the meta
    comp, _ = compress_tree(params, spec, plan=plan)
    ctx = shd.ParallelContext.for_mesh(mesh)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, comp, step=1, extra_meta=plan_to_meta(spec, plan))
        spec2, plan2 = plan_from_meta(ckpt.read_meta(d)["extra"])
        template, _ = compress_tree(m.init(jax.random.PRNGKey(1)), spec2,
                                    plan=plan2)
        sh = shd.params_shardings(template, ctx, fsdp=False)
        out, _ = ckpt.restore(d, template, shardings=sh)
    rwq = out["blocks"]["attn"]["wq"]
    assert rwq.bits == 4 and _spec_entries(rwq.mags)[-1] == "model"
    assert out["blocks"]["attn"]["wo"].m == 16
    np.testing.assert_array_equal(
        np.asarray(rwq.mags), np.asarray(comp["blocks"]["attn"]["wq"].mags))
    eng2 = ServingEngine(m, out, max_len=32, batch_slots=4, mesh=mesh)
    got2 = {r.uid: r.tokens for r in eng2.run(_requests())}
    assert got2 == want, (got2, want)
    print("mixed_precision ok:", want)


def check_fleet():
    """The SLO fleet scheduler on the mesh: chunked prefill + a per-round
    token budget under data=2,model=4 sharding is token-identical to the
    single-device plain paged engine, the page pool keeps its sharding
    across chunked rounds, and the SLO stats account every request."""
    m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    reqs = lambda: [Request(uid=i,
                            prompt=(np.arange(1 + i, 4 + i * 4) % 64)
                            .astype(np.int32),
                            max_new_tokens=5) for i in range(3)]
    ref = ServingEngine(m, params, max_len=32, batch_slots=2, page_size=4,
                        forms=True)
    want = {r.uid: r.tokens for r in ref.run(reqs())}

    mesh = make_mesh(MeshConfig(data=2, model=4))
    eng = ServingEngine(m, params, max_len=32, batch_slots=2, page_size=4,
                        forms=True, mesh=mesh,
                        slo={"prefill_chunk": 4, "step_token_budget": 8})
    assert _spec_entries(eng.cache.pool["k"])[1] == "data", \
        eng.cache.pool["k"].sharding
    got = {r.uid: r.tokens for r in eng.run(reqs())}
    assert got == want, (got, want)
    slo = eng.stats()["slo"]
    assert slo["completed"] == 3, slo
    assert slo["chunked_prefill"]["calls"] > 0, slo
    # chunked commits kept the pool donated and mesh-placed
    assert _spec_entries(eng.cache.pool["k"])[1] == "data"
    print("fleet ok:", want)


def check_kernel_mesh():
    """The Pallas kernel path itself on a data=2,model=2 mesh (interpret
    mode here; compiled on a TPU, where Mosaic kernels need the per-device
    shard_map of kernels/ops.py): paged FORMS decode is token-identical to
    the single-device engine, with row-parallel weights placed K-sharded."""
    from repro.forms import FormsSpec

    m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    spec = FormsSpec(m=8, prefer_ref=False)
    ref = ServingEngine(m, params, max_len=32, batch_slots=4, spec=spec,
                        page_size=8)
    want = {r.uid: r.tokens for r in ref.run(_requests())}

    mesh = make_mesh(MeshConfig(data=2, model=2))
    eng = ServingEngine(m, params, max_len=32, batch_slots=4, spec=spec,
                        page_size=8, mesh=mesh)
    down = eng.params["blocks"]["mlp"]["down"]
    assert _spec_entries(down.mags)[-2] == "model", down.mags.sharding
    got = {r.uid: r.tokens for r in eng.run(_requests())}
    assert got == want, (got, want)
    print("kernel_mesh ok:", want)


def check_repair():
    """Self-healing on an 8-device mesh: stuck-at faults injected into one
    mesh-sharded compressed leaf drift the health probes, the scan's
    per-shard scoreboard names the corrupted devices, automatic repair
    re-encodes the leaf with its NamedSharding preserved (no retrace, no
    resharding), and greedy serving returns to single-device parity."""
    from repro.reliability import FaultModel, HealthConfig

    m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    ref = ServingEngine(m, params, max_len=32, batch_slots=4, forms=True,
                        page_size=8)
    want = {r.uid: r.tokens for r in ref.run(_requests())}

    mesh = make_mesh(MeshConfig(data=2, model=4))
    eng = ServingEngine(m, params, max_len=32, batch_slots=4, forms=True,
                        mesh=mesh, page_size=8,
                        health=HealthConfig(probe_every=1,
                                            drift_threshold=1e-3))
    leaf = "blocks/attn/wq"
    before = eng.params["blocks"]["attn"]["wq"].mags.sharding
    assert _spec_entries(eng.params["blocks"]["attn"]["wq"].mags)[-1] \
        == "model"
    rep = eng.inject_faults(FaultModel(p_stuck_on=0.05, seed=2),
                            paths=[leaf])
    assert rep.codes_changed > 0, rep.summary()
    # injection is a host-side transform but must keep the mesh placement
    assert eng.params["blocks"]["attn"]["wq"].mags.sharding == before
    got = {r.uid: r.tokens for r in eng.run(_requests())}
    assert got == want, (got, want)
    h = eng.stats()["health"]
    assert h["repairs"] >= 1, h
    drift_events = [e for e in h["events"] if e["event"] == "drift"]
    assert drift_events and leaf in drift_events[0]["leaves"], h["events"]
    # the scoreboard localized the corruption to specific devices
    assert h["flagged"][leaf]["replicas"], h["flagged"]
    # repair re-encoded in place: sharding survives, codes are clean again
    assert eng.params["blocks"]["attn"]["wq"].mags.sharding == before
    print("repair ok:", h["flagged"][leaf]["bad_codes"], "codes repaired")


def check_kv_counters():
    """On a mesh the page pool is sharded and the paged-attention kernel is
    not partitioned, so every paged model step reads gathered views: the
    engine counts gathered steps only (decode rounds and chunk calls)."""
    m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    mesh = make_mesh(MeshConfig(data=2, model=1))
    eng = ServingEngine(m, params, max_len=32, batch_slots=2, page_size=4,
                        mesh=mesh,
                        slo={"prefill_chunk": 4, "step_token_budget": 8})
    eng.run(_requests(3, new=5))
    c = eng.stats()["counters"]
    assert "decode.kv_in_place_steps" not in c, c
    assert c["decode.kv_gathered_steps"] == (
        c["decode.rounds"] * eng.decode_block + c["prefill.calls"]), c
    print("kv_counters ok:", c["decode.kv_gathered_steps"])


if __name__ == "__main__":
    globals()[f"check_{sys.argv[1]}"]()
