"""Checks of the benchmark's own arithmetic and of its correctness check,
off the chip at a tiny size.

  JAX_PLATFORMS=cpu python -m pytest -q chipbench/test_chipbench.py

* the trace reduction on a hand-built trace;
* the dense family's operation and byte counts against hand counts, and
  its weights, projected weights, gaps and counts against those the
  harness gave before families were looked up by name;
* a family that comes as one new file is found from the configuration file
  that names it, served and judged;
* the traffic generator: the same work for every seed, in another order;
  the chat mix's Poisson arrivals;
* ``ttft_p90_ms`` on a hand-built timeline, a failed request a miss;
* the plain reference against the program's own float32 forward;
* a run of the harness (without its look for a chip) comes out correct, and
  comes out not correct with the timed path broken: a token altered where
  it is produced, the decode step's state left unchanged, half of the batch
  left out (also at a real cell's slot count and sample size); the 8-bit
  control, judged by the run's own comparison, is not correct;
* a fixed window stops serving when it closes and checks the requests
  that finished.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import shutil
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import devtrace  # noqa: E402
import run  # noqa: E402
import timeline  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

dense = run.load_family("dense")

TINY = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 256, "qkv_bias": True,
        "tie_embeddings": True, "rope_theta": 10000.0, "norm_eps": 1e-05,
        "sliding_window": None, "dtype": "bfloat16"}
FORMS = {"m": 8, "bits": 8}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


# --------------------------------------------------------------------------
# trace reduction
# --------------------------------------------------------------------------

def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=a, duration_ns=b - a)
                            for a, b, n in evs]) for ln, evs in lines])


def test_trace_reduction_on_a_hand_built_trace():
    host = _plane("/host:CPU", [("main", [
        (0, 100, "chipbench.decode/32x4/1000/30"),
        (120, 300, "chipbench.prefill/32x128"),
        (400, 450, "other.span"),
        (500, 600, "chipbench.decode/32x4/2000/32")])])
    dev = _plane("/device:TPU:0", [
        ("XLA Modules", [(10, 90, "jit__decode_fn(1)"),
                         (130, 290, "jit__chunk_fn(2)"),
                         (510, 590, "jit__decode_fn(1)"),
                         (700, 800, "jit__decode_fn(1)")]),   # after the slice
        ("XLA Ops", [(10, 90, "%while.1 = (s32[]) while(...)"),
                     (20, 40, "%polarized_matmul.3 = f32[32,8960]{1,0} custom-call(...)"),
                     (50, 60, "%fusion.7 = bf16[32,128]{1,0} fusion(...)"),
                     (130, 290, "%polarized_matmul.9 = f32[4096,1536]{1,0} custom-call(...)"),
                     (510, 590, "%fusion.7 = bf16[32,128]{1,0} fusion(...)")])])
    red = devtrace.reduce_planes([host, dev])
    assert red.lo == 0 and red.hi == 600
    assert red.window_s == pytest.approx(600e-9)
    assert red.busy_s == pytest.approx((80 + 160 + 80) * 1e-9)
    dec = red.calls("decode")
    assert [c[2] for c in dec] == [[32, 4, 1000, 30], [32, 4, 2000, 32]]
    assert red.calls("prefill")[0][2] == [32, 128]
    mods = red.seconds_in(red.module_events("jit__decode_fn"), dec)
    assert mods == pytest.approx([80e-9, 80e-9])
    calls = sorted(dec + red.calls("prefill"))
    assert sum(red.seconds_in(red.kernel_events(), calls)) == pytest.approx(180e-9)
    gaps = red.idle_gaps()
    sched = devtrace.HOST_LABEL[None]
    assert [g[0] for g in gaps] == ["decode dispatch", sched, sched,
                                    "decode dispatch"]
    assert sum(g[1] for g in gaps) == pytest.approx(280e-9)
    bd = dict((k, v) for k, v in red.breakdown()["device_ops"])
    assert bd["polarized_matmul.9 f32[4096,1536]"] == pytest.approx(160e-9)
    assert bd["while.1"] == pytest.approx(50e-9)     # 80 - 20 - 10
    assert bd["fusion.7 bf16[32,128]"] == pytest.approx(90e-9)


# --------------------------------------------------------------------------
# operation and byte counts
# --------------------------------------------------------------------------

def test_work_counts_match_hand_counts():
    mc = TINY
    # per block: q 64x64, k 64x32, v 64x32, o 64x64, gate/up 64x128, down 128x64
    per_block = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 2 * 64 * 128 + 128 * 64
    assert dense.matmul_params(mc) == 2 * per_block + 64 * 256
    assert dense.token_flops(mc, 10) == 2.0 * dense.matmul_params(mc) + 4 * 2 * 4 * 16 * 10
    assert dense.prefill_flops(mc, 3, 4) == pytest.approx(
        sum(dense.token_flops(mc, p + 1) for p in range(3, 7)))
    windowed = dict(mc, sliding_window=5)
    assert dense.prefill_flops(windowed, 0, 9) == pytest.approx(
        sum(dense.token_flops(windowed, p + 1) for p in range(9)))
    assert dense.attended(windowed, 9) == 5
    assert dense.kv_bytes_per_position(mc) == 2 * 2 * 2 * 16 * 2
    # a 64x64 projected matrix: 4096 codes, 8x64 signs, 64 f32 scales
    assert work.projected_bytes(64, 64, FORMS) == 4096 + 512 + 256
    blocks = 2 * sum(work.projected_bytes(k, n, FORMS)
                     for _, k, n in dense.layer_matmuls(mc))
    head = 256 * 64 * 2
    rows, norms, bias = 32 * 64 * 2, 5 * 64 * 2, 2 * (64 + 64) * 2
    assert dense.decode_weight_bytes(mc, FORMS, 32) == blocks + head + rows + norms + bias
    ops, nbytes = work.kernel_call(128, 64, 32, FORMS)
    assert ops == 2 * 32 * 128 * 64
    assert nbytes == 128 * 64 + 16 * 64 + 4 * 64 + 32 * 128 * 2 + 32 * 64 * 2
    by_ops, by_bytes = dense.kernel_least_seconds(mc, FORMS, 32, 4, PEAK)
    assert by_ops == 0.0                    # 32 rows: every call is bytes-bound
    want = 2 * 4 * sum(work.kernel_call(k, n, 32, FORMS)[1]
                       for _, k, n in dense.layer_matmuls(mc)) / PEAK["hbm_bytes_per_s"]
    assert by_bytes == pytest.approx(want)
    assert dense.decode_flops(mc, 3, 50) == pytest.approx(
        3 * 2.0 * dense.matmul_params(mc) + 4 * 2 * 4 * 16 * 50)
    untied = dict(mc, tie_embeddings=False)
    assert [n for n, *_ in dense.projected_matmuls(untied)][-1] == "head"


# what the harness gave before families were looked up by name: sha256 of
# the float32 weights and projected weights (leaf by leaf: path, dtype,
# shape, bytes), of the gaps of one request (program and control) and the
# work counts, at TINY and at an untied, windowed TINY without biases, for
# seed 2**33 + 7
PARENT = {
    "tied": {
        "weights": "8c147b0603085a2f86b9ed3f1eacee54a0d8deed0326c094901df91a9081dfb9",
        "projected": "a182b288970944df22526f42c8662544e9a6d3cf0cf4d7ad486e9853f96fdf87",
        "gaps": "f32fd06162d144259cd6f3cb3358023f7280e9061722bf17415fa215972875a1",
        "control": "59564be07b017732151ebf52c36ead9a77f0965e86711d5a7f320f0069b9f3c0",
        "work": (90112, 185344.0, 566272.0, 732160.0, 256, 125056.0,
                 (0.0, 1.0652600732600733e-06),
                 (0.0, 2.0591277167277167e-05))},
    "untied": {
        "weights": "2ef34a83bc9221bc2dcca4348fa564cebbdcc04af97c80debfeb6f272df25e35",
        "projected": "09ad51dd6874c254bdb79ee76f0512f21f2168cc43d92d05a9c7721c1a9498c5",
        "gaps": "e9ad8e4680345a0571b6ff1c73916a47df6de1413d65bc06088fe47bfde0fd2c",
        "control": "0d9956bbf09ba7908d43a0756e793da15f99c5e2c041bf5fa2c6fb550c2a899a",
        "work": (90112, 183296.0, 566272.0, 731648.0, 256, 111232.0,
                 (0.0, 1.2603076923076923e-06),
                 (0.0, 2.381581440781441e-05))},
}


def _tree_sha(tree):
    import jax
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("variant", ["tied", "untied"])
def test_dense_family_matches_the_harness_before_families(variant):
    """Looked up by name, the dense family gives bit for bit the weights,
    projected weights, gaps and work counts the harness gave before."""
    mc = TINY if variant == "tied" else dict(
        TINY, tie_embeddings=False, sliding_window=6, qkv_bias=False)
    family = run.load_family("dense")
    want, seed = PARENT[variant], 2 ** 33 + 7
    assert _tree_sha(family.engine_params(mc, FORMS, seed)) == want["weights"]
    projected = family.project_params(family.make_params(mc, seed),
                                      FORMS["m"], FORMS["bits"])
    assert _tree_sha(projected) == want["projected"]
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 256, 40).astype(np.int32)
    served = rng.integers(0, 256, 12).astype(np.int32)
    for control, key in ((False, "gaps"), (True, "control")):
        g = family.gaps(mc, FORMS, seed, [prompt], [served], 64, 16,
                        control=control)
        assert g.dtype == np.float32 and g.shape == (12,)
        assert hashlib.sha256(g.tobytes()).hexdigest() == want[key]
        twice = family.gaps(mc, FORMS, seed, [prompt] * 2, [served] * 2, 64,
                            16, control=control)
        assert twice.tobytes() == g.tobytes() * 2
    got = (family.matmul_params(mc), family.token_flops(mc, 10),
           family.decode_flops(mc, 3, 50), family.prefill_flops(mc, 3, 4),
           family.kv_bytes_per_position(mc),
           family.decode_weight_bytes(mc, FORMS, 32),
           family.kernel_least_seconds(mc, FORMS, 32, 4, PEAK),
           family.kernel_least_seconds(mc, FORMS, 4096, 1, PEAK))
    assert got == want["work"]


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

def _mix(name):
    """A mix file with the rate a cell would give it, or (``open_loop``) an
    open-loop Poisson mix built on decode_heavy's lengths."""
    if name == "open_loop":
        return dict(traffic.load_mix("decode_heavy"), arrivals="poisson",
                    rate=2.0, window="drain", prefill_chunk=128,
                    step_token_budget=256)
    return dict(traffic.load_mix(name), backlog_rate=4.0)


@pytest.mark.parametrize("mix", ["decode_heavy", "open_loop", "long_context",
                                 "chat"])
def test_traffic_same_work_for_every_seed(mix):
    m = _mix(mix)
    a = traffic.generate(m, 2 ** 33 + 1, 30, 1000)
    b = traffic.generate(m, 7, 30, 1000)
    again = traffic.generate(m, 2 ** 33 + 1, 30, 1000)
    assert len(a) == len(b) == traffic.n_requests(m, 30)
    for f in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(f, a)) == sorted(map(f, b))
    gaps = [np.sort(np.diff([r.arrival_s for r in x])) for x in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1])
    assert a[0].arrival_s == b[0].arrival_s == 0.0
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in again]
    assert all(len(r.prompt) + r.max_new_tokens < m["max_len"] for r in a)
    assert all(0 <= t < 1000 for r in a for t in r.prompt)
    if m["order"] == "fixed":
        assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
        assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
        assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
        assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]


def test_chat_arrivals_are_poisson_at_its_rate():
    """The chat mix: ceil(seconds x rate) requests, the first due at t=0,
    the gaps the stratified quantiles of an exponential of mean 1 / rate,
    in one order for every seed."""
    m = traffic.load_mix("chat")
    assert m["arrivals"] == "poisson" and m["order"] == "fixed"
    rate = m["rate"]
    for seconds in (50, 20):
        reqs = traffic.generate(m, 2 ** 31 + 11, seconds, 1000)
        n = math.ceil(seconds * rate)
        assert len(reqs) == n
        gaps = np.diff([r.arrival_s for r in reqs])
        k = n - 1
        want = -np.log1p(-(np.arange(k) + 0.5) / k) / rate
        np.testing.assert_allclose(np.sort(gaps), want, rtol=1e-12)
        assert abs(gaps.mean() * rate - 1) < 0.05
        assert reqs[0].arrival_s == 0.0


def test_backlog_rate_belongs_to_the_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = run.load_cell(bench, w["name"])
        assert "backlog_rate" not in traffic.load_mix(cell["traffic"])
        if cell["mix"]["arrivals"] == "backlog":
            assert cell["mix"]["backlog_rate"] == cell["backlog_rate"]


# --------------------------------------------------------------------------
# the reference against the program
# --------------------------------------------------------------------------

@pytest.mark.parametrize("tied,window", [(True, None), (False, 6)])
def test_reference_agrees_with_program_forward(tied, window):
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.forms import FormsSpec, compress_tree, decompress_tree
    from repro.models.registry import build

    mc = dict(TINY, tie_embeddings=tied, sliding_window=window,
              qkv_bias=tied)
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), **dict(mc, dtype="float32"))
    params = dense.make_params(mc, 11)
    served, _ = compress_tree(params, FormsSpec(**FORMS))
    unpacked = decompress_tree(served)
    projected = dense.project_params(jax.tree.map(jnp.copy, params),
                                     FORMS["m"], FORMS["bits"])
    for a, b in zip(jax.tree.leaves(unpacked), jax.tree.leaves(projected)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    toks = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = build(cfg).forward(unpacked,
                                  {"tokens": jnp.asarray(toks)[None]})[0][0]
    padded = np.zeros(dense.Q_BLOCK, np.int32)
    padded[:40] = toks
    hidden = dense._hidden(projected, jnp.asarray(padded), mc, False)[:40]
    got = jnp.matmul(hidden, dense._head(projected),
                     precision=dense.HIGHEST)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # tokens decoded greedily by the program's float32 forward read a gap 0
    seq = list(toks[:30])
    with jax.default_matmul_precision("highest"):
        for _ in range(10):
            lg = build(cfg).forward(unpacked, {"tokens": jnp.asarray(seq)[None]})[0]
            seq.append(int(jnp.argmax(lg[0, -1])))
    gaps = dense.request_gaps(projected, mc, toks[:30], np.asarray(seq[30:]),
                              64, 16)
    assert gaps.shape == (10,)
    assert float(gaps.max()) < 1e-5


# --------------------------------------------------------------------------
# harness runs, sound and broken
# --------------------------------------------------------------------------

LIMIT = 0.01


def _cell(mix_name, sample_tokens=1000, limit=LIMIT, **mix_over):
    mix = _mix(mix_name)
    mix.update(slots=4, max_len=128,
               prompt_len={"dist": "uniform", "min": 8, "max": 40},
               output_len={"dist": "uniform", "min": 6, "max": 30})
    if mix["prefill_chunk"]:
        mix.update(prefill_chunk=16, step_token_budget=48)
    if mix["arrivals"] == "poisson":
        mix.update(rate=8.0)
    mix.update(mix_over)
    return {"config": "tiny", "traffic": mix_name, "name": "tiny." + mix_name,
            "chips": 1, "mix": mix,
            "check": {"max_logit_gap": limit, "sample_tokens": sample_tokens},
            "config_file": {"arch": "qwen2-1.5b", "family": "dense",
                            "model": dict(TINY), "forms": FORMS,
                            "serving": {"page_size": 16, "decode_block": 4}},
            "family": dense}


def _run(cell, hook=None, keep=None, seconds=1.0):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return run.run_cell(bench, cell, 2 ** 33 + 3, seconds, False,
                        engine_hook=hook, peak=PEAK, keep=keep)


@pytest.mark.parametrize("mix", ["decode_heavy", "open_loop", "chat"])
def test_sound_run_is_correct_and_control_is_not(mix):
    keep = {}
    cell = _cell(mix)
    out = _run(cell, keep=keep, seconds=30.0 if mix == "decode_heavy" else 1.0)
    assert keep["run"].returned            # the backlog drained in the window
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] >= 4
    assert list(out)[-1] == "check"
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    want = {m["name"] for m in run.cell_metrics(bench, cell["name"], False)}
    assert "setup_s" in want and set(out["metrics"]) == want
    low = run.reference_gaps(cell, 2 ** 33 + 3, keep["run"].planned,
                             keep["run"].served, keep["sample"], control=True)
    assert low.max() > max(LIMIT, 3 * keep["gaps"].max())
    assert not run.is_correct(run.judge(cell, keep["run"], keep["sample"], low))


def test_fixed_window_stops_serving_when_it_closes():
    """A backlog deeper than the window: serving stops at the close, and the
    requests that finished by then are checked.  Which requests finish
    depends on the machine's speed; over such samples this size reads gaps
    of 0.0012-0.0117 and its 8-bit control 0.111-0.134, hence the limit."""
    keep = {}
    cell = _cell("decode_heavy", sample_tokens=400, limit=0.03,
                 backlog_rate=200.0,
                 output_len={"dist": "uniform", "min": 20, "max": 60})
    out = _run(cell, keep=keep, seconds=1.5)
    r = keep["run"]
    assert not r.returned and r.backlog_left() > 0
    assert out["correct"], out["check"]
    assert 0 < out["attempted"] == len(r.served) < len(r.planned)
    assert "timeline_mismatch" not in out["check"]
    assert all(len(r.served[u]) == r.planned[u].max_new_tokens for u in r.served)
    assert all(c["t0"] < r.t0 + 1.5 for c in r.recorder.rounds)
    low = run.reference_gaps(cell, 2 ** 33 + 3, r.planned, r.served,
                             keep["sample"], control=True)
    assert not run.is_correct(run.judge(cell, r, keep["sample"], low))


def test_sample_covers_every_quarter_of_the_slots():
    """At a real cell's slots and sample size the longest request alone
    covers the sample's tokens; the sample still reaches every quarter."""
    cell = json.load(open(os.path.join(HERE, "cells",
                                       "qwen2-1.5b-forms.decode_heavy.json")))
    slots = traffic.load_mix(cell["traffic"])["slots"]
    want = cell["check"]["sample_tokens"]
    lengths = {u: (want + 200 if u == 5 else 64) for u in range(3 * slots)}
    slot_of = {u: u % slots for u in lengths}
    for seed in (1, 2 ** 33 + 5, 77):
        sample = run.pick_sample(lengths, slot_of, slots, seed, want)
        assert sample[0] == 5
        assert {4 * slot_of[u] // slots for u in sample} == {0, 1, 2, 3}


def test_recorder_tells_prompts_apart_across_short_chunks():
    """A round's budget can grant a prompt a first chunk shorter than the
    recorder's key; the request is told apart once enough tokens arrived."""
    import timeline

    class Runner:
        decode_block = 4

        def bucket_for(self, n):
            return n

        def prefill_chunk(self, tokens, positions, tables, cols, temps):
            return np.full(tokens.shape[0], 7, np.int32)

        def prefill_slot(self, *a, **kw):
            raise AssertionError

        def decode_round(self, *a, **kw):
            raise AssertionError

    rng = np.random.default_rng(0)
    planned = [traffic.Planned(uid=u, prompt=rng.integers(0, 99, 12).astype(np.int32),
                               max_new_tokens=3, arrival_s=0.0) for u in range(2)]
    planned[1] = dataclasses.replace(planned[1], prompt=np.concatenate(
        [planned[0].prompt[:3], planned[1].prompt[3:]]))   # shared first tokens
    runner = Runner()
    rec = timeline.Recorder(runner, planned)
    rec.start(0.0)
    p = planned[1].prompt
    for pos, take in ((0, 3), (3, 6), (9, 3)):
        toks = np.zeros((2, 8), np.int32)
        toks[1, :take] = p[pos:pos + take]
        tables = np.zeros((2, 4), np.int32)
        tables[1] = 1
        runner.prefill_chunk(toks, np.array([0, pos]), tables,
                             np.array([0, take - 1]), np.zeros(2))
    assert rec.slot_uid == {1: 1}
    assert rec.tracks[1].tokens == [7] and rec.tracks[0].tokens == []
    rec.stop()


def _alter_tokens(engine):
    orig = engine.runner.decode_round

    def broken(*a, **kw):
        out, counts = orig(*a, **kw)
        out = out.copy()
        out[0] = (out[0] + 1) % TINY["vocab_size"]
        return out, counts
    engine.runner.decode_round = broken


def _half_batch(engine):
    orig = engine.runner.decode_round

    def broken(*a, **kw):
        out, counts = orig(*a, **kw)
        out = out.copy()
        out[:, out.shape[1] // 2:] = 0
        return out, counts
    engine.runner.decode_round = broken


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.serving import kv_cache
    hook = {"token_altered": _alter_tokens, "half_batch": _half_batch}.get(fault)
    if fault == "state_unchanged":
        monkeypatch.setattr(kv_cache, "commit_tokens",
                            lambda cache, *a, **kw: cache)
    out = _run(_cell("decode_heavy"), hook=hook)
    assert not out["correct"], out["check"]


def _upper_half(engine):
    """Half of the batch left out: the upper half of the slots gets the
    lower half's tokens."""
    orig = engine.runner.decode_round

    def broken(*a, **kw):
        out, counts = orig(*a, **kw)
        out = out.copy()
        half = out.shape[1] // 2
        out[:, half:] = out[:, :half]
        return out, counts
    engine.runner.decode_round = broken


def test_half_batch_fault_at_the_cells_own_size():
    """decode_heavy's 32 slots and 400-token sample, outputs longer than the
    sample: the half-batch fault is not correct, the sound run is."""
    cell_file = json.load(open(os.path.join(
        HERE, "cells", "qwen2-1.5b-forms.decode_heavy.json")))
    slots = traffic.load_mix("decode_heavy")["slots"]
    want = cell_file["check"]["sample_tokens"]
    cell = _cell("decode_heavy", sample_tokens=want, slots=slots,
                 max_len=512, backlog_rate=slots / 60.0,
                 output_len={"dist": "uniform", "min": want + 8,
                             "max": want + 40})
    sound = _run(cell, seconds=60.0)
    assert sound["correct"], sound["check"]
    assert sound["check"]["checked_slot_groups"]["value"] == 4
    broken = _run(cell, hook=_upper_half, seconds=60.0)
    assert not broken["correct"], broken["check"]


# --------------------------------------------------------------------------
# a family that comes as one new file
# --------------------------------------------------------------------------

def _files_sha(top):
    h = hashlib.sha256()
    for d, dirs, files in sorted(os.walk(top)):
        dirs[:] = sorted(x for x in dirs if x != "__pycache__")
        for name in sorted(files):
            path = os.path.join(d, name)
            h.update(os.path.relpath(path, top).encode())
            with open(path, "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def test_a_family_in_a_new_file_is_found_served_and_judged(tmp_path,
                                                          monkeypatch):
    """A configuration file names a family that exists only as a new file
    (a copy of the dense family under another name): the harness finds it
    by that name, serves the cell with its weights and judges the run with
    its reference, and no file of the benchmark changes."""
    before = _files_sha(HERE)
    for d in ("cells", "configs", "families"):
        (tmp_path / d).mkdir()
    shutil.copy(os.path.join(HERE, "families", "dense.py"),
                tmp_path / "families" / "dense_copy.py")
    config = {"name": "tiny-copy", "arch": "qwen2-1.5b", "family": "dense_copy",
              "model": dict(TINY), "forms": FORMS,
              "serving": {"page_size": 16, "decode_block": 4}}
    (tmp_path / "configs" / "tiny-copy.json").write_text(json.dumps(config))
    cell_file = {"config": "tiny-copy", "traffic": "decode_heavy",
                 "backlog_rate": 4.0,
                 "check": {"max_logit_gap": LIMIT, "sample_tokens": 1000}}
    (tmp_path / "cells" / "tiny-copy.decode_heavy.json").write_text(
        json.dumps(cell_file))
    monkeypatch.setattr(run, "CELLS", str(tmp_path / "cells"))
    monkeypatch.setattr(run, "CONFIGS", str(tmp_path / "configs"))
    monkeypatch.setattr(run, "FAMILIES", str(tmp_path / "families"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["workloads"].append({"name": "tiny-copy.decode_heavy",
                               "config": "tiny-copy",
                               "traffic": "decode_heavy", "chips": 1,
                               "why": "a family that comes as a new file"})
    cell = run.load_cell(bench, "tiny-copy.decode_heavy")
    family = cell["family"]
    assert family is not dense
    assert os.path.dirname(family.__file__) == str(tmp_path / "families")
    cell["mix"] = _cell("decode_heavy")["mix"]
    keep = {}
    out = run.run_cell(bench, cell, 2 ** 33 + 3, 30.0, False, peak=PEAK,
                       keep=keep)
    assert keep["run"].returned and keep["run"].work is family
    assert out["correct"], out["check"]
    assert out["check"]["checked_tokens"]["value"] > 0
    low = run.reference_gaps(cell, 2 ** 33 + 3, keep["run"].planned,
                             keep["run"].served, keep["sample"], control=True)
    assert not run.is_correct(run.judge(cell, keep["run"], keep["sample"], low))
    assert _files_sha(HERE) == before


def test_harness_names_no_family():
    """The harness, its helpers and the metric readers take a family's
    weights, reference and counts from the configuration file's family."""
    names = [os.path.join(HERE, f) for f in ("run.py", "control.py",
                                             "sweep.py", "timeline.py")]
    names += [os.path.join(HERE, "metrics", f)
              for f in os.listdir(os.path.join(HERE, "metrics"))
              if f.endswith(".py")]
    for path in names:
        text = open(path).read()
        assert "dense" not in text, path
        assert "import work" not in text and "import reference" not in text, path


# --------------------------------------------------------------------------
# time to first token
# --------------------------------------------------------------------------

def _timeline(ttft_ms, failed=()):
    """A drained run of requests due a second apart, whose first tokens
    come ``ttft_ms`` after their due times; ``failed`` ones are served one
    token short."""
    planned = {u: traffic.Planned(uid=u, prompt=np.zeros(8, np.int32),
                                  max_new_tokens=3, arrival_s=float(u))
               for u in range(len(ttft_ms))}
    tracks = {u: timeline.Track(first_t=100.0 + u + t / 1e3)
              for u, t in enumerate(ttft_ms)}
    served = {u: [1, 2] if u in failed else [1, 2, 3] for u in planned}
    cell = {"config_file": {"model": {"vocab_size": 100}},
            "mix": {"window": "drain"}}
    return timeline.Run(cell=cell, seed=0, seconds=20.0, planned=planned,
                        served=served, returned=True,
                        recorder=NS(tracks=tracks, trace=None), stats={},
                        setup_s=1.0, t0=100.0, peak=PEAK)


def test_ttft_p90_on_a_hand_built_timeline():
    read = run.load_reader("ttft_p90_ms")
    ttft = [10.0 * (u + 1) for u in range(20)]          # 10 .. 200 ms
    # rank 0.9 x 19 = 17.1: between the 18th and 19th smallest
    assert read(_timeline(ttft)) == pytest.approx(
        float(np.percentile(ttft, 90)))
    assert read(_timeline(ttft)) == pytest.approx(181.0)
    # request 0 (10 ms) failed: a miss, ranked above every other
    assert read(_timeline(ttft, failed={0})) == pytest.approx(191.0)
    # a request that never got its first token is a miss too
    r = _timeline(ttft)
    r.recorder.tracks[5].first_t = None
    assert read(r) == pytest.approx(191.0)
    # misses that reach the percentile leave it without a value
    assert read(_timeline(ttft, failed={0, 1, 2})) is None
