"""Chip smoke run: qwen2-1.5b at its published widths served on a TPU.

  python chip_smoke.py             # one chip: dense and FORMS engines
  python chip_smoke.py --chips 4   # FORMS on a data=2,model=2 mesh vs one chip

Weights are random (``model.init(PRNGKey(0))``, jitted); prompts are drawn from
``SEED``.  One chip: the model is served through ``ServingEngine`` as
``launch/serve.py`` builds it (paged cache, page_size 16, 8 slots, max_len
1024), once dense and once FORMS-compressed (m=8, 8-bit), with 8 greedy
requests of 128-512 prompt tokens and 32 new tokens each.  It checks that

* the FORMS engine's compiled decode step contains the Pallas kernel
  (``tpu_custom_call``), not the jnp oracle or the interpreter;
* each engine's prefill logits for one prompt agree with a plain f32
  ``model.forward`` at ``highest`` matmul precision (over the dense tree,
  or the decompressed served tree for FORMS), and the FORMS error is at
  most twice the dense engine's: the kernel adds no error beyond what bf16
  activations already cost;
* every request finishes with 32 tokens, all inside the vocabulary.

``--chips 4`` runs only the mesh phase: the FORMS engine on a
data=2,model=2 mesh over four chips against the single-device engine on
device 0, same prompts; first-step logits must agree within
``MESH_LOGIT_TOL`` (relative L2), the mesh engine's error against the f32
reference may be at most twice the one-device engine's, and greedy token
agreement is reported.

Timings are smoke timings of one short run, not a benchmark.  The last line
of standard output is the JSON result; the script exits non-zero without
printing it when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-1.5b"
SLOTS, MAX_LEN, PAGE_SIZE = 8, 1024, 16
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, (128, 512), 32
FORMS_ARGS = dict(m=8, bits=8)
SEED = 0
# first-step logits of the mesh engine vs the one-device engine, relative
# L2: both run bf16 activations, the mesh sums some products in another
# order, so they may differ by bf16 rounding but not by a wrong shard
MESH_LOGIT_TOL = 2e-2
KERNEL = 'custom_call_target="tpu_custom_call"'


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds spent in XLA compilation (persistent-cache hits included, at
    the cost of loading them), read from JAX's own compile events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def lap(self) -> float:
        out, self.seconds = self.seconds, 0.0
        return out


def make_requests(vocab: int, seed: int):
    from repro.serving.engine import Request
    rng = np.random.RandomState(seed)
    lo, hi = PROMPT_LEN
    return [Request(uid=i,
                    prompt=rng.randint(0, vocab, size=rng.randint(lo, hi + 1)
                                       ).astype(np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i in range(N_REQUESTS)]


def build_engine(model, params, spec, mesh=None):
    from repro.serving.engine import ServingEngine
    return ServingEngine(model, params, max_len=MAX_LEN, batch_slots=SLOTS,
                         spec=spec, page_size=PAGE_SIZE, mesh=mesh)


def prefill_logits(eng, prompt):
    """Last-position logits of ``prompt`` from the engine's served tree,
    through the model's paged prefill under the engine's spec and mesh:
    what the engine's prefill step computes before it samples."""
    import jax
    import jax.numpy as jnp
    from repro.distributed.sharding import parallel_context
    from repro.forms import default_spec

    toks, n = eng.runner.padded_prompt(prompt)
    n_pages = -(-toks.shape[1] // PAGE_SIZE)
    cache = eng.model.init_paged_cache(n_pages + 1, PAGE_SIZE, 1, MAX_LEN)
    pages = jnp.arange(1, n_pages + 1, dtype=jnp.int32)

    def fn(p, t, c):
        with default_spec(eng.spec):
            return eng.model.prefill_paged(p, t, c, pages, 0, n)[0]

    with parallel_context(eng.ctx):
        out = jax.jit(fn)(eng.params, jnp.asarray(toks), cache)
    return np.asarray(out, np.float32).reshape(-1)


def reference_logits(cfg, params, prompt):
    """Last-position logits of a plain f32 forward at highest precision."""
    import jax
    import jax.numpy as jnp
    from repro.models.registry import build

    model = build(dataclasses.replace(cfg, dtype="float32"))
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])(
            params, jnp.asarray(prompt, jnp.int32)[None])
    return np.asarray(out[0, -1], np.float32)


def rel_err(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def decode_kernel_count(eng) -> int:
    """``tpu_custom_call``s in the engine's compiled decode step."""
    import jax
    import jax.numpy as jnp
    from repro.distributed.sharding import parallel_context

    z = jnp.zeros(SLOTS, jnp.int32)
    with parallel_context(eng.ctx):
        compiled = eng.runner._decode.lower(
            eng.params, eng.cache, z, z,
            jnp.asarray(eng.scheduler.block_tables, jnp.int32),
            jnp.zeros(SLOTS, jnp.float32), jax.random.PRNGKey(0)).compile()
    return compiled.as_text().count(KERNEL)


def check_results(results, vocab: int, label: str):
    assert len(results) == N_REQUESTS, (label, len(results))
    for r in results:
        toks = np.asarray(r.tokens)
        assert toks.shape == (NEW_TOKENS,), (label, r.uid, toks.shape)
        assert ((toks >= 0) & (toks < vocab)).all(), (label, r.uid, toks)


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def serve(label, eng, reqs, vocab, clock):
    """Run the requests twice: the first run compiles, the second is the
    smoke timing.  Returns the second run's results."""
    t0 = time.perf_counter()
    check_results(eng.run(reqs), vocab, label)
    first_s = time.perf_counter() - t0
    compile_s = clock.lap()
    t0 = time.perf_counter()
    results = eng.run(reqs)
    warm_s = time.perf_counter() - t0
    check_results(results, vocab, label)
    n_tok = sum(len(r.tokens) for r in results)
    log(f"{label}: first run {first_s:.2f}s ({compile_s:.2f}s XLA compile); "
        f"smoke timing (not a benchmark): {n_tok} tokens in {warm_s:.2f}s = "
        f"{n_tok / warm_s:.1f} tok/s; peak_bytes_in_use {peak_bytes()}")
    return results


def one_chip(cfg, seed: int, clock) -> None:
    import jax
    from repro.forms import FormsSpec, decompress_tree
    from repro.models.registry import build

    model = build(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    reqs = make_requests(cfg.vocab_size, seed)
    prompt = reqs[0].prompt
    log(f"model {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads}; {N_REQUESTS} requests, prompts "
        f"{[len(r.prompt) for r in reqs]}, {NEW_TOKENS} new tokens each; "
        f"init {clock.lap():.2f}s XLA compile")

    dense = build_engine(model, params, None)
    serve("dense", dense, reqs, cfg.vocab_size, clock)
    dense_err = rel_err(prefill_logits(dense, prompt),
                        reference_logits(cfg, params, prompt))
    del dense
    gc.collect()

    t0 = time.perf_counter()
    forms = build_engine(model, params, FormsSpec(**FORMS_ARGS))
    log(f"forms: compressed in {time.perf_counter() - t0:.2f}s "
        f"({clock.lap():.2f}s XLA compile): "
        f"{forms.compression_report.summary()}; "
        f"peak_bytes_in_use {peak_bytes()}")
    del params
    gc.collect()
    n_kernels = decode_kernel_count(forms)
    log(f"forms: {n_kernels} tpu_custom_call in the compiled decode step "
        f"({clock.lap():.2f}s XLA compile)")
    assert n_kernels > 0, "FORMS decode step holds no Pallas kernel"
    serve("forms", forms, reqs, cfg.vocab_size, clock)
    forms_err = rel_err(prefill_logits(forms, prompt),
                        reference_logits(cfg, decompress_tree(forms.params),
                                         prompt))
    log(f"prefill logits vs f32 reference (relative L2, prompt of "
        f"{len(prompt)}): dense {dense_err:.6f}, forms {forms_err:.6f} "
        f"(limit {2 * dense_err:.6f}); {clock.lap():.2f}s XLA compile")
    assert np.isfinite(dense_err) and np.isfinite(forms_err)
    assert forms_err <= 2 * dense_err, (forms_err, dense_err)


def four_chips(cfg, seed: int, clock) -> None:
    import jax
    from repro.configs.base import MeshConfig
    from repro.forms import FormsSpec, decompress_tree
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build

    model = build(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    reqs = make_requests(cfg.vocab_size, seed)
    spec = FormsSpec(**FORMS_ARGS)
    mesh = make_mesh(MeshConfig(data=2, model=2))
    log(f"model {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}; mesh {dict(mesh.shape)} over "
        f"{len(jax.devices())} devices vs one device")
    single = build_engine(model, params, spec)
    sharded = build_engine(model, params, spec, mesh=mesh)
    del params
    gc.collect()
    log(f"engines built ({clock.lap():.2f}s XLA compile); peak_bytes_in_use "
        f"{peak_bytes()}")
    logits = [(prefill_logits(sharded, r.prompt),
               prefill_logits(single, r.prompt)) for r in reqs]
    errs = [rel_err(got, want) for got, want in logits]
    log(f"first-step logits, mesh vs one device (relative L2): max "
        f"{max(errs):.6f}, per prompt {[round(e, 6) for e in errs]} "
        f"(limit {MESH_LOGIT_TOL})")
    ref = reference_logits(cfg, decompress_tree(single.params),
                           reqs[0].prompt)
    mesh_err, single_err = (rel_err(x, ref) for x in logits[0])
    log(f"prefill logits vs f32 reference (relative L2, prompt of "
        f"{len(reqs[0].prompt)}): one device {single_err:.6f}, mesh "
        f"{mesh_err:.6f} (limit {2 * single_err:.6f}); "
        f"{clock.lap():.2f}s XLA compile")
    assert mesh_err <= 2 * single_err, (mesh_err, single_err)
    want = {r.uid: r.tokens for r in serve("single", single, reqs,
                                           cfg.vocab_size, clock)}
    got = {r.uid: r.tokens for r in serve("mesh", sharded, reqs,
                                          cfg.vocab_size, clock)}
    same = sum(int(a == b) for u in want for a, b in zip(want[u], got[u]))
    log(f"greedy token agreement mesh vs one device: {same}/"
        f"{N_REQUESTS * NEW_TOKENS}; identical requests "
        f"{sum(want[u] == got[u] for u in want)}/{N_REQUESTS}")
    assert max(errs) <= MESH_LOGIT_TOL, errs


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data=2,model=2 mesh phase")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devices[0].platform!r}); this check runs only on a TPU")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {len(devices)}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
        f"cache {cache_dir}")

    from repro.configs import get_config
    cfg = get_config(ARCH)
    clock = CompileClock()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(cfg, SEED, clock)
    log(f"total {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
