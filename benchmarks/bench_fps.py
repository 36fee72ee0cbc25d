"""Paper Figs 13/14: frame-per-second speedup composition vs original ISAAC,
driven by the measured crossbar reduction + measured EIC of the trained CNN —
plus the serving hot-path microbench (bulk prefill vs stepwise, donated
chunked decode vs a per-token host-sync loop)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, time_fn, trained_forms_cnn
from repro.core import crossbar as xbar
from repro.core import perfmodel as pm
from repro.core.quantization import quantize_activations
from repro.core.zeroskip import eic_stats
from repro.data.synthetic import image_batch
from repro.models import cnn as cnn_mod


def serving_hot_path(smoke: bool = False) -> None:
    """Prefill/decode hot-path numbers on the CPU oracle path.

    * ``serving.prefill``: one bulk ``model.prefill`` call vs the pre-PR
      admit loop (one jitted decode step per prompt token, sequentially
      dispatched) for a 64-token prompt.
    * ``serving.decode``: tokens/s of the donated chunked decode loop with
      on-device sampling vs a per-token loop that syncs logits to the host
      and samples there (the pre-PR steady state).
    """
    import jax
    import jax.numpy as jnp

    from benchmarks.common import tiny_serving_cfg
    from repro.models.registry import build
    from repro.serving.engine import ServingEngine

    cfg = tiny_serving_cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt_len, max_len, slots, block = 64, 160, 4, 8
    iters = 3 if smoke else 5
    prompt = np.arange(prompt_len, dtype=np.int32) % cfg.vocab_size

    eng = ServingEngine(model, params, max_len=max_len, batch_slots=slots,
                        decode_block=block)
    us_bulk = time_fn(lambda: eng.prefill_slot(0, prompt), iters=iters,
                      warmup=1)

    # pre-PR prefill: one jitted decode step per prompt token
    dec = jax.jit(model.decode_step)
    state = {"cache": model.init_cache(slots, max_len)}

    def stepwise_prefill():
        c = state["cache"]
        for t in range(prompt_len - 1):
            toks = jnp.full((slots, 1), int(prompt[t]), jnp.int32)
            _, c = dec(eng.params, toks, c, jnp.array(t, jnp.int32))
        state["cache"] = c
        return c

    us_step = time_fn(stepwise_prefill, iters=iters, warmup=1)
    emit("serving.prefill_bulk", us_bulk, f"prompt={prompt_len}")
    emit("serving.prefill_stepwise", us_step, f"prompt={prompt_len}")
    emit("serving.prefill_speedup", 0.0, f"{us_step / us_bulk:.1f}x")

    # steady-state decode: donated chunked device loop vs host-sync loop
    toks = np.zeros(slots, np.int32)
    pos = np.full(slots, prompt_len, np.int32)
    temps = np.zeros(slots, np.float32)
    us_chunk = time_fn(lambda: eng.decode_chunk(toks, pos, temps),
                       iters=iters, warmup=1)
    new_tps = slots * block / (us_chunk / 1e6)

    state["cache"] = model.init_cache(slots, max_len)

    def host_loop():
        c = state["cache"]
        for i in range(block):
            lg, c = dec(eng.params, jnp.asarray(toks)[:, None], c,
                        jnp.asarray(pos + i))
            np.argmax(np.asarray(lg.astype(jnp.float32))[:, 0], axis=-1)
        state["cache"] = c

    us_host = time_fn(host_loop, iters=iters, warmup=1)
    old_tps = slots * block / (us_host / 1e6)
    emit("serving.decode_device_loop", us_chunk,
         f"tok/s={new_tps:.0f};block={block}")
    emit("serving.decode_host_loop", us_host, f"tok/s={old_tps:.0f}")
    emit("serving.decode_speedup", 0.0, f"{new_tps / old_tps:.2f}x")


def serving_paged(smoke: bool = False) -> None:
    """Paged-vs-dense serving rows (tokens/s, cache HBM bytes, max
    concurrent slots) at the SAME cache-memory budget.

    The dense engine pays ``max_len`` rows per slot; the paged engine pays
    each request's actual footprint from a shared page pool, so the same
    HBM admits more concurrent requests (here 2x the slots on an equal-row
    pool).  On the CPU oracle the tok/s pair mostly tracks the extra
    gather/scatter cost — the rows exist so the perf trajectory catches
    regressions in the paged decode path and the concurrency claim.
    """
    import jax

    from benchmarks.common import tiny_serving_cfg
    from repro.models.registry import build
    from repro.serving.engine import Request, ServingEngine

    cfg = tiny_serving_cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len, block = 160, 8
    n_req, new = (8, 16) if smoke else (16, 24)
    prompt_len, page = 16, 16

    def requests():
        rng = np.random.RandomState(0)
        return [Request(uid=i,
                        prompt=rng.randint(0, cfg.vocab_size,
                                           size=prompt_len),
                        max_new_tokens=new) for i in range(n_req)]

    rows = {}
    for label, kw in (("dense", dict(batch_slots=4)),
                      ("paged", dict(batch_slots=8, page_size=page,
                                     num_pages=4 * max_len // page,
                                     prefix_cache=True))):
        eng = ServingEngine(model, params, max_len=max_len,
                            decode_block=block, **kw)
        eng.run(requests())                      # compile + warm
        t0 = time.perf_counter()
        results = eng.run(requests())
        dt = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in results)
        rows[label] = (dt, toks, eng)
        emit(f"serving.{label}_run", dt * 1e6,
             f"tok/s={toks / dt:.0f};hbm_bytes={eng.cache_bytes()};"
             f"max_concurrent={eng.scheduler.max_concurrent}")
    (ddt, dtoks, deng), (pdt, ptoks, peng) = rows["dense"], rows["paged"]
    emit("serving.paged_vs_dense", 0.0,
         f"concurrency={peng.scheduler.max_concurrent / max(1, deng.scheduler.max_concurrent):.1f}x;"
         f"hbm={peng.cache_bytes() / deng.cache_bytes():.2f}x;"
         f"tok_s={ptoks / pdt / (dtoks / ddt):.2f}x")


def serving_speculative(smoke: bool = False) -> None:
    """Self-speculative decoding rows: acceptance rate and decode tok/s vs
    the plain paged engine (DESIGN.md §6e).

    The target is a small TRAINED LM (benchmarks/common.trained_toy_lm —
    speculation exploits model redundancy, which random weights don't
    have); the draft is the target's own weights at 4-bit through the
    shared quantize_leaf path, keeping every 8th layer (a 1-of-8-layer
    early-exit draft).  K=4 drafts verify in one bounded multi-token
    forward per round, so at the ~0.9 acceptance the trained toy reaches,
    each round emits ~4.6 tokens for ~5/8 + ~1.3 target-steps of compute —
    the decode-tok/s ratio row is the criterion the CI trajectory watches
    (>= 1.3x on the CPU oracle; measured ~1.5x).
    """
    from benchmarks.common import trained_toy_lm
    from repro.serving.engine import Request, ServingEngine

    t = trained_toy_lm(num_layers=8, steps=100 if smoke else 160)
    model, params = t["model"], t["params"]
    max_len, block, k = 160, 8, 4
    n_req, new = (4, 64) if smoke else (8, 96)
    iters = 3

    def requests():
        rng = np.random.RandomState(0)
        return [Request(uid=i, prompt=t["prompt_fn"](rng, 8),
                        max_new_tokens=new) for i in range(n_req)]

    engines = {}
    for label, kw in (
            ("baseline", {}),
            ("speculative", dict(speculate=True, draft_k=k, draft_bits=4,
                                 draft_mode="int", draft_layer_step=8))):
        eng = ServingEngine(model, params, max_len=max_len, batch_slots=4,
                            decode_block=block, page_size=16, **kw)
        eng.run(requests())                      # compile + warm
        engines[label] = eng
    # decode-attributed tok/s (the per-request decode_ms split) — prefill
    # admission cost is reported separately so the speculative engine's
    # double prefill doesn't pollute the decode-rate criterion.  The two
    # engines are measured INTERLEAVED and take per-engine medians, so a
    # load spike on a shared CI runner hits both sides, not one.
    runs = {label: [] for label in engines}
    for _ in range(iters):
        for label, eng in engines.items():
            results = eng.run(requests())
            dec_ms = sum(r.decode_ms for r in results)
            pf_ms = sum(r.prefill_ms for r in results)
            dec_toks = sum(len(r.tokens) - 1 for r in results)
            runs[label].append((dec_toks / (dec_ms / 1e3), dec_ms, pf_ms))
    rows = {}
    for label, eng in engines.items():
        rr = sorted(runs[label])
        tps, dec_ms, pf_ms = rr[len(rr) // 2]
        rows[label] = tps
        derived = (f"decode_tok/s={tps:.0f};prefill_ms={pf_ms:.0f};"
                   f"requests={n_req}x{new}")
        if eng.speculative:
            sp = eng.stats()["speculate"]
            derived += (f";acceptance={sp['acceptance']:.3f}"
                        f";tok_per_round={sp['tokens_per_round']:.2f}"
                        f";draft_bits=4;k={k}")
        emit(f"serving.{label}_decode", dec_ms * 1e3, derived)
    emit("serving.speculative_vs_baseline", 0.0,
         f"decode_tok_s={rows['speculative'] / rows['baseline']:.2f}x")


def serving_mixed_precision(smoke: bool = False) -> None:
    """Auto mixed-precision rows (DESIGN.md §6h): the ``forms.autobits``
    sensitivity-driven per-leaf bit allocation served end to end vs the
    uniform 8-bit tree.

    Four engines over the trained toy LM, measured interleaved with
    per-engine medians like the speculative section:

    * ``uniform8`` — plain FORMS serving at uniform 8-bit (the PR-1
      baseline configuration);
    * ``draft_uniform4`` — speculative serving on the uniform8 target with
      the PR-5-style hand-picked draft (uniform 4-bit forms, 1-of-8-layer
      early exit);
    * ``draft_auto`` — the SAME uniform8 target with the allocator-derived
      draft (``plan_draft_bits`` at the modeled cost of the uniform 4-bit
      draft) — the apples-to-apples draft row: only the draft differs, so
      its acceptance must meet/beat ``draft_uniform4``'s;
    * ``auto`` — the full auto plan: target compressed with the
      ``plan_auto_bits`` knapsack under the accuracy budget, draft from
      the same sensitivity table — the headline row vs ``uniform8``.

    Honest-measurement note: the CPU oracle stores magnitudes as uint8
    regardless of the allocated width, so lower bits do NOT change the
    measured per-step matmul time — the crossbar win is reported as the
    ThroughputSpec-modeled speedup, while the MEASURED decode tok/s win of
    the auto engine comes from speculation (acceptance is bits-sensitive,
    exactly what the allocator optimizes).  Accuracy is measured, not
    modeled: held-out NLL on the toy LM's own perm-cycle stream (random
    tokens would reward blunt models — NLL falls toward uniform).  The
    fixture trains polarization-aware (``polarize_every``): serving a
    FORMS-compressed projection of a RAW trained model measures noise (the
    one-shot polarization projection costs ~0.5 rel-L2 and destroys the
    layer redundancy every draft depends on).

    Trajectory criteria the CI smoke rows watch: auto decode tok/s >=
    uniform8 within the measured accuracy budget, and auto-draft
    acceptance >= the uniform-4 draft's.
    """
    import jax.numpy as jnp

    from benchmarks.common import trained_toy_lm
    from repro.forms import autobits as AB
    from repro.forms.spec import FormsSpec
    from repro.forms.tree import compress_tree
    from repro.serving.engine import Request, ServingEngine

    t = trained_toy_lm(num_layers=8, steps=100 if smoke else 160,
                       polarize_every=10)
    model, params = t["model"], t["params"]
    spec = FormsSpec()
    max_len, block, k = 160, 8, 4
    n_req, new = (4, 64) if smoke else (8, 96)
    iters = 3
    # the polarization-trained toy quantizes extremely well (uniform-4 costs
    # ~1e-4 nats), so a tight budget is what exercises real mixing: at 1e-3
    # the validated allocator lands a 2/4/6-bit histogram instead of
    # degenerating to all-2-bit
    budget = 0.001

    def stream(seed: int, nb: int = 4, bs: int = 8, ln: int = 32):
        rng = np.random.RandomState(seed)
        return [jnp.asarray(np.stack([t["prompt_fn"](rng, ln)
                                      for _ in range(bs)]))
                for _ in range(nb)]

    calib = stream(0)
    acfg = AB.AutoBitsConfig(acc_budget=budget)
    table = AB.measure_sensitivity(model, params, spec, acfg, calib=calib)
    plan = AB.plan_auto_bits(model, params, spec, acfg, calib=calib,
                             table=table)
    draft = AB.plan_draft_bits(table, match_bits=4)

    # measured accuracy delta on a held-out stream (same compression the
    # engines serve; forward consumes the compressed leaves directly)
    heldout = stream(1)
    comp_uni, _ = compress_tree(params, spec)
    comp_plan, _ = compress_tree(params, spec, plan=plan.specs())
    nll_uni = AB.measured_nll(model, comp_uni, heldout)
    nll_plan = AB.measured_nll(model, comp_plan, heldout)
    acc_delta = nll_plan - nll_uni

    def requests():
        rng = np.random.RandomState(0)
        return [Request(uid=i, prompt=t["prompt_fn"](rng, 8),
                        max_new_tokens=new) for i in range(n_req)]

    draft_kw = dict(speculate=True, draft_k=k, draft_bits=4,
                    draft_mode="forms", draft_layer_step=8)
    engines = {}
    for label, kw in (
            ("uniform8", dict(spec=spec)),
            ("draft_uniform4", dict(spec=spec, **draft_kw)),
            ("draft_auto", dict(spec=spec, draft_plan=draft.specs(),
                                **draft_kw)),
            ("auto", dict(spec=spec, plan=plan.specs(),
                          draft_plan=draft.specs(), **draft_kw))):
        eng = ServingEngine(model, params, max_len=max_len, batch_slots=4,
                            decode_block=block, page_size=16, **kw)
        eng.run(requests())                      # compile + warm
        engines[label] = eng
    runs = {label: [] for label in engines}
    for _ in range(iters):
        for label, eng in engines.items():
            results = eng.run(requests())
            dec_ms = sum(r.decode_ms for r in results)
            dec_toks = sum(len(r.tokens) - 1 for r in results)
            runs[label].append((dec_toks / (dec_ms / 1e3), dec_ms))
    tps, accept = {}, {}
    hist = "/".join(f"{n}x{b}b" for b, n in plan.histogram().items())
    dhist = "/".join(f"{n}x{b}b" for b, n in draft.histogram().items())
    for label, eng in engines.items():
        rr = sorted(runs[label])
        tps[label], dec_ms = rr[len(rr) // 2]
        derived = f"decode_tok/s={tps[label]:.0f};requests={n_req}x{new}"
        if eng.speculative:
            sp = eng.stats()["speculate"]
            accept[label] = sp["acceptance"]
            derived += (f";acceptance={sp['acceptance']:.3f}"
                        f";tok_per_round={sp['tokens_per_round']:.2f}")
        if label == "auto":
            derived += (f";modeled_speedup={plan.modeled_speedup:.2f}x"
                        f";acc_delta={acc_delta:+.4f};budget={budget}"
                        f";bits={hist};draft_bits={dhist}")
        emit(f"serving.mixed_precision.{label}_decode", dec_ms * 1e3,
             derived)
    emit("serving.mixed_precision.auto_vs_uniform8", 0.0,
         f"decode_tok_s={tps['auto'] / tps['uniform8']:.2f}x"
         f";modeled={plan.modeled_speedup:.2f}x"
         f";acc_delta={acc_delta:+.4f};budget={budget};bits={hist}")
    emit("serving.mixed_precision.auto_draft_vs_uniform4", 0.0,
         f"acceptance={accept['draft_auto']:.3f}"
         f"_vs_{accept['draft_uniform4']:.3f}"
         f";decode_tok_s={tps['draft_auto'] / tps['draft_uniform4']:.2f}x"
         f";predicted_dnll={draft.predicted_dl:.4f};draft_bits={dhist}")


def serving_zeroskip(smoke: bool = False) -> None:
    """Zero-skipping rows: decode tok/s vs MEASURED activation sparsity
    (DESIGN.md §6g) — the paper's headline throughput mechanism exercised
    on the real paged decode path rather than the analytical EIC model.

    Two parts:

    * a synthetic ops-level sweep: the compressed matmul at fragment-
      structured input sparsity 0/50/75/90%, dense vs ``zero_skip`` — the
      kernel-level win as a function of sparsity;
    * the trained toy LM (ReLU MLP + fragment-structured activation
      sparsification, ``cfg.act_sparsity``) served by two engines that
      differ ONLY in ``ServingEngine(zero_skip=...)``, measured
      interleaved with per-engine medians; a third engine with
      ``zero_skip_stats=True`` reports the measured per-layer sparsity
      (its host callbacks would pollute the timed engines).  Greedy
      decodes must be token-identical — the skip changes schedule, not
      math.

    The trajectory criterion the CI smoke rows watch: >= 1.2x decode
    tok/s over the paged dense baseline at >= 50% measured fragment
    sparsity (measured here: ~1.5x at 0.56 overall).
    """
    import jax
    import jax.numpy as jnp

    from benchmarks.common import trained_toy_lm
    from repro.forms.spec import FormsSpec
    from repro.kernels import ops
    from repro.serving.engine import Request, ServingEngine

    # --- synthetic sparsity sweep (ops level, oracle path) ---------------
    m, M, K, N = 4, 8, 2048, 2048
    key = jax.random.PRNGKey(0)
    mags = jax.random.randint(key, (K, N), 0, 256).astype(jnp.uint8)
    signs = jnp.where(jax.random.normal(key, (K // m, N)) > 0, 1, -1
                      ).astype(jnp.int8)
    scale = (jax.random.uniform(key, (1, N)) * 0.01).astype(jnp.float32)
    iters = 3 if smoke else 5
    x_dense = jax.random.normal(key, (M, K), jnp.float32)
    us_dense = time_fn(jax.jit(lambda x: ops.polarized_matmul(
        x, mags, signs, scale, m=m)), x_dense, iters=iters)
    rng = np.random.RandomState(0)
    for sparsity in (0.5, 0.75, 0.9):
        # whole-fragment sparsity shared across rows, so compaction's
        # batch-union occupancy matches the per-row pattern
        frag_mask = (rng.rand(K // m) >= sparsity).astype(np.float32)
        x = x_dense * jnp.asarray(np.repeat(frag_mask, m))[None, :]
        keep = min(1.0, (1.0 - sparsity) * 1.3)
        f = jax.jit(lambda x, k=keep: ops.polarized_matmul(
            x, mags, signs, scale, m=m, zero_skip="compact",
            zero_skip_keep=k))
        us = time_fn(f, x, iters=iters)
        emit(f"serving.zeroskip_synth_s{int(sparsity * 100)}", us,
             f"speedup={us_dense / us:.2f}x;dense_us={us_dense:.0f};"
             f"K={K};m={m};keep={keep:.2f}")

    # --- trained toy LM, served end to end -------------------------------
    levels = (0.75,) if smoke else (0.5, 0.75)
    layers, steps = (3, 15) if smoke else (4, 40)
    n_req, new = 2, 40
    for drop in levels:
        t = trained_toy_lm(num_layers=layers, steps=steps,
                           d_model=256, d_ff=2048, vocab_size=256,
                           mlp_act="relu", act_sparsity=drop,
                           act_fragment=4)
        model, params = t["model"], t["params"]
        keep = min(1.0, (1.0 - drop) * 1.4)
        eng_kw = dict(max_len=96, batch_slots=1, decode_block=8,
                      page_size=16, forms=True, fragment=4)

        def requests(new_toks=new):
            rq = np.random.RandomState(0)
            return [Request(uid=i, prompt=t["prompt_fn"](rq, 8),
                            max_new_tokens=new_toks) for i in range(n_req)]

        engines, toks = {}, {}
        for label, kw in (("baseline", {}),
                          ("zeroskip", dict(zero_skip="compact",
                                            zero_skip_keep=keep))):
            eng = ServingEngine(model, params, **eng_kw, **kw)
            toks[label] = [r.tokens for r in eng.run(requests())]  # + warm
            engines[label] = eng
        identical = toks["baseline"] == toks["zeroskip"]

        runs = {label: [] for label in engines}
        for _ in range(iters):
            for label, eng in engines.items():
                results = eng.run(requests())
                dec_ms = sum(r.decode_ms for r in results)
                dec_toks = sum(len(r.tokens) - 1 for r in results)
                runs[label].append((dec_toks / (dec_ms / 1e3), dec_ms))

        # measured sparsity from a separate stats engine (short run: the
        # per-layer fractions are deterministic for greedy decode)
        stats_eng = ServingEngine(model, params, zero_skip="compact",
                                  zero_skip_keep=keep, zero_skip_stats=True,
                                  **eng_kw)
        stats_eng.run(requests(16))
        sp = stats_eng.stats()["sparsity"]
        frag = sp["overall"]["fragment_sparsity"]
        mlp = sp["layers"].get("down", {}).get("fragment_sparsity", 0.0)

        rows = {}
        for label in engines:
            rr = sorted(runs[label])
            tps, dec_ms = rr[len(rr) // 2]
            rows[label] = tps
            emit(f"serving.zeroskip_{label}_d{int(drop * 100)}", dec_ms * 1e3,
                 f"decode_tok/s={tps:.0f};requests={n_req}x{new}")
        emit(f"serving.zeroskip_vs_baseline_d{int(drop * 100)}", 0.0,
             f"decode_tok_s={rows['zeroskip'] / rows['baseline']:.2f}x;"
             f"measured_frag_sparsity={frag:.2f};mlp_frag_sparsity={mlp:.2f}"
             f";skip_frac={1.0 - keep:.2f};mode=compact;"
             f"token_identical={identical}")


def run(smoke: bool = False) -> None:
    serving_hot_path(smoke=smoke)
    serving_paged(smoke=smoke)
    serving_speculative(smoke=smoke)
    serving_mixed_precision(smoke=smoke)
    serving_zeroskip(smoke=smoke)
    fragments = (8,) if smoke else (8, 16)
    kw = (dict(pretrain_steps=20, admm_steps=30, finetune_steps=10)
          if smoke else {})
    for fragment in fragments:
        t = trained_forms_cnn(fragment=min(fragment, 8), **kw)
        shapes = cnn_mod.crossbar_weight_shapes(t["cfg"], t["projected"])
        rep = xbar.reduction_report(shapes, shapes, xbar.CrossbarSpec(),
                                    t["spec"].quant, baseline_bits=32)
        img, _ = image_batch(t["ds"], 9100)
        _, acts = cnn_mod.forward(t["cfg"], t["projected"], img,
                                  collect_activations=True)
        eics = []
        for _, a in acts:
            codes, _ = quantize_activations(a.reshape(a.shape[0], -1),
                                            t["spec"].input_bits)
            eics.append(eic_stats(codes, fragment,
                                  t["spec"].input_bits).mean_eic)
        mean_eic = float(np.mean(eics))
        sp = pm.fps_speedup(crossbar_reduction_prune=rep.prune_factor,
                            crossbar_reduction_quant=rep.quant_factor,
                            fragment=fragment, mean_eic=mean_eic)
        emit(f"fig13.pruned_quantized_isaac.m{fragment}", 0.0,
             f"{sp['pruned_quantized_isaac']:.1f}x")
        emit(f"fig13.forms_model_opt.m{fragment}", 0.0,
             f"{sp['forms_model_opt']:.1f}x")
        emit(f"fig13.forms_full_zero_skip.m{fragment}", 0.0,
             f"{sp['forms_full_zero_skip']:.1f}x;mean_eic={mean_eic:.1f}")
    # the paper's published envelope for reference
    emit("fig13.published_envelope", 0.0,
         "pruned-isaac=7.5-200.8x;forms-model=4-109.6x;forms-full=10.7-377.9x")


if __name__ == "__main__":
    run()
