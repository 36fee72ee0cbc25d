"""Serving launcher: paged KV cache + bulk prefill + donated batched decode
with optional FORMS compression, mesh sharding and self-speculative decoding.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --requests 8 --forms --decode-block 8

  # paged KV cache with prompt-prefix sharing (attention families):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --page-size 16 --prefix-cache

  # self-speculative decoding: a 4-bit draft derived from the served weights
  # drafts 4 tokens per round, the target verifies them in one forward:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --forms --speculate --draft-bits 4 --draft-k 4 --stats-every 16

  # tensor/data-parallel decode on the compressed pytree (8 devices):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --forms --mesh data=2,model=4 --fake-devices 8

  # SLO fleet scheduling (DESIGN.md §6i): chunked prefill + priorities +
  # deadlines under seeded open-loop sustained load with one adversarial
  # long prompt in the mix:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --prefill-chunk 32 --step-token-budget 128 --deadline-ms 500 \
      --loadgen n=64,rate=100,batch-frac=0.25,adversarial=96

  # fault-tolerant serving: inject ReRAM faults into the live compressed
  # weights, probe for logit drift every 8 rounds, auto-repair:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --forms --fault-sigma 0.1 --fault-stuck 0.001 --fault-repair \
      --probe-every 8

  # activation zero-skipping (the paper's headline throughput mechanism):
  # skip dead input tiles in the compressed matmuls, report measured
  # per-layer sparsity:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --forms --zero-skip block --zero-skip-stats

  # auto mixed precision: Fisher-sensitivity sweep + modeled-throughput
  # knapsack picks per-leaf magnitude bits under an accuracy budget; the
  # engine serves the heterogeneous tree and reports greedy parity vs the
  # uniform width that fits the same budget:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --forms --auto-bits --acc-budget 0.05

  # ... and derive the speculative draft from the same sensitivity table
  # (per-leaf bits at the modeled cost of a uniform --draft-bits draft):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --forms --auto-bits --speculate --auto-draft --draft-bits 4

With ``--forms`` the weights are compressed via ``repro.forms.compress_tree``
and the engine decodes directly on the compressed pytree (uint8 magnitudes +
int8 fragment signs through the polarized-matmul kernel).  ``--decode-block``
sets how many tokens the jitted decode loop produces per host sync.
``--page-size`` (default 16, ``0`` disables) serves the attention families
from a paged KV pool — admission is by free-page budget, so short requests
only hold the pages they need — and ``--prefix-cache`` shares page-aligned
prompt prefixes across concurrent requests (DESIGN.md §6d).
``--speculate`` (paged families) serves with self-speculative decoding
(DESIGN.md §6e): ``--draft-bits``/``--draft-mode``/``--draft-fragment``
control the low-bit draft derived from the target's own weights,
``--draft-layer-step n`` keeps every n-th layer (early-exit drafts for
trained models), ``--draft-k`` bounds the drafts verified per round, and
per-slot adaptive K shrinks a slot's draft length when its acceptance
drops.  ``--stats-every N`` prints a page-pool/acceptance stat line every N
decode rounds.  ``--mesh data=D,model=M`` runs the engine SPMD over a
device mesh (see launch/mesh.py): compressed leaves co-shard along N, KV
caches shard slots (or page pools) over the data axes; ``--fake-devices N``
forces N host devices (CPU demo/testing — on real fleets the device count
comes from the runtime).

Reliability (``--forms`` only; DESIGN.md §6f): ``--fault-sigma`` /
``--fault-stuck`` / ``--fault-drift`` corrupt the live compressed weights
with the seeded ReRAM fault model (lognormal conductance variation,
stuck-at cells, retention drift) before serving; ``--encoding vecom``
compresses with VECOM-style reference-column offset compensation so the
read-back cancels column-correlated variation.  ``--fault-repair`` arms
the health monitor: golden-prompt drift probes every ``--probe-every``
decode rounds, per-leaf scoreboards in ``engine.stats()``, and automatic
re-encoding of flagged leaves from the clean reference copy without
dropping in-flight requests.

Zero-skipping (``--forms`` only; DESIGN.md §6g): ``--zero-skip block``
skips whole all-zero input tiles in the polarized matmul (bit-identical to
dense), ``--zero-skip compact`` gathers live fragments into a smaller
matmul when sparsity is high (``--zero-skip-keep`` sets the fragment
budget; exact either way, dense fallback when the budget is exceeded).
``--zero-skip-stats`` measures per-layer activation sparsity on the decode
path and prints it with the final stats (costs one host callback per
matmul per decode step).

Auto mixed precision (``--forms`` only; DESIGN.md §6h): ``--auto-bits``
runs ``forms.autobits`` — a Fisher-diagonal sensitivity sweep over the
crossbar leaves plus a greedy bits-down knapsack on the modeled ADC
throughput — and serves the resulting ``{path: FormsSpec}`` plan as a
heterogeneous compressed tree.  ``--acc-budget`` bounds the predicted
NLL increase; the launcher also serves the *uniform* width that fits the
same budget and reports greedy token parity between the two (asserted
exact when the plan degenerates to that uniform width).  With
``--speculate --auto-draft`` the draft's per-leaf bits come from the same
sensitivity table at the modeled cost of a uniform ``--draft-bits`` draft.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-1.5b", choices=ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--forms", action="store_true",
                    help="serve on the FORMS-compressed pytree")
    ap.add_argument("--fragment", type=int, default=8)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--sign-rule", default="energy", choices=("sum", "energy"))
    ap.add_argument("--auto-bits", action="store_true",
                    help="auto mixed precision: Fisher-sensitivity sweep + "
                         "modeled-throughput knapsack assigns per-leaf "
                         "magnitude bits under --acc-budget (forms serving "
                         "only)")
    ap.add_argument("--acc-budget", type=float, default=0.05, metavar="NATS",
                    help="predicted mean-NLL increase budget of the "
                         "--auto-bits plan vs the uniform --bits tree")
    ap.add_argument("--auto-draft", action="store_true",
                    help="derive the speculative draft's per-leaf bits from "
                         "the --auto-bits sensitivity table at the modeled "
                         "cost of a uniform --draft-bits draft")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--decode-block", type=int, default=4,
                    help="tokens decoded per jitted dispatch (host syncs "
                         "once per block)")
    ap.add_argument("--prompt-len", type=int, default=None,
                    help="fixed prompt length (default: random 2-5)")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable cache donation (debugging)")
    ap.add_argument("--page-size", type=int, default=16, metavar="ROWS",
                    help="KV-cache page size for paged serving (attention "
                         "families; recurrent families always use the dense "
                         "slot cache); 0 = dense slot cache")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size (default: every slot can hold a "
                         "full max_len request)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share page-aligned prompt prefixes across "
                         "concurrent requests (paged serving only)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    metavar="TOKENS",
                    help="SLO fleet scheduler (serving/sched.py): prefill "
                         "prompts in page-aligned chunks of ~TOKENS "
                         "interleaved with decode rounds, so one long "
                         "prompt can't stall every active decode "
                         "(0 = whole-prompt admission); any SLO flag "
                         "switches the engine to the fleet scheduler")
    ap.add_argument("--step-token-budget", type=int, default=None,
                    metavar="TOKENS",
                    help="fleet scheduler per-round token budget shared by "
                         "decode and chunked prefill (0 = unbounded)")
    ap.add_argument("--priority-default", default=None,
                    choices=("interactive", "batch"),
                    help="fleet scheduler priority class for requests that "
                         "don't set one (interactive preempts batch by "
                         "page eviction)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="fleet scheduler default completion deadline "
                         "relative to arrival; admission is "
                         "earliest-deadline-first within priority, misses "
                         "are counted per class in stats()['slo']")
    ap.add_argument("--loadgen", default=None, metavar="SPEC",
                    help="drive the engine with the seeded open-loop load "
                         "generator (serving/loadgen.py) instead of the "
                         "--requests batch: comma-separated keys, e.g. "
                         "'n=64,rate=100,seed=0,batch-frac=0.25,"
                         "adversarial=96' (n, rate, seed, prompt-lo, "
                         "prompt-hi, out-lo, out-hi, batch-frac, "
                         "deadline-ms, batch-deadline-ms, adversarial, "
                         "adversarial-count)")
    ap.add_argument("--speculate", action="store_true",
                    help="self-speculative decoding: low-bit draft + "
                         "one-forward verification (paged families only)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="max draft tokens verified per speculative round")
    ap.add_argument("--draft-bits", type=int, default=4,
                    help="draft magnitude bits")
    ap.add_argument("--draft-mode", default="forms",
                    choices=("forms", "int"),
                    help="draft weights: FORMS low-bit compression or the "
                         "symmetric int serving grid")
    ap.add_argument("--draft-fragment", type=int, default=None,
                    help="forms-mode draft fragment size m (default: the "
                         "target's geometry)")
    ap.add_argument("--draft-layer-step", type=int, default=1,
                    help="keep every n-th layer in the draft (early-exit "
                         "draft; 1 = full depth)")
    ap.add_argument("--no-adaptive-k", action="store_true",
                    help="disable per-slot adaptive draft length")
    ap.add_argument("--stats-every", type=int, default=0, metavar="ROUNDS",
                    help="print pool/acceptance stats every N decode rounds")
    ap.add_argument("--zero-skip", default="off",
                    choices=("off", "block", "compact"),
                    help="activation zero-skipping in the compressed "
                         "matmuls: 'block' skips all-zero input tiles "
                         "(bit-identical), 'compact' gathers live fragments "
                         "into a smaller matmul (forms serving only)")
    ap.add_argument("--zero-skip-keep", type=float, default=0.5,
                    metavar="FRAC",
                    help="compaction fragment budget as a fraction of K/m; "
                         "the compact path falls back to dense when more "
                         "fragments are live")
    ap.add_argument("--zero-skip-stats", action="store_true",
                    help="measure per-layer activation sparsity on the "
                         "decode path (one host callback per matmul per "
                         "step) and print it with the final stats")
    ap.add_argument("--mlp-act", default=None,
                    choices=("silu", "gelu", "relu"),
                    help="override the MLP activation (relu + "
                         "--act-sparsity is the regime zero-skipping "
                         "exploits; changes the model)")
    ap.add_argument("--act-sparsity", type=float, default=None, metavar="FRAC",
                    help="fragment-structured activation sparsification: "
                         "drop this fraction of MLP input fragments per row "
                         "(keep the strongest by max|x|; changes the model)")
    ap.add_argument("--act-fragment", type=int, default=None,
                    help="fragment size for --act-sparsity (align with "
                         "--fragment so dropped fragments map onto whole "
                         "skip units; default: ModelConfig's)")
    ap.add_argument("--encoding", default="binary",
                    choices=("binary", "vecom"),
                    help="cell-level encoding of the compressed weights: "
                         "plain bit-slice or VECOM-style reference-column "
                         "offset compensation (reliability)")
    ap.add_argument("--fault-sigma", type=float, default=None,
                    help="inject lognormal conductance variation of this "
                         "scale into the live compressed weights")
    ap.add_argument("--fault-stuck", type=float, default=None,
                    help="per-cell stuck-at fault probability (split evenly "
                         "between stuck-SET and stuck-RESET)")
    ap.add_argument("--fault-drift", type=float, default=None, metavar="T",
                    help="retention time for drift injection "
                         "((1+T)^-nu conductance decay)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="fault-injection RNG seed")
    ap.add_argument("--fault-repair", action="store_true",
                    help="arm the health monitor: probe for logit drift and "
                         "auto-repair corrupted leaves from the reference "
                         "copy (forms serving only)")
    ap.add_argument("--probe-every", type=int, default=16, metavar="ROUNDS",
                    help="decode rounds between health probes "
                         "(with --fault-repair)")
    ap.add_argument("--drift-threshold", type=float, default=1e-3,
                    help="max-abs logit drift that triggers scan/repair")
    ap.add_argument("--mesh", default=None, metavar="AXES",
                    help='device mesh as "data=D,model=M" (sharded serving); '
                         "omit for single-device")
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="force N host-platform devices (CPU demo/testing)")
    args = ap.parse_args()
    use_compile_cache()

    if args.fake_devices:
        # must land before the first jax backend touch below
        from repro.launch.mesh import force_host_device_count
        force_host_device_count(args.fake_devices)
    import jax

    from repro.forms import FormsSpec
    from repro.models.registry import build
    from repro.reliability import FaultModel, HealthConfig
    from repro.serving.engine import Request, ServingEngine

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    act_over = {k: v for k, v in (("mlp_act", args.mlp_act),
                                  ("act_sparsity", args.act_sparsity),
                                  ("act_fragment", args.act_fragment))
                if v is not None}
    if act_over:
        cfg = dataclasses.replace(cfg, **act_over)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    fault_args = (args.fault_sigma, args.fault_stuck, args.fault_drift)
    wants_faults = any(v is not None for v in fault_args)
    if (wants_faults or args.fault_repair) and not args.forms:
        raise SystemExit("--fault-*/--encoding model ReRAM cells, which only "
                         "exist for compressed weights: add --forms")
    if (args.zero_skip != "off" or args.zero_skip_stats) and not args.forms:
        raise SystemExit("--zero-skip/--zero-skip-stats act on the FORMS "
                         "matmul path: add --forms")
    slo_flags = [n for n, v in (("--prefill-chunk", args.prefill_chunk),
                                ("--step-token-budget",
                                 args.step_token_budget),
                                ("--priority-default", args.priority_default),
                                ("--deadline-ms", args.deadline_ms),
                                ("--loadgen", args.loadgen))
                 if v is not None]
    if slo_flags:
        if not args.page_size:
            raise SystemExit(f"{'/'.join(slo_flags)} need the SLO fleet "
                             "scheduler, which schedules KV pages (chunked "
                             "prefill, preemption-by-page-eviction): drop "
                             "--page-size 0")
        if not model.supports_paged:
            raise SystemExit(f"{'/'.join(slo_flags)} need the SLO fleet "
                             f"scheduler, but family {cfg.family!r} has no "
                             "paged path (O(1) recurrent state — nothing to "
                             "chunk or evict): pick an attention family")
    if args.loadgen is not None and args.prompt_len is not None:
        raise SystemExit("--loadgen draws its own prompt-length mix from "
                         "the seed: drop --prompt-len (or drop --loadgen "
                         "for fixed-length prompts)")
    lg_cfg = None
    if args.loadgen is not None:
        from repro.serving.loadgen import LoadGenConfig
        kv: dict = {}
        for part in filter(None, args.loadgen.split(",")):
            if "=" not in part:
                raise SystemExit(f"--loadgen: expected key=value, "
                                 f"got {part!r}")
            k, v = part.split("=", 1)
            kv[k.strip()] = v.strip()
        known = {"n": int, "rate": float, "seed": int, "prompt-lo": int,
                 "prompt-hi": int, "out-lo": int, "out-hi": int,
                 "batch-frac": float, "deadline-ms": float,
                 "batch-deadline-ms": float, "adversarial": int,
                 "adversarial-count": int}
        bad = sorted(set(kv) - set(known))
        if bad:
            raise SystemExit(f"--loadgen: unknown key(s) {bad}; "
                             f"known: {sorted(known)}")
        g = {k: known[k](v) for k, v in kv.items()}
        lg_cfg = LoadGenConfig(
            n_requests=g.get("n", args.requests),
            rate=g.get("rate", 100.0), seed=g.get("seed", 0),
            prompt_len=(g.get("prompt-lo", 2), g.get("prompt-hi", 8)),
            out_len=(g.get("out-lo", 4),
                     g.get("out-hi", args.max_new_tokens)),
            batch_frac=g.get("batch-frac", 0.25),
            deadline_ms=g.get("deadline-ms"),
            batch_deadline_ms=g.get("batch-deadline-ms"),
            adversarial_len=g.get("adversarial", 0),
            adversarial_count=g.get("adversarial-count", 1),
            vocab=cfg.vocab_size, temperature=args.temperature)
    slo = None
    if slo_flags:
        from repro.serving.sched import SLOConfig
        slo = SLOConfig(
            prefill_chunk=(args.prefill_chunk
                           if args.prefill_chunk is not None else 32),
            step_token_budget=(args.step_token_budget
                               if args.step_token_budget is not None
                               else 128),
            default_priority=args.priority_default or "interactive",
            default_deadline_ms=args.deadline_ms)
    spec = (FormsSpec(m=args.fragment, bits=args.bits, rule=args.sign_rule,
                      encoding=args.encoding)
            if args.forms else None)
    if (args.auto_bits or args.auto_draft) and not args.forms:
        raise SystemExit("--auto-bits/--auto-draft pick per-leaf FORMS "
                         "bit-widths: add --forms")
    if args.auto_draft and not args.auto_bits:
        raise SystemExit("--auto-draft reuses the --auto-bits sensitivity "
                         "table: add --auto-bits")
    auto = plan = draft_plan = None
    if args.auto_bits:
        from repro.forms import autobits as AB
        acfg = AB.AutoBitsConfig(acc_budget=args.acc_budget)
        auto = AB.plan_auto_bits(model, params, spec, acfg)
        plan = auto.specs()
        print(f"auto-bits: {auto.summary()}")
        for pth, grp, dl in auto.top_groups():
            print(f"auto-bits: most sensitive {pth} col-group {grp} "
                  f"(dl {dl:.2e})")
        if args.auto_draft:
            if args.draft_mode != "forms":
                raise SystemExit("--auto-draft plans FORMS bit-widths: use "
                                 "--draft-mode forms")
            dplan = AB.plan_draft_bits(auto.table,
                                       match_bits=args.draft_bits)
            draft_plan = dplan.specs()
            print(f"auto-bits draft: {dplan.summary()}")
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh, parse_mesh_arg
        mesh_cfg = parse_mesh_arg(args.mesh)
        if mesh_cfg.num_devices > jax.device_count():
            raise SystemExit(
                f"--mesh {args.mesh} needs {mesh_cfg.num_devices} devices, "
                f"have {jax.device_count()} (try --fake-devices "
                f"{mesh_cfg.num_devices} on CPU)")
        mesh = make_mesh(mesh_cfg)
    engine = ServingEngine(model, params, max_len=args.max_len,
                           batch_slots=args.slots, spec=spec,
                           plan=plan, draft_plan=draft_plan,
                           decode_block=args.decode_block,
                           donate=not args.no_donate, mesh=mesh,
                           page_size=args.page_size or None,
                           num_pages=args.num_pages,
                           prefix_cache=args.prefix_cache,
                           speculate=args.speculate,
                           draft_k=args.draft_k, draft_bits=args.draft_bits,
                           draft_mode=args.draft_mode,
                           draft_fragment=args.draft_fragment,
                           draft_layer_step=args.draft_layer_step,
                           adaptive_k=not args.no_adaptive_k,
                           health=(HealthConfig(
                               probe_every=args.probe_every,
                               drift_threshold=args.drift_threshold)
                               if args.fault_repair else None),
                           stats_every=args.stats_every,
                           zero_skip=args.zero_skip,
                           zero_skip_keep=args.zero_skip_keep,
                           zero_skip_stats=args.zero_skip_stats,
                           slo=slo)
    if engine.compression_report is not None:
        print(f"forms: {engine.compression_report.summary()} "
              f"(encoding={args.encoding})")
    if wants_faults:
        stuck = (args.fault_stuck or 0.0) / 2
        report = engine.inject_faults(FaultModel(
            sigma=args.fault_sigma or 0.0, p_stuck_on=stuck,
            p_stuck_off=stuck, t=args.fault_drift or 0.0,
            seed=args.fault_seed))
        print(f"faults: {report.summary()}")
    if engine.paged:
        alloc = engine.page_allocator
        print(f"paged cache: {alloc.capacity} pages x {engine.page_size} "
              f"rows (+1 scratch), {engine.cache_bytes()/2**20:.1f} MiB, "
              f"prefix_cache={'on' if engine.prefix_cache else 'off'}")
    elif args.page_size:
        print(f"paged cache: unsupported for family {cfg.family!r} "
              "(O(1) recurrent state) — dense slot cache")
    if engine.speculative:
        detail = ("int grid" if args.draft_mode == "int"
                  else engine.draft_report.summary())
        print(f"speculate: k={args.draft_k}, {args.draft_bits}-bit "
              f"{args.draft_mode} draft, layer_step={args.draft_layer_step} "
              f"({detail})")
    elif args.speculate:
        print(f"speculate: unsupported for family {cfg.family!r} or dense "
              "cache — plain decode")
    if mesh is not None:
        n_sharded = sum(
            1 for s in jax.tree_util.tree_leaves(engine.param_shardings)
            if hasattr(s, "spec")
            and any(e is not None for e in tuple(s.spec)))
        print(f"mesh: {dict(mesh.shape)} over {jax.device_count()} devices, "
              f"{n_sharded} param leaves sharded")
    if lg_cfg is not None:
        from repro.serving.loadgen import generate
        reqs = generate(lg_cfg)
        print(f"loadgen: {lg_cfg.n_requests} requests at "
              f"{lg_cfg.rate:.0f}/s (seed {lg_cfg.seed}, "
              f"batch_frac {lg_cfg.batch_frac}, "
              f"adversarial {lg_cfg.adversarial_len})")
    else:
        rng = np.random.RandomState(0)
        plen = lambda: (args.prompt_len if args.prompt_len
                        else rng.randint(2, 6))
        reqs = [Request(uid=i,
                        prompt=rng.randint(0, cfg.vocab_size, size=plen()),
                        max_new_tokens=args.max_new_tokens,
                        temperature=args.temperature)
                for i in range(args.requests)]
    t0 = time.perf_counter()
    results = engine.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in results)
    for r in results[:4]:
        print(f"req {r.uid}: {r.tokens}")
    pf = np.mean([r.prefill_ms for r in results])
    dm = np.mean([r.decode_ms for r in results])
    print(f"{len(results)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s, forms={args.forms}, "
          f"block={args.decode_block}); "
          f"mean prefill {pf:.1f}ms, mean decode share {dm:.1f}ms")
    stats = engine.stats()
    parts = [f"rounds {stats['rounds']}",
             f"max_concurrent {stats['max_concurrent']}"]
    if "pages" in stats:
        pg = stats["pages"]
        parts.append(f"pages hw {pg['high_water']}/{pg['capacity']} "
                     f"(shared {pg['shared']})")
    if "prefix_hits" in stats:
        parts.append(f"prefix_hits {stats['prefix_hits']}")
    if "speculate" in stats:
        sp = stats["speculate"]
        parts.append(f"acceptance {sp['acceptance']:.2f} "
                     f"tok/round {sp['tokens_per_round']:.2f}")
    if "health" in stats:
        h = stats["health"]
        parts.append(f"probes {h['probes']} repairs {h['repairs']} "
                     f"drift {h['last_drift']:.2e}")
    if "sparsity" in stats:
        ov = stats["sparsity"]["overall"]
        parts.append(f"sparsity elem {ov['elem_sparsity']:.2f} "
                     f"frag {ov['fragment_sparsity']:.2f} "
                     f"({ov['calls']} matmuls)")
    print("stats: " + ", ".join(parts))
    # which programs compiled, per width: a width seen twice recompiled
    compiles = sorted((k[len("runner.compiles."):], v)
                      for k, v in stats["counters"].items()
                      if k.startswith("runner.compiles."))
    if compiles:
        print("compiles: " + ", ".join(f"{k} {v}" for k, v in compiles))
    if "slo" in stats:
        s = stats["slo"]
        print(f"slo: ttft p50 {s['ttft_ms']['p50']:.1f}ms "
              f"p99 {s['ttft_ms']['p99']:.1f}ms, "
              f"itl p50 {s['inter_token_ms']['p50']:.2f}ms "
              f"p99 {s['inter_token_ms']['p99']:.2f}ms, "
              f"preempt {s['preemptions']} (resumed {s['resumes']}), "
              f"miss {s['deadline_misses']}, "
              f"chunks {s['chunked_prefill']['calls']}"
              f"/{s['chunked_prefill']['tokens']}tok")
        for cls, c in s["per_class"].items():
            print(f"slo[{cls}]: {c['completed']} done, "
                  f"ttft p99 {c['ttft_ms']['p99']:.1f}ms, "
                  f"itl p99 {c['inter_token_ms']['p99']:.2f}ms, "
                  f"miss {c['deadline_misses']}, "
                  f"preempt {c['preemptions']}, "
                  f"queue peak {c['queue_peak']}")
    if "health" in stats:
        for ev in stats["health"]["events"]:
            print(f"health[{ev['round']}]: "
                  + ", ".join(f"{k}={v}" for k, v in ev.items()
                              if k != "round"))
    if "sparsity" in stats:
        for tag, s in stats["sparsity"]["layers"].items():
            print(f"sparsity[{tag}]: elem {s['elem_sparsity']:.2f} "
                  f"frag {s['fragment_sparsity']:.2f} calls {s['calls']}")
    if auto is not None and args.temperature == 0.0:
        # greedy parity vs the uniform width that fits the same budget: the
        # mixed plan must never cost more (modeled) than that uniform tree,
        # and when the allocator degenerates to exactly that width the two
        # engines must emit identical tokens (same weights -> same greedy
        # argmax).  A genuinely mixed plan serves different weights, so
        # token agreement is reported, not asserted.
        from repro.forms import autobits as AB
        u = AB.uniform_bits_for_budget(auto.table, args.acc_budget)
        u_seconds = AB.uniform_seconds(auto.table, u)
        assert auto.modeled_seconds <= u_seconds + 1e-12, \
            f"mixed plan modeled slower than uniform {u}b at equal budget"
        uni = ServingEngine(model, params, max_len=args.max_len,
                            batch_slots=args.slots,
                            spec=dataclasses.replace(spec, bits=u),
                            decode_block=args.decode_block,
                            donate=not args.no_donate,
                            page_size=args.page_size or None,
                            num_pages=args.num_pages)
        ures = {r.uid: list(r.tokens) for r in uni.run(
            [Request(uid=r.uid, prompt=np.asarray(r.prompt),
                     max_new_tokens=args.max_new_tokens)
             for r in reqs])}
        got = {r.uid: list(r.tokens) for r in results}
        pairs = [(got[u_], ures[u_]) for u_ in got]
        agree = (sum(sum(a == b for a, b in zip(x, y)) for x, y in pairs)
                 / max(1, sum(len(x) for x, _ in pairs)))
        degenerate = set(auto.bits.values()) == {u}
        if degenerate:
            assert all(x == y for x, y in pairs), \
                "plan degenerated to the uniform width but tokens differ"
        print(f"auto-bits parity: matched-budget uniform {u}b, modeled "
              f"{u_seconds / max(auto.modeled_seconds, 1e-30):.2f}x slower "
              f"than plan, greedy token agreement {agree:.2f}"
              + (" (exact, asserted)" if degenerate else ""))


if __name__ == "__main__":
    main()
