"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit);
``--json PATH`` additionally dumps every row as a JSON artifact (the per-PR
perf trajectory CI accumulates), and ``--smoke`` runs only bench_fps +
bench_kernels at tiny shapes (the CI smoke job).
Sections:
  tables I/II  -> bench_accuracy       (accuracy + crossbar reduction)
  fig 6        -> bench_fragment_size  (accuracy vs fragment size + sign-rule ablation)
  fig 8        -> bench_eic            (EIC stats on real activations)
  tables III-V -> bench_hw_model       (area/power/throughput model vs published)
  figs 13/14   -> bench_fps            (FPS speedup composition + serving hot path)
  table VI     -> bench_variation      (device-variation robustness)
  kernels      -> bench_kernels        (wall-times, oracle + interpret sanity)
  system       -> bench_train_serve    (train/decode step micro-bench)
  reliability  -> bench_reliability    (fault injection: accuracy/tok-s vs
                                        sigma, plain vs vecom, self-healing)

Cross-PR trajectories (repo root, appended per run): bench_reliability
writes ``BENCH_reliability.json``; ``--smoke`` additionally appends the
``serving.*`` rows of bench_fps to ``BENCH_serving.json``.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import common
from benchmarks.common import header
from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="bench_fps + bench_kernels only, at tiny shapes")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all emitted rows to PATH as JSON")
    args = ap.parse_args()
    use_compile_cache()

    from benchmarks import (bench_accuracy, bench_eic, bench_fps,
                            bench_fragment_size, bench_hw_model,
                            bench_kernels, bench_reliability,
                            bench_train_serve, bench_variation)
    header()
    if args.smoke:
        sections = [
            ("figs13_14", lambda: bench_fps.run(smoke=True)),
            ("kernels", lambda: bench_kernels.run(smoke=True)),
            ("reliability", lambda: bench_reliability.run(smoke=True)),
        ]
    else:
        sections = [
            ("tables_I_II", bench_accuracy.run),
            ("fig6", bench_fragment_size.run),
            ("fig8", bench_eic.run),
            ("tables_III_V", bench_hw_model.run),
            ("figs13_14", bench_fps.run),
            ("tableVI", bench_variation.run),
            ("kernels", bench_kernels.run),
            ("system", bench_train_serve.run),
            ("reliability", bench_reliability.run),
        ]
    failures = []
    for name, fn in sections:
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if args.json:
        common.write_json(args.json)
    if args.smoke:
        # serving perf trajectory across PRs (repo root), from the rows the
        # bench_fps serving sections already emit
        import os
        serving = [r for r in common.rows() if r[0].startswith("serving.")]
        if serving:
            common.append_trajectory(
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "BENCH_serving.json"),
                serving, label="smoke")
    if failures:
        print(f"# FAILED sections: {failures}", flush=True)
        sys.exit(1)
    print("# ALL BENCHMARKS OK", flush=True)


if __name__ == "__main__":
    main()
