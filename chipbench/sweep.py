"""Sweep the rate of a cell's Poisson mix on the chip, to find its knee.

  python chipbench/sweep.py --workload <cell> --seconds <s> --seed <n> --rates <r> ...

For each rate, one run of the cell as ``run.py`` makes it (the same set-up,
engine, window and check) with the mix's ``rate`` replaced, all in one
process.  One line of JSON per rate on standard output: the median time to
first token of each quarter of the arrivals, the last quarter's over the
first's, whether the run was correct and the cell's end-to-end metrics.
The knee is the highest rate at which that ratio is at most ``KNEE_RATIO``;
a cell below the knee offers about four fifths of it.  The sweep goes up
from the lowest rate and stops after two rates in a row past the knee.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402

KNEE_RATIO = 1.5


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run.load_cell(bench, args.workload)
    if cell["mix"]["arrivals"] != "poisson":
        run.log(f"chipbench sweep: {args.workload} has no Poisson rate")
        return 2
    run.use_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("chipbench sweep: no TPU found")
        return 3
    above = 0
    for rate in sorted(args.rates):
        if above == 2:
            break                 # two rates in a row past the knee
        keep = {}
        at = dict(cell, mix=dict(cell["mix"], rate=rate))
        out = run.run_cell(bench, at, args.seed, args.seconds, False, keep=keep)
        q = keep["run"].quarter_ttft_ms()
        print(json.dumps({
            "workload": args.workload, "rate": rate,
            "requests": len(keep["run"].planned), "quarter_ttft_ms": q,
            "last_over_first": q[-1] / q[0],
            "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
            flush=True)
        above = above + 1 if q[-1] > KNEE_RATIO * q[0] else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
