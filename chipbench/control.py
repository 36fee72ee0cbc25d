"""The control of a cell's correctness check, on the chip.

  python chipbench/control.py --workload <cell> --seconds <s> --seeds <a> <b> ...

For each seed it makes a short run of the cell as ``run.py`` does (the same
set-up, traffic, engine and window), then puts the control in the program's
place over the same sample of finished requests: the configuration's
family (``chipbench/families/<family>.py``, its ``gaps`` with ``control``)
has its reference, computed a precision step below the configuration's
(8-bit floating point for bfloat16), pick each token, and its float32
reference read the gap.  Those gaps go through the run's own comparison
(``run.judge``), which has to find the control not correct.  One line of
JSON per seed on standard output, with the program's widest gap and
``correct`` and the control's.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run.load_cell(bench, args.workload)
    run.use_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("chipbench control: no TPU found")
        return 3
    for seed in args.seeds:
        keep = {}
        out = run.run_cell(bench, cell, seed, args.seconds, False, keep=keep)
        low = run.reference_gaps(cell, seed, keep["run"].planned,
                                 keep["run"].served, keep["sample"],
                                 control=True)
        check = run.judge(cell, keep["run"], keep["sample"], low)
        for name, c in check.items():
            run.log(f"control check {name}: {c['value']} (limit {c['limit']})")
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "tokens": int(keep["gaps"].size),
            "program_gap": float(keep["gaps"].max()),
            "program_correct": out["correct"],
            "control_gap": check["max_logit_gap"]["value"],
            "control_correct": run.is_correct(check),
            "tokens_per_s": out["metrics"].get("tokens_per_s", {}).get("value")}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
