"""One run of a cell, and the program's own breakdowns of it.

  python3 chipbench/progreport.py --workload <cell> --seed <n> --seconds <s> \
      [--trace 0|1] [--tracer on]

Runs the cell as ``run.py`` does and prints its result line, then one JSON
line read from the program's tracer (``progtrace.py``): device time by named
scope as a share of the runner programs' device time, device idle by the
innermost program span, the in-program metrics beside their recorder twins,
per-request times from the ``request.*`` spans, the scheduler's rounds from
the ``sched.round`` spans, runner calls by program and width, compilations
per program and width, and the longest spans (where a host stall fell).
The tracer records while the profiler runs, so with ``--trace 1`` the spans
are the traced slice's; ``--tracer on`` turns it on before warm-up, so that
they cover the whole window (and what the tracer costs can be measured
against a run without it).
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import progtrace  # noqa: E402
import run as harness  # noqa: E402

TWINS = (("decode_round_span_ms", "decode_round_ms"),
         ("decode_rows_used", "decode_slot_occupancy"))


def _q(values: List[float]) -> Dict[str, float]:
    v = np.asarray(values, float)
    return {"n": len(v), "p50": float(np.percentile(v, 50)),
            "p95": float(np.percentile(v, 95)), "max": float(v.max())}


def requests(sp: List[Dict]) -> Dict[str, Dict]:
    """From the ``request.*`` spans: time to first token (``request.queue``
    start to ``request.prefill`` end) of requests admitted with the tracer
    on, and mean time between tokens (``request.decode`` over its tokens
    after the first) of requests that finished with it on, in ms."""
    per: Dict[int, Dict[str, Dict]] = collections.defaultdict(dict)
    for s in sp:
        if s["name"].startswith("request."):
            per[s["attrs"]["uid"]].setdefault(s["name"], s)
    ttft = [1e3 * (d["request.prefill"]["end"] - d["request.queue"]["start"])
            for d in per.values()
            if "request.queue" in d and "request.prefill" in d]
    itl = [1e3 * (s["end"] - s["start"]) / (s["attrs"]["tokens"] - 1)
           for d in per.values() if (s := d.get("request.decode"))
           and s["attrs"]["tokens"] > 1]
    return {k: _q(v) for k, v in (("ttft_ms", ttft), ("itl_ms", itl)) if v}


def rounds(sp: List[Dict]) -> Optional[Dict[str, float]]:
    """Mean slots decoding and prefilling and requests queued at the start
    of each ``sched.round``."""
    rs = [s["attrs"] for s in sp if s["name"] == "sched.round"]
    if not rs:
        return None
    return {"n": len(rs), **{k: float(np.mean([r[k] for r in rs]))
                             for k in ("decoding", "prefilling", "queued")}}


def calls(sp: List[Dict]) -> Dict[str, Dict[str, float]]:
    """Per ``<program>.<width>`` of the ``runner.dispatch`` spans: calls and
    mean host ms in the dispatch; per ``runner.decode``/``runner.prefill``:
    calls and mean rows."""
    out: Dict[str, List] = collections.defaultdict(list)
    for s in sp:
        a = s["attrs"]
        if s["name"] == "runner.dispatch":
            out[f"{a['program']}.{a['width']}"].append(s["end"] - s["start"])
        elif s["name"] in ("runner.decode", "runner.prefill"):
            out[s["name"] + " rows"].append(a["rows"])
    return {k: {"n": len(v), "mean": float(np.mean(v)) *
                (1 if k.endswith(" rows") else 1e3)}
            for k, v in sorted(out.items())}


def longest(sp: List[Dict], t0: float, k: int = 8) -> List[List]:
    """The ``k`` longest spans of the scheduler and the runner: name, start
    (s into the window), length (ms) and the parent's name."""
    names = {s["id"]: s["name"] for s in sp}
    own = [s for s in sp if s["name"].startswith(("sched.", "runner.",
                                                  "health."))]
    own.sort(key=lambda s: s["end"] - s["start"], reverse=True)
    return [[s["name"], round(s["start"] - t0, 3),
             round(1e3 * (s["end"] - s["start"]), 3), names.get(s["parent"])]
            for s in own[:k]]


def report(run) -> Dict:
    """The program's breakdowns of one run (see the module's docstring)."""
    c = progtrace.counters(run) or {}
    out: Dict = {"compiles": {k[len("runner.compiles."):]: v
                              for k, v in sorted(c.items())
                              if k.startswith("runner.compiles.")},
                 "twins": {a: [harness.load_reader(n)(run) for n in (a, b)]
                           for a, b in TWINS}}
    by = progtrace.scope_seconds(run)
    if by:
        total = by.pop("programs")
        out["scope_share"] = {k: 100.0 * v / total for k, v in
                              sorted(by.items(), key=lambda kv: -kv[1])}
        out["programs_s"] = total
    idle = progtrace.idle_by_span(run)
    if idle:
        out["idle_s"] = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
        out["slice_s"] = (run.trace.hi - run.trace.lo) * 1e-9
    sp = progtrace.spans(run)
    if sp:
        st = run.stats["trace"]
        out.update(spans=len(sp), dropped=st["dropped"],
                   requests=requests(sp), rounds=rounds(sp), calls=calls(sp),
                   longest=longest(sp, getattr(run, "t0", 0.0)))
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--tracer", choices=("follow", "on"), default="follow")
    args = ap.parse_args(argv)

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.load_cell(bench, args.workload)
    harness.use_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.log("chipbench: progreport needs a TPU")
        return 3
    keep: Dict = {}
    hook = (lambda e: e.tracer.enable()) if args.tracer == "on" else None
    out = harness.run_cell(bench, cell, args.seed, args.seconds,
                           bool(args.trace), engine_hook=hook, keep=keep)
    print(json.dumps(out), flush=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "trace": args.trace, "tracer": args.tracer,
                      **report(keep["run"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
