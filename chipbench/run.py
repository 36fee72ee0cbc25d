"""Chip benchmark of FORMS serving: one cell, one run.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its files are
found by name: ``chipbench/cells/<cell>.json`` (the cell's configuration,
traffic mix and correctness limit), ``chipbench/configs/<config>.json``
(model sizes, FORMS format, page size, family),
``chipbench/families/<family>.py`` (the family's weights, plain reference
and work counts), ``chipbench/traffic/<mix>.json`` (arrivals, lengths,
slots) and ``chipbench/metrics/<metric>.py`` (one reader per metric).

A run has the family make the weights from the seed, builds a
``ServingEngine`` on the fleet scheduler, warms up every program shape the
mix can use, then serves the mix's requests through ``ServingEngine.run``
and times the calls the scheduler makes into the runner; a fixed window
stops serving when it closes; a ``drain`` window serves every request to
its end.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiler trace of a slice of the window.  After
the window the program is freed and the family's plain float32 reference
checks a sample of the finished requests, drawn across the batch's slots.
The last line of standard output is the result; the run exits non-zero
without printing one when JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".chipbench_cache")
CELLS, CONFIGS, FAMILIES = (os.path.join(HERE, d)
                            for d in ("cells", "configs", "families"))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import timeline  # noqa: E402
import traffic  # noqa: E402
# the system under test
from repro.configs import get_config  # noqa: E402
from repro.forms import FormsSpec  # noqa: E402
from repro.models.registry import build  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402

# the traced slice of a --trace 1 window starts the mix's ``trace_at`` share
# of the way into the window's expected length and lasts TRACE_SECONDS
TRACE_SECONDS = 3.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """The ``read(run)`` function of ``chipbench/metrics/<name>.py``."""
    return load_module(os.path.join(HERE, "metrics", name + ".py"),
                       "metric_" + name).read


@functools.lru_cache(maxsize=None)
def _family_at(path: str):
    return load_module(path, "family_" + os.path.basename(path)[:-3])


def load_family(name: str):
    """The module ``chipbench/families/<name>.py``, loaded once: a
    configuration family's ``engine_params``, its plain reference's
    ``gaps`` and the work counts metric readers take as ``run.work`` (each
    family's docstring sets them out)."""
    return _family_at(os.path.join(FAMILIES, name + ".py"))


def cell_metrics(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a cell reports: its end-to-end ones, or with a trace its
    per-layer ones; an entry with ``workloads`` only in the cells named."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_cell(bench: Dict, name: str) -> Dict:
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    cell = load_json(CELLS, name + ".json")
    if (cell["config"], cell["traffic"]) != (entry["config"], entry["traffic"]):
        raise SystemExit(f"chipbench: cells/{name}.json names "
                         f"{cell['config']}/{cell['traffic']}, BENCHMARK.json "
                         f"{entry['config']}/{entry['traffic']}")
    mix = traffic.load_mix(cell["traffic"])
    if mix["arrivals"] == "backlog":
        # how deep a backlog keeps a configuration busy through a fixed
        # window depends on its speed: the cell sizes it, the mix does not
        if "backlog_rate" in mix or "backlog_rate" not in cell:
            raise SystemExit(f"chipbench: a backlog's backlog_rate belongs in "
                             f"cells/{name}.json, not in the mix")
        mix["backlog_rate"] = cell["backlog_rate"]
    config_file = load_json(CONFIGS, cell["config"] + ".json")
    return dict(cell, name=name, chips=entry["chips"], config_file=config_file,
                family=load_family(config_file["family"]), mix=mix)


def cache_entries() -> int:
    path = os.path.join(CACHE, "jax")
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached, so that only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", os.path.join(CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class GcClock:
    """Python's garbage collections while armed: (start, seconds, generation)."""

    def __init__(self):
        self.armed = False
        self.pauses: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self.armed:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info["generation"]))


class CompileCounter:
    """XLA compilations (or loads from the persistent cache) while armed."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------

def build_engine(cell: Dict, params: Any, seed: int) -> ServingEngine:
    cf, mix = cell["config_file"], cell["mix"]
    cfg = dataclasses.replace(get_config(cf["arch"]), **cf["model"])
    return ServingEngine(
        build(cfg), params, max_len=mix["max_len"], batch_slots=mix["slots"],
        spec=FormsSpec(**cf["forms"]), page_size=cf["serving"]["page_size"],
        decode_block=cf["serving"]["decode_block"], rng_seed=seed % (1 << 31),
        slo=dict(prefill_chunk=mix["prefill_chunk"],
                 step_token_budget=mix["step_token_budget"], preempt=False))


def warm_up(engine, cell: Dict, planned: List[traffic.Planned]) -> int:
    """Compile every program the window can call, on scratch pages: the
    decode round, and each chunked-prefill width (or with whole-prompt
    admission each prefill bucket of this run's prompts).  Returns the
    number of programs run."""
    runner, mix = engine.runner, cell["mix"]
    slots = mix["slots"]
    tables = np.zeros_like(engine.scheduler.block_tables)
    zi, zf = np.zeros(slots, np.int32), np.zeros(slots, np.float32)
    runner.decode_round(zi, zi, zf, block_tables=tables, active=[False] * slots)
    n = 1
    if mix["prefill_chunk"]:
        top = runner.chunk_width(mix["prefill_chunk"])
        widths = {runner.chunk_width(w) for w in range(1, top + 1)}
        for w in sorted(widths):
            runner.prefill_chunk(np.zeros((slots, w), np.int32), zi, tables,
                                 zi, zf)
            n += 1
    else:
        ps = runner.page_size
        for b in sorted({runner.bucket_for(len(r.prompt)) for r in planned}):
            runner.prefill_slot(0, np.zeros(b, np.int32),
                                pages=np.zeros(-(-b // ps), np.int32))
            n += 1
    return n


def to_requests(planned: List[traffic.Planned]) -> List[Request]:
    return [Request(uid=p.uid, prompt=p.prompt, max_new_tokens=p.max_new_tokens,
                    arrival_s=p.arrival_s) for p in planned]


def device_info() -> Dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

SLOT_GROUPS = 4   # the sample holds a request from each quarter of the slots


def pick_sample(lengths: Dict[int, int], slot_of: Dict[int, Optional[int]],
                slots: int, seed: int, tokens: int) -> List[int]:
    """Requests to check, drawn from the seed: the one with the most served
    tokens, one from each quarter of the batch's slots (a fault in part of
    the batch is then seen whatever the seed), then others in random order
    until ``tokens`` are covered.  ``lengths`` maps each finished request to
    its served tokens, ``slot_of`` to the slot that served it."""
    uids = sorted(lengths)
    if not uids:
        return []
    rng = np.random.default_rng([seed, 7])
    out = [max(uids, key=lambda u: (lengths[u], -u))]
    groups = min(SLOT_GROUPS, slots)
    for g in range(groups):
        lo, hi = g * slots // groups, (g + 1) * slots // groups
        inside = [u for u in uids if slot_of.get(u) is not None
                  and lo <= slot_of[u] < hi]
        if inside and not set(inside) & set(out):
            out.append(int(rng.choice(inside)))
    total = sum(lengths[u] for u in out)
    for u in rng.permutation(uids):
        if total >= tokens:
            break
        if int(u) not in out:
            out.append(int(u))
            total += lengths[int(u)]
    return out


def reference_gaps(cell: Dict, seed: int, planned: Dict[int, Any],
                   served: Dict[int, List[int]], sample: List[int],
                   control: bool = False) -> np.ndarray:
    """Per checked token, the reference's best logit minus its logit of the
    served token (``control``: of the token the control puts first), over
    the sampled requests: the family's ``gaps``."""
    if not sample:
        return np.zeros(0, np.float32)
    cf, mix = cell["config_file"], cell["mix"]
    return cell["family"].gaps(
        cf["model"], cf["forms"], seed, [planned[u].prompt for u in sample],
        [served[u] for u in sample], mix["max_len"], mix["output_len"]["max"],
        control=control)


def judge(cell: Dict, run: timeline.Run, sample: List[int], gaps: np.ndarray
          ) -> Dict[str, Dict]:
    """Every number the run's ``correct`` compares, each with its limit:
    the requests checked finished in full, the timeline read from the
    runner calls agrees with what the program returned (when it returned),
    and the sampled tokens lie within the limit of the float32 reference's
    best."""
    failed = run.failed_uids()
    ok = {u: t for u, t in run.served.items() if u not in failed}
    check = {
        "finished_requests": {"value": len(run.served), "limit": 1},
        "failed_requests": {"value": len(failed), "limit": 0},
    }
    if run.returned:
        check["timeline_mismatch"] = {"value": run.timeline_mismatches(),
                                      "limit": 0}
    check["max_logit_gap"] = {
        "value": float(gaps.max()) if gaps.size else float("inf"),
        "limit": cell["check"]["max_logit_gap"]}
    check["checked_tokens"] = {
        "value": int(gaps.size),
        "limit": min(cell["check"]["sample_tokens"],
                     sum(len(t) for t in ok.values()))}
    check["checked_slot_groups"] = {
        "value": len({SLOT_GROUPS * run.tracks[u].slot // cell["mix"]["slots"]
                      for u in sample if run.tracks[u].slot is not None}),
        "limit": len({SLOT_GROUPS * run.tracks[u].slot // cell["mix"]["slots"]
                      for u in ok if run.tracks[u].slot is not None})}
    return check


def is_correct(check: Dict[str, Dict]) -> bool:
    """Counts of failures and gaps at most their limit; counts of what was
    checked at least theirs."""
    at_least = ("finished_requests", "checked_tokens", "checked_slot_groups")
    return all(c["value"] >= c["limit"] if name in at_least
               else c["value"] <= c["limit"] for name, c in check.items())


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run_cell(bench: Dict, cell: Dict, seed: int, seconds: float, trace: bool,
             engine_hook=None, peak: Optional[Dict] = None,
             keep: Optional[Dict] = None) -> Dict:
    """Serve one cell's traffic and return the result line's object.

    ``engine_hook(engine)``, when given, is applied to the built engine
    before warm-up, and ``peak`` stands in for the device's row of
    ``peaks.json`` (the chipbench tests use both off the chip).  ``keep``,
    when given, receives the run, the checked sample and its gaps
    (``control.py`` reads them)."""
    import jax

    import devtrace

    # a device missing from the peak table is an error, before any work
    peak = peak or load_json(HERE, "peaks.json")[jax.devices()[0].device_kind]
    counter = CompileCounter()
    gcc = GcClock()
    entries = cache_entries()
    mix, cf = cell["mix"], cell["config_file"]
    mc = cf["model"]
    planned = traffic.generate(mix, seed, seconds, mc["vocab_size"])
    by_uid = {p.uid: p for p in planned}

    params = cell["family"].engine_params(mc, cf["forms"], seed)
    engine = build_engine(cell, params, seed)
    del params
    if engine_hook is not None:
        engine_hook(engine)
    n_warm = warm_up(engine, cell, planned)
    rec = timeline.Recorder(engine.runner, planned)
    if trace:
        expected = max(seconds, max(p.arrival_s for p in planned))
        rec.trace = devtrace.Slice(os.path.join(CACHE, "trace", cell["name"]),
                                  start=mix["trace_at"] * expected,
                                  seconds=TRACE_SECONDS)
    # the set-up's objects leave the collector's view, so that a collection
    # in the window walks only what the window made
    gc.collect()
    gc.freeze()
    counter.armed = gcc.armed = True
    t0 = time.perf_counter()
    setup_s = t0 - PROCESS_START
    rec.start(t0, close_at=t0 + seconds if mix["window"] == "fixed" else None)
    try:
        results = engine.run(to_requests(planned))
    except timeline.WindowClosed:
        results = None
    rec.stop()
    counter.armed = gcc.armed = False
    gc.unfreeze()
    stats = engine.stats()
    mem = peak_bytes()
    returned = results is not None
    if returned:
        served = {r.uid: [int(t) for t in r.tokens] for r in results}
    else:
        served = {u: list(t.tokens) for u, t in rec.tracks.items()
                  if t.done_t is not None}
    del engine, results
    gc.collect()

    run = timeline.Run(cell=cell, seed=seed, seconds=seconds, planned=by_uid,
                       served=served, returned=returned, recorder=rec,
                       stats=stats, setup_s=setup_s, t0=t0, peak=peak,
                       trace=rec.trace.reduce() if trace else None)
    log(f"chipbench: {cell['name']} seed {seed}: {len(planned)} requests; "
        f"{mix['window']} window of {run.window_s:.3f} s with "
        f"{run.window_tokens} output tokens ({run.output_tokens} in all, "
        f"last completion at {max(t.done_t or 0 for t in rec.tracks.values()) - t0:.3f} s); "
        f"set-up {setup_s:.2f} s ({n_warm} programs warmed); "
        f"{counter.count} compilations after set-up "
        f"({counter.seconds:.3f} s); decode rounds {len(rec.rounds)} "
        f"({sum(r['t1'] - r['t0'] for r in rec.rounds):.3f} s), prefill "
        f"calls {len(rec.prefills)} "
        f"({sum(p['t1'] - p['t0'] for p in rec.prefills):.3f} s)")
    if rec.rounds:
        d = np.array([r["t1"] - r["t0"] for r in rec.rounds]) * 1e3
        p50 = np.median(d)
        slow = np.argsort(d)[::-1][:3]
        log(f"chipbench: decode round ms: p10 {np.percentile(d, 10):.2f}, "
            f"p50 {p50:.2f}, p90 {np.percentile(d, 90):.2f}, max {d.max():.2f}; "
            f"{int((d > 1.25 * p50).sum())} rounds over 1.25 x p50, "
            f"{(d - p50).clip(0).sum() / 1e3:.3f} s above p50 in all; longest "
            f"at {', '.join(f'{rec.rounds[i]['t0'] - t0:.2f} s' for i in slow)}")
    if gcc.pauses:
        t, longest, g = max(gcc.pauses, key=lambda p: p[1])
        log(f"chipbench: python gc in the window: {len(gcc.pauses)} "
            f"collections ({sum(p[2] == 2 for p in gcc.pauses)} of generation "
            f"2), {sum(p[1] for p in gcc.pauses):.3f} s in all, longest "
            f"{longest:.3f} s (generation {g}) at {t - t0:.2f} s")
    if mix["window"] == "fixed":
        log(f"chipbench: requests finished {len(served)}, not yet started "
            f"when the window closed {run.backlog_left()} (0 means the "
            f"backlog ran out inside it); serving "
            f"{'drained' if returned else 'stopped at the close'}")
    if mix["arrivals"] == "poisson":
        lag = run.generator_lag_ms()
        log(f"chipbench: open-loop lag (due time to the start of the next "
            f"call into the runner), ms: p50 {np.percentile(lag, 50):.3f}, "
            f"p99 {np.percentile(lag, 99):.3f}, max {lag.max():.3f}")
        log(f"chipbench: median time to first token by quarter of arrivals, "
            f"ms: {', '.join(f'{x:.1f}' for x in run.quarter_ttft_ms())}")
    log(f"chipbench: peak_bytes_in_use after the window {mem}; compilation "
        f"cache entries {entries} at start, {cache_entries()} after the window")

    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # correctness: the requests that finished were served in full, and a
    # sample of them agrees with the float32 reference
    failed = run.failed_uids()
    ok = [u for u in served if u not in failed]
    sample = pick_sample({u: len(served[u]) for u in ok},
                         {u: rec.tracks[u].slot for u in ok}, mix["slots"],
                         seed, cell["check"]["sample_tokens"])
    gaps = reference_gaps(cell, seed, by_uid, served, sample)
    if keep is not None:
        keep.update(run=run, sample=sample, gaps=gaps)
    check = judge(cell, run, sample, gaps)
    correct = is_correct(check)
    out = {"correct": bool(correct), "attempted": len(served),
           "failed": len(failed), "metrics": metrics,
           "device": dict(device_info(), memory_peak_bytes=mem)}
    if trace:
        out["device"].update(busy_s=run.trace.busy_s,
                             window_s=run.trace.window_s)
        out["breakdown"] = run.trace.breakdown()
    out["check"] = check
    for name, c in check.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = load_cell(bench, args.workload)
    use_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"chipbench: {args.workload} needs {cell['chips']} TPU chip(s); "
            f"JAX finds {len(devices)} {devices[0].platform} device(s)")
        return 3
    out = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
