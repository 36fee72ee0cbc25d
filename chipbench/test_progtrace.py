"""Checks of the readers of the program's own spans, counters and named
scopes (progtrace.py and the metrics that use it), off the chip.

  JAX_PLATFORMS=cpu python -m pytest -q chipbench/test_progtrace.py

* each reader on a hand-built trace, with the program's spans on a clock of
  their own: an idle gap inside ``runner.dispatch`` counts in
  ``dispatch_idle_share``, one inside ``runner.wait`` does not;
* each reader returns None for a run of a program without the tracer;
* in a harness run the in-program twins read what the recorder reads.
"""
from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import devtrace  # noqa: E402
import progtrace  # noqa: E402
import run as harness  # noqa: E402

NEW = ("kv_cache_share", "dispatch_idle_share", "prefill_useful_share",
       "decode_round_span_ms", "decode_rows_used")
OFFSET = 5.0     # the program's clock reads 5 s more than the profiler's


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=a, duration_ns=b - a)
                            for a, b, n in evs]) for ln, evs in lines])


def _span(name, a, b, i, parent=None, **attrs):
    return {"name": name, "start": a * 1e-9 + OFFSET, "end": b * 1e-9 + OFFSET,
            "id": i, "parent": parent, "attrs": attrs}


def _traced_run():
    """Two runner calls in a slice of 8000 ns: a decode round
    (1000-5000) and a chunked prefill (6000-9000)."""
    host = _plane("/host:CPU", [("main", [
        (1000, 5000, "chipbench.decode/2x4/100/2"),
        (6000, 9000, "chipbench.prefill/2x8")])])
    dev = _plane("/device:TPU:0", [
        ("XLA Modules", [(1800, 4800, "jit__decode_fn(1)"),
                         (7000, 8800, "jit__chunk_fn(2)")]),
        ("XLA Ops", [(1800, 2800, "%fusion.1 = bf16[4,8]{1,0} fusion(...)"),
                     (2800, 4800, "%while.2 = (s32[], f32[4]) while(...)"),
                     (3000, 3500, "%fusion.3 = f32[4]{0} fusion(...)"),
                     (7000, 8000, "%fusion.1 = bf16[2,16]{1,0} fusion(...)"),
                     (8000, 8800, "%custom-call.5 = f32[8]{0} custom-call(...)")])])
    spans = [_span("sched.round", 800, 9200, 1, round=7, decoding=2,
                   prefilling=1, queued=5),
             _span("runner.decode", 900, 5100, 2, 1, rows=2, steps=4),
             _span("runner.prepare", 1100, 1500, 3, 2),
             _span("runner.dispatch", 1500, 2000, 4, 2, program="decode",
                   width=4),
             _span("runner.wait", 2000, 4900, 5, 2),
             _span("runner.prefill", 5900, 9100, 6, 1, rows=2, width=8),
             _span("runner.prepare", 6100, 6400, 7, 6),
             _span("runner.dispatch", 6400, 6900, 8, 6, program="chunk",
                   width=8),
             _span("runner.wait", 6900, 8900, 9, 6),
             _span("request.queue", 0, 900, 10, uid=3),
             _span("request.prefill", 900, 9000, 14, uid=3),
             _span("request.decode", 1000, 3000, 15, uid=4, tokens=4,
                   preemptions=0)]
    hlo = {
        "decode.4": _hlo("jit__decode_fn", [
            ("fusion.1", "bf16[4,8]{1,0}", "fusion", "kv_gather"),
            ("while.2", "(s32[], f32[4]{0})", "while", "layers"),
            ("fusion.3", "f32[4]{0}", "fusion", "attention")]),
        # two chunk widths share a module name: the shapes tell them apart
        "chunk.16": _hlo("jit__chunk_fn", [
            ("fusion.1", "bf16[2,32]{1,0}", "fusion", "attention")]),
        "chunk.8": _hlo("jit__chunk_fn", [
            ("fusion.1", "bf16[2,16]{1,0}", "fusion", "kv_commit"),
            ("custom-call.5", "f32[8]{0}", "custom-call", None)])}
    counters = {"decode.rounds": 4, "decode.rows": 32, "decode.tokens": 30,
                "prefill.calls": 3, "prefill.tokens": 40, "prefill.rows": 64,
                "runner.compiles.decode.4": 1}
    stats = {"counters": counters,
             "trace": {"spans": spans, "dropped": 0, "hlo": hlo}}
    recorder = NS(rounds=[{"t0": 0.0, "t1": 4.0e-6, "steps": 4, "slots": 8,
                           "delivered": 30}])
    return NS(trace=devtrace.reduce_planes([host, dev]), stats=stats,
              stalled_s=0.0, recorder=recorder, t0=OFFSET)


def _hlo(module, ops):
    """A compiled program's HLO text with one computation of ``ops``:
    (name, result type, opcode, scope or None)."""
    lines = [f"HloModule {module}, entry_computation_layout={{()->()}}", "",
             "ENTRY %main () -> f32[4] {"]
    for name, rtype, opcode, scope in ops:
        meta = (f', metadata={{op_name="jit({module[4:]})/{scope}/op"}}'
                if scope else "")
        lines.append(f"  %{name} = {rtype} {opcode}(){meta}")
    return "\n".join(lines + ["}"])


def _read(name, run):
    return harness.load_reader(name)(run)


def test_readers_on_a_hand_built_trace():
    run = _traced_run()
    by = progtrace.scope_seconds(run)
    assert by["programs"] == pytest.approx(4800e-9)
    assert by["kv_gather"] == pytest.approx(1000e-9)
    assert by["kv_commit"] == pytest.approx(1000e-9)      # not chunk.16's
    assert by["attention"] == pytest.approx(500e-9)
    assert by["layers"] == pytest.approx(1500e-9)         # the loop's self time
    assert by[progtrace.NO_SCOPE] == pytest.approx(800e-9)
    assert _read("kv_cache_share", run) == pytest.approx(100 * 2000 / 4800)
    # idle in the slice: 1000-1800, 4800-7000, 8800-9000; under prepare or
    # dispatch: 1100-1800 and 6100-6900 (the gaps in runner.wait, 4800-4900
    # and 8800-8900, do not count)
    assert _read("dispatch_idle_share", run) == pytest.approx(100 * 1500 / 8000)
    idle = progtrace.idle_by_span(run)
    assert idle["in call: runner.wait"] == pytest.approx(300e-9)
    assert idle["in call: runner.prepare"] == pytest.approx(700e-9)
    assert idle["in call: runner.dispatch"] == pytest.approx(800e-9)
    assert idle["in call: runner.decode"] == pytest.approx(200e-9)
    assert idle["in call: runner.prefill"] == pytest.approx(200e-9)
    assert idle["outside: sched.round"] == pytest.approx(800e-9)
    assert idle["outside: runner.decode"] == pytest.approx(100e-9)
    assert idle["outside: runner.prefill"] == pytest.approx(100e-9)
    assert sum(idle.values()) == pytest.approx(3200e-9)
    assert _read("prefill_useful_share", run) == pytest.approx(62.5)
    assert _read("decode_rows_used", run) == pytest.approx(93.75)
    # the one complete runner.decode span, 900-5100
    assert _read("decode_round_span_ms", run) == pytest.approx(4200e-6)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_the_harness_stalls_leave_the_decode_span(kind):
    """The profiler stops inside a later call, whose scheduler span is in
    the ring without children (they opened after the stop): 6200 ns long,
    it holds the stop's stall and is left out.  A second complete round of
    2200 ns counts."""
    run = _traced_run()
    sp = run.stats["trace"]["spans"]
    sp += [_span("runner.decode", 20000, 22200, 12, 1),
           _span("runner.prepare", 20100, 20300, 13, 12),
           _span("runner." + kind, 9500, 15700, 11, 1)]
    assert _read("decode_round_span_ms", run) == pytest.approx(
        (4200 + 2200) / 2 * 1e-6)
    # no complete decode span: nothing to read
    run.stats["trace"]["spans"] = [s for s in sp if s["id"] not in (2, 12)]
    assert _read("decode_round_span_ms", run) is None


def test_readers_find_nothing_without_the_tracer():
    run = _traced_run()
    parent = NS(trace=run.trace, stats={"rounds": 4, "pages": {}},
                stalled_s=0.1)
    untraced = NS(trace=None, stats=run.stats, stalled_s=0.0)
    for name in NEW:
        assert _read(name, parent) is None, name
    for name in ("kv_cache_share", "dispatch_idle_share"):
        assert _read(name, untraced) is None, name
    assert progtrace.idle_by_span(parent) is None


def test_the_new_metrics_are_per_layer_entries():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert name in entries and "workloads" not in entries[name]
        assert os.path.exists(os.path.join(HERE, "metrics", name + ".py"))


def test_in_program_twins_read_what_the_recorder_reads():
    """A fixed window at a tiny size: the scheduler's counters and the
    ``runner.decode`` span read the same rounds as the recorder."""
    sys.path.insert(0, HERE)
    import test_chipbench as tc
    keep = {}
    cell = tc._cell("decode_heavy", sample_tokens=400, limit=0.03,
                    backlog_rate=200.0,
                    output_len={"dist": "uniform", "min": 20, "max": 60})
    # the tracer on from before warm-up, so that its spans cover the window
    tc._run(cell, hook=lambda e: e.tracer.enable(), keep=keep, seconds=1.5)
    run = keep["run"]
    assert _read("decode_rows_used", run) == pytest.approx(
        _read("decode_slot_occupancy", run), abs=1e-9)
    span_ms, outside_ms = (_read("decode_round_span_ms", run),
                           _read("decode_round_ms", run))
    # the span holds the recorder's timing of the same call, and a little
    assert outside_ms <= span_ms < outside_ms * 1.05 + 0.5
    assert 0 < _read("prefill_useful_share", run) <= 100
    # no profiler ran: no device trace, so its readers find nothing
    for name in ("kv_cache_share", "dispatch_idle_share"):
        assert _read(name, run) is None, name


HLO = """HloModule jit__decode_fn, entry_computation_layout={()->()}

%fused_computation.10 (param_0: bf16[8,4], param_1: s32[]) -> bf16[8,4] {
  %param_0 = bf16[8,4]{1,0} parameter(0)
  %param_1 = s32[] parameter(1)
  ROOT %dynamic-update-slice.8 = bf16[8,4]{1,0} dynamic-update-slice(%param_0, %param_0, %param_1)
}

%gather_body (p: (s32[], bf16[8,4])) -> (s32[], bf16[8,4]) {
  %p = (s32[], bf16[8,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %buf = bf16[8,4]{1,0} get-tuple-element(%p), index=1
  %dus_fusion.6 = bf16[8,4]{1,0} fusion(%buf, %i), kind=kLoop, calls=%fused_computation.10
  ROOT %t = (s32[], bf16[8,4]{1,0}) tuple(%i, %dus_fusion.6)
}

%fused_computation.3 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %exp.1 = f32[4]{0} exponential(%param_0.1), metadata={op_name="jit(_decode_fn)/while/body/attention/exp"}
}

ENTRY %main (pool: bf16[8,4], x: f32[4]) -> (bf16[2,4,4], f32[4]) {
  %pool = bf16[8,4]{1,0} parameter(0), metadata={op_name="pool"}
  %x = f32[4]{0} parameter(1)
  %zero = s32[] constant(0)
  %broadcast.2343.clone = bf16[8,4]{1,0} broadcast(%zero), dimensions={}
  %tuple.167 = (s32[], bf16[8,4]{1,0}) tuple(%zero, %broadcast.2343.clone)
  %while.57 = (s32[], bf16[8,4]{1,0}) while(%tuple.167), condition=%gather_body, body=%gather_body, metadata={op_name="jit(_decode_fn)/while/body/closed_call/kv_gather/gather"}
  %gte = bf16[8,4]{1,0} get-tuple-element(%while.57), index=1
  %bitcast.298 = bf16[2,4,4]{2,1,0} bitcast(%gte)
  %copy.132 = bf16[2,4,4]{1,2,0} copy(%bitcast.298)
  %fusion.3 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.3
  %add.1 = f32[4]{0} add(%fusion.3, %x)
  ROOT %out = (bf16[2,4,4]{1,2,0}, f32[4]{0}) tuple(%copy.132, %add.1)
}
"""


def test_op_scopes_name_what_the_compiler_made_without_metadata():
    got = progtrace.op_scopes(HLO)
    assert got["module"] == "jit__decode_fn"
    ops = got["ops"]
    assert ops["while.57"] == ["()", "kv_gather"]
    # the gather loop's body, the buffer that seeds it, its result's copy
    assert ops["dus_fusion.6"] == ["bf16[8,4]", "kv_gather"]
    assert ops["broadcast.2343.clone"][1] == "kv_gather"
    assert ops["copy.132"] == ["bf16[2,4,4]", "kv_gather"]
    # a fusion takes its body's scope; other unnamed work stays unnamed
    assert ops["fusion.3"] == ["f32[4]", "attention"]
    assert ops["add.1"][1] is None and ops["pool"][1] is None


def test_progreport_reads_the_hand_built_run():
    import progreport
    run = _traced_run()
    rep = progreport.report(run)
    assert rep["compiles"] == {"decode.4": 1}
    assert rep["twins"]["decode_round_span_ms"] == pytest.approx(
        [4200e-6, 4e-3 / 1], rel=1e-6)
    assert rep["twins"]["decode_rows_used"] == pytest.approx(
        [93.75, 93.75])
    assert rep["scope_share"]["kv_gather"] == pytest.approx(100 * 1000 / 4800)
    assert sum(rep["scope_share"].values()) == pytest.approx(100)
    assert rep["idle_s"]["in call: runner.dispatch"] == pytest.approx(800e-9)
    assert rep["slice_s"] == pytest.approx(8000e-9)
    assert rep["rounds"] == {"n": 1, "decoding": 2.0, "prefilling": 1.0,
                             "queued": 5.0}
    assert rep["calls"]["decode.4"]["n"] == 1
    assert rep["calls"]["runner.prefill rows"]["mean"] == 2
    assert rep["longest"][0][:3] == ["sched.round", 0.0, 0.008]
    # one request with queue and prefill spans, one with a decode span
    assert rep["requests"]["ttft_ms"]["p50"] == pytest.approx(9000e-6)
    assert rep["requests"]["itl_ms"]["p50"] == pytest.approx(
        1e3 * 2000e-9 / 3)
