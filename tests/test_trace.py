"""The serving program's tracer (serving/trace.py): the no-op span while off,
nesting and the bounded ring while on, following a profiler session, the
spans and counters of a served run, compile counts per program and width,
and the named scopes on the device step."""
import collections
import dataclasses
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models.registry import build
from repro.serving import trace as T
from repro.serving.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_reduced("yi-9b"), dtype="float32",
                              num_layers=2, d_model=32, num_heads=2,
                              num_kv_heads=2, head_dim=16, d_ff=64,
                              vocab_size=64)
    m = build(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _reqs(n=3, new=6):
    return [Request(uid=i, prompt=(np.arange(1 + i, 6 + 5 * i) % 64)
                    .astype(np.int32), max_new_tokens=new)
            for i in range(n)]


def _engine(tiny, chunked=True, trace=False, **kw):
    """A paged engine on the fleet scheduler (``chunked`` admission or
    whole prompts), or with ``slo=None`` on the plain scheduler; its
    tracer on from the start when ``trace``."""
    m, params = tiny
    slo = ({"prefill_chunk": 4, "step_token_budget": 8} if chunked
           else {"prefill_chunk": 0, "step_token_budget": 0})
    eng = ServingEngine(m, params, max_len=32, batch_slots=2, page_size=4,
                        **{"slo": slo, **kw})
    if trace:
        eng.tracer.enable()
    return eng


def _children(spans):
    kids = collections.defaultdict(list)
    for s in spans:
        kids[s["parent"]].append(s["name"])
    return kids


# ---------------------------------------------------------------------------
# the tracer itself
# ---------------------------------------------------------------------------

def test_off_span_is_the_shared_noop_and_records_nothing():
    tr = T.Tracer()
    assert not tr.on
    sp = tr.span("runner.decode", rows=3)
    assert sp is T.NO_SPAN and tr.span("sched.round") is sp
    with sp:
        with tr.span("runner.wait"):
            pass
    tr.record("request.queue", 0.0, 1.0, uid=1)
    with tr.timed("runner.prefill") as t:
        pass
    assert t.seconds >= 0.0
    tr.count("decode.rounds")
    tr.count("decode.rows", 8)
    assert not tr.spans and tr.dropped == 0
    st = tr.stats()
    assert "trace" not in st
    assert st["counters"] == {"decode.rounds": 1, "decode.rows": 8}


def test_on_spans_nest_and_the_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(T, "SPAN_RING", 4)
    tr = T.Tracer()
    tr.enable()
    with tr.span("sched.round", round=0) as outer:
        with tr.span("runner.decode", rows=2) as inner:
            with tr.span("runner.dispatch"):
                pass
    spans = tr.stats()["trace"]["spans"]
    by = {s["name"]: s for s in spans}
    assert by["sched.round"]["parent"] is None
    assert by["runner.decode"]["parent"] == outer.id
    assert by["runner.dispatch"]["parent"] == inner.id
    assert by["runner.decode"]["attrs"] == {"rows": 2}
    r = by["sched.round"]
    assert r["start"] <= by["runner.decode"]["start"] <= \
        by["runner.decode"]["end"] <= r["end"]
    for i in range(3):
        tr.record("request.queue", float(i), float(i + 1), uid=i)
    st = tr.stats()["trace"]
    assert len(st["spans"]) == 4 and st["dropped"] == 2
    # the oldest rolled off, the newest stay
    assert [s["name"] for s in st["spans"]][-3:] == ["request.queue"] * 3
    tr.enabled = False
    assert tr.span("x") is T.NO_SPAN


def test_tracer_follows_a_profiler_session(tmp_path):
    tr = T.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tr.on
        with tr.span("sched.round"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert not tr.on
    assert tr.span("sched.round") is T.NO_SPAN
    assert [s["name"] for s in tr.stats()["trace"]["spans"]] == ["sched.round"]


def test_profiler_session_is_found(tmp_path):
    """The tracer reads JAX's private profiler state: if that moves, this
    fails rather than the tracer silently never following a profile."""
    assert T._PROFILE_STATE is not None and not T.profiler_running()
    with jax.profiler.trace(str(tmp_path)):
        assert T.profiler_running()
    assert not T.profiler_running()


# ---------------------------------------------------------------------------
# a served run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunked", [True, False])
def test_fleet_run_spans_and_counters(tiny, chunked):
    reqs = _reqs()
    want = {r.uid: r.tokens for r in _engine(tiny, chunked, trace=False)
            .run(reqs)}
    eng = _engine(tiny, chunked, trace=True)
    got = {r.uid: r.tokens for r in eng.run(reqs)}
    assert got == want                       # the tracer changes no token
    st = eng.stats()
    spans, c = st["trace"]["spans"], st["counters"]
    assert st["trace"]["dropped"] == 0
    names = collections.Counter(s["name"] for s in spans)
    assert names["sched.round"] == st["rounds"]
    assert names["runner.decode"] == c["decode.rounds"] > 0
    assert names["runner.prefill"] == c["prefill.calls"] > 0
    kids = _children(spans)
    for s in spans:
        if s["name"] in ("runner.decode", "runner.prefill"):
            assert kids[s["id"]] == ["runner.prepare", "runner.dispatch",
                                     "runner.wait"], s
    served = sum(len(t) for t in got.values())
    assert c["decode.tokens"] == served - len(reqs)   # first tokens: prefill
    assert c["prefill.tokens"] == sum(len(r.prompt) for r in reqs)
    assert c["decode.rows"] == c["decode.rounds"] * 2 * eng.decode_block
    # decode rounds read K/V in place; only chunk calls gather
    assert c["decode.kv_in_place_steps"] == (c["decode.rounds"]
                                             * eng.decode_block)
    if chunked:
        assert st["slo"]["chunked_prefill"] == {
            "calls": c["prefill.calls"], "tokens": c["prefill.tokens"]}
        assert c["decode.kv_gathered_steps"] == c["prefill.calls"]
    else:
        assert "decode.kv_gathered_steps" not in c
    # request spans: queue <= prefill <= decode for every request
    per = collections.defaultdict(dict)
    for s in spans:
        if s["name"].startswith("request."):
            per[s["attrs"]["uid"]][s["name"]] = s
    assert set(per) == {r.uid for r in reqs}
    for uid, d in per.items():
        q, p, dec = (d["request.queue"], d["request.prefill"],
                     d["request.decode"])
        assert q["start"] <= q["end"] <= p["start"] <= p["end"] \
            <= dec["start"] <= dec["end"]
        assert dec["attrs"]["tokens"] == len(got[uid])


def test_plain_scheduler_spans_and_counters(tiny):
    reqs = _reqs()
    eng = _engine(tiny, trace=True, slo=None)
    results = eng.run(reqs)
    got = {r.uid: r.tokens for r in results}
    st = eng.stats()
    spans, c = st["trace"]["spans"], st["counters"]
    names = collections.Counter(s["name"] for s in spans)
    assert names["sched.round"] == st["rounds"] == c["decode.rounds"]
    assert names["runner.prefill"] == c["prefill.calls"] == len(reqs)
    assert names["request.decode"] == len(reqs)
    assert c["decode.tokens"] == sum(len(t) for t in got.values()) - len(reqs)
    assert c["prefill.tokens"] == sum(len(r.prompt) for r in reqs)
    # the requests' times are the runner spans' own, split over the round
    for name, ms in (("runner.decode", [r.decode_ms for r in results]),
                     ("runner.prefill", [r.prefill_ms for r in results])):
        span_ms = sum(s["end"] - s["start"] for s in spans
                      if s["name"] == name) * 1e3
        assert sum(ms) == pytest.approx(span_ms, rel=1e-9)


def test_health_probe_span(tiny):
    from repro.reliability.health import HealthConfig
    eng = _engine(tiny, trace=True, slo=None, forms=True,
                  health=HealthConfig(probe_every=2))
    eng.run(_reqs(n=2))
    names = [s["name"] for s in eng.stats()["trace"]["spans"]]
    assert "health.probe" in names


def test_compiles_counted_per_program_and_width(tiny):
    eng = _engine(tiny)
    runner, slots = eng.runner, eng.slots
    zi, zf = np.zeros(slots, np.int32), np.zeros(slots, np.float32)
    tables = np.zeros_like(eng.scheduler.block_tables)

    def chunk(w):
        runner.prefill_chunk(np.zeros((slots, w), np.int32), zi, tables, zi,
                             zf)
        return dict(eng.stats()["counters"])

    c1 = chunk(8)
    assert c1.get("runner.compiles.chunk.8") == 1
    c2 = chunk(8)                             # a repeated width: none
    assert c2.get("runner.compiles.chunk.8") == 1
    c3 = chunk(16)                            # a new width: one
    assert c3.get("runner.compiles.chunk.16") == 1
    assert c3.get("runner.compiles.chunk.8") == 1
    runner.decode_round(zi, zi, zf, block_tables=tables, active=[False] * 2)
    runner.decode_round(zi, zi, zf, block_tables=tables, active=[False] * 2)
    assert eng.stats()["counters"]["runner.compiles.decode.4"] == 1


def test_kv_step_counters_split_in_place_from_gathered(tiny):
    """A plain paged decode round reads K/V straight from the pool: it adds
    ``decode_block`` to ``decode.kv_in_place_steps``; a chunked-prefill
    call reads gathered views: it adds 1 to ``decode.kv_gathered_steps``."""
    eng = _engine(tiny)
    runner, slots, blk = eng.runner, eng.slots, eng.decode_block
    zi, zf = np.zeros(slots, np.int32), np.zeros(slots, np.float32)
    tables = np.zeros_like(eng.scheduler.block_tables)
    counters = lambda: dict(eng.stats()["counters"])
    runner.decode_round(zi, zi, zf, block_tables=tables, active=[False] * 2)
    c = counters()
    assert c["decode.kv_in_place_steps"] == blk
    assert "decode.kv_gathered_steps" not in c
    runner.prefill_chunk(np.zeros((slots, 8), np.int32), zi, tables, zi, zf)
    c = counters()
    assert c["decode.kv_gathered_steps"] == 1
    assert c["decode.kv_in_place_steps"] == blk
    runner.decode_round(zi, zi, zf, block_tables=tables, active=[False] * 2)
    assert counters()["decode.kv_in_place_steps"] == 2 * blk


def test_mesh_engine_counts_gathered_steps_only():
    """A pool sharded over a mesh is read through gathered views on every
    step; the counters of a served run on a data=2 mesh say so.  Runs in a
    child process with two host devices (the pytest process keeps one)."""
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "_sharded_child.py"),
         "kv_counters", "2"],
        capture_output=True, text=True, timeout=560, env=env, cwd=root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "kv_counters ok" in proc.stdout


# ---------------------------------------------------------------------------
# named scopes on the device step
# ---------------------------------------------------------------------------

def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]+)"', compiled_text))


def test_device_programs_carry_named_scopes(tiny):
    eng = _engine(tiny)
    r, slots = eng.runner, eng.slots
    tables = np.zeros_like(eng.scheduler.block_tables)
    zi = np.zeros(slots, np.int32)
    zf = np.zeros(slots, np.float32)
    key = jax.random.PRNGKey(0)
    dec = r._decode.lower(r.params, r.cache, zi, zi, tables, zf, key)
    chunk = r._get_chunk(8).lower(r.params, r.cache,
                                  np.zeros((slots, 8), np.int32), zi, tables,
                                  zi, zf, key)
    for lowered, scopes in ((dec, ("kv_commit", "attention", "mlp",
                                   "lm_head", "sampling")),
                            (chunk, ("kv_gather", "kv_commit", "attention",
                                     "mlp", "lm_head", "sampling"))):
        names = "\n".join(_op_names(lowered.compile().as_text()))
        for scope in scopes:
            assert f"/{scope}/" in names, scope
    # single-token decode reads the pages in place: nothing is gathered
    assert "/kv_gather/" not in "\n".join(
        _op_names(dec.compile().as_text()))


@pytest.mark.parametrize("trace", [False, True])
def test_requests_that_share_a_uid_are_served(tiny, trace):
    """Nothing asks uids to be unique: two requests with one uid are both
    served, by the plain scheduler and the fleet's, tracer on or off."""
    reqs = _reqs(n=2)
    reqs[1] = dataclasses.replace(reqs[1], uid=reqs[0].uid)
    want = sorted(len(r.tokens) for r in _engine(tiny).run(_reqs(n=2)))
    for eng in (_engine(tiny, trace=trace, slo=None),
                _engine(tiny, trace=trace)):
        got = eng.run(reqs)
        assert [r.uid for r in got] == [0, 0]
        assert sorted(len(r.tokens) for r in got) == want


def test_compiled_programs_kept_only_while_traced(tiny):
    """An untraced engine keeps no lowering and reports no programs; a
    traced one reports the compiled HLO text of each program it called,
    whose instructions carry the named scopes."""
    off = _engine(tiny)
    off.run(_reqs(n=2))
    assert not off.runner._lowered and "trace" not in off.stats()
    eng = _engine(tiny, trace=True)
    eng.run(_reqs(n=2))
    hlo = eng.stats()["trace"]["hlo"]
    assert "decode.4" in hlo and any(k.startswith("chunk.") for k in hlo)
    assert hlo["decode.4"].startswith("HloModule jit__decode_fn")
    assert "/kv_commit/" in hlo["decode.4"]
    assert "/kv_gather/" not in hlo["decode.4"]
    assert all("/kv_gather/" in text for k, text in hlo.items()
               if k.startswith("chunk."))
