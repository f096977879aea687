"""Per-request tracing and latency attribution (DESIGN.md §13).

The load-bearing pins:

* **bitwise invisibility** — tracing is host-only (timestamps are taken
  only where the engine already synchronises), so a traced engine's token
  streams are bitwise those of an untraced one across ring/paged ×
  bf16/int8 with fused windows and chunked prefill on.
* **attribution by construction** — each request's phase segments exactly
  partition [t_submit, t_finish], so ``explain()`` shares sum to 100% for
  every finished request of a mixed workload (chunked prefill, preemption,
  deadline expiry, degradation).
* **export consistency** — the Perfetto JSON and the jsonl feed describe
  the same per-request spans one-to-one.
* **crash continuity** — timelines carried through snapshot/restore stay
  contiguous: spans open at the crash close with a recovery marker and a
  ``recovery`` segment bridges crash → resume.
* **engine phases on the profiler's clock** — ``Engine.step``'s
  ``engine.*`` spans reach a ``jax.profiler`` capture with or without an
  enabled tracer, nest under one ``engine.step`` each, and name the
  device-blocking calls ``*.wait`` and nothing else.
"""

import glob
import itertools
import json
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist.fault_tolerance import FailureInjector, run_serve_with_restarts
from repro.kernels import autotune
from repro.models import registry
from repro.serve import (Engine, JsonlSink, NullSink, Request, SamplingParams,
                         Tracer, format_explain)
from repro.serve.trace import CATEGORIES, recent_spans

CFG = get_config("smollm_135m").reduced()
PARAMS = registry.init_model(jax.random.PRNGKey(0), CFG)

MAX_LEN = 32
EOS = 11

# the acceptance matrix: ring/paged × bf16/int8, fused windows + chunked
# piggyback prefill on — the paths where the tracer hooks are densest
CONFIGS = {
    "ring-bf16": dict(decode_ticks=4, prefill_chunk=2),
    "ring-int8": dict(decode_ticks=4, prefill_chunk=2, kv_quant=True),
    "paged-bf16": dict(kv_layout="paged", block_size=8, decode_ticks=4,
                       prefill_chunk=8),
    "paged-int8": dict(kv_layout="paged", block_size=8, decode_ticks=4,
                       prefill_chunk=8, kv_quant=True),
}
_ENGINES = {}
_RID = itertools.count()


def _engine(name, traced):
    key = (name, traced)
    if key not in _ENGINES:
        _ENGINES[key] = Engine(PARAMS, CFG, batch=2, max_len=MAX_LEN,
                               trace="mem" if traced else None,
                               **CONFIGS[name])
    eng = _ENGINES[key]
    eng.finished = []
    eng.reset_stats()
    return eng


def _request(rid, key=None, temperature=0.0, max_new=5, **kw):
    key = rid if key is None else key
    prompt = [(7 * key + i) % (CFG.vocab_size - 1) + 1
              for i in range(4 + key % 3)]
    return Request(rid=rid, prompt=prompt, priority=key % 2,
                   sampling=SamplingParams(temperature=temperature, seed=key,
                                           max_new=max_new, eos_id=EOS,
                                           counter_offset=100 * key), **kw)


def _assert_contiguous(report):
    segs = report["segments"]
    assert segs, "finished request with no segments"
    for a, b in zip(segs, segs[1:]):
        assert b["t0"] == pytest.approx(a["t1"]), "timeline gap"
    assert sum(report["shares"].values()) == pytest.approx(1.0, abs=0.01)


# --------------------------------------------------------------- unit layer


def test_from_spec_parsing(tmp_path):
    assert Tracer.from_spec(None).enabled is False
    t = Tracer.from_spec("mem")
    assert t.enabled and isinstance(t.sink, NullSink) and t._retain
    assert Tracer.from_spec(t) is t                    # tracer passes through

    combo = Tracer.from_spec(f"perfetto:{tmp_path}/t.json,"
                             f"jsonl:{tmp_path}/t.jsonl")
    assert combo.perfetto_path == f"{tmp_path}/t.json"
    assert isinstance(combo.sink, JsonlSink) and combo._retain

    feed_only = Tracer.from_spec(str(tmp_path / "feed.jsonl"))
    assert isinstance(feed_only.sink, JsonlSink)
    assert not feed_only._retain                       # pure stream: no memory

    class Sink:
        def write(self, records):
            pass

    sink = Sink()
    assert Tracer.from_spec(sink).sink is sink
    with pytest.raises(ValueError):
        Tracer.from_spec("csv:/tmp/x")
    with pytest.raises(TypeError):
        Tracer.from_spec(42)


def test_phase_segments_partition_wall_exactly():
    """The attribution invariant, driven by hand: every transition closes at
    t and reopens at t, degradation rotates the open span, and explain()
    decomposes the wall exactly."""
    tr = Tracer()
    tr.begin(0, 10.0, priority=1)
    tr.phase(0, "prefill", 10.5, slot=0)
    tr.phase(0, "decode", 11.0, slot=0)
    tr.set_degraded(True, 11.25)
    tr.set_degraded(False, 11.75)
    tr.finish(0, 12.0, "length")
    rep = tr.explain(0)
    assert rep["done"] and rep["finish_reason"] == "length"
    assert rep["wall_s"] == pytest.approx(2.0)
    assert rep["seconds"]["queue"] == pytest.approx(0.5)
    assert rep["seconds"]["prefill"] == pytest.approx(0.5)
    assert rep["seconds"]["decode"] == pytest.approx(0.5)
    assert rep["seconds"]["degraded"] == pytest.approx(0.5)
    _assert_contiguous(rep)
    assert rep["segments"][0]["t0"] == 10.0
    assert rep["segments"][-1]["t1"] == 12.0

    line = format_explain(rep)
    assert line.startswith("req 0:") and "[length]" in line
    assert "degraded=25.0%" in line


def test_tracer_snapshot_restore_bridges_open_spans():
    tr = Tracer()
    tr.begin(7, 1.0)
    tr.phase(7, "decode", 1.2, slot=0)
    snap = json.loads(json.dumps(tr.snapshot(1.4)))    # prove JSON-able

    tr2 = Tracer()
    tr2.restore(snap, t=1.9)
    tr2.finish(7, 2.0, "length")
    rep = tr2.explain(7)
    phases = [(s["phase"], round(s["t1"] - s["t0"], 6))
              for s in rep["segments"]]
    assert phases == [("queued", 0.2), ("decode", 0.2),
                      ("recovery", 0.5), ("decode", 0.1)]
    _assert_contiguous(rep)
    # the pre-crash decode span carries the recovery mark on the feed
    marked = [r for r in tr2.records()
              if r.get("kind") == "span" and r.get("recovery") == 1]
    assert len(marked) == 1 and marked[0]["name"] == "decode"


def test_explain_live_request_attributes_up_to_now():
    tr = Tracer()
    tr.begin(3, 5.0)
    rep = tr.explain(3, now=7.0)
    assert not rep["done"]
    assert rep["wall_s"] == pytest.approx(2.0)
    assert rep["shares"]["queue"] == pytest.approx(1.0)
    assert "live" in format_explain(rep)


# ------------------------------------------------------- engine integration


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_tracing_is_bitwise_invisible(name):
    """Acceptance pin: tracing on vs off changes no token stream, and every
    finished request explains to shares summing to 100%."""
    def serve(traced):
        eng = _engine(name, traced)
        rid0 = next(_RID)
        for _ in range(3):
            next(_RID)
        for k in range(4):
            eng.submit(_request(rid0 + k, key=k, temperature=0.8))
        eng.run(300)
        return eng, {r.rid - rid0: (list(r.out), r.finish_reason)
                     for r in eng.finished}

    _, want = serve(False)
    eng, got = serve(True)
    assert got == want
    for r in eng.finished:
        rep = eng.explain(r.rid)
        assert rep["done"] and rep["finish_reason"] == r.finish_reason
        assert rep["seconds"]["decode"] > 0.0 or r.out == []
        _assert_contiguous(rep)


def test_explain_requires_an_enabled_tracer():
    eng = _engine("ring-bf16", traced=False)
    with pytest.raises(RuntimeError, match="trace"):
        eng.explain(0)


def test_explain_shares_sum_on_mixed_workload():
    """The ISSUE acceptance workload: chunked prefill + pool pressure
    (preemptions) + a deadline expiry, all on one traced engine — every
    finished request's shares sum to 100% ± 1%."""
    eng = Engine(PARAMS, CFG, batch=2, max_len=MAX_LEN, kv_layout="paged",
                 block_size=4, num_blocks=5, prefix_cache=False,
                 decode_ticks=2, prefill_chunk=4, scheduler="priority",
                 trace="mem")
    reqs = [_request(r, key=r, max_new=8) for r in range(3)]
    reqs.append(_request(3, key=3, deadline_s=0.0))    # expires in queue
    for r in reqs:
        eng.submit(r)
    done = eng.run(400)
    assert len(done) == 4
    assert eng.stats["preemptions"] >= 1               # pressure was real
    reasons = {r.rid: r.finish_reason for r in done}
    assert reasons[3] == "deadline"

    saw_stall = False
    for r in done:
        rep = eng.explain(r.rid)
        assert rep["done"] and rep["finish_reason"] == reasons[r.rid]
        _assert_contiguous(rep)
        saw_stall = saw_stall or rep["seconds"]["preempt_stall"] > 0.0
    assert saw_stall, "a preempted request must show preempt_stall time"
    # the expired request never left the queue: 100% queue share
    rep = eng.explain(3)
    assert rep["dominant"] == "queue"
    assert rep["shares"]["queue"] == pytest.approx(1.0, abs=0.01)


def test_queue_and_pool_provenance_events_reach_the_feed():
    eng = Engine(PARAMS, CFG, batch=2, max_len=MAX_LEN, kv_layout="paged",
                 block_size=4, num_blocks=5, prefix_cache=False,
                 trace="mem")
    for r in range(3):
        eng.submit(_request(r, key=r, max_new=8))
    eng.run(300)
    events = {r["name"] for r in eng.trace.records()
              if r.get("kind") == "event"}
    assert {"submit", "finish", "queue_enter"} <= events
    # pool pressure (num_blocks=5) forces preempts → requeue provenance
    assert "queue_requeue" in events
    waves = [r for r in eng.trace.records()
             if r.get("kind") == "span" and r.get("cat") == "wave"]
    assert any(r["name"] == "prefill_wave" and r["rid"] is None
               for r in waves)
    assert any(r["name"] == "decode_window" and r["rid"] is None
               for r in waves)
    # engine wave spans are mirrored by per-request detail spans
    assert any(r["rid"] is not None and r["name"].startswith("decode[")
               for r in waves)


def test_deadlock_breaker_emits_reprefill_event():
    """The last-resort block reclamation (DESIGN.md §6 deadlock breaker)
    shows up on the feed: a pool too small for two growing requests forces
    a queued preempted holder to give its blocks back and re-prefill."""
    eng = Engine(PARAMS, CFG, batch=2, max_len=16, kv_layout="paged",
                 block_size=4, num_blocks=3, prefix_cache=False,
                 trace="mem")
    for r in range(2):
        eng.submit(Request(rid=r, prompt=[1 + r, 2, 3], max_new=10))
    done = eng.run(300)
    assert len(done) == 2
    events = [r for r in eng.trace.records() if r.get("kind") == "event"]
    reprefills = [e for e in events if e["name"] == "reprefill"]
    assert reprefills and all("pos" in e and "rid" in e for e in reprefills)
    for r in done:
        _assert_contiguous(eng.explain(r.rid))


# ----------------------------------------------------------------- exports


def test_perfetto_export_matches_jsonl_feed(tmp_path):
    """Acceptance pin: the Perfetto export and the jsonl feed agree
    one-to-one on per-request spans (same (rid, name, duration) multiset)."""
    pf_path = tmp_path / "trace.json"
    feed_path = tmp_path / "trace.jsonl"
    eng = Engine(PARAMS, CFG, batch=2, max_len=MAX_LEN, decode_ticks=2,
                 trace=f"perfetto:{pf_path},jsonl:{feed_path}")
    for r in range(3):
        eng.submit(_request(r, key=r))
    eng.run(300)
    eng.trace.close()

    feed = [json.loads(l) for l in feed_path.read_text().splitlines()]
    feed_spans = sorted(
        (r["rid"], r["name"], round(1e6 * (r["t1"] - r["t0"])))
        for r in feed if r.get("kind") == "span" and r.get("rid") is not None)
    pf = json.loads(pf_path.read_text())
    pf_spans = sorted(
        (e["tid"], e["name"], round(e["dur"]))
        for e in pf["traceEvents"] if e["ph"] == "X" and e["pid"] == 1)
    assert pf_spans == feed_spans and feed_spans
    # request tracks are named, engine track exists
    names = [e for e in pf["traceEvents"] if e["ph"] == "M"]
    assert any(e["args"].get("name") == "engine" for e in names)
    assert any(e["args"].get("name") == "req 0" for e in names)
    # engine-track spans (waves) land on pid 0
    assert any(e["ph"] == "X" and e["pid"] == 0
               for e in pf["traceEvents"])
    # counters sampled every tick
    assert any(e["ph"] == "C" for e in pf["traceEvents"])


def test_trace_sink_crash_is_isolated():
    """The SinkBuffer contract holds for the trace feed too: a raising sink
    degrades to NullSink without disturbing serving."""

    class BoomSink:
        def write(self, records):
            raise IOError("disk full")

        def close(self):
            pass

    eng = Engine(PARAMS, CFG, batch=2, max_len=MAX_LEN,
                 trace=Tracer(sink=BoomSink(), flush_every=1))
    for r in range(2):
        eng.submit(_request(r, key=r))
    done = eng.run(200)
    assert len(done) == 2
    assert all(r.finish_reason in ("length", "eos") for r in done)
    assert eng.trace.sink_errors == 1
    assert isinstance(eng.trace.sink, NullSink)


# --------------------------------------------------------- crash continuity


def test_trace_continuity_across_injected_crash(tmp_path):
    """A mid-window crash + restart keeps every timeline contiguous: spans
    open at the crash close with a recovery marker, a recovery segment
    bridges to resume, shares still sum to 100%, and streams stay bitwise
    those of an uninterrupted (untraced) run."""
    kw = dict(batch=2, max_len=MAX_LEN, kv_layout="paged", block_size=8,
              decode_ticks=2)
    ref = Engine(PARAMS, CFG, **kw)
    for r in range(4):
        ref.submit(_request(r, key=r, temperature=0.8))
    ref.run(300)
    want = {r.rid: (list(r.out), r.finish_reason) for r in ref.finished}

    snap_path = str(tmp_path / "snap.json")
    injector = FailureInjector(crash_at={2: "mid_window"})

    def make_engine():
        return Engine(PARAMS, CFG, injector=injector,
                      snapshot_path=snap_path, trace="mem", **kw)

    def submit(engine):
        for r in range(4):
            engine.submit(_request(r, key=r, temperature=0.8))

    eng = run_serve_with_restarts(make_engine, submit,
                                  snapshot_path=snap_path, ticks=300)
    assert injector.fired == {(2, "mid_window")}
    assert {r.rid: (list(r.out), r.finish_reason)
            for r in eng.finished} == want

    bridged = 0
    for r in eng.finished:
        rep = eng.explain(r.rid)
        assert rep["done"]
        _assert_contiguous(rep)
        if any(s["phase"] == "recovery" for s in rep["segments"]):
            bridged += 1
    assert bridged > 0, "spans open at the crash must get a recovery bridge"
    recs = eng.trace.records()
    assert any(r.get("kind") == "event" and r.get("name") == "recovery"
               for r in recs)
    # pre-crash history was re-injected for the post-restore export
    assert any(r.get("carried") == 1 for r in recs)


# --------------------------------------------------------- autotune events


def test_autotune_observer_feeds_cache_events():
    tr = Tracer()
    autotune.clear_cache()
    shape = (2, 64, 3, 3, 64)
    block = autotune.best_block("decode_attention", shape, "int8", 8,
                                "flash", "unit-test")
    key = autotune.cache_key("decode_attention", shape, "int8", 8, "flash",
                             "unit-test")
    autotune._CACHE[key] = block                       # a sweep ran
    autotune.best_block("decode_attention", shape, "int8", 8, "flash",
                        "unit-test")
    del autotune._CACHE[key]
    events = [r for r in tr.records() if r.get("kind") == "event"]
    assert [e["name"] for e in events] == ["autotune_model_pick",
                                           "autotune_cache_hit"]
    assert events[0]["key"] == key
    assert tuple(events[1]["block"]) == tuple(block)


def test_dropped_tracer_unregisters_from_autotune():
    import weakref

    tr = Tracer()
    ref = weakref.ref(tr)
    assert tr in autotune._OBSERVERS
    del tr
    assert ref() is None and all(o is not None
                                 for o in autotune._OBSERVERS)


# ------------------------------------------------- engine phase spans


WAITS = {"engine.prefill.wait", "engine.decode.wait", "engine.spec.verify.wait"}
PHASE_CONFIGS = {
    "paged": dict(kv_layout="paged", block_size=8, decode_ticks=2,
                  prefill_chunk=8, kv_quant=True),
    "ring": dict(decode_ticks=2),
    "spec": dict(kv_layout="paged", block_size=8, spec_decode=True,
                 draft_k=3),
}


class _BlockSpy:
    """``numpy`` for the engine module that marks every device-to-host copy
    with a ``test.block`` annotation on the profiler's clock."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def asarray(x, *a, **k):
        if isinstance(x, jax.Array):
            with jax.profiler.TraceAnnotation("test.block"):
                return np.asarray(x, *a, **k)
        return np.asarray(x, *a, **k)


def _host_events(trace_dir):
    """(name, start, end) of every engine span and blocking-copy mark in
    the newest xplane under ``trace_dir``."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine.", "test.block")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def _serve_steps(eng):
    for k in range(4):
        eng.submit(_request(k, key=k, temperature=0.8, max_new=6))
    steps = 0
    while len(eng.scheduler) or any(s is not None for s in eng.slots):
        eng.step()
        steps += 1
    return steps, {r.rid: (list(r.out), r.finish_reason) for r in eng.finished}


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("name", sorted(PHASE_CONFIGS))
def test_engine_phases_land_on_the_profiler_clock(name, tmp_path, monkeypatch):
    """Under ``jax.profiler`` an untraced engine writes one ``engine.step``
    per step, every other ``engine.*`` span nests inside one, the ``.wait``
    spans are exactly its blocking device-to-host copies, and the token
    streams are bitwise those of a run without the profiler."""
    import repro.serve.engine as engine_mod

    kw = dict(batch=2, max_len=MAX_LEN, **PHASE_CONFIGS[name])
    _, want = _serve_steps(Engine(PARAMS, CFG, **kw))

    eng = Engine(PARAMS, CFG, **kw)
    monkeypatch.setattr(engine_mod, "np", _BlockSpy())
    jax.profiler.start_trace(str(tmp_path))
    try:
        steps, got = _serve_steps(eng)
    finally:
        jax.profiler.stop_trace()
    assert got == want

    events = _host_events(str(tmp_path))
    whole = [e for e in events if e[0] == "engine.step"]
    parts = [e for e in events if e[0].startswith("engine.") and e not in whole]
    blocks = [e for e in events if e[0] == "test.block"]
    assert len(whole) == steps and parts and blocks
    assert all(sum(_inside(p, w) for w in whole) == 1 for p in parts)
    waits = [p for p in parts if p[0].endswith(".wait")]
    assert {w[0] for w in waits} <= WAITS
    assert all(sum(_inside(b, w) for w in waits) == 1 for b in blocks)
    assert all(sum(_inside(b, w) for b in blocks) == 1 for w in waits)
    names = {p[0] for p in parts}
    assert {"engine.admit", "engine.bookkeeping", "engine.prefill.wait",
            "engine.decode.prep", "engine.decode.drain"} <= names
    assert ("engine.spec.verify.wait" in names) == (name == "spec")


def test_enabled_tracer_records_engine_spans():
    """An enabled tracer also writes the phases as engine-track records,
    and the wave records and ``stats`` timings read the same dispatch and
    wait spans: each phase is timed in one place."""
    eng = Engine(PARAMS, CFG, batch=2, max_len=MAX_LEN, decode_ticks=2,
                 trace="mem")
    steps, _ = _serve_steps(eng)
    recs = eng.trace.records()
    spans = [r for r in recs if r.get("cat") == "engine"]
    assert sum(r["name"] == "engine.step" for r in spans) == steps
    assert all(r["rid"] is None and r["t1"] >= r["t0"] for r in spans)
    assert all("tick" in r and "active" in r for r in spans
               if r["name"] == "engine.step")
    waves = {name: [r for r in recs if r.get("cat") == "wave"
                    and r["name"] == name and r["rid"] is None]
             for name in ("prefill_wave", "decode_window")}
    for wave, stat, phase in (("prefill_wave", "prefill_s", "prefill"),
                              ("decode_window", "decode_s", "decode")):
        assert len(waves[wave]) == eng.stats[stat.replace("_s", "_calls")]
        assert sum(r["t1"] - r["t0"] for r in waves[wave]) == \
            pytest.approx(eng.stats[stat])
        dispatch = [r for r in spans if r["name"] == f"engine.{phase}.dispatch"]
        wait = [r for r in spans if r["name"] == f"engine.{phase}.wait"]
        assert [(r["t0"], r["t1"]) for r in waves[wave]] == \
            [(d["t0"], w["t1"]) for d, w in zip(dispatch, wait)]
    perfetto = eng.trace.perfetto()["traceEvents"]
    assert any(e["ph"] == "X" and e["pid"] == 0 and e["cat"] == "engine"
               for e in perfetto)


def test_flight_recorder_keeps_the_phases_of_an_untraced_engine():
    """Every phase also goes to the process's flight recorder on
    ``perf_counter_ns``, tracer off: one ``engine.step`` per step, each
    other phase inside one, in the order they closed."""
    start = time.perf_counter_ns()
    steps, _ = _serve_steps(Engine(PARAMS, CFG, batch=2, max_len=MAX_LEN,
                                   decode_ticks=2))
    spans = [s for s in recent_spans() if s[1] >= start]
    whole = [s for s in spans if s[0] == "engine.step"]
    assert len(whole) == steps
    assert all(s[0].startswith("engine.") and s[1] <= s[2] for s in spans)
    assert all(sum(w[1] <= p[1] and p[2] <= w[2] for w in whole) == 1
               for p in spans if p not in whole)
    assert [s[2] for s in spans] == sorted(s[2] for s in spans)
