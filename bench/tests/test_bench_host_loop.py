"""The host-loop metrics: the engine's phases, read from the program's
flight recorder on the client's clock, carried onto the trace's clock
through the ``bench.window`` stamps, give a step's own host time and the
device's idle time inside it; they read nothing where the program or the
run holds nothing to read, and move no reading the benchmark had."""

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the import path)

import types

import pytest

from bench_tiny import TINY_MODEL, bench_run, tiny_cell
from benchlib import host_loop
from benchlib.spec import metric_reader
from benchlib.trace import Event, Reduced, load_xplane, reduce
from reference import forward

DEV, HOST = "/device:TPU:0", "/host:CPU"
T0 = 10.0                     # the client's reading at the window's start, s
METRICS = ("host_ms_per_step.closed", "host_ms_per_step.open",
           "host_idle_ms_per_step.closed", "host_idle_ms_per_step.open")


def ev(plane, line, name, start, dur, **stats):
    return Event(plane, line, name, float(start), float(dur), stats)


def device_trace():
    """A window of 0..1000 ns on the trace's clock, the client's own spans
    and one device's operations: busy [100, 250) ∪ [300, 400) ∪ [800, 1000)."""
    return [
        ev(HOST, "python", "bench.window", 0, 1000),
        ev(HOST, "python", "bench.step", 0, 600),
        ev(HOST, "python", "PjitFunction(fused_decode)", 50, 100),
        ev(HOST, "python", "bench.wait", 700, 250),
        ev(DEV, "XLA Modules", "jit_fused_decode(1)", 100, 300),
        ev(DEV, "XLA Ops", "flash_attention.3", 100, 100, long_name="custom-call paged"),
        ev(DEV, "XLA Ops", "fusion.7", 150, 100),
        ev(DEV, "XLA Ops", "copy.2", 300, 100),
        ev(DEV, "XLA Modules", "jit_paged_prefill(2)", 800, 400),
        ev(DEV, "XLA Ops", "fusion.9", 800, 400),
        ev(DEV, "XLA Ops", "fusion.1", -500, 100),
    ]


# (name, start, end) on the trace's clock: two steps wholly inside the
# window, one that crosses its end and one before it
PHASES = [
    ("engine.step", 0, 600), ("engine.admit", 0, 40),
    ("engine.decode.prep", 40, 60), ("engine.decode.dispatch", 60, 100),
    ("engine.decode.wait", 100, 400), ("engine.decode.drain", 400, 550),
    ("engine.bookkeeping", 550, 600),
    ("engine.step", 700, 990), ("engine.admit", 700, 720),
    ("engine.prefill.dispatch", 720, 790), ("engine.prefill.wait", 790, 960),
    ("engine.prefill.drain", 960, 990),
    ("engine.step", 990, 1100), ("engine.decode.wait", 995, 1090),
    ("engine.step", -300, -100), ("engine.decode.wait", -250, -150),
]


def recorded(phases=PHASES, rate=1.0):
    """The phases as the flight recorder holds them: in the order they
    closed, on ``perf_counter_ns``, whose ns last ``rate`` of the trace's."""
    return [(n, T0 * 1e9 + s / rate, T0 * 1e9 + e / rate)
            for n, s, e in sorted(phases, key=lambda p: p[2])]


def context(reduced, rate=1.0):
    return types.SimpleNamespace(trace=reduced, trace_t0=T0,
                                 trace_t1=T0 + 1000e-9 / rate)


@pytest.fixture
def recorder(monkeypatch):
    """Point the readers at a flight recorder of the test's choosing."""
    held = {"spans": recorded()}
    monkeypatch.setattr(host_loop, "program_spans", lambda: held["spans"])
    return held


def test_steps_are_joined_to_the_trace_clock(recorder):
    steps = host_loop.steps(context(reduce(device_trace())))
    assert [(s.start, s.end) for s in steps] == \
        [pytest.approx((0.0, 600.0)), pytest.approx((700.0, 990.0))]
    assert [s.wait_ns for s in steps] == [pytest.approx(300.0), pytest.approx(170.0)]
    idle = host_loop.Idle(reduce(device_trace()))
    # [0, 600) less [100, 250) and [300, 400); [700, 990) less [800, 990)
    assert idle.ns(0, 600) == 350.0 and idle.ns(700, 990) == 100.0


@pytest.mark.parametrize("suffix", ["closed", "open"])
def test_the_host_loop_metrics_by_hand(recorder, suffix):
    ctx = context(reduce(device_trace()))
    # a step less its waits: 600 - 300 and 290 - 170, mean 210 ns
    assert metric_reader(f"host_ms_per_step.{suffix}")(ctx) == pytest.approx(210e-6)
    # idle inside a step: 350 and 100 ns, mean 225 ns
    assert metric_reader(f"host_idle_ms_per_step.{suffix}")(ctx) == pytest.approx(225e-6)


def test_a_clock_of_another_offset_and_a_slightly_other_rate(recorder):
    rate = 1 + 4e-4
    recorder["spans"] = recorded(rate=rate)
    ctx = context(reduce(device_trace()), rate=rate)
    assert metric_reader("host_ms_per_step.closed")(ctx) == pytest.approx(210e-6)
    assert metric_reader("host_idle_ms_per_step.closed")(ctx) == pytest.approx(225e-6)


@pytest.mark.parametrize("case", ["no trace", "no window stamps", "no recorder",
                                  "recorder starts inside the window",
                                  "clocks of other rates", "no step inside"])
def test_nothing_to_read_gives_nothing(recorder, case):
    ctx = context(reduce(device_trace()))
    if case == "no trace":
        ctx.trace = None
    elif case == "no window stamps":
        ctx.trace_t0 = ctx.trace_t1 = None
    elif case == "no recorder":
        recorder["spans"] = None
    elif case == "recorder starts inside the window":
        recorder["spans"] = recorded([p for p in PHASES if p[2] > 0])
    elif case == "clocks of other rates":
        ctx.trace_t1 = T0 + 3e-3             # the client's window is 3 ms longer
    elif case == "no step inside":
        recorder["spans"] = recorded([p for p in PHASES if p[1] < 0])
    for name in METRICS:
        assert metric_reader(name)(ctx) is None


def test_a_program_without_a_recorder_reads_nothing(monkeypatch):
    """The readers run on a program that keeps no flight recorder (an older
    one) and leave their metrics out."""
    import repro.serve.trace as program_trace

    assert isinstance(host_loop.program_spans(), list)
    monkeypatch.delattr(program_trace, "recent_spans")
    assert host_loop.program_spans() is None
    for name in METRICS:
        assert metric_reader(name)(context(reduce(device_trace()))) is None


def existing_readings(r):
    """What the accepted readers and the breakdown read of a trace."""
    ctx = types.SimpleNamespace(
        trace=r, trace_counters={"ticks": 4, "prefill_tokens": 512},
        work=forward.work(TINY_MODEL), nbmax=16,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        decode_contexts=lambda: [100, 200, 300], first_tokens_in_trace=lambda: [],
        ideal_hits=lambda: {})
    names = ["device_idle_pct.closed", "decode_step_ms.closed",
             "prefill_ms_per_ktok.open", "attn_roofline_pct.closed", "mfu.open"]
    return ({n: metric_reader(n)(ctx) for n in names},
            r.window, r.devices, r.busy_ns, r.top_ops(),
            sorted(ns for _, ns in r.gaps))


def test_engine_phases_move_no_accepted_reading():
    """The engine's annotations join the host's main thread of the trace:
    every accepted reading stays, and a gap changes its name only where an
    ``engine.*`` span is the shortest host event over its middle."""
    phases = [ev(HOST, "python", n, s, e - s) for n, s, e in PHASES]
    plain, traced = reduce(device_trace()), reduce(device_trace() + phases)
    assert existing_readings(traced) == existing_readings(plain)
    assert all(v is not None for v in existing_readings(plain)[0].values())
    renamed = {n for n, _ in traced.gaps} - {n for n, _ in plain.gaps}
    assert all(":engine." in n for n in renamed)


def test_a_tiny_engine_under_the_profiler(tmp_path):
    """On real clocks: an engine served under ``jax.profiler``, its window
    stamped as the harness stamps it; the recorder's steps, carried onto
    the trace's clock, are the capture's own ``engine.step`` spans."""
    import time

    import jax

    from benchlib.weights import make_params
    from repro.serve.engine import Engine, Request, SamplingParams

    run = bench_run()
    cell = tiny_cell("closed", 0.5)
    cfg = run.program_config(cell)
    batch, max_len, kw = run.engine_args(cell.settings["engine"], 1)
    eng = Engine(make_params(cfg, 1, TINY_MODEL["vocab_size"]), cfg, batch, max_len, **kw)

    def serve(first):
        for k in range(3):
            eng.submit(Request(rid=first + k, prompt=[first + k + 1] * (20 + 7 * k),
                               sampling=SamplingParams(temperature=0.0, max_new=9)))
        while len(eng.scheduler) or any(s is not None for s in eng.slots):
            eng.step()

    serve(0)                                  # compiles
    jax.profiler.start_trace(str(tmp_path))
    window = jax.profiler.TraceAnnotation("bench.window")
    window.__enter__()
    t0 = time.perf_counter()
    serve(10)
    t1 = time.perf_counter()
    window.__exit__(None, None, None)
    jax.profiler.stop_trace()

    events = load_xplane(str(tmp_path))
    win = next(e for e in events if e.name == "bench.window")
    ctx = types.SimpleNamespace(
        trace=Reduced(window=(win.start, win.end), devices=[], busy_ns=0.0,
                      ops=[], modules=[], gaps=[]),
        trace_t0=t0, trace_t1=t1)
    steps = host_loop.steps(ctx)
    want = sorted((e.start, e.end) for e in events if e.name == "engine.step")
    assert len(steps) == len(want) > 0
    for s, (a, b) in zip(steps, want):
        assert abs(s.start - a) < 1e6 and abs(s.end - b) < 1e6      # 1 ms
    waits = [e for e in events if e.name.endswith(".wait")]
    for s, (a, b) in zip(steps, want):
        got = sum(w.dur for w in waits if w.start >= a and w.end <= b)
        assert s.wait_ns == pytest.approx(got, abs=2e5)
