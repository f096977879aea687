"""The trace reduction on a small synthetic trace: busy time is the union
of a device's operations inside the client's window, idle gaps are named by
what the host's main thread was doing, and time adds up by name."""

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the import path)

import pytest

from benchlib.trace import Event, reduce

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur, **stats):
    return Event(plane, line, name, float(start), float(dur), stats)


def synthetic():
    return [
        # the traced window: 0..1000 ns on the host's main thread
        ev(HOST, "python", "bench.window", 0, 1000),
        ev(HOST, "python", "bench.step", 0, 600),
        ev(HOST, "python", "PjitFunction(fused_decode)", 50, 100),
        ev(HOST, "python", "bench.wait", 700, 250),
        # one program and its ops; two ops overlap and count once
        ev(DEV, "XLA Modules", "jit_fused_decode(1)", 100, 300),
        ev(DEV, "XLA Ops", "flash_attention.3", 100, 100, long_name="custom-call paged"),
        ev(DEV, "XLA Ops", "fusion.7", 150, 100),
        ev(DEV, "XLA Ops", "copy.2", 300, 100),
        # a second program whose last op runs past the window
        ev(DEV, "XLA Modules", "jit_paged_prefill(2)", 800, 400),
        ev(DEV, "XLA Ops", "fusion.9", 800, 400),
        # outside the window: ignored
        ev(DEV, "XLA Ops", "fusion.1", -500, 100),
    ]


def test_busy_is_the_union_inside_the_window():
    r = reduce(synthetic())
    assert r.window == (0.0, 1000.0) and r.devices == [DEV]
    # [100, 250) ∪ [300, 400) ∪ [800, 1000) = 150 + 100 + 200
    assert r.busy_ns == 450.0
    assert r.window_s == pytest.approx(1e-6) and r.busy_s == pytest.approx(450e-9)


def test_idle_gaps_named_by_the_host():
    r = reduce(synthetic())
    gaps = dict((n, round(s * 1e9)) for n, s in r.top_gaps())
    # [0,100) under bench.step at 50: innermost PjitFunction covers 50
    # [250,300) and [400,800): middles 275 (bench.step) and 600 (bench.step
    # ends at 600: still covered), [400,800) is the longest
    assert r.gaps[0][1] == 400.0
    assert sum(ns for _, ns in r.gaps) == 1000.0 - 450.0
    assert "bench.step:PjitFunction(fused_decode)" in gaps
    assert all(name.startswith(("bench.", "host")) for name, _ in r.gaps)


def test_time_by_operation_and_program():
    r = reduce(synthetic())
    assert r.op_seconds(r"flash_attention") == pytest.approx(100e-9)
    assert r.op_seconds(r"paged") == pytest.approx(100e-9)      # matched in a stat
    assert r.module_seconds(r"fused_decode") == pytest.approx(300e-9)
    assert r.module_seconds(r"paged_prefill") == pytest.approx(400e-9)
    top = dict(r.top_ops())
    assert top["fusion"] == pytest.approx(500e-9)    # fusion.7 + fusion.9, by stem


def test_nothing_to_read_gives_nothing():
    assert reduce([e for e in synthetic() if e.name != "bench.window"]) is None
    assert reduce([e for e in synthetic() if e.plane == HOST]) is None
