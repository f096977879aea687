"""A whole run of the harness on the CPU at a tiny size, past its look for
a chip: the served tokens pass the reference comparison, the reference's
lower-precision control fails it, and so does a run whose sampled tokens
are altered where they are produced.

The tiny limit, 0.03, lies between the readings it was set from (CPU, 3 s
windows, seeds 1, 2, 3 and 2**33 + 5, both mixes): the program's widest
gap read at most 0.0078 and the control's at least 0.076."""

import pytest

from bench_tiny import bench_run, tiny_cell

LIMIT = 0.03


def quiet(*a, **k):
    pass


@pytest.mark.parametrize("kind", ["closed", "open"])
def test_served_tokens_pass_and_the_control_fails(kind):
    cell = tiny_cell(kind, LIMIT)
    r = bench_run().run_cell(cell, 1, 3.0, False, control=True, log=quiet)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["window"]["compiles"] == 0          # set-up compiled every program
    assert r["checks"]["tokens_compared"]["value"] >= 48
    assert r["correct"] is True, r["checks"]
    assert r["control_gap"] > LIMIT
    assert list(r)[-1] == "control_gap" and list(r)[-2] == "checks"
    # the closed loop reports throughput and set-up; the open loop its tails too
    assert set(r["metrics"]) == {m.name for m in cell.end_to_end}
    assert {"output_tok_s", "setup_s"} <= set(r["metrics"])
    assert ("ttft_p95_ms" in r["metrics"]) == (kind == "open")


def test_a_token_altered_where_it_is_produced_fails(monkeypatch):
    import repro.serve.engine as engine_mod

    sample = engine_mod.sample_tokens

    def altered(logits, *args):
        return (sample(logits, *args) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine_mod, "sample_tokens", altered)
    r = bench_run().run_cell(tiny_cell("closed", LIMIT), 1, 3.0, False, log=quiet)
    assert r["checks"]["tokens_compared"]["value"] > 0
    assert r["correct"] is False
    assert r["checks"]["max_logit_gap"]["value"] > LIMIT


def test_traced_run_reports_layer_metrics():
    """On the CPU the trace holds no device plane: the trace readers find
    nothing and are left out; the counters' readers still report."""
    r = bench_run().run_cell(tiny_cell("open", LIMIT), 2, 3.0, True, log=quiet)
    assert r["correct"] is True
    assert {"compile_s", "queue_wait_p95_ms", "prefix_hit_pct"} <= set(r["metrics"])
    assert "device_idle_pct.open" not in r["metrics"]
    assert 0 < r["metrics"]["prefix_hit_pct"]["value"] < 100
