#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its model,
architecture module (reference, work counts, width checks), traffic mix,
engine settings and per-layer metric readers are found by name under
``bench/`` (``benchlib/spec.py``).  One process: it checks that JAX sees the
chips the cell asks for (and exits non-zero, printing no result, when it
does not), makes the weights on the device from ``--seed``, builds
``repro.serve.Engine``, compiles every program the window can call (set-up,
``setup_s``), then offers the cell's traffic from an in-process client for
``--seconds`` and checks what was served against the plain float32
reference.  ``--trace 0`` reports the cell's end-to-end metrics; ``--trace
1`` runs the same window with a profiler trace of a sub-window and reports
its per-layer metrics, the device's busy and window seconds and a
breakdown.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

from benchlib.spec import SpecError, load_cell  # noqa: E402

WIDTH_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
              "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
              "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
              "tie_word_embeddings": "tie_embeddings", "rms_norm_eps": "norm_eps",
              "rope_theta": "rope_theta"}


class NoChip(RuntimeError):
    pass


def require_chips(chips: int):
    """The devices JAX found, if they are at least ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     "this benchmark runs on the chip and has no fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPUs, JAX found {len(devs)}")
    from repro.kernels import dispatch

    if dispatch.resolve_backend().name != "pallas-tpu":
        raise NoChip("the kernel dispatcher would not run the compiled Pallas "
                     "kernels (is REPRO_KERNEL_BACKEND set?)")
    return devs[:chips]


def program_config(cell):
    """The program's ModelConfig, checked against the configuration file:
    the dense widths above and the architecture module's ``WIDTH_KEYS``
    (an attribute, or a method of no arguments, of ModelConfig)."""
    from repro.configs import get_config

    cfg = get_config(cell.config["program_config"])
    if cell.config.get("program_reduced"):
        cfg = cfg.reduced()           # the tests' tiny stand-in; no cell uses it
    model = cell.config["model"]
    for key, attr in dict(WIDTH_KEYS, **getattr(cell.arch, "WIDTH_KEYS", {})).items():
        runs = getattr(cfg, attr)
        runs = runs() if callable(runs) else runs
        if key in model and runs != model[key]:
            raise SpecError(f"{cell.config['name']}: the program runs {attr}="
                            f"{runs!r}, the file states {key}={model[key]!r}")
    return cfg


# The engine settings every cell gets unless its ``engine`` entry says
# otherwise; any other key of that entry is passed to ``Engine`` as it is.
ENGINE_DEFAULTS = {"kv_quant": True, "kv_layout": "paged", "prefix_cache": True,
                   "decode_ticks": 4, "prefill_chunk": 512}


def engine_args(entry: dict, chips: int):
    """``(batch, max_len, kwargs)`` of ``Engine`` from a cell's ``engine``
    entry: ``slots`` and ``max_len``; ``pool_tokens`` (the paged pool, in
    tokens of ``block_size``); ``mesh`` as ``[data, model]``, whose product
    is the cell's chips (a cell on more than one chip has to give it);
    ``policy`` as the fields of ``QuantPolicy``; every other key, and the
    defaults above, as ``Engine``'s keyword of that name."""
    kw = dict(ENGINE_DEFAULTS, **entry)
    batch, max_len = int(kw.pop("slots")), int(kw.pop("max_len"))
    pool = kw.pop("pool_tokens", None)
    if pool is not None:
        if "block_size" not in kw or pool % kw["block_size"]:
            raise SpecError(f"pool_tokens {pool} is not a whole number of blocks "
                            f"of block_size {kw.get('block_size')}")
        kw["num_blocks"] = pool // kw["block_size"]
    mesh = kw.pop("mesh", None)
    if mesh is None and chips > 1:
        raise SpecError(f"the cell asks for {chips} chips but gives no engine mesh")
    if mesh is not None:
        data, model = (int(x) for x in mesh)
        if data * model != chips:
            raise SpecError(f"engine mesh {mesh} does not cover the cell's {chips} chips")
        from repro.launch.mesh import make_serve_mesh

        kw["mesh"] = make_serve_mesh(data, model)
    if kw.get("policy") is not None:
        from repro.numerics.policy import QuantPolicy

        kw["policy"] = QuantPolicy(**kw["policy"])
    return batch, max_len, kw


def use_compile_cache():
    """JAX's persistent compilation cache at the checkout's fixed directory
    (or ``$JAX_COMPILATION_CACHE_DIR``), every program kept, so that only a
    cell's first run in a checkout compiles.  Entry points call it; the
    tests do not."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class TracedWindow:
    """Between engine steps: start the profiler ``start_s`` into the window
    and stop it ``length_s`` later, snapshotting the engine's counters at
    both ends; the span ``bench.window`` marks it on the trace's clock."""

    def __init__(self, client, start_s, length_s, counters):
        self.client, self.counters = client, counters
        self.start_s, self.length_s = start_s, length_s
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.state = "before"
        self.t0 = self.t1 = None
        self.c0 = self.c1 = None

    def __call__(self, now):
        import jax

        t0 = self.client.t0
        if self.state == "before" and now >= t0 + self.start_s:
            jax.profiler.start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.c0, self.t0 = self.counters(), self.client.clock()
            self.state = "on"
        elif self.state == "on" and (now >= t0 + self.start_s + self.length_s
                                     or now >= self.client.t_end):
            self.c1, self.t1 = self.counters(), self.client.clock()
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def deltas(self):
        return {k: self.c1[k] - self.c0[k] for k in self.c0}


def run_cell(cell, seed: int, seconds: float, trace: bool, devices=None,
             log=print, load=None, control=False):
    """One run of ``cell``; returns the result line's object.  ``load``
    replaces the cell's load level and ``control`` also reads the
    reference's lower-precision control on the same sample: the knee sweep
    (``sweep.py``) and the limit's set-up (``calibrate.py``) use them, a
    benchmark run never does."""
    import jax

    from benchlib import correct as corr
    from benchlib.client import Client
    from benchlib.compile_clock import CompileClock
    from benchlib.e2e import attempted_failed, end_to_end
    from benchlib.layer import Ctx
    from benchlib.peaks import peaks_for
    from benchlib.spec import metric_reader
    from benchlib.traffic import Traffic
    from benchlib.warmup import warm
    from benchlib.weights import make_params
    from repro.serve.engine import Engine, Request, SamplingParams

    clock = CompileClock()
    devices = devices or jax.devices()[:cell.chips]
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    st, mix, model = cell.settings, cell.mix, cell.config["model"]
    eng_cfg = st["engine"]
    cfg = program_config(cell)

    marks = [("start", PROCESS_START), ("jax", time.perf_counter())]
    params = make_params(cfg, seed, model["vocab_size"],
                         getattr(cell.arch, "weight_kind", None))
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    batch, max_len, kwargs = engine_args(eng_cfg, len(devices))
    eng = Engine(params, cfg, batch, max_len, **kwargs)
    marks.append(("engine", time.perf_counter()))
    traffic = Traffic(mix, load or st["load"], model["vocab_size"], seed)
    n_warm = warm(eng, Request, SamplingParams, traffic.min_prompt())
    marks.append(("warm-up", time.perf_counter()))
    compile_s, compiles, hits = clock.seconds, clock.count, clock.hits
    log(f"setup: {n_warm} warm-up requests; {compiles} programs, {hits} from "
        f"the persistent cache, {compile_s:.3f} s; " + ", ".join(
            f"{b[0]} {b[1] - a[1]:.3f} s" for a, b in zip(marks, marks[1:])),
        file=sys.stderr)

    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: contextlib.nullcontext()))
    client = Client(eng, traffic, request_cls=Request, sampling_cls=SamplingParams,
                    annotate=annotate)

    def counters():
        s = eng.stats
        return {"prefill_tokens": s["prefill_tokens"], "prefix_hit_tokens":
                s["prefix_hit_tokens"], "decode_calls": s["decode_calls"],
                "prefill_calls": s["prefill_calls"], "ticks": eng.tick,
                "preemptions": s["preemptions"]}

    hooks, tw = [], None
    if trace:
        tw = TracedWindow(client, st["trace"]["start_s"],
                          st["trace"]["length_s"], counters)
        hooks.append(tw)
    setup_s = time.perf_counter() - PROCESS_START
    c_start = counters()
    records = client.run(seconds, hooks)
    c_end = counters()
    window_compiles = clock.count - compiles
    for rec in records:                     # requests still running at the close
        if not rec.done:
            rec.out = list(rec.req.out)
    e2e = end_to_end(records, client.t0, client.t_end)
    attempted, failed = attempted_failed(records, client.t_end)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    log(f"window: {attempted} requests due, {e2e['_tokens']} tokens, "
        f"{window_compiles} compiles inside the window, "
        f"{c_end['preemptions'] - c_start['preemptions']} preemptions", file=sys.stderr)

    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": {"platform": dev.platform,
                                       "kind": dev.device_kind,
                                       "count": len(devices),
                                       "memory_peak_bytes": peak}}
    # how the window went, for a reader of the record (not a metric)
    result["window"] = {
        "tokens": e2e["_tokens"], "unanswered": sum(
            1 for r in records if r.due < client.t_end
            and not (r.token_t and r.token_t[0] <= client.t_end)),
        "queued_at_close": len(eng.scheduler),
        "preemptions": c_end["preemptions"] - c_start["preemptions"],
        "compiles": window_compiles, "compile_s": compile_s,
        "compiled_in_setup": compiles - hits}
    reduced = None
    if trace:
        from benchlib import trace as tr

        if tw.state == "on":
            tw(float("inf"))
        if tw.state == "done":
            reduced = tr.reduce(tr.load_xplane(tw.dir))
        shutil.rmtree(tw.dir, ignore_errors=True)
        ctx = Ctx(model=model, work=cell.arch.work(model), peaks=peaks or {},
                  settings=st, records=records, t0=client.t0, t_end=client.t_end,
                  compile_s=compile_s, block_size=getattr(eng, "block_size", None),
                  nbmax=getattr(eng, "nbmax", None),
                  counters={k: c_end[k] - c_start[k] for k in c_end},
                  trace=reduced,
                  trace_t0=tw.t0, trace_t1=tw.t1,
                  trace_counters=tw.deltas() if tw.state == "done" else {})
        for m in cell.per_layer:
            v = metric_reader(m.name)(ctx)
            if v is not None:
                result["metrics"][m.name] = {"value": v, "unit": m.unit}
        if reduced is not None:
            result["device"]["busy_s"] = reduced.busy_s
            result["device"]["window_s"] = reduced.window_s
            result["breakdown"] = {"device_ops": [list(x) for x in reduced.top_ops()],
                                   "idle_gaps": [list(x) for x in reduced.top_gaps()]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            result["metrics"][m.name] = {"value": values[m.name], "unit": m.unit}

    # the reference runs after the window, with the engine's state freed
    eng.cache = None
    del eng, client
    gc.collect()
    check = st["check"]
    picked = corr.sample(records, seed, check["sample_tokens"])
    gap, low, n_tok = corr.gaps(cell.arch, params, model, picked, eng_cfg["max_len"],
                                check["rows"], control=control)
    limit = check["max_logit_gap"]
    result["correct"] = corr.verdict(gap, n_tok, limit)
    result["checks"] = {"max_logit_gap": {"value": gap, "limit": limit},
                        "tokens_compared": {"value": n_tok, "limit": 1}}
    if control:
        result["control_gap"] = low
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        devices = require_chips(cell.chips)
    except (SpecError, NoChip) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
