"""A configuration names its architecture: ``reference/<architecture>.py``
gives the reference, the work counts, the width checks and the weight
kinds (``benchlib/spec.py``).  Both cells resolve to ``forward`` and read
what they read before the module was a seam, bit for bit; a stub module
named by a configuration receives every call; a missing or incomplete one
is refused before any device work.

The pins below were recorded on the CPU at commit d875b79, before the
seam: the weights' checksums, the gaps on the fixed records and the
readers' values on a fixed context."""

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the import path)

import hashlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from bench_tiny import BENCH, ROOT, TINY_MODEL, bench_run
from benchlib import correct, spec, work
from benchlib.peaks import peaks_for
from benchlib.weights import make_params
from reference import forward

with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    CELLS = [w["name"] for w in json.load(fh)["workloads"]]
SEED = 2**33 + 5
SHAPES = [(40, 20), (17, 9), (60, 30)]        # (prompt, served) of the fixed records


def records(vocab=512):
    rng = np.random.default_rng(7)
    return [types.SimpleNamespace(prompt=rng.integers(0, vocab, p).tolist(),
                                  out=rng.integers(0, vocab, n).tolist(),
                                  done=True, finish="length", rid=i)
            for i, (p, n) in enumerate(SHAPES)]


def tiny_weights(kind=None):
    from repro.configs import get_config

    return make_params(get_config("smollm_135m").reduced(), SEED, 512, kind)


@pytest.mark.parametrize("name", CELLS)
def test_cells_resolve_to_forward(name):
    cell = spec.load_cell(name)
    assert "architecture" not in cell.config
    assert cell.arch is spec.architecture("forward")
    assert cell.arch.__file__ == os.path.join(BENCH, "reference", "forward.py")
    assert cell.arch.WIDTH_KEYS == {"head_dim": "hd"}
    assert not hasattr(cell.arch, "weight_kind")


@pytest.mark.parametrize("program,digest", [
    ("minicpm_2b", "cecff2b59a0d9ddba2a12435068753b8988b6c770d0b46ee9f72c7a6d7e2f991"),
    ("smollm_135m", "c9b3fd65e91cd4f2fb2bcdcecf693d22ff700947e5799eb8398394c30813cb1c"),
])
def test_weights_of_the_reduced_trees_are_the_parents(program, digest):
    import jax

    from repro.configs import get_config

    cfg = get_config(program).reduced()
    arch = spec.architecture("forward")
    p = make_params(cfg, SEED, cfg.vocab_size, getattr(arch, "weight_kind", None))
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(p):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == digest


@pytest.mark.parametrize("control,pinned", [
    (False, ("0x1.525b1c0000000p+0", None, 59)),
    (True, ("0x1.525b1c0000000p+0", "0x1.0586800000000p-3", 59)),
])
def test_gaps_through_the_module_are_forwards(control, pinned):
    """``correct.gaps`` through the cell's module against the gaps worked
    out here from a direct call of ``forward.logits_at``, and against the
    parent's readings."""
    w, recs = tiny_weights(), records()
    arch = spec.load_cell(CELLS[-1]).arch
    served, low, n = correct.gaps(arch, w, TINY_MODEL, recs, 128, 32, control=control)
    want_served, want_low = 0.0, 0.0
    for rec in recs:
        tokens, rows, out, k = correct._padded(rec, 128, 32)
        ref = np.asarray(forward.logits_at(w, TINY_MODEL, tokens, rows))[:k]
        best = ref.max(axis=-1)
        want_served = max(want_served, float((best - ref[np.arange(k), out]).max()))
        if control:
            pick = np.asarray(forward.logits_at(w, TINY_MODEL, tokens, rows,
                                                control=True))[:k].argmax(axis=-1)
            want_low = max(want_low, float((best - ref[np.arange(k), pick]).max()))
    assert served == want_served and n == sum(s for _, s in SHAPES)
    assert low == (want_low if control else None)
    assert (served.hex(), low.hex() if control else None, n) == pinned


def fixed_context(cell, **counts):
    eng = cell.settings["engine"]
    return types.SimpleNamespace(
        nbmax=-(-eng["max_len"] // eng["block_size"]), peaks=peaks_for("TPU v5 lite"),
        trace=types.SimpleNamespace(window_s=10.0, op_seconds=lambda kernel: 0.25),
        trace_counters={"prefill_tokens": 788},
        decode_contexts=lambda: [1, 127, 128, 2047, 2048],
        first_tokens_in_trace=lambda: [types.SimpleNamespace(rid=0, prompt=[0] * 1000),
                                       types.SimpleNamespace(rid=1, prompt=[0] * 300)],
        ideal_hits=lambda: {0: 512, 1: 0}, **counts)


@pytest.mark.parametrize("name,mfu,roofline", [
    (CELLS[0], "0x1.a31249e2db942p-3", "0x1.ab8b22dd44d95p-2"),
    (CELLS[1], "0x1.48dfd0a11289cp-7", "0x1.ae586b233ec72p-6"),
])
def test_work_is_the_dims_counts_times_layers(name, mfu, roofline):
    cell = spec.load_cell(name)
    model = cell.config["model"]
    w, m = cell.arch.work(model), work.Dims.of(model)
    ctx = fixed_context(cell, work=w)
    for c in (1, 127, 128, 2047, 2048):
        assert w.attention_flops(c) == work.attention_flops(m, c)
        assert w.decode_attention_bytes(c, ctx.nbmax) == \
            work.decode_attention_bytes(m, c, ctx.nbmax) * m.layers
        assert w.decode_attention_flops(c) == work.decode_attention_flops(m, c) * m.layers
    for head in (False, True):
        assert w.token_matmul_flops(head) == work.token_matmul_flops(m, head)
    assert w.head_flops() == 2 * m.d * m.vocab
    assert w.kv_bytes_per_token() == work.kv_bytes_per_token(m)
    assert w.weight_bytes() == work.weight_bytes(m)
    assert spec.metric_reader("mfu.closed")(ctx).hex() == mfu
    assert spec.metric_reader("attn_roofline_pct.closed")(ctx).hex() == roofline


STUB = '''
"""A stand-in architecture that records every call it receives."""
from reference import forward

CALLS = []
WIDTH_KEYS = {"stub_width": "d_model"}


def weight_kind(path, shape):
    CALLS.append(("weight_kind", path))
    return "norm" if "wo" in path else None


def logits_at(w, model, tokens, rows, *, control=False):
    CALLS.append(("logits_at", control))
    return forward.logits_at(w, model, tokens, rows, control=control)


class Work:
    def __getattr__(self, name):
        def count(*args, **kwargs):
            args += tuple(kwargs.values())
            CALLS.append((name,) + args)
            return 1000 + sum(args)
        return count


def work(model):
    CALLS.append(("work", model["stub_width"]))
    return Work()
'''


def stub_bench(tmp_path, module=STUB, architecture="stub", **model):
    """A benchmark directory of one cell whose configuration names
    ``architecture`` and holds ``module`` at ``reference/stub.py``."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "cells", "reference"):
        (bench / sub).mkdir(parents=True)
    if module is not None:
        (bench / "reference" / "stub.py").write_text(module)
    config = {"name": "stub", "program_config": "smollm_135m", "program_reduced": True,
              "architecture": architecture,
              "model": dict(TINY_MODEL, **dict({"stub_width": 128}, **model))}
    (bench / "configs" / "stub.json").write_text(json.dumps(config))
    mix = {"loop": "closed", "block": 4, "prompt": {"dist": "uniform", "lo": 40, "hi": 120},
           "reply": {"dist": "uniform", "lo": 8, "hi": 24}}
    (bench / "traffic" / "mix.json").write_text(json.dumps(mix))
    settings = {"engine": dict(bench_tiny.TINY_ENGINE), "load": {"clients": 4},
                "trace": {"start_s": 0.5, "length_s": 1.0},
                "check": {"sample_tokens": 48, "rows": 32, "max_logit_gap": 0.03}}
    (bench / "cells" / "stub.cell.json").write_text(json.dumps(settings))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        benchmark = json.load(fh)
    benchmark["workloads"] = [{"name": "stub.cell", "config": "stub", "traffic": "mix",
                               "chips": 1, "why": "a stand-in architecture"}]
    benchmark["end_to_end"] = [dict(m, workloads=["stub.cell"]) if m.get("workloads")
                               else m for m in benchmark["end_to_end"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return str(bench)


def test_a_stub_architecture_receives_every_call(tmp_path):
    run = bench_run()
    cell = spec.load_cell("stub.cell", bench_dir=stub_bench(tmp_path))
    stub, calls = cell.arch, cell.arch.CALLS
    assert stub.__file__ == str(tmp_path / "bench" / "reference" / "stub.py")

    # the width check: the module's keys beside the dense ones
    run.program_config(cell)
    bad = spec.load_cell("stub.cell", bench_dir=stub_bench(tmp_path / "bad", stub_width=64))
    with pytest.raises(spec.SpecError, match="stub_width"):
        run.program_config(bad)

    # the weights: the module names the kind of the leaves it claims,
    # every leaf keeps its draw
    import jax

    mine, plain = (jax.tree_util.tree_leaves_with_path(p)
                   for p in (tiny_weights(stub.weight_kind), tiny_weights()))
    assert [c[1] for c in calls if c[0] == "weight_kind"] == \
        [jax.tree_util.keystr(k) for k, _ in mine]
    for (k, a), (_, b) in zip(mine, plain):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if "wo" in jax.tree_util.keystr(k):
            assert abs(a.mean() - 1) < 0.01 and abs(b.mean()) < 0.01
        else:
            assert np.array_equal(a, b)

    # the reference and its control
    w, recs = tiny_weights(), records()
    assert correct.gaps(stub, w, TINY_MODEL, recs, 128, 32, control=True) == \
        correct.gaps(spec.architecture("forward"), w, TINY_MODEL, recs, 128, 32,
                     control=True)
    assert ("logits_at", False) in calls and ("logits_at", True) in calls

    # the readers take every count from the module
    ctx = fixed_context(cell, work=stub.work(cell.config["model"]))
    del calls[:]
    mfu = spec.metric_reader("mfu.closed")(ctx)
    ctxs, prefill, firsts = [1, 127, 128, 2047, 2048], 788, 2
    mean_ctx = ((513 + 1000) * 488 / 2 + 301 * 300 / 2) / 788
    flops = sum(1001 + 1000 + c for c in ctxs) + (prefill * (1000 + 1001 * mean_ctx)
                                                  + firsts * 1000)
    assert mfu == 100.0 * flops / (10.0 * ctx.peaks["bf16_flops"])
    assert ("head_flops",) in calls and ("token_matmul_flops", False) in calls
    del calls[:]
    roof = spec.metric_reader("attn_roofline_pct.closed")(ctx)
    need = max(sum(1000 + c + ctx.nbmax for c in ctxs) / ctx.peaks["hbm_bytes_per_s"],
               sum(1000 + c for c in ctxs) / ctx.peaks["bf16_flops"])
    assert roof == 100.0 * need / 0.25
    assert {c[0] for c in calls} == {"decode_attention_bytes", "decode_attention_flops"}


def test_a_run_takes_everything_from_the_stub(tmp_path):
    """A whole run of the tiny cell under the stub: the weights, the
    per-layer context and both readings of the reference come from it."""
    cell = spec.load_cell("stub.cell", bench_dir=stub_bench(tmp_path))
    r = bench_run().run_cell(cell, 3, 2.0, True, control=True, log=lambda *a, **k: None)
    kinds = {c[0] for c in cell.arch.CALLS}
    assert {"weight_kind", "work", "logits_at"} <= kinds
    assert ("logits_at", True) in cell.arch.CALLS and ("work", 128) in cell.arch.CALLS
    assert r["checks"]["tokens_compared"]["value"] > 0 and r["control_gap"] is not None


@pytest.mark.parametrize("module,architecture,says", [
    (None, "stub", "no architecture module"),
    (STUB.replace("def logits_at", "def not_logits_at"), "stub", "lacks logits_at"),
    (STUB.replace("def work", "def not_work"), "stub", "lacks work"),
    (STUB, "../stub", "not a module name"),
])
def test_a_missing_or_incomplete_module_is_refused(tmp_path, module, architecture, says):
    bench = stub_bench(tmp_path, module=module, architecture=architecture)
    with pytest.raises(spec.SpecError, match=says):
        spec.load_cell("stub.cell", bench_dir=bench)


def test_run_exits_2_on_a_missing_module_before_any_device_work(tmp_path):
    import shutil

    bench = stub_bench(tmp_path, module=None)
    for sub in ("benchlib", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(BENCH, "run.py"), bench)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "stub.cell",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "no architecture module" in p.stderr and "TPU" not in p.stderr
