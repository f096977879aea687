"""Helpers of the benchmark's CPU tests: the import paths, ``bench/run.py``
as a module, and a tiny stand-in of a cell.

Each test file imports this module first: it puts ``bench/`` and ``src/``
on the import path.  (A ``conftest.py`` here would share the module name
``conftest`` with ``tests/conftest.py``, which the repository's tests import
by that name.)  Nothing here loads the TPU's library or describes a
topology."""

import functools
import importlib.util
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@functools.lru_cache(maxsize=None)
def bench_run():
    """``bench/run.py`` as a module (loaded by path: ``run`` is a common
    name among the repository's scripts)."""
    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

# the tiny stand-in of a cell: smollm-135m's program config cut by its own
# ModelConfig.reduced() (d 128, 4/2 heads, head dim 32, ff 256, vocab 512,
# 2 layers), served by the same engine path as the real cells
TINY_MODEL = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
              "vocab_size": 512, "tie_word_embeddings": True, "rms_norm_eps": 1e-6,
              "rope_theta": 10000.0}
TINY_ENGINE = {"slots": 4, "max_len": 256, "block_size": 16, "pool_tokens": 1024,
               "prefill_chunk": 32, "decode_ticks": 4}


def tiny_cell(kind: str, limit: float):
    from benchlib.spec import Cell, load_cell

    if kind == "closed":
        mix = {"loop": "closed", "block": 4, "prompt": {"dist": "uniform", "lo": 40, "hi": 120},
               "reply": {"dist": "uniform", "lo": 8, "hi": 24}}
        load, real = {"clients": 4}, load_cell("minicpm-2b.longdoc-closed")
    else:
        mix = {"loop": "open", "block": 8, "system_prompts": {"count": 3, "tokens": 32, "zipf": 1.0},
               "turns": {"dist": "uniform", "lo": 2, "hi": 4},
               "user": {"dist": "lognormal", "median": 12, "sigma": 0.5, "lo": 4, "hi": 24},
               "reply": {"dist": "lognormal", "median": 10, "sigma": 0.5, "lo": 4, "hi": 24},
               "think_s": {"dist": "exponential", "mean": 0.2}, "max_context": 256}
        load, real = {"session_rate_per_s": 4.0}, load_cell("smollm-135m.sessions-open")
    config = {"name": "tiny", "program_config": "smollm_135m", "program_reduced": True,
              "model": dict(TINY_MODEL)}
    settings = {"engine": dict(TINY_ENGINE), "load": load,
                "trace": {"start_s": 0.5, "length_s": 1.0},
                "check": {"sample_tokens": 48, "rows": 32, "max_logit_gap": limit}}
    return Cell(name=f"tiny-{kind}", chips=1, config=config, mix=mix, settings=settings,
                arch=real.arch, end_to_end=real.end_to_end, per_layer=real.per_layer)
