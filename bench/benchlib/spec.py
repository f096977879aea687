"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  Each lives in a file of its own under the benchmark's directory:

- ``configs/<config>.json``: the model as it is run (published widths, what
  was changed and assumed, the deployment it stands for);
- ``traffic/<traffic>.json``: the mix's parameters, read by the one general
  generator in ``benchlib/traffic.py``;
- ``cells/<workload>.json``: the engine settings, the load level, the traced
  sub-window and the correctness limit of this pairing;
- ``metrics/<metric>.py`` (or ``metrics/<base>.py`` for ``<base>.<suffix>``):
  the reader of one per-layer metric;
- ``reference/<architecture>.py``: the architecture the configuration names
  under its optional key ``architecture`` (``forward``, the Llama-family
  decoder, where the key is absent).  It is the one place that says what
  the architecture is, and gives:

  - ``logits_at(w, model, tokens, rows, *, control=False)``: the plain
    float32 reference's logits after each position in ``rows``, and with
    ``control`` its lower-precision control (``benchlib/correct.py``);
  - ``work(model)``: the whole model's work counts, summed over its layers,
    that the per-layer readers take as ``ctx.work`` (``benchlib/layer.py``);
  - optionally ``WIDTH_KEYS``, ``{file key: ModelConfig attribute}`` pairs
    (an attribute, or a method of no arguments) that
    ``run.program_config`` checks beside the dense widths;
  - optionally ``weight_kind(path, shape)``: a leaf's kind (``"norm"``,
    ``"embed"``, ``"head"`` or ``"matrix"``) where the name rule of
    ``benchlib/weights.py`` would misread it, else None.

A later cell, configuration, architecture, mix or metric is new files plus
new entries in ``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass, field
from types import ModuleType
from typing import Callable, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """A cell, or a file it names, is missing or malformed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}") from None


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: Optional[str] = None
    layer: Optional[str] = None
    workloads: Optional[List[str]] = None


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    settings: dict
    arch: ModuleType              # reference/<architecture>.py
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def _metric(entry: dict) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"],
                  better=entry["better"], source=entry["source"],
                  moves=entry.get("moves"), layer=entry.get("layer"),
                  workloads=entry.get("workloads"))


@functools.lru_cache(maxsize=None)
def _load_module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod           # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


def architecture(name: str, bench_dir: str = BENCH_DIR) -> ModuleType:
    """The module ``reference/<name>.py``, loaded by path (once a process),
    if it gives ``logits_at`` and ``work``."""
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_-]*", name):
        raise SpecError(f"architecture {name!r} is not a module name")
    path = os.path.join(bench_dir, "reference", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"no architecture module {os.path.relpath(path, ROOT)}")
    mod = _load_module(path, f"bench_reference_{name.replace('-', '_')}")
    missing = [f for f in ("logits_at", "work") if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"architecture module {name!r} lacks {', '.join(missing)}")
    return mod


def load_cell(workload: str, bench_dir: str = BENCH_DIR,
              benchmark: Optional[dict] = None) -> Cell:
    """The cell named ``workload`` with its configuration, mix, settings,
    architecture module and the metrics it reports, all found by name."""
    if benchmark is None:
        benchmark = _load_json(os.path.join(os.path.dirname(bench_dir),
                                            "BENCHMARK.json"))
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    config = _load_json(os.path.join(bench_dir, "configs", f"{w['config']}.json"))
    mix = _load_json(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json"))
    settings = _load_json(os.path.join(bench_dir, "cells", f"{workload}.json"))
    e2e = [_metric(m) for m in benchmark["end_to_end"]
           if m.get("workloads") is None or workload in m["workloads"]]
    reported = {m.name for m in e2e}
    per_layer = [_metric(m) for m in benchmark["per_layer"]
                 if (workload in m["workloads"] if m.get("workloads") is not None
                     else m["moves"] in reported)]
    return Cell(name=workload, chips=int(w["chips"]), config=config, mix=mix,
                settings=settings,
                arch=architecture(config.get("architecture", "forward"), bench_dir),
                end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(ctx)`` of the per-layer metric ``name``: ``metrics/<name>.py``,
    else ``metrics/<base>.py`` for a name split by suffix (``mfu.closed``
    and ``mfu.open`` share ``metrics/mfu.py``)."""
    base = name.split(".", 1)[0]
    for stem in (name, base):
        path = os.path.join(bench_dir, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return _load_module(
                path, f"bench_metric_{stem.replace('.', '_').replace('-', '_')}").read
    raise SpecError(f"no reader for per-layer metric {name!r} under metrics/")
