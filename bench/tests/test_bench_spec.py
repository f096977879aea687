"""The benchmark is driven by data: every cell of ``BENCHMARK.json`` finds
its configuration, mix, settings and metric readers by name, and the
command refuses to run without a chip."""

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the import path)

import json
import os
import subprocess
import sys

import pytest

from benchlib import spec

BENCH = spec.BENCH_DIR
with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as fh:
    BENCHMARK = json.load(fh)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_finds_its_files_and_metrics(name):
    cell = spec.load_cell(name)
    assert cell.config["name"] == cell.name.split(".")[0]
    assert {m.name for m in cell.end_to_end} >= {"setup_s", "output_tok_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m.name))
    eng = cell.settings["engine"]
    assert eng["pool_tokens"] % eng["block_size"] == 0
    assert eng["prefill_chunk"] % eng["block_size"] == 0


def test_configs_state_what_they_changed():
    for c in BENCHMARK["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as fh:
            f = json.load(fh)
        assert f["name"] == c["name"] and f["source"].startswith(c["source"])
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        assert set(f["published"]) == set(f["reduced"])
        assert all(f["model"][k] != f["published"][k] for k in f["reduced"])


def test_unknown_cell_is_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such.cell")


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
             spec.ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_with_only_the_benchmark(tmp_path):
    import shutil

    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
             tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
