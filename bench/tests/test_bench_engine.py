"""A cell's engine comes from its ``engine`` entry: the harness's defaults
in one place, every other key passed to ``Engine`` by name, a mesh for a
cell on more than one chip; and the warm-up, through ``submit``/``step``
alone, follows whatever engine that builds."""

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the import path)

import json
import os
import subprocess
import sys

import pytest

from bench_tiny import BENCH, ROOT, TINY_ENGINE, bench_run, tiny_cell
from benchlib.spec import SpecError


def test_defaults_and_overrides():
    run = bench_run()
    batch, max_len, kw = run.engine_args(TINY_ENGINE, 1)
    assert (batch, max_len) == (4, 256)
    assert kw["num_blocks"] == 1024 // 16 and "pool_tokens" not in kw
    assert {k: kw[k] for k in run.ENGINE_DEFAULTS if k not in TINY_ENGINE} == \
        {k: v for k, v in run.ENGINE_DEFAULTS.items() if k not in TINY_ENGINE}
    _, _, kw = run.engine_args(dict(TINY_ENGINE, kv_layout="ring", spec_decode=True,
                                    draft_k=3, policy={"scheme": "dither"}), 1)
    assert kw["kv_layout"] == "ring" and kw["spec_decode"] and kw["draft_k"] == 3
    assert kw["policy"].scheme == "dither"


@pytest.mark.parametrize("entry,chips", [({}, 4), ({"mesh": [2, 1]}, 4),
                                         ({"pool_tokens": 1000}, 1)])
def test_engine_entries_that_cannot_run_are_refused(entry, chips):
    with pytest.raises(SpecError):
        bench_run().engine_args(dict(TINY_ENGINE, **entry), chips)


@pytest.mark.parametrize("engine", [{"kv_layout": "ring"}, {"decode_ticks": 2}])
def test_warm_up_follows_the_cell_engine(engine):
    """Another layout or window length, set in the cell alone, is warmed up
    whole: nothing compiles inside the window, and what it serves passes."""
    cell = tiny_cell("open", 0.03)
    cell.settings["engine"].update(engine)
    r = bench_run().run_cell(cell, 3, 3.0, False, log=lambda *a, **k: None)
    assert r["window"]["compiles"] == 0
    assert r["attempted"] > 0 and r["correct"] is True, r["checks"]


def test_a_four_chip_cell_serves_on_its_mesh():
    """On four CPU devices, a cell with ``chips`` 4 and a (2, 2) mesh serves
    through the sharded engine, compiles nothing inside its window, passes
    its check, and reports four devices."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from bench_tiny import bench_run, tiny_cell\n"
        "import json\n"
        "cell = tiny_cell('closed', 0.03); cell.chips = 4\n"
        "cell.settings['engine']['mesh'] = [2, 2]\n"
        "r = bench_run().run_cell(cell, 7, 3.0, False, log=lambda *a, **k: None)\n"
        "print(json.dumps(r))\n" % os.path.join(BENCH, "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["window"]["compiles"] == 0
    assert r["correct"] is True, r["checks"]
