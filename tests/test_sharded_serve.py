"""Sharded serving (DESIGN.md §9): a mesh-sharded engine must emit the
*bitwise* token stream of the single-device engine.

The serve layout is reduction-preserving — QKV column-parallel, attention
heads all-gathered before a replicated W_O, decode slots / paged pools
partitioned on 'data', KV heads on 'model' — so no f32 reduction is ever
re-associated by sharding, and the dither KV codes hash coordinates that
are independent of slot placement and shard count.  These tests pin that
contract over kv_layout ∈ {ring, paged} × KV dtype ∈ {bf16, int8}:

* the (1, 1) mesh runs everywhere (tier-1: single CPU device) and pins the
  shard_map path itself against the unmeshed engine;
* (2, 1) / (1, 2) / (2, 2) meshes run when ≥ 4 devices exist — CI forces
  them with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``;
* the GQA fallback (n_kv_heads % tp != 0 → fully replicated TP compute,
  mirroring dist/sharding's head-count guards) is pinned on a (1, 2) mesh.
"""

from dataclasses import replace

import jax
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_serve_mesh
from repro.models import registry
from repro.serve.engine import Engine, Request
from repro.serve.sampling import SamplingParams

N_DEV = len(jax.devices())
needs4 = pytest.mark.skipif(
    N_DEV < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)")
needs2 = pytest.mark.skipif(N_DEV < 2, reason="needs 2 devices")

CFG = get_config("smollm_135m").reduced()      # 4 heads / 2 KV heads
CFG_MQA = replace(CFG, n_kv_heads=1)           # 1 % tp != 0 → GQA fallback
_PARAMS = {}


def _params(cfg):
    key = cfg.n_kv_heads
    if key not in _PARAMS:
        _PARAMS[key] = registry.init_model(jax.random.PRNGKey(0), cfg)
    return _PARAMS[key]


def _stream(cfg, mesh, kv_layout, kv_quant, *, temperature=0.0, spec=False):
    """Serve a fixed 6-request mix; return the full per-request streams."""
    eng = Engine(_params(cfg), cfg, batch=4, max_len=48, kv_quant=kv_quant,
                 kv_layout=kv_layout,
                 block_size=8 if kv_layout == "paged" else None, mesh=mesh,
                 **(dict(spec_decode=True, draft_k=4) if spec else {}))
    for r in range(6):
        prompt = [(7 * r + i) % (cfg.vocab_size - 1) + 1
                  for i in range(5 + r % 3)]
        eng.submit(Request(rid=r, prompt=prompt,
                           sampling=SamplingParams(temperature=temperature,
                                                   max_new=6, seed=r,
                                                   counter_offset=1000 * r)))
    done = eng.run(ticks=200)
    assert len(done) == 6
    if spec:
        assert eng.metrics.summary()["counters"].get("spec_windows", 0) > 0
    return sorted((r.rid, tuple(r.out), r.finish_reason) for r in done)


_BASE = {}


def _baseline(cfg, kv_layout, kv_quant):
    key = (cfg.n_kv_heads, kv_layout, kv_quant)
    if key not in _BASE:
        _BASE[key] = _stream(cfg, None, kv_layout, kv_quant)
    return _BASE[key]


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_mesh_1x1_parity(kv_layout, kv_quant):
    """The shard_map serve path on a trivial (1, 1) mesh is bitwise the
    unmeshed engine — runs in tier-1 on a single CPU device."""
    got = _stream(CFG, make_serve_mesh(1, 1), kv_layout, kv_quant)
    assert got == _baseline(CFG, kv_layout, kv_quant)


@needs4
@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
@pytest.mark.parametrize("mesh_shape", [(2, 1), (1, 2), (2, 2)],
                         ids=["dp2", "tp2", "dp2tp2"])
def test_mesh_parity(mesh_shape, kv_layout, kv_quant):
    """data-, model- and jointly-sharded streams are bitwise the
    single-device stream (the ISSUE-5 acceptance criterion)."""
    got = _stream(CFG, make_serve_mesh(*mesh_shape), kv_layout, kv_quant)
    assert got == _baseline(CFG, kv_layout, kv_quant)


@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_spec_mesh_1x1_parity(kv_layout):
    """Speculative decode through the shard_map serve path on a (1, 1)
    mesh is bitwise the unmeshed *plain* engine — the spec window's verify
    and bulk-commit dispatches preserve the stream contract under mesh
    placement (tier-1, single CPU device)."""
    got = _stream(CFG, make_serve_mesh(1, 1), kv_layout, False, spec=True)
    assert got == _baseline(CFG, kv_layout, False)


@needs4
@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
@pytest.mark.parametrize("mesh_shape", [(2, 1), (1, 2), (2, 2)],
                         ids=["dp2", "tp2", "dp2tp2"])
def test_spec_mesh_parity(mesh_shape, kv_layout):
    """Speculative streams on data-, model- and jointly-sharded meshes are
    bitwise the single-device plain stream: dither KV codes hash absolute
    coordinates, so a bulk-committed window is placement-independent just
    like sequential decode (DESIGN.md §14)."""
    got = _stream(CFG, make_serve_mesh(*mesh_shape), kv_layout, False,
                  spec=True)
    assert got == _baseline(CFG, kv_layout, False)


@needs4
def test_mesh_parity_sampled():
    """Temperature sampling is per-row hash noise, so parity survives
    non-greedy decoding too (ring, int8 KV, (2, 2))."""
    base = _stream(CFG, None, "ring", True, temperature=0.8)
    got = _stream(CFG, make_serve_mesh(2, 2), "ring", True, temperature=0.8)
    assert got == base


@needs2
@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_gqa_fallback_parity(kv_layout):
    """n_kv_heads=1 cannot split a 2-way model axis: the engine must fall
    back to replicated TP compute (heads_sharded False) and still match the
    single-device stream bitwise."""
    mesh = make_serve_mesh(1, 2)
    eng = Engine(_params(CFG_MQA), CFG_MQA, batch=4, max_len=48,
                 kv_layout=kv_layout,
                 block_size=8 if kv_layout == "paged" else None, mesh=mesh)
    assert eng.heads_sharded is False
    assert eng._cfg_local.n_kv_heads == CFG_MQA.n_kv_heads
    got = _stream(CFG_MQA, mesh, kv_layout, True)
    assert got == _baseline(CFG_MQA, kv_layout, True)


@needs2
def test_mesh_requires_batch_divisible():
    with pytest.raises(ValueError, match="multiple of the mesh's data axis"):
        Engine(_params(CFG), CFG, batch=3, max_len=32,
               mesh=make_serve_mesh(2, 1))


def test_mesh_rejects_recurrent_archs():
    cfg = get_config("mamba2_370m").reduced()
    with pytest.raises(ValueError, match="attention-only"):
        Engine(registry.init_model(jax.random.PRNGKey(0), cfg), cfg,
               batch=2, max_len=32, mesh=make_serve_mesh(1, 1))


@needs4
def test_paged_pool_partitioned_per_shard():
    """The paged pool splits into per-data-shard sub-pools: admission
    budget, trash id and block tables are shard-local (DESIGN.md §9)."""
    eng = Engine(_params(CFG), CFG, batch=4, max_len=48, kv_layout="paged",
                 block_size=8, num_blocks=12, mesh=make_serve_mesh(2, 2))
    assert len(eng.pools) == 2
    assert eng.num_blocks == 12 and eng._nb_local == 6
    assert all(p.trash == 6 for p in eng.pools)
    # device pool: 2 shards × (6 + 1 trash) blocks back to back
    assert eng.cache["layers"][0]["k"].shape[1] == 14
    for r in range(4):
        eng.submit(Request(rid=r, prompt=[r + 1] * 5,
                           sampling=SamplingParams(max_new=4)))
    done = eng.run(ticks=60)
    assert len(done) == 4
    assert {eng._slot_shard(i) for i in range(4)} == {0, 1}
    assert eng.pool_stats()["live"] == 0       # all released on finish


# ------------------------------------------------------- program names


PROGRAM_CASES = {
    # engine settings -> the programs its serving and restore paths compile
    "paged": (dict(kv_layout="paged", block_size=8, decode_ticks=2,
                   prefill_chunk=8, kv_quant=True),
              {"fused_decode", "paged_prefill", "sample_tokens", "replay_step"}),
    "ring-spec": (dict(spec_decode=True, draft_k=3),
                  {"prefill_step", "merge_prefill", "sample_tokens",
                   "spec_verify", "spec_commit"}),
    "ring-chunked": (dict(decode_ticks=2, prefill_chunk=4),
                     {"chunked_prefill", "fused_decode", "sample_tokens"}),
}


@pytest.mark.parametrize("shape", [None, (1, 1), pytest.param((2, 2), marks=needs4)],
                         ids=["one-device", "mesh1x1", "mesh2x2"])
@pytest.mark.parametrize("case", sorted(PROGRAM_CASES))
def test_programs_compile_under_their_role_names(case, shape, monkeypatch):
    """Every program the engine compiles lowers to ``jit_<role>`` on one
    device and under a mesh alike, so a profiler trace's ``XLA Modules``
    line names it (the benchmark finds ``fused_decode`` and
    ``paged_prefill`` there by name)."""
    import re

    import repro.serve.engine as engine_mod

    names, real = set(), engine_mod._jit

    def spy(fn, **kw):
        jitted, seen = real(fn, **kw), []

        def call(*a, **k):
            if not seen:
                seen.append(True)
                text = jitted.lower(*a, **k).as_text()
                names.add(re.match(r"module @(\w+)", text).group(1))
            return jitted(*a, **k)

        return call

    monkeypatch.setattr(engine_mod, "_jit", spy)
    kw, roles = PROGRAM_CASES[case]
    mesh = make_serve_mesh(*shape) if shape else None
    make = lambda: Engine(_params(CFG), CFG, batch=2, max_len=32, mesh=mesh, **kw)
    eng = make()
    for r in range(2):
        eng.submit(Request(rid=r, prompt=[3 + r, 5, 7, 9, 11],
                           sampling=SamplingParams(max_new=5, seed=r)))
    eng.step()
    if "replay_step" in roles:                  # slots hold generated tokens
        make().restore(eng.snapshot())
    eng.run(8)
    assert names == {f"jit_{role}" for role in roles}
