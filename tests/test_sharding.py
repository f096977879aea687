"""Sharding rules: every generated spec must evenly divide its dim on the
production mesh, for every assigned architecture (param + cache trees)."""

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.dist import sharding as shd
from repro.models import registry

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axis_size(mesh, entry):
    axes = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in axes:
        n *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
    return n


def _check(tree_shapes, specs, mesh):
    flat_s, _ = jax.tree_util.tree_flatten(tree_shapes)
    flat_p = jax.tree_util.tree_flatten(specs, is_leaf=lambda x: isinstance(x, P))[0]
    assert len(flat_s) == len(flat_p)
    for leaf, spec in zip(flat_s, flat_p):
        assert len(spec) <= leaf.ndim, (leaf.shape, spec)
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            size = _axis_size(mesh, entry)
            assert leaf.shape[i] % size == 0, (leaf.shape, spec, i)


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh", [MESH, MESH_MP], ids=["single", "multi"])
def test_param_specs_divide(arch, mesh):
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda k: registry.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    specs = shd.param_specs(shapes, cfg, mesh)
    _check(shapes, specs, mesh)


@pytest.mark.parametrize("arch", ["qwen2_1_5b", "recurrentgemma_9b",
                                  "mamba2_370m", "whisper_small"])
def test_cache_specs_divide(arch):
    cfg = get_config(arch)
    params = jax.eval_shape(lambda k: registry.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    frames = (jax.ShapeDtypeStruct((128, cfg.n_enc_tokens, cfg.d_model),
                                   "bfloat16") if cfg.is_encdec else None)
    cache = jax.eval_shape(
        lambda p, f: registry.make_cache(p, cfg, 128, 32768, frames=f),
        params, frames)
    specs = shd.cache_specs(cache, cfg, MESH)
    _check(cache, specs, MESH)


def test_attention_sharding_respects_head_counts():
    """wq shards only when n_heads % tp == 0; wk/wv only when kv does."""
    cfg = get_config("granite_3_8b")  # 32 q heads (÷16 ✓), 8 kv heads (✗)
    shapes = jax.eval_shape(lambda k: registry.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    specs = shd.param_specs(shapes, cfg, MESH)
    blk = specs["blocks"][0]["attn"]
    assert blk["wq"] == P(None, None, "model")
    assert blk["wk"] == P(None, None, None)   # 8 kv heads can't split 16 ways
    assert blk["wo"] == P(None, "model", None)


# ---------------------------------------------------------------------------
# sharded serving (DESIGN.md §9): paged cache specs + the serve param subset
# ---------------------------------------------------------------------------

SERVE_MESHES = {
    (1, 1): AbstractMesh((1, 1), ("data", "model")),
    (2, 1): AbstractMesh((2, 1), ("data", "model")),
    (1, 2): AbstractMesh((1, 2), ("data", "model")),
}


def _paged_cache_shapes(cfg, batch=4, max_len=64, bs=8, dp=1, tp=1,
                        kv_quant=True):
    """The engine's paged cache on a (dp, tp) mesh: int8 scale rows pad per
    head shard when the KV heads split ``tp`` ways."""
    params = jax.eval_shape(lambda k: registry.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    heads = tp if shd.serve_heads_shardable(cfg, tp) else 1
    return jax.eval_shape(
        lambda p: registry.make_cache(p, cfg, batch, max_len,
                                      kv_quant=kv_quant, kv_layout="paged",
                                      block_size=bs, data_shards=dp,
                                      head_shards=heads),
        params)


@pytest.mark.parametrize("mesh_shape", sorted(SERVE_MESHES), ids=str)
def test_cache_specs_paged_divide(mesh_shape):
    """Paged cache specs (pools, block tables, pos) stay legal on every
    serve mesh — block axis on 'data', KV heads on 'model'."""
    cfg = get_config("smollm_135m").reduced()     # 2 KV heads
    dp, tp = mesh_shape
    mesh = SERVE_MESHES[mesh_shape]
    cache = _paged_cache_shapes(cfg, dp=dp, tp=tp)
    specs = shd.cache_specs(cache, cfg, mesh)
    _check(cache, specs, mesh)
    ent = specs["layers"][0]          # stacked entry: leading repeat axis
    assert specs["pos"] == (P("data") if dp > 1 else P(None))
    assert specs["block_tables"] == P("data" if dp > 1 else None, None)
    # repeat axis never shards; the pool-block axis carries 'data'
    assert ent["k"][0] is None
    assert ent["k"][1] == ("data" if dp > 1 else None)
    # heads-major pool (L, blocks, H, hd, bs): heads at stacked dim 2
    assert ent["k"][2] == ("model" if tp > 1 else None)
    assert ent["k_scale"][2] == ("model" if tp > 1 else None)


def test_cache_specs_paged_gqa_fallback():
    """n_kv_heads % tp != 0 → the head dim stays replicated (the same
    guard the engine's replicated-TP fallback mirrors)."""
    cfg = get_config("smollm_135m").reduced()
    from dataclasses import replace
    cfg = replace(cfg, n_kv_heads=1)              # MQA: 1 % 2 != 0
    mesh = SERVE_MESHES[(1, 2)]
    cache = _paged_cache_shapes(cfg, tp=2)
    specs = shd.cache_specs(cache, cfg, mesh)
    _check(cache, specs, mesh)
    ent = specs["layers"][0]
    assert ent["k"][2] is None
    assert ent["v"][2] is None
    assert ent["k_scale"][2] is None
    assert not shd.serve_heads_shardable(cfg, 2)


def test_cache_specs_ring_stack_axis_not_data_sharded():
    """Stacked ring entries carry the scan repeat axis first: the batch
    rule must target axis 1, never the repeat axis (regression — the
    pre-§9 rule sharded axis 0 of stacked entries on 'data')."""
    cfg = get_config("smollm_135m").reduced()
    params = jax.eval_shape(lambda k: registry.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda p: registry.make_cache(p, cfg, 4, 64, kv_quant=True), params)
    specs = shd.cache_specs(cache, cfg, SERVE_MESHES[(2, 1)])
    ent = specs["layers"][0]
    assert ent["k"][0] is None and ent["k"][1] == "data"
    assert ent["k_pos"][0] is None and ent["k_pos"][1] == "data"


def test_serve_param_specs_reduction_preserving():
    """Serve params shard only the QKV projections (column-parallel,
    head-guarded); W_O / MLP / embeddings stay replicated so no f32
    contraction is ever split (the bitwise stream-parity contract)."""
    cfg = get_config("smollm_135m").reduced()     # 4 heads / 2 KV heads
    params = jax.eval_shape(lambda k: registry.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    specs = shd.serve_param_specs(params, cfg, SERVE_MESHES[(1, 2)])
    blk = specs["blocks"][0]["attn"]
    assert blk["wq"] == P(None, None, "model")
    assert blk["wk"] == P(None, None, "model")
    assert blk["wo"] == P(None, None, None)       # replicated, all-gathered in
    mlp = specs["blocks"][0]["mlp"]
    assert all(e is None for leaf in mlp.values() for e in leaf)
    assert all(e is None for e in specs["embed"])
    # GQA fallback: nothing shards
    from dataclasses import replace
    mqa = replace(cfg, n_kv_heads=1)
    specs = shd.serve_param_specs(params, mqa, SERVE_MESHES[(1, 2)])
    assert specs["blocks"][0]["attn"]["wq"] == P(None, None, None)
