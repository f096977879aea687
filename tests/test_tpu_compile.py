"""Compile rehearsal: every Pallas kernel of the serving path, compiled for a
described (not attached) TPU v5e at smollm-135m's published widths.

Interpret mode never refuses a block layout, a dtype conversion or a VMEM
footprint the chip's compiler rejects; this file does, with no chip.  The
topology is described inside a fixture — never at import — so only the
pytest worker that runs this file loads the TPU compiler, and every worker
collects the same tests.  A test passes when Mosaic lowers the kernel and
the program fits the device (``memory_analysis``); nothing runs.  Scoped
VMEM is checked twice: Mosaic refuses a kernel that overflows it, and the
autotuner's working-set model of the chosen tile must sit under the
budget.
"""

import functools
import os

import jax
import numpy as np
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels import ops as kops
from repro.kernels.decode_attention import (decode_attention_call,
                                            paged_decode_attention_call,
                                            paged_verify_attention_call,
                                            verify_attention_call)

# smollm-135m (configs/smollm_135m.py): 9 query / 3 KV heads, head dim 64,
# d_model 576, d_ff 1536; serving batch 8, ring cap 2048, pool block 16,
# draft_k 4 (the engine's default verify width)
B, NKV, GROUP, HD, CAP, BS, KQ = 8, 3, 3, 64, 2048, 16, 4
D_MODEL, D_FF = 576, 1536


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described device is written to the persistent cache
    # but can never be read back without the chip: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled


def _logical_bytes(shapes):
    return sum(int(np.prod(s)) * np.dtype(d).itemsize for s, d in shapes)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("rows", ["decode", "verify"])
@pytest.mark.parametrize("layout", ["ring", "paged"])
def test_attention_compiles_for_v5e(one_chip, layout, rows, kv):
    """Ring / paged × decode / verify × bf16 / int8 KV, with the tile the
    engine would run: the ring decode autotune pick (verify runs the same
    tile) or the pool block.  The kernels are called directly: the
    dispatcher refuses 'pallas-tpu' on a host whose default backend is not
    a TPU.  The cache is not padded in HBM beyond its slot or block axis,
    and the tile's VMEM working set is under the budget."""
    kdt = jnp.int8 if kv == "int8" else jnp.bfloat16
    quantized = kv == "int8"
    verify = rows == "verify"
    qshape = (B, KQ, NKV, GROUP, HD) if verify else (B, NKV, GROUP, HD)
    if layout == "ring":
        cache = (B, CAP, NKV, HD)
        extra = [((B, CAP), jnp.int32)]                       # k_pos
        block = autotune.best_block("decode_attention",
                                    (B, CAP, NKV, GROUP, HD), kv,
                                    8 if quantized else 16, "flash",
                                    "pallas-tpu")
        fn = functools.partial(verify_attention_call if verify
                               else decode_attention_call, block=block)
    else:
        nbmax = CAP // BS
        cache = (B * nbmax + 1, BS, NKV, HD)                 # + trash block
        extra = [((B, nbmax), jnp.int32)]                     # block tables
        fn = (paged_verify_attention_call if verify
              else paged_decode_attention_call)
        block = (BS,)
    scales = [(cache[:3], jnp.float32)] * 2 if quantized else []
    assert autotune.decode_attention_vmem_bytes(
        block, nkv=NKV, hd=HD, group=GROUP, quantized=quantized,
        kq=KQ if verify else 1) <= autotune.VMEM_BUDGET_BYTES

    def step(q, k, v, aux, pos, *sc):
        ks, vs = sc if quantized else (None, None)
        return fn(q, k, v, aux, pos, ks, vs, interpret=False)

    shapes = [(qshape, jnp.bfloat16), (cache, kdt), (cache, kdt), *extra,
              ((B,), jnp.int32), *scales]
    compiled = _compile(step, one_chip, *shapes)
    # the compiler lays a cache out in HBM with its small (n_kv, hd) plane
    # off the minor tile, so it pads only the ring's slot axis (no padding
    # at 2048) or the pool's block axis (1025 → 1152 blocks, 12.4%) — not
    # the 10–21× of the kernel's VMEM tiles
    logical = _logical_bytes(shapes)
    hbm = compiled.memory_analysis().argument_size_in_bytes
    assert logical <= hbm <= 1.15 * logical, (hbm, logical)


@pytest.mark.parametrize("m", [B, B * 128], ids=["decode", "prefill"])
def test_quantize_compiles_for_v5e(one_chip, m):
    """Dither-quantise an (m, d_ff) activation to 8-bit codes, decode (B
    rows) and prefill (B × 128-token prompts)."""
    block = autotune.best_block("quantize", (m, D_FF), "float32", 8,
                                "dither", "pallas-tpu")
    assert autotune.quantize_vmem_bytes(block) <= autotune.VMEM_BUDGET_BYTES

    def step(x, counter):
        return kops.quantize_2d(x, bits=8, lo=-1.0, hi=1.0, counter=counter,
                                block=block, interpret=False)

    _compile(step, one_chip, ((m, D_FF), jnp.float32), ((), jnp.int32))


@pytest.mark.parametrize("m,k,n", [(B, D_MODEL, D_FF), (B, D_FF, D_MODEL),
                                   (B * 128, D_MODEL, D_FF)],
                         ids=["decode-up", "decode-down", "prefill-up"])
def test_dither_matmul_compiles_for_v5e(one_chip, m, k, n):
    """The fused dither matmul of the MLP under ``policy=dither``, decode
    (B rows) and prefill (B × 128-token prompts): K = 576 has no
    lane-aligned divisor, so its K tile is the whole of K."""
    block = autotune.best_block("matmul", (m, k, n), "float32", 8, "dither",
                                "pallas-tpu")
    assert autotune.matmul_vmem_bytes(block) <= autotune.VMEM_BUDGET_BYTES

    def step(a, b, counter):
        return kops.dither_matmul(a, b, bits=8, counter=counter,
                                  a_range=(-1.0, 1.0), b_range=(-1.0, 1.0),
                                  block=block, interpret=False)

    _compile(step, one_chip, ((m, k), jnp.float32), ((k, n), jnp.float32),
             ((), jnp.int32))


def test_verify_forward_sums_rows_like_decode_for_v5e(one_chip, monkeypatch):
    """The speculative verify forward must sum each row as the decode step
    does, or its rows part from sequential decode on the chip (DESIGN.md
    §14).  The TPU compiler lays a (B, K, d) row reduction out differently
    from a (B, 1, d) one and sums it in another order, which no CPU test
    sees; the verify forward therefore runs on a (B·K, 1, d) view.  Every
    f32 sum of both compiled programs (the RMSNorm means) must reduce the
    same dimensions to the same rank."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.kernels import dispatch
    from repro.models import registry

    # the model resolves its attention backend at trace time: take the
    # chip's, so the programs hold the kernels the engine runs there
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("smollm_135m"), n_layers=2)
    params = jax.eval_shape(
        lambda: registry.init_model(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(
        lambda p: registry.make_cache(p, cfg, B, 256, kv_quant=True), params)
    place = functools.partial(jax.tree.map, lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip))
    sums = {}
    for name, fn, tok in (("decode", registry.apply_decode, (B,)),
                          ("verify", registry.apply_verify, (B, KQ))):
        text = jax.jit(lambda p, t, c, fn=fn: fn(p, cfg, t, c)).lower(
            place(params), place(jax.ShapeDtypeStruct(tok, jnp.int32)),
            place(cache)).compile().as_text()
        assert "tpu_custom_call" in text
        sums[name] = {
            (m.group(1).count(",") + 1, m.group(2))
            for line in text.splitlines() if 'reduce_sum"' in line
            for m in [re.search(r"= f32\[([\d,]+)\]\S* reduce\(.*"
                                r"dimensions=\{([\d,]+)\}", line)] if m}
    assert sums["decode"] and sums["verify"] == sums["decode"], sums
