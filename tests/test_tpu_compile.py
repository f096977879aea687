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
from repro.models.transformer import kv_shapes

# smollm-135m (configs/smollm_135m.py): 9 query / 3 KV heads, head dim 64,
# d_model 576, d_ff 1536; serving batch 8, ring cap 2048, pool block 128
# (positions are the cache's minor axis, so a block is a whole number of
# 128-lane tiles), draft_k 4 (the engine's default verify width)
B, NKV, GROUP, HD, CAP, BS, KQ = 8, 3, 3, 64, 2048, 128, 4
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
    a TPU.  The cache is a layer-stacked stack of one, read at layer 0.
    The heads-major, positions-minor cache is not padded in HBM, and the
    tile's VMEM working set is under the budget."""
    kdt = jnp.int8 if kv == "int8" else jnp.bfloat16
    quantized = kv == "int8"
    verify = rows == "verify"
    qshape = (B, KQ, NKV, GROUP, HD) if verify else (B, NKV, GROUP, HD)
    if layout == "ring":
        arrays = kv_shapes((1, B), NKV, HD, CAP, quantized=quantized)
        extra = [((1, B, CAP), jnp.int32)]                    # k_pos
        block = autotune.best_block("decode_attention",
                                    (B, CAP, NKV, GROUP, HD), kv,
                                    8 if quantized else 16, "flash",
                                    "pallas-tpu")
        fn = functools.partial(verify_attention_call if verify
                               else decode_attention_call, block=block)
    else:
        nbmax = CAP // BS
        arrays = kv_shapes((1, B * nbmax + 1), NKV, HD, BS,   # + trash block
                           quantized=quantized)
        extra = [((B, nbmax), jnp.int32)]                     # block tables
        fn = (paged_verify_attention_call if verify
              else paged_decode_attention_call)
        block = (BS,)
    scales = ([(arrays["k_scale"], jnp.float32)] * 2 if quantized else [])
    assert autotune.decode_attention_vmem_bytes(
        block, nkv=NKV, hd=HD, group=GROUP, quantized=quantized,
        kq=KQ if verify else 1) <= autotune.VMEM_BUDGET_BYTES

    def step(q, k, v, aux, pos, *sc):
        ks, vs = sc if quantized else (None, None)
        return fn(q, k, v, aux, pos, ks, vs, layer=0, interpret=False)

    shapes = [(qshape, jnp.bfloat16), (arrays["k"], kdt), (arrays["v"], kdt),
              *extra, ((B,), jnp.int32), *scales]
    compiled = _compile(step, one_chip, *shapes)
    # the (hd, positions) and (scale rows, positions) planes are whole
    # tiles, so the compiler stores the cache as the kernel reads it
    logical = _logical_bytes(shapes)
    hbm = compiled.memory_analysis().argument_size_in_bytes
    assert logical <= hbm <= 1.15 * logical, (hbm, logical)


def test_pool_blocks_off_the_lane_tile_are_refused_for_v5e(one_chip):
    """A pool block of 16 positions (what the position-major pool ran on
    the chip) would lie on the 128-lane axis, a (64, 16) plane of a
    (64, 128) tile: the compiler stores such a pool in another layout and
    re-lays the whole pool out around every kernel call — the copy this
    layout exists to remove.  So the kernel refuses it on the chip, a
    128-position pool compiles with no relayout, and the autotuner offers
    the chip only lane multiples (128 for a short max_len)."""
    from repro.kernels.decode_attention import _flash_call, check_pool_block

    def pool_program(bs, checked):
        nbmax = CAP // bs
        arrays = kv_shapes((1, B * nbmax + 1), NKV, HD, bs, quantized=True)
        shapes = [((B, 1, NKV, GROUP, HD), jnp.bfloat16),
                  (arrays["k"], jnp.int8), (arrays["v"], jnp.int8),
                  ((B, nbmax), jnp.int32), ((B,), jnp.int32),
                  (arrays["k_scale"], jnp.float32),
                  (arrays["v_scale"], jnp.float32)]

        def step(q, k, v, bt, pos, ks, vs):
            if checked:
                return paged_verify_attention_call(
                    q, k, v, bt, pos, ks, vs, layer=0, interpret=False)
            return _flash_call(q, k, v, pos, jnp.zeros((1,), jnp.int32), bt,
                               ks, vs, None, bk=bs, window=0,
                               interpret=False)

        text = _compile(step, one_chip, *shapes).as_text()
        return _pool_moves(text, {arrays["k"], arrays["k_scale"]})

    with pytest.raises(ValueError, match="multiple of 128"):
        pool_program(16, checked=True)
    with pytest.raises(ValueError, match="multiple of 128"):
        check_pool_block(64)
    check_pool_block(256)
    assert not pool_program(128, checked=True)
    # the reason: compiled past the check, the 16-position pool is moved
    assert pool_program(16, checked=False)

    for max_len in (64, 256, 2048):
        for backend, ok in (("pallas-tpu", lambda b: b % 128 == 0),
                            ("xla-ref", lambda b: b <= max_len)):
            (pick,) = autotune.best_block(
                "paged_attention", (B, max_len, NKV, GROUP, HD), "int8", 8,
                "flash", backend)
            assert ok(pick), (backend, max_len, pick)


def test_ring_off_a_lane_multiple_compiles_for_v5e(one_chip):
    """A ring whose window or max_len is off a multiple of 128 positions
    — 2000 here, at minicpm-2b's 36 KV heads — is built a whole number of
    lane tiles long (2048), so the autotuner's tile divides it, fits the
    VMEM budget, and the kernel compiles unpadded."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.transformer import ring_len

    cfg = get_config("minicpm_2b")
    nkv, hd = cfg.n_kv_heads, cfg.hd()
    group = cfg.n_heads // nkv
    b, max_len = 4, 2000
    cap = ring_len(cfg, max_len)
    assert cap == 2048
    assert ring_len(dataclasses.replace(cfg, window=200), max_len) == 256
    assert ring_len(cfg, 96) == 96        # one lane tile or less: as is
    block = autotune.best_block("decode_attention",
                                (b, cap, nkv, group, hd), "int8", 8, "flash",
                                "pallas-tpu")
    assert block[0] % 128 == 0 and cap % block[0] == 0, block
    assert autotune.decode_attention_vmem_bytes(
        block, nkv=nkv, hd=hd, group=group,
        quantized=True) <= autotune.VMEM_BUDGET_BYTES
    arrays = kv_shapes((1, b), nkv, hd, cap, quantized=True)
    shapes = [((b, nkv, group, hd), jnp.bfloat16),
              (arrays["k"], jnp.int8), (arrays["v"], jnp.int8),
              ((1, b, cap), jnp.int32), ((b,), jnp.int32),
              (arrays["k_scale"], jnp.float32),
              (arrays["v_scale"], jnp.float32)]
    compiled = _compile(
        lambda q, k, v, kp, pos, ks, vs: decode_attention_call(
            q, k, v, kp, pos, ks, vs, layer=0, block=block,
            interpret=False), one_chip, *shapes)
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


def _pool_moves(text, shapes):
    """(name, opcode, shape) of every instruction of a compiled program —
    inside fusions too, and fusions named for what they fuse — that
    re-lays out, slices or rewrites an array of one of ``shapes``.  An
    asynchronous copy that keeps the array's row-major layout is a move
    between memory spaces (the compiler may stage a small array in VMEM
    for a whole program), not a relayout, and is not counted."""
    import re

    moves = ("copy", "dynamic-slice", "dynamic-update-slice")
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]"
                     r"\{([\d,]*)[^}]*\}\S* ([\w\-]+)\(", line)
        if not m:
            continue
        name, dims, layout, op = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        row_major = layout == ",".join(map(str, range(len(shape) - 1, -1, -1)))
        if op in ("copy-start", "copy-done") and row_major:
            continue
        if shape in shapes and (op.startswith("copy") or op in moves
                                or any(w in name for w in moves)):
            found.append((name, op, shape))
    return found


@pytest.mark.parametrize("program", ["fused_decode", "paged_prefill"])
def test_engine_programs_keep_the_pool_in_place_for_v5e(one_chip, monkeypatch,
                                                        program):
    """The paged int8 pool stays where it lies in the engine's two programs
    that write it: a 4-tick decode window (``jit_fused_decode``) and a
    prefix-hit paged prefill (``jit_paged_prefill``), each with the cache
    donated, at smollm widths with 2 layers, 8 slots and 128 + 1 blocks of
    256.  No copy, dynamic slice or dynamic update — fused or not — has the
    shape of the layer-stacked pool or of one layer's pool (K/V codes or
    their scales): the layer scan carries the stack in its loop state,
    writes land in whole blocks in place, and the kernel reads the stack
    through its layer and block indices.  The window's temporaries stay
    below the pool's own bytes, and the compiler stores every argument
    unpadded (≤ 1.15× its logical bytes)."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import dispatch
    from repro.models import registry
    from repro.serve.engine import make_fused_decode, make_paged_prefill

    # the model resolves its attention backend at trace time: take the
    # chip's, so the window holds the kernels the engine runs there
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    bs, nblocks, nbmax = 256, 128, CAP // 256
    cfg = dataclasses.replace(get_config("smollm_135m"), n_layers=2)
    params = jax.eval_shape(
        lambda: registry.init_model(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda p: registry.make_cache(
        p, cfg, B, CAP, kv_quant=True, kv_layout="paged", block_size=bs,
        num_blocks=nblocks), params)
    pool = jax.tree.leaves(cache["layers"])
    shapes = {leaf.shape for leaf in pool} | {leaf.shape[1:] for leaf in pool}
    i32, row = jnp.int32, jax.ShapeDtypeStruct((B,), jnp.int32)
    if program == "fused_decode":
        fn = jax.jit(make_fused_decode(cfg, None, n_ticks=4),
                     donate_argnums=(2,))
        args = (params, row, cache, row, jax.ShapeDtypeStruct((), i32),
                jax.ShapeDtypeStruct((B,), jnp.float32), row, row, row,
                jax.ShapeDtypeStruct((B,), jnp.bool_), row,
                jax.ShapeDtypeStruct((B, 8), i32))
    else:
        fn = jax.jit(functools.partial(
            make_paged_prefill(cfg, None, kv_quant=True),
            prefix_blocks=nbmax), donate_argnums=(5,))
        args = (params, jax.ShapeDtypeStruct((B, bs), i32), row, row,
                jax.ShapeDtypeStruct((B, nbmax), i32), cache, row,
                jax.ShapeDtypeStruct((), i32))
    args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), args)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert not _pool_moves(text, shapes), _pool_moves(text, shapes)
    mem = compiled.memory_analysis()
    if program == "fused_decode":
        assert "tpu_custom_call" in text
        pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in pool)
        assert mem.temp_size_in_bytes < pool_bytes, (
            mem.temp_size_in_bytes, pool_bytes)
    logical = _logical_bytes([(a.shape, a.dtype)
                              for a in jax.tree.leaves(args)])
    assert logical <= mem.argument_size_in_bytes <= 1.15 * logical, (
        mem.argument_size_in_bytes, logical)
