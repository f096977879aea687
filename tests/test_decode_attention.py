"""Decode-attention kernel parity + single-dispatch engine tick (PR 3).

The dispatch contract for ``decode_attention`` is the split-K online-softmax
recurrence over cache-length blocks (kernels/ref.decode_attention_ref): the
``pallas-interpret`` kernel must be **bit-identical** to the ``xla-ref``
oracle for the same block across kv_quant on/off × sliding window on/off ×
GQA group sizes; the oracle itself must match the pre-kernel full-softmax
einsum path to float-association tolerance; and the engine's fused
``decode_and_sample`` tick must reproduce the PR-2 two-call
(decode_step + sample_tokens) token stream exactly."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_argmax_margin

from repro.configs import get_config
from repro.kernels import dispatch, ref
from repro.kernels.decode_attention import shrink_block
from repro.models import registry
from repro.serve import Engine, Request, SamplingParams, make_serve_fns
from repro.serve.engine import make_decode_and_sample
from repro.serve.sampling import sample_tokens


def _ring_inputs(seed, *, b=3, cap=64, nkv=2, group=2, hd=32, quantized=False,
                 pos_vals=(5, 40, 63)):
    """A realistic ring-cache snapshot: slot s of row i holds the latest
    prompt/decode position p ≡ s (mod cap) with p ≤ pos_i; unwritten slots
    carry k_pos = -1 (and arbitrary codes — masking must hide them).  Drawn
    position-major, stored as the engine stores the ring: heads-major,
    positions minor — K/V (b, nkv, hd, cap), scales (b, nkv, cap) — as a
    layer-stacked cache of one layer (``layer=0``)."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, nkv, group, hd)), jnp.bfloat16)
    pos = jnp.asarray(pos_vals[:b], jnp.int32)
    kpos = np.full((b, cap), -1, np.int64)
    for i in range(b):
        for p in range(int(pos_vals[i]) + 1):
            kpos[i, p % cap] = p
    k_pos = jnp.asarray(kpos, jnp.int32)
    if quantized:
        k = jnp.asarray(rng.integers(-127, 128, size=(b, cap, nkv, hd)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=(b, cap, nkv, hd)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.1, 2.0, size=(b, cap, nkv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.1, 2.0, size=(b, cap, nkv)), jnp.float32)
    else:
        k = jnp.asarray(rng.normal(size=(b, cap, nkv, hd)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(b, cap, nkv, hd)), jnp.bfloat16)
        ks = vs = None
    k, v, ks, vs = (_minor(x) for x in (k, v, ks, vs))
    k, v, k_pos, ks, vs = (None if x is None else x[None]
                           for x in (k, v, k_pos, ks, vs))
    return q, k, v, k_pos, pos, ks, vs


def _minor(x):
    """Position-major (b, cap, ...) → the stored (b, ..., cap)."""
    return None if x is None else jnp.moveaxis(x, 1, -1)


def _major(x):
    """The stored (b, ..., cap) → position-major (b, cap, ...)."""
    return None if x is None else jnp.moveaxis(x, -1, 1)


def _einsum_baseline(q, k, v, k_pos, pos, ks, vs, window):
    """The pre-PR-3 ``_attention_decode`` einsum path, f32 logits/probs (the
    old path additionally rounded logits and probabilities to bf16; f32 here
    isolates the association difference from that storage rounding).  Takes
    the stored ring layout, a stack of one layer."""
    k_pos = k_pos[0]
    k, v, ks, vs = (_major(None if x is None else x[0])
                    for x in (k, v, ks, vs))
    b, cap, nkv, hd = k.shape
    group = q.shape[2]
    logits = jnp.einsum("bhgd,bkhd->bhgk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    if ks is not None:
        logits = logits * (ks / 127.0).transpose(0, 2, 1)[:, :, None, :]
    posb = jnp.broadcast_to(pos, (b,))
    valid = (k_pos >= 0) & (k_pos <= posb[:, None])
    if window:
        valid = valid & (k_pos > posb[:, None] - window)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if vs is not None:
        probs = probs * (vs / 127.0).transpose(0, 2, 1)[:, :, None, :]
    return jnp.einsum("bhgk,bkhd->bhgd", probs, v.astype(jnp.float32))


# ---------------------------------------------------------------------------
# backend parity: pallas-interpret ≡ xla-ref, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("window", [0, 16], ids=["full", "window16"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_interpret_bit_identical_to_xla_ref(quantized, window, group):
    """The Pallas kernel body mirrors the oracle's recurrence op-for-op, so
    interpret mode is bit-identical for every kv_quant × window × GQA-group
    configuration and every block size."""
    q, k, v, k_pos, pos, ks, vs = _ring_inputs(
        group, group=group, quantized=quantized)
    for bk in (16, 64):
        out_i = dispatch.decode_attention(
            q, k, v, k_pos, pos, k_scale=ks, v_scale=vs, layer=0,
            window=window, block=(bk,), backend="pallas-interpret")
        out_r = dispatch.decode_attention(
            q, k, v, k_pos, pos, k_scale=ks, v_scale=vs, layer=0,
            window=window, block=(bk,), backend="xla-ref")
        assert out_i.dtype == jnp.float32
        assert jnp.array_equal(out_i, out_r), (quantized, window, group, bk)


def test_interpret_autotuned_block_matches_explicit():
    """block=None routes Pallas backends through the autotuner's VMEM-model
    pick; the result must equal the same explicitly-passed block."""
    from repro.kernels import autotune

    q, k, v, k_pos, pos, ks, vs = _ring_inputs(9, quantized=True)
    picked = autotune.best_block(
        "decode_attention", (3, 64, 2, 2, 32), "int8", 8, "flash",
        "pallas-interpret")
    auto = dispatch.decode_attention(q, k, v, k_pos, pos, k_scale=ks,
                                     v_scale=vs, layer=0,
                                     backend="pallas-interpret")
    explicit = dispatch.decode_attention(q, k, v, k_pos, pos, k_scale=ks,
                                         v_scale=vs, layer=0,
                                         block=tuple(picked),
                                         backend="pallas-interpret")
    assert jnp.array_equal(auto, explicit)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("window", [0, 16], ids=["full", "window16"])
def test_oracle_matches_full_softmax_einsum(quantized, window):
    """The split-K recurrence equals the full-softmax einsum path up to
    float-summation association (it is *more* precise than the retired
    in-model path, which stored logits and probabilities in bf16)."""
    q, k, v, k_pos, pos, ks, vs = _ring_inputs(11, quantized=quantized)
    out_r = ref.decode_attention_ref(q, k, v, k_pos, pos, ks, vs, layer=0,
                                     window=window, block=(16,))
    base = _einsum_baseline(q, k, v, k_pos, pos, ks, vs, window)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(base),
                               rtol=2e-4, atol=2e-4)


def _as_pool(x, bt, bs):
    """Scatter an unwrapped stored ring array (1, B, ..., cap) into a paged
    pool (1, blocks, ..., bs) through block table ``bt``: logical block j
    of slot b lands at physical block bt[b, j]."""
    if x is None:
        return None
    x = _major(x[0])
    b, cap = x.shape[:2]
    blocks = x.reshape((b * (cap // bs), bs) + x.shape[2:])
    order = np.argsort(np.asarray(bt).reshape(-1))
    return _minor(blocks[order])[None]


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("window", [0, 16], ids=["full", "window16"])
@pytest.mark.parametrize("kind", ["paged-decode", "ring-verify",
                                  "paged-verify"])
def test_paged_and_verify_oracles_match_full_softmax_einsum(kind, quantized,
                                                            window):
    """Paged decode and each verify row (query at pos+t, keys up to pos+t)
    equal the full-softmax einsum at that position up to float-summation
    association — an independent check of the gather, mask, scale fold and
    row position that the bitwise interpret ≡ xla-ref pins cannot give."""
    kq, bs = 3, 16
    # the cache is written through pos + kq - 1 and never wraps, so ring
    # slot p holds position p and a pool block's implicit positions agree
    q, k, v, k_pos, last, ks, vs = _ring_inputs(
        17, quantized=quantized, pos_vals=(7, 42, 62))
    pos = last - (kq - 1)
    b, cap = k_pos.shape[1:]
    bt = jnp.asarray(np.random.default_rng(3).permutation(b * cap // bs)
                     .reshape(b, cap // bs), jnp.int32)
    pool = [_as_pool(x, bt, bs) for x in (k, v, ks, vs)]
    qv = jnp.asarray(np.random.default_rng(5).normal(
        size=(b, kq) + q.shape[1:]), jnp.bfloat16)
    if kind == "paged-decode":
        outs = [ref.paged_decode_attention_ref(q, *pool[:2], bt, last,
                                               *pool[2:], layer=0,
                                               window=window)]
        qs, ps = [q], [last]
    elif kind == "ring-verify":
        out = ref.verify_attention_ref(qv, k, v, k_pos, pos, ks, vs,
                                       layer=0, window=window, block=(16,))
        outs = [out[:, t] for t in range(kq)]
        qs, ps = [qv[:, t] for t in range(kq)], [pos + t for t in range(kq)]
    else:
        out = ref.paged_verify_attention_ref(qv, *pool[:2], bt, pos,
                                             *pool[2:], layer=0,
                                             window=window)
        outs = [out[:, t] for t in range(kq)]
        qs, ps = [qv[:, t] for t in range(kq)], [pos + t for t in range(kq)]
    for o, qt, pt in zip(outs, qs, ps):
        base = _einsum_baseline(qt, k, v, k_pos, pt, ks, vs, window)
        np.testing.assert_allclose(np.asarray(o), np.asarray(base),
                                   rtol=2e-4, atol=2e-4)


def test_block_size_invariance_and_masked_slot_independence():
    """The recurrence result is block-size independent (to association
    noise), and slots hidden by the mask — unwritten, future, or outside
    the sliding window — cannot leak into the output even with poisoned
    codes."""
    q, k, v, k_pos, pos, ks, vs = _ring_inputs(13, quantized=True)
    outs = [ref.decode_attention_ref(q, k, v, k_pos, pos, ks, vs, layer=0,
                                     window=16, block=(bk,))
            for bk in (8, 16, 32, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-6)

    # poison every masked slot's codes and scales: masked logits become the
    # -1e30 sentinel either way and exp to exactly 0, so the output must be
    # *bitwise* unchanged
    posb = np.asarray(pos)[:, None]
    kp = np.asarray(k_pos)[0]
    valid = jnp.asarray((kp >= 0) & (kp <= posb) & (kp > posb - 16))
    vm = valid[:, None, None, :]          # positions are minor, under the
    #                                       stack of one (broadcast)
    k_bad = jnp.where(vm, k, jnp.int8(127))
    v_bad = jnp.where(vm, v, jnp.int8(-128))
    ks_bad = jnp.where(valid[:, None, :], ks, 1e4)
    vs_bad = jnp.where(valid[:, None, :], vs, 1e4)
    clean = ref.decode_attention_ref(q, k, v, k_pos, pos, ks, vs, layer=0,
                                     window=16, block=(16,))
    poisoned = ref.decode_attention_ref(q, k_bad, v_bad, k_pos, pos, ks_bad,
                                        vs_bad, layer=0, window=16,
                                        block=(16,))
    assert jnp.array_equal(clean, poisoned)


def test_shrink_block_divides_cap():
    assert shrink_block(512, 64) == 64
    assert shrink_block(48, 64) == 32
    assert shrink_block(1, 7) == 1
    assert shrink_block(7, 7) == 7


# ---------------------------------------------------------------------------
# engine: fused decode_and_sample ≡ the PR-2 two-call tick
# ---------------------------------------------------------------------------

CFG = get_config("smollm_135m").reduced()
PARAMS = registry.init_model(jax.random.PRNGKey(0), CFG)


def test_decode_and_sample_matches_two_call_path():
    """One fused dispatch per tick emits exactly the tokens the PR-2 engine's
    separate jit(decode_step) + jit(sample_tokens) calls produced, over a
    multi-tick greedy + temperature mix on the int8 cache."""
    batch, max_len = 2, 32
    prefill_step, decode_step = make_serve_fns(
        CFG, None, max_len=max_len, kv_quant=True)
    fused = jax.jit(make_decode_and_sample(CFG, None))
    decode = jax.jit(decode_step)
    sample = jax.jit(sample_tokens)

    toks = jnp.asarray([[3, 1, 4, 1], [5, 9, 2, 6]], jnp.int32)
    lengths = jnp.full((batch,), 4, jnp.int32)
    offsets = jnp.asarray([0, 1000], jnp.int32)
    temps = jnp.asarray([0.0, 0.9], jnp.float32)
    topks = jnp.asarray([0, 8], jnp.int32)
    seeds = jnp.asarray([0, 7], jnp.int32)

    last_logits, cache_a = jax.jit(prefill_step)(PARAMS, toks, lengths,
                                                 offsets, 0)
    counters = offsets
    token = sample(last_logits, temps, topks, seeds, counters)
    counters = counters + 1
    cache_b = jax.tree.map(jnp.copy, cache_a)

    token_a = token_b = token
    ctr_a = ctr_b = counters
    for tick in range(6):
        token_a, ctr_a, cache_a = fused(PARAMS, token_a, cache_a, offsets,
                                        tick, temps, topks, seeds, ctr_a)
        logits, cache_b = decode(PARAMS, token_b, cache_b, offsets, tick)
        # slot 0 decodes greedily: its fused ≡ two-call parity assumes the
        # argmax isn't a float coin-flip between the two logit paths
        assert_argmax_margin(logits[0], min_margin=1e-3,
                             context=f"greedy slot 0, tick {tick}")
        token_b = sample(logits, temps, topks, seeds, ctr_b)
        ctr_b = ctr_b + 1
        assert jnp.array_equal(token_a, token_b), tick
        assert jnp.array_equal(ctr_a, ctr_b)


def test_engine_stream_matches_manual_two_call_loop():
    """Full engine (device-resident state, donated cache, fused tick) vs a
    hand-driven PR-2-style loop with the same single admission wave: every
    emitted token identical."""
    batch, max_len, max_new = 2, 32, 5
    prompts = [[3, 1, 4, 1, 5], [2, 7, 1, 8, 2]]
    sp = [SamplingParams(temperature=0.0, max_new=max_new),
          SamplingParams(temperature=1.1, top_k=16, seed=4, max_new=max_new,
                         counter_offset=500)]

    eng = Engine(PARAMS, CFG, batch=batch, max_len=max_len, kv_quant=True)
    for r, p in enumerate(prompts):
        eng.submit(Request(rid=r, prompt=list(p), sampling=sp[r]))
    done = sorted(eng.run(40), key=lambda r: r.rid)
    got = [r.out for r in done]

    # manual PR-2-style loop: jitted prefill, then decode + sample per tick
    prefill_step, decode_step = make_serve_fns(
        CFG, None, max_len=max_len, kv_quant=True)
    prefill = jax.jit(prefill_step)
    decode = jax.jit(decode_step)
    sample = jax.jit(sample_tokens)
    toks = jnp.asarray(prompts, jnp.int32)
    lengths = jnp.full((batch,), 5, jnp.int32)
    offsets = jnp.asarray([s.counter_offset for s in sp], jnp.int32)
    temps = jnp.asarray([s.temperature for s in sp], jnp.float32)
    topks = jnp.asarray([s.top_k for s in sp], jnp.int32)
    seeds = jnp.asarray([s.seed for s in sp], jnp.int32)

    last_logits, cache = prefill(PARAMS, toks, lengths, offsets, 0)
    counters = offsets
    assert_argmax_margin(last_logits[0], min_margin=1e-3,
                         context="greedy slot 0, prefill logits")
    token = sample(last_logits, temps, topks, seeds, counters)
    counters = counters + 1
    want = [[int(token[i])] for i in range(batch)]
    for tick in range(max_new - 1):
        logits, cache = decode(PARAMS, token, cache, offsets, tick)
        assert_argmax_margin(logits[0], min_margin=1e-3,
                             context=f"greedy slot 0, tick {tick}")
        token = sample(logits, temps, topks, seeds, counters)
        counters = counters + 1
        for i in range(batch):
            want[i].append(int(token[i]))
    assert got == want
