"""Paged flash-decode parity + paged-engine equivalence (PR 4, DESIGN.md §6).

Three contracts:

1. **Backend parity** — ``dispatch.paged_decode_attention`` on
   ``pallas-interpret`` is bit-identical to the ``xla-ref`` oracle for
   every kv_quant × window × GQA-group configuration.
2. **Layout parity** — for the same token stream, the paged pool (blocks
   scattered anywhere, reached through the block table) produces output
   bit-identical to the dense ring path run with the same cache tile
   (bs == bk): the recurrence is step-for-step the same, which is the
   bit-reusability property that makes prefix blocks shareable.
3. **Engine equivalence** — the paged engine (continuous batching, block
   allocation, paged prefill) emits exactly the ring engine's tokens, and
   a prefix-cache hit produces the same logits/tokens as a cold prefill of
   the full prompt.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import dispatch
from repro.models import registry
from repro.serve import Engine, Request, SamplingParams

CFG = get_config("smollm_135m").reduced()
PARAMS = registry.init_model(jax.random.PRNGKey(0), CFG)


def _dual_layout_inputs(seed, *, b=3, bs=16, max_len=64, nkv=2, group=2,
                        hd=32, quantized=True, pos_vals=(5, 40, 63)):
    """One token stream materialised in BOTH cache layouts: the dense ring
    (k_pos-tracked) and the paged pool (blocks permuted through a block
    table, plus a trash block holding poison).  Written position-major
    below, then stored heads-major with positions minor, as the engine
    stores them: ring (b, nkv, hd, max_len), pool (blocks, nkv, hd, bs),
    scales (…, nkv, positions) — each a layer-stacked cache of one layer
    (``layer=0``)."""
    rng = np.random.default_rng(seed)
    nbmax = max_len // bs
    num_blocks = b * nbmax
    q = jnp.asarray(rng.normal(size=(b, nkv, group, hd)), jnp.bfloat16)
    pos = jnp.asarray(pos_vals[:b], jnp.int32)

    if quantized:
        def draw_kv():
            return rng.integers(-127, 128, size=(2, nkv, hd))
        kdt, sdt = np.int8, np.float32
    else:
        def draw_kv():
            return rng.normal(size=(2, nkv, hd))
        kdt, sdt = np.float32, np.float32

    ring = {n: np.zeros((b, max_len, nkv, hd), kdt) for n in ("k", "v")}
    ring_s = {n: np.zeros((b, max_len, nkv), sdt) for n in ("ks", "vs")}
    kpos = np.full((b, max_len), -1, np.int64)
    pool = {n: np.zeros((num_blocks + 1, bs, nkv, hd), kdt) for n in ("k", "v")}
    pool_s = {n: np.zeros((num_blocks + 1, bs, nkv), sdt) for n in ("ks", "vs")}
    # poison the trash block: it must never be read (unallocated entries)
    for n in ("k", "v"):
        pool[n][num_blocks] = 111 if quantized else 1e4
    bt = np.full((b, nbmax), num_blocks, np.int32)
    perm = rng.permutation(num_blocks)
    nalloc = 0
    for i in range(b):
        for p in range(int(pos_vals[i]) + 1):
            kv = draw_kv()
            sc = rng.uniform(0.1, 2.0, size=(2, nkv))
            ring["k"][i, p], ring["v"][i, p] = kv
            ring_s["ks"][i, p], ring_s["vs"][i, p] = sc
            kpos[i, p] = p
            j, t = p // bs, p % bs
            if t == 0:
                bt[i, j] = perm[nalloc]
                nalloc += 1
            phys = bt[i, j]
            pool["k"][phys, t], pool["v"][phys, t] = kv
            pool_s["ks"][phys, t], pool_s["vs"][phys, t] = sc

    cast = jnp.int8 if quantized else jnp.bfloat16

    def store(x, dt=None):    # position axis 1 → minor; a stack of one
        return jnp.asarray(np.moveaxis(x, 1, -1), dt)[None]

    out = dict(
        q=q, pos=pos,
        ring_k=store(ring["k"], cast), ring_v=store(ring["v"], cast),
        k_pos=jnp.asarray(kpos, jnp.int32)[None],
        pool_k=store(pool["k"], cast), pool_v=store(pool["v"], cast),
        bt=jnp.asarray(bt),
    )
    if quantized:
        out.update(ring_ks=store(ring_s["ks"]), ring_vs=store(ring_s["vs"]),
                   pool_ks=store(pool_s["ks"]), pool_vs=store(pool_s["vs"]))
    else:
        out.update(ring_ks=None, ring_vs=None, pool_ks=None, pool_vs=None)
    return out


# ---------------------------------------------------------------------------
# 1+2: backend parity and layout parity, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("window", [0, 16], ids=["full", "window16"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_paged_interpret_bit_identical_to_xla_ref(quantized, window, group):
    """Bitwise for every GQA group, group == 1 included: kernel and oracle
    run one head-batched dot, so a single-row (GEMV-shaped) contraction is
    summed in the same order on both sides (an unbatched one-row dot on
    XLA's CPU backend is not — that once forced an allclose pin here)."""
    d = _dual_layout_inputs(group, group=group, quantized=quantized)
    out_i = dispatch.paged_decode_attention(
        d["q"], d["pool_k"], d["pool_v"], d["bt"], d["pos"],
        k_scale=d["pool_ks"], v_scale=d["pool_vs"], layer=0, window=window,
        backend="pallas-interpret")
    out_r = dispatch.paged_decode_attention(
        d["q"], d["pool_k"], d["pool_v"], d["bt"], d["pos"],
        k_scale=d["pool_ks"], v_scale=d["pool_vs"], layer=0, window=window,
        backend="xla-ref")
    assert out_i.dtype == jnp.float32
    assert jnp.array_equal(out_i, out_r), (quantized, window, group)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("window", [0, 16], ids=["full", "window16"])
def test_paged_bit_identical_to_ring_same_stream(quantized, window):
    """Acceptance: for the same token stream, paged output == ring output
    *bitwise* when the ring runs the pool's block size as its cache tile —
    the recurrences are step-for-step identical, so where a block lives
    (contiguous ring slot vs permuted pool block) cannot matter.  The
    paged trash block is poisoned, so this also proves unallocated table
    entries never leak in."""
    bs = 16
    d = _dual_layout_inputs(7, bs=bs, quantized=quantized)
    for backend in ("xla-ref", "pallas-interpret"):
        ring = dispatch.decode_attention(
            d["q"], d["ring_k"], d["ring_v"], d["k_pos"], d["pos"],
            k_scale=d["ring_ks"], v_scale=d["ring_vs"], layer=0, window=window,
            block=(bs,), backend=backend)
        paged = dispatch.paged_decode_attention(
            d["q"], d["pool_k"], d["pool_v"], d["bt"], d["pos"],
            k_scale=d["pool_ks"], v_scale=d["pool_vs"], layer=0, window=window,
            backend=backend)
        assert jnp.array_equal(ring, paged), (quantized, window, backend)


def test_paged_gqa_and_single_block_edge():
    """MQA-style group=4 with a cache exactly one block long (bs == max_len)
    — the recurrence degenerates to a single masked softmax pass."""
    d = _dual_layout_inputs(3, b=2, bs=32, max_len=32, group=4,
                            quantized=True, pos_vals=(0, 31))
    out_i = dispatch.paged_decode_attention(
        d["q"], d["pool_k"], d["pool_v"], d["bt"], d["pos"],
        k_scale=d["pool_ks"], v_scale=d["pool_vs"], layer=0,
        backend="pallas-interpret")
    out_r = dispatch.paged_decode_attention(
        d["q"], d["pool_k"], d["pool_v"], d["bt"], d["pos"],
        k_scale=d["pool_ks"], v_scale=d["pool_vs"], layer=0,
        backend="xla-ref")
    assert jnp.array_equal(out_i, out_r)
    assert not bool(jnp.any(jnp.isnan(out_r)))


# ---------------------------------------------------------------------------
# 3: engine equivalence (cold, prefix-hit, preemption-resume)
# ---------------------------------------------------------------------------


def _prompts(seed, n, length):
    key = jax.random.PRNGKey(seed)
    return np.asarray(
        jax.random.randint(key, (n, length), 1, CFG.vocab_size)).tolist()


def _run_engine(prompts, max_new, *, kv_layout="ring", max_len=32, **kw):
    eng = Engine(PARAMS, CFG, batch=len(prompts), max_len=max_len,
                 kv_layout=kv_layout, **kw)
    for r, p in enumerate(prompts):
        eng.submit(Request(rid=r, prompt=list(p), max_new=max_new))
    done = sorted(eng.run(60 + 4 * max_new), key=lambda r: r.rid)
    return eng, [r.out for r in done]


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
def test_paged_engine_matches_ring_engine(kv_quant):
    """Cold paged serving (block-aligned scatter, paged prefill, paged
    flash-decode) emits exactly the ring engine's greedy tokens.  The ring
    cap equals the pool block size here, so both layouts run the identical
    single-block recurrence and the streams must match token for token."""
    prompts = _prompts(0, 2, 5)
    _, ring = _run_engine(prompts, 6, kv_layout="ring", kv_quant=kv_quant)
    _, paged = _run_engine(prompts, 6, kv_layout="paged", block_size=32,
                           kv_quant=kv_quant)
    assert paged == ring


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
def test_prefix_hit_matches_cold_prefill(kv_quant):
    """Acceptance: a prefix-cache hit produces the same tokens as a cold
    prefill of the full prompt — the shared blocks hold exactly the codes
    a cold prefill would write (counter = absolute position), and the
    suffix attends them through the pool gather."""
    shared = _prompts(11, 1, 8)[0]                # 2 full blocks at bs=4

    def serve(prefix_cache):
        eng = Engine(PARAMS, CFG, batch=2, max_len=32, kv_layout="paged",
                     block_size=4, kv_quant=kv_quant,
                     prefix_cache=prefix_cache)
        for r in range(4):
            eng.submit(Request(rid=r, prompt=shared + [10 + r, 30 + r],
                               max_new=5))
        done = sorted(eng.run(100), key=lambda r: r.rid)
        return eng, [r.out for r in done]

    hit_eng, hit = serve(True)
    cold_eng, cold = serve(False)
    assert hit == cold
    assert hit_eng.stats["prefix_hit_tokens"] > 0
    assert cold_eng.stats["prefix_hit_tokens"] == 0


def test_paged_preemption_resumes_not_reprefills():
    """A starved pool (fewer blocks than the active set needs) preempts a
    request back through the scheduler with its blocks intact; the resumed
    stream equals unconstrained serial execution — nothing re-prefilled,
    nothing lost (the PR-4 replacement for the ring 'preempted' finish)."""
    prompts = [[1 + r, 2, 3] for r in range(3)]
    eng = Engine(PARAMS, CFG, batch=2, max_len=32, kv_layout="paged",
                 block_size=4, num_blocks=5, prefix_cache=False)
    for r, p in enumerate(prompts):
        eng.submit(Request(rid=r, prompt=list(p), max_new=8))
    done = sorted(eng.run(200), key=lambda r: r.rid)
    assert eng.stats["preemptions"] >= 1
    assert [r.finish_reason for r in done] == ["length"] * 3
    # serial ring reference: one slot, plenty of cache
    ref = Engine(PARAMS, CFG, batch=1, max_len=32)
    for r, p in enumerate(prompts):
        ref.submit(Request(rid=r, prompt=list(p), max_new=8))
    ref_done = sorted(ref.run(200), key=lambda r: r.rid)
    assert [r.out for r in done] == [r.out for r in ref_done]


def test_paged_deadlock_breaks_via_reprefill():
    """Every block held by preempted queued requests and nothing active:
    the engine flips victims to re-prefill mode and completes the whole
    wave.  Output lengths and finish reasons are exact; token values after
    a re-prefill resume are only rounding-equal to the uninterrupted run
    (deeper-layer KV re-enters through the batched prefill — the
    prefill≡decode divergence pinned since PR 2), so this pins liveness +
    budget, not the stream."""
    eng = Engine(PARAMS, CFG, batch=2, max_len=16, kv_layout="paged",
                 block_size=4, num_blocks=3, prefix_cache=False)
    for r in range(2):
        eng.submit(Request(rid=r, prompt=[1 + r, 2, 3], max_new=10))
    done = sorted(eng.run(300), key=lambda r: r.rid)
    assert [(r.rid, len(r.out), r.finish_reason) for r in done] == \
        [(0, 10, "length"), (1, 10, "length")]
    assert eng.stats["preemptions"] >= 2
    assert eng.pool.live_blocks == 0


def test_paged_pool_capacity_below_dense_ring():
    """The headline memory property: a pool sized well under
    batch × max_len serves a full wave whose *live* token demand fits,
    where the dense ring would have needed cap × slots up front."""
    eng = Engine(PARAMS, CFG, batch=4, max_len=64, kv_layout="paged",
                 block_size=8, num_blocks=12)   # 96 slots vs 256 dense
    for r in range(4):
        eng.submit(Request(rid=r, prompt=[1 + r, 2, 3, 4], max_new=6))
    done = sorted(eng.run(120), key=lambda r: r.rid)
    assert len(done) == 4
    assert all(len(r.out) == 6 for r in done)
    assert eng.pool.live_blocks == 0            # all released on finish


def test_paged_restart_determinism():
    """Replaying the same submissions on a fresh paged engine reproduces
    every token — counters are position-keyed, block placement is
    irrelevant to the math."""
    def run():
        eng = Engine(PARAMS, CFG, batch=2, max_len=32, kv_layout="paged",
                     block_size=4, kv_quant=True)
        for r in range(4):
            eng.submit(Request(
                rid=r, prompt=[1 + r, 2, 3, 4, 5],
                sampling=SamplingParams(temperature=0.8, top_k=16, seed=r,
                                        max_new=5, counter_offset=100 * r)))
        return [(r.rid, tuple(r.out), r.finish_reason)
                for r in sorted(eng.run(80), key=lambda r: r.rid)]

    assert run() == run()


def test_paged_rejects_unservable_requests():
    eng = Engine(PARAMS, CFG, batch=1, max_len=8, kv_layout="paged",
                 block_size=4, num_blocks=1)
    eng.submit(Request(rid=0, prompt=list(range(1, 20)), max_new=4))
    eng.submit(Request(rid=1, prompt=[1, 2, 3, 4, 5], max_new=4))  # > 1 block
    done = sorted(eng.run(20), key=lambda r: r.rid)
    assert [r.finish_reason for r in done] == ["rejected", "rejected"]


@pytest.mark.parametrize("path", ["prefill", "decode"])
def test_pool_codes_are_the_position_major_codes_transposed(path):
    """Prefix-block reuse rests on the codes a position holds being a pure
    function of (value, position + request offset, element index): the
    position-major pool stored the quantiser's (…, n_kv, hd) output as it
    came.  The heads-major pool must hold exactly those codes and scales,
    only transposed.  K/V values go in at known positions through the paged
    prefill's block scatter or the decode step's per-position write, into
    one layer of a layer-stacked pool, and a transposed read of every
    written block equals the position-major quantiser output bitwise; the
    other layers and the padding scale rows stay zero."""
    from repro.models import transformer as tfm

    rng = np.random.default_rng(21)
    b, s, nkv, hd, bs, layers, layer = 2, 24, 2, 8, 8, 3, 1
    nbmax, nbp = s // bs, 2 * (s // bs) + 1
    srows = tfm.scale_rows(nkv)
    bt = jnp.asarray(rng.permutation(nbp - 1).reshape(b, nbmax), jnp.int32)
    off = jnp.asarray([0, 1000], jnp.int32)
    kv = [jnp.asarray(rng.normal(size=(b, s, nkv, hd)), jnp.bfloat16)
          for _ in range(2)]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    # the position-major codes and scales, as the quantiser returns them
    idx4 = tfm._kv_elem_idx(nkv, hd)
    want = [tfm._kv_q8(x, (pos + off[:, None])[:, :, None, None], idx4, seed)
            for x, seed in zip(kv, (101, 102))]

    stack = {name: jnp.zeros(shape, jnp.int8 if name in "kv"
                             else jnp.float32)
             for name, shape in tfm.kv_shapes(
                 (layers, nbp), nkv, hd, bs, quantized=True).items()}
    assert stack["k_scale"].shape[-2] == srows
    if path == "prefill":
        got = tfm._paged_scatter_entry(
            stack, layer, kv[0], kv[1], pos, jnp.full((b,), s, jnp.int32),
            jnp.zeros((b,), jnp.int32), bt, True, off)
    else:                      # one token a tick, as _attention_decode writes
        got = stack
        for t in range(s):
            rows = bt[:, t // bs][:, None]
            slots = jnp.full((b, 1), t % bs, jnp.int32)
            for (codes, scales), c, sc in zip(want, ("k", "v"),
                                             ("k_scale", "v_scale")):
                got[c] = tfm._write_positions(got[c], layer, rows, slots,
                                              codes[:, t:t + 1])
                got[sc] = tfm._write_positions(got[sc], layer, rows, slots,
                                               scales[:, t:t + 1])

    for (codes, scales), c, sc in zip(want, ("k", "v"), ("k_scale", "v_scale")):
        for i in range(b):
            for j in range(nbmax):
                blk = slice(j * bs, (j + 1) * bs)
                phys = int(bt[i, j])
                # transposed read: positions back to the major axis
                read = jnp.moveaxis(got[c][layer, phys], -1, 0)
                read_s = jnp.moveaxis(got[sc][layer, phys], -1, 0)
                assert jnp.array_equal(read, codes[i, blk]), (c, i, j)
                assert jnp.array_equal(read_s[:, :nkv], scales[i, blk])
                assert not jnp.any(read_s[:, nkv:])       # padding rows
        others = [lyr for lyr in range(layers) if lyr != layer]
        assert not jnp.any(got[c][jnp.asarray(others)])
        assert not jnp.any(got[sc][jnp.asarray(others)])


@pytest.mark.parametrize("length", [384, 24])
def test_write_positions_matches_an_elementwise_scatter(length):
    """The whole-row write equals writing each column on its own: several
    writes per slot — two in one row, one past the end (dropped) — into
    one layer of a layer-stacked array, for K/V columns, scale columns
    (fewer values than padded rows) and ring positions."""
    from repro.models import transformer as tfm

    rng = np.random.default_rng(length)
    layers, layer, rows_n, nkv, hd = 3, 2, 4, 2, 8
    srows = tfm.scale_rows(nkv)
    rows = jnp.asarray([[1, 1, 1], [3, 3, 0]], jnp.int32)
    edge = min(127, length - 2)
    slots = jnp.asarray([[edge, edge + 1, length],
                         [5, 6, length - 1]], jnp.int32)
    for inner, vshape in (((nkv, hd), (nkv, hd)), ((srows,), (nkv,)),
                          ((), ())):
        arr = jnp.asarray(rng.normal(size=(layers, rows_n) + inner
                                     + (length,)), jnp.float32)
        vals = jnp.asarray(rng.normal(size=(2, 3) + vshape), jnp.float32)
        want = np.array(arr)
        for bi in range(2):
            for u in range(3):
                if int(slots[bi, u]) < length:
                    col = np.zeros(inner, np.float32)
                    col[tuple(slice(0, n) for n in vshape)] = vals[bi, u]
                    want[layer, int(rows[bi, u]), ..., int(slots[bi, u])] = col
        got = tfm._write_positions(arr, layer, rows, slots, vals)
        assert np.array_equal(np.asarray(got), want), inner


def test_engine_refuses_a_sub_lane_pool_block_on_tpu(monkeypatch):
    """On the chip a pool block must be whole 128-position lane tiles
    (``decode_attention.check_pool_block``): the engine refuses an
    explicit 16 up front and picks a lane multiple when left to choose."""
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="multiple of 128"):
        Engine(PARAMS, CFG, batch=2, max_len=32, kv_layout="paged",
               block_size=16, kv_quant=True)
    eng = Engine(PARAMS, CFG, batch=2, max_len=512, kv_layout="paged",
                 kv_quant=True)
    assert eng.block_size == 128
