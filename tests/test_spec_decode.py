"""Speculative decoding over the dither KV cache (DESIGN.md §14).

Three test layers pin the draft-and-verify path:

* **Bulk-commit stream parity** — the spec engine's emitted token stream is
  bitwise the plain engine's for every ring/paged × bf16/int8-KV ×
  greedy/temperature configuration, including the accept-all edge (a replay
  oracle drafter: every window commits ``draft_k`` tokens) and the
  reject-at-every-position edge (an anti-replay drafter: every window
  commits exactly row 0).  This is the position-purity consequence the
  design leans on: a dither KV code is a function of (value, absolute
  position, element index) only — never of *when* or *how many at a time*
  the write happened — so a bulk commit of k accepted tokens writes the
  exact bytes sequential decode would have.

* **Verify-kernel backend parity** — ``verify_attention`` /
  ``paged_verify_attention`` are bit-identical between ``pallas-interpret``
  and the ``xla-ref`` oracle across kv_quant × GQA group × window, and the
  oracle's row ``t`` is bitwise the one-token decode oracle evaluated at
  ``pos + t`` over the same cache (rows drafted beyond ``pos + t`` are
  masked to exp() = 0.0 contributions at the same slot locations sequential
  decode leaves empty — identical association order, identical sums).

* **Rejected-suffix rollback** — after windows whose drafts all reject, the
  spec engine's cache bytes (and, paged, the pool's refcounts, free list
  and prefix-cache index) are identical to a never-drafted engine's at the
  same emitted length: ``spec_commit`` scrubs stale draft slots back to
  init values and ``KVPool.truncate`` exactly reverses ``append_block``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import dispatch
from repro.models import registry
from repro.serve import Engine, Request, SamplingParams
from repro.serve.draft import (Drafter, FixedDrafter, PromptLookupDrafter,
                               ReplayDrafter)

CFG = get_config("smollm_135m").reduced()
PARAMS = registry.init_model(jax.random.PRNGKey(0), CFG)


def _prompts(n, length=6):
    return [[(7 * r + i) % (CFG.vocab_size - 1) + 1 for i in range(length)]
            for r in range(n)]


class AntiReplayDrafter(Drafter):
    """Proposes the *wrong* token at every position: the recorded stream's
    token shifted by one in vocab space.  Guarantees reject-at-every-
    position (each verify window commits exactly row 0), which is the
    harness for the rollback tests — the engine still makes sequential
    progress, but every window exercises the scrub + truncate path."""

    def __init__(self, streams):
        self.replay = ReplayDrafter(streams)

    def propose(self, context, k):
        good = self.replay.propose(context, k)
        return [(t + 1) % CFG.vocab_size for t in good]


def _serve(*, spec, drafter=None, kv_layout="ring", kv_quant=False,
           temperature=0.0, max_new=8, requests=2, max_len=32, batch=2,
           draft_k=4):
    kw = {}
    if kv_layout == "paged":
        kw = dict(kv_layout="paged", block_size=4)
    eng = Engine(PARAMS, CFG, batch=batch, max_len=max_len, kv_quant=kv_quant,
                 spec_decode=spec, draft_k=draft_k if spec else 4,
                 drafter=drafter, **kw)
    for r, p in enumerate(_prompts(requests)):
        eng.submit(Request(rid=r, prompt=p,
                           sampling=SamplingParams(temperature=temperature,
                                                   top_k=8 if temperature else 0,
                                                   seed=r, max_new=max_new,
                                                   counter_offset=1000 * r)))
    done = eng.run(ticks=requests * (max_new + 6) + 20)
    return {r.rid: list(r.out) for r in done}, eng


# ---------------------------------------------------------------------------
# bulk-commit stream parity: spec ≡ plain, bitwise, across the engine grid
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "temp"])
@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_spec_stream_bitwise_equals_plain(kv_layout, kv_quant, temperature):
    """The headline contract: speculation changes *when* tokens are
    computed, never *which* — acceptance is exact token match against the
    engine's own stateless sampler, so greedy and temperature streams are
    both bitwise invariant."""
    plain, _ = _serve(spec=False, kv_layout=kv_layout, kv_quant=kv_quant,
                      temperature=temperature)
    spec, eng = _serve(spec=True, drafter=PromptLookupDrafter(),
                       kv_layout=kv_layout, kv_quant=kv_quant,
                       temperature=temperature)
    assert spec == plain
    mc = eng.metrics.summary()["counters"]
    assert mc.get("spec_windows", 0) > 0
    # every token after each request's prefill-emitted first one came
    # through a spec window
    assert mc.get("spec_emitted_tokens", 0) == sum(
        len(o) - 1 for o in spec.values())


@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_accept_all_edge_commits_full_windows(kv_layout):
    """Replay-oracle drafting: every draft matches, so every window commits
    its whole budget and the accept counters saturate — the bulk-commit
    fast path where the scrub mask is empty."""
    plain, _ = _serve(spec=False, kv_layout=kv_layout)
    streams = {tuple(p): plain[r] for r, p in enumerate(_prompts(2))}
    spec, eng = _serve(spec=True, drafter=ReplayDrafter(streams),
                       kv_layout=kv_layout)
    assert spec == plain
    mc = eng.metrics.summary()["counters"]
    assert mc["spec_accepted_tokens"] == mc["spec_draft_tokens"] > 0
    # full accept: both slots decode in lockstep — 7 post-prefill tokens
    # per request in windows of budget 4 then 3 → 2 engine windows total
    assert mc["spec_windows"] == 2
    assert mc["spec_emitted_tokens"] == 14


@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_reject_every_position_edge_still_progresses(kv_layout):
    """Anti-replay drafting: every draft is wrong, so every window commits
    exactly row 0 (plain decode's tick) — wrong drafts cost latency, never
    correctness or progress."""
    plain, _ = _serve(spec=False, kv_layout=kv_layout)
    streams = {tuple(p): plain[r] for r, p in enumerate(_prompts(2))}
    spec, eng = _serve(spec=True, drafter=AntiReplayDrafter(streams),
                       kv_layout=kv_layout)
    assert spec == plain
    mc = eng.metrics.summary()["counters"]
    assert mc["spec_accepted_tokens"] == 0
    assert mc["spec_draft_tokens"] > 0
    # one token per slot per window, both slots in lockstep: 7 windows
    # emit the 14 post-prefill tokens
    assert mc["spec_windows"] == 7
    assert mc["spec_emitted_tokens"] == 14


def test_empty_and_short_proposals_pad_safely():
    """A drafter may return fewer than ``draft_k - 1`` tokens (or none):
    the window pads with zeros, scores them anyway, and the stream is still
    bitwise plain — padding rows only commit on an exact match."""
    plain, _ = _serve(spec=False)
    spec, _ = _serve(spec=True, drafter=FixedDrafter([3]))
    assert spec == plain


# ---------------------------------------------------------------------------
# verify-kernel backend parity + the per-row sequential-equivalence oracle
# ---------------------------------------------------------------------------


def _ring_verify_inputs(seed, *, b=2, cap=32, nkv=2, group=2, hd=16, kq=3,
                        quantized=False, pos_vals=(5, 20)):
    """A ring snapshot mid-verify: slots hold positions up to
    ``pos + kq - 1`` (base row + drafted rows already scattered); unwritten
    slots carry k_pos = -1 and arbitrary codes that masking must hide.
    Drawn position-major, stored as the engine stores it (``_minor``)."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, kq, nkv, group, hd)), jnp.bfloat16)
    pos = jnp.asarray(pos_vals[:b], jnp.int32)
    kpos = np.full((b, cap), -1, np.int64)
    for i in range(b):
        for p in range(int(pos_vals[i]) + kq):
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
    return q, k, v, k_pos[None], pos, ks, vs


def _minor(x):
    """Position-major (n, positions, ...) → the cache's stored heads-major,
    positions-minor (n, ..., positions), as a layer-stacked cache of one
    layer (``layer=0``)."""
    return None if x is None else jnp.moveaxis(x, 1, -1)[None]


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("window", [0, 16], ids=["full", "window16"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_verify_interpret_bit_identical_to_xla_ref(quantized, window, group):
    """The Pallas verify kernel mirrors the oracle's per-row recurrence
    op-for-op: bit-identical across kv_quant × window × GQA group for
    every split-K block size."""
    q, k, v, k_pos, pos, ks, vs = _ring_verify_inputs(
        group, group=group, quantized=quantized)
    for bk in (8, 32):
        out_i = dispatch.verify_attention(
            q, k, v, k_pos, pos, k_scale=ks, v_scale=vs, layer=0,
            window=window, block=(bk,), backend="pallas-interpret")
        out_r = dispatch.verify_attention(
            q, k, v, k_pos, pos, k_scale=ks, v_scale=vs, layer=0,
            window=window, block=(bk,), backend="xla-ref")
        assert out_i.dtype == jnp.float32
        assert jnp.array_equal(out_i, out_r), (quantized, window, group, bk)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("group", [1, 2])
def test_paged_verify_interpret_bit_identical_to_xla_ref(quantized, group):
    """Paged verify: the tile is the pool block on every backend, so
    interpret must match the oracle bit-for-bit (including junk rows in
    partially-filled and out-of-table blocks, which masking hides), for
    group == 1 too — both sides run the same head-batched dots
    (tests/test_paged_attention.py)."""
    rng = np.random.default_rng(11 + group)
    b, bs, nbmax, nblocks, nkv, hd, kq = 2, 4, 6, 16, 2, 16, 3
    q = jnp.asarray(rng.normal(size=(b, kq, nkv, group, hd)), jnp.bfloat16)
    pos = jnp.asarray([5, 13], jnp.int32)
    bt = jnp.asarray(rng.permutation(nblocks - 1)[:b * nbmax].reshape(b, nbmax),
                     jnp.int32)
    if quantized:
        k = jnp.asarray(rng.integers(-127, 128, size=(nblocks, bs, nkv, hd)),
                        jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=(nblocks, bs, nkv, hd)),
                        jnp.int8)
        ks = jnp.asarray(rng.uniform(0.1, 2.0, size=(nblocks, bs, nkv)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.1, 2.0, size=(nblocks, bs, nkv)),
                         jnp.float32)
    else:
        k = jnp.asarray(rng.normal(size=(nblocks, bs, nkv, hd)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(nblocks, bs, nkv, hd)), jnp.bfloat16)
        ks = vs = None
    k, v, ks, vs = (_minor(x) for x in (k, v, ks, vs))
    out_i = dispatch.paged_verify_attention(
        q, k, v, bt, pos, k_scale=ks, v_scale=vs, layer=0,
        backend="pallas-interpret")
    out_r = dispatch.paged_verify_attention(
        q, k, v, bt, pos, k_scale=ks, v_scale=vs, layer=0,
        backend="xla-ref")
    assert out_i.dtype == jnp.float32
    assert jnp.array_equal(out_i, out_r), (quantized, group)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_verify_row_equals_sequential_decode_row(quantized):
    """The stream-parity linchpin, at the kernel level: verify row ``t``
    over a cache holding drafted positions up to ``pos + kq - 1`` is
    bitwise the one-token decode oracle at ``pos + t`` over the *same*
    cache.  Drafted-but-future slots contribute exp() = 0.0 terms at the
    slot locations sequential decode leaves empty — same association
    order, same sums — so acceptance implies bitwise logits row by row."""
    q, k, v, k_pos, pos, ks, vs = _ring_verify_inputs(7, quantized=quantized)
    kq = q.shape[1]
    ver = dispatch.verify_attention(q, k, v, k_pos, pos, k_scale=ks,
                                    v_scale=vs, layer=0, backend="xla-ref")
    for t in range(kq):
        one = dispatch.decode_attention(q[:, t], k, v, k_pos, pos + t,
                                        k_scale=ks, v_scale=vs, layer=0,
                                        backend="xla-ref")
        assert jnp.array_equal(ver[:, t], one), t


# ---------------------------------------------------------------------------
# rejected-suffix rollback: cache bytes + pool state ≡ never-drafted
# ---------------------------------------------------------------------------


def _lockstep_engines(kv_layout, kv_quant=False, max_new=10):
    """A spec engine whose drafts all reject (1 token per window) and a
    plain engine, stepped in lockstep at full batch occupancy.  Full
    occupancy matters for the byte comparison: the plain fused-decode path
    eagerly writes (deterministic, never-read) junk into *dead* ring rows
    each tick, while the verify path's write gate drops dead-row scatters
    entirely — both harmless, but their bytes differ, so the
    byte-identity contract is over rows a request can actually read."""
    plain, _ = _serve(spec=False, kv_layout=kv_layout, kv_quant=kv_quant,
                      requests=2, max_new=max_new)
    streams = {tuple(p): plain[r] for r, p in enumerate(_prompts(2))}
    kw = {}
    if kv_layout == "paged":
        kw = dict(kv_layout="paged", block_size=4)
    engs = []
    for spec in (True, False):
        eng = Engine(PARAMS, CFG, batch=2, max_len=32, kv_quant=kv_quant,
                     spec_decode=spec, draft_k=4,
                     drafter=AntiReplayDrafter(streams) if spec else None,
                     **kw)
        for r, p in enumerate(_prompts(2)):
            eng.submit(Request(rid=r, prompt=p,
                               sampling=SamplingParams(max_new=max_new,
                                                       seed=r,
                                                       counter_offset=1000 * r)))
        engs.append(eng)
    return engs[0], engs[1]


def _readable_paged_bytes(eng):
    """Paged cache bytes a request can actually read: for each slot, the
    rows of its table's blocks at positions below ``_slot_pos``.  Rows at
    or past a slot's position are write targets, not state — plain prefill
    leaves deterministic pad junk in the tail of a partial block, which the
    verify path overwrites with draft K/V and then scrubs back to init — and
    the trash block plus free-list blocks are never read at all.  Pool
    *bookkeeping* (refcounts, free-list order, tables) is still compared
    exactly in the test body.  Leaves without a block axis (``pos``) are
    returned whole; the in-block position is every pool leaf's minor
    axis."""
    nbp = eng.num_blocks + 1
    bs = eng.block_size
    out = []
    for leaf in jax.tree_util.tree_leaves(
            {k: v for k, v in eng.cache.items() if k != "block_tables"}):
        a = np.asarray(leaf)
        bax = next((i for i, d in enumerate(a.shape) if d == nbp), None)
        if bax is None:
            out.append(a)
            continue
        for slot, req in enumerate(eng.slots):
            if req is None:
                continue
            table = eng.pool._tables[req.rid]
            pos = int(eng._slot_pos[slot])
            for li, phys in enumerate(table):
                rows = min(max(pos - li * bs, 0), bs)
                blk = np.take(a, phys, axis=bax)
                out.append(np.take(blk, range(rows), axis=-1))
    return out


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
def test_rollback_ring_cache_bytes_equal_never_drafted(kv_quant):
    """After every window of an all-reject run, the ring cache is byte-
    identical to the never-drafted engine's at the same position: the
    commit scrub restores rejected draft slots to exact init values (zero
    codes, zero scales, k_pos = -1), and accepted-prefix bytes need no
    touch-up at all (position-purity)."""
    spec_eng, plain_eng = _lockstep_engines("ring", kv_quant=kv_quant)
    for _ in range(14):
        spec_eng.step()
        plain_eng.step()
        assert list(spec_eng._slot_pos) == list(plain_eng._slot_pos)
        a = jax.tree_util.tree_leaves(spec_eng.cache)
        b = jax.tree_util.tree_leaves(plain_eng.cache)
        assert len(a) == len(b)
        for la, lb in zip(a, b):
            assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_rollback_paged_pool_state_equal_never_drafted():
    """Paged all-reject run: at every window the pool's refcounts, free
    list (order included — ``truncate`` exactly reverses ``append_block``),
    block tables and prefix-cache index match the never-drafted engine's,
    and every readable pool row (positions below each slot's ``_slot_pos``,
    through its own table) is byte-identical."""
    spec_eng, plain_eng = _lockstep_engines("paged")
    for _ in range(14):
        spec_eng.step()
        plain_eng.step()
        assert list(spec_eng._slot_pos) == list(plain_eng._slot_pos)
        ps, pp = spec_eng.pool, plain_eng.pool
        assert ps._ref == pp._ref
        assert ps._free == pp._free
        assert list(ps._cached.keys()) == list(pp._cached.keys())
        assert {r: t for r, t in ps._tables.items()} == \
               {r: t for r, t in pp._tables.items()}
        sl = _readable_paged_bytes(spec_eng)
        pl = _readable_paged_bytes(plain_eng)
        assert len(sl) == len(pl)
        for la, lb in zip(sl, pl):
            assert la.shape == lb.shape
            assert np.array_equal(la, lb)


# ---------------------------------------------------------------------------
# guard rails: configs speculation must refuse
# ---------------------------------------------------------------------------


def test_spec_rejects_draft_k_one():
    with pytest.raises(ValueError):
        Engine(PARAMS, CFG, batch=2, max_len=32, spec_decode=True, draft_k=1)


def test_spec_rejects_quant_policy():
    """Policy fake-quant scales are tensor-global (absmax over the whole
    activation), so a (B, K) verify activation quantises differently from a
    (B,) decode activation — not row-pure, so speculation refuses it."""
    from repro.numerics.policy import QuantPolicy
    with pytest.raises(ValueError):
        Engine(PARAMS, CFG, batch=2, max_len=32, spec_decode=True,
               policy=QuantPolicy(scheme="dither"))


def test_spec_rejects_effective_sliding_window():
    """A ring cap below max_len means verify rows would overwrite slots
    earlier rows still attend to — speculation requires the full ring."""
    import dataclasses
    wcfg = dataclasses.replace(CFG, window=8)
    wparams = registry.init_model(jax.random.PRNGKey(0), wcfg)
    with pytest.raises(ValueError):
        Engine(wparams, wcfg, batch=2, max_len=32, spec_decode=True)


def test_spec_rejects_moe():
    """MoE capacity ranks are a cumsum over *all* dispatched tokens, so a
    verify row competes with its own future draft rows — not row-pure."""
    mcfg = get_config("granite_moe_1b_a400m").reduced()
    mparams = registry.init_model(jax.random.PRNGKey(0), mcfg)
    assert not registry.supports_spec_decode(mcfg)
    with pytest.raises(ValueError):
        Engine(mparams, mcfg, batch=2, max_len=32, spec_decode=True)
