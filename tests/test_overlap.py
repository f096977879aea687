"""Overlapped engine (DESIGN.md §11): windowed multi-tick decode and
chunked piggyback prefill are *stream-preserving*.

Contracts pinned here:

* **N-tick ≡ N single ticks, bitwise** — the fused decode window scans the
  exact per-tick ops of the ``decode_ticks=1`` engine (same sampler hash,
  same KV writes, dead rows frozen), so every per-request token stream and
  finish reason is bit-identical for any window length, including under
  temperature sampling, for ring/paged × bf16/int8 KV.
* **chunked prefill ≡ whole-prompt prefill at stream level (greedy)** — the
  dither KV codes key on absolute position + per-request offset, so a
  chunk writes the codes whole-prompt prefill would have written; the
  chunk's history join re-associates the softmax reduction (split
  softmax), which perturbs logits at bf16 epsilon — the same documented
  drift as the paged prefix join — so the pinned invariant is greedy
  token-stream equality, the repo's standard parity currency.
* chunk/block **boundary edges**: prompt length at / one-below / one-above
  the chunk and block sizes, prefix-cache hits that end mid-chunk, empty
  prompts, oversized chunks (clamped), and preempt-resume of a
  half-prefilled request.
* the same parity on a (1, 1) mesh in tier-1, and (2, 1)/(1, 2)/(2, 2)
  under CI's forced-4-device step.
"""

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

CFG = get_config("smollm_135m").reduced()
PARAMS = registry.init_model(jax.random.PRNGKey(0), CFG)

BLOCK = 8                                  # paged pool block size under test


def _serve(prompts, *, max_new=6, temperature=0.0, batch=2, max_len=48,
           **eng_kw):
    """Serve ``prompts`` on a fresh engine; return the canonical stream
    fingerprint [(rid, tokens, finish_reason), ...] plus the engine."""
    if eng_kw.get("kv_layout") == "paged":
        eng_kw.setdefault("block_size", BLOCK)
    eng = Engine(PARAMS, CFG, batch=batch, max_len=max_len, **eng_kw)
    for r, prompt in enumerate(prompts):
        eng.submit(Request(rid=r, prompt=list(prompt),
                           sampling=SamplingParams(
                               temperature=temperature, max_new=max_new,
                               seed=r, eos_id=11, stop_ids=(77,),
                               counter_offset=500 * r)))
    done = eng.run(ticks=400)
    assert len(done) == len(prompts)
    return sorted((d.rid, tuple(d.out), d.finish_reason) for d in done), eng


def _mix(n=6):
    # Fixture chosen tie-free: greedy argmax margins stay clear of the
    # split-softmax / prefix-join bf16 drift for every layout × kv_quant ×
    # chunk × decode_ticks combination below (conftest.assert_argmax_margin
    # philosophy — near-tie fixtures get reseeded, not worked around).
    # Re-pinned from (13·r + i) under jax 0.9.0: the old mix's logits held
    # exact bf16 top-2 ties (margin 0.0) where the chunked int8 streams
    # parted from the whole-prompt ones (ring: request 0 token 3, request 4
    # token 5, in the chunked path; paged: request 1 token 0, in the
    # whole-prompt path).
    return [[(17 * r + i + 2) % (CFG.vocab_size - 1) + 1
             for i in range(6 + 3 * r)] for r in range(n)]


_BASE = {}


def _baseline(kv_layout, kv_quant, temperature=0.0):
    key = (kv_layout, kv_quant, temperature)
    if key not in _BASE:
        _BASE[key], _ = _serve(_mix(), kv_layout=kv_layout,
                               kv_quant=kv_quant, temperature=temperature)
    return _BASE[key]


# ---------------------------------------------------------------------------
# multi-tick fused decode ≡ single ticks (bitwise)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
@pytest.mark.parametrize("n", [3, 4])
def test_fused_window_matches_single_ticks(kv_layout, kv_quant, n):
    got, eng = _serve(_mix(), kv_layout=kv_layout, kv_quant=kv_quant,
                      decode_ticks=n)
    assert got == _baseline(kv_layout, kv_quant)
    if eng.pools:
        assert eng.pool_stats()["live"] == 0


@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_fused_window_bitwise_under_temperature(kv_layout):
    """The window is bitwise even for sampled decoding: the sampler hash
    keys on (seed, counter = offset + emitted), both of which the fused
    scan advances exactly as N single ticks do."""
    want = _baseline(kv_layout, False, temperature=0.8)
    got, _ = _serve(_mix(), kv_layout=kv_layout, temperature=0.8,
                    decode_ticks=4)
    assert got == want


def test_fused_window_under_pool_pressure():
    """A pool too small to cover full windows caps per-window budgets
    (_paged_cap) instead of changing behaviour: streams still match the
    one-tick engine, and preempted requests still resume correctly."""
    want, _ = _serve(_mix(), kv_layout="paged", num_blocks=12)
    got, eng = _serve(_mix(), kv_layout="paged", num_blocks=12,
                      decode_ticks=4)
    assert got == want
    assert eng.pool_stats()["live"] == 0


def test_decode_ticks_validation():
    with pytest.raises(ValueError):
        Engine(PARAMS, CFG, batch=2, max_len=16, decode_ticks=0)


# ---------------------------------------------------------------------------
# chunked prefill ≡ whole-prompt prefill (greedy stream level)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_chunked_prefill_matches_whole_prompt(kv_layout, kv_quant):
    got, eng = _serve(_mix(), kv_layout=kv_layout, kv_quant=kv_quant,
                      prefill_chunk=5)
    assert got == _baseline(kv_layout, kv_quant)
    if eng.pools:
        assert eng.pool_stats()["live"] == 0


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_chunked_prefill_with_fused_windows(kv_layout, kv_quant):
    """The full overlapped configuration — piggyback chunks admitted
    between 4-tick decode windows — still reproduces the unoverlapped
    engine's streams."""
    got, _ = _serve(_mix(), kv_layout=kv_layout, kv_quant=kv_quant,
                    prefill_chunk=5, decode_ticks=4)
    assert got == _baseline(kv_layout, kv_quant)


# ---------------------------------------------------------------------------
# chunk / block boundary edges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_layout,chunk", [("ring", 5), ("paged", BLOCK)])
def test_prompt_lengths_straddling_boundaries(kv_layout, chunk):
    """Prompt length exactly at / one below / one above the chunk size and
    the block size (and multiples) — the partial-final-chunk and
    full-final-chunk paths must agree with whole-prompt prefill."""
    lens = sorted({chunk - 1, chunk, chunk + 1,
                   2 * chunk - 1, 2 * chunk, 2 * chunk + 1, 1})
    prompts = [[(23 * r + i) % (CFG.vocab_size - 1) + 1 for i in range(n)]
               for r, n in enumerate(lens)]
    want, _ = _serve(prompts, kv_layout=kv_layout)
    got, _ = _serve(prompts, kv_layout=kv_layout, prefill_chunk=chunk)
    assert got == want


def test_empty_prompt_and_oversized_chunk():
    """Empty prompts take the BOS substitution through the chunked path,
    and a chunk larger than max_len is clamped (ring) / the whole prompt
    lands in one wave — both degenerate to whole-prompt prefill.  (The
    middle prompt was [5, 6, 7] until jax 0.9.0, whose whole-prompt logits
    put tokens 217 and 499 in an exact top-2 tie, margin 0.0, at that
    request's second step.)"""
    prompts = [[], [5, 6, 8], []]
    want, _ = _serve(prompts)
    for chunk in (2, 10 ** 6):
        got, _ = _serve(prompts, prefill_chunk=chunk)
        assert got == want


def test_paged_chunk_rounds_to_block_multiple():
    eng = Engine(PARAMS, CFG, batch=2, max_len=32, kv_layout="paged",
                 block_size=BLOCK, prefill_chunk=BLOCK + 3)
    assert eng.prefill_chunk == BLOCK                 # rounded down
    eng2 = Engine(PARAMS, CFG, batch=2, max_len=32, kv_layout="paged",
                  block_size=BLOCK, prefill_chunk=1)
    assert eng2.prefill_chunk == BLOCK                # floor one block


def test_prefix_hit_ending_mid_chunk():
    """A prefix-cache hit hands the request a block-aligned start; the
    remaining suffix here is shorter than one chunk, so the first (only)
    chunk is a partial one riding the prefix-join path.  The warm stream
    must equal the cold stream."""
    p_long = [(3 * i) % (CFG.vocab_size - 1) + 1 for i in range(2 * BLOCK)]
    p_warm = p_long[:2 * BLOCK - 3] + [401, 402]      # shares 1 full block+
    cold, _ = _serve([p_warm], kv_layout="paged", prefill_chunk=BLOCK)

    eng = Engine(PARAMS, CFG, batch=2, max_len=48, kv_layout="paged",
                 block_size=BLOCK, prefill_chunk=BLOCK)
    eng.submit(Request(rid=0, prompt=p_long,
                       sampling=SamplingParams(max_new=6, seed=0,
                                               counter_offset=0)))
    eng.run(ticks=100)
    eng.submit(Request(rid=1, prompt=p_warm,
                       sampling=SamplingParams(max_new=6, seed=0, eos_id=11,
                                               stop_ids=(77,),
                                               counter_offset=0)))
    done = eng.run(ticks=200)
    warm = [d for d in done if d.rid == 1][0]
    assert eng.stats["prefix_hit_tokens"] >= BLOCK    # the hit happened
    assert (0, tuple(warm.out), warm.finish_reason) == cold[0]


def test_preempt_resume_half_prefilled():
    """White-box: preempt a request mid-prefill (state == 'prefilling',
    blocks intact) and let admission resume it — it must rejoin the chunk
    waves at its _pf_pos and finish with the undisturbed engine's exact
    stream."""
    prompts = _mix(3)
    prompts[0] = [(5 * i) % (CFG.vocab_size - 1) + 1 for i in range(4 * BLOCK)]
    want, _ = _serve(prompts, kv_layout="paged", prefill_chunk=BLOCK)

    eng = Engine(PARAMS, CFG, batch=2, max_len=48, kv_layout="paged",
                 block_size=BLOCK, prefill_chunk=BLOCK)
    for r, prompt in enumerate(prompts):
        eng.submit(Request(rid=r, prompt=list(prompt),
                           sampling=SamplingParams(
                               max_new=6, seed=r, eos_id=11, stop_ids=(77,),
                               counter_offset=500 * r)))
    preempted = False
    for _ in range(400):
        if not preempted:
            for i, s in enumerate(eng.slots):
                if s is not None and s.state == "prefilling" \
                        and 0 < s._pf_pos < len(s.prompt):
                    eng._preempt_requeue(i, s)
                    preempted = True
                    break
        eng.step()
        if not len(eng.scheduler) and all(s is None for s in eng.slots):
            break
    assert preempted, "fixture never caught a half-prefilled slot"
    got = sorted((d.rid, tuple(d.out), d.finish_reason) for d in eng.finished)
    assert got == want
    assert eng.stats["preemptions"] >= 1
    assert eng.pool_stats()["live"] == 0


# ---------------------------------------------------------------------------
# mesh parity: (1,1) in tier-1; 4-device shapes under CI's forced step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_mesh_1x1_overlap_parity(kv_layout):
    """The shard_map fused-window + chunked-prefill path on a trivial
    (1, 1) mesh is stream-identical to the unmeshed one-tick engine."""
    got, _ = _serve(_mix(), kv_layout=kv_layout, decode_ticks=4,
                    prefill_chunk=5 if kv_layout == "ring" else BLOCK,
                    mesh=make_serve_mesh(1, 1), batch=2)
    assert got == _baseline(kv_layout, False)


_BASE4 = {}


@needs4
@pytest.mark.parametrize("dp,tp", [(2, 1), (1, 2), (2, 2)])
@pytest.mark.parametrize("kv_layout", ["ring", "paged"])
def test_mesh_overlap_parity(kv_layout, dp, tp):
    """Windowed decode + chunked prefill sharded on (data, model) meshes
    reproduces the unmeshed single-tick streams (CI forces 4 devices)."""
    if kv_layout not in _BASE4:
        _BASE4[kv_layout], _ = _serve(_mix(), kv_layout=kv_layout, batch=4)
    got, _ = _serve(_mix(), kv_layout=kv_layout, decode_ticks=4,
                    prefill_chunk=5 if kv_layout == "ring" else BLOCK,
                    mesh=make_serve_mesh(dp, tp), batch=4)
    assert got == _BASE4[kv_layout]
