"""Uniform model API over all architecture families.

``init_model / apply_model / make_cache / apply_decode`` hide the
decoder-only vs encoder-decoder split so the trainer, server, dry-run and
tests treat every assigned arch identically.  Batches are dicts:

  tokens  (B, S) int32            — always present
  embeds  (B, F, d_model) bf16    — vlm patch embeddings (stub frontend)
  frames  (B, T_enc, d_model) bf16 — audio frame embeddings (stub frontend)
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models.config import ModelConfig
from repro.numerics.policy import QuantPolicy

Params = Dict[str, Any]

__all__ = [
    "init_model", "apply_model", "make_cache", "apply_decode", "batch_spec",
    "apply_prefill", "apply_prefill_chunked", "apply_prefill_paged",
    "merge_prefill", "supports_batched_prefill", "supports_paged_kv",
    "supports_chunked_prefill", "supports_spec_decode", "apply_verify",
    "spec_commit",
]


def init_model(key, cfg: ModelConfig) -> Params:
    if cfg.is_encdec:
        return encdec.init_encdec(key, cfg)
    return transformer.init_params(key, cfg)


def apply_model(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    *,
    policy: Optional[QuantPolicy] = None,
    counter=0,
    remat: bool = True,
) -> jax.Array:
    """Full-sequence logits for training / prefill."""
    if cfg.is_encdec:
        return encdec.forward_encdec(
            params, cfg, batch["tokens"], batch["frames"],
            policy=policy, counter=counter, remat=remat,
        )
    return transformer.forward(
        params, cfg, batch["tokens"], embeds=batch.get("embeds"),
        policy=policy, counter=counter, remat=remat,
    )


def make_cache(params: Params, cfg: ModelConfig, batch_size: int, max_len: int,
               frames: Optional[jax.Array] = None, *, policy=None,
               kv_quant: bool = False, kv_layout: str = "ring",
               block_size: Optional[int] = None,
               num_blocks: Optional[int] = None,
               data_shards: int = 1, head_shards: int = 1) -> Params:
    """Decode-cache constructor.  ``data_shards`` > 1 lays the paged block
    pool out as shard-local sub-pools (one trash block each) for the sharded
    serving engine — ``num_blocks`` then counts blocks per shard
    (DESIGN.md §9); ring caches need no layout change (the slot dim shards
    directly).  ``head_shards`` is the number of shards the KV heads split
    into, for the per-shard padding of int8 scale rows."""
    if cfg.is_encdec:
        assert frames is not None
        if kv_layout != "ring":
            raise ValueError("paged KV layout requires an attention-only "
                             "decoder (see supports_paged_kv)")
        return encdec.init_encdec_cache(params, cfg, frames, batch_size, max_len,
                                        policy=policy)
    if kv_layout != "ring" and not supports_paged_kv(cfg):
        raise ValueError("paged KV layout requires an attention-only decoder "
                         f"(arch {cfg.name!r} has recurrent state)")
    return transformer.init_cache(cfg, batch_size, max_len, kv_quant=kv_quant,
                                  kv_layout=kv_layout, block_size=block_size,
                                  num_blocks=num_blocks,
                                  data_shards=data_shards,
                                  head_shards=head_shards)


def apply_decode(params: Params, cfg: ModelConfig, token: jax.Array, cache: Params,
                 *, policy=None, counter=0, kv_offset=None):
    if cfg.is_encdec:
        return encdec.decode_step_encdec(params, cfg, token, cache,
                                         policy=policy, counter=counter,
                                         kv_offset=kv_offset)
    return transformer.decode_step(params, cfg, token, cache,
                                   policy=policy, counter=counter,
                                   kv_offset=kv_offset)


def supports_batched_prefill(cfg: ModelConfig) -> bool:
    """True when prompts can prefill in one batched forward that also emits
    the decode cache: attention-only decoders.  SSM / RG-LRU layers carry
    recurrent state whose value at each slot's prompt boundary is not
    recoverable from the chunked full-sequence pass, and the encoder-decoder
    shares that constraint through its fallback — both use the scanned
    prefill inside ``apply_prefill`` instead (DESIGN.md §6)."""
    return (not cfg.is_encdec
            and all(cfg.layer_kind(i) == "attn" for i in range(cfg.n_layers)))


def supports_spec_decode(cfg: ModelConfig) -> bool:
    """True when draft-and-verify decode (DESIGN.md §14) preserves the
    bitwise stream contract: attention-only decoders without MoE.  SSM /
    RG-LRU recurrences have no multi-token verify form, and MoE capacity
    ranks are a cumsum over every token in a dispatch — a k-token verify row
    would compete for expert capacity with its own future draft positions,
    which sequential decode never does."""
    return supports_batched_prefill(cfg) and not cfg.n_experts


def apply_verify(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 cache: Params, *, policy=None, counter=0, kv_offset=None,
                 alive=None, wcap=None):
    """Score K draft positions per slot in one forward (transformer
    ``verify_step``); requires ``supports_spec_decode(cfg)``."""
    return transformer.verify_step(params, cfg, tokens, cache, policy=policy,
                                   counter=counter, kv_offset=kv_offset,
                                   alive=alive, wcap=wcap)


def spec_commit(cache: Params, new_pos, written, *, draft_k: int) -> Params:
    """Bulk-commit + rejected-suffix scrub after a verify forward."""
    return transformer.spec_commit(cache, new_pos, written, draft_k=draft_k)


def supports_paged_kv(cfg: ModelConfig) -> bool:
    """True when the arch can serve from the paged block-pool KV cache
    (DESIGN.md §6): attention-only decoders.  Recurrent layers carry O(1)
    state with no per-position cache to page, and the encoder-decoder's
    cross-KV is a fixed full-precision tensor — both stay on the ring/dense
    layout."""
    return supports_batched_prefill(cfg)


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """True when prompts can prefill in block-aligned or ring chunks spread
    over several engine steps (DESIGN.md §11): attention-only decoders.
    Recurrent layers would need their hidden state checkpointed at every
    chunk boundary; they keep the scanned whole-prompt fallback."""
    return supports_batched_prefill(cfg)


def apply_prefill_chunked(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,    # (B, S) right-padded prompt chunks
    lengths: jax.Array,   # (B,) chunk lengths; 0 marks an inactive row
    starts: jax.Array,    # (B,) absolute start position of each chunk
    cache: Params,        # live ring cache (merged in place by the caller)
    *,
    policy=None,
    counter=0,
    kv_quant: bool = False,
    kv_offset=None,
):
    """Chunked ring prefill → (last-chunk-token logits (B, vocab_size), the
    live cache with the chunk K/V merged in).  Continuation chunks
    (``starts > 0``) re-read their slot's earlier positions from the ring
    inside attention instead of recomputing them (DESIGN.md §11)."""
    b, s = tokens.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    logits, cache = transformer.prefill_with_cache_chunked(
        params, cfg, tokens, lengths, starts, cache, policy=policy,
        counter=counter, kv_quant=kv_quant, kv_offset=kv_offset)
    last = jnp.clip(lengths - 1, 0, s - 1)
    last_logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]
    return last_logits, cache


def apply_prefill_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,    # (B, S) right-padded prompt suffixes
    lengths: jax.Array,   # (B,) suffix lengths; 0 marks an inactive row
    starts: jax.Array,    # (B,) block-aligned absolute start positions
    block_tables: jax.Array,
    cache: Params,
    *,
    policy=None,
    counter=0,
    kv_quant: bool = False,
    kv_offset=None,
    prefix_blocks: int = 0,
):
    """Paged batched prefill → (last-suffix-token logits (B, vocab_size),
    the live cache with the suffix blocks scattered in).  Prefix-hit rows
    (``starts > 0``) skip recomputing the shared prefix — its K/V is
    gathered from the refcounted pool blocks inside attention."""
    b, s = tokens.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    logits, cache = transformer.prefill_with_cache_paged(
        params, cfg, tokens, lengths, starts, block_tables, cache,
        policy=policy, counter=counter, kv_quant=kv_quant,
        kv_offset=kv_offset, prefix_blocks=prefix_blocks)
    last = jnp.clip(lengths - 1, 0, s - 1)
    last_logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]
    return last_logits, cache


def merge_prefill(cfg: ModelConfig, old: Params, new: Params,
                  active: jax.Array) -> Params:
    """Per-slot cache insertion: rows of ``new`` where ``active`` (B,) bool
    replace rows of ``old`` — how a prefill result enters the engine cache."""
    if cfg.is_encdec:
        return encdec.merge_cache_encdec(old, new, active)
    return transformer.merge_cache(old, new, active)


def apply_prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,    # (B, S) right-padded prompts
    lengths: jax.Array,   # (B,) true lengths; 0 marks an inactive row
    max_len: int,
    *,
    policy=None,
    counter=0,
    kv_quant: bool = False,
    kv_offset=None,
    cache0: Optional[Params] = None,
    frames: Optional[jax.Array] = None,
):
    """Batched prefill → (last-token logits (B, vocab_size), decode cache).

    Attention-only decoders run ``transformer.prefill_with_cache`` (one
    batched forward, KV scattered into the ring cache).  Architectures with
    recurrent state (SSM / RG-LRU) or an encoder fall back to a *scanned*
    prefill: ``lax.scan`` of the decode step over the padded prompt inside
    this one jitted call (active-masked so short prompts freeze early) —
    still O(S) sequential steps, but batched on-device with no host
    round-trips.  ``cache0`` seeds the fallback (required for enc-dec, whose
    cross-KV comes from ``frames`` otherwise).
    """
    b, s = tokens.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    if supports_batched_prefill(cfg):
        logits, cache = transformer.prefill_with_cache(
            params, cfg, tokens, lengths, max_len, policy=policy,
            counter=counter, kv_quant=kv_quant, kv_offset=kv_offset)
        last = jnp.clip(lengths - 1, 0, s - 1)
        last_logits = jnp.take_along_axis(
            logits, last[:, None, None], axis=1)[:, 0]
        return last_logits, cache

    if cache0 is None:
        cache0 = make_cache(params, cfg, b, max_len, frames=frames,
                            policy=policy, kv_quant=kv_quant)

    def step(carry, xs):
        cache, last_logits = carry
        tok, t = xs
        logits, new_cache = apply_decode(params, cfg, tok, cache,
                                         policy=policy, counter=counter,
                                         kv_offset=kv_offset)
        active = t < lengths
        cache = merge_prefill(cfg, cache, new_cache, active)
        last_logits = jnp.where(active[:, None], logits, last_logits)
        return (cache, last_logits), None

    init = (cache0, jnp.zeros((b, cfg.vocab_size), jnp.float32))
    (cache, last_logits), _ = jax.lax.scan(
        step, init, (tokens.T, jnp.arange(s, dtype=jnp.int32)))
    return last_logits, cache


def batch_spec(cfg: ModelConfig, batch: int, seq: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for a training batch (launch/dryrun)."""
    spec = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    if cfg.frontend == "vit_stub":
        spec["embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.is_encdec:
        spec["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_enc_tokens, cfg.d_model), jnp.bfloat16)
    return spec
