"""Whisper-style encoder-decoder (arXiv:2212.04356) — transformer backbone.

Per the assignment the conv audio frontend is a STUB: ``input_specs()``
provides precomputed frame embeddings (B, n_enc_tokens, d_model).  Encoder is
bidirectional; decoder is causal self-attention + cross-attention over the
encoder output.  Decoder self-attention uses RoPE (deviation from Whisper's
learned positions, noted in DESIGN.md §7 — keeps position tables O(1) for the
assigned 32k decode shape).  GELU MLPs, pre-LayerNorm, as in Whisper.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ModelConfig
from repro.models.transformer import _attention_decode, kv_shapes, ring_len
from repro.numerics.policy import QuantPolicy, dense

Params = Dict[str, Any]

__all__ = ["init_encdec", "encode", "forward_encdec", "decode_step_encdec",
           "init_encdec_cache", "merge_cache_encdec"]


def _ln(d):
    return {"g": jnp.ones((d,), jnp.bfloat16), "b": jnp.zeros((d,), jnp.bfloat16)}


def _init_enc_layer(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": _ln(cfg.d_model),
        "attn": layers.init_attention(k1, cfg),
        "ln2": _ln(cfg.d_model),
        "mlp": layers.init_mlp(k2, cfg.d_model, cfg.d_ff, "gelu"),
    }


def _init_dec_layer(key, cfg: ModelConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": _ln(cfg.d_model),
        "attn": layers.init_attention(k1, cfg),
        "ln_x": _ln(cfg.d_model),
        "xattn": layers.init_attention(k2, cfg, cross=True),
        "ln2": _ln(cfg.d_model),
        "mlp": layers.init_mlp(k3, cfg.d_model, cfg.d_ff, "gelu"),
    }


def init_encdec(key, cfg: ModelConfig) -> Params:
    ke, kd, kt, kp = jax.random.split(key, 4)
    enc = [_init_enc_layer(jax.random.fold_in(ke, i), cfg) for i in range(cfg.n_enc_layers)]
    dec = [_init_dec_layer(jax.random.fold_in(kd, i), cfg) for i in range(cfg.n_layers)]
    return {
        "enc_blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *enc),
        "enc_pos": layers._init(kp, (cfg.n_enc_tokens, cfg.d_model), scale=0.02),
        "enc_norm": _ln(cfg.d_model),
        "dec_blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *dec),
        "embed": layers.init_embedding(kt, cfg.vocab_padded(), cfg.d_model),
        "final_norm": _ln(cfg.d_model),
    }


def _lnorm(x, p, eps):
    return layers.layer_norm(x, p["g"], p["b"], eps)


def encode(params, cfg: ModelConfig, frames: jax.Array, *, policy=None, counter=0):
    """frames: (B, n_enc_tokens, d_model) stub embeddings → encoder output."""
    b, s, _ = frames.shape
    x = frames.astype(jnp.bfloat16) + params["enc_pos"][None, :s]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def body(h, bp):
        a, _ = layers.attention(
            bp["attn"], cfg, _lnorm(h, bp["ln1"], cfg.norm_eps), positions,
            causal=False, policy=policy, counter=counter, use_rope=False,
        )
        h = h + a
        h = h + layers.mlp(bp["mlp"], _lnorm(h, bp["ln2"], cfg.norm_eps), "gelu",
                           policy, counter)
        return h, None

    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return _lnorm(x, params["enc_norm"], cfg.norm_eps)


def forward_encdec(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    frames: jax.Array,
    *,
    policy: Optional[QuantPolicy] = None,
    counter=0,
    remat: bool = True,
):
    """Training / prefill forward → logits (B, S, vocab)."""
    enc = encode(params, cfg, frames, policy=policy, counter=counter)
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def body(h, bp):
        a, _ = layers.attention(
            bp["attn"], cfg, _lnorm(h, bp["ln1"], cfg.norm_eps), positions,
            causal=True, policy=policy, counter=counter,
        )
        h = h + a
        c, _ = layers.attention(
            bp["xattn"], cfg, _lnorm(h, bp["ln_x"], cfg.norm_eps), positions,
            causal=False, kv_src=enc, policy=policy, counter=counter,
            use_rope=False,
        )
        h = h + c
        h = h + layers.mlp(bp["mlp"], _lnorm(h, bp["ln2"], cfg.norm_eps), "gelu",
                           policy, counter)
        return h, None

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(body_fn, x, params["dec_blocks"])
    x = _lnorm(x, params["final_norm"], cfg.norm_eps)
    return jnp.matmul(x, params["embed"].T).astype(jnp.float32)  # tied head


def init_encdec_cache(params, cfg: ModelConfig, frames, batch: int, max_len: int,
                      *, policy=None):
    """Build the decode cache: ring self-KV per layer + precomputed cross-KV.

    ``pos`` / ``k_pos`` are per-slot, matching the decoder-only cache layout
    (the serving engine admits requests into slots at different times).
    """
    enc = encode(params, cfg, frames, policy=policy)
    hd, nkv = cfg.hd(), cfg.n_kv_heads
    xk, xv = _stacked_xkv(params, enc, cfg, batch)
    # self-KV in the decoder ring's layout (transformer.kv_shapes)
    cap = ring_len(cfg, max_len)
    self_kv = {name: jnp.zeros(shape, jnp.bfloat16) for name, shape in
               kv_shapes((cfg.n_layers, batch), nkv, hd, cap,
                         quantized=False).items()}
    self_kv["k_pos"] = jnp.full((cfg.n_layers, batch, cap), -1, jnp.int32)
    return {"pos": jnp.zeros((batch,), jnp.int32), "self": self_kv,
            "cross_k": xk, "cross_v": xv}


def merge_cache_encdec(old, new, active):
    """Per-slot cache insertion (cf. transformer.merge_cache): rows of ``new``
    where ``active`` (B,) replace rows of ``old``.  Self-KV leaves carry batch
    at axis 1 (leading layer axis); the static cross-KV is kept from ``old``."""
    def sel(o, n):
        shp = [1] * n.ndim
        shp[1] = active.shape[0]
        return jnp.where(active.reshape(shp), n, o)

    return {
        "pos": jnp.where(active, new["pos"], old["pos"]),
        "self": jax.tree.map(sel, old["self"], new["self"]),
        "cross_k": old["cross_k"], "cross_v": old["cross_v"],
    }


def _stacked_xkv(params, enc, cfg, batch):
    hd, nkv = cfg.hd(), cfg.n_kv_heads

    def body(_, bp):
        k = jnp.matmul(enc, bp["xattn"]["wk"]).reshape(batch, -1, nkv, hd)
        v = jnp.matmul(enc, bp["xattn"]["wv"]).reshape(batch, -1, nkv, hd)
        return None, (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))

    _, (xk, xv) = jax.lax.scan(body, None, params["dec_blocks"])
    return xk, xv


def decode_step_encdec(
    params: Params,
    cfg: ModelConfig,
    token: jax.Array,  # (B,)
    cache: Params,
    *,
    policy: Optional[QuantPolicy] = None,
    counter=0,
    kv_offset=None,  # accepted for API parity; the encdec self-KV is bf16
):
    """One decoder token with self-KV ring cache and static cross-KV.

    ``cache["pos"]`` is per-slot (B,), as in the decoder-only path.
    """
    import math as _math

    pos = cache["pos"]
    x = jnp.take(params["embed"], token[:, None], axis=0)
    b = x.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd()

    def body(carry, xs):
        # the self-KV stack rides in the loop state, written in place
        h, entry = carry
        bp, layer, xk, xv = xs
        a, entry = _attention_decode(
            bp["attn"], cfg, _lnorm(h, bp["ln1"], cfg.norm_eps), entry, pos,
            policy, counter, layer=layer,
        )
        h = h + a
        # cross attention against the precomputed encoder KV
        hq = _lnorm(h, bp["ln_x"], cfg.norm_eps)
        q = dense(hq, bp["xattn"]["wq"], policy, counter, seed=1).reshape(
            b, 1, nkv, nh // nkv, hd)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, xk).astype(jnp.float32) / _math.sqrt(hd)
        probs = jax.nn.softmax(logits, axis=-1).astype(h.dtype)
        c = jnp.einsum("bhgqk,bkhd->bqhgd", probs, xv).reshape(b, 1, nh * hd)
        h = h + dense(c, bp["xattn"]["wo"], policy, counter, seed=4)
        h = h + layers.mlp(bp["mlp"], _lnorm(h, bp["ln2"], cfg.norm_eps), "gelu",
                           policy, counter)
        return (h, entry), None

    xs = (params["dec_blocks"], jnp.arange(cfg.n_layers, dtype=jnp.int32),
          cache["cross_k"], cache["cross_v"])
    (x, self_kv), _ = jax.lax.scan(body, (x, cache["self"]), xs)
    x = _lnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.matmul(x, params["embed"].T)[:, 0].astype(jnp.float32)
    logits = logits[:, : cfg.vocab_size]  # drop vocab padding for sampling
    new_cache = {
        "pos": pos + 1,
        "self": self_kv,
        "cross_k": cache["cross_k"], "cross_v": cache["cross_v"],
    }
    return logits, new_cache
