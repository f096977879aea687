"""The work the algorithm needs, in operations and bytes, from shapes
alone (never from the compiled program, whose count moves when a kernel
changes).  Read by the per-layer metrics and tested against hand counts."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    d: int
    ff: int
    nh: int
    nkv: int
    hd: int
    layers: int
    vocab: int

    @classmethod
    def of(cls, model: dict) -> "Dims":
        nh = model["num_attention_heads"]
        return cls(d=model["hidden_size"], ff=model["intermediate_size"],
                   nh=nh, nkv=model["num_key_value_heads"],
                   hd=model.get("head_dim") or model["hidden_size"] // nh,
                   layers=model["num_hidden_layers"], vocab=model["vocab_size"])


def layer_params(m: Dims) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o and
    the three SwiGLU matrices."""
    return (m.d * m.nh * m.hd + 2 * m.d * m.nkv * m.hd + m.nh * m.hd * m.d
            + 3 * m.d * m.ff)


def token_matmul_flops(m: Dims, head: bool) -> int:
    """2·params of one token through every layer, plus the LM head when the
    token's logits are needed (a decode step, the last prompt token)."""
    return 2 * m.layers * layer_params(m) + (2 * m.d * m.vocab if head else 0)


def attention_flops(m: Dims, ctx: int) -> int:
    """Scores and weighted values of one query over ``ctx`` keys, every
    layer: 2·ctx·hd each, per query head."""
    return 4 * ctx * m.nh * m.hd * m.layers


def decode_attention_bytes(m: Dims, ctx: int, nbmax: int) -> int:
    """Bytes one paged int8 decode-attention call must move for one row,
    per layer: the live tokens' K and V codes (1 byte each) and their f32
    scales (one per position and KV head, for K and for V), the bf16 query,
    the f32 output, and the row's block table and position."""
    return (ctx * m.nkv * (2 * m.hd + 2 * 4) + m.nh * m.hd * 2
            + m.nh * m.hd * 4 + nbmax * 4 + 4)


def decode_attention_flops(m: Dims, ctx: int) -> int:
    """4·ctx·heads·hd per row per layer."""
    return 4 * ctx * m.nh * m.hd


def kv_bytes_per_token(m: Dims) -> int:
    """The int8 dither KV cache's bytes per token: codes of K and V in every
    layer plus their f32 scales."""
    return m.layers * m.nkv * (2 * m.hd + 2 * 4)


def weight_bytes(m: Dims, tied: bool = True) -> int:
    return 2 * (m.layers * (layer_params(m) + 2 * m.d) + m.d * m.vocab * (1 if tied else 2) + m.d)
