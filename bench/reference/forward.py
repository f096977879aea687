"""The architecture module ``forward``: a Llama-family decoder, the one a
configuration runs where its file names no ``architecture``
(``benchlib/spec.py`` lists what such a module gives).  Here that is
``logits_at``, the plain float32 forward as the configuration file states
it (``configs/<name>.json``, the ``model`` keys); ``work``, the counts of
``benchlib/work.py`` summed over the layers, all alike; and ``WIDTH_KEYS``,
which says that the file's ``head_dim`` is the program's.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU an f32 product
otherwise runs in bf16 passes.  The weights are the benchmark's own
(``benchlib/weights.py``) in the served bf16, upcast here layer by layer.

Per layer: h = x + o(attn(norm1(x))) · r, then x' = h + mlp(norm2(h)) · r,
where r = scale_depth / sqrt(layers) (MiniCPM's residual scale; 1 for a
plain Llama).  Attention is causal with rotary position embedding (halves
rotated, theta from the file) and grouped KV heads; the MLP is SwiGLU.  The
input embedding is multiplied by scale_emb, and the final hidden state is
divided by hidden_size / dim_model_base before the tied head (both 1 for a
plain Llama).  Both configurations tie the head to the embedding; an untied
one is refused.

``control=True`` computes the same forward one precision step below the one
the configuration states: every weight matrix (and the tied embedding) is
rounded to int8 per output channel, and the cached keys and values to int4
per position and head.  It is the lower-precision path a later change might
be tempted to take, and the comparison in ``benchlib/correct.py`` has to
refuse it.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from benchlib import work as counts

HI = jax.lax.Precision.HIGHEST

# checked by ``run.program_config`` beside the dense widths
WIDTH_KEYS = {"head_dim": "hd"}


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _q_int(w, bits, axis):
    """Symmetric round-to-nearest to ``bits`` with one absmax scale per slice
    reduced over ``axis`` (dequantised back to f32)."""
    top = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -top, top) * s


def hidden_states(w, model: dict, tokens, *, control: bool = False):
    """Final-norm hidden states (T, d) of one sequence ``tokens`` (T,)."""
    d = model["hidden_size"]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or d // nh
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    layers = model["num_hidden_layers"]
    r = model.get("scale_depth", math.sqrt(layers)) / math.sqrt(layers)
    t = tokens.shape[0]
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    emb = w["embed"].astype(jnp.float32)
    if control:
        emb = _q_int(emb, 8, axis=-1)
    x = jnp.take(emb, tokens, axis=0) * model.get("scale_emb", 1.0)

    def mat(a):
        a = a.astype(jnp.float32)
        return _q_int(a, 8, axis=-2) if control else a

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = _rms(x, p["ln1"].astype(jnp.float32), eps)
        q = _rope(_mm(h, mat(a["wq"])).reshape(t, nh, hd), pos, theta)
        k = _rope(_mm(h, mat(a["wk"])).reshape(t, nkv, hd), pos, theta)
        v = _mm(h, mat(a["wv"])).reshape(t, nkv, hd)
        if control:
            k, v = _q_int(k, 4, axis=-1), _q_int(v, 4, axis=-1)
        qg = q.reshape(t, nkv, nh // nkv, hd)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v,
                       precision=HI).reshape(t, nh * hd)
        x = x + _mm(o, mat(a["wo"])) * r
        h2 = _rms(x, p["ln2"].astype(jnp.float32), eps)
        f = jax.nn.silu(_mm(h2, mat(m["wg"]))) * _mm(h2, mat(m["wu"]))
        return x + _mm(f, mat(m["wd"])) * r, None

    for stack in w["blocks"]:
        x, _ = jax.lax.scan(layer, x, stack)
    for p in w["remainder"]:
        x, _ = layer(x, p)
    x = _rms(x, w["final_norm"].astype(jnp.float32), eps)
    return x / (d / model.get("dim_model_base", d)), emb


@functools.partial(jax.jit, static_argnames=("model_items", "control"))
def _logits_rows(w, tokens, rows, *, model_items, control):
    model = dict(model_items)
    if not model.get("tie_word_embeddings", False):
        raise ValueError("the reference serves tied embeddings only")
    x, emb = hidden_states(w, model, tokens, control=control)
    logits = jnp.matmul(jnp.take(x, rows, axis=0), emb.T, precision=HI)
    return logits[:, : model["vocab_size"]]


def logits_at(w, model: dict, tokens, rows, *, control: bool = False):
    """Logits (len(rows), vocab) of the next token after each position in
    ``rows`` of the sequence ``tokens``."""
    items = tuple(sorted((k, v if not isinstance(v, list) else tuple(v))
                         for k, v in model.items()))
    return _logits_rows(w, jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(rows, jnp.int32), model_items=items,
                        control=control)


@dataclass(frozen=True)
class Work:
    """Whole-model counts of a decoder whose layers are all alike: the
    per-layer counts of ``benchlib/work.py`` times the layers."""
    m: counts.Dims
    tied: bool

    def token_matmul_flops(self, head: bool) -> int:
        return counts.token_matmul_flops(self.m, head)

    def attention_flops(self, ctx) -> int:
        return counts.attention_flops(self.m, ctx)

    def decode_attention_bytes(self, ctx: int, nbmax: int) -> int:
        return counts.decode_attention_bytes(self.m, ctx, nbmax) * self.m.layers

    def decode_attention_flops(self, ctx: int) -> int:
        return counts.decode_attention_flops(self.m, ctx) * self.m.layers

    def head_flops(self) -> int:
        """2·d·vocab: one row of logits."""
        return 2 * self.m.d * self.m.vocab

    def kv_bytes_per_token(self) -> int:
        return counts.kv_bytes_per_token(self.m)

    def weight_bytes(self) -> int:
        return counts.weight_bytes(self.m, self.tied)


def work(model: dict) -> Work:
    return Work(counts.Dims.of(model), bool(model.get("tie_word_embeddings", True)))
