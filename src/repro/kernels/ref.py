"""Pure-jnp oracles for the Pallas kernels.

Bit-exact references: the kernels and these oracles share the same integer
hash / permutation / dither-bit math (repro.core.rounding), so tests assert
exact equality of integer codes and tight allclose on float outputs.  The
attention oracles restate the kernels' online-softmax step op for op (they
do not call the kernel module's step), so interpret-mode kernels match them
bitwise while a slip in either copy shows up as a parity failure.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import rounding
from repro.kernels.decode_attention import shrink_block

__all__ = [
    "quantize_codes_ref",
    "dither_round_ref",
    "stochastic_round_ref",
    "dither_matmul_ref",
    "decode_attention_ref",
    "paged_decode_attention_ref",
    "verify_attention_ref",
    "paged_verify_attention_ref",
]


def _flat_index(shape) -> jax.Array:
    n_rows, n_cols = shape
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    return r * jnp.uint32(n_cols) + c


def quantize_codes_ref(
    x: jax.Array,
    *,
    scale: float,
    zero: float,
    bits: int,
    scheme: str,
    counter: int,
    seed: int,
    n_pulses: int,
    fmt: str = "spread",
) -> jax.Array:
    """Quantise a 2-D tensor to k-bit integer codes with the given rounding.

    codes = clip(round_scheme((x - zero) * scale), 0, 2^bits - 1), where the
    element index used by the hash PRNG is the *global* flattened (row-major)
    index — the same value the tiled kernel reconstructs from its grid
    coordinates.
    """
    assert x.ndim == 2
    levels = (1 << bits) - 1
    scaled = (x.astype(jnp.float32) - zero) * scale
    idx = _flat_index(x.shape)
    if scheme == "deterministic":
        codes = rounding.deterministic_round(scaled)
    elif scheme == "stochastic":
        u = rounding.hash_uniform(seed, idx, counter)
        fl = jnp.floor(scaled)
        codes = fl + (u < scaled - fl).astype(jnp.float32)
    elif scheme == "dither":
        fl = jnp.floor(scaled)
        slot = rounding.slot_index(counter, idx, n_pulses, seed=seed, fmt=fmt)
        u = rounding.hash_uniform(seed ^ 0xD1CE, idx, counter)
        codes = fl + rounding.dither_bit(scaled - fl, slot, u, n_pulses)
    else:
        raise ValueError(scheme)
    return jnp.clip(codes, 0.0, float(levels)).astype(jnp.int32)


def dither_round_ref(x, *, scale, zero, bits, counter, seed, n_pulses):
    return quantize_codes_ref(
        x, scale=scale, zero=zero, bits=bits, scheme="dither",
        counter=counter, seed=seed, n_pulses=n_pulses,
    )


def stochastic_round_ref(x, *, scale, zero, bits, counter, seed):
    return quantize_codes_ref(
        x, scale=scale, zero=zero, bits=bits, scheme="stochastic",
        counter=counter, seed=seed, n_pulses=2,
    )


def dither_matmul_ref(
    a: jax.Array,
    b: jax.Array,
    *,
    bits: int,
    scheme: str = "dither",
    a_range=(0.0, 1.0),
    b_range=(0.0, 1.0),
    counter: int = 0,
    seed: int = 0,
    fmt: str = "spread",
) -> jax.Array:
    """Oracle for the fused quantise+matmul kernel (the §VIII 'separate' variant).

    Both operands are quantised once (A with seed, B with seed+1; dither
    N_pulses: N_A = b.shape[1], N_B = a.shape[0] per §VII), multiplied on the
    integer grid, and affinely mapped back to the real domain.
    """
    (p, q), (q2, r) = a.shape, b.shape
    assert q == q2
    levels = float((1 << bits) - 1)
    sa = levels / (a_range[1] - a_range[0])
    sb = levels / (b_range[1] - b_range[0])
    ca = quantize_codes_ref(
        a, scale=sa, zero=a_range[0], bits=bits, scheme=scheme,
        counter=counter, seed=seed, n_pulses=max(r, 2), fmt=fmt,
    ).astype(jnp.float32)
    cb = quantize_codes_ref(
        b, scale=sb, zero=b_range[0], bits=bits, scheme=scheme,
        counter=counter, seed=seed + 1, n_pulses=max(p, 2), fmt=fmt,
    ).astype(jnp.float32)
    cc = ca @ cb
    out = cc / (sa * sb)
    if a_range[0] != 0.0 or b_range[0] != 0.0:
        out = (
            out
            + a_range[0] * cb.sum(axis=0)[None, :] / sb
            + b_range[0] * ca.sum(axis=1)[:, None] / sa
            + q * a_range[0] * b_range[0]
        )
    return out


def _flash_ref(q, k, v, tables, pos, k_scale, v_scale, k_pos, *,
               window: int, layer) -> jax.Array:
    """The split-K online-softmax recurrence over a tiled cache, batched
    over slots: a ``lax.scan`` over logical tiles j that gathers each
    slot's physical tile ``tables[b, min(j, pos[b]//bk)]`` of layer
    ``layer`` and updates the running max / sum / value state.  k, v
    (L, n_tiles, n_kv, hd, bk); k_scale, v_scale (L, n_tiles, rows, bk) or
    None, rows ≥ n_kv (rows past n_kv are padding); k_pos (n_tiles, bk), the
    layer's own, or None for the pool's implicit positions ``j·bk + t``.

    Per tile: int8→query-dtype upcast, an f32-accumulated
    (n_kv, group, hd)·(n_kv, hd, bk) dot batched over slot and KV head,
    post-dot key-scale fold, -1e30 masking of unwritten, future and
    out-of-window keys, then value scales on the unnormalised weights and
    a value dot over the position (lane) axis — the Pallas kernel body's
    order, so the two agree bitwise where both run on one backend."""
    bsz, nkv, group, hd = q.shape
    bk = k.shape[-1]
    inv = float(1.0 / math.sqrt(hd))
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (bsz,))
    tables = jnp.asarray(tables, jnp.int32)
    last = pos // bk
    quantized = k_scale is not None

    def step(carry, j):
        m, s, acc = carry
        jc = jnp.minimum(j, last)                          # clamped logical
        phys = jnp.take_along_axis(tables, jc[:, None], axis=1)[:, 0]

        def take(x):
            return x[layer, phys]

        logits = jax.lax.dot_general(
            q, take(k).astype(q.dtype),
            dimension_numbers=(((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        ) * inv                                            # (B, n_kv, group, bk)
        if quantized:
            ks = take(k_scale)[:, :nkv]                    # (B, n_kv, bk)
            logits = logits * (ks[:, :, None, :] * (1.0 / 127.0))
        kp = (k_pos[phys] if k_pos is not None
              else jc[:, None] * bk + jnp.arange(bk, dtype=jnp.int32)[None])
        kp = kp[:, None, None, :]
        pb = pos[:, None, None, None]
        valid = (kp >= 0) & (kp <= pb)
        if window:
            valid = valid & (kp > pb - window)
        logits = jnp.where(valid, logits, -1e30)

        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        s_new = s * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            vs = take(v_scale)[:, :nkv]
            p = p * (vs[:, :, None, :] * (1.0 / 127.0))
        acc_new = acc * alpha + jax.lax.dot_general(
            p, take(v).astype(jnp.float32),
            dimension_numbers=(((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        )
        act = (j <= last)[:, None, None, None]
        return (jnp.where(act, m_new, m), jnp.where(act, s_new, s),
                jnp.where(act, acc_new, acc)), None

    init = (
        jnp.full((bsz, nkv, group, 1), -jnp.inf, jnp.float32),
        jnp.zeros((bsz, nkv, group, 1), jnp.float32),
        jnp.zeros((bsz, nkv, group, hd), jnp.float32),
    )
    (m, s, acc), _ = jax.lax.scan(
        step, init, jnp.arange(tables.shape[1], dtype=jnp.int32))
    return acc / s


def decode_attention_ref(
    q: jax.Array,        # (B, n_kv, group, hd) bf16/f32 — post-RoPE queries
    k: jax.Array,        # (L, B, n_kv, hd, cap) int8 codes or bf16
    v: jax.Array,        # (L, B, n_kv, hd, cap)
    k_pos: jax.Array,    # (L, B, cap) int32
    pos: jax.Array,      # (B,) int32 per-slot absolute decode position
    k_scale: jax.Array | None = None,   # (L, B, rows, cap) f32 when int8
    v_scale: jax.Array | None = None,
    *,
    layer,               # index into the leading layer axis
    window: int = 0,
    block: tuple | None = None,
) -> jax.Array:
    """Oracle for the flash-decode attention kernel → (B, n_kv, group, hd) f32.

    The dispatch-level contract for ``decode_attention`` is the *split-K
    online-softmax recurrence over cache-length blocks* — this function IS
    that contract, in plain jnp: the ring is cut into cap/bk tiles (a pool
    whose block table is ``b·n_tiles + j``) and ``_flash_ref`` runs the
    kernel's step, restated, over them, so ``pallas-interpret`` is bit-identical
    to this oracle for the same ``block``.  Mathematically it equals the
    pre-kernel full-softmax einsum path (softmax over every valid slot);
    numerically it differs only by float-summation association — and it is
    *more* precise, since the value dot accumulates in f32 instead of the
    einsum path's bf16 probabilities (tests/test_decode_attention.py pins
    both properties).

    ``block=None`` → one block of the whole cap: the recurrence collapses
    to a single masked softmax pass — the fast XLA path the serving engine
    uses off-TPU.
    """
    bsz, cap = k.shape[1], k.shape[-1]
    # both paths MUST shrink `block` to the same divisor of cap or the
    # bit-parity contract silently breaks
    bk = shrink_block(cap if block is None else block[0], cap)
    nt = cap // bk

    def tiles(x):     # layer's (B, ..., cap) → (1, B·nt, ..., bk), a relayout
        if x is None:
            return None
        t = jax.lax.dynamic_index_in_dim(x, layer, keepdims=False)
        t = jnp.moveaxis(t.reshape(t.shape[:-1] + (nt, bk)), -2, 1)
        return t.reshape((1, bsz * nt) + t.shape[2:])

    kp = tiles(k_pos)
    return _flash_ref(
        q, tiles(k), tiles(v),
        jnp.arange(bsz * nt, dtype=jnp.int32).reshape(bsz, nt), pos,
        tiles(k_scale), tiles(v_scale), kp[0], window=window, layer=0)


def paged_decode_attention_ref(
    q: jax.Array,        # (B, n_kv, group, hd) bf16/f32 — post-RoPE queries
    k: jax.Array,        # (L, n_blocks, n_kv, hd, bs) int8 codes or bf16
    v: jax.Array,        # (L, n_blocks, n_kv, hd, bs)
    block_tables: jax.Array,  # (B, nbmax) int32 physical block per logical
    pos: jax.Array,      # (B,) int32 per-slot absolute decode position
    k_scale: jax.Array | None = None,   # (L, n_blocks, rows, bs) f32
    v_scale: jax.Array | None = None,
    *,
    layer,               # index into the leading layer axis
    window: int = 0,
) -> jax.Array:
    """Oracle for the paged flash-decode kernel → (B, n_kv, group, hd) f32.

    The same split-K online-softmax recurrence as ``decode_attention_ref``,
    with the per-block gather routed through the block table: logical block
    j of slot b lives at physical pool block ``block_tables[b, j]``, and the
    key position of in-block slot t is the *implicit* ``j·bs + t`` (the pool
    is append-only; no stored k_pos).  The cache-length tile is pinned to
    the pool block size, so for bs == bk this is bit-identical to the ring
    recurrence on the same token stream — the reuse guarantee that makes
    prefix blocks shareable across requests (DESIGN.md §6)."""
    return _flash_ref(q, k, v, block_tables, pos, k_scale, v_scale, None,
                      window=window, layer=layer)


def verify_attention_ref(
    q: jax.Array,        # (B, kq, n_kv, group, hd) — post-RoPE draft queries
    k: jax.Array,        # (L, B, n_kv, hd, cap) int8 codes or bf16
    v: jax.Array,        # (L, B, n_kv, hd, cap)
    k_pos: jax.Array,    # (L, B, cap) int32
    pos: jax.Array,      # (B,) int32 per-slot base (first-row) position
    k_scale: jax.Array | None = None,   # (L, B, rows, cap) f32 when int8
    v_scale: jax.Array | None = None,
    *,
    layer,
    window: int = 0,
    block: tuple | None = None,
) -> jax.Array:
    """Oracle for the multi-token verify kernel → (B, kq, n_kv, group, hd)
    f32 (DESIGN.md §14).

    *Literally* ``decode_attention_ref`` once per query row: row t runs
    the one-token recurrence at position pos+t over the same cache, and
    the rows stack on axis 1.  That construction — rather than one fused
    (kq·group, bk) logit tile — is deliberate: batched dots are not
    row-pure across the M dimension on every XLA backend (1-ulp
    association drift), and the spec-decode contract is that row t's
    output is *bitwise* what sequential decode at pos+t would produce.
    The Pallas verify kernels mirror this with a static per-row loop over
    one-token-shaped dots, so kernel↔oracle parity holds per row too
    (tests/test_spec_decode.py)."""
    kq = q.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.stack(
        [decode_attention_ref(q[:, t], k, v, k_pos, pos + t,
                              k_scale, v_scale, layer=layer, window=window,
                              block=block)
         for t in range(kq)], axis=1)


def paged_verify_attention_ref(
    q: jax.Array,        # (B, kq, n_kv, group, hd) — post-RoPE draft queries
    k: jax.Array,        # (L, n_blocks, n_kv, hd, bs) int8 codes or bf16
    v: jax.Array,        # (L, n_blocks, n_kv, hd, bs)
    block_tables: jax.Array,  # (B, nbmax) int32 physical block per logical
    pos: jax.Array,      # (B,) int32 per-slot base (first-row) position
    k_scale: jax.Array | None = None,   # (L, n_blocks, rows, bs) f32
    v_scale: jax.Array | None = None,
    *,
    layer,
    window: int = 0,
) -> jax.Array:
    """Oracle for the paged multi-token verify kernel →
    (B, kq, n_kv, group, hd) f32.  ``paged_decode_attention_ref`` once per
    query row at position pos+t, stacked on axis 1 — by construction
    bitwise what sequential paged decode produces per row, on every
    backend (the tile is pinned to the pool block; see
    ``verify_attention_ref`` on why the rows are not fused)."""
    kq = q.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.stack(
        [paged_decode_attention_ref(q[:, t], k, v, block_tables, pos + t,
                                    k_scale, v_scale, layer=layer,
                                    window=window)
         for t in range(kq)], axis=1)
