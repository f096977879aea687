"""Pallas TPU kernels: flash-decode attention over the KV cache
(DESIGN.md §2/§3 — the serving hot path), in two cache layouts: the dense
per-slot ring buffer (``decode_attention_call``) and the paged block pool
(``paged_decode_attention_call``, DESIGN.md §6), each with a multi-token
verify variant that scores kq speculative query rows per slot
(``verify_attention_call`` / ``paged_verify_attention_call``, DESIGN.md
§14).  One-token decode *is* verify with kq = 1, so all four entry points
run the same kernel body.

One grid program per (batch slot, cache tile), every KV head in it:

  grid = (B, n_tiles), cache tiles innermost (sequential);
  each step streams one (bk, n_kv, hd) K tile and V tile through VMEM,
  computes the (n_kv, group, bk) logit tile with one head-batched dot and
  folds it into an online-softmax state (running max m, running sum s,
  f32 value accumulator) held in VMEM scratch — the split-K flash-decode
  recurrence, so the full (cap,) logit row is never materialised.

Mosaic layout: a block's two minor dims must be (8, 128)-aligned or span
the whole array.  K/V blocks take the whole (n_kv, hd) minor plane of the
cache's own (…, n_kv, hd) layout, so no cache is copied or re-laid-out.
The small per-position side arrays (int8 scales, ring ``k_pos``) are
re-laid *tile-major* per call — (n_tiles, n_kv, bk) and (n_tiles, 1, bk),
a few KiB per layer — so their blocks also span whole minor planes and
every divisor of the cache length is a legal tile.  Both layouts then
address the cache the same way: the ring is a pool whose block table is
``b·n_tiles + j``.

The int8 dither-quantised cache is consumed *as codes*: the K tile is
upcast int8→bf16 in registers (tile-sized, never the full cache), the dot
runs int8-codes·bf16-query with f32 accumulation, and the per-position
``k_scale``/``v_scale`` fold in *after* the dot — the paper's "compute on
the pulse-coded representation" argument applied to attention (the same
fold as the unary dot-products of arXiv:2307.03204).  Keeping the codes
un-dequantised in HBM is what preserves the §VII variance analysis
(arXiv:2207.10321).

Masking is in-kernel: slot validity (``k_pos >= 0``, ring only),
causality (``k_pos <= pos``) and the sliding window
(``k_pos > pos - window``).  **Length-aware tile skipping**: the per-slot
positions and the tile table are scalar-prefetch operands, so the index
maps clamp the logical tile to ``(pos + kq - 1) // bk`` — Pallas elides
the copy when the tile index repeats, and a ``pl.when`` guard skips the
compute, so a slot at position p reads ceil((p+1)/bk) tiles, not all.

Numerics contract: ``flash_step`` is the one statement of the recurrence
(op order, f32 state, -1e30 mask); the kernels call it per query row on
one slot and the ``kernels/ref`` oracles — the ``xla-ref`` dispatcher
backend — call it batched over slots, with the same head-batched dot, so
``pallas-interpret`` is bit-identical to the oracle for the same tile
(tests/test_decode_attention.py, tests/test_paged_attention.py,
tests/test_spec_decode.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_call", "paged_decode_attention_call",
           "verify_attention_call", "paged_verify_attention_call",
           "flash_step", "key_valid", "shrink_block"]

_NEG_BIG = -1e30  # matches the pre-kernel einsum path's mask value


def shrink_block(bk: int, cap: int) -> int:
    """Largest block size ≤ bk that divides cap (cap stays un-padded: ring
    slots are positional state, padding would invent phantom slots).  Every
    divisor is a legal Mosaic tile: the tile axis is never one of a block's
    two minor dims (see the module docstring)."""
    bk = max(1, min(bk, cap))
    while cap % bk:
        bk -= 1
    return bk


def flash_step(q, k, v, k_scale, v_scale, valid, m, s, acc):
    """One split-K online-softmax step over one cache tile, all KV heads.

    Shapes, with any number of leading batch dims ``...`` (none inside a
    kernel program, the slot axis in the oracles):
    q (..., n_kv, group, hd); k, v (..., bk, n_kv, hd) int8 codes or bf16;
    k_scale, v_scale (..., n_kv, bk) f32 or None; ``valid`` broadcastable
    to (..., n_kv, group, bk); m, s (..., n_kv, group, 1) and
    acc (..., n_kv, group, hd) f32.  Returns the updated (m, s, acc).

    The dots batch over the leading dims and the KV head in one
    ``dot_general`` whatever the leading rank: XLA's CPU backend sums an
    unbatched one-row (group == 1) dot in another order than a batched one,
    so the kernel and the oracle must both batch for their results to be
    bitwise equal.
    """
    n = q.ndim - 3
    lead = tuple(range(n))
    inv = float(1.0 / math.sqrt(q.shape[-1]))
    logits = jax.lax.dot_general(
        q, k.astype(q.dtype),  # int8→bf16 upcast, tile only
        dimension_numbers=(((n + 2,), (n + 2,)), (lead + (n,), lead + (n + 1,))),
        preferred_element_type=jnp.float32,
    ) * inv                                           # (..., n_kv, group, bk)
    if k_scale is not None:
        # per-position key scales fold in after the codes dot
        logits = logits * (k_scale[..., None, :] * (1.0 / 127.0))
    logits = jnp.where(valid, logits, _NEG_BIG)

    m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new)
    s_new = s * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if v_scale is not None:
        # per-position value scales attach to the (unnormalised) weights
        p = p * (v_scale[..., None, :] * (1.0 / 127.0))
    acc_new = acc * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32),
        dimension_numbers=(((n + 2,), (n,)), (lead + (n,), lead + (n + 1,))),
        preferred_element_type=jnp.float32,
    )
    return m_new, s_new, acc_new


def key_valid(kp, qp, window: int):
    """Mask of the keys at positions ``kp`` a query at ``qp`` attends to:
    written (``kp >= 0``), causal, and inside the sliding window."""
    valid = (kp >= 0) & (kp <= qp)
    if window:
        valid = valid & (kp > qp - window)
    return valid


def _flash_body(pos_ref, tab_ref, *refs, bk: int, kq: int, window: int,
                quantized: bool, ring: bool):
    """One (slot b, logical tile j) program: the ``flash_step`` recurrence
    for kq query rows at positions pos_b .. pos_b+kq-1.

    Operand refs, in order: q (kq, n_kv, group, hd); K and V tiles
    (bk, n_kv, hd); when quantized the k/v scale tiles (n_kv, bk); on the
    ring the k_pos tile (1, bk) — the pool's key positions are implicit,
    ``j·bk + t``, since a pool block is append-only, never a ring; the
    (kq, n_kv, group, hd) output; and the m, s, acc scratch.

    The row loop is a *static Python* loop so each row runs the exact
    one-token dot shapes, op order and mask at position pos_b+t — a fused
    (kq·group)-row logit tile would change the float-summation shape and
    break the bitwise stream-parity contract of speculative decoding
    (DESIGN.md §14).  Row t freezes on tiles ``j > (pos_b+t)//bk``, so its
    processed-tile set matches sequential decode exactly.
    """
    del tab_ref  # consumed by the index maps only
    q_ref, k_ref, v_ref, *side, out_ref, m_ref, s_ref, acc_ref = refs
    ks_ref, vs_ref = side[:2] if quantized else (None, None)
    kpos_ref = side[-1] if ring else None
    b, j = pl.program_id(0), pl.program_id(1)
    pos_b = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j <= (pos_b + kq - 1) // bk)  # deepest tile any row touches
    def _accumulate():
        k, v = k_ref[...], v_ref[...]
        ks = ks_ref[...] if quantized else None
        vs = vs_ref[...] if quantized else None
        kp = (kpos_ref[...] if ring
              else j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1))
        for t in range(kq):
            qp = pos_b + t                # this row's absolute query position
            m_prev, s_prev, acc_prev = m_ref[t], s_ref[t], acc_ref[t]
            m_new, s_new, acc_new = flash_step(
                q_ref[t], k, v, ks, vs, key_valid(kp, qp, window),
                m_prev, s_prev, acc_prev)
            act = j <= qp // bk           # row-t processed-tile freeze
            m_ref[t] = jnp.where(act, m_new, m_prev)
            s_ref[t] = jnp.where(act, s_new, s_prev)
            acc_ref[t] = jnp.where(act, acc_new, acc_prev)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] = acc_ref[...] / s_ref[...]


def _tile_major(x: jax.Array, bk: int) -> jax.Array:
    """(n, L, n_kv) per-position scales → (n·L/bk, n_kv, bk): one row per
    cache tile, positions on lanes (a layout change only, no float ops)."""
    n, length, nkv = x.shape
    return (x.reshape(n, length // bk, bk, nkv).transpose(0, 1, 3, 2)
            .reshape(n * (length // bk), nkv, bk))


def _flash_call(q, k, v, tables, pos, k_scale, v_scale, k_pos, *,
                window: int, interpret: bool) -> jax.Array:
    """The kernel over a tiled cache → (B, kq, n_kv, group, hd) f32.

    q (B, kq, n_kv, group, hd); k, v (n_tiles, bk, n_kv, hd); tables
    (B, nt) int32 physical tile of each slot's logical tile; k_scale,
    v_scale (n_tiles, n_kv, bk) tile-major or None; k_pos (n_tiles, 1, bk)
    on the ring, None for the pool (implicit positions)."""
    bsz, kq, nkv, group, hd = q.shape
    bk = k.shape[1]
    nt = tables.shape[1]
    quantized = k_scale is not None
    ring = k_pos is not None
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (bsz,))
    tables = jnp.asarray(tables, jnp.int32)

    def tile(b, j, p_, t_):
        return t_[b, jnp.minimum(j, (p_[b] + kq - 1) // bk)]

    inputs = [q, k, v]
    in_specs = [
        pl.BlockSpec((None, kq, nkv, group, hd),
                     lambda b, j, p_, t_: (b, 0, 0, 0, 0)),
        pl.BlockSpec((None, bk, nkv, hd),
                     lambda b, j, p_, t_: (tile(b, j, p_, t_), 0, 0, 0)),
        pl.BlockSpec((None, bk, nkv, hd),
                     lambda b, j, p_, t_: (tile(b, j, p_, t_), 0, 0, 0)),
    ]
    side = [k_scale, v_scale] if quantized else []
    side += [k_pos] if ring else []
    for x in side:
        inputs.append(x)
        in_specs.append(pl.BlockSpec(
            (None,) + x.shape[1:],
            lambda b, j, p_, t_: (tile(b, j, p_, t_), 0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, nt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, kq, nkv, group, hd),
                               lambda b, j, p_, t_: (b, 0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kq, nkv, group, 1), jnp.float32),
            pltpu.VMEM((kq, nkv, group, 1), jnp.float32),
            pltpu.VMEM((kq, nkv, group, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_flash_body, bk=bk, kq=kq, window=window,
                          quantized=quantized, ring=ring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, kq, nkv, group, hd),
                                       jnp.float32),
        name="flash_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pos, tables, *inputs)


@functools.partial(
    jax.jit, static_argnames=("window", "block", "interpret"),
)
def verify_attention_call(
    q: jax.Array,        # (B, kq, n_kv, group, hd) — post-RoPE draft queries
    k: jax.Array,        # (B, cap, n_kv, hd) int8 codes or bf16
    v: jax.Array,        # (B, cap, n_kv, hd)
    k_pos: jax.Array,    # (B, cap) int32 — absolute position per ring slot
    pos: jax.Array,      # (B,) int32 — per-slot base (first-row) position
    k_scale: jax.Array | None = None,   # (B, cap, n_kv) f32 when int8
    v_scale: jax.Array | None = None,
    *,
    window: int = 0,
    block: tuple = (512,),
    interpret: bool = True,
) -> jax.Array:
    """Multi-token verify attention over the ring cache →
    (B, kq, n_kv, group, hd) f32.

    Query row t of slot b attends as if decoding at absolute position
    ``pos[b] + t`` — the draft rows' K/V must already sit in the cache
    (the verify forward writes them before attending, mirroring the decode
    write-then-attend order).  ``block = (bk,)`` is the cache-length tile
    (shrunk to a divisor of cap), shared with the one-token kernel so the
    per-row recurrence matches it bit-for-bit.
    """
    bsz, cap, nkv, hd = k.shape
    bk = shrink_block(block[0], cap)
    nt = cap // bk
    quantized = k_scale is not None
    # splitting the (major) cache axis into tiles is a free reshape
    tiles = lambda x: x.reshape((bsz * nt, bk) + x.shape[2:])  # noqa: E731
    return _flash_call(
        q, tiles(k), tiles(v),
        jnp.arange(bsz * nt, dtype=jnp.int32).reshape(bsz, nt), pos,
        _tile_major(k_scale, bk) if quantized else None,
        _tile_major(v_scale, bk) if quantized else None,
        k_pos.reshape(bsz * nt, 1, bk), window=window, interpret=interpret)


def decode_attention_call(q, k, v, k_pos, pos, k_scale=None, v_scale=None, *,
                          window: int = 0, block: tuple = (512,),
                          interpret: bool = True) -> jax.Array:
    """Flash-decode attention over the ring cache → (B, n_kv, group, hd) f32:
    ``verify_attention_call`` with one query row.  q is (B, n_kv, group,
    hd); ``block = (bk,)`` is the cache-length tile (shrunk to a divisor of
    cap).  The f32 output is unprojected attention; callers cast and apply
    W_O."""
    return verify_attention_call(
        q[:, None], k, v, k_pos, pos, k_scale, v_scale, window=window,
        block=block, interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify_attention_call(
    q: jax.Array,        # (B, kq, n_kv, group, hd) — post-RoPE draft queries
    k: jax.Array,        # (n_blocks, bs, n_kv, hd) int8 codes or bf16 pool
    v: jax.Array,        # (n_blocks, bs, n_kv, hd)
    block_tables: jax.Array,  # (B, nbmax) int32 physical block per logical
    pos: jax.Array,      # (B,) int32 — per-slot base (first-row) position
    k_scale: jax.Array | None = None,   # (n_blocks, bs, n_kv) f32 when int8
    v_scale: jax.Array | None = None,
    *,
    window: int = 0,
    interpret: bool = True,
) -> jax.Array:
    """Paged multi-token verify attention → (B, kq, n_kv, group, hd) f32.

    The cache tile IS the pool block (bs = ``k.shape[1]``): the index maps
    gather physical block ``block_tables[b, min(j, (pos[b]+kq-1)//bs)]``
    via the scalar-prefetched table, so a slot reads its own blocks
    wherever they live in the pool, and shared prefix blocks are fetched
    from the same physical tiles for every request that holds them.  The
    tile is pinned by the pool, so each query row is bit-identical to
    sequential paged decode at ``pos[b] + t`` on every backend, and for
    bs == bk to the ring kernel on the same token stream.  The engine must
    have allocated blocks covering every row it intends to accept; deeper
    rows read whatever the (clamped) table gather returns and their output
    is discarded host-side."""
    quantized = k_scale is not None
    bs = k.shape[1]
    return _flash_call(
        q, k, v, block_tables, pos,
        _tile_major(k_scale, bs) if quantized else None,
        _tile_major(v_scale, bs) if quantized else None,
        None, window=window, interpret=interpret)


def paged_decode_attention_call(q, k, v, block_tables, pos, k_scale=None,
                                v_scale=None, *, window: int = 0,
                                interpret: bool = True) -> jax.Array:
    """Paged flash-decode attention → (B, n_kv, group, hd) f32:
    ``paged_verify_attention_call`` with one query row (q is
    (B, n_kv, group, hd))."""
    return paged_verify_attention_call(
        q[:, None], k, v, block_tables, pos, k_scale, v_scale,
        window=window, interpret=interpret)[:, 0]
