"""Pallas TPU kernels: flash-decode attention over the KV cache
(DESIGN.md §2/§3 — the serving hot path), in two cache layouts: the dense
per-slot ring buffer (``decode_attention_call``) and the paged block pool
(``paged_decode_attention_call``, DESIGN.md §6), each with a multi-token
verify variant that scores kq speculative query rows per slot
(``verify_attention_call`` / ``paged_verify_attention_call``, DESIGN.md
§14).  One-token decode *is* verify with kq = 1, so all four entry points
run the same kernel body.

**Cache layout: heads-major, positions minor** (``transformer.kv_shapes``).
The pool is (n_blocks, n_kv, hd, bs) per layer and the ring
(B, n_kv, hd, cap), with per-position int8 scales (…, rows, bs|cap) whose
rows are the KV heads padded to the f32 sublane tile
(``transformer.scale_rows``); a layer-stacked cache adds a leading layer
axis.  A tile is then
(n_kv, hd, bk): its minor plane (hd, bk) is the chip's dense (sublane,
lane) tile — hd = 64 rows of bk ≥ 128 lanes, unpadded for int8 and bf16 —
so the compiler stores the cache in HBM exactly as the kernel reads it
and never re-lays it out around the custom call.  (A position-major (…, bk, n_kv, hd) cache pads
its small (n_kv, hd) plane to the (8, 128) tile, so the compiler keeps it
transposed in HBM and copies it into the kernel's layout on every call.)

One grid program per (batch slot, cache tile), every KV head in it:

  grid = (B, n_tiles), cache tiles innermost (sequential);
  each step streams one (n_kv, hd, bk) K tile and V tile through VMEM,
  computes the (n_kv, group, bk) logit tile with one head-batched
  (n_kv, group, hd)·(n_kv, hd, bk) dot and folds it into an
  online-softmax state (running max m, running sum s, f32 value
  accumulator) held in VMEM scratch — the split-K flash-decode
  recurrence, so the full (cap,) logit row is never materialised.

**Addressing through scalar prefetch.**  The per-slot positions, the
layer index and (pool) the block table are scalar-prefetch operands, so
the index maps address a tile where it lies: ``(layer, table[b, jc], 0,
0, 0)`` in the pool, ``(layer, b, 0, 0, jc)`` on the ring.  The kernel
takes the whole layer-stacked cache and a layer index, so a layer scan
that carries the stack as loop state hands it over without slicing a
layer out; a single layer's array is the stack of one.  On the chip a
tile is a whole number of 128-position lane tiles: a pool block must be
a multiple of 128 positions (``check_pool_block`` refuses any other —
the pool would be stored padded and re-laid out per call), and the ring
is built a multiple of 128 long beyond one lane tile
(``transformer.ring_len``), so its autotuned tile divides it.

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
(``k_pos > pos - window``).  **Length-aware tile skipping**: the index
maps clamp the logical tile to ``(pos + kq - 1) // bk`` — Pallas elides
the copy when the tile index repeats, and a ``pl.when`` guard skips the
compute, so a slot at position p reads ceil((p+1)/bk) tiles, not all.

Numerics contract: ``flash_step`` is the one statement of the recurrence
(op order, f32 state, -1e30 mask); the kernels call it per query row on
one slot and the ``kernels/ref`` oracles — the ``xla-ref`` dispatcher
backend — restate it batched over slots, with the same head-batched dots,
so ``pallas-interpret`` is bit-identical to the oracle for the same tile
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
           "check_pool_block", "flash_step", "key_valid", "shrink_block"]

_NEG_BIG = -1e30  # matches the pre-kernel einsum path's mask value
LANE = 128        # the chip's lane width: positions per minor tile


def shrink_block(bk: int, cap: int) -> int:
    """Largest block size ≤ bk that divides cap (cap stays un-padded: ring
    slots are positional state, padding would invent phantom slots).  In
    interpret mode every divisor is a legal tile; on the chip a ring tile
    lies on the lane axis and must be a multiple of 128 or the whole cap,
    which the autotuner's candidates are (``decode_attention_candidates``)."""
    bk = max(1, min(bk, cap))
    while cap % bk:
        bk -= 1
    return bk


def check_pool_block(bs: int) -> None:
    """Refuse a pool block the chip cannot store as the kernel reads it.
    Positions are the pool's minor (lane) axis, so a block of fewer than
    128 positions — or any size off a multiple of 128 — is padded to whole
    128-lane tiles in HBM: 8× the pool's bytes at 16 positions, and the
    compiler re-lays the pool out around every kernel call."""
    if bs % LANE:
        raise ValueError(
            f"paged KV block size {bs} is not a multiple of {LANE}: on a "
            f"TPU the pool's positions lie on the {LANE}-wide lane axis, so "
            "the pool would be padded and re-laid out around every kernel "
            f"call; use a block_size that is a multiple of {LANE}")


def flash_step(q, k, v, k_scale, v_scale, valid, m, s, acc):
    """One split-K online-softmax step over one cache tile, all KV heads.

    Shapes, with any number of leading batch dims ``...`` (none inside a
    kernel program, the slot axis in the oracles):
    q (..., n_kv, group, hd); k, v (..., n_kv, hd, bk) int8 codes or bf16;
    k_scale, v_scale (..., n_kv, bk) f32 or None — the caller passes only
    the KV heads' rows of a padded scale tile; ``valid`` broadcastable
    to (..., n_kv, group, bk); m, s (..., n_kv, group, 1) and
    acc (..., n_kv, group, hd) f32.  Returns the updated (m, s, acc).

    The logit dot is the plain batched matmul (n_kv, group, hd)·
    (n_kv, hd, bk); the value dot contracts the lane (position) axis of
    both operands.  The dots batch over the leading dims and the KV head
    in one ``dot_general`` whatever the leading rank: XLA's CPU backend
    sums an unbatched one-row (group == 1) dot in another order than a
    batched one, so the kernel and the oracle must both batch for their
    results to be bitwise equal.
    """
    n = q.ndim - 3
    batch = tuple(range(n + 1))           # leading dims and the KV head
    inv = float(1.0 / math.sqrt(q.shape[-1]))
    logits = jax.lax.dot_general(
        q, k.astype(q.dtype),  # int8→bf16 upcast, tile only
        dimension_numbers=(((n + 2,), (n + 1,)), (batch, batch)),
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
        dimension_numbers=(((n + 2,), (n + 2,)), (batch, batch)),
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


def _flash_body(*refs, bk: int, kq: int, window: int, quantized: bool,
                ring: bool):
    """One (slot b, logical tile j) program: the ``flash_step`` recurrence
    for kq query rows at positions pos_b .. pos_b+kq-1.

    Refs, in order: the scalar-prefetch positions, layer index and (pool)
    block table, consumed by the index maps; q (kq, n_kv, group, hd); K and
    V tiles (n_kv, hd, bk); when quantized the k/v scale tiles (rows, bk),
    rows ≥ n_kv (``transformer.scale_rows`` pads them to the f32 tile);
    on the ring the k_pos tile (1, bk) — the pool's key positions are
    implicit, ``j·bk + t``, since a pool block is append-only, never a
    ring; the (kq, n_kv, group, hd) output; and the m, s, acc scratch.

    The row loop is a *static Python* loop so each row runs the exact
    one-token dot shapes, op order and mask at position pos_b+t — a fused
    (kq·group)-row logit tile would change the float-summation shape and
    break the bitwise stream-parity contract of speculative decoding
    (DESIGN.md §14).  Row t freezes on tiles ``j > (pos_b+t)//bk``, so its
    processed-tile set matches sequential decode exactly.
    """
    pos_ref = refs[0]
    refs = refs[2 if ring else 3:]
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
        nkv = k.shape[0]          # scale rows past the heads are padding
        ks = ks_ref[:nkv, :] if quantized else None
        vs = vs_ref[:nkv, :] if quantized else None
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


def _flash_call(q, k, v, pos, layer, tables, k_scale, v_scale, k_pos, *,
                bk: int, window: int, interpret: bool) -> jax.Array:
    """The kernel over a layer-stacked cache → (B, kq, n_kv, group, hd) f32.

    q (B, kq, n_kv, group, hd); ``layer`` (1,) int32.  Pool (``tables``
    (B, nt) int32, each slot's physical block per logical block): k, v
    (L, n_blocks, n_kv, hd, bk) and k_scale, v_scale (L, n_blocks, rows,
    bk).  Ring (``tables`` None): k, v (L, B, n_kv, hd, cap), k_scale,
    v_scale (L, B, rows, cap), tiles of bk along cap, and the layer's
    k_pos (B, 1, cap).  Scale rows ≥ n_kv; those past n_kv are padding."""
    bsz, kq, nkv, group, hd = q.shape
    ring = tables is None
    quantized = k_scale is not None
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (bsz,))
    nt = k.shape[-1] // bk if ring else tables.shape[1]

    def tile(b, j, p_):             # logical tile, clamped to the last read
        return jnp.minimum(j, (p_[b] + kq - 1) // bk)

    if ring:
        prefetch = (pos, layer)

        def where(b, j, p_, l_):    # (layer, slot, tile along the lanes)
            return l_[0], b, tile(b, j, p_)
    else:
        prefetch = (pos, layer, jnp.asarray(tables, jnp.int32))

        def where(b, j, p_, l_, t_):  # (layer, physical block, whole block)
            return l_[0], t_[b, tile(b, j, p_)], 0

    def kv_map(b, j, *refs):
        lyr, n, t = where(b, j, *refs)
        return lyr, n, 0, 0, t

    def scale_map(b, j, *refs):
        lyr, n, t = where(b, j, *refs)
        return lyr, n, 0, t

    def row_map(b, j, *refs):
        return b, 0, 0, 0, 0

    inputs = [q, k, v]
    in_specs = [pl.BlockSpec((None, kq, nkv, group, hd), row_map),
                pl.BlockSpec((None, None, nkv, hd, bk), kv_map),
                pl.BlockSpec((None, None, nkv, hd, bk), kv_map)]
    if quantized:
        inputs += [k_scale, v_scale]
        in_specs += [pl.BlockSpec((None, None, k_scale.shape[-2], bk),
                                  scale_map)] * 2
    if ring:
        inputs.append(k_pos)
        in_specs.append(pl.BlockSpec(
            (None, 1, bk), lambda b, j, p_, l_: (b, 0, tile(b, j, p_))))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(bsz, nt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, kq, nkv, group, hd), row_map),
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
    )(*prefetch, *inputs)


def _layer_operand(layer) -> jax.Array:
    """The layer index as the kernel's (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


@functools.partial(
    jax.jit, static_argnames=("window", "block", "interpret"),
)
def verify_attention_call(
    q: jax.Array,        # (B, kq, n_kv, group, hd) — post-RoPE draft queries
    k: jax.Array,        # (L, B, n_kv, hd, cap) int8 codes or bf16
    v: jax.Array,        # (L, B, n_kv, hd, cap)
    k_pos: jax.Array,    # (L, B, cap) int32 — absolute position per slot
    pos: jax.Array,      # (B,) int32 — per-slot base (first-row) position
    k_scale: jax.Array | None = None,   # (L, B, rows, cap) f32 when int8
    v_scale: jax.Array | None = None,
    *,
    layer: jax.Array | int,   # index into the leading layer axis
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
    per-row recurrence matches it bit-for-bit.  The cache arrays are
    layer-stacked and the kernel reads layer ``layer``'s tiles in place.
    """
    bk = shrink_block(block[0], k.shape[-1])
    kp = jax.lax.dynamic_index_in_dim(k_pos, layer, keepdims=False)
    return _flash_call(q, k, v, pos, _layer_operand(layer), None, k_scale,
                       v_scale, kp[:, None, :], bk=bk, window=window,
                       interpret=interpret)


def decode_attention_call(q, k, v, k_pos, pos, k_scale=None, v_scale=None,
                          *, layer, window: int = 0,
                          block: tuple = (512,),
                          interpret: bool = True) -> jax.Array:
    """Flash-decode attention over the ring cache → (B, n_kv, group, hd) f32:
    ``verify_attention_call`` with one query row.  q is (B, n_kv, group,
    hd); ``block = (bk,)`` is the cache-length tile (shrunk to a divisor of
    cap).  The f32 output is unprojected attention; callers cast and apply
    W_O."""
    return verify_attention_call(
        q[:, None], k, v, k_pos, pos, k_scale, v_scale, layer=layer,
        window=window, block=block, interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify_attention_call(
    q: jax.Array,        # (B, kq, n_kv, group, hd) — post-RoPE draft queries
    k: jax.Array,        # (L, n_blocks, n_kv, hd, bs) int8 codes or bf16
    v: jax.Array,        # (L, n_blocks, n_kv, hd, bs)
    block_tables: jax.Array,  # (B, nbmax) int32 physical block per logical
    pos: jax.Array,      # (B,) int32 — per-slot base (first-row) position
    k_scale: jax.Array | None = None,   # (L, n_blocks, rows, bs) f32
    v_scale: jax.Array | None = None,
    *,
    layer: jax.Array | int,   # index into the leading layer axis
    window: int = 0,
    interpret: bool = True,
) -> jax.Array:
    """Paged multi-token verify attention → (B, kq, n_kv, group, hd) f32.

    The cache tile IS the pool block (bs = ``k.shape[-1]``): the index maps
    gather physical block ``block_tables[b, min(j, (pos[b]+kq-1)//bs)]`` of
    layer ``layer`` via the scalar-prefetched table and layer index, so a
    slot reads its own blocks wherever they live in the pool, and shared
    prefix blocks are fetched from the same physical tiles for every
    request that holds them.  The tile is pinned by the pool, so each
    query row is bit-identical to sequential paged decode at ``pos[b] + t``
    on every backend, and for bs == bk to the ring kernel on the same token
    stream.  The engine must have allocated blocks covering every row it
    intends to accept; deeper rows read whatever the (clamped) table gather
    returns and their output is discarded host-side.

    On the chip the pool block must be a whole number of 128-position lane
    tiles (``check_pool_block``)."""
    if not interpret:
        check_pool_block(k.shape[-1])
    return _flash_call(q, k, v, pos, _layer_operand(layer), block_tables,
                       k_scale, v_scale, None, bk=k.shape[-1], window=window,
                       interpret=interpret)


def paged_decode_attention_call(q, k, v, block_tables, pos, k_scale=None,
                                v_scale=None, *, layer, window: int = 0,
                                interpret: bool = True) -> jax.Array:
    """Paged flash-decode attention → (B, n_kv, group, hd) f32:
    ``paged_verify_attention_call`` with one query row (q is
    (B, n_kv, group, hd))."""
    return paged_verify_attention_call(
        q[:, None], k, v, block_tables, pos, k_scale, v_scale, layer=layer,
        window=window, interpret=interpret)[:, 0]
