"""Decoder-only model assembly for every assigned architecture family.

Layers are *stacked per pattern position and scanned* (MaxText-style
scan-over-layers): for a block pattern of period P and R repeats, parameters
live as P pytrees whose leaves carry a leading (R, ...) axis, and the forward
pass is one ``lax.scan`` over R — this keeps HLO size and compile time
independent of depth (essential for the 512-device dry-run) and gives
per-repeat remat for free.  ``n_layers % P`` remainder layers are unrolled.

Decode uses a unified ring-buffer KV cache: capacity C = window (local
attention) or max_len (full attention), with *per-slot* absolute positions
(``cache["pos"]`` (B,), ``k_pos`` (B, C)) driving the mask — slots admitted
at different times decode independently, which is what the serving engine's
continuous batching needs (DESIGN.md §6).  One code path covers full,
sliding-window, SSM and RG-LRU layers (the latter two carry O(1) recurrent
states instead).  ``prefill_with_cache`` materialises the same cache from a
single batched forward.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.dist import ctx as dist_ctx
from repro.kernels.decode_attention import LANE
from repro.models import hybrid, layers, moe, ssm
from repro.models.config import ModelConfig
from repro.numerics.policy import QuantPolicy, dense

Params = Dict[str, Any]

__all__ = [
    "init_params", "forward", "decode_step", "init_cache", "prefill",
    "prefill_with_cache", "prefill_with_cache_chunked",
    "prefill_with_cache_paged", "merge_cache", "verify_step", "spec_commit",
]


def _kv_q8(t, ctr, idx, seed):
    """Dither-round K/V to int8 codes + per-position scales (DESIGN.md §2/§6).

    One quantiser for every cache write path — decode step, ring prefill
    scatter and paged prefill scatter — so the codes a position holds are a
    function of (value, absolute position + per-request offset, element
    index) only, never of *which* path wrote them.  That invariance is what
    makes paged prefix blocks bit-reusable across requests (DESIGN.md §6):
    dither codes are deterministic in absolute position (the Θ(1/N²)
    construction), where stochastic rounding would need hidden RNG state.
    ``ctr`` and ``idx`` broadcast against ``t``; callers pass the absolute
    position (+ offset) as ``ctr`` and the decode-step element index
    pattern as ``idx``.
    """
    from repro.core import rounding as _rnd

    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) + 1e-6
    scaled = t.astype(jnp.float32) / scale[..., None] * 127.0 + 128.0
    slot_d = _rnd.lcg_slot(ctr, idx, 16, seed=seed)
    u = _rnd.hash_uniform(seed ^ 0xD1CE, idx, ctr)
    codes = jnp.floor(scaled) + _rnd.dither_bit(
        scaled - jnp.floor(scaled), slot_d, u, 16)
    return (jnp.clip(codes, 0.0, 255.0) - 128.0).astype(jnp.int8), scale


def _kv_elem_idx(nkv: int, hd: int) -> jax.Array:
    """The (1, 1, nkv, hd) element-index pattern every KV-quantiser call
    site hashes with: global index head·hd + lane, broadcasting over batch
    rows and sequence positions.

    Deliberately *independent of the batch row*: a position's int8 codes
    must be a pure function of (value, absolute position + request offset,
    head, lane) — the bit-reusability contract behind paged prefix sharing
    (a shared block must not remember which slot wrote it, DESIGN.md §6)
    and behind sharded serving (continuous-batching slot placement shifts
    when slots partition across data shards, and the stream must not shift
    with it, DESIGN.md §9).  Distinct requests decorrelate through the
    counter term instead (position + per-request ``counter_offset``).
    Under tensor-parallel head sharding the model sees local heads; the
    shard's global head offset comes from ``dist.ctx.serve_shard_scope``.
    """
    info = dist_ctx.kv_shard_info()
    head0 = (info["head0"] if info is not None and info["heads_sharded"]
             else 0)
    head = jnp.asarray(head0, jnp.uint32) + jnp.arange(nkv, dtype=jnp.uint32)
    lane = jnp.arange(hd, dtype=jnp.uint32)
    return (head[:, None] * jnp.uint32(hd)
            + lane[None, :]).reshape(1, 1, nkv, hd)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _period(cfg: ModelConfig) -> int:
    return len(cfg.block_pattern) if cfg.block_pattern else 1


def _init_block(key, cfg: ModelConfig, kind: str) -> Params:
    d = cfg.d_model
    keys = jax.random.split(key, 4)
    p: Params = {"ln1": jnp.ones((d,), jnp.bfloat16)}
    if kind == "attn":
        p["attn"] = layers.init_attention(keys[0], cfg)
    elif kind == "rglru":
        p["rec"] = hybrid.init_rglru(keys[0], cfg)
    elif kind == "ssm":
        p["ssm"] = ssm.init_ssm(keys[0], cfg)
    else:
        raise ValueError(kind)
    if kind != "ssm":  # mamba2 blocks are norm→SSD only
        p["ln2"] = jnp.ones((d,), jnp.bfloat16)
        if cfg.n_experts:
            p["moe"] = moe.init_moe(keys[1], cfg)
        else:
            p["mlp"] = layers.init_mlp(keys[1], d, cfg.d_ff, cfg.mlp_act)
    return p


def init_params(key, cfg: ModelConfig) -> Params:
    p_ = _period(cfg)
    rep, rem = divmod(cfg.n_layers, p_)
    k_embed, k_head, k_blocks, k_rem = jax.random.split(key, 4)

    blocks = []
    if rep:
        for pos in range(p_):
            kind = cfg.layer_kind(pos)
            inits = [
                _init_block(jax.random.fold_in(k_blocks, pos * 1000 + r), cfg, kind)
                for r in range(rep)
            ]
            blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *inits))
    remainder = [
        _init_block(jax.random.fold_in(k_rem, i), cfg, cfg.layer_kind(rep * p_ + i))
        for i in range(rem)
    ]

    vp = cfg.vocab_padded()
    params: Params = {
        "embed": layers.init_embedding(k_embed, vp, cfg.d_model),
        "blocks": blocks,
        "remainder": remainder,
        "final_norm": jnp.ones((cfg.d_model,), jnp.bfloat16),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers._init(k_head, (cfg.d_model, vp), scale=0.02)
    return params


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


def scale_rows(nkv: int, head_shards: int = 1) -> int:
    """Rows of a positions-minor int8 scale array: its KV heads padded, per
    head shard, to a multiple of 8 — the f32 sublane tile.  A (n_kv, bs)
    plane of fewer rows pads on the chip anyway, so the compiler would
    store the array transposed and re-lay it out around every kernel call;
    padded, it is stored as the kernel reads it.  Padding rows hold zeros
    and are never read."""
    per = nkv // head_shards
    return head_shards * (-(-per // 8) * 8)


def ring_len(cfg: ModelConfig, max_len: int) -> int:
    """Positions a ring cache holds: the window or ``max_len``, rounded up
    to whole 128-position lane tiles beyond the first.  Positions are the
    ring's minor axis, so a longer ring off a lane multiple would leave
    the chip's kernel no tile that divides it but the whole ring, which
    need not fit VMEM.  The extra positions change nothing: a position
    lands at ``pos % ring_len``, and every read masks keys by position and
    window (``k_pos``), so an unwritten or out-of-window slot is never
    attended."""
    cap = min(cfg.window, max_len) if cfg.window else max_len
    return cap if cap <= LANE else -(-cap // LANE) * LANE


def kv_shapes(lead: tuple, nkv: int, hd: int, length: int, *,
              quantized: bool, head_shards: int = 1) -> dict:
    """Shapes of one attention layer's cache arrays — the one statement of
    the cache layout, for the model, the kernels' tests and tools.  Ring
    ``lead`` is (B,), the pool's (n_blocks,); ``length`` is the ring's
    positions or the pool block.  Heads-major with positions minor: K/V
    (*lead, n_kv, hd, length) and, int8, per-position scales (*lead,
    ``scale_rows``, length), so every (hd, positions) and (rows, positions)
    plane is the chip's dense tile and the decode kernel reads the cache
    where it lies (kernels/decode_attention.py)."""
    lead = tuple(lead)
    kv = lead + (nkv, hd, length)
    shapes = {"k": kv, "v": kv}
    if quantized:
        sc = lead + (scale_rows(nkv, head_shards), length)
        shapes.update(k_scale=sc, v_scale=sc)
    return shapes


def _kv_zeros(lead, cfg: ModelConfig, length: int, kv_quant: bool,
              head_shards: int):
    """Zeroed K/V (and int8 scale) arrays in the ``kv_shapes`` layout."""
    kv_dt = jnp.int8 if kv_quant else jnp.bfloat16
    return {name: jnp.zeros(shape, kv_dt if name in ("k", "v")
                            else jnp.float32)
            for name, shape in kv_shapes(
                lead, cfg.n_kv_heads, cfg.hd(), length, quantized=kv_quant,
                head_shards=head_shards).items()}


def _pad_rows(t, rows: int):
    """Pad axis -2 of a (…, n_kv, positions) scale array to ``rows``."""
    pad = [(0, 0)] * t.ndim
    pad[-2] = (0, rows - t.shape[-2])
    return jnp.pad(t, pad)


def _cache_entry(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                 kv_quant: bool = False, head_shards: int = 1):
    if kind == "attn":
        # Dither-quantised int8 cache when kv_quant (DESIGN.md §6 — the
        # paper's unbiased rounding applied to KV compression): codes + one
        # per-position, per-head scale; written with counter = pos (plus
        # an optional per-request offset, DESIGN.md §6), so re-decodes of
        # the same slot over time average out (§VII).
        cap = ring_len(cfg, max_len)
        entry = _kv_zeros((batch,), cfg, cap, kv_quant, head_shards)
        entry["k_pos"] = jnp.full((batch, cap), -1, jnp.int32)
        return entry
    if kind == "rglru":
        return hybrid.init_rglru_state(cfg, batch)
    if kind == "ssm":
        return ssm.init_ssm_state(cfg, batch)
    raise ValueError(kind)


def _paged_cache_entry(cfg: ModelConfig, kind: str, num_blocks: int,
                       block_size: int, kv_quant: bool,
                       data_shards: int = 1, head_shards: int = 1):
    """One attention layer's share of the paged block pool (DESIGN.md §6):
    ``num_blocks`` usable blocks of ``block_size`` token slots each, plus a
    trailing *trash* block (physical id ``num_blocks``) that absorbs writes
    routed through unallocated block-table entries — scatters never need a
    validity branch, and reads of the trash block are always masked.

    Sharded serving (DESIGN.md §9) partitions the pool on the 'data' axis:
    the leading block axis holds ``data_shards`` shard-local pools of
    ``num_blocks + 1`` blocks back to back, each with its *own* trash block,
    so block-table entries stay shard-local physical ids and every shard's
    scatter/gather runs on its local (num_blocks+1, ...) slice.

    Blocks are laid out by ``kv_shapes``; the quantiser still runs on the
    logical (..., n_kv, hd) values, and only the store is transposed."""
    if kind != "attn":
        raise ValueError("paged KV layout requires attention-only layers")
    return _kv_zeros((data_shards * (num_blocks + 1),), cfg, block_size,
                     kv_quant, head_shards)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               kv_quant: bool = False, kv_layout: str = "ring",
               block_size: Optional[int] = None,
               num_blocks: Optional[int] = None,
               data_shards: int = 1, head_shards: int = 1) -> Params:
    """Build the decode cache.  For the paged layout ``num_blocks`` counts
    usable blocks *per data shard* (``data_shards`` = 1 outside sharded
    serving, so it is simply the pool capacity) and ``block_tables`` entries
    are shard-local physical ids whose unset value is the shard-local trash
    block ``num_blocks`` (DESIGN.md §6/§9).  ``head_shards`` is the number
    of shards the KV heads split into; int8 scale rows are padded per
    shard (``scale_rows``)."""
    paged = kv_layout == "paged"
    if kv_layout not in ("ring", "paged"):
        raise ValueError(f"unknown kv_layout {kv_layout!r}")
    if data_shards < 1 or batch % data_shards:
        raise ValueError(f"batch {batch} must divide into {data_shards} "
                         "data shards")
    if paged:
        if not block_size or block_size <= 0:
            raise ValueError("paged kv_layout requires a positive block_size")
        nbmax = -(-max_len // block_size)          # blocks per full request
        num_blocks = (num_blocks if num_blocks is not None
                      else (batch // data_shards) * nbmax)
    p_ = _period(cfg)
    rep, rem = divmod(cfg.n_layers, p_)
    stacked = []
    if rep:
        for pos in range(p_):
            kind = cfg.layer_kind(pos)
            one = (_paged_cache_entry(cfg, kind, num_blocks, block_size,
                                      kv_quant, data_shards, head_shards)
                   if paged else _cache_entry(cfg, kind, batch, max_len,
                                              kv_quant, head_shards))
            stacked.append(
                jax.tree.map(lambda x: jnp.broadcast_to(x, (rep,) + x.shape), one)
            )
    remainder = [
        (_paged_cache_entry(cfg, cfg.layer_kind(rep * p_ + i), num_blocks,
                            block_size, kv_quant, data_shards, head_shards)
         if paged else
         _cache_entry(cfg, cfg.layer_kind(rep * p_ + i), batch, max_len,
                      kv_quant, head_shards))
        for i in range(rem)
    ]
    # "pos" is *per-slot* (B,): the serving engine admits requests into slots
    # at different times, so every slot decodes at its own absolute position.
    cache = {"pos": jnp.zeros((batch,), jnp.int32), "layers": stacked,
             "remainder": remainder}
    if paged:
        # logical → physical block map per slot; unset entries point at the
        # trash block so writes through them are harmless and reads masked
        cache["block_tables"] = jnp.full((batch, nbmax), num_blocks,
                                         jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# decode attention over the ring cache
# ---------------------------------------------------------------------------


def _layer_of(stack, layer):
    """One layer's entry of a layer-stacked cache entry (a dynamic slice)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        stack)


def _with_layer(stack, layer, entry):
    """``stack`` with layer ``layer`` replaced by ``entry``, in place."""
    return jax.tree.map(
        lambda a, e: jax.lax.dynamic_update_index_in_dim(
            a, e.astype(a.dtype), layer, 0), stack, entry)


def _write_positions(arr, layer, rows, slots, vals):
    """Write per-position columns into layer ``layer`` of a layer-stacked,
    positions-minor cache array, in place: ``vals[b, r]`` — an
    (n_kv[, hd]) column, or a scalar for ``k_pos`` — lands at position
    ``slots[b, r]`` of pool block / ring row ``rows[b, r]``.  A slot equal
    to the array's length drops its write.  rows, slots (B, R) int32.

    Scattering single positions into the lane axis, or gathering a window
    at a dynamic lane offset, makes the chip's compiler re-lay the whole
    array out around the op (each column is strided across tiles).  So
    each write reads the whole block or ring row that holds its position,
    sets the columns of every write of its slot that lands in it, and the
    whole blocks are scattered back: a gather and a scatter over the major
    (layer, block) axes only, in the array's own layout.  Copies of one
    block therefore carry the same bytes; distinct slots never share a
    block they write (a decoding slot owns its tail block, copy-on-write,
    DESIGN.md §6) except the trash block, which is never read."""
    length = arr.shape[-1]
    blk = arr[layer, rows]                                # (B, R, *inner, len)
    nin = blk.ndim - 3
    # write u lands in block copy t when both name the same block
    same = ((rows[:, :, None] == rows[:, None, :])
            & (slots < length)[:, None, :])                  # (B, Rt, Ru)
    lane = jnp.arange(length, dtype=jnp.int32)
    spread = (slice(None), slice(None)) + (None,) * nin + (slice(None),)
    vals = jnp.asarray(vals, arr.dtype)
    vals = jnp.pad(vals, [(0, 0), (0, 0)] + [
        (0, a - v) for a, v in zip(blk.shape[2:-1], vals.shape[2:])])
    for u in range(rows.shape[1]):
        hit = same[:, :, u, None] & (lane == slots[:, None, u, None])
        blk = jnp.where(hit[spread], vals[:, u][:, None, ..., None], blk)
    return arr.at[layer, rows].set(blk)


def _attention_decode(params, cfg: ModelConfig, x, cache, pos, policy, counter,
                      kv_offset=None, block_tables=None, *, layer):
    """One-token attention against the KV cache.  x: (B, 1, d).

    ``pos`` is the per-slot absolute position — scalar or (B,) — so slots
    admitted at different times decode independently.  ``kv_offset`` (B,)
    optionally shifts the dither counter of the int8 KV quantiser per slot
    (the engine threads each request's counter offset through it so
    concurrent requests walk independent pulse sequences, DESIGN.md §6).
    ``block_tables`` (B, nbmax) selects the *paged* cache layout: the new
    token scatters into pool block ``block_tables[b, pos//bs]`` at in-block
    position ``pos % bs`` and attention gathers through the table
    (``dispatch.paged_decode_attention``); without it the cache is the
    dense per-slot ring.  The cache entry is layer-stacked (a leading layer
    axis; a single layer is a stack of one): the new token's codes and
    scales are scattered into layer ``layer`` in place and the kernel reads
    the layer where it lies, so no layer's cache is ever sliced out or
    rewritten whole.
    """
    b = x.shape[0]
    hd, nh, nkv = cfg.hd(), cfg.n_heads, cfg.n_kv_heads
    paged = block_tables is not None
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    q = dense(x, params["wq"], policy, counter, seed=1).reshape(b, 1, nh, hd)
    k = dense(x, params["wk"], policy, counter, seed=2).reshape(b, 1, nkv, hd)
    v = dense(x, params["wv"], policy, counter, seed=3).reshape(b, 1, nkv, hd)
    if cfg.qkv_bias and "bq" in params:
        q = q + params["bq"].reshape(1, 1, nh, hd)
        k = k + params["bk"].reshape(1, 1, nkv, hd)
        v = v + params["bv"].reshape(1, 1, nkv, hd)
    posv = pos[:, None]
    q = layers.rope(q, posv, cfg.rope_theta)
    k = layers.rope(k, posv, cfg.rope_theta)

    # position is the minor axis of every layout: K/V are
    # (..., n_kv, hd, bs|cap), scales (..., n_kv, bs|cap)
    length = cache["k"].shape[-1]
    if paged:
        # physical block holding this token; engine guarantees it is
        # allocated (and uniquely owned — copy-on-write happens host-side)
        # before the tick, or points at the trash block for idle slots
        row = jnp.take_along_axis(block_tables, (pos // length)[:, None],
                                  axis=1)[:, 0]
    else:
        row = jnp.arange(b)
    rows, slots = row[:, None], jnp.mod(pos, length)[:, None]   # (B, 1)

    def write(name, vals):
        return _write_positions(cache[name], layer, rows, slots, vals)

    new_cache = {}
    if cache["k"].dtype == jnp.int8:
        # dither-round the new K/V token into int8 codes; the counter is the
        # per-slot absolute position (+ per-request offset)
        ctr = pos if kv_offset is None else pos + jnp.broadcast_to(
            jnp.asarray(kv_offset, jnp.int32), (b,))
        ctr4 = ctr.reshape(b, 1, 1, 1)
        idx4 = _kv_elem_idx(nkv, hd)
        kq, ks = _kv_q8(k, ctr4, idx4, 101)
        vq, vs = _kv_q8(v, ctr4, idx4, 102)
        new_cache["k_scale"] = write("k_scale", ks)
        new_cache["v_scale"] = write("v_scale", vs)
    else:
        kq, vq = k, v
    new_cache["k"] = write("k", kq)
    new_cache["v"] = write("v", vq)
    if not paged:
        new_cache["k_pos"] = write("k_pos", pos[:, None])

    # flash-decode over the cache through the kernel dispatcher (DESIGN.md
    # §2/§3): int8 codes stay codes — upcast tile-by-tile in VMEM,
    # per-position scales folded in after the dot — with validity /
    # causality / sliding-window masking and length-aware block skipping
    # in-kernel.  Backend: $REPRO_KERNEL_BACKEND or the platform default
    # (TPU → pallas-tpu, else the jitted xla-ref oracle).
    from repro.kernels import dispatch as _dispatch

    group = nh // nkv
    qg = q[:, 0].reshape(b, nkv, group, hd)
    if paged:
        attn = _dispatch.paged_decode_attention(
            qg, new_cache["k"], new_cache["v"], block_tables, pos,
            k_scale=new_cache.get("k_scale"),
            v_scale=new_cache.get("v_scale"),
            layer=layer, window=cfg.window or 0,
        )
    else:
        attn = _dispatch.decode_attention(
            qg, new_cache["k"], new_cache["v"], new_cache["k_pos"], pos,
            k_scale=new_cache.get("k_scale"),
            v_scale=new_cache.get("v_scale"),
            layer=layer, window=cfg.window or 0,
        )
    # sharded serving: heads all-gather before the replicated W_O so the
    # output contraction stays whole (bitwise contract, DESIGN.md §9);
    # identity outside a serve shard scope / under the GQA fallback
    out = dist_ctx.gather_heads(attn.astype(x.dtype).reshape(b, 1, nh * hd))
    return dense(out, params["wo"], policy, counter, seed=4), new_cache


# ---------------------------------------------------------------------------
# block application (shared by train / prefill / decode)
# ---------------------------------------------------------------------------


def _apply_block(
    bp: Params,
    cfg: ModelConfig,
    kind: str,
    x: jax.Array,
    positions,
    *,
    policy,
    counter,
    cache_entry=None,
    pos=None,
    window_override=None,
    kv_offset=None,
    collect_kv=False,
    block_tables=None,
    layer=None,
):
    """One block.  A ``cache_entry`` is layer-stacked and ``layer`` indexes
    it: the attention writes and reads that layer in place; a recurrent
    state is small, and is sliced out and written back."""
    h = layers.rms_norm(x, bp["ln1"], cfg.norm_eps)
    new_cache = cache_entry
    if kind == "attn":
        window = cfg.window if window_override is None else window_override
        if cache_entry is not None:
            out, new_cache = _attention_decode(bp["attn"], cfg, h, cache_entry,
                                               pos, policy, counter,
                                               kv_offset=kv_offset,
                                               block_tables=block_tables,
                                               layer=layer)
        else:
            out, kv = layers.attention(
                bp["attn"], cfg, h, positions, causal=True, window=window,
                policy=policy, counter=counter, return_kv=collect_kv,
            )
            if collect_kv:
                new_cache = kv
    elif kind in ("rglru", "ssm"):
        step, block_fn, key = ((hybrid.rglru_decode_step, hybrid.rglru_block,
                                "rec") if kind == "rglru" else
                               (ssm.ssm_decode_step, ssm.ssm_block, "ssm"))
        if cache_entry is not None:
            state = _layer_of(cache_entry, layer)
            out, new_state = step(bp[key], cfg, h, state, policy, counter)
            new_cache = _with_layer(cache_entry, layer, new_state)
        else:
            out = block_fn(bp[key], cfg, h, policy, counter)
    else:
        raise ValueError(kind)
    x = x + out

    if "mlp" in bp or "moe" in bp:
        h2 = layers.rms_norm(x, bp["ln2"], cfg.norm_eps)
        if "moe" in bp:
            x = x + moe.moe_ffn(bp["moe"], cfg, h2, policy, counter)
        else:
            x = x + layers.mlp(bp["mlp"], h2, cfg.mlp_act, policy, counter)
    return x, new_cache


# ---------------------------------------------------------------------------
# forward (train / prefill) and decode
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg, tokens, embeds):
    x = jnp.take(params["embed"], tokens, axis=0)
    if embeds is not None:  # multimodal stub frontend: prepend patch/frame embeds
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    return x


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    embeds: Optional[jax.Array] = None,
    policy: Optional[QuantPolicy] = None,
    counter=0,
    remat: bool = True,
) -> jax.Array:
    """Full-sequence forward → logits (B, S_total, vocab)."""
    x = _embed_inputs(params, cfg, tokens, embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    p_ = _period(cfg)

    def body(carry, xs):
        h = carry
        for pos_i in range(p_):
            kind = cfg.layer_kind(pos_i)
            h, _ = _apply_block(
                xs[pos_i], cfg, kind, h, positions, policy=policy, counter=counter,
            )
        return h, None

    body_fn = jax.checkpoint(body) if remat else body
    if params["blocks"]:
        x, _ = jax.lax.scan(body_fn, x, tuple(params["blocks"]))
    rep = cfg.n_layers // p_
    for i, bp in enumerate(params["remainder"]):
        kind = cfg.layer_kind(rep * p_ + i)
        x, _ = _apply_block(bp, cfg, kind, x, positions, policy=policy, counter=counter)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return dense(x, head, policy, counter, seed=9).astype(jnp.float32)


def prefill(params, cfg, tokens, *, embeds=None, policy=None, counter=0):
    """Prefill forward, logits only (the dry-run's compute-roofline cell).

    The serving engine uses ``prefill_with_cache`` below, which additionally
    materialises the ring-buffer decode cache; for roofline purposes prefill
    cost is the forward pass itself.
    """
    return forward(params, cfg, tokens, embeds=embeds, policy=policy,
                   counter=counter, remat=False)


def _positions_minor(t):
    """(B, L, n_kv[, hd]) values or scales, position-major as the model
    computes them → the cache's stored (B, n_kv[, hd], L)."""
    return jnp.moveaxis(t, 1, -1)


def _positions_major(t):
    """Inverse of ``_positions_minor``: a stored (B, n_kv[, hd], L) cache
    array → (B, L, n_kv[, hd])."""
    return jnp.moveaxis(t, -1, 1)


def _prefill_entry(cfg: ModelConfig, kv, lengths, cap: int, kv_quant: bool,
                   kv_offset):
    """Scatter one attention layer's full-sequence K/V into a ring cache entry.

    kv: post-RoPE ``(k, v)``, each (B, S, n_kv_heads, hd).  Ring slot j ends
    up holding the *last* prompt position p ≡ j (mod cap) below the slot's
    prompt length — bit-identical layout to what token-by-token decode
    writes would have left behind (including the dither-quantised int8
    codes, whose counter is the absolute position + per-request offset).
    """
    k_full, v_full = kv
    b, s = k_full.shape[0], k_full.shape[1]
    j = jnp.arange(cap, dtype=jnp.int32)[None, :]
    last = lengths[:, None].astype(jnp.int32) - 1              # (B, 1)
    pj = last - jnp.mod(last - j, cap)                         # (B, cap)
    valid = pj >= 0
    idx = jnp.clip(pj, 0, s - 1)
    gk = jnp.take_along_axis(k_full, idx[:, :, None, None], axis=1)
    gv = jnp.take_along_axis(v_full, idx[:, :, None, None], axis=1)
    k_pos = jnp.where(valid, pj, -1).astype(jnp.int32)

    if not kv_quant:
        zero = jnp.zeros((), jnp.bfloat16)
        return {
            "k": _positions_minor(jnp.where(valid[:, :, None, None],
                                            gk.astype(jnp.bfloat16), zero)),
            "v": _positions_minor(jnp.where(valid[:, :, None, None],
                                            gv.astype(jnp.bfloat16), zero)),
            "k_pos": k_pos,
        }

    off = (jnp.zeros((b,), jnp.int32) if kv_offset is None
           else jnp.broadcast_to(jnp.asarray(kv_offset, jnp.int32), (b,)))
    ctr = (pj + off[:, None])[:, :, None, None]                # (B, cap, 1, 1)
    nkv, hd = k_full.shape[2], k_full.shape[3]
    # same (row-independent) element indices as the decode-step quantiser —
    # see _kv_elem_idx for why the batch row must not enter the hash
    idx4 = _kv_elem_idx(nkv, hd)

    def q8(t, seed):
        q, scale = _kv_q8(t, ctr, idx4, seed)
        return (jnp.where(valid[:, :, None, None], q, jnp.int8(0)),
                jnp.where(valid[:, :, None], scale, 0.0))

    kq, ks = q8(gk, 101)
    vq, vs = q8(gv, 102)
    srows = scale_rows(nkv)
    return {"k": _positions_minor(kq), "v": _positions_minor(vq),
            "k_scale": _pad_rows(_positions_minor(ks), srows),
            "v_scale": _pad_rows(_positions_minor(vs), srows),
            "k_pos": k_pos}


def prefill_with_cache(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,    # (B, S) right-padded prompts
    lengths: jax.Array,   # (B,) true prompt lengths (0 = inactive row)
    max_len: int,
    *,
    policy: Optional[QuantPolicy] = None,
    counter=0,
    kv_quant: bool = False,
    kv_offset=None,
):
    """Batched prefill: one full-sequence forward that also materialises the
    ring-buffer decode cache (DESIGN.md §6).

    Attention-only architectures: every prompt token's K/V is computed in a
    single batched forward (right-padded; causal masking keeps real tokens
    blind to the padding) and scattered into the per-slot ring cache, so
    prompt cost is one forward instead of O(prompt_len) decode ticks.
    Returns ``(logits, cache)`` — logits (B, S, vocab_size) f32, and a cache
    whose ``pos`` is ``lengths``.  Architectures with recurrent state (SSM /
    RG-LRU) or an encoder are served by the scanned fallback in
    ``models/registry.apply_prefill`` instead.
    """
    for i in range(cfg.n_layers):
        if cfg.layer_kind(i) != "attn":
            raise ValueError("prefill_with_cache requires attention-only "
                             "layers; use registry.apply_prefill")
    x = jnp.take(params["embed"], tokens, axis=0)
    b, s, _ = x.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    p_ = _period(cfg)

    def body(carry, xs):
        h = carry
        kvs = []
        for pos_i in range(p_):
            h, kv = _apply_block(
                xs[pos_i], cfg, "attn", h, positions, policy=policy,
                counter=counter, collect_kv=True,
            )
            kvs.append(kv)
        return h, tuple(kvs)

    kv_stacked = ()
    if params["blocks"]:
        x, kv_stacked = jax.lax.scan(body, x, tuple(params["blocks"]))
    kv_rem = []
    for i, bp in enumerate(params["remainder"]):
        x, kv = _apply_block(bp, cfg, "attn", x, positions, policy=policy,
                             counter=counter, collect_kv=True)
        kv_rem.append(kv)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = dense(x, head, policy, counter, seed=9).astype(jnp.float32)
    logits = logits[:, :, : cfg.vocab_size]

    entry = functools.partial(_prefill_entry, cfg, lengths=lengths,
                              cap=ring_len(cfg, max_len),
                              kv_quant=kv_quant, kv_offset=kv_offset)
    # stacked pattern positions carry a leading repeat axis — vmap over it
    stacked = [jax.vmap(lambda kv: entry(kv))(kv) for kv in kv_stacked]
    remainder = [entry(kv) for kv in kv_rem]
    cache = {"pos": lengths, "layers": stacked, "remainder": remainder}
    return logits, cache


# ---------------------------------------------------------------------------
# chunked ring prefill: chunk forward + ring-history join + ring scatter
# ---------------------------------------------------------------------------


def _ring_scatter_chunk(entry, k, v, lengths, starts, kv_quant: bool,
                        kv_offset):
    """Merge one chunk's post-RoPE K/V (B, S, nkv, hd) into a live ring
    entry.  Gather-select form (the `_prefill_entry` idiom, inverted): for
    every ring slot j the chunk *covers* j iff some chunk position p ≡ j
    (mod cap) with p < starts + lengths — chunk positions are consecutive
    and the engine clamps chunks to ≤ cap tokens, so each slot is covered
    at most once and non-covered slots keep their old contents exactly
    (no scatter, no duplicate-index ordering hazard).  The int8 path
    quantises with counter = absolute position (+ per-request offset) and
    the decode-step element indices, so a chunk writes codes bit-identical
    to what whole-prompt prefill or token-by-token decode would have left
    at the same positions (DESIGN.md §6/§11).  The entry is stored with
    positions minor; the select runs in the stored layout."""
    cap = entry["k"].shape[-1]
    b, s = k.shape[0], k.shape[1]
    nkv, hd = k.shape[2], k.shape[3]
    j = jnp.arange(cap, dtype=jnp.int32)[None, :]              # (1, cap)
    st = starts[:, None].astype(jnp.int32)
    t = jnp.mod(j - st, cap)                                   # chunk index
    covered = t < lengths[:, None]                             # (B, cap)
    pj = st + t                                                # absolute pos
    idx = jnp.clip(t, 0, s - 1)
    gk = jnp.take_along_axis(k, idx[:, :, None, None], axis=1)
    gv = jnp.take_along_axis(v, idx[:, :, None, None], axis=1)
    k_pos = jnp.where(covered, pj, entry["k_pos"]).astype(jnp.int32)
    c4 = covered[:, None, None, :]                             # (B, 1, 1, cap)

    if not kv_quant:
        dt = entry["k"].dtype
        return {"k": jnp.where(c4, _positions_minor(gk.astype(dt)),
                               entry["k"]),
                "v": jnp.where(c4, _positions_minor(gv.astype(dt)),
                               entry["v"]),
                "k_pos": k_pos}

    off = (jnp.zeros((b,), jnp.int32) if kv_offset is None
           else jnp.broadcast_to(jnp.asarray(kv_offset, jnp.int32), (b,)))
    ctr = (pj + off[:, None])[:, :, None, None]
    idx4 = _kv_elem_idx(nkv, hd)
    kq, ks = _kv_q8(gk, ctr, idx4, 101)
    vq, vs = _kv_q8(gv, ctr, idx4, 102)
    c3 = covered[:, None, :]
    srows = entry["k_scale"].shape[-2]

    def scales(t, old):
        return jnp.where(c3, _pad_rows(_positions_minor(t), srows), old)

    return {"k": jnp.where(c4, _positions_minor(kq), entry["k"]),
            "v": jnp.where(c4, _positions_minor(vq), entry["v"]),
            "k_scale": scales(ks, entry["k_scale"]),
            "v_scale": scales(vs, entry["v_scale"]),
            "k_pos": k_pos}


def _ring_chunk_attention(params, cfg: ModelConfig, x, positions, lengths,
                          starts, entry, policy, counter, kv_quant: bool,
                          kv_offset):
    """Chunk attention for the chunked ring prefill: queries at absolute
    positions ``starts + t`` attend the in-batch chunk K/V
    (relative-causal, the cold path's grouped einsums) plus the slot's
    *already-written history* gathered from the live ring entry —
    positions with ``0 <= k_pos < start``, dequantised per position and
    joined before the softmax, exactly the paged prefill's prefix-join
    construction applied to the ring layout (DESIGN.md §11).  Returns
    ``(out, new_entry)`` with the chunk K/V merged into the ring."""
    b, s, _ = x.shape
    hd, nh, nkv = cfg.hd(), cfg.n_heads, cfg.n_kv_heads
    import math as _math

    q = dense(x, params["wq"], policy, counter, seed=1)
    k = dense(x, params["wk"], policy, counter, seed=2)
    v = dense(x, params["wv"], policy, counter, seed=3)
    if cfg.qkv_bias and "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    q = layers.rope(q, positions, cfg.rope_theta)
    k = layers.rope(k, positions, cfg.rope_theta)

    window = cfg.window or 0
    group = nh // nkv
    qg = q.reshape(b, s, nkv, group, hd)
    m_ss = layers.make_causal_mask(s, s, window=window)
    logits_s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) \
        / _math.sqrt(hd)
    logits_s = jnp.where(m_ss[None, None, None, :, :], logits_s, -1e30)

    # history join: the ring holds every still-reachable earlier position
    # (k_pos ∈ [0, start)); garbage slots carry k_pos = -1 or ≥ start and
    # mask out, so idle-window writes never leak into chunk attention
    hk, hv = _positions_major(entry["k"]), _positions_major(entry["v"])
    if "k_scale" in entry:
        hk = (hk.astype(jnp.float32)
              * (_positions_major(entry["k_scale"])[..., :nkv, None] / 127.0)
              ).astype(x.dtype)
        hv = (hv.astype(jnp.float32)
              * (_positions_major(entry["v_scale"])[..., :nkv, None] / 127.0)
              ).astype(x.dtype)
    kp = entry["k_pos"][:, None, :]                        # (B, 1, cap)
    q_abs = positions[:, :, None]                          # (B, S, 1)
    vp = (kp >= 0) & (kp < starts[:, None, None])
    if window:
        vp = vp & (kp > q_abs - window)
    logits_p = jnp.einsum("bqhgd,bkhd->bhgqk", qg, hk).astype(jnp.float32) \
        / _math.sqrt(hd)
    logits_p = jnp.where(vp[:, None, None, :, :], logits_p, -1e30)
    cap = entry["k"].shape[-1]
    probs = jax.nn.softmax(
        jnp.concatenate([logits_p, logits_s], axis=-1), axis=-1
    ).astype(x.dtype)
    out = (jnp.einsum("bhgqk,bkhd->bqhgd", probs[..., :cap], hv)
           + jnp.einsum("bhgqk,bkhd->bqhgd", probs[..., cap:], v))
    out = dist_ctx.gather_heads(out.reshape(b, s, nh * hd))
    out = dense(out, params["wo"], policy, counter, seed=4)

    new_entry = _ring_scatter_chunk(entry, k, v, lengths, starts, kv_quant,
                                    kv_offset)
    return out, new_entry


def _ring_chunk_block(bp, cfg: ModelConfig, x, positions, lengths, starts,
                      entry, policy, counter, kv_quant, kv_offset):
    """One transformer block of the chunked ring prefill — ``_apply_block``'s
    attn branch with the history-joining attention above."""
    h = layers.rms_norm(x, bp["ln1"], cfg.norm_eps)
    out, new_entry = _ring_chunk_attention(
        bp["attn"], cfg, h, positions, lengths, starts, entry, policy,
        counter, kv_quant, kv_offset)
    x = x + out
    if "mlp" in bp or "moe" in bp:
        h2 = layers.rms_norm(x, bp["ln2"], cfg.norm_eps)
        if "moe" in bp:
            x = x + moe.moe_ffn(bp["moe"], cfg, h2, policy, counter)
        else:
            x = x + layers.mlp(bp["mlp"], h2, cfg.mlp_act, policy, counter)
    return x, new_entry


def prefill_with_cache_chunked(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,    # (B, S) right-padded prompt *chunks*
    lengths: jax.Array,   # (B,) chunk lengths (0 = inactive row)
    starts: jax.Array,    # (B,) absolute position of each chunk's token 0
    cache: Params,        # live ring cache; chunk KV merges in place
    *,
    policy: Optional[QuantPolicy] = None,
    counter=0,
    kv_quant: bool = False,
    kv_offset=None,
):
    """Chunked ring prefill: one batched forward over per-slot prompt
    *chunks* that merges their K/V into the live ring cache (DESIGN.md
    §11).  A continuation chunk sets ``starts[b] > 0``: tokens before the
    start are not recomputed — their K/V is read back from the slot's own
    ring entry inside each layer's attention and joined before the
    softmax, so every chunk sees one joint distribution over its whole
    history.  ``starts = 0`` with the full prompt length degenerates to
    whole-prompt prefill of a fresh slot.  Chunks must be ≤ the ring
    capacity (the engine clamps).  Returns ``(logits (B, S, vocab_size),
    cache')`` with per-slot ``pos`` advanced to ``starts + lengths`` for
    active rows."""
    for i in range(cfg.n_layers):
        if cfg.layer_kind(i) != "attn":
            raise ValueError("chunked prefill requires attention-only "
                             "layers; use registry.apply_prefill")
    x = jnp.take(params["embed"], tokens, axis=0)
    b, s, _ = x.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    positions = starts[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    p_ = _period(cfg)

    def body(carry, xs):
        h = carry
        bp, ce = xs
        new_entries = []
        for pos_i in range(p_):
            h, ne = _ring_chunk_block(
                bp[pos_i], cfg, h, positions, lengths, starts, ce[pos_i],
                policy, counter, kv_quant, kv_offset)
            new_entries.append(ne)
        return h, tuple(new_entries)

    if params["blocks"]:
        x, new_layers = jax.lax.scan(
            body, x, (tuple(params["blocks"]), tuple(cache["layers"])))
    else:
        new_layers = ()
    new_rem = []
    for i, bp in enumerate(params["remainder"]):
        x, ne = _ring_chunk_block(
            bp, cfg, x, positions, lengths, starts, cache["remainder"][i],
            policy, counter, kv_quant, kv_offset)
        new_rem.append(ne)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = dense(x, head, policy, counter, seed=9).astype(jnp.float32)
    logits = logits[:, :, : cfg.vocab_size]
    new_cache = {
        "pos": jnp.where(lengths > 0, starts + lengths, cache["pos"]),
        "layers": list(new_layers),
        "remainder": new_rem,
    }
    return logits, new_cache


# ---------------------------------------------------------------------------
# paged prefill: suffix forward + prefix gather + block-pool scatter
# ---------------------------------------------------------------------------


def _gather_prefix(entry, layer, block_tables, prefix_blocks: int):
    """Gather the leading ``prefix_blocks`` logical blocks of every slot
    from layer ``layer`` of a layer-stacked pool entry → position-major
    tensors over
    (B, prefix_blocks·bs, ...).  Only those blocks are read.  Unallocated
    table entries point at the trash block; the caller masks those
    positions (implicit position ≥ the slot's prefix length)."""
    ix = (layer, block_tables[:, :prefix_blocks])          # (B, P) blocks

    def gather(x):            # (B, P, n_kv[, hd], bs) → (B, P·bs, n_kv[, hd])
        g = jnp.moveaxis(x[ix], -1, 2)
        return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])

    out = [gather(entry["k"]), gather(entry["v"])]
    if "k_scale" in entry:
        nkv = entry["k"].shape[-3]            # scale rows past it are padding
        out += [gather(entry["k_scale"])[..., :nkv],
                gather(entry["v_scale"])[..., :nkv]]
    else:
        out += [None, None]
    return out


def _paged_scatter_entry(entry, layer, k, v, positions, lengths, starts,
                         block_tables, kv_quant: bool, kv_offset):
    """Scatter one layer's suffix K/V (post-RoPE, (B, S, nkv, hd)) into its
    pool blocks — layer ``layer`` of a layer-stacked entry, in place.
    Suffix token s lands in logical block
    ``starts//bs + s//bs`` at in-block position ``s % bs`` (starts are
    block-aligned); blocks beyond the suffix length route to the trash
    block.  Whole blocks are written, transposed to the pool's
    positions-minor layout.  The int8 path quantises with counter =
    absolute position (+ per-request offset) and the decode-step element
    indices, on the logical (…, nkv, hd) values, so the codes are
    bit-identical to what token-by-token decode would have written — the
    bit-reusability contract behind prefix sharing (DESIGN.md §6)."""
    nbp, bs = entry["k"].shape[-4], entry["k"].shape[-1]
    trash = nbp - 1
    b, s = k.shape[0], k.shape[1]
    nkv, hd = k.shape[2], k.shape[3]
    nbmax = block_tables.shape[1]
    s_pad = -(-s // bs) * bs
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    pos_pad = starts[:, None] + jnp.arange(s_pad, dtype=jnp.int32)[None, :]
    jb_count = s_pad // bs
    jb = jnp.arange(jb_count, dtype=jnp.int32)[None, :]              # (1, JB)
    needed = jb * bs < lengths[:, None]                              # (B, JB)
    tj = jnp.clip(starts[:, None] // bs + jb, 0, nbmax - 1)
    phys = jnp.where(needed, jnp.take_along_axis(block_tables, tj, axis=1),
                     trash).reshape(-1)                              # (B·JB,)
    ix = (layer, phys)

    def blocks(t):            # (B, S_pad, nkv[, hd]) → (B·JB, nkv[, hd], bs)
        t = t.reshape((b * jb_count, bs) + t.shape[2:])
        return jnp.moveaxis(t, 1, -1)

    if not kv_quant:
        dt = entry["k"].dtype
        return {"k": entry["k"].at[ix].set(blocks(k.astype(dt))),
                "v": entry["v"].at[ix].set(blocks(v.astype(dt)))}

    off = (jnp.zeros((b,), jnp.int32) if kv_offset is None
           else jnp.broadcast_to(jnp.asarray(kv_offset, jnp.int32), (b,)))
    ctr = (pos_pad + off[:, None])[:, :, None, None]     # (B, S_pad, 1, 1)
    idx4 = _kv_elem_idx(nkv, hd)
    kq, ks = _kv_q8(k, ctr, idx4, 101)
    vq, vs = _kv_q8(v, ctr, idx4, 102)
    srows = entry["k_scale"].shape[-2]
    return {"k": entry["k"].at[ix].set(blocks(kq)),
            "v": entry["v"].at[ix].set(blocks(vq)),
            "k_scale": entry["k_scale"].at[ix].set(
                _pad_rows(blocks(ks), srows)),
            "v_scale": entry["v_scale"].at[ix].set(
                _pad_rows(blocks(vs), srows))}


def _paged_prefill_attention(params, cfg: ModelConfig, x, positions, lengths,
                             starts, block_tables, entry, layer, policy,
                             counter, kv_quant: bool, kv_offset,
                             prefix_blocks: int):
    """Suffix attention for the paged prefill: queries at absolute positions
    ``starts + t`` attend the in-batch suffix K/V (relative-causal, exactly
    the cold path's ``layers.attention`` grouped-einsum ops) plus — when
    ``prefix_blocks > 0`` — the prefix K/V gathered from the shared pool
    blocks, dequantised per position and joined *before* the softmax, so a
    prefix-hit request sees one joint distribution over its whole history.
    Returns ``(out, new_entry)`` with the suffix K/V scattered into the
    pool."""
    b, s, _ = x.shape
    hd, nh, nkv = cfg.hd(), cfg.n_heads, cfg.n_kv_heads
    import math as _math

    q = dense(x, params["wq"], policy, counter, seed=1)
    k = dense(x, params["wk"], policy, counter, seed=2)
    v = dense(x, params["wv"], policy, counter, seed=3)
    if cfg.qkv_bias and "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    q = layers.rope(q, positions, cfg.rope_theta)
    k = layers.rope(k, positions, cfg.rope_theta)

    window = cfg.window or 0
    group = nh // nkv
    qg = q.reshape(b, s, nkv, group, hd)
    # within-suffix mask is relative (suffix rows share one block-aligned
    # start each), identical to the cold path's make_causal_mask
    m_ss = layers.make_causal_mask(s, s, window=window)
    logits_s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) \
        / _math.sqrt(hd)
    logits_s = jnp.where(m_ss[None, None, None, :, :], logits_s, -1e30)

    if prefix_blocks:
        pk, pv, pks, pvs = _gather_prefix(entry, layer, block_tables,
                                          prefix_blocks)
        if pks is not None:
            pk = (pk.astype(jnp.float32) * (pks[..., None] / 127.0)).astype(x.dtype)
            pv = (pv.astype(jnp.float32) * (pvs[..., None] / 127.0)).astype(x.dtype)
        s_pre = pk.shape[1]
        kp = jnp.arange(s_pre, dtype=jnp.int32)[None, None, :]   # implicit pos
        q_abs = positions[:, :, None]
        vp = kp < starts[:, None, None]                          # (B, S, S_pre)
        if window:
            vp = vp & (kp > q_abs - window)
        logits_p = jnp.einsum("bqhgd,bkhd->bhgqk", qg, pk).astype(jnp.float32) \
            / _math.sqrt(hd)
        logits_p = jnp.where(vp[:, None, None, :, :], logits_p, -1e30)
        probs = jax.nn.softmax(
            jnp.concatenate([logits_p, logits_s], axis=-1), axis=-1
        ).astype(x.dtype)
        out = (jnp.einsum("bhgqk,bkhd->bqhgd", probs[..., :s_pre], pv)
               + jnp.einsum("bhgqk,bkhd->bqhgd", probs[..., s_pre:], v))
    else:
        probs = jax.nn.softmax(logits_s, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    out = dist_ctx.gather_heads(out.reshape(b, s, nh * hd))
    out = dense(out, params["wo"], policy, counter, seed=4)

    new_entry = _paged_scatter_entry(entry, layer, k, v, positions, lengths,
                                     starts, block_tables, kv_quant, kv_offset)
    return out, new_entry


def _paged_prefill_block(bp, cfg: ModelConfig, x, positions, lengths, starts,
                         block_tables, entry, layer, policy, counter,
                         kv_quant, kv_offset, prefix_blocks):
    """One transformer block of the paged prefill — ``_apply_block``'s attn
    branch with the prefix-aware attention above in place of
    ``layers.attention``."""
    h = layers.rms_norm(x, bp["ln1"], cfg.norm_eps)
    out, new_entry = _paged_prefill_attention(
        bp["attn"], cfg, h, positions, lengths, starts, block_tables, entry,
        layer, policy, counter, kv_quant, kv_offset, prefix_blocks)
    x = x + out
    if "mlp" in bp or "moe" in bp:
        h2 = layers.rms_norm(x, bp["ln2"], cfg.norm_eps)
        if "moe" in bp:
            x = x + moe.moe_ffn(bp["moe"], cfg, h2, policy, counter)
        else:
            x = x + layers.mlp(bp["mlp"], h2, cfg.mlp_act, policy, counter)
    return x, new_entry


def prefill_with_cache_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,    # (B, S) right-padded prompt *suffixes*
    lengths: jax.Array,   # (B,) suffix lengths (0 = inactive row)
    starts: jax.Array,    # (B,) block-aligned absolute position of token 0
    block_tables: jax.Array,  # (B, nbmax) int32 — full logical→physical map
    cache: Params,        # live paged cache; suffix KV scatters in place
    *,
    policy: Optional[QuantPolicy] = None,
    counter=0,
    kv_quant: bool = False,
    kv_offset=None,
    prefix_blocks: int = 0,
):
    """Batched paged prefill: one forward over the prompt *suffixes* that
    scatters their K/V into pool blocks (DESIGN.md §6).

    A prefix-cache hit sets ``starts[b] > 0``: tokens before the start are
    *not* recomputed — their K/V is gathered from the shared, refcounted
    pool blocks inside each layer's attention (``prefix_blocks`` bounds the
    gather; 0 on cold waves makes this exactly the cold batched prefill).
    ``starts`` must be multiples of the pool block size.  Returns
    ``(logits (B, S, vocab_size), cache')`` where ``cache'`` is the live
    cache with the suffix blocks written, per-slot ``pos`` advanced to
    ``starts + lengths`` for active rows, and ``block_tables`` installed.
    """
    for i in range(cfg.n_layers):
        if cfg.layer_kind(i) != "attn":
            raise ValueError("paged prefill requires attention-only layers")
    x = jnp.take(params["embed"], tokens, axis=0)
    b, s, _ = x.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    positions = starts[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

    def block(bp, kind, h, entry, layer):
        del kind  # attention-only (checked above)
        return _paged_prefill_block(
            bp, cfg, h, positions, lengths, starts, block_tables, entry,
            layer, policy, counter, kv_quant, kv_offset, prefix_blocks)

    # the pool rides in the layer scan's carry: each layer gathers only
    # its prefix blocks and writes only the suffix blocks, in place
    x, new_layers, new_rem = _scan_layers(params, cfg, cache, x, block)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = dense(x, head, policy, counter, seed=9).astype(jnp.float32)
    logits = logits[:, :, : cfg.vocab_size]
    new_cache = {
        "pos": jnp.where(lengths > 0, starts + lengths, cache["pos"]),
        "block_tables": block_tables,
        "layers": new_layers,
        "remainder": new_rem,
    }
    return logits, new_cache


def merge_cache(old: Params, new: Params, active: jax.Array) -> Params:
    """Per-slot cache insertion: rows of ``new`` where ``active`` (B,) bool
    replace rows of ``old`` — how prefill results enter the live engine
    cache, and how the scanned-prefill fallback freezes finished slots.

    Stacked pattern entries carry batch at axis 1 (leading repeat axis),
    remainder entries at axis 0; ``pos`` is (B,).  Paged caches never merge
    — their prefill scatters into the shared pool in place.
    """
    if "block_tables" in old or "block_tables" in new:
        raise ValueError("merge_cache applies to ring caches only; the paged "
                         "prefill writes the pool in place")
    def sel(axis):
        def f(o, n):
            shp = [1] * n.ndim
            shp[axis] = active.shape[0]
            return jnp.where(active.reshape(shp), n, o)
        return f

    return {
        "pos": jnp.where(active, new["pos"], old["pos"]),
        "layers": jax.tree.map(sel(1), old["layers"], new["layers"]),
        "remainder": jax.tree.map(sel(0), old["remainder"], new["remainder"]),
    }


def _scan_layers(params, cfg: ModelConfig, cache, x, block):
    """Run ``block(bp, kind, h, entry, layer) -> (h, entry')`` over every
    layer: one ``lax.scan`` over the stacked repeats, then the unrolled
    remainder.  The stacked cache entries ride in the scan's *carry* with
    the repeat index as ``layer``, so each block updates its layer of the
    stack in place — carried as scanned inputs and outputs instead, the
    stack would be sliced per layer, rewritten whole into a fresh output
    stack and copied back into the loop state on every call.  A remainder
    entry is a single layer: it runs as a stack of one, at layer 0.
    Returns ``(x, stacked entries', remainder entries')``."""
    p_ = _period(cfg)
    rep = cfg.n_layers // p_
    stacks = tuple(cache["layers"])
    if params["blocks"]:
        def body(carry, xs):
            h, st = carry
            bp, r = xs
            st = list(st)
            for pos_i in range(p_):
                h, st[pos_i] = block(bp[pos_i], cfg.layer_kind(pos_i), h,
                                     st[pos_i], r)
            return (h, tuple(st)), None

        (x, stacks), _ = jax.lax.scan(
            body, (x, stacks),
            (tuple(params["blocks"]), jnp.arange(rep, dtype=jnp.int32)))
    new_rem = []
    for i, bp in enumerate(params["remainder"]):
        one = jax.tree.map(lambda a: a[None], cache["remainder"][i])
        x, ne = block(bp, cfg.layer_kind(rep * p_ + i), x, one, 0)
        new_rem.append(jax.tree.map(lambda a: a[0], ne))
    return x, list(stacks), new_rem


def decode_step(
    params: Params,
    cfg: ModelConfig,
    token: jax.Array,   # (B,) int32 — the most recent token
    cache: Params,
    *,
    policy: Optional[QuantPolicy] = None,
    counter=0,
    kv_offset=None,
):
    """One decode step: (B,) token + cache → (B, vocab) logits, new cache.

    ``cache["pos"]`` is per-slot (B,); every slot advances by one.
    ``kv_offset`` (B,) shifts the int8-KV dither counter per slot
    (per-request counter offsets, DESIGN.md §6).  A cache carrying
    ``block_tables`` decodes against the paged block pool instead of the
    ring (the tables are loop-invariant across layers — every layer's pool
    shares one logical→physical map).

    The layer scan carries the stacked cache as loop state, not as scanned
    inputs and outputs: each layer writes its new token into the donated
    stack in place and the kernel reads that layer where it lies, so no
    step copies, slices or rewrites a layer's cache (``_scan_layers``).
    """
    pos = cache["pos"]
    block_tables = cache.get("block_tables")
    x = jnp.take(params["embed"], token[:, None], axis=0)
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]

    def block(bp, kind, h, entry, layer):
        return _apply_block(
            bp, cfg, kind, h, positions, policy=policy, counter=counter,
            cache_entry=entry, pos=pos, kv_offset=kv_offset,
            block_tables=block_tables, layer=layer)

    x, new_layer_caches, new_rem = _scan_layers(params, cfg, cache, x, block)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = dense(x, head, policy, counter, seed=9)[:, 0].astype(jnp.float32)
    logits = logits[:, : cfg.vocab_size]  # drop vocab padding for sampling
    new_cache = {
        "pos": pos + 1,
        "layers": new_layer_caches,
        "remainder": new_rem,
    }
    if block_tables is not None:
        new_cache["block_tables"] = block_tables
    return logits, new_cache


# ---------------------------------------------------------------------------
# speculative verify: k-token scoring + bulk commit (DESIGN.md §14)
# ---------------------------------------------------------------------------


def _attention_verify(params, cfg: ModelConfig, x, cache, pos, policy, counter,
                      kv_offset=None, alive=None, wcap=None,
                      block_tables=None, *, kq, layer):
    """k-token verify attention against the KV cache.  x: (B·K, 1, d),
    slot-major: row b·K + t is slot b's draft row t.

    Row t scores draft position ``pos + t``; every op is the *row-pure*
    analogue of ``_attention_decode`` so row t is bitwise what a one-token
    decode at ``pos + t`` would compute (given the same inputs — the
    bulk-commit contract, DESIGN.md §14).  The dense projections run on
    the decode step's (rows, 1, d) shape with K× the rows, the attention
    dots go through the per-row verify kernels, and the dither quantiser
    sees the same (value, position + offset, element index) triples decode
    would.

    All K draft positions are written up-front; the per-position causal mask
    (``k_pos``/implicit block positions ≤ query position) hides not-yet-
    "real" slots from earlier rows exactly as empty slots are hidden in
    decode.  ``alive`` (B,) bool and ``wcap`` (B,) bound the writes: row t
    of slot b writes only when ``alive[b] and t < wcap[b]`` — dead rows and
    over-budget draft positions route to a dropped out-of-bounds ring index
    or the paged trash block, so the verify forward never dirties cache
    state the commit cannot account for.
    """
    b = x.shape[0] // kq
    hd, nh, nkv = cfg.hd(), cfg.n_heads, cfg.n_kv_heads
    paged = block_tables is not None
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    q = dense(x, params["wq"], policy, counter, seed=1).reshape(b, kq, nh, hd)
    k = dense(x, params["wk"], policy, counter, seed=2).reshape(b, kq, nkv, hd)
    v = dense(x, params["wv"], policy, counter, seed=3).reshape(b, kq, nkv, hd)
    if cfg.qkv_bias and "bq" in params:
        q = q + params["bq"].reshape(1, 1, nh, hd)
        k = k + params["bk"].reshape(1, 1, nkv, hd)
        v = v + params["bv"].reshape(1, 1, nkv, hd)
    offs = jnp.arange(kq, dtype=jnp.int32)[None, :]
    posv = pos[:, None] + offs                                  # (B, K)
    q = layers.rope(q, posv, cfg.rope_theta)
    k = layers.rope(k, posv, cfg.rope_theta)

    if alive is None:
        alive = jnp.ones((b,), bool)
    if wcap is None:
        wcap = jnp.full((b,), kq, jnp.int32)
    writable = ((offs < jnp.asarray(wcap, jnp.int32)[:, None])
                & jnp.asarray(alive, bool)[:, None])            # (B, K)

    # positions are minor (see _attention_decode); ``layer`` indexes the
    # layer-stacked entry, written in place
    length = cache["k"].shape[-1]
    if paged:
        nbp = cache["k"].shape[-4]
        lb = jnp.clip(posv // length, 0, block_tables.shape[1] - 1)
        row = jnp.take_along_axis(block_tables, lb, axis=1)
        # non-writable draft positions go to the trash block (their logical
        # block may be unallocated or beyond this row's write budget)
        row = jnp.where(writable, row, nbp - 1)
        slot = jnp.mod(posv, length)
    else:
        row = jnp.broadcast_to(jnp.arange(b)[:, None], (b, kq))
        # slot == cap is out of bounds: the write drops it
        slot = jnp.where(writable, jnp.mod(posv, length), length)

    def write(name, vals):
        return _write_positions(cache[name], layer, row, slot, vals)

    new_cache = {}
    if cache["k"].dtype == jnp.int8:
        ctr = posv if kv_offset is None else posv + jnp.broadcast_to(
            jnp.asarray(kv_offset, jnp.int32), (b,))[:, None]
        ctr4 = ctr.reshape(b, kq, 1, 1)
        idx4 = _kv_elem_idx(nkv, hd)
        k8, ks = _kv_q8(k, ctr4, idx4, 101)
        v8, vs = _kv_q8(v, ctr4, idx4, 102)
        new_cache["k_scale"] = write("k_scale", ks)
        new_cache["v_scale"] = write("v_scale", vs)
    else:
        k8, v8 = k, v
    new_cache["k"] = write("k", k8)
    new_cache["v"] = write("v", v8)
    if not paged:
        new_cache["k_pos"] = write("k_pos", posv)

    from repro.kernels import dispatch as _dispatch

    group = nh // nkv
    qg = q.reshape(b, kq, nkv, group, hd)
    if paged:
        attn = _dispatch.paged_verify_attention(
            qg, new_cache["k"], new_cache["v"], block_tables, pos,
            k_scale=new_cache.get("k_scale"),
            v_scale=new_cache.get("v_scale"),
            layer=layer, window=cfg.window or 0,
        )
    else:
        attn = _dispatch.verify_attention(
            qg, new_cache["k"], new_cache["v"], new_cache["k_pos"], pos,
            k_scale=new_cache.get("k_scale"),
            v_scale=new_cache.get("v_scale"),
            layer=layer, window=cfg.window or 0,
        )
    out = dist_ctx.gather_heads(
        attn.astype(x.dtype).reshape(b * kq, 1, nh * hd))
    return dense(out, params["wo"], policy, counter, seed=4), new_cache


def _apply_verify_block(bp, cfg: ModelConfig, x, *, policy, counter,
                        cache_entry, pos, kv_offset, alive, wcap,
                        block_tables, kq, layer):
    """Verify-forward transformer block over x (B·K, 1, d): attention-only
    archs (the ``supports_spec_decode`` gate), so no SSM/RG-LRU branches.
    MLP and norms see the decode step's (rows, 1, d) shape; MoE is excluded
    by the gate (its capacity ranks cumsum over every token in the
    dispatch, so a verify row would compete with its own future draft
    positions)."""
    h = layers.rms_norm(x, bp["ln1"], cfg.norm_eps)
    out, new_cache = _attention_verify(bp["attn"], cfg, h, cache_entry, pos,
                                       policy, counter, kv_offset=kv_offset,
                                       alive=alive, wcap=wcap,
                                       block_tables=block_tables, kq=kq,
                                       layer=layer)
    x = x + out
    if "mlp" in bp or "moe" in bp:
        h2 = layers.rms_norm(x, bp["ln2"], cfg.norm_eps)
        if "moe" in bp:
            x = x + moe.moe_ffn(bp["moe"], cfg, h2, policy, counter)
        else:
            x = x + layers.mlp(bp["mlp"], h2, cfg.mlp_act, policy, counter)
    return x, new_cache


def verify_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, K) int32 — last committed token + k-1 drafts
    cache: Params,
    *,
    policy: Optional[QuantPolicy] = None,
    counter=0,
    kv_offset=None,
    alive=None,         # (B,) bool — rows holding a live request
    wcap=None,          # (B,) int32 — per-row cache-write budget (≤ K)
):
    """Score K draft positions per slot in one forward → (B, K, vocab)
    logits + the cache with all K positions written (DESIGN.md §14).

    ``logits[:, t]`` is bitwise the (B, vocab) logits ``decode_step`` would
    return at position ``pos + t`` after sequentially committing
    ``tokens[:, 1..t]`` — provided those tokens match what the sequential
    stream would have sampled (the accept condition the engine checks).
    ``cache["pos"]`` is *not* advanced: the caller commits the accepted
    prefix with ``spec_commit`` once accept lengths are known.

    The forward runs on a (B·K, 1, d) view of the rows — the decode step's
    own shape with K× the rows — not on (B, K, d): a TPU compiler lays a
    (B, K, d) row reduction (RMSNorm's mean) out differently from a
    (B, 1, d) one and sums its rows in another order, which breaks the
    bitwise contract on the chip while every CPU test still passes.
    """
    pos = cache["pos"]
    block_tables = cache.get("block_tables")
    b, kq = tokens.shape
    x = jnp.take(params["embed"], tokens.reshape(b * kq, 1), axis=0)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    def block(bp, kind, h, entry, layer):
        del kind  # attention-only (the supports_spec_decode gate)
        return _apply_verify_block(
            bp, cfg, h, policy=policy, counter=counter, cache_entry=entry,
            pos=pos, kv_offset=kv_offset, alive=alive, wcap=wcap,
            block_tables=block_tables, kq=kq, layer=layer)

    x, new_layer_caches, new_rem = _scan_layers(params, cfg, cache, x, block)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = dense(x, head, policy, counter, seed=9).reshape(b, kq, -1)
    logits = logits.astype(jnp.float32)[..., : cfg.vocab_size]
    new_cache = {
        "pos": pos,
        "layers": new_layer_caches,
        "remainder": new_rem,
    }
    if block_tables is not None:
        new_cache["block_tables"] = block_tables
    return logits, new_cache


def spec_commit(cache: Params, new_pos, written, *, draft_k: int) -> Params:
    """Bulk-commit a verified window: ``pos`` advances to ``new_pos`` and the
    rejected suffix — draft positions in ``[new_pos, pos + written)`` — is
    scrubbed back to the never-written state (codes/scales zeroed, ring
    ``k_pos`` reset to -1) so the cache is byte-identical to one that only
    ever decoded the accepted tokens (DESIGN.md §14).

    The accepted prefix needs no touch-up: dither codes are position-pure,
    so the bytes the verify forward wrote at positions ``< new_pos`` are
    already exactly what sequential decode would have written.  ``written``
    (B,) is the per-row write budget the verify forward ran with (0 for
    dead rows); ``draft_k`` is the static window width.
    """
    old = jnp.asarray(cache["pos"], jnp.int32)
    new_pos = jnp.asarray(new_pos, jnp.int32)
    written = jnp.asarray(written, jnp.int32)
    b = old.shape[0]
    offs = jnp.arange(draft_k, dtype=jnp.int32)[None, :]
    p = old[:, None] + offs                                     # (B, K)
    stale = (offs < written[:, None]) & (p >= new_pos[:, None])
    block_tables = cache.get("block_tables")
    paged = block_tables is not None

    def scrub(e, lead):
        # positions are minor: K/V (..., n_kv, hd, len), scales (..., n_kv,
        # len), ring k_pos (..., B, len)
        length = e["k"].shape[-1]
        if paged:
            nbp = e["k"].shape[-4]
            lb = jnp.clip(p // length, 0, block_tables.shape[1] - 1)
            row = jnp.take_along_axis(block_tables, lb, axis=1)
            row = jnp.where(stale, row, nbp - 1)       # non-stale → trash
            slot = jnp.mod(p, length)
        else:
            row = jnp.broadcast_to(jnp.arange(b)[:, None], p.shape)
            slot = jnp.where(stale, jnp.mod(p, length), length)  # OOB drop

        def write(a, val):
            # every layer of the stack (a remainder entry: a stack of one)
            # scrubs the same positions, each in place
            stack = a if lead else a[None]
            fill = jnp.full(row.shape + stack.shape[2:-1], val, a.dtype)
            stack = jax.lax.fori_loop(
                0, stack.shape[0],
                lambda l, st: _write_positions(st, l, row, slot, fill), stack)
            return stack if lead else stack[0]

        return {name: write(a, -1 if name == "k_pos" else 0)
                for name, a in e.items()}

    new_cache = {
        "pos": new_pos,
        "layers": [scrub(e, True) for e in cache["layers"]],
        "remainder": [scrub(e, False) for e in cache["remainder"]],
    }
    if paged:
        new_cache["block_tables"] = block_tables
    return new_cache
