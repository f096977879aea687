"""Kernel backend registry + dispatch (DESIGN.md §3).

One entry point per hot-path kernel — ``matmul`` (the fused §VIII 'separate'
quantise+multiply), ``quantize`` (elementwise codes), ``decode_attention``
(flash-decode over the serving ring KV cache, int8 dither codes consumed
in-kernel), ``paged_decode_attention`` (the same recurrence over the
paged block pool, gathered through a scalar-prefetched block table) and
their multi-token ``verify_attention`` / ``paged_verify_attention``
variants (k speculative query rows per slot, DESIGN.md §14) —
routed to one of three interchangeable backends:

* ``pallas-tpu``       — the compiled Pallas kernels (real TPU).
* ``pallas-interpret`` — the *same* kernel bodies evaluated in Pallas
  interpret mode; slow, but bit-exact with pallas-tpu, so CPU CI exercises
  the production code path (the parity tests in tests/test_dispatch.py).
* ``xla-ref``          — the pure-jnp oracles from kernels/ref.py lowered by
  XLA; the fast CPU path and the correctness anchor all backends must match.

Selection: an explicit ``backend=`` argument wins, then the
``REPRO_KERNEL_BACKEND`` environment variable, then platform detection
(TPU → pallas-tpu, anything else → xla-ref).  The aliases ``auto`` and
``pallas`` resolve the same way (``pallas`` insists on a Pallas backend:
interpret mode off-TPU).  All schemes share one PRNG contract — codes are a
stateless hash of (seed, element index, counter) — so switching backends
never changes results, only speed.

When no ``block`` is given, Pallas backends ask the autotuner: a cached
measured winner if one exists for (shape, dtype, bits, scheme, backend),
else the VMEM-budget model pick (kernels/autotune.py).

Sharded serving (DESIGN.md §9) calls every entry point *inside*
``shard_map``: the kernels see shard-local shapes — B/dp batch rows,
n_kv_heads/tp heads, the data shard's local block pool — and need no
mesh awareness of their own; per-shard results are bitwise the
single-device ones because batch rows and KV heads are embarrassingly
parallel dims of every kernel here.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels import ops as kops
from repro.kernels.decode_attention import (decode_attention_call,
                                            paged_decode_attention_call,
                                            paged_verify_attention_call,
                                            verify_attention_call)

__all__ = [
    "KernelBackend", "register_backend", "available_backends",
    "resolve_backend", "resolve_policy_backend", "matmul", "quantize",
    "decode_attention", "paged_decode_attention",
    "verify_attention", "paged_verify_attention", "DEFAULT_CPU_BACKEND",
]

ENV_VAR = "REPRO_KERNEL_BACKEND"
DEFAULT_CPU_BACKEND = "xla-ref"


@dataclass(frozen=True)
class KernelBackend:
    """A named implementation of the hot-path kernels.

    ``matmul(a, b, *, bits, scheme, counter, seed, a_range, b_range, fmt,
    block)`` → (M, N) f32;  ``quantize(x, *, bits, lo, hi, scheme, counter,
    seed, n_pulses, fmt, block)`` → (M, N) int32 codes;
    ``decode_attention(q, k, v, k_pos, pos, *, k_scale, v_scale, layer,
    window, block)`` → (B, n_kv, group, hd) f32 flash-decode attention over
    the ring KV cache (``layer`` indexes the cache's leading layer axis).
    ``block`` may be ignored by backends without a tiling concept
    — except for ``decode_attention``, where the block *is* part of the
    split-K recurrence contract and every backend honours it (xla-ref
    defaults to one whole-cap block).  ``paged_decode_attention(q, k, v,
    block_tables, pos, *, k_scale, v_scale, layer, window)`` is the
    paged-pool variant (DESIGN.md §6): the cache tile is pinned to the pool
    block size by the array layout, so it takes no ``block`` argument.
    """

    name: str
    matmul: Callable
    quantize: Callable
    decode_attention: Optional[Callable] = None
    paged_decode_attention: Optional[Callable] = None
    # multi-token verify variants (speculative decoding, DESIGN.md §14):
    # ``verify_attention(q, k, v, k_pos, pos, *, k_scale, v_scale, layer,
    # window, block)`` scores (B, kq, n_kv, group, hd) draft queries; the paged
    # variant again takes no block (tile = pool block)
    verify_attention: Optional[Callable] = None
    paged_verify_attention: Optional[Callable] = None


_REGISTRY: dict = {}


def register_backend(backend: KernelBackend) -> KernelBackend:
    _REGISTRY[backend.name] = backend
    return backend


def available_backends() -> tuple:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# built-in backends
# ---------------------------------------------------------------------------


def _make_pallas(name: str, interpret: bool) -> KernelBackend:
    def _matmul(a, b, *, bits, scheme, counter, seed, a_range, b_range, fmt,
                block):
        return kops.dither_matmul(
            a, b, bits=bits, scheme=scheme, counter=counter, seed=seed,
            a_range=a_range, b_range=b_range, fmt=fmt, block=block,
            interpret=interpret)

    def _quantize(x, *, bits, lo, hi, scheme, counter, seed, n_pulses, fmt,
                  block):
        return kops.quantize_2d(
            x, bits=bits, lo=lo, hi=hi, scheme=scheme, counter=counter,
            seed=seed, n_pulses=n_pulses, fmt=fmt, block=block,
            interpret=interpret)

    def _decode_attention(q, k, v, k_pos, pos, *, k_scale, v_scale, layer,
                          window, block):
        return decode_attention_call(
            q, k, v, k_pos, pos, k_scale, v_scale, layer=layer, window=window,
            block=tuple(block), interpret=interpret)

    def _paged_decode_attention(q, k, v, block_tables, pos, *, k_scale,
                                v_scale, layer, window):
        return paged_decode_attention_call(
            q, k, v, block_tables, pos, k_scale, v_scale, layer=layer,
            window=window, interpret=interpret)

    def _verify_attention(q, k, v, k_pos, pos, *, k_scale, v_scale, layer,
                          window, block):
        return verify_attention_call(
            q, k, v, k_pos, pos, k_scale, v_scale, layer=layer, window=window,
            block=tuple(block), interpret=interpret)

    def _paged_verify_attention(q, k, v, block_tables, pos, *, k_scale,
                                v_scale, layer, window):
        return paged_verify_attention_call(
            q, k, v, block_tables, pos, k_scale, v_scale, layer=layer,
            window=window, interpret=interpret)

    return register_backend(
        KernelBackend(name, _matmul, _quantize, _decode_attention,
                      _paged_decode_attention, _verify_attention,
                      _paged_verify_attention))


def _make_xla_ref() -> KernelBackend:
    # jit the oracle so xla-ref is the *fast* CPU path, not an eager one;
    # counter AND seed stay traced (the hash PRNG takes them as data), so
    # seed sweeps never recompile
    @functools.partial(jax.jit, static_argnames=(
        "bits", "scheme", "a_range", "b_range", "fmt"))
    def _matmul_jit(a, b, counter, seed, *, bits, scheme, a_range, b_range,
                    fmt):
        return ref.dither_matmul_ref(
            a.astype(jnp.float32), b.astype(jnp.float32), bits=bits,
            scheme=scheme, a_range=a_range, b_range=b_range,
            counter=counter, seed=seed, fmt=fmt)

    def _matmul(a, b, *, bits, scheme, counter, seed, a_range, b_range, fmt,
                block):
        del block  # XLA fuses; no explicit tiling
        return _matmul_jit(a, b, jnp.asarray(counter, jnp.int32),
                           jnp.asarray(seed, jnp.int32), bits=bits,
                           scheme=scheme, a_range=a_range, b_range=b_range,
                           fmt=fmt)

    @functools.partial(jax.jit, static_argnames=(
        "bits", "lo", "hi", "scheme", "n_pulses", "fmt"))
    def _quantize_jit(x, counter, seed, *, bits, lo, hi, scheme, n_pulses,
                      fmt):
        scale = ((1 << bits) - 1) / (hi - lo)
        return ref.quantize_codes_ref(
            x.astype(jnp.float32), scale=scale, zero=lo, bits=bits,
            scheme=scheme, counter=counter, seed=seed, n_pulses=n_pulses,
            fmt=fmt)

    def _quantize(x, *, bits, lo, hi, scheme, counter, seed, n_pulses, fmt,
                  block):
        del block
        return _quantize_jit(x, jnp.asarray(counter, jnp.int32),
                             jnp.asarray(seed, jnp.int32), bits=bits,
                             lo=lo, hi=hi, scheme=scheme, n_pulses=n_pulses,
                             fmt=fmt)

    @functools.partial(jax.jit, static_argnames=("window", "block"))
    def _decattn_jit(q, k, v, k_pos, pos, k_scale, v_scale, layer, *,
                     window, block):
        return ref.decode_attention_ref(
            q, k, v, k_pos, pos, k_scale, v_scale, layer=layer, window=window,
            block=block)

    def _decode_attention(q, k, v, k_pos, pos, *, k_scale, v_scale, layer,
                          window, block):
        # the oracle honours `block` (it is part of the split-K contract);
        # None collapses to one whole-cap block — the fast XLA serving path
        return _decattn_jit(q, k, v, k_pos, jnp.asarray(pos, jnp.int32),
                            k_scale, v_scale, layer, window=window,
                            block=None if block is None else tuple(block))

    @functools.partial(jax.jit, static_argnames=("window",))
    def _paged_jit(q, k, v, block_tables, pos, k_scale, v_scale, layer, *,
                   window):
        return ref.paged_decode_attention_ref(
            q, k, v, block_tables, pos, k_scale, v_scale, layer=layer,
            window=window)

    def _paged_decode_attention(q, k, v, block_tables, pos, *, k_scale,
                                v_scale, layer, window):
        # the paged recurrence's tile is the pool block itself, so the
        # oracle runs the exact kernel recurrence — no whole-cap collapse
        return _paged_jit(q, k, v, block_tables,
                          jnp.asarray(pos, jnp.int32), k_scale, v_scale,
                          layer, window=window)

    @functools.partial(jax.jit, static_argnames=("window", "block"))
    def _verify_jit(q, k, v, k_pos, pos, k_scale, v_scale, layer, *, window,
                    block):
        return ref.verify_attention_ref(
            q, k, v, k_pos, pos, k_scale, v_scale, layer=layer, window=window,
            block=block)

    def _verify_attention(q, k, v, k_pos, pos, *, k_scale, v_scale, layer,
                          window, block):
        # same block semantics as decode: None collapses to one whole-cap
        # block, which is also what the serving decode path uses off-TPU —
        # keeping verify and decode on the same association order is what
        # makes the spec-decode stream bitwise ≡ plain decode (DESIGN.md §14)
        return _verify_jit(q, k, v, k_pos, jnp.asarray(pos, jnp.int32),
                           k_scale, v_scale, layer, window=window,
                           block=None if block is None else tuple(block))

    @functools.partial(jax.jit, static_argnames=("window",))
    def _paged_verify_jit(q, k, v, block_tables, pos, k_scale, v_scale,
                          layer, *, window):
        return ref.paged_verify_attention_ref(
            q, k, v, block_tables, pos, k_scale, v_scale, layer=layer,
            window=window)

    def _paged_verify_attention(q, k, v, block_tables, pos, *, k_scale,
                                v_scale, layer, window):
        return _paged_verify_jit(q, k, v, block_tables,
                                 jnp.asarray(pos, jnp.int32), k_scale,
                                 v_scale, layer, window=window)

    return register_backend(
        KernelBackend("xla-ref", _matmul, _quantize, _decode_attention,
                      _paged_decode_attention, _verify_attention,
                      _paged_verify_attention))


_make_pallas("pallas-tpu", interpret=False)
_make_pallas("pallas-interpret", interpret=True)
_make_xla_ref()


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(name: Optional[str] = None) -> KernelBackend:
    """Explicit name > $REPRO_KERNEL_BACKEND > platform detection.

    Aliases: ``auto`` → pallas-tpu on TPU else the fast CPU reference;
    ``pallas`` → pallas-tpu on TPU else pallas-interpret; ``ref`` → xla-ref.
    ``pallas-tpu`` named off-TPU (explicitly or through the environment)
    raises here, before any kernel is traced.
    """
    if name is None or name == "auto":
        # 'auto' (and unset) defer to the environment before the platform
        # pick, so $REPRO_KERNEL_BACKEND redirects policy-driven call sites
        # (QuantPolicy.resolved passes 'auto' explicitly).
        name = os.environ.get(ENV_VAR) or "auto"
    if name == "auto":
        name = "pallas-tpu" if _on_tpu() else DEFAULT_CPU_BACKEND
    elif name == "pallas":
        name = "pallas-tpu" if _on_tpu() else "pallas-interpret"
    elif name == "ref":
        name = "xla-ref"
    try:
        backend = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel backend {name!r}; available: {available_backends()}"
        ) from None
    if name == "pallas-tpu" and not _on_tpu():
        raise RuntimeError(
            "kernel backend 'pallas-tpu' compiles for a TPU, but JAX's "
            f"default backend is {jax.default_backend()!r}; use "
            "'pallas-interpret' (the same kernel bodies, interpreted) or "
            "'xla-ref' off-TPU")
    return backend


def resolve_policy_backend(name: str) -> str:
    """QuantPolicy.backend resolution: 'jnp' (the unfused fake-quant path)
    passes through; everything else resolves to a concrete backend name."""
    if name == "jnp":
        return name
    return resolve_backend(name).name


# ---------------------------------------------------------------------------
# dispatch entry points
# ---------------------------------------------------------------------------


def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bits: int,
    scheme: str = "dither",
    counter=0,
    seed: int = 0,
    a_range: tuple = (0.0, 1.0),
    b_range: tuple = (0.0, 1.0),
    fmt: str = "spread",
    block: Optional[tuple] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Quantised A @ B through the selected backend (§VIII 'separate')."""
    be = resolve_backend(backend)
    if block is None and be.name.startswith("pallas"):
        (m, k), (_, n) = a.shape, b.shape
        block = autotune.best_block("matmul", (m, k, n), str(a.dtype), bits,
                                    scheme, be.name)
    return be.matmul(a, b, bits=bits, scheme=scheme, counter=counter,
                     seed=seed, a_range=a_range, b_range=b_range, fmt=fmt,
                     block=block)


def quantize(
    x: jax.Array,
    *,
    bits: int,
    lo: float = 0.0,
    hi: float = 1.0,
    scheme: str = "dither",
    counter=0,
    seed: int = 0,
    n_pulses: int = 16,
    fmt: str = "spread",
    block: Optional[tuple] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """k-bit integer codes of ``x`` through the selected backend."""
    be = resolve_backend(backend)
    if block is None and be.name.startswith("pallas"):
        block = autotune.best_block("quantize", x.shape, str(x.dtype), bits,
                                    scheme, be.name)
    return be.quantize(x, bits=bits, lo=lo, hi=hi, scheme=scheme,
                       counter=counter, seed=seed, n_pulses=n_pulses, fmt=fmt,
                       block=block)


def _ring_autotune_block(k, group: int, backend: str) -> tuple:
    """The autotuned ring tile for an (L, B, n_kv, hd, cap) cache, keyed
    by its per-layer (B, cap, n_kv, group, hd) shape."""
    b, nkv, hd, cap = k.shape[-4:]
    bits = 8 if k.dtype == jnp.int8 else 16
    return autotune.best_block("decode_attention", (b, cap, nkv, group, hd),
                               str(k.dtype), bits, "flash", backend)


def decode_attention(
    q: jax.Array,        # (B, n_kv_heads, group, hd) — post-RoPE queries
    k: jax.Array,        # (L, B, n_kv_heads, hd, cap) int8 codes or bf16
    v: jax.Array,        # (L, B, n_kv_heads, hd, cap)
    k_pos: jax.Array,    # (L, B, cap) int32 absolute position per slot
    pos: jax.Array,      # (B,) int32 per-slot decode position
    *,
    k_scale: Optional[jax.Array] = None,   # (L, B, n_kv, cap) f32 int8
    v_scale: Optional[jax.Array] = None,
    layer,               # index into the cache's leading layer axis
    window: int = 0,
    block: Optional[tuple] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Flash-decode attention over the ring KV cache → (B, n_kv, group, hd)
    f32, through the selected backend (DESIGN.md §2/§6).

    The int8 dither-quantised cache is consumed as codes — upcast tile-by-
    tile in VMEM, scales folded in after the dot — so the decode path never
    materialises a full-cap fp copy of the cache.  ``block=(bk,)`` is the
    cache-length tile of the split-K online-softmax recurrence; Pallas
    backends autotune it, xla-ref defaults to one whole-cap block.  The
    cache arrays carry a leading layer axis and the kernel reads layer
    ``layer`` in place.
    """
    be = resolve_backend(backend)
    if block is None and be.name.startswith("pallas"):
        block = _ring_autotune_block(k, q.shape[2], be.name)
    return be.decode_attention(q, k, v, k_pos, pos, k_scale=k_scale,
                               v_scale=v_scale, layer=layer, window=window,
                               block=block)


def paged_decode_attention(
    q: jax.Array,        # (B, n_kv_heads, group, hd) — post-RoPE queries
    k: jax.Array,        # (L, n_blocks, n_kv_heads, hd, bs) int8 or bf16
    v: jax.Array,        # (L, n_blocks, n_kv_heads, hd, bs)
    block_tables: jax.Array,  # (B, nbmax) int32 physical block per logical
    pos: jax.Array,      # (B,) int32 per-slot decode position
    *,
    k_scale: Optional[jax.Array] = None,  # (L, n_blocks, n_kv, bs) f32
    v_scale: Optional[jax.Array] = None,
    layer,               # index into the pool's leading layer axis
    window: int = 0,
    backend: Optional[str] = None,
) -> jax.Array:
    """Paged flash-decode attention over the block-pool KV cache →
    (B, n_kv, group, hd) f32, through the selected backend (DESIGN.md §6).

    The split-K tile is the pool block itself (``bs = k.shape[-1]``, chosen
    at pool-creation time from ``autotune.best_block('paged_attention',
    ...)``), so unlike the ring entry point there is no per-call ``block``:
    every backend runs the same per-block recurrence, and ``xla-ref`` is
    the bit-exact oracle rather than a whole-cap collapse.  The Pallas
    backends gather cache tiles through the scalar-prefetched block table
    and layer index, which is what makes refcount-shared prefix blocks
    readable by several requests at once, and a layer-stacked pool readable
    by layer, without any copy.
    """
    be = resolve_backend(backend)
    return be.paged_decode_attention(q, k, v, block_tables, pos,
                                     k_scale=k_scale, v_scale=v_scale,
                                     layer=layer, window=window)


def verify_attention(
    q: jax.Array,        # (B, kq, n_kv_heads, group, hd) — draft queries
    k: jax.Array,        # (L, B, n_kv_heads, hd, cap) int8 codes or bf16
    v: jax.Array,        # (L, B, n_kv_heads, hd, cap)
    k_pos: jax.Array,    # (L, B, cap) int32 absolute position per slot
    pos: jax.Array,      # (B,) int32 per-slot base (first-row) position
    *,
    k_scale: Optional[jax.Array] = None,   # (L, B, n_kv, cap) f32
    v_scale: Optional[jax.Array] = None,
    layer,
    window: int = 0,
    block: Optional[tuple] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Multi-token verify attention over the ring KV cache →
    (B, kq, n_kv, group, hd) f32, through the selected backend
    (DESIGN.md §14).

    Query row t of slot b attends as if decoding at position ``pos[b]+t``
    (per-row causal mask and processed-block freeze), so accepted draft
    rows reproduce sequential decode's attention bitwise on the same tile.
    That tile is therefore the one ``decode_attention`` picks for this
    cache, never one tuned for the verify shape: Pallas backends look up
    the decode key, and xla-ref defaults to one whole-cap block, as its
    decode path does (DESIGN.md §14).
    """
    be = resolve_backend(backend)
    if block is None and be.name.startswith("pallas"):
        block = _ring_autotune_block(k, q.shape[3], be.name)
    return be.verify_attention(q, k, v, k_pos, pos, k_scale=k_scale,
                               v_scale=v_scale, layer=layer, window=window,
                               block=block)


def paged_verify_attention(
    q: jax.Array,        # (B, kq, n_kv_heads, group, hd) — draft queries
    k: jax.Array,        # (L, n_blocks, n_kv_heads, hd, bs) int8 or bf16
    v: jax.Array,        # (L, n_blocks, n_kv_heads, hd, bs)
    block_tables: jax.Array,  # (B, nbmax) int32 physical block per logical
    pos: jax.Array,      # (B,) int32 per-slot base (first-row) position
    *,
    k_scale: Optional[jax.Array] = None,  # (L, n_blocks, n_kv, bs) f32
    v_scale: Optional[jax.Array] = None,
    layer,
    window: int = 0,
    backend: Optional[str] = None,
) -> jax.Array:
    """Paged multi-token verify attention → (B, kq, n_kv, group, hd) f32.

    The tile is the pool block (no per-call ``block``), so every backend
    runs the identical per-row recurrence and row t matches sequential
    paged decode at position pos+t bitwise — tile-pinned stream parity on
    every backend, not just xla-ref (DESIGN.md §14).
    """
    be = resolve_backend(backend)
    return be.paged_verify_attention(q, k, v, block_tables, pos,
                                     k_scale=k_scale, v_scale=v_scale,
                                     layer=layer, window=window)
