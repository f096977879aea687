"""Pallas TPU kernel: fused dither-quantised matmul (paper §VIII 'separate'
variant, the production path — DESIGN.md §2 "AND-gate multiply → MXU matmul").

C = dequant( Q_dither(A) @ Q_dither(B) ) computed tile-by-tile:

  grid = (M/bm, N/bn, K/bk), K innermost (sequential accumulation);
  A tile (bm, bk) and B tile (bk, bn) are quantised to k-bit codes *in VMEM*
  (recomputed per grid step — rounding is a stateless hash of
  (seed, element, counter), so requantisation is free of statistical cost),
  multiplied on the MXU, accumulated in an f32 VMEM scratch.  Affine-zero
  cross terms are accumulated alongside via row/col code sums so signed
  ranges ([-1, 1] weights) are exact.

Default tiles (bm, bn, bk) = (256, 256, 512): A 512 KiB + B 512 KiB +
acc 256 KiB + sums ≈ 1.3 MiB VMEM — fits v5e VMEM with double buffering.
All dims multiples of (8, 128) f32 tiling and the 128×128 MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rounding

__all__ = ["dither_matmul_kernel_call"]


def _quantize_tile(x, row0, col0, n_cols, *, scale, zero, bits, scheme, seed, n_pulses, fmt, counter):
    """Quantise one VMEM tile to codes (f32-valued integers, clipped)."""
    bm, bn = x.shape
    scaled = (x - zero) * scale
    fl = jnp.floor(scaled)
    f = scaled - fl
    row = row0 + jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 0)
    col = col0 + jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 1)
    idx = row * jnp.uint32(n_cols) + col
    if scheme == "deterministic":
        codes = jnp.floor(scaled + 0.5)
    elif scheme == "stochastic":
        u = rounding.hash_uniform(seed, idx, counter)
        codes = fl + (u < f).astype(jnp.float32)
    elif scheme == "dither":
        slot = rounding.slot_index(counter, idx, n_pulses, seed=seed, fmt=fmt)
        u = rounding.hash_uniform(seed ^ 0xD1CE, idx, counter)
        codes = fl + rounding.dither_bit(f, slot, u, n_pulses)
    else:
        raise ValueError(scheme)
    return jnp.clip(codes, 0.0, float((1 << bits) - 1))


def _matmul_body(
    counter_ref,
    a_ref,
    b_ref,
    out_ref,
    acc_ref,
    rowsum_ref,
    colsum_ref,
    *,
    bits: int,
    scheme: str,
    seed: int,
    sa: float,
    sb: float,
    a_zero: float,
    b_zero: float,
    k_total: int,
    a_cols: int,
    b_cols: int,
    n_pulses_a: int,
    n_pulses_b: int,
    fmt: str,
    block: tuple,
):
    bm, bn, bk = block
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    counter = counter_ref[0, 0].astype(jnp.uint32)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        rowsum_ref[...] = jnp.zeros_like(rowsum_ref)
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    ca = _quantize_tile(
        a_ref[...], i * bm, k * bk, a_cols,
        scale=sa, zero=a_zero, bits=bits, scheme=scheme, seed=seed,
        n_pulses=n_pulses_a, fmt=fmt, counter=counter,
    )
    cb = _quantize_tile(
        b_ref[...], k * bk, j * bn, b_cols,
        scale=sb, zero=b_zero, bits=bits, scheme=scheme, seed=seed + 1,
        n_pulses=n_pulses_b, fmt=fmt, counter=counter,
    )
    acc_ref[...] += jax.lax.dot(
        ca, cb, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    # cross-term accumulators for affine zeros (Σ_j codes along K)
    rowsum_ref[...] += jnp.sum(ca, axis=1, keepdims=True)
    colsum_ref[...] += jnp.sum(cb, axis=0, keepdims=True)

    @pl.when(k == nk - 1)
    def _finish():
        out = acc_ref[...] / (sa * sb)
        out += a_zero * colsum_ref[...] / sb
        out += b_zero * rowsum_ref[...] / sa
        out += float(k_total) * a_zero * b_zero
        out_ref[...] = out


@functools.partial(
    jax.jit,
    static_argnames=(
        "bits", "scheme", "seed", "a_range", "b_range", "fmt", "block",
        "interpret", "true_shape",
    ),
)
def dither_matmul_kernel_call(
    a: jax.Array,
    b: jax.Array,
    counter: jax.Array,
    *,
    bits: int,
    scheme: str = "dither",
    seed: int = 0,
    a_range: tuple = (0.0, 1.0),
    b_range: tuple = (0.0, 1.0),
    fmt: str = "spread",
    block: tuple = (256, 256, 512),
    interpret: bool = True,
    true_shape: tuple | None = None,
) -> jax.Array:
    """Fused quantise+matmul.  a: (M, K) f32, b: (K, N) f32 → (M, N) f32.

    Dither pulse counts follow §VII: N_A = N (each A element reused per
    output column), N_B = M.  Shapes must divide the block (ops.py pads).
    ``true_shape=(m, k, n)`` gives the pre-padding dims so the PRNG element
    indices and pulse counts are identical to the unpadded oracle.
    """
    (m, k), (k2, n) = a.shape, b.shape
    assert k == k2
    tm, tk, tn = true_shape or (m, k, n)
    bm, bn, bk = min(block[0], m), min(block[1], n), min(block[2], k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, block)
    levels = float((1 << bits) - 1)
    sa = levels / (a_range[1] - a_range[0])
    sb = levels / (b_range[1] - b_range[0])
    counter = counter.reshape(1, 1).astype(jnp.int32)

    body = functools.partial(
        _matmul_body,
        bits=bits, scheme=scheme, seed=seed, sa=sa, sb=sb,
        a_zero=a_range[0], b_zero=b_range[0], k_total=tk,
        a_cols=tk, b_cols=tn,
        n_pulses_a=max(tn, 2), n_pulses_b=max(tm, 2),
        fmt=fmt, block=(bm, bn, bk),
    )
    return pl.pallas_call(
        body,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        name="dither_matmul",
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(counter, a, b)
