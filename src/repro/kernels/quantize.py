"""Pallas TPU kernel: tiled elementwise quantisation with dither /
stochastic / deterministic rounding (paper §VII).

The kernel quantises a 2-D f32 tensor to k-bit integer codes, tile by tile
(BlockSpec VMEM tiling).  Per element it evaluates the counter-indexed dither
pulse lazily — LCG permutation slot + murmur-hash Bernoulli tail — i.e. pure
VPU integer math; no pulse sequences are materialised (DESIGN.md §2).

Layout notes (TPU target):
  * blocks default to (256, 256) f32 — 256 KiB in, 256 KiB out (int32), well
    under the ~16 MiB VMEM budget, multiples of the (8, 128) f32 tile.
  * the counter is a (1, 1) int32 operand so that advancing i_s between
    steps does NOT retrace/recompile; everything else is compile-time static.
  * validated on CPU via interpret=True against kernels/ref.py (bit-exact).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import rounding

__all__ = ["quantize_kernel_call"]


def _quantize_body(
    counter_ref,
    x_ref,
    out_ref,
    *,
    scale: float,
    zero: float,
    bits: int,
    scheme: str,
    seed: int,
    n_pulses: int,
    fmt: str,
    n_cols: int,
    block: tuple,
):
    """One (bm, bn) tile: codes = clip(round((x - zero)·scale), 0, 2^k−1)."""
    bm, bn = block
    pid_m = pl.program_id(0)
    pid_n = pl.program_id(1)
    counter = counter_ref[0, 0].astype(jnp.uint32)

    x = x_ref[...]
    scaled = (x - zero) * scale
    fl = jnp.floor(scaled)
    f = scaled - fl

    # Global flattened (row-major) element index — matches the ref oracle.
    row = pid_m * bm + jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 0)
    col = pid_n * bn + jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 1)
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

    levels = float((1 << bits) - 1)
    out_ref[...] = jnp.clip(codes, 0.0, levels).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "zero", "bits", "scheme", "seed", "n_pulses", "fmt", "block",
        "interpret",
    ),
)
def quantize_kernel_call(
    x: jax.Array,
    counter: jax.Array,
    *,
    scale: float,
    zero: float,
    bits: int,
    scheme: str = "dither",
    seed: int = 0,
    n_pulses: int = 16,
    fmt: str = "spread",
    block: tuple = (256, 256),
    interpret: bool = True,
) -> jax.Array:
    """Tiled quantisation.  x: (M, N) f32, counter: (1, 1) int32 → (M, N) int32.

    M, N must be divisible by the block shape (callers pad; the ops.py
    wrapper handles padding/unpadding automatically).
    """
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0, (x.shape, block)
    counter = counter.reshape(1, 1).astype(jnp.int32)

    body = functools.partial(
        _quantize_body,
        scale=scale, zero=zero, bits=bits, scheme=scheme, seed=seed,
        n_pulses=n_pulses, fmt=fmt, n_cols=n, block=(bm, bn),
    )
    return pl.pallas_call(
        body,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),  # counter (scalar)
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        name="dither_quantize",
        interpret=interpret,
    )(counter, x)
