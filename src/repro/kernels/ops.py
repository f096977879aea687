"""jit'd public wrappers for the Pallas kernels (padding, dtype, dispatch).

``interpret`` defaults to auto: real TPU → compiled kernel, anything else →
interpret mode (Python evaluation of the same kernel body), so tests/CI on
CPU exercise identical code paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dither_matmul import dither_matmul_kernel_call
from repro.kernels.quantize import quantize_kernel_call

__all__ = ["quantize_2d", "dither_matmul", "on_tpu"]

_LANE = 128  # TPU vector lane width: minor tile dims are multiples of it


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad2(x: jax.Array, bm: int, bn: int, value: float = 0.0) -> jax.Array:
    m, n = x.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)), constant_values=value)
    return x


def quantize_2d(
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
    block: tuple = (256, 256),
    interpret: bool | None = None,
) -> jax.Array:
    """Quantise a 2-D f32 array to k-bit int32 codes via the Pallas kernel."""
    if interpret is None:
        interpret = not on_tpu()
    m, n = x.shape
    scale = ((1 << bits) - 1) / (hi - lo)
    xp = _pad2(x.astype(jnp.float32), *block, value=lo)
    counter = jnp.asarray(counter, jnp.int32).reshape(1, 1)
    # NOTE: padding changes n_cols → flat indices differ from the unpadded
    # oracle only in the padded region, because the kernel derives n_cols
    # from the padded width.  We therefore pass the padded width to ref in
    # tests; statistically the index is just a PRNG stream id.
    codes = quantize_kernel_call(
        xp, counter, scale=scale, zero=lo, bits=bits, scheme=scheme,
        seed=seed, n_pulses=n_pulses, fmt=fmt, block=block, interpret=interpret,
    )
    return codes[:m, :n]


def dither_matmul(
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
    block: tuple = (256, 256, 512),
    interpret: bool | None = None,
) -> jax.Array:
    """Fused k-bit quantised matmul (§VIII 'separate'), padded to blocks.

    Zero-padding is exact: padding A/B with the range zero-point contributes
    code 0 … but code 0 maps back to `lo`, so instead we pad with `lo` and
    slice the result — cross terms from padded K rows would bias the output
    when lo ≠ 0, so K padding pads A with a_lo-equivalent zeros AND masks by
    padding B's rows with b's zero-point.  To keep the kernel exact we
    require K % bk == 0: the wrapper shrinks bk to the largest multiple of
    128 that divides K, or takes the whole of K when none does.
    """
    if interpret is None:
        interpret = not on_tpu()
    (m, k), (_, n) = a.shape, b.shape
    bm, bn, bk = block
    # exact K handling: a K tile that divides K (no K padding ⇒ no bias)
    # and is lane-aligned, or else the whole of K — the A tile's minor dim
    # must be a multiple of 128 or span the array on the chip
    bk = next((t for t in range(min(bk, k) // _LANE * _LANE, 0, -_LANE)
               if k % t == 0), k)
    ap = _pad2(a.astype(jnp.float32), bm, bk, value=a_range[0])
    bp = _pad2(b.astype(jnp.float32), bk, bn, value=b_range[0])
    counter = jnp.asarray(counter, jnp.int32).reshape(1, 1)
    out = dither_matmul_kernel_call(
        ap, bp, counter, bits=bits, scheme=scheme, seed=seed,
        a_range=a_range, b_range=b_range, fmt=fmt, block=(bm, bn, bk),
        interpret=interpret, true_shape=(m, k, n),
    )
    return out[:m, :n]
