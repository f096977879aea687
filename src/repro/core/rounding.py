"""Rounding schemes (paper §II-C, §VII): deterministic, stochastic, dither.

Dither rounding (§VII): ``d(α, i) = ⌊α⌋ + X_i`` where {X_i} is the dither-
computing representation (§II-D) of ``frac(α)`` and ``i = σ(i_s mod N)`` is
driven by a counter i_s.  This module implements the *lazy, counter-indexed*
TPU-native reduction (DESIGN.md §2): pulse i is a threshold test on the
permuted slot index plus a hashed Bernoulli tail — O(1) integer math per
element, no pulse tensors.  The same bit-exact semantics are shared by the
Pallas kernels (kernels/ref.py delegates here).

All randomness is a stateless xorshift/murmur hash of
(seed, element_index, counter) so results are reproducible and identical
across jnp / Pallas-interpret / Pallas-TPU paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "deterministic_round",
    "stochastic_round",
    "dither_round",
    "dither_bit",
    "hash_uniform",
    "lcg_slot",
    "slot_index",
    "DitherState",
]

# numpy scalars (not jnp) so Pallas kernel bodies see literals, not captures
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


def _mix(h: jax.Array) -> jax.Array:
    """murmur3 fmix32 — a high-quality 32-bit finaliser (mod-2³² wraparound
    is intended; numpy warns on scalar uint32 overflow, so silence locally)."""
    with np.errstate(over="ignore"):
        h = h ^ (h >> 16)
        h = h * _M1
        h = h ^ (h >> 13)
        h = h * _M2
        h = h ^ (h >> 16)
        return h


def _u32(v):
    """Coerce to uint32, keeping Python ints as numpy literals (Pallas-safe)."""
    if isinstance(v, jax.Array):
        return v.astype(jnp.uint32)
    if isinstance(v, np.ndarray):
        return v.astype(np.uint32)
    return np.uint32(int(v) & 0xFFFFFFFF)


def hash_uniform(seed, idx, counter) -> jax.Array:
    """Stateless uniform in [0,1) from (seed, element index, counter).

    Pure uint32 ops — portable to Pallas kernel bodies unchanged.
    """
    seed, idx, counter = _u32(seed), _u32(idx), _u32(counter)
    with np.errstate(over="ignore"):
        h = _mix(seed ^ _GOLDEN)
        h = _mix(h ^ idx * _M1)
        h = _mix(h ^ counter * _M2)
    # 24-bit mantissa → exact float32 uniform on [0,1); the convert goes
    # through int32 (exact below 2²⁴) because Mosaic has no uint32 → f32
    return (h >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))


def _coprime_multiplier(n: int) -> int:
    a = max(1, int(round(0.6180339887 * n))) | 1
    while _gcd(a, n) != 1:
        a += 2
    return a


def _gcd(a: int, b: int) -> int:
    while b:
        a, b = b, a % b
    return a


def lcg_slot(counter, idx, n_pulses: int, seed: int = 0) -> jax.Array:
    """σ(i_s mod N) with a linear-congruential permutation σ (per-element phase).

    ``idx`` decorrelates elements of a tensor: each element walks the same
    permutation with its own phase offset (equivalent to an element-specific
    σ, which the paper allows — "σ is either a deterministic or a random
    permutation").
    """
    a = _coprime_multiplier(n_pulses)
    counter, idx = _u32(counter), _u32(idx)
    n = np.uint32(n_pulses)
    phase = _mix(idx ^ _u32(seed) ^ _GOLDEN)
    q = (counter + phase) % n
    return (np.uint32(a) * q + (phase >> 8)) % n


def slot_index(counter, idx, n_pulses: int, seed: int = 0, fmt: str = "spread") -> jax.Array:
    """σ(i_s mod N) for either paper pulse format (§II-B / §VI roles).

    ``fmt='spread'`` (Format 2, the default): the LCG permutation σ of
    ``lcg_slot`` — successive counters jump through the pulse sequence.
    ``fmt='unary'`` (Format 1): identity σ with a per-element phase —
    successive counters walk the slots in order.  Both visit every slot
    exactly once per N counters, so the O(1/N) time-averaged SEM of §VII
    holds for either; the choice matters when two dithered operands meet
    (left operand Format 1, right operand Format 2 decorrelates products).
    """
    if fmt == "spread":
        return lcg_slot(counter, idx, n_pulses, seed=seed)
    if fmt == "unary":
        counter, idx = _u32(counter), _u32(idx)
        phase = _mix(idx ^ _u32(seed) ^ _GOLDEN)
        return (counter + phase) % np.uint32(n_pulses)
    raise ValueError(f"unknown pulse format {fmt!r}")


# ---------------------------------------------------------------------------
# rounding schemes
# ---------------------------------------------------------------------------


def deterministic_round(x: jax.Array) -> jax.Array:
    """round(x) = ⌊x + 0.5⌋ (the paper's definition — half-up, not banker's)."""
    return jnp.floor(x + 0.5)


def stochastic_round(x: jax.Array, seed, counter=0) -> jax.Array:
    """⌊x⌋ + Bernoulli(frac(x)), hash-PRNG driven (§II-C / [8])."""
    x = jnp.asarray(x, jnp.float32)
    flat_idx = jnp.arange(x.size, dtype=jnp.uint32).reshape(x.shape)
    u = hash_uniform(seed, flat_idx, counter)
    f = x - jnp.floor(x)
    return jnp.floor(x) + (u < f).astype(x.dtype)


def dither_bit(frac: jax.Array, slot: jax.Array, u: jax.Array, n_pulses: int) -> jax.Array:
    """Pulse value X_{σ(i)} of the §II-D dither representation, lazily.

    ``frac`` ∈ [0,1], ``slot`` = σ(i_s mod N) ∈ {0..N-1}, ``u`` ~ U[0,1).

    x ≤ 1/2: n = ⌊Nx⌋, δ = (Nx − n)/(N − n):   bit = [slot < n] or Bern(δ)
    x > 1/2: n = ⌈Nx⌉, δ = (n − Nx)/n:          bit = [slot < n]·Bern(1−δ)
    """
    N = float(n_pulses)
    f = jnp.asarray(frac, jnp.float32)
    slot = slot.astype(jnp.int32).astype(jnp.float32)  # < N ≪ 2²⁴: exact

    lo = f <= 0.5
    n_lo = jnp.floor(N * f)
    delta_lo = jnp.where(N - n_lo > 0, (N * f - n_lo) / jnp.maximum(N - n_lo, 1.0), 0.0)
    n_hi = jnp.ceil(N * f)
    delta_hi = jnp.where(n_hi > 0, (n_hi - N * f) / jnp.maximum(n_hi, 1.0), 0.0)

    n = jnp.where(lo, n_lo, n_hi)
    head = slot < n
    p = jnp.where(
        lo,
        jnp.where(head, 1.0, delta_lo),
        jnp.where(head, 1.0 - delta_hi, 0.0),
    )
    return (u < p).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_pulses",))
def dither_round(x: jax.Array, counter, seed, n_pulses: int) -> jax.Array:
    """Dither rounding d(α, i_s) = ⌊α⌋ + X_{σ(i_s mod N)} (paper §VII).

    ``counter`` is the global use-counter i_s (scalar int, or an array
    broadcastable to x for per-use indices, e.g. the k column index in the
    per-partial-product matmul variant).  Negative α handled by reflecting
    through ⌊α⌋ (the paper: "the case α<0 can be handled similarly").
    """
    x = jnp.asarray(x, jnp.float32)
    fl = jnp.floor(x)
    f = x - fl  # ∈ [0,1) for any sign of x
    flat_idx = jnp.arange(x.size, dtype=jnp.uint32).reshape(x.shape)
    counter = jnp.asarray(counter)
    slot = lcg_slot(counter, flat_idx, n_pulses, seed=seed)
    u = hash_uniform(_u32(seed) ^ np.uint32(0xD1CE), flat_idx, counter)
    return fl + dither_bit(f, slot, u, n_pulses)


class DitherState:
    """Tiny counter registry so call sites can thread i_s functionally.

    Usage::

        st = DitherState(seed=0)
        y, st = st.round(x, n_pulses=64)
    """

    def __init__(self, seed: int = 0, counter: int = 0):
        self.seed = int(seed)
        self.counter = int(counter)

    def round(self, x: jax.Array, n_pulses: int):
        y = dither_round(x, self.counter, self.seed, n_pulses)
        return y, DitherState(self.seed, self.counter + 1)
