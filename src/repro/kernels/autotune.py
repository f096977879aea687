"""Block-size autotuner for the Pallas kernels (DESIGN.md §3).

Two modes, both keyed by ``(kind, M, K, N, dtype, bits, scheme, backend)``:

* **model-driven pick** (``best_block``) — no execution: enumerate (bm, bn,
  bk) candidates aligned to the TPU f32 tile (8, 128) and the 128×128 MXU,
  reject those whose working set exceeds the VMEM budget (double-buffered
  operand tiles + f32 accumulator + cross-term sums), and pick the largest
  surviving tile (fewest grid steps → best MXU occupancy).  This is what the
  dispatcher uses when no measurement is cached, so the hot path never pays
  a tuning cost it didn't ask for.
* **measured sweep** (``autotune_matmul`` / ``autotune_quantize``) — time
  each candidate via a caller-supplied runner and cache the winner, in
  memory and (when ``REPRO_AUTOTUNE_CACHE`` names a JSON file) on disk, so
  one tuning run amortises across processes.  ``benchmarks/kernel_bench.py``
  is the normal driver and emits the sweep as a JSON perf artifact.

The runner indirection keeps this module free of a dispatch import (dispatch
imports us for ``best_block``).
"""

from __future__ import annotations

import json
import os
import time
import weakref
from typing import Callable, Dict, List, Optional, Tuple

__all__ = [
    "VMEM_BUDGET_BYTES",
    "matmul_vmem_bytes", "quantize_vmem_bytes", "decode_attention_vmem_bytes",
    "matmul_candidates", "quantize_candidates", "decode_attention_candidates",
    "paged_attention_candidates",
    "best_block", "autotune_matmul", "autotune_quantize",
    "autotune_decode_attention", "autotune_paged_attention",
    "cache_key", "load_cache", "save_cache", "clear_cache",
    "register_observer",
]

# Observability (DESIGN.md §13): tracers register here so winner-cache
# hits/misses and measured recompute sweeps show up on the serving timeline
# instead of as mystery gaps.  WeakSet: a dropped tracer unregisters itself,
# so short-lived engines never pin observers.  Observers are duck-typed —
# anything with an ``autotune_event(kind, **fields)`` method.
_OBSERVERS: "weakref.WeakSet" = weakref.WeakSet()


def register_observer(obs) -> None:
    """Register an object (held weakly) whose ``autotune_event`` method
    receives autotuner cache events: ``autotune_cache_hit``,
    ``autotune_model_pick``, ``autotune_sweep``."""
    _OBSERVERS.add(obs)


def _notify(kind: str, **fields) -> None:
    for obs in list(_OBSERVERS):
        try:
            obs.autotune_event(kind, **fields)
        except Exception:  # noqa: BLE001 — observability must not gate tuning
            pass

# v5e VMEM is ~16 MiB/core; leave headroom for the compiler's own buffers.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
_VMEM_USABLE_FRACTION = 0.75

# TPU f32 native tile and MXU edge (pallas_guide: sublane×lane = 8×128).
_SUBLANE, _LANE = 8, 128

_F32 = 4


def matmul_vmem_bytes(block: Tuple[int, int, int]) -> int:
    """Working-set model for the fused matmul kernel at one grid step:
    double-buffered A (bm, bk) and B (bk, bn) input tiles, the f32
    accumulator + output tile (bm, bn), and the affine-zero cross-term rows
    and columns.  The in-kernel quantiser keeps tile-sized temporaries
    (element index, hash, fraction, codes), modelled as four copies of each
    operand tile: at (512, 512, 576) that gives 16.2 MiB against the 17 MiB
    the TPU compiler reports for the kernel's scoped VMEM."""
    bm, bn, bk = block
    a_tile = bm * bk * _F32
    b_tile = bk * bn * _F32
    acc = bm * bn * _F32
    out = bm * bn * _F32
    sums = (bm + bn) * _F32
    temps = 4 * (a_tile + b_tile)
    return 2 * (a_tile + b_tile) + acc + out + sums + temps


def quantize_vmem_bytes(block: Tuple[int, int]) -> int:
    """Elementwise kernel: double-buffered f32 input and int32 output tiles,
    plus the hash quantiser's tile-sized temporaries, modelled as ten
    tiles: at (512, 1024) that gives 28 MiB against the 27.9 MiB the TPU
    compiler reports for the kernel's scoped VMEM."""
    bm, bn = block
    tile = bm * bn * _F32
    return 2 * 2 * tile + 10 * tile


def _plane_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of one (rows, cols) minor plane: Mosaic pads rows to the
    dtype's sublane tile (8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit) and
    cols to the 128-lane width."""
    sub = _SUBLANE * _F32 // itemsize
    return -(-rows // sub) * sub * -(-cols // _LANE) * _LANE * itemsize


def decode_attention_vmem_bytes(block: Tuple[int], *, nkv: int, hd: int,
                                group: int, quantized: bool,
                                kq: int = 1) -> int:
    """Working-set model for the flash-decode / verify kernel at one grid
    step, with every minor plane padded to its VMEM tile: double-buffered
    K and V tiles (n_kv, hd, bk) — positions on the lanes — in their
    storage dtype (int8 codes or bf16), their in-kernel upcasts (K to bf16,
    V to f32), the (n_kv, group, bk) logit and weight tiles, the
    per-position (n_kv, bk) scale and (1, bk) k_pos rows, and the
    online-softmax state, query and output for the ``kq`` query rows
    scored per program."""
    (bk,) = block
    elem = 1 if quantized else 2
    kv_tiles = 2 * 2 * nkv * _plane_bytes(hd, bk, elem)
    upcast = nkv * (_plane_bytes(hd, bk, 2) + _plane_bytes(hd, bk, _F32))
    logits = 2 * nkv * _plane_bytes(group, bk, _F32)
    scales = 2 * 2 * _plane_bytes(nkv, bk, _F32) if quantized else 0
    kpos = 2 * _plane_bytes(1, bk, 4)
    heads = kq * nkv
    state = heads * (2 * _plane_bytes(group, 1, _F32)
                     + _plane_bytes(group, hd, _F32))
    q_out = 2 * heads * (_plane_bytes(group, hd, 2)
                         + _plane_bytes(group, hd, _F32))
    return kv_tiles + upcast + logits + scales + kpos + state + q_out


def _tile_sizes(dim: int, quantum: int, ceiling: int) -> List[int]:
    """Power-of-two multiples of ``quantum`` up to min(dim, ceiling), falling
    back to the (smaller) dim itself so CPU-scale shapes stay tunable."""
    sizes = []
    t = quantum
    while t <= min(dim, ceiling):
        sizes.append(t)
        t *= 2
    if not sizes:
        sizes.append(dim)
    return sizes


def matmul_candidates(m: int, k: int, n: int) -> List[Tuple[int, int, int]]:
    """(bm, bn, bk) candidates under the VMEM budget, MXU/f32-tile aligned
    when the shape allows it.  The K tile must divide K (the kernel never
    pads K) and, on the chip, be a multiple of 128 or all of K."""
    budget = VMEM_BUDGET_BYTES * _VMEM_USABLE_FRACTION
    cands = []
    k_tiles = [t for t in _tile_sizes(k, _LANE, 1024) if k % t == 0] or [k]
    for bm in _tile_sizes(m, _SUBLANE * 4, 512):
        for bn in _tile_sizes(n, _LANE, 512):
            for bk in k_tiles:
                if matmul_vmem_bytes((bm, bn, bk)) <= budget:
                    cands.append((bm, bn, bk))
    return cands


def quantize_candidates(m: int, n: int) -> List[Tuple[int, int]]:
    budget = VMEM_BUDGET_BYTES * _VMEM_USABLE_FRACTION
    return [
        (bm, bn)
        for bm in _tile_sizes(m, _SUBLANE * 4, 1024)
        for bn in _tile_sizes(n, _LANE, 1024)
        if quantize_vmem_bytes((bm, bn)) <= budget
    ]


def decode_attention_candidates(cap: int, *, nkv: int, hd: int, group: int,
                                quantized: bool) -> List[Tuple[int]]:
    """(bk,) cache-length tile candidates under the VMEM budget.  A ring
    tile lies on the lane axis of the positions-minor cache, so candidates
    are lane-quantum multiples that divide the cap; tiny caps (CPU-scale
    serving tests) fall back to the cap itself so every shape stays
    tunable."""
    budget = VMEM_BUDGET_BYTES * _VMEM_USABLE_FRACTION
    cands = [
        (bk,)
        for bk in _tile_sizes(cap, _LANE, 4096)
        if cap % bk == 0
        and decode_attention_vmem_bytes((bk,), nkv=nkv, hd=hd, group=group,
                                        quantized=quantized) <= budget
    ]
    return cands or [(cap,)]


def paged_attention_candidates(max_len: int, *, nkv: int, hd: int,
                               group: int, quantized: bool,
                               lane_aligned: bool = False
                               ) -> List[Tuple[int]]:
    """(bs,) pool-block-size candidates for the paged KV cache.

    Unlike the ring kernel's per-call cache tile, the paged split-K tile is
    the pool block itself — fixed when the pool is allocated, because the
    block is both the kernel's gather granularity *and* the allocator's
    unit of capacity/prefix-sharing (serve/kvpool.py).  Candidates are
    sublane-quantum multiples: small enough that a short request wastes
    little of its last block, large enough that the per-block VMEM tile
    keeps the MXU fed; the same working-set model as the ring kernel
    rejects oversized blocks.  ``lane_aligned`` (the chip's kernels) keeps
    only multiples of the 128-wide lane axis that positions lie on
    (``decode_attention.check_pool_block``), at least one lane tile even
    when ``max_len`` is shorter."""
    budget = VMEM_BUDGET_BYTES * _VMEM_USABLE_FRACTION
    quantum = _LANE if lane_aligned else _SUBLANE
    cands = [
        (bs,)
        for bs in _tile_sizes(max_len, quantum, 1024)
        if bs <= max_len and not (lane_aligned and bs % _LANE)
        and decode_attention_vmem_bytes((bs,), nkv=nkv, hd=hd, group=group,
                                        quantized=quantized) <= budget
    ]
    return cands or [(_LANE if lane_aligned else max(1, max_len),)]


# ---------------------------------------------------------------------------
# winner cache: in-memory dict, optionally persisted to a JSON file
# ---------------------------------------------------------------------------

_CACHE: Dict[str, tuple] = {}
_CACHE_LOADED_FROM: Optional[str] = None


def cache_key(kind: str, shape: tuple, dtype, bits: int, scheme: str,
              backend: str) -> str:
    return "|".join([kind, "x".join(map(str, shape)), str(dtype), str(bits),
                     scheme, backend])


def _cache_path() -> Optional[str]:
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or None


def load_cache(path: Optional[str] = None) -> Dict[str, tuple]:
    """Merge the JSON winner cache at ``path`` (or $REPRO_AUTOTUNE_CACHE)
    into the in-memory cache.  Missing/corrupt files are treated as empty."""
    global _CACHE_LOADED_FROM
    path = path or _cache_path()
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                _CACHE.update({k: tuple(v) for k, v in json.load(f).items()})
            _CACHE_LOADED_FROM = path
        except (OSError, ValueError):
            pass
    return _CACHE


def save_cache(path: Optional[str] = None) -> Optional[str]:
    path = path or _cache_path()
    if not path:
        return None
    # merge-write: winners persisted by other processes survive, this
    # process's entries win on key conflicts
    merged: Dict[str, list] = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                merged.update(json.load(f))
        except (OSError, ValueError):
            pass
    merged.update({k: list(v) for k, v in _CACHE.items()})
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    # atomic rename: parallel bench/CI runs each write a complete temp file
    # and swap it in, so a concurrent reader/writer never sees a truncated
    # cache (last swap wins; its content includes the merge above)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(dict(sorted(merged.items())), f, indent=1)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    return path


def clear_cache() -> None:
    _CACHE.clear()


def best_block(kind: str, shape: tuple, dtype, bits: int, scheme: str,
               backend: str):
    """Cached winner if a sweep ran for this key; otherwise the model-driven
    pick: the largest candidate under the VMEM budget (ties → larger bk for
    matmul, i.e. fewest sequential grid steps per output tile)."""
    if _cache_path() and _CACHE_LOADED_FROM != _cache_path():
        load_cache()
    key = cache_key(kind, shape, dtype, bits, scheme, backend)
    hit = _CACHE.get(key)
    if hit is not None:
        _notify("autotune_cache_hit", key=key, block=list(hit))
        return tuple(hit)
    _notify("autotune_model_pick", key=key)
    if kind == "matmul":
        m, k, n = shape
        cands = matmul_candidates(m, k, n)
        return max(cands, key=lambda b: (b[0] * b[1] * b[2], b[2]))
    if kind == "quantize":
        m, n = shape
        return max(quantize_candidates(m, n), key=lambda b: b[0] * b[1])
    if kind == "decode_attention":
        _b, cap, nkv, group, hd = shape
        cands = decode_attention_candidates(
            cap, nkv=nkv, hd=hd, group=group, quantized="int8" in str(dtype))
        # largest tile = fewest sequential cache blocks per (slot, head);
        # length-aware skipping still prunes at this granularity
        return max(cands, key=lambda b: b[0])
    if kind == "paged_attention":
        _b, max_len, nkv, group, hd = shape
        cands = paged_attention_candidates(
            max_len, nkv=nkv, hd=hd, group=group,
            quantized="int8" in str(dtype),
            lane_aligned=backend == "pallas-tpu")
        # the pool block is also the allocation/prefix-sharing unit, so the
        # model pick balances kernel tile size against granularity: the
        # largest candidate that still gives a full-length request ≥ 4
        # blocks (falls back to the smallest candidate for tiny max_len)
        fitting = [c for c in cands if c[0] * 4 <= max_len]
        return (max(fitting, key=lambda b: b[0]) if fitting
                else min(cands, key=lambda b: b[0]))
    raise ValueError(f"unknown kernel kind {kind!r}")


# ---------------------------------------------------------------------------
# measured sweeps
# ---------------------------------------------------------------------------


def _time_block(run: Callable[[tuple], object], block: tuple,
                repeats: int) -> float:
    run(block)  # compile / warm up outside the timed region
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = run(block)
        getattr(out, "block_until_ready", lambda: None)()
        best = min(best, time.perf_counter() - t0)
    return best


def _sweep(kind: str, shape: tuple, dtype, bits: int, scheme: str,
           backend: str, candidates: List[tuple],
           run: Callable[[tuple], object], repeats: int):
    key = cache_key(kind, shape, dtype, bits, scheme, backend)
    recompute = key in _CACHE  # re-sweeping a key that already had a winner
    t0 = time.perf_counter()
    results = []
    for block in candidates:
        try:
            dt = _time_block(run, block, repeats)
        except Exception:  # noqa: BLE001 — an illegal tiling just loses the sweep
            continue
        results.append({"block": list(block), "seconds": dt})
    if not results:
        raise RuntimeError(f"no runnable {kind} block candidate for {shape}")
    results.sort(key=lambda r: r["seconds"])
    winner = tuple(results[0]["block"])
    _CACHE[key] = winner
    save_cache()
    _notify("autotune_sweep", key=key, winner=list(winner),
            candidates=len(results), recompute=recompute,
            sweep_s=time.perf_counter() - t0)
    return winner, results


def autotune_matmul(m: int, k: int, n: int, *, bits: int, scheme: str,
                    backend: str, run: Callable[[tuple], object],
                    dtype="float32", repeats: int = 2,
                    candidates: Optional[List[tuple]] = None):
    """Measure ``run(block)`` over the candidate set, cache and return the
    winner.  Returns (winner_block, per-candidate results sorted by time)."""
    cands = candidates or matmul_candidates(m, k, n)
    return _sweep("matmul", (m, k, n), dtype, bits, scheme, backend, cands,
                  run, repeats)


def autotune_quantize(m: int, n: int, *, bits: int, scheme: str, backend: str,
                      run: Callable[[tuple], object], dtype="float32",
                      repeats: int = 2,
                      candidates: Optional[List[tuple]] = None):
    cands = candidates or quantize_candidates(m, n)
    return _sweep("quantize", (m, n), dtype, bits, scheme, backend, cands,
                  run, repeats)


def autotune_decode_attention(b: int, cap: int, nkv: int, group: int, hd: int,
                              *, backend: str, run: Callable[[tuple], object],
                              dtype="int8", repeats: int = 2,
                              candidates: Optional[List[tuple]] = None):
    """Measured (bk,) sweep for the flash-decode attention kernel.  ``dtype``
    is the cache storage dtype ('int8' or 'bfloat16'); bits follow from it."""
    quantized = "int8" in str(dtype)
    cands = candidates or decode_attention_candidates(
        cap, nkv=nkv, hd=hd, group=group, quantized=quantized)
    return _sweep("decode_attention", (b, cap, nkv, group, hd), dtype,
                  8 if quantized else 16, "flash", backend, cands, run,
                  repeats)


def autotune_paged_attention(b: int, max_len: int, nkv: int, group: int,
                             hd: int, *, backend: str,
                             run: Callable[[tuple], object],
                             dtype="int8", repeats: int = 2,
                             candidates: Optional[List[tuple]] = None):
    """Measured (bs,) pool-block-size sweep for paged decode attention.

    ``run((bs,))`` must build a pool with that block size and time a decode
    pass — the block size is baked into the pool layout, so unlike the ring
    sweep each candidate re-allocates the cache.  The winner is what
    ``serve/kvpool.py`` (and the engine's ``kv_layout='paged'`` path) picks
    up when no explicit ``block_size`` is given."""
    quantized = "int8" in str(dtype)
    cands = candidates or paged_attention_candidates(
        max_len, nkv=nkv, hd=hd, group=group, quantized=quantized,
        lane_aligned=backend == "pallas-tpu")
    return _sweep("paged_attention", (b, max_len, nkv, group, hd), dtype,
                  8 if quantized else 16, "flash", backend, cands, run,
                  repeats)
