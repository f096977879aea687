"""Pallas kernel benchmark: backend × block-shape sweep with a JSON artifact.

Sweeps the fused dither-matmul, elementwise quantise, and flash-decode
attention kernels over the dispatcher backends (pallas-interpret / xla-ref
on CPU; pallas-tpu on TPU) and a tile-size grid from the autotuner's
candidate model, checking every timed configuration against the
kernels/ref.py oracles.  The decode-attention sweep additionally times the
retired full-softmax einsum path (which upcast the whole int8 cache to fp)
as a baseline and reports analytic per-token HBM bytes for both, across
cap ∈ {256, 1024, 4096} under ``--full``.  Numbers on CPU are relative
(interpret mode trades speed for bit-exactness with the TPU path); they
guide BlockSpec choices and catch regressions — absolute TPU perf comes
from the §Roofline dry-run terms.

Standalone CLI (emits the perf artifact future PRs diff against):

  PYTHONPATH=src python benchmarks/kernel_bench.py --backend all \
      [--smoke | --full] [--autotune] \
      [--out benchmarks/artifacts/kernel_bench.json]

The artifact schema is documented in benchmarks/README.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ is None or __package__ == "":  # `python benchmarks/kernel_bench.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_root, os.path.join(_root, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import machine_calibration
from repro.kernels import autotune, dispatch, ref

DEFAULT_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "artifacts", "kernel_bench.json")

ARTIFACT_VERSION = 3


def _cpu_backends():
    if jax.default_backend() == "tpu":
        return ["pallas-tpu", "xla-ref"]
    return ["pallas-interpret", "xla-ref"]


def _time_call(fn, repeats: int = 3) -> float:
    """Best-of-N wall time in µs (first call compiles, outside the timing)."""
    fn().block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _matmul_blocks(m: int, k: int, n: int, full: bool):
    cands = autotune.matmul_candidates(m, k, n)
    return cands if full else cands[:3]


def _quantize_blocks(m: int, n: int, full: bool):
    cands = autotune.quantize_candidates(m, n)
    return cands if full else cands[:2]


def _ring_cache(rng, b, cap, nkv, hd, pos_frac=0.75):
    """Synthetic int8 dither-code ring cache at 3/4 occupancy (so the
    length-aware block skipping shows up in the timings and byte counts)."""
    pos_val = max(0, int(cap * pos_frac) - 1)
    q = jnp.asarray(rng.normal(size=(b, nkv, 2, hd)), jnp.bfloat16)
    kpos = np.full((b, cap), -1, np.int64)
    for i in range(b):
        kpos[i, : pos_val + 1] = np.arange(pos_val + 1)
    k = jnp.asarray(rng.integers(-127, 128, size=(b, cap, nkv, hd)), jnp.int8)
    v = jnp.asarray(rng.integers(-127, 128, size=(b, cap, nkv, hd)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.1, 2.0, size=(b, cap, nkv)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.1, 2.0, size=(b, cap, nkv)), jnp.float32)
    # stored as the engine stores the ring (heads-major, positions minor;
    # transformer.kv_shapes), as a layer-stacked cache of one layer
    k, v, ks, vs = (jnp.moveaxis(x, 1, -1)[None] for x in (k, v, ks, vs))
    return (q, k, v, jnp.asarray(kpos, jnp.int32)[None],
            jnp.full((b,), pos_val, jnp.int32), ks, vs), pos_val


def decode_attn_bytes_per_token(cap, nkv, hd, *, pos, bk, quantized=True,
                                fp_upcast=False):
    """Analytic per-token attention HBM read bytes for one slot, one layer.

    The flash path reads ceil((pos+1)/bk) cache blocks of int8 K + V codes
    plus their f32 scales and k_pos; the einsum baseline read the whole cap
    *and* materialised an fp32 upcast of both code tensors."""
    elem = 1 if quantized else 2
    slots = cap if bk is None else min(cap, math.ceil((pos + 1) / bk) * bk)
    bytes_ = nkv * (2 * slots * hd * elem)              # K + V codes
    if quantized:
        bytes_ += nkv * 2 * slots * 4                   # k_scale + v_scale
    bytes_ += slots * 4                                 # k_pos
    if fp_upcast:
        bytes_ += nkv * 2 * cap * hd * 4                # full-cap fp32 copies
    return int(bytes_)


@jax.jit
def _einsum_decode_baseline(q, k, v, k_pos, pos, ks, vs):
    """The retired pre-PR-3 decode path: upcast the entire int8 ring cache
    to fp, full (cap,) logits + softmax, scales folded outside the kernel
    (on the stored positions-minor stack of one layer, read
    position-major)."""
    k, v, ks, vs = (jnp.moveaxis(x[0], -1, 1) for x in (k, v, ks, vs))
    k_pos = k_pos[0]
    b, cap, nkv, hd = k.shape
    x_dtype = q.dtype
    logits = jnp.einsum("bhgd,bkhd->bhgk", q,
                        k.astype(x_dtype)).astype(jnp.float32) / math.sqrt(hd)
    logits = logits * (ks / 127.0).transpose(0, 2, 1)[:, :, None, :]
    valid = (k_pos >= 0) & (k_pos <= pos[:, None])
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x_dtype)
    pv = probs * (vs / 127.0).transpose(0, 2, 1)[:, :, None, :].astype(x_dtype)
    return jnp.einsum("bhgk,bkhd->bhgd", pv, v.astype(x_dtype))


def sweep_decode_attention(caps, backends=None, do_autotune: bool = False):
    """Flash-decode attention sweep: tok/s and bytes/token vs the einsum
    baseline across cache capacities.  Returns (rows, results, winners)."""
    backends = backends or _cpu_backends()
    rng = np.random.default_rng(7)
    b, nkv, group, hd = 2, 2, 2, 64
    rows, results, winners = [], [], {}
    for cap in caps:
        (q, k, v, k_pos, pos, ks, vs), pos_val = _ring_cache(rng, b, cap, nkv, hd)
        ref_out = ref.decode_attention_ref(q, k, v, k_pos, pos, ks, vs,
                                           layer=0, block=(16,))
        base_us = _time_call(lambda: _einsum_decode_baseline(
            q, k, v, k_pos, pos, ks, vs))
        base_bytes = decode_attn_bytes_per_token(cap, nkv, hd, pos=pos_val,
                                                 bk=None, fp_upcast=True)
        for backend in backends:
            cands = autotune.decode_attention_candidates(
                cap, nkv=nkv, hd=hd, group=group, quantized=True)
            blocks = [None] if backend == "xla-ref" else [None] + cands[:3]
            for blk in blocks:
                out = dispatch.decode_attention(
                    q, k, v, k_pos, pos, k_scale=ks, v_scale=vs, layer=0, block=blk,
                    backend=backend)
                err = float(jnp.max(jnp.abs(out - ref_out)))
                us = _time_call(lambda: dispatch.decode_attention(
                    q, k, v, k_pos, pos, k_scale=ks, v_scale=vs, layer=0, block=blk,
                    backend=backend))
                eff_bk = (cap if blk is None and backend == "xla-ref"
                          else (blk or autotune.best_block(
                              "decode_attention", (b, cap, nkv, group, hd),
                              "int8", 8, "flash", backend))[0])
                bpt = decode_attn_bytes_per_token(cap, nkv, hd, pos=pos_val,
                                                  bk=eff_bk)
                label = "auto" if blk is None else str(blk[0])
                rows.append((
                    f"kernel_decode_attn[{backend}|cap={cap}|bk={label}]", us,
                    f"tok_s={b * 1e6 / us:.0f} bytes/tok={bpt} "
                    f"einsum_bytes/tok={base_bytes} max_err={err:.1e}"))
                results.append({
                    "kernel": "decode_attention", "backend": backend,
                    "shape": [b, cap, nkv, group, hd], "cap": cap,
                    "block": list(blk) if blk else None, "us": us,
                    "tok_s": b * 1e6 / us,
                    "us_einsum_baseline": base_us,
                    "bytes_per_token": bpt,
                    "bytes_per_token_einsum": base_bytes,
                    "max_abs_err_vs_ref": err,
                })
        if do_autotune:
            for backend in backends:
                if backend == "xla-ref":
                    continue
                winner, _ = autotune.autotune_decode_attention(
                    b, cap, nkv, group, hd, backend=backend, repeats=1,
                    run=lambda blk: dispatch.decode_attention(
                        q, k, v, k_pos, pos, k_scale=ks, v_scale=vs,
                        layer=0, block=tuple(blk), backend=backend),
                    candidates=autotune.decode_attention_candidates(
                        cap, nkv=nkv, hd=hd, group=group, quantized=True)[:3])
                key = autotune.cache_key(
                    "decode_attention", (b, cap, nkv, group, hd), "int8", 8,
                    "flash", backend)
                winners[key] = list(winner)
                rows.append((f"kernel_autotune_decode_attn[{backend}|cap={cap}]",
                             0.0, f"winner={winner[0]}"))
    return rows, results, winners


def sweep(full: bool = False, backends=None, do_autotune: bool = False,
          smoke: bool = False):
    """Sweep; returns (rows, artifact).  rows = (name, us, derived) for the
    benchmarks/run.py CSV harness."""
    backends = backends or _cpu_backends()
    m = k = n = 256 if full else 128
    a = jax.random.uniform(jax.random.PRNGKey(0), (m, k))
    b = jax.random.uniform(jax.random.PRNGKey(1), (k, n))
    ref_out = ref.dither_matmul_ref(a, b, bits=8, scheme="dither")

    rows, results = [], []
    for backend in backends:
        blocks = ([None] if backend == "xla-ref"
                  else [None] + _matmul_blocks(m, k, n, full))
        for blk in blocks:
            out = dispatch.matmul(a, b, bits=8, scheme="dither", block=blk,
                                  backend=backend)
            err = float(jnp.max(jnp.abs(out - ref_out)))
            us = _time_call(lambda: dispatch.matmul(
                a, b, bits=8, scheme="dither", block=blk, backend=backend))
            label = "auto" if blk is None else "x".join(map(str, blk))
            rows.append((f"kernel_matmul[{backend}|blk={label}]", us,
                         f"max_err={err:.1e}"))
            results.append({
                "kernel": "dither_matmul", "backend": backend,
                "shape": [m, k, n], "bits": 8, "scheme": "dither",
                "block": list(blk) if blk else None, "us": us,
                "max_abs_err_vs_ref": err,
            })

    qm, qn = (512, 512) if full else (256, 256)
    x = jax.random.uniform(jax.random.PRNGKey(2), (qm, qn), minval=-1, maxval=1)
    ref_codes = ref.quantize_codes_ref(x, scale=255 / 2, zero=-1, bits=8,
                                       scheme="dither", counter=0, seed=0,
                                       n_pulses=16)
    for backend in backends:
        blocks = ([None] if backend == "xla-ref"
                  else [None] + _quantize_blocks(qm, qn, full))
        for blk in blocks:
            codes = dispatch.quantize(x, bits=8, lo=-1, hi=1, scheme="dither",
                                      block=blk, backend=backend)
            exact = bool(jnp.array_equal(codes, ref_codes))
            us = _time_call(lambda: dispatch.quantize(
                x, bits=8, lo=-1, hi=1, scheme="dither", block=blk,
                backend=backend))
            label = "auto" if blk is None else "x".join(map(str, blk))
            rows.append((f"kernel_quantize[{backend}|blk={label}]", us,
                         f"codes_exact={exact}"))
            results.append({
                "kernel": "quantize", "backend": backend, "shape": [qm, qn],
                "bits": 8, "scheme": "dither",
                "block": list(blk) if blk else None, "us": us,
                "codes_exact_vs_ref": exact,
            })

    winners = {}
    if do_autotune:
        for backend in backends:
            if backend == "xla-ref":
                continue  # no tiling concept
            winner, _sweep = autotune.autotune_matmul(
                m, k, n, bits=8, scheme="dither", backend=backend,
                repeats=1,
                run=lambda blk: dispatch.matmul(
                    a, b, bits=8, scheme="dither", block=tuple(blk),
                    backend=backend),
                candidates=_matmul_blocks(m, k, n, full))
            key = autotune.cache_key("matmul", (m, k, n), "float32", 8,
                                     "dither", backend)
            winners[key] = list(winner)
            rows.append((f"kernel_autotune_matmul[{backend}]", 0.0,
                         f"winner={'x'.join(map(str, winner))}"))
            q_winner, _qsweep = autotune.autotune_quantize(
                qm, qn, bits=8, scheme="dither", backend=backend,
                repeats=1,
                run=lambda blk: dispatch.quantize(
                    x, bits=8, lo=-1, hi=1, scheme="dither",
                    block=tuple(blk), backend=backend),
                candidates=_quantize_blocks(qm, qn, full))
            q_key = autotune.cache_key("quantize", (qm, qn), "float32", 8,
                                       "dither", backend)
            winners[q_key] = list(q_winner)
            rows.append((f"kernel_autotune_quantize[{backend}]", 0.0,
                         f"winner={'x'.join(map(str, q_winner))}"))

    # flash-decode attention: cap grid scales with the mode (--smoke keeps
    # CI to one small cap; --full covers the ISSUE's 256/1024/4096 sweep)
    caps = [256] if smoke else ([256, 1024, 4096] if full else [256, 1024])
    da_rows, da_results, da_winners = sweep_decode_attention(
        caps, backends=backends, do_autotune=do_autotune)
    rows += da_rows
    results += da_results
    winners.update(da_winners)

    artifact = {
        "version": ARTIFACT_VERSION,
        "generated_by": "benchmarks/kernel_bench.py",
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "unix_time": time.time(),
        "calibration": machine_calibration(),
        "results": results,
        "autotune_winners": winners,
    }
    return rows, artifact


def run(full: bool = False):
    """benchmarks/run.py harness entry point: rows only (harness prints CSV)."""
    rows, _ = sweep(full=full, smoke=not full)
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="default",
                    help="'all', 'default' (platform pick + reference), or a "
                         "comma list of dispatcher backend names")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale shapes, the full tile grid, and the "
                         "cap ∈ {256,1024,4096} decode-attention sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="CI shape: small matmul/quantize shapes and a "
                         "single-cap decode-attention sweep")
    ap.add_argument("--autotune", action="store_true",
                    help="run the measured block sweep and cache winners")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="JSON artifact path ('' to skip writing)")
    args = ap.parse_args(argv)

    if args.backend == "all":
        backends = list(dispatch.available_backends())
        if jax.default_backend() != "tpu":
            backends.remove("pallas-tpu")  # uncompilable off-TPU
    elif args.backend == "default":
        backends = _cpu_backends()
    else:
        backends = [dispatch.resolve_backend(b).name
                    for b in args.backend.split(",")]

    rows, artifact = sweep(full=args.full, backends=backends,
                           do_autotune=args.autotune, smoke=args.smoke)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {args.out} ({len(artifact['results'])} results)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
