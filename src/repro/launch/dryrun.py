import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST be the first import side effect: the two lines above run before jax
locks the device count (do not move them; do not import repro/jax first).

For each cell this proves, without hardware:
  * the sharding config is coherent (SPMD partitioning succeeds),
  * the program fits (memory_analysis per device),
  * and extracts the roofline terms (cost_analysis FLOPs/bytes + collective
    bytes parsed from the post-SPMD HLO).

Cells (DESIGN.md §5):
  train_4k     train_step   seq 4096,   global batch 256
  prefill_32k  prefill      seq 32768,  global batch 32
  decode_32k   decode_step  cache 32768, batch 128 (1 new token)
  long_500k    decode_step  cache 524288, batch 1 — sub-quadratic archs only

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh single,multi --out experiments/dryrun.json
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

def np_prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out

from repro.configs import ARCH_IDS, get_config
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.models.config import ModelConfig
from repro.numerics.policy import QuantPolicy
from repro.optim.adamw import AdamW
from repro.train import trainer

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

# v5e-class hardware model (per chip)
PEAK_FLOPS = 197e12       # bf16
HBM_BW = 819e9            # bytes/s
ICI_BW = 50e9             # bytes/s/link

_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
        "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9a-z]*)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shapes_bytes(text: str) -> float:
    total = 0.0
    for m in _SHAPE_RE.finditer(text):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str, loop_factor: int = 1) -> Dict[str, float]:
    """Sum result bytes of every collective in the post-SPMD HLO.

    Collectives inside non-ENTRY computations (scan-over-layers while bodies,
    remat bodies) execute once per loop iteration, so they are weighted by
    ``loop_factor`` (= layer-scan trip count) — the HLO text lists them once.
    Wire accounting: all-reduce ≈ 2× its size over a ring; all-gather /
    reduce-scatter / all-to-all / permute ≈ 1×.

    bf16 normalisation: the CPU backend's float-normalisation pass upcasts
    every bf16 tensor (and all-reduce reducer) to f32 — a TPU compile keeps
    them bf16.  f32 collectives that are provably promoted bf16 (reducer
    named '*promoted*', or fed by a convert fusion) are counted at half
    size; genuine f32 collectives (fp32 logits/loss) count fully.
    """
    sums: Dict[str, float] = {}
    factor = 1.0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and ("(" in stripped and ")" in stripped):
            # computation header — ENTRY runs once, others are loop/remat bodies
            factor = 1.0 if stripped.startswith("ENTRY") else float(loop_factor)
            continue
        for op in _OPS:
            i = line.find(op + "(")
            if i <= 0 or line[i - 1] not in " %=":
                continue
            left = line[:i]
            if "=" not in left:
                continue
            b = _shapes_bytes(left.split("=", 1)[1])
            if "f32" in left and ("promoted" in line or "convert" in line):
                b *= 0.5  # promoted-bf16 collective: TPU moves bf16
            sums[op] = sums.get(op, 0.0) + b * factor
            break
    wire = (
        2.0 * sums.get("all-reduce", 0.0)
        + sums.get("all-gather", 0.0)
        + sums.get("reduce-scatter", 0.0)
        + sums.get("all-to-all", 0.0)
        + sums.get("collective-permute", 0.0)
    )
    sums["wire_bytes"] = wire
    return sums


def _sds_with_sharding(tree_shapes, specs, mesh):
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=NamedSharding(mesh, shd._validated(sp, s.shape, mesh))),
        tree_shapes, specs,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)),
    )


def _replicated_sds(tree_shapes, mesh):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=NamedSharding(mesh, P())),
        tree_shapes,
    )


def build_lowered(arch: str, shape: str, mesh, *, policy=None, microbatch: int = 0,
                  remat: bool = True, kv_quant: bool = False,
                  extra: dict | None = None):
    """Lower one cell.  Returns (lowered, info) or raises."""
    cfg = get_config(arch)
    meta = SHAPES[shape]
    kind, seq, batch = meta["kind"], meta["seq"], meta["batch"]
    if extra:
        cfg = cfg  # reserved for per-cell config overrides

    key = jax.random.PRNGKey(0)
    params_shapes = jax.eval_shape(lambda k: registry.init_model(k, cfg), key)
    pspecs = shd.param_specs(params_shapes, cfg, mesh)
    params_sds = _sds_with_sharding(params_shapes, pspecs, mesh)
    dp = shd.data_axes(mesh)

    if kind == "train":
        state_shapes = jax.eval_shape(lambda k: trainer.init_train_state(k, cfg), key)
        sspecs = {
            "params": pspecs,
            "opt": {
                "m": pspecs, "v": pspecs,
                "step": P(),
            },
            "counter": P(),
        }
        state_sds = _sds_with_sharding(state_shapes, sspecs, mesh)
        bspecs = shd.batch_specs(cfg, mesh)
        batch_shapes = registry.batch_spec(cfg, batch, seq)
        batch_sds = {
            k: jax.ShapeDtypeStruct(
                v.shape, v.dtype,
                sharding=NamedSharding(mesh, shd._validated(bspecs[k], v.shape, mesh)))
            for k, v in batch_shapes.items()
        }
        step_fn = trainer.make_train_step(
            cfg, AdamW(lr=1e-4), policy=policy, microbatch=microbatch, remat=remat)
        jitted = jax.jit(step_fn, donate_argnums=(0,))
        lowered = jitted.lower(state_sds, batch_sds)
        return lowered, dict(cfg=cfg, kind=kind, seq=seq, batch=batch,
                             microbatch=microbatch)

    if kind == "prefill":
        bspecs = shd.batch_specs(cfg, mesh)
        batch_shapes = registry.batch_spec(cfg, batch, seq)
        batch_shapes.pop("labels")
        batch_sds = {
            k: jax.ShapeDtypeStruct(
                v.shape, v.dtype,
                sharding=NamedSharding(mesh, shd._validated(bspecs[k], v.shape, mesh)))
            for k, v in batch_shapes.items()
        }
        chunks = max(int(extra.get("prefill_chunks", 1)) if extra else 1, 1)

        def prefill_fn(params, b):
            if chunks > 1:
                # batch-chunked prefill (lax.map): sequences stream through
                # in waves — the serving layer's natural behaviour — cutting
                # activation HBM by the chunk count.  Batch-major split so
                # DP sharding survives the reshape (same trick as µbatch).
                def split(x):
                    n = x.shape[0]
                    return x.reshape(n // chunks, chunks,
                                     *x.shape[1:]).swapaxes(0, 1)
                bs = jax.tree.map(split, b)
                return jax.lax.map(
                    lambda mb: registry.apply_model(params, cfg, mb,
                                                    policy=policy, remat=False),
                    bs)
            return registry.apply_model(params, cfg, b, policy=policy, remat=False)

        lowered = jax.jit(prefill_fn).lower(params_sds, batch_sds)
        return lowered, dict(cfg=cfg, kind=kind, seq=seq, batch=batch,
                             prefill_chunks=chunks)

    # decode
    if not _decode_supported(cfg, shape):
        raise SkipCell(f"{arch} × {shape}: needs sub-quadratic attention "
                       f"(full-attention KV at 500k is skipped per DESIGN.md §5)")
    frames_sds = None
    if cfg.is_encdec:
        frames_sds = jax.ShapeDtypeStruct(
            (batch, cfg.n_enc_tokens, cfg.d_model), jnp.bfloat16)
    tp = mesh.shape.get("model", 1)
    heads = tp if shd.serve_heads_shardable(cfg, tp) else 1
    cache_shapes = jax.eval_shape(
        lambda p, f: registry.make_cache(p, cfg, batch, seq, frames=f,
                                         kv_quant=kv_quant,
                                         head_shards=heads),
        params_shapes, frames_sds,
    )
    cspecs = shd.cache_specs(cache_shapes, cfg, mesh)
    cache_sds = _sds_with_sharding(cache_shapes, cspecs, mesh)
    token_sds = jax.ShapeDtypeStruct(
        (batch,), jnp.int32,
        sharding=NamedSharding(mesh, shd._validated(P(dp), (batch,), mesh)))

    def decode_fn(params, token, cache):
        return registry.apply_decode(params, cfg, token, cache, policy=policy)

    lowered = jax.jit(decode_fn, donate_argnums=(2,)).lower(
        params_sds, token_sds, cache_sds)
    cache_bytes = sum(
        float(np_prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(cache_shapes)
    )
    return lowered, dict(cfg=cfg, kind=kind, seq=seq, batch=batch,
                         cache_bytes_global=cache_bytes)


class SkipCell(Exception):
    pass


def _decode_supported(cfg: ModelConfig, shape: str) -> bool:
    if shape != "long_500k":
        return True
    return cfg.sub_quadratic()


def analytic_memory_bytes(info, mesh) -> float:
    """Model-based per-device HBM traffic (the roofline memory term).

    The HLO-parsed byte sums reflect CPU-backend fusion granularity (every
    elementwise op streams HBM) and overestimate a real TPU compile 5-100×;
    they are recorded as diagnostics.  This analytic estimate assumes
    TPU-grade fusion:

      train:   2 param reads (fwd+bwd) + f32 optimizer m/v read+write +
               param write + ~12 activation passes per layer (remat reload
               included) over the local token slab
      prefill: 1 param read + ~6 activation passes per layer
      decode:  1 param read + 1 full cache read + cache slice write
    """
    cfg, kind = info["cfg"], info["kind"]
    tp = mesh.shape.get("model", 1)
    dp = mesh.size // tp
    p_bytes = cfg.param_count() * 2.0 / tp          # bf16 shards
    tokens_dev = info["batch"] * info["seq"] / dp
    act_pass = tokens_dev * cfg.d_model * 2.0       # one bf16 tensor pass
    L = cfg.n_layers + (cfg.n_enc_layers if cfg.is_encdec else 0)
    if kind == "train":
        opt = cfg.param_count() * (4.0 + 4.0) * 2.0 / tp   # m,v f32 r+w
        return 3.0 * p_bytes + opt + 12.0 * act_pass * L
    if kind == "prefill":
        return p_bytes + 6.0 * act_pass * L
    cache = info.get("cache_bytes_global", 0.0) / max(dp, 1)
    return p_bytes + cache


def model_flops(cfg: ModelConfig, kind: str, seq: int, batch: int) -> float:
    """6·N·D (train) / 2·N·D (forward) with N = active params."""
    n = cfg.param_count(active_only=bool(cfg.n_experts))
    if kind == "train":
        tokens = batch * seq
        return 6.0 * n * tokens
    if kind == "prefill":
        return 2.0 * n * batch * seq
    return 2.0 * n * batch  # decode: one token per sequence


def loop_factors(info) -> list:
    """Per-nesting-depth while trip counts:
    [µbatches,] layer_repeats [, ssd_chunks | attention_q_chunks]."""
    cfg0 = info["cfg"]
    p_ = len(cfg0.block_pattern) if cfg0.block_pattern else 1
    rep = max(cfg0.n_layers // p_, 1)
    factors = [rep]
    if info["kind"] in ("train", "prefill"):
        if cfg0.family == "ssm":
            factors.append(max(info["seq"] // max(cfg0.ssm_chunk, 1), 1))
        elif (info["seq"] > 4096 and cfg0.n_heads
              and cfg0.n_heads % 16 == 0):
            # chunked-prefill attention scan (layers.attention)
            factors.append(info["seq"] // 4096)
    mb = info.get("microbatch", 0)
    if mb and mb > 1 and info["kind"] == "train":
        factors = [mb] + factors
    pc = info.get("prefill_chunks", 0)
    if pc and pc > 1 and info["kind"] == "prefill":
        factors = [pc] + factors
    return factors


def analyse(lowered, info, mesh) -> Dict[str, Any]:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    n_dev = mesh.size
    factors = loop_factors(info)
    rep = factors[0] if len(factors) == 1 else factors[1] if info.get("microbatch", 0) > 1 else factors[0]

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    hlo_text = compiled.as_text()
    from repro.launch.hlo_cost import hlo_cost
    weighted = hlo_cost(hlo_text, loop_factor=factors)
    raw_flops, raw_bytes = flops_dev, bytes_dev
    # loop-weighted dot flops (cost_analysis counts scan bodies 1×); the
    # memory term uses the fusion-optimistic stream-bytes estimate, with the
    # unfused upper bound recorded alongside.
    flops_dev = max(weighted["dot_flops"], flops_dev)
    bytes_upper = max(weighted["hbm_bytes"], bytes_dev)
    bytes_dev = weighted["stream_bytes"] or bytes_upper
    coll = {k: v for k, v in weighted["collectives"].items()}
    coll["wire_bytes"] = weighted["wire_bytes"]

    cfg, kind = info["cfg"], info["kind"]
    mf = model_flops(cfg, kind, info["seq"], info["batch"])
    compute_s = flops_dev / PEAK_FLOPS
    mem_model_bytes = analytic_memory_bytes(info, mesh)
    memory_s = mem_model_bytes / HBM_BW
    memory_parsed_s = bytes_dev / HBM_BW
    collective_s = coll["wire_bytes"] / ICI_BW
    dominant = max(
        [("compute", compute_s), ("memory", memory_s), ("collective", collective_s)],
        key=lambda kv: kv[1],
    )[0]
    return {
        "devices": n_dev,
        "compile_seconds": round(compile_s, 1),
        "per_device": {
            "flops": flops_dev,
            "hbm_bytes": bytes_dev,
            "hbm_bytes_unfused_upper": bytes_upper,
            "raw_cost_analysis": {"flops": raw_flops, "bytes": raw_bytes},
            "loop_factor": rep,
            "collective_wire_bytes": coll["wire_bytes"],
            "collectives": {k: v for k, v in coll.items() if k != "wire_bytes"},
            "memory_analysis": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            },
        },
        "roofline_seconds": {
            "compute": compute_s,
            "memory": memory_s,
            "memory_hlo_parsed": memory_parsed_s,
            "collective": collective_s,
        },
        "memory_model_bytes": mem_model_bytes,
        "dominant": dominant,
        "model_flops_total": mf,
        "useful_flops_ratio": (mf / (flops_dev * n_dev)) if flops_dev else 0.0,
    }


HBM_BUDGET = 14e9  # leave ~2 GB headroom on a 16 GB v5e chip


def run_cell(arch: str, shape: str, mesh_kind: str, auto_microbatch: bool = False,
             **kw) -> Dict[str, Any]:
    devices = jax.devices()
    if mesh_kind == "multi":
        mesh = make_production_mesh(multi_pod=True)
    else:
        import numpy as np
        mesh = jax.sharding.Mesh(
            np.array(devices[:256]).reshape(16, 16), ("data", "model"))
    try:
        from repro.dist import ctx
        with ctx.mesh_context(mesh):
            mb = kw.pop("microbatch", 0) or 1
            dp_total = mesh.size // mesh.shape.get("model", 1)
            mb_cap = max(SHAPES[shape]["batch"] // dp_total, 1)
            kind = SHAPES[shape]["kind"]
            pc = 1
            while True:
                lowered, info = build_lowered(
                    arch, shape, mesh, microbatch=mb,
                    extra={"prefill_chunks": pc}, **kw)
                out = analyse(lowered, info, mesh)
                temp = out["per_device"]["memory_analysis"]["temp_bytes"]
                if not auto_microbatch or temp <= HBM_BUDGET:
                    break
                if kind == "train" and mb < mb_cap:
                    mb *= 2  # gradient accumulation until the step fits
                elif kind == "prefill" and pc < mb_cap:
                    pc *= 2  # batch-chunked prefill waves
                else:
                    break
            out["prefill_chunks"] = pc
            out["microbatch"] = mb
            out["fits_hbm"] = bool(temp <= HBM_BUDGET + 2e9)
        out.update(status="ok", arch=arch, shape=shape, mesh=mesh_kind)
    except SkipCell as e:
        out = dict(status="skip", arch=arch, shape=shape, mesh=mesh_kind, reason=str(e))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="experiments/dryrun.json")
    ap.add_argument("--policy", default="none",
                    choices=["none", "dither", "stochastic", "deterministic"])
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--auto-microbatch", action="store_true",
                    help="double gradient-accumulation µbatches until the "
                         "train step fits the 16 GB HBM budget")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--kv-quant", action="store_true",
                    help="dither-quantised int8 KV cache for decode cells")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")
    policy = None if args.policy == "none" else QuantPolicy(scheme=args.policy)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                if (arch, shape, mk) in done:
                    continue
                t0 = time.time()
                try:
                    r = run_cell(arch, shape, mk, policy=policy,
                                 microbatch=args.microbatch,
                                 auto_microbatch=args.auto_microbatch,
                                 remat=not args.no_remat,
                                 kv_quant=args.kv_quant)
                except Exception as e:  # noqa: BLE001 — record, keep sweeping
                    r = dict(status="error", arch=arch, shape=shape, mesh=mk,
                             error=f"{type(e).__name__}: {e}",
                             trace=traceback.format_exc()[-2000:])
                r["wall_seconds"] = round(time.time() - t0, 1)
                results.append(r)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                status = r["status"]
                dom = r.get("dominant", "-")
                print(f"[{status:5s}] {arch:24s} {shape:12s} {mk:6s} "
                      f"dom={dom} wall={r['wall_seconds']}s", flush=True)


if __name__ == "__main__":
    main()
