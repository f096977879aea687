#!/usr/bin/env python3
"""On-chip smoke test: serve smollm-135m at its published widths on one TPU.

    python chip_smoke.py               # one chip: engine phases + kernel parity
    python chip_smoke.py --chips 4     # four chips: (2, 2) mesh ≡ one device

One process drives every phase.  It exits non-zero, and prints no result
line, when JAX finds no TPU, when the kernel dispatcher would not run the
compiled Pallas kernels, or when any phase fails.  On success the last line
of standard output is one JSON object naming the device.

One chip, smollm-135m (30 layers, d 576, 9/3 heads, head dim 64, ff 1536,
vocab 49152; random weights from ``--seed``, since no checkpoint ships with
the repository), each phase through ``Engine`` as ``launch/serve.py`` builds
it — two waves of 8 greedy requests with 256-token prompts that share a
128-token prefix (one pool block of 128 positions, the smallest the chip
takes), 32 new tokens each, max_len 1024:

  ring-bf16, ring-int8          the dense per-slot ring cache
  paged-int8-ticks4-prefix      paged pool, 4-tick windows, wave 2 hits the
                                prefix blocks wave 1 left in the pool
  paged-int8-spec               speculative decoding; its streams must equal
                                the phase above, bitwise (DESIGN.md §14)
  ring-bf16-dither              int8 dither policy through the fused
                                ``pallas-tpu`` matmul kernel

Each checks that every request finished on ``length``, that no sampled
logits row held a non-finite value, and that the decode step's program
calls ``tpu_custom_call`` (a Pallas kernel, not the XLA oracle).  Compile seconds
and tokens/s are printed for information.  Then the attention kernels are
held against the ``xla-ref`` oracle on the chip at serving shapes, and the
quantise kernel's codes must equal the oracle's exactly.

Four chips (``--chips 4``) run only the mesh contract of DESIGN.md §9 with
qwen2-1.5b, whose 2 KV heads divide the model axis, at its published widths
with depth cut to 4 layers: the (2, 2) mesh engine must emit the same
streams as the one-device engine, ring and paged int8, and its KV pools and
block tables must be spread over the four chips (one wave of 8 requests,
16 new tokens each, max_len 256).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

WAVE, PROMPT, SHARED, MAX_NEW, MAX_LEN, BLOCK = 8, 256, 128, 32, 1024, 128


class PhaseFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseFailure(msg)


def device_report(chips: int):
    """Print what JAX sees; exit non-zero unless it is ``chips`` TPUs served
    by the compiled Pallas kernels."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found only {dev.platform} "
                 "devices; this script runs on the chip and has no fallback")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPUs, JAX "
                 f"found {len(devs)}")
    from repro.kernels import dispatch
    from repro.models.transformer import kv_shapes

    backend = dispatch.resolve_backend().name
    print(f"kernel_backend={backend}", flush=True)
    if backend != "pallas-tpu":
        sys.exit(f"chip_smoke: kernel backend resolved to {backend!r}, not "
                 "'pallas-tpu' (is REPRO_KERNEL_BACKEND set?)")
    return dev, len(devs)


class CompileClock:
    """Seconds JAX spent in backend compilation, read per phase."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def watch_logits():
    """Count sampled logits rows holding NaN/Inf.  The engine samples on the
    device and never returns logits, so the count rides a debug callback
    wrapped around its sampler (installed before any engine is built)."""
    import jax
    import jax.numpy as jnp

    import repro.serve.engine as engine_mod

    sample = engine_mod.sample_tokens
    bad = [0]

    def add(n):
        bad[0] += int(n)

    def checked(logits, *args):
        jax.debug.callback(add, jnp.sum(~jnp.all(jnp.isfinite(logits), -1)))
        return sample(logits, *args)

    engine_mod.sample_tokens = checked
    return bad


def spy_decode(eng, factory: str):
    """Record the argument shapes of the first decode dispatch the engine's
    ``factory`` builds, so its program can be lowered again and read."""
    import jax

    seen = []
    build = getattr(eng, factory)

    def spec(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        return x

    def wrapped(n):
        fn = build(n)

        def call(*args):
            if not seen:
                seen.append((fn, jax.tree.map(spec, args)))
            return fn(*args)

        return call

    setattr(eng, factory, wrapped)
    return seen


def waves(cfg, seed):
    """Two waves of ``WAVE`` prompts; all share one ``SHARED``-token prefix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, cfg.vocab_size, SHARED).tolist()
    return [[prefix + rng.integers(1, cfg.vocab_size,
                                   PROMPT - SHARED).tolist()
             for _ in range(WAVE)] for _ in range(2)]


def serve(params, cfg, prompts, *, max_new=MAX_NEW, max_len=MAX_LEN, **kw):
    """Serve each wave to completion on one engine; returns (engine,
    {rid: tokens}, finish reasons, decode tok/s of the last wave)."""
    from repro.serve.engine import Engine, Request
    from repro.serve.sampling import SamplingParams

    eng = Engine(params, cfg, WAVE, max_len, **kw)
    spy = spy_decode(eng, "_spec_for" if kw.get("spec_decode")
                     else "_fused_for")
    rid, rate = 0, 0.0
    for wave in prompts:
        for prompt in wave:
            eng.submit(Request(rid=rid, prompt=prompt, sampling=SamplingParams(
                max_new=max_new, seed=rid)))
            rid += 1
        s0, t0 = eng.stats["decode_tokens"], eng.stats["decode_s"]
        eng.run(ticks=4 * max_new + 40)
        rate = ((eng.stats["decode_tokens"] - s0)
                / max(eng.stats["decode_s"] - t0, 1e-9))
    done = {r.rid: r for r in eng.finished}
    check(len(done) == rid, f"{len(done)}/{rid} requests finished")
    return eng, spy, {i: tuple(r.out) for i, r in done.items()}, \
        {r.finish_reason for r in done.values()}, rate


def engine_phases(params, cfg, seed, clock, bad_logits):
    """Run every engine phase; returns the failures (a failed phase does
    not stop the later ones, so one run reports them all)."""
    from repro.numerics.policy import QuantPolicy

    prompts = waves(cfg, seed)
    paged = dict(kv_layout="paged", kv_quant=True, block_size=BLOCK)
    phases = [
        ("ring-bf16", {}),
        ("ring-int8", dict(kv_quant=True)),
        ("paged-int8-ticks4-prefix", dict(paged, decode_ticks=4)),
        ("paged-int8-spec", dict(paged, spec_decode=True)),
        ("ring-bf16-dither", dict(policy=QuantPolicy(
            scheme="dither", bits=8, backend="pallas-tpu"))),
    ]
    streams, failures = {}, []
    for name, kw in phases:
        try:
            engine_phase(name, kw, params, cfg, prompts, streams, clock,
                         bad_logits)
        except PhaseFailure as e:
            print(f"phase {name}: FAILED {e}", flush=True)
            failures.append(str(e))
    return failures


def engine_phase(name, kw, params, cfg, prompts, streams, clock, bad_logits):
    c0, b0, t0 = clock.seconds, bad_logits[0], time.perf_counter()
    eng, spy, streams[name], reasons, rate = serve(params, cfg, prompts, **kw)
    wall = time.perf_counter() - t0
    check(reasons == {"length"}, f"{name}: finish reasons {reasons}")
    check(all(len(s) == MAX_NEW for s in streams[name].values()),
          f"{name}: a stream is not {MAX_NEW} tokens long")
    nonfinite = bad_logits[0] - b0
    check(nonfinite == 0, f"{name}: {nonfinite} non-finite logits rows")
    check(bool(spy), f"{name}: no decode dispatch ran")
    fn, args = spy[0]
    # the lowered program names each Pallas kernel it calls; lowering it
    # again costs a trace, not a second compile
    h0 = time.perf_counter()
    hlo = fn.lower(*args).as_text()
    hlo_s = time.perf_counter() - h0
    kernels = hlo.count("@tpu_custom_call")
    check(kernels > 0, f"{name}: decode step holds no tpu_custom_call")
    extra = ""
    if "prefix" in name:
        hit = eng.stats["prefix_hit_tokens"]
        check(hit > 0, f"{name}: no prefix hit")
        extra = f" prefix_hit_tokens={hit}"
    if name == "paged-int8-spec":
        plain = streams.get("paged-int8-ticks4-prefix", {})
        differ = sorted(r for r in streams[name]
                        if streams[name][r] != plain.get(r))
        check(not differ, f"{name}: speculative streams differ from plain "
              f"decode for requests {differ}")
        mc = eng.metrics.summary()["counters"]
        extra = (f" spec_equals_plain=True "
                 f"drafted={int(mc.get('spec_draft_tokens', 0))} "
                 f"accepted={int(mc.get('spec_accepted_tokens', 0))}")
    if "dither" in name:
        check('kernel_name = "dither_matmul"' in hlo,
              f"{name}: decode step holds no dither_matmul kernel")
    print(f"phase {name}: ok requests={len(streams[name])} "
          f"finish=length tpu_custom_calls={kernels} "
          f"compile_s={clock.seconds - c0:.1f} wall_s={wall:.1f} "
          f"lower_s={hlo_s:.1f} decode_tok_s={rate:.0f}{extra}",
          flush=True)


def kernel_parity(seed):
    """Pallas on the chip against the xla-ref oracle, serving shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import dispatch
    from repro.models.transformer import kv_shapes

    b, nkv, group, hd, kq = WAVE, 3, 3, 64, 4
    rng = np.random.default_rng(seed)
    pos = rng.integers(PROMPT, MAX_LEN - kq, b).astype(np.int32)
    k_pos = np.where(np.arange(MAX_LEN)[None] <= pos[:, None] + kq - 1,
                     np.arange(MAX_LEN)[None], -1).astype(np.int32)
    nbmax = MAX_LEN // BLOCK
    tables = rng.permutation(b * nbmax).reshape(b, nbmax).astype(np.int32)

    def cache(n, length, quantized):
        # the engine's layout (kv_shapes), as a stack of one layer
        shapes = kv_shapes((1, n), nkv, hd, length, quantized=quantized)
        if quantized:
            codes = rng.integers(-127, 128, shapes["k"]).astype(np.int8)
            scales = rng.uniform(0.1, 2.0, shapes["k_scale"])
            return jnp.asarray(codes), jnp.asarray(scales, jnp.float32)
        return jnp.asarray(rng.standard_normal(shapes["k"]),
                           jnp.bfloat16), None

    for quantized in (False, True):
        for layout in ("ring", "paged"):
            n, length = ((b, MAX_LEN) if layout == "ring"
                         else (b * nbmax + 1, BLOCK))
            (k, ks), (v, vs) = (cache(n, length, quantized),
                                cache(n, length, quantized))
            aux = k_pos[None] if layout == "ring" else tables
            # the oracle's f32 value dot runs at XLA's default TPU precision
            # (bf16 operands on the MXU) and the kernel's may too: one bf16
            # rounding of the softmax weights moves a convex combination of
            # value rows by at most 2^-9·max|V| on each side, so the pair
            # may differ by 2^-8·max|V|; every other step is the same f32
            # arithmetic
            vmax = float(jnp.max(jnp.abs(
                v.astype(jnp.float32) * (vs[:, :, :nkv, None, :] / 127.0
                                         if quantized else 1.0))))
            tol = 2.0 ** -8 * vmax
            for rows in (1, kq):
                qshape = (b, nkv, group, hd) if rows == 1 else (
                    b, rows, nkv, group, hd)
                q = jnp.asarray(rng.standard_normal(qshape), jnp.bfloat16)
                fn = {("ring", 1): dispatch.decode_attention,
                      ("ring", kq): dispatch.verify_attention,
                      ("paged", 1): dispatch.paged_decode_attention,
                      ("paged", kq): dispatch.paged_verify_attention}[
                          layout, rows]

                def run(backend, fn=fn):
                    return jax.jit(lambda *a: fn(
                        *a, k_scale=ks, v_scale=vs, layer=0,
                        backend=backend))(q, k, v, aux, pos)

                got, want = run("pallas-tpu"), run("xla-ref")
                check(bool(jnp.all(jnp.isfinite(got))),
                      f"attention {layout} rows={rows}: non-finite output")
                err = float(jnp.max(jnp.abs(got - want)))
                name = (f"{layout}-{'verify' if rows > 1 else 'decode'}-"
                        f"{'int8' if quantized else 'bf16'}")
                check(err <= tol, f"attention {name}: max|Δ|={err:.3e} > "
                      f"tol {tol:.3e}")
                print(f"parity attention {name}: max_abs_err={err:.3e} "
                      f"tol={tol:.3e} ok", flush=True)

    x = jnp.asarray(rng.uniform(-1.0, 1.0, (1024, 2048)), jnp.float32)
    codes = {be: jax.jit(lambda x, be=be: dispatch.quantize(
        x, bits=8, lo=-1.0, hi=1.0, counter=3, seed=seed, backend=be))(x)
        for be in ("pallas-tpu", "xla-ref")}
    mismatch = int(jnp.sum(codes["pallas-tpu"] != codes["xla-ref"]))
    check(mismatch == 0, f"quantize: {mismatch} codes differ from the oracle")
    print(f"parity quantize dither 8-bit (1024, 2048): codes_equal=True",
          flush=True)


def mesh_phase(seed):
    """(2, 2) mesh engine ≡ one-device engine, bitwise, ring and paged int8."""
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_serve_mesh
    from repro.models import registry

    cfg = dataclasses.replace(get_config("qwen2_1_5b"), n_layers=4)
    params = registry.init_model(jax.random.PRNGKey(seed), cfg)
    prompts = waves(cfg, seed)[:1]
    mesh = make_serve_mesh(2, 2)
    for layout in ("ring", "paged"):
        kw = dict(kv_quant=True, kv_layout=layout,
                  block_size=BLOCK if layout == "paged" else None,
                  max_new=16, max_len=256)
        _, _, one, r1, _ = serve(params, cfg, prompts, **kw)
        eng, _, four, r4, _ = serve(params, cfg, prompts, mesh=mesh, **kw)
        check(r1 == r4 == {"length"}, f"mesh {layout}: finish {r1} / {r4}")
        check(eng.heads_sharded, f"mesh {layout}: KV heads not sharded")
        leaf = eng.cache["layers"][0]["k"]
        placed = [("k", leaf)]
        if layout == "paged":
            placed.append(("block_tables", eng.cache["block_tables"]))
        for what, arr in placed:
            sh = arr.sharding
            check(len(sh.device_set) == 4 and not sh.is_fully_replicated,
                  f"mesh {layout}: {what} is not spread over 4 chips ({sh})")
            print(f"mesh {layout} {what}: shape={arr.shape} "
                  f"spec={getattr(sh, 'spec', None)} "
                  f"shard={arr.addressable_shards[0].data.shape} "
                  f"devices={len(sh.device_set)}", flush=True)
        check(one == four, f"mesh {layout}: (2,2) streams differ from one "
              "device")
        print(f"phase mesh-2x2-{layout}-int8: ok requests={len(four)} "
              "streams_equal_one_device=True", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev, count = device_report(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile_cache={enable_compile_cache()}", flush=True)
    clock = CompileClock()
    try:
        if args.chips == 4:
            mesh_phase(args.seed)
        else:
            import jax

            from repro.configs import get_config
            from repro.models import registry

            cfg = get_config("smollm_135m")
            print(f"model={cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
                  f"heads={cfg.n_heads}/{cfg.n_kv_heads} hd={cfg.hd()} "
                  f"ff={cfg.d_ff} vocab={cfg.vocab_size} seed={args.seed}")
            print(f"traffic: batch={WAVE} waves=2 prompt={PROMPT} "
                  f"shared_prefix={SHARED} max_new={MAX_NEW} "
                  f"max_len={MAX_LEN} paged_block={BLOCK}", flush=True)
            bad_logits = watch_logits()
            params = registry.init_model(jax.random.PRNGKey(args.seed), cfg)
            failures = engine_phases(params, cfg, args.seed, clock,
                                     bad_logits)
            try:
                kernel_parity(args.seed)
            except PhaseFailure as e:
                print(f"parity: FAILED {e}", flush=True)
                failures.append(str(e))
            if failures:
                raise PhaseFailure("; ".join(failures))
    except PhaseFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    print(f"compile_s_total={clock.seconds:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
