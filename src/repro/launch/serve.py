"""Serving launcher: drive the two-phase engine over a synthetic request mix.

Admits requests through the scheduler, prefills prompts with the batched
``prefill_step`` and decodes under per-request sampling (DESIGN.md §6):

  PYTHONPATH=src python -m repro.launch.serve --arch smollm_135m --reduced \
      --requests 6 --batch 4 --max-new 8 --temperature 0.8 --top-k 40 \
      --sched priority
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.numerics.policy import QuantPolicy
from repro.serve.engine import Engine, Request
from repro.serve.sampling import SamplingParams


def serve_main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=5)
    ap.add_argument("--policy", default="none",
                    choices=["none", "dither", "stochastic", "deterministic"])
    ap.add_argument("--kernel-backend", default="jnp",
                    help="policy matmul backend: 'jnp' (unfused fake-quant) "
                         "or a kernel-dispatcher backend/alias "
                         "(auto, pallas, pallas-interpret, pallas-tpu, xla-ref)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="dither-quantised int8 KV cache (2× decode memory)")
    ap.add_argument("--kv-layout", default="ring", choices=["ring", "paged"],
                    help="KV cache layout: dense per-slot ring, or the paged "
                         "block pool with prefix caching + continuous "
                         "batching (attention-only archs)")
    ap.add_argument("--block-size", type=int, default=None,
                    help="paged pool block size in tokens (default: autotune "
                         "model pick)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged pool capacity in blocks (default: matches "
                         "the dense ring, batch × ceil(max_len/bs))")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request prefix-block reuse")
    ap.add_argument("--decode-ticks", type=int, default=1,
                    help="decode ticks fused into one device dispatch; the "
                         "host drains tokens/metrics once per window "
                         "(DESIGN.md §11)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative draft-and-verify decode (DESIGN.md "
                         "§14): prompt-lookup drafting + one multi-token "
                         "verify dispatch per window; the emitted stream "
                         "stays bitwise the plain-decode stream")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculative window width: 1 pending token + "
                         "draft-k - 1 drafted tokens per verify dispatch")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="piggyback chunked prefill: admit prompts in chunks "
                         "of this many tokens between decode windows "
                         "(paged: rounded to a block multiple; default: "
                         "whole-prompt prefill)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="serve sharded on a (data, model) mesh, e.g. "
                         "'2,2' (DESIGN.md §9; needs data×model devices — "
                         "on CPU force them with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 = softmax sampling")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed (request r uses seed + r)")
    ap.add_argument("--sched", default="fcfs", choices=["fcfs", "priority"],
                    help="admission policy ('priority' favours high "
                         "Request.priority; the demo gives odd rids +1)")
    ap.add_argument("--metrics", default=None, metavar="SINK",
                    help="stream per-tick engine metrics (DESIGN.md §10): "
                         "'stdout', or 'jsonl:<path>' / a *.jsonl path.  "
                         "Unset = collect but don't stream; the summary "
                         "prints either way")
    ap.add_argument("--trace", default=None, metavar="SPEC",
                    help="per-request span tracing (DESIGN.md §13): 'mem' "
                         "(in-memory, enables the end-of-run attribution "
                         "summary), 'perfetto:<path>' (Chrome-trace JSON "
                         "for ui.perfetto.dev), 'jsonl:<path>' (streaming "
                         "event feed), comma-combinable.  Unset = off "
                         "(zero overhead)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request wall-clock deadline from submission "
                         "(DESIGN.md §12): queued or running, a request "
                         "past it finishes with reason 'deadline'")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="bounded admission queue: submissions past this "
                         "depth shed per --shed-policy (DESIGN.md §12)")
    ap.add_argument("--shed-policy", default="reject-new",
                    choices=["reject-new", "evict-lowest-priority"],
                    help="what a full queue sheds: the newcomer, or the "
                         "lowest-priority queued request when the "
                         "newcomer outranks it")
    ap.add_argument("--snapshot-path", default=None, metavar="PATH",
                    help="persist an atomic engine snapshot every window "
                         "(JSON; DESIGN.md §12) — the crash-recovery point")
    ap.add_argument("--resume", action="store_true",
                    help="restore from --snapshot-path if it exists and "
                         "continue (bitwise for policy-free serving) "
                         "instead of submitting the synthetic workload")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    policy = (None if args.policy == "none"
              else QuantPolicy(scheme=args.policy, backend=args.kernel_backend))

    mesh = None
    if args.mesh:
        from repro.launch.mesh import parse_serve_mesh
        try:
            mesh = parse_serve_mesh(args.mesh)
        except ValueError as e:
            ap.error(str(e))

    params = registry.init_model(jax.random.PRNGKey(0), cfg)
    frames = (jnp.zeros((args.batch, cfg.n_enc_tokens, cfg.d_model), jnp.bfloat16)
              if cfg.is_encdec else None)
    engine = Engine(params, cfg, args.batch, args.max_len, policy=policy,
                    frames=frames, kv_quant=args.kv_quant and not cfg.is_encdec,
                    scheduler=args.sched, kv_layout=args.kv_layout,
                    block_size=args.block_size, num_blocks=args.num_blocks,
                    prefix_cache=not args.no_prefix_cache, mesh=mesh,
                    metrics=args.metrics, trace=args.trace,
                    decode_ticks=args.decode_ticks,
                    prefill_chunk=args.prefill_chunk,
                    queue_cap=args.queue_cap, shed_policy=args.shed_policy,
                    snapshot_path=args.snapshot_path,
                    spec_decode=args.spec_decode, draft_k=args.draft_k)
    resumed = False
    if args.resume and args.snapshot_path and os.path.exists(args.snapshot_path):
        with open(args.snapshot_path) as fh:
            engine.restore(json.load(fh))
        resumed = True
        print(f"resumed from {args.snapshot_path} at tick {engine.tick} "
              f"({len(engine.finished)} finished, "
              f"{len(engine.scheduler)} queued)")
    if not resumed:
        for r in range(args.requests):
            prompt = [(7 * r + i) % (cfg.vocab_size - 1) + 1
                      for i in range(args.prompt_len)]
            engine.submit(Request(
                rid=r, prompt=prompt, priority=r % 2,
                deadline_s=(args.deadline_ms / 1e3
                            if args.deadline_ms is not None else None),
                sampling=SamplingParams(temperature=args.temperature,
                                        top_k=args.top_k, seed=args.seed + r,
                                        max_new=args.max_new,
                                        counter_offset=1000 * r)))
    t0 = time.time()
    done = engine.run(ticks=args.requests * (args.max_new + 6) + 20)
    dt = time.time() - t0
    for r in sorted(done, key=lambda x: x.rid):
        ttft = f"{1e3 * r.ttft:.0f}ms" if r.ttft is not None else "-"
        print(f"req {r.rid} [{r.finish_reason}] ttft={ttft}: {r.out}")
    st = engine.stats
    pf = st["prefill_tokens"] / st["prefill_s"] if st["prefill_s"] else 0.0
    dc = st["decode_tokens"] / st["decode_s"] if st["decode_s"] else 0.0
    print(f"served {len(done)}/{args.requests} requests in {dt:.2f}s "
          f"(prefill {pf:.0f} tok/s over {st['prefill_calls']} calls, "
          f"decode {dc:.0f} tok/s over {st['decode_calls']} ticks)")
    if args.kv_layout == "paged":
        ps = engine.pool_stats()            # summed across data-shard pools
        print(f"paged pool: block_size={engine.block_size} "
              f"blocks={engine.num_blocks} allocs={ps['allocated']} "
              f"evictions={ps['evicted']} "
              f"prefix_hit_tokens={st['prefix_hit_tokens']} "
              f"preemptions={st['preemptions']} "
              f"cached_now={ps['cached']}")
    if args.spec_decode:
        mc0 = engine.metrics.summary()["counters"]
        drafted = int(mc0.get("spec_draft_tokens", 0))
        acc = int(mc0.get("spec_accepted_tokens", 0))
        rate = acc / drafted if drafted else 0.0
        print(f"spec-decode: k={args.draft_k} "
              f"windows={int(mc0.get('spec_windows', 0))} "
              f"drafted={drafted} accepted={acc} accept_rate={rate:.2f} "
              f"emitted={int(mc0.get('spec_emitted_tokens', 0))}")
    if mesh is not None:
        print(f"mesh: data={engine.dp} model={engine.tp} "
              f"heads_sharded={engine.heads_sharded} "
              f"slots/shard={args.batch // engine.dp}")
    ms = engine.metrics.summary()
    mc = ms["counters"]
    print(f"metrics: ticks={ms['ticks']} "
          f"queue_depth_mean={ms['gauges'].get('queue_depth', {}).get('mean', 0):.2f} "
          f"occupancy_mean={ms['gauges'].get('batch_occupancy', {}).get('mean', 0):.2f} "
          f"ttft_p95={1e3 * ms['ttft_s']['p95']:.1f}ms "
          f"itl_p95={1e3 * ms['itl_s']['p95']:.1f}ms "
          f"sink_errors={ms['sink_errors']}")
    print(f"fault: deadline_expired={int(mc.get('finish_deadline', 0))} "
          f"shed={int(mc.get('finish_shed', 0))} "
          f"recoveries={int(mc.get('recoveries', 0))} "
          f"slow_windows={int(mc.get('slow_windows', 0))} "
          f"degrade_events={int(mc.get('degrade_events', 0))}")
    if engine.trace.enabled:
        # end-of-run latency attribution (DESIGN.md §13): one line per
        # finished request, wall time decomposed into phase shares
        from repro.serve.trace import format_explain
        for r in sorted(done, key=lambda x: x.rid):
            print("explain " + format_explain(engine.explain(r.rid)))
        engine.trace.close()      # flush the jsonl feed, write the perfetto
        if engine.trace.perfetto_path:
            print(f"trace: wrote perfetto export to "
                  f"{engine.trace.perfetto_path} "
                  f"(open at https://ui.perfetto.dev)")
    engine.metrics.close()


if __name__ == "__main__":
    serve_main()
