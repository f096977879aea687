"""Training launcher: end-to-end driver with checkpoint/restart.

CPU-scale example (reduced configs) and the production entry point (full
configs under a real TPU mesh — same code path, bigger mesh):

  PYTHONPATH=src python -m repro.launch.train --arch smollm_135m --reduced \
      --steps 50 --batch 8 --seq 128 --policy dither --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.dist import ctx
from repro.dist.fault_tolerance import FailureInjector, StragglerWatchdog
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.numerics.policy import QuantPolicy
from repro.optim.adamw import AdamW
from repro.optim.schedules import cosine_schedule, wsd_schedule
from repro.train import trainer

__all__ = ["train_main", "run_training"]


def run_training(
    cfg,
    *,
    steps: int,
    batch: int,
    seq: int,
    policy=None,
    grad_policy=None,
    ckpt_dir=None,
    ckpt_every: int = 20,
    seed: int = 0,
    schedule: str = "cosine",
    peak_lr: float = 3e-4,
    injector: FailureInjector | None = None,
    log=print,
):
    """One training run; resumes from the latest checkpoint if present.
    Returns (final_state_step, losses)."""
    mesh = make_local_mesh()
    lr = (wsd_schedule(peak_lr, 10, steps // 2, steps // 2)
          if schedule == "wsd" else cosine_schedule(peak_lr, 10, steps))
    opt = AdamW(lr=lr)
    step_fn = jax.jit(trainer.make_train_step(cfg, opt, policy=policy,
                                              grad_policy=grad_policy))
    state = trainer.init_train_state(jax.random.PRNGKey(seed), cfg)

    ck = Checkpointer(ckpt_dir) if ckpt_dir else None
    start = 0
    if ck is not None:
        latest = ck.latest_step()
        if latest is not None:
            state = ck.restore(latest, state)
            start = latest
            log(f"resumed from step {start}")

    dcfg = DataConfig(batch=batch, seq=seq, seed=seed)
    watchdog = StragglerWatchdog()
    losses = []
    with ctx.mesh_context(mesh):
        for step in range(start, steps):
            t0 = time.time()
            data = synthetic_batch(cfg, dcfg, step)
            if injector:
                injector.maybe_fail(step, "before_save")
            state, metrics = step_fn(state, data)
            losses.append(float(metrics["loss"]))
            dt = time.time() - t0
            if watchdog.observe(step, dt):
                log(f"straggler flagged at step {step} ({dt:.2f}s)")
            if ck is not None and (step + 1) % ckpt_every == 0:
                ck.save_async(step + 1, state)
                if injector:
                    injector.maybe_fail(step, "after_save")
            if step % 10 == 0:
                log(f"step {step:5d} loss {losses[-1]:.4f} ({dt*1e3:.0f} ms)")
    if ck is not None:
        ck.wait()
        ck.save(steps, state)
    return steps, losses


def train_main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--policy", default="none",
                    choices=["none", "dither", "stochastic", "deterministic"])
    ap.add_argument("--policy-bits", type=int, default=8)
    ap.add_argument("--kernel-backend", default="jnp",
                    help="policy matmul backend: 'jnp' (unfused fake-quant) "
                         "or a kernel-dispatcher backend/alias "
                         "(auto, pallas, pallas-interpret, pallas-tpu, xla-ref)")
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "dither", "stochastic"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "wsd"])
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    policy = (None if args.policy == "none"
              else QuantPolicy(scheme=args.policy, bits=args.policy_bits,
                               backend=args.kernel_backend))
    gpolicy = (None if args.grad_compress == "none"
               else QuantPolicy(scheme=args.grad_compress, bits=8))
    steps, losses = run_training(
        cfg, steps=args.steps, batch=args.batch, seq=args.seq,
        policy=policy, grad_policy=gpolicy, ckpt_dir=args.ckpt_dir,
        schedule=args.schedule, peak_lr=args.lr,
    )
    print(f"done: {steps} steps; loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    train_main()
