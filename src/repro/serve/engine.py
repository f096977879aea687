"""Serving: two-phase (batched prefill → batched decode) engine.

``make_serve_fns`` builds the two jit-able entry points — ``prefill_step``
and ``decode_step`` — and ``Engine`` is the host-side loop that drives them
(DESIGN.md §6): a :class:`~repro.serve.scheduler.Scheduler` admits queued
requests into free decode slots; admitted prompts run through the *batched*
``prefill_step`` (right-padded prompt batch, one forward pass, KV written
per-slot into the shared ring cache, prefill logits seeding the first
sampled token); the steady state is one ``decode_step`` per tick over every
active slot.  Per-request :class:`~repro.serve.sampling.SamplingParams`
drive greedy/temperature/top-k sampling, EOS/stop handling and the
per-request dither-counter offsets; slots are preempted at ``max_len`` and
recycled; streaming callbacks fire per emitted token.

The numerics policy — and therefore the fused kernel backend — applies to
prefill and decode alike, so weight-quantised serving exercises the same
dispatcher path as training.

``Engine(..., mesh=...)`` runs the same loop sharded over a
('data', 'model') mesh (DESIGN.md §9): decode slots and the paged block
pools partition on 'data' (one shard-local ``KVPool`` per data shard), KV
heads on 'model' (replicated fallback when the GQA head count does not
divide), and every jitted step executes per-shard under ``shard_map``.
The layout is reduction-preserving — QKV column-parallel, heads
all-gathered before a replicated W_O, no psums — so for policy-free bf16
and int8-KV serving the sharded token stream is *bitwise* the
single-device stream (tests/test_sharded_serve.py).
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field, replace as _dc_replace
from typing import Callable, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import ctx as dist_ctx
from repro.dist import sharding as dist_sharding
from repro.dist.fault_tolerance import StragglerWatchdog
from repro.models import registry
from repro.models.config import ModelConfig
from repro.numerics.policy import QuantPolicy
from repro.serve.draft import Drafter, PromptLookupDrafter
from repro.serve.kvpool import KVPool
from repro.serve.metrics import Metrics
from repro.serve.sampling import SamplingParams, sample_tokens
from repro.serve.scheduler import Scheduler
from repro.serve.trace import Tracer

__all__ = ["make_serve_fns", "make_decode_and_sample", "make_fused_decode",
           "make_paged_prefill", "make_chunked_prefill", "make_spec_verify",
           "Engine", "Request", "SamplingParams", "Scheduler", "KVPool",
           "Metrics", "Drafter", "PromptLookupDrafter"]


def _jit(fn, **kw):
    """``jax.jit`` for the engine's device programs.  On a TPU they compile
    without excess precision: the compiler may otherwise keep a bf16 value
    in f32 across the ops it fuses, and it fuses the verify forward, the
    decode step and a sharded step differently, so the same row would round
    differently in each and the bitwise contracts of DESIGN.md §9 and §14
    would not hold there.  Off a TPU the option is left at its default."""
    if jax.default_backend() == "tpu":
        kw["compiler_options"] = {"xla_allow_excess_precision": False}
    return jax.jit(fn, **kw)


def make_serve_fns(cfg: ModelConfig, policy: Optional[QuantPolicy] = None, *,
                   max_len: int, kv_quant: bool = False, frames=None):
    """Build the two jit-able serving entry points (DESIGN.md §6).

    ``prefill_step(params, tokens, lengths, kv_offset, counter)`` maps a
    right-padded (B, S) prompt batch + (B,) true lengths to the last-prompt-
    token logits (B, vocab) and a full decode cache whose per-slot positions
    equal ``lengths`` — attention-only architectures do this in one batched
    forward (``transformer.prefill_with_cache``); recurrent/enc-dec
    architectures fall back to a scanned on-device prefill
    (``registry.apply_prefill``).  ``decode_step(params, token, cache,
    kv_offset, counter)`` is one token for every slot.  The engine jits the
    prefill step directly and drives decode through the fused
    ``make_decode_and_sample`` tick below; ``decode_step`` remains the
    standalone two-call building block (launch/dryrun.py rooflines the same
    prefill-forward and decode-step compute at pod scale, and the parity
    tests replay it against the fused path).  ``policy`` is resolved here so
    the traced steps embed a concrete kernel-dispatcher backend.
    """
    policy = policy.resolved() if policy is not None else None
    batched = registry.supports_batched_prefill(cfg)

    def prefill_step(params, tokens, lengths, kv_offset=None, counter=0):
        cache0 = None
        if not batched:
            cache0 = registry.make_cache(
                params, cfg, tokens.shape[0], max_len, frames=frames,
                policy=policy, kv_quant=kv_quant)
        return registry.apply_prefill(
            params, cfg, tokens, lengths, max_len, policy=policy,
            counter=counter, kv_quant=kv_quant, kv_offset=kv_offset,
            cache0=cache0)

    def decode_step(params, token, cache, kv_offset=None, counter=0):
        return registry.apply_decode(params, cfg, token, cache, policy=policy,
                                     counter=counter, kv_offset=kv_offset)

    return prefill_step, decode_step


def make_decode_and_sample(cfg: ModelConfig,
                           policy: Optional[QuantPolicy] = None):
    """Build the fused single-dispatch decode tick (DESIGN.md §6).

    One jitted call per generated token: ``decode_and_sample(params, token,
    cache, kv_offset, counter, temps, topks, seeds, counters)`` runs the
    model decode step *and* the per-slot sampler on device and returns
    ``(tokens (B,) int32, counters + 1, new cache)`` — the PR-2 engine's
    ``decode_step`` + ``sample_tokens`` pair collapsed into one device
    dispatch, so the steady-state tick costs one host→device launch instead
    of two.  The sampling counters advance on device (one emitted token per
    tick per slot); the engine refreshes its device-resident copies only
    when slot state actually changes.  Token-stream equivalence with the
    two-call path is pinned by tests/test_decode_attention.py.
    """
    policy = policy.resolved() if policy is not None else None

    def decode_and_sample(params, token, cache, kv_offset, counter,
                          temps, topks, seeds, counters):
        logits, new_cache = registry.apply_decode(
            params, cfg, token, cache, policy=policy, counter=counter,
            kv_offset=kv_offset)
        toks = sample_tokens(logits, temps, topks, seeds, counters)
        return toks, counters + 1, new_cache

    return decode_and_sample


def make_fused_decode(cfg: ModelConfig, policy: Optional[QuantPolicy] = None,
                      *, n_ticks: int = 1):
    """Build the windowed multi-tick decode dispatch (DESIGN.md §11).

    ``fused_decode(params, token, cache, kv_offset, counter, temps, topks,
    seeds, counters, alive, budgets, stops)`` runs ``n_ticks`` fused
    decode-and-sample ticks in one jitted call via ``lax.scan`` and returns
    ``(tokens (n_ticks, B), last_token (B,), counters, cache')`` — the host
    drains one (n_ticks, B) token matrix per window instead of syncing every
    tick.  Finish detection moves on-device as an ``alive`` bitmask: a slot
    dies when its sampled token lands in its ``stops`` row ((B, W) int32,
    -1-padded — EOS is folded in) or when it has emitted ``budgets[b]``
    tokens this window (max_new / max_len / paged-block coverage, computed
    host-side).  Dead and idle rows keep decoding but are *inert*: their
    sampled token, sampling counter and cache position freeze, and (paged)
    their block-table row is masked to the trash block so a finished slot
    can never scribble over blocks headed for the prefix cache.  Because a
    live slot's ops are bitwise those of the n_ticks=1 scan, an N-tick
    window reproduces N single ticks exactly (tests/test_overlap.py).
    """
    policy = policy.resolved() if policy is not None else None

    def fused_decode(params, token, cache, kv_offset, counter,
                     temps, topks, seeds, counters, alive, budgets, stops):
        paged = "block_tables" in cache
        if paged:
            leaf = (jax.tree.leaves(cache["layers"][0])[0] if cache["layers"]
                    else jax.tree.leaves(cache["remainder"][0])[0])
            # shard-local pool leading dim is blocks + 1; last id is trash
            nbp = leaf.shape[1] if cache["layers"] else leaf.shape[0]
            trash = jnp.int32(nbp - 1)

        def body(carry, j):
            token, cache, counters, alive, emitted = carry
            pos0 = cache["pos"]
            step_cache = cache
            if paged:
                step_cache = dict(cache)
                step_cache["block_tables"] = jnp.where(
                    alive[:, None], cache["block_tables"], trash)
            logits, new_cache = registry.apply_decode(
                params, cfg, token, step_cache, policy=policy,
                counter=counter + j, kv_offset=kv_offset)
            toks = sample_tokens(logits, temps, topks, seeds, counters)
            toks = jnp.where(alive, toks, token)
            new_cache["pos"] = jnp.where(alive, new_cache["pos"], pos0)
            if paged:
                new_cache["block_tables"] = cache["block_tables"]
            counters = jnp.where(alive, counters + 1, counters)
            emitted = emitted + alive.astype(jnp.int32)
            hit = jnp.any(toks[:, None] == stops, axis=1)
            alive = alive & ~hit & (emitted < budgets)
            return (toks, new_cache, counters, alive, emitted), toks

        carry0 = (token, cache, counters, alive, jnp.zeros_like(counters))
        (token, cache, counters, _, _), toks_all = jax.lax.scan(
            body, carry0, jnp.arange(n_ticks, dtype=jnp.int32))
        return toks_all, token, counters, cache

    return fused_decode


def make_spec_verify(cfg: ModelConfig, policy: Optional[QuantPolicy] = None,
                     *, draft_k: int):
    """Build the speculative verify dispatch (DESIGN.md §14).

    ``spec_verify(params, drafts, cache, kv_offset, counter, temps, topks,
    seeds, counters, alive, wcap)`` scores ``draft_k`` positions per slot in
    one jitted call and returns ``(sampled (B, K) int32, cache')``.
    ``drafts[:, 0]`` is each slot's last committed token (the pending decode
    input) and ``drafts[:, 1:]`` the drafter's proposals; ``sampled[:, t]``
    is what the engine's sampler — stateless in (seed, counter + t) — draws
    from row t's logits, which are bitwise the sequential decode logits at
    position ``pos + t`` whenever rows 1..t matched (the accept condition
    the host walk checks).  All K positions are written to the (donated)
    cache up to each row's ``wcap`` budget; ``pos`` does not advance — the
    host follows up with one ``spec_commit`` dispatch once accept lengths
    are known.  Dead rows (``alive`` false) write nothing: ring writes
    route out of bounds and paged writes (plus their block-table reads)
    route to the trash block, mirroring the fused decode window's masking.
    """
    policy = policy.resolved() if policy is not None else None

    def spec_verify(params, drafts, cache, kv_offset, counter,
                    temps, topks, seeds, counters, alive, wcap):
        paged = "block_tables" in cache
        step_cache = cache
        if paged:
            leaf = (jax.tree.leaves(cache["layers"][0])[0] if cache["layers"]
                    else jax.tree.leaves(cache["remainder"][0])[0])
            nbp = leaf.shape[1] if cache["layers"] else leaf.shape[0]
            step_cache = dict(cache)
            step_cache["block_tables"] = jnp.where(
                alive[:, None], cache["block_tables"], jnp.int32(nbp - 1))
        logits, new_cache = registry.apply_verify(
            params, cfg, drafts, step_cache, policy=policy, counter=counter,
            kv_offset=kv_offset, alive=alive, wcap=wcap)
        if paged:
            new_cache["block_tables"] = cache["block_tables"]
        # row t samples with the counter sequential decode would have used
        # at position pos + t — with bitwise-equal logits the draw is
        # bitwise the sequential draw, for greedy and temperature alike
        sampled = jnp.stack(
            [sample_tokens(logits[:, t], temps, topks, seeds, counters + t)
             for t in range(draft_k)], axis=1)
        return sampled, new_cache

    return spec_verify


def make_chunked_prefill(cfg: ModelConfig,
                         policy: Optional[QuantPolicy] = None, *,
                         kv_quant: bool = False):
    """Build the jit-able chunked ring prefill step (DESIGN.md §11).

    ``chunked_prefill(params, tokens, lengths, starts, cache, kv_offset,
    counter)`` runs one batched forward over per-slot prompt *chunks* at
    absolute positions ``starts + t``, joins each slot's already-written
    ring history inside attention, merges the chunk K/V into the (donated)
    live ring cache and returns ``(last_chunk_logits, cache')``.  The paged
    engine needs no analogue — ``make_paged_prefill`` already takes
    block-aligned ``starts``, so a paged chunk is just a suffix call."""
    policy = policy.resolved() if policy is not None else None

    def chunked_prefill(params, tokens, lengths, starts, cache, kv_offset,
                        counter):
        return registry.apply_prefill_chunked(
            params, cfg, tokens, lengths, starts, cache, policy=policy,
            counter=counter, kv_quant=kv_quant, kv_offset=kv_offset)

    return chunked_prefill


def make_paged_prefill(cfg: ModelConfig, policy: Optional[QuantPolicy] = None,
                       *, kv_quant: bool = False):
    """Build the jit-able paged prefill step (DESIGN.md §6).

    ``paged_prefill(params, tokens, lengths, starts, block_tables, cache,
    kv_offset, counter, prefix_blocks=...)`` runs one batched forward over
    the prompt *suffixes*, scatters their K/V into the pool blocks named by
    ``block_tables`` and returns ``(last_logits, cache')`` — the live cache
    is donated by the engine, so the pool updates in place.
    ``prefix_blocks`` is static (0 on cold waves — exactly the cold batched
    prefill — or the table width when any admitted request hit the prefix
    cache), so the engine compiles at most two variants.
    """
    policy = policy.resolved() if policy is not None else None

    def paged_prefill(params, tokens, lengths, starts, block_tables, cache,
                      kv_offset, counter, *, prefix_blocks: int = 0):
        return registry.apply_prefill_paged(
            params, cfg, tokens, lengths, starts, block_tables, cache,
            policy=policy, counter=counter, kv_quant=kv_quant,
            kv_offset=kv_offset, prefix_blocks=prefix_blocks)

    return paged_prefill


@dataclass
class Request:
    """One generation request.

    Lifecycle (DESIGN.md §6): ``queued`` → (scheduler admits) → ``active``
    → ``done`` with ``finish_reason`` ∈ {"eos", "stop", "length",
    "preempted", "rejected", "deadline", "shed"}.  ``sampling`` carries the
    per-request decode controls; ``max_new`` is a convenience override of
    ``sampling.max_new`` kept from the original API.  ``stream`` (if set)
    is called as ``stream(request, token)`` for every emitted token.
    Timing fields are host-clock seconds: ``ttft`` = time-to-first-token
    from submission, ``itl`` = inter-token latencies.  ``deadline_s`` is a
    wall-clock budget from submission (DESIGN.md §12): the engine expires
    the request — queued or running — once the budget elapses, checked
    once per window drain with zero extra device dispatches.
    """

    rid: int
    prompt: List[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    priority: int = 0
    max_new: Optional[int] = None
    deadline_s: Optional[float] = None
    stream: Optional[Callable[["Request", int], None]] = None
    out: List[int] = field(default_factory=list)
    done: bool = False
    finish_reason: Optional[str] = None
    state: str = "new"
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    itl: List[float] = field(default_factory=list)
    # paged-pool lifecycle state (engine-internal): a preempted request's
    # frozen decode position / pending input token (blocks stay in the
    # pool, so re-admission resumes instead of re-prefilling), and the
    # count of its pool blocks sealed into the prefix cache so far
    _resume: Optional[dict] = None
    _sealed: int = 0
    # chunked-prefill progress (engine-internal): tokens of the prompt
    # already written to cache while state == "prefilling" (DESIGN.md §11)
    _pf_pos: int = 0

    @property
    def ttft(self) -> Optional[float]:
        if self.t_first is None or self.t_submit is None:
            return None
        return self.t_first - self.t_submit

    def effective_max_new(self) -> int:
        return self.max_new if self.max_new is not None else self.sampling.max_new


def _bucket(n: int) -> int:
    """Round a prompt length up to a power of two (≥ 8) so the jitted
    prefill compiles once per bucket, not once per prompt length."""
    b = 8
    while b < n:
        b *= 2
    return b


class Engine:
    """Host-side continuous-batching loop over the two jitted serve fns.

    Fixed decode batch B (the slot count) over a shared per-slot ring-buffer
    KV cache.  Each :meth:`step`:

    1. asks the scheduler for requests to fill free slots; admitted prompts
       are right-padded into a (B, S_bucket) batch and run through the
       batched ``prefill_step`` — the prompt costs one forward pass, its KV
       lands in the admitted slots, and the prefill logits seed each
       request's first sampled token;
    2. runs one fused ``decode_and_sample`` call for every active slot —
       model decode step *and* per-request sampling
       (:class:`SamplingParams`) in a single device dispatch per tick;
    3. retires slots on EOS/stop tokens, ``max_new``, or ``max_len``
       preemption, freeing them for the next admission wave.

    Steady-state host↔device traffic is minimal: the per-slot sampling
    state (temperature / top-k / seed / counter-offset arrays) and the last
    sampled token live **device-resident** and are re-uploaded only when
    slot membership changes (admission), with the sampling counters and
    last tokens advancing on device inside the fused step; and the ring
    cache argument is **donated** to the jitted decode and prefill-merge
    steps, so the B×cap×layers KV updates in place instead of
    double-buffering every tick.

    The policy dither counter advances once per engine tick ("rounding in
    time", §VII); per-request ``counter_offset`` shifts the int8-KV and
    sampling counters so concurrent requests walk independent pulse
    sequences and restarts replay identically (DESIGN.md §6).

    Fault tolerance (DESIGN.md §12): per-request deadlines and a queue TTL
    expire stale work once per window drain; ``queue_cap`` bounds the queue
    with a shed policy ('reject-new' / 'evict-lowest-priority'); pool
    pressure past ``degrade_high`` steps the decode window down to single
    ticks and pauses prefix-cache insertion until pressure clears past
    ``degrade_low``; :meth:`snapshot`/:meth:`restore` give bitwise crash
    recovery (host truth serialized, device KV re-materialized by replay).
    """

    SHED_POLICIES = ("reject-new", "evict-lowest-priority")

    def __init__(self, params, cfg: ModelConfig, batch: int, max_len: int,
                 policy: Optional[QuantPolicy] = None, frames=None,
                 kv_quant: bool = False,
                 scheduler: Union[str, Scheduler] = "fcfs",
                 kv_layout: str = "ring",
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 mesh=None,
                 metrics: Union[None, str, Metrics] = None,
                 trace: Union[None, str, Tracer] = None,
                 decode_ticks: int = 1,
                 prefill_chunk: Optional[int] = None,
                 queue_cap: Optional[int] = None,
                 shed_policy: str = "reject-new",
                 queue_ttl_s: Optional[float] = None,
                 injector=None,
                 watchdog: Union[None, bool, StragglerWatchdog] = True,
                 snapshot_path: Optional[str] = None,
                 snapshot_every: int = 1,
                 degrade_high: float = 0.90,
                 degrade_low: float = 0.70,
                 spec_decode: bool = False,
                 draft_k: int = 4,
                 drafter: Optional[Drafter] = None):
        self.params, self.cfg, self.batch, self.max_len = params, cfg, batch, max_len
        policy = policy.resolved() if policy is not None else None
        self.policy = policy
        self.kv_quant = kv_quant
        self._frames = frames
        if kv_layout not in ("ring", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if kv_layout == "paged" and not registry.supports_paged_kv(cfg):
            raise ValueError("kv_layout='paged' requires an attention-only "
                             f"decoder; {cfg.name!r} is not one")
        self.kv_layout = kv_layout
        self.decode_ticks = int(decode_ticks)
        if self.decode_ticks < 1:
            raise ValueError(f"decode_ticks must be >= 1, got {decode_ticks}")
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1 (or None)")
            if not registry.supports_chunked_prefill(cfg):
                raise ValueError("chunked prefill requires an attention-only "
                                 f"decoder; {cfg.name!r} is not one")
        self.prefill_chunk = prefill_chunk

        # ---- speculative decoding (DESIGN.md §14): draft-and-verify decode
        # windows; every gate protects the bitwise stream contract
        self.spec_decode = bool(spec_decode)
        self.draft_k = int(draft_k)
        self.drafter = drafter if drafter is not None else PromptLookupDrafter()
        if self.spec_decode:
            if self.draft_k < 2:
                raise ValueError(f"draft_k must be >= 2, got {draft_k}")
            if not registry.supports_spec_decode(cfg):
                raise ValueError(
                    "spec_decode requires an attention-only decoder without "
                    f"MoE; {cfg.name!r} is not one (SSM/RG-LRU recurrences "
                    "have no multi-token verify form, and MoE capacity ranks "
                    "couple a verify row to its own future draft positions)")
            if policy is not None and policy.enabled:
                raise ValueError(
                    "spec_decode requires policy=None: the activation "
                    "quantiser's tensor-global absmax couples verify rows, "
                    "so they would not be bitwise the sequential steps")
            if kv_layout == "ring" and cfg.window and cfg.window < max_len:
                raise ValueError(
                    "spec_decode over the ring layout needs ring capacity "
                    f"= max_len; window={cfg.window} < max_len={max_len} "
                    "would let the verify forward overwrite positions its "
                    "own earlier rows still attend (use kv_layout='paged')")

        # ---- fault-tolerance / overload knobs (DESIGN.md §12)
        if shed_policy not in self.SHED_POLICIES:
            raise ValueError(f"unknown shed_policy {shed_policy!r}; expected "
                             f"one of {self.SHED_POLICIES}")
        if queue_cap is not None and int(queue_cap) < 1:
            raise ValueError(f"queue_cap must be >= 1 (or None), got {queue_cap}")
        if not (0.0 < degrade_low <= degrade_high <= 1.0):
            raise ValueError("need 0 < degrade_low <= degrade_high <= 1, got "
                             f"({degrade_low}, {degrade_high})")
        self.queue_cap = None if queue_cap is None else int(queue_cap)
        self.shed_policy = shed_policy
        self.queue_ttl_s = queue_ttl_s
        self.injector = injector
        self.watchdog = (StragglerWatchdog() if watchdog is True
                         else (watchdog or None))
        self.snapshot_path = snapshot_path
        self.snapshot_every = max(1, int(snapshot_every))
        self.degrade_high, self.degrade_low = degrade_high, degrade_low
        self._degraded = False
        self._now = time.time          # injectable clock (deadline tests)
        self._steps_since_snap = 0
        self._step_tick = 0            # tick at window start (injector key)
        self._last_window_s = 0.0

        # ---- mesh layout (DESIGN.md §9): decode slots partition on 'data',
        # KV heads on 'model' (replicated fallback when the GQA head count
        # does not divide — mirroring dist.sharding._TP_RULES' guards)
        self.mesh = mesh
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names,
                             (int(mesh.shape[a]) for a in mesh.axis_names)))
            self.dp = int(sizes.get("data", 1))
            self.tp = int(sizes.get("model", 1))
            if not registry.supports_batched_prefill(cfg):
                raise ValueError(
                    "mesh serving requires an attention-only decoder "
                    f"(arch {cfg.name!r} has recurrent state / an encoder)")
            if batch % self.dp:
                raise ValueError(f"batch {batch} must be a multiple of the "
                                 f"mesh's data axis ({self.dp})")
        else:
            self.dp = self.tp = 1
        self.heads_sharded = (mesh is not None
                               and dist_sharding.serve_heads_shardable(
                                   cfg, self.tp))
        # int8 scale rows pad per head shard (transformer.scale_rows)
        self._head_shards = self.tp if self.heads_sharded else 1
        # inside shard_map the model code sees local shapes: scale the head
        # counts down (head_dim pinned so cfg.hd() is unchanged)
        self._cfg_local = (_dc_replace(cfg,
                                       n_heads=cfg.n_heads // self.tp,
                                       n_kv_heads=cfg.n_kv_heads // self.tp,
                                       head_dim=cfg.hd())
                           if self.heads_sharded else cfg)

        if kv_layout == "ring":
            # the ring-chunk scatter covers each slot's ring at most once per
            # chunk only while the chunk fits the ring capacity
            ring_cap = (min(cfg.window, max_len) if cfg.window else max_len)
            if self.prefill_chunk is not None:
                self.prefill_chunk = min(self.prefill_chunk, ring_cap)

        if kv_layout == "paged":
            from repro.kernels import autotune as _autotune
            from repro.kernels import dispatch as _dispatch
            from repro.kernels.decode_attention import check_pool_block

            backend = _dispatch.resolve_backend(None).name
            if block_size is None:
                nkv = max(1, cfg.n_kv_heads)
                shape = (batch, max_len, nkv,
                         max(1, cfg.n_heads // nkv), cfg.hd())
                dtype = "int8" if kv_quant else "bfloat16"
                block_size = _autotune.best_block(
                    "paged_attention", shape, dtype, 8 if kv_quant else 16,
                    "flash", backend)[0]
            self.block_size = bs = int(block_size)
            if backend == "pallas-tpu":
                check_pool_block(bs)
            self.nbmax = -(-max_len // bs)
            if self.prefill_chunk is not None:
                # paged chunks stay block-aligned so every continuation chunk
                # starts at a block boundary (the paged prefill's contract)
                self.prefill_chunk = max(bs, self.prefill_chunk // bs * bs)
            # default capacity matches the dense ring's token count; callers
            # under-provision it to exercise continuous batching / eviction.
            # Under a mesh the pool partitions on 'data': each data shard
            # owns num_blocks/dp blocks (its admission budget) plus its own
            # trash block, and block tables carry shard-local physical ids.
            total = (int(num_blocks) if num_blocks is not None
                     else batch * self.nbmax)
            total = -(-total // self.dp) * self.dp     # round up to dp
            self.num_blocks = total
            self._nb_local = total // self.dp
            # prefix reuse requires prefill numerics that depend only on
            # token identity + absolute position: policy off, or the
            # counter-independent deterministic rounding scheme.  (The int8
            # KV quantiser is always position-keyed; its per-request offset
            # seeds the prefix-hash chain instead.)
            self._prefix_enabled = bool(prefix_cache) and (
                policy is None or policy.scheme == "deterministic")
            self.pools = [KVPool(self._nb_local, bs,
                                 prefix_cache=self._prefix_enabled)
                          for _ in range(self.dp)]
            self._trash = self._nb_local          # shard-local trash id
            self._rid_shard: dict = {}            # rid → data shard holding it
            self.cache = registry.make_cache(
                params, cfg, batch, max_len, frames=frames, policy=policy,
                kv_quant=kv_quant, kv_layout="paged", block_size=bs,
                num_blocks=self._nb_local, data_shards=self.dp,
                head_shards=self._head_shards)
            self._bt = np.full((batch, self.nbmax), self._trash, np.int32)
            self._bt_dirty = True
        else:
            self.pools = []
            self.cache = registry.make_cache(params, cfg, batch, max_len,
                                             frames=frames, policy=policy,
                                             kv_quant=kv_quant,
                                             head_shards=self._head_shards)

        cfg_l = self._cfg_local
        prefill_step, decode_step = make_serve_fns(
            cfg_l, policy, max_len=max_len, kv_quant=kv_quant, frames=frames)
        self._sample = _jit(sample_tokens)

        def merge_prefill(old, new, act):
            return registry.merge_prefill(cfg, old, new, act)

        self._merge = _jit(merge_prefill, donate_argnums=(0,))
        self._paged_variants: dict = {}
        # windowed decode dispatches compile once per distinct window length
        # (decode_ticks plus any shorter drain tails) — see _fused_for
        self._fused_variants: dict = {}
        # speculative verify/commit dispatches, one pair per draft_k
        self._spec_variants: dict = {}
        self._commit_variants: dict = {}
        if mesh is None:
            self._prefill = _jit(prefill_step)
            if kv_layout == "paged":
                self._prefill_paged = _jit(
                    make_paged_prefill(cfg_l, policy, kv_quant=kv_quant),
                    static_argnames=("prefix_blocks",), donate_argnums=(5,))
            elif self.prefill_chunk is not None:
                self._prefill_chunked = _jit(
                    make_chunked_prefill(cfg_l, policy, kv_quant=kv_quant),
                    donate_argnums=(4,))
        else:
            # the same jitted steps, run per-shard under shard_map: every
            # in/out leaf carries an explicit PartitionSpec, and the body is
            # wrapped in a serve shard scope so the KV quantiser hashes
            # global element indices and attention heads all-gather before
            # the replicated W_O (the bitwise-parity contract, DESIGN.md §9)
            P = jax.sharding.PartitionSpec
            row, tok2, sc = P("data"), P("data", None), P()
            self._pspec = dist_sharding.serve_param_specs(params, cfg, mesh)
            self._cspec = dist_sharding.cache_specs(self.cache, cfg, mesh)
            # the ring prefill's output cache mirrors the ring engine cache;
            # the paged engine prefills through _paged_prefill_call instead
            self._prefill = (_jit(self._mesh_wrap(
                prefill_step,
                (self._pspec, tok2, row, row, sc),
                (tok2, self._cspec))) if kv_layout == "ring" else None)
            # fused decode: the (n_ticks, B) token matrix shards its slot
            # axis (axis 1) on 'data'; everything per-slot rides 'data' rows
            self._in_specs_fused = (self._pspec, row, self._cspec, row, sc,
                                    row, row, row, row, row, row, tok2)
            self._out_specs_fused = (P(None, "data"), row, row, self._cspec)
            # speculative verify: drafts/sampled (B, K) shard rows on 'data'
            self._in_specs_spec = (self._pspec, tok2, self._cspec, row, sc,
                                   row, row, row, row, row, row)
            self._out_specs_spec = (tok2, self._cspec)
            self._in_specs_commit = (self._cspec, row, row)
            if kv_layout == "paged":
                self._in_specs_paged = (self._pspec, tok2, row, row, tok2,
                                        self._cspec, row, sc)
                self._out_specs_paged = (tok2, self._cspec)
            elif self.prefill_chunk is not None:
                self._prefill_chunked = _jit(self._mesh_wrap(
                    make_chunked_prefill(cfg_l, policy, kv_quant=kv_quant),
                    (self._pspec, tok2, row, row, self._cspec, row, sc),
                    (tok2, self._cspec)), donate_argnums=(4,))

        self.scheduler = (Scheduler(scheduler) if isinstance(scheduler, str)
                          else scheduler)
        self.slots: List[Optional[Request]] = [None] * batch
        self.finished: List[Request] = []
        self.tick = 0
        # per-slot state: host mirrors for bookkeeping, plus device-resident
        # copies refreshed only when slot membership changes (admission);
        # steady-state decode ticks advance the device copies in place
        self._last_token = np.zeros((batch,), np.int32)
        self._slot_pos = np.zeros((batch,), np.int64)
        self._temps = np.zeros((batch,), np.float32)
        self._topks = np.zeros((batch,), np.int32)
        self._seeds = np.zeros((batch,), np.int32)
        self._offsets = np.zeros((batch,), np.int32)
        self._counters = np.zeros((batch,), np.int32)
        self._dev = {}
        self._dev_dirty = True
        # per-window paged write budget: slot → positions covered by already-
        # allocated blocks (set by _pre_decode_paged, read by _decode_tick)
        self._paged_cap: dict = {}
        self.stats = {"prefill_s": 0.0, "prefill_tokens": 0, "prefill_calls": 0,
                      "decode_s": 0.0, "decode_tokens": 0, "decode_calls": 0,
                      "prefix_hit_tokens": 0, "preemptions": 0}
        # observability surface (DESIGN.md §10): host-side counters, per-tick
        # gauges and TTFT/ITL histograms behind a buffered crash-isolated
        # sink.  Accepts a Metrics instance, a sink spec ('stdout',
        # 'jsonl:<path>', a sink object) or None (collect, don't stream).
        self.metrics = (metrics if isinstance(metrics, Metrics)
                        else Metrics(sink=metrics))
        # per-request tracing (DESIGN.md §13): span timelines + latency
        # attribution, host-timestamped only where the engine already syncs
        # — zero extra device dispatches, off by default (the engine
        # phases' profiler annotations, ``Tracer.span``, stay on).
        # Accepts a Tracer, a spec string ('mem', 'perfetto:<path>',
        # 'jsonl:<path>', comma-combinable), a sink object, or None (off).
        self.trace = Tracer.from_spec(trace)
        if self.trace.enabled:
            # queue/block provenance rides the tracer's event feed; the
            # hooks stay None (and cost nothing) on an untraced engine
            self.scheduler.on_event = self.trace.event
            for pool in self.pools:
                pool.on_event = self.trace.event

    # ------------------------------------------------------------- mesh glue

    def _mesh_wrap(self, fn, in_specs, out_specs):
        """Run ``fn`` per-shard under ``shard_map`` on the engine mesh, with
        the serve shard scope installed so model code maps its local batch
        rows / KV heads back to global coordinates (DESIGN.md §9).  The
        wrapper carries ``fn``'s name, so the program compiles under the
        same module name (``jit_fused_decode``, ...) as on one device."""
        nkv_local = self._cfg_local.n_kv_heads
        heads_sharded = self.heads_sharded

        def body(*args):
            head0 = (jax.lax.axis_index("model") * nkv_local
                     if heads_sharded else 0)
            with dist_ctx.serve_shard_scope(head0=head0,
                                            heads_sharded=heads_sharded):
                return fn(*args)

        body.__name__ = body.__qualname__ = fn.__name__
        return jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _paged_prefill_call(self, *args, prefix_blocks: int):
        """Dispatch the paged prefill: the single-device engine keeps one
        jitted fn with a static ``prefix_blocks``; the mesh engine builds
        (at most two — 0 and nbmax) shard_map variants instead, since
        shard_map bodies take positional args only."""
        if self.mesh is None:
            return self._prefill_paged(*args, prefix_blocks=prefix_blocks)
        fn = self._paged_variants.get(prefix_blocks)
        if fn is None:
            base = make_paged_prefill(self._cfg_local, self.policy,
                                      kv_quant=self.kv_quant)

            def paged_prefill(*a):
                return base(*a, prefix_blocks=prefix_blocks)

            fn = _jit(self._mesh_wrap(paged_prefill, self._in_specs_paged,
                                      self._out_specs_paged),
                      donate_argnums=(5,))
            self._paged_variants[prefix_blocks] = fn
        return fn(*args)

    def _fused_for(self, n: int):
        """The windowed decode dispatch for an ``n``-tick window, compiled on
        first use and cached — steady state uses ``decode_ticks`` only, so
        this compiles once (plus once per distinct stop-set bucket width via
        the (B, W) ``stops`` argument shape)."""
        fn = self._fused_variants.get(n)
        if fn is None:
            base = make_fused_decode(self._cfg_local, self.policy, n_ticks=n)
            if self.mesh is None:
                fn = _jit(base, donate_argnums=(2,))
            else:
                fn = _jit(self._mesh_wrap(base, self._in_specs_fused,
                                             self._out_specs_fused),
                             donate_argnums=(2,))
            self._fused_variants[n] = fn
        return fn

    def _spec_for(self, k: int):
        """The speculative verify dispatch for a ``k``-row window, compiled
        on first use (steady state uses ``draft_k`` only)."""
        fn = self._spec_variants.get(k)
        if fn is None:
            base = make_spec_verify(self._cfg_local, self.policy, draft_k=k)
            if self.mesh is None:
                fn = _jit(base, donate_argnums=(2,))
            else:
                fn = _jit(self._mesh_wrap(base, self._in_specs_spec,
                                             self._out_specs_spec),
                             donate_argnums=(2,))
            self._spec_variants[k] = fn
        return fn

    def _spec_commit_for(self, k: int):
        """The bulk-commit + rejected-suffix-scrub dispatch for ``k``-row
        windows: ``fn(cache, new_pos, written) -> cache`` (cache donated)."""
        fn = self._commit_variants.get(k)
        if fn is None:
            def spec_commit(cache, new_pos, written):
                return registry.spec_commit(cache, new_pos, written,
                                            draft_k=k)

            if self.mesh is None:
                fn = _jit(spec_commit, donate_argnums=(0,))
            else:
                fn = _jit(self._mesh_wrap(spec_commit, self._in_specs_commit,
                                          self._cspec),
                          donate_argnums=(0,))
            self._commit_variants[k] = fn
        return fn

    # ------------------------------------------------------ pool aggregates

    @property
    def pool(self) -> Optional[KVPool]:
        """The shard-local block pool (data shard 0) — the *whole* pool on a
        single-shard engine, which is what pre-mesh callers expect; use
        :attr:`pools` / :meth:`pool_stats` for per-shard views under a mesh
        (DESIGN.md §9)."""
        return self.pools[0] if self.pools else None

    def pool_stats(self) -> dict:
        """Allocator stats summed across the per-data-shard pools, plus the
        aggregate ``live``/``cached`` block counts."""
        agg = {"live": 0, "cached": 0}
        for p in self.pools:
            for k, v in p.stats.items():
                agg[k] = agg.get(k, 0) + v
            agg["live"] += p.live_blocks
            agg["cached"] += p.cached_blocks
        return agg

    def _slot_shard(self, i: int) -> int:
        return i // (self.batch // self.dp)

    def _pool_of(self, rid: int) -> KVPool:
        return self.pools[self._rid_shard[rid]]

    # ------------------------------------------------------------------ API

    def reset_stats(self):
        """Zero the throughput counters *and* the metrics surface
        (benchmarks call this after a warm-up wave so compile time stays
        out of the measured rates and histograms)."""
        self.stats = {k: type(v)() for k, v in self.stats.items()}
        self.metrics.reset()

    def submit(self, req: Request):
        """Enqueue a request, applying overload admission control
        (DESIGN.md §12) when ``queue_cap`` is set: a full queue either
        sheds the newcomer ('reject-new') or evicts the queued request
        with the lowest priority — latest arrival among ties — when the
        newcomer outranks it ('evict-lowest-priority').  Preempted
        requests re-enter through the scheduler's ``requeue`` and are
        never shed: they hold pool blocks and their place in line."""
        req.state = "queued"
        if req.t_submit is None:
            req.t_submit = time.time()
        self.trace.begin(req.rid, req.t_submit, priority=req.priority)
        if self.queue_cap is not None and \
                len(self.scheduler) >= self.queue_cap:
            victim = req
            if self.shed_policy == "evict-lowest-priority":
                lowest = min(self.scheduler.queued(),
                             key=lambda r: (r.priority, -r._arrival))
                if lowest.priority < req.priority:
                    self.scheduler.pop(lowest)
                    victim = lowest
            self._finish_queued(victim, "shed")
            if victim is req:
                return
        self.scheduler.submit(req)

    def explain(self, rid: int) -> dict:
        """Latency-attribution report for a traced request (DESIGN.md §13):
        wall time decomposed into queue / prefill / decode / preempt_stall /
        degraded / recovery shares that sum to 100%, with the dominant term
        named.  Requires the engine to have been constructed with
        ``trace=...``; raises ``KeyError`` for an unknown rid."""
        if not self.trace.enabled:
            raise RuntimeError("tracing is disabled; construct the Engine "
                               "with trace='mem' (or a sink spec) to explain "
                               "requests")
        return self.trace.explain(rid, now=self._now())

    def step(self) -> List[Request]:
        """One engine window: expire deadlines, admit, run one batched
        prefill wave, decode every active slot, observe the window wall
        time, persist a snapshot.  Returns the requests still active after
        the window.  The five ``injector`` crash points fire in this order
        (keyed on the tick at window start); a crashed engine's host state
        is mid-mutation — discard it and restore a fresh engine from the
        snapshot (``run_serve_with_restarts``).

        Each phase runs inside a fixed-name ``Tracer.span`` under
        ``engine.step`` (DESIGN.md §13): ``engine.admit``, then
        ``engine.prefill.{prep,dispatch,wait,drain}``, the decode window's
        ``engine.decode.*`` (or ``engine.spec.*``) and
        ``engine.bookkeeping``; only the ``*.wait`` spans block on the
        device."""
        span = self.trace.span
        active = sum(1 for s in self.slots if s is not None)
        with span("engine.step", tick=self.tick, active=active) as whole:
            self._step_tick = self.tick
            with span("engine.admit"):
                self._maybe_fail("pre_admit")
                self._expire_deadlines()
                self._update_pressure()
                if self.kv_layout == "paged":
                    self._admit_paged()
                else:
                    self._admit_ring()
            self._prefill_wave()
            if any(s is not None for s in self.slots):
                if self.spec_decode:
                    self._spec_decode_tick()
                else:
                    self._decode_tick()
            with span("engine.bookkeeping"):
                self._observe_window(time.time() - whole.t0)
                self._maybe_fail("sink_write")
                self._record_tick_metrics()
                self._maybe_fail("post_drain")
                self._maybe_snapshot()
        return [s for s in self.slots if s is not None]

    def run(self, ticks: int) -> List[Request]:
        """Drive :meth:`step` until the queue and slots drain (or ``ticks``
        elapse); returns every request finished so far."""
        for _ in range(ticks):
            self.step()
            if not len(self.scheduler) and all(s is None for s in self.slots):
                break
        self.metrics.flush()          # drain the tail of the gauge buffer
        self.trace.flush()
        if self.snapshot_path is not None:
            self.write_snapshot(self.snapshot_path)
        return self.finished

    # ----------------------------------------- fault tolerance (DESIGN.md §12)

    def _maybe_fail(self, phase: str):
        """One injector crash point, keyed on the tick at window start so a
        chaos test can name any phase of a specific window."""
        if self.injector is not None:
            self.injector.maybe_fail(self._step_tick, phase)

    def _finish_queued(self, req: Request, reason: str):
        """Retire a request that never reaches a slot this time (shed at
        submission, or expired while queued).  A preempted block-holder
        releases its blocks — expiry must not leak pool capacity."""
        if self.pools and req.rid in self._rid_shard:
            self._pool_of(req.rid).release(req.rid)
            self._rid_shard.pop(req.rid, None)
        req._resume = None
        req.done, req.finish_reason, req.state = True, reason, "done"
        self.finished.append(req)
        self.metrics.inc("finished_requests")
        self.metrics.inc(f"finish_{reason}")
        self.trace.finish(req.rid, self._now(), reason)

    def _expire_deadlines(self):
        """Expire overdue requests, once per window drain, *before*
        admission — a pure host-side scan over the queue and the slots
        (zero device dispatches; a cancelled running slot reuses the
        normal finish path, whose block release the engine already pays
        on every finish).  Queued requests expire on their own
        ``deadline_s`` or the engine-wide ``queue_ttl_s``; running ones
        only on their ``deadline_s`` (TTL is a queue-staleness bound, not
        an execution cap)."""
        ttl = self.queue_ttl_s
        now = self._now()

        def age(r):
            return now - (r.t_submit if r.t_submit is not None else now)

        for req in self.scheduler.queued():
            if (req.deadline_s is not None and age(req) > req.deadline_s) or \
                    (ttl is not None and age(req) > ttl):
                self.scheduler.pop(req)
                self._finish_queued(req, "deadline")
        for i, req in enumerate(self.slots):
            if req is not None and req.deadline_s is not None \
                    and age(req) > req.deadline_s:
                self._finish(i, req, "deadline")

    def _window_ticks(self) -> int:
        """The decode window length this step: ``decode_ticks``, stepped
        down to 1 while degraded (shorter windows = more frequent
        admission/preemption decisions under block scarcity).  Safe to vary
        freely — window length is bitwise stream-preserving (§11)."""
        return 1 if self._degraded else self.decode_ticks

    def _update_pressure(self):
        """Graceful degradation under pool pressure (DESIGN.md §12), with
        hysteresis so the engine does not flap at the watermark: when live
        blocks cross ``degrade_high`` of capacity, decode windows drop to
        single ticks and prefix-cache *insertion* pauses (finished blocks
        return to the free list instead of lingering as cached copies —
        sealing resumes where it left off once pressure clears below
        ``degrade_low``).  Both effects are stream-preserving: window
        length is bitwise-invariant (§11) and prefix hit vs cold is
        stream-pinned (§6), so degradation never changes emitted tokens."""
        if not self.pools:
            return
        share = sum(p.live_blocks for p in self.pools) / self.num_blocks
        if not self._degraded and share >= self.degrade_high:
            self._degraded = True
            self.metrics.inc("degrade_events")
            now = self._now()
            self.trace.event("degraded", t=now, tick=self.tick,
                             live_share=share)
            self.trace.set_degraded(True, now)
        elif self._degraded and share <= self.degrade_low:
            self._degraded = False
            now = self._now()
            self.trace.event("restored", t=now, tick=self.tick,
                             live_share=share)
            self.trace.set_degraded(False, now)

    def _observe_window(self, seconds: float):
        """Feed the straggler watchdog one window wall time; flagged
        windows bump the ``slow_windows`` counter and log an event on the
        tracer's feed (DESIGN.md §13 — lifecycle events unified there)."""
        self._last_window_s = seconds
        if self.watchdog is not None and \
                self.watchdog.observe(self._step_tick, seconds):
            self.metrics.inc("slow_windows")
            self.trace.event("slow_window", tick=self._step_tick,
                             window_s=seconds)

    def _maybe_snapshot(self):
        if self.snapshot_path is None:
            return
        self._steps_since_snap += 1
        if self._steps_since_snap >= self.snapshot_every:
            self._steps_since_snap = 0
            self.write_snapshot(self.snapshot_path)

    def write_snapshot(self, path: str):
        """Atomically persist :meth:`snapshot` as JSON (tmp + ``os.replace``
        — a crash mid-write can never corrupt the previous snapshot)."""
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(self.snapshot(), fh)
        os.replace(tmp, path)

    # ------------------------------------------------------------ internals

    def _record_tick_metrics(self):
        """One per-tick gauge snapshot (DESIGN.md §10).  Every value is a
        host-side int/float the engine already tracks — scheduler depth,
        slot occupancy, the cumulative ``stats`` counters and the pool
        allocator's host bookkeeping — so this adds **no device dispatch**
        (and no device→host sync) to the tick."""
        active = sum(1 for s in self.slots if s is not None)
        gauges = dict(
            queue_depth=len(self.scheduler),
            active_slots=active,
            batch_occupancy=active / self.batch,
            finished_total=len(self.finished),
            prefill_tokens=self.stats["prefill_tokens"],
            decode_tokens=self.stats["decode_tokens"],
            prefix_hit_tokens=self.stats["prefix_hit_tokens"],
            preemptions=self.stats["preemptions"],
            window_s=self._last_window_s,
            degraded=int(self._degraded),
        )
        if self.pools:
            ps = self.pool_stats()
            gauges.update(
                live_blocks=ps["live"], cached_blocks=ps["cached"],
                free_blocks=sum(p.free_blocks for p in self.pools))
        self.metrics.tick(**gauges)
        self.trace.counters(t=self._now(), **gauges)

    def _refresh_device_state(self):
        """Re-upload the per-slot sampling state and last tokens if any slot
        changed since the previous tick (admission marks the state dirty);
        in steady state this is a no-op and decode ticks touch the host only
        to read the sampled tokens back."""
        if self._dev_dirty:
            self._dev = {
                "temps": jnp.asarray(self._temps),
                "topks": jnp.asarray(self._topks),
                "seeds": jnp.asarray(self._seeds),
                "offsets": jnp.asarray(self._offsets),
                "counters": jnp.asarray(self._counters),
                "last_token": jnp.asarray(self._last_token),
            }
            self._dev_dirty = False

    def _admit_ring(self):
        """Admit queued requests into free ring slots in state
        ``prefilling``; :meth:`_prefill_wave` then writes their prompts —
        whole, or in ``prefill_chunk``-token chunks, one chunk wave per
        engine step (Sarathi-style piggyback prefill, DESIGN.md §11), so a
        long prompt never stalls running decodes for its full length."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free:
            return
        now = time.time()
        for req in self.scheduler.admit(len(free)):
            if len(req.prompt) > self.max_len:
                self._finish_queued(req, "rejected")
                continue
            i = free.pop(0)
            self.slots[i] = req
            req.state, req.t_admit = "prefilling", now
            self.trace.phase(req.rid, "prefill", now, slot=i)
            req._pf_pos = 0
            self._set_slot_sampling(i, req)
            self._slot_pos[i] = 0
            self._dev_dirty = True

    def _prefill_wave(self):
        """One batched prefill wave over every ``prefilling`` slot
        (DESIGN.md §6, §11).  Each slot writes its next ``prefill_chunk``
        tokens from ``_pf_pos`` (all of them with ``prefill_chunk=None``);
        a slot whose last chunk lands samples its first token from the
        wave's logits and turns ``active``.  The ring writes a whole prompt
        through ``prefill_step`` and merges it into the live cache, and a
        chunk through the chunked prefill, which joins the slot's ring
        history inside attention; the paged prefill writes into the pool
        blocks named by the block table.  Paged chunks are block-aligned,
        so every continuation chunk rides the prefix-hit path — earlier
        chunks' K/V is gathered from the slot's own blocks.  Because the
        dither KV codes key on absolute position (``starts + t``) and the
        first token on the prefill-final logits, the chunked stream is the
        whole-prompt stream (tests/test_overlap.py)."""
        waving = [(i, s) for i, s in enumerate(self.slots)
                  if s is not None and s.state == "prefilling"]
        if not waving:
            return
        span = self.trace.span
        paged = self.kv_layout == "paged"
        chunk = self.prefill_chunk or (self.max_len + 1)
        with span("engine.prefill.prep"):
            lens = np.zeros((self.batch,), np.int32)
            starts = np.zeros((self.batch,), np.int32)
            pieces = {}
            for i, req in waving:
                pf = req._pf_pos
                pieces[i] = self._tokens_written(req)[pf:pf + chunk]
                lens[i] = len(pieces[i])
                starts[i] = pf
            toks = np.zeros((self.batch, _bucket(int(lens.max()))), np.int32)
            for i, p in pieces.items():
                toks[i, : len(p)] = p
            self._dev_dirty = True
            self._refresh_device_state()
            toks_d, lens_d = jnp.asarray(toks), jnp.asarray(lens)
            # what the layout's prefill takes besides tokens and lengths
            if paged:
                layout_args = (jnp.asarray(starts), jnp.asarray(self._bt))
                self._bt_dirty = False
            elif self.prefill_chunk is not None:
                layout_args = (jnp.asarray(starts),)
            else:
                layout_args = (jnp.asarray(lens > 0),)      # merge mask
        dev = self._dev
        with span("engine.prefill.dispatch") as dispatch:
            if paged:
                logits, self.cache = self._paged_prefill_call(
                    self.params, toks_d, lens_d, *layout_args, self.cache,
                    dev["offsets"], self.tick,
                    prefix_blocks=self.nbmax if starts.any() else 0)
            elif self.prefill_chunk is not None:
                logits, self.cache = self._prefill_chunked(
                    self.params, toks_d, lens_d, *layout_args, self.cache,
                    dev["offsets"], self.tick)
            else:
                logits, fresh = self._prefill(
                    self.params, toks_d, lens_d, dev["offsets"], self.tick)
                self.cache = self._merge(self.cache, fresh, *layout_args)
            first_dev = self._sample(logits, dev["temps"], dev["topks"],
                                     dev["seeds"], dev["counters"])
        with span("engine.prefill.wait") as wait:
            first = np.asarray(first_dev)
        with span("engine.prefill.drain"):
            self.stats["prefill_s"] += wait.t1 - dispatch.t0
            self.stats["prefill_tokens"] += int(lens.sum())
            self.stats["prefill_calls"] += 1
            self.trace.wave(
                "prefill_wave", dispatch.t0, wait.t1,
                [(req.rid, f"prefill[{int(starts[i]) // chunk}]",
                  {"slot": i, "tokens": int(lens[i])}) for i, req in waving],
                tick=self._step_tick)
            # the prefill dispatch is ordered before any later gather, so
            # the chunk's full blocks are now safely publishable for hits
            now = time.time()
            for i, req in waving:
                req._pf_pos += len(pieces[i])
                self._slot_pos[i] = req._pf_pos
                if paged:
                    self._seal_full_blocks(req, req._pf_pos)
                if req._pf_pos >= len(self._tokens_written(req)):
                    req.state = "active"
                    self._emit(i, req, int(first[i]), now)
            # _emit advanced host counters / last tokens for these slots;
            # re-sync the device copies before the decode window reads them
            self._dev_dirty = True

    # ----------------------------------------------------- paged internals

    def _tokens_written(self, req: Request) -> List[int]:
        """Every token with (or about to get) a cache position: the prompt
        (BOS-substituted if empty) followed by the generated stream —
        position p holds ``seq[p]``, which is what block sealing and
        resume-by-reprefill both rely on."""
        return (list(req.prompt) or [1]) + list(req.out)

    def _set_bt_row(self, i: int, table: List[int]):
        self._bt[i, :] = self._trash
        if table:
            self._bt[i, : len(table)] = table
        self._bt_dirty = True

    def _sync_block_tables(self):
        if self._bt_dirty:
            self.cache["block_tables"] = jnp.asarray(self._bt)
            self._bt_dirty = False

    def _set_slot_sampling(self, i: int, req: Request):
        sp = req.sampling
        self._temps[i] = sp.temperature
        self._topks[i] = sp.top_k
        self._seeds[i] = sp.seed
        self._offsets[i] = sp.counter_offset
        self._counters[i] = sp.counter_offset + len(req.out)

    def _release_slot_blocks(self, i: int, req: Request):
        self._pool_of(req.rid).release(req.rid)
        self._rid_shard.pop(req.rid, None)
        self._set_bt_row(i, [])
        self.cache["pos"] = self.cache["pos"].at[i].set(0)
        self._slot_pos[i] = 0

    def _preempt_requeue(self, i: int, req: Request):
        """Out-of-blocks preemption: freeze the slot's host state and send
        the request back through the scheduler *with its blocks intact* —
        re-admission resumes decode from the frozen position instead of
        re-prefilling (the PR-4 replacement for the ring engine's hard
        'preempted' finish)."""
        req._resume = {"pos": int(self._slot_pos[i]),
                       "last_token": int(self._last_token[i]),
                       "t": time.time(), "reprefill": False,
                       "prefilling": req.state == "prefilling"}
        self.trace.phase(
            req.rid, "preempt_stall", req._resume["t"], slot=i,
            blocks=len(self._pool_of(req.rid).table(req.rid)))
        req.state = "queued"
        self.slots[i] = None
        self._set_bt_row(i, [])
        self.cache["pos"] = self.cache["pos"].at[i].set(0)
        self.scheduler.requeue(req)
        self.stats["preemptions"] += 1

    def _release_for_reprefill(self, req: Request):
        """Deadlock breaker (last resort): a *queued* preempted request
        gives its blocks back to the pool; on re-admission it re-prefills
        its full history (prompt + generated so far).  Counters replay
        exactly — KV quantiser = absolute position + offset, sampling =
        offset + emitted count — so the first layer's int8 codes are
        bit-identical; deeper layers re-enter through the batched prefill
        and agree with the decode-written cache to rounding only (the same
        prefill≡decode divergence tests/test_serve.py has always pinned),
        so a greedy near-tie after resume may break differently.  The
        primary preemption path (blocks intact) has no such divergence."""
        self._pool_of(req.rid).forget(req.rid)
        self._rid_shard.pop(req.rid, None)
        req._sealed = 0
        if req._resume is None:
            req._resume = {"pos": 0, "last_token": 0, "t": time.time()}
        req._resume["reprefill"] = True
        self.trace.event("reprefill", rid=req.rid, t=self._now(),
                         pos=req._resume["pos"])
        # 'preemptions' counts preemption *events* — a requeue-with-blocks
        # and a later block reclamation are two events for one request
        self.stats["preemptions"] += 1

    def _resume_slot(self, i: int, req: Request):
        # invariant: slot i is on the data shard holding req's blocks
        # (admission only resumes onto the home shard, DESIGN.md §9)
        st = req._resume
        req._resume = None
        self.slots[i] = req
        # a request preempted mid-prefill rejoins the chunk waves where it
        # stopped (its _pf_pos and blocks survived the round trip)
        req.state = "prefilling" if st.get("prefilling") else "active"
        self.trace.phase(
            req.rid, "prefill" if st.get("prefilling") else "decode",
            self._now(), slot=i, resumed=1,
            shard=self._rid_shard[req.rid],
            blocks=len(self._pool_of(req.rid).table(req.rid)))
        self._set_slot_sampling(i, req)
        self._last_token[i] = st["last_token"]
        self._slot_pos[i] = st["pos"]
        self._set_bt_row(i, self._pool_of(req.rid).table(req.rid))
        self.cache["pos"] = self.cache["pos"].at[i].set(st["pos"])
        self._dev_dirty = True

    def _seal_full_blocks(self, req: Request, n_tokens: int):
        """Publish every full block below ``n_tokens`` into the prefix
        cache (chained-hash order).  Callers only invoke this after the
        device writes for those blocks were dispatched — a same-wave hit
        would race the scatter.  Paused while degraded (DESIGN.md §12):
        ``req._sealed`` does not advance, so sealing resumes from the same
        block once pressure clears."""
        if not self._prefix_enabled or self._degraded:
            return
        bs = self.block_size
        pool = self._pool_of(req.rid)
        seq = self._tokens_written(req)
        while req._sealed < n_tokens // bs:
            j = req._sealed
            pool.seal_block(req.rid, j, seq[j * bs:(j + 1) * bs])
            req._sealed += 1

    def _admit_paged(self):
        """Continuous-batching admission (DESIGN.md §6/§9): admit while a
        slot *and* that slot's data-shard pool allow — prefix-hit requests
        only need blocks (and prefill compute) for their unshared suffix;
        preempted requests resume in place *on their home shard* (their
        blocks live in that shard's pool).  New requests pick the shard
        with the longest cached prefix, then the most free blocks.
        Head-of-line order is preserved: the first request no eligible
        shard can serve stops admission (after the deadlock breaker below
        has had its chance).  Admitted requests wait in ``prefilling`` for
        :meth:`_prefill_wave`."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free:
            return
        bs = self.block_size
        free_by_shard: dict = {}
        for i in free:                       # slot order ⇒ shard-local order
            free_by_shard.setdefault(self._slot_shard(i), []).append(i)

        def take_slot(shard: int) -> int:
            slots_d = free_by_shard[shard]
            i = slots_d.pop(0)
            if not slots_d:
                del free_by_shard[shard]
            return i

        admitted = []                       # (slot, req, suffix, start)
        while free_by_shard:
            req = self.scheduler.peek()
            if req is None:
                break
            if req._resume is not None and not req._resume.get("reprefill"):
                # resume with blocks intact; may need one block to continue
                shard = self._rid_shard[req.rid]
                if shard not in free_by_shard:
                    break        # HOL: the home shard has no free slot yet
                pool = self.pools[shard]
                pos = req._resume["pos"]
                needs_block = (pos % bs == 0
                               and pos // bs >= len(pool.table(req.rid)))
                if needs_block and pool.free_blocks < 1:
                    if self._break_deadlock(req, 1, shard):
                        continue
                    break
                self.scheduler.pop(req)
                if needs_block:
                    phys = pool.append_block(req.rid)
                    assert phys is not None
                self._resume_slot(take_slot(shard), req)
                continue

            seq = self._tokens_written(req)      # prompt (+ out on reprefill)
            if len(req.prompt) > self.max_len or \
                    self.pools[0].blocks_needed(min(len(seq) + 1,
                                                    self.max_len)) \
                    > self._nb_local:
                self.scheduler.pop(req)
                # a reprefill-resumed request whose grown history no longer
                # fits was *served* up to the pool's capacity — that is a
                # 'length' stop, not a rejection of an unserved request
                self._finish_queued(req, "length" if req.out else "rejected")
                continue
            seed = req.sampling.counter_offset if self.kv_quant else 0
            # rank eligible shards: longest cached prefix first, then most
            # free blocks (ties keep the lowest shard — deterministic)
            ranked = sorted(
                ((pool.match_prefix(seq, seed), shard)
                 for shard, pool in ((s, self.pools[s])
                                     for s in free_by_shard)),
                key=lambda t: (-len(t[0][0]),
                               -self.pools[t[1]].free_blocks, t[1]))
            table = shard = None
            for (shared, chain), cand in ranked:
                table = self.pools[cand].allocate(req.rid, len(seq),
                                                  shared, chain)
                if table is not None:
                    shard = cand
                    break
            if table is None:
                (shared, _), cand = ranked[0]
                if self._break_deadlock(
                        req,
                        self.pools[cand].blocks_needed(len(seq))
                        - len(shared), cand):
                    continue
                break
            self._rid_shard[req.rid] = shard
            self.scheduler.pop(req)
            req._sealed = len(shared)
            req._resume = None
            start = len(shared) * bs
            i = take_slot(shard)
            admitted.append((i, req, start))

        # place admitted requests into their slots in ``prefilling`` state —
        # their full-history blocks are already allocated (held across
        # windows), so the chunk waves below only *write* into them
        now = time.time()
        for i, req, start in admitted:
            self.slots[i] = req
            req.state = "prefilling"
            req._pf_pos = start
            if req.t_admit is None:
                req.t_admit = now
            self.trace.phase(
                req.rid, "prefill", now, slot=i,
                shard=self._rid_shard[req.rid],
                blocks=len(self._pool_of(req.rid).table(req.rid)),
                prefix_tokens=start)
            self._set_slot_sampling(i, req)
            self._slot_pos[i] = start
            self._set_bt_row(i, self._pool_of(req.rid).table(req.rid))
            self.stats["prefix_hit_tokens"] += start

    def _break_deadlock(self, head: Request, blocks_short: int,
                        shard: int = 0) -> bool:
        """Admission stalled on the queue head with every slot of ``shard``
        idle: make room in that shard's pool by taking blocks back from
        *queued* preempted requests holding blocks there (youngest
        preemption first — the least progress to re-prefill), or, if the
        head itself holds everything, flip it to reprefill mode so its own
        blocks free up.  Returns True when the caller should retry
        admission."""
        per = self.batch // self.dp
        if any(self.slots[i] is not None
               for i in range(shard * per, (shard + 1) * per)):
            return False     # active slots will finish/preempt and free blocks
        pool = self.pools[shard]
        holders = [r for r in self.scheduler.queued()
                   if r is not head and r._resume is not None
                   and self._rid_shard.get(r.rid) == shard
                   and pool.table(r.rid)]
        holders.sort(key=lambda r: -r._resume["t"])
        made_room = False
        for victim in holders:
            self._release_for_reprefill(victim)
            made_room = True
            if pool.free_blocks >= blocks_short:
                return True
        if (not made_room and head._resume is not None
                and self._rid_shard.get(head.rid) == shard
                and pool.table(head.rid)):
            self._release_for_reprefill(head)
            return True
        return made_room

    def _pre_decode_paged(self, window: Optional[int] = None):
        """Before each decode window: the window writes this slot's next
        ``w = min(decode_ticks, budget)`` positions, so blocks covering
        ``[p, p + w)`` must exist *now* — the host cannot allocate
        mid-window.  Sealing of filled blocks happens here (their device
        writes are complete); when the pool can only cover part of the
        window, the slot's per-window budget is capped instead of finishing
        early (``_paged_cap``, read by _decode_tick) so tight pools behave
        exactly like decode_ticks=1; zero coverage preempts-and-requeues,
        and ``max_len`` is a hard stop ('length' — the paged pool has no
        ring wrap to overwrite).  Slots still mid-prefill are skipped: they
        decode nothing and their blocks are already allocated.  ``window``
        overrides the window length (the speculative tick passes
        ``draft_k``; rollback gives surplus coverage back, so partial
        acceptance never strands blocks)."""
        self._maybe_fail("pool_alloc")
        bs = self.block_size
        for i, req in [(i, s) for i, s in enumerate(self.slots)
                       if s is not None and s.state == "active"]:
            pool = self.pools[self._slot_shard(i)]
            p = int(self._slot_pos[i])
            if p >= self.max_len:
                self._finish(i, req, "length")
                continue
            self._seal_full_blocks(req, p)
            w = min(self._window_ticks() if window is None else window,
                    self.max_len - p,
                    max(1, req.effective_max_new() - len(req.out)))
            pre = len(pool.table(req.rid))
            need = (p + w - 1) // bs + 1
            while len(pool.table(req.rid)) < need:
                phys = pool.append_block(req.rid)
                if phys is None:
                    break
                self._bt[i, len(pool.table(req.rid)) - 1] = phys
                self._bt_dirty = True
            covered = len(pool.table(req.rid)) * bs - p
            if covered <= 0:
                if pool.holders == 1:
                    # nothing to evict or preempt — this shard's pool itself
                    # is the capacity limit for its lone request
                    self._finish(i, req, "length")
                else:
                    self._preempt_requeue(i, req)
                continue
            self._paged_cap[i] = covered
            if p // bs < pre:
                # the window starts inside a pre-existing block (partial
                # tail or a resume) — copy-on-write guard before writing
                self._ensure_tail_writable(i, req, p // bs)

    def _ensure_tail_writable(self, i: int, req: Request, logical: int):
        """Copy-on-write guard before this tick's decode write: the tail
        block is uniquely owned by construction (only full blocks are ever
        sealed/shared), so this is normally a refcount check and nothing
        more — but if a future sharing path ever hands out a partial
        block, the write copies it private instead of corrupting every
        other holder.  Pool exhaustion during the copy preempts like any
        other allocation failure."""
        shard = self._slot_shard(i)
        old = int(self._bt[i, logical])
        try:
            phys, copied = self.pools[shard].ensure_writable(req.rid, logical)
        except MemoryError:
            self._preempt_requeue(i, req)
            return
        if copied:
            self._copy_pool_block(shard, old, int(phys))
            self._bt[i, logical] = phys
            self._bt_dirty = True

    def _copy_pool_block(self, shard: int, src: int, dst: int):
        """Duplicate one physical block's contents across every layer's
        pool arrays (stacked pattern entries carry a leading repeat axis).
        ``src``/``dst`` are shard-local ids; the device pool lays the
        shards' sub-pools back to back (DESIGN.md §9), so the global index
        offsets by shard·(blocks-per-shard + 1)."""
        off = shard * (self._nb_local + 1)
        src, dst = off + src, off + dst
        self.cache["layers"] = [
            jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), e)
            for e in self.cache["layers"]]
        self.cache["remainder"] = [
            jax.tree.map(lambda a: a.at[dst].set(a[src]), e)
            for e in self.cache["remainder"]]

    def _window_budgets(self, n: int):
        """The active slots and their per-slot window budgets (max_new /
        max_len / paged block coverage, at most ``n``), with the ``alive``
        mask the window's dispatch takes."""
        active = [(i, s) for i, s in enumerate(self.slots)
                  if s is not None and s.state == "active"]
        alive = np.zeros((self.batch,), bool)
        budgets = np.zeros((self.batch,), np.int32)
        for i, req in active:
            b = min(n, req.effective_max_new() - len(req.out),
                    self.max_len - int(self._slot_pos[i]))
            if self.kv_layout == "paged":
                b = min(b, self._paged_cap[i])
            alive[i] = True
            budgets[i] = b
        return active, alive, budgets

    @staticmethod
    def _stop_set(req: Request) -> set:
        ss = set(req.sampling.stop_set())
        if req.sampling.eos_id is not None:
            ss.add(req.sampling.eos_id)
        return ss

    def _decode_tick(self):
        """One decode *window*: ``decode_ticks`` fused scan ticks in a
        single device dispatch, then one host drain of the (n, B) token
        matrix (DESIGN.md §11).  Per-slot window budgets (max_new /
        max_len / paged block coverage) and stop sets ride down as device
        arrays so finish detection never syncs mid-window; the drain walks
        each slot's column up to its first stop hit and re-runs the exact
        per-token finish logic of the one-tick engine (``_emit``)."""
        span = self.trace.span
        n = self._window_ticks()
        with span("engine.decode.prep"):
            self._paged_cap = {}
            if self.kv_layout == "paged":
                self._pre_decode_paged()
                self._sync_block_tables()
            active, alive, budgets = self._window_budgets(n)
            if not active:
                return
            stop_sets = {i: self._stop_set(req) for i, req in active}
            # bucket the stop-set width so the (B, W) stops array compiles
            # per power-of-two width, not per distinct stop-set size
            wmax = max(1, max(len(ss) for ss in stop_sets.values()))
            W = 1
            while W < wmax:
                W *= 2
            stops = np.full((self.batch, W), -1, np.int32)  # -1 never sampled
            for i, ss in stop_sets.items():
                for j, t in enumerate(sorted(ss)):
                    stops[i, j] = t
            self._refresh_device_state()
            window_args = (jnp.asarray(alive), jnp.asarray(budgets),
                           jnp.asarray(stops))
        dev = self._dev
        with span("engine.decode.dispatch", n_ticks=n, W=W) as dispatch:
            toks_dev, last_dev, counters_dev, self.cache = self._fused_for(n)(
                self.params, dev["last_token"], self.cache, dev["offsets"],
                self.tick, dev["temps"], dev["topks"], dev["seeds"],
                dev["counters"], *window_args)
        with span("engine.decode.wait") as wait:
            toks = np.asarray(toks_dev)           # (n, B) — the window drain
        with span("engine.decode.drain"):
            # crash point between the device window and the host drain: the
            # window's tokens are lost with the process, never half-emitted
            self._maybe_fail("mid_window")
            # the fused window advanced counters and produced the next input
            # token on device — keep those copies resident (no re-upload next
            # window; dead rows froze, matching the host mirrors below)
            self._dev["counters"] = counters_dev
            self._dev["last_token"] = last_dev
            self.tick += n
            self.stats["decode_s"] += wait.t1 - dispatch.t0
            self.stats["decode_calls"] += 1
            now = time.time()
            kept = {}
            for i, req in active:
                col = toks[:, i]
                ss = stop_sets[i]
                m = int(budgets[i])               # tokens this slot really kept
                for j in range(m):
                    if int(col[j]) in ss:
                        m = j + 1
                        break
                kept[i] = m
                self._emit_window(i, req, col[:m], now)
            self.trace.wave(
                "decode_window", dispatch.t0, wait.t1,
                [(req.rid, f"decode[w{self._step_tick}]",
                  {"slot": i, "tokens": kept[i]}) for i, req in active],
                tick=self._step_tick, n_ticks=n)

    def _spec_decode_tick(self):
        """One speculative window (DESIGN.md §14): draft ``draft_k - 1``
        tokens per slot host-side, score all ``draft_k`` positions in one
        verify dispatch, then commit the longest prefix each slot's own
        sampler agrees with.  Acceptance is *exact token match* — row t's
        logits are bitwise the sequential decode logits whenever rows 1..t
        matched, and the sampler is stateless in (seed, counter) — so the
        emitted stream is bitwise the plain-decode stream for greedy and
        temperature alike; a window always commits at least row 0's sampled
        token (plain decode's tick), so wrong drafts cost latency, never
        progress.  Supersedes ``decode_ticks`` while spec_decode is on: the
        verify window *is* the engine window.  Rejected suffixes roll back
        in the same commit dispatch (scrub to never-written bytes); paged
        slots then return surplus draft-coverage blocks via
        ``KVPool.truncate``, leaving pool state as if never drafted."""
        span = self.trace.span
        K = self.draft_k
        with span("engine.decode.prep"):
            self._paged_cap = {}
            if self.kv_layout == "paged":
                self._pre_decode_paged(window=K)
                self._sync_block_tables()
            active, alive, budgets = self._window_budgets(K)
            if not active:
                return
            self._refresh_device_state()
            alive_d, budgets_d = jnp.asarray(alive), jnp.asarray(budgets)
        with span("engine.spec.draft") as draft:
            drafts = np.zeros((self.batch, K), np.int32)
            n_drafted = {}
            for i, req in active:
                drafts[i, 0] = self._last_token[i]
                prop = self.drafter.propose(list(req.prompt) + req.out, K - 1)
                nd = min(len(prop), K - 1)
                if nd:
                    drafts[i, 1:1 + nd] = prop[:nd]
                n_drafted[i] = nd
            drafts_d = jnp.asarray(drafts)
        dev = self._dev
        with span("engine.spec.verify.dispatch", n_ticks=K) as verify:
            toks_dev, self.cache = self._spec_for(K)(
                self.params, drafts_d, self.cache, dev["offsets"], self.tick,
                dev["temps"], dev["topks"], dev["seeds"], dev["counters"],
                alive_d, budgets_d)
        with span("engine.spec.verify.wait") as wait:
            toks = np.asarray(toks_dev)               # (B, K) sampled per row
        with span("engine.spec.accept") as accept_span:
            self._maybe_fail("mid_window")
            self.tick += 1
            self.stats["decode_s"] += wait.t1 - verify.t0
            self.stats["decode_calls"] += 1
            # host accept walk: row t committed iff its *input* draft matched
            # row t-1's sampled token (= what sequential decode would have
            # fed), cut at the first stop/EOS hit like the plain window drain
            accept = {}
            for i, req in active:
                b = int(budgets[i])
                m = 1
                while m < b and drafts[i, m] == toks[i, m - 1]:
                    m += 1
                ss = self._stop_set(req)
                for j in range(m):
                    if int(toks[i, j]) in ss:
                        m = j + 1
                        break
                accept[i] = m
        with span("engine.spec.commit") as commit:
            # bulk commit + rejected-suffix scrub in one dispatch, against
            # the *pre-truncation* block tables (the scrub needs the draft
            # mapping)
            new_pos = np.asarray(self._slot_pos, np.int32)
            for i, _ in active:
                new_pos[i] += accept[i]
            self.cache = self._spec_commit_for(K)(
                self.cache, jnp.asarray(new_pos), budgets_d)
            if self.kv_layout == "paged":
                bs = self.block_size
                # reverse slot order, truncate returning newest-first: the
                # appends to each pool's free list exactly mirror the pop
                # order of this window's draft-coverage allocation across
                # *all* slots, so the free list (order included) is restored
                # to its never-drafted state — the pool-state parity the
                # rollback tests pin (DESIGN.md §14)
                for i, req in reversed(active):
                    pool = self.pools[self._slot_shard(i)]
                    keep = max(1, -(-int(new_pos[i]) // bs))
                    have = len(pool.table(req.rid))
                    if have > keep:
                        pool.truncate(req.rid, keep)
                        self._bt[i, keep:have] = self._trash
                        self._bt_dirty = True
        with span("engine.decode.drain"):
            now = time.time()
            for i, req in active:
                m = accept[i]
                self._emit_window(i, req, toks[i, :m], now)
                self.metrics.inc("spec_draft_tokens", int(budgets[i]) - 1)
                self.metrics.inc("spec_accepted_tokens", m - 1)
                self.metrics.inc("spec_emitted_tokens", m)
            self.metrics.inc("spec_windows")
            # the device sampling counters ran ahead (all K rows drew); the
            # host mirrors advanced by the accept length in _emit — re-upload
            self._dev_dirty = True
            self.trace.wave(
                "spec_draft", draft.t0, draft.t1,
                [(req.rid, f"draft[w{self._step_tick}]",
                  {"slot": i, "drafted": n_drafted[i]}) for i, req in active],
                tick=self._step_tick)
            self.trace.wave(
                "spec_verify", verify.t0, wait.t1,
                [(req.rid, f"verify[w{self._step_tick}]",
                  {"slot": i, "k": K, "budget": int(budgets[i])})
                 for i, req in active],
                tick=self._step_tick, n_ticks=K)
            self.trace.wave(
                "spec_commit", accept_span.t0, commit.t1,
                [(req.rid, f"commit[w{self._step_tick}]",
                  {"slot": i, "accepted": accept[i]}) for i, req in active],
                tick=self._step_tick)

    def _emit_window(self, i: int, req: Request, toks, now: float):
        """Emit the ``m`` tokens a window kept for slot ``i``.  They arrived
        over one host drain interval, so each is stamped at an equal share
        of it (ITL = interval/m per token instead of one m-sized
        observation per drain)."""
        m = len(toks)
        t_prev = req.t_last if req.t_last is not None else now
        share = (now - t_prev) / m
        for j in range(m):
            self._slot_pos[i] += 1
            self._emit(i, req, int(toks[j]), t_prev + share * (j + 1))
        self.stats["decode_tokens"] += m

    def _emit(self, i: int, req: Request, tok: int, now: float):
        req.out.append(tok)
        if req.t_first is None:
            req.t_first = now
            self.trace.phase(req.rid, "decode", now, slot=i)
            if req.ttft is not None:
                self.metrics.observe_ttft(req.ttft)
        else:
            req.itl.append(now - req.t_last)
            self.metrics.observe_itl(now - req.t_last)
        req.t_last = now
        self._counters[i] += 1
        self._last_token[i] = tok
        if req.stream is not None:
            req.stream(req, tok)

        sp = req.sampling
        if sp.eos_id is not None and tok == sp.eos_id:
            self._finish(i, req, "eos")
        elif tok in sp.stop_set():
            self._finish(i, req, "stop")
        elif len(req.out) >= req.effective_max_new():
            self._finish(i, req, "length")
        elif self.kv_layout == "ring" and self._slot_pos[i] >= self.max_len:
            # the slot's ring cache is full: preempt so the next admission
            # wave can recycle it (the request keeps what it generated).
            # The paged engine has no ring wrap — it requeues-with-blocks on
            # pool pressure instead (_preempt_requeue) and treats max_len as
            # a hard 'length' stop in _pre_decode_paged.
            self._finish(i, req, "preempted")

    def _finish(self, i: int, req: Request, reason: str):
        req.done, req.finish_reason, req.state = True, reason, "done"
        self.finished.append(req)
        self.metrics.inc("finished_requests")
        self.metrics.inc(f"finish_{reason}")
        self.trace.finish(req.rid, req.t_last if req.t_last is not None
                          else self._now(), reason, slot=i)
        self.slots[i] = None
        if self.kv_layout == "paged":
            # seal what the prompt + generation filled (future prefix hits),
            # then drop the references — sealed blocks linger in the pool's
            # LRU prefix cache until allocation pressure evicts them
            self._seal_full_blocks(req, int(self._slot_pos[i]))
            self._release_slot_blocks(i, req)

    # --------------------------------- snapshot / restore (DESIGN.md §12)

    @staticmethod
    def _req_to_state(req: Request) -> dict:
        sp = req.sampling
        return {
            "rid": req.rid, "prompt": list(req.prompt),
            "out": list(req.out), "priority": req.priority,
            "max_new": req.max_new, "deadline_s": req.deadline_s,
            "done": req.done, "finish_reason": req.finish_reason,
            "state": req.state,
            "t_submit": req.t_submit, "t_admit": req.t_admit,
            "t_first": req.t_first, "t_last": req.t_last,
            "itl": list(req.itl),
            "arrival": getattr(req, "_arrival", None),
            "resume": req._resume, "sealed": req._sealed,
            "pf_pos": req._pf_pos,
            "sampling": {"temperature": sp.temperature, "top_k": sp.top_k,
                         "seed": sp.seed, "max_new": sp.max_new,
                         "eos_id": sp.eos_id,
                         "stop_ids": list(sp.stop_ids),
                         "counter_offset": sp.counter_offset},
        }

    @staticmethod
    def _req_from_state(st: dict,
                        streams: Optional[dict] = None) -> Request:
        sps = st["sampling"]
        req = Request(
            rid=st["rid"], prompt=list(st["prompt"]),
            sampling=SamplingParams(
                temperature=sps["temperature"], top_k=sps["top_k"],
                seed=sps["seed"], max_new=sps["max_new"],
                eos_id=sps["eos_id"], stop_ids=tuple(sps["stop_ids"]),
                counter_offset=sps["counter_offset"]),
            priority=st["priority"], max_new=st["max_new"],
            deadline_s=st["deadline_s"])
        req.out = list(st["out"])
        req.done, req.finish_reason = st["done"], st["finish_reason"]
        req.state = st["state"]
        req.t_submit, req.t_admit = st["t_submit"], st["t_admit"]
        req.t_first, req.t_last = st["t_first"], st["t_last"]
        req.itl = list(st["itl"])
        if st["arrival"] is not None:
            req._arrival = st["arrival"]
        req._resume = st["resume"]
        req._sealed, req._pf_pos = st["sealed"], st["pf_pos"]
        if streams is not None:
            req.stream = streams.get(req.rid)
        return req

    def snapshot(self) -> dict:
        """Serialize **all host-side truth** as one JSON-able dict
        (DESIGN.md §12): queue + per-slot request states (tokens emitted,
        ``_pf_pos`` prefill progress, preempt-resume records), pool block
        tables / refcounts / prefix index, the per-slot sampler mirrors
        (seed / offset / counter / last token), stats and metrics.  Device
        state is deliberately absent — it is a pure function of this host
        truth (dither KV codes are position-pure, the sampler is a
        stateless hash), which is exactly what :meth:`restore` exploits.
        Streaming callbacks cannot be serialized; ``restore(...,
        streams={rid: cb})`` re-attaches them."""
        return {
            "version": 1,
            "layout": {
                "kv_layout": self.kv_layout, "batch": self.batch,
                "max_len": self.max_len, "kv_quant": bool(self.kv_quant),
                "decode_ticks": self.decode_ticks,
                "prefill_chunk": self.prefill_chunk,
                "block_size": getattr(self, "block_size", None),
                "num_blocks": getattr(self, "num_blocks", None),
                "dp": self.dp, "tp": self.tp,
            },
            "tick": self.tick,
            "degraded": self._degraded,
            "scheduler": self.scheduler.snapshot(),
            "queue": [self._req_to_state(r) for r in self.scheduler.queued()],
            "slots": [None if s is None else self._req_to_state(s)
                      for s in self.slots],
            "finished": [self._req_to_state(r) for r in self.finished],
            "slot_state": {
                "last_token": [int(x) for x in self._last_token],
                "slot_pos": [int(x) for x in self._slot_pos],
                "temps": [float(x) for x in self._temps],
                "topks": [int(x) for x in self._topks],
                "seeds": [int(x) for x in self._seeds],
                "offsets": [int(x) for x in self._offsets],
                "counters": [int(x) for x in self._counters],
            },
            "pools": [p.snapshot() for p in self.pools],
            "rid_shard": {str(r): s for r, s in self._rid_shard.items()}
                         if self.pools else {},
            "stats": dict(self.stats),
            "metrics": self.metrics.snapshot(),
            "trace": self.trace.snapshot(self._now()),
        }

    def restore(self, snap: dict, streams: Optional[dict] = None) -> "Engine":
        """Adopt a :meth:`snapshot` and re-materialize the device KV so the
        engine continues **bitwise** where the snapshot was taken
        (policy-free / deterministic-scheme serving — the §12 contract,
        tests/test_serve_fault.py).  Works on a freshly constructed engine
        of the same layout *or* in place on a crashed one (every mutable
        field is overwritten; the device cache is rebuilt from scratch).

        Host truth is copied back verbatim; then :meth:`_replay_device_state`
        rebuilds each occupied slot's KV: the written prompt region through
        the engine's own prefill path (position-pure codes ⇒ the original
        prefill's bits) and the generated region by **teacher-forced decode
        replay** — each committed token re-runs the fused decode-step math
        with sampling discarded, so the decode-written KV is bit-identical
        too.  Unheld prefix-cache blocks are dropped (see
        ``KVPool.restore``); free capacity is unchanged."""
        lay = snap["layout"]
        mine = {
            "kv_layout": self.kv_layout, "batch": self.batch,
            "max_len": self.max_len, "kv_quant": bool(self.kv_quant),
            "decode_ticks": self.decode_ticks,
            "prefill_chunk": self.prefill_chunk,
            "block_size": getattr(self, "block_size", None),
            "num_blocks": getattr(self, "num_blocks", None),
            "dp": self.dp, "tp": self.tp,
        }
        diff = {k for k in mine if lay.get(k) != mine[k]}
        if diff:
            raise ValueError("snapshot layout does not match this engine: "
                             + ", ".join(f"{k}={lay.get(k)!r}!={mine[k]!r}"
                                         for k in sorted(diff)))
        self.tick = int(snap["tick"])
        self._degraded = bool(snap["degraded"])
        queue = [self._req_from_state(st, streams) for st in snap["queue"]]
        self.scheduler.restore(snap["scheduler"], queue)
        self.slots = [None if st is None else self._req_from_state(st, streams)
                      for st in snap["slots"]]
        self.finished = [self._req_from_state(st, streams)
                         for st in snap["finished"]]
        ss = snap["slot_state"]
        self._last_token = np.asarray(ss["last_token"], np.int32)
        self._slot_pos = np.asarray(ss["slot_pos"], np.int64)
        self._temps = np.asarray(ss["temps"], np.float32)
        self._topks = np.asarray(ss["topks"], np.int32)
        self._seeds = np.asarray(ss["seeds"], np.int32)
        self._offsets = np.asarray(ss["offsets"], np.int32)
        self._counters = np.asarray(ss["counters"], np.int32)
        self.stats = dict(snap["stats"])
        self.metrics.restore(snap["metrics"])
        # resume the request timelines (spans open at crash close with a
        # recovery marker; absent in pre-v9 snapshots → no-op)
        self.trace.restore(snap.get("trace"), t=self._now())
        self._paged_cap = {}
        self._steps_since_snap = 0
        if self.pools:
            for pool, ps in zip(self.pools, snap["pools"]):
                pool.restore(ps)
            self._rid_shard = {int(r): int(s)
                               for r, s in snap["rid_shard"].items()}
        # fresh device cache, then deterministic re-materialization of
        # every occupied slot's KV (and of the block-table mirror)
        if self.kv_layout == "paged":
            self.cache = registry.make_cache(
                self.params, self.cfg, self.batch, self.max_len,
                frames=self._frames, policy=self.policy,
                kv_quant=self.kv_quant, kv_layout="paged",
                block_size=self.block_size, num_blocks=self._nb_local,
                data_shards=self.dp, head_shards=self._head_shards)
            self._bt = np.full((self.batch, self.nbmax), self._trash,
                               np.int32)
            for i, req in enumerate(self.slots):
                if req is not None:
                    self._bt[i, :len(self._pool_of(req.rid).table(req.rid))] \
                        = self._pool_of(req.rid).table(req.rid)
            self._bt_dirty = True
        else:
            self.cache = registry.make_cache(
                self.params, self.cfg, self.batch, self.max_len,
                frames=self._frames, policy=self.policy,
                kv_quant=self.kv_quant, head_shards=self._head_shards)
        self._dev_dirty = True
        self._replay_device_state()
        self._dev_dirty = True
        self.metrics.inc("recoveries")
        return self

    def _replay_fn_for(self):
        """The jitted teacher-forced replay step (compiled on first use):
        the fused decode tick's model math with sampling stripped — the
        input token is *given*, not sampled — and the same inert-row
        freezing (position pinned, paged rows masked to the trash block)."""
        fn = getattr(self, "_replay_fn", None)
        if fn is not None:
            return fn
        cfg_l, policy = self._cfg_local, self.policy
        paged = self.kv_layout == "paged"

        def replay_step(params, token, cache, kv_offset, counter, alive):
            pos0 = cache["pos"]
            step_cache = cache
            if paged:
                leaf = (jax.tree.leaves(cache["layers"][0])[0]
                        if cache["layers"]
                        else jax.tree.leaves(cache["remainder"][0])[0])
                nbp = leaf.shape[1] if cache["layers"] else leaf.shape[0]
                step_cache = dict(cache)
                step_cache["block_tables"] = jnp.where(
                    alive[:, None], cache["block_tables"],
                    jnp.int32(nbp - 1))
            _, new_cache = registry.apply_decode(
                params, cfg_l, token, step_cache, policy=policy,
                counter=counter, kv_offset=kv_offset)
            new_cache["pos"] = jnp.where(alive, new_cache["pos"], pos0)
            if paged:
                new_cache["block_tables"] = cache["block_tables"]
            return new_cache

        if self.mesh is None:
            fn = _jit(replay_step, donate_argnums=(2,))
        else:
            P = jax.sharding.PartitionSpec
            row, sc = P("data"), P()
            fn = _jit(self._mesh_wrap(
                replay_step,
                (self._pspec, row, self._cspec, row, sc, row),
                self._cspec), donate_argnums=(2,))
        self._replay_fn = fn
        return fn

    def _replay_device_state(self):
        """Re-materialize the device KV for every occupied slot, bitwise.

        Two regions per slot, split at the prompt boundary: positions the
        original run wrote via *prefill* are re-prefilled through the same
        batched prefill path (dither codes are position-pure, so the bits
        match); positions written via *decode* are replayed teacher-forced
        — one decode step per committed token, inert rows frozen — which
        reproduces the decode-written bits exactly (re-prefilling them
        instead would only agree to rounding: the prefill≡decode
        first-layer-only divergence tests/test_serve.py pins).  Slots
        restored mid-reprefill (``_resume['reprefill']`` histories) treat
        prompt + generated as one prefill region, matching what the
        original engine would write on re-admission."""
        occupied = [(i, s) for i, s in enumerate(self.slots)
                    if s is not None]
        if self.kv_layout == "paged":
            self._sync_block_tables()
        if not occupied:
            return
        prompt_part, gen_tokens = {}, {}
        for i, req in occupied:
            written = int(self._slot_pos[i])
            seq = self._tokens_written(req)        # prompt (+ out: reprefill)
            prompt_len = len(list(req.prompt) or [1])
            if req.state == "prefilling":
                # mid-prefill: everything written so far came via prefill
                prompt_part[i], gen_tokens[i] = seq[:written], []
            else:
                p = min(written, prompt_len)
                prompt_part[i] = seq[:p]
                gen_tokens[i] = list(req.out)[:written - p]

        lens = np.zeros((self.batch,), np.int32)
        for i, _ in occupied:
            lens[i] = len(prompt_part[i])
        if lens.max() > 0:
            s_bucket = _bucket(int(lens.max()))
            toks = np.zeros((self.batch, s_bucket), np.int32)
            for i, _ in occupied:
                toks[i, :lens[i]] = prompt_part[i]
            self._dev_dirty = True
            self._refresh_device_state()
            if self.kv_layout == "paged":
                starts = np.zeros((self.batch,), np.int32)
                bt_dev = jnp.asarray(self._bt)
                self._bt_dirty = False
                _, self.cache = self._paged_prefill_call(
                    self.params, jnp.asarray(toks), jnp.asarray(lens),
                    jnp.asarray(starts), bt_dev, self.cache,
                    self._dev["offsets"], self.tick, prefix_blocks=0)
            else:
                _, pf_cache = self._prefill(
                    self.params, jnp.asarray(toks), jnp.asarray(lens),
                    self._dev["offsets"], self.tick)
                self.cache = self._merge(self.cache, pf_cache,
                                         jnp.asarray(lens > 0))

        depth = max(len(g) for g in gen_tokens.values()) \
            if gen_tokens else 0
        if depth:
            replay = self._replay_fn_for()
            for t in range(depth):
                token = np.zeros((self.batch,), np.int32)
                alive = np.zeros((self.batch,), bool)
                for i, _ in occupied:
                    g = gen_tokens[i]
                    if t < len(g):
                        token[i], alive[i] = g[t], True
                self.cache = replay(
                    self.params, jnp.asarray(token), self.cache,
                    jnp.asarray(self._offsets), self.tick,
                    jnp.asarray(alive))
