"""PartitionSpec rules for param / cache / batch pytrees.

Megatron-style layout on a ('data', 'model') mesh (optionally with a leading
'pod' DP axis):

* attention — QKV column-parallel, O row-parallel, keyed on *head counts*:
  ``wq``/``wo`` shard only when ``n_heads % tp == 0`` and ``wk``/``wv`` only
  when ``n_kv_heads % tp == 0`` (GQA head counts often don't divide the TP
  axis; the attention layer then falls back to sequence parallelism —
  ``ctx.seq_shard_attention``).
* MLP / MoE experts — up/gate column-parallel (last dim), down row-parallel
  (second-to-last dim).
* embeddings — vocab-parallel (the vocab dim is padded to the TP axis by
  ``ModelConfig.vocab_padded``).
* everything else (norms, biases on d_model, routers, SSM scan params) —
  replicated: small, or accuracy-critical (DESIGN.md §5).

Every emitted entry is divisibility-guarded against the concrete mesh, so
any (arch × mesh) combination yields a legal spec tree: a dim that does not
divide simply stays replicated.  Specs are emitted at the leaf's full rank
(explicit ``None`` per dim) and mirror the param tree structurally.
"""

from __future__ import annotations

import math
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["param_specs", "cache_specs", "batch_specs", "data_axes",
           "serve_param_specs", "serve_heads_shardable"]

_DP_AXIS_NAMES = ("pod", "data")

# leaf-name → (which dim shards on 'model' counted from the END, head-count
# attribute guarding it or None for plain dim divisibility)
_LAST, _SECOND_LAST = 1, 2
_TP_RULES = {
    # attention projections (head-count guarded)
    "wq": (_LAST, "n_heads"),
    "bq": (_LAST, "n_heads"),
    "wk": (_LAST, "n_kv_heads"),
    "wv": (_LAST, "n_kv_heads"),
    "bk": (_LAST, "n_kv_heads"),
    "bv": (_LAST, "n_kv_heads"),
    "wo": (_SECOND_LAST, "n_heads"),
    # MLP / MoE expert FFNs: column-parallel up/gate, row-parallel down
    "wg": (_LAST, None),
    "wu": (_LAST, None),
    "bu": (_LAST, None),
    "wd": (_SECOND_LAST, None),
    # SSM fused input projection is column-parallel; output row-parallel
    "in_proj": (_LAST, None),
    "out_proj": (_SECOND_LAST, None),
    # vocab-parallel embedding / head: embed is (vocab, d), head is (d, vocab)
    "embed": (_SECOND_LAST, None),
    "lm_head": (_LAST, None),
}


def _mesh_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def data_axes(mesh):
    """The DP spec entry for this mesh: ('pod', 'data'), 'data', or None."""
    present = tuple(a for a in _DP_AXIS_NAMES if a in _mesh_sizes(mesh))
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def _validated(spec: P, shape, mesh) -> P:
    """Clamp a spec to a concrete leaf shape on a concrete mesh: entries past
    the rank are dropped; absent axes and non-dividing sizes become None."""
    sizes = _mesh_sizes(mesh)
    out = []
    for i, entry in enumerate(tuple(spec)[: len(shape)]):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        if any(a not in sizes for a in axes):
            out.append(None)
            continue
        size = math.prod(int(sizes[a]) for a in axes)
        out.append(entry if size > 1 and shape[i] % size == 0 else None)
    return P(*out)


def _leaf_name(path) -> str:
    for key in reversed(path):
        if isinstance(key, jax.tree_util.DictKey):
            return str(key.key)
    return ""


def _replicated(ndim: int) -> P:
    return P(*((None,) * ndim))


def param_specs(params: Any, cfg, mesh) -> Any:
    """Spec tree mirroring ``params`` (leaves may be arrays or ShapeDtypeStructs)."""
    tp = int(_mesh_sizes(mesh).get("model", 1))

    def rule(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        name = _leaf_name(path)
        if name in _TP_RULES:
            from_end, head_attr = _TP_RULES[name]
            if from_end <= len(shape):
                dim = len(shape) - from_end
                guard = (getattr(cfg, head_attr) if head_attr else shape[dim])
                if tp > 1 and guard % tp == 0 and shape[dim] % tp == 0:
                    spec = [None] * len(shape)
                    spec[dim] = "model"
                    return P(*spec)
            return _replicated(len(shape))
        return _replicated(len(shape))

    return jax.tree_util.tree_map_with_path(rule, params)


def serve_heads_shardable(cfg, tp: int) -> bool:
    """Can the serving engine split attention heads across a ``tp``-way
    'model' axis?  Requires the *KV* head count to divide (GQA head counts
    often don't — the engine then falls back to fully replicated TP compute,
    mirroring ``_TP_RULES``'s head-count guards; DESIGN.md §9).  ``n_heads``
    divides whenever ``n_kv_heads`` does (``n_heads = group · n_kv_heads``)."""
    return tp > 1 and cfg.n_kv_heads > 0 and cfg.n_kv_heads % tp == 0 \
        and cfg.n_heads % tp == 0


def serve_param_specs(params: Any, cfg, mesh) -> Any:
    """Spec tree for the *serving* path (DESIGN.md §9): only the QKV
    projections shard (column-parallel on 'model', head-count guarded);
    everything else — W_O, MLP, embeddings, norms — stays replicated.

    This is deliberately a subset of :func:`param_specs`: the training
    layout's row-parallel W_O / W_down produce partial products that a psum
    reassociates, which breaks the engine's bitwise sharded ≡ single-device
    stream contract (the same fixed-reduction-layout argument as the scaled
    unary dot-products of arXiv:2307.03204).  The serve layout instead
    all-gathers the (small) attention-head activations before a replicated
    W_O — every f32 contraction stays whole, and the KV cache (the serving
    memory bottleneck) still shards ``tp``-way on its head dim.
    """
    tp = int(_mesh_sizes(mesh).get("model", 1))
    shardable = serve_heads_shardable(cfg, tp)
    qkv = {"wq", "bq", "wk", "wv", "bk", "bv"}

    def rule(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        name = _leaf_name(path)
        if shardable and name in qkv and shape[-1] % tp == 0:
            spec = [None] * len(shape)
            spec[-1] = "model"
            return P(*spec)
        return _replicated(len(shape))

    return jax.tree_util.tree_map_with_path(rule, params)


# cache leaves whose *entry-local* dim 1 is a (KV) head dim: the
# heads-major, positions-minor self-attention KV — ring (B, H, hd, cap) and
# paged pool (n_blocks+1, H, hd, bs) arrays and their (…, rows, cap|bs)
# scales, whose rows are the heads padded per head shard
# (transformer.scale_rows) — and the SSM state "h" (B, nh, hd, n); the
# enc-dec cross-attention caches (B, S, H, hd) carry heads at entry-local
# dim 2.
_HEADS_AT_2 = {"cross_k", "cross_v"}
_HEADS_AT_1 = {"h", "k", "v", "k_scale", "v_scale"}
_SCALE_LEAVES = {"k_scale", "v_scale"}
# tree keys under which cache entries carry a leading stack axis (scanned
# layer repeats / the enc-dec per-layer stacks); "remainder" entries do not
_STACKED_KEYS = {"layers", "self", "cross_k", "cross_v"}


def cache_specs(cache: Any, cfg, mesh) -> Any:
    """Spec tree for decode caches (ring and paged): the per-slot batch dim
    (and the paged layout's pool-block axis) → DP axes, KV/SSM head dims →
    'model'; every entry divisibility-guarded (DESIGN.md §9).

    Stacked entries (under ``layers`` / the enc-dec per-layer stacks) carry
    a leading repeat axis which is *never* sharded — the batch/head rules
    shift right by one.  A cache carrying ``block_tables`` is the paged
    layout: per-layer pool arrays are ``(n_shards·(n_blocks+1), H, ...)``
    and shard on their leading block axis (each data shard owns its blocks
    plus its own trash block); ``block_tables`` / ``pos`` shard on the slot
    dim.
    """
    sizes = _mesh_sizes(mesh)
    dp = data_axes(mesh)
    dp_axes_tuple = dp if isinstance(dp, tuple) else ((dp,) if dp else ())
    dp_size = math.prod(int(sizes[a]) for a in dp_axes_tuple) if dp_axes_tuple else 1
    tp = int(sizes.get("model", 1))
    # paged caches (identified by a "block_tables" key) need no special
    # branch: dim 0 of an entry is the batch dim on ring layouts and the
    # pool-block axis on paged ones, and both shard on the DP axes; the
    # entry-local head dim is 1 in both layouts.
    heads_split = serve_heads_shardable(cfg, tp)

    def rule(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        name = _leaf_name(path)
        stacked = any(isinstance(k, jax.tree_util.DictKey)
                      and str(k.key) in _STACKED_KEYS for k in path)
        off = 1 if stacked else 0
        spec = [None] * len(shape)
        # dim 0 of every entry (after the stack axis): per-slot batch rows on
        # the ring layouts, the pool-block axis on paged k/v/scale leaves
        if (dp and dp_size > 1 and off < len(shape)
                and shape[off] % dp_size == 0):
            spec[off] = dp
        head_dim = (2 if name in _HEADS_AT_2 else
                    1 if name in _HEADS_AT_1 else None)
        if head_dim is not None:
            head_dim += off
            if name in _SCALE_LEAVES:
                # scale rows follow the KV heads: the engine builds them
                # padded per head shard whenever the heads split
                if heads_split:
                    assert shape[head_dim] % tp == 0, (name, shape, tp)
                    spec[head_dim] = "model"
            elif (head_dim < len(shape) and tp > 1
                    and shape[head_dim] % tp == 0):
                spec[head_dim] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, cache)


def batch_specs(cfg, mesh) -> dict:
    """Specs for training/prefill batches: batch dim on the DP axes."""
    dp = data_axes(mesh)
    return {
        "tokens": P(dp, None),
        "labels": P(dp, None),
        "embeds": P(dp, None, None),
        "frames": P(dp, None, None),
    }
