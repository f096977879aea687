"""Ambient mesh context for mesh-agnostic model code.

Layers call ``ctx.constrain(x, ...)`` unconditionally; the call resolves to a
``with_sharding_constraint`` only when a mesh has been installed with
``mesh_context`` (launch/train, launch/serve, dry-run), and to identity
otherwise — so the same model code runs on a single CPU device and on a
(16, 16) v5e pod without branches at the call sites.

Every constraint entry is validated against the live mesh: axes the mesh
does not have, and dims the axis size does not divide, degrade to ``None``
(replicated) instead of erroring.  That is what makes reduced CPU configs
and ragged head counts safe on any topology.
"""

from __future__ import annotations

import contextlib
import math
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "mesh_context", "current_mesh", "axis_size", "tp_size", "dp_axes",
    "dp_shards", "seq_shard_attention", "constrain",
    "serve_shard_scope", "kv_shard_info", "gather_heads",
]

_MESH_STACK: list = []

# DP axes in outer-to-inner order; "model" is the TP axis (launch/mesh.py).
_DP_AXIS_NAMES = ("pod", "data")


@contextlib.contextmanager
def mesh_context(mesh):
    """Install ``mesh`` as the ambient mesh for ``constrain`` / size queries."""
    _MESH_STACK.append(mesh)
    try:
        yield mesh
    finally:
        _MESH_STACK.pop()


def current_mesh():
    return _MESH_STACK[-1] if _MESH_STACK else None


def _mesh_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def axis_size(name: str) -> int:
    mesh = current_mesh()
    if mesh is None:
        return 1
    return int(_mesh_sizes(mesh).get(name, 1))


def tp_size() -> int:
    """Tensor-parallel width (the 'model' mesh axis; 1 outside a mesh)."""
    return axis_size("model")


def dp_axes():
    """The data-parallel spec entry: ('pod', 'data') on multi-pod meshes,
    plain 'data' otherwise.  Usable directly as one PartitionSpec entry."""
    mesh = current_mesh()
    if mesh is None:
        return "data"
    present = tuple(a for a in _DP_AXIS_NAMES if a in _mesh_sizes(mesh))
    if not present:
        return "data"
    return present if len(present) > 1 else present[0]


def dp_shards() -> int:
    """Total number of data-parallel shards under the current mesh."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    sizes = _mesh_sizes(mesh)
    return int(math.prod(sizes.get(a, 1) for a in _DP_AXIS_NAMES))


def seq_shard_attention(n_heads: int) -> bool:
    """Sequence-parallel attention: used when TP is on but the (GQA) head
    count cannot split across the 'model' axis — tokens shard instead and
    QKV/O weights stay replicated (dist/sharding.py emits the matching
    replicated specs)."""
    tp = tp_size()
    return tp > 1 and n_heads % tp != 0


# ---------------------------------------------------------------------------
# serve-time shard scope (inside shard_map, DESIGN.md §9)
# ---------------------------------------------------------------------------
#
# The sharded serving engine runs its jitted steps under ``shard_map``: model
# code sees *local* shapes (batch rows of this data shard, KV heads of this
# model shard), but two things must stay functions of the GLOBAL coordinates
# for sharded and single-device streams to be bitwise-equal:
#
# 1. the int8 KV quantiser's element indices (its dither hash is keyed on the
#    global (row, head, element) index — DESIGN.md §6's bit-reusability
#    contract), and
# 2. the all-gather of attention heads before the (replicated) W_O matmul —
#    the serve TP layout keeps every f32 contraction un-split, so sharding
#    never reassociates a reduction (DESIGN.md §9).
#
# The engine installs this scope around the shard_map body; outside it (no
# mesh, or code paths like training that shard via GSPMD instead) both
# helpers degrade to identity / None.

_SERVE_SHARD: list = []


@contextlib.contextmanager
def serve_shard_scope(*, head0, heads_sharded: bool,
                      model_axis: str = "model"):
    """Install the per-shard → global coordinate map for one traced serve
    step.  ``head0`` is the shard's global KV-head offset (a traced scalar,
    ``lax.axis_index`` times the local head count; 0 under the fallback);
    ``heads_sharded`` records whether the 'model' axis actually splits the
    heads (False = GQA replicated fallback, DESIGN.md §9).  Batch rows need
    no offset on purpose: everything the model hashes is row-independent
    (see ``transformer._kv_elem_idx``)."""
    _SERVE_SHARD.append({
        "head0": head0, "heads_sharded": bool(heads_sharded),
        "model_axis": model_axis,
    })
    try:
        yield
    finally:
        _SERVE_SHARD.pop()


def kv_shard_info() -> Optional[dict]:
    """The active serve shard scope (None outside sharded serving) — the KV
    quantiser reads global element-index offsets from it."""
    return _SERVE_SHARD[-1] if _SERVE_SHARD else None


def gather_heads(x: jax.Array) -> jax.Array:
    """All-gather the (sharded) attention-head dim of ``x`` (last axis)
    across the 'model' axis — identity outside sharded serving or under the
    GQA replicated fallback.  Concatenation order equals the global head
    order, so the gathered activation is bitwise the single-device one; the
    consuming W_O matmul then contracts the full head dim on every shard
    instead of psum-ing partial products (DESIGN.md §9)."""
    info = kv_shard_info()
    if info is None or not info["heads_sharded"]:
        return x
    return jax.lax.all_gather(x, info["model_axis"], axis=x.ndim - 1,
                              tiled=True)


def _validated_entry(entry, dim: int, sizes: dict):
    """Keep a spec entry only if all its axes exist and their product divides
    the dim; otherwise replicate that dim."""
    if entry is None:
        return None
    axes = entry if isinstance(entry, tuple) else (entry,)
    if any(a not in sizes for a in axes):
        return None
    size = math.prod(int(sizes[a]) for a in axes)
    if size <= 1 or dim % size != 0:
        return None
    return entry


def constrain(x: jax.Array, *entries) -> jax.Array:
    """``with_sharding_constraint(x, P(*entries))`` under the ambient mesh;
    identity when no mesh is installed (or the mesh is a single device).

    One entry per dim of ``x``; each entry is an axis name, a tuple of axis
    names, or None.  Invalid entries (absent axis / non-dividing size)
    degrade to None per dim rather than erroring.
    """
    mesh = current_mesh()
    if mesh is None or mesh.size <= 1:
        return x
    sizes = _mesh_sizes(mesh)
    spec = tuple(
        _validated_entry(e, d, sizes) for e, d in zip(entries, x.shape)
    )
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
