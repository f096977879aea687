"""Random weights for the system under test, made by the benchmark from
``--seed`` on the device in one jitted call, in the type they are served in
(bf16).  The program is asked only for the *layout* of its parameter tree
(``jax.eval_shape`` of its initialiser, no values); the values are the
benchmark's, so the reference can use them without taking anything the
program made."""

from __future__ import annotations


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (seeds may pass
    32 bits): the low 31 bits seed the key, the rest is folded in."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _kind(path: str) -> str:
    if "ln1" in path or "ln2" in path or "final_norm" in path:
        return "norm"
    if "embed" in path:
        return "embed"
    if "lm_head" in path:
        return "head"
    return "matrix"


def make_params(program_cfg, seed: int, vocab: int, weight_kind=None):
    """The parameter tree of ``program_cfg`` filled from ``seed``: matrices
    N(0, 1/fan_in), the embedding N(0, 0.02²) with its vocabulary padding
    rows zero, an untied head N(0, 0.02²), norm gains 1 + N(0, 0.05²).  A
    leaf's kind comes from its name, unless ``weight_kind(path, shape)`` (the
    architecture module's, where it has one) names it; leaf ``i`` is drawn
    from ``fold_in(key, i)`` either way."""
    import jax
    import jax.numpy as jnp

    from repro.models import registry

    shapes = jax.eval_shape(
        lambda: registry.init_model(jax.random.PRNGKey(0), program_cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    kinds = []
    for key_path, leaf in flat:
        path = jax.tree_util.keystr(key_path)
        kinds.append((weight_kind and weight_kind(path, leaf.shape)) or _kind(path))

    def make(key):
        out = []
        for i, ((_, s), kind) in enumerate(zip(flat, kinds)):
            z = jax.random.normal(jax.random.fold_in(key, i), s.shape, jnp.float32)
            if kind == "norm":
                v = 1.0 + 0.05 * z
            elif kind == "embed":
                rows = jnp.arange(s.shape[0])[:, None] < vocab
                v = jnp.where(rows, 0.02 * z, 0.0)
            elif kind == "head":
                cols = jnp.arange(s.shape[-1])[None, :] < vocab
                v = jnp.where(cols, 0.02 * z, 0.0)
            else:
                v = z / jnp.sqrt(jnp.float32(s.shape[-2]))
            out.append(v.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(seed_key(seed))
