"""Production meshes.  Functions, not module constants — importing this file
never touches jax device state (the dry-run sets device-count env first)."""

from __future__ import annotations

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_local_mesh", "make_serve_mesh",
           "parse_serve_mesh"]


def _auto_kw(n):
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """Target topology: one v5e pod = (data=16, model=16) = 256 chips;
    multi-pod adds a leading 'pod' DP axis (2 × 256 = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_kw(len(axes)))


def make_local_mesh():
    """Whatever this host has (CPU container: 1 device) as (data, model)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), **_auto_kw(2))


def make_serve_mesh(data: int, model: int):
    """A concrete ('data', 'model') mesh for the sharded serving engine
    (DESIGN.md §9).  Plain ``jax.sharding.Mesh`` — the engine runs its steps
    under ``shard_map``, which wants explicitly-managed (non-Auto) axes.
    Works on any backend; CPU CI forces devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    devs = jax.devices()
    if data * model > len(devs):
        raise ValueError(
            f"mesh ({data}, {model}) needs {data * model} devices, have "
            f"{len(devs)} (on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    arr = np.asarray(devs[: data * model]).reshape(data, model)
    return jax.sharding.Mesh(arr, ("data", "model"))


def parse_serve_mesh(spec: str):
    """Parse a CLI ``--mesh`` value ('DATA,MODEL', e.g. '2,2') into a serve
    mesh — the one parser both launch/serve.py and serve_bench.py use, so
    the flag's syntax and errors cannot drift between them."""
    try:
        data, model = (int(x) for x in spec.split(","))
    except ValueError:
        raise ValueError(
            f"--mesh expects DATA,MODEL (e.g. '2,2'); got {spec!r}"
        ) from None
    if data < 1 or model < 1:
        raise ValueError(f"--mesh axes must be positive; got {spec!r}")
    return make_serve_mesh(data, model)
