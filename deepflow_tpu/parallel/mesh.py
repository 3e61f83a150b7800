"""Device mesh construction + param sharding helpers."""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("data", "fsdp", "tensor")


def factor_devices(n: int) -> tuple[int, int, int]:
    """Factor n devices into (data, fsdp, tensor) mesh dims.

    Heuristic: tensor gets up to 4 (ICI-local), fsdp absorbs the middle,
    data the rest — mirrors common v5e fsdp+tp layouts.
    """
    tensor = 1
    for t in (4, 2):
        if n % t == 0 and n >= t:
            tensor = t
            break
    rem = n // tensor
    fsdp = 1
    for f in (8, 4, 2):
        if rem % f == 0 and rem >= f:
            fsdp = f
            break
    data = rem // fsdp
    return (data, fsdp, tensor)


def make_mesh(devices=None, shape: tuple[int, int, int] | None = None) -> Mesh:
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if shape is None:
        shape = factor_devices(n)
    assert int(np.prod(shape)) == n, f"mesh {shape} != {n} devices"
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, AXES)


def shard_map(f, *, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    """jax.shard_map with replication checking off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def shard_params(params, specs, mesh: Mesh):
    """Place a param tree onto the mesh according to a PartitionSpec tree."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs,
        is_leaf=lambda x: isinstance(x, P) or not isinstance(x, dict))


def named_sharding_tree(specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
