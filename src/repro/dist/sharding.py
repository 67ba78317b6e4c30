"""Logical-axis sharding rules.

Model code names array dimensions with *logical* axes ("batch", "mlp",
"fsdp", ...); this module maps them onto the physical mesh axes of
``repro.launch.mesh`` (pod / data / model).  The mapping degrades
gracefully: a rule whose mesh axes are absent, already taken, or do not
divide the dimension falls back to replication, so the same model code
runs on 1 CPU device and on the 512-way production mesh.

  * ``pspec(dims, shape, rules, mesh)``  -> PartitionSpec
  * ``logical_sharding(dims, shape, mesh)`` -> NamedSharding
  * ``shard(x, *dims)``  -> ``reshard`` under the ambient mesh (no-op
    outside any mesh, e.g. single-device tests)
"""

from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec


# logical axis -> ordered mesh axes it may shard over.  Batch-like axes
# span pod x data (DP across the DCN and inside the pod); weight fan-in
# shards over data (FSDP); heads/ffn/vocab/experts shard over model (TP).
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "cache_batch": ("pod", "data"),
    # the Sweep engine's run axis (independent (config, scenario)
    # points): batch-like, spans DP axes
    "run": ("pod", "data"),
    "fsdp": ("data",),
    "vocab": ("model",),
    "embed": (),
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "act_heads": ("model",),
    "act_embed": (),
    "experts": ("model",),
    "seq": (),
    "kv_seq": (),
    "state": (),
    "conv": (),
}


def _mesh_sizes(mesh) -> dict[str, int]:
    return dict(mesh.shape)


def pspec(dims, shape, rules, mesh) -> PartitionSpec:
    """PartitionSpec for logical ``dims`` of an array of ``shape``.

    Each entry of ``dims`` is a logical axis name or None.  A logical
    axis shards over the subset of its rule's mesh axes that exist in
    ``mesh`` and are not already used by an earlier dim — but only when
    their combined size divides the dimension; otherwise the dim is
    replicated (None).
    """
    sizes = _mesh_sizes(mesh)
    used: set[str] = set()
    out = []
    for name, dim in zip(dims, shape):
        axes = tuple(a for a in rules.get(name or "", ())
                     if a in sizes and a not in used)
        total = math.prod(sizes[a] for a in axes) if axes else 1
        if not axes or total == 1 or dim % total != 0:
            out.append(None)
            continue
        used.update(axes)
        out.append(axes if len(axes) > 1 else axes[0])
    return PartitionSpec(*out)


def logical_sharding(dims, shape, mesh) -> NamedSharding:
    """NamedSharding for ``dims`` under DEFAULT_RULES."""
    return NamedSharding(mesh, pspec(dims, shape, DEFAULT_RULES, mesh))


def _ambient_mesh():
    """The mesh of the enclosing ``jax.set_mesh`` scope, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def sweep_mesh(n_devices: int | None = None, axis: str = "run"):
    """1-axis device mesh for ``Sweep.run(mesh=...)``.

    Takes the first ``n_devices`` local devices (all by default) on one
    axis named ``axis``; the Sweep engine shards its run batch over
    every axis of whatever mesh it is given, so any custom mesh works —
    this is just the common single-axis spelling.
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(f"n_devices={n} outside 1..{len(devs)}")
    import numpy as np
    return jax.sharding.Mesh(np.array(devs[:n]), (axis,))


def shard(x, *dims):
    """Constrain ``x``'s sharding by logical dims under the ambient mesh.

    Inside a ``with jax.set_mesh(mesh):`` scope (``jax.make_mesh``
    meshes, whose axes are Explicit) this is ``reshard``; with no mesh
    (unit tests, single device) it is the identity, so model code can
    call it unconditionally.
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    return jax.sharding.reshard(x, pspec(dims, x.shape, DEFAULT_RULES, mesh))
