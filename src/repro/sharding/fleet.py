"""Mesh plumbing for the fleet/cell batch axis of rollout sweeps.

The LLM side of the repo shards parameters over ("data", "model") meshes
(``partition.py``); rollout sweeps need something much simpler — a 1-D
mesh over one batch-like axis (fleets within a driver, or cells within a
packed sweep), with every other leaf replicated. On a single-device host
``fleet_mesh()`` returns ``None`` and callers fall through to plain
``vmap``, so CPU CI exercises the identical compiled path minus the
device placement.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FLEET_AXIS = "fleet"


def fleet_mesh(n_devices: Optional[int] = None) -> Optional[Mesh]:
    """1-D mesh over the local devices, or ``None`` on a 1-device host."""
    devices = jax.devices()
    n = min(n_devices or len(devices), len(devices))
    if n <= 1:
        return None
    # Mesh directly: jax.make_mesh defaults to Explicit axes, and the
    # fleet placement relies on Auto (compiler-propagated) sharding
    return Mesh(np.array(devices[:n]), (FLEET_AXIS,))


def pad_to_devices(n_items: int, mesh: Optional[Mesh]) -> int:
    """Smallest count >= n_items divisible by the mesh's device count."""
    if mesh is None:
        return n_items
    d = mesh.devices.size
    return ((n_items + d - 1) // d) * d


def shard_leading_axis(tree, mesh: Optional[Mesh]):
    """Place every leaf with its leading axis split over the fleet mesh.

    Leading dims must divide the device count (use ``pad_to_devices``).
    ``mesh=None`` is the single-device fallback: the tree is returned
    untouched and downstream ``vmap``/``scan`` run unsharded.
    """
    if mesh is None:
        return tree

    def put(x):
        spec = P(FLEET_AXIS, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, tree)


def map_leading_axis(fn, mesh: Optional[Mesh]):
    """Run ``fn`` on each device's block of the leading axis.

    ``fn`` must treat that axis as a batch (a ``vmap`` over cells or
    members), with every argument and output leaf carrying it. The
    compiler cannot partition a Pallas (Mosaic) kernel over a sharded
    operand, so the cell and member programs are mapped explicitly: each
    device runs ``fn`` on its own block and no collective is needed.
    ``mesh=None`` returns ``fn`` unchanged.
    """
    if mesh is None:
        return fn
    spec = P(FLEET_AXIS)
    # check_vma off: a Pallas call's out_shape carries no varying-axes
    # annotation, and nothing here crosses devices to check
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


def replicate(tree, mesh: Optional[Mesh]):
    """Replicate every leaf across the mesh (no-op when ``mesh`` is None)."""
    if mesh is None:
        return tree
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)
