"""Entering ``shard_map`` and sizing mesh axes.

The transport collectives (``protocol``), the sharded reconstruction
(``kernels.qz_sharded``) and the scan-over-rounds sharded driver
(``train.fit``) all run bodies under ``shard_map``.  The map takes its
mesh from the context, so callers enter ``jax.set_mesh(mesh)``; a map
nested in another map's body (the sharded reconstruction inside a
sharded federated round) composes with the partially-manual context
mesh.
"""

from __future__ import annotations

from typing import Sequence

import jax


def axis_size(axis_names: Sequence[str]) -> int:
    """Total device count across the named mesh axes, inside shard_map."""
    return jax.lax.axis_size(tuple(axis_names))


def shard_map(f, axis_names: Sequence[str], in_specs, out_specs):
    """``jax.shard_map`` manual over ``axis_names`` of the context mesh
    (entered with ``jax.set_mesh``)."""
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(axis_names), check_vma=False)
