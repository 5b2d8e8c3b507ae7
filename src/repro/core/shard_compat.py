"""``shard_map`` with replication checking off — the one place it is set.

Every shard_map user in the repo (``core.distributed``, ``repro.dist``,
tests) imports :func:`shard_map_compat` from here. Replication checking is
disabled: the solver/curvature collectives deliberately produce replicated
outputs from sharded inputs (Gram psums, factor broadcasts), which the
strict checker rejects.
"""
from __future__ import annotations

import jax

__all__ = ["shard_map_compat"]


def shard_map_compat(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map(f)`` with ``check_vma=False``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
