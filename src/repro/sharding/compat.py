"""shard_map with the replication check off, for every call site.

    from repro.sharding.compat import shard_map_nocheck
    fn = shard_map_nocheck(body, mesh=mesh, in_specs=..., out_specs=...)
"""
from __future__ import annotations

from jax import shard_map


def shard_map_nocheck(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check (``check_vma``) disabled."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
