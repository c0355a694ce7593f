"""Ambient mesh context for modules that need explicit collectives
(shard_map paths) deep inside a traced model function, plus the
``shard_map`` entry point they share."""
from __future__ import annotations

import jax


def shard_map_compat(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with per-shard replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


_MESH = None


def set_mesh(mesh) -> None:
    global _MESH
    _MESH = mesh


def get_mesh():
    return _MESH


_CACHE_SPECS = None


def set_cache_specs(specs) -> None:
    """PartitionSpec pytree for the decode cache (see sharding.decode_shardings)."""
    global _CACHE_SPECS
    _CACHE_SPECS = specs


def get_cache_specs():
    return _CACHE_SPECS
