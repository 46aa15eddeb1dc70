"""The sharding names every module of the repo imports, in one place.

The repo targets the one installed JAX (pyproject.toml pins it), so these
are plain imports: if a JAX bump moves one, fix it here and only here.
"""

from __future__ import annotations

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "Mesh",
    "NamedSharding",
    "PartitionSpec",
    "shard_map",
]
