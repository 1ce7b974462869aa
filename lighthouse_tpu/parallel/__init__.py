"""Multi-chip parallelism: the device mesh + sharding layout of the
verification pipeline (see mesh.py)."""

from .mesh import (get_mesh, mesh_shape_key, pad_sets, parse_mesh_shape,
                   put_pk_grid, put_sets, put_single, replicated_sharding,
                   reset_mesh_cache, sets_sharding)

__all__ = ["get_mesh", "mesh_shape_key", "pad_sets", "parse_mesh_shape",
           "put_pk_grid", "put_sets", "put_single", "replicated_sharding",
           "reset_mesh_cache", "sets_sharding"]
