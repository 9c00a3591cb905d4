"""Mesh construction for multi-chip scale-out.

The data-parallel fan-out axis of the leader pipeline (the reference's
N-verify-tile round-robin, fd_verify.c:46) mapped onto a jax.sharding.Mesh
(mesh.py; multihost.py for meshes across hosts).  The path that carries
pipeline traffic over n chips is the verify stage's own, `[verify]
devices = n` (runtime/verify.py: mesh_row_sharding, place_rows), which
takes `make_mesh` and `AXIS` from here.  Nothing under this package
imports runtime/ or models/ (tests/test_platform.py holds the layers).
"""

from .mesh import (  # noqa: F401
    AXIS,
    batch_sharding,
    make_mesh,
    pad_to_multiple,
    shard_verify_args,
    sharded_verify,
)
