"""Parallelism layer: device meshes, sharding rules, collectives.

The reference's distributed story is a CPU RPC mesh (pkg/replication
transport.go) plus single-device GPU kernels; the TPU-native design keeps
a host-side control plane (replication module) and moves the bulk data
plane onto XLA collectives over ICI/DCN (SURVEY.md §2.8, §5).
"""

from nornicdb_tpu.parallel.mesh import (  # noqa: F401
    MeshSpec,
    best_mesh,
    data_mesh,
    make_mesh,
    shard_map_unchecked,
    sharded_cosine_topk,
)
