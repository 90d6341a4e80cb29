"""Device mesh construction + sharded vector-search collectives.

Multi-chip kNN: the embedding matrix is row-sharded across the ``data``
mesh axis (each chip holds C/n rows in its HBM); every chip computes its
local top-k and the results merge with one all-gather over ICI. This is
the TPU-native replacement for the reference's single-GPU search fan-out
(pkg/gpu/accelerator.go GPUEmbeddingIndex.Search) and scales it to slices.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nornicdb_tpu.ops.similarity import EXACT


@dataclass(frozen=True)
class MeshSpec:
    """Named mesh axes used across the framework.

    - ``dp``: data parallel (batch)
    - ``tp``: tensor parallel (hidden/heads)
    - ``sp``: sequence/context parallel (ring attention)
    """

    dp: int = 1
    tp: int = 1
    sp: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.tp * self.sp


def best_mesh(n_devices: int) -> MeshSpec:
    """Factor a device count into (dp, tp, sp) favoring dp (batch) first,
    then tp, then sp — the right default for embedding inference."""
    dp, tp, sp = 1, 1, 1
    rem = n_devices
    # give tp the smallest prime factor pack up to 4, sp up to 2, dp the rest
    if rem % 2 == 0 and rem >= 4:
        tp = 2
        rem //= 2
    if rem % 2 == 0 and rem >= 4:
        sp = 2
        rem //= 2
    dp = rem
    return MeshSpec(dp=dp, tp=tp, sp=sp)


def make_mesh(
    spec: Optional[MeshSpec] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if spec is None:
        spec = MeshSpec(dp=len(devices))
    if spec.size != len(devices):
        raise ValueError(f"mesh spec {spec} does not cover {len(devices)} devices")
    arr = np.array(devices).reshape(spec.dp, spec.tp, spec.sp)
    return Mesh(arr, axis_names=("dp", "tp", "sp"))


def data_mesh(n: Optional[int] = None) -> Mesh:
    """1-D mesh over all (or n) devices for row-sharded vector search."""
    devices = jax.devices()[: n or len(jax.devices())]
    return Mesh(np.array(devices), axis_names=("data",))


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: the local top-k
    bodies intentionally mix replicated queries with sharded rows."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.partial(jax.jit, static_argnames=("k", "mesh_holder"))
def _sharded_topk_impl(queries, matrix, valid, k, mesh_holder):
    mesh = mesh_holder.mesh
    n_shards = mesh.shape["data"]
    shard_rows = matrix.shape[0] // n_shards
    # every member of the global top-k is within the top-min(k, rows) of its
    # own shard, so gathering local_k per shard merges to the EXACT top-k
    local_k = min(k, shard_rows)

    def local_topk(q, m, v):
        # q: [B, D] replicated; m: [rows/n, D]; v: [rows/n]
        scores = jnp.matmul(q, m.T, precision=EXACT)
        scores = jnp.where(v[None, :], scores, -1e30)
        s, i = jax.lax.top_k(scores, local_k)
        # local indices -> global row ids
        shard = jax.lax.axis_index("data")
        gi = i + shard * shard_rows
        # merge across shards over ICI
        all_s = jax.lax.all_gather(s, "data", axis=1, tiled=True)  # [B, n*local_k]
        all_i = jax.lax.all_gather(gi, "data", axis=1, tiled=True)
        top_s, pos = jax.lax.top_k(all_s, k)
        top_i = jnp.take_along_axis(all_i, pos, axis=1)
        return top_s, top_i

    return shard_map_unchecked(
        local_topk,
        mesh=mesh,
        in_specs=(P(), P("data", None), P("data")),
        out_specs=(P(), P()),
    )(queries, matrix, valid)


class _MeshHolder:
    """Hashable wrapper so a Mesh can ride through static_argnames."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __hash__(self):
        return hash(
            (tuple(self.mesh.axis_names), tuple(d.id for d in self.mesh.devices.flat))
        )

    def __eq__(self, other):
        return (
            isinstance(other, _MeshHolder)
            and tuple(self.mesh.axis_names) == tuple(other.mesh.axis_names)
            and [d.id for d in self.mesh.devices.flat]
            == [d.id for d in other.mesh.devices.flat]
        )


def sharded_cosine_topk(
    queries: jnp.ndarray,
    matrix: jnp.ndarray,
    valid: jnp.ndarray,
    k: int,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Multi-device exact kNN: row-shard ``matrix`` over the mesh's
    ``data`` axis, local top-k per chip, one all-gather merge.
    ``matrix.shape[0]`` must be divisible by the shard count (use
    ops.similarity.pad_dim capacity + valid mask)."""
    mesh = mesh or data_mesh()
    n = mesh.shape["data"]
    c = matrix.shape[0]
    if c % n != 0:
        raise ValueError(f"capacity {c} not divisible by {n} shards")
    k = min(k, c)
    sharding = NamedSharding(mesh, P("data", None))
    matrix = jax.device_put(matrix, sharding)
    valid = jax.device_put(valid, NamedSharding(mesh, P("data")))
    queries = jax.device_put(queries, NamedSharding(mesh, P()))
    return _sharded_topk_impl(queries, matrix, valid, k, _MeshHolder(mesh))
