"""Ring attention: exact attention over a sequence sharded across devices.

Long-context is first-class in the TPU design (SURVEY.md §2.8): each
device holds S/n query/key/value blocks; K/V blocks rotate around the
``sp`` ring with jax.lax.ppermute (ICI neighbor exchange) while each
device accumulates its queries' attention with the numerically-stable
streaming-softmax (flash/online) update. Compute overlaps the rotation —
no device ever materializes the full [S, S] score matrix or the full K/V.

This is exact (matches dense attention to float tolerance), not an
approximation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nornicdb_tpu.parallel.mesh import shard_map_unchecked


def _ring_attention_local(q, k, v, mask, axis_name: str):
    """Per-device body under shard_map.

    q: [B, Sq, H, D] local queries; k/v: [B, Sk, H, D] local K/V block;
    mask: [B, Sk] local key validity. Rotates k/v/mask n-1 times.
    """
    n = jax.lax.psum(1, axis_name)
    scale = q.shape[-1] ** -0.5

    def attend_block(q, k, v, kmask):
        # [B, H, Sq, Sk]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = jnp.where(kmask[:, None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)  # [B,H,Sq,1]
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return o, m[..., 0], l[..., 0]  # o:[B,Sq,H,D], m/l:[B,H,Sq]

    def combine(acc, new):
        o_a, m_a, l_a = acc
        o_n, m_n, l_n = new
        m = jnp.maximum(m_a, m_n)
        ca = jnp.exp(m_a - m)
        cn = jnp.exp(m_n - m)
        o = (
            o_a * jnp.transpose(ca, (0, 2, 1))[..., None]
            + o_n * jnp.transpose(cn, (0, 2, 1))[..., None]
        )
        l = l_a * ca + l_n * cn
        return o, m, l

    def step(carry, _):
        (k, v, kmask), acc = carry
        new = attend_block(q, k, v, kmask)
        acc = combine(acc, new)
        # rotate K/V block to the next device on the ring (ICI neighbor)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        kmask = jax.lax.ppermute(kmask, axis_name, perm)
        return ((k, v, kmask), acc), None

    b, sq, h, d = q.shape
    acc0 = (
        jnp.zeros((b, sq, h, d), jnp.float32),
        jnp.full((b, h, sq), -jnp.inf, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
    )
    ((_, _, _), (o, m, l)), _ = jax.lax.scan(
        step,
        ((k.astype(jnp.float32), v.astype(jnp.float32), mask), acc0),
        None,
        length=n,
    )
    l = jnp.maximum(l, 1e-30)
    return (o / jnp.transpose(l, (0, 2, 1))[..., None]).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,  # [B, S] key validity
    mesh: Optional[Mesh] = None,
    axis_name: str = "sp",
    batch_axis: Optional[str] = None,  # mesh axis carrying the batch (dp)
    head_axis: Optional[str] = None,  # mesh axis carrying the heads (tp)
) -> jnp.ndarray:
    """Exact attention with the sequence dim sharded over ``axis_name``.

    ``batch_axis``/``head_axis`` declare how B and H are already sharded on
    the same mesh so the ring only rotates over the sequence axis (no
    spurious gathers of dp/tp-sharded operands). Outside a mesh (or axis
    size 1) this degrades to dense attention."""
    if mask is None:
        mask = jnp.ones(q.shape[:2], dtype=bool)
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        return _dense_attention(q, k, v, mask)

    qkv_spec = P(batch_axis, axis_name, head_axis, None)
    fn = shard_map_unchecked(
        functools.partial(_ring_attention_local, axis_name=axis_name),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, P(batch_axis, axis_name)),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, mask)


def _dense_attention(q, k, v, mask):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)).astype(q.dtype)
