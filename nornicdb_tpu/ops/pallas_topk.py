"""Pallas TPU kernel: fused cosine-similarity + blockwise top-k.

Replaces the two-pass device path (matmul → materialize [B,C] scores in
HBM → top_k) with a single fused kernel that never writes the score
matrix back to HBM. The reference fuses the same way in its Metal path
(shaders_darwin.metal topk_select over cosine_similarity_normalized
outputs, 43-360) and CUDA path (cuda_kernels.cu:263-420); on TPU the
equivalent is one Pallas kernel that

- streams [BLOCK_C, D] tiles of the embedding matrix HBM→VMEM via the
  grid pipeline,
- computes the [B, BLOCK_C] score tile on the MXU,
- applies the validity mask (capacity-padded buffers, SURVEY.md §7
  "dynamic shapes"), and
- reduces the tile to [B, KPAD] block-local winners in VMEM,

leaving only an [nblocks*KPAD]-wide final top-k for XLA — O(C/BLOCK_C·K)
HBM traffic instead of O(C).

Two-stage (block-local winners → global merge) is the standard TPU
top-k decomposition; exactness holds because the global top-k of the
union of block top-k's equals the full top-k whenever k <= KPAD.
"""

from __future__ import annotations

import functools
import logging
from typing import Tuple

import jax
import jax.numpy as jnp

from nornicdb_tpu.ops.similarity import EXACT, cosine_topk_auto

logger = logging.getLogger(__name__)

NEG_INF = -1e30
_KPAD = 128  # lane-aligned per-block winner count (k <= _KPAD)
_BLOCK_C = 1024  # matrix rows per grid step (4 MB VMEM tile at D=1024)


def _block_topk_kernel(q_ref, m_ref, mask_ref, s_out_ref, i_out_ref, *, k: int):
    """One grid step: score a [BLOCK_C, D] tile against all queries and
    keep the tile's top-k per query row."""
    import jax.experimental.pallas as pl

    step = pl.program_id(0)
    block_c = m_ref.shape[0]

    # [B, BLOCK_C] scores on the MXU; inputs are pre-normalized so
    # cosine == dot.
    scores = jax.lax.dot_general(
        q_ref[:], m_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=EXACT,
        preferred_element_type=jnp.float32,
    )
    # mask block is [BLOCK_C] float {0,1} (1-D: lane tiling only, no
    # sublane constraint — a [1, BLOCK_C] 2-D block violates the TPU's
    # (8, 128) tiling requirement); invalid -> NEG_INF
    scores = scores + (mask_ref[:][None, :] - 1.0) * 1e30

    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    base = step * block_c

    s_cols = []
    i_cols = []
    for _ in range(k):
        m = jnp.max(scores, axis=1)  # [B]
        is_max = scores == m[:, None]
        idx = jnp.min(jnp.where(is_max, col, block_c), axis=1)  # [B]
        s_cols.append(m)
        i_cols.append(base + idx)
        scores = jnp.where(col == idx[:, None], NEG_INF, scores)

    b = scores.shape[0]
    fill_s = jnp.full((b, _KPAD - k), NEG_INF, dtype=jnp.float32)
    fill_i = jnp.zeros((b, _KPAD - k), dtype=jnp.int32)
    s_out_ref[0] = jnp.concatenate(
        [jnp.stack(s_cols, axis=1), fill_s], axis=1
    )
    i_out_ref[0] = jnp.concatenate(
        [jnp.stack(i_cols, axis=1).astype(jnp.int32), fill_i], axis=1
    )


@functools.partial(
    jax.jit, static_argnames=("k", "block_c", "interpret")
)
def _fused_cosine_topk_impl(
    queries: jnp.ndarray,  # [B, D] normalized, B % 8 == 0
    matrix: jnp.ndarray,  # [C, D] normalized, C % block_c == 0
    maskf: jnp.ndarray,  # [C] float32 {0,1}
    k: int,
    block_c: int,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, d = queries.shape
    c = matrix.shape[0]
    nblocks = c // block_c

    kernel = functools.partial(_block_topk_kernel, k=k)
    out_shape = (
        jax.ShapeDtypeStruct((nblocks, b, _KPAD), jnp.float32),
        jax.ShapeDtypeStruct((nblocks, b, _KPAD), jnp.int32),
    )
    grid_spec = pl.GridSpec(
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (block_c, d), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (block_c,), lambda i: (i,), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=(
            pl.BlockSpec(
                (1, b, _KPAD), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, b, _KPAD), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
            ),
        ),
    )
    block_s, block_i = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * b * c * d,
            bytes_accessed=c * d * 4 + b * d * 4 + nblocks * b * _KPAD * 8,
            transcendentals=0,
        ),
    )(queries, matrix, maskf)

    # global merge: [B, nblocks*KPAD] -> top-k (pad lanes hold NEG_INF)
    all_s = jnp.transpose(block_s, (1, 0, 2)).reshape(b, nblocks * _KPAD)
    all_i = jnp.transpose(block_i, (1, 0, 2)).reshape(b, nblocks * _KPAD)
    top_s, pos = jax.lax.top_k(all_s, k)
    top_i = jnp.take_along_axis(all_i, pos, axis=1)
    return top_s, top_i


_said: set = set()


def _xla_instead(reason: str, why: str, queries, matrix, valid, k):
    """The kernel was asked for and cannot run: say so once per reason,
    then serve the XLA program (same contract, same HBM routing)."""
    if reason not in _said:
        _said.add(reason)
        logger.warning("fused Pallas top-k requested but %s; serving the "
                       "XLA cosine_topk instead", why)
    return cosine_topk_auto(queries, matrix, valid, k)


def fused_cosine_topk(
    queries: jnp.ndarray,
    matrix: jnp.ndarray,
    valid: jnp.ndarray,
    k: int,
    *,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused exact cosine top-k (Pallas). Same contract as
    ops.similarity.cosine_topk: inputs L2-normalized, returns
    (scores [B,k], indices [B,k]).

    The kernel compiles for a TPU backend only, and only for shapes that
    meet its tiling constraints (D % 128, C % block, k <= 128, B <= 256 —
    compiled on a v5e up to B=256 at 8192 x 1024). Anything else is served
    by the XLA program, logged once. ``interpret=True`` is the emulation
    the tests and the CPU rehearsal of chip_smoke.py ask for by name;
    nothing selects it on its own.
    """
    b, d = queries.shape
    c = matrix.shape[0]
    k_eff = min(k, c)
    block_c = min(_BLOCK_C, c)
    if not interpret and jax.default_backend() != "tpu":
        return _xla_instead(
            "backend", f"the backend is {jax.default_backend()!r}",
            queries, matrix, valid, k)
    if (
        d % 128 != 0
        or c % block_c != 0
        or k_eff > _KPAD
        or k_eff < 1
        or b > 256  # VMEM bound: queries + score tile must fit
    ):
        return _xla_instead(
            "shape", f"shape B={b} C={c} D={d} k={k} is outside its tiling",
            queries, matrix, valid, k)

    b_pad = max(8, -(-b // 8) * 8)
    if b_pad != b:
        queries = jnp.pad(queries, ((0, b_pad - b), (0, 0)))
    maskf = valid.astype(jnp.float32)
    s, idx = _fused_cosine_topk_impl(
        queries, matrix, maskf, k_eff, block_c, interpret
    )
    return s[:b], idx[:b]
