"""Transformer text encoder (bge-m3-style) in flax.

The reference embeds with bge-m3 (an XLM-RoBERTa-large derivative) through
llama.cpp (pkg/embed/local_gguf.go:57 LocalGGUFEmbedder). Here the encoder
is a native JAX/flax module designed for TPU:

- bfloat16 activations, f32 normalization; parameters are held in
  float32 (what ``init`` and every loader give) and each module rounds
  its own to ``cfg.dtype`` when called, so the inference stack hands
  ``apply`` a tree whose matrices it rounded ONCE
  (``embed/embedder.py`` ``JaxEncoderEmbedder``: same operands, no
  conversion of the token table a call); training applies the float32
  tree itself;
- every activation carries a logical sharding annotation so the same
  module runs single-chip or pjit-sharded over a (dp, tp, sp) mesh with
  XLA inserting the collectives (scaling-book recipe);
- mean pooling + L2 norm = drop-in embedding vectors for the search
  stack.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.lax import with_sharding_constraint as _wsc
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class EncoderConfig:
    vocab_size: int = 30522
    hidden_size: int = 384
    num_layers: int = 6
    num_heads: int = 6
    mlp_dim: int = 1536
    max_len: int = 512
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    # logical mesh axes ('' disables the constraint when no mesh is active)
    shard_activations: bool = False
    # when a mesh with sp > 1 is attached, attention routes through ring
    # attention (sequence-sharded, no [S, S] materialization)
    mesh: Any = None
    # single-chip fused Pallas attention; resolved at CONSTRUCTION by the
    # inference stack (never set for training: the kernel has no vjp, and
    # never combined with a multi-device mesh: pallas_call has no GSPMD
    # partitioning rule)
    use_flash_attention: bool = False

    @staticmethod
    def tiny() -> "EncoderConfig":
        return EncoderConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                             num_heads=4, mlp_dim=128, max_len=128)

    @staticmethod
    def mini() -> "EncoderConfig":
        """The committed-checkpoint shape (models/pretrain.py): big
        enough to learn topic-level co-occurrence structure (8k hash
        vocab keeps collisions from blurring topical terms), small
        enough that the fp16 checkpoint stays a few MB in git."""
        return EncoderConfig(vocab_size=8192, hidden_size=160,
                             num_layers=2, num_heads=4, mlp_dim=640,
                             max_len=512, dtype=jnp.float32)

    @staticmethod
    def bge_m3_like() -> "EncoderConfig":
        """XLM-R-large shape (bge-m3's backbone)."""
        return EncoderConfig(vocab_size=250_002, hidden_size=1024,
                             num_layers=24, num_heads=16, mlp_dim=4096,
                             max_len=8192)


def _maybe_shard(x: jnp.ndarray, cfg: EncoderConfig, spec: P) -> jnp.ndarray:
    """Annotate activation sharding; under plain jit (no mesh) this is a
    no-op, under pjit it pins [batch->dp, seq->sp, hidden->tp]."""
    if not cfg.shard_activations:
        return x
    try:
        return _wsc(x, spec)
    except RuntimeError as exc:
        # tolerate ONLY the no-mesh case (single-device run of a shardable
        # config); genuine sharding errors must fail loudly
        if "non-empty mesh" in str(exc):
            return x
        raise


def flash_attention_enabled() -> bool:
    """Opt-in fused Pallas attention (NORNICDB_PALLAS_ATTENTION=1),
    compiled and matched against the XLA arm on a v5e (chip_smoke.py
    phase 5); whether it becomes a default is a measured decision not
    yet taken. Consumed at encoder CONSTRUCTION by the inference
    embedder; the training path never opts in (the kernel has no vjp).
    The kernel compiles for a TPU backend only: asked for anywhere else,
    it says so and the XLA arm serves."""
    import os

    if os.environ.get("NORNICDB_PALLAS_ATTENTION", "0") != "1":
        return False
    if jax.default_backend() != "tpu":
        logging.getLogger(__name__).warning(
            "NORNICDB_PALLAS_ATTENTION=1 but the backend is %r; the "
            "encoder uses the XLA attention arm", jax.default_backend())
        return False
    return True


class MultiHeadAttention(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        d = cfg.hidden_size
        h = cfg.num_heads
        head_dim = d // h
        # qkv projections: kernel sharded over tp on the head axis
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            features=(h, head_dim), axis=-1, dtype=cfg.dtype, name=name,
        )
        q = dense("query")(x)  # [B, S, h, hd]
        k = dense("key")(x)
        v = dense("value")(x)
        q = _maybe_shard(q, cfg, P("dp", "sp", "tp", None))
        if cfg.mesh is not None and cfg.mesh.shape.get("sp", 1) > 1:
            # sequence-parallel path: exact ring attention over the sp axis
            # (K/V blocks rotate via ppermute; no [S, S] materialization)
            from nornicdb_tpu.parallel.ring_attention import ring_attention

            out = ring_attention(
                q, k, v, mask, mesh=cfg.mesh,
                axis_name="sp", batch_axis="dp", head_axis="tp",
            )
        elif cfg.use_flash_attention and cfg.mesh is None:
            # fused Pallas path: blockwise online-softmax attention, no
            # [S, S] HBM matrix (ops/pallas_attention.py). Construction-
            # time opt-in for single-chip inference only — no vjp, and
            # no GSPMD partitioning rule for the custom call.
            from nornicdb_tpu.ops.pallas_attention import flash_attention

            out = flash_attention(q, k, v, mask)
        else:
            k = _maybe_shard(k, cfg, P("dp", None, "tp", None))
            v = _maybe_shard(v, cfg, P("dp", None, "tp", None))
            scale = head_dim ** -0.5
            # [B, h, S, S] — XLA fuses the softmax chain
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            big_neg = jnp.finfo(cfg.dtype).min
            logits = jnp.where(mask[:, None, None, :], logits, big_neg)
            weights = jax.nn.softmax(
                logits.astype(jnp.float32), axis=-1
            ).astype(cfg.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
        out = _maybe_shard(out, cfg, P("dp", "sp", "tp", None))
        return nn.DenseGeneral(
            features=d, axis=(-2, -1), dtype=cfg.dtype, name="out"
        )(out)


class TransformerLayer(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        y = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x)
        y = MultiHeadAttention(cfg, name="attn")(y, mask)
        x = x + y
        y = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
        y = nn.Dense(cfg.mlp_dim, dtype=cfg.dtype, name="mlp_up")(y)
        y = _maybe_shard(y, cfg, P("dp", "sp", "tp"))
        y = nn.gelu(y)
        y = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="mlp_down")(y)
        x = x + y
        return _maybe_shard(x, cfg, P("dp", "sp", None))


class Encoder(nn.Module):
    """Token ids -> L2-normalized sentence embedding."""

    cfg: EncoderConfig

    @nn.compact
    def __call__(
        self, token_ids: jnp.ndarray, attention_mask: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        cfg = self.cfg
        if attention_mask is None:
            attention_mask = (token_ids != 0)
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="tok_embed"
        )(token_ids)
        pos = jnp.arange(token_ids.shape[1])[None, :]
        x = x + nn.Embed(
            cfg.max_len, cfg.hidden_size, dtype=cfg.dtype, name="pos_embed"
        )(pos)
        x = _maybe_shard(x, cfg, P("dp", "sp", None))
        for i in range(cfg.num_layers):
            x = TransformerLayer(cfg, name=f"layer_{i}")(x, attention_mask)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
        # masked mean pooling
        m = attention_mask[:, :, None].astype(jnp.float32)
        pooled = jnp.sum(x.astype(jnp.float32) * m, axis=1) / jnp.maximum(
            jnp.sum(m, axis=1), 1.0
        )
        from nornicdb_tpu.ops.similarity import l2_normalize

        return l2_normalize(pooled)


def param_sharding_rules(cfg: EncoderConfig):
    """Logical->mesh partitioning for pjit: attention heads and MLP width
    over ``tp``, embeddings over ``tp`` on the hidden axis, everything else
    replicated. Applied by models.train.make_sharded_train_step."""

    def rule(path: str, value) -> P:
        if value.ndim == 1:
            return P()
        if "tok_embed" in path or "pos_embed" in path:
            return P(None, "tp")
        if "attn" in path and ("query" in path or "key" in path or "value" in path):
            if value.ndim == 3:
                return P(None, "tp", None)  # kernel [d, h, hd] — heads over tp
            return P("tp", None)  # bias [h, hd]
        if "attn" in path and "out" in path:
            if value.ndim == 3:
                return P("tp", None, None)  # kernel [h, hd, d]
            return P()
        if "mlp_up" in path and value.ndim == 2:
            return P(None, "tp")  # [d, 4d]
        if "mlp_down" in path and value.ndim == 2:
            return P("tp", None)  # [4d, d]
        return P(*([None] * value.ndim))

    return rule
