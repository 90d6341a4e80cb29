"""Real-weight import path: HuggingFace encoder checkpoints → flax.

The reference ships working bge-m3 inference over vendored llama.cpp
(pkg/embed/local_gguf.go:57,100 LocalGGUFEmbedder). This image has no
network, so real bge-m3 weights are unreachable — but the import path
must exist so that the day a checkpoint IS reachable it is "drop in
weights, done". This module provides:

- ``HFEncoder``: a flax module that reproduces the BERT/RoBERTa
  (XLM-R = RoBERTa arch, bge-m3's backbone) computation graph exactly
  — post-LayerNorm blocks, token-type embeddings, erf GELU, RoBERTa's
  pad-offset position ids — so imported weights produce the same
  embeddings the published model does (validated numerically against
  ``transformers``' torch implementation in
  tests/test_hf_import.py).
- ``import_hf_params``: state-dict name mapping (works for
  ``bert.*`` / ``roberta.*`` / bare prefixes, safetensors or
  torch .bin or npz).
- ``load_hf_model_dir``: one-call load of a local HF model directory
  (config.json + model.safetensors [+ tokenizer files]).
- ``HFEncoderEmbedder``: embed_batch over the imported model with the
  model's own tokenizer (AutoTokenizer from local files; never
  downloads).

Set ``NORNICDB_TPU_MODEL_DIR=/path/to/model`` to make an imported
model the DB's default embedder (db.DB._default_embedder checks
``default_model_dir()`` ahead of the committed mini encoder;
``NORNICDB_TPU_EMBEDDER=hash`` still force-overrides everything).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class HFEncoderConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    intermediate_size: int
    max_position_embeddings: int
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    pad_token_id: int = 0
    # 'bert' = arange position ids; 'roberta' (XLM-R, bge-m3 backbone) =
    # cumsum-of-mask ids offset past the padding idx
    arch: str = "bert"
    pooling: str = "mean"  # 'mean' | 'cls'
    dtype: Any = jnp.float32

    @staticmethod
    def from_hf_config(cfg: Dict[str, Any]) -> "HFEncoderConfig":
        model_type = cfg.get("model_type", "bert")
        arch = "roberta" if model_type in (
            "roberta", "xlm-roberta", "camembert") else "bert"
        return HFEncoderConfig(
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=int(cfg["hidden_size"]),
            num_layers=int(cfg["num_hidden_layers"]),
            num_heads=int(cfg["num_attention_heads"]),
            intermediate_size=int(cfg["intermediate_size"]),
            max_position_embeddings=int(cfg["max_position_embeddings"]),
            type_vocab_size=int(cfg.get("type_vocab_size", 2)),
            layer_norm_eps=float(cfg.get("layer_norm_eps", 1e-12)),
            pad_token_id=int(cfg.get("pad_token_id", 0) or 0),
            arch=arch,
        )


class HFEncoder(nn.Module):
    """BERT/RoBERTa-faithful encoder: token ids -> pooled embedding.

    Post-LN residual blocks (unlike models.encoder.Encoder, which is
    pre-LN by design for from-scratch TPU training) — faithfulness is
    the point here: published weights assume this exact graph."""

    cfg: HFEncoderConfig

    @nn.compact
    def __call__(
        self,
        token_ids: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        cfg = self.cfg
        if attention_mask is None:
            attention_mask = (token_ids != cfg.pad_token_id)
        mask = attention_mask.astype(jnp.int32)
        if cfg.arch == "roberta":
            # RoBERTa position ids: running count of non-pad tokens,
            # shifted past the padding index (HF create_position_ids_
            # from_input_ids semantics)
            positions = jnp.cumsum(mask, axis=1) * mask + cfg.pad_token_id
        else:
            positions = jnp.broadcast_to(
                jnp.arange(token_ids.shape[1])[None, :], token_ids.shape
            )
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="tok_embed")(token_ids)
        x = x + nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                         dtype=cfg.dtype, name="pos_embed")(positions)
        x = x + nn.Embed(max(cfg.type_vocab_size, 1), cfg.hidden_size,
                         dtype=cfg.dtype, name="type_embed")(
            jnp.zeros_like(token_ids))
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="emb_ln")(x)
        neg = jnp.finfo(jnp.float32).min
        bias = jnp.where(attention_mask[:, None, None, :], 0.0, neg)
        head_dim = cfg.hidden_size // cfg.num_heads
        scale = head_dim ** -0.5
        for i in range(cfg.num_layers):
            pre = f"layer_{i}"
            q = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         name=f"{pre}_q")(x)
            k = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         name=f"{pre}_k")(x)
            v = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         name=f"{pre}_v")(x)

            def heads(t):
                return t.reshape(t.shape[0], t.shape[1],
                                 cfg.num_heads, head_dim)

            logits = jnp.einsum("bqhd,bkhd->bhqk", heads(q), heads(k))
            logits = logits * scale + bias
            w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            a = jnp.einsum("bhqk,bkhd->bqhd", w.astype(cfg.dtype), heads(v))
            a = a.reshape(a.shape[0], a.shape[1], cfg.hidden_size)
            a = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         name=f"{pre}_o")(a)
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                             name=f"{pre}_attn_ln")(x + a)
            m = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                         name=f"{pre}_mlp_up")(x)
            m = nn.gelu(m, approximate=False)  # HF 'gelu' is erf-based
            m = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         name=f"{pre}_mlp_down")(m)
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                             name=f"{pre}_mlp_ln")(x + m)
        if cfg.pooling == "cls":
            pooled = x[:, 0, :].astype(jnp.float32)
        else:
            m = attention_mask[:, :, None].astype(jnp.float32)
            pooled = jnp.sum(x.astype(jnp.float32) * m, axis=1) / jnp.maximum(
                jnp.sum(m, axis=1), 1.0)
        from nornicdb_tpu.ops.similarity import l2_normalize

        return l2_normalize(pooled)


# -- state-dict import -----------------------------------------------------

_PREFIXES = ("bert.", "roberta.", "model.", "encoder.model.", "")


def _strip_prefix(names: Sequence[str]) -> str:
    for pre in _PREFIXES:
        if pre and sum(1 for n in names if n.startswith(pre)) > len(names) // 2:
            return pre
    return ""


def import_hf_params(
    tensors: Dict[str, np.ndarray], cfg: HFEncoderConfig
) -> Dict[str, Any]:
    """Map a HF BERT/RoBERTa state dict onto HFEncoder's param tree.

    ``tensors``: name -> array (from safetensors, torch .bin, or npz).
    Raises KeyError with the missing HF name when the checkpoint does
    not cover the config's shape."""
    pre = _strip_prefix(list(tensors))

    def t(name: str) -> np.ndarray:
        full = pre + name
        if full not in tensors:
            raise KeyError(f"checkpoint missing tensor {full!r}")
        return np.asarray(tensors[full], np.float32)

    def dense(hf: str) -> Dict[str, np.ndarray]:
        # torch Linear stores [out, in]; flax Dense kernels are [in, out]
        return {"kernel": t(hf + ".weight").T, "bias": t(hf + ".bias")}

    def ln(hf: str) -> Dict[str, np.ndarray]:
        return {"scale": t(hf + ".weight"), "bias": t(hf + ".bias")}

    params: Dict[str, Any] = {
        "tok_embed": {"embedding": t("embeddings.word_embeddings.weight")},
        "pos_embed": {
            "embedding": t("embeddings.position_embeddings.weight")},
        "type_embed": {
            "embedding": (
                t("embeddings.token_type_embeddings.weight")
                if pre + "embeddings.token_type_embeddings.weight" in tensors
                else np.zeros((max(cfg.type_vocab_size, 1), cfg.hidden_size),
                              np.float32))},
        "emb_ln": ln("embeddings.LayerNorm"),
    }
    for i in range(cfg.num_layers):
        hf = f"encoder.layer.{i}"
        params[f"layer_{i}_q"] = dense(f"{hf}.attention.self.query")
        params[f"layer_{i}_k"] = dense(f"{hf}.attention.self.key")
        params[f"layer_{i}_v"] = dense(f"{hf}.attention.self.value")
        params[f"layer_{i}_o"] = dense(f"{hf}.attention.output.dense")
        params[f"layer_{i}_attn_ln"] = ln(f"{hf}.attention.output.LayerNorm")
        params[f"layer_{i}_mlp_up"] = dense(f"{hf}.intermediate.dense")
        params[f"layer_{i}_mlp_down"] = dense(f"{hf}.output.dense")
        params[f"layer_{i}_mlp_ln"] = ln(f"{hf}.output.LayerNorm")
    return params


def read_checkpoint_tensors(path: str) -> Dict[str, np.ndarray]:
    """Load name->array from .safetensors, torch .bin/.pt, or .npz."""
    if path.endswith(".safetensors"):
        from safetensors.numpy import load_file

        return dict(load_file(path))
    if path.endswith(".npz"):
        return {k: v for k, v in np.load(path).items()}
    # torch pickle (weights_only=True: no arbitrary code execution)
    import torch

    sd = torch.load(path, map_location="cpu", weights_only=True)
    if hasattr(sd, "state_dict"):
        sd = sd.state_dict()
    return {k: v.detach().cpu().numpy() for k, v in sd.items()}


_WEIGHT_FILES = (
    "model.safetensors", "pytorch_model.bin", "model.npz",
)


def load_hf_model_dir(model_dir: str, pooling: str = "mean"):
    """(cfg, params) from a local HF model directory."""
    with open(os.path.join(model_dir, "config.json"), encoding="utf-8") as f:
        cfg = HFEncoderConfig.from_hf_config(json.load(f))
    if pooling != cfg.pooling:
        import dataclasses

        cfg = dataclasses.replace(cfg, pooling=pooling)
    for fname in _WEIGHT_FILES:
        path = os.path.join(model_dir, fname)
        if os.path.exists(path):
            tensors = read_checkpoint_tensors(path)
            return cfg, import_hf_params(tensors, cfg)
    raise FileNotFoundError(
        f"no weight file in {model_dir!r} (looked for {_WEIGHT_FILES})")


class HFEncoderEmbedder:
    """embed/embed_batch over an imported HF encoder, using the model's
    own tokenizer (AutoTokenizer over LOCAL files only — never
    downloads). Drop-in for the Embedder protocol (embed/embedder.py)."""

    def __init__(self, model_dir: str, pooling: str = "mean",
                 max_batch: int = 16, max_len: int = 512):
        import threading

        cfg, params = load_hf_model_dir(model_dir, pooling=pooling)
        self.cfg = cfg
        # the importer returns a NumPy tree: place it on the device once
        # instead of shipping every weight with every call
        self.params = jax.device_put(params)
        self.model = HFEncoder(cfg)
        self.dims = cfg.hidden_size
        self.max_batch = max_batch
        self.max_len = min(max_len, cfg.max_position_embeddings - 2)
        from transformers import AutoTokenizer

        self.tokenizer = AutoTokenizer.from_pretrained(
            model_dir, local_files_only=True)
        self._jit = jax.jit(
            lambda p, ids, m: self.model.apply({"params": p}, ids, m))
        self._lock = threading.Lock()

    def embed_batch(self, texts: Sequence[str]) -> List[List[float]]:
        out: List[List[float]] = []
        for start in range(0, len(texts), self.max_batch):
            chunk = list(texts[start:start + self.max_batch])
            enc = self.tokenizer(
                chunk, padding=True, truncation=True,
                max_length=self.max_len, return_tensors="np")
            ids = enc["input_ids"].astype(np.int32)
            mask = enc["attention_mask"].astype(bool)
            with self._lock:
                vecs = self._jit(self.params, jnp.asarray(ids),
                                 jnp.asarray(mask))
            out.extend(np.asarray(vecs, np.float32).tolist())
        return out

    def embed(self, text: str) -> List[float]:
        return self.embed_batch([text])[0]


def default_model_dir() -> Optional[str]:
    """NORNICDB_TPU_MODEL_DIR when it points at a loadable model dir."""
    d = os.environ.get("NORNICDB_TPU_MODEL_DIR", "")
    if d and os.path.exists(os.path.join(d, "config.json")):
        return d
    return None
