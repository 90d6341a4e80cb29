"""Plain reference for the bge-m3-shaped (XLM-RoBERTa family) text encoder,
its parameters from the seed, and its control.

Imports nothing of the program. The forward pass follows the published
architecture as the configuration's file sizes it, pre-norm as the program
builds it: token + position embeddings; ``num_layers`` blocks of
LayerNorm -> multi-head attention (scaled dot product, padding masked) ->
residual, LayerNorm -> Dense -> GELU (tanh form) -> Dense -> residual; a
final LayerNorm; masked mean over the tokens; L2 norm. Everything in
float32 with matmul precision ``highest``, one document at a time at its
own padded width, the blocks run by ``lax.scan`` over stacked parameters so
that one small program per width serves all 24 layers.

Parameters are made on the device in one jitted call from the seed, in
float32 (the type the program holds them in; it computes in bfloat16):
kernels N(0, 1/fan_in), embeddings N(0, 1), biases and LayerNorm offsets
N(0, 0.02^2), LayerNorm scales 1 + N(0, 0.1^2).

Control (the nearest precision below the bfloat16 the configuration
states): the same forward pass with both operands of every matrix product
rounded to float8 (e4m3) first.
"""

from __future__ import annotations

import hashlib
import re
from typing import Any, Dict, List, Sequence

import numpy as np

PAD_ID = 0
CLS_ID = 1
_WORD = re.compile(r"[a-z0-9]+|[^\sa-z0-9]")


def tokenize(text: str, vocab_size: int, max_len: int) -> List[int]:
    """The hash tokenizer the configuration assumes: CLS, then one id per
    lower-cased run of letters and digits or single other character,
    blake2s(token) mod (vocab - 2) + 2, cut at ``max_len``."""
    ids = [CLS_ID]
    for tok in _WORD.findall(text.lower()):
        if len(ids) >= max_len:
            break
        h = int.from_bytes(
            hashlib.blake2s(tok.encode("utf-8"), digest_size=4).digest(),
            "little")
        ids.append(2 + h % (vocab_size - 2))
    return ids


def make_params(cfg: Dict[str, Any], seed: int):
    """The parameter tree, named as a flax module of this architecture
    names it, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    d, h = int(cfg["hidden_size"]), int(cfg["num_heads"])
    hd = d // h
    mlp, layers = int(cfg["mlp_dim"]), int(cfg["num_layers"])
    vocab, max_len = int(cfg["vocab_size"]), int(cfg["max_len"])

    def build(key):
        keys = iter(jax.random.split(key, 64))

        def normal(shape, std):
            return std * jax.random.normal(next(keys), shape, jnp.float32)

        def norm_pair(shape):
            return {"scale": 1.0 + normal(shape, 0.1),
                    "bias": normal(shape, 0.02)}

        stacked = {
            "ln1": norm_pair((layers, d)),
            "ln2": norm_pair((layers, d)),
            "attn": {
                name: {"kernel": normal((layers, d, h, hd), d ** -0.5),
                       "bias": normal((layers, h, hd), 0.02)}
                for name in ("query", "key", "value")},
            "mlp_up": {"kernel": normal((layers, d, mlp), d ** -0.5),
                       "bias": normal((layers, mlp), 0.02)},
            "mlp_down": {"kernel": normal((layers, mlp, d), mlp ** -0.5),
                         "bias": normal((layers, d), 0.02)},
        }
        stacked["attn"]["out"] = {
            "kernel": normal((layers, h, hd, d), d ** -0.5),
            "bias": normal((layers, d), 0.02)}
        tree = {"tok_embed": {"embedding": normal((vocab, d), 1.0)},
                "pos_embed": {"embedding": normal((max_len, d), 1.0)},
                "ln_final": {k: v[0] for k, v in norm_pair((1, d)).items()}}
        for i in range(layers):
            tree[f"layer_{i}"] = jax.tree_util.tree_map(
                lambda x, i=i: x[i], stacked)
        return tree

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31)))


def stack_layers(params, layers: int):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[params[f"layer_{i}"] for i in range(layers)])


def _layer_norm(x, p):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]


def _forward(cfg: Dict[str, Any], fp8: bool):
    """The jitted forward pass for one [1, W] row of ids."""
    import jax
    import jax.numpy as jnp

    d, h = int(cfg["hidden_size"]), int(cfg["num_heads"])
    hd = d // h

    def q8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if fp8 else x

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b), precision="highest")

    def block(x_mask, p):
        x, mask = x_mask
        y = _layer_norm(x, p["ln1"])
        a = p["attn"]
        q = mm("sd,dhk->shk", y, a["query"]["kernel"]) + a["query"]["bias"]
        k = mm("sd,dhk->shk", y, a["key"]["kernel"]) + a["key"]["bias"]
        v = mm("sd,dhk->shk", y, a["value"]["kernel"]) + a["value"]["bias"]
        logits = mm("qhk,shk->hqs", q, k) * hd ** -0.5
        logits = jnp.where(mask[None, None, :], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        o = mm("hqs,shk->qhk", w, v)
        x = x + mm("qhk,hkd->qd", o, a["out"]["kernel"]) + a["out"]["bias"]
        y = _layer_norm(x, p["ln2"])
        y = mm("sd,dm->sm", y, p["mlp_up"]["kernel"]) + p["mlp_up"]["bias"]
        y = jax.nn.gelu(y, approximate=True)
        y = mm("sm,md->sd", y, p["mlp_down"]["kernel"]) \
            + p["mlp_down"]["bias"]
        return (x + y, mask), None

    @jax.jit
    def forward(tok, pos, ln_final, stacked, ids):
        mask = ids != PAD_ID
        x = tok[ids] + pos[: ids.shape[0]]
        (x, _), _ = jax.lax.scan(block, (x, mask), stacked)
        x = _layer_norm(x, ln_final)
        m = mask[:, None].astype(jnp.float32)
        pooled = jnp.sum(x * m, axis=0) / jnp.maximum(jnp.sum(m), 1.0)
        return pooled / jnp.maximum(jnp.linalg.norm(pooled), 1e-12)

    return forward


def embed(cfg: Dict[str, Any], params, id_lists: Sequence[Sequence[int]],
          fp8: bool = False) -> np.ndarray:
    """[n, hidden] float32 unit vectors, one document at a time, each at
    the next power of two of its own length (padding masked)."""
    import jax.numpy as jnp

    layers = int(cfg["num_layers"])
    stacked = stack_layers(params, layers)
    forward = _forward(cfg, fp8)
    out = []
    for ids in id_lists:
        width = 16
        while width < len(ids):
            width *= 2
        row = np.zeros((width,), np.int32)
        row[: len(ids)] = ids
        out.append(np.asarray(forward(
            params["tok_embed"]["embedding"],
            params["pos_embed"]["embedding"], params["ln_final"], stacked,
            jnp.asarray(row))))
    return np.stack(out)


def worst_distance(served: np.ndarray, reference: np.ndarray) -> float:
    """The largest Euclidean distance between a served unit vector and the
    reference's for the same document."""
    s = served / np.linalg.norm(served, axis=1, keepdims=True)
    return float(np.max(np.linalg.norm(s - reference, axis=1)))
