"""Embedder implementations.

Reference: pkg/embed — ``Embedder`` interface (embed.go:71), the local
GGUF/llama.cpp provider (local_gguf.go:57) with crash recovery, and the
cached decorator (cached_embedder.go). The TPU-native local provider is
``JaxEncoderEmbedder``: the flax encoder jitted once per power-of-two
(batch, width) bucket, so the compile universe is log2-sized on both axes.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from typing import List, Optional, Protocol, Sequence, Set, Tuple

import numpy as np

from nornicdb_tpu.embed.tokenizer import CHUNK_OVERLAP, CHUNK_SIZE, HashTokenizer, chunk_tokens
from nornicdb_tpu.obs import REGISTRY, declare_kind, record_dispatch
from nornicdb_tpu.obs import span as _span

logger = logging.getLogger(__name__)

# the encoder forward as a dispatch kind: b = rows, k = width of the
# padded id array the device is given
declare_kind("encoder")
_TOKENS_C = REGISTRY.counter(
    "nornicdb_embed_tokens_total",
    "Tokens handed to the encoder forward: real (the id lists' lengths) "
    "and padded (rows x width of the array the device is given)",
    labels=("kind",))
_PARAM_BYTES_G = REGISTRY.gauge(
    "nornicdb_embed_param_bytes",
    "Device bytes of the encoder's parameters: held (the tree the caller "
    "handed) and forward (the tree the jitted forward is given; smaller "
    "where the encoder computes in a narrower dtype than it stores)",
    labels=("tree",))

# a batch of FULL_BATCH_ROWS rows or more is never narrower than
# FULL_BATCH_MIN_WIDTH (``JaxEncoderEmbedder._run`` says why); 16 is the
# embed queue's batch, and the queue seals by the same floor
FULL_BATCH_ROWS = 16
FULL_BATCH_MIN_WIDTH = 256


def width_bucket(tokens: int) -> int:
    """The power-of-two width an id list of ``tokens`` ids is padded to
    (``ops.similarity.pow2_bucket`` floored at 16, without importing JAX:
    the embed queue seals by it)."""
    return max(16, 1 << max(tokens - 1, 0).bit_length())


def _working_copy(params, dtype):
    """The tree the forward is handed: every leaf of two or more
    dimensions (the tables, the kernels, the attention's [h, hd] biases)
    in ``dtype``, the rounding flax's ``promote_dtype`` would otherwise
    repeat inside every call; 1-D leaves (LayerNorm, the Dense biases)
    as they are."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if x.ndim >= 2 else x, params)


def _tree_bytes(tree) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


class Embedder(Protocol):
    dims: int

    def embed(self, text: str) -> List[float]: ...

    def embed_batch(self, texts: Sequence[str]) -> List[List[float]]: ...


class HashEmbedder:
    """Deterministic, dependency-free embedder (test double + offline
    default). Token-hash bag-of-features, L2-normalized — similar texts
    share tokens, so cosine behaves sensibly."""

    def __init__(self, dims: int = 256):
        self.dims = dims
        self._tok = HashTokenizer(vocab_size=1 << 22)

    def embed(self, text: str) -> List[float]:
        v = np.zeros(self.dims, dtype=np.float32)
        ids = self._tok.encode(text, max_len=4096)[1:]  # drop CLS
        for tid in ids:
            v[tid % self.dims] += 1.0
            v[(tid >> 8) % self.dims] += 0.5
        n = np.linalg.norm(v)
        if n > 1e-12:
            v /= n
        return v.tolist()

    def embed_batch(self, texts: Sequence[str]) -> List[List[float]]:
        return [self.embed(t) for t in texts]


class JaxEncoderEmbedder:
    """Local TPU embedder over the flax encoder.

    - ``params`` is what the caller handed, placed on the device once,
      at construction, in the dtype it came in (float32 from every
      loader); the jitted forward is given ``_forward_params``: the same
      tree with its matrices cast ONCE to ``cfg.dtype``, the dtype the
      modules compute in, so no call converts a table or reads a
      float32 kernel again. Where ``cfg.dtype`` is the leaves' own (a
      float32 configuration) the two are one tree;
    - pads token widths AND the batch dimension to power-of-two buckets
      (jit cache stays small; pad rows are dropped);
    - batches up to ``max_batch`` texts per device call;
    - ``embed_batch`` runs a whole document at its full width, up to
      ``cfg.max_len`` tokens (the whole-document vector); ``embed_chunks``
      gives a long document's 512/50 windows a vector each, a second
      product (reference ChunkEmbeddings, db.go:224);
    - a batch of 16 rows or more is at least 256 wide (``_run``).
    """

    def __init__(
        self,
        model=None,
        params=None,
        cfg=None,
        max_batch: int = 64,
        seed: int = 0,
    ):
        import jax

        from nornicdb_tpu.models.encoder import Encoder, EncoderConfig

        if cfg is None:
            from nornicdb_tpu.models.encoder import flash_attention_enabled

            cfg = EncoderConfig(
                use_flash_attention=flash_attention_enabled())
        if model is None:
            model = Encoder(cfg)
        if params is None:
            params = model.init(
                jax.random.PRNGKey(seed),
                np.ones((1, 8), np.int32),
            )["params"]
        self.cfg = cfg
        self.model = model
        # checkpoint loaders hand back NumPy trees; left on the host,
        # every call would ship every weight to the device again
        self.params = jax.device_put(params)
        dtype = np.dtype(cfg.dtype)
        self._forward_params = self.params
        if any(x.ndim >= 2 and x.dtype != dtype
               for x in jax.tree_util.tree_leaves(self.params)):
            # one named program, run here: set-up pays the cast once
            self._forward_params = jax.jit(
                _working_copy, static_argnums=1)(self.params, dtype)
        _PARAM_BYTES_G.labels("held").set(_tree_bytes(self.params))
        _PARAM_BYTES_G.labels("forward").set(
            _tree_bytes(self._forward_params))
        self.dims = cfg.hidden_size
        self.max_batch = max_batch
        self.tokenizer = HashTokenizer(cfg.vocab_size)
        self._jit = jax.jit(
            lambda p, ids: model.apply({"params": p}, ids)
        )
        self._lock = threading.Lock()
        # every (batch, width) shape dispatched so far == the programs
        # this embedder has made XLA compile
        self.shapes: Set[Tuple[int, int]] = set()

    _bucket_width = staticmethod(width_bucket)

    def _run(self, id_lists: List[List[int]]) -> np.ndarray:
        import jax.numpy as jnp

        from nornicdb_tpu.ops.similarity import pow2_bucket

        n = len(id_lists)
        width = self._bucket_width(max(len(x) for x in id_lists))
        rows = pow2_bucket(n)
        if rows >= FULL_BATCH_ROWS:
            # a rule on the array about to be dispatched, which is all
            # this method sees: a full batch (the embed queue's 16 rows)
            # never takes a program narrower than 256. Under that width a
            # 16-row pass is bound by reading the float32 weights and
            # converting the token table, so (16,128) or (16,64) would
            # save a few ms a batch and each costs a whole-depth compile
            # the first time a queue sealed by length holds sixteen short
            # documents. Fewer rows (a query's (1,16), a document's
            # (r,512) chunks) keep their own width.
            width = max(width, FULL_BATCH_MIN_WIDTH)
        width = min(width, self.cfg.max_len)
        arr = np.zeros((rows, width), np.int32)
        real = 0
        for i, ids in enumerate(id_lists):
            ids = ids[:width]
            arr[i, : len(ids)] = ids
            real += len(ids)
        arr[n:] = arr[0]  # pad rows repeat row 0 (no all-masked rows)
        try:
            # one timing for the span and the dispatch record: from
            # asking for this embedder's lock to the vectors on the host
            # (the jitted call returns at enqueue; np.asarray waits)
            t0 = time.perf_counter()
            with _span("encoder.forward", rows=rows, width=width):
                with self._lock:
                    self.shapes.add(arr.shape)
                    out = self._jit(self._forward_params, jnp.asarray(arr))
                out = np.asarray(out, dtype=np.float32)
            record_dispatch("encoder", rows, width,
                            time.perf_counter() - t0)
            _TOKENS_C.labels("real").inc(real)
            _TOKENS_C.labels("padded").inc(rows * width)
        except Exception:
            # the einsum attention arm materialises [B, heads, S, S]; a
            # batch too large for HBM must name its shape, not vanish
            logger.error("encoder forward failed at (batch, width)=%s",
                         arr.shape)
            raise
        return out[:n]

    def embed_batch(self, texts: Sequence[str]) -> List[List[float]]:
        out: List[List[float]] = []
        for start in range(0, len(texts), self.max_batch):
            batch = texts[start : start + self.max_batch]
            id_lists = [
                self.tokenizer.encode(t, max_len=self.cfg.max_len) for t in batch
            ]
            vecs = self._run(id_lists)
            out.extend(v.tolist() for v in vecs)
        return out

    def embed(self, text: str) -> List[float]:
        return self.embed_batch([text])[0]

    def embed_chunks(self, text: str) -> List[List[float]]:
        """Per-chunk embeddings for long documents (512/50 windows)."""
        ids = self.tokenizer.encode(text, max_len=1_000_000)
        chunks = chunk_tokens(
            ids, min(CHUNK_SIZE, self.cfg.max_len), CHUNK_OVERLAP
        )
        vecs: List[List[float]] = []
        for start in range(0, len(chunks), self.max_batch):
            vecs.extend(
                v.tolist() for v in self._run(chunks[start : start + self.max_batch])
            )
        return vecs


class CachedEmbedder:
    """LRU cache decorator (reference: cached_embedder.go)."""

    def __init__(self, inner: Embedder, capacity: int = 10_000):
        self.inner = inner
        self.capacity = capacity
        self.dims = inner.dims
        self._cache: "OrderedDict[str, List[float]]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        # expose the inner chunk path (uncached: chunk texts rarely repeat)
        if hasattr(inner, "embed_chunks"):
            self.embed_chunks = inner.embed_chunks

    def embed(self, text: str) -> List[float]:
        with self._lock:
            if text in self._cache:
                self._cache.move_to_end(text)
                self.hits += 1
                return list(self._cache[text])
        v = self.inner.embed(text)
        with self._lock:
            self.misses += 1
            self._cache[text] = list(v)
            while len(self._cache) > self.capacity:
                self._cache.popitem(last=False)
        return v

    def embed_batch(self, texts: Sequence[str]) -> List[List[float]]:
        with self._lock:
            # dedupe: repeated texts must cost one device call, not N
            missing = list(dict.fromkeys(t for t in texts if t not in self._cache))
        if missing:
            fresh = self.inner.embed_batch(missing)
            with self._lock:
                self.misses += len(missing)
                for t, v in zip(missing, fresh):
                    self._cache[t] = list(v)
                while len(self._cache) > self.capacity:
                    self._cache.popitem(last=False)
        out = []
        with self._lock:
            for t in texts:
                v = self._cache.get(t)
                if v is None:  # evicted between batches; recompute
                    v = self.inner.embed(t)
                else:
                    self._cache.move_to_end(t)
                out.append(list(v))
        return out
