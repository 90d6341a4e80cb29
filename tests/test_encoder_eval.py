"""Committed-checkpoint search quality gate.

The reference ships trained bge-m3 weights and gates quality with JSONL
eval suites (pkg/eval/harness.go:175-272, cmd/eval). Equivalent here:
the committed mini encoder (models/checkpoints/encoder_mini.npz, trained
by models/pretrain.py) must clear quality gates on the committed suite:

1. absolute thresholds with headroom over the measured band of the
   r4 training recipe (topic-grouped cross-document positives,
   asymmetric query/document windows, symmetric InfoNCE — best
   checkpoints measure MRR ~0.80-0.88, recall ~0.40-0.42; the r3 gate
   values of 0.5/0.5/0.75 were committed without a passing run and are
   replaced by these measured-with-margin floors);
2. trained must beat a RANDOM-INIT encoder of the same shape by a wide
   MRR margin — training carries signal, not just architecture (the r3
   failure mode: committed weights scored BELOW random);
3. trained must beat the purely LEXICAL HashEmbedder on recall —
   the semantic encoder must retrieve same-topic documents lexical
   overlap alone cannot."""

import json
import os

import numpy as np
import pytest

from nornicdb_tpu.eval import EvalHarness, Thresholds
from nornicdb_tpu.models.pretrain import (
    default_checkpoint_path,
    load_checkpoint,
    load_default_embedder,
)

DATA = os.path.join(os.path.dirname(__file__), "data")
DOCS = os.path.join(DATA, "encoder_eval_docs.jsonl")
SUITE = os.path.join(DATA, "encoder_eval.jsonl")


def _load_docs():
    docs = []
    with open(DOCS, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line and not line.startswith("#"):
                docs.append(json.loads(line))
    return docs


def _harness_over(embedder, thresholds):
    docs = _load_docs()
    ids = [d["id"] for d in docs]
    mat = np.asarray(
        embedder.embed_batch([d["text"] for d in docs]), np.float32
    )
    mat /= np.maximum(np.linalg.norm(mat, axis=1, keepdims=True), 1e-12)

    def search_fn(query, limit):
        q = np.asarray(embedder.embed(query), np.float32)
        q /= max(float(np.linalg.norm(q)), 1e-12)
        order = np.argsort(-(mat @ q))[:limit]
        return [ids[i] for i in order]

    return EvalHarness(search_fn, thresholds)


@pytest.fixture(scope="module")
def trained():
    emb = load_default_embedder()
    if emb is None:
        pytest.fail("committed encoder checkpoint missing "
                    "(models/checkpoints/encoder_mini.npz)")
    return emb


def test_checkpoint_is_committed_and_small():
    path = default_checkpoint_path()
    assert path is not None
    assert os.path.getsize(path) < 8_000_000, "checkpoint too big for git"


def test_trained_encoder_clears_thresholds(trained):
    # floors sit ~15-30% under the measured band of the committed
    # checkpoint (see module docstring); a regression in pretraining
    # or the embedder path drops below them
    result = _harness_over(
        trained,
        Thresholds(precision=0.30, recall=0.30, mrr=0.70),
    ).run_file(SUITE)
    summary = result.to_dict()
    assert result.passed, summary


def test_trained_beats_lexical_hash_on_recall(trained):
    """Semantic value-add gate: the trained encoder must retrieve
    same-topic documents that pure lexical overlap cannot (the hash
    embedder measures ~0.34 recall on this suite)."""
    from nornicdb_tpu.embed.embedder import HashEmbedder

    loose = Thresholds(precision=0.0, recall=0.0, mrr=0.0)
    trained_res = _harness_over(trained, loose).run_file(SUITE)
    hash_res = _harness_over(HashEmbedder(), loose).run_file(SUITE)
    assert trained_res.recall > hash_res.recall, (
        trained_res.to_dict(), hash_res.to_dict(),
    )


def test_trained_beats_random_init(trained):
    """The committed weights must carry learned signal: same shape,
    random params, same tokenizer — quality should collapse."""
    from nornicdb_tpu.embed.embedder import JaxEncoderEmbedder
    from nornicdb_tpu.models.encoder import Encoder

    cfg, _ = load_checkpoint(default_checkpoint_path())
    random_emb = JaxEncoderEmbedder(model=Encoder(cfg), cfg=cfg, seed=123)
    loose = Thresholds(precision=0.0, recall=0.0, mrr=0.0)
    trained_res = _harness_over(trained, loose).run_file(SUITE)
    random_res = _harness_over(random_emb, loose).run_file(SUITE)
    assert trained_res.mrr > random_res.mrr + 0.1, (
        trained_res.to_dict(), random_res.to_dict(),
    )
    assert trained_res.recall > random_res.recall


def test_db_default_embedder_is_trained_encoder(monkeypatch):
    """db.open() without an explicit embedder uses the committed
    checkpoint (reference default: local embeddings always on,
    embed.go; here the committed mini encoder plays bge-m3's role)."""
    import nornicdb_tpu
    from nornicdb_tpu.embed.embedder import CachedEmbedder, JaxEncoderEmbedder

    # other test files of the same worker force the hash embedder for
    # the whole process (os.environ.setdefault)
    monkeypatch.delenv("NORNICDB_TPU_EMBEDDER", raising=False)
    db = nornicdb_tpu.open(auto_embed=False)
    try:
        emb = db._embedder
        assert isinstance(emb, CachedEmbedder)
        assert isinstance(emb.inner, JaxEncoderEmbedder)
    finally:
        db.close()
