"""The controls have to come out as not correct, at a size a test run can
hold (the chip runs at the cells' own sizes are in PERF.md).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os

import numpy as np

from benchmark.lib import loader

ROOT = loader.ROOT


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_search_control_reads_above_the_limit_and_exact_below():
    from benchmark.systems.qdrant_collection import make_vectors

    cfg = _config("qdrant-bge-m3-2m")
    ref = loader.load_reference(cfg, ROOT)
    limit = 100
    vectors = make_vectors(11, 16384, 1024, 16, 1.0)
    rng = np.random.default_rng(12)
    rows = rng.integers(0, len(vectors), 16)
    queries = (vectors[rows] + np.float32(0.25 / 32.0)
               * rng.standard_normal((16, 1024), dtype=np.float32))
    # the program's place taken by float32 arithmetic as the index does it
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    scores = vectors @ qn.T
    ids, vals = [], []
    for i in range(len(queries)):
        col = scores[:, i]
        top = np.argsort(-col, kind="stable")[:limit]
        ids.append(top)
        vals.append(col[top].astype(np.float64))
    exact = ref.judge(vectors, queries, ids, vals, limit)
    assert exact["score_err"] <= cfg["limits"]["score_err_max"]
    assert exact["rank_gap"] <= cfg["limits"]["rank_gap_max"]
    c_ids, c_vals = ref.control_answers(vectors, queries, limit)
    control = ref.judge(vectors, queries, c_ids, c_vals, limit)
    assert control["score_err"] > cfg["limits"]["score_err_max"]
    # a row that does not belong is seen by rank_gap
    wrong = [a.copy() for a in ids]
    outsider = int(np.argmin(scores[:, 0]))
    wrong[0][-1] = outsider
    assert ref.judge(vectors, queries, wrong, vals, limit)["rank_gap"] \
        > cfg["limits"]["rank_gap_max"]


def test_encoder_control_reads_above_the_limit():
    cfg = _config("ingest-bge-m3")
    ref = loader.load_reference(cfg, ROOT)
    small = dict(cfg["rehearse"])
    params = ref.make_params(small, 7)
    rng = np.random.default_rng(8)
    id_lists = [[1] + rng.integers(2, small["vocab_size"], n).tolist()
                for n in (12, 90, 300)]
    exact = ref.embed(small, params, id_lists)
    assert np.allclose(np.linalg.norm(exact, axis=1), 1.0, atol=1e-5)
    assert ref.worst_distance(exact, exact) < 1e-6
    fp8 = ref.embed(small, params, id_lists, fp8=True)
    assert ref.worst_distance(fp8, exact) > cfg["limits"]["vector_dist_max"]


def test_tokenizer_matches_the_one_the_configuration_assumes():
    from nornicdb_tpu.embed.tokenizer import HashTokenizer

    cfg = _config("ingest-bge-m3")
    ref = loader.load_reference(cfg, ROOT)
    text = "w17 w4 Alpha-beta w99 Doc"
    assert ref.tokenize(text, 250002, 8192) \
        == HashTokenizer(250002).encode(text, max_len=8192)
    assert ref.tokenize("a b c d", 1024, 3) \
        == HashTokenizer(1024).encode("a b c d", max_len=3)
