"""The hybrid cell's control has to come out as not correct, and so has a
whole rehearsed run with the timed path broken underneath: a passage that
belongs left out, a hit's lexical row joined to the wrong vector slot, the
fuse at k 10 where the configuration says 60. Unbroken, it is correct.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import io
import json
import os
from contextlib import redirect_stdout

import numpy as np
import pytest

from benchmark.lib import loader

ROOT = loader.ROOT
CELL = "hybrid1m-c32"


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "hybrid-native-1m.json"), encoding="utf-8") as f:
        return json.load(f)


def _run(seed, control=None):
    from benchmark import run as bench_run

    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "2",
            "--trace", "0", "--rehearse"]
    if control:
        argv += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        assert bench_run.main(argv) == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["metrics"] == {} and result["rehearsal"] is True
    return result


def _failed(result):
    return {k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]}


def test_unbroken_is_correct():
    result = _run(2147483701)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_the_control_is_not_correct():
    result = _run(2147483702, control=_config()["control"])
    assert result["correct"] is False
    assert _failed(result) & {"vector_dist_max", "vec_score_err_max",
                              "lex_score_err_max"}, result["checks"]


@pytest.mark.parametrize("fault", ["left_out", "wrong_join", "rrf_k_10"])
def test_a_broken_fuse_is_not_correct(monkeypatch, fault):
    from nornicdb_tpu.search.hybrid_fused import FusedHybrid
    from nornicdb_tpu.search.service import SearchService

    if fault == "left_out":        # the best fused passage, not served
        inner = SearchService._fused_hybrid_trio

        def trio(self, *a, **kw):
            out = inner(self, *a, **kw)
            if out is not None and len(out["fused"]) > 12:
                out = dict(out, fused=out["fused"][1:])
            return out

        monkeypatch.setattr(SearchService, "_fused_hybrid_trio", trio)
    elif fault == "wrong_join":    # lexical row r joined to r + 1's slot
        inner = FusedHybrid._ensure_map

        def shifted(self, snap, mutations):
            import jax.numpy as jnp

            l2v = inner(self, snap, mutations)
            return None if l2v is None else jnp.roll(l2v, 1)

        monkeypatch.setattr(FusedHybrid, "_ensure_map", shifted)
    else:                          # reciprocal ranks from 10, not from 60
        inner = FusedHybrid.__init__

        def init(self, *a, **kw):
            inner(self, *a, **kw)
            self.rrf_k = 10

        monkeypatch.setattr(FusedHybrid, "__init__", init)
    result = _run(2147483703)
    assert result["correct"] is False
    assert _failed(result) & {"fused_gap_max", "fused_score_err_max"}, \
        result["checks"]


def test_the_reference_alone_judges_its_own_answers_correct():
    """The plain implementation served in the program's place reads 0
    everywhere; its low-precision twin passes the limits the
    configuration sets."""
    cfg = _config()
    ref = loader.load_reference(cfg, ROOT)
    rng = np.random.default_rng(5)
    words = [f"w{i:x}" for i in range(65, 65 + 500)]
    texts = [" ".join(rng.choice(words, int(rng.integers(10, 40))))
             + " Passage" for _ in range(3000)]
    vectors = rng.standard_normal((3000, 32)).astype(np.float32)
    queries = [" ".join(rng.choice(words, 4, replace=False))
               for _ in range(8)]
    qv = rng.standard_normal((8, 32)).astype(np.float32)
    lex = ref.Lexical(texts, [w for q in queries for w in ref.tokens(q)])
    args = (1.2, 0.75, 60.0, 30, 10)
    lim = cfg["limits"]
    tol = (lim["lex_score_err_max"], lim["vec_score_err_max"])
    exact = ref.judge(lex, vectors, queries, qv,
                      ref.answers(lex, vectors, queries, qv, *args),
                      *args, *tol)
    assert exact["fused_gap"] == 0.0 and exact["fused_score_err"] < 1e-12
    assert exact["lex_err_max"] < 1e-12 and exact["vec_err_max"] < 2e-6
    low = ref.judge(lex, vectors, queries, qv,
                    ref.answers(lex, vectors, queries, qv, *args,
                                low_precision=True), *args, *tol)
    assert low["lex_err_max"] > lim["lex_score_err_max"]
    assert low["vec_err_max"] > lim["vec_score_err_max"]
    # the published token rule, on what a generated passage never holds
    assert ref.tokens("The Quick-brown fox's 1st a I x " + "z" * 41) \
        == ["quick", "brown", "fox", "1st"]
    from nornicdb_tpu.search.bm25 import tokenize

    for text in ("Ünïcode café 42", "to be or not to be", texts[0]):
        assert ref.tokens(text) == tokenize(text)
