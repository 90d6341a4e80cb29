"""Drives a whole run (the look for a chip skipped: ``--rehearse``) with the
timed path broken underneath, and sees ``correct`` come out false; and
unbroken, true. The fault a serving cell can have: an answer altered where
it is produced.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import io
import json
from contextlib import redirect_stdout

import numpy as np
import pytest


def _run(cell, seed):
    from benchmark import run as bench_run

    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                             "--seconds", "2", "--trace", "0",
                             "--rehearse"])
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["metrics"] == {} and result["rehearsal"] is True
    return result


def test_search_unbroken_is_correct():
    result = _run("vec2m-c32", 2147483649)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("fault", ["score", "row"])
def test_search_answer_altered_is_not_correct(monkeypatch, fault):
    from nornicdb_tpu.search.vector_index import BruteForceIndex

    inner = BruteForceIndex.search_batch

    def altered(self, queries, k=10, exact=False):
        out = inner(self, queries, k, exact)
        for hits in out:
            if len(hits) > 3:
                if fault == "score":      # a score a thousandth off
                    hits[2] = (hits[2][0], hits[2][1] - 1e-3)
                else:                     # a row that does not belong
                    stranger = next(e for e in self._ext_ids
                                    if e is not None
                                    and e not in {h[0] for h in hits})
                    hits[2] = (stranger, hits[2][1])
        return out

    monkeypatch.setattr(BruteForceIndex, "search_batch", altered)
    result = _run("vec2m-c32", 2147483650)
    assert result["correct"] is False
    failed = {k for k, c in result["checks"].items()
              if not c["value"] <= c["limit"]}
    assert failed & {"score_err_max", "rank_gap_max"}, result["checks"]


def test_ingest_unbroken_is_correct():
    result = _run("ingest-bulk-4k", 2147483651)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_ingest_vector_altered_is_not_correct(monkeypatch):
    from nornicdb_tpu.embed.embedder import JaxEncoderEmbedder

    inner = JaxEncoderEmbedder._run

    def altered(self, id_lists):
        out = np.array(inner(self, id_lists))
        out[:, 0] += 0.5                  # every vector bent one way
        return out

    monkeypatch.setattr(JaxEncoderEmbedder, "_run", altered)
    result = _run("ingest-bulk-4k", 2147483652)
    assert result["correct"] is False
    assert result["checks"]["vector_dist_max"]["value"] \
        > result["checks"]["vector_dist_max"]["limit"]
