"""The tree the encoder's jitted forward is handed (ISSUE 36).

``JaxEncoderEmbedder`` keeps what the caller handed (``params``, float32
from every loader) and gives its forward a working copy whose matrices
are cast once, at construction, to the dtype the modules compute in.
CPU, tiny widths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nornicdb_tpu import obs
from nornicdb_tpu.embed import embedder as embedder_mod
from nornicdb_tpu.embed.embedder import JaxEncoderEmbedder
from nornicdb_tpu.models.encoder import Encoder, EncoderConfig

LAYER_NORMS = ("ln1", "ln2", "ln_final")
TEXTS = ["w1 w2 w3", "the capital of norway is oslo",
         " ".join(f"w{i}" for i in range(40))]


def tiny(dtype) -> EncoderConfig:
    return EncoderConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                         num_heads=4, mlp_dim=128, max_len=128, dtype=dtype)


def handed(cfg: EncoderConfig):
    return Encoder(cfg).init(jax.random.PRNGKey(3),
                             np.ones((1, 8), np.int32))["params"]


def leaves_by_path(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(tree)}


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def param_bytes(tree_label: str) -> float:
    return obs.REGISTRY.get(
        "nornicdb_embed_param_bytes").labels(tree_label).value


@pytest.fixture(scope="module")
def bf16():
    cfg = tiny(jnp.bfloat16)
    params = handed(cfg)
    return cfg, params, JaxEncoderEmbedder(cfg=cfg, params=params)


class TestCastEngages:
    def test_matrices_hold_the_bits_of_a_bfloat16_cast(self, bf16):
        _, params, emb = bf16
        held = leaves_by_path(params)
        forward = leaves_by_path(emb._forward_params)
        assert forward.keys() == held.keys()
        matrices = [p for p, x in held.items() if x.ndim >= 2]
        # two tables; a layer: q, k, v kernels and [h, hd] biases, out,
        # mlp_up, mlp_down kernels
        assert len(matrices) == 2 + 2 * 9
        for path in matrices:
            assert forward[path].dtype == jnp.bfloat16, path
            want = np.asarray(held[path].astype(jnp.bfloat16))
            assert np.array_equal(np.asarray(forward[path]).view(np.uint16),
                                  want.view(np.uint16)), path

    def test_one_dimensional_leaves_stay_as_handed(self, bf16):
        _, params, emb = bf16
        held = leaves_by_path(params)
        forward = leaves_by_path(emb._forward_params)
        flat = [p for p, x in held.items() if x.ndim < 2]
        norms = [p for p in flat if any(f"'{n}'" in p for n in LAYER_NORMS)]
        assert len(norms) == 2 * (2 * 2 + 1)    # scale and bias of five
        assert set(flat) - set(norms)           # the Dense biases too
        for path in flat:
            assert forward[path].dtype == jnp.float32, path
            assert np.array_equal(np.asarray(forward[path]),
                                  np.asarray(held[path])), path

    def test_params_is_what_the_caller_handed(self, bf16):
        _, params, emb = bf16
        held = leaves_by_path(params)
        kept = leaves_by_path(emb.params)
        assert kept.keys() == held.keys()
        for path, leaf in kept.items():
            assert isinstance(leaf, jax.Array)
            assert leaf.dtype == jnp.float32, path
            assert np.array_equal(np.asarray(leaf),
                                  np.asarray(held[path])), path

    def test_vectors_lie_beside_those_of_the_float32_tree(self, bf16):
        cfg, params, emb = bf16
        served = np.asarray(emb.embed_batch(TEXTS), np.float32)
        for text, vec in zip(TEXTS, served):
            ids = emb.tokenizer.encode(text, max_len=cfg.max_len)
            width = emb._bucket_width(len(ids))
            arr = np.zeros((1, width), np.int32)
            arr[0, :len(ids)] = ids
            ref = np.asarray(emb.model.apply({"params": params}, arr),
                             np.float32)[0]
            cosine = float(vec @ ref / (np.linalg.norm(vec)
                                        * np.linalg.norm(ref)))
            assert 1.0 - cosine < 2e-3, (text, cosine)


class TestNothingToCast:
    def test_float32_forward_tree_is_params_itself(self, monkeypatch):
        def no_cast(*_a, **_k):
            raise AssertionError("a cast program was built")

        monkeypatch.setattr(embedder_mod, "_working_copy", no_cast)
        cfg = tiny(jnp.float32)
        emb = JaxEncoderEmbedder(cfg=cfg, params=handed(cfg))
        assert emb._forward_params is emb.params
        assert np.isfinite(emb.embed("w1 w2")).all()

    def test_mini_shape_in_float32_has_one_tree(self):
        emb = JaxEncoderEmbedder(cfg=EncoderConfig.mini())
        assert emb._forward_params is emb.params

    def test_the_default_embedder_computes_in_bfloat16(self):
        """``load_checkpoint`` builds its config with the default compute
        dtype, not ``mini()``'s float32: the committed checkpoint's
        embedder is one the cast engages for."""
        from nornicdb_tpu.models.pretrain import load_default_embedder

        emb = load_default_embedder()
        if emb is None:
            pytest.skip("no committed checkpoint")
        assert emb.cfg.dtype == jnp.bfloat16
        assert {x.dtype for x in jax.tree_util.tree_leaves(emb.params)} \
            == {np.dtype(np.float32)}
        assert tree_bytes(emb._forward_params) < tree_bytes(emb.params)
        assert np.isfinite(emb.embed("the capital of norway is oslo")).all()


class TestHooksTheBenchmarkReads:
    def test_a_stub_at_jit_is_handed_the_working_copy(self):
        cfg = tiny(jnp.bfloat16)
        emb = JaxEncoderEmbedder(cfg=cfg, params=handed(cfg))
        assert "lambda" in getattr(emb._jit, "__name__", "")
        forward, seen = emb._jit, []

        def tap(tree, ids):
            seen.append((tree, tuple(ids.shape)))
            return forward(tree, ids)

        emb._jit = tap
        emb.embed_batch(["a b c", "d e f g h"])
        assert [shape for _, shape in seen] == [(2, 16)]
        assert seen[0][0] is emb._forward_params
        assert seen[0][0] is not emb.params


class TestGauge:
    @pytest.mark.parametrize("dtype,smaller", [(jnp.bfloat16, True),
                                                (jnp.float32, False)])
    def test_held_and_forward_are_the_trees_bytes(self, dtype, smaller):
        cfg = tiny(dtype)
        emb = JaxEncoderEmbedder(cfg=cfg, params=handed(cfg))
        assert param_bytes("held") == tree_bytes(emb.params)
        assert param_bytes("forward") == tree_bytes(emb._forward_params)
        if smaller:
            assert param_bytes("forward") < param_bytes("held")
        else:
            assert param_bytes("forward") == param_bytes("held")
