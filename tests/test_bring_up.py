"""Repairs made for the first run on the chip (ISSUE 21): nothing on the
ingest -> embed -> index -> search path may hide the device, and the
process edge (compile cache, children's backend) is set in one place.
"""

import logging
import os
import subprocess
import sys

import numpy as np
import pytest

from nornicdb_tpu import jaxenv


class TestEncoderOnDevice:
    def test_host_params_are_placed_at_construction(self):
        """Checkpoint loaders (models/pretrain.load_checkpoint, the HF
        importer) hand back NumPy trees; the embedder must place them on
        the device once, not ship every weight with every call."""
        import jax

        from nornicdb_tpu.embed.embedder import JaxEncoderEmbedder
        from nornicdb_tpu.models.encoder import Encoder, EncoderConfig

        cfg = EncoderConfig.tiny()
        host = jax.tree_util.tree_map(
            np.asarray, Encoder(cfg).init(
                jax.random.PRNGKey(0), np.ones((1, 8), np.int32))["params"])
        assert all(isinstance(x, np.ndarray)
                   for x in jax.tree_util.tree_leaves(host))
        emb = JaxEncoderEmbedder(cfg=cfg, params=host)
        leaves = jax.tree_util.tree_leaves(emb.params)
        assert leaves and all(isinstance(x, jax.Array) for x in leaves)
        assert all(x.devices() == {jax.devices()[0]} for x in leaves)
        assert np.isfinite(emb.embed("w1 w2")).all()

    def test_batch_sizes_compile_only_pow2_shapes(self):
        """The embed queue hands over 1..16 rows; every batch size must
        land on the pow2 ladder, with the pad rows dropped. A full batch
        of 16 is never narrower than min(256, max_len) (128 here)."""
        from nornicdb_tpu.embed.embedder import JaxEncoderEmbedder
        from nornicdb_tpu.models.encoder import EncoderConfig

        emb = JaxEncoderEmbedder(cfg=EncoderConfig.tiny())
        rng = np.random.default_rng(0)
        alone = np.asarray(emb.embed("w1 w2 w3"))
        for n in rng.permutation(np.arange(1, 17)):
            texts = ["w1 w2 w3"] + [f"w{i} w{i + 1}" for i in range(n - 1)]
            vecs = np.asarray(emb.embed_batch(texts))
            assert vecs.shape == (n, emb.dims)
            # a row's embedding does not depend on its batch-mates or pads
            np.testing.assert_allclose(vecs[0], alone, atol=2e-2)
        assert emb.shapes == {(b, 16) for b in (1, 2, 4, 8)} | {
            (16, min(256, emb.cfg.max_len))}
        assert emb._jit._cache_size() == 5

    def test_failed_forward_names_its_shape(self, caplog):
        from nornicdb_tpu.embed.embedder import JaxEncoderEmbedder
        from nornicdb_tpu.models.encoder import EncoderConfig

        emb = JaxEncoderEmbedder(cfg=EncoderConfig.tiny())

        def oom(*_a):
            raise RuntimeError("RESOURCE_EXHAUSTED")

        emb._jit = oom
        with caplog.at_level(logging.ERROR), pytest.raises(RuntimeError):
            emb.embed_batch(["a b c"] * 3)
        assert "(batch, width)=(4, 16)" in caplog.text


class TestDefaultEmbedderFallback:
    def test_backend_error_is_not_swallowed(self, monkeypatch):
        """A JAX backend that will not initialise is the caller's to
        see, not a reason to write hash embeddings."""
        import nornicdb_tpu
        from nornicdb_tpu.models import pretrain

        def broken():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.delenv("NORNICDB_TPU_EMBEDDER", raising=False)
        monkeypatch.delenv("NORNICDB_TPU_MODEL_DIR", raising=False)
        monkeypatch.setattr(pretrain, "load_default_embedder", broken)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            nornicdb_tpu.open()

    def test_missing_checkpoint_still_falls_back(self, monkeypatch):
        import nornicdb_tpu
        from nornicdb_tpu.embed.embedder import HashEmbedder
        from nornicdb_tpu.models import pretrain

        monkeypatch.delenv("NORNICDB_TPU_EMBEDDER", raising=False)
        monkeypatch.delenv("NORNICDB_TPU_MODEL_DIR", raising=False)
        monkeypatch.setattr(pretrain, "load_default_embedder",
                            lambda: None)
        db = nornicdb_tpu.open()
        try:
            assert isinstance(db._embedder.inner, HashEmbedder)
        finally:
            db.close()


class TestQueryEmbeddingErrors:
    def _service(self, embedder):
        from nornicdb_tpu.search.service import SearchService
        from nornicdb_tpu.storage import MemoryEngine, NamespacedEngine

        return SearchService(NamespacedEngine(MemoryEngine(), "t"),
                             embedder=embedder)

    def test_local_embedder_error_reaches_the_caller(self):
        class Broken:
            dims = 8

            def embed(self, text):
                raise RuntimeError("device lost")

        with pytest.raises(RuntimeError, match="device lost"):
            self._service(Broken()).search("anything")

    def test_remote_transport_error_degrades_counted(self):
        from nornicdb_tpu.embed.http_providers import EmbedHTTPError
        from nornicdb_tpu.obs import audit

        class Remote:
            dims = 8

            def embed(self, text):
                raise EmbedHTTPError("POST http://x failed")

        before = audit.degrade_summary()["by_reason"].get("error", 0)
        assert self._service(Remote()).search("anything") == []
        assert audit.degrade_summary()["by_reason"]["error"] == before + 1


class TestCompileCache:
    def test_env_placement_is_left_alone(self, monkeypatch, tmp_path):
        import jax

        monkeypatch.setenv(jaxenv.CACHE_ENV, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert jaxenv.ensure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_the_fixed_in_checkout_path(self, monkeypatch):
        import jax

        monkeypatch.delenv(jaxenv.CACHE_ENV, raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jaxenv.ensure_compile_cache() == os.path.join(
            repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache")
        assert jaxenv.ensure_compile_cache() == jaxenv.DEFAULT_CACHE_DIR

    def test_env_placed_cache_is_where_jax_writes(self, tmp_path):
        """With the variable set the program sets no directory in code
        and JAX writes only there."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "from nornicdb_tpu.jaxenv import ensure_compile_cache\n"
            "print(ensure_compile_cache())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0.0)\n"
            "jax.jit(lambda x: x @ x.T + 1)(jnp.ones((64, 64)))"
            ".block_until_ready()\n" % repo)
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=str(tmp_path),
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 jaxenv.CACHE_ENV: str(tmp_path / "cc")})
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip() == str(tmp_path / "cc")
        assert os.listdir(tmp_path / "cc")


class TestChildrenStayOffTheChip:
    def test_child_env_pins_cpu_over_an_inherited_tpu(self):
        env = jaxenv.cpu_child_env({"JAX_PLATFORMS": "tpu", "X": "1"})
        assert env == {"JAX_PLATFORMS": "cpu", "X": "1"}
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            capture_output=True, text=True, timeout=120,
            env=jaxenv.cpu_child_env({**os.environ,
                                      "JAX_PLATFORMS": "tpu"}))
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip() == "cpu"


class TestRequestedKernelSaysSo:
    def test_topk_off_tpu_logs_once_and_serves_xla(self, caplog):
        import jax.numpy as jnp

        from nornicdb_tpu.ops import pallas_topk
        from nornicdb_tpu.ops.similarity import cosine_topk, l2_normalize

        rng = np.random.default_rng(0)
        m = l2_normalize(jnp.asarray(rng.standard_normal((256, 128)),
                                     jnp.float32))
        q = l2_normalize(jnp.asarray(rng.standard_normal((8, 128)),
                                     jnp.float32))
        valid = jnp.ones((256,), bool)
        pallas_topk._said.discard("backend")
        with caplog.at_level(logging.WARNING):
            s, i = pallas_topk.fused_cosine_topk(q, m, valid, 5)
            pallas_topk.fused_cosine_topk(q, m, valid, 5)
        assert caplog.text.count("fused Pallas top-k requested") == 1
        rs, ri = cosine_topk(q, m, valid, 5)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))

    def test_attention_flag_off_tpu_says_so(self, monkeypatch, caplog):
        from nornicdb_tpu.models.encoder import flash_attention_enabled

        monkeypatch.setenv("NORNICDB_PALLAS_ATTENTION", "1")
        with caplog.at_level(logging.WARNING):
            assert flash_attention_enabled() is False
        assert "NORNICDB_PALLAS_ATTENTION=1 but the backend" in caplog.text


def test_device_graph_backend_failure_is_not_host_mode(monkeypatch):
    from nornicdb_tpu.query import device_graph

    class NoBackend:
        @staticmethod
        def default_backend():
            raise RuntimeError("Unable to initialize backend 'tpu'")

    device_graph._cpu_backend.cache_clear()
    monkeypatch.setattr(device_graph, "_jx", lambda: NoBackend)
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            device_graph._cpu_backend()
    finally:
        device_graph._cpu_backend.cache_clear()
