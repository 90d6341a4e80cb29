"""Contrastive pretraining for the committed mini encoder checkpoint.

The reference ships real bge-m3 weights for local embedding
(pkg/embed/local_gguf.go:57,100 over vendored llama.cpp). This image has
no network, so the equivalent here is a small encoder trained IN-REPO on
locally-available English prose — Python standard-library module
docstrings plus this repo's own documentation — with an InfoNCE
objective (models/train.py): two word-windows of the same document are
positives, in-batch others are negatives. The result learns topical
co-occurrence structure on top of the hash tokenizer, which is what
separates it from the bag-of-hashes HashEmbedder baseline: windows that
share a topic but not exact words still land near each other.

The trained checkpoint is committed (models/checkpoints/encoder_mini.npz,
fp16, ~1.5 MB) and is the DB's default embedder (db.py); quality is
gated in CI by tests/test_encoder_eval.py over a committed JSONL suite.

CLI: python -m nornicdb_tpu.models.pretrain [out.npz] [steps]
"""

from __future__ import annotations

import io
import os
import random
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# modules whose docstrings form the training corpus: stable, offline,
# real English across distinct technical topics
_CORPUS_MODULES = [
    "abc", "argparse", "array", "asyncio", "base64", "bisect", "calendar",
    "cmath", "codecs", "collections", "colorsys", "concurrent.futures",
    "configparser", "contextlib", "copy", "csv", "ctypes", "datetime",
    "decimal", "difflib", "dis", "doctest", "email", "enum", "fileinput",
    "fnmatch", "fractions", "functools", "getpass", "gettext", "glob",
    "gzip", "hashlib", "heapq", "hmac", "html", "http", "imaplib",
    "importlib", "inspect", "io", "ipaddress", "itertools", "json",
    "keyword", "linecache", "locale", "logging", "lzma", "mailbox",
    "math", "mimetypes", "multiprocessing", "netrc", "numbers",
    "operator", "os", "pathlib", "pdb", "pickle", "pickletools",
    "platform", "plistlib", "poplib", "pprint", "profile", "pstats",
    "py_compile", "queue", "quopri", "random", "re", "reprlib",
    "sched", "secrets", "selectors", "shelve", "shlex", "shutil",
    "signal", "smtplib", "socket", "socketserver", "sqlite3", "ssl",
    "stat", "statistics", "string", "stringprep", "struct", "subprocess",
    "symtable", "sysconfig", "tabnanny", "tarfile", "tempfile",
    "textwrap", "threading", "timeit", "token", "tokenize", "trace",
    "traceback", "types", "typing", "unicodedata", "unittest", "urllib",
    "uuid", "venv", "warnings", "wave", "weakref", "webbrowser",
    "xml", "zipapp", "zipfile", "zlib",
]


def build_corpus(min_words: int = 12) -> List[Tuple[str, str]]:
    """(group, text) documents: stdlib module + member (class/function)
    docstrings + repo doc sections.

    The GROUP is the retrieval-relevant unit: all docstrings of one
    stdlib module are about one topic, exactly the granularity search
    eval groups documents at. Contrastive pairs drawn from two DIFFERENT
    documents of the same group (make_batch) teach topic-level
    clustering — same-document windows alone only teach document
    identity, which is why the round-3 recipe's recall plateaued at the
    lexical baseline. Repo doc sections cover many topics per file, so
    each section is its own group (same-doc windows)."""
    docs: List[Tuple[str, str]] = []
    seen = set()

    def take(group: str, text: Optional[str]) -> None:
        text = (text or "").strip()
        if len(text.split()) >= min_words and text[:80] not in seen:
            seen.add(text[:80])
            docs.append((group, text))

    for name in _CORPUS_MODULES:
        try:
            import importlib

            mod = importlib.import_module(name)
        except Exception:
            continue
        group = name.split(".")[0]
        take(group, mod.__doc__)
        for member in vars(mod).values():
            try:
                take(group, getattr(member, "__doc__", None))
                if isinstance(member, type):
                    for sub in vars(member).values():
                        take(group, getattr(sub, "__doc__", None))
            except Exception:
                continue
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for fname in ("README.md", "SURVEY.md", "COMPONENTS.md"):
        path = os.path.join(repo, fname)
        if os.path.exists(path):
            with io.open(path, encoding="utf-8") as f:
                text = f.read()
            # split large docs into section-sized documents
            for si, part in enumerate(re.split(r"\n#+ ", text)):
                if len(part.split()) >= 25:
                    docs.append((f"{fname}#{si}", part))
    return docs


def _window(words: List[str], rng: random.Random,
            lo: int, hi: int, drop: float) -> str:
    n = len(words)
    w = rng.randint(lo, hi)
    start = rng.randint(0, max(0, n - w))
    win = [t for t in words[start: start + w] if rng.random() > drop]
    return " ".join(win) if win else words[start]


def make_batch(
    groups: Dict[str, List[List[str]]],
    group_names: List[str],
    tokenizer,
    rng: random.Random,
    batch: int,
    seq_len: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """One (anchor, positive) pair per DISTINCT group.

    - anchor: short (4-14 word) heavy-dropout window — query-shaped;
    - positive: longer window from a DIFFERENT document of the same
      group when the group has several (topic-level positive), else
      from the same document (identity-level fallback);
    - one pair per group per batch, so in-batch negatives are never
      secretly same-topic (same-group negatives would push the very
      structure we want apart)."""
    picks = rng.sample(group_names, min(batch, len(group_names)))
    a = np.zeros((len(picks), seq_len), np.int32)
    p = np.zeros((len(picks), seq_len), np.int32)
    for row, g in enumerate(picks):
        members = groups[g]
        d1 = rng.randrange(len(members))
        if len(members) > 1:  # topic-level positive: a DIFFERENT doc
            d2 = rng.randrange(len(members) - 1)
            if d2 >= d1:
                d2 += 1
        else:
            d2 = d1  # singleton group: identity-level fallback
        wa = _window(members[d1], rng, 4, 14, drop=0.3)
        wp = _window(members[d2], rng, 16, 48, drop=0.1)
        for arr, text in ((a, wa), (p, wp)):
            ids = tokenizer.encode(text, max_len=seq_len)
            arr[row, : len(ids)] = ids
    return a, p


def train_mini(
    steps: int = 3000,
    batch: int = 128,
    seq_len: int = 64,
    learning_rate: float = 3e-4,
    seed: int = 0,
    log_every: int = 200,
    eval_hook=None,
):
    """Train the mini encoder; returns (cfg, params, final_loss).

    ``eval_hook(step, params)`` (optional) is called every ``log_every``
    steps for in-training quality probes."""
    import functools

    import jax
    import optax

    from nornicdb_tpu.embed.tokenizer import HashTokenizer
    from nornicdb_tpu.models.encoder import EncoderConfig
    from nornicdb_tpu.models.train import (
        contrastive_train_step,
        create_train_state,
    )

    cfg = EncoderConfig.mini()
    tokenizer = HashTokenizer(cfg.vocab_size)
    groups: Dict[str, List[List[str]]] = {}
    for g, text in build_corpus():
        groups.setdefault(g, []).append(text.split())
    group_names = sorted(groups)
    batch = min(batch, len(group_names))
    rng = random.Random(seed)
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=learning_rate,
        warmup_steps=min(100, steps // 10), decay_steps=steps,
        end_value=learning_rate * 0.03,
    )
    model, state = create_train_state(
        cfg, jax.random.PRNGKey(seed), learning_rate=schedule,
        seq_len=seq_len,
    )
    step_fn = jax.jit(functools.partial(contrastive_train_step, model))
    loss = float("nan")
    for step in range(steps):
        a, p = make_batch(groups, group_names, tokenizer, rng, batch,
                          seq_len)
        state, loss_arr = step_fn(state, a, p)
        if log_every and (step + 1) % log_every == 0:
            loss = float(loss_arr)
            print(f"step {step + 1}/{steps} loss {loss:.4f}", flush=True)
            if eval_hook is not None:
                eval_hook(step + 1, state.params)
    return cfg, state.params, float(loss_arr)


# -- checkpoint io ---------------------------------------------------------


def save_checkpoint(path: str, cfg, params) -> None:
    """fp16 flax-serialized params + the config fields that shape them."""
    import jax
    from flax import serialization

    half = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float16), params
    )
    blob = serialization.to_bytes(half)
    np.savez_compressed(
        path,
        params=np.frombuffer(blob, dtype=np.uint8),
        meta=np.asarray([
            cfg.vocab_size, cfg.hidden_size, cfg.num_layers,
            cfg.num_heads, cfg.mlp_dim, cfg.max_len,
        ], dtype=np.int64),
    )


def load_checkpoint(path: str):
    """Returns (cfg, params) with fp32 params."""
    import jax
    from flax import serialization

    from nornicdb_tpu.models.encoder import Encoder, EncoderConfig

    data = np.load(path if path.endswith(".npz") else path + ".npz")
    meta = [int(x) for x in data["meta"]]
    cfg = EncoderConfig(
        vocab_size=meta[0], hidden_size=meta[1], num_layers=meta[2],
        num_heads=meta[3], mlp_dim=meta[4], max_len=meta[5],
    )
    model = Encoder(cfg)
    template = model.init(
        jax.random.PRNGKey(0), np.ones((1, 8), np.int32)
    )["params"]
    half_template = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float16), template
    )
    params = serialization.from_bytes(
        half_template, data["params"].tobytes()
    )
    params = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float32), params
    )
    return cfg, params


def default_checkpoint_path() -> Optional[str]:
    """Path of the committed mini checkpoint, or None if absent."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "checkpoints", "encoder_mini.npz",
    )
    return path if os.path.exists(path) else None


def load_default_embedder():
    """The DB's default semantic embedder: the committed mini encoder
    behind the batched jax embedder; None when no checkpoint is
    committed (callers fall back to HashEmbedder)."""
    path = default_checkpoint_path()
    if path is None:
        return None
    from nornicdb_tpu.embed.embedder import JaxEncoderEmbedder
    from nornicdb_tpu.models.encoder import Encoder

    cfg, params = load_checkpoint(path)
    return JaxEncoderEmbedder(model=Encoder(cfg), params=params, cfg=cfg)


def main() -> None:  # pragma: no cover
    import sys

    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "checkpoints", "encoder_mini.npz",
    )
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 400
    os.makedirs(os.path.dirname(out), exist_ok=True)
    cfg, params, loss = train_mini(steps=steps)
    save_checkpoint(out, cfg, params)
    size = os.path.getsize(out) / 1e6
    print(f"saved {out} ({size:.2f} MB, final loss {loss:.4f})")


if __name__ == "__main__":  # pragma: no cover
    main()
