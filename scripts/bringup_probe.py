#!/usr/bin/env python3
"""bringup_probe.py — the measurements behind PERF.md's bring-up facts.

One process, one chip, every section in one command; writes
``chiprun_out/bringup_probe.json`` (``_tiny.json`` under ``--tiny``) and
prints each row as it is measured.
Not a benchmark: single runs of a few repeats, no baseline, no claim.

  precision  DEFAULT / HIGH / HIGHEST through the product's own top-k
             (ops/similarity.EXACT patched, jit caches cleared): agreement
             with a float64 host reference at 8,192 and 131,072 rows, and
             time per dispatch from a host-bound shape up to a matrix that
             half fills HBM at B=256; the device BM25 program at 131,072
             documents
  kernels    Pallas fused top-k against XLA's matmul+top_k, same precision
  encoder    24L/1024-wide forward per (batch, width): steady time where it
             runs, XLA's compile-time memory analysis where it may not
  dispatch   BruteForceIndex.search_batch per batch bucket, warm
  children   a JAX child beside this chip-owning process, pinned and not

    python3 scripts/bringup_probe.py [--only precision,kernels] [--tiny]

``--tiny`` shrinks every shape so the script runs end to end on a CPU (the
Pallas kernel then runs in interpret mode); its timings mean nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from nornicdb_tpu.jaxenv import cpu_child_env, ensure_compile_cache  # noqa: E402

T0 = time.time()


def log(msg: str) -> None:
    print(f"[{time.time() - T0:7.1f}s] {msg}", flush=True)


def steady_ms(fn, reps: int = 10) -> float:
    """Median wall milliseconds of ``fn()`` after one untimed call."""
    jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t)
    return round(float(np.median(times)) * 1e3, 3)


def device_block() -> dict:
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "hbm_limit_bytes": stats.get("bytes_limit")}


PRECISIONS = {"default": jax.lax.Precision.DEFAULT,
              "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}


def set_precision(prec) -> None:
    """Point every exact-tier matmul at ``prec`` and drop what was traced
    with the old one."""
    from nornicdb_tpu.ops import similarity
    from nornicdb_tpu.search import device_bm25

    similarity.EXACT = device_bm25.EXACT = prec
    similarity._cosine_topk_impl.clear_cache()
    similarity._cosine_topk_chunked_impl.clear_cache()
    device_bm25._bm25_topk.clear_cache()


def unit_rows(key, n: int, d: int):
    """[n, d] float32 rows of norm ~1, made on the device a block at a
    time so that a matrix of half the HBM needs no second copy."""
    block = min(n, 1 << 18)
    make = jax.jit(lambda k: jax.random.normal(k, (block, d), jnp.float32)
                   * np.float32(d ** -0.5))
    if n == block:
        return make(key)
    put = jax.jit(lambda buf, rows, i: jax.lax.dynamic_update_slice(
        buf, rows, (i * block, 0)), donate_argnums=0)
    buf = jnp.zeros((n, d), jnp.float32)
    for i, k in enumerate(jax.random.split(key, n // block)):
        buf = put(buf, make(k), i)
    return buf


def agreement(tiny: bool) -> dict:
    """Recall@10, rank-1 and worst cosine error against float64 NumPy."""
    from nornicdb_tpu.ops import similarity

    out = {}
    d = 128 if tiny else 1024
    for n in ((1024,) if tiny else (8192, 131072)):
        rng = np.random.default_rng(n)
        m = rng.standard_normal((n, d)).astype(np.float32)
        m /= np.linalg.norm(m, axis=1, keepdims=True)
        q = rng.standard_normal((64, d)).astype(np.float32)
        q /= np.linalg.norm(q, axis=1, keepdims=True)
        truth = q.astype(np.float64) @ m.astype(np.float64).T
        want = np.argsort(-truth, axis=1)[:, :10]
        mj, qj = jnp.asarray(m), jnp.asarray(q)
        valid = jnp.ones((n,), bool)
        for name, prec in PRECISIONS.items():
            set_precision(prec)
            s, i = similarity.cosine_topk_auto(qj, mj, valid, 10)
            s, i = np.asarray(s), np.asarray(i)
            recall = np.mean([len(set(a) & set(b)) / 10.0
                              for a, b in zip(i, want)])
            err = np.abs(s - np.take_along_axis(truth, i, axis=1)).max()
            out[f"{n}x{d}/{name}"] = {
                "recall_at_10": round(float(recall), 5),
                "rank1": round(float(np.mean(i[:, 0] == want[:, 0])), 5),
                "max_abs_err": float(err)}
            log(f"agreement {n}x{d} {name}: {out[f'{n}x{d}/{name}']}")
    return out


def topk_times(tiny: bool) -> dict:
    """Time per cosine_topk_auto dispatch, by precision, from the smoke's
    shape up to a matrix of 2^21 rows (8.6 GB) at B=256."""
    from nornicdb_tpu.ops import similarity

    out = {}
    d = 128 if tiny else 1024
    shapes = ((1024, 8),) if tiny else (
        (8192, 64), (262144, 256), (2097152, 64), (2097152, 256))
    matrix, rows = None, 0
    for n, b in shapes:
        if n != rows:
            del matrix
            matrix = unit_rows(jax.random.PRNGKey(n), n, d)
            rows = n
        valid = jnp.ones((n,), bool)
        q = unit_rows(jax.random.PRNGKey(b), b, d)
        flops = 2.0 * b * n * d
        for name, prec in PRECISIONS.items():
            set_precision(prec)
            ms = steady_ms(
                lambda: similarity.cosine_topk_auto(q, matrix, valid, 32),
                reps=5)
            out[f"{n}x{d}/B{b}/{name}"] = {
                "ms": ms, "tflops": round(flops / ms / 1e9, 2),
                "matrix_read_gbps": round(n * d * 4 / ms / 1e6, 1)}
            log(f"topk {n}x{d} B={b} {name}: {out[f'{n}x{d}/B{b}/{name}']}")
    del matrix
    return out


def bm25_times(tiny: bool) -> dict:
    """The device BM25 program (segment-sum into [U, C], then the
    idf-weighted [B, U] x [U, C] matmul) at 131,072 documents, eight terms
    a query, 64 postings a term."""
    from nornicdb_tpu.search import device_bm25

    out = {}
    c = 2048 if tiny else 131072
    rng = np.random.default_rng(0)
    post_cap = c * 16
    post_doc = jnp.asarray(rng.integers(0, c, post_cap), jnp.int32)
    post_tf = jnp.asarray(rng.integers(1, 4, post_cap), jnp.int32)
    doc_len = jnp.asarray(rng.integers(8, 64, c), jnp.int32)
    alive = jnp.ones((c,), jnp.float32)
    for b in ((4,) if tiny else (32, 256)):
        u, p = 8 * b, 8 * b * 64
        ptr = jnp.asarray(rng.integers(0, post_cap, p), jnp.int32)
        urow = jnp.asarray(np.repeat(np.arange(u), 64), jnp.int32)
        sel = np.zeros((b, u), np.float32)
        for qi in range(b):
            sel[qi, qi * 8:(qi + 1) * 8] = rng.uniform(1.0, 8.0, 8)
        sel = jnp.asarray(sel)
        for name, prec in PRECISIONS.items():
            set_precision(prec)
            ms = steady_ms(lambda: device_bm25._bm25_topk(
                ptr, urow, sel, post_doc, post_tf, doc_len, alive,
                jnp.float32(36.0), k=32), reps=5)
            out[f"C{c}/B{b}/U{u}/P{p}/{name}"] = {"ms": ms}
            log(f"bm25 C={c} B={b} U={u} P={p} {name}: {ms} ms")
    return out


def section_precision(tiny: bool) -> dict:
    from nornicdb_tpu.ops import similarity

    shipped = similarity.EXACT
    try:
        return {"shipped": str(shipped), "agreement": agreement(tiny),
                "topk": topk_times(tiny), "bm25": bm25_times(tiny)}
    finally:
        set_precision(shipped)


def section_kernels(tiny: bool) -> dict:
    from nornicdb_tpu.ops.pallas_topk import fused_cosine_topk
    from nornicdb_tpu.ops.similarity import cosine_topk

    out = {}
    n, d = (1024, 128) if tiny else (8192, 1024)
    matrix = unit_rows(jax.random.PRNGKey(1), n, d)
    valid = jnp.ones((n,), bool)
    for b in ((8,) if tiny else (8, 64, 256)):
        q = unit_rows(jax.random.PRNGKey(b), b, d)
        ps, pi = fused_cosine_topk(q, matrix, valid, 10, interpret=tiny)
        xs, xi = cosine_topk(q, matrix, valid, 10)
        out[str(b)] = {
            "ids_equal": bool(np.array_equal(np.asarray(pi),
                                             np.asarray(xi))),
            "max_score_diff": float(np.abs(np.asarray(ps)
                                           - np.asarray(xs)).max()),
            "pallas_ms": steady_ms(lambda: fused_cosine_topk(
                q, matrix, valid, 10, interpret=tiny)),
            "xla_ms": steady_ms(lambda: cosine_topk(q, matrix, valid, 10))}
        log(f"kernels top-k B={b}: {out[str(b)]}")
    return out


def section_encoder(tiny: bool) -> dict:
    from nornicdb_tpu.embed.embedder import JaxEncoderEmbedder
    from nornicdb_tpu.models.encoder import EncoderConfig

    cfg = EncoderConfig(vocab_size=2048, hidden_size=128, num_layers=2,
                        num_heads=4, mlp_dim=256, max_len=512) if tiny \
        else EncoderConfig.bge_m3_like()
    t = time.time()
    emb = JaxEncoderEmbedder(cfg=cfg, seed=0)
    jax.block_until_ready(emb.params)
    out = {"init_s": round(time.time() - t, 2), "run": {}, "memory": {}}
    # (batch, width, timed calls): one call where a call takes 20 s
    run = ((1, 16, 3), (16, 64, 3)) if tiny else (
        (1, 16, 10), (16, 512, 5), (16, 2048, 3), (16, 4096, 3),
        (1, 8192, 3), (2, 8192, 1))
    for b, s, reps in run:
        ids = np.ones((b, s), np.int32)
        t = time.time()
        first = emb._jit(emb.params, ids)
        jax.block_until_ready(first)
        row = {"first_s": round(time.time() - t, 2),
               "steady_ms": steady_ms(lambda: emb._jit(emb.params, ids),
                                      reps=reps),
               "finite": bool(np.isfinite(np.asarray(first)).all())}
        out["run"][f"{b}x{s}"] = row
        log(f"encoder run ({b},{s}): {row}")
    # compile only: what XLA says the program needs, or why it refuses
    analyse = ((16, 64),) if tiny else (
        (16, 512), (16, 2048), (16, 4096), (64, 2048), (1, 8192),
        (2, 8192), (4, 8192), (16, 8192))
    for b, s in analyse:
        ids = np.ones((b, s), np.int32)
        t = time.time()
        try:
            mem = emb._jit.lower(emb.params, ids).compile().memory_analysis()
            row = {"compile_s": round(time.time() - t, 1),
                   "temp_gb": round(mem.temp_size_in_bytes / 1e9, 2),
                   "arg_gb": round(mem.argument_size_in_bytes / 1e9, 2)}
        except Exception as exc:  # noqa: BLE001 — the refusal is the result
            row = {"error": f"{type(exc).__name__}: {str(exc)[:300]}"}
        out["memory"][f"{b}x{s}"] = row
        log(f"encoder memory ({b},{s}): {row}")
    return out


def section_dispatch(tiny: bool) -> dict:
    from nornicdb_tpu.search.vector_index import BruteForceIndex

    n, d = (2112, 128) if tiny else (8192, 1024)
    rng = np.random.default_rng(0)
    index = BruteForceIndex(dims=d)
    index.add_batch([(f"v{i}", v) for i, v in
                     enumerate(rng.standard_normal((n, d), np.float32))])
    out = {}
    for b in (1, 2, 4, 8, 16, 32):
        q = rng.standard_normal((b, d), np.float32)
        t = time.time()
        index.search_batch(q, 32)
        first_s = round(time.time() - t, 2)
        times = []
        for _ in range(20):
            t = time.perf_counter()
            index.search_batch(q, 32)  # returns host lists: synchronous
            times.append(time.perf_counter() - t)
        out[str(b)] = {"first_s": first_s,
                       "warm_ms": round(float(np.median(times)) * 1e3, 3)}
        log(f"dispatch search_batch B={b}: {out[str(b)]}")
    return out


def section_children(tiny: bool) -> dict:
    """This process holds the chip. A child that inherits the environment
    goes after it too; one spawned through cpu_child_env must not."""
    code = "import jax; print(jax.devices()[0].platform)"
    out = {}
    for name, env in (("pinned", cpu_child_env()),
                      ("inherited", dict(os.environ))):
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        out[name] = {"exit": proc.returncode,
                     "stdout": proc.stdout.strip(),
                     "stderr_tail": proc.stderr.strip()[-300:]}
        log(f"children {name}: exit {proc.returncode} "
            f"{proc.stdout.strip()!r}")
    return out


SECTIONS = (("precision", section_precision), ("kernels", section_kernels),
            ("encoder", section_encoder), ("dispatch", section_dispatch),
            ("children", section_children))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", default="",
                    help="comma-separated sections (default: all)")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU-sized shapes; timings mean nothing")
    args = ap.parse_args()
    only = {s for s in args.only.split(",") if s}
    unknown = only - {name for name, _ in SECTIONS}
    if unknown:
        ap.error(f"unknown sections: {sorted(unknown)}")
    ensure_compile_cache()
    doc = {"device": device_block(), "tiny": args.tiny}
    log(f"device: {doc['device']}")
    if not args.tiny and doc["device"]["platform"] == "cpu":
        print("bringup_probe: no accelerator (use --tiny to rehearse)",
              file=sys.stderr)
        return 1
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, "bringup_probe_tiny.json" if args.tiny
                            else "bringup_probe.json")
    for name, fn in SECTIONS:
        if only and name not in only:
            continue
        doc[name] = fn(args.tiny)
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
    log("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
