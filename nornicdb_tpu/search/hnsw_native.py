"""ctypes loader for the native HNSW connect-phase kernel.

See native/nornichnsw.cpp. Loading is lazy and failure-tolerant: when
the toolchain or .so is unavailable the wave build uses its Python
connect path (same semantics, pinned by
tests/test_ann_stack.py::TestNativeConnect) and says so once."""

from __future__ import annotations

import ctypes
import logging
import os
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

_lib: Optional[ctypes.CDLL] = None
_tried = False


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    try:
        from nornicdb_tpu._native import load_build_module

        so = load_build_module("build_hnsw.py").build()
        lib = ctypes.CDLL(so)
        lib.hnsw_connect.argtypes = [
            ctypes.POINTER(ctypes.c_float),   # vectors
            ctypes.c_int64,                   # dims
            ctypes.POINTER(ctypes.c_int32),   # nbr
            ctypes.POINTER(ctypes.c_int32),   # cnt
            ctypes.c_int64,                   # width
            ctypes.c_int64,                   # m_forward
            ctypes.c_int64,                   # level_cap
            ctypes.POINTER(ctypes.c_int64),   # wave_slots
            ctypes.POINTER(ctypes.c_int64),   # cand_off
            ctypes.POINTER(ctypes.c_int64),   # cand_slots
            ctypes.POINTER(ctypes.c_float),   # cand_dists
            ctypes.c_int64,                   # n_wave
        ]
        lib.hnsw_connect.restype = None
        # a stale fallback .so (rebuild impossible) may predate the wave
        # kernel — keep the connect kernel usable without it
        if not hasattr(lib, "hnsw_wave_search"):
            _lib = lib
            return _lib
        lib.hnsw_wave_search.argtypes = [
            ctypes.POINTER(ctypes.c_float),   # vectors
            ctypes.c_int64,                   # dims
            ctypes.POINTER(ctypes.c_void_p),  # nbr level pointers
            ctypes.POINTER(ctypes.c_void_p),  # cnt level pointers
            ctypes.POINTER(ctypes.c_int64),   # widths
            ctypes.c_int64,                   # n_levels
            ctypes.POINTER(ctypes.c_float),   # queries
            ctypes.c_int64,                   # B
            ctypes.POINTER(ctypes.c_int64),   # query_levels
            ctypes.c_int64,                   # entry_slot
            ctypes.c_int64,                   # ef
            ctypes.c_int64,                   # capacity
            ctypes.POINTER(ctypes.c_int64),   # out_slots
            ctypes.POINTER(ctypes.c_float),   # out_dists
        ]
        lib.hnsw_wave_search.restype = None
        _lib = lib
    except Exception:
        logger.warning("native HNSW library unavailable; the Python "
                       "connect path serves", exc_info=True)
        _lib = None
    return _lib


def wave_search(lib, vectors: np.ndarray, nbr_levels, cnt_levels,
                queries: np.ndarray, query_levels: np.ndarray,
                entry_slot: int, ef: int,
                capacity: int) -> "tuple[np.ndarray, np.ndarray]":
    """Run the native wave layer-search. Returns (dists, slots) shaped
    [B, n_levels, ef] (+inf / -1 padded), ascending per (query, level).
    All adjacency arrays must be C-contiguous int32."""
    p = ctypes.POINTER
    n_levels = len(nbr_levels)
    B = queries.shape[0]
    nbr_ptrs = (ctypes.c_void_p * n_levels)(
        *[a.ctypes.data for a in nbr_levels])
    cnt_ptrs = (ctypes.c_void_p * n_levels)(
        *[a.ctypes.data for a in cnt_levels])
    widths = np.asarray([a.shape[1] for a in nbr_levels], np.int64)
    out_slots = np.empty((B, n_levels, ef), np.int64)
    out_dists = np.empty((B, n_levels, ef), np.float32)
    lib.hnsw_wave_search(
        vectors.ctypes.data_as(p(ctypes.c_float)),
        vectors.shape[1],
        nbr_ptrs,
        cnt_ptrs,
        widths.ctypes.data_as(p(ctypes.c_int64)),
        n_levels,
        queries.ctypes.data_as(p(ctypes.c_float)),
        B,
        np.ascontiguousarray(query_levels, np.int64).ctypes.data_as(
            p(ctypes.c_int64)),
        entry_slot,
        ef,
        capacity,
        out_slots.ctypes.data_as(p(ctypes.c_int64)),
        out_dists.ctypes.data_as(p(ctypes.c_float)),
    )
    return out_dists, out_slots


def connect_wave(lib, vectors: np.ndarray, nbr: np.ndarray,
                 cnt: np.ndarray, m_forward: int, level_cap: int,
                 wave_slots: np.ndarray, cand_off: np.ndarray,
                 cand_slots: np.ndarray, cand_dists: np.ndarray) -> None:
    """All arrays must be C-contiguous with the dtypes the kernel
    expects; adjacency (nbr/cnt) is mutated in place."""
    p = ctypes.POINTER
    lib.hnsw_connect(
        vectors.ctypes.data_as(p(ctypes.c_float)),
        vectors.shape[1],
        nbr.ctypes.data_as(p(ctypes.c_int32)),
        cnt.ctypes.data_as(p(ctypes.c_int32)),
        nbr.shape[1],
        m_forward,
        level_cap,
        wave_slots.ctypes.data_as(p(ctypes.c_int64)),
        cand_off.ctypes.data_as(p(ctypes.c_int64)),
        cand_slots.ctypes.data_as(p(ctypes.c_int64)),
        cand_dists.ctypes.data_as(p(ctypes.c_float)),
        len(wave_slots),
    )
