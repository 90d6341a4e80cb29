"""BM25 fulltext index (Okapi BM25, compact postings).

Re-expresses the reference's BM25 v2 engine (pkg/search/fulltext_index_v2.go:51
``FulltextIndexV2``: compact postings, top-k pruning, batch indexing) and its
tokenizer (pkg/indexing/config.go ``TokenizeForBM25``). Pointer-chasing
stays on CPU; scoring is vectorized with NumPy over postings arrays.

Also provides the BM25 seed-selection used to order HNSW builds and to
sample k-means training sets (reference: bm25_seed_provider.go:12
``bm25SeedDocIDs``, docs/release-notes-since-v1.0.11.md:75-151 — lexically
discriminative docs first → 2.7x faster 1M-vector HNSW build).
"""

from __future__ import annotations

import itertools
import math
import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_TOKEN_RE = re.compile(r"[a-z0-9]+")

# minimal english stopword set (reference keeps indexing light-weight)
STOPWORDS = frozenset(
    """a an and are as at be by for from has he in is it its of on that the
    to was were will with this these those i you your not or but if then
    than so we they them there here what which who whom when where how"""
    .split()
)

K1 = 1.2
B = 0.75

# the fewest fresh documents ``index_batch`` counts in one pass. Its work
# is a Python step a distinct term, which a small batch does not spread
# over many postings. 56-word passages, Zipf over 262,144 words, into an
# empty index, us a document with the posting arrays a device snapshot
# then asks for (CPU host, PR 28): 256 documents 88 against the loop's
# 89, 1,024 83 / 81, 4,096 70 / 78, 16,384 59 / 78, 200,000 50 / 76;
# without those arrays the loop is ahead up to 8,192 (62 / 58)
BULK_MIN_DOCS = 4096

# every ASCII character that is not a letter or a digit, to a space: on
# ASCII text one translate and one split give the runs _TOKEN_RE finds,
# three times as fast
_ASCII_SPLIT = str.maketrans(
    {chr(c): " " for c in range(128) if not chr(c).isalnum()})


def _raw_tokens(text: str) -> List[str]:
    """Maximal lower-cased runs of [a-z0-9], unfiltered."""
    low = text.lower()
    if low.isascii():
        return low.translate(_ASCII_SPLIT).split()
    return _TOKEN_RE.findall(low)


def _keeps(tok: str, min_len: int = 2, max_len: int = 40) -> bool:
    return min_len <= len(tok) <= max_len and tok not in STOPWORDS


def tokenize(text: str, min_len: int = 2, max_len: int = 40) -> List[str]:
    """Lowercase alphanumeric tokens, stopword- and length-filtered."""
    return [tok for tok in _raw_tokens(text)
            if min_len <= len(tok) <= max_len and tok not in STOPWORDS]


class _Posting:
    __slots__ = ("doc_ids", "tfs", "_np_ids", "_np_tfs")

    def __init__(self):
        self.doc_ids: List[int] = []
        self.tfs: List[int] = []
        self._np_ids: Optional[np.ndarray] = None
        self._np_tfs: Optional[np.ndarray] = None

    def arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        """Cached numpy views of the posting — rebuilding them from the
        Python lists on every query dominated search wall-clock. The
        cache key is the list length (postings only ever append; compaction
        swaps in fresh _Posting objects)."""
        if self._np_ids is None or self._np_ids.size != len(self.doc_ids):
            self._np_ids = np.asarray(self.doc_ids, dtype=np.int64)
            self._np_tfs = np.asarray(self.tfs, dtype=np.float32)
        return self._np_ids, self._np_tfs


class BM25Index:
    """Incremental BM25 index over (doc_id -> text). Thread-safe."""

    def __init__(self):
        self._lock = threading.RLock()
        self._postings: Dict[str, _Posting] = {}
        self._doc_len: List[int] = []  # internal idx -> token count
        self._ext_ids: List[str] = []  # internal idx -> external id
        self._int_of: Dict[str, int] = {}
        self._alive: List[bool] = []
        self._total_len = 0
        self._n_alive = 0
        # per-term LIVE document frequency, maintained incrementally on
        # add/remove/tombstone — scoring and seed selection read it in
        # O(1) instead of re-counting live postings per query (the old
        # seed_doc_ids did an O(terms * postings) Python sum)
        self._df: Dict[str, int] = {}
        # slot -> unique terms of that doc, so a tombstone can decrement
        # the live df counters without re-tokenizing
        self._doc_terms: List[Optional[Tuple[str, ...]]] = []
        # cached numpy doc_len/alive, invalidated by generation counter
        self._mut_gen = 0
        self._np_gen = -1
        self._np_doc_len: Optional[np.ndarray] = None
        self._np_alive: Optional[np.ndarray] = None
        # changelog of (mutation gen, ext_id) for adds/updates — the
        # device snapshot (device_bm25.py) exact-scores these between
        # rebuilds (read-your-writes), mirroring BruteForceIndex's
        # changelog discipline. Length-capped; _changelog_floor marks
        # how far back it reaches. Compaction remaps slots, so it
        # advances the floor past every outstanding marker.
        self._changelog: List[Tuple[int, str]] = []
        self._changelog_floor = 0
        # compaction counter: slot ids are only meaningful between
        # compactions, so snapshot consumers pin reads on it
        self.compactions = 0
        # total posting entries across all terms, maintained
        # incrementally so the resource-accounting scrape never walks
        # the vocabulary (tombstones keep their postings until
        # compaction, which recounts)
        self._n_postings = 0

    def _np_state(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._np_gen != self._mut_gen:
            self._np_doc_len = np.asarray(self._doc_len, dtype=np.float32)
            self._np_alive = np.asarray(self._alive, dtype=bool)
            self._np_gen = self._mut_gen
        return self._np_doc_len, self._np_alive

    # -- indexing --------------------------------------------------------

    def index(self, doc_id: str, text: str) -> None:
        with self._lock:
            if doc_id in self._int_of:
                self._remove_locked(doc_id)
            self._maybe_compact_locked()
            self._mut_gen += 1
            toks = tokenize(text)
            idx = len(self._ext_ids)
            self._ext_ids.append(doc_id)
            self._int_of[doc_id] = idx
            self._doc_len.append(len(toks))
            self._alive.append(True)
            self._total_len += len(toks)
            self._n_alive += 1
            counts: Dict[str, int] = {}
            for t in toks:
                counts[t] = counts.get(t, 0) + 1
            for t, c in counts.items():
                p = self._postings.get(t)
                if p is None:
                    p = self._postings[t] = _Posting()
                p.doc_ids.append(idx)
                p.tfs.append(c)
                self._df[t] = self._df.get(t, 0) + 1
            self._n_postings += len(counts)
            self._doc_terms.append(tuple(counts))
            self._log_change_locked(doc_id)

    def changelog_cap(self) -> int:
        """Current changelog length cap (what _trim_changelog_locked
        cuts to) — reported next to depth by the accounting layer."""
        return max(4096, len(self._ext_ids) // 4)

    def resource_stats(self) -> Dict[str, float]:
        """Memory + freshness accounting for obs/resources.py: postings
        footprint (incremental entry count — never an O(vocab) walk),
        tombstone pressure, and changelog depth vs cap."""
        with self._lock:
            n_slots = len(self._ext_ids)
            # per posting entry: one int in doc_ids + one in tfs (list
            # slots + boxed ints ~= 16B each conservatively as arrays)
            postings_b = self._n_postings * 16
            return {
                "rows": self._n_alive,
                "capacity": n_slots,
                "device_bytes": 0,  # host index; the CSR snapshot owns HBM
                "host_bytes": postings_b + n_slots * 24,
                "dead_fraction": round(
                    (n_slots - self._n_alive) / max(n_slots, 1), 6),
                "changelog_depth": len(self._changelog),
                "changelog_cap": self.changelog_cap(),
                "mutations": self._mut_gen,
                "postings": self._n_postings,
                "terms": len(self._postings),
            }

    def _log_change_locked(self, doc_id: str) -> None:
        self._changelog.append((self._mut_gen, doc_id))
        self._trim_changelog_locked()

    def _trim_changelog_locked(self) -> None:
        cut = len(self._changelog) - self.changelog_cap()
        if cut > 0:
            self._changelog_floor = self._changelog[cut - 1][0]
            del self._changelog[:cut]

    def changed_since(self, seq: int) -> Optional[List[str]]:
        """ext_ids added or UPDATED after mutation ``seq`` (latest first,
        deduped). Deletes are not reported — consumers live-filter those.
        Returns None when the changelog was trimmed (or slots remapped
        by compaction) past ``seq``: the consumer must rebuild or take
        the host-exact path instead."""
        with self._lock:
            if seq < self._changelog_floor:
                return None
            out: List[str] = []
            for s, eid in reversed(self._changelog):
                if s <= seq:
                    break
                out.append(eid)
        return list(dict.fromkeys(out))

    def index_batch(self, docs: Sequence[Tuple[str, str]]) -> None:
        """Reference: IndexBatch (fulltext_index_v2.go:114). Leaves the
        index in exactly the state ``index`` called once a doc, in
        order, would. ``BULK_MIN_DOCS`` or more fresh ids into an index
        without tombstones (a bulk load) are counted with NumPy, one
        sort over (term, doc) pairs for the whole batch; anything else
        (an id that is indexed already or comes twice, a tombstone that
        a compaction could meet half way) takes the loop."""
        docs = list(docs)
        with self._lock:
            ids = [d for d, _ in docs]
            if (len(docs) < BULK_MIN_DOCS
                    or self._n_alive != len(self._ext_ids)
                    or len(set(ids)) != len(ids)
                    or not self._int_of.keys().isdisjoint(ids)):
                for doc_id, text in docs:
                    self.index(doc_id, text)
                return
            self._index_fresh_locked(ids, [t for _, t in docs])

    def _index_fresh_locked(self, ids: List[str],
                            texts: List[str]) -> None:
        n, n0 = len(ids), len(self._ext_ids)
        # tokens as they stand in the text; the length and stop-word
        # rules are applied once a distinct token, not once a token
        raw_lists = [_raw_tokens(t) for t in texts]
        raw_lens = np.fromiter(map(len, raw_lists), np.int64, n)
        flat = list(itertools.chain.from_iterable(raw_lists))
        # in order of first occurrence over the batch: the order a
        # doc-by-doc load would have met (and inserted) the terms in
        seen = list(dict.fromkeys(flat))
        code_of = {t: i for i, t in enumerate(seen)}
        codes = np.fromiter(map(code_of.__getitem__, flat), np.int64,
                            len(flat))
        del flat
        kept = np.fromiter(map(_keeps, seen), bool, len(seen))
        docs = np.repeat(np.arange(n, dtype=np.int64), raw_lens)
        if not kept.all():
            keep = kept[codes]
            dropped_in = np.flatnonzero(np.bincount(
                docs[~keep], minlength=n))
            codes, docs = codes[keep], docs[keep]
        else:
            dropped_in = ()
        lens = np.bincount(docs, minlength=n)
        # one sort by (term, doc): each run is a posting, its length the tf
        pairs, tfs = np.unique(codes * n + docs, return_counts=True)
        del codes, docs
        post_term = pairs // n
        post_doc = pairs % n + n0
        del pairs
        post_tf = tfs.astype(np.float32)
        bounds = np.searchsorted(post_term,
                                 np.arange(len(seen) + 1, dtype=np.int64))
        doc_list, tf_list = post_doc.tolist(), tfs.tolist()
        for ti, t in enumerate(seen):
            lo, hi = int(bounds[ti]), int(bounds[ti + 1])
            if lo == hi:
                continue                      # a token the rules drop
            p = self._postings.get(t)
            if p is None:
                p = self._postings[t] = _Posting()
                # the whole posting is this run: hand arrays() its cache
                # (0.08 s against 1.7-1.9 s of list conversions when a
                # snapshot of 200,000 documents is built, CPU, PR 28)
                p._np_ids, p._np_tfs = post_doc[lo:hi], post_tf[lo:hi]
            p.doc_ids.extend(doc_list[lo:hi])
            p.tfs.extend(tf_list[lo:hi])
            self._df[t] = self._df.get(t, 0) + (hi - lo)
        doc_terms = [tuple(dict.fromkeys(toks)) for toks in raw_lists]
        for i in dropped_in:
            doc_terms[i] = tuple(t for t in doc_terms[i] if kept[code_of[t]])
        self._ext_ids.extend(ids)
        self._int_of.update(zip(ids, range(n0, n0 + n)))
        self._doc_len.extend(lens.tolist())
        self._alive.extend([True] * n)
        self._doc_terms.extend(doc_terms)
        self._total_len += int(lens.sum())
        self._n_alive += n
        self._n_postings += len(doc_list)
        gen0 = self._mut_gen
        self._mut_gen += n
        # the cap grows by at most one entry a doc, so trimming once at
        # the end keeps what trimming after every doc would have kept
        self._changelog.extend(zip(range(gen0 + 1, gen0 + n + 1), ids))
        self._trim_changelog_locked()

    def _remove_locked(self, doc_id: str) -> None:
        idx = self._int_of.pop(doc_id, None)
        if idx is None or not self._alive[idx]:
            return
        self._mut_gen += 1
        self._alive[idx] = False
        self._total_len -= self._doc_len[idx]
        self._n_alive -= 1
        for t in self._doc_terms[idx] or ():
            left = self._df.get(t, 0) - 1
            if left > 0:
                self._df[t] = left
            else:
                self._df.pop(t, None)
        self._doc_terms[idx] = None  # release the tombstone's term list

    def remove(self, doc_id: str) -> None:
        with self._lock:
            self._remove_locked(doc_id)

    def _maybe_compact_locked(self) -> None:
        """Re-indexing tombstones the old slot; without compaction a
        hot-update workload grows slots and postings without bound. Rebuild
        in place once dead slots dominate."""
        n_slots = len(self._ext_ids)
        if n_slots < 1024 or self._n_alive * 2 > n_slots:
            return
        remap: Dict[int, int] = {}
        new_ext: List[str] = []
        new_len: List[int] = []
        new_terms: List[Optional[Tuple[str, ...]]] = []
        for old_idx, ext in enumerate(self._ext_ids):
            if self._alive[old_idx]:
                remap[old_idx] = len(new_ext)
                new_ext.append(ext)
                new_len.append(self._doc_len[old_idx])
                new_terms.append(self._doc_terms[old_idx])
        new_postings: Dict[str, _Posting] = {}
        new_df: Dict[str, int] = {}
        for t, p in self._postings.items():
            np_post = _Posting()
            for did, tf in zip(p.doc_ids, p.tfs):
                new_idx = remap.get(did)
                if new_idx is not None:
                    np_post.doc_ids.append(new_idx)
                    np_post.tfs.append(tf)
            if np_post.doc_ids:
                new_postings[t] = np_post
                new_df[t] = len(np_post.doc_ids)
        self._ext_ids = new_ext
        self._doc_len = new_len
        self._alive = [True] * len(new_ext)
        self._int_of = {e: i for i, e in enumerate(new_ext)}
        self._postings = new_postings
        self._df = new_df
        self._doc_terms = new_terms
        self._n_postings = sum(
            len(p.doc_ids) for p in new_postings.values())
        self._mut_gen += 1
        self.compactions += 1
        # slots were remapped: every outstanding snapshot marker is now
        # meaningless, so invalidate the whole changelog window
        self._changelog.clear()
        self._changelog_floor = self._mut_gen

    def __contains__(self, doc_id: str) -> bool:
        with self._lock:
            idx = self._int_of.get(doc_id)
            return idx is not None and self._alive[idx]

    def __len__(self) -> int:
        return self._n_alive

    def ids(self) -> list:
        """Live (non-tombstoned) document ids."""
        with self._lock:
            return [e for e, i in self._int_of.items() if self._alive[i]]

    # -- scoring ---------------------------------------------------------

    def _idf(self, df: int) -> float:
        n = max(self._n_alive, 1)
        return math.log(1.0 + (n - df + 0.5) / (df + 0.5))

    @property
    def mut_gen(self) -> int:
        """Mutation generation — bumped on every add/update/remove/
        compaction. Derived device snapshots key freshness off it."""
        return self._mut_gen

    def term_stats(self, terms: Sequence[str]) -> Tuple[Dict[str, int], int, float]:
        """(live df per term, n_alive, avgdl) in one lock acquisition —
        the host-side idf inputs the device scorer shares with this
        index, read from the incremental counters."""
        with self._lock:
            avgdl = max(self._total_len / max(self._n_alive, 1), 1.0)
            return ({t: self._df.get(t, 0) for t in terms},
                    self._n_alive, avgdl)

    def search(self, query: str, k: int = 10) -> List[Tuple[str, float]]:
        """Top-k (doc_id, bm25_score). Accumulates scores over the query
        terms' postings with NumPy (vectorized tf normalization)."""
        with self._lock:
            return self._search_locked(tokenize(query), k)

    def search_batch(
        self, queries: Sequence[str], k: int = 10
    ) -> List[List[Tuple[str, float]]]:
        """Batched host search: one lock acquisition for the whole batch,
        one result list per query. The host fallback of the device path
        (device_bm25.DeviceBM25.search_batch) shares this contract, so
        callers swap between them without reshaping results."""
        with self._lock:
            return [self._search_locked(tokenize(q), k) for q in queries]

    def _search_locked(self, toks_seq: Sequence[str],
                       k: int) -> List[Tuple[str, float]]:
        # terms iterate in SORTED order and idf is cast to float32:
        # per-doc accumulation then happens in the same order and
        # precision as the device scorer's flattened-entry segment sum,
        # keeping host and device rankings aligned
        toks = sorted(set(toks_seq))
        if not toks or self._n_alive == 0:
            return []
        n_docs = len(self._ext_ids)
        avgdl = max(self._total_len / max(self._n_alive, 1), 1.0)
        scores = np.zeros(n_docs, dtype=np.float32)
        doc_len, alive = self._np_state()
        touched = np.zeros(n_docs, dtype=bool)
        for t in toks:
            p = self._postings.get(t)
            if p is None:
                continue
            ids, tfs = p.arrays()
            # scoring runs over LIVE postings only: a tombstoned slot
            # (re-index leaves one) must not surface — and the df the
            # idf sees is the incremental live counter, which equals
            # the live-posting count by construction
            live = alive[ids]
            ids, tfs = ids[live], tfs[live]
            df = self._df.get(t, 0)
            if df == 0 or ids.size == 0:
                continue
            idf = np.float32(self._idf(df))
            dl = doc_len[ids]
            tf_norm = tfs * (K1 + 1.0) / (tfs + K1 * (1.0 - B + B * dl / avgdl))
            scores[ids] += idf * tf_norm
            touched[ids] = True
        mask = touched & alive
        cand = np.nonzero(mask)[0]
        if cand.size == 0:
            return []
        order = cand[np.argsort(-scores[cand], kind="stable")][:k]
        return [(self._ext_ids[i], float(scores[i])) for i in order]

    def score_docs(
        self, tokens: Sequence[str], doc_ids: Sequence[str]
    ) -> Dict[str, float]:
        """Exact BM25 scores of specific live docs for a tokenized query
        (only docs matching >= 1 term appear). The device snapshot's
        read-your-writes delta side-scan: docs indexed after the
        snapshot are scored here, host-exact, and merged into the
        device top-k."""
        with self._lock:
            toks = sorted(set(tokens))
            want: Dict[int, str] = {}
            for eid in doc_ids:
                idx = self._int_of.get(eid)
                if idx is not None and self._alive[idx]:
                    want[idx] = eid
            if not toks or not want:
                return {}
            avgdl = max(self._total_len / max(self._n_alive, 1), 1.0)
            out: Dict[str, float] = {}
            for t in toks:
                p = self._postings.get(t)
                df = self._df.get(t, 0)
                if p is None or df == 0:
                    continue
                idf = np.float32(self._idf(df))
                ids, tfs = p.arrays()
                # postings append in strictly increasing slot order, so
                # membership is a binary search, not a scan
                want_idx = sorted(want)
                pos = np.searchsorted(ids, want_idx)
                for idx, j in zip(want_idx, pos):
                    if j >= ids.size or int(ids[j]) != idx:
                        continue
                    eid = want[idx]
                    tf = np.float32(tfs[j])
                    dl = np.float32(self._doc_len[idx])
                    tf_norm = tf * np.float32(K1 + 1.0) / (
                        tf + np.float32(K1) * np.float32(1.0 - B + B * dl / avgdl))
                    out[eid] = float(np.float32(out.get(eid, 0.0))
                                     + idf * tf_norm)
            return out

    def csr_snapshot(self) -> Dict[str, object]:
        """Flatten the live postings into CSR arrays for the device
        scorer (device_bm25.py): sorted terms, per-term offset ranges
        over (doc_row, tf) columns in live-row space, plus doc lengths
        and row ext ids. Tombstoned slots are dropped and slot ids are
        remapped to a dense 0..n_live row space."""
        with self._lock:
            doc_len, alive = self._np_state()
            rows = np.nonzero(alive)[0] if len(self._ext_ids) else \
                np.zeros((0,), dtype=np.int64)
            n_slots = len(self._ext_ids)
            remap = np.full(n_slots, -1, dtype=np.int32)
            remap[rows] = np.arange(len(rows), dtype=np.int32)
            terms = sorted(self._postings)
            doc_parts: List[np.ndarray] = []
            tf_parts: List[np.ndarray] = []
            offsets = np.zeros(len(terms) + 1, dtype=np.int64)
            total = 0
            for ti, t in enumerate(terms):
                ids, tfs = self._postings[t].arrays()
                live = alive[ids]
                doc_parts.append(remap[ids[live]])
                tf_parts.append(tfs[live])
                total += int(live.sum())
                offsets[ti + 1] = total
            return {
                "gen": self._mut_gen,
                "compactions": self.compactions,
                "terms": terms,
                "vocab": {t: i for i, t in enumerate(terms)},
                "offsets": offsets,
                "post_doc": (np.concatenate(doc_parts)
                             if doc_parts else np.zeros(0, np.int32)),
                "post_tf": (np.concatenate(tf_parts).astype(np.float32)
                            if tf_parts else np.zeros(0, np.float32)),
                "doc_len": doc_len[rows].astype(np.float32),
                "row_ids": [self._ext_ids[int(s)] for s in rows],
                # original slot per row: consumers live-filter by SLOT
                # (an update tombstones the old slot while the ext id
                # stays live at a new one)
                "slots": rows.astype(np.int64),
            }

    def alive_slots(
        self, slots: Sequence[int],
        expect_compactions: Optional[int] = None,
    ) -> Optional[np.ndarray]:
        """Bool per slot id: still live? Slot ids are only meaningful in
        the slot space they were snapshotted from, so the read and the
        compaction check happen under ONE lock hold: when
        ``expect_compactions`` no longer matches (a compaction remapped
        slots since the snapshot), returns None and the caller must
        fall back rather than trust resurrected slot ids."""
        with self._lock:
            if expect_compactions is not None \
                    and self.compactions != expect_compactions:
                return None
            n = len(self._alive)
            return np.asarray(
                [0 <= s < n and self._alive[int(s)] for s in slots],
                dtype=bool)

    # -- seed selection (BM25-seeded builds) ------------------------------

    def seed_doc_ids(
        self, max_seeds: int = 2048, n_terms: int = 256, per_term: Optional[int] = None
    ) -> List[str]:
        """Lexically discriminative docs: take the `n_terms` highest-IDF
        terms (ignoring hapax noise) and collect their top-tf docs, up to
        `max_seeds`, highest-signal first. These anchor HNSW insertion
        order and k-means init (reference: search.go:3785-3871)."""
        with self._lock:
            if self._n_alive == 0:
                return []
            ranked_terms = []
            for t in self._postings:
                # incremental live-df counter: O(1) per term instead of
                # the old O(postings) alive-scan per term per call
                df = self._df.get(t, 0)
                if df < 2:  # hapax terms don't discriminate clusters
                    continue
                ranked_terms.append((self._idf(df), t))
            ranked_terms.sort(reverse=True)
            per_term = per_term or max(1, max_seeds // max(n_terms, 1))
            seen: Dict[int, None] = {}
            for _, t in ranked_terms[:n_terms]:
                p = self._postings[t]
                order = np.argsort(-np.asarray(p.tfs))[:per_term]
                for j in order:
                    idx = p.doc_ids[int(j)]
                    if self._alive[idx]:
                        seen.setdefault(idx, None)
                if len(seen) >= max_seeds:
                    break
            return [self._ext_ids[i] for i in list(seen)[:max_seeds]]

    # -- persistence ------------------------------------------------------

    def to_dict(self) -> dict:
        with self._lock:
            return {
                "ext_ids": list(self._ext_ids),
                "doc_len": list(self._doc_len),
                "alive": [bool(a) for a in self._alive],
                "postings": {
                    t: {"ids": list(p.doc_ids), "tfs": list(p.tfs)}
                    for t, p in self._postings.items()
                },
            }

    @classmethod
    def from_dict(cls, d: dict) -> "BM25Index":
        idx = cls()
        idx._ext_ids = list(d["ext_ids"])
        idx._doc_len = list(d["doc_len"])
        idx._alive = list(d["alive"])
        idx._int_of = {
            e: i for i, e in enumerate(idx._ext_ids) if idx._alive[i]
        }
        terms_per_doc: List[List[str]] = [[] for _ in idx._ext_ids]
        for t, p in d["postings"].items():
            post = _Posting()
            post.doc_ids = list(p["ids"])
            post.tfs = list(p["tfs"])
            idx._postings[t] = post
            df = 0
            for did in post.doc_ids:
                if idx._alive[did]:
                    df += 1
                    terms_per_doc[did].append(t)
            if df:
                idx._df[t] = df
        idx._doc_terms = [
            tuple(ts) if idx._alive[i] else None
            for i, ts in enumerate(terms_per_doc)
        ]
        idx._total_len = sum(
            l for l, a in zip(idx._doc_len, idx._alive) if a
        )
        idx._n_alive = sum(1 for a in idx._alive if a)
        idx._n_postings = sum(
            len(p.doc_ids) for p in idx._postings.values())
        return idx
