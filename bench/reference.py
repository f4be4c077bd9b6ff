"""Plain reference: exhaustive scoring of the generated corpus, and the check.

The default ``reference`` module of a configuration (``bench/spec.py``):
``make_reference(corpus, order, ends, cfg)``.

Independent of the program: it takes the corpus, the topic arrangement and
the configuration, and computes everything else itself. Its semantics are
the configuration's: BM25 (``bm25_k1``, ``bm25_b``, Lucene's non-negative
idf) in float32, one global linear map to ``impact_bits``-bit integer
impacts (``ceil(score * (2^bits - 1) / max score)``, clipped to
``[1, 2^bits - 1]``), a document's score the sum of its query terms'
impacts, and the top-k ordered by score descending, then docid ascending,
over the arranged docids.

The guarantees it holds an answer to:

* ``rank_safe`` (no budget): the answer is the exhaustive top-k.
* ``certified`` (a budget): the answer is the exhaustive top-k over the
  ranges it says it processed, taken in decreasing BoundSum order (the sum
  over query terms of the term's highest impact in the range; ties by range
  id). An answer that says it exited safe or exhausted the ranges is the
  exhaustive top-k. So every document it misses scores at most the larger
  of its k-th score and the BoundSum of the first range it did not process.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.gen import Corpus

__all__ = ["Answer", "Reference", "make_reference"]


@dataclasses.dataclass(frozen=True)
class Answer:
    """What the check reads of one served query."""

    doc_ids: np.ndarray
    scores: np.ndarray
    ranges_processed: int
    exit_reason: str  # "safe" | "budget" | "exhausted"


class Reference:
    def __init__(
        self,
        corpus: Corpus,
        order: np.ndarray,
        ends: np.ndarray,
        k: int,
        bits: int,
        k1: float,
        b: float,
    ):
        self.k = int(k)
        self.n_docs = corpus.n_docs
        self.n_ranges = int(ends.shape[0])
        new_of_old = np.empty(corpus.n_docs, np.int64)
        new_of_old[order] = np.arange(corpus.n_docs)
        self.range_of = np.searchsorted(ends, np.arange(corpus.n_docs), side="right")

        # BM25 per (doc, term) in float32, then the global linear quantizer.
        doc_of = np.repeat(np.arange(corpus.n_docs), np.diff(corpus.doc_ptr))
        df = np.bincount(corpus.doc_terms, minlength=corpus.n_terms).astype(np.int64)
        n = corpus.n_docs
        idf = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
        avg_len = float(max(corpus.doc_lens.astype(np.float64).mean(), 1.0))
        tf = corpus.doc_tfs.astype(np.float64)
        norm = k1 * (1.0 - b + b * corpus.doc_lens[doc_of].astype(np.float64) / avg_len)
        score = (idf[corpus.doc_terms] * tf * (k1 + 1.0) / (tf + norm)).astype(np.float32)
        top = (1 << int(bits)) - 1
        scale = top / max(float(score.max()), 1e-9)
        impact = np.clip(np.ceil(score.astype(np.float64) * scale), 1, top).astype(np.int64)

        # Postings grouped by term; docids are the arranged (new) ids.
        by_term = np.argsort(corpus.doc_terms, kind="stable")
        self.ptr = np.zeros(corpus.n_terms + 1, np.int64)
        np.cumsum(np.bincount(corpus.doc_terms, minlength=corpus.n_terms), out=self.ptr[1:])
        self.docs = new_of_old[doc_of[by_term]]
        self.impacts = impact[by_term]

    def scores(self, q: np.ndarray) -> np.ndarray:
        """Exact integer score of every (arranged) docid for query ``q``."""
        sl = [slice(self.ptr[t], self.ptr[t + 1]) for t in np.asarray(q) if t >= 0]
        if not sl:
            return np.zeros(self.n_docs, np.int64)
        docs = np.concatenate([self.docs[s] for s in sl])
        imps = np.concatenate([self.impacts[s] for s in sl])
        return np.bincount(docs, weights=imps, minlength=self.n_docs).astype(np.int64)

    def range_order(self, q: np.ndarray) -> np.ndarray:
        """Ranges in decreasing BoundSum order for ``q`` (ties: range id)."""
        bsum = np.zeros(self.n_ranges, np.int64)
        for t in np.asarray(q):
            if t < 0:
                continue
            s = slice(self.ptr[t], self.ptr[t + 1])
            u = np.zeros(self.n_ranges, np.int64)
            np.maximum.at(u, self.range_of[self.docs[s]], self.impacts[s])
            bsum += u
        return np.argsort(-bsum, kind="stable")

    def topk(self, acc: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Top-k of positive scores: score descending, docid ascending."""
        nz = np.flatnonzero(acc > 0)
        k = min(self.k, nz.size)
        if k == 0:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        kth = np.partition(acc[nz], nz.size - k)[nz.size - k]
        cand = nz[acc[nz] >= kth]
        sel = cand[np.lexsort((cand, -acc[cand]))][:k]
        return sel, acc[sel]

    def expected(self, q: np.ndarray, answer: Answer | None, guarantee: str):
        """The answer the guarantee allows for ``q``: (doc_ids, scores)."""
        acc = self.scores(q)
        if guarantee == "certified" and answer is not None and answer.exit_reason == "budget":
            done = self.range_order(q)[: max(0, min(answer.ranges_processed, self.n_ranges))]
            acc = np.where(np.isin(self.range_of, done), acc, 0)
        return self.topk(acc)

    def check(self, q: np.ndarray, answer: Answer, guarantee: str) -> bool:
        """True when ``answer`` is what the guarantee allows, bit for bit."""
        if not 0 <= answer.ranges_processed <= self.n_ranges:
            return False
        ids, sc = self.expected(q, answer, guarantee)
        return (
            np.array_equal(np.asarray(answer.doc_ids, np.int64), ids)
            and np.array_equal(np.asarray(answer.scores, np.int64), sc)
        )

    def recall(self, q: np.ndarray, answer: Answer) -> tuple[int, int]:
        """(found, wanted): exhaustive top-k documents the answer holds.

        An answered document that ties the exhaustive k-th score counts as
        found, since either document is a correct k-th answer.
        """
        acc = self.scores(q)
        ids, sc = self.topk(acc)
        if ids.size == 0:
            return 0, 0
        got = np.asarray(answer.doc_ids, np.int64)
        got = got[(got >= 0) & (got < self.n_docs)]
        found = int((acc[got] >= sc[-1]).sum())
        return min(found, ids.size), int(ids.size)


def make_reference(corpus: Corpus, order: np.ndarray, ends: np.ndarray, cfg: dict) -> Reference:
    """The reference at the configuration's k, impact bits and BM25."""
    return Reference(
        corpus, order, ends, int(cfg["k"]), int(cfg["impact_bits"]),
        float(cfg["bm25_k1"]), float(cfg["bm25_b"]),
    )
