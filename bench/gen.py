"""Inputs of a run, all made from ``--seed``: corpus, queries, arrival schedule.

The default ``inputs`` module of a configuration (``bench/spec.py``): the
corpus, its arrangement into ranges, the queries, and the program's index
built from them (``build_index``). The arrival schedule is the traffic's,
for every configuration.

The semantics follow the repo's planted-topic generator (``repro.data.synth``):
each topic owns a permuted Zipf distribution over the vocabulary, a document
draws its tokens from its topic's distribution, and a query draws distinct
content terms from its topic's highest tf-idf vocabulary. Everything here is
vectorised, so a 30M-token corpus takes seconds instead of minutes.

What a seed may change and what it may not: every seed gets the same *set*
of sizes (topic sizes, document lengths, query lengths, inter-arrival gaps),
drawn once from the configuration's ``shape_seed`` or from fixed quantiles,
and the seed only permutes them and draws the content (which terms, which
topic, which order). The content still moves the work: which terms a query
draws sets how many blocks it plans and how long its posting lists are, so
seeds of one cell differ by more than two runs of one seed do.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "Corpus",
    "arrange",
    "build_index",
    "make_corpus",
    "make_queries",
    "make_schedule",
]

# Independent random streams of one seed.
_CORPUS, _QUERIES, _ARRIVALS, _LEAD_IN = 0, 1, 2, 3


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


@dataclasses.dataclass(frozen=True)
class Corpus:
    """Bag-of-words corpus in CSR layout, doc -> (term, tf), terms sorted."""

    n_docs: int
    n_terms: int
    n_topics: int
    doc_ptr: np.ndarray  # [n_docs + 1] int64
    doc_terms: np.ndarray  # [nnz] int32
    doc_tfs: np.ndarray  # [nnz] int32
    doc_topic: np.ndarray  # [n_docs] int32
    doc_lens: np.ndarray  # [n_docs] int64 tokens per document

    @property
    def nnz(self) -> int:
        return int(self.doc_terms.shape[0])


def _split(total: int, weights: np.ndarray) -> np.ndarray:
    """Integer parts of ``total`` in proportion to ``weights`` (largest remainder)."""
    w = np.asarray(weights, np.float64)
    exact = total * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    short = total - int(out.sum())
    out[np.argsort(-(exact - out), kind="stable")[:short]] += 1
    return out


def _alias_table(weights: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Walker's alias table: draw ``j`` uniformly, keep it with ``prob[j]``,
    else take ``alias[j]``; each index comes out in proportion to its weight."""
    n = weights.size
    scaled = weights * (n / weights.sum())
    prob = np.ones(n)
    alias = np.arange(n)
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        prob[s], alias[s] = scaled[s], g
        scaled[g] -= 1.0 - scaled[s]
        (small if scaled[g] < 1.0 else large).append(g)
    return prob, alias


def make_corpus(cfg: dict, seed: int) -> Corpus:
    """The configuration's corpus for ``seed``.

    Keys read: n_docs, n_terms, n_topics, mean_doc_len, doc_len_sigma,
    min_doc_len, zipf_s, topic_concentration, shape_seed.
    """
    n_docs, n_terms, n_topics = int(cfg["n_docs"]), int(cfg["n_terms"]), int(cfg["n_topics"])
    shape = np.random.default_rng(int(cfg["shape_seed"]))
    rng = _rng(seed, _CORPUS)

    # Sizes: one multiset for every seed (Dirichlet-imbalanced topics,
    # lognormal lengths), handed out in a seeded order.
    sizes = _split(n_docs, shape.dirichlet(np.full(n_topics, float(cfg["topic_concentration"]))))
    lens = np.maximum(
        int(cfg["min_doc_len"]),
        shape.lognormal(np.log(cfg["mean_doc_len"]), cfg["doc_len_sigma"], size=n_docs),
    ).astype(np.int64)
    doc_topic = rng.permutation(np.repeat(rng.permutation(n_topics), sizes)).astype(np.int32)
    lens = rng.permutation(lens)

    # Each topic: the shared Zipf pmf over a permuted vocabulary whose small
    # common head (function words) keeps its rank in every topic.
    prob, alias = _alias_table(np.arange(1, n_terms + 1, dtype=np.float64) ** -float(cfg["zipf_s"]))
    n_common = max(8, n_terms // 200)
    perms = np.tile(np.arange(n_terms, dtype=np.int32), (n_topics, 1))
    for t in range(n_topics):
        perms[t, n_common:] = rng.permutation(perms[t, n_common:])

    doc_of_token = np.repeat(np.arange(n_docs, dtype=np.int64), lens)
    rank = rng.integers(0, n_terms, size=doc_of_token.size)
    rank = np.where(rng.random(rank.size) < prob[rank], rank, alias[rank])
    term = perms[doc_topic[doc_of_token], rank].astype(np.int64)
    del rank
    keys, tfs = np.unique(doc_of_token * n_terms + term, return_counts=True)
    del doc_of_token, term
    docs = keys // n_terms
    doc_ptr = np.zeros(n_docs + 1, np.int64)
    np.cumsum(np.bincount(docs, minlength=n_docs), out=doc_ptr[1:])
    return Corpus(
        n_docs=n_docs,
        n_terms=n_terms,
        n_topics=n_topics,
        doc_ptr=doc_ptr,
        doc_terms=(keys % n_terms).astype(np.int32),
        doc_tfs=tfs.astype(np.int32),
        doc_topic=doc_topic,
        doc_lens=lens,
    )


def arrange(corpus: Corpus) -> tuple[np.ndarray, np.ndarray]:
    """Docs sorted by topic, one range per non-empty topic.

    Returns ``(order, ends)``: ``order[new_id] = old_id`` and ``ends[r]``
    one past the last new docid of range ``r``. The topic labels stand in
    for the topical clustering the paper takes as an input.
    """
    order = np.argsort(corpus.doc_topic, kind="stable").astype(np.int64)
    counts = np.bincount(corpus.doc_topic, minlength=corpus.n_topics)
    ends = np.cumsum(counts[counts > 0]).astype(np.int64)
    return order, ends


def _program_corpus(c: Corpus):
    from repro.data.synth import Corpus as ProgramCorpus

    return ProgramCorpus(
        n_docs=c.n_docs,
        n_terms=c.n_terms,
        doc_ptr=c.doc_ptr,
        doc_terms=c.doc_terms,
        doc_tfs=c.doc_tfs,
        doc_topic=c.doc_topic,
        n_topics=c.n_topics,
    )


def build_index(cfg: dict, corpus: Corpus, order: np.ndarray, ends: np.ndarray):
    """The program's ``build_index`` over the corpus, at the configuration's BM25 and impact bits."""
    from repro.core import build_index as program_build_index
    from repro.core.bm25 import BM25Params
    from repro.core.reorder import Arrangement

    return program_build_index(
        _program_corpus(corpus),
        arrangement=Arrangement(order, ends, "clustered"),
        bits=int(cfg["impact_bits"]),
        params=BM25Params(k1=float(cfg["bm25_k1"]), b=float(cfg["bm25_b"])),
    )


def _length_counts(n: int, length_pmf: dict) -> tuple[np.ndarray, np.ndarray]:
    lengths = np.asarray(sorted(int(k) for k in length_pmf), np.int64)
    pmf = np.asarray([float(length_pmf[str(k)]) for k in lengths])
    return lengths, _split(n, pmf)


def make_queries(
    corpus: Corpus, traffic: dict, n: int, seed: int, lead_in: bool = False
) -> np.ndarray:
    """``[n, max_len]`` int32 query terms, -1 padded, each row sorted.

    ``lead_in`` draws from a stream of its own: queries of the same mix
    that the server saw before the window (see ``bench/layouts/single.lead_in``).

    Keys read from the traffic mix: ``query_lengths`` (length -> share),
    ``term_pool`` (candidates per topic), ``df_min``, ``df_max_frac``.
    A query's topic is uniform; its terms are distinct and drawn, weighted
    by topic tf-idf mass, from the topic's pool of content words (document
    frequency within [df_min, df_max_frac * n_docs]).
    """
    rng = _rng(seed, _LEAD_IN if lead_in else _QUERIES)
    V, T = corpus.n_terms, corpus.n_topics
    doc_of = np.repeat(np.arange(corpus.n_docs), np.diff(corpus.doc_ptr))
    df = np.bincount(corpus.doc_terms, minlength=V)
    idf = np.log(1.0 + corpus.n_docs / np.maximum(df, 1))
    mass = np.bincount(
        corpus.doc_topic[doc_of].astype(np.int64) * V + corpus.doc_terms,
        weights=corpus.doc_tfs,
        minlength=T * V,
    ).reshape(T, V)
    informative = mass * idf[None, :]
    df_max = max(int(traffic["df_min"]) + 1, int(traffic["df_max_frac"] * corpus.n_docs))
    content = (df >= int(traffic["df_min"])) & (df <= df_max)
    informative *= content[None, :]

    pool = int(traffic["term_pool"])
    top = np.argsort(-informative, axis=1, kind="stable")[:, :pool]  # [T, pool]
    weight = np.take_along_axis(informative, top, axis=1)

    lengths, counts = _length_counts(n, traffic["query_lengths"])
    qlen = rng.permutation(np.repeat(lengths, counts))
    topic = rng.integers(0, T, size=n)
    # Gumbel top-L: the same law as drawing L terms one by one without
    # replacement, each in proportion to its weight.
    with np.errstate(divide="ignore"):
        keys = np.log(weight[topic]) + rng.gumbel(size=(n, pool))
    pick = np.argsort(-keys, axis=1, kind="stable")[:, : int(lengths.max())]
    terms = np.take_along_axis(top[topic], pick, axis=1).astype(np.int32)
    ok = np.isfinite(np.take_along_axis(keys, pick, axis=1))
    terms[(np.arange(terms.shape[1])[None, :] >= qlen[:, None]) | ~ok] = -1
    terms = np.where(terms < 0, np.iinfo(np.int32).max, terms)
    terms.sort(axis=1)
    return np.where(terms == np.iinfo(np.int32).max, -1, terms).astype(np.int32)


def _intensity(traffic: dict, seconds: float, grid: int = 20_000):
    """Cumulative offered load Lambda(t) on a fine grid, for time rescaling."""
    rate = float(traffic["rate_qps"])
    phases = traffic.get("phases") or [[1.0, 1.0]]
    period = sum(float(p[0]) for p in phases)
    t = np.linspace(0.0, seconds, grid + 1)
    bounds = np.cumsum([0.0] + [float(p[0]) for p in phases])
    mult = np.asarray([float(p[1]) for p in phases])
    phase = np.searchsorted(bounds, np.mod(t[:-1], period), side="right") - 1
    lam = np.concatenate([[0.0], np.cumsum(rate * mult[phase] * np.diff(t))])
    return t, lam


def make_schedule(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times of the window's queries: ascending seconds in ``[0, seconds)``.

    A Poisson process of rate ``rate_qps`` whose rate is multiplied, phase
    by phase, by ``phases`` (``[[seconds, multiplier], ...]``, repeated;
    absent means a constant rate). The count is the expected one, and the
    unit-rate gaps are the exponential's quantiles in a seeded order, mapped
    back through the cumulative intensity.
    """
    t, lam = _intensity(traffic, seconds)
    n = int(round(lam[-1]))
    u = (np.arange(n + 1) + 0.5) / (n + 1)
    gaps = _rng(seed, _ARRIVALS).permutation(-np.log1p(-u))
    at = np.cumsum(gaps)[:n] * (lam[-1] / gaps.sum())
    return np.minimum(np.interp(at, lam, t), np.nextafter(seconds, 0.0))
