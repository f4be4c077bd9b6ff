"""Hypothesis property tests on the system's core invariants.

Random corpora/queries at small scale; each property is one the engine's
correctness rests on:
  * BoundSum admissibility: sum_t U[t,r] upper-bounds every document score
    inside range r (the safe-termination proof's premise);
  * end-to-end rank safety: the safe traversal equals the oracle on
    arbitrary corpora, not just the shared fixtures;
  * quantization order preservation (up to quantization ties).
"""

from __future__ import annotations

import numpy as np
from hypothesis import example, given, settings, strategies as st

from repro.core.clustered_index import build_index
from repro.core.oracle import exhaustive_scores, exhaustive_topk
from repro.core.quantize import fit_quantizer
from repro.core.range_daat import Engine
from repro.data.synth import make_corpus, make_query_log


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**30), n_ranges=st.sampled_from([2, 4, 7]))
def test_boundsum_is_admissible(seed, n_ranges):
    corpus = make_corpus(n_docs=300, n_terms=300, n_topics=4,
                         mean_doc_len=40, seed=seed % 1000)
    idx = build_index(corpus, n_ranges=n_ranges, strategy="clustered")
    ql = make_query_log(corpus, n_queries=4, seed=seed % 997)
    range_of = np.searchsorted(idx.range_ends, np.arange(idx.n_docs), "right")
    for i in range(ql.n_queries):
        q = [int(t) for t in ql.terms[i] if t >= 0]
        scores = exhaustive_scores(idx, np.asarray(q))
        bsum = idx.bounds_dense[q].sum(axis=0)
        for r in range(idx.n_ranges):
            m = range_of == r
            if m.any():
                assert scores[m].max() <= bsum[r], (r, scores[m].max(), bsum[r])


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**30))
# Seed 103: a doc tied at the k-th score with a smaller docid sits in a
# later range; non-strict pruning / safe stop used to drop it.
@example(seed=103)
def test_safe_traversal_safe_on_random_corpora(seed):
    corpus = make_corpus(n_docs=250, n_terms=250, n_topics=3,
                         mean_doc_len=30, seed=seed % 1000)
    idx = build_index(corpus, n_ranges=3, strategy="clustered_random")
    eng = Engine(idx, k=5)
    ql = make_query_log(corpus, n_queries=3, seed=seed % 991)
    for i in range(ql.n_queries):
        res = eng.traverse(eng.plan(ql.terms[i]))
        ids, vals = eng.topk_docs(res.state)
        oid, osc = exhaustive_topk(idx, ql.terms[i], 5)
        assert ids.tolist() == oid.tolist()
        assert vals.tolist() == osc.tolist()


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    bits=st.sampled_from([4, 6, 8, 10]),
)
def test_quantizer_preserves_order_up_to_ties(seed, bits):
    rng = np.random.default_rng(seed)
    scores = rng.uniform(0.01, 10.0, size=64).astype(np.float32)
    q = fit_quantizer(scores, bits=bits)
    imp = q.quantize(scores)
    order = np.argsort(scores)
    assert np.all(np.diff(imp[order]) >= 0)  # monotone in the float order
    # Round trip is within one quantization step.
    back = q.dequantize(imp)
    assert np.all(np.abs(back - scores) <= 1.0 / q.scale + 1e-6)
