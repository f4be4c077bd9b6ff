"""The inputs are a function of the seed, and every seed gets the same sizes."""

import numpy as np

from bench import gen
from bench.tests.conftest import tiny_config, tiny_traffic

BIG = 2**31 + 12345  # seeds run past 32 signed bits


def _inputs(seed):
    cfg, tr = tiny_config(), tiny_traffic(sla=True)
    c = gen.make_corpus(cfg, seed)
    s = gen.make_schedule(tr, 6.0, seed)
    return c, s, gen.make_queries(c, tr, len(s), seed)


def test_same_seed_same_inputs():
    (c1, s1, q1), (c2, s2, q2) = _inputs(BIG), _inputs(BIG)
    for a, b in [(c1.doc_ptr, c2.doc_ptr), (c1.doc_terms, c2.doc_terms), (c1.doc_tfs, c2.doc_tfs),
                 (c1.doc_topic, c2.doc_topic), (s1, s2), (q1, q2)]:
        assert np.array_equal(a, b)


def test_other_seed_other_inputs_same_sizes():
    (c1, s1, q1), (c2, s2, q2) = _inputs(BIG), _inputs(BIG + 1)
    assert not np.array_equal(c1.doc_terms[:1000], c2.doc_terms[:1000])
    assert not np.array_equal(s1, s2)
    assert not np.array_equal(q1, q2)
    # The same multisets of sizes, handed out in another order.
    assert np.array_equal(np.sort(c1.doc_lens), np.sort(c2.doc_lens))
    assert np.array_equal(np.sort(np.bincount(c1.doc_topic)), np.sort(np.bincount(c2.doc_topic)))
    assert len(s1) == len(s2)
    assert np.array_equal(np.sort((q1 >= 0).sum(1)), np.sort((q2 >= 0).sum(1)))


def test_queries_are_distinct_content_terms():
    c, s, q = _inputs(7)
    df = np.bincount(c.doc_terms, minlength=c.n_terms)
    for row in q:
        t = row[row >= 0]
        assert t.size >= 1 and np.unique(t).size == t.size
        assert np.all(df[t] >= 5)


def test_schedule_follows_the_phases():
    tr = tiny_traffic(rate=100.0, sla=True)  # 4 s at 50/s, 1 s at 300/s
    due = gen.make_schedule(tr, 10.0, 3)
    assert len(due) == 1000 and np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 10.0
    burst = ((due % 5.0) >= 4.0).sum()
    assert 0.5 < burst / len(due) < 0.7  # 300 of 500 per cycle in the burst


def test_topic_arrangement_is_a_partition_by_topic():
    c, _, _ = _inputs(5)
    order, ends = gen.arrange(c)
    assert np.array_equal(np.sort(order), np.arange(c.n_docs))
    topics = c.doc_topic[order]
    starts = np.concatenate([[0], ends[:-1]])
    for a, b in zip(starts, ends):
        assert np.all(topics[a:b] == topics[a])
