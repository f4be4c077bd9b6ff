"""A configuration's own ``inputs`` module, as a test names it.

The default corpus and queries, with every topic's range cut in two, so
that the index and the reference both have to follow this arrangement.
Each call is recorded in ``CALLS``.
"""

import numpy as np

from bench import gen

CALLS: list[str] = []


def make_corpus(cfg, seed):
    CALLS.append("make_corpus")
    return gen.make_corpus(cfg, seed)


def arrange(corpus):
    CALLS.append("arrange")
    order, ends = gen.arrange(corpus)
    starts = np.concatenate([[0], ends[:-1]])
    mids = (starts + ends) // 2
    return order, np.unique(np.concatenate([mids[mids > starts], ends]))


def make_queries(corpus, traffic, n, seed, lead_in=False):
    CALLS.append("make_queries")
    return gen.make_queries(corpus, traffic, n, seed, lead_in=lead_in)


def build_index(cfg, corpus, order, ends):
    CALLS.append("build_index")
    return gen.build_index(cfg, corpus, order, ends)
