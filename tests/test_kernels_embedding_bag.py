"""Embedding-bag kernel + EmbeddingBag semantics vs oracle."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.embedding_bag.ops import bag_reduce
from repro.models.embedding import embedding_bag, embedding_bag_ragged


@pytest.mark.parametrize("B,L,D", [(4, 3, 8), (17, 20, 32), (128, 200, 64), (33, 7, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bag_reduce_matches_ref(B, L, D, dtype):
    rng = np.random.default_rng(B * 1000 + L)
    rows = rng.normal(0, 1, size=(B, L, D)).astype(np.float32)
    w = rng.normal(0, 1, size=(B, L)).astype(np.float32)
    got = bag_reduce(jnp.asarray(rows, dtype), jnp.asarray(w, dtype), impl="pallas")
    ref = bag_reduce(jnp.asarray(rows, dtype), jnp.asarray(w, dtype), impl="xla")
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_embedding_bag_padding_and_mean():
    table = jnp.asarray(np.arange(20, dtype=np.float32).reshape(10, 2))
    ids = jnp.asarray([[1, 2, -1], [3, -1, -1]], jnp.int32)
    out_sum = embedding_bag(table, ids, combine="sum")
    np.testing.assert_allclose(np.asarray(out_sum[0]), table[1] + table[2])
    np.testing.assert_allclose(np.asarray(out_sum[1]), table[3])
    out_mean = embedding_bag(table, ids, combine="mean")
    np.testing.assert_allclose(np.asarray(out_mean[0]), (table[1] + table[2]) / 2)
    np.testing.assert_allclose(np.asarray(out_mean[1]), table[3])


def test_embedding_bag_pallas_path_matches_xla():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(50, 16)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, 50, size=(8, 5)), jnp.int32)
    a = embedding_bag(table, ids, impl="xla")
    b = embedding_bag(table, ids, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n_bags=st.integers(1, 12),
    n_ids=st.integers(1, 64),
)
def test_property_ragged_equals_dense_grouping(seed, n_bags, n_ids):
    """Ragged segment-sum bags == manual per-bag sums."""
    rng = np.random.default_rng(seed)
    table = rng.normal(size=(30, 4)).astype(np.float32)
    flat = rng.integers(0, 30, size=n_ids).astype(np.int32)
    seg = np.sort(rng.integers(0, n_bags, size=n_ids)).astype(np.int32)
    out = embedding_bag_ragged(jnp.asarray(table), jnp.asarray(flat), jnp.asarray(seg), n_bags)
    expect = np.zeros((n_bags, 4), np.float32)
    for i, s in zip(flat, seg):
        expect[s] += table[i]
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-5)
