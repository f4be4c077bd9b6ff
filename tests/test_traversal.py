"""Traversal correctness: rank-safety, pruning soundness, anytime behavior."""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.clustered_index import BLOCK, block_rows, shard_device_index
from repro.core.metrics import rbo
from repro.core.oracle import exhaustive_scores, exhaustive_topk
from repro.core.range_daat import Engine, init_state, pack_impacts
from repro.core.anytime import run_query_anytime, Fixed
from repro.obs import Instrumentation
from repro.serving.sharded import ShardedEngine


def _score_multiset(state):
    ids = np.asarray(state.ids)
    vals = np.asarray(state.vals)
    return sorted(vals[ids >= 0].tolist(), reverse=True)


def test_safe_traversal_matches_oracle(engine, index, queries):
    for q in queries:
        plan = engine.plan(q)
        res = engine.traverse(plan)
        _, osc = exhaustive_topk(index, q, engine.k)
        assert _score_multiset(res.state) == sorted(osc.tolist(), reverse=True)


def test_safe_traversal_k100(index, queries):
    eng = Engine(index, k=100)
    for q in queries[:4]:
        res = eng.traverse(eng.plan(q))
        _, osc = exhaustive_topk(index, q, 100)
        assert _score_multiset(res.state) == sorted(osc.tolist(), reverse=True)


def test_range_oblivious_also_safe(index, queries):
    """Docid-order traversal with global bounds must still be rank-safe."""
    eng = Engine(index, k=10, ordering="docid", bounds="global")
    for q in queries[:6]:
        res = eng.traverse(eng.plan(q))
        _, osc = exhaustive_topk(index, q, 10)
        assert _score_multiset(res.state) == sorted(osc.tolist(), reverse=True)


def test_no_block_pruning_still_safe(engine, index, queries):
    for q in queries[:4]:
        res = engine.traverse(engine.plan(q), prune_blocks=False)
        _, osc = exhaustive_topk(index, q, 10)
        assert _score_multiset(res.state) == sorted(osc.tolist(), reverse=True)


def test_budget_scores_never_exceed_truth(engine, index, queries):
    """Anytime (unsafe) exits return only true-or-partial scores."""
    for q in queries[:6]:
        plan = engine.plan(q)
        res = engine.traverse(plan, budget_postings=500, safe_stop=False)
        truth = exhaustive_scores(index, q)
        ids = np.asarray(res.state.ids)
        vals = np.asarray(res.state.vals)
        for d, v in zip(ids, vals):
            if d >= 0:
                assert v <= truth[d]


def test_budget_monotone_quality(engine, index, queries):
    """More budget -> same or better RBO vs exhaustive (on average)."""
    deltas = []
    for q in queries[:8]:
        plan = engine.plan(q)
        oid, _ = exhaustive_topk(index, q, 10)
        lo = engine.traverse(plan, budget_postings=300, safe_stop=False)
        hi = engine.traverse(plan, budget_postings=10**9)
        ids_lo, _ = engine.topk_docs(lo.state)
        ids_hi, _ = engine.topk_docs(hi.state)
        deltas.append(
            rbo(ids_hi.tolist(), oid.tolist()) - rbo(ids_lo.tolist(), oid.tolist())
        )
    assert np.mean(deltas) >= 0.0


def test_fixed_policy_limits_ranges(engine, queries):
    plan = engine.plan(queries[0])
    res = run_query_anytime(engine, plan, policy=Fixed(2), budget_ms=1e9)
    assert res.ranges_processed <= 2


def test_host_executor_matches_oracle_when_unlimited(engine, index, queries):
    for q in queries[:4]:
        plan = engine.plan(q)
        res = run_query_anytime(engine, plan, policy=None, budget_ms=float("inf"))
        oid, osc = exhaustive_topk(index, q, 10)
        assert sorted(res.scores.tolist(), reverse=True) == sorted(
            osc.tolist(), reverse=True
        )
        assert res.exit_reason in ("exhausted", "safe")


def test_boundsum_order_front_loads_mass(engine, index, queries):
    """BoundSum-first processing finds top-1 earlier than docid order."""
    wins = 0
    total = 0
    for q in queries:
        plan = engine.plan(q)
        oid, _ = exhaustive_topk(index, q, 1)
        if oid.size == 0:
            continue
        top_range = int(
            np.searchsorted(index.range_ends, oid[0], side="right")
        )
        pos_bs = int(np.nonzero(plan.order_host == top_range)[0][0])
        pos_docid = top_range
        total += 1
        if pos_bs <= pos_docid:
            wins += 1
    assert total > 0 and wins / total >= 0.5


def test_safe_exit_skips_work_vs_exhaustive(engine, queries):
    """Safe termination should usually process fewer than all ranges."""
    processed = []
    R = engine.index.n_ranges
    for q in queries:
        res = engine.traverse(engine.plan(q))
        processed.append(int(res.ranges_processed))
    assert min(processed) <= R  # sanity
    assert np.mean(processed) <= R


# ------------------------------------------- block rows vs flat offsets


def _flat_offset_step(index, vals, ids, blk, rest, r, k, s_pad):
    """One range step the way the flat-offset scorer took it, in numpy:
    prune by the heap's threshold, read every lane at ``blk_start + lane``
    of the [nnz] arrays, accumulate, take the range's top-k, merge."""
    th = vals[-1]
    safe = np.clip(blk, 0, None)
    lens = index.blk_len[safe]
    keep = (blk >= 0) & (index.blk_maximp[safe].astype(np.int64) + rest >= th)
    starts = np.where(blk >= 0, index.blk_start[safe], -1)
    lane = np.arange(BLOCK)
    offs = np.clip(starts[:, None] + lane, 0, index.nnz - 1)
    valid = (lane < lens[:, None]) & keep[:, None] & (starts >= 0)[:, None]
    r0 = int(index.range_starts[r])
    acc = np.zeros(s_pad, np.int64)
    np.add.at(acc, index.docs[offs][valid] - r0, index.impacts[offs][valid])
    loc = np.lexsort((np.arange(s_pad), -acc))[:k]  # lax.top_k: ties by index
    top = acc[loc]
    cand = np.where(top > 0, loc + r0, -1)
    all_v = np.concatenate([vals, top]).astype(np.int32)
    all_i = np.concatenate([ids, cand]).astype(np.int32)
    i_key = np.where(all_i >= 0, all_i, 2**31 - 1)
    sel = np.lexsort((i_key, -all_v.astype(np.int64)))[:k]
    return all_v[sel], all_i[sel], int(lens[keep].sum()), int(keep.sum())


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("docs_format", ["int32", "packed"])
@pytest.mark.parametrize("impact_dtype", ["int32", "int8"])
def test_range_steps_match_flat_offset_reference(
    index, queries, impact_dtype, docs_format, impl
):
    """Chained ``score_range_step`` calls over the block rows equal the
    flat-offset reference bit for bit: heap, postings and blocks after
    every range, with -1 padded tables and blocks pruned by the heap."""
    eng = Engine(index, k=10, impl=impl, impact_dtype=impact_dtype,
                 docs_format=docs_format)
    pruned = 0
    for q in queries[:3 if impl == "xla" else 1]:
        plan = eng.plan(q)
        blk, rest = np.asarray(plan.blk_tab), np.asarray(plan.rest_tab)
        assert (blk < 0).any()  # tables are padded to a power of two
        state = init_state(eng.k)
        vals = np.zeros(eng.k, np.int32)
        ids = np.full(eng.k, -1, np.int32)
        postings = blocks = 0
        for i in range(index.n_ranges):
            state = eng.step(plan, state, i)
            r = int(plan.order_host[i])
            vals, ids, p, b = _flat_offset_step(
                index, vals, ids, blk[r], rest[r], r, eng.k, eng.s_pad
            )
            postings, blocks = postings + p, blocks + b
            pruned += int((blk[r] >= 0).sum()) - b
            np.testing.assert_array_equal(np.asarray(state.vals), vals)
            np.testing.assert_array_equal(np.asarray(state.ids), ids)
            assert int(state.postings) == postings
            assert int(state.blocks) == blocks
    assert pruned > 0  # the threshold pruned real blocks along the way


@pytest.mark.parametrize(
    "docs_format,impact_dtype",
    [("int32", "int32"), ("int32", "int8"), ("packed", "int8")],
)
def test_sharded_stack_holds_each_shards_rows(index, docs_format, impact_dtype):
    """Shard-major rows: shard s's first NB_s rows are its own block rows,
    the rows padding it to the largest shard's NB are zero."""
    eng = Engine(index, k=10, impact_dtype=impact_dtype, docs_format=docs_format)
    shards = shard_device_index(index, 3)
    nbs = [sh.blk_len.shape[0] for sh in shards]
    assert len(set(nbs)) > 1  # unequal NB per shard
    se = ShardedEngine(eng, 3, shards=shards, use_mesh=False)
    imps = np.asarray(se.dix.blk_imps)
    assert imps.shape == (3, max(nbs), BLOCK)
    assert imps.dtype == np.dtype(impact_dtype)
    for s, sh in enumerate(shards):
        geometry = (sh.blk_start, sh.blk_len)
        want = block_rows(pack_impacts(sh.impacts, impact_dtype), *geometry)
        np.testing.assert_array_equal(imps[s, : nbs[s]], want)
        assert not imps[s, nbs[s]:].any()
        if docs_format == "int32":
            docs = np.asarray(se.dix.blk_docs)
            np.testing.assert_array_equal(
                docs[s, : nbs[s]], block_rows(sh.docs.astype(np.int32), *geometry)
            )
            assert not docs[s, nbs[s]:].any()
        else:
            assert se.dix.blk_docs is None


@pytest.mark.parametrize("docs_format", ["int32", "packed"])
def test_index_block_fill_gauge(index, docs_format):
    """``index_block_fill`` is the percent of uploaded block lanes that
    hold a posting, counted here lane by lane."""
    obs = Instrumentation.make()
    Engine(index, k=10, docs_format=docs_format, obs=obs)
    lanes = np.arange(BLOCK)[None, :] < index.blk_len[:, None]
    want = 100.0 * lanes.sum() / lanes.size
    assert lanes.sum() == index.nnz
    got = obs.metrics.gauge("index_block_fill").value()
    assert got == pytest.approx(want, rel=1e-12)
    assert 0.0 < got < 100.0
