"""Differential harness: bitwise parity between engine configurations.

The repo's correctness story leans on one invariant, stated many times in
DESIGN.md: every execution path over the same index — scorer impl, impact
storage dtype, docid encoding, shard count — must produce *bitwise*
identical results: doc ids, scores, tie-breaks, work counters, and exit
reasons. This module is the single place that invariant is mechanised so
each new representation (int8 impacts, packed docids, ...) pins itself
with one `assert_bitwise_equal_engines` call instead of ad-hoc loops.

Two layers:

  * ``EngineConfig`` + ``assert_bitwise_equal_engines`` — build two engines
    over one index and compare every host-observable of every query.
  * ``assert_batch_matches_sequential`` / ``assert_sharded_matches_engine``
    — the batched-vs-looped and sharded-vs-single parity assertions shared
    by the serving test suites.

All comparisons normalise through ``np.asarray(...).tolist()`` so device
arrays, numpy scalars, and plain ints compare by value, and a failure
names the query and both configurations.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro.core.clustered_index import ClusteredIndex, build_index
from repro.core.range_daat import Engine
from repro.serving import ShardedEngine

__all__ = [
    "EngineConfig",
    "build_engine",
    "observe_query",
    "assert_results_equal",
    "assert_bitwise_equal_engines",
    "assert_batch_matches_sequential",
    "assert_sharded_matches_engine",
    "assert_exit_reason_conservation",
]

INT32_MAX = 2**31 - 1


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """One engine construction recipe; the harness compares two of these."""

    impact_dtype: str = "int32"
    docs_format: str = "int32"
    impl: str = "xla"
    n_shards: int = 1

    def describe(self) -> str:
        return (
            f"{self.impl}/{self.impact_dtype}/{self.docs_format}"
            f"/shards={self.n_shards}"
        )


def build_engine(index: ClusteredIndex, cfg: EngineConfig, k: int = 5):
    """An ``Engine`` (or vmap-path ``ShardedEngine``) per the config."""
    eng = Engine(
        index,
        k=k,
        impl=cfg.impl,
        impact_dtype=cfg.impact_dtype,
        docs_format=cfg.docs_format,
    )
    if cfg.n_shards > 1:
        return ShardedEngine(eng, cfg.n_shards, use_mesh=False)
    return eng


def observe_query(engine, plan, budget=None, max_ranges=None) -> dict:
    """Every host-observable of one traversal, as plain Python values."""
    kw = {}
    if budget is not None:
        kw["budget_postings"] = int(budget)
    if max_ranges is not None:
        kw["max_ranges"] = int(max_ranges)
    if isinstance(engine, ShardedEngine):
        r = engine.traverse(plan, **kw)
        return {
            "doc_ids": np.asarray(r.doc_ids).tolist(),
            "scores": np.asarray(r.scores).tolist(),
            "postings": int(r.postings),
            "blocks": int(r.blocks),
            "shard_postings": np.asarray(r.shard_postings).tolist(),
            "shard_ranges": np.asarray(r.shard_ranges).tolist(),
            "shard_exit_reasons": list(r.shard_exit_reasons),
            "fidelity_bound": int(r.fidelity_bound),
            "exact": bool(r.exact),
        }
    res = engine.traverse(plan, **kw)
    ids, vals = engine.topk_docs(res.state)
    return {
        "doc_ids": ids.tolist(),
        "scores": vals.tolist(),
        "postings": int(np.asarray(res.state.postings)),
        "blocks": int(np.asarray(res.state.blocks)),
        "ranges_processed": int(res.ranges_processed),
        "exit_safe": bool(res.exit_safe),
        "exit_budget": bool(res.exit_budget),
    }


def assert_results_equal(ra: dict, rb: dict, context: str = "") -> None:
    """Field-by-field equality with a failure message naming the field."""
    assert ra.keys() == rb.keys(), f"{context}: observable sets differ"
    for key in ra:
        assert ra[key] == rb[key], (
            f"{context}: {key} diverged\n  a: {ra[key]}\n  b: {rb[key]}"
        )


def assert_bitwise_equal_engines(
    cfg_a: EngineConfig,
    cfg_b: EngineConfig,
    corpus,
    queries: Sequence[np.ndarray],
    budgets=None,
    max_ranges=None,
    k: int = 5,
    n_ranges: int = 4,
    strategy: str = "clustered",
    bits: int = 8,
    seed: int = 0,
) -> None:
    """Pin two engine configs bitwise-equal over a corpus and query set.

    ``corpus`` may be a ``Corpus`` (an index is built with the keyword
    build parameters) or an already-built ``ClusteredIndex``. ``budgets``
    and ``max_ranges`` are optional per-query sequences; both sides of
    query ``i`` receive identical caps, so the assertion also covers
    budget-exit timing, not just exhaustive runs.
    """
    if cfg_a.n_shards != cfg_b.n_shards:
        raise ValueError(
            "differential configs must agree on n_shards (per-shard "
            f"observables aren't comparable): {cfg_a.n_shards} vs "
            f"{cfg_b.n_shards}"
        )
    if isinstance(corpus, ClusteredIndex):
        index = corpus
    else:
        index = build_index(
            corpus, n_ranges=n_ranges, strategy=strategy, bits=bits, seed=seed
        )
    ea = build_engine(index, cfg_a, k=k)
    eb = build_engine(index, cfg_b, k=k)
    planner = ea.engine if isinstance(ea, ShardedEngine) else ea
    for i, q in enumerate(queries):
        plan = planner.plan(q)
        b = None if budgets is None else budgets[i]
        m = None if max_ranges is None else max_ranges[i]
        assert_results_equal(
            observe_query(ea, plan, b, m),
            observe_query(eb, plan, b, m),
            context=f"query {i}: {cfg_a.describe()} vs {cfg_b.describe()}",
        )


def assert_batch_matches_sequential(
    eng: Engine, plans, batch_results, budgets=None, max_ranges=None
) -> None:
    """Batched serving results == looped single-query ``Engine.traverse``.

    ``batch_results`` is any sequence of ``BatchResult``-shaped records
    (``BatchEngine.run_batch`` output, or per-lane results from the
    in-flight loop); comparison covers ids, scores, exit flags, and the
    postings/blocks/ranges work counters.
    """
    for i, (plan, br) in enumerate(zip(plans, batch_results)):
        single = observe_query(
            eng,
            plan,
            None if budgets is None else budgets[i],
            None if max_ranges is None else max_ranges[i],
        )
        got = {
            "doc_ids": np.asarray(br.doc_ids).tolist(),
            "scores": np.asarray(br.scores).tolist(),
            "postings": int(br.postings),
            "blocks": int(br.blocks),
            "ranges_processed": int(br.ranges_processed),
            "exit_safe": bool(br.exit_safe),
            "exit_budget": bool(br.exit_budget),
        }
        assert_results_equal(got, single, context=f"query {i}: batch vs loop")


def assert_exit_reason_conservation(
    obs, counter_name: str, expected_reasons: Sequence[str],
    context: str = "", **fixed_labels
) -> None:
    """Telemetry exit-reason counters conserve queries (DESIGN.md §13).

    ``expected_reasons`` is the per-query exit reason list recomputed from
    the *returned* results — the ground truth the caller already holds.
    The counter named ``counter_name`` in ``obs``'s registry, restricted
    to label sets matching ``fixed_labels`` (e.g. ``server="inflight"``),
    must (a) sum to ``len(expected_reasons)`` — every query served is
    counted exactly once, none dropped, none double-counted — and (b)
    match the returned reasons as a multiset, so telemetry can never
    report an exit mix the results contradict.
    """
    import collections

    want = collections.Counter(str(r) for r in expected_reasons)
    counter = obs.metrics.counter(counter_name)
    fixed = {str(k): str(v) for k, v in fixed_labels.items()}
    got: collections.Counter = collections.Counter()
    for key in counter.labelsets():
        labels = dict(key)
        if any(labels.get(k) != v for k, v in fixed.items()):
            continue
        got[labels.get("reason", "")] += int(counter.value(**labels))
    ctx = context or counter_name
    assert sum(got.values()) == len(expected_reasons), (
        f"{ctx}: counted {sum(got.values())} queries in {counter_name}"
        f"{fixed or ''}, served {len(expected_reasons)}"
    )
    assert got == want, (
        f"{ctx}: exit-reason mix diverged\n  telemetry: {dict(got)}"
        f"\n  results:   {dict(want)}"
    )


def assert_sharded_matches_engine(
    se: ShardedEngine, plans, safe_stop: bool = True
) -> None:
    """Exhaustive-budget sharded top-k == single-device top-k, bitwise."""
    eng = se.engine
    for i, plan in enumerate(plans):
        single = eng.traverse(plan, safe_stop=safe_stop)
        sids, svals = eng.topk_docs(single.state)
        sh = se.traverse(plan, safe_stop=safe_stop)
        ctx = f"query {i}: {se.n_shards}-shard vs single"
        assert np.asarray(sh.doc_ids).tolist() == sids.tolist(), f"{ctx} ids"
        assert np.asarray(sh.scores).tolist() == svals.tolist(), f"{ctx} scores"
        assert sh.exact and sh.fidelity_bound == 0, ctx
        assert all(
            r in ("safe", "exhausted") for r in sh.shard_exit_reasons
        ), ctx
