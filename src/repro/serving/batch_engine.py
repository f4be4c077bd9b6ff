"""Batched anytime query engine (vmapped device traversal).

``BatchEngine`` wraps the single-query ``core.range_daat.Engine`` with a
batch execution path: plans are snapped to a small ladder of static shapes
(see ``bucketing``), stacked into one pytree per shape, and traversed by a
single ``batched_traverse`` dispatch per group. Budgets are **per query** —
the postings/range caps travel down the vmap lane with the plan, so a heavy
query exhausts *its* budget while light lanes in the same batch run to safe
or exhaustive completion. Results are bitwise identical to looping
``device_traverse`` over the same plans (tests/test_batch_serving.py).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.range_daat import (
    Engine,
    QueryPlan,
    batched_traverse,
    exit_reason,
)
from repro.obs.profiler import jit_cache_size
from repro.serving.bucketing import (
    BucketSpec,
    batch_ladder,
    dummy_plan,
    iter_bucket_chunks,
    stack_plans,
)

__all__ = ["BatchResult", "BatchEngine", "INT32_MAX", "lane_result"]

INT32_MAX = 2**31 - 1


class BatchResult(NamedTuple):
    """Host-side per-query outcome of a batched traversal."""

    doc_ids: np.ndarray  # [<=k] int32, score-desc / docid-asc
    scores: np.ndarray  # [<=k] int32
    ranges_processed: int
    postings: int
    blocks: int
    exit_safe: bool
    exit_budget: bool

    @property
    def exit_reason(self) -> str:
        return exit_reason(self.exit_safe, self.exit_budget)


def lane_result(
    vals: np.ndarray,
    ids: np.ndarray,
    postings: np.ndarray,
    blocks: np.ndarray,
    ranges: np.ndarray,
    safe: np.ndarray,
    budg: np.ndarray,
    lane: int,
) -> BatchResult:
    """Unpack one lane of host-side batched traversal state.

    Shared by the micro-batch chunk path and the in-flight slot loop so
    both servers materialise byte-identical ``BatchResult``s from the same
    lane state.
    """
    keep = ids[lane] >= 0
    return BatchResult(
        doc_ids=ids[lane][keep],
        scores=vals[lane][keep],
        ranges_processed=int(ranges[lane]),
        postings=int(postings[lane]),
        blocks=int(blocks[lane]),
        exit_safe=bool(safe[lane]),
        exit_budget=bool(budg[lane]),
    )


def _per_query(value, n: int, default: int) -> np.ndarray:
    """Broadcast a scalar-or-sequence budget to an [n] int32 array."""
    if value is None:
        return np.full(n, default, dtype=np.int32)
    arr = np.asarray(value, dtype=np.int64)
    if arr.ndim == 0:
        arr = np.full(n, int(arr), dtype=np.int64)
    if arr.shape != (n,):
        raise ValueError(f"budget shape {arr.shape} != ({n},)")
    return np.clip(arr, 0, INT32_MAX).astype(np.int32)


class BatchEngine:
    """Micro-batch executor over a cluster-skipping index.

    Shape discipline: every dispatch has shape (batch_bucket, R,
    width_bucket) with R and s_pad fixed by the index, so the XLA program
    cache is bounded by ``len(width buckets) x len(batch buckets)``.
    ``compiled_shapes`` records which (batch, width) programs have been
    requested — tests use it to assert the recompile bound holds.
    """

    def __init__(
        self, engine: Engine, spec: BucketSpec | None = None, obs=None
    ):
        from repro.obs import NOOP

        self.engine = engine
        self.spec = spec or BucketSpec()
        self.obs = obs if obs is not None else NOOP
        self.compiled_shapes: set[tuple[int, int]] = set()
        self.batches_run = 0

    @classmethod
    def from_artifact(
        cls, path: str, spec: BucketSpec | None = None, **engine_kwargs
    ) -> "BatchEngine":
        """Serve a saved index artifact (DESIGN.md §8).

        ``engine_kwargs`` pass through to ``core.range_daat.Engine``;
        ``impact_dtype`` defaults to the artifact's stored dtype, so an
        int8 artifact serves with int8 postings impacts in HBM.
        """
        return cls(Engine.from_artifact(path, **engine_kwargs), spec)

    # ------------------------------------------------------------- planning
    def plan(self, q_terms: np.ndarray) -> QueryPlan:
        return self.engine.plan(q_terms)

    def plan_many(self, queries: Sequence[np.ndarray]) -> list[QueryPlan]:
        return [self.engine.plan(q) for q in queries]

    # ------------------------------------------------------------ execution
    def run_batch(
        self,
        plans: Sequence[QueryPlan],
        budget_postings=None,
        max_ranges=None,
        safe_stop: bool = True,
        prune_blocks: bool = True,
    ) -> list[BatchResult]:
        """Traverse ``plans`` in vmapped groups; results keep input order.

        ``budget_postings`` / ``max_ranges`` may be None (unbounded), a
        scalar applied to every query, or a length-len(plans) sequence of
        per-query caps.
        """
        n = len(plans)
        if n == 0:
            return []
        budgets = _per_query(budget_postings, n, INT32_MAX)
        maxr = _per_query(max_ranges, n, INT32_MAX)

        results: list[BatchResult | None] = [None] * n
        for width, chunk in iter_bucket_chunks(plans, self.spec):
            self._run_chunk(
                [plans[i] for i in chunk],
                chunk,
                width,
                budgets,
                maxr,
                safe_stop,
                prune_blocks,
                results,
            )
        return results  # type: ignore[return-value]

    def _run_chunk(
        self,
        chunk_plans: list[QueryPlan],
        chunk_idx: list[int],
        width: int,
        budgets: np.ndarray,
        maxr: np.ndarray,
        safe_stop: bool,
        prune_blocks: bool,
        results: list,
    ) -> None:
        batch = self.spec.batch_bucket(len(chunk_plans))
        prof = self.obs.profiler if self.obs.enabled else None
        if prof is not None:
            clk = self.obs.clock
            t_plan0 = clk()
        bp = stack_plans(chunk_plans, width, batch)

        # Dummy lanes get zero budgets -> they exit at i=0 having done no work.
        b = np.zeros(batch, dtype=np.int32)
        m = np.zeros(batch, dtype=np.int32)
        b[: len(chunk_idx)] = budgets[chunk_idx]
        m[: len(chunk_idx)] = maxr[chunk_idx]

        eng = self.engine
        if prof is not None:
            cache0 = jit_cache_size(batched_traverse)
            t_disp0 = clk()
        res = batched_traverse(
            eng.dix,
            bp.blk_tab,
            bp.rest_tab,
            bp.order,
            bp.ordered_bounds,
            jnp.asarray(b),
            jnp.asarray(m),
            s_pad=eng.s_pad,
            k=eng.k,
            safe_stop=safe_stop,
            prune_blocks=prune_blocks,
            impl=eng.impl,
            docs_format=eng.docs_format,
        )
        self.compiled_shapes.add((batch, width))
        self.batches_run += 1
        if prof is not None:
            # Timing-only: the extra sync point moves the device wait out
            # of the np.asarray conversions below; results are untouched.
            t_dev0 = clk()
            jax.block_until_ready(res)  # analysis: allow[HOSTSYNC]
            t_dev1 = clk()

        # analysis: allow[HOSTSYNC] this IS run_batch's drain boundary:
        # every lane of the chunk is finished and consumed right below.
        vals = np.asarray(res.state.vals)
        ids = np.asarray(res.state.ids)
        postings = np.asarray(res.state.postings)
        blocks = np.asarray(res.state.blocks)
        ranges = np.asarray(res.ranges_processed)
        safe = np.asarray(res.exit_safe)
        budg = np.asarray(res.exit_budget)
        if prof is not None:
            t_xfer1 = clk()
            prof.record_dispatch(
                "batch_engine",
                (batch, width),
                cache_before=cache0,
                cache_after=jit_cache_size(batched_traverse),
                plan_ms=(t_disp0 - t_plan0) * 1e3,
                dispatch_ms=(t_dev0 - t_disp0) * 1e3,
                device_ms=(t_dev1 - t_dev0) * 1e3,
                transfer_ms=(t_xfer1 - t_dev1) * 1e3,
            )
            prof.record_hbm_once("batch_engine", eng.dix._asdict())
        for lane, qi in enumerate(chunk_idx):
            results[qi] = lane_result(
                vals, ids, postings, blocks, ranges, safe, budg, lane
            )
        if self.obs.enabled:
            self.obs.observe("batch_engine_chunk_lanes", len(chunk_idx))
            for lane in range(len(chunk_idx)):
                self.obs.count(
                    "batch_engine_queries",
                    reason=exit_reason(bool(safe[lane]), bool(budg[lane])),
                )

    # ---------------------------------------------------------------- misc
    def warmup(self, widths: Sequence[int] | None = None) -> None:
        """Pre-compile every (batch_bucket, width) program for given widths."""
        R = self.engine.index.n_ranges
        for w in widths or (self.spec.min_width,):
            dummy = dummy_plan(R, self.spec.width_bucket(w))
            for nb in batch_ladder(self.spec):
                self.run_batch([dummy] * nb)
