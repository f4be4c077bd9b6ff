"""Replicated shard groups: a query-parallel ``data`` axis over the shard
mesh (DESIGN.md §9).

``ReplicaGroupEngine`` composes the §4 range-shard axis with the
query-parallel ``data`` axis whose collective shape ``serve/distributed_ir``
already established: a 2-D (data x shard) mesh where every row holds a full
copy of the sharded index and each row serves a slice of the micro-batch.
The dispatch body is the *same* program as the single-replica mesh path
(``serving.sharded.make_mesh_dispatch`` with ``data_axis=``): the per-query
traversal and the ``range_daat.merge_topk`` broker merge never see the
replica axis, so an N-replica dispatch is **bitwise identical** to serving
the same queries on one replica — replication buys throughput, never a
different answer.

Fallbacks keep the engine total: with fewer than ``n_replicas * n_shards``
devices the group serves through the wrapped ``ShardedEngine`` unchanged
(its vmap or 1-D mesh path), and the control plane drops to the same path
when the health ledger reports a degraded replica row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import replica_mesh
from repro.serving.sharded import ShardedEngine, make_mesh_dispatch, place_index

__all__ = ["ReplicaGroupEngine"]


class ReplicaGroupEngine:
    """N data-parallel replicas of a ``ShardedEngine``.

    Drop-in wherever a ``ShardedEngine`` is accepted (``ShardedBatchEngine``
    takes either): planning, budget splitting, and result unpacking delegate
    to the wrapped engine; only ``dispatch`` changes, sharding the batch
    axis over the replica rows of a (data x shard) mesh. ``use_mesh``:
    None = auto (replicate when the runtime has n_replicas * n_shards
    devices), True = require the 2-D mesh, False = always fall back to the
    wrapped engine's own path (useful on one device, where replica rows
    cannot add throughput but the scheduling logic still runs).
    """

    def __init__(
        self,
        sengine: ShardedEngine,
        n_replicas: int,
        use_mesh: bool | None = None,
        data_axis: str = "data",
        mesh_axis: str = "shard",
    ):
        if n_replicas < 1:
            raise ValueError(f"need n_replicas >= 1, got {n_replicas}")
        self.sengine = sengine
        self.n_replicas = n_replicas
        self._data_axis = data_axis
        self._shard_axis = mesh_axis
        need = n_replicas * sengine.n_shards
        if use_mesh is None:
            use_mesh = n_replicas > 1 and jax.device_count() >= need
        self.group_mesh = (
            replica_mesh(n_replicas, sengine.n_shards, data_axis, mesh_axis)
            if use_mesh
            else None
        )
        if self.group_mesh is not None:
            # The group's own copy of the index, placed once: each device
            # holds its shard column's rows, replicated down the data axis.
            self.group_dix, self.group_doc_base = place_index(
                sengine.dix, sengine.doc_base, self.group_mesh, mesh_axis,
                sengine.docs_format,
            )
        self._group_fns: dict = {}
        self.dispatches = 0  # replica-mesh dispatches actually issued

    def __getattr__(self, name):
        # Everything but dispatch (and the replica plumbing above) is the
        # wrapped engine's: shard_plan, split_*_budget, _to_results, shards,
        # cuts, query_shard_mass, ... — the ShardedBatchEngine contract.
        return getattr(self.sengine, name)

    # ------------------------------------------------------------- dispatch
    def dispatch(
        self, blk, rest, order, bounds, budgets, maxr,
        safe_stop: bool = True, prune_blocks: bool = True,
    ):
        """Run one (batch x shard) step across all replica rows.

        The batch axis is padded to a multiple of ``n_replicas`` with inert
        zero-budget lanes (the §3 dummy-lane discipline) so it divides
        evenly over the ``data`` axis; pad lanes are sliced off the output.
        """
        if self.group_mesh is None:
            return self.sengine.dispatch(
                blk, rest, order, bounds, budgets, maxr,
                safe_stop=safe_stop, prune_blocks=prune_blocks,
            )
        n = blk.shape[0]
        pad = (-n) % self.n_replicas
        if pad:
            zb = lambda a: np.concatenate(  # noqa: E731
                [np.asarray(a), np.zeros((pad,) + np.asarray(a).shape[1:],
                                         np.asarray(a).dtype)]
            )
            blk = np.concatenate(
                [np.asarray(blk), np.full((pad,) + np.asarray(blk).shape[1:],
                                          -1, np.int32)]
            )
            rest, order, bounds = zb(rest), zb(order), zb(bounds)
            budgets, maxr = zb(budgets), zb(maxr)

        key = (safe_stop, prune_blocks)
        if key not in self._group_fns:
            se = self.sengine
            self._group_fns[key] = make_mesh_dispatch(
                self.group_mesh,
                self._shard_axis,
                s_pad=se.s_pad,
                k=se.k,
                safe_stop=safe_stop,
                prune_blocks=prune_blocks,
                impl=se.impl,
                data_axis=self._data_axis,
                docs_format=se.docs_format,
            )
        out = self._group_fns[key](
            self.group_dix,
            self.group_doc_base,
            jnp.asarray(blk),
            jnp.asarray(rest),
            jnp.asarray(order),
            jnp.asarray(bounds),
            jnp.asarray(budgets, jnp.int32),
            jnp.asarray(maxr, jnp.int32),
        )
        self.dispatches += 1
        if self.obs.enabled:  # delegates to the wrapped engine's handle
            self.obs.count("replica_dispatches")
            self.obs.observe("replica_pad_lanes", pad)
        if pad:
            out = _slice_pad(out, n)
        return out


def _slice_pad(out: tuple, n: int) -> tuple:
    """Drop pad lanes *on-device*: a lazy slice per leaf, so the dispatch
    stays asynchronous and the host copy happens at the caller's drain
    boundary (``_to_results``), not mid-dispatch."""
    return tuple(x[:n] for x in out)
