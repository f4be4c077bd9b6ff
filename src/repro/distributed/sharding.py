"""Mesh/sharding context shared by models, trainer, and dry-run."""

from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = [
    "ShardCtx",
    "named",
    "data_spec",
    "replica_mesh",
    "retrieval_mesh",
]


def retrieval_mesh(n_shards: int, axis: str = "shard") -> Mesh:
    """1-D mesh for range-sharded retrieval (DESIGN.md §4).

    One mesh axis carrying index shards; raises if the runtime exposes fewer
    devices than shards (tests force CPU host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    n_dev = jax.device_count()
    if n_dev < n_shards:
        raise ValueError(
            f"retrieval_mesh needs {n_shards} devices, have {n_dev}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count before "
            "importing jax, or use the single-device vmap path"
        )
    return jax.make_mesh((n_shards,), (axis,), devices=jax.devices()[:n_shards])


def replica_mesh(
    n_replicas: int,
    n_shards: int,
    data_axis: str = "data",
    shard_axis: str = "shard",
) -> Mesh:
    """2-D (data x shard) mesh for replicated shard groups (DESIGN.md §9).

    Rows carry full index replicas (query parallelism over ``data_axis``),
    columns carry range shards; needs ``n_replicas * n_shards`` devices.
    """
    need = n_replicas * n_shards
    n_dev = jax.device_count()
    if n_dev < need:
        raise ValueError(
            f"replica_mesh needs {n_replicas} x {n_shards} = {need} devices, "
            f"have {n_dev}; set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count before importing jax, or drop to 1 replica"
        )
    return jax.make_mesh(
        (n_replicas, n_shards), (data_axis, shard_axis),
        devices=jax.devices()[:need],
    )


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Which mesh axes carry data parallelism and which carry model/TP/EP.

    data_axes is ("pod", "data") on the multi-pod mesh, ("data",) otherwise.
    """

    mesh: Mesh
    data_axes: tuple = ("data",)
    model_axis: str = "model"

    @property
    def n_model(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def n_data(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


def named(ctx: ShardCtx | None, tree, specs):
    """Apply with_sharding_constraint when a ctx is present (no-op locally)."""
    if ctx is None:
        return tree
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, ctx.sharding(s)),
        tree,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def data_spec(ctx: ShardCtx, *trailing) -> P:
    """Batch-sharded spec: first dim over all data axes."""
    return P(ctx.data_axes, *trailing)
