"""GraphSAGE (mean aggregator) over explicit edge lists.

Message passing = gather(src) -> segment_sum over dst -> degree-normalize,
the jax-native SpMM substitute (JAX sparse is BCOO-only; see DESIGN.md).
Three entry points share the same layer math:

  * full-graph forward (full_graph_sm / ogb_products shapes);
  * sampled layered-subgraph forward (minibatch_lg shape, hop k uses the
    k-th sampled edge set);
  * batched small graphs with mean readout (molecule shape).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

__all__ = ["SAGEConfig", "init_sage", "sage_forward", "sage_forward_sampled", "sage_forward_graphs", "sage_param_specs"]


@dataclasses.dataclass(frozen=True)
class SAGEConfig:
    n_layers: int
    d_in: int
    d_hidden: int
    n_classes: int
    sample_sizes: tuple[int, ...] = (25, 10)
    dtype: Any = jnp.float32


def init_sage(key, cfg: SAGEConfig):
    layers = []
    d = cfg.d_in
    for i in range(cfg.n_layers):
        out = cfg.n_classes if i == cfg.n_layers - 1 else cfg.d_hidden
        k1, k2, key = jax.random.split(key, 3)
        layers.append(
            {
                "w_self": dense_init(k1, d, out, cfg.dtype),
                "w_neigh": dense_init(k2, d, out, cfg.dtype),
                "b": jnp.zeros((out,), cfg.dtype),
            }
        )
        d = out
    return {"layers": layers}


def _mean_aggregate(h, src_idx, dst_idx, n_nodes):
    """mean_{(s,d) in E} h[s] grouped by d; padded (-1) edges drop out."""
    valid = src_idx >= 0
    msgs = jnp.where(valid[:, None], h[jnp.clip(src_idx, 0)], 0)
    dst = jnp.where(valid, dst_idx, n_nodes)  # out-of-range -> dropped
    summed = jax.ops.segment_sum(msgs, dst, num_segments=n_nodes + 1)[:-1]
    deg = jax.ops.segment_sum(
        valid.astype(h.dtype), dst, num_segments=n_nodes + 1
    )[:-1]
    return summed / jnp.maximum(deg, 1.0)[:, None]


def _sage_layer(layer, h, agg, last: bool):
    out = jnp.dot(h, layer["w_self"]) + jnp.dot(agg, layer["w_neigh"]) + layer["b"]
    if last:
        return out
    out = jax.nn.relu(out)
    norm = jnp.linalg.norm(out, axis=-1, keepdims=True)
    return out / jnp.maximum(norm, 1e-6)


def sage_forward(params, feats, edges, cfg: SAGEConfig):
    """Full-graph forward. feats [N, d]; edges [E, 2] (src, dst)."""
    h = feats.astype(cfg.dtype)
    n = feats.shape[0]
    for i, layer in enumerate(params["layers"]):
        agg = _mean_aggregate(h, edges[:, 0], edges[:, 1], n)
        h = _sage_layer(layer, h, agg, i == len(params["layers"]) - 1)
    return h  # [N, n_classes] at the last layer


def sage_forward_sampled(params, feats, hops, cfg: SAGEConfig, n_batch: int):
    """Layered-subgraph forward: hop k's edges feed layer k (outermost first).

    feats [n_sub, d] over the union node set; returns logits for the first
    n_batch nodes (the seed batch).
    """
    h = feats.astype(cfg.dtype)
    n = feats.shape[0]
    L = len(params["layers"])
    for i, layer in enumerate(params["layers"]):
        src, dst = hops[L - 1 - i]  # outermost hop aggregates first
        agg = _mean_aggregate(h, src, dst, n)
        h = _sage_layer(layer, h, agg, i == L - 1)
    return h[:n_batch]


def sage_forward_graphs(params, feats, edges, graph_ids, n_graphs, cfg: SAGEConfig):
    """Batched small graphs: node embeddings -> mean readout per graph."""
    h = feats.astype(cfg.dtype)
    n = feats.shape[0]
    for i, layer in enumerate(params["layers"]):
        agg = _mean_aggregate(h, edges[:, 0], edges[:, 1], n)
        h = _sage_layer(layer, h, agg, i == len(params["layers"]) - 1)
    summed = jax.ops.segment_sum(h, graph_ids, num_segments=n_graphs)
    counts = jax.ops.segment_sum(
        jnp.ones((n,), h.dtype), graph_ids, num_segments=n_graphs
    )
    return summed / jnp.maximum(counts, 1.0)[:, None]


def sage_loss(logits, labels):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[:, None], axis=-1
    )[:, 0]
    return jnp.mean(lse - gold)


def sage_param_specs(cfg: SAGEConfig, model_axis: str = "model"):
    """Feature-dim TP for the hidden layers (hidden dims are small; the
    heavy axis for GNN is edges/data — handled by input sharding)."""
    from jax.sharding import PartitionSpec as P

    specs = []
    for i in range(cfg.n_layers):
        specs.append({"w_self": P(), "w_neigh": P(), "b": P()})
    return {"layers": specs}


# ------------------------------------------------- sharded full-batch (§Perf)
#
# Baseline full-batch training replicates node features and psums the
# aggregated messages — collective-dominant on ogb_products (EXPERIMENTS.md
# §Roofline). This variant partitions NODES contiguously over the data axis
# and EDGES by destination owner (host-side, free at load time): the
# segment_sum becomes LOCAL, and the only collective is one bf16 all_gather
# of the (much narrower) layer activations — an all-gather of N*d_hidden
# bf16 instead of an all-reduce of N*d_hidden fp32 per layer per direction.


def sage_forward_sharded(params, feats_loc, agg0_loc, edges_loc,
                         cfg: SAGEConfig, n_nodes: int, shard_ctx):
    """Node/dst-partitioned full-batch forward inside shard_map.

    feats_loc [N/D, d]  — this rank's node block (contiguous);
    agg0_loc  [N/D, d]  — PRECOMPUTED first-hop mean aggregate (the layer-1
                          neighbor mean is weight-independent, so it is a
                          data-pipeline constant — the SIGN trick — and its
                          feature gather disappears from every step);
    edges_loc [E/D, 2]  — edges whose dst lives in this block (-1 padded).
    Returns local logits [N/D, n_classes].
    """
    import jax
    from jax.sharding import PartitionSpec as P

    da = shard_ctx.data_axes

    def body(p, h_loc, a0_loc, e_loc):
        n_loc = h_loc.shape[0]
        # Linearized data-rank (axis-major order matches P(da) layout).
        d_rank = jnp.zeros((), jnp.int32)
        for a in da:
            d_rank = d_rank * shard_ctx.mesh.shape[a] + jax.lax.axis_index(a)
        base = d_rank * n_loc
        h = h_loc.astype(cfg.dtype)
        L = len(p["layers"])
        for i, layer in enumerate(p["layers"]):
            if i == 0:
                agg = a0_loc.astype(cfg.dtype)  # precomputed, zero collectives
            else:
                # bf16 all_gather of hidden activations (innermost axis
                # first so ordering matches the global layout).
                h_full = h.astype(jnp.bfloat16)
                for a in reversed(da):
                    h_full = jax.lax.all_gather(h_full, a, axis=0, tiled=True)
                src = e_loc[:, 0]
                dst_local = jnp.where(e_loc[:, 1] >= 0, e_loc[:, 1] - base, n_loc)
                valid = (src >= 0) & (dst_local >= 0) & (dst_local < n_loc)
                msgs = jnp.where(
                    valid[:, None], h_full[jnp.clip(src, 0)].astype(cfg.dtype), 0
                )
                summed = jax.ops.segment_sum(
                    msgs, jnp.where(valid, dst_local, n_loc),
                    num_segments=n_loc + 1,
                )[:-1]
                deg = jax.ops.segment_sum(
                    valid.astype(cfg.dtype), jnp.where(valid, dst_local, n_loc),
                    num_segments=n_loc + 1,
                )[:-1]
                agg = summed / jnp.maximum(deg, 1.0)[:, None]
            h = _sage_layer(layer, h, agg, i == L - 1)
        return h

    fn = jax.shard_map(
        body,
        mesh=shard_ctx.mesh,
        in_specs=(P(), P(da, None), P(da, None), P(da, None)),
        out_specs=P(da, None),
        check_vma=False,
    )
    return fn(params, feats_loc, agg0_loc, edges_loc)


def sage_loss_per_node(logits, labels):
    """Per-node CE (no reduction) — sharded-variant loss masks padding."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[:, None], axis=-1
    )[:, 0]
    return lse - gold
