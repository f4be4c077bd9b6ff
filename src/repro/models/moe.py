"""Mixture-of-Experts FFN with capacity-based top-k routing and EP sharding.

Expert parallelism is fused with tensor parallelism (DESIGN.md §5): experts
are sharded over the mesh ``model`` axis; tokens arrive replicated across
that axis (the standard TP activation layout), each model rank dispatches
only to the experts it owns, and the combine is a single ``psum`` over
``model`` — the same all-reduce a dense TP FFN would issue, so EP costs no
extra collective in the baseline. Dispatch uses a local argsort over
(token, slot) pairs — no global sort, no cross-shard data-dependent
communication. Tokens beyond an expert's capacity are dropped (standard
capacity-factor semantics; the aux load-balance loss keeps drops rare).

One math path, two entries: ``_moe_compute`` runs on a device-local token
block for the expert slice [e_lo, e_lo + E_loc); the single-device path uses
the full slice, the shard_map path derives the slice from axis_index.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init

__all__ = ["MoEConfig", "init_moe", "moe_ffn"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int  # per-expert hidden width
    n_shared: int = 0  # shared experts, each d_ff wide
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01


def init_moe(key, d_model: int, cfg: MoEConfig, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 5)
    E, F = cfg.n_experts, cfg.d_ff
    p = {
        "router": dense_init(ks[0], d_model, E, jnp.float32),
        "w_gate": jax.vmap(lambda k: dense_init(k, d_model, F, dtype))(
            jax.random.split(ks[1], E)
        ),
        "w_up": jax.vmap(lambda k: dense_init(k, d_model, F, dtype))(
            jax.random.split(ks[2], E)
        ),
        "w_down": jax.vmap(lambda k: dense_init(k, F, d_model, dtype))(
            jax.random.split(ks[3], E)
        ),
    }
    if cfg.n_shared:
        fs = cfg.n_shared * cfg.d_ff
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(k1, d_model, fs, dtype),
            "w_up": dense_init(k2, d_model, fs, dtype),
            "w_down": dense_init(k3, fs, d_model, dtype),
        }
    return p


def _route(router_w, x, cfg: MoEConfig):
    """fp32 routing: renormalized top-k probs + Switch-style aux loss."""
    logits = jnp.dot(x.astype(jnp.float32), router_w)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)  # [T, k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)  # mean router prob per expert
    ce = jax.nn.one_hot(topi[:, 0], cfg.n_experts, dtype=jnp.float32).mean(0)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return topv, topi, aux


def _dispatch_ranks(flat_e: jnp.ndarray, n_buckets: int):
    """Rank of each (token, slot) within its bucket, via stable local argsort."""
    N = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(n_buckets), side="left")
    rank_sorted = jnp.arange(N, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    return jnp.zeros((N,), jnp.int32).at[order].set(rank_sorted)


def _expert_ffn(p, buf):
    """buf [E_loc, C, d] through each expert's SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])


def _shared_ffn(p, x):
    g = jnp.dot(x, p["w_gate"])
    u = jnp.dot(x, p["w_up"])
    return jnp.dot(jax.nn.silu(g) * u, p["w_down"])


def _capacity(n_tokens: int, cfg: MoEConfig) -> int:
    c = math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, int(c))


def _moe_compute(params, x, cfg: MoEConfig, e_lo, E_loc: int):
    """Dispatch/compute/combine for the expert slice [e_lo, e_lo + E_loc).

    ``params`` expert leaves hold the local slice [E_loc, ...]; ``e_lo`` may
    be a traced scalar (shard_map) or 0. Returns the partial output (zeros
    for slots owned by other ranks) and the aux loss.
    """
    T, d = x.shape
    topv, topi, aux = _route(params["router"], x, cfg)
    k = cfg.top_k
    C = _capacity(T, cfg)

    flat_e = topi.reshape(-1)  # [T*k] global expert ids
    local = (flat_e >= e_lo) & (flat_e < e_lo + E_loc)
    e_local = jnp.where(local, flat_e - e_lo, E_loc).astype(jnp.int32)  # E_loc = trash
    rank = _dispatch_ranks(e_local, E_loc + 1)
    keep = local & (rank < C)

    e_idx = jnp.where(keep, e_local, E_loc)
    c_idx = jnp.where(keep, rank, C - 1)
    x_rep = jnp.repeat(x, k, axis=0)  # [T*k, d]
    buf = jnp.zeros((E_loc + 1, C, d), x.dtype)
    buf = buf.at[e_idx, c_idx].add(jnp.where(keep[:, None], x_rep, 0))

    y_buf = _expert_ffn(params, buf[:E_loc])
    y_buf = jnp.concatenate([y_buf, jnp.zeros((1, C, d), y_buf.dtype)], 0)
    y_slots = jnp.where(keep[:, None], y_buf[e_idx, c_idx], 0)
    w = topv.reshape(-1).astype(x.dtype)
    y = (y_slots * w[:, None]).reshape(T, k, d).sum(axis=1)
    return y, aux


def moe_ffn(params, x2d: jnp.ndarray, cfg: MoEConfig, shard_ctx=None):
    """MoE FFN over flat tokens x2d [T, d]. Returns (y [T, d], aux scalar)."""
    if shard_ctx is None:
        y, aux = _moe_compute(params, x2d, cfg, 0, cfg.n_experts)
        if cfg.n_shared:
            y = y + _shared_ffn(params["shared"], x2d)
        return y, aux

    model_axis = shard_ctx.model_axis
    data_axes = shard_ctx.data_axes
    n_model = shard_ctx.mesh.shape[model_axis]
    E_loc = cfg.n_experts // n_model

    def body(p, x):
        e_lo = jax.lax.axis_index(model_axis) * E_loc
        y, aux = _moe_compute(p, x, cfg, e_lo, E_loc)
        if cfg.n_shared:
            # Shared expert hidden is sharded over the model axis; its
            # partial sums ride the same psum as the routed combine.
            y = y + _shared_ffn(p["shared"], x)
        y = jax.lax.psum(y, model_axis)
        aux = jax.lax.pmean(aux, data_axes)
        return y, aux

    param_specs = {
        "router": P(),
        "w_gate": P(model_axis, None, None),
        "w_up": P(model_axis, None, None),
        "w_down": P(model_axis, None, None),
    }
    if cfg.n_shared:
        param_specs["shared"] = {
            "w_gate": P(None, model_axis),
            "w_up": P(None, model_axis),
            "w_down": P(model_axis, None),
        }
    fn = jax.shard_map(
        body,
        mesh=shard_ctx.mesh,
        in_specs=(param_specs, P(data_axes, None)),
        out_specs=(P(data_axes, None), P()),
        check_vma=False,
    )
    return fn(params, x2d)


def moe_ffn_decode_ep_all(params, x2d: jnp.ndarray, cfg: MoEConfig, shard_ctx):
    """Decode-time MoE with experts sharded over the WHOLE (data, model) grid.

    Serving deepseek-v3 on 256 chips cannot hold 256 experts at E/16 per
    chip (84 GB/device); with EP over data x model each chip owns exactly
    E / 256 experts. Token counts at decode are tiny (the whole batch is a
    few hundred rows), so the exchange is an all_gather of x (a few MB) and
    one psum of the combined output — negligible next to the weight
    residency it buys. Used by the split_kv decode variant (§Perf cell A').
    """
    # EP grid = data x model (pods replicate experts — the pod axis is the
    # throughput-replication axis, DESIGN.md §5).
    ep_axes = ("data", shard_ctx.model_axis)
    n_all = 1
    for a in ep_axes:
        n_all *= shard_ctx.mesh.shape[a]
    if cfg.n_experts % n_all != 0:
        # Fall back to model-axis EP when experts don't cover the grid.
        return moe_ffn(params, x2d, cfg, shard_ctx)
    E_loc = cfg.n_experts // n_all

    def body(p, x_loc):
        # Rebuild this pod's (tiny) token block on every rank.
        x_full = jax.lax.all_gather(x_loc, "data", axis=0, tiled=True)
        # Linearized rank over the (data, model) EP grid.
        rank = (
            jax.lax.axis_index("data") * shard_ctx.mesh.shape[shard_ctx.model_axis]
            + jax.lax.axis_index(shard_ctx.model_axis)
        )
        e_lo = rank * E_loc
        y_full, aux = _moe_compute(p, x_full, cfg, e_lo, E_loc)
        # fp32 psums: bf16 psum under partial-manual shard_map (pod stays
        # auto on the multi-pod mesh) trips an XLA-CPU crash; fp32 is also
        # the numerically right accumulator for a 256-way combine.
        y_full = jax.lax.psum(y_full.astype(jnp.float32), ep_axes)
        if cfg.n_shared:
            y_full = y_full + jax.lax.psum(
                _shared_ffn(p["shared"], x_full).astype(jnp.float32),
                shard_ctx.model_axis,
            )
        y_full = y_full.astype(x_full.dtype)
        # Slice back this data-shard's tokens.
        T_loc = x_loc.shape[0]
        d_rank = jax.lax.axis_index("data")
        y_loc = jax.lax.dynamic_slice_in_dim(y_full, d_rank * T_loc, T_loc, 0)
        return y_loc, aux

    param_specs = {
        "router": P(),
        "w_gate": P(ep_axes, None, None),
        "w_up": P(ep_axes, None, None),
        "w_down": P(ep_axes, None, None),
    }
    if cfg.n_shared:
        param_specs["shared"] = {
            "w_gate": P(None, shard_ctx.model_axis),
            "w_up": P(None, shard_ctx.model_axis),
            "w_down": P(shard_ctx.model_axis, None),
        }
    fn = jax.shard_map(
        body,
        mesh=shard_ctx.mesh,
        in_specs=(param_specs, P("data", None)),
        out_specs=(P("data", None), P()),
        axis_names=frozenset(ep_axes),
        check_vma=False,
    )
    return fn(params, x2d)
