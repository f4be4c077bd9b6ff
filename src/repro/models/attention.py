"""Attention variants for the assigned LM architectures.

  * GQA with optional per-head QK RMSNorm (qwen3) and QKV bias (qwen2.5).
    Grouped einsums keep KV unreplicated — queries are reshaped to
    [B, S, KV, G, D] instead of repeating the KV heads G times.
  * MLA (DeepSeek-V2/V3): low-rank compressed KV — the decode cache stores
    only (c_kv, k_rope) per token, which is what makes deepseek-v3-671b
    decode_32k feasible (DESIGN.md §5).

Full-sequence paths (training / prefill) are *query-chunked*: scores never
materialize beyond [B, KV, G, chunk, T]. All score einsums run on bf16
operands with fp32 accumulation (preferred_element_type), the MXU-native
pattern; softmax in fp32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope, dense_init, rms_norm, rms_norm_init

__all__ = ["GQAConfig", "MLAConfig", "init_gqa", "gqa", "init_mla", "mla"]

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class GQAConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0


def _grouped_attention(q5, k, v, *, q_positions, kv_valid_len, chunk: int):
    """Causal grouped attention.

    q5: [B, S, KV, G, Dk]; k: [B, T, KV, Dk]; v: [B, T, KV, Dv].
    q_positions: [S] absolute position of each query row.
    kv_valid_len: scalar — keys at index >= this are masked (cache tail);
                  causality additionally masks keys beyond each query's pos.
    Returns [B, S, KV, G, Dv].
    """
    B, S, KV, G, Dk = q5.shape
    T = k.shape[1]
    scale = 1.0 / np.sqrt(Dk)
    if S % chunk != 0:
        chunk = S
    nc = S // chunk
    kpos = jnp.arange(T)

    def one_chunk(qc, pc):
        # qc: [B, C, KV, G, Dk]; pc: [C] positions
        s = jnp.einsum(
            "bckgd,btkd->bkgct", qc, k, preferred_element_type=jnp.float32
        ) * scale
        mask = (kpos[None, :] <= pc[:, None]) & (kpos[None, :] < kv_valid_len)
        s = jnp.where(mask[None, None, None], s, _NEG)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgct,btkd->bckgd", w, v)

    if nc == 1:
        return one_chunk(q5, q_positions)
    qr = q5.reshape(B, nc, chunk, KV, G, Dk).transpose(1, 0, 2, 3, 4, 5)
    pr = q_positions.reshape(nc, chunk)
    outs = jax.lax.map(lambda a: one_chunk(a[0], a[1]), (qr, pr))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, KV, G, -1)


# --------------------------------------------------------------------- GQA


def init_gqa(key, cfg: GQAConfig, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * cfg.head_dim, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * cfg.head_dim, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * cfg.head_dim, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * cfg.head_dim, cfg.d_model, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * cfg.head_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * cfg.head_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * cfg.head_dim,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rms_norm_init(cfg.head_dim, dtype)
        p["k_norm"] = rms_norm_init(cfg.head_dim, dtype)
    return p


def gqa(
    params,
    x: jnp.ndarray,  # [B, S, d]
    rope_table: jnp.ndarray,
    cfg: GQAConfig,
    *,
    positions: jnp.ndarray,  # [S] absolute positions of x's rows
    cache: dict | None = None,  # {"k": [B, Smax, KV, D], "v": ...}
    cache_pos: jnp.ndarray | None = None,  # scalar: tokens already cached
    chunk: int = 512,
):
    """Returns (out [B, S, d], new_cache)."""
    B, S, _ = x.shape
    q = jnp.dot(x, params["wq"])
    k = jnp.dot(x, params["wk"])
    v = jnp.dot(x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    q = apply_rope(q, rope_table, positions)
    k = apply_rope(k, rope_table, positions)
    q5 = q.reshape(B, S, cfg.n_kv_heads, cfg.group, cfg.head_dim)

    if cache is not None:
        k_all = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, cache_pos, 0, 0)
        )
        v_all = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, cache_pos, 0, 0)
        )
        new_cache = {"k": k_all, "v": v_all}
        valid = cache_pos + S
    else:
        k_all, v_all, new_cache = k, v, None
        valid = S

    out = _grouped_attention(
        q5, k_all, v_all, q_positions=positions, kv_valid_len=valid, chunk=chunk
    )
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    return jnp.dot(out, params["wo"]), new_cache


# --------------------------------------------------------------------- MLA


def init_mla(key, cfg: MLAConfig, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 6)
    qk_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        "w_dq": dense_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype),
        "q_norm": rms_norm_init(cfg.q_lora_rank, dtype),
        "w_uq": dense_init(ks[1], cfg.q_lora_rank, cfg.n_heads * qk_head, dtype),
        "w_dkv": dense_init(
            ks[2], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype
        ),
        "kv_norm": rms_norm_init(cfg.kv_lora_rank, dtype),
        "w_uk": dense_init(
            ks[3], cfg.kv_lora_rank, cfg.n_heads * cfg.qk_nope_head_dim, dtype
        ),
        "w_uv": dense_init(
            ks[4], cfg.kv_lora_rank, cfg.n_heads * cfg.v_head_dim, dtype
        ),
        "wo": dense_init(ks[5], cfg.n_heads * cfg.v_head_dim, cfg.d_model, dtype),
    }


def mla(
    params,
    x: jnp.ndarray,
    rope_table: jnp.ndarray,
    cfg: MLAConfig,
    *,
    positions: jnp.ndarray,
    cache: dict | None = None,  # {"c_kv": [B, Smax, R], "k_rope": [B, Smax, rd]}
    cache_pos: jnp.ndarray | None = None,
    chunk: int = 512,
):
    """MLA attention. Cache stores the compressed (c_kv, k_rope) only.

    Baseline path expands the compressed cache to per-head K/V each call;
    the absorbed-matmul decode optimization is a §Perf hillclimb candidate.
    """
    B, S, _ = x.shape
    H = cfg.n_heads
    qk_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    cq = rms_norm(jnp.dot(x, params["w_dq"]), params["q_norm"])
    q = jnp.dot(cq, params["w_uq"]).reshape(B, S, H, qk_head)
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim :], rope_table, positions)

    dkv = jnp.dot(x, params["w_dkv"])
    c_kv = rms_norm(dkv[..., : cfg.kv_lora_rank], params["kv_norm"])
    k_rope = apply_rope(
        dkv[..., cfg.kv_lora_rank :][:, :, None, :], rope_table, positions
    )[:, :, 0, :]  # shared single rope head [B, S, rope_dim]

    if cache is not None:
        c_kv_all = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, cache_pos, 0)
        )
        k_rope_all = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, cache_pos, 0)
        )
        new_cache = {"c_kv": c_kv_all, "k_rope": k_rope_all}
        valid = cache_pos + S
    else:
        c_kv_all, k_rope_all, new_cache = c_kv, k_rope, None
        valid = S
    T = c_kv_all.shape[1]

    # Effective per-head keys: concat(up-projected nope, shared rope head).
    k_nope = jnp.dot(c_kv_all, params["w_uk"]).reshape(
        B, T, H, cfg.qk_nope_head_dim
    )
    k_eff = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope_all[:, :, None, :], (B, T, H, cfg.qk_rope_head_dim))],
        axis=-1,
    )
    v = jnp.dot(c_kv_all, params["w_uv"]).reshape(B, T, H, cfg.v_head_dim)
    q_eff = jnp.concatenate([q_nope, q_rope], axis=-1)

    out = _grouped_attention(
        q_eff.reshape(B, S, H, 1, qk_head),
        k_eff,
        v,
        q_positions=positions,
        kv_valid_len=valid,
        chunk=chunk,
    )
    out = out.reshape(B, S, H * cfg.v_head_dim)
    return jnp.dot(out, params["wo"]), new_cache



# ----------------------------------------------------- split-KV decode (§Perf)
#
# Decode with the KV cache sharded along the SEQUENCE axis over the mesh
# ``model`` axis (flash-decoding / split-KV): every rank attends over its
# 1/M chunk of the context and the partial softmaxes merge with the classic
# (max, sumexp, weighted-sum) reduction. The batch-sharded baseline cache
# does not even fit HBM for the decode_32k cells (EXPERIMENTS.md §Roofline);
# this layout shards the cache batch x seq = data x model.
#
# Projection weights KEEP sharded storage: inputs to row-sharded weights
# arrive feature-sharded via shard_map in_specs (P(..., 'model')) and a psum
# completes the contraction; outputs of column-sharded weights are assembled
# with a tiled all_gather. No partition-indexed dynamic slices — besides
# being cleaner SPMD, the traced-index form trips an XLA-CPU partitioner
# crash on bf16 ("Invalid binary instruction opcode copy"), recorded in
# EXPERIMENTS.md §Perf as a refuted-implementation note.


def _splitkv_merge(mi, li, oi, axis_name):
    """Merge per-chunk partial softmax results across ``axis_name``.

    mi/li [..., 1] chunk max / sumexp; oi [..., D] chunk weighted sum.
    """
    M = jax.lax.pmax(mi, axis_name)
    scale = jnp.exp(mi - M)
    num = jax.lax.psum(oi * scale, axis_name)
    den = jax.lax.psum(li * scale, axis_name)
    return num / jnp.maximum(den, 1e-30)


def gqa_decode_splitkv(
    params, x, rope_table, cfg: GQAConfig, cache, cache_pos, shard_ctx,
):
    """GQA decode step with seq-sharded cache. x [B, 1, d] (B over data)."""
    from jax.sharding import PartitionSpec as P

    m_axis = shard_ctx.model_axis

    def body(p, k_cache, v_cache, xm, pos):
        # xm: [B, 1, d/M] — this rank's feature slice of x.
        B = xm.shape[0]
        S_loc = k_cache.shape[1]
        m = jax.lax.axis_index(m_axis)
        positions = pos + jnp.arange(1)

        q = jax.lax.psum(jnp.dot(xm, p["wq"]), m_axis)
        k = jax.lax.psum(jnp.dot(xm, p["wk"]), m_axis)
        v = jax.lax.psum(jnp.dot(xm, p["wv"]), m_axis)
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = q.reshape(B, 1, cfg.n_heads, cfg.head_dim)
        k = k.reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"])
            k = rms_norm(k, p["k_norm"])
        q = apply_rope(q, rope_table, positions)
        k = apply_rope(k, rope_table, positions)

        # Insert the new token's K/V on the rank owning position ``pos``
        # (slice-level conditional write).
        owner = pos // S_loc
        local = jnp.where(owner == m, pos - owner * S_loc, 0)
        mine = owner == m

        def _masked_write(cache4, new4):
            cur = jax.lax.dynamic_slice(cache4, (0, local, 0, 0), new4.shape)
            val = jnp.where(mine, new4.astype(cache4.dtype), cur)
            return jax.lax.dynamic_update_slice(cache4, val, (0, local, 0, 0))

        k_cache = _masked_write(k_cache, k)
        v_cache = _masked_write(v_cache, v)

        # Partial attention over the local seq chunk.
        q5 = q.reshape(B, 1, cfg.n_kv_heads, cfg.group, cfg.head_dim)
        scale = 1.0 / np.sqrt(cfg.head_dim)
        logits = jnp.einsum(
            "bqkgd,bskd->bkgqs", q5, k_cache,
            preferred_element_type=jnp.float32,
        ) * scale  # [B, KV, G, 1, S_loc]
        kpos = m * S_loc + jnp.arange(S_loc)
        valid = (kpos <= pos)[None, None, None, None, :]
        logits = jnp.where(valid, logits, _NEG)
        mi = jnp.max(logits, axis=-1, keepdims=True)
        pexp = jnp.where(valid, jnp.exp(logits - mi), 0.0)
        li = jnp.sum(pexp, axis=-1, keepdims=True)
        oi = jnp.einsum("bkgqs,bskd->bkgqd", pexp, v_cache.astype(jnp.float32))

        out = _splitkv_merge(mi, li, oi, m_axis)  # [B,KV,G,1,D] merged
        out = out.transpose(0, 3, 1, 2, 4).reshape(
            B, 1, cfg.n_heads * cfg.head_dim
        ).astype(xm.dtype)
        # wo column-sharded on d_model: local part + tiled all_gather.
        y_part = jnp.dot(out, p["wo"])  # [B, 1, d/M]
        y = jax.lax.all_gather(y_part, m_axis, axis=2, tiled=True)
        return y, k_cache, v_cache

    p_specs = {
        "wq": P(m_axis, None), "wk": P(m_axis, None), "wv": P(m_axis, None),
        "wo": P(None, m_axis),
    }
    if cfg.qkv_bias:
        p_specs.update({"bq": P(), "bk": P(), "bv": P()})
    if cfg.qk_norm:
        p_specs.update({"q_norm": P(), "k_norm": P()})
    da = shard_ctx.data_axes
    # ALL-manual shard_map (every mesh axis listed): bf16 psum under
    # partial-manual shard_map hits an XLA-CPU partitioner crash
    # ("Invalid binary instruction opcode copy") — recorded in §Perf.
    fn = jax.shard_map(
        body,
        mesh=shard_ctx.mesh,
        in_specs=(p_specs, P(da, m_axis, None, None),
                  P(da, m_axis, None, None), P(da, None, m_axis), P()),
        out_specs=(P(da, None, None), P(da, m_axis, None, None),
                   P(da, m_axis, None, None)),
        check_vma=False,
    )
    out, k_new, v_new = fn(params, cache["k"], cache["v"], x, cache_pos)
    return out, {"k": k_new, "v": v_new}


def mla_decode_splitkv(
    params, x, rope_table, cfg: MLAConfig, cache, cache_pos, shard_ctx,
):
    """MLA decode: seq-sharded compressed cache + ABSORBED matmuls.

    Beyond-paper wins stacked here (§Perf cell A'):
      * cache (c_kv, k_rope) sharded batch x seq — fits HBM at 32k;
      * absorbed q (q_nope @ w_uk folded per step) — attention runs in the
        512-dim compressed space; the baseline's per-step cache expansion
        (T x H x (nope+v) matmuls over the whole context) disappears;
      * w_uk/w_uv enter replicated (33 MB/layer) — the price of absorption;
        every other projection keeps sharded storage (row-sharded with
        feature-sharded inputs, or column-sharded with a tiled all_gather).
    """
    from jax.sharding import PartitionSpec as P

    m_axis = shard_ctx.model_axis
    H = cfg.n_heads
    R = cfg.kv_lora_rank

    def body(p, c_kv, k_rope, xm, pos):
        B = xm.shape[0]
        S_loc = c_kv.shape[1]
        m = jax.lax.axis_index(m_axis)
        positions = pos + jnp.arange(1)

        cq = rms_norm(
            jax.lax.psum(jnp.dot(xm, p["w_dq"]), m_axis), p["q_norm"]
        )  # [B, 1, q_lora] replicated
        qk_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        # w_uq column-sharded on (H*qk_head): parts -> tiled all_gather.
        q_part = jnp.dot(cq, p["w_uq"])  # [B, 1, H*qk/M]
        q = jax.lax.all_gather(q_part, m_axis, axis=2, tiled=True).reshape(
            B, 1, H, qk_head
        )
        q_nope = q[..., : cfg.qk_nope_head_dim]
        q_rope = apply_rope(q[..., cfg.qk_nope_head_dim :], rope_table, positions)

        dkv = jax.lax.psum(jnp.dot(xm, p["w_dkv"]), m_axis)
        c_new = rms_norm(dkv[..., :R], p["kv_norm"])
        kr_new = apply_rope(
            dkv[..., R:][:, :, None, :], rope_table, positions
        )[:, :, 0, :]

        owner = pos // S_loc
        local = jnp.where(owner == m, pos - owner * S_loc, 0)
        mine = owner == m

        def _masked_write3(cache3, new3):
            cur = jax.lax.dynamic_slice(cache3, (0, local, 0), new3.shape)
            val = jnp.where(mine, new3.astype(cache3.dtype), cur)
            return jax.lax.dynamic_update_slice(cache3, val, (0, local, 0))

        c_kv = _masked_write3(c_kv, c_new)
        k_rope = _masked_write3(k_rope, kr_new)

        # Absorbed query: fold w_uk into q once per step.
        w_uk = p["w_uk"].reshape(R, H, cfg.qk_nope_head_dim)
        q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)  # [B,1,H,R]
        scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        logits = (
            jnp.einsum("bqhr,bsr->bhqs", q_abs, c_kv,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope,
                         preferred_element_type=jnp.float32)
        ) * scale  # [B, H, 1, S_loc]
        kpos = m * S_loc + jnp.arange(S_loc)
        valid = (kpos <= pos)[None, None, None, :]
        logits = jnp.where(valid, logits, _NEG)
        mi = jnp.max(logits, axis=-1, keepdims=True)
        pexp = jnp.where(valid, jnp.exp(logits - mi), 0.0)
        li = jnp.sum(pexp, axis=-1, keepdims=True)
        o_c = jnp.einsum("bhqs,bsr->bhqr", pexp, c_kv.astype(jnp.float32))
        o_c = _splitkv_merge(mi, li, o_c, m_axis)  # [B,H,1,R]

        w_uv = p["w_uv"].reshape(R, H, cfg.v_head_dim)
        out = jnp.einsum("bhqr,rhd->bqhd", o_c.astype(xm.dtype), w_uv)
        out = out.reshape(B, 1, H * cfg.v_head_dim)
        y_part = jnp.dot(out, p["wo"])  # wo column-sharded on d_model
        y = jax.lax.all_gather(y_part, m_axis, axis=2, tiled=True)
        return y, c_kv, k_rope

    p_specs = {
        "w_dq": P(m_axis, None), "q_norm": P(), "w_uq": P(None, m_axis),
        "w_dkv": P(m_axis, None), "kv_norm": P(),
        "w_uk": P(), "w_uv": P(),  # replicated: absorbed-path operands
        "wo": P(None, m_axis),
    }
    da = shard_ctx.data_axes
    fn = jax.shard_map(
        body,
        mesh=shard_ctx.mesh,
        in_specs=(p_specs, P(da, m_axis, None), P(da, m_axis, None),
                  P(da, None, m_axis), P()),
        out_specs=(P(da, None, None), P(da, m_axis, None),
                   P(da, m_axis, None)),
        check_vma=False,
    )
    out, c_new, kr_new = fn(params, cache["c_kv"], cache["k_rope"], x, cache_pos)
    return out, {"c_kv": c_new, "k_rope": kr_new}


def gqa_prefill_splitkv(
    params, x, rope_table, cfg: GQAConfig, cache, chunk_idx, shard_ctx,
    q_sub: int = 512,
):
    """One prefill chunk with the seq-sharded cache layout (§Perf cell A).

    x [B, C, d] where C == S_max / n_model — each chunk is owned by exactly
    one model rank, so the cache write is a masked full-slice set. Attention
    runs as sequence-parallel partial softmax: every rank scores the chunk's
    queries against ITS cache slice and the (max, sumexp, sum) merge psums
    combine — ring-attention-lite, one hop. q/k/v arrive via feature-sharded
    row contractions (psum); wo is column-sharded (tiled all_gather). The
    resulting cache layout is IDENTICAL to gqa_decode_splitkv's, so prefill
    and decode share one serving layout.
    """
    from jax.sharding import PartitionSpec as P

    m_axis = shard_ctx.model_axis
    da = shard_ctx.data_axes

    def body(p, k_cache, v_cache, xm, c_idx):
        B, C, _ = xm.shape
        S_loc = k_cache.shape[1]
        m = jax.lax.axis_index(m_axis)
        pos0 = c_idx * C
        positions = pos0 + jnp.arange(C)

        q = jax.lax.psum(jnp.dot(xm, p["wq"]), m_axis)
        k = jax.lax.psum(jnp.dot(xm, p["wk"]), m_axis)
        v = jax.lax.psum(jnp.dot(xm, p["wv"]), m_axis)
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = q.reshape(B, C, cfg.n_heads, cfg.head_dim)
        k = k.reshape(B, C, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(B, C, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"])
            k = rms_norm(k, p["k_norm"])
        q = apply_rope(q, rope_table, positions)
        k = apply_rope(k, rope_table, positions)

        # Chunk C == S_loc: rank c_idx owns the whole write.
        mine = (c_idx % jax.lax.axis_size(m_axis)) == m
        k_cache = jnp.where(mine, k.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(mine, v.astype(v_cache.dtype), v_cache)

        # Sequence-parallel attention: q sub-chunks vs the local slice.
        q5 = q.reshape(B, C, cfg.n_kv_heads, cfg.group, cfg.head_dim)
        scale = 1.0 / np.sqrt(cfg.head_dim)
        kpos = m * S_loc + jnp.arange(S_loc)
        nsub = max(C // q_sub, 1)
        sub = C // nsub

        def one_sub(args):
            qc, qpos = args  # [B, sub, KV, G, D], [sub]
            logits = jnp.einsum(
                "bqkgd,bskd->bkgqs", qc, k_cache,
                preferred_element_type=jnp.float32,
            ) * scale
            valid = kpos[None, :] <= qpos[:, None]  # [sub, S_loc]
            vmask = valid[None, None, None]
            logits = jnp.where(vmask, logits, _NEG)
            mi = jnp.max(logits, axis=-1, keepdims=True)
            pexp = jnp.where(vmask, jnp.exp(logits - mi), 0.0)
            li = jnp.sum(pexp, axis=-1, keepdims=True)
            oi = jnp.einsum("bkgqs,bskd->bkgqd", pexp,
                            v_cache.astype(jnp.float32))
            return _splitkv_merge(mi, li, oi, m_axis)  # [B,KV,G,sub,D]

        qr = q5.reshape(B, nsub, sub, cfg.n_kv_heads, cfg.group,
                        cfg.head_dim).transpose(1, 0, 2, 3, 4, 5)
        pr = positions.reshape(nsub, sub)
        outs = jax.lax.map(one_sub, (qr, pr))  # [nsub, B, KV, G, sub, D]
        out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(
            B, C, cfg.n_heads * cfg.head_dim
        ).astype(xm.dtype)
        y_part = jnp.dot(out, p["wo"])
        y = jax.lax.all_gather(y_part, m_axis, axis=2, tiled=True)
        return y, k_cache, v_cache

    p_specs = {
        "wq": P(m_axis, None), "wk": P(m_axis, None), "wv": P(m_axis, None),
        "wo": P(None, m_axis),
    }
    if cfg.qkv_bias:
        p_specs.update({"bq": P(), "bk": P(), "bv": P()})
    if cfg.qk_norm:
        p_specs.update({"q_norm": P(), "k_norm": P()})
    fn = jax.shard_map(
        body,
        mesh=shard_ctx.mesh,
        in_specs=(p_specs, P(da, m_axis, None, None),
                  P(da, m_axis, None, None), P(da, None, m_axis), P()),
        out_specs=(P(da, None, None), P(da, m_axis, None, None),
                   P(da, m_axis, None, None)),
        check_vma=False,
    )
    out, k_new, v_new = fn(params, cache["k"], cache["v"], x, chunk_idx)
    return out, {"k": k_new, "v": v_new}
