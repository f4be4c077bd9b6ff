"""The paper's own system as a dry-run architecture: ``anytime-ir``.

One 256-chip node = 16 ISN shards (model axis) x 16-way query parallelism
(data axis); multi-pod doubles query throughput (pod axis = replication —
§7 of the paper). Sizes model a web-scale node: 64M docs / 4B postings
across shards, 256 topical ranges, 256-query batches, k=10.

The serve step is serve/distributed_ir.make_sharded_query_fn — per-shard
anytime traversal (postings budget = the per-ISN SLA quantum) + the broker
all_gather merge.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import Arch, ShapeInfo
from repro.distributed.sharding import ShardCtx
from repro.serve import distributed_ir as dir_mod

_FULL = dict(
    n_queries=256, n_shards=16, r_loc=16, b_width=512,
    nnz_loc=256_000_000, nb_loc=2_097_152, s_pad=262_144, k=10,
)
_REDUCED = dict(
    n_queries=8, n_shards=1, r_loc=8, b_width=64,
    nnz_loc=65_536, nb_loc=2048, s_pad=1024, k=10,
)


class AnytimeIRArch(Arch):
    name = "anytime-ir"
    family = "ir"
    # "i8": impacts stored at their native quantized width (int8) — the
    # paper quantizes to 8 bits anyway; storing them at int32 (baseline)
    # wastes 3 bytes/posting of HBM traffic. §Perf cell C.
    variants = ("baseline", "i8")

    def shapes(self):
        return {
            "serve_anytime": ShapeInfo(
                "serve_anytime", "serve",
                "256-query batch, 16 ISN shards, SLA postings budget",
            ),
            "serve_exhaustive": ShapeInfo(
                "serve_exhaustive", "serve",
                "same, unlimited budget (rank-safe baseline)",
            ),
        }

    def model_config(self, reduced: bool = False):
        return dict(_REDUCED if reduced else _FULL)

    def init_params(self, key, cfg):
        del key
        # "Params" = the sharded index arrays (stateless serving).
        arrays, _ = dir_mod.sharded_query_specs(**cfg)
        return arrays

    def param_shapes(self, cfg):
        return self.init_params(None, cfg)

    def _with_variant(self, cfg, variant):
        import jax.numpy as jnp

        if variant == "i8":
            return dict(cfg, impact_dtype=jnp.int8)
        return cfg

    def input_specs(self, cfg, shape):
        _, tables = dir_mod.sharded_query_specs(**cfg)
        return {"tables": tables}

    def make_batch(self, cfg, shape, seed: int = 0):
        raise NotImplementedError(
            "anytime-ir smoke coverage lives in tests/test_distributed_ir.py "
            "(real index build + oracle comparison)"
        )

    def build_step(self, cfg, shape, shard_ctx: ShardCtx | None = None,
                   variant: str = "baseline"):
        del variant  # the step is dtype-agnostic (int8 widens on gather)
        budget = 2**31 - 1 if shape == "serve_exhaustive" else cfg["nnz_loc"] // 64
        if shard_ctx is None:
            mesh = jax.make_mesh((1, 1), ("data", "model"))
            shard_ctx = ShardCtx(mesh=mesh, data_axes=("data",), model_axis="model")
        fn = dir_mod.make_sharded_query_fn(
            shard_ctx, s_pad=cfg["s_pad"], k=cfg["k"], budget=budget
        )

        def step(arrays, batch):
            return fn(arrays, batch["tables"])

        return step, "serve"

    def param_pspecs(self, cfg, params, variant: str = "baseline", ctx=None):
        del variant, ctx
        m = "model"
        return dir_mod.ShardedIndexArrays(
            blk_docs=P(m, None, None), blk_imps=P(m, None, None),
            blk_len=P(m, None), blk_maximp=P(m, None), range_starts=P(m, None),
            doc_base=P(m), s_pad=cfg["s_pad"], k=cfg["k"],
        )

    def batch_pspecs(self, cfg, shape, ctx: ShardCtx, variant: str = "baseline"):
        del variant
        da = ctx.data_axes
        m = ctx.model_axis
        return {
            "tables": (
                P(da, m, None, None), P(da, m, None, None),
                P(da, m, None), P(da, m, None),
            )
        }
