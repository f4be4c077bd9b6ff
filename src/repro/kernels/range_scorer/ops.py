"""Jitted public wrapper for the range scorer.

``score_blocks`` is the one entry point the traversal engine calls; ``impl``
selects the XLA scatter path (fast on CPU, the oracle) or the Pallas one-hot
MXU kernel, which ``kernel.pallas_interpret`` interprets on CPU and compiles
on TPU. ``docs_format``
selects how block docids reach the scorer: ``"int32"`` gathers the blocks'
docid rows, ``"packed"`` decodes per-block bit-packed deltas in place
(DESIGN.md §12) — the two are bitwise-identical by contract. Impacts are
gathered as block rows in both. Both paths
run their posting gathers under the named scope ``gather`` and their
accumulation under ``accumulate``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.range_scorer import ref
from repro.kernels.range_scorer.kernel import (
    scatter_accumulate_pallas,
    unpack_locals_pallas,
)
from repro.kernels.range_scorer.ref import IMPACT_BIAS  # noqa: F401 — re-export

DOCS_FORMATS = ("int32", "packed")

__all__ = ["DOCS_FORMATS", "IMPACT_BIAS", "score_blocks"]


@functools.partial(
    jax.jit, static_argnames=("s_pad", "impl", "docs_format")
)
def score_blocks(
    blk_docs: jnp.ndarray | None,
    blk_imps: jnp.ndarray,
    ids: jnp.ndarray,
    lens: jnp.ndarray,
    keep: jnp.ndarray,
    range_start: jnp.ndarray,
    *,
    s_pad: int,
    impl: str = "xla",
    docs_format: str = "int32",
    pack_words: jnp.ndarray | None = None,
    pack_dir: jnp.ndarray | None = None,
    pack_firsts: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Accumulate surviving posting blocks into an int32 [s_pad] accumulator.

    ``blk_docs`` and ``blk_imps`` are the index's ``[NB, BLOCK]`` block
    rows and ``ids`` the ``[B]`` block ids to score (-1 pads). Under
    ``docs_format="packed"``, ``blk_docs`` is ignored (pass None) and the
    per-block merged directory (``pack_dir``, see
    ``core.clustered_index.pack_dir_entries``) and first-docid column plus
    the shared ``pack_words`` stream are required.
    """
    if docs_format not in DOCS_FORMATS:
        raise ValueError(f"docs_format {docs_format!r} not in {DOCS_FORMATS}")
    if docs_format == "packed" and (
        pack_words is None or pack_dir is None or pack_firsts is None
    ):
        raise ValueError("docs_format='packed' requires all pack_* arrays")
    if impl == "xla":
        if docs_format == "packed":
            return ref.score_blocks_packed_ref(
                pack_words, blk_imps, ids, lens,
                pack_dir, pack_firsts, keep, range_start, s_pad,
            )
        return ref.score_blocks_ref(
            blk_docs, blk_imps, ids, lens, keep, range_start, s_pad
        )
    if impl == "pallas":
        if docs_format == "packed":
            with jax.named_scope("gather"):
                local = unpack_locals_pallas(
                    pack_words, ids, lens,
                    pack_dir, pack_firsts, keep, range_start,
                )
                valid = ref._lane_valid(ids, lens, keep)
                v = ref.gather_block_impacts(blk_imps, ids)
                vals = jnp.where(valid, v, 0).astype(jnp.int32).reshape(local.shape)
        else:
            with jax.named_scope("gather"):
                local, vals = ref.gather_block_postings(
                    blk_docs, blk_imps, ids, lens, keep, range_start
                )
        with jax.named_scope("accumulate"):
            return scatter_accumulate_pallas(local, vals, s_pad=s_pad)
    raise ValueError(f"unknown impl {impl!r}")
