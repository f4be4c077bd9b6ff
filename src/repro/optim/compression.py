"""Int8 error-feedback gradient compression for the cross-pod reduction.

On a multi-pod mesh the data-parallel gradient all-reduce crosses the
pod-interconnect (DCI), the slowest link in the system. This module cuts
that wire traffic ~4x by exchanging int8 block-quantized gradients
(all_gather of s8 payloads + fp32 block scales, local dequant-sum) instead
of an fp32 all-reduce. An error-feedback buffer re-injects the quantization
error next step (EF-SGD construction — convergence-neutral in practice).

Implementation: an ALL-manual ``shard_map`` whose body only references the
pod axis (non-pod axes are manual-but-unreferenced; partial-manual trips an
XLA-CPU partitioner crash). The s8 all-gather is visible in the
dry-run HLO — the §Perf collective table picks it up directly.

Error buffers carry a leading pod dimension (per-pod state); callers shard
them over (pod, data) so the fp32 buffer adds params/n_data bytes per chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["compress_psum_pod", "init_error_buffers"]

_BLOCK = 256


def _quantize(x: jnp.ndarray):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % _BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, _BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    q = jnp.round(blocks / jnp.maximum(scale, 1e-12)).astype(jnp.int8)
    return q, scale


def _dequantize(q, scale, shape):
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape)


def init_error_buffers(params_like, n_pods: int):
    """Per-pod fp32 error state, leading dim = n_pods."""
    return jax.tree.map(
        lambda g: jnp.zeros((n_pods, *g.shape), jnp.float32), params_like
    )


def compress_psum_pod(grad_fn, mesh, pod_axis: str = "pod"):
    """Wrap a per-pod gradient fn with int8 EF compression over ``pod``.

    grad_fn(batch_shard) -> per-pod grads pytree (params are closed over and
    remain GSPMD-sharded on the non-pod axes). Returns
    fn(batch, err) -> (mean grads across pods, new err buffers).
    """

    def body(batch_shard, err):
        g = grad_fn(batch_shard)
        n_pods = jax.lax.axis_size(pod_axis)

        def one(gl, el):
            el = el[0]  # leading pod dim -> local slice
            gf = gl.astype(jnp.float32) + el
            q, scale = _quantize(gf)
            # Compressed exchange: s8 payload + fp32 block scales on the wire.
            q_all = jax.lax.all_gather(q, pod_axis)  # [P, blocks, 256] int8
            s_all = jax.lax.all_gather(scale, pod_axis)
            summed = jnp.sum(
                q_all.astype(jnp.float32) * s_all, axis=0
            ).reshape(-1)[: gf.size].reshape(gf.shape)
            new_e = gf - _dequantize(q, scale, gf.shape)
            return summed / n_pods, new_e[None]

        pairs = jax.tree.map(one, g, err)
        grads = jax.tree.map(
            lambda t: t[0], pairs, is_leaf=lambda t: isinstance(t, tuple)
        )
        new_err = jax.tree.map(
            lambda t: t[1], pairs, is_leaf=lambda t: isinstance(t, tuple)
        )
        return grads, new_err

    # ALL-manual (every mesh axis listed): pod-only partial-manual hits the
    # same XLA-CPU partitioner crash as the attention psums. Non-pod axes are
    # simply unreferenced in the body, so the collective pattern is unchanged.
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(pod_axis), P(pod_axis)),
        out_specs=(P(), P(pod_axis)),
        axis_names=frozenset(mesh.axis_names),
        check_vma=False,
    )
