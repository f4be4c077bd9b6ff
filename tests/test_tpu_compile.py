"""Compile rehearsals of the serving path for a described TPU v5e.

Nothing here runs on a chip: each test lowers a program for a ``v5e:2x2``
topology that is described, not attached, and asks the TPU compiler to
compile it. That catches what interpret mode cannot (block shapes off the
(8, 128) tiling, kernels that overflow VMEM, meshes that do not partition)
at no chip time. ``chip_smoke.py`` is the run on the chip.

The topology is described inside a module fixture, never at import time:
only the worker that runs this file loads the TPU compiler. Kernels are
forced to compile by patching ``kernel.pallas_interpret`` (the platform
here is the CPU), and the persistent compilation cache is off around the
compiles, since a program compiled for an absent chip cannot be read back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.clustered_index import BLOCK
from repro.core.range_daat import (
    DeviceIndex,
    TopKState,
    TraverseCarry,
    batched_traverse,
    batched_traverse_resume,
)
from repro.kernels.range_scorer import kernel
from repro.serving.sharded import index_specs, make_mesh_dispatch

# Widths of the chip_smoke.py index (about 20M int8 postings over 64
# ranges, held as N_BLOCKS block rows of BLOCK lanes) and of its in-flight
# server (16 slots, 64-block tables).
N_BLOCKS = 4_800_000
N_WORDS = 8_000_000
N_TERMS = 50_000
R = 64
B = 64
S_PAD = 8192
SLOTS = 16
K = 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels compile rather than interpret; no persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(kernel, "pallas_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # drop programs traced with interpret=True
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding):
    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return s


def _index(s, docs_format, lead=()):
    packed = docs_format == "packed"
    return DeviceIndex(
        blk_docs=None if packed else s(lead + (N_BLOCKS, BLOCK)),
        blk_imps=s(lead + (N_BLOCKS, BLOCK), jnp.int8),
        blk_len=s(lead + (N_BLOCKS,)),
        blk_maximp=s(lead + (N_BLOCKS,)),
        bounds_dense=s(lead + ((1, 1) if lead else (N_TERMS, R))),
        range_starts=s(lead + (R,)),
        range_sizes=s(lead + (R,)),
        **(
            dict(
                pack_words=s(lead + (N_WORDS,), jnp.uint32),
                pack_dir=s(lead + (N_BLOCKS,)),
                pack_first=s(lead + (N_BLOCKS,)),
            )
            if packed
            else {}
        ),
    )


def test_scatter_kernel_compiles_for_v5e(one_chip, compiled_kernels):
    s = _spec(one_chip)
    fn = jax.jit(
        lambda ids, vals: kernel.scatter_accumulate_pallas(ids, vals, s_pad=S_PAD)
    )
    text = fn.lower(s((B * 128,)), s((B * 128,))).compile().as_text()
    assert "tpu_custom_call" in text


def test_unpack_kernel_compiles_for_v5e(one_chip, compiled_kernels):
    s = _spec(one_chip)
    compiled = kernel.unpack_locals_pallas.lower(
        s((N_WORDS,), jnp.uint32), s((B,)), s((B,)), s((B,)), s((B,)),
        s((B,), jnp.bool_), s(()),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("docs_format", ["int32", "packed"])
def test_batched_traverse_compiles_with_pallas_scorer(
    one_chip, compiled_kernels, docs_format
):
    s = _spec(one_chip)
    tables = (s((SLOTS, R, B)), s((SLOTS, R, B)), s((SLOTS, R)), s((SLOTS, R)))
    budgets = (s((SLOTS,)), s((SLOTS,)))
    text = batched_traverse.lower(
        _index(s, docs_format), *tables, *budgets,
        s_pad=S_PAD, k=K, impl="pallas", docs_format=docs_format,
    ).compile().as_text()
    assert "tpu_custom_call" in text


def test_inflight_resume_step_compiles_with_pallas_scorer(one_chip, compiled_kernels):
    s = _spec(one_chip)
    carry = TraverseCarry(
        i=s((SLOTS,)),
        state=TopKState(
            vals=s((SLOTS, K)), ids=s((SLOTS, K)),
            postings=s((SLOTS,)), blocks=s((SLOTS,)),
        ),
        exit_safe=s((SLOTS,), jnp.bool_),
        exit_budget=s((SLOTS,), jnp.bool_),
    )
    text = batched_traverse_resume.lower(
        _index(s, "packed"),
        s((SLOTS, R, B)), s((SLOTS, R, B)), s((SLOTS, R)), s((SLOTS, R)),
        s((SLOTS,)), s((SLOTS,)), carry,
        s_pad=S_PAD, k=K, quantum=2, impl="pallas", docs_format="packed",
    ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [K, 1000])
def test_inflight_carry_reset_and_pack_compile_for_v5e(one_chip, compiled_kernels, k):
    """The two small programs around each in-flight dispatch: the lane reset
    of the device-resident carry and its pack into one int32 array."""
    from repro.serving.inflight import _pack_carry, _reset_lanes

    s = _spec(one_chip)
    carry = TraverseCarry(
        i=s((SLOTS,)),
        state=TopKState(
            vals=s((SLOTS, k)), ids=s((SLOTS, k)),
            postings=s((SLOTS,)), blocks=s((SLOTS,)),
        ),
        exit_safe=s((SLOTS,), jnp.bool_),
        exit_budget=s((SLOTS,), jnp.bool_),
    )
    reset = _reset_lanes.lower(carry, s((SLOTS,)), k=k).compile()
    out = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), reset.out_info)
    assert out == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), carry)
    packed = _pack_carry.lower(carry).compile().out_info
    assert (packed.shape, packed.dtype) == ((SLOTS, 2 * k + 5), jnp.int32)


@pytest.mark.parametrize("replicas,shards", [(1, 4), (2, 2)])
def test_mesh_dispatch_compiles_for_v5e_2x2(topo, compiled_kernels, replicas, shards):
    """The four-chip broker step: index placed one shard per device."""
    devices = list(topo.devices)[: replicas * shards]
    if replicas == 1:
        mesh, data_axis = Mesh(devices, ("shard",)), None
    else:
        mesh = Mesh([devices[:shards], devices[shards:]], ("data", "shard"))
        data_axis = "data"
    n = SLOTS
    specs = index_specs("shard", "int32")
    dix = jax.tree.map(
        lambda sds, p: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, p)
        ),
        _index(lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype),
               "int32", lead=(shards,)),
        specs,
    )

    def at(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=NamedSharding(mesh, spec))

    da = data_axis
    fn = make_mesh_dispatch(
        mesh, "shard", s_pad=S_PAD, k=K, safe_stop=True, prune_blocks=True,
        impl="xla", data_axis=data_axis,
    )
    compiled = fn.lower(
        dix,
        at((shards,), P("shard")),
        at((n, shards, R, B), P(da, "shard")),
        at((n, shards, R, B), P(da, "shard")),
        at((n, shards, R), P(da, "shard")),
        at((n, shards, R), P(da, "shard")),
        at((n, shards), P(da, "shard")),
        at((n, shards), P(da, "shard")),
    ).compile()
    assert "all-gather" in compiled.as_text()
