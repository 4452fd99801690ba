"""Ahead-of-time compiles of the device path's kernels for a TPU v5e.

The chip's compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology with no chip attached.  It refuses what interpret
mode accepts — unaligned blocks, too much VMEM, a Mosaic kernel that
would need automatic partitioning — so these compiles guard every
kernel on the load-and-serve path at the sizes the stream uses.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.compbin_decode.ops import (_decode_impl, _decode_sharded,
                                              stream_bucket_ids)
from repro.kernels.segment_sum import segment_sum
from repro.kernels.segment_sum.ops import edge_block

#: a stream bucket of a scale-22 Kronecker partition (~8 M edges)
STREAM_IDS = stream_bucket_ids(8_000_000)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


@pytest.mark.parametrize("b", [1, 2, 3, 4])
def test_decode_compiles_for_one_chip(one_chip, b):
    packed = jax.ShapeDtypeStruct((STREAM_IDS * b,), jnp.uint8,
                                  sharding=one_chip)
    compiled = _compile(functools.partial(
        _decode_impl, b=b, n=STREAM_IDS, block_rows=256, interpret=False),
        packed)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 4 * STREAM_IDS
    # the planar staging transpose is the only temp: its (rows, 128, b)
    # u8 intermediate pads each ID's b bytes to one 128-lane row
    assert mem.temp_size_in_bytes <= 128 * STREAM_IDS


@pytest.mark.parametrize("E,N,block_e", [(64, 64, 128),
                                         (65536, 1024, 512),
                                         (65536, 8192, 512)])
def test_segment_sum_compiles_at_chosen_blocks(one_chip, E, N, block_e):
    assert edge_block(E) == block_e
    msgs = jax.ShapeDtypeStruct((E, 128), jnp.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((E,), jnp.int32, sharding=one_chip)
    _compile(functools.partial(segment_sum, num_segments=N,
                               interpret=False), msgs, ids)


def test_decode_partitions_over_four_chips(topo):
    mesh = Mesh(topo.devices, ("data",))
    b = 3
    packed = jax.ShapeDtypeStruct((STREAM_IDS * b,), jnp.uint8,
                                  sharding=NamedSharding(mesh, P("data")))
    compiled = _compile(functools.partial(
        _decode_sharded, b=b, block_rows=256, interpret=False, mesh=mesh,
        axis="data"), packed)
    # each chip decodes its own quarter: no gather of the packed stream
    assert compiled.memory_analysis().output_size_in_bytes \
        == STREAM_IDS * 4 // 4
