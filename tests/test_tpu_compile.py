"""The relational Pallas kernels compile for a TPU v5e at the paper's XL
scale (5M rows), without a chip: the TPU compiler is asked for a described
``v5e:2x2`` topology. Interpret-mode tests cannot see what the chip's
compiler refuses (scalars in VMEM, tiles that break the 8x128 rule, shape
casts Mosaic cannot lay out); these compiles can.

The topology is described inside a module-scoped fixture — never while a
module is imported — so every test worker collects the same tests and only
the worker running this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.filter_count import filter_count
from repro.kernels.merge_join import merge_join_count
from repro.kernels.segment_agg import segment_agg
from repro.kernels.topk_mask import topk_merge

N = 5_000_000   # the paper's XL Wisconsin table
GROUPS = 100    # onePercent's domain


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


BLOCK_VARIANTS = {
    "plain": {},
    "block_ids": {"block_ids": (0, 7, 600, 1220)},
    "block_ids_arr": "arr",
}


@pytest.mark.parametrize("variant", list(BLOCK_VARIANTS))
def test_filter_count_compiles(one_chip, variant):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    args = [s((3, N), jnp.int32), s((3, 2), jnp.int32), s((), jnp.int32)]
    if BLOCK_VARIANTS[variant] == "arr":
        fn = lambda c, b, n, ids: filter_count(c, b, n, interpret=False,
                                               block_ids_arr=ids)
        args.append(s((64,), jnp.int32))
    else:
        kw = BLOCK_VARIANTS[variant]
        fn = lambda c, b, n: filter_count(c, b, n, interpret=False, **kw)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("variant", list(BLOCK_VARIANTS))
@pytest.mark.parametrize("op", ["sum", "max", "min"])
def test_segment_agg_compiles(one_chip, op, variant):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    args = [s((N, 2), jnp.float32), s((N,), jnp.int32), s((), jnp.int32)]
    if BLOCK_VARIANTS[variant] == "arr":
        fn = lambda v, g, n, ids: segment_agg(v, g, GROUPS, n, op=op,
                                              interpret=False,
                                              block_ids_arr=ids)
        args.append(s((64,), jnp.int32))
    else:
        kw = BLOCK_VARIANTS[variant]
        fn = lambda v, g, n: segment_agg(v, g, GROUPS, n, op=op,
                                         interpret=False, **kw)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_merge_join_count_compiles(one_chip):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    fn = lambda l, r, nl, nr: merge_join_count(l, r, nl, nr, interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, s((N,), jnp.int32), s((N,), jnp.int32), s((), jnp.int32),
        s((), jnp.int32))


def test_topk_merge_compiles(one_chip):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    fn = lambda sc, m, n: topk_merge(sc, m, n, 5, interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, s((N,), jnp.float32), s((N,), jnp.bool_), s((), jnp.int32))


def test_sharded_filter_count_compiles(topo):
    """The per-shard grid of the shard_map path: each of 4 chips scans its
    own surviving blocks through a scalar-prefetched ``block_ids_arr``."""
    from repro.engine.distributed import dist_kernel_filter_count

    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",),
                axis_types=(AxisType.Auto,))
    cols = _spec(NamedSharding(mesh, P(None, "data")), (3, N), jnp.int32)
    bounds = _spec(NamedSharding(mesh, P()), (3, 2), jnp.int32)
    shard_blocks = np.full((4, 8), -1, np.int32)
    shard_blocks[:, :3] = [[0, 1, 2], [5, 6, 7], [100, 101, 102], [3, 4, 9]]

    def fn(c, b):
        return dist_kernel_filter_count(mesh, ("data",), c, b,
                                        backend="pallas",
                                        shard_blocks=shard_blocks,
                                        interpret=False)

    txt = _compiled_text(fn, cols, bounds)
    assert "tpu_custom_call" in txt and "all-reduce" in txt
