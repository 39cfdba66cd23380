"""Fused multi-predicate filter + count (paper expressions 1/3/11).

One pass over k conjunct columns: each grid step loads a (k, BLOCK) tile
into VMEM, evaluates the ANDed range predicates on the VPU, and accumulates
a popcount into a (1,1) SMEM accumulator. Predicate *constants* arrive
as a (k, 2) SMEM operand so randomized benchmark literals reuse the compiled
kernel. This is the engine's answer to "SELECT COUNT(*) WHERE ..." — no
intermediate mask column ever touches HBM.

**Block skipping**: ``block_ids`` (a static tuple of surviving block
indices, produced by the planner's bind-time zone-map test) drives the grid
through the ``index_map`` — the id list rides in as a scalar-prefetch
operand (``PrefetchScalarGridSpec``), the grid size is the number of
*surviving* blocks, not the total, and the index_map fetches each step's
physical tile by id, so pruned tiles are never DMA'd out of HBM. The kernel
reads the same scalar ref to rebuild the row-index base for the ``n_valid``
edge check, keeping results bit-identical to the unskipped launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 4096


def _body(bounds_ref, nvalid_ref, cols_ref, out_ref, base):
    """Shared predicate/accumulate body; ``base`` is the first physical row
    index of this step's tile."""
    k, b = cols_ref.shape  # (k, BLOCK) int32 tile
    idx = base + jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    ok = idx < nvalid_ref[0, 0]
    for j in range(k):  # k is static: one row per conjunct, scalar bounds
        col = cols_ref[j:j + 1, :]
        ok = ok & (col >= bounds_ref[j, 0]) & (col <= bounds_ref[j, 1])
    out_ref[0, 0] += jnp.sum(ok.astype(jnp.int32))


def _kernel(bounds_ref, nvalid_ref, cols_ref, out_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[0, 0] = jnp.int32(0)

    _body(bounds_ref, nvalid_ref, cols_ref, out_ref,
          step * cols_ref.shape[1])


def _kernel_ids(ids_ref, bounds_ref, nvalid_ref, cols_ref, out_ref):
    """Block-skipping variant: the grid enumerates surviving blocks; the
    scalar-prefetched id list yields each step's physical block id so the
    validity base is exact."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[0, 0] = jnp.int32(0)

    _body(bounds_ref, nvalid_ref, cols_ref, out_ref,
          ids_ref[step] * cols_ref.shape[1])


def _kernel_ids_arr(ids_ref, bounds_ref, nvalid_ref, cols_ref, out_ref):
    """Runtime-id variant (per-shard grids under shard_map): the id list is
    a TRACED scalar-prefetch operand padded with ``-1`` sentinels up to a
    common length, so every shard shares one compiled grid while scanning a
    different surviving set. The index_map clamps pad ids to tile 0 (some
    tile must be addressed); the body is gated off for them, so a pad step
    contributes nothing and the count stays bit-identical."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[0, 0] = jnp.int32(0)

    @pl.when(ids_ref[step] >= 0)
    def _run():
        _body(bounds_ref, nvalid_ref, cols_ref, out_ref,
              ids_ref[step] * cols_ref.shape[1])


# Scalars (predicate bounds, the valid-row count, the count accumulator)
# live in SMEM: the TPU stores scalars there and not in VMEM.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _resolve_interpret(interpret):
    # None = auto: compiled Pallas on real TPUs, interpret mode elsewhere
    # (the kernels' semantics are validated everywhere, compiled where the
    # hardware exists).
    return jax.default_backend() != "tpu" if interpret is None else interpret


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "block_ids"))
def filter_count(cols: jax.Array, bounds: jax.Array, n_valid,
                 *, block: int = BLOCK, interpret: bool | None = None,
                 block_ids: tuple | None = None,
                 block_ids_arr: jax.Array | None = None) -> jax.Array:
    """cols: (k, n) int32; bounds: (k, 2); n_valid scalar. -> int32 count.

    ``block_ids``: optional static tuple of surviving block indices (units
    of ``block`` rows over the unpadded layout); the grid visits only those
    tiles. Skipped blocks provably contain no matching rows, so the count
    is bit-identical to the full launch.

    ``block_ids_arr``: TRACED (m,) int32 alternative, padded with ``-1``
    sentinels at the END — the per-shard form: under shard_map every shard
    binds its own local id list of a common padded length, so one compiled
    grid serves all shards. Mutually exclusive with ``block_ids``."""
    interpret = _resolve_interpret(interpret)
    k, n = cols.shape
    pad = (-n) % block
    if pad:
        cols = jnp.pad(cols, ((0, 0), (0, pad)))
    nb = cols.shape[1] // block
    args = [bounds.astype(jnp.int32),
            jnp.asarray(n_valid, jnp.int32).reshape(1, 1),
            cols.astype(jnp.int32)]
    if block_ids_arr is not None:
        assert block_ids is None, "block_ids and block_ids_arr are exclusive"
        ids = block_ids_arr.astype(jnp.int32)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(int(ids.shape[0]),),
            in_specs=[
                _SMEM,
                _SMEM,
                pl.BlockSpec((k, block),
                             lambda i, ids: (0, jnp.maximum(ids[i], 0))),
            ],
            out_specs=_SMEM,
        )
        out = pl.pallas_call(
            _kernel_ids_arr,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
            interpret=interpret,
            name="filter_count",
        )(ids, *args)
        return out[0, 0]
    if block_ids is None:
        out = pl.pallas_call(
            _kernel,
            grid=(nb,),
            in_specs=[
                _SMEM,                                       # bounds: resident
                _SMEM,                                       # n_valid scalar
                pl.BlockSpec((k, block), lambda i: (0, i)),  # column tile
            ],
            out_specs=_SMEM,                                 # accumulator
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
            interpret=interpret,
            name="filter_count",
        )(*args)
        return out[0, 0]
    assert all(0 <= b < nb for b in block_ids), (block_ids, nb)
    # grid = surviving blocks; the scalar-prefetched id list feeds the
    # index_map, so pruned tiles are never fetched at all.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(len(block_ids),),
        in_specs=[
            _SMEM,
            _SMEM,
            pl.BlockSpec((k, block), lambda i, ids: (0, ids[i])),
        ],
        out_specs=_SMEM,
    )
    out = pl.pallas_call(
        _kernel_ids,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=interpret,
        name="filter_count",
    )(jnp.asarray(block_ids, jnp.int32), *args)
    return out[0, 0]
