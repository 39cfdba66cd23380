"""Group-by aggregation as one-hot matmul — the MXU segment reduction
(paper expressions 4/8; also the MoE combine primitive).

Per grid step: a (BLOCK,) tile of group ids becomes a (G, BLOCK) one-hot
matrix multiplied against the (BLOCK, C) value tile on the MXU, accumulating
(G, C) partial sums in the output block (revisited every step — Pallas keeps
it resident in VMEM). Bounded-domain keys (Wisconsin mod-columns, MoE expert
ids) make G small, so the one-hot GEMM beats scatter-adds on TPU, which has
no efficient random-access memory path.

``op`` selects the reduction: "sum" (the MXU matmul above) or "max"/"min"
(VPU select-and-reduce over the same one-hot tile, one value column at a
time — not sum-shaped, so no matmul, but the same blocked revisit pattern
keeps the (G, C) accumulator in VMEM). The max/min launch takes its values
column-major, (C, n), so each column is a lane-dense (1, BLOCK) row.
max/min feed group extremes for the kernel execution mode and the
incrementally-maintained views of the streaming ingestion subsystem.

``block_ids`` drives the grid through only the listed blocks (zone-map
block skipping): the id list rides in as a scalar-prefetch operand feeding
the index_map, and the kernel reads the same ref to rebuild the ``n_valid``
base — skipped blocks hold no live rows for this launch's mask, so partials
are bit-identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 2048

_INIT = {"sum": 0.0, "max": -jnp.inf, "min": jnp.inf}


def _body(op, nvalid_ref, gid_ref, val_ref, out_ref, base):
    gids = gid_ref[...]  # (1, BLOCK)
    b = gids.shape[1]
    G = out_ref.shape[0]
    live = (base + jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)) < nvalid_ref[0, 0]
    live = live & (gids >= 0) & (gids < G)
    onehot = jax.lax.broadcasted_iota(jnp.int32, (G, b), 0) == gids
    if op == "sum":
        vals = val_ref[...]  # (BLOCK, C)
        oh = onehot.astype(jnp.float32) * live.astype(jnp.float32)
        out_ref[...] += jax.lax.dot(oh, vals.astype(jnp.float32),
                                    preferred_element_type=jnp.float32)
        return
    # max/min: value tiles arrive as (C, BLOCK) rows, one (G, BLOCK)
    # select-and-reduce per column
    sel = onehot & live
    red = jnp.max if op == "max" else jnp.min
    comb = jnp.maximum if op == "max" else jnp.minimum
    for c in range(val_ref.shape[0]):
        cand = jnp.where(sel, val_ref[c:c + 1, :].astype(jnp.float32), _INIT[op])
        out_ref[:, c:c + 1] = comb(out_ref[:, c:c + 1],
                                   red(cand, axis=1, keepdims=True))


def _kernel(op, nvalid_ref, gid_ref, val_ref, out_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _INIT[op])

    _body(op, nvalid_ref, gid_ref, val_ref, out_ref,
          step * gid_ref.shape[1])


def _kernel_ids(op, ids_ref, nvalid_ref, gid_ref, val_ref, out_ref):
    """Block-skipping variant: grid over surviving blocks only; the scalar-
    prefetched id list rebuilds the validity base per step."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _INIT[op])

    _body(op, nvalid_ref, gid_ref, val_ref, out_ref,
          ids_ref[step] * gid_ref.shape[1])


def _kernel_ids_arr(op, ids_ref, nvalid_ref, gid_ref, val_ref, out_ref):
    """Runtime-id variant (per-shard grids under shard_map): the id list is
    a TRACED scalar-prefetch operand padded with ``-1`` sentinels — one
    compiled grid of the max surviving count serves every shard. Pad steps
    clamp to tile 0 in the index_map and are gated off here, so the partial
    aggregates stay bit-identical."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _INIT[op])

    @pl.when(ids_ref[step] >= 0)
    def _run():
        _body(op, nvalid_ref, gid_ref, val_ref, out_ref,
              ids_ref[step] * gid_ref.shape[1])


@functools.partial(jax.jit,
                   static_argnames=("num_groups", "op", "block", "interpret",
                                    "block_ids"))
def segment_agg(values: jax.Array, gids: jax.Array, num_groups: int, n_valid,
                *, op: str = "sum", block: int = BLOCK,
                interpret: bool | None = None,
                block_ids: tuple | None = None,
                block_ids_arr: jax.Array | None = None) -> jax.Array:
    """values: (n, c) f32; gids: (n,) int32 -> (num_groups, c) per-group
    ``op``-reductions. Groups with no live member hold the identity
    (0 / -inf / +inf) — callers mask by count.

    ``interpret=None`` auto-detects: compiled Pallas on TPU, interpret mode
    elsewhere. ``block_ids`` (static tuple, units of ``block`` rows) makes
    the grid visit only the listed blocks — sound whenever every live row
    with gid ≥ 0 lives in a listed block. ``block_ids_arr`` is the TRACED
    (m,) int32 per-shard alternative, ``-1``-padded at the end (mutually
    exclusive with ``block_ids``)."""
    assert op in _INIT, op
    from repro.kernels.filter_count import _SMEM, _resolve_interpret
    interpret = _resolve_interpret(interpret)
    n, c = values.shape
    pad = (-n) % block
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        gids = jnp.pad(gids, (0, pad))
    nb = values.shape[0] // block
    if op == "sum":
        vspec = lambda at: pl.BlockSpec((block, c), lambda i, *ids: (at(i, *ids), 0))
    else:
        values = values.T
        vspec = lambda at: pl.BlockSpec((c, block), lambda i, *ids: (0, at(i, *ids)))
    args = [jnp.asarray(n_valid, jnp.int32).reshape(1, 1),
            gids.astype(jnp.int32).reshape(1, -1), values]
    if block_ids_arr is not None:
        assert block_ids is None, "block_ids and block_ids_arr are exclusive"
        ids = block_ids_arr.astype(jnp.int32)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(int(ids.shape[0]),),
            in_specs=[
                _SMEM,
                pl.BlockSpec((1, block),
                             lambda i, ids: (0, jnp.maximum(ids[i], 0))),
                vspec(lambda i, ids: jnp.maximum(ids[i], 0)),
            ],
            out_specs=pl.BlockSpec((num_groups, c), lambda i, ids: (0, 0)),
        )
        return pl.pallas_call(
            functools.partial(_kernel_ids_arr, op),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((num_groups, c), jnp.float32),
            interpret=interpret,
            name="segment_agg",
        )(ids, *args)
    if block_ids is None:
        return pl.pallas_call(
            functools.partial(_kernel, op),
            grid=(nb,),
            in_specs=[
                _SMEM,
                pl.BlockSpec((1, block), lambda i: (0, i)),
                vspec(lambda i: i),
            ],
            out_specs=pl.BlockSpec((num_groups, c), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((num_groups, c), jnp.float32),
            interpret=interpret,
            name="segment_agg",
        )(*args)
    assert all(0 <= b < nb for b in block_ids), (block_ids, nb)
    # grid = surviving blocks; the scalar-prefetched id list feeds the
    # index_map, so pruned tiles are never fetched at all.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(len(block_ids),),
        in_specs=[
            _SMEM,
            pl.BlockSpec((1, block), lambda i, ids: (0, ids[i])),
            vspec(lambda i, ids: ids[i]),
        ],
        out_specs=pl.BlockSpec((num_groups, c), lambda i, ids: (0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel_ids, op),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_groups, c), jnp.float32),
        interpret=interpret,
        name="segment_agg",
    )(jnp.asarray(block_ids, jnp.int32), *args)
