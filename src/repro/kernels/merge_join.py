"""Block sorted-merge join count (paper expression 12).

TPU-native replacement for hybrid-hash join: both key columns arrive sorted
(from a sorted index, or one engine sort). The grid walks (left-block ×
right-block) pairs; sortedness means only O(diagonal) pairs can overlap, so
each pair first checks its zone (block min/max) and skips the O(BL·BR)
equality popcount unless ranges intersect — block-granular merge join, brute
equality inside a block (a (BL, BR) VPU compare, duplicate-correct).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.filter_count import _SMEM, _resolve_interpret

BLOCK = 1024


def _kernel(nl_ref, nr_ref, l_ref, r_ref, out_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[0, 0] = jnp.int32(0)

    bl, br = l_ref.shape[1], r_ref.shape[1]
    # zone check: block ranges must intersect (sorted ⇒ min/max at the ends)
    l_lo, l_hi = l_ref[0, 0], l_ref[0, bl - 1]
    r_lo, r_hi = r_ref[0, 0], r_ref[0, br - 1]
    overlap = (l_lo <= r_hi) & (r_lo <= l_hi)

    @pl.when(overlap)
    def _count():
        # the left tile as a (BL, 1) column: a row cannot be reshaped across
        # the lane/sublane boundary, so transpose a lane-broadcast copy
        l_col = jnp.transpose(jnp.broadcast_to(l_ref[...], (128, bl)))[:, :1]
        lm = (i * bl + jax.lax.broadcasted_iota(jnp.int32, (bl, 1), 0)) \
            < nl_ref[0, 0]
        rm = (j * br + jax.lax.broadcasted_iota(jnp.int32, (1, br), 1)) \
            < nr_ref[0, 0]
        eq = (l_col == r_ref[...]) & lm & rm
        out_ref[0, 0] += jnp.sum(eq.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def merge_join_count(lkeys: jax.Array, rkeys: jax.Array, nl, nr,
                     *, block: int = BLOCK,
                     interpret: bool | None = None) -> jax.Array:
    """lkeys/rkeys: sorted int32 (valid prefix of length nl/nr; +inf-style
    sentinel padding after). -> int32 join cardinality."""
    interpret = _resolve_interpret(interpret)
    def padto(a):
        pad = (-a.shape[0]) % block
        if pad:
            a = jnp.pad(a, (0, pad), constant_values=jnp.iinfo(jnp.int32).max)
        return a

    l = padto(lkeys.astype(jnp.int32))
    r = padto(rkeys.astype(jnp.int32))
    out = pl.pallas_call(
        _kernel,
        grid=(l.shape[0] // block, r.shape[0] // block),
        in_specs=[
            _SMEM,
            _SMEM,
            pl.BlockSpec((1, block), lambda i, j: (0, i)),
            pl.BlockSpec((1, block), lambda i, j: (0, j)),
        ],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=interpret,
        name="merge_join_count",
    )(jnp.asarray(nl, jnp.int32).reshape(1, 1),
      jnp.asarray(nr, jnp.int32).reshape(1, 1),
      l.reshape(1, -1), r.reshape(1, -1))
    return out[0, 0]
