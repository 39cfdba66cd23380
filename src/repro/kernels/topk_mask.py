"""Masked per-block top-k (paper expression 9: ORDER BY ... LIMIT k).

Distributed top-k never sorts the dataset: each block yields its k local
maxima (k rounds of max + mask-out on the VPU — k is tiny, LIMIT 5 in the
benchmark), the (n/BLOCK, k) candidates merge with one small host-side
top_k. The kernel emits (values, global row indices) per block; dead rows
enter as -inf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.filter_count import _SMEM, _resolve_interpret

BLOCK = 4096
NEG = float("-inf")


def _kernel(nvalid_ref, scores_ref, mask_ref, vals_ref, idx_ref):
    step = pl.program_id(0)
    s = scores_ref[...]  # (1, BLOCK) f32
    b = s.shape[1]
    base = step * b
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    live = (base + lane) < nvalid_ref[0, 0]
    s = jnp.where((mask_ref[...] != 0) & live, s, NEG)
    k = vals_ref.shape[-1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    vals = jnp.zeros((1, k), jnp.float32)
    idx = jnp.zeros((1, k), jnp.int32)
    for kk in range(k):  # k is static & small
        v = jnp.max(s, axis=1, keepdims=True)
        a = jnp.argmax(s, axis=1, keepdims=True).astype(jnp.int32)
        vals = jnp.where(slot == kk, v, vals)
        idx = jnp.where(slot == kk, base + a, idx)
        s = jnp.where(lane == a, NEG, s)
    vals_ref[...] = vals
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def block_topk(scores: jax.Array, mask: jax.Array, n_valid, k: int,
               *, block: int = BLOCK, interpret: bool | None = None):
    """scores (n,), mask (n,) -> (values (nb, k), indices (nb, k)).

    The mask travels as int32 (the TPU has no bool memory tiles), and each
    block's k results land in a (1, k) row of an (nb, 1, k) output, whose
    trailing block dims equal the array's."""
    interpret = _resolve_interpret(interpret)
    n = scores.shape[0]
    pad = (-n) % block
    if pad:
        scores = jnp.pad(scores, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    nb = scores.shape[0] // block
    vals, idx = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[
            _SMEM,
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_specs=[pl.BlockSpec((None, 1, k), lambda i: (i, 0, 0)),
                   pl.BlockSpec((None, 1, k), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((nb, 1, k), jnp.int32)],
        interpret=interpret,
        name="block_topk",
    )(jnp.asarray(n_valid, jnp.int32).reshape(1, 1),
      scores.astype(jnp.float32).reshape(1, -1),
      mask.astype(jnp.int32).reshape(1, -1))
    return vals.reshape(nb, k), idx.reshape(nb, k)


def topk_merge(scores, mask, n_valid, k: int, *, block: int = BLOCK,
               interpret: bool | None = None):
    """Full top-k: block_topk + one small merge (the k×nb candidate set)."""
    vals, idx = block_topk(scores, mask, n_valid, k, block=block,
                           interpret=interpret)
    flat_v = vals.reshape(-1)
    flat_i = idx.reshape(-1)
    top_v, pos = jax.lax.top_k(flat_v, k)
    return top_v, flat_i[pos]
