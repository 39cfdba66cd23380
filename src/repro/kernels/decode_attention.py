"""Flash-decode: single-token attention against a long KV cache, Pallas TPU.

Grid (B, KV, S/BK): the sequential dim streams cache blocks through VMEM
with online-softmax state per (kv-head × G q-heads). Per-sequence valid
length masks dead cache slots (padded/unwritten); a production variant would
bound the KV walk with scalar-prefetched lengths — here every block is
visited and masked (noted; the masked blocks cost bandwidth only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.filter_count import _resolve_interpret

DEFAULT_BK = 1024
NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, scale: float, bk: int, nkb: int):
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (BK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, BK)
    pos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(pos < len_ref[0, 0], s, NEG)
    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[:, 0] = m_new

    @pl.when(jk == nkb - 1)
    def _emit():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def flash_decode(q, k, v, lengths, *, bk: int = DEFAULT_BK,
                 interpret: bool | None = None):
    """q: (B,H,D); k,v: (B,KV,S,D); lengths: (B,) -> (B,H,D)."""
    interpret = _resolve_interpret(interpret)
    B, H, D = q.shape
    KV, S = k.shape[1], k.shape[2]
    G = H // KV
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    nkb = S // bk
    scale = 1.0 / np.sqrt(D)
    qg = q.reshape(B, KV, G, D)

    kernel = functools.partial(_kernel, scale=scale, bk=bk, nkb=nkb)
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, nkb),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, j: (b, 0)),
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32).reshape(B, 1), qg, k, v)
    return out.reshape(B, H, D)
