"""Causal GQA flash attention (prefill/train forward), Pallas TPU.

Grid (B, H, Sq/BQ, Skv/BK): the innermost (sequential) dim walks KV blocks
with the classic online-softmax state (m, l, acc) living in VMEM scratch;
out-of-causal-range KV blocks are skipped via ``pl.when``; the normalized
tile and its logsumexp are written when the last in-range KV block retires.
lse is emitted because the custom_vjp backward (kernels/ops.py) recomputes
probabilities from (q, k, v, lse) instead of materializing them — the whole
point vs. the XLA path (EXPERIMENTS.md §Perf iteration 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.filter_count import _resolve_interpret

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
            *, scale: float, causal: bool, bq: int, bk: int, nkb: int):
    iq = pl.program_id(2)
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    last = jnp.minimum(((iq + 1) * bq - 1) // bk, nkb - 1) if causal else nkb - 1
    in_range = (jk * bk <= (iq + 1) * bq - 1) if causal else True

    @pl.when(in_range)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new

    @pl.when(jk == last)
    def _emit():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, 0] + jnp.log(l)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_mha_fwd(q, k, v, *, causal: bool = True, bq: int = DEFAULT_BQ,
                  bk: int = DEFAULT_BK, interpret: bool | None = None):
    """q: (B,H,Sq,D); k,v: (B,KV,Skv,D) -> (out (B,H,Sq,D), lse (B,H,Sq))."""
    interpret = _resolve_interpret(interpret)
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nqb, nkb = Sq // bq, Skv // bk
    scale = 1.0 / np.sqrt(D)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nkb=nkb)
    scratch = [
        pltpu.VMEM((bq, D), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nqb, nkb),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, g=G: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, g=G: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
    return out, lse
