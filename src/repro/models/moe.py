"""Fine-grained MoE (DeepSeek-MoE / Moonlight family): shared experts +
top-k routed experts, expert-parallel over the mesh "model" axis.

TPU-native design (DESIGN.md §2): tokens stay sharded over the data axes and
*replicated* over "model" (they already are at the FFN input of a TP block).
Each model rank therefore dispatches only to its E/M local experts and emits a
partial token output; one psum over "model" combines — the same all-gather +
psum comm pattern as a dense TP MLP, with **no token all-to-all at all**.
Dispatch itself is sort-based with a capacity bound (static shapes), and the
combine is the one-hot ``segment_sum`` primitive the DataFrame group-by also
uses (kernels/segment_agg.py is its Pallas form).

Per-rank routing is recomputed redundantly on every model rank — 2·T·d·E
FLOPs, noise against the expert GEMMs — buying zero-collective dispatch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig, MoESpec
from repro.models.layers import he_init, mlp
from repro.models.sharding import current_ctx

from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P


def init_moe(key, cfg: ArchConfig, spec: MoESpec) -> dict:
    d, fe, E = cfg.d_model, spec.d_ff_expert, spec.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": he_init(ks[0], (d, E)),
        "experts": {
            "w1": he_init(ks[1], (E, d, fe), fan_in=d),
            "w3": he_init(ks[2], (E, d, fe), fan_in=d),
            "w2": he_init(ks[3], (E, fe, d), fan_in=fe),
        },
    }
    if spec.num_shared:
        from repro.models.layers import init_mlp

        p["shared"] = init_mlp(ks[4], d, spec.num_shared * fe, gated=True)
    return p


def _capacity(tokens: int, spec: MoESpec) -> int:
    return max(int(math.ceil(tokens * spec.top_k * spec.capacity_factor / spec.num_experts)), 4)


def _local_moe(xl, router_w, w1, w3, w2, *, spec: MoESpec, e_local: int,
               rank, psum, pmean):
    """Per-(data, model)-shard MoE body. xl: (B_loc, S, d)."""
    B, S, d = xl.shape
    T = B * S
    xf = xl.reshape(T, d)
    k = spec.top_k
    E = spec.num_experts
    C = _capacity(T, spec)
    off = rank * e_local

    logits = (xf @ router_w.astype(xf.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)  # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # switch-style load-balance aux loss over *global* tokens
    onehot_frac = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(pmean(onehot_frac) * pmean(mean_prob)) / k

    # -- local dispatch (sort-based rank-in-expert, capacity C) --------------
    flat_idx = idx.reshape(-1)  # (T*k,)
    flat_gate = gates.reshape(-1)
    is_local = (flat_idx >= off) & (flat_idx < off + e_local)
    lidx = jnp.clip(flat_idx - off, 0, e_local - 1)
    sort_key = jnp.where(is_local, lidx, e_local).astype(jnp.int32)
    order = jnp.argsort(sort_key, stable=True)
    sorted_key = sort_key[order]
    starts = jnp.searchsorted(sorted_key, jnp.arange(e_local + 1), side="left")
    rank_sorted = jnp.arange(T * k) - starts[jnp.clip(sorted_key, 0, e_local)]
    rank_in_e = jnp.zeros((T * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = is_local & (rank_in_e < C)
    slot = lidx * C + jnp.minimum(rank_in_e, C - 1)
    token_of = jnp.arange(T * k) // k

    contrib = jnp.where(keep[:, None], xf[token_of], 0)
    xdisp = jax.ops.segment_sum(contrib, slot, num_segments=e_local * C)
    xdisp = xdisp.reshape(e_local, C, d)

    # -- expert FFN (swiglu), E_local experts resident on this rank ----------
    h1 = jnp.einsum("ecd,edf->ecf", xdisp, w1.astype(xdisp.dtype))
    h3 = jnp.einsum("ecd,edf->ecf", xdisp, w3.astype(xdisp.dtype))
    yd = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h1) * h3, w2.astype(xdisp.dtype))

    # -- combine: gather own slots, weight, sum over k, psum over model ------
    y_flat = yd.reshape(e_local * C, d)
    w = jnp.where(keep, flat_gate, 0.0).astype(y_flat.dtype)
    y_tok = y_flat[slot] * w[:, None]
    y_part = y_tok.reshape(T, k, d).sum(axis=1)
    y = psum(y_part)
    return y.reshape(B, S, d), aux


def moe_ffn(x: jax.Array, p: dict, cfg: ArchConfig, spec: MoESpec) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss). Shared experts add on top (dense TP)."""
    ctx = current_ctx()
    if ctx is not None and ctx.axes.model in ctx.mesh.shape \
            and spec.num_experts % ctx.mesh.shape[ctx.axes.model] == 0 \
            and ctx.mesh.shape[ctx.axes.model] > 1:
        mesh, axes = ctx.mesh, ctx.axes
        M = mesh.shape[axes.model]
        e_local = spec.num_experts // M
        dp = axes.data if len(axes.data) > 1 else axes.data[0]

        def mapped(xl, router_w, w1, w3, w2):
            r = jax.lax.axis_index(axes.model)
            y_l, aux_l = _local_moe(
                xl, router_w, w1, w3, w2, spec=spec, e_local=e_local,
                rank=r,
                psum=lambda v: jax.lax.psum(v, axes.model),
                pmean=lambda v: jax.lax.pmean(v, axes.data),
            )
            # identical across model ranks; pmean makes replication provable
            return y_l, jax.lax.pmean(aux_l, axes.model)

        gather_dt = jnp.bfloat16 if cfg.moe_gather_dtype == "bf16" else None
        cast = (lambda w: w.astype(gather_dt)) if gather_dt else (lambda w: w)
        y, aux = _shard_map(
            mapped, mesh=mesh,
            in_specs=(P(dp, None, None), P(None, None),
                      P(axes.model, None, None), P(axes.model, None, None),
                      P(axes.model, None, None)),
            out_specs=(P(dp, None, None), P()),
        )(x, p["router"], cast(p["experts"]["w1"]), cast(p["experts"]["w3"]),
          cast(p["experts"]["w2"]))
    else:
        y, aux = _local_moe(
            x, p["router"], p["experts"]["w1"], p["experts"]["w3"],
            p["experts"]["w2"], spec=spec, e_local=spec.num_experts,
            rank=0, psum=lambda v: v, pmean=lambda v: v,
        )
    if "shared" in p:
        y = y + mlp(x, p["shared"])
    return y, aux
