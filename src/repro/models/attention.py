"""GQA attention: memory-blocked (query-chunked) prefill/train path, KV-cache
decode path, optional sliding window, qk-norm, biases, cross-attention.

The XLA path here is the *algorithmically same* computation as the Pallas
flash kernels in ``repro/kernels`` (online per-chunk softmax over query
blocks, fp32 accumulation): scores never materialize beyond one
(B, KV, G, chunk_q, S_kv) block, which is what keeps the 32k-prefill cells
inside HBM. Kernel selection is a config flag; the dry-run lowers this path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ArchConfig
from repro.models.layers import apply_rope, he_init, rms_norm
from repro.models.sharding import constrain

NEG_INF = -1e30


def init_attention(key, cfg: ArchConfig, d_in: int | None = None,
                   d_kv_in: int | None = None, rope: bool = True) -> dict:
    d_in = d_in or cfg.d_model
    d_kv_in = d_kv_in or d_in
    hq = cfg.n_heads * cfg.d_head
    hkv = cfg.n_kv_heads * cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": he_init(ks[0], (d_in, hq)),
        "wk": he_init(ks[1], (d_kv_in, hkv)),
        "wv": he_init(ks[2], (d_kv_in, hkv)),
        "wo": he_init(ks[3], (hq, cfg.d_model), fan_in=hq),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq,))
        p["bk"] = jnp.zeros((hkv,))
        p["bv"] = jnp.zeros((hkv,))
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.d_head,))
        p["k_norm"] = jnp.ones((cfg.d_head,))
    return p


def _project_qkv(x, x_kv, p, cfg: ArchConfig, positions, positions_kv, rope: bool):
    B, Sq, _ = x.shape
    Skv = x_kv.shape[1]
    q = x @ p["wq"].astype(x.dtype)
    k = x_kv @ p["wk"].astype(x.dtype)
    v = x_kv @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = constrain(q, "data", None, "model").reshape(B, Sq, cfg.n_heads, cfg.d_head)
    k = constrain(k, "data", None, None).reshape(B, Skv, cfg.n_kv_heads, cfg.d_head)
    v = constrain(v, "data", None, None).reshape(B, Skv, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions_kv, cfg.rope_theta)
    return q, k, v


def _blocked_attention(q, k, v, q_pos, k_pos, *, causal: bool, window: int, chunk_q: int):
    """q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd). Returns (B,Sq,H,hd).

    lax.scan over query chunks; per chunk the full key range is visited with
    an fp32 masked softmax (one block of scores live at a time).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(hd)
    chunk = min(chunk_q, Sq)
    n = Sq // chunk
    rem = Sq - n * chunk

    kg = k.reshape(B, -1, KV, hd)
    vg = v.reshape(B, -1, KV, hd)

    def one_chunk(qc, qpos_c):
        qq = qc.reshape(B, qc.shape[1], KV, G, hd)
        scores = jnp.einsum("bckgh,bskh->bkgcs", qq, kg, preferred_element_type=jnp.float32)
        scores = scores * scale
        if causal:
            m = qpos_c[:, None] >= k_pos[None, :]
            if window:
                m &= (qpos_c[:, None] - k_pos[None, :]) < window
            scores = jnp.where(m[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgcs,bskh->bckgh", probs.astype(v.dtype), vg)
        return out.reshape(B, -1, H, hd)

    if n > 0:
        qs = q[:, : n * chunk].reshape(B, n, chunk, H, hd).swapaxes(0, 1)
        ps = q_pos[: n * chunk].reshape(n, chunk)

        def body(_, xs):
            qc, pc = xs
            return None, one_chunk(qc, pc)

        _, outs = jax.lax.scan(body, None, (qs, ps))
        out = outs.swapaxes(0, 1).reshape(B, n * chunk, H, hd)
    else:
        out = jnp.zeros((B, 0, H, hd), q.dtype)
    if rem:
        out = jnp.concatenate([out, one_chunk(q[:, n * chunk:], q_pos[n * chunk:])], axis=1)
    return out


def attention_core(q, k, v, q_pos, k_pos, cfg: ArchConfig, *, causal: bool):
    """Dispatch between the baseline blocked-softmax path and the flash
    custom_vjp op (cfg.attn_impl). Flash covers the aligned full-window
    case; sliding windows stay on the blocked path."""
    aligned = (q.shape[1] == k.shape[1])
    if cfg.attn_impl == "flash" and cfg.sliding_window == 0 and aligned:
        from repro.kernels import ops as kops

        qt = q.transpose(0, 2, 1, 3)  # (B,H,S,D)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        out = kops.flash_attention(qt, kt, vt, causal, cfg.chunk_q)
        return out.transpose(0, 2, 1, 3)
    return _blocked_attention(q, k, v, q_pos, k_pos, causal=causal,
                              window=cfg.sliding_window, chunk_q=cfg.chunk_q)


def attention(x, p, cfg: ArchConfig, *, x_kv=None, causal=True, rope=True,
              positions=None, positions_kv=None) -> jax.Array:
    """Full-sequence (train/prefill) attention. x: (B, S, d_in)."""
    B, Sq, _ = x.shape
    x_kv = x if x_kv is None else x_kv
    Skv = x_kv.shape[1]
    if positions is None:
        positions = jnp.arange(Sq)
    if positions_kv is None:
        positions_kv = positions if x_kv.shape[1] == Sq else jnp.arange(Skv)
    q, k, v = _project_qkv(x, x_kv, p, cfg, positions, positions_kv, rope)
    out = attention_core(q, k, v, positions, positions_kv, cfg, causal=causal)
    out = out.reshape(B, Sq, cfg.n_heads * cfg.d_head)
    return out @ p["wo"].astype(x.dtype)


# -- KV-cache decode -------------------------------------------------------------


def init_kv_cache(cfg: ArchConfig, n_layers: int, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> dict:
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def cache_spec(cfg: ArchConfig, n_layers: int, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def update_cache_layer(cache_k_l, cache_v_l, k_new, v_new, pos):
    """Masked one-hot write at ``pos`` — sharding-friendly (no gather/scatter
    across the sequence-sharded cache dim; see DESIGN.md §5).

    cache_*_l: (B, S, KV, hd); k_new/v_new: (B, T, KV, hd) with T << S.
    """
    S = cache_k_l.shape[1]
    T = k_new.shape[1]
    onehot = (jnp.arange(S)[:, None] == (pos + jnp.arange(T))[None, :]).astype(cache_k_l.dtype)
    add_k = jnp.einsum("st,btkh->bskh", onehot, k_new.astype(cache_k_l.dtype))
    add_v = jnp.einsum("st,btkh->bskh", onehot, v_new.astype(cache_v_l.dtype))
    keep = (1 - onehot.sum(axis=1))[None, :, None, None]
    return cache_k_l * keep + add_k, cache_v_l * keep + add_v


def update_cache_layer_dus(cache_k_l, cache_v_l, k_new, v_new, pos):
    """In-place dynamic_update_slice cache write (optimized mode): with the
    cache donated, XLA aliases the buffer and only the written row moves —
    vs. the one-hot path's two full-cache passes (§Perf iteration)."""
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache_k_l, k_new.astype(cache_k_l.dtype), pos, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache_v_l, v_new.astype(cache_v_l.dtype), pos, axis=1)
    return ck, cv


def _decode_attention_smap(q, k_new, v_new, cache_k_l, cache_v_l, pos, cfg, ctx):
    """Explicit shard_map decode: the cache sequence dim stays shard-LOCAL,
    so the cache write is a 1-token in-place DUS on the owning rank (GSPMD's
    sharded-dim DUS lowers to a full-buffer select — §Perf iteration C4) and
    the softmax reduces over "model" with two tiny psums."""
    from jax import shard_map as _sm
    from jax.sharding import PartitionSpec as P

    mesh, axes = ctx.mesh, ctx.axes
    M = axes.model
    dp = axes.data if len(axes.data) > 1 else axes.data[0]
    KV, G, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.d_head
    nm = mesh.shape[M]

    def local(q, kn, vn, ck, cv, pos):
        B, S_loc = ck.shape[0], ck.shape[1]
        rank = jax.lax.axis_index(M)
        # -- 1-token in-place write on the owning rank --------------------
        lpos = pos - rank * S_loc
        in_range = (lpos >= 0) & (lpos < S_loc)
        idx = jnp.clip(lpos, 0, S_loc - 1)
        old_k = jax.lax.dynamic_slice_in_dim(ck, idx, 1, axis=1)
        old_v = jax.lax.dynamic_slice_in_dim(cv, idx, 1, axis=1)
        wk = jnp.where(in_range, kn.astype(ck.dtype), old_k)
        wv = jnp.where(in_range, vn.astype(cv.dtype), old_v)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, wk, idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, wv, idx, axis=1)
        # -- local scores + distributed online softmax ---------------------
        qq = q.reshape(B, 1, KV, G, hd)
        s = jnp.einsum("bckgh,bskh->bkgcs", qq, ck,
                       preferred_element_type=jnp.float32) / np.sqrt(hd)
        kpos = rank * S_loc + jnp.arange(S_loc)
        valid = kpos <= pos
        if cfg.sliding_window:
            valid &= (pos - kpos) < cfg.sliding_window
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)
        m = jax.lax.pmax(m_loc, M)
        p_ = jnp.exp(s - m[..., None])
        l = jax.lax.psum(jnp.sum(p_, axis=-1), M)
        o = jnp.einsum("bkgcs,bskh->bckgh", p_.astype(cv.dtype), cv)
        o = jax.lax.psum(o.astype(jnp.float32), M)  # (B, 1, KV, G, hd)
        norm = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]  # (B,1,KV,G,1)
        return (o / norm).astype(q.dtype), ck, cv

    kwargs = dict(
        mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, None, None, None),
                  P(dp, None, None, None), P(dp, M, None, None),
                  P(dp, M, None, None), P()),
        out_specs=(P(dp, None, None, None, None), P(dp, M, None, None),
                   P(dp, M, None, None)))
    smapped = _sm(local, **kwargs, check_vma=False)
    return smapped(q, k_new, v_new, cache_k_l, cache_v_l, pos)


def decode_attention(x, p, cfg: ArchConfig, cache_k_l, cache_v_l, pos, *, rope=True):
    """Single-token decode. x: (B, 1, d); cache_*_l: (B, S, KV, hd).

    Returns (out (B,1,d), new_k (B,S,KV,hd), new_v). Softmax statistics reduce
    over the (possibly model-axis-sharded) cache sequence dim.
    """
    from repro.models.sharding import current_ctx

    B = x.shape[0]
    S = cache_k_l.shape[1]
    positions = pos + jnp.arange(x.shape[1])
    q, k_new, v_new = _project_qkv(x, x, p, cfg, positions, positions, rope)
    ctx = current_ctx()
    if cfg.decode_cache_update == "shardmap" and ctx is not None \
            and S % ctx.mesh.shape[ctx.axes.model] == 0:
        out5, ck, cv = _decode_attention_smap(q, k_new, v_new, cache_k_l,
                                              cache_v_l, pos, cfg, ctx)
        out = out5.reshape(B, 1, cfg.n_heads * cfg.d_head)
        return out @ p["wo"].astype(x.dtype), ck, cv
    upd = update_cache_layer_dus if cfg.decode_cache_update == "dus" \
        else update_cache_layer
    ck, cv = upd(cache_k_l, cache_v_l, k_new, v_new, pos)

    KV, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    qq = q.reshape(B, 1, KV, G, cfg.d_head)
    scores = jnp.einsum("bckgh,bskh->bkgcs", qq, ck, preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(cfg.d_head)
    kpos = jnp.arange(S)
    m = kpos[None, :] <= positions[:, None]
    if cfg.sliding_window:
        m &= (positions[:, None] - kpos[None, :]) < cfg.sliding_window
    scores = jnp.where(m[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgcs,bskh->bckgh", probs.astype(cv.dtype), cv)
    out = out.reshape(B, 1, cfg.n_heads * cfg.d_head)
    return out @ p["wo"].astype(x.dtype), ck, cv
