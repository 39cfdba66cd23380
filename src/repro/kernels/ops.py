"""Public kernel ops: backend dispatch + the flash custom_vjp.

``flash_attention`` is the training-grade op: forward via the Pallas kernel
(TPU) or an XLA online-softmax twin (same math, used where Pallas cannot
compile — e.g. the CPU-hosted dry-run); EITHER way the custom_vjp saves only
(q, k, v, out, lse) and the backward *recomputes* probabilities blockwise —
no (Sq × Skv) probability tensor is ever stored. Swapping the models'
attention onto this op is §Perf iteration 1 (memory-roofline win).

Backend selection for the relational kernels: ``backend=None`` takes
:func:`default_backend` — compiled Pallas on a TPU, the XLA twins elsewhere;
"pallas" forces the kernels (interpret mode off the TPU), "xla" the twins.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode as _flash_decode_pallas
from repro.kernels.filter_count import _resolve_interpret
from repro.kernels.filter_count import filter_count as _filter_count
from repro.kernels.flash_attention import flash_mha_fwd as _flash_fwd_pallas
from repro.kernels.merge_join import grid_size as _merge_join_grid
from repro.kernels.merge_join import merge_join_count as _merge_join
from repro.kernels.segment_agg import segment_agg as _segment_agg
from repro.kernels.topk_mask import topk_merge as _topk_merge
from repro.runtime import telemetry as tel

# Trace-time dispatch counters: the kernel execution mode's tests assert the
# relational kernels are actually on the lowered path (one tick per trace,
# not per run — cached executables don't re-trace).
DISPATCH_COUNTS: dict[str, int] = {}

# Where the ``kernel.*`` counts of the kernels being traced go: a compiled
# query's own record (``recording_launches``), else straight to the registry.
_RECORD = threading.local()


def reset_dispatch_counts() -> None:
    DISPATCH_COUNTS.clear()


@contextlib.contextmanager
def recording_launches(into: dict):
    """Trace a program with its ``kernel.*`` counts recorded in ``into``
    (series id -> count) instead of the registry: the caller adds ``into``
    to the registry once per execution of what it traced. A retrace starts
    the record afresh."""
    into.clear()
    prev = getattr(_RECORD, "into", None)
    _RECORD.into = into
    try:
        yield into
    finally:
        _RECORD.into = prev


def count_kernel(name: str, value: int = 1, **labels) -> None:
    """Add to a ``kernel.*`` counter. Inside ``recording_launches`` the count
    belongs to the program being traced and is added on each execution;
    outside it the kernel runs eagerly, once, and counts at once."""
    into = getattr(_RECORD, "into", None)
    if into is None:
        tel.inc(name, value, **labels)
        return
    key = tel.series_key(name, labels)
    into[key] = into.get(key, 0) + int(value)


def _tick(name: str, grid: Optional[int] = None,
          blocks_total: Optional[int] = None,
          backend: Optional[str] = None,
          interpret: Optional[bool] = None) -> None:
    """One tick per trace into ``DISPATCH_COUNTS``, and the launch's
    ``kernel.*`` counts: which backend (pallas/xla), interpret vs compiled,
    and — for the block-skipping kernels — grid size vs the component's
    physical block count (scanned/skipped in kernel-block units)."""
    DISPATCH_COUNTS[name] = DISPATCH_COUNTS.get(name, 0) + 1
    pallas = _use_pallas(backend)
    count_kernel("kernel.launches_total", kernel=name,
                 backend="pallas" if pallas else "xla",
                 interpret=str(pallas and _resolve_interpret(interpret)).lower())
    if grid is not None:
        count_kernel("kernel.grid_blocks_total", grid, kernel=name)
        if blocks_total is not None:
            count_kernel("kernel.blocks_scanned_total", grid, kernel=name)
            count_kernel("kernel.blocks_skipped_total", blocks_total - grid,
                         kernel=name)


def default_backend() -> str:
    """The one rule for an unspecified kernel backend: the Pallas kernels
    wherever they compile (a TPU), their XLA twins elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _use_pallas(backend: Optional[str]) -> bool:
    return (backend or default_backend()) == "pallas"


# -- relational kernels ------------------------------------------------------------

# Zone-map block size the planner's block-skip lists are expressed in: the
# filter_count kernel's own tile. segment_agg's smaller BLOCK is bridged by
# _expand_block_ids below (one zone block = several kernel blocks).
from repro.kernels.filter_count import BLOCK as ZONE_BLOCK_ROWS


def _expand_block_ids(block_ids, zone_block: int, block: int,
                      n: int) -> tuple:
    """Re-express zone-block ids in units of a kernel's own (smaller or
    equal) block size, clipped to the kernel's padded block count."""
    if block_ids is None:
        return None
    assert zone_block % block == 0, (zone_block, block)
    r = zone_block // block
    nb = -(-n // block)
    out = tuple(j for b in block_ids
                for j in range(b * r, min((b + 1) * r, nb)))
    assert out, (block_ids, zone_block, block, n)  # layout mismatch otherwise
    return out


def shard_block_arrays(block_ids, zone_block: int, block: int, n_shards: int,
                       blocks_per_shard: int, rows_per_shard: int) -> np.ndarray:
    """Expand a flat shard-aware zone-block id tuple into the per-shard
    KERNEL-block id matrix the distributed wrappers scalar-prefetch: row
    ``s`` lists shard ``s``'s surviving local kernel-block ids (units of
    ``block`` rows over the shard's own chunk), ``-1``-padded at the END to
    the max surviving count (always >= 1 so the grid is non-empty — an
    all-``-1`` row is a shard with nothing to scan). The zone layout places
    flat block ``s * blocks_per_shard + j`` wholly inside shard ``s``, so
    the expansion never crosses a shard boundary."""
    assert zone_block % block == 0, (zone_block, block)
    r = zone_block // block
    nb_local = -(-rows_per_shard // block)
    per: list[list[int]] = [[] for _ in range(n_shards)]
    for b in block_ids:
        s, j = divmod(int(b), blocks_per_shard)
        per[s].extend(range(j * r, min((j + 1) * r, nb_local)))
    m = max(1, max(len(p) for p in per))
    out = np.full((n_shards, m), -1, np.int32)
    for s, p in enumerate(per):
        out[s, : len(p)] = p
    return out


def filter_count(cols, bounds, n_valid, backend: Optional[str] = None,
                 block_ids: Optional[tuple] = None,
                 block_ids_arr=None,
                 interpret: Optional[bool] = None):
    from repro.kernels.filter_count import BLOCK as _FC_BLOCK
    if block_ids_arr is not None:
        # per-shard ids (already kernel-block units, -1-padded): grid length
        # is the padded list; true scanned/skipped counts come from the
        # bound list in the distributed wrapper, not here.
        _tick("filter_count", grid=int(block_ids_arr.shape[0]),
              backend=backend, interpret=interpret)
        if _use_pallas(backend):
            return _filter_count(cols, bounds, n_valid,
                                 block_ids_arr=block_ids_arr,
                                 interpret=interpret)
        return ref.filter_count(cols, bounds, n_valid,
                                block_ids_arr=block_ids_arr, block=_FC_BLOCK)
    ids = _expand_block_ids(block_ids, ZONE_BLOCK_ROWS, _FC_BLOCK,
                            cols.shape[1])
    nb = -(-cols.shape[1] // _FC_BLOCK)
    _tick("filter_count", grid=len(ids) if ids is not None else nb,
          blocks_total=nb, backend=backend, interpret=interpret)
    if _use_pallas(backend):
        return _filter_count(cols, bounds, n_valid, block_ids=ids,
                             interpret=interpret)
    return ref.filter_count(cols, bounds, n_valid, block_ids=ids,
                            block=_FC_BLOCK)


def segment_agg(values, gids, num_groups, n_valid, op: str = "sum",
                backend: Optional[str] = None,
                block_ids: Optional[tuple] = None,
                block_ids_arr=None,
                interpret: Optional[bool] = None):
    from repro.kernels.segment_agg import BLOCK as _SA_BLOCK
    if block_ids_arr is not None:
        _tick("segment_agg", grid=int(block_ids_arr.shape[0]),
              backend=backend, interpret=interpret)
        if _use_pallas(backend):
            return _segment_agg(values, gids, num_groups, n_valid, op=op,
                                block_ids_arr=block_ids_arr,
                                interpret=interpret)
        return ref.segment_agg(values, gids, num_groups, n_valid, op,
                               block_ids_arr=block_ids_arr, block=_SA_BLOCK)
    ids = _expand_block_ids(block_ids, ZONE_BLOCK_ROWS, _SA_BLOCK,
                            values.shape[0])
    nb = -(-values.shape[0] // _SA_BLOCK)
    _tick("segment_agg", grid=len(ids) if ids is not None else nb,
          blocks_total=nb, backend=backend, interpret=interpret)
    if _use_pallas(backend):
        return _segment_agg(values, gids, num_groups, n_valid, op=op,
                            block_ids=ids, interpret=interpret)
    return ref.segment_agg(values, gids, num_groups, n_valid, op,
                           block_ids=ids, block=_SA_BLOCK)


def sort_join_keys(keys, mask, presorted: bool = False):
    """Prep one side for merge_join_count's sortedness contract: int32 keys,
    dead rows replaced by the +inf-style sentinel, ascending sort (skipped
    when the keys come from a sorted index). Shared by the single-device and
    shard-local kernel join paths."""
    if presorted:  # index order: valid ascending, sentinel tail
        return keys.astype(jnp.int32)
    sent = jnp.iinfo(jnp.int32).max
    return jnp.sort(jnp.where(mask, keys.astype(jnp.int32), sent))


def merge_join_count(lkeys, rkeys, nl, nr, backend: Optional[str] = None):
    """Equi-join cardinality over SORTED key columns (valid prefix of length
    nl/nr, +inf-style sentinel padding after). The XLA twin exploits the same
    sortedness contract via binary search — ref.merge_join_count's O(nl·nr)
    compare matrix is a test oracle, not an execution path.

    The kernel's counts: ``grid`` is one launch's pair capacity and
    ``blocks_total`` the full (left × right) tile grid, so
    ``blocks_skipped_total`` is what the overlapping band leaves out. They
    are fixed when the program is traced; the extra launches of a band
    wider than one launch (heavy duplicate keys) show only in the device
    trace, as more ``merge_join_count`` events per call."""
    if _use_pallas(backend):
        grid, total = _merge_join_grid(lkeys.shape[0], rkeys.shape[0])
        _tick("merge_join_count", grid=grid, blocks_total=total,
              backend=backend)
        return _merge_join(lkeys, rkeys, nl, nr)
    _tick("merge_join_count", backend=backend)
    lo = jnp.searchsorted(rkeys, lkeys, side="left")
    hi = jnp.minimum(jnp.searchsorted(rkeys, lkeys, side="right"), nr)
    lm = jnp.arange(lkeys.shape[0]) < nl
    return jnp.sum(jnp.where(lm, jnp.maximum(hi - lo, 0), 0), dtype=jnp.int32)


def topk(scores, mask, n_valid, k, backend: Optional[str] = None):
    """Masked top-k over the valid prefix: (values (k,), global indices (k,));
    identical tie-breaking (lowest index first) on both backends."""
    _tick("topk", backend=backend)
    if _use_pallas(backend):
        return _topk_merge(scores, mask, n_valid, k)
    live = mask & (jnp.arange(scores.shape[0]) < n_valid)
    s = jnp.where(live, scores.astype(jnp.float32), -jnp.inf)
    vals, idx = jax.lax.top_k(s, k)
    return vals, idx.astype(jnp.int32)


# -- flash attention (training-grade custom_vjp) -------------------------------------


def _xla_flash_fwd(q, k, v, causal: bool, bq: int):
    """Online-softmax forward in plain jnp (scan over q blocks), emitting
    (out, lse) — identical contract to the Pallas kernel."""
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(D)
    bq = min(bq, Sq)
    nqb = Sq // bq
    rem = Sq - nqb * bq
    kg = k.astype(jnp.float32)
    vg = v.astype(jnp.float32)

    def one(qc, qpos):
        qq = qc.reshape(B, KV, G, -1, D).astype(jnp.float32) * scale
        s = jnp.einsum("bkgqd,bksd->bkgqs", qq, kg)
        if causal:
            m = qpos[:, None] >= jnp.arange(Skv)[None, :]
            s = jnp.where(m[None, None, None], s, -1e30)
        mx = jnp.max(s, axis=-1)
        p = jnp.exp(s - mx[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bkgqs,bksd->bkgqd", p, vg) / jnp.maximum(l, 1e-30)[..., None]
        lse = mx + jnp.log(jnp.maximum(l, 1e-30))
        qlen = qq.shape[3]
        return o.reshape(B, H, qlen, D), lse.reshape(B, H, qlen)

    outs, lses = [], []
    if nqb:
        qs = q[:, :, : nqb * bq].reshape(B, H, nqb, bq, D).transpose(2, 0, 1, 3, 4)
        ps = jnp.arange(nqb * bq).reshape(nqb, bq)

        def body(_, xs):
            qc, pp = xs
            o, ls = one(qc.transpose(0, 1, 2, 3), pp)
            return None, (o, ls)

        _, (o_s, l_s) = jax.lax.scan(body, None, (qs, ps))
        outs.append(o_s.transpose(1, 2, 0, 3, 4).reshape(B, H, nqb * bq, D))
        lses.append(l_s.transpose(1, 2, 0, 3).reshape(B, H, nqb * bq))
    if rem:
        o, ls = one(q[:, :, nqb * bq:], jnp.arange(nqb * bq, Sq))
        outs.append(o)
        lses.append(ls)
    out = jnp.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]
    lse = jnp.concatenate(lses, axis=2) if len(lses) > 1 else lses[0]
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, bq: int = 512,
                    backend: str = "xla"):
    """GQA attention, O(S) residuals. q: (B,H,Sq,D); k,v: (B,KV,Skv,D)."""
    out, _ = _flash_fwd_dispatch(q, k, v, causal, bq, backend)
    return out


def _flash_fwd_dispatch(q, k, v, causal, bq, backend):
    if backend == "pallas":
        return _flash_fwd_pallas(q, k, v, causal=causal, bq=min(bq, q.shape[2]))
    return _xla_flash_fwd(q, k, v, causal, bq)


def _flash_fwd_rule(q, k, v, causal, bq, backend):
    out, lse = _flash_fwd_dispatch(q, k, v, causal, bq, backend)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, bq, backend, res, do):
    """Recompute-probabilities backward, blocked over q chunks (no (Sq×Skv)
    residual). Standard flash equations with the saved lse."""
    q, k, v, out, lse = res
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    bq_ = min(bq, Sq)
    nqb = Sq // bq_
    rem = Sq - nqb * bq_

    def chunk_grads(qc, oc, dc, lc, qpos):
        qf = qc.reshape(B, KV, G, -1, D).astype(jnp.float32)
        of = oc.reshape(B, KV, G, -1, D).astype(jnp.float32)
        df = dc.reshape(B, KV, G, -1, D).astype(jnp.float32)
        lf = lc.reshape(B, KV, G, -1)
        s = jnp.einsum("bkgqd,bksd->bkgqs", qf * scale, kf)
        if causal:
            m = qpos[:, None] >= jnp.arange(Skv)[None, :]
            s = jnp.where(m[None, None, None], s, -1e30)
        p = jnp.exp(s - lf[..., None])  # exact probs from lse
        dp = jnp.einsum("bkgqd,bksd->bkgqs", df, vf)
        delta = jnp.sum(df * of, axis=-1)  # (B,KV,G,q)
        ds = p * (dp - delta[..., None])
        dqc = jnp.einsum("bkgqs,bksd->bkgqd", ds, kf) * scale
        dkc = jnp.einsum("bkgqs,bkgqd->bksd", ds, qf) * scale
        dvc = jnp.einsum("bkgqs,bkgqd->bksd", p, df)
        return dqc.reshape(B, H, -1, D), dkc, dvc

    dq_parts = []
    dk = jnp.zeros((B, KV, Skv, D), jnp.float32)
    dv = jnp.zeros((B, KV, Skv, D), jnp.float32)
    if nqb:
        def split4(a):
            return a[:, :, : nqb * bq_].reshape(B, H, nqb, bq_, D).transpose(2, 0, 1, 3, 4)

        qs = split4(q)
        os_ = split4(out)
        dos = split4(do)
        ls = lse[:, :, : nqb * bq_].reshape(B, H, nqb, bq_).transpose(2, 0, 1, 3)
        ps = jnp.arange(nqb * bq_).reshape(nqb, bq_)

        def body(carry, xs):
            dk_, dv_ = carry
            qc, oc, dc, lc, pp = xs
            dqc, dkc, dvc = chunk_grads(qc, oc, dc, lc, pp)
            return (dk_ + dkc, dv_ + dvc), dqc

        (dk, dv), dq_s = jax.lax.scan(body, (dk, dv), (qs, os_, dos, ls, ps))
        dq_parts.append(dq_s.transpose(1, 2, 0, 3, 4).reshape(B, H, nqb * bq_, D))
    if rem:
        dqc, dkc, dvc = chunk_grads(q[:, :, nqb * bq_:], out[:, :, nqb * bq_:],
                                    do[:, :, nqb * bq_:], lse[:, :, nqb * bq_:],
                                    jnp.arange(nqb * bq_, Sq))
        dk = dk + dkc
        dv = dv + dvc
        dq_parts.append(dqc)
    dq = jnp.concatenate(dq_parts, axis=2) if len(dq_parts) > 1 else dq_parts[0]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_decode(q, k, v, lengths, backend: Optional[str] = None):
    """Single-token decode attention. q: (B,H,D); k,v: (B,KV,S,D)."""
    if _use_pallas(backend):
        return _flash_decode_pallas(q, k, v, lengths)
    return ref.decode_attention(q, k, v, lengths)
