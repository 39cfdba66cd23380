"""Explicit shared-nothing relational operators (shard_map + hand-placed
collectives) — the optimized execution mode of the engine.

The GSPMD mode (plain jnp under jit) lets XLA insert collectives; it tends to
all-gather whole columns for sorts/joins. This module is the beyond-paper
optimized path: every operator does shard-local work sized O(rows/shard) and
merges with the *minimal* collective —

  operator          local work                merge collective
  ----------------- ------------------------- -------------------------------
  filter+count      masked popcount           psum (4 B)
  scalar agg        local min/max/sum         psum/pmax/pmin (4-8 B)
  group-by agg      segment_sum (G buckets)   psum (G × aggs)
  top-k             local lax.top_k(k)        all_gather(k) + final top_k
  limit(n)          local compact(n)          all_gather(n) + recompact
  join count        local sort + probe        all_gather of build keys
                    (or hash all-to-all repartition — see
                    ``hash_repartition_counts``)
  index range count searchsorted per shard    psum

All functions take (mesh, data_axes); on a 1-device mesh they degenerate to
the local op (tests run both paths and assert equality).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map as _shard_map

from repro.engine import physical


def _dp(data_axes: tuple[str, ...]):
    return data_axes if len(data_axes) > 1 else data_axes[0]


def _smap(mesh, data_axes, fn, in_specs, out_specs):
    # check_vma=False: the replication checker cannot statically see that
    # all_gather + identical local computation yields replicated outputs
    # (merge-style operators below are deterministic post-gather).
    return _shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)


# -- scalar aggregation -----------------------------------------------------------


def dist_count(mesh: Mesh, data_axes, mask: jax.Array) -> jax.Array:
    dp = _dp(data_axes)

    def local(m):
        return jax.lax.psum(jnp.sum(m, dtype=jnp.int32), data_axes)

    return _smap(mesh, data_axes, local, (P(dp),), P())(mask)


def dist_agg(mesh: Mesh, data_axes, op: str, col: jax.Array, mask: jax.Array):
    dp = _dp(data_axes)

    def local(c, m):
        v = physical.agg_scalar({"c": c}, m, op, "c")
        if op in ("count", "sum"):
            return jax.lax.psum(v, data_axes)
        if op == "max":
            return jax.lax.pmax(v, data_axes)
        if op == "min":
            return jax.lax.pmin(v, data_axes)
        if op == "mean":
            s = jax.lax.psum(jnp.sum(jnp.where(m, c, 0).astype(jnp.float32)), data_axes)
            n = jax.lax.psum(jnp.sum(m, dtype=jnp.int32), data_axes)
            return s / jnp.maximum(n, 1)
        raise ValueError(op)

    cspec = P(dp) if col.ndim == 1 else P(dp, None)
    return _smap(mesh, data_axes, local, (cspec, P(dp)), P())(col, mask)


# -- group by ----------------------------------------------------------------------


def dist_group_agg(mesh: Mesh, data_axes, key_col, mask, lo: int, num_groups: int,
                   aggs, value_cols: dict):
    """Bounded-domain group-by: local segment reduction, psum merge.

    ``aggs``: [(out_name, op, col|None)]; ``value_cols``: {col: array}.
    ``mean`` decomposes into psum(sum)/psum(count). Output replicated
    (G rows — the merged group table)."""
    dp = _dp(data_axes)
    names = sorted(value_cols)
    # decompose mean into sum+count primitives
    prim: list[tuple[str, str, Optional[str]]] = [("__n__", "count", None)]
    for o, op, c in aggs:
        if op == "mean":
            prim.append((f"__sum_{o}", "sum", c))
        else:
            prim.append((o, op, c))

    def local(key, m, *cols):
        env = {"__key__": key, **dict(zip(names, cols))}
        out, _ = physical.group_agg(env, m, "__key__", lo, num_groups, prim)
        merged = {}
        for o, op, c in prim:
            if op in ("count", "sum"):
                merged[o] = jax.lax.psum(out[o], data_axes)
            elif op == "max":
                merged[o] = jax.lax.pmax(out[o], data_axes)
            elif op == "min":
                merged[o] = jax.lax.pmin(out[o], data_axes)
        return out["__key__"], tuple(merged[o] for o, _, _ in prim)

    in_specs = (P(dp), P(dp)) + tuple(P(dp) for _ in names)
    out_specs = (P(), tuple(P() for _ in prim))
    key_out, vals = _smap(mesh, data_axes, local, in_specs, out_specs)(
        key_col, mask, *[value_cols[n] for n in names])
    merged = {o: v for (o, _, _), v in zip(prim, vals)}
    out = {"__key__": key_out}
    for o, op, c in aggs:
        if op == "mean":
            out[o] = merged[f"__sum_{o}"] / jnp.maximum(merged["__n__"], 1)
        else:
            out[o] = merged[o]
    return out, merged["__n__"] > 0


# -- top-k / limit -----------------------------------------------------------------


def dist_topk(mesh: Mesh, data_axes, env: dict, mask, key: str, k: int,
              ascending: bool, select=physical._select_topk):
    """Local top-k then k-per-shard gather + final top-k (ring merge).
    ``select`` swaps the selection primitive (the kernel mode passes the
    block_topk Pallas kernel); the merge structure is identical."""
    dp = _dp(data_axes)
    names = sorted(env)

    def local(m, *cols):
        e = dict(zip(names, cols))
        le, lm = physical.topk(e, m, key, min(k, m.shape[0]), ascending,
                               select=select)
        ge = {n: jax.lax.all_gather(le[n], data_axes, tiled=True) for n in names}
        gm = jax.lax.all_gather(lm, data_axes, tiled=True)
        return physical.topk(ge, gm, key, k, ascending, select=select)

    in_specs = (P(dp),) + tuple(P(dp) if env[n].ndim == 1 else P(dp, None) for n in names)
    out_specs = ({n: P() if env[n].ndim == 1 else P(None, None) for n in names}, P())
    return _smap(mesh, data_axes, local, in_specs, out_specs)(
        mask, *[env[n] for n in names])


def dist_limit(mesh: Mesh, data_axes, env: dict, mask, n: int):
    """Local compact(n) + gather + global first-n (order: shard-major)."""
    dp = _dp(data_axes)
    names = sorted(env)

    def local(m, *cols):
        e = dict(zip(names, cols))
        le, lm = physical.limit(e, m, n)
        ge = {k2: jax.lax.all_gather(le[k2], data_axes, tiled=True) for k2 in names}
        gm = jax.lax.all_gather(lm, data_axes, tiled=True)
        return physical.limit(ge, gm, n)

    in_specs = (P(dp),) + tuple(P(dp) if env[nm].ndim == 1 else P(dp, None) for nm in names)
    out_specs = ({nm: P() if env[nm].ndim == 1 else P(None, None) for nm in names}, P())
    return _smap(mesh, data_axes, local, in_specs, out_specs)(
        mask, *[env[nm] for nm in names])


# -- joins -------------------------------------------------------------------------


def dist_join_count(mesh: Mesh, data_axes, lkey, lmask, rkey, rmask,
                    presorted_right: bool = False) -> jax.Array:
    """Broadcast-merge join count: gather build-side keys (sorted), probe
    locally with binary search, psum. The AFrame-Index analogue — with a
    sorted index the build side skips its local sort."""
    dp = _dp(data_axes)

    def local(lk, lm, rk, rm):
        sentinel = physical._maxval(rk.dtype)
        rs = rk if presorted_right else jnp.sort(jnp.where(rm, rk, sentinel))
        n_r_local = jnp.sum(rm)
        rs_g = jax.lax.all_gather(rs, data_axes, tiled=True)  # gathered sorted runs
        rs_g = jnp.sort(rs_g)  # merge runs (single vector sort)
        n_r = jax.lax.psum(n_r_local, data_axes)
        lo = jnp.searchsorted(rs_g, lk, side="left")
        hi = jnp.searchsorted(rs_g, lk, side="right")
        hi = jnp.minimum(hi, n_r)
        cnt = jnp.where(lm, jnp.maximum(hi - lo, 0), 0)
        return jax.lax.psum(jnp.sum(cnt, dtype=jnp.int64), data_axes)

    return _smap(mesh, data_axes, local, (P(dp), P(dp), P(dp), P(dp)), P())(
        lkey, lmask, rkey, rmask)


def hash_repartition_counts(mesh: Mesh, data_axes, lkey, lmask, rkey, rmask,
                            capacity_factor: float = 2.0) -> jax.Array:
    """Hybrid-hash analogue: all-to-all repartition both sides by key hash so
    matching keys land on one shard, then local sort-merge count + psum.

    Static capacity per (src, dst) bucket with an overflow-drop counter
    (returned as part of a tuple in tests); capacity_factor=2 keeps drops at
    0 for uniform keys (Wisconsin)."""
    dp = _dp(data_axes)
    nsh = int(np.prod([mesh.shape[a] for a in data_axes]))

    def local(lk, lm, rk, rm):
        def repartition(k, m):
            n = k.shape[0]
            cap = int(np.ceil(n / nsh * capacity_factor))
            dest = (k.astype(jnp.uint32) % nsh).astype(jnp.int32)
            dest = jnp.where(m, dest, nsh)  # dead rows -> overflow bucket
            order = jnp.argsort(dest)
            ds = dest[order]
            ks = k[order]
            starts = jnp.searchsorted(ds, jnp.arange(nsh + 1), side="left")
            rank = jnp.arange(n) - starts[jnp.clip(ds, 0, nsh)]
            keep = (ds < nsh) & (rank < cap)
            slot = jnp.clip(ds, 0, nsh - 1) * cap + jnp.minimum(rank, cap - 1)
            slot = jnp.where(keep, slot, nsh * cap)  # trash slot for drops
            buf = jnp.zeros((nsh * cap + 1,), k.dtype).at[slot].set(ks)[:-1]
            bm = jnp.zeros((nsh * cap + 1,), jnp.bool_).at[slot].set(keep)[:-1]
            dropped = jnp.sum(m, dtype=jnp.int32) - jnp.sum(keep, dtype=jnp.int32)
            buf = buf.reshape(nsh, cap)
            bm = bm.reshape(nsh, cap)
            # all_to_all: axis 0 is the destination shard
            buf = jax.lax.all_to_all(buf, data_axes, split_axis=0, concat_axis=0,
                                     tiled=True)
            bm = jax.lax.all_to_all(bm, data_axes, split_axis=0, concat_axis=0,
                                    tiled=True)
            return buf.reshape(-1), bm.reshape(-1), dropped

        lbuf, lbm, ldrop = repartition(lk, lm)
        rbuf, rbm, rdrop = repartition(rk, rm)
        cnt = physical.join_count(lbuf, lbm, rbuf, rbm)
        total = jax.lax.psum(cnt.astype(jnp.int32), data_axes)
        drops = jax.lax.psum(ldrop + rdrop, data_axes)
        return total, drops

    return _smap(mesh, data_axes, local, (P(dp), P(dp), P(dp), P(dp)),
                 (P(), P()))(lkey, lmask, rkey, rmask)


# -- kernel-mode compositions -------------------------------------------------------
#
# The kernel execution mode runs the Pallas relational kernels shard-locally
# and merges partials with the same minimal collectives as the shard_map
# operators above: filter-count / group-agg psum their partial counts/sums,
# join-count gathers the (sorted) build side. (Kernel top-k reuses dist_topk
# with the block_topk selection primitive — no separate composition needed.)


def dist_kernel_filter_count(mesh: Mesh, data_axes, cols_mat: jax.Array,
                             bounds: jax.Array, backend=None,
                             block_ids=None, shard_blocks=None,
                             interpret=None) -> jax.Array:
    """cols_mat: (k, n) int32 predicate tile, row-sharded on axis 1; bounds:
    (k, 2) replicated runtime params. Each shard runs filter_count over its
    local tile (any padding rows arrive pre-folded as a mask row with bounds
    (1, 1)); merge is one 4-byte psum.

    ``block_ids`` are zone-block survivors over the GLOBAL row layout
    (single-shard meshes only, where local == global). ``shard_blocks`` is
    the multi-shard form: a host (n_shards, m) int32 matrix of per-shard
    LOCAL kernel-block ids, ``-1``-padded to the max surviving count
    (``ops.shard_block_arrays``). Row ``s`` rides to shard ``s`` through a
    ``P(dp, None)``-sharded operand, so every shard's scalar-prefetched
    grid scans only its own survivors — one compiled grid for all shards,
    pad steps are gated no-ops."""
    from repro.kernels import ops
    from repro.kernels.filter_count import BLOCK as _FC_BLOCK

    dp = _dp(data_axes)
    if block_ids is not None:
        nsh = int(np.prod([mesh.shape[a] for a in data_axes]))
        assert nsh == 1, "global block_ids require a single-shard mesh " \
                         "(use shard_blocks on multi-shard meshes)"
    if shard_blocks is not None:
        assert block_ids is None
        sb = np.asarray(shard_blocks, np.int32)
        nsh = int(np.prod([mesh.shape[a] for a in data_axes]))
        assert sb.shape[0] == nsh, (sb.shape, nsh)
        # true scanned/skipped accounting lives here, where the pad -1s are
        # visible — the per-shard grid length over-counts by the padding.
        nb_local = -(-(cols_mat.shape[1] // nsh) // _FC_BLOCK)
        scanned = int((sb >= 0).sum())
        ops.count_kernel("kernel.blocks_scanned_total", scanned,
                         kernel="filter_count")
        ops.count_kernel("kernel.blocks_skipped_total",
                         nsh * nb_local - scanned, kernel="filter_count")

        def local_arr(cm, b, ids):
            c = ops.filter_count(cm, b, cm.shape[1], backend=backend,
                                 block_ids_arr=ids.reshape(-1),
                                 interpret=interpret)
            return jax.lax.psum(c, data_axes)

        return _smap(mesh, data_axes, local_arr,
                     (P(None, dp), P(None, None), P(dp, None)), P())(
            cols_mat, bounds, jnp.asarray(sb))

    def local(cm, b):
        c = ops.filter_count(cm, b, cm.shape[1], backend=backend,
                             block_ids=block_ids, interpret=interpret)
        return jax.lax.psum(c, data_axes)

    return _smap(mesh, data_axes, local, (P(None, dp), P(None, None)), P())(
        cols_mat, bounds)


def dist_kernel_group_agg(mesh: Mesh, data_axes, gids: jax.Array,
                          values: jax.Array, num_groups: int, op: str = "sum",
                          backend=None, block_ids=None, shard_blocks=None,
                          interpret=None) -> jax.Array:
    """gids: (n,) int32 (-1 for dead rows); values: (n, C) f32. Shard-local
    one-hot segment reductions, minimal-collective merge (psum for sums,
    pmax/pmin for extremes) -> replicated (G, C). ``block_ids`` /
    ``shard_blocks`` as in :func:`dist_kernel_filter_count` (shard_blocks
    ids are in segment_agg's OWN kernel-block units)."""
    from repro.kernels import ops
    from repro.kernels.segment_agg import BLOCK as _SA_BLOCK

    dp = _dp(data_axes)
    merge = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin}[op]
    if block_ids is not None:
        nsh = int(np.prod([mesh.shape[a] for a in data_axes]))
        assert nsh == 1, "global block_ids require a single-shard mesh " \
                         "(use shard_blocks on multi-shard meshes)"
    if shard_blocks is not None:
        assert block_ids is None
        sb = np.asarray(shard_blocks, np.int32)
        nsh = int(np.prod([mesh.shape[a] for a in data_axes]))
        assert sb.shape[0] == nsh, (sb.shape, nsh)
        nb_local = -(-(gids.shape[0] // nsh) // _SA_BLOCK)
        scanned = int((sb >= 0).sum())
        ops.count_kernel("kernel.blocks_scanned_total", scanned,
                         kernel="segment_agg")
        ops.count_kernel("kernel.blocks_skipped_total",
                         nsh * nb_local - scanned, kernel="segment_agg")

        def local_arr(g, v, ids):
            out = ops.segment_agg(v, g, num_groups, v.shape[0], op=op,
                                  backend=backend,
                                  block_ids_arr=ids.reshape(-1),
                                  interpret=interpret)
            return merge(out, data_axes)

        return _smap(mesh, data_axes, local_arr,
                     (P(dp), P(dp, None), P(dp, None)), P(None, None))(
            gids, values, jnp.asarray(sb))

    def local(g, v):
        out = ops.segment_agg(v, g, num_groups, v.shape[0], op=op,
                              backend=backend, block_ids=block_ids,
                              interpret=interpret)
        return merge(out, data_axes)

    return _smap(mesh, data_axes, local, (P(dp), P(dp, None)), P(None, None))(
        gids, values)


def dist_kernel_join_count(mesh: Mesh, data_axes, lkey, lmask, rkey, rmask,
                           presorted_right: bool = False, backend=None) -> jax.Array:
    """Broadcast-merge join count on the merge_join kernel: sort the local
    probe shard, gather+merge the (sorted) build side, run the block merge
    join per shard, psum. With a sorted index the build side skips its local
    sort (``presorted_right``)."""
    from repro.kernels import ops

    dp = _dp(data_axes)

    def local(lk, lm, rk, rm):
        ls = ops.sort_join_keys(lk, lm)
        rs_local = ops.sort_join_keys(rk, rm, presorted=presorted_right)
        rs = jnp.sort(jax.lax.all_gather(rs_local, data_axes, tiled=True))
        nl = jnp.sum(lm, dtype=jnp.int32)
        nr = jax.lax.psum(jnp.sum(rm, dtype=jnp.int32), data_axes)
        c = ops.merge_join_count(ls, rs, nl, nr, backend=backend)
        return jax.lax.psum(c.astype(jnp.int32), data_axes)

    return _smap(mesh, data_axes, local, (P(dp), P(dp), P(dp), P(dp)), P())(
        lkey, lmask, rkey, rmask)


# -- index -------------------------------------------------------------------------


def dist_index_count(mesh: Mesh, data_axes, sorted_keys, valid, lo, hi):
    """Index-only range count: per-shard binary search + psum.

    ``valid``: the base table's validity column (per-shard num_valid is its
    local popcount — padding rows sort to the +inf tail of the index)."""
    from repro.engine.index import index_count_local

    dp = _dp(data_axes)

    def local(sk, v, lo_, hi_):
        nv = jnp.sum(v, dtype=jnp.int32)
        c = index_count_local(sk, nv, lo_ if lo is not None else None,
                              hi_ if hi is not None else None)
        return jax.lax.psum(c.astype(jnp.int32), data_axes)

    lo_a = jnp.asarray(lo if lo is not None else 0)
    hi_a = jnp.asarray(hi if hi is not None else 0)
    return _smap(mesh, data_axes, local, (P(dp), P(dp), P(), P()), P())(
        sorted_keys, valid, lo_a, hi_a)


def dist_shadow_count(mesh: Mesh, data_axes, sorted_keys, valid, anti_keys,
                      lo, hi):
    """Anti-matter subtrahend of the index-only count: the (replicated,
    pre-deduplicated) tombstone keys probe each shard's sorted primary
    index, per-shard occurrence counts psum — the same collective shape as
    :func:`dist_index_count`."""
    from repro.engine.index import shadow_count_local

    dp = _dp(data_axes)

    def local(sk, v, ak, lo_, hi_):
        nv = jnp.sum(v, dtype=jnp.int32)
        c = shadow_count_local(sk, nv, ak,
                               lo_ if lo is not None else None,
                               hi_ if hi is not None else None)
        return jax.lax.psum(c.astype(jnp.int32), data_axes)

    lo_a = jnp.asarray(lo if lo is not None else 0)
    hi_a = jnp.asarray(hi if hi is not None else 0)
    return _smap(mesh, data_axes, local,
                 (P(dp), P(dp), P(), P(), P()), P())(
        sorted_keys, valid, anti_keys, lo_a, hi_a)
