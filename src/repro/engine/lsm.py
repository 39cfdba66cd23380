"""LSM storage for streaming ingestion — AsterixDB's feed path on the
JAX/Pallas engine (paper §III-A).

AsterixDB feeds append to LSM components with online index maintenance; the
device-resident analogue here:

  * a **flush** turns the host buffer into a *run*: a block-padded (and
    mesh-sharded) columnar Table with its own sorted secondary indexes and
    zone maps, registered beside the base table. Flush cost is O(batch),
    never O(base).
  * **mutations** follow AsterixDB's anti-matter design (paper §III, live
    ingestion): a delete/upsert buffers an *anti-matter* record; the flushed
    run's table carries a per-row matter/anti-matter flag plus the primary
    key (anti rows are ``__valid__`` False, so every matter path ignores
    them), and a sorted anti-key array rides along for query-time visibility
    probes. An anti-matter record *annihilates* all matter with its key in
    strictly older components — newest component wins; an upsert is an
    anti-matter record plus fresh matter in the same run.
  * queries over a fed dataset execute as **base ∪ runs** (the ``UnionRuns``
    plan node): per-component index probes / kernel launches, one final
    merge — results are identical to querying the compacted dataset,
    including after upserts/deletes (the planner subtracts each component's
    contribution that newer anti-matter shadows).
  * **compaction** is deferred until a size-ratio policy fires, then folds
    every component into the base with a key-ordered newest-component-wins
    merge — annihilated matter and all tombstones are dropped (the only
    O(base) step, amortized over many flushes). The leveled policy variant
    instead merges same-level run groups into the next level, keeping every
    merge O(level), and full-compacts only on the size-ratio trigger.
  * **materialized views** (``Session.create_view``) are group-by aggregates
    maintained *incrementally*: each flush runs only the delta batch through
    the ``segment_agg`` path and merges partial aggregates — the paper's
    live-dashboard scenario. The f32 kernel path is gated by the same
    exactness reasoning the kernel execution mode uses; batches that cannot
    be proven exact fall back to native-dtype host reduction. Deletes and
    upserts feed *retraction* deltas: counts/sums take negative deltas; a
    retracted group max/min that touches the current extremum triggers an
    exact host recompute of the affected groups.
"""
from __future__ import annotations

import copy
import dataclasses
import threading
import time
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import plan as P
from repro.core.catalog import INTERNAL_COLUMNS, Dataset, Manifest, open_widen
from repro.engine.table import (ColumnMeta, Table, is_lane_column,
                                pad_to_block)
from repro.runtime import telemetry as tel
from repro.runtime.fault import StorageFault

RUN_BLOCK = 1024      # runs are padded to this row multiple
_F32_EXACT = 1 << 24  # every int in [-2^24, 2^24] is exactly representable


class ManifestConflict(RuntimeError):
    """A merge built off one manifest lost the CAS at publish time: a
    concurrent publish (flush or another merge) invalidated the component
    segment it planned against. The built components are discarded; the
    caller replans against the current manifest and retries."""


def _fault(session, point: str) -> None:
    """Consult the session's storage FaultPlan (runtime/fault.py) at one
    named crash point; raises StorageFault on a scheduled arrival."""
    plan = getattr(session, "fault_plan", None)
    if plan is not None:
        plan.check(point)


class _ManifestView:
    """A Dataset proxy bound to one captured manifest: ``runs`` is the
    pinned run list, every other attribute delegates to the base. Compaction
    policies plan against this view, so their decision and the CAS-validated
    merge both reference the same component set even while writers keep
    publishing."""

    def __init__(self, base: Dataset, manifest: Manifest):
        self._base = base
        self.runs = list(manifest.runs)

    def __getattr__(self, item):
        return getattr(self._base, item)


@dataclasses.dataclass(frozen=True)
class CompactionPolicy:
    """Deferred-compaction trigger (AsterixDB's size-ratio merge policy
    analogue): compact when the accumulated run burden — visible matter plus
    tombstones plus base rows the tombstones annihilated — reaches
    ``size_ratio`` × visible base rows, or when more than ``max_runs``
    components pile up. ``size_ratio=0`` degenerates to compact-every-flush
    (the benchmark baseline)."""

    size_ratio: float = 1.0
    max_runs: int = 8

    def plan(self, ds: Dataset) -> list[tuple]:
        """Compaction actions to run after a flush: ``("full",)`` merges
        every component into the base."""
        return [("full",)] if should_compact(ds, self) else []


@dataclasses.dataclass(frozen=True)
class LeveledCompactionPolicy(CompactionPolicy):
    """Leveled/tiered variant (the ROADMAP's planner-visible cost trade):
    flushes land in level 0; when a level accumulates ``fanin`` runs they
    merge into ONE run at the next level (an O(level) merge that drops
    annihilated matter early), so read amplification stays
    ~``levels × fanin`` instead of growing with every flush. The inherited
    size-ratio trigger still forces the full O(base) fold — with
    ``size_ratio=0`` the policy degenerates to compact-every-flush exactly
    like the tiered default."""

    level0_runs: int = 4    # runs tolerated at level 0 before a level merge
    level_ratio: int = 4    # fanin of every level above 0

    def fanin(self, level: int) -> int:
        return max(self.level0_runs if level == 0 else self.level_ratio, 2)

    def plan(self, ds: Dataset) -> list[tuple]:
        if should_compact(ds, self):
            return [("full",)]
        by_level: dict[int, list[int]] = {}
        for i, r in enumerate(ds.runs):
            by_level.setdefault(r.level, []).append(i)
        for level in sorted(by_level):
            idxs = by_level[level]
            if len(idxs) >= self.fanin(level):
                # same-level runs are contiguous by construction (levels are
                # non-increasing along the run list)
                return [("merge", idxs[0], idxs[-1] + 1, level + 1)]
        return []


def should_compact(ds: Dataset, policy: CompactionPolicy) -> bool:
    if not ds.runs:
        return False
    if len(ds.runs) > policy.max_runs:
        return True
    # Run burden discounts annihilated rows from the visible term but charges
    # the tombstones themselves and every component's shadowed matter (base
    # AND runs): all of it is storage a compaction would reclaim.
    burden = sum(r.num_live_rows + r.anti_rows + r.annihilated_rows
                 for r in ds.runs)
    burden += ds.annihilated_rows
    return burden >= policy.size_ratio * max(ds.num_live_rows, 1)


# -- runs -------------------------------------------------------------------


def make_run(session, base: Dataset, table: Table,
             anti_keys: Optional[np.ndarray] = None) -> Dataset:
    """Build one device-resident run from a flush batch: stats → (optional)
    open-widen → sort by the base's primary → append anti-matter rows →
    block-pad (+shard) → per-run sorted secondary indexes with zone maps.
    O(batch) throughout.

    ``anti_keys`` are the primary keys this run's anti-matter annihilates in
    older components. They materialize twice: as table rows flagged
    ``__antimatter__`` (``__valid__`` False — no matter path ever sees them)
    and as the sorted ``anti_keys_arr`` device array query-time visibility
    probes search. Column stats/zone spans are harvested from matter only."""
    from repro.engine.session import _collect_stats

    t0 = time.perf_counter()
    live = table.num_rows
    # `like` hint: a run's dict-lane presence follows the base table's, so
    # the column set stays uniform across every component in the union.
    table = _collect_stats(table, like=base.table.meta)
    if not base.closed:
        table = open_widen(table)
    primary = base.primary_index
    host_keys = None
    if primary is not None:
        order = np.argsort(np.asarray(table.columns[primary.column]),
                           kind="stable")
        cols = {k: np.asarray(v)[order] for k, v in table.columns.items()}
        meta = dict(table.meta)
        meta[primary.column] = dataclasses.replace(meta[primary.column],
                                                   sorted_ascending=True)
        table = Table(cols, meta, table.num_rows)
        host_keys = np.asarray(table.columns[primary.column])
    anti_sorted = None
    n_anti = 0 if anti_keys is None else len(anti_keys)
    if n_anti:
        key_col = primary.column
        kdt = np.asarray(table.columns[key_col]).dtype
        anti_sorted = np.sort(np.asarray(anti_keys).astype(kdt))
        table = _append_anti_rows(table, key_col, anti_sorted)
    table = pad_to_block(table, RUN_BLOCK)
    if session.mesh is not None:
        table = table.shard(session.mesh, session.data_axes)
    from repro.core.stats import harvest_block_zones, mesh_shards
    # stable component id: a per-dataset monotone uid, never reused — the
    # run keeps this address for life, compactions around it notwithstanding
    uid = session.catalog.next_run_uid(base.dataverse, base.name)
    run = Dataset(name=f"{base.name}@run{uid}", uid=uid,
                  dataverse=base.dataverse, table=table, closed=base.closed,
                  engine_owned=True,  # flush-built: safe to device-delete
                  live_rows=live, anti_rows=n_anti,
                  anti_keys_arr=None if anti_sorted is None
                  else jnp.asarray(anti_sorted),
                  host_anti_keys=anti_sorted,
                  host_keys=host_keys,
                  # intra-run zone maps, harvested in the same flush pass
                  # that builds the sorted indexes (matter rows only: anti
                  # rows and block padding never widen a span). Sharded
                  # sessions harvest the per-shard layout so block lists
                  # re-base to each row partition.
                  block_zones=harvest_block_zones(
                      table, mesh_shards(session.mesh, session.data_axes)))
    if primary is not None:
        run.indexes["primary"] = session._build_index(table, primary.column,
                                                      "primary")
    for ix in base.indexes.values():
        if ix.kind == "secondary":
            run.indexes[f"ix_{ix.column}"] = session._build_index(
                table, ix.column, "secondary")
    ds_label = f"{base.dataverse}.{base.name}"
    tel.inc("lsm.runs_built_total", dataset=ds_label)
    tel.observe("lsm.run_build_seconds", time.perf_counter() - t0,
                dataset=ds_label)
    tel.observe("lsm.run_build_rows", live, dataset=ds_label)
    return run


def _append_anti_rows(table: Table, key_col: str,
                      anti_sorted: np.ndarray) -> Table:
    """Anti-matter rows ride after the matter prefix: key column carries the
    annihilated key, every other column is zero, ``__antimatter__`` True and
    ``__valid__`` False (invisible to matter paths and index builds)."""
    m = table.num_rows
    t = len(anti_sorted)
    cols: dict[str, np.ndarray] = {}
    for k, v in table.columns.items():
        a = np.asarray(v)
        if k == key_col:
            pad = anti_sorted
        elif a.ndim == 2:
            pad = np.zeros((t, a.shape[1]), a.dtype)
        else:
            pad = np.zeros(t, a.dtype)
        cols[k] = np.concatenate([a, pad], axis=0)
    cols["__antimatter__"] = np.concatenate(
        [np.zeros(m, bool), np.ones(t, bool)])
    cols["__valid__"] = np.concatenate([np.ones(m, bool), np.zeros(t, bool)])
    meta = dict(table.meta)  # matter-only stats survive the append
    return Table(cols, meta, m + t)


def register_run(session, base: Dataset, run: Dataset) -> Optional[dict]:
    """Publish the run: one atomic manifest swap under the catalog lock
    (publish-then-retire — the swap bumps the LSN and statistics epoch, so
    every level of the Session plan cache, keyed on (epoch, LSN), rebinds
    and a cached executable for the old component set becomes unreachable).
    Snapshots pinned on the old manifest keep reading exactly the old
    component set.

    The publish happens FIRST, then the soft-state bookkeeping: when the
    run carries anti-matter, every older component's annihilation
    bookkeeping updates (O(tombstones · log component) host searches over
    the clustered key copies); when a materialized view is registered over
    the dataset, the newly annihilated rows are also gathered and returned
    for its retraction — without a view the gather is skipped entirely. A
    crash between publish and bookkeeping (the "post-swap" fault point)
    leaves the manifest committed and only soft state stale — recover()
    replays the bookkeeping from the hard rows."""
    cat = session.catalog
    if cat.store is not None:
        # persist the run's segment OFF the catalog lock (the heavy tensor
        # write); publish's durable-commit step below only links it. The
        # store's in-flight tracking protects it from GC until then.
        cat.store.write_component(base.dataverse, base.name, run)
    with cat.lock:
        # re-read the CURRENT manifest: the base the caller fetched may have
        # been swapped by a concurrent background compaction since
        cur = cat.manifest(base.dataverse, base.name)
        older = cur.components
        _fault(session, "pre-swap")
        cat.publish(base.dataverse, base.name, cur.base,
                    tuple(cur.runs) + (run,))
        _fault(session, "post-swap")
        retracted = None
        if run.anti_rows:
            gather = any((v.dataverse, v.dataset) == (base.dataverse, base.name)
                         for v in getattr(session, "views", {}).values())
            retracted = _annihilate_older(older, run, gather=gather)
    return retracted


def _annihilate_older(older, run: Dataset,
                      gather: bool = True) -> Optional[dict]:
    """Apply one new run's anti-key set to the strictly older components
    ``older``: count (and, with ``gather``, collect) the matter rows it
    newly shadows. A key a previous tombstone already covered is skipped —
    its matter was discounted then, so nothing double-subtracts. Callers
    hold the catalog lock: the bookkeeping sets this mutates are read (and
    copied) under the same lock by merges and stats."""
    anti_set = set(np.asarray(run.anti_keys_arr).tolist())
    gathered: list[dict[str, np.ndarray]] = []
    for comp in older:
        new = anti_set - comp.annihilated_keys
        if not new or comp.host_keys is None or not len(comp.host_keys):
            continue
        ak = np.sort(np.fromiter(new, dtype=comp.host_keys.dtype,
                                 count=len(new)))
        lo = np.searchsorted(comp.host_keys, ak, side="left")
        hi = np.searchsorted(comp.host_keys, ak, side="right")
        occ = hi - lo
        total = int(occ.sum())
        if not total:
            continue
        # record only keys that actually hit matter: a duplicate tombstone
        # for a miss re-probes later and finds 0 again (nothing can double-
        # discount), and the visibility masks stay proportional to rows
        # killed, not tombstones issued.
        comp.annihilated_keys |= set(ak[occ > 0].tolist())
        comp.annihilated_rows += total
        if not gather:
            continue
        # the matter prefix is clustered by the primary key, so index-space
        # positions ARE table row positions: gather the dying rows (device
        # gather of `total` rows) for view retraction.
        idx = np.concatenate([np.arange(l, h) for l, h in zip(lo, hi)
                              if h > l])
        gathered.append({k: np.asarray(v[jnp.asarray(idx)])
                         for k, v in comp.table.columns.items()
                         if k not in INTERNAL_COLUMNS
                         and not k.startswith("__ix")
                         and not is_lane_column(k)})
    if not gathered:
        return None
    names = list(gathered[0])
    return {k: np.concatenate([g[k] for g in gathered], axis=0)
            for k in names}


def host_visible_mask(comp: Dataset, key_col: Optional[str],
                      annihilated: Optional[set] = None) -> np.ndarray:
    """Host-side visibility of one component's physical rows: valid matter
    (anti rows and padding are ``__valid__`` False) minus rows newer
    components' anti-matter annihilated. ``annihilated`` overrides the
    component's live kill-set with a copy captured under the catalog lock —
    merges pass it so a concurrent flush mutating the live set mid-build
    cannot race the mask (the flushed tombstones are reconciled at swap
    time instead)."""
    mask = np.asarray(comp.table.valid).copy()
    anti = comp.table.columns.get("__antimatter__")
    if anti is not None:
        mask &= ~np.asarray(anti)
    kill_set = comp.annihilated_keys if annihilated is None else annihilated
    if kill_set and key_col is not None:
        keys = np.asarray(comp.table.columns[key_col])
        kill = np.fromiter(kill_set, dtype=keys.dtype, count=len(kill_set))
        mask &= ~np.isin(keys, kill)
    return mask


def _visible_columns(comp: Dataset, key_col: Optional[str],
                     annihilated: Optional[set] = None) -> dict[str, np.ndarray]:
    mask = host_visible_mask(comp, key_col, annihilated)
    # per-component dict lanes are dropped: merged/compacted outputs rebuild
    # coherent lanes through _collect_stats (the merge-on-compaction remap).
    return {k: np.asarray(v)[mask] for k, v in comp.table.columns.items()
            if k not in INTERNAL_COLUMNS and not is_lane_column(k)}


def _merge_meta(metas: list[ColumnMeta], total_rows: int) -> ColumnMeta:
    base = metas[0]
    lo = hi = distinct = None
    bounded = all(m.lo is not None and m.hi is not None for m in metas)
    if bounded:
        lo = min(m.lo for m in metas)
        hi = max(m.hi for m in metas)
    if all(m.distinct is not None for m in metas):
        # summing per-component distincts is only a TRUE distinct count when
        # the components cannot share values (pairwise-disjoint ranges) —
        # otherwise it saturates at the row count and would falsely certify
        # a duplicated key as unique to the materializing-join guard. With
        # possible overlap only max(component distinct) is provable.
        spans = sorted((m.lo, m.hi) for m in metas) if bounded else []
        disjoint = bool(spans) and all(
            spans[i][1] < spans[i + 1][0] for i in range(len(spans) - 1))
        if len(metas) == 1 or disjoint:
            distinct = min(sum(m.distinct for m in metas), total_rows)
        else:
            distinct = max(m.distinct for m in metas)
    return ColumnMeta(base.dtype, lo, hi, distinct, base.is_string, False)


def compact(session, ds: Dataset, manifest: Optional[Manifest] = None) -> Dataset:
    """Fold base ∪ runs into a fresh base with a key-ordered newest-
    component-wins merge: each component contributes only the matter no
    newer component's anti-matter annihilated (upserted rows survive once,
    deleted rows not at all), all tombstones drop — nothing older remains
    for them to annihilate — and the primary re-sort restores the clustered
    key order. One host merge, one re-shard, one index rebuild. Component
    stats merge so the catalog bounds stay truthful for the new key/value
    domains the runs introduced.

    Concurrency: the merge plans against ``manifest`` (default: the current
    one), builds the new base entirely OFF the catalog lock, and commits
    with a CAS-validated atomic swap — if a concurrent publish changed the
    base or reordered the merged segment, raises :class:`ManifestConflict`
    (nothing published; the caller replans and retries). Runs flushed while
    the merge was building survive the swap untouched and their anti keys
    are reconciled against the fresh base at swap time."""
    cat = session.catalog
    dv, name = ds.dataverse, ds.name
    ensure_soft(session, dv, name)  # kill-sets/host keys must be live
    t0 = time.perf_counter()
    tel.inc("lsm.compaction.attempts_total", kind="full")
    with cat.lock:
        m0 = manifest if manifest is not None else cat.manifest(dv, name)
        comps = m0.components
        # copy the kill-sets under the lock: a concurrent flush mutates the
        # live sets, and the swap-time reconciliation below covers exactly
        # the tombstones that land after this point
        kills = [set(c.annihilated_keys) for c in comps]
    key_col = m0.base.primary_index.column \
        if m0.base.primary_index is not None else None
    parts = [_visible_columns(c, key_col, kills[i])
             for i, c in enumerate(comps)]
    names = list(parts[0])
    merged = {k: np.concatenate([p[k] for p in parts], axis=0) for k in names}
    total = len(next(iter(merged.values()))) if names else 0
    metas = [c.table.meta for c in comps]
    meta = {k: _merge_meta([mm[k] for mm in metas], total) for k in names}
    secondary = [ix.column for ix in m0.base.indexes.values()
                 if ix.kind == "secondary"]
    _fault(session, "mid-merge")
    new_base = session._build_dataset(name, Table(merged, meta), dataverse=dv,
                                      closed=m0.base.closed,
                                      indexes=secondary, primary=key_col,
                                      stats_like=m0.base.table.meta)
    # compaction-built buffers are engine-exclusive (merged copies), unlike a
    # user-loaded base whose arrays may be shared with the caller's Table
    new_base.engine_owned = True
    if cat.store is not None:
        cat.store.write_component(dv, name, new_base)  # off-lock, pre-CAS
    try:
        with cat.lock:
            cur = cat.manifest(dv, name)
            if cur.base is not m0.base \
                    or tuple(cur.runs[:len(m0.runs)]) != tuple(m0.runs):
                tel.inc("lsm.compaction.conflicts_total", kind="full")
                raise ManifestConflict(
                    f"{dv}.{name}: component set changed under a full "
                    f"compaction (planned at lsn {m0.lsn}, now {cur.lsn})")
            newer = cur.runs[len(m0.runs):]  # flushed while the merge built
            _fault(session, "pre-swap")
            cat.publish(dv, name, new_base, newer)
            _fault(session, "post-swap")
            # reconcile: the surviving newer runs' tombstones still shadow
            # matter now living in the fresh base — replay their bookkeeping
            for r in newer:
                if r.anti_rows:
                    _annihilate_older((new_base,), r, gather=False)
    except ManifestConflict:
        if cat.store is not None:  # orphan segment: never committed
            cat.store.discard_component(dv, name, new_base)
        raise
    tel.inc("lsm.compactions_total", kind="full")
    tel.observe("lsm.compaction_seconds", time.perf_counter() - t0,
                kind="full")
    return new_base


def merge_runs(session, ds: Dataset, start: int, end: int, level: int,
               manifest: Optional[Manifest] = None) -> Dataset:
    """Leveled-compaction step: fold the contiguous run segment
    ``runs[start:end]`` of ``manifest`` (default: the current one) into ONE
    run at ``level`` — O(segment), never touching the base. Newest-wins
    inside the segment is already encoded in each member's annihilation
    bookkeeping (a member's matter shadowed by any newer component — inside
    or outside the segment — is dropped here), and the merged run keeps the
    union of member anti-key sets: older components still need them to
    subtract at query time.

    Concurrency mirrors :func:`compact`: build off-lock against kill-set
    copies, CAS-validate that the member segment is still intact (by
    component identity), publish one new manifest with the merged run in
    the segment's slot — its stable uid is fresh; surviving neighbours keep
    their addresses. Anti keys of runs flushed mid-build reconcile against
    the merged run at swap time."""
    cat = session.catalog
    dv, name = ds.dataverse, ds.name
    ensure_soft(session, dv, name)  # kill-sets/host keys must be live
    t0 = time.perf_counter()
    tel.inc("lsm.compaction.attempts_total", kind="level")
    with cat.lock:
        m0 = manifest if manifest is not None else cat.manifest(dv, name)
        members = tuple(m0.runs[start:end])
        kills = [set(m.annihilated_keys) for m in members]
    key_col = m0.base.primary_index.column \
        if m0.base.primary_index is not None else None
    parts = [_visible_columns(c, key_col, kills[i])
             for i, c in enumerate(members)]
    names = list(parts[0])
    merged_cols = {k: np.concatenate([p[k] for p in parts], axis=0)
                   for k in names}
    anti_parts = [np.asarray(m.anti_keys_arr) for m in members
                  if m.anti_rows]
    anti_union = np.unique(np.concatenate(anti_parts)) if anti_parts else None
    _fault(session, "mid-merge")
    run = make_run(session, m0.base, Table(merged_cols), anti_keys=anti_union)
    run.level = level
    if cat.store is not None:
        cat.store.write_component(dv, name, run)  # off-lock, pre-CAS
    try:
        with cat.lock:
            cur = cat.manifest(dv, name)
            if cur.base is not m0.base:
                tel.inc("lsm.compaction.conflicts_total", kind="level")
                raise ManifestConflict(
                    f"{dv}.{name}: base swapped under a level merge "
                    f"(planned at lsn {m0.lsn}, now {cur.lsn})")
            try:
                s = cur.runs.index(members[0])  # identity: Dataset eq is
                #                                 id-based
            except ValueError:
                s = -1
            if s < 0 or tuple(cur.runs[s:s + len(members)]) != members:
                tel.inc("lsm.compaction.conflicts_total", kind="level")
                raise ManifestConflict(
                    f"{dv}.{name}: merged run segment no longer contiguous "
                    f"(planned at lsn {m0.lsn}, now {cur.lsn})")
            tail = cur.runs[s + len(members):]
            # matter annihilated by newer-than-segment components known at
            # build time was dropped above; tombstones that landed mid-build
            # replay here (occurrence-counted, so stats stay truthful)
            for newer in tail:
                if newer.anti_rows:
                    _annihilate_older((run,), newer, gather=False)
            _fault(session, "pre-swap")
            cat.publish(dv, name, cur.base, cur.runs[:s] + (run,) + tail)
            _fault(session, "post-swap")
    except ManifestConflict:
        if cat.store is not None:  # orphan segment: never committed
            cat.store.discard_component(dv, name, run)
        raise
    tel.inc("lsm.compactions_total", kind="level")
    tel.observe("lsm.compaction_seconds", time.perf_counter() - t0,
                kind="level")
    return run


# -- background compaction ---------------------------------------------------


class BackgroundCompactor:
    """Runs the compaction policies (size-ratio, leveled, read-amplification
    — the same triggers the synchronous path uses) on a worker thread, off
    the ingest hot path. Writers call :meth:`notify` after each flush; the
    worker drains notified datasets to policy quiescence.

    Every merge builds fresh components entirely OFF the catalog lock and
    commits with one CAS-validated atomic manifest swap, so:

      * readers never block — a query's snapshot capture takes the lock for
        O(datasets) metadata only, and a running merge holds the lock only
        for the swap itself;
      * a concurrent flush that invalidates the planned segment raises
        :class:`ManifestConflict` — the worker replans against the current
        manifest and retries with exponential backoff, bounded by
        ``max_retries`` consecutive failures per dataset;
      * an injected :class:`~repro.runtime.fault.StorageFault` aborts the
        attempt identically: hard state is untouched (the swap never
        happened, or happened atomically), so the retry rebuilds from
        intact components.

    Any other exception is a bug: the worker gives up on that dataset,
    counts it in ``lsm.compactor.errors_total``, and the next caller of
    :meth:`wait_idle`, :meth:`wait_below` or :meth:`close` raises it.

    Writers needing backpressure (Feed's write stall) call
    :meth:`wait_below`, which sleeps on the worker's progress condition
    until the dataset's run count drops under the cap.

    The pending queue is sharded **per dataverse**: each dataverse gets its
    own worker thread (created lazily at first notify), so one tenant's
    long O(base) merge can never starve another tenant's compaction —
    multi-tenant isolation at the compaction layer. Workers share one
    condition variable; ``wait_idle``/``close`` span all of them."""

    def __init__(self, session, policy: Optional[CompactionPolicy] = None,
                 max_retries: int = 5, backoff_s: float = 0.002):
        self.session = session
        self.policy = policy if policy is not None else CompactionPolicy()
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.stats = {"level_merges": 0, "compactions": 0, "conflicts": 0,
                      "retries": 0, "faults": 0, "giveups": 0, "errors": 0}
        for k in self.stats:  # seed the mirrored registry series
            tel.inc(f"lsm.compactor.{k}_total", 0)
        self._cv = threading.Condition()
        # per-dataverse pending shards and their (lazily created) workers
        self._pending: dict[str, set[tuple[str, str]]] = {}
        self._inflight: dict[str, int] = {}
        self._threads: dict[str, threading.Thread] = {}
        self._stop = False
        # the first unexpected worker failure; re-raised to the next waiter
        self.error: Optional[BaseException] = None

    # -- control -----------------------------------------------------------

    def notify(self, dataverse: str, name: str) -> None:
        """Mark a dataset dirty (a flush just published); returns at once.
        The notification lands on the dataset's dataverse shard, spawning
        that shard's worker on first use."""
        with self._cv:
            if self._stop:
                return
            self._pending.setdefault(dataverse, set()).add((dataverse, name))
            if dataverse not in self._threads:
                t = threading.Thread(
                    target=self._worker, args=(dataverse,), daemon=True,
                    name=f"lsm-compactor-{dataverse}")
                self._threads[dataverse] = t
                t.start()
                tel.set_gauge("lsm.compactor.workers", len(self._threads))
            self._cv.notify_all()

    def wait_idle(self, timeout: float = 30.0) -> bool:
        """Block until every dataverse worker has drained its notifications
        (tests and benchmarks use this as a barrier). True if all went idle
        in time."""
        deadline = time.perf_counter() + timeout
        with self._cv:
            while any(self._pending.values()) or any(self._inflight.values()):
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    self._raise_error()
                    return False
                self._cv.wait(min(remaining, 0.05))
        self._raise_error()
        return True

    def wait_below(self, dataverse: str, name: str, cap: int,
                   timeout: float) -> float:
        """Write-stall backpressure: block until the dataset's run count
        drops below ``cap`` (or timeout). Returns seconds stalled."""
        t0 = time.perf_counter()
        with self._cv:
            while not self._stop:
                try:
                    n = len(self.session.catalog.manifest(dataverse, name).runs)
                except KeyError:
                    break
                if n < cap:
                    break
                remaining = timeout - (time.perf_counter() - t0)
                if remaining <= 0:
                    break
                self._cv.wait(min(remaining, 0.05))
        self._raise_error()
        return time.perf_counter() - t0

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
            threads = list(self._threads.values())
        for t in threads:
            t.join(timeout=30.0)
        self._raise_error()

    def _raise_error(self) -> None:
        """A worker that died of an unexpected exception stops compacting
        its dataverse; whoever waits on the compactor next sees why."""
        if self.error is not None:
            raise RuntimeError("background compaction failed") from self.error

    def __enter__(self) -> "BackgroundCompactor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- workers (one per dataverse) ---------------------------------------

    def _worker(self, dataverse: str) -> None:
        while True:
            with self._cv:
                while not self._pending.get(dataverse) and not self._stop:
                    self._cv.wait()
                if self._stop:
                    return
                key = self._pending[dataverse].pop()
                self._inflight[dataverse] = \
                    self._inflight.get(dataverse, 0) + 1
            try:
                self._drain(key)
            finally:
                with self._cv:
                    self._inflight[dataverse] -= 1
                    self._cv.notify_all()

    def _drain(self, key: tuple[str, str]) -> None:
        """Run the policy to quiescence for one dataset: each iteration
        replans against the CURRENT manifest (a lost CAS or injected fault
        backs off and replans; merges may cascade across levels)."""
        cat = self.session.catalog
        failures = 0
        delay = self.backoff_s
        while not self._stop:
            try:
                base = cat.get(*key)
            except KeyError:
                return  # dataset dropped
            m = base.manifest
            actions = self.policy.plan(_ManifestView(base, m))
            if not actions:
                return
            act = actions[0]
            try:
                if act[0] == "full":
                    compact(self.session, base, manifest=m)
                    self._bump("compactions")
                else:
                    _, s, e, level = act
                    merge_runs(self.session, base, s, e, level, manifest=m)
                    self._bump("level_merges")
                failures = 0
                delay = self.backoff_s
            except ManifestConflict:
                self._bump("conflicts")
                failures += 1
            except StorageFault:
                self._bump("faults")
                failures += 1
            except Exception as e:  # a bug, not a retryable condition
                self._bump("errors")
                self.error = self.error or e
                return
            finally:
                with self._cv:
                    self._cv.notify_all()  # progress signal for stalled writers
            if failures:
                if failures > self.max_retries:
                    self._bump("giveups")
                    return  # dataset stays serveable, just under-compacted
                self._bump("retries")
                time.sleep(delay)
                delay *= 2

    def _bump(self, key: str) -> None:
        """One compactor event: the local dict (the back-compat ``stats``
        surface tests read) and its registry mirror move together."""
        self.stats[key] += 1
        tel.inc(f"lsm.compactor.{key}_total")


# -- crash recovery: rebuild soft state from hard state -----------------------


def recover(session, dataverse: str, name: str, lazy: bool = False) -> None:
    """Crash recovery: rebuild every component's SOFT state from its HARD
    state — the split the fault-injection tests assert.

    Hard state (survives an injected crash at any fault point): each
    component's columnar table — matter rows, anti-matter rows with the
    ``__antimatter__`` flag and the key column, the ``__valid__`` mask —
    plus the manifest itself (swapped atomically: after a crash it is
    either the old or the new one, never half of each) and the index
    INVENTORY (which columns, which kinds).

    Soft state (rebuilt here): index payloads (sorted keys / row ids / zone
    arrays), block zone maps, host-side clustered-key and anti-key copies,
    the annihilation bookkeeping (replayed newest-wins in manifest order),
    and materialized-view partials (reseeded from visible rows).

    With ``lazy`` the rebuild is only MARKED: each component flips
    ``soft_stale`` and the dataset joins ``catalog.stale``; the first bind
    (query, point lookup, flush, compaction, view seed) pays the rebuild
    via :func:`ensure_soft`. Cold start over a large catalog is then
    dominated by manifest load + WAL replay, not index builds."""
    cat = session.catalog
    if lazy:
        with cat.lock:
            m = cat.manifest(dataverse, name)
            for comp in m.components:
                comp.soft_stale = True
            cat.stale.add((dataverse, name))
        return
    with cat.lock:
        m = cat.manifest(dataverse, name)
    for comp in m.components:
        _rebuild_soft(session, comp)
        comp.soft_stale = False
    with cat.lock:
        for i, run in enumerate(m.runs):
            if run.anti_rows:
                _annihilate_older((m.base,) + tuple(m.runs[:i]), run,
                                  gather=False)
        cat.stale.discard((dataverse, name))
        cat.bump_stats_epoch()
    session.reseed_views(dataverse, name)


def ensure_soft(session, dataverse: str, name: str) -> None:
    """First-bind hook of the lazy rebuild: if the dataset carries
    soft-stale components (cold-start mounts), rebuild their soft state now
    — indexes, zone maps, host key copies, anti arrays — and replay the
    annihilation bookkeeping newest-wins across the whole component chain.
    O(1) when nothing is stale (one set-membership probe), so every bind
    site calls it unconditionally."""
    cat = session.catalog
    if (dataverse, name) not in cat.stale:
        return
    with cat.lock:
        if (dataverse, name) not in cat.stale:
            return  # another binder won the race
        try:
            m = cat.manifest(dataverse, name)
        except KeyError:
            cat.stale.discard((dataverse, name))
            return
        t0 = time.perf_counter()
        for comp in m.components:
            if comp.soft_stale:
                _rebuild_soft(session, comp)
                comp.soft_stale = False
        # annihilation bookkeeping is cross-component: replay the full
        # chain in manifest order (idempotent for freshly-zeroed sets)
        for i, run in enumerate(m.runs):
            if run.anti_rows:
                _annihilate_older((m.base,) + tuple(m.runs[:i]), run,
                                  gather=False)
        cat.stale.discard((dataverse, name))
        cat.bump_stats_epoch()
    tel.inc("storage.lazy_rebuilds_total")
    tel.observe("storage.lazy_rebuild_seconds", time.perf_counter() - t0)


def _rebuild_soft(session, comp: Dataset) -> None:
    """Rebuild one component's soft state from its table columns: the same
    passes create_dataset/make_run run at build time, so the rebuilt state
    is bit-identical to the pre-crash state."""
    from repro.core.stats import harvest_block_zones, mesh_shards

    t = comp.table
    valid = np.asarray(t.valid)
    anti_col = t.columns.get("__antimatter__")
    anti_mask = np.asarray(anti_col) if anti_col is not None \
        else np.zeros(t.num_rows, bool)
    comp.live_rows = int(valid.sum())
    comp.annihilated_rows = 0
    comp.annihilated_keys = set()
    primary_col = None
    for ix in comp.indexes.values():
        if ix.kind == "primary":
            primary_col = ix.column
    comp.anti_rows = int(anti_mask.sum())
    if comp.anti_rows and primary_col is not None:
        anti_sorted = np.sort(np.asarray(t.columns[primary_col])[anti_mask])
        comp.anti_keys_arr = jnp.asarray(anti_sorted)
        comp.host_anti_keys = anti_sorted
    else:
        comp.anti_keys_arr = None
        comp.host_anti_keys = None
    if primary_col is not None:
        # matter prefix is clustered: masking preserves the sorted order
        comp.host_keys = np.asarray(t.columns[primary_col])[valid]
    comp.block_zones = harvest_block_zones(
        t, mesh_shards(session.mesh, session.data_axes))
    for key, ix in list(comp.indexes.items()):
        comp.indexes[key] = session._build_index(t, ix.column, ix.kind)


# -- incrementally-maintained materialized views ----------------------------

_VIEW_OPS = ("count", "sum", "mean", "max", "min")


class MaterializedView:
    """A continuously-maintained group-by aggregate over a fed dataset (the
    paper's live Twitter dashboard). State is dense per-group partials over a
    dynamically-widening key domain; each flush applies only the delta batch.
    ``result()`` matches a from-scratch group-by query bit-for-bit for
    integer columns (sums tracked in int64/float64, means divided in f32
    exactly like the query path)."""

    def __init__(self, name: str, dataverse: str, dataset: str, key: str,
                 aggs, predicate=None):
        for s in aggs:
            if s.op not in _VIEW_OPS:
                raise ValueError(f"view aggregate {s.op!r} not in {_VIEW_OPS}")
        self.name = name
        self.dataverse, self.dataset = dataverse, dataset
        self.key = key
        self.aggs = list(aggs)
        self.predicate = None
        if predicate is not None:
            self.predicate = copy.deepcopy(predicate)
            for lit in self.predicate.literals():
                lit.slot = None  # evaluate un-parameterized on delta batches
        self._sum_cols = []
        self._max_cols, self._min_cols = [], []
        for s in self.aggs:
            if s.op in ("sum", "mean") and s.column not in self._sum_cols:
                self._sum_cols.append(s.column)
            elif s.op == "max" and s.column not in self._max_cols:
                self._max_cols.append(s.column)
            elif s.op == "min" and s.column not in self._min_cols:
                self._min_cols.append(s.column)
        self.lo: Optional[int] = None
        self._counts: Optional[np.ndarray] = None
        self._sums: dict[str, np.ndarray] = {}
        self._maxs: dict[str, np.ndarray] = {}
        self._mins: dict[str, np.ndarray] = {}
        self._key_dtype = None
        self._dtypes: dict[str, np.dtype] = {}
        self.stats = {"refreshes": 0, "rows_applied": 0,
                      "kernel_batches": 0, "exact_fallback_batches": 0,
                      "retractions": 0, "rows_retracted": 0,
                      "extremum_recomputes": 0}

    @classmethod
    def from_plan(cls, name: str, plan: P.Plan) -> "MaterializedView":
        """Accepts GroupAgg(keys=[k], aggs) over Scan or Filter(Scan)."""
        if not isinstance(plan, P.GroupAgg) or len(plan.keys) != 1:
            raise ValueError(
                "create_view needs a single-key group-by aggregate "
                "(df.groupby(key).agg(...)-shaped plan)")
        child = plan.children[0]
        predicate = None
        if isinstance(child, P.Filter):
            predicate = child.predicate
            child = child.children[0]
        if not isinstance(child, P.Scan) or "@" in child.dataset:
            raise ValueError(
                "create_view supports GroupAgg over a (optionally filtered) "
                "dataset scan")
        return cls(name, child.dataverse, child.dataset, plan.keys[0],
                   list(plan.aggs), predicate)

    # -- state ------------------------------------------------------------

    def reset(self) -> None:
        """Drop the materialized partials (view state is SOFT state):
        recovery reseeds from the dataset's visible rows, exactly like
        create_view's initial seed."""
        self.lo = None
        self._counts = None
        self._sums, self._maxs, self._mins = {}, {}, {}
        self._key_dtype = None
        self._dtypes = {}

    def _ensure_domain(self, klo: int, khi: int) -> None:
        if self._counts is None:
            self.lo = klo
            g = khi - klo + 1
            self._counts = np.zeros(g, np.int64)
            self._sums = {c: np.zeros(g, np.float64) for c in self._sum_cols}
            self._maxs = {c: np.full(g, -np.inf) for c in self._max_cols}
            self._mins = {c: np.full(g, np.inf) for c in self._min_cols}
            return
        g = self._counts.shape[0]
        new_lo = min(self.lo, klo)
        new_hi = max(self.lo + g - 1, khi)
        if new_lo == self.lo and new_hi == self.lo + g - 1:
            return
        left, right = self.lo - new_lo, new_hi - (self.lo + g - 1)

        def grow(a, fill):
            return np.pad(a, (left, right), constant_values=fill)

        self._counts = grow(self._counts, 0)
        self._sums = {c: grow(a, 0.0) for c, a in self._sums.items()}
        self._maxs = {c: grow(a, -np.inf) for c, a in self._maxs.items()}
        self._mins = {c: grow(a, np.inf) for c, a in self._mins.items()}
        self.lo = new_lo

    def _delta_exact_for_kernel(self, n: int, cols: dict[str, np.ndarray],
                                live: np.ndarray) -> bool:
        """Same exactness reasoning as the kernel execution mode's group-agg
        gate, but against the *actual* delta batch: f32 partials are
        bit-exact when every per-group count/sum/extreme stays an integer
        below 2^24."""
        if n >= _F32_EXACT:
            return False
        for c in self._sum_cols + self._max_cols + self._min_cols:
            a = cols[c]
            if not np.issubdtype(a.dtype, np.integer):
                return False
            vals = a[live]
            maxabs = int(np.abs(vals).max()) if vals.size else 0
            bound = n * maxabs if c in self._sum_cols else maxabs
            if bound >= _F32_EXACT:
                return False
        return True

    def apply_delta(self, cols: dict[str, np.ndarray],
                    valid: Optional[np.ndarray] = None) -> None:
        n = len(next(iter(cols.values())))
        self.stats["refreshes"] += 1
        if n == 0:
            return
        live = np.ones(n, bool) if valid is None else np.asarray(valid, bool).copy()
        if self.predicate is not None:
            env = {k: jnp.asarray(v) for k, v in cols.items()}
            live &= np.asarray(self.predicate.evaluate(env, []), bool)
        if not live.any():
            return
        keys = np.asarray(cols[self.key])
        self._key_dtype = keys.dtype
        for c in self._sum_cols + self._max_cols + self._min_cols:
            self._dtypes[c] = np.asarray(cols[c]).dtype
        kl = keys[live]
        self._ensure_domain(int(kl.min()), int(kl.max()))
        g = self._counts.shape[0]
        gid = np.where(live, keys.astype(np.int64) - self.lo, -1).astype(np.int32)
        self.stats["rows_applied"] += int(live.sum())
        if self._delta_exact_for_kernel(n, cols, live):
            self._apply_kernel(cols, gid, g, n)
        else:
            self._apply_exact(cols, gid, live, g)

    def _apply_kernel(self, cols, gid, g, n) -> None:
        """Delta partials via the segment_agg kernel path (one fused sum
        launch + one launch per extreme family), merged into int64/float64
        state — the same launch shapes a flush-sized GroupAgg would run."""
        from repro.kernels import ops as kops

        self.stats["kernel_batches"] += 1
        gid_j = jnp.asarray(gid)
        tiles = [jnp.ones(n, jnp.float32)]
        tiles += [jnp.asarray(cols[c]).astype(jnp.float32) for c in self._sum_cols]
        part = np.asarray(kops.segment_agg(jnp.stack(tiles, axis=1), gid_j, g, n))
        self._counts += part[:, 0].astype(np.int64)
        for i, c in enumerate(self._sum_cols):
            self._sums[c] += part[:, 1 + i].astype(np.float64)
        if self._max_cols:
            vals = jnp.stack([jnp.asarray(cols[c]).astype(jnp.float32)
                              for c in self._max_cols], axis=1)
            part = np.asarray(kops.segment_agg(vals, gid_j, g, n, op="max"))
            for i, c in enumerate(self._max_cols):
                np.maximum(self._maxs[c], part[:, i].astype(np.float64),
                           out=self._maxs[c])
        if self._min_cols:
            vals = jnp.stack([jnp.asarray(cols[c]).astype(jnp.float32)
                              for c in self._min_cols], axis=1)
            part = np.asarray(kops.segment_agg(vals, gid_j, g, n, op="min"))
            for i, c in enumerate(self._min_cols):
                np.minimum(self._mins[c], part[:, i].astype(np.float64),
                           out=self._mins[c])

    def apply_retraction(self, cols: dict[str, np.ndarray],
                         recompute=None) -> None:
        """Retract rows previously applied (their OLD values — the matter a
        flush's anti-matter just annihilated). Counts and sums take exact
        negative deltas (int64/float64 state); means follow for free. A
        retracted group max/min is *not* subtractable: when a retracted
        value touches the stored extremum, ``recompute(op, column, keys)``
        — the exact host fallback the Session provides, scanning the
        dataset's current visible rows — repairs exactly the affected
        groups. Groups whose count hits zero reset to identity so future
        inserts re-aggregate from scratch."""
        n = len(next(iter(cols.values()))) if cols else 0
        if n == 0 or self._counts is None:
            return
        self.stats["retractions"] += 1
        live = np.ones(n, bool)
        if self.predicate is not None:
            env = {k: jnp.asarray(v) for k, v in cols.items()}
            live &= np.asarray(self.predicate.evaluate(env, []), bool)
        if not live.any():
            return
        keys = np.asarray(cols[self.key])
        kl = keys[live]
        self._ensure_domain(int(kl.min()), int(kl.max()))
        g = self._counts.shape[0]
        ix = (kl.astype(np.int64) - self.lo).astype(np.int64)
        self.stats["rows_retracted"] += int(live.sum())
        self._counts -= np.bincount(ix, minlength=g).astype(np.int64)
        for c in self._sum_cols:
            vals = np.asarray(cols[c])[live].astype(np.float64)
            self._sums[c] -= np.bincount(ix, weights=vals, minlength=g)
        emptied = self._counts <= 0
        for c, op, state in [(c, "max", self._maxs) for c in self._max_cols] \
                + [(c, "min", self._mins) for c in self._min_cols]:
            vals = np.asarray(cols[c])[live].astype(np.float64)
            # groups where a retracted value ties the stored extremum: the
            # extremum may have just left the group — recompute those exactly
            hit = np.zeros(g, bool)
            touched = vals >= state[c][ix] if op == "max" else vals <= state[c][ix]
            hit[ix[touched]] = True
            hit &= ~emptied  # empty groups just reset below
            if hit.any():
                if recompute is None:
                    raise ValueError(
                        f"view {self.name!r}: retraction touched a group "
                        f"{op} and no exact recompute fallback is available")
                self.stats["extremum_recomputes"] += 1
                group_keys = (self.lo + np.nonzero(hit)[0]).astype(np.int64)
                state[c][hit] = recompute(op, c, group_keys)
            state[c][emptied] = -np.inf if op == "max" else np.inf
        for c in self._sum_cols:
            self._sums[c][emptied] = 0.0
        self._counts[emptied] = 0

    def _apply_exact(self, cols, gid, live, g) -> None:
        """Native-dtype host fallback when f32 exactness cannot be proven
        (float columns, huge batches): bincount sums in float64 (exact to
        2^53) + ufunc.at extremes."""
        self.stats["exact_fallback_batches"] += 1
        ix = gid[live]
        self._counts += np.bincount(ix, minlength=g).astype(np.int64)
        for c in self._sum_cols:
            vals = np.asarray(cols[c])[live].astype(np.float64)
            self._sums[c] += np.bincount(ix, weights=vals, minlength=g)
        for c in self._max_cols:
            np.maximum.at(self._maxs[c], ix, np.asarray(cols[c])[live])
        for c in self._min_cols:
            np.minimum.at(self._mins[c], ix, np.asarray(cols[c])[live])

    def result(self) -> dict[str, np.ndarray]:
        """The materialized group table (groups with at least one row), in
        the same dtypes the equivalent group-by query returns."""
        if self._counts is None:
            return {self.key: np.array([], dtype=np.int64),
                    **{s.out_name: np.array([]) for s in self.aggs}}
        live = self._counts > 0
        g = self._counts.shape[0]
        out = {self.key: (self.lo + np.arange(g))[live].astype(self._key_dtype)}
        counts = self._counts[live]
        for s in self.aggs:
            if s.op == "count":
                out[s.out_name] = counts.astype(np.int32)
            elif s.op == "sum":
                out[s.out_name] = self._sums[s.column][live].astype(
                    self._dtypes[s.column])
            elif s.op == "mean":  # f32 sum / f32 count, as the query path
                out[s.out_name] = (self._sums[s.column][live].astype(np.float32)
                                   / counts.astype(np.float32))
            elif s.op == "max":
                out[s.out_name] = self._maxs[s.column][live].astype(
                    self._dtypes[s.column])
            else:
                out[s.out_name] = self._mins[s.column][live].astype(
                    self._dtypes[s.column])
        return out
