"""Session: the client's connection to the engine (the paper's AsterixDB
REST endpoint analogue). Owns the catalog, the mesh, the executable cache,
and the timing hooks the DataFrame benchmark reads (creation time vs
expression time, paper §IV-D).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections.abc import Mapping
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core import plan as P
from repro.core.catalog import (INTERNAL_COLUMNS, Catalog, Dataset, IndexInfo,
                                open_widen)
from repro.core.compiler import (CompiledQuery, ExecContext, compile_physical,
                                 compile_plan)
from repro.core.optimizer import optimize
from repro.core.physical_planner import build_pruner, plan_physical
from repro.engine.table import Table
from repro.runtime import telemetry as tel

from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as PS


# Monotone per-process session ids: the `sid` label that keeps each
# session's series separate inside the process-wide registry.
_SESSION_IDS = itertools.count()


class _StatsView(Mapping):
    """``Session.stats`` as a read-only view over the telemetry registry.

    Same keys and values as the old seeded dict (``dict(sess.stats)`` and
    ``sess.stats["hits"]`` behave identically), but the counters live in ONE
    place — the registry — instead of being double-booked. ``hits`` sums the
    variant- and executable-level plan-cache hits (the two sites the old
    counter incremented at); entry-level hits are a separate, new series.
    ``point_lookups`` is seeded like every other key — the old dict left it
    unseeded and read it with ``.get``."""

    _KEYS = ("compiles", "hits", "optimizes", "plans",
             "pruned_components", "point_lookups")

    def __init__(self, sid: str):
        self._sid = sid

    def _value(self, key: str):
        if key == "hits":
            return (tel.counter_value("session.plan_cache.hits_total",
                                      level="variant", sid=self._sid)
                    + tel.counter_value("session.plan_cache.hits_total",
                                        level="executable", sid=self._sid))
        return tel.counter_value(f"session.{key}_total", sid=self._sid)

    def __getitem__(self, key: str):
        if key not in self._KEYS:
            raise KeyError(key)
        return self._value(key)

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self) -> int:
        return len(self._KEYS)

    def __repr__(self) -> str:
        return repr({k: self._value(k) for k in self._KEYS})


class _TimingsView(Mapping):
    """``Session.timings`` as a read-only view over the registry's last-*
    gauges. Fixed key set — the old dict grew one ``create:<dv>.<name>``
    key per dataset forever; per-dataset timing now lives in the
    ``session.create_dataset_seconds`` histogram series instead."""

    _GAUGES = {
        "last_execute": "session.last_execute_seconds",
        "last_point_lookup": "session.last_point_lookup_seconds",
        "last_create": "session.last_create_seconds",
        "last_view_recompute": "session.last_view_recompute_seconds",
    }

    def __init__(self, sid: str):
        self._sid = sid

    def __getitem__(self, key: str):
        name = self._GAUGES.get(key)
        v = tel.gauge_value(name, sid=self._sid) if name else None
        if v is None:
            raise KeyError(key)
        return v

    def __iter__(self):
        for key, name in self._GAUGES.items():
            if tel.gauge_value(name, sid=self._sid) is not None:
                yield key

    def __len__(self) -> int:
        return sum(1 for _ in self)

    def __repr__(self) -> str:
        return repr({k: self[k] for k in self})


@dataclasses.dataclass
class _PlanEntry:
    """One raw-fingerprint plan-cache entry, valid for a single
    (statistics epoch, manifest LSN) pair — a publish bumps both, so a
    stale entry can never resolve a retired component. ``variants`` is the
    third cache level: prune signature → (executable, literal binding)."""

    epoch: int
    lsn: int
    opt: P.Plan                  # optimized logical plan
    opt_fp: str
    raw_lits0: list              # the entry-creation call's literals (binding anchors)
    pruner: "object"             # physical_planner.Pruner
    variants: dict = dataclasses.field(default_factory=dict)


class Session:
    def __init__(self, mesh: Optional[Mesh] = None, mode: str = "auto",
                 data_axes: tuple[str, ...] = ("data",),
                 enable_index: bool = True, enable_pushdown: bool = True,
                 enable_prune: bool = True, enable_block_skip: bool = True,
                 kernel_backend: Optional[str] = None,
                 kernel_interpret: Optional[bool] = None,
                 catalog: Optional[Catalog] = None,
                 fault_plan: Optional[object] = None,
                 storage: Optional[object] = None):
        """mode: 'auto' (shard_map when a mesh is given), 'gspmd',
        'shard_map', or 'kernel' (the cost-based planner lowers fusable plan
        shapes onto the Pallas relational kernels; anything uncovered falls
        back to the gspmd / shard_map lowering).

        ``enable_prune`` turns bind-time zone-map run pruning on/off (off is
        only useful for benchmarking the pruning win); ``enable_block_skip``
        does the same for the intra-component block level (the surviving
        blocks of a predicate-constrained scan). Block skipping is fully
        shard-aware: zone maps are harvested per mesh row partition, the
        bind-time survivor list is re-based into per-shard local lists, and
        each shard's kernel grid / gather scans only its own survivors.

        ``kernel_backend`` feeds the kernels/ops dispatch: 'pallas' forces
        the Pallas kernels (interpret mode off-TPU), 'xla' the jnp twins;
        None takes ``kernels.ops.default_backend()`` (pallas on a TPU).
        ``kernel_interpret`` overrides the Pallas interpret auto-detection
        (None = compiled on TPU, interpret elsewhere).

        ``catalog`` shares another session's catalog (concurrent serving:
        reader sessions bind snapshots of a writer session's datasets; each
        session keeps its own plan caches). ``fault_plan`` arms the storage
        fault points (runtime/fault.py FaultPlan) for crash-consistency
        tests.

        ``storage`` attaches a durable store (runtime/durable.py): a
        DurableStore instance or a path to open one at. Every manifest
        publish then gains a durable-commit step (checksummed component
        segments + an atomically-renamed manifest generation) and feeds
        write an fsynced WAL — see ``Session.open`` for cold-start
        recovery of such a directory."""
        self.catalog = catalog if catalog is not None else Catalog()
        self.fault_plan = fault_plan
        self.storage = None
        if storage is not None:
            from repro.engine import lsm
            from repro.runtime.durable import DurableStore

            store = storage if isinstance(storage, DurableStore) \
                else DurableStore(storage)
            # the store's crash points consult THIS session's FaultPlan —
            # one fault source for in-memory and I/O points alike
            store._fault = lambda point: lsm._fault(self, point)
            self.catalog.attach_store(store)
            self.storage = store
        self.recovery_report: Optional[dict] = None
        self.mesh = mesh
        if mode == "auto":
            mode = "shard_map" if mesh is not None and mesh.devices.size > 1 else "gspmd"
        if mode == "local":  # historical alias for the single-program lowering
            mode = "gspmd"
        if mode not in ("gspmd", "shard_map", "kernel"):
            raise ValueError(f"unknown mode {mode!r}: "
                             "expected auto | gspmd | shard_map | kernel")
        if kernel_backend not in (None, "xla", "pallas"):
            raise ValueError(f"unknown kernel_backend {kernel_backend!r}: "
                             "expected None | xla | pallas")
        self.mode = mode
        if kernel_backend is None and mode == "kernel":
            from repro.kernels import ops as kops
            kernel_backend = kops.default_backend()
        self.kernel_backend = kernel_backend
        self.kernel_interpret = kernel_interpret
        self.data_axes = data_axes
        self.enable_index = enable_index
        self.enable_pushdown = enable_pushdown
        self.enable_prune = enable_prune
        self.enable_block_skip = enable_block_skip
        # Three-level plan cache:
        #   1. raw (pre-optimization) fingerprint → _PlanEntry, valid for one
        #      stats epoch: repeated query shapes skip the optimizer and the
        #      pruner *build* entirely;
        #   2. per entry, (stats_epoch, prune signature) → (executable,
        #      literal binding): randomized literals that keep the same
        #      surviving-run set rebind into the cached executable; literals
        #      that change which runs the zone maps prune rebuild only the
        #      physical plan (the optimizer output is reused);
        #   3. (physical fingerprint, epoch) → executable dedup across
        #      logical shapes (a point == and a range >=/<= predicate still
        #      share one compiled program, exactly like the old two-level
        #      cache).
        # Epoch keying is the invalidation mechanism: any flush / compaction
        # / DDL bumps catalog.stats_epoch, so a stale executable (which bakes
        # in shapes, access paths, and the LSM component set) can never run
        # against a changed catalog — a dropped run is unreachable.
        self._plans: dict[str, _PlanEntry] = {}
        self._compiled: dict[tuple, CompiledQuery] = {}
        # stats/timings are back-compat VIEWS over the registry, keyed by
        # this session's `sid` label. Counters are seeded here so every
        # series exists (and reads 0) before the first query.
        self.sid = str(next(_SESSION_IDS))
        for key in _StatsView._KEYS:
            if key == "hits":
                for level in ("entry", "variant", "executable"):
                    tel.inc("session.plan_cache.hits_total", 0,
                            level=level, sid=self.sid)
            else:
                tel.inc(f"session.{key}_total", 0, sid=self.sid)
        self.stats = _StatsView(self.sid)
        self.timings = _TimingsView(self.sid)
        # incrementally-maintained materialized views (engine/lsm.py),
        # refreshed from each feed flush's delta batch.
        self.views: dict[str, "object"] = {}

    # -- durable cold start --------------------------------------------------

    @classmethod
    def open(cls, path, lazy: bool = True, **kwargs) -> "Session":
        """Cold-start crash recovery: open a durable storage directory
        (``Session(storage=...).``'s on-disk layout) and reconstruct the
        catalog —

          1. load each dataset's newest checksum-valid manifest generation
             (a corrupt manifest or segment is quarantined and the previous
             generation serves instead — ``storage.corruption_total``);
          2. mount the component segments back onto the session's mesh and
             republish them (the catalog LSN resumes past the recovered
             high-water mark, run uids past the highest mounted uid);
          3. mark soft state for lazy rebuild-at-first-bind (``lazy=False``
             rebuilds indexes/zone maps eagerly, PR 6's ``recover``);
          4. replay the WAL tail — acked batches whose covering flush never
             committed — through the normal flush path, in order, skipping
             batches at or below the manifest's ``wal_upto`` (idempotence
             when the crash hit between commit and truncate).

        Returns the session with ``recovery_report`` populated. Raises
        ``StorageLockError`` if a live process holds the directory."""
        from repro.engine import ingest, lsm
        from repro.runtime.durable import DurableStore

        t0 = time.perf_counter()
        store = path if isinstance(path, DurableStore) else DurableStore(path)
        corrupt0 = tel.counter_value("storage.corruption_total") or 0
        sess = cls(storage=store, **kwargs)
        cat = sess.catalog
        report: dict = {"datasets": {}, "seconds": 0.0,
                        "corruption_events": 0, "wal_replayed_batches": 0}
        try:
            loads = []
            for dv, name in store.list_datasets():
                loads.append((dv, name) + store.load_dataset(dv, name))
            # restore the LSN high-water mark BEFORE any publish, so every
            # mounted generation commits with a strictly newer LSN than
            # anything already on disk
            with cat.lock:
                for dv, name, record, _, _ in loads:
                    cat.lsn = max(cat.lsn, int(record["lsn"]))
            for dv, name, record, segments, ds_report in loads:
                base = _mount_component(
                    sess, dv, record["base"]["seg"],
                    *segments[record["base"]["seg"]])
                runs = tuple(
                    _mount_component(sess, dv, r["seg"], *segments[r["seg"]])
                    for r in record["runs"])
                with cat.lock:
                    key = (dv, name)
                    max_uid = max((r.uid for r in runs), default=-1)
                    cat._run_uids[key] = max(cat._run_uids.get(key, 0),
                                             max_uid + 1)
                    cat.publish(dv, name, base, runs)
                lsm.recover(sess, dv, name, lazy=lazy)
                tail = store.wal_tail(dv, name)
                replayed = 0
                if tail:
                    # the replay feed IS the normal ingest path: validate,
                    # buffer, flush, publish — only WAL re-appends are off
                    lsm.ensure_soft(sess, dv, name)
                    feed = ingest.Feed(
                        sess, name, dv, flush_rows=1 << 62,
                        policy=lsm.CompactionPolicy(
                            size_ratio=float("inf"), max_runs=1 << 30))
                    feed._replay = True
                    for seq, kind, payload in tail:
                        lsm._fault(sess, "mid-replay")
                        if kind == "push":
                            feed.push(payload)
                        elif kind == "upsert":
                            feed.upsert(payload)
                        else:
                            feed.delete(payload["__keys__"])
                        replayed += 1
                    feed.flush()
                    tel.inc("storage.wal_replayed_batches_total", replayed)
                report["wal_replayed_batches"] += replayed
                report["datasets"][f"{dv}.{name}"] = {
                    "lsn": int(record["lsn"]),
                    "components": 1 + len(runs),
                    "wal_replayed_batches": replayed,
                    "manifest_fallbacks": ds_report["fallbacks"],
                    "quarantined": ds_report["quarantined"],
                }
        except BaseException:
            store.close()
            raise
        report["seconds"] = time.perf_counter() - t0
        report["corruption_events"] = int(
            (tel.counter_value("storage.corruption_total") or 0) - corrupt0)
        tel.observe("storage.recovery_seconds", report["seconds"])
        sess.recovery_report = report
        return sess

    def close(self) -> None:
        """Release the durable store (directory lock + WAL handles). A
        memory-only session is a no-op. Crash tests call this to simulate
        process death before reopening the same directory."""
        if self.storage is not None:
            self.storage.close()

    def _ensure_bound(self, plan: P.Plan) -> None:
        """Lazy-rebuild hook on the query path: before binding, rebuild the
        soft state of any scanned dataset still stale from a cold-start
        mount. O(1) when the catalog has no stale datasets — the common
        case costs one set check."""
        if not self.catalog.stale:
            return
        from repro.engine import lsm

        for node in P.walk(plan):
            if isinstance(node, P.Scan):
                lsm.ensure_soft(self, node.dataverse,
                                node.dataset.partition("@")[0])

    # -- DDL ----------------------------------------------------------------

    def create_dataset(self, name: str, table: Table, dataverse: str = "Default",
                       closed: bool = True, indexes: Sequence[str] = (),
                       primary: Optional[str] = None) -> Dataset:
        """Register (and shard) a dataset; optionally build indexes.

        ``primary`` sorts the stored table by that column (clustered);
        ``indexes`` build secondary sorted indexes per shard."""
        t0 = time.perf_counter()
        with tel.span("session.create_dataset", sid=self.sid,
                      dataset=f"{dataverse}.{name}"):
            ds = self._build_dataset(name, table, dataverse=dataverse,
                                     closed=closed, indexes=indexes,
                                     primary=primary)
            self.catalog.register(ds)
            self._invalidate_plans()
        tel.set_gauge("session.last_create_seconds",
                      time.perf_counter() - t0, sid=self.sid)
        return ds

    def _build_dataset(self, name: str, table: Table, dataverse: str = "Default",
                       closed: bool = True, indexes: Sequence[str] = (),
                       primary: Optional[str] = None,
                       stats_like: Optional[Mapping] = None) -> Dataset:
        """Build (stats → widen → cluster → shard → index) WITHOUT touching
        the catalog: background compaction builds replacement bases off the
        hot path and publishes them separately with one atomic manifest
        swap. ``stats_like`` (compaction: the retiring base's meta) keeps
        the string dict-lane decision sticky so runs flushed mid-merge stay
        column-uniform with the replacement base."""
        table = _collect_stats(table, like=stats_like)  # DBMS-style stats on load
        if not closed:
            table = open_widen(table)
        host_keys = None
        if primary is not None:
            order = np.argsort(np.asarray(table.columns[primary]), kind="stable")
            cols = {k: np.asarray(v)[order] for k, v in table.columns.items()}
            meta = dict(table.meta)
            meta[primary] = dataclasses.replace(meta[primary],
                                                sorted_ascending=True)
            table = Table(cols, meta, table.num_rows)
            # host copy of the clustered key order: anti-matter annihilation
            # bookkeeping (engine/lsm.py) binary-searches it at flush time
            host_keys = np.asarray(table.columns[primary])
        if self.mesh is not None:
            table = table.shard(self.mesh, self.data_axes)
        from repro.core.stats import harvest_block_zones
        ds = Dataset(name=name, dataverse=dataverse, table=table, closed=closed,
                     host_keys=host_keys,
                     # per-shard zone layout: sharded meshes get block lists
                     # local to each row partition (stats.BlockZones)
                     block_zones=harvest_block_zones(table, self.n_shards))
        if primary is not None:
            ds.indexes["primary"] = self._build_index(table, primary, "primary")
        for col in indexes:
            ds.indexes[f"ix_{col}"] = self._build_index(table, col, "secondary")
        return ds

    def _invalidate_plans(self) -> None:
        """Free cached plans eagerly. Correctness never depends on this call:
        every cache level is keyed by ``catalog.stats_epoch`` (bumped on DDL,
        feed flush, and compaction), so stale entries are unreachable — this
        just reclaims the memory."""
        self._plans.clear()
        self._compiled.clear()

    def _build_index(self, table: Table, column: str, kind: str) -> IndexInfo:
        sk, rid, zmin, zmax = _index_builder(self.mesh, self.data_axes)(
            table.columns[column], table.valid)
        return IndexInfo(name=f"{kind}:{column}", column=column, kind=kind,
                         sorted_keys=sk, row_ids=rid,
                         zone_min=zmin, zone_max=zmax)

    # -- materialized views (continuous queries over fed datasets) ----------

    def create_view(self, name: str, frame_or_plan) -> "object":
        """Register a continuously-maintained group-by aggregate (the
        paper's live-dashboard scenario): ``frame_or_plan`` is an AFrame (or
        its plan) of shape ``groupby(key).agg(...)`` over a — optionally
        filtered — dataset scan. The view is seeded from the dataset's
        current contents (base ∪ runs) and from then on refreshed
        *incrementally* from each feed flush's delta batch."""
        from repro.engine.lsm import MaterializedView

        plan = getattr(frame_or_plan, "_plan", frame_or_plan)
        view = MaterializedView.from_plan(name, plan)
        from repro.engine import lsm
        lsm.ensure_soft(self, view.dataverse, view.dataset)
        with self.catalog.snapshot() as snap:
            self._seed_view(view, snap.components(view.dataverse,
                                                  view.dataset))
        self.views[name] = view
        return view

    def _seed_view(self, view, comps) -> None:
        """Seed (or reseed) one view from a pinned component tuple."""
        from repro.engine.lsm import host_visible_mask
        from repro.engine.table import is_lane_column

        base = comps[0]
        key_col = base.primary_index.column \
            if base.primary_index is not None else None
        for comp in comps:
            cols = {k: np.asarray(v) for k, v in comp.table.columns.items()
                    if k not in INTERNAL_COLUMNS and not is_lane_column(k)}
            # seed from VISIBLE rows only: anti rows are __valid__ False, and
            # matter newer components already annihilated must not count
            view.apply_delta(cols, host_visible_mask(comp, key_col))

    def reseed_views(self, dataverse: str, dataset: str) -> None:
        """Rebuild every view over the dataset from scratch (crash recovery:
        view partials are soft state — lsm.recover calls this after the
        component-level rebuild)."""
        targets = [v for v in self.views.values()
                   if (v.dataverse, v.dataset) == (dataverse, dataset)]
        if not targets:
            return
        with self.catalog.snapshot() as snap:
            comps = snap.components(dataverse, dataset)
            for view in targets:
                view.reset()
                self._seed_view(view, comps)

    def read_view(self, name: str) -> dict:
        """The materialized result — no query execution, dashboard-latency."""
        return self.views[name].result()

    def drop_view(self, name: str) -> None:
        self.views.pop(name, None)

    def refresh_views(self, dataverse: str, dataset: str,
                      delta_cols: dict, retracted: Optional[dict] = None) -> None:
        """Apply one flushed delta batch to every view over the dataset
        (called by Feed.flush). ``retracted`` carries the OLD rows this
        flush's anti-matter annihilated: counts/sums take exact negative
        deltas; a retracted group extremum falls back to the exact host
        recompute over the dataset's current visible rows."""
        for view in self.views.values():
            if (view.dataverse, view.dataset) == (dataverse, dataset):
                view.apply_delta(delta_cols)
                if retracted is not None:
                    view.apply_retraction(retracted,
                                          recompute=self._view_recompute(view))

    def _view_recompute(self, view):
        """The exact extremum-repair fallback: host-scan the dataset's
        visible rows (base ∪ runs, newest-wins masks applied) and recompute
        ``op(column)`` for exactly the affected groups. O(dataset) — but it
        runs only when a retraction removed a group's current max/min, the
        one delta that is fundamentally not incremental."""
        from repro.engine.lsm import host_visible_mask

        def recompute(op: str, column: str, group_keys: np.ndarray) -> np.ndarray:
            import jax.numpy as jnp

            t0 = time.perf_counter()
            tel.inc("session.view_recomputes_total", sid=self.sid,
                    view=getattr(view, "name", "?"))
            with self.catalog.snapshot() as snap:
                comps = snap.components(view.dataverse, view.dataset)
                ds = comps[0]
                key_col = ds.primary_index.column \
                    if ds.primary_index is not None else None
                keys_parts, vals_parts = [], []
                for comp in comps:
                    mask = host_visible_mask(comp, key_col)
                    if view.predicate is not None:
                        env = {k: jnp.asarray(v)
                               for k, v in comp.table.columns.items()}
                        mask &= np.asarray(view.predicate.evaluate(env, []),
                                           bool)
                    keys_parts.append(
                        np.asarray(comp.table.columns[view.key])[mask])
                    vals_parts.append(
                        np.asarray(comp.table.columns[column])[mask])
            keys = np.concatenate(keys_parts)
            vals = np.concatenate(vals_parts).astype(np.float64)
            # one sort, then a binary-searched slice per affected group —
            # total work O(n log n + matching rows), not O(groups × n)
            order = np.argsort(keys, kind="stable")
            ks, vs = keys[order], vals[order]
            lo = np.searchsorted(ks, group_keys, side="left")
            hi = np.searchsorted(ks, group_keys, side="right")
            identity = -np.inf if op == "max" else np.inf
            out = np.full(len(group_keys), identity, np.float64)
            for i, (l, h) in enumerate(zip(lo, hi)):
                if h > l:
                    sel = vs[l:h]
                    out[i] = sel.max() if op == "max" else sel.min()
            dt = time.perf_counter() - t0
            tel.observe("session.view_recompute_seconds", dt, sid=self.sid)
            tel.set_gauge("session.last_view_recompute_seconds", dt,
                          sid=self.sid)
            return out

        return recompute

    # -- point lookups (the one path that bypasses compilation) -------------

    def point_lookup(self, dataverse: str, dataset: str, key):
        """Primary-key point lookup: per-component host binary searches over
        the clustered key copies, walked newest → oldest — the first
        component owning the key decides (fresh matter wins, a tombstone
        kills every older occurrence; an upsert run carries both, and its
        matter is checked first because its anti set applies to strictly
        older components only). No kernel launch, no compile, no plan-cache
        traffic: O(components × log rows).

        Returns the matching row(s) as ``{column: np.ndarray}`` or None
        (absent or deleted). ``last_physical`` / ``last_prune_report``
        reflect the lookup so ``explain``-style readers see a PointLookup
        node."""
        from repro.core import physical as PH
        from repro.core.catalog import INTERNAL_COLUMNS
        from repro.engine import lsm

        lsm.ensure_soft(self, dataverse, dataset)
        t0 = time.perf_counter()
        with self.catalog.snapshot() as snap:
            comps = list(snap.components(dataverse, dataset))
        ds = comps[0]
        primary = ds.primary_index
        if primary is None:
            raise ValueError(
                f"point lookup needs a primary key on {dataverse}.{dataset} "
                "(create the dataset with primary=<column>)")
        probed = skipped = 0
        shards = 1
        shard_probes = 0
        found_in = tombstoned_by = None
        result = None
        for comp in reversed(comps):  # newest component wins
            hk = comp.host_keys
            if hk is not None and len(hk):
                # zone short-circuit: the clustered copy is sorted, so its
                # ends ARE the key span — a miss costs two comparisons.
                if key < hk[0] or key > hk[-1]:
                    skipped += 1
                else:
                    # shard routing: the per-shard key zone spans identify
                    # the owning row partition(s); only their slice of the
                    # clustered copy is searched (host-side — no gather of
                    # the other shards' key ranges).
                    wlo, whi, owners, comp_shards = _route_key(
                        comp, primary.column, key, len(hk))
                    shards = max(shards, comp_shards)
                    if owners == 0:
                        skipped += 1  # key falls between the shard spans
                        continue
                    probed += 1
                    shard_probes += owners
                    lo = wlo + int(np.searchsorted(hk[wlo:whi], key,
                                                   side="left"))
                    hi = wlo + int(np.searchsorted(hk[wlo:whi], key,
                                                   side="right"))
                    if hi > lo:
                        # matter prefix is clustered by the primary key:
                        # index-space positions are table row positions
                        from repro.engine.table import is_lane_column
                        result = {
                            c: np.asarray(v[lo:hi])
                            for c, v in comp.table.columns.items()
                            if c not in INTERNAL_COLUMNS
                            and not c.startswith("__ix")
                            and not is_lane_column(c)}
                        found_in = f"{comp.dataverse}.{comp.name}"
                        break
            if comp.anti_rows:
                ak = comp.host_anti_keys if comp.host_anti_keys is not None \
                    else np.asarray(comp.anti_keys_arr)
                pos = int(np.searchsorted(ak, key))
                if pos < len(ak) and ak[pos] == key:
                    tombstoned_by = f"{comp.dataverse}.{comp.name}"
                    break  # deleted: nothing older is visible
        node = PH.PointLookup(dataverse, dataset, primary.column,
                              components=len(comps), probed=probed,
                              skipped=skipped, found_in=found_in,
                              tombstoned_by=tombstoned_by,
                              shards=shards, shard_probes=shard_probes)
        node.est_rows = 0 if result is None else len(next(iter(result.values())))
        node.cost = probed * 2.0  # binary-search pairs; never a scan
        if tombstoned_by is not None:
            node.note = (f"key is anti-matter in {tombstoned_by} — deleted, "
                         f"older occurrences invisible")
        elif found_in is not None:
            node.note = f"resolved in {found_in} (newest component with the key)"
        else:
            node.note = "key absent from every component span"
        self.last_physical = node
        from repro.core.physical import prune_report
        self.last_prune_report = prune_report(node)
        dt = time.perf_counter() - t0
        tel.inc("session.point_lookups_total", sid=self.sid)
        tel.observe("session.point_lookup_seconds", dt, sid=self.sid)
        tel.set_gauge("session.last_point_lookup_seconds", dt, sid=self.sid)
        return result

    def explain_lookup(self, dataverse: str, dataset: str, key) -> str:
        """The PointLookup plan for ``get(key)``, rendered like explain()."""
        from repro.core.physical import format_plan

        self.point_lookup(dataverse, dataset, key)
        return format_plan(self.last_physical)

    # -- query execution -------------------------------------------------------

    def exec_context(self, catalog=None) -> ExecContext:
        """``catalog`` is any catalog-read-surface object — execution passes
        the query's pinned Snapshot so compile-time component reads (shadow
        probe constants, leaf tables) bind against the snapshot, not the
        moving catalog."""
        return ExecContext(catalog=catalog if catalog is not None
                           else self.catalog, mesh=self.mesh,
                           data_axes=self.data_axes, mode=self.mode,
                           kernel_backend=self.kernel_backend,
                           kernel_interpret=self.kernel_interpret)

    @property
    def n_shards(self) -> int:
        """Row-partition count of this session's mesh (1 when meshless) —
        the layout zone maps are harvested over and block lists re-base to."""
        from repro.core.stats import mesh_shards

        return mesh_shards(self.mesh, self.data_axes)

    def _block_skip(self) -> bool:
        """Block skipping works on any mesh: surviving-block lists are
        expressed per shard (stats.BlockZones shard layout), so per-shard
        kernel grids and gathers consume their own local lists."""
        return self.enable_block_skip

    def _optimize(self, plan: P.Plan, catalog) -> P.Plan:
        tel.inc("session.optimizes_total", sid=self.sid)
        with tel.span("session.optimize", sid=self.sid):
            return optimize(plan, catalog,
                            enable_pushdown=self.enable_pushdown)

    def _plan_entry(self, plan: P.Plan, raw_fp: str, raw_lits: list,
                    snap) -> _PlanEntry:
        """Level 1: optimized plan + pruner per (raw fingerprint, epoch,
        LSN) — optimization, pruner construction, and stats all bind the
        pinned snapshot."""
        e = self._plans.get(raw_fp)
        if e is not None and (e.epoch, e.lsn) == (snap.stats_epoch, snap.lsn):
            tel.inc("session.plan_cache.hits_total", level="entry",
                    sid=self.sid)
            return e
        tel.inc("session.plan_cache.misses_total", level="entry",
                sid=self.sid)
        if e is not None:  # stale epoch/LSN: sweep dead executables with it
            self._compiled = {k: v for k, v in self._compiled.items()
                              if k[1:] == (snap.stats_epoch, snap.lsn)}
        opt = self._optimize(plan, snap)
        with tel.span("session.prune_build", sid=self.sid):
            pruner = build_pruner(opt, snap, raw_lits,
                                  n_shards=self.n_shards)
        e = _PlanEntry(snap.stats_epoch, snap.lsn, opt, opt.fingerprint(),
                       list(raw_lits), pruner)
        self._plans[raw_fp] = e
        return e

    def _variant(self, e: _PlanEntry, raw_lits: list, snap):
        """Levels 2+3: prune signature → (executable, binding); executables
        dedup'd across logical shapes by physical fingerprint, keyed on the
        snapshot's (epoch, LSN) so a stale executable can never read a
        retired component."""
        from repro.core.expr import ordered_lits
        from repro.core.physical_planner import NO_PRUNE

        with tel.span("session.prune", sid=self.sid):
            decisions = e.pruner.decide([l.value for l in raw_lits],
                                        block_skip=self._block_skip()) \
                if self.enable_prune else NO_PRUNE
        var = e.variants.get(decisions.signature)
        if var is not None:
            tel.inc("session.plan_cache.hits_total", level="variant",
                    sid=self.sid)
            return var
        tel.inc("session.plan_cache.misses_total", level="variant",
                sid=self.sid)
        with tel.span("session.plan", sid=self.sid):
            phys = plan_physical(e.opt, snap, mode=self.mode,
                                 decisions=decisions,
                                 enable_index=self.enable_index)
        tel.inc("session.plans_total", sid=self.sid)
        key = (phys.fingerprint(), e.epoch, e.lsn)
        cq = self._compiled.get(key)
        if cq is None:
            with tel.span("session.compile", sid=self.sid):
                cq = compile_physical(e.opt, phys, self.exec_context(snap))
            self._compiled[key] = cq
            tel.inc("session.compiles_total", sid=self.sid)
        else:
            tel.inc("session.plan_cache.hits_total", level="executable",
                    sid=self.sid)
            # reuse the executable but surface THIS binding's physical plan
            # (its pruning rationale) for explain/stats readers.
            cq = dataclasses.replace(cq, physical=phys)
        # Bind against THIS entry's physical-plan literals: an executable
        # dedup'd from another logical shape has the same fingerprint, hence
        # the same slot order, but its Lit objects chain to the OTHER raw
        # plan — only this plan's lits resolve against raw_lits0.
        from repro.core import physical as PH
        binding = _literal_binding(e.raw_lits0,
                                   ordered_lits(PH.all_exprs(phys)))
        var = (cq, binding)
        e.variants[decisions.signature] = var
        return var

    def execute(self, plan: P.Plan):
        """Optimize → cost-plan (pruning at bind time) → compile (cached) →
        run → numpy-ify.

        A repeat of a query shape (the benchmark's randomized literals) reads
        its literal values off the un-optimized plan, re-decides zone-map
        pruning (pure interval arithmetic), and — when the surviving-run set
        is unchanged — binds straight into the cached executable's param
        slots: no optimizer pass, no planner pass, no re-compile.

        Snapshot isolation: the query pins one immutable catalog snapshot
        up front and optimizes, prunes, compiles, and executes entirely
        against it — a concurrent flush or background compaction publishing
        mid-query cannot change what this plan reads (it binds the NEXT
        query, which captures a fresh snapshot).
        """
        from repro.core.expr import ordered_lits
        from repro.core.physical import prune_report

        # Phase spans: session.query holds them all; session.bind is the
        # plan's fingerprint, literals and snapshot pin; session.execute.run
        # splits into gathering the inputs, dispatching the program and
        # waiting for the device; session.fetch brings the result to host.
        with tel.query_span("session.query", sid=self.sid, mode=self.mode):
            t0 = time.perf_counter()
            with tel.span("session.bind", sid=self.sid):
                raw_fp = plan.fingerprint()
                raw_lits = ordered_lits(P.all_exprs(plan))
                self._ensure_bound(plan)
                snap = self.catalog.snapshot()
            with snap, tel.span("session.execute", sid=self.sid,
                                mode=self.mode):
                e = self._plan_entry(plan, raw_fp, raw_lits, snap)
                cq, binding = self._variant(e, raw_lits, snap)
                params = _bind_params(binding, raw_lits)
                with tel.span("session.execute.run", sid=self.sid):
                    with tel.span("session.execute.gather", sid=self.sid):
                        tables = cq.gather_tables(snap)
                    with tel.span("session.execute.dispatch", sid=self.sid):
                        out = cq.call(tables, params)
                    with tel.span("session.execute.wait", sid=self.sid):
                        out = jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            tel.inc("session.executes_total", sid=self.sid, mode=self.mode)
            tel.set_gauge("session.last_execute_seconds", dt, sid=self.sid)
            self.last_optimized = e.opt
            self.last_physical = cq.physical
            self.last_prune_report = prune_report(cq.physical)
            tel.inc("session.pruned_components_total",
                    self.last_prune_report["pruned"], sid=self.sid)
            with tel.span("session.fetch", sid=self.sid):
                if cq.kind == "scalar":
                    vals = {k: np.asarray(v).item() for k, v in out.items()}
                    return vals if len(vals) > 1 \
                        else next(iter(vals.values()))
                env, mask = out
                return _materialize(env, mask, cq.kind)

    def explain(self, plan: P.Plan, analyze: bool = False) -> str:
        """The costed physical plan for ``plan``, rendered with per-operator
        cost estimates and the zone-map pruning rationale — what AsterixDB's
        EXPLAIN shows for the optimized Hyracks job. Runs the optimizer and
        planner but compiles/executes nothing.

        ``analyze=True`` additionally EXECUTES the query (``profile``) and
        annotates every operator line with measured self/total wall time and
        the actual row count beside the cost-model estimates."""
        if analyze:
            return self.profile(plan)["text"]
        from repro.core.expr import ordered_lits
        from repro.core.physical import format_plan

        raw_lits = ordered_lits(P.all_exprs(plan))
        self._ensure_bound(plan)
        with self.catalog.snapshot() as snap:
            e = self._plan_entry(plan, plan.fingerprint(), raw_lits, snap)
            decisions = e.pruner.decide([l.value for l in raw_lits],
                                        block_skip=self._block_skip()) \
                if self.enable_prune else None
            from repro.core.physical_planner import NO_PRUNE
            phys = plan_physical(e.opt, snap, mode=self.mode,
                                 decisions=decisions or NO_PRUNE,
                                 enable_index=self.enable_index)
        return format_plan(phys)

    def profile(self, plan: P.Plan) -> dict:
        """``explain(analyze=True)``'s engine: run ``plan`` through the full
        cached pipeline under span capture, time the jitted end-to-end run,
        then measure every operator's subtree standalone
        (``compiler.profile_physical``) so the rendered plan shows measured
        wall time and actual rows beside the cost estimates.

        Returns ``{"text", "result", "measures", "prune_report"}`` —
        ``result`` is exactly what ``execute(plan)`` returns."""
        from repro.core.compiler import profile_physical
        from repro.core.expr import ordered_lits
        from repro.core.physical import format_plan, prune_report

        tel.inc("session.profiles_total", sid=self.sid)
        raw_lits = ordered_lits(P.all_exprs(plan))
        self._ensure_bound(plan)
        with self.catalog.snapshot() as snap:
            with tel.span("session.profile", sid=self.sid, mode=self.mode):
                e = self._plan_entry(plan, plan.fingerprint(), raw_lits, snap)
                cq, binding = self._variant(e, raw_lits, snap)
                params = _bind_params(binding, raw_lits)
                tables = cq.gather_tables(snap)
                t0 = time.perf_counter()
                out = jax.block_until_ready(cq.call(tables, params))
                jit_seconds = time.perf_counter() - t0
                measures = profile_physical(cq.physical,
                                            self.exec_context(snap),
                                            tables, params)
        measures["jit_seconds"] = jit_seconds
        self.last_optimized = e.opt
        self.last_physical = cq.physical
        self.last_prune_report = prune_report(cq.physical)
        if cq.kind == "scalar":
            vals = {k: np.asarray(v).item() for k, v in out.items()}
            result = vals if len(vals) > 1 else next(iter(vals.values()))
        else:
            env, mask = out
            result = _materialize(env, mask, cq.kind)
        return {"text": format_plan(cq.physical, analyze=measures),
                "result": result, "measures": measures,
                "prune_report": self.last_prune_report}

    def persist(self, plan: P.Plan, name: str, dataverse: str = "Default") -> Dataset:
        """CREATE DATASET AS <query> — result stays engine-resident (paper
        Input 15: no data ever leaves storage)."""
        self._ensure_bound(plan)
        with self.catalog.snapshot() as snap:
            opt = self._optimize(plan, snap)
            cq = compile_plan(opt, self.exec_context(snap),
                              enable_index=self.enable_index,
                              enable_prune=self.enable_prune)
            out = cq.run(snap)
        if cq.kind == "scalar":
            raise ValueError("cannot persist a scalar result")
        from repro.engine.table import is_lane_column
        env, mask = out
        # strip the inputs' per-component dict lanes: concatenated ids from
        # different components don't share a dictionary — _collect_stats
        # rebuilds coherent lanes for the persisted table.
        cols = {k: v for k, v in env.items() if not is_lane_column(k)}
        cols["__valid__"] = mask
        table = _collect_stats(Table(cols, num_rows=int(mask.shape[0])))
        from repro.core.stats import harvest_block_zones
        ds = Dataset(name=name, dataverse=dataverse, table=table, closed=True,
                     block_zones=harvest_block_zones(table, self.n_shards))
        self.catalog.register(ds)
        self._invalidate_plans()
        return ds


# One jitted index builder per (mesh, data_axes): the sort/zone-map program
# is column-independent, so every dataset/run index build on the same mesh
# reuses one executable (retraced only per array shape). A per-call closure
# would re-jit on EVERY flush and dominate streaming-ingest cost.
_INDEX_BUILDERS: dict = {}


def _index_builder(mesh, data_axes):
    key = (mesh, tuple(data_axes))
    fn = _INDEX_BUILDERS.get(key)
    if fn is None:
        from repro.engine.index import build_index_local

        def build(k, v):
            ix = build_index_local(k, v, "", "build")
            return ix.sorted_keys, ix.row_ids, ix.zone_min, ix.zone_max

        if mesh is not None and mesh.devices.size > 1:
            dp = data_axes if len(data_axes) > 1 else data_axes[0]
            fn = jax.jit(_shard_map(
                build, mesh=mesh, in_specs=(PS(dp), PS(dp)),
                out_specs=(PS(dp), PS(dp), PS(dp), PS(dp))))
        else:
            fn = jax.jit(build)
        _INDEX_BUILDERS[key] = fn
    return fn


def _literal_binding(raw_lits, opt_lits) -> list[tuple[str, object]]:
    """Map each optimized-plan param slot back to the raw plan's literals.

    The optimizer shares user Lit objects with the raw plan and marks any
    literal it synthesizes from one (the ``==``-as-range mirror bound) with
    ``source``; a literal reachable from neither is a plan constant (sentinel
    range bounds) and rebinds to its compile-time value. The binding lets a
    plan-cache hit feed fresh literal values into the executable without
    re-running the optimizer.

    A literal the planner synthesized through a value TRANSFORM (the dict-id
    bounds of a string predicate) carries a ``binder`` callable plus the
    user ``sources`` it derives from: the binding records the transform and
    each source's resolution, so a rebind maps the fresh string literal
    through the same dictionary."""
    index = {id(l): j for j, l in enumerate(raw_lits)}

    def resolve(lit):
        src = lit
        while id(src) not in index and getattr(src, "source", None) is not None:
            src = src.source
        if id(src) in index:
            return ("raw", index[id(src)])
        return ("const", lit.value)

    binding: list[tuple[str, object]] = []
    for lit in opt_lits:
        binder = getattr(lit, "binder", None)
        if binder is not None:
            refs = tuple(resolve(s) for s in lit.sources)
            binding.append(("xform", (binder, refs)))
        else:
            binding.append(resolve(lit))
    return binding


def _bind_params(binding, raw_lits):
    from repro.core.expr import encode_param

    def value(kind, v):
        return raw_lits[v].value if kind == "raw" else v

    out = []
    for kind, v in binding:
        if kind == "xform":
            binder, refs = v
            out.append(encode_param(binder(*[value(k, r) for k, r in refs])))
        else:
            out.append(encode_param(value(kind, v)))
    return out


def _route_key(comp, key_col: str, key, n_keys: int):
    """Shard-route a point lookup inside one component: fold the clustered
    key column's per-shard zone spans into one [lo, hi] per row partition
    and return the ``host_keys`` window covering the owning shard(s) —
    ``(window_lo, window_hi, owning_shards, n_shards)``. The matter prefix
    is clustered, so owning shards are a contiguous run and the merged
    window stays one slice (a duplicate key straddling a shard boundary is
    still found whole). Components without a sharded zone layout fall back
    to the full window."""
    bz = comp.block_zones
    if bz is None or bz.n_shards <= 1 or not bz.rows_per_shard:
        return 0, n_keys, 1, 1
    span = bz.span_of(key_col)
    if span is None:
        return 0, n_keys, 1, bz.n_shards
    per = span.reshape(bz.n_shards, bz.blocks_per_shard, 2)
    owners = np.nonzero((per[:, :, 0].min(axis=1) <= key)
                        & (key <= per[:, :, 1].max(axis=1)))[0]
    if not len(owners):
        return 0, 0, 0, bz.n_shards
    wlo = min(int(owners[0]) * bz.rows_per_shard, n_keys)
    whi = min((int(owners[-1]) + 1) * bz.rows_per_shard, n_keys)
    return wlo, whi, len(owners), bz.n_shards


def _mount_component(session: Session, dataverse: str, seg: str,
                     arrays: Mapping, meta: Mapping) -> Dataset:
    """Rehydrate one LSM component from its durable segment: hard state
    only — table columns (re-sharded onto the session's mesh), column
    metadata, and the index *inventory* (payloads stay None until the
    lazy soft-state rebuild at first bind)."""
    from repro.runtime.durable import _meta_from_json

    cols, cmeta = {}, {}
    for cname, mjson in meta["columns"]:
        cols[cname] = arrays[cname]
        cmeta[cname] = _meta_from_json(mjson)
    table = Table(cols, cmeta, int(meta["num_rows"]))
    if session.mesh is not None:
        table = table.shard(session.mesh, session.data_axes)
    ds = Dataset(name=meta["name"], dataverse=dataverse, table=table,
                 closed=bool(meta["closed"]), live_rows=meta["live_rows"],
                 anti_rows=int(meta["anti_rows"]), level=int(meta["level"]),
                 uid=int(meta["uid"]), engine_owned=True, seg_name=seg,
                 soft_stale=True)
    for key, ix_name, column, kind in meta["indexes"]:
        ds.indexes[key] = IndexInfo(name=ix_name, column=column, kind=kind)
    return ds


def _collect_stats(table: Table, like: Optional[Mapping] = None) -> Table:
    """Fill missing lo/hi/distinct for numeric columns (the statistics a
    DBMS gathers at load; the bounded-domain group-by and index selection
    read them from the catalog). Integer columns get lo/hi/distinct; float
    columns get a NaN-safe lo/hi envelope (no distinct — float domains are
    never group-by keys), so float predicates participate in run-level
    zone-span pruning too.

    String columns additionally grow their derived integer lanes here
    (engine/table.py): an always-on order-preserving ``__pfx_<col>`` prefix
    lane (int32 — zone-map pruning only), and a per-component sorted
    dictionary-id lane ``__dict_<col>`` (int32 — what string ==/IN/group-by
    lower onto the kernels through) when the live distinct count stays
    under ``DICT_THRESHOLD``. ``like`` is the base table's meta when
    building an LSM run: dict-lane presence follows the hint instead of the
    threshold, so lane presence stays uniform across one dataset's
    components (the union-concat lowering requires a uniform column set)."""
    from repro.engine.table import (DICT_THRESHOLD, ColumnMeta,
                                    decode_strings, dict_lane_name,
                                    is_lane_column, pack_prefix,
                                    prefix_lane_name)

    meta = dict(table.meta)
    cols = dict(table.columns)
    live = None  # lazily-computed visible-row mask (string lanes only)

    def live_mask():
        nonlocal live
        if live is None:
            m = np.ones(table.num_rows, bool)
            v = cols.get("__valid__")
            if v is not None:
                m &= np.asarray(v)
            am = cols.get("__antimatter__")
            if am is not None:
                m &= ~np.asarray(am)
            live = m
        return live

    for name, col in table.columns.items():
        if name in INTERNAL_COLUMNS or is_lane_column(name):
            continue
        m = meta.get(name)
        a = np.asarray(col)
        if a.ndim == 2 and a.dtype == np.uint8:
            pfx = prefix_lane_name(name)
            if pfx not in cols:
                packed = pack_prefix(a)
                lm = live_mask()
                plo, phi = ((int(packed[lm].min()), int(packed[lm].max()))
                            if lm.any() else (None, None))
                cols[pfx] = packed
                meta[pfx] = ColumnMeta(np.dtype(np.int32), plo, phi)
            dname = dict_lane_name(name)
            if dname not in cols:
                lm = live_mask()
                uniq, inv = np.unique(a[lm], axis=0, return_inverse=True)
                inv = np.asarray(inv).reshape(-1)
                hint = getattr(like.get(name), "dict_values", None) \
                    if like is not None else None
                want_dict = (hint is not None) if like is not None \
                    else len(uniq) <= DICT_THRESHOLD
                new = m if m is not None else ColumnMeta(a.dtype,
                                                         is_string=True)
                new = dataclasses.replace(new, distinct=len(uniq))
                if want_dict:
                    # dead rows carry id -1: every consumer masks them, and
                    # the lane's zone span covers live ids [0, G-1] only.
                    ids = np.full(a.shape[0], -1, np.int32)
                    ids[lm] = inv.astype(np.int32)
                    cols[dname] = ids
                    g = len(uniq)
                    meta[dname] = ColumnMeta(np.dtype(np.int32),
                                             0 if g else None,
                                             g - 1 if g else None, g)
                    new = dataclasses.replace(
                        new, dict_values=tuple(decode_strings(uniq)))
                meta[name] = new
            continue
        if m is not None and m.lo is not None:
            continue
        if a.ndim != 1 or not a.size:
            continue
        if np.issubdtype(a.dtype, np.integer):
            lo, hi = int(a.min()), int(a.max())
            distinct = min(hi - lo + 1, a.size)
            meta[name] = ColumnMeta(a.dtype, lo, hi, distinct)
        elif np.issubdtype(a.dtype, np.floating) and not np.all(np.isnan(a)):
            meta[name] = ColumnMeta(a.dtype, float(np.nanmin(a)),
                                    float(np.nanmax(a)))
    return Table(cols, meta, table.num_rows)


def _materialize(env: dict, mask, kind: str) -> dict[str, np.ndarray]:
    """Compact to valid rows on the host (result delivery boundary).
    Derived string lanes are storage internals — never delivered."""
    from repro.engine.table import is_lane_column

    m = np.asarray(mask)
    out = {}
    for k, v in env.items():
        if is_lane_column(k):
            continue
        a = np.asarray(v)
        out[k] = a[m]
    return out
