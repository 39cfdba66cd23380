"""Snapshot-isolated concurrent serving (the robustness tentpole): background
compaction off the ingest hot path, storage fault injection at every named
crash point, hard/soft state recovery, write-stall backpressure, and an
oracle-replay stress test across all three execution modes.

The oracle is a plain dict (key -> row) maintained by the test; every reader
observation must be bit-identical to it no matter where compaction, retries,
or injected crashes are in flight — compaction and recovery are invisible to
readers by construction."""
import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.frame import AFrame
from repro.engine import lsm
from repro.engine.ingest import Feed
from repro.engine.session import Session
from repro.engine.table import Table
from repro.runtime import telemetry as tel
from repro.runtime.fault import STORAGE_FAULT_POINTS, FaultPlan, StorageFault

MODES = ["gspmd", "shard_map", "kernel"]

# never triggers on its own: tests drive compaction explicitly
DEFERRED = lsm.CompactionPolicy(size_ratio=100.0, max_runs=64)


def _session(mode, catalog=None):
    if mode == "shard_map":
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        return Session(mesh=mesh, mode="shard_map", catalog=catalog)
    return Session(mode=mode, catalog=catalog)


def _rows(keys, rng=None):
    """Schema: k (primary), v in [1, 100] (positive: a zero group-sum means
    an empty group), g in [0, 5)."""
    keys = np.asarray(keys, dtype=np.int32)
    if rng is None:
        vals = 1 + (keys.astype(np.int64) * 7 % 100).astype(np.int32)
    else:
        vals = rng.integers(1, 101, size=len(keys), dtype=np.int32)
    return {"k": keys, "v": vals, "g": (keys % 5).astype(np.int32)}


def _setup(mode, n=48, indexes=()):
    sess = _session(mode)
    rows = _rows(np.arange(n))
    sess.create_dataset("Live", Table(dict(rows)), dataverse="d",
                        primary="k", indexes=list(indexes))
    oracle = {int(k): (int(v), int(g))
              for k, v, g in zip(rows["k"], rows["v"], rows["g"])}
    return sess, oracle


def _expected(oracle):
    gsum = {}
    for v, g in oracle.values():
        gsum[g] = gsum.get(g, 0) + v
    return {"len": len(oracle),
            "sum": sum(v for v, _ in oracle.values()),
            "g2_count": sum(1 for _, g in oracle.values() if g == 2),
            "gsum": {g: s for g, s in gsum.items() if s != 0}}


def _observe(df):
    """One reader observation (each query pins its own snapshot)."""
    out = df.groupby("g").agg({"v": "sum"})
    gcol = np.asarray(out["g"]).tolist()
    vname = next(c for c in out if c != "g")
    vcol = np.asarray(out[vname]).tolist()
    return {"len": len(df),
            "sum": int(df["v"].sum()),
            "g2_count": len(df[df["g"] == 2]),
            "gsum": {int(g): int(s) for g, s in zip(gcol, vcol) if s != 0}}


# -- background compaction ---------------------------------------------------


def test_background_compactor_folds_runs_and_preserves_results():
    sess, oracle = _setup("gspmd")
    df = AFrame("d", "Live", session=sess)
    with lsm.BackgroundCompactor(
            sess, policy=lsm.LeveledCompactionPolicy(
                size_ratio=100.0, max_runs=64, level0_runs=2,
                level_ratio=2)) as bc:
        feed = Feed(sess, "Live", "d", flush_rows=8, policy=DEFERRED,
                    compactor=bc)
        for i in range(6):
            keys = np.arange(48 + 8 * i, 48 + 8 * (i + 1))
            rows = _rows(keys)
            feed.push(rows)
            for k, v, g in zip(rows["k"], rows["v"], rows["g"]):
                oracle[int(k)] = (int(v), int(g))
        assert bc.wait_idle(30.0)
        # leveled folding actually ran and reduced the component count
        assert bc.stats["level_merges"] >= 1
        assert len(sess.catalog.get("d", "Live").runs) < 6
    assert _observe(df) == _expected(oracle)


def test_no_reader_blocks_on_running_compaction(monkeypatch):
    """A reader landing MID-MERGE answers from its pinned snapshot in
    milliseconds while the worker spends >1s building the new base — the
    catalog lock is held for the O(datasets) swap only, never the build."""
    sess, oracle = _setup("gspmd", n=200)
    feed = Feed(sess, "Live", "d", flush_rows=20, policy=DEFERRED)
    for i in range(3):
        feed.push(_rows(np.arange(200 + 20 * i, 220 + 20 * i)))
    for k in range(200, 260):
        oracle[k] = (1 + k * 7 % 100, k % 5)
    reader = _session("gspmd", catalog=sess.catalog)
    df = AFrame("d", "Live", session=reader)
    assert _observe(df) == _expected(oracle)  # warm the reader's plan cache

    started = threading.Event()
    real = lsm._visible_columns

    def slow_visible(*a, **kw):
        started.set()
        time.sleep(0.35)  # 4 components -> the merge build takes >1.4s
        return real(*a, **kw)

    monkeypatch.setattr(lsm, "_visible_columns", slow_visible)
    with lsm.BackgroundCompactor(
            sess, policy=lsm.CompactionPolicy(size_ratio=0.0)) as bc:
        bc.notify("d", "Live")
        assert started.wait(10.0)
        t0 = time.perf_counter()
        got = _observe(df)
        dt = time.perf_counter() - t0
        assert got == _expected(oracle)
        assert dt < 0.3, f"reader blocked {dt:.2f}s on a running compaction"
        assert bc.wait_idle(30.0)
        assert bc.stats["compactions"] >= 1
    monkeypatch.setattr(lsm, "_visible_columns", real)
    assert len(sess.catalog.get("d", "Live").runs) == 0
    assert _observe(df) == _expected(oracle)


def test_compactor_bug_reaches_the_waiter(monkeypatch):
    """An exception that is neither a lost CAS nor a storage fault is a bug:
    the compactor counts it, leaves the manifest untouched and raises it to
    the next waiter instead of reporting an idle, healthy worker."""
    sess, oracle = _setup("gspmd")
    feed = Feed(sess, "Live", "d", flush_rows=8, policy=DEFERRED)
    feed.push(_rows(np.arange(48, 56)))
    oracle.update({k: (1 + k * 7 % 100, k % 5) for k in range(48, 56)})

    def broken(*a, **kw):
        raise ValueError("merge bug")

    real = lsm._visible_columns
    monkeypatch.setattr(lsm, "_visible_columns", broken)
    errors0 = tel.counter_value("lsm.compactor.errors_total") or 0
    bc = lsm.BackgroundCompactor(sess, policy=lsm.CompactionPolicy(size_ratio=0.0))
    bc.notify("d", "Live")
    with pytest.raises(RuntimeError, match="background compaction failed") as ei:
        bc.wait_idle(30.0)
    assert isinstance(ei.value.__cause__, ValueError)
    assert bc.stats["errors"] == 1
    assert tel.counter_value("lsm.compactor.errors_total") == errors0 + 1
    with pytest.raises(RuntimeError):
        bc.close()
    monkeypatch.setattr(lsm, "_visible_columns", real)
    assert len(sess.catalog.get("d", "Live").runs) == 1
    assert _observe(AFrame("d", "Live", session=sess)) == _expected(oracle)


def test_write_stall_backpressures_writer_not_readers():
    """Past the hard run cap the WRITER blocks (bounded by the stall
    timeout); a concurrent reader still answers correctly."""
    sess, oracle = _setup("gspmd")
    # worker never folds anything -> the run count can only grow
    with lsm.BackgroundCompactor(sess, policy=DEFERRED) as bc:
        feed = Feed(sess, "Live", "d", flush_rows=8, policy=DEFERRED,
                    compactor=bc, stall_runs=2, stall_timeout_s=0.15)
        for i in range(3):
            rows = _rows(np.arange(48 + 8 * i, 56 + 8 * i))
            feed.push(rows)
            for k, v, g in zip(rows["k"], rows["v"], rows["g"]):
                oracle[int(k)] = (int(v), int(g))
        assert feed.stats["stalls"] >= 1
        assert feed.stats["stall_s"] > 0.0
        reader = _session("gspmd", catalog=sess.catalog)
        assert _observe(AFrame("d", "Live", session=reader)) == \
            _expected(oracle)


def test_proportional_stall_delay_curve():
    """The AsterixDB-style proportional delay: zero below the warning
    fraction of the cap, growing linearly with pressure, saturating at the
    configured maximum (the hard cap itself stays a blocking ceiling)."""
    from repro.core.physical_planner import STALL_WARN_FRAC
    from repro.engine.ingest import stall_delay

    assert stall_delay(0.0, 0.1) == 0.0
    assert stall_delay(STALL_WARN_FRAC - 0.01, 0.1) == 0.0  # under warn
    assert stall_delay(STALL_WARN_FRAC, 0.1) == 0.0         # curve starts
    mid = (STALL_WARN_FRAC + 1.0) / 2
    assert 0.0 < stall_delay(mid, 0.1) < 0.1
    assert stall_delay(1.0, 0.1) == pytest.approx(0.1)      # cap -> max
    assert stall_delay(5.0, 0.1) == pytest.approx(0.1)      # saturates
    assert stall_delay(1.0, 0.0) == 0.0                     # disabled
    # monotone non-decreasing across the whole pressure range
    samples = [stall_delay(p, 0.1) for p in np.linspace(0, 2, 41)]
    assert all(b >= a for a, b in zip(samples, samples[1:]))


def test_proportional_stall_slows_writer_before_hard_cap():
    """Approaching the cap, each flush sleeps a growing delay (soft stalls)
    instead of running full speed into the hard stall — and the delay is
    charged to the same stall accounting."""
    sess, oracle = _setup("gspmd")
    with lsm.BackgroundCompactor(sess, policy=DEFERRED) as bc:
        feed = Feed(sess, "Live", "d", flush_rows=8, policy=DEFERRED,
                    compactor=bc, stall_runs=8, stall_timeout_s=0.15,
                    stall_delay_s=0.02)
        for i in range(7):  # run count climbs 1..7: pressure crosses 0.75
            rows = _rows(np.arange(48 + 8 * i, 56 + 8 * i))
            feed.push(rows)
            for k, v, g in zip(rows["k"], rows["v"], rows["g"]):
                oracle[int(k)] = (int(v), int(g))
        assert feed.stats["stalls"] == 0          # never hit the ceiling
        assert feed.stats["soft_stalls"] >= 1     # but did slow down
        assert feed.stats["stall_s"] > 0.0
        reader = _session("gspmd", catalog=sess.catalog)
        assert _observe(AFrame("d", "Live", session=reader)) == \
            _expected(oracle)


def test_background_compactor_retries_through_injected_fault():
    """A mid-merge crash on the worker thread is absorbed by its bounded
    retry loop — the writer never sees it, and the fold still lands."""
    sess, oracle = _setup("gspmd")
    sess.fault_plan = FaultPlan.once("mid-merge")
    with lsm.BackgroundCompactor(
            sess, policy=lsm.CompactionPolicy(size_ratio=0.0),
            backoff_s=0.001) as bc:
        feed = Feed(sess, "Live", "d", flush_rows=8, policy=DEFERRED,
                    compactor=bc)
        rows = _rows(np.arange(48, 56))
        feed.push(rows)  # no StorageFault reaches the writer
        for k, v, g in zip(rows["k"], rows["v"], rows["g"]):
            oracle[int(k)] = (int(v), int(g))
        assert bc.wait_idle(30.0)
        assert bc.stats["faults"] >= 1 and bc.stats["retries"] >= 1
    assert len(sess.catalog.get("d", "Live").runs) == 0  # fold landed
    assert _observe(AFrame("d", "Live", session=sess)) == _expected(oracle)
    assert sess.fault_plan.fired == [("mid-merge", 0)]


def test_per_dataverse_compactor_isolation(monkeypatch):
    """The pending queue is sharded per dataverse: a stalled (long) merge in
    one dataverse must not delay another dataverse's compaction — each shard
    gets its own worker thread, created lazily at first notify."""
    sess, _ = _setup("gspmd")  # dataverse "d"
    rows = _rows(np.arange(48))
    sess.create_dataset("Other", Table(dict(rows)), dataverse="d2",
                        primary="k")

    release = threading.Event()
    entered = threading.Event()
    real = lsm._visible_columns

    def gated_visible(comp, *a, **kw):
        if comp.dataverse == "d":     # block ONLY dataverse d's merge
            entered.set()
            assert release.wait(30.0)
        return real(comp, *a, **kw)

    monkeypatch.setattr(lsm, "_visible_columns", gated_visible)
    with lsm.BackgroundCompactor(
            sess, policy=lsm.CompactionPolicy(size_ratio=0.0)) as bc:
        feed_d = Feed(sess, "Live", "d", flush_rows=8, policy=DEFERRED,
                      compactor=bc)
        feed_d.push(_rows(np.arange(48, 56)))
        assert entered.wait(10.0)     # d's worker is parked mid-merge
        assert tel.gauge_value("lsm.compactor.workers") == 1

        feed_d2 = Feed(sess, "Other", "d2", flush_rows=8, policy=DEFERRED,
                       compactor=bc)
        feed_d2.push(_rows(np.arange(48, 56)))
        # d2's shard compacts to quiescence while d is still blocked
        deadline = time.time() + 15.0
        while time.time() < deadline and \
                len(sess.catalog.get("d2", "Other").runs) > 0:
            time.sleep(0.02)
        assert len(sess.catalog.get("d2", "Other").runs) == 0, \
            "dataverse d2 compaction starved by d's stalled merge"
        assert tel.gauge_value("lsm.compactor.workers") == 2
        assert len(sess.catalog.get("d", "Live").runs) == 1  # still parked
        release.set()
        assert bc.wait_idle(30.0)
    assert len(sess.catalog.get("d", "Live").runs) == 0


# -- crash points on the synchronous path ------------------------------------


def _apply(oracle, rows=None, upserts=None, deletes=()):
    if rows is not None:
        for k, v, g in zip(rows["k"], rows["v"], rows["g"]):
            oracle[int(k)] = (int(v), int(g))
    if upserts is not None:
        for k, v, g in zip(upserts["k"], upserts["v"], upserts["g"]):
            oracle[int(k)] = (int(v), int(g))
    for k in deletes:
        oracle.pop(int(k), None)


@pytest.mark.parametrize("point", STORAGE_FAULT_POINTS)
def test_crash_at_every_point_keeps_readers_bit_identical(point):
    """The hard/soft split, end to end: a crash at ANY fault point leaves
    the manifest either fully old or fully new (never half), reader results
    bit-identical to the matching oracle state throughout, and recover() +
    the buffer-as-WAL discipline resumes ingestion exactly once."""
    sess, oracle = _setup("gspmd")
    # size_ratio=0 folds on every flush -> "mid-merge" is reachable inline
    feed = Feed(sess, "Live", "d", flush_rows=10**9,
                policy=lsm.CompactionPolicy(size_ratio=0.0))
    df = AFrame("d", "Live", session=sess)
    feed.push(_rows(np.arange(48, 56)))
    feed.flush()
    _apply(oracle, rows=_rows(np.arange(48, 56)))
    assert _observe(df) == _expected(oracle)

    # batch B mixes all three mutation kinds so annihilation bookkeeping,
    # anti arrays, and view deltas are all in play at the crash
    fresh = _rows(np.arange(56, 61))
    ups = {"k": np.arange(10, 16, dtype=np.int32),
           "v": np.full(6, 77, dtype=np.int32),
           "g": (np.arange(10, 16) % 5).astype(np.int32)}
    dels = np.array([3, 4, 50], dtype=np.int32)
    feed.push(fresh)
    feed.upsert(ups)
    feed.delete(dels)

    sess.fault_plan = FaultPlan.once(point)
    with pytest.raises(StorageFault):
        feed.flush()
    assert sess.fault_plan.fired == [(point, 0)]
    sess.fault_plan = None

    if point in ("flush", "pre-swap"):
        # nothing published: readers still see the pre-crash state ...
        assert _observe(df) == _expected(oracle)
        feed.flush()  # ... and the buffer is the WAL: replay applies once
        _apply(oracle, rows=fresh, upserts=ups, deletes=dels)
        assert _observe(df) == _expected(oracle)
    else:
        # the atomic swap committed the flush before the crash: readers see
        # the batch even though soft-state bookkeeping was cut short
        _apply(oracle, rows=fresh, upserts=ups, deletes=dels)
        assert _observe(df) == _expected(oracle)
        lsm.recover(sess, "d", "Live")
        assert _observe(df) == _expected(oracle)
        if point == "post-swap":
            feed.drop_buffer()  # committed: replaying would double-apply

    # the pipeline is healthy after recovery: mutate + flush again
    feed.push(_rows(np.arange(61, 66)))
    feed.delete(np.array([56], dtype=np.int32))
    feed.flush()
    _apply(oracle, rows=_rows(np.arange(61, 66)), deletes=[56])
    assert _observe(df) == _expected(oracle)
    assert len(df[df["k"] == 3]) == 0 and len(df[df["k"] == 10]) == 1


def test_recover_rebuilds_corrupted_soft_state_bit_identical():
    """Hard state (component tables + manifest) is sufficient: wipe every
    piece of soft state — index payloads, zone maps, host key copies, anti
    arrays, bookkeeping — and recover() rebuilds it all bit-identically."""
    sess, oracle = _setup("gspmd", indexes=["v"])
    feed = Feed(sess, "Live", "d", flush_rows=10**9, policy=DEFERRED)
    feed.push(_rows(np.arange(48, 60)))
    feed.upsert({"k": np.arange(5, 9, dtype=np.int32),
                 "v": np.full(4, 55, dtype=np.int32),
                 "g": (np.arange(5, 9) % 5).astype(np.int32)})
    feed.delete(np.array([20, 21], dtype=np.int32))
    feed.flush()
    _apply(oracle, rows=_rows(np.arange(48, 60)),
           upserts={"k": np.arange(5, 9), "v": np.full(4, 55),
                    "g": np.arange(5, 9) % 5},
           deletes=[20, 21])
    df = AFrame("d", "Live", session=sess)

    def suite():
        obs = _observe(df)
        obs["v_range"] = len(df[(df["v"] >= 10) & (df["v"] <= 60)])
        obs["probe"] = (len(df[df["k"] == 20]), len(df[df["k"] == 5]))
        return obs

    before = suite()
    comps = sess.catalog.components("d", "Live")
    assert any(c.anti_keys_arr is not None for c in comps)
    for comp in comps:
        comp.live_rows = 0
        comp.annihilated_rows = 10 ** 6
        comp.annihilated_keys = set()
        comp.host_keys = None
        comp.block_zones = None
        if comp.anti_keys_arr is not None:
            comp.anti_keys_arr = comp.anti_keys_arr[:0]
        for info in comp.indexes.values():
            if info.kind == "secondary":
                info.sorted_keys = None
                info.row_ids = None
                info.zone_min = None
                info.zone_max = None
    lsm.recover(sess, "d", "Live")
    assert suite() == before
    for comp in comps:
        assert comp.host_keys is not None
        for info in comp.indexes.values():
            if info.kind == "secondary":
                assert info.sorted_keys is not None
    assert any(len(np.asarray(c.anti_keys_arr)) for c in comps
               if c.anti_keys_arr is not None)


# -- oracle-replay stress: concurrent compactor, faults, all three modes -----


def _stress(mode, seed, n_ops=9, fault=None, fault_at=0):
    """Drive a random op sequence against a writer with a REAL background
    compactor racing (leveled, fanin 2 — folds constantly), a shared-catalog
    reader observing after every flush, and optionally one injected crash.
    Every observation must equal the dict oracle exactly: compaction is
    result-preserving, so the race never shows."""
    rng = np.random.default_rng(seed)
    sess, oracle = _setup(mode)
    shadow = dict(oracle)  # oracle ∪ buffered-but-unflushed ops
    reader = _session(mode, catalog=sess.catalog)
    df = AFrame("d", "Live", session=reader)
    next_k = 48
    flush_i = 0
    with lsm.BackgroundCompactor(
            sess, policy=lsm.LeveledCompactionPolicy(
                size_ratio=6.0, max_runs=64, level0_runs=2, level_ratio=2),
            backoff_s=0.001) as bc:
        feed = Feed(sess, "Live", "d", flush_rows=10**9, policy=DEFERRED,
                    compactor=bc)
        ops = rng.choice(["push", "upsert", "delete", "flush"], size=n_ops,
                         p=[0.35, 0.2, 0.15, 0.3])
        for op in list(ops) + ["flush"]:
            if op == "push":
                n = int(rng.integers(1, 10))
                rows = _rows(np.arange(next_k, next_k + n), rng)
                next_k += n
                feed.push(rows)
                _apply(shadow, rows=rows)
            elif op == "upsert":
                keys = sorted(shadow)
                if not keys:
                    continue
                pick = rng.choice(keys, size=min(6, len(keys)), replace=False)
                ups = _rows(np.sort(pick), rng)
                feed.upsert(ups)
                _apply(shadow, upserts=ups)
            elif op == "delete":
                keys = sorted(shadow)
                if not keys:
                    continue
                pick = np.sort(rng.choice(keys, size=min(4, len(keys)),
                                          replace=False)).astype(np.int32)
                feed.delete(pick)
                _apply(shadow, deletes=pick)
            else:
                if fault is not None and flush_i == fault_at:
                    sess.fault_plan = FaultPlan(schedule={fault: (0,)})
                try:
                    feed.flush()
                except StorageFault:
                    # the crash hit the WRITER path (worker-side crashes are
                    # absorbed by its retry loop and never surface here)
                    pt = sess.fault_plan.fired[-1][0]
                    sess.fault_plan = None
                    if pt == "post-swap":
                        # committed: repair soft state, don't replay the WAL
                        lsm.recover(sess, "d", "Live")
                        feed.drop_buffer()
                    else:
                        feed.flush()  # nothing landed: replay the buffer
                sess.fault_plan = None
                flush_i += 1
                oracle = dict(shadow)  # every flush path applies exactly once
                assert _observe(df) == _expected(oracle), \
                    f"[{mode} seed={seed}] reader diverged after flush {flush_i}"
        assert bc.wait_idle(30.0)
        # quiescent end state: a FRESH reader session agrees too
        final = _expected(dict(shadow))
        assert _observe(df) == final
        df2 = AFrame("d", "Live",
                     session=_session(mode, catalog=sess.catalog))
        assert _observe(df2) == final


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", [0, 1])
def test_stress_concurrent_ops_match_oracle(mode, seed):
    _stress(mode, seed)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("fault", STORAGE_FAULT_POINTS)
def test_stress_with_injected_crash_matches_oracle(mode, fault):
    _stress(mode, seed=2, fault=fault, fault_at=1)


def test_stress_hypothesis_random_schedules():
    """Property form of the stress driver (optional dependency, like the
    other hypothesis suites): random seeds, op counts, and crash points."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 10**6), n_ops=st.integers(4, 12),
           fault=st.sampled_from((None,) + STORAGE_FAULT_POINTS),
           fault_at=st.integers(0, 2))
    def run(seed, n_ops, fault, fault_at):
        _stress("gspmd", seed, n_ops=n_ops, fault=fault, fault_at=fault_at)

    run()
