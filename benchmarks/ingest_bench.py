"""Streaming-ingestion benchmark: sustained rows/sec and query freshness.

Two variants ingest the same stream into the same base table:

  * ``compact-every-flush`` — the pre-LSM behaviour: every flush de-shards,
    concatenates, re-sorts and re-indexes the whole base (O(base) per batch).
    Expressed as ``CompactionPolicy(size_ratio=0)``.
  * ``deferred``           — the LSM path: flushes become device-resident
    runs (O(batch)), compaction fires only on the size-ratio policy.

Reported per size: sustained ingest rows/sec (wall time of push+flush+any
compaction), the deferred/baseline speedup, and query-freshness latency
(time to answer ``COUNT(*)`` + an indexed range count right after each
flush — base ∪ runs, including the recompile a fresh component set forces).

The deferred variant additionally runs a **query-freshness-under-selectivity
sweep**: with N runs resident, a range predicate on the monotone ``unique2``
key that hits exactly 1 of the N runs is answered with zone-map pruning on
vs. off — tracking the pruning win (latency + physical rows touched + runs
skipped) in ``results/bench/ingest.json`` across PRs.

A **mutation sweep** rides along: the same stream replayed as append-only
vs. upsert-heavy vs. delete-heavy workloads (anti-matter records through
``Feed.upsert``/``Feed.delete``), each with deferred and compact-every-flush
policies — sustained mutation ops/sec, post-flush query freshness, and an
uncompacted == compacted consistency check per cell.

A **block_skip sweep** measures the second pruning level: selective range
predicates over a clustered (sorted, unindexed) column, with bind-time
block zone-map skipping on vs. off — latency plus blocks touched, which
must scale with the predicate's block footprint, not the dataset. A
**block_skip_sharded sweep** repeats the cell over an 8-device mesh of
this process (on the CPU: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
on the command line): zone maps are laid out per row partition and each
shard's kernel grid scans only its own survivors.

A **concurrent-serving sweep** replays the stream with a reader thread
(its own Session on the SHARED catalog) hammering an indexed range count
the whole time, under two serving modes: ``synchronous`` (merges run
inline on the writer) vs ``background`` (a BackgroundCompactor thread,
write-stall backpressure only past the hard run cap). Reported per cell:
reader p50/p99/max latency, per-batch writer latency p50/p99, and
write-stall seconds. The reader p99 of the background cell is asserted
under a hard cap — the "no query ever blocks on a running compaction"
guarantee, enforced where it would regress first.
"""
from __future__ import annotations

import json
import pathlib
import threading
import time

import numpy as np

from repro.core.frame import AFrame
from repro.data import wisconsin
from repro.engine import lsm
from repro.engine.ingest import Feed
from repro.engine.session import Session

# size: (base_rows, n_batches, batch_rows)
SIZES = {
    "XS": (2_000, 6, 512),
    "S": (10_000, 10, 1_024),
    "M": (50_000, 16, 2_048),
    "L": (150_000, 24, 2_048),
}

POLICIES = {
    "compact-every-flush": lambda: lsm.CompactionPolicy(size_ratio=0.0),
    "deferred": lambda: lsm.CompactionPolicy(size_ratio=1.0, max_runs=8),
}


def _stream(base_rows: int, n_batches: int, batch_rows: int):
    """Pre-generated arrival batches (unique2 keys keep increasing — the
    timestamped-tweet pattern)."""
    batches = []
    for i in range(n_batches):
        t = wisconsin.generate(batch_rows, seed=1_000 + i)
        rows = {k: np.asarray(v) for k, v in t.columns.items()}
        rows["unique2"] = rows["unique2"] + base_rows + i * batch_rows
        batches.append(rows)
    return batches


def _run_variant(size: str, variant: str, mode: str = "gspmd") -> dict:
    base_rows, n_batches, batch_rows = SIZES[size]
    base = wisconsin.generate(base_rows, seed=7)
    sess = Session(mode=mode)
    sess.create_dataset("Stream", base, dataverse="bench",
                        indexes=["onePercent"], primary="unique2")
    feed = Feed(sess, "Stream", "bench", flush_rows=batch_rows,
                policy=POLICIES[variant]())
    batches = _stream(base_rows, n_batches, batch_rows)
    df = AFrame("bench", "Stream", session=sess)
    len(df)  # warm the count executable for the base-only shape

    ingest_s = 0.0
    freshness = []
    for rows in batches:
        t0 = time.perf_counter()
        feed.push(rows)  # flush_rows == batch_rows: flushes synchronously
        ingest_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        n = len(df)
        len(df[(df["onePercent"] >= 10) & (df["onePercent"] <= 30)])
        freshness.append(time.perf_counter() - t0)
        assert n == base_rows + feed.stats["ingested"]
    total_rows = n_batches * batch_rows
    out = {
        "size": size,
        "variant": variant,
        "rows": total_rows,
        "batches": n_batches,
        "ingest_s": round(ingest_s, 4),
        "rows_per_s": round(total_rows / ingest_s, 1),
        "freshness_median_s": round(float(np.median(freshness)), 4),
        "freshness_p95_s": round(float(np.percentile(freshness, 95)), 4),
        "flushes": feed.stats["flushes"],
        "compactions": feed.stats["compactions"],
        "final_runs": feed.stats["runs"],
    }
    if variant == "deferred" and feed.stats["runs"] >= 2:
        out["prune_sweep"] = _selectivity_sweep(
            sess, df, base_rows, n_batches, batch_rows, feed.stats["runs"])
    return out


def _selectivity_sweep(sess: Session, df: AFrame, base_rows: int,
                       n_batches: int, batch_rows: int, n_runs: int,
                       repeats: int = 5) -> dict:
    """Selective range count hitting exactly 1 of the resident runs, with
    zone-map pruning on vs. off (the planner's bind-time decision): reports
    the latency and the rows-touched / runs-pruned the physical plan shows.
    Toggling ``enable_prune`` is cache-safe — the two settings produce
    different prune signatures, so they bind different executables."""
    lo = base_rows + (n_batches - 1) * batch_rows  # the newest run's key span
    hi = lo + batch_rows - 1
    sweep: dict = {"runs_resident": n_runs}
    for prune in (True, False):
        sess.enable_prune = prune
        label = "pruned" if prune else "unpruned"
        n = len(df[(df["unique2"] >= lo) & (df["unique2"] <= hi)])  # warm/compile
        assert n == batch_rows, (n, batch_rows)
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            len(df[(df["unique2"] >= lo) & (df["unique2"] <= hi)])
            times.append(time.perf_counter() - t0)
        report = sess.last_prune_report
        sweep[label] = {
            "query_median_s": round(float(np.median(times)), 5),
            "rows_touched": int(report["rows_touched"]),
            "components": int(report["components"]),
            "runs_pruned": int(report["pruned"]),
            "rows_pruned": int(report["rows_pruned"]),
        }
    sess.enable_prune = True
    p, u = sweep["pruned"], sweep["unpruned"]
    sweep["query_speedup"] = round(
        u["query_median_s"] / max(p["query_median_s"], 1e-9), 2)
    print(f"     prune sweep (1 of {n_runs} runs hit): "
          f"{p['runs_pruned']}/{p['components']} components pruned, "
          f"rows touched {u['rows_touched']:,} -> {p['rows_touched']:,}, "
          f"query {u['query_median_s']*1e3:.1f} -> "
          f"{p['query_median_s']*1e3:.1f} ms "
          f"({sweep['query_speedup']}x)")
    return sweep


def _block_skip_sweep(size: str, repeats: int = 5) -> list[dict]:
    """Intra-run block skipping (the second pruning level): a clustered
    dataset (rows sorted by the primary key, a time-ordered ``unique2``-like
    column with no secondary index) takes selective range predicates of
    decreasing selectivity, with the bind-time block zone-map test on vs.
    off. Reports latency plus the blocks-touched accounting from the
    physical plan — the blocks scanned must shrink proportionally to the
    predicate's block footprint. Runs in kernel mode: the filter_count grid
    is driven through the surviving-block list."""
    base_rows, _, _ = SIZES[size]
    n = max(base_rows, 8 * 4096)  # at least 8 zone blocks
    ids = np.arange(n, dtype=np.int32)
    rng = np.random.default_rng(11)
    table_cols = {"id": ids, "ts": ids.copy(),
                  "val": rng.integers(0, 100, n).astype(np.int32)}
    from repro.engine.table import Table

    sess = Session(mode="kernel", enable_index=False)
    sess.create_dataset("Clustered", Table(table_cols), dataverse="bench",
                        primary="id")
    df = AFrame("bench", "Clustered", session=sess)
    n_blocks = -(-n // 4096)
    rows = []
    for label, span_blocks in (("1-block", 1),
                               ("10pct", max(n_blocks // 10, 1)),
                               ("50pct", max(n_blocks // 2, 1))):
        lo = 4096  # start on a block boundary past block 0
        hi = min(lo + span_blocks * 4096 - 1, n - 1)
        cell: dict = {"size": size, "variant": "block_skip",
                      "selectivity": label, "n_rows": n,
                      "blocks_total": n_blocks}
        for skip in (True, False):
            sess.enable_block_skip = skip
            tag = "skipped" if skip else "unskipped"
            want = hi - lo + 1
            got = len(df[(df["ts"] >= lo) & (df["ts"] <= hi)])  # warm/compile
            assert got == want, (got, want)
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                len(df[(df["ts"] >= lo) & (df["ts"] <= hi)])
                times.append(time.perf_counter() - t0)
            rep = sess.last_prune_report
            cell[tag] = {
                "query_median_s": round(float(np.median(times)), 5),
                "blocks_scanned": int(rep["blocks_scanned"]),
                "blocks_skipped": int(rep["blocks_skipped"]),
            }
        sess.enable_block_skip = True
        s, u = cell["skipped"], cell["unskipped"]
        cell["query_speedup"] = round(
            u["query_median_s"] / max(s["query_median_s"], 1e-9), 2)
        print(f"  {size:>2} block_skip {label:<8} blocks "
              f"{u['blocks_scanned']} -> {s['blocks_scanned']} "
              f"of {n_blocks}  query {u['query_median_s']*1e3:.2f} -> "
              f"{s['query_median_s']*1e3:.2f} ms "
              f"({cell['query_speedup']}x)")
        rows.append(cell)
    return rows


def _string_predicate_sweep(size: str, repeats: int = 5) -> list[dict]:
    """String fast-path sweep (the PR 9 tentpole): equality predicates on a
    LOW-cardinality clustered string column (dictionary-id lane → lowered
    onto the filter_count kernel, dict-id zone maps skip blocks) and on a
    HIGH-cardinality clustered column (past DICT_THRESHOLD: no dict lane,
    the big-endian prefix lane's zone maps do the skipping), each with the
    bind-time block test on vs. off. Reports latency, blocks touched, and
    whether the plan lowered onto the kernel."""
    from repro.core import physical as PH
    from repro.engine.table import Table, encode_strings

    base_rows, _, _ = SIZES[size]
    n = max(base_rows, 8 * 4096)
    n_blocks = -(-n // 4096)
    # low cardinality: one tag per zone block (16 distinct << threshold);
    # high cardinality: sorted unique names (prefix spans are disjoint)
    lo_tags = ["T%02d" % ((i // 4096) % 16) for i in range(n)]
    hi_names = ["u%07d" % i for i in range(n)]
    sess = Session(mode="kernel", enable_index=False)
    sess.create_dataset("Str", Table({
        "id": np.arange(n, dtype=np.int32),
        "tag": encode_strings(lo_tags),
        "name": encode_strings(hi_names),
    }), dataverse="bench", primary="id")
    df = AFrame("bench", "Str", session=sess)
    rows = []
    for label, col, lit, want in (
            ("low-card:dict", "tag", "T03", 4096 * len(
                [b for b in range(n_blocks) if b % 16 == 3])),
            ("high-card:prefix", "name", "u%07d" % (4096 * 2 + 7), 1)):
        cell: dict = {"size": size, "variant": "string_predicate",
                      "column": col, "cardinality": label.split(":")[0],
                      "pruning_lane": label.split(":")[1], "n_rows": n,
                      "blocks_total": n_blocks}
        for skip in (True, False):
            sess.enable_block_skip = skip
            tag = "skipped" if skip else "unskipped"
            got = len(df[df[col] == lit])  # warm/compile
            assert got == want, (label, got, want)
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                len(df[df[col] == lit])
                times.append(time.perf_counter() - t0)
            rep = sess.last_prune_report
            cell[tag] = {
                "query_median_s": round(float(np.median(times)), 5),
                "blocks_scanned": int(rep["blocks_scanned"]),
                "blocks_skipped": int(rep["blocks_skipped"]),
            }
        sess.enable_block_skip = True
        cell["kernel_lowered"] = any(
            isinstance(nd, PH.KernelRangeCount)
            for nd in PH.walk(sess.last_physical))
        s, u = cell["skipped"], cell["unskipped"]
        cell["query_speedup"] = round(
            u["query_median_s"] / max(s["query_median_s"], 1e-9), 2)
        print(f"  {size:>2} string_predicate {label:<16} blocks "
              f"{u['blocks_scanned']} -> {s['blocks_scanned']} "
              f"of {n_blocks}  kernel={cell['kernel_lowered']}  query "
              f"{u['query_median_s']*1e3:.2f} -> "
              f"{s['query_median_s']*1e3:.2f} ms "
              f"({cell['query_speedup']}x)")
        rows.append(cell)
    return rows


def _block_skip_sharded_sweep(size: str, repeats: int = 5,
                              devices: int = 8) -> list[dict]:
    """Multi-shard variant of the block-skip sweep: the same clustered
    dataset laid out over a ``devices``-way mesh of this process's first
    devices (raises when fewer exist — on the CPU, provide them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``), where the zone
    maps are harvested per row partition and each shard's kernel grid
    scans only its own surviving blocks."""
    from repro.engine.table import Table
    from repro.launch.mesh import make_local_mesh
    from repro.runtime import telemetry as tel

    base_rows, _, _ = SIZES[size]
    n = max(base_rows, devices * 4096)
    n -= n % devices  # even row partitions -> the sharded zone-map layout
    ids = np.arange(n, dtype=np.int32)
    rng = np.random.default_rng(11)
    sess = Session(mesh=make_local_mesh(data=devices, model=1),
                   mode="kernel", enable_index=False)
    sess.create_dataset(
        "Clustered",
        Table({"id": ids, "ts": ids.copy(),
               "val": rng.integers(0, 100, n).astype(np.int32)}),
        dataverse="bench", primary="id")
    df = AFrame("bench", "Clustered", session=sess)
    n_blocks = sess.catalog.get("bench", "Clustered").block_zones.n_blocks
    skipped_before = tel.counter_value("kernel.blocks_skipped_total",
                                       kernel="filter_count") or 0
    cells = []
    for label, span_blocks in (("1-block", 1),
                               ("10pct", max(n_blocks // 10, 1)),
                               ("50pct", max(n_blocks // 2, 1))):
        lo = 4096
        hi = min(lo + span_blocks * 4096 - 1, n - 1)
        cell = {"size": size, "variant": "block_skip_sharded",
                "selectivity": label, "n_rows": n, "shards": devices,
                "blocks_total": n_blocks}
        for skip in (True, False):
            sess.enable_block_skip = skip
            tag = "skipped" if skip else "unskipped"
            want = hi - lo + 1
            got = len(df[(df["ts"] >= lo) & (df["ts"] <= hi)])  # warm/compile
            assert got == want, (got, want)
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                len(df[(df["ts"] >= lo) & (df["ts"] <= hi)])
                times.append(time.perf_counter() - t0)
            rep = sess.last_prune_report
            cell[tag] = {
                "query_median_s": round(float(np.median(times)), 5),
                "blocks_scanned": int(rep["blocks_scanned"]),
                "blocks_skipped": int(rep["blocks_skipped"]),
            }
        sess.enable_block_skip = True
        s, u = cell["skipped"], cell["unskipped"]
        cell["query_speedup"] = round(
            u["query_median_s"] / max(s["query_median_s"], 1e-9), 2)
        cells.append(cell)
        print(f"  {size:>2} block_skip_sharded {label:<8} "
              f"({devices} shards) blocks {u['blocks_scanned']} -> "
              f"{s['blocks_scanned']} of {n_blocks}  query "
              f"{u['query_median_s']*1e3:.2f} -> "
              f"{s['query_median_s']*1e3:.2f} ms ({cell['query_speedup']}x)")
    skipped = (tel.counter_value("kernel.blocks_skipped_total",
                                 kernel="filter_count") or 0) - skipped_before
    cells.append({"size": size, "variant": "block_skip_sharded:telemetry",
                  "blocks_skipped_total": int(skipped)})
    return cells


# Hard cap on the background cell's reader tail latency: generously above a
# post-flush recompile, far below an O(base) merge a blocked reader would eat.
READER_P99_CAP_S = 2.0


def _serving_cell(size: str, serving: str) -> dict:
    """One concurrent-serving cell: writer replays the stream while a reader
    thread on the shared catalog runs an indexed range count continuously."""
    base_rows, n_batches, batch_rows = SIZES[size]
    sess = Session()
    sess.create_dataset("Serve", wisconsin.generate(base_rows, seed=7),
                        dataverse="bench", indexes=["onePercent"],
                        primary="unique2")
    # real triggers, small cap: compaction fires repeatedly during the replay
    policy = lsm.CompactionPolicy(size_ratio=1.0, max_runs=4)
    reader = Session(catalog=sess.catalog)
    rdf = AFrame("bench", "Serve", session=reader)
    len(rdf[(rdf["onePercent"] >= 10) & (rdf["onePercent"] <= 30)])  # warm

    stop = threading.Event()
    lat: list[float] = []

    def read_loop():
        while not stop.is_set():
            t0 = time.perf_counter()
            len(rdf[(rdf["onePercent"] >= 10) & (rdf["onePercent"] <= 30)])
            lat.append(time.perf_counter() - t0)

    bc = (lsm.BackgroundCompactor(sess, policy=policy)
          if serving == "background" else None)
    feed = Feed(sess, "Serve", "bench", flush_rows=batch_rows,
                policy=policy, compactor=bc)
    batches = _stream(base_rows, n_batches, batch_rows)
    t = threading.Thread(target=read_loop, daemon=True)
    t.start()
    write_lat = []
    t_all = time.perf_counter()
    try:
        for rows in batches:
            t0 = time.perf_counter()
            feed.push(rows)  # flush_rows == batch_rows: flushes synchronously
            write_lat.append(time.perf_counter() - t0)
        if bc is not None:
            bc.wait_idle(60.0)
        ingest_s = time.perf_counter() - t_all
    finally:
        stop.set()
        t.join(timeout=30.0)
        if bc is not None:
            bc.close()
    lat_arr = np.asarray(lat) if lat else np.asarray([0.0])
    cell = {
        "size": size,
        "variant": f"serving:{serving}",
        "serving": serving,
        "rows": n_batches * batch_rows,
        "ingest_s": round(ingest_s, 4),
        "rows_per_s": round(n_batches * batch_rows / ingest_s, 1),
        "writer_batch_p50_s": round(float(np.median(write_lat)), 4),
        "writer_batch_p99_s": round(float(np.percentile(write_lat, 99)), 4),
        "reader_queries": len(lat),
        "reader_p50_s": round(float(np.median(lat_arr)), 5),
        "reader_p99_s": round(float(np.percentile(lat_arr, 99)), 5),
        "reader_max_s": round(float(lat_arr.max()), 5),
        "write_stalls": feed.stats.get("stalls", 0),
        "write_stall_s": round(feed.stats.get("stall_s", 0.0), 4),
        "compactions": feed.stats["compactions"] + (
            bc.stats["compactions"] + bc.stats["level_merges"]
            if bc is not None else 0),
        "final_runs": len(sess.catalog.get("bench", "Serve").runs),
    }
    if serving == "background":
        assert cell["reader_p99_s"] < READER_P99_CAP_S, (
            f"reader p99 {cell['reader_p99_s']}s breaches the no-block cap "
            f"({READER_P99_CAP_S}s) — a query waited on compaction")
    return cell


def _serving_sweep(size: str) -> list[dict]:
    rows = []
    per = {}
    for serving in ("synchronous", "background"):
        r = _serving_cell(size, serving)
        per[serving] = r
        rows.append(r)
        print(f"  {size:>2} serving:{serving:<12} "
              f"reader p50 {r['reader_p50_s']*1e3:6.1f} ms  "
              f"p99 {r['reader_p99_s']*1e3:7.1f} ms  "
              f"writer batch p99 {r['writer_batch_p99_s']*1e3:7.1f} ms  "
              f"stall {r['write_stall_s']*1e3:6.1f} ms  "
              f"({r['reader_queries']} reads, "
              f"{r['compactions']} compactions)")
    speedup = (per["synchronous"]["writer_batch_p99_s"]
               / max(per["background"]["writer_batch_p99_s"], 1e-9))
    rows.append({"size": size, "variant": "serving:speedup",
                 "writer_p99_speedup": round(speedup, 2)})
    print(f"  {size:>2} background-compaction writer p99 speedup: "
          f"{speedup:.1f}x")
    return rows


# mutation mix per workload: fractions of batches issued as (push, upsert,
# delete); deletes target previously-ingested keys, upserts overwrite them.
MUTATION_WORKLOADS = {
    "append-only": (1.0, 0.0, 0.0),
    "upsert-heavy": (0.4, 0.6, 0.0),
    "delete-heavy": (0.4, 0.2, 0.4),
}


def _run_mutation_cell(size: str, workload: str, variant: str) -> dict:
    """One mutation-sweep cell: replay the stream with the workload's
    push/upsert/delete mix, measure sustained mutation ops/sec and post-
    flush freshness, then assert uncompacted == compacted."""
    base_rows, n_batches, batch_rows = SIZES[size]
    base = wisconsin.generate(base_rows, seed=7)
    sess = Session()
    sess.create_dataset("MutStream", base, dataverse="bench",
                        indexes=["onePercent"], primary="unique2")
    feed = Feed(sess, "MutStream", "bench", flush_rows=batch_rows,
                policy=POLICIES[variant]())
    df = AFrame("bench", "MutStream", session=sess)
    len(df)  # warm the base-only count executable

    push_f, upsert_f, delete_f = MUTATION_WORKLOADS[workload]
    rng = np.random.default_rng(13)
    batches = _stream(base_rows, n_batches, batch_rows)
    kinds = rng.choice(["push", "upsert", "delete"], size=n_batches,
                       p=[push_f, upsert_f, delete_f])
    hi_key = base_rows
    ops = 0
    mutate_s = 0.0
    freshness = []
    for i, rows in enumerate(batches):
        kind = kinds[i]
        t0 = time.perf_counter()
        if kind == "push":
            feed.push(rows)
            hi_key = int(np.asarray(rows["unique2"]).max()) + 1
        elif kind == "upsert":
            rows = dict(rows)
            rows["unique2"] = rng.choice(hi_key, size=batch_rows,
                                         replace=False).astype(
                np.asarray(rows["unique2"]).dtype)
            feed.upsert(rows)
        else:
            keys = rng.choice(hi_key, size=batch_rows, replace=False)
            feed.delete(keys.astype(np.asarray(rows["unique2"]).dtype))
        feed.flush()
        mutate_s += time.perf_counter() - t0
        ops += batch_rows
        t0 = time.perf_counter()
        len(df)
        len(df[(df["onePercent"] >= 10) & (df["onePercent"] <= 30)])
        freshness.append(time.perf_counter() - t0)
    uncompacted = len(df)
    feed.compact()
    assert len(df) == uncompacted, "mutation invariant violated"
    return {
        "size": size,
        "variant": f"mutation:{workload}:{variant}",
        "workload": workload,
        "policy": variant,
        "ops": ops,
        "ops_per_s": round(ops / mutate_s, 1),
        "freshness_median_s": round(float(np.median(freshness)), 4),
        "freshness_p95_s": round(float(np.percentile(freshness, 95)), 4),
        "flushes": feed.stats["flushes"],
        "compactions": feed.stats["compactions"],
        "level_merges": feed.stats["level_merges"],
        "final_rows": uncompacted,
        "mutation_ops": int(feed.stats["deletes"] + feed.stats["upserts"]),
        "tombstones_flushed": int(feed.stats["tombstones_flushed"]),
    }


def _mutation_sweep(size: str) -> list[dict]:
    rows = []
    for workload in MUTATION_WORKLOADS:
        per_policy = {}
        for variant in POLICIES:
            r = _run_mutation_cell(size, workload, variant)
            per_policy[variant] = r
            rows.append(r)
            print(f"  {size:>2} {workload:<13} {variant:<20} "
                  f"{r['ops_per_s']:>10,.0f} ops/s  freshness p50 "
                  f"{r['freshness_median_s'] * 1e3:6.1f} ms  "
                  f"(compactions={r['compactions']})")
        speedup = (per_policy["deferred"]["ops_per_s"]
                   / per_policy["compact-every-flush"]["ops_per_s"])
        rows.append({"size": size, "variant": f"mutation:{workload}:speedup",
                     "mutation_speedup": round(speedup, 2)})
    return rows


def _durability_cell(size: str, durability: str) -> dict:
    """One durability cell: the deferred-policy stream with the WAL off
    (memory-only), on with per-batch fsync, on without fsync, or on with
    compact-every-flush (the 1-component recovery point). Durable cells
    additionally close the session and time ``Session.open`` cold-start
    recovery over the resulting component chain."""
    import shutil
    import tempfile

    from repro.runtime.durable import DurableStore

    base_rows, n_batches, batch_rows = SIZES[size]
    base = wisconsin.generate(base_rows, seed=7)
    policy = lsm.CompactionPolicy(size_ratio=0.0) \
        if durability == "wal-fsync-compacted" \
        else lsm.CompactionPolicy(size_ratio=1.0, max_runs=8)
    tmp = None
    if durability == "memory-only":
        sess = Session()
    else:
        tmp = tempfile.mkdtemp(prefix="repro-durability-")
        store = DurableStore(tmp, wal_fsync=(durability != "wal-nofsync"))
        sess = Session(storage=store)
    sess.create_dataset("Stream", base, dataverse="bench",
                        indexes=["onePercent"], primary="unique2")
    feed = Feed(sess, "Stream", "bench", flush_rows=batch_rows, policy=policy)
    batches = _stream(base_rows, n_batches, batch_rows)
    ingest_s = 0.0
    # batch 0 is the warm-up: it pays the flush-path compilations (cached
    # process-wide by shape), which would otherwise bill the first cell
    for i, rows in enumerate(batches):
        t0 = time.perf_counter()
        feed.push(rows)  # flush_rows == batch_rows: flushes synchronously
        if i > 0:
            ingest_s += time.perf_counter() - t0
    total_rows = (n_batches - 1) * batch_rows
    out = {
        "size": size,
        "variant": "durability",
        "durability": durability,
        "rows": total_rows,
        "ingest_s": round(ingest_s, 4),
        "rows_per_s": round(total_rows / ingest_s, 1),
        "components": 1 + len(sess.catalog.get("bench", "Stream").runs),
    }
    if tmp is not None:
        expect = base_rows + n_batches * batch_rows
        sess.close()
        t0 = time.perf_counter()
        re = Session.open(tmp)
        recovery_s = time.perf_counter() - t0
        n = len(AFrame("bench", "Stream", session=re))
        assert n == expect, (n, expect)
        out["recovery_s"] = round(recovery_s, 4)
        re.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _durability_sweep(size: str) -> list[dict]:
    """WAL-on vs memory-only ingest throughput, fsync-batching sensitivity,
    and cold-start recovery latency vs resident component count."""
    _durability_cell(size, "memory-only")  # throwaway pass: warm every
    #                                        flush/compaction executable so
    #                                        no timed cell bills compiles
    cells = [_durability_cell(size, d) for d in
             ("memory-only", "wal-fsync", "wal-nofsync",
              "wal-fsync-compacted")]
    by = {c["durability"]: c for c in cells}
    overhead = by["memory-only"]["rows_per_s"] / by["wal-fsync"]["rows_per_s"]
    fsync_cost = (by["wal-nofsync"]["rows_per_s"]
                  / by["wal-fsync"]["rows_per_s"])
    for c in cells:
        rec = f"  recovery {c['recovery_s'] * 1e3:7.1f} ms " \
              f"({c['components']} comps)" if "recovery_s" in c else ""
        print(f"  {size:>2} durability {c['durability']:<20} "
              f"{c['rows_per_s']:>12,.0f} rows/s{rec}")
    print(f"  {size:>2} WAL ingest overhead: {overhead:.2f}x   "
          f"fsync cost: {fsync_cost:.2f}x")
    cells.append({"size": size, "variant": "durability",
                  "durability": "summary",
                  "wal_overhead_x": round(overhead, 3),
                  "fsync_cost_x": round(fsync_cost, 3)})
    return cells


def run_ingest_bench(sizes=None, out_path: pathlib.Path | None = None) -> list[dict]:
    names = list(sizes) if sizes else ["XS", "S"]
    rows = []
    for size in names:
        per_size = {}
        for variant in POLICIES:
            r = _run_variant(size, variant)
            per_size[variant] = r
            rows.append(r)
            print(f"  {size:>2} {variant:<20} {r['rows_per_s']:>12,.0f} rows/s  "
                  f"freshness p50 {r['freshness_median_s'] * 1e3:7.1f} ms  "
                  f"(compactions={r['compactions']})")
        speedup = (per_size["deferred"]["rows_per_s"]
                   / per_size["compact-every-flush"]["rows_per_s"])
        print(f"  {size:>2} deferred-compaction ingest speedup: {speedup:.1f}x")
        rows.append({"size": size, "variant": "speedup",
                     "ingest_speedup": round(speedup, 2)})
        rows.extend(_block_skip_sweep(size))
        rows.extend(_string_predicate_sweep(size))
        rows.extend(_block_skip_sharded_sweep(size))
        rows.extend(_mutation_sweep(size))
        rows.extend(_serving_sweep(size))
        rows.extend(_durability_sweep(size))
    # attach the engine-wide telemetry snapshot (counters/gauges/histograms
    # accumulated across every sweep above — plan cache, flush/compaction,
    # write stalls, retired-manifest bytes, kernel launches); spans are
    # dropped: the ring holds only the trailing queries and bloats the file.
    from repro.runtime import telemetry as tel
    rows.append({"variant": "telemetry",
                 "snapshot": tel.snapshot(include_spans=False)})
    if out_path is not None:
        out_path.write_text(json.dumps(rows, indent=2) + "\n")
        print(f"ingest benchmark -> {out_path}")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=str, default="XS,S")
    args = ap.parse_args()
    out = pathlib.Path(__file__).resolve().parents[1] / "results" / "bench"
    out.mkdir(parents=True, exist_ok=True)
    run_ingest_bench(args.sizes.split(","), out / "ingest.json")
