"""Smoke run of the AFrame engine's main path on a TPU.

    python chip_smoke.py                    # one chip, the paper's XL scale
    python chip_smoke.py --four-chips       # the row-partitioned path, 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 50000          # rehearsal
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --rows 50000 --four-chips           # rehearsal

Everything runs in this one process, through the entry points a user calls
(``Session``, ``AFrame``, ``Feed``, ``model_udf``). One chip: the Wisconsin
table at the paper's XL size (5M rows of 100 B) is loaded into a gspmd and a
kernel session, the paper's 12 expressions run in both and are checked
exactly against the numpy oracle of ``benchmarks/wisconsin_bench.py``, the
kernel session must have launched every Pallas relational kernel compiled
(not interpreted), a feed of pushes/upserts/deletes with a materialized view
is checked against a newest-wins oracle, and ``paper-lm`` at its published
widths scores a token column through ``AFrame.map``. ``--four-chips`` runs
only the sharded path: the same tables row-partitioned over a 4-device mesh
in shard_map and kernel sessions, the 12 expressions, a block-skipping range
count and a shard-routed point lookup, all against the same oracle.

Each phase prints one JSON line (its result, seconds and check); the last
line is ``{"ok": ..., "device": {"platform", "kind", "count"}}``. ``ok`` is
true only when every phase passed on a TPU at the full 5M rows; the exit
code is 0 only then. ``--rows`` shrinks the Wisconsin tables and nothing
else, so a run with it never ends ``ok: true``. Without an accelerator the
script needs ``--rows`` (a CPU rehearsal, Pallas in interpret mode, a
64-row token column): it then runs every phase and still ends ``ok: false``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

XL_ROWS = 5_000_000             # the paper's XL Wisconsin size (§IV-A)
SEED = 11                       # data seed (wisconsin_bench.build_variants)
LITERAL_SEED = 5                # literal seed (wisconsin_bench.run_benchmark)
TOKEN_ROWS, TOKEN_LEN = 4096, 128
CPU_TOKEN_ROWS = 64             # the token column of a CPU rehearsal
UDF_MICROBATCH = 512
DV = "bench"


def _plain(o):
    import numpy as np

    if isinstance(o, np.generic):
        return o.item()
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def emit(rec: dict) -> None:
    print(json.dumps(rec, default=_plain), flush=True)


def bytes_in_use(devices) -> list:
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]


def operators(sess) -> str:
    from repro.core import physical as PH

    names = []
    for node in PH.walk(sess.last_physical):
        n = type(node).__name__
        if n not in names:
            names.append(n)
    return ">".join(names)


# -- the 12 paper expressions vs the numpy oracle -----------------------------


def _canon(name: str, out):
    """Both sides of one expression in one comparable form."""
    import numpy as np

    if name == "4_group_count":
        if isinstance(out, dict):
            return np.asarray(out["count"])[np.argsort(out["oddOnePercent"])]
        return np.asarray(out)
    if name == "8_group_max":
        if "twenty" in out:
            return {int(k): int(v) for k, v in zip(out["twenty"], out["max_four"])}
        return {int(k): int(v) for k, v in out.items()}
    if name == "5_map_head":
        return np.asarray(out["stringu1"] if isinstance(out, dict) else out)
    if isinstance(out, dict):
        return {k: np.asarray(v) for k, v in out.items()}
    return int(np.asarray(out))


def _equal(got, want) -> bool:
    import numpy as np

    if isinstance(want, dict) and isinstance(got, dict):
        if not set(want) <= set(got):
            return False
        return all(_equal(got[k], want[k]) for k in want)
    if isinstance(want, np.ndarray):
        got = np.asarray(got)
        return got.shape == want.shape and bool(np.array_equal(got, want))
    return got == want


def run_expressions(label: str, sess, oracle, n_rows: int):
    """Each expression twice with the benchmark's seeded literals: the first
    call compiles, the second is warm. Both must equal the oracle. Returns
    (all equal, the operators each expression ran)."""
    import jax
    import numpy as np
    from benchmarks.wisconsin_bench import EXPRESSIONS, AFrameVariant

    v = AFrameVariant(label, sess, "data").create()
    all_ok, plans = True, {}
    for name, fn in EXPRESSIONS:
        rng_v = np.random.default_rng(LITERAL_SEED)
        rng_o = np.random.default_rng(LITERAL_SEED)
        times, same = [], []
        for _ in range(2):
            t0 = time.perf_counter()
            got = jax.block_until_ready(fn(v, rng_v, n_rows))
            times.append(time.perf_counter() - t0)
            want = fn(oracle, rng_o, n_rows)
            same.append(_equal(_canon(name, got), _canon(name, want)))
        ok = all(same)
        all_ok &= ok
        plans[name] = operators(sess)
        emit({"phase": "queries", "session": label, "expression": name,
              "ok": ok, "first_call_s": times[0], "warm_call_s": times[1],
              "operators": plans[name],
              "check": "equals the numpy oracle on both calls"})
    return all_ok, plans


# -- phases --------------------------------------------------------------------


def load(sessions: dict, table, devices) -> dict:
    """``data`` and ``data_r`` in every session, indexed as aframe-index."""
    for sess in sessions.values():
        for name in ("data", "data_r"):
            sess.create_dataset(name, table, dataverse=DV, closed=True,
                                indexes=["onePercent", "unique1"],
                                primary="unique2")
    return {"sessions": list(sessions), "bytes_in_use": bytes_in_use(devices),
            "check": "datasets registered; bytes_in_use per device"}


def oracle_for(table):
    import numpy as np
    from benchmarks.wisconsin_bench import NumpyEager

    o = NumpyEager(disk_dir=None)
    o.df = {k: np.asarray(v) for k, v in table.columns.items()}
    return o


def launches(kernel: str) -> int:
    from repro.runtime import telemetry as tel

    return tel.counter_value("kernel.launches_total", kernel=kernel,
                             backend="pallas", interpret="false") or 0


def kernels_phase(plans: dict) -> dict:
    """Every relational kernel ran as compiled Pallas, and group-by reached
    segment_agg with both a sum-shaped and a max-shaped aggregate."""
    from repro.runtime import telemetry as tel

    want = ("filter_count", "segment_agg", "merge_join_count", "topk")
    counts = {k: launches(k) for k in want}
    seg = {e: "KernelSegmentAgg" in plans.get(e, "")
           for e in ("4_group_count", "8_group_max")}
    return {"launches": counts, "segment_agg_plans": seg,
            "launch_series": tel.registry().counters("kernel.launches_total"),
            "ok": all(counts.values()) and all(seg.values()),
            "check": "kernel.launches_total{backend=pallas,interpret=false} "
                     "> 0 for each kernel; expressions 4 (sum) and 8 (max) "
                     "ran KernelSegmentAgg"}


def ingest_phase(sess, n_rows: int) -> dict:
    """A Feed of pushes, upserts and deletes over a fresh keyed dataset,
    flushed into runs, then compacted by the background compactor; a
    group-by view (count, sum, max) is maintained throughout."""
    import numpy as np
    from repro.core import plan as P
    from repro.core.frame import AFrame
    from repro.engine import lsm
    from repro.engine.ingest import Feed
    from repro.engine.table import Table
    from repro.runtime import telemetry as tel

    rng = np.random.default_rng(SEED + 1)
    base_n = max(n_rows // 5, 8192)
    batch = max(base_n // 16, 1024)

    # values below 8 keep every batch's f32 partial sums under 2^24 (the
    # seed's 1M rows included), the view's gate for the segment_agg kernel;
    # back to a realistic range once ROADMAP Queue 1 item 6 removes the gate
    def rows(ids):
        return {"id": ids.astype(np.int32),
                "grp": rng.integers(0, 100, len(ids)).astype(np.int32),
                "val": rng.integers(0, 8, len(ids)).astype(np.int32)}

    truth = {}  # newest-wins oracle: id -> (grp, val)

    def apply(kind, payload):
        if kind == "delete":
            for k in payload.tolist():
                truth.pop(k, None)
        else:
            for k, g, v in zip(payload["id"].tolist(), payload["grp"].tolist(),
                               payload["val"].tolist()):
                truth[k] = (g, v)

    base = rows(np.arange(base_n))
    apply("push", base)
    sess.create_dataset("events", Table(base), dataverse="ingest",
                        primary="id")
    view_plan = P.GroupAgg(P.Scan("events", "ingest"), ["grp"], [
        P.AggSpec("count", "count", None), P.AggSpec("sum_val", "sum", "val"),
        P.AggSpec("max_val", "max", "val")])
    view = sess.create_view("events_by_grp", view_plan)
    seg_before = launches("segment_agg")
    policy = lsm.CompactionPolicy(size_ratio=1e9, max_runs=64)
    feed = Feed(sess, "events", "ingest", flush_rows=10 ** 9, policy=policy)
    next_id = base_n
    for step in range(4):
        batches = [("push", rows(np.arange(next_id, next_id + batch))),
                   ("upsert", rows(rng.choice(next_id, batch, replace=False))),
                   ("delete", rng.choice(next_id, batch // 4, replace=False)
                    .astype(np.int32))]
        next_id += batch
        for kind, payload in batches:
            getattr(feed, kind)(payload)
            apply(kind, payload)
        feed.flush()
    n_runs = len(sess.catalog.get("ingest", "events").manifest.runs)

    keys = np.fromiter(truth, np.int64)
    vals = np.array([truth[k] for k in keys.tolist()], np.int64)
    lo, hi = base_n // 3, base_n + 2 * batch
    want_range = int(((keys >= lo) & (keys <= hi)).sum())
    df = AFrame("ingest", "events", session=sess)

    def range_count():
        return len(df[(df["id"] >= lo) & (df["id"] <= hi)])

    got_runs = range_count()
    with lsm.BackgroundCompactor(sess, lsm.CompactionPolicy(size_ratio=0.0)) as bc:
        bc.notify("ingest", "events")
        idle = bc.wait_idle(600.0)
    got_compacted = range_count()
    runs_after = len(sess.catalog.get("ingest", "events").manifest.runs)

    g = vals[:, 0]
    want_view = {int(k): (int((g == k).sum()), int(vals[g == k, 1].sum()),
                          int(vals[g == k, 1].max())) for k in np.unique(g)}
    res = view.result()
    got_view = {int(k): (int(c), int(s), int(m)) for k, c, s, m in zip(
        res["grp"], res["count"], res["sum_val"], res["max_val"])}
    view_kernel = view.stats["kernel_batches"] > 0 \
        and launches("segment_agg") > seg_before
    errors = tel.counter_value("lsm.compactor.errors_total") or 0
    checks = {"range_count_runs": got_runs == want_range,
              "range_count_compacted": got_compacted == want_range,
              "view": got_view == want_view, "view_ran_pallas": view_kernel,
              "runs_resident_then_compacted": n_runs >= 2 and runs_after == 0,
              "compactor_idle_no_errors": idle and errors == 0}
    return {"ok": all(checks.values()), "checks": checks,
            "rows": len(truth), "runs_before_compaction": n_runs,
            "range_count": got_runs, "range_count_compacted": got_compacted,
            "range_count_oracle": want_range, "view_groups": len(got_view),
            "view_kernel_batches": view.stats["kernel_batches"],
            "compactor_errors": errors,
            "check": "range count over base+runs and after compaction, and "
                     "the view, equal the newest-wins oracle; the view ran "
                     "the Pallas segment_agg"}


def model_udf_phase(sess, n_tokens: int) -> dict:
    """paper-lm at its published widths scores a token column through
    AFrame.map; predictions must match a direct jitted call."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core.frame import AFrame
    from repro.engine.table import Table
    from repro.models.registry import get_api
    from repro.udf import model_udf

    cfg = get_config("paper-lm")
    params = get_api(cfg).init(jax.random.key(SEED), cfg)
    handle = model_udf.register_model("sentiment", params, cfg, classes=3,
                                      microbatch=UDF_MICROBATCH)
    tokens = np.random.default_rng(SEED).integers(
        0, cfg.vocab, (n_tokens, TOKEN_LEN)).astype(np.int32)
    sess.create_dataset("tweets", Table({
        "id": np.arange(n_tokens, dtype=np.int32), "tokens": tokens}),
        dataverse="udf")
    df = AFrame("udf", "tweets", session=sess).map(handle, "tokens",
                                                   name="sentiment")
    t0 = time.perf_counter()
    out = df[["id", "sentiment"]].collect()
    udf_s = time.perf_counter() - t0
    got = np.asarray(out["sentiment"])[np.argsort(out["id"])]
    t0 = time.perf_counter()
    direct = np.asarray(jax.jit(model_udf.get_udf("sentiment"))(tokens))
    direct_s = time.perf_counter() - t0
    differ = float(np.mean(got != direct)) if got.shape == direct.shape else 1.0
    return {"ok": differ <= 1e-3, "tokens": [n_tokens, TOKEN_LEN],
            "d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "share_differing": differ, "udf_first_call_s": udf_s,
            "direct_first_call_s": direct_s,
            "check": ">= 99.9% of AFrame.map predictions equal a direct "
                     "jitted call"}


def four_chip_extras(sess, table, n_rows: int) -> dict:
    """Per-shard block skipping on a clustered column, and a point lookup
    routed to the owning shard."""
    import numpy as np
    from repro.core import physical as PH
    from repro.core.frame import AFrame
    from repro.engine.table import Table
    from repro.runtime import telemetry as tel

    ids = np.arange(n_rows, dtype=np.int32)
    sess.create_dataset("clustered", Table({"id": ids, "ts": ids.copy()}),
                        dataverse=DV, primary="id")
    df = AFrame(DV, "clustered", session=sess)
    skipped0 = tel.counter_value("kernel.blocks_skipped_total",
                                 kernel="filter_count") or 0
    lo = n_rows // 4 + 4096           # one zone block inside shard 1
    hi = lo + 4096 - 1
    got = len(df[(df["ts"] >= lo) & (df["ts"] <= hi)])
    rep = sess.last_prune_report
    krc = any(isinstance(n, PH.KernelRangeCount)
              for n in PH.walk(sess.last_physical))
    skipped = (tel.counter_value("kernel.blocks_skipped_total",
                                 kernel="filter_count") or 0) - skipped0
    key = 3 * n_rows // 4 + 17        # a row of shard 3
    row = AFrame(DV, "data", session=sess).get(key)
    ph = sess.last_physical
    raw = {k: np.asarray(v) for k, v in table.columns.items()}
    row_ok = row is not None and all(
        np.array_equal(np.asarray(row[k])[0], raw[k][key]) for k in raw)
    checks = {"range_count": got == hi - lo + 1,
              "kernel_range_count": krc,
              "blocks_skipped": rep["blocks_skipped"] > 0 and skipped > 0,
              "lookup_row": row_ok,
              "lookup_routed": ph.shards == 4 and 1 <= ph.shard_probes < 4}
    return {"ok": all(checks.values()), "checks": checks,
            "range_count": got, "range_count_oracle": hi - lo + 1,
            "blocks_scanned": rep["blocks_scanned"],
            "blocks_skipped": rep["blocks_skipped"],
            "lookup_shards": ph.shards, "lookup_shard_probes": ph.shard_probes,
            "check": "range count exact with blocks skipped by per-shard "
                     "kernel grids; get(key) equals the oracle row and "
                     "searched fewer than all shards"}


def timed(phase: str, fn, *args) -> bool:
    t0 = time.perf_counter()
    rec = fn(*args)
    rec = {"phase": phase, "ok": rec.pop("ok", True),
           "seconds": time.perf_counter() - t0, **rec}
    emit(rec)
    return rec["ok"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="Wisconsin rows (default: the paper's XL, 5M); "
                         "needed without an accelerator, and a run with "
                         "another size never reports ok")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-partitioned path on 4 devices")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and args.rows is None:
        print(f"no accelerator (JAX platform {devices[0].platform!r}); "
              "a CPU rehearsal needs --rows", file=sys.stderr)
        return 2
    n_rows = args.rows or XL_ROWS

    from repro.data import wisconsin
    from repro.engine.session import Session

    oks = []
    t0 = time.perf_counter()
    table = wisconsin.generate(n_rows, seed=SEED)
    oracle = oracle_for(table)
    emit({"phase": "generate", "ok": True, "rows": n_rows,
          "seconds": time.perf_counter() - t0})

    if args.four_chips:
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(data=4, model=1)
        sessions = {"shard_map": Session(mesh=mesh, mode="shard_map"),
                    "kernel": Session(mesh=mesh, mode="kernel")}
        oks.append(timed("load", load, sessions, table,
                         list(mesh.devices.flat)))
        for label, sess in sessions.items():
            oks.append(run_expressions(label, sess, oracle, n_rows)[0])
        oks.append(timed("sharded_access", four_chip_extras,
                         sessions["kernel"], table, n_rows))
    else:
        sessions = {"gspmd": Session(mode="gspmd"),
                    "kernel": Session(mode="kernel")}
        oks.append(timed("load", load, sessions, table, devices[:1]))
        for label, sess in sessions.items():
            ok, plans = run_expressions(label, sess, oracle, n_rows)
            oks.append(ok)
        kernel = sessions["kernel"]
        oks.append(timed("kernels", kernels_phase, plans))
        oks.append(timed("ingest", ingest_phase, kernel, n_rows))
        n_tokens = TOKEN_ROWS if on_tpu else CPU_TOKEN_ROWS
        oks.append(timed("model_udf", model_udf_phase, kernel, n_tokens))

    ok = all(oks) and on_tpu and n_rows == XL_ROWS
    emit({"ok": ok, "device": {"platform": devices[0].platform,
                               "kind": devices[0].device_kind,
                               "count": len(devices)}})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
