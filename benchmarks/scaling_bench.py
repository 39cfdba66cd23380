"""Speedup / scaleup (paper §IV-D2, Tables VII/VIII).

Every (shards, rows) point runs in THIS process, on a mesh over the first
``shards`` devices of ``jax.devices()``; a point that asks for more devices
than exist raises. One process owns every device, so on an accelerator
host no child ever competes for a chip. On the CPU the caller provides host
devices on the command line::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m benchmarks.run --scaling

There one physical core executes all shards, so wall-clock cannot show
hardware speedup — the curves then measure the *distribution overhead
structure* (per-shard work + collective emulation) only.
"""
from __future__ import annotations

import json
import pathlib
import time

import numpy as np


def run_point(shards: int, rows: int) -> dict:
    from benchmarks.wisconsin_bench import (EXPRESSIONS, RUNS, WARMUP,
                                            AFrameVariant)
    from repro.data import wisconsin
    from repro.engine.session import Session
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(data=shards, model=1) if shards > 1 else None
    sess = Session(mesh=mesh, mode="shard_map" if shards > 1 else "gspmd")
    table = wisconsin.generate(rows, seed=11)
    for name in ("data", "data_r"):
        sess.create_dataset(name, table, dataverse="bench", closed=True,
                            indexes=["onePercent", "unique1"],
                            primary="unique2")
    v = AFrameVariant("aframe-index", sess, "data")
    t0 = time.perf_counter()
    v.create()
    creation = time.perf_counter() - t0
    out = {}
    for name, fn in EXPRESSIONS:
        rng = np.random.default_rng(5)
        ts = []
        for _ in range(WARMUP + RUNS):
            t0 = time.perf_counter()
            fn(v, rng, rows)
            ts.append(time.perf_counter() - t0)
        out[name] = float(np.mean(ts[WARMUP:]))
    return {"shards": shards, "rows": rows, "creation_s": creation,
            "expr_s": out}


def speedup(rows: int = 200_000, shard_counts=(1, 2, 4, 8)) -> list[dict]:
    """Fixed data, growing shards (paper Table VII)."""
    return [run_point(s, rows) for s in shard_counts]


def scaleup(rows_per_shard: int = 50_000, shard_counts=(1, 2, 4, 8)) -> list[dict]:
    """Data grows with shards (paper Table VIII)."""
    return [run_point(s, rows_per_shard * s) for s in shard_counts]


def run_scaling(out_json: pathlib.Path, quick: bool = False) -> dict:
    counts = (1, 2, 4) if quick else (1, 2, 4, 8)
    res = {"speedup": speedup(100_000 if quick else 200_000, counts),
           "scaleup": scaleup(25_000 if quick else 50_000, counts)}
    out_json.parent.mkdir(parents=True, exist_ok=True)
    out_json.write_text(json.dumps(res, indent=2))
    for kind in ("speedup", "scaleup"):
        print(f"-- {kind} --")
        for rec in res[kind]:
            tot = sum(rec["expr_s"].values())
            print(f"  shards={rec['shards']:2d} rows={rec['rows']:7d} "
                  f"sum(expr)={tot*1e3:9.1f}ms create={rec['creation_s']*1e3:7.1f}ms")
    return res
