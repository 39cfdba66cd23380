"""Benchmark orchestrator — one entry per paper table/figure.

  --single-node : Fig. 8-11 / Tables V-VI (12 expressions × variants × sizes)
  --scaling     : Tables VII-VIII (speedup / scaleup over 1..8 devices)
  --model       : Fig. 5/6 analogue (model-UDF / serve / train rates)
  --roofline    : §Roofline table from the dry-run artifacts
  --ingest      : streaming ingestion (deferred compaction vs
                  compact-every-flush rows/sec + query freshness)
  (no flags)    : quick versions of all of the above

Outputs land in results/bench/. Everything runs in this one process;
``--scaling`` and ``--ingest`` build meshes of up to 8 devices, which on the
CPU come from ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on the
command line.
"""
from __future__ import annotations

import argparse
import json
import pathlib

OUT = pathlib.Path(__file__).resolve().parents[1] / "results" / "bench"


def _mode_comparison(rows: list[dict]) -> dict:
    """Per-size, per-expression gspmd (aframe-schema) vs kernel
    (aframe-kernel) expression timings + speedup — the BENCH_*.json artifact
    that tracks the fused-kernel win across PRs."""
    out: dict = {}
    for r in rows:
        if r["variant"] not in ("aframe-schema", "aframe-kernel"):
            continue
        cell = out.setdefault(r["size"], {}).setdefault(r["expression"], {})
        key = "gspmd_s" if r["variant"] == "aframe-schema" else "kernel_s"
        cell[key] = r["expr_s"]
    for exprs in out.values():
        for cell in exprs.values():
            if "gspmd_s" in cell and "kernel_s" in cell and cell["kernel_s"] > 0:
                cell["speedup"] = round(cell["gspmd_s"] / cell["kernel_s"], 3)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--single-node", action="store_true")
    ap.add_argument("--scaling", action="store_true")
    ap.add_argument("--model", action="store_true")
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--ingest", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="full dataset sizes (XS..XL); default quick=XS,S")
    ap.add_argument("--sizes", type=str, default=None,
                    help="comma-separated size names (e.g. XS) — overrides "
                         "--full; used by the CI smoke run")
    args = ap.parse_args()
    run_all = not (args.single_node or args.scaling or args.model
                   or args.roofline or args.ingest)
    OUT.mkdir(parents=True, exist_ok=True)

    if args.single_node or run_all:
        from benchmarks.wisconsin_bench import SIZES, run_benchmark

        if args.sizes:
            sizes = {k: SIZES[k] for k in args.sizes.split(",")}
        elif args.full:
            sizes = SIZES
        else:
            sizes = {k: SIZES[k] for k in ("XS", "S")}
        print(f"== single-node DataFrame benchmark (sizes={list(sizes)}) ==")
        rows = run_benchmark(sizes, OUT / "single_node.csv")
        bench = _mode_comparison(rows)
        bench_path = OUT.parents[1] / "BENCH_wisconsin.json"
        bench_path.write_text(json.dumps(bench, indent=2) + "\n")
        print(f"gspmd-vs-kernel comparison -> {bench_path}")

    if args.ingest or run_all:
        from benchmarks.ingest_bench import SIZES as INGEST_SIZES, run_ingest_bench

        if args.sizes:
            sizes = [s for s in args.sizes.split(",") if s in INGEST_SIZES]
        elif args.full:
            sizes = list(INGEST_SIZES)
        else:
            sizes = ["XS", "S"]
        print(f"== streaming ingestion benchmark (sizes={sizes}) ==")
        run_ingest_bench(sizes, OUT / "ingest.json")

    if args.scaling or run_all:
        from benchmarks.scaling_bench import run_scaling

        print("== speedup / scaleup (meshes over this process's devices) ==")
        run_scaling(OUT / "scaling.json", quick=not args.full)

    if args.model or run_all:
        from benchmarks.model_bench import run_model_bench

        print("== model UDF / serve / train ==")
        (OUT / "model.json").write_text(json.dumps(run_model_bench(), indent=2))

    if args.roofline or run_all:
        from benchmarks.roofline_table import markdown_table, summary

        print("== roofline (from dry-run artifacts) ==")
        md = markdown_table("pod")
        (OUT / "roofline_pod.md").write_text(md)
        print(md)
        print(json.dumps(summary("pod"), indent=2))


if __name__ == "__main__":
    main()
