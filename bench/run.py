"""Runs one benchmark cell once, on the accelerator of this machine:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``. The last line of standard
output is the result; the numbers compared with the reference, each beside
its limit, are the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, the run exits 3 and prints no result.
JAX's persistent compilation cache is kept in ``.jax_cache`` at the root
of the checkout, so only the first run of a cell there compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench.harness import runner

    chips = runner.cell_entry(runner.benchmark(), args.workload)["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform!r} device(s)", file=sys.stderr)
        return 3
    result = runner.run(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=T_START)
    runner.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
