"""Runs a cell with its control, the broken reference named in the cell's
configuration, in the program's place, on several seeds in one process:

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Each seed prints one JSON line with the numbers compared and their limits;
every control run has to come out ``"correct": false``. The benchmark's
own runs never run this.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import runner

    bm = runner.benchmark()
    control = runner.load_config(
        bm, runner.cell_entry(bm, args.workload)["config"])["control"]
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        res = runner.run(args.workload, seed, args.seconds, False,
                         control=control, log=lambda *_: None)
        passed += res["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": control, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
