"""Readings that the output check's limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s> \
        [--control]

One process runs the cell's window and check on each seed in turn (the
same set-up, window and sample as ``run.py``) and prints the widest
logit gap each reads.  ``--control`` builds the program's plan at one
quantization bit fewer than the configuration states (3 for its 4): the
nearest lower precision, which the check has to fail.  The readings and
the limit set from them are in PERF.md.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    import jax

    import harness
    import opcount

    if jax.default_backend() != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    layout = harness.Layout()
    cfg = opcount.load_config(
        harness.load_cell(layout, args.workload)["config"], layout.configs)
    bits = cfg["quantization_bits"] - 1 if args.control else None
    gaps = []
    for seed in (int(s) for s in args.seeds.split(",")):
        line = harness.run(args.workload, seed, args.seconds, False,
                           time.perf_counter(), layout, point_bits=bits)
        gap = line["checks"]["logit_gap"]["value"]
        gaps.append(gap)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control, "logit_gap": gap,
                          "compared": line["checks"]["compared"]["value"],
                          "attempted": line["attempted"],
                          "failed": line["failed"]}), flush=True)
    print(json.dumps({"workload": args.workload, "control": args.control,
                      "min": min(gaps), "max": max(gaps)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
