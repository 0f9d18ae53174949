"""Run one benchmark cell on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration with weights from ``--seed``, warms the
batch sizes its traffic uses, serves the traffic through ``CNNServer``
for ``--seconds``, checks a seeded sample of the answers against the
plain reference, and prints one JSON line last: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device`` and, traced,
``breakdown``; the numbers compared, each beside its limit, come last in
that line under ``checks`` and as the last lines on standard error.

It exits non-zero, printing no result, unless JAX's default backend is a
TPU with at least as many chips as the cell asks for.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    import harness

    layout = harness.Layout()
    bench = json.loads(layout.benchmark.read_text())
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    if args.workload not in chips:
        print(f"unknown workload {args.workload!r}; known: {sorted(chips)}",
              file=sys.stderr)
        return 2
    backend = jax.default_backend()
    if backend != "tpu" or jax.device_count() < chips[args.workload]:
        print(f"bench: needs {chips[args.workload]} TPU chip(s), JAX has "
              f"{jax.device_count()} {backend} device(s)", file=sys.stderr)
        return 1
    line = harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_PROCESS_START, layout)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
