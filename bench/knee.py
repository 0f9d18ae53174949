"""Sweep an open-loop cell's arrival rate once, to find its knee.

    python3 bench/knee.py --workload <poisson cell> --seed <n> --seconds <s> \
        --rates 200,400,800

One process, one warmed server; each rate runs the cell's open loop for
``--seconds``.  The knee is the highest rate whose p99 latency (due time
to answer) is at most ``--p99-limit-ms`` (10 ms: MLPerf Inference v0.5's
server-scenario bound for MobileNet-v1) with no growing backlog; where no
rate meets the limit, the highest without a growing backlog.  A backlog
grows where the last fifth of the requests waits more than twice as long
as the first fifth and more than the limit.  A cell's rate is written
into its workload file by hand, at about four fifths of the knee.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--p99-limit-ms", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    import harness

    if jax.default_backend() != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 1
    sv = harness.set_up(args.workload, args.seed, False, harness.Layout())
    rows = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        p = dict(sv.cell["params"], rate_per_s=rate)
        req = harness.Requests(args.seed, 0)
        t_start, t_end, n = harness.open_loop(
            sv.server, sv.model, sv.images, p, args.seconds,
            args.seed + k, req, harness.annotate(False))
        done = np.asarray(req.done)
        lat = (np.where(np.isnan(done), np.nanmax(done), done)
               - np.asarray(req.due))[:n] * 1e3
        fifth = max(n // 5, 1)
        first, last = float(np.mean(lat[:fifth])), float(np.mean(lat[-fifth:]))
        row = {"rate_per_s": rate, "requests": n,
               "unanswered": int(np.sum(np.isnan(done[:n]))),
               "p50_ms": float(np.percentile(lat, 50)),
               "p99_ms": float(np.percentile(lat, 99)),
               "first_fifth_ms": first, "last_fifth_ms": last,
               "mean_batch": float(np.mean([b[0] for b in req.batches])),
               "growing": bool(last > 2 * first
                               and last > args.p99_limit_ms)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        sv.server.reset()
        time.sleep(0.5)
    ok = [r for r in rows if not r["growing"] and not r["unanswered"]]
    meet = [r for r in ok if r["p99_ms"] <= args.p99_limit_ms]
    knee = max(meet or ok, key=lambda r: r["rate_per_s"], default=None)
    print(json.dumps({"knee_rate_per_s": knee and knee["rate_per_s"],
                      "rule": "p99 limit" if meet else "no growing backlog"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
