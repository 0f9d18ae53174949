"""Benchmark harness: one entry per paper table/figure.

Prints ``name,value,derived`` CSV rows per benchmark plus wall time.
Usage:  PYTHONPATH=src python -m benchmarks.run [--only NAME] [--skip NAME]
"""
from __future__ import annotations

import argparse
import sys
import time

from repro import compile_cache

from . import (chaos_bench, fig4_5_scalability, fig6_utilization,
               fig10_11_fps, kernel_bench, noise_ablation, overload_bench,
               sdc_bench, serve_bench, table2_vdpe_size, table3_dkv_census,
               table4_comb_switch, table8_area_proportionate)

BENCHES = {
    "table2_vdpe_size": table2_vdpe_size.run,
    "fig4_5_scalability": fig4_5_scalability.run,
    "table3_dkv_census": table3_dkv_census.run,
    "table4_comb_switch": table4_comb_switch.run,
    "fig6_utilization": fig6_utilization.run,
    "table8_area_proportionate": table8_area_proportionate.run,
    "fig10_11_fps": fig10_11_fps.run,
    "kernel_bench": kernel_bench.run,
    "noise_ablation": noise_ablation.run,
    "serve_bench": serve_bench.run,     # smoke settings by default
    "chaos_bench": chaos_bench.run,     # fault-injection scenarios
    "sdc_bench": sdc_bench.run,         # silent-data-corruption defense
    "overload_bench": overload_bench.run,  # brownout ladder under overload
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip", action="append", default=[],
                    help="bench name to leave out (repeatable); e.g. the "
                         "nightly runs serve_bench separately in non-smoke "
                         "mode")
    args = ap.parse_args()
    unknown = [n for n in [args.only, *args.skip]
               if n is not None and n not in BENCHES]
    if unknown:
        ap.error(f"unknown bench name(s) {unknown}; "
                 f"choose from {sorted(BENCHES)}")
    compile_cache.enable()
    failures = 0
    for name, fn in BENCHES.items():
        if args.only and args.only != name:
            continue
        if name in args.skip:
            continue
        t0 = time.monotonic()
        print(f"### {name}")
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}: {e}")
        print(f"{name},wall_s,{time.monotonic() - t0:.2f}")
        print()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
