"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (per repo convention).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run --only mrf # substring filter
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (
    bench_bayesnet,
    bench_halo,
    bench_interp,
    bench_ky_vs_cdf,
    bench_lm_decode,
    bench_mrf,
    bench_roofline,
    bench_schmoo,
    bench_serve,
    bench_sota_table,
)

# Suites run in this process and share its device.  None may start a
# child that needs the accelerator: this process holds it once a suite
# has run (bench_halo's child is pinned to the CPU; device-scaling runs
# are `python -m benchmarks.bench_serve --scaling`, a process of their own).
SUITES = [
    ("schmoo", bench_schmoo),          # Fig. 6
    ("ky_vs_cdf", bench_ky_vs_cdf),    # §II-B 3x claim
    ("interp", bench_interp),          # §II-B IU claim
    ("mrf", bench_mrf),                # Fig. 7 (MRF)
    ("bayesnet", bench_bayesnet),      # Fig. 7 (BN)
    ("serve", bench_serve),            # ours: posterior query service
    ("halo", bench_halo),              # §II-A / Fig. 3b
    ("lm_decode", bench_lm_decode),    # ours: KY as LM token sampler
    ("sota_table", bench_sota_table),  # Table II
    ("roofline", bench_roofline),      # §Roofline table
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in SUITES:
        if args.only and args.only not in name:
            continue
        try:
            mod.main(report=print)
        except Exception as e:
            failures += 1
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
