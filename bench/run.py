#!/usr/bin/env python3
"""The benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload penguin.scribble --seed 7 \
        --seconds 30 --trace 0

Prints progress on stderr, then the numbers compared for ``correct``
beside their limits as the last lines of stderr, and one JSON object as
the last line of stdout: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics and ``breakdown``), ``device`` and, last, ``checks``.
Exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell needs.
"""
from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import cell as cell_mod
    from harness.spec import load_cell

    cell = load_cell(args.workload)
    try:
        result = cell_mod.run_cell(cell, args.seed, args.seconds,
                                   bool(args.trace), T_PROC0, say=say)
    except cell_mod.NoChip as exc:
        say(str(exc))
        return 2
    for name, c in result["checks"].items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
