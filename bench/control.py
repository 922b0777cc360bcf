#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, and the knee sweep, in
one process (set-up is paid once per run, compiles once per process).

    python3 bench/control.py --workload asia.steady --seeds 1,2,3 \
        --variants sound,k8,stuck,altered --seconds 20
    python3 bench/control.py --workload asia.steady --seeds 5 \
        --rates 20,40,80 --seconds 30

Variants:
* ``sound``: the cell as it runs.
* ``k8``: the precision control: the program's own lower-precision path,
  8-bit Knuth-Yao weights (``PosteriorEngine(k=8)``) for the stated 14.
* ``stuck``: a round that returns its state unchanged, with counts and
  moments of that state (the chains never move).
* ``altered``: every answer altered where it is produced (each marginal
  reversed over its labels at retirement).

Runs with ``--rates`` warm up as the benchmark does; of the others only
the first of each variant does (it puts the variant's programs in the
compile cache), since only their ``correct`` readings are used.  Prints one
JSON line per run: variant, seed, rate, ``correct``, the
numbers compared, the end-to-end metrics, and for ``--rates`` the
backlog at the window's close and the median latency of each half of
the window.
"""
from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


@contextlib.contextmanager
def planted(variant: str):
    """Break the timed path underneath the harness for one run."""
    from repro.serve import engine as eng

    if variant == "stuck":
        import jax.numpy as jnp

        orig = eng.PosteriorEngine._plan

        def _plan(self, name, pattern):
            prog, runner, hit = orig(self, name, pattern)

            def stuck(key, x, offset, *rest):
                _, rc, xm, xsq, st = runner(key, x, offset, *rest)
                flat = x.reshape(x.shape[0], -1)
                onehot = (flat[..., None] == jnp.arange(rc.shape[-1]))
                spr = self.sweeps_per_round
                xf = flat.astype(jnp.float32)
                return (x, onehot.astype(rc.dtype) * spr, xf, xf * xf, st)
            return prog, stuck, hit

        eng.PosteriorEngine._plan = _plan
        try:
            yield
        finally:
            eng.PosteriorEngine._plan = orig
    elif variant == "altered":
        orig = eng.GroupRun._retire

        def _retire(self, s, reason="max-sweeps"):
            orig(self, s, reason)
            m = s.entry.result.marginals
            for k in m:
                m[k] = m[k][::-1].copy()

        eng.GroupRun._retire = _retire
        try:
            yield
        finally:
            eng.GroupRun._retire = orig
    else:
        yield


def sweep_stats(run) -> dict:
    from harness import window

    mid = (run.t0 + run.t1) / 2
    halves = []
    for a, b in ((run.t0, mid), (mid, run.t1)):
        lat = window.latencies_s(run.records, a, b, run.t_stop)
        halves.append(window.percentile_ms(lat, 50))
    due = run.due()
    backlog = sum(1 for r in due
                  if r["t_recv"] is None or r["t_recv"] > run.t1)
    return {"p50_halves_ms": halves, "backlog_at_close": backlog,
            "due": len(due)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="sound")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    from harness import cell as cell_mod
    from harness.spec import load_cell

    cell = load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    t_proc = T_PROC0
    for rate in rates:
        for variant in args.variants.split(","):
            for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
                kept = []
                with planted(variant):
                    res = cell_mod.run_cell(
                        cell, seed, args.seconds, False, t_proc,
                        rate_qps=rate, keep=kept,
                        warm=rate is not None or i == 0,
                        engine_kw={"k": 8} if variant == "k8" else None,
                        say=lambda m: print(m, file=sys.stderr, flush=True))
                t_proc = time.monotonic()
                line = {"variant": variant, "seed": seed, "rate": rate,
                        "correct": res["correct"],
                        "checks": {k: v["value"] for k, v in
                                   res["checks"].items()},
                        "metrics": {k: v["value"] for k, v in
                                    res["metrics"].items()}}
                if rate is not None:
                    line.update(sweep_stats(kept[0]))
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
