"""Posterior query service under synthetic traffic (repro.serve).

Measures what a serving stack cares about: queries/s and MSample/s for a
cold plan cache (compiler chain + XLA compile on the critical path) vs a
warm one (pure sampling), plus bits/sample and the cache hit rate.
Traffic cycles a small set of evidence patterns, as repeat sensor
traffic does — the regime the (network, evidence-pattern) plan cache is
designed for.  Both served families are covered: Bayesian networks
(:func:`run`) and masked MRF grids — scribble pixel-mask evidence —
(:func:`run_mrf`, which also checks queued-vs-``answer_batch``
bit-identity for the MRF path).

Invocation forms:

  PYTHONPATH=src:. python -m benchmarks.bench_serve                # CSV rows
  PYTHONPATH=src:. python -m benchmarks.bench_serve --smoke --stream \\
      --json BENCH_serve.json                                      # CI smoke
  PYTHONPATH=src:. python -m benchmarks.bench_serve \\
      --force-host-devices 4 --mesh-shape 4                        # sharded
  PYTHONPATH=src:. python -m benchmarks.bench_serve --scaling 1,2,4,8 \\
      --json BENCH_scaling.json     # device-scaling subprocesses, nothing else

Every report also carries a ``map`` section (annealed MAP/MPE queries/s
under assignment-stability retirement, :func:`run_map`) and a
``filtering`` section (temporal dynamic-BN filtering: per-slice latency
of warm-started streaming-sensor slices vs cold re-solves, with the
per-slice plan-cache hit rate the gate holds at 100% after slice 0,
:func:`run_filtering`) — see ``docs/inference_modes.md``.

``--stream`` adds the open-loop streaming benchmark: traffic arrives at
a fixed rate (default 4x the measured synchronous rate), is served
through the admission queue (:mod:`repro.serve.queue`), and reported as
queries/s plus p50/p99 latency against a one-query-at-a-time
synchronous baseline — with a bitwise identity check of queued vs
``answer_batch`` results for the same traffic.

``--json`` emits a machine-readable report (queries/s, MSample/s,
**ESS/s** — effective samples per second, the honest analogue of the
paper's MSample/s — bits/sample, cold/warm, stream metrics) so CI can
track the perf trajectory;
``benchmarks/check_serve_regression.py`` gates CI on it against
``benchmarks/baselines/BENCH_serve.json``.  The report carries the
engine's ``retirement`` mode so the gate can refuse to compare a
rank-mode run against a legacy-mode baseline.  ``-`` writes it to
stdout.

``--overload`` adds the scale-out front-end overload benchmark
(:func:`run_overload`): a fresh HTTP server (``repro.serve.server``)
first proves served results **bitwise identical** to an in-process
``answer_batch`` on the same seed, then its sustained closed-loop
capacity is measured, and traffic is offered open-loop at 2x that
capacity against a per-tenant token-bucket quota set to capacity — the
report carries served p50/p99 latency and the shed rate (429/503), and
``check_serve_regression`` holds the shed-rate floor plus a bounded
p99 (shedding at the front door instead of queue collapse).

``--scaling`` runs only per-device-count throughput points, each in a
child process (forced-host devices on the CPU); the parent stays off
jax so that on an accelerator each child can own the chip.

``--diagnostics-json`` additionally runs the same traffic under both
retirement rules (``legacy`` plain split-R̂ vs ``rank`` rank-R̂ + ESS)
and writes a ``BENCH_diagnostics.json`` artifact with per-mode
sweeps-to-retirement and ESS/s — the latency/statistical-quality
trade-off the diagnostics subsystem exists to expose.

Telemetry (``repro.serve.telemetry``, see ``docs/observability.md``):
the ``--stream`` run records with a live recorder, so its report
section carries a ``latency_breakdown`` (wait/plan/service from the
lifecycle spans) and a metrics-registry snapshot; ``--trace-out`` /
``--metrics-json`` write the Perfetto trace and ``engine.stats()``
snapshot as CI artifacts.  Every report also carries a
``telemetry_overhead`` section (null vs live recorder ESS/s on
identical traffic, self-relative) which
``benchmarks/check_serve_regression.py`` gates at ≤ 5%.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import row

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pass(engine, traffic):
    t0 = time.perf_counter()
    results = engine.answer_batch(traffic)
    dt = time.perf_counter() - t0
    samples = sum(r.n_node_samples for r in results)
    return dt, samples, results


def _ess(results) -> float:
    """Total worst-case ESS over a pass (see repro.serve.cli.ess_total)."""
    from repro.serve.cli import ess_total
    return ess_total(results)


def run(name, network, *, n_queries=32, n_patterns=3, budget=2048,
        chains=16, mesh=None, report=print):
    """Cold + warm pass over one network's traffic; returns metrics."""
    from repro.pgm import networks
    from repro.serve.cli import synthetic_traffic
    from repro.serve.engine import PosteriorEngine

    bn = getattr(networks, network)()
    traffic = synthetic_traffic(
        bn, network, n_queries, n_patterns, np.random.default_rng(0), budget)
    engine = PosteriorEngine({network: bn}, chains_per_query=chains,
                             burn_in=32, mesh=mesh)
    cold_dt, cold_samples, cold_results = _pass(engine, traffic)
    warm_dt, warm_samples, results = _pass(engine, traffic)
    conv = sum(r.converged for r in results)
    bits = float(np.mean([r.bits_per_sample for r in results]))
    s = engine.cache.stats
    report(row(
        f"serve_{name}_cold", cold_dt / n_queries * 1e6,
        f"qps={n_queries/cold_dt:.2f};MSample/s={cold_samples/cold_dt/1e6:.3f}"))
    report(row(
        f"serve_{name}_warm", warm_dt / n_queries * 1e6,
        f"qps={n_queries/warm_dt:.2f};MSample/s={warm_samples/warm_dt/1e6:.3f};"
        f"ESS/s={_ess(results)/warm_dt:.1f};"
        f"speedup={cold_dt/warm_dt:.1f}x;hit_rate={s.hit_rate:.2f};"
        f"converged={conv}/{n_queries}"))
    return {
        "name": name,
        "network": network,
        "n_queries": n_queries,
        "retirement": engine.retirement,
        "cold": {"wall_s": cold_dt, "queries_per_s": n_queries / cold_dt,
                 "msample_per_s": cold_samples / cold_dt / 1e6,
                 "ess_per_s": _ess(cold_results) / cold_dt},
        "warm": {"wall_s": warm_dt, "queries_per_s": n_queries / warm_dt,
                 "msample_per_s": warm_samples / warm_dt / 1e6,
                 "ess_per_s": _ess(results) / warm_dt},
        "bits_per_sample": bits,
        "cache_hit_rate": s.hit_rate,
        "converged": conv,
    }


def _identical(a, b) -> bool:
    return (a.n_samples == b.n_samples
            and (a.rhat == b.rhat
                 or (np.isnan(a.rhat) and np.isnan(b.rhat)))
            and set(a.marginals) == set(b.marginals)
            and all(np.array_equal(a.marginals[k], b.marginals[k])
                    for k in a.marginals)
            and a.map_assignment == b.map_assignment
            and a.map_energy == b.map_energy)


def run_mrf(name, *, h=16, w=16, n_queries=12, n_patterns=2, budget=1024,
            chains=8, mesh=None, report=print):
    """Masked-MRF serving benchmark: cold + warm qps for scribble-mask
    traffic over a Potts grid, plus the queued-vs-``answer_batch``
    identity bit — the pixel-evidence twin of :func:`run`."""
    from repro.pgm.networks import penguin_task
    from repro.serve.cli import synthetic_mrf_traffic
    from repro.serve.engine import PosteriorEngine
    from repro.serve.queue import AdmissionQueue

    network = "mrf_penguin"
    mrf, _ = penguin_task(h=h, w=w)
    traffic = synthetic_mrf_traffic(
        mrf, network, n_queries, n_patterns, np.random.default_rng(0), budget)
    kw = dict(chains_per_query=chains, burn_in=32, mesh=mesh)
    engine = PosteriorEngine({network: mrf}, **kw)
    cold_dt, cold_samples, cold_results = _pass(engine, traffic)
    warm_dt, warm_samples, results = _pass(engine, traffic)
    conv = sum(r.converged for r in results)
    bits = float(np.mean([r.bits_per_sample for r in results]))
    s = engine.cache.stats

    # identity: same traffic, same seeds -> queued == caller-batched
    eng_a = PosteriorEngine({network: mrf}, **kw, seed=7)
    ref = eng_a.answer_batch(traffic)
    eng_b = PosteriorEngine({network: mrf}, **kw, seed=7)
    queue_b = AdmissionQueue(eng_b, max_wait_ms=3_600_000.0,
                             max_group_lanes=n_queries * chains)
    try:
        handles = [queue_b.submit(q) for q in traffic]
        queue_b.flush()
        streamed = [hd.result(timeout=600) for hd in handles]
    finally:
        queue_b.close()
    identical = all(_identical(a, b) for a, b in zip(ref, streamed))

    report(row(
        f"serve_{name}_cold", cold_dt / n_queries * 1e6,
        f"qps={n_queries/cold_dt:.2f};MSample/s={cold_samples/cold_dt/1e6:.3f}"))
    report(row(
        f"serve_{name}_warm", warm_dt / n_queries * 1e6,
        f"qps={n_queries/warm_dt:.2f};MSample/s={warm_samples/warm_dt/1e6:.3f};"
        f"ESS/s={_ess(results)/warm_dt:.1f};"
        f"speedup={cold_dt/warm_dt:.1f}x;hit_rate={s.hit_rate:.2f};"
        f"converged={conv}/{n_queries};identical={identical}"))
    return {
        "name": name,
        "network": network,
        "grid": [h, w],
        "n_queries": n_queries,
        "retirement": engine.retirement,
        "cold": {"wall_s": cold_dt, "queries_per_s": n_queries / cold_dt,
                 "msample_per_s": cold_samples / cold_dt / 1e6,
                 "ess_per_s": _ess(cold_results) / cold_dt},
        "warm": {"wall_s": warm_dt, "queries_per_s": n_queries / warm_dt,
                 "msample_per_s": warm_samples / warm_dt / 1e6,
                 "ess_per_s": _ess(results) / warm_dt},
        "bits_per_sample": bits,
        "cache_hit_rate": s.hit_rate,
        "converged": conv,
        "identical": bool(identical),
    }


def run_ising(name, *, side=16, beta=0.35, n_queries=12, n_patterns=2,
              budget=1024, chains=8, mesh=None, report=print):
    """Sparse-Ising serving benchmark: cold + warm qps for spin-clamp
    traffic over a 2D-torus ferromagnet, plus the queued-vs-
    ``answer_batch`` identity bit — the sparse-graph twin of
    :func:`run_mrf`."""
    from repro.pgm.networks import ising_torus
    from repro.serve.cli import synthetic_ising_traffic
    from repro.serve.engine import PosteriorEngine
    from repro.serve.queue import AdmissionQueue

    network = "ising_torus"
    model = ising_torus(side, beta=beta)
    traffic = synthetic_ising_traffic(
        model, network, n_queries, n_patterns, np.random.default_rng(0),
        budget)
    kw = dict(chains_per_query=chains, burn_in=32, mesh=mesh)
    engine = PosteriorEngine({network: model}, **kw)
    cold_dt, cold_samples, cold_results = _pass(engine, traffic)
    warm_dt, warm_samples, results = _pass(engine, traffic)
    conv = sum(r.converged for r in results)
    bits = float(np.mean([r.bits_per_sample for r in results]))
    s = engine.cache.stats

    # identity: same traffic, same seeds -> queued == caller-batched
    eng_a = PosteriorEngine({network: model}, **kw, seed=7)
    ref = eng_a.answer_batch(traffic)
    eng_b = PosteriorEngine({network: model}, **kw, seed=7)
    queue_b = AdmissionQueue(eng_b, max_wait_ms=3_600_000.0,
                             max_group_lanes=n_queries * chains)
    try:
        handles = [queue_b.submit(q) for q in traffic]
        queue_b.flush()
        streamed = [hd.result(timeout=600) for hd in handles]
    finally:
        queue_b.close()
    identical = all(_identical(a, b) for a, b in zip(ref, streamed))

    report(row(
        f"serve_{name}_cold", cold_dt / n_queries * 1e6,
        f"qps={n_queries/cold_dt:.2f};MSample/s={cold_samples/cold_dt/1e6:.3f}"))
    report(row(
        f"serve_{name}_warm", warm_dt / n_queries * 1e6,
        f"qps={n_queries/warm_dt:.2f};MSample/s={warm_samples/warm_dt/1e6:.3f};"
        f"ESS/s={_ess(results)/warm_dt:.1f};"
        f"speedup={cold_dt/warm_dt:.1f}x;hit_rate={s.hit_rate:.2f};"
        f"converged={conv}/{n_queries};identical={identical}"))
    return {
        "name": name,
        "network": network,
        "side": side,
        "n_queries": n_queries,
        "retirement": engine.retirement,
        "cold": {"wall_s": cold_dt, "queries_per_s": n_queries / cold_dt,
                 "msample_per_s": cold_samples / cold_dt / 1e6,
                 "ess_per_s": _ess(cold_results) / cold_dt},
        "warm": {"wall_s": warm_dt, "queries_per_s": n_queries / warm_dt,
                 "msample_per_s": warm_samples / warm_dt / 1e6,
                 "ess_per_s": _ess(results) / warm_dt},
        "bits_per_sample": bits,
        "cache_hit_rate": s.hit_rate,
        "converged": conv,
        "identical": bool(identical),
    }


def run_million_spin(*, side=1024, beta=0.3, chains=2, sweeps=4,
                     report=print):
    """Million-spin capacity datapoint (weekly CI, not the push gate):
    compile a ``side x side`` torus (~``side**2`` spins) through the
    sparse chain — parallel MIS coloring + degree-bucketed plans — and
    measure compile wall plus steady-state spin-updates/s of the fused
    sweep.  Returns a JSON-able dict; correctness is covered by the
    tier-1 Onsager test, this row tracks *scale*."""
    import time as _time

    import jax

    from repro.pgm.networks import ising_torus
    from repro.pgm.sparse_compile import (
        compile_factor_graph, init_fg_states, make_fg_sweep)

    model = ising_torus(side, beta=beta)
    t0 = _time.perf_counter()
    prog = compile_factor_graph(model)
    compile_s = _time.perf_counter() - t0

    sweep = make_fg_sweep(prog)
    key = jax.random.PRNGKey(0)
    x = init_fg_states(key, prog, chains)
    # one warm-up sweep pays the jit; then time the steady state
    x, _ = sweep(key, x)
    x.block_until_ready()
    t0 = _time.perf_counter()
    for i in range(sweeps):
        key, sub = jax.random.split(key)
        x, _ = sweep(sub, x)
    x.block_until_ready()
    sweep_s = (_time.perf_counter() - t0) / sweeps
    updates_per_s = chains * model.n / sweep_s
    report(row("serve_million_spin_sweep", sweep_s * 1e6,
               f"spins={model.n};colors={prog.n_colors};chains={chains};"
               f"compile_s={compile_s:.1f};"
               f"Mupdates/s={updates_per_s/1e6:.2f}"))
    return {
        "side": side,
        "n_spins": int(model.n),
        "n_colors": int(prog.n_colors),
        "chains": chains,
        "compile_s": compile_s,
        "sweep_s": sweep_s,
        "mupdates_per_s": updates_per_s / 1e6,
    }


def run_stream(name, network, *, n_queries=32, n_patterns=2, budget=2048,
               chains=16, rate_qps=0.0, max_wait_ms=250.0, mesh=None,
               trace_out="", metrics_out="", report=print):
    """Open-loop streaming benchmark: queued admission vs one-query-at-a-
    time synchronous serving over the same traffic, plus a bitwise
    identity check of queued vs ``answer_batch`` results.

    The queued engine runs with a live telemetry recorder, so the
    returned metrics carry a ``latency_breakdown`` (wait / plan /
    service from the lifecycle spans) and a ``metrics`` registry
    snapshot; ``trace_out`` / ``metrics_out`` additionally write the
    Perfetto trace and the ``engine.stats()`` snapshot as artifacts.
    The synchronous baseline engine stays on the no-op recorder so the
    speedup denominator is a telemetry-free number."""
    from repro.pgm import networks
    from repro.serve.cli import measure_stream, synthetic_traffic
    from repro.serve.engine import PosteriorEngine
    from repro.serve.queue import AdmissionQueue
    from repro.serve.telemetry import Telemetry

    bn = getattr(networks, network)()
    traffic = synthetic_traffic(
        bn, network, n_queries, n_patterns, np.random.default_rng(0), budget)
    kw = dict(chains_per_query=chains, burn_in=32, mesh=mesh)

    # shared protocol (repro.serve.cli.measure_stream): sync baseline +
    # open-loop queued replay.  The 8x multiplier keeps the admission
    # window full — far above what one-at-a-time serving sustains, which
    # is the regime the queue exists for (machine-relative, CI-stable).
    stream_engine = PosteriorEngine({network: bn}, **kw,
                                    telemetry=Telemetry())
    metrics, _ = measure_stream(
        stream_engine,
        PosteriorEngine({network: bn}, **kw),
        traffic, rate_qps=rate_qps, rate_multiplier=8.0,
        max_wait_ms=max_wait_ms)

    # identity: same traffic, same seeds -> queued == caller-batched, bitwise
    eng_a = PosteriorEngine({network: bn}, **kw, seed=7)
    ref = eng_a.answer_batch(traffic)
    eng_b = PosteriorEngine({network: bn}, **kw, seed=7)
    queue_b = AdmissionQueue(eng_b, max_wait_ms=3_600_000.0,
                             max_group_lanes=n_queries * chains)
    try:
        handles = [queue_b.submit(q) for q in traffic]
        queue_b.flush()
        streamed = [h.result(timeout=600) for h in handles]
    finally:
        queue_b.close()
    identical = all(_identical(a, b) for a, b in zip(ref, streamed))

    bd = metrics.get("latency_breakdown", {})
    report(row(
        f"serve_{name}_stream",
        1e6 / max(metrics["queries_per_s"], 1e-9),
        f"qps={metrics['queries_per_s']:.2f};"
        f"sync_qps={metrics['sync_queries_per_s']:.2f};"
        f"speedup={metrics['speedup']:.2f}x;"
        f"MSample/s={metrics['msample_per_s']:.3f};"
        f"ESS/s={metrics['ess_per_s']:.1f};"
        f"p50_ms={metrics['p50_ms']:.1f};p99_ms={metrics['p99_ms']:.1f};"
        + "".join(f"{p}_p50_ms={bd[p]['p50_ms']:.1f};"
                  for p in ("wait", "plan", "service") if p in bd)
        + f"groups={metrics['dispatched_groups']};"
        f"backfilled={metrics['backfilled']};identical={identical}"))
    if trace_out:
        stream_engine.telemetry.write_trace(trace_out)
        report(f"# wrote {trace_out}")
    if metrics_out:
        with open(metrics_out, "w") as f:
            json.dump(stream_engine.stats(), f, indent=2)
        report(f"# wrote {metrics_out}")
    return {"name": name, "network": network,
            "retirement": stream_engine.retirement,
            **{k: v for k, v in metrics.items() if k != "submitted"},
            "metrics": stream_engine.telemetry.metrics_snapshot(),
            "identical": bool(identical)}


def run_overload(network="asia", *, n_queries=6, n_patterns=2, budget=256,
                 chains=8, overload_factor=2.0, capacity_passes=3,
                 n_offered=None, report=print):
    """Scale-out front-end overload benchmark (SLO serving under 2x
    offered load) — three phases against one HTTP server process:

    1. **identity** — a fresh single-worker server serves the traffic
       via ``/v2/batch``; marginals must come back bitwise identical to
       a fresh in-process ``answer_batch`` on the same seed (floats
       survive JSON exactly; the engine PRNG advances with traffic, so
       only the *first* batch on a fresh server can be compared);
    2. **capacity** — closed-loop sequential serving over the now-warm
       plans measures the sustained queries/s one worker holds;
    3. **overload** — a second front end over the same warm pool gets a
       per-tenant token bucket at exactly that capacity (small burst)
       and is offered open-loop traffic at ``overload_factor`` times
       capacity.  Over-quota requests shed with 429 (+ Retry-After)
       at the front door, so the admitted subset keeps bounded latency
       instead of every caller timing out in a collapsing queue.

    Reported: capacity/offered qps, shed rate, served p50/p99 ms and
    ``mean_service_ms`` (1000/capacity) — the self-relative yardstick
    ``check_serve_regression`` holds p99 against."""
    import threading

    from repro.pgm import networks
    from repro.serve.cli import synthetic_traffic
    from repro.serve.client import ServeClient, ServeHTTPError
    from repro.serve.engine import PosteriorEngine
    from repro.serve.protocol import wire_marginals
    from repro.serve.server import start_in_thread
    from repro.serve.worker import WorkerPool

    bn = getattr(networks, network)()
    registry = {network: bn}
    traffic = synthetic_traffic(
        bn, network, n_queries, n_patterns, np.random.default_rng(0), budget)
    kw = dict(chains_per_query=chains, burn_in=32, seed=7)
    pool = WorkerPool(lambda name: PosteriorEngine(registry, **kw), 1,
                      queue_kwargs={"max_wait_ms": 5.0})
    fe = start_in_thread(pool, port=0)
    overload_fe = None
    try:
        client = ServeClient("127.0.0.1", fe.port)
        # -- phase 1: bitwise identity (fresh server, first batch) ----
        wire = client.query_batch(traffic)
        ref = PosteriorEngine(registry, **kw).answer_batch(traffic)
        identical = all(
            set(wire_marginals(w)) == {str(k) for k in r.marginals}
            and all(np.array_equal(wire_marginals(w)[str(k)],
                                   np.asarray(m, np.float64))
                    for k, m in r.marginals.items())
            for w, r in zip(wire, ref))

        # -- phase 2: closed-loop capacity on warm plans --------------
        n_cap = len(traffic) * capacity_passes
        t0 = time.perf_counter()
        for i in range(n_cap):
            client.query(traffic[i % len(traffic)])
        capacity_qps = n_cap / (time.perf_counter() - t0)

        # -- phase 3: open-loop overload at 2x capacity ---------------
        offered_qps = overload_factor * capacity_qps
        if n_offered is None:  # ~2s of offered traffic, bounded
            n_offered = int(min(200, max(32, 2 * offered_qps)))
        overload_fe = start_in_thread(
            pool, port=0, quota_qps=capacity_qps, quota_burst=2.0)
        oclient = ServeClient("127.0.0.1", overload_fe.port)
        lock = threading.Lock()
        outcomes: list[tuple[str, float]] = []

        def _one(i: int) -> None:
            t = time.perf_counter()
            try:
                oclient.query(traffic[i % len(traffic)])
                kind = "served"
            except ServeHTTPError as exc:
                kind = "shed" if exc.status in (429, 503) else "error"
            except Exception:
                kind = "error"
            with lock:
                outcomes.append((kind, (time.perf_counter() - t) * 1e3))

        threads = []
        t_start = time.perf_counter()
        for i in range(n_offered):
            due = t_start + i / offered_qps
            while True:
                dt = due - time.perf_counter()
                if dt <= 0:
                    break
                time.sleep(min(dt, 0.01))
            th = threading.Thread(target=_one, args=(i,), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=300)
        wall = time.perf_counter() - t_start
    finally:
        if overload_fe is not None:
            overload_fe.stop_thread()
        fe.stop_thread()
        pool.close(drain=False, timeout=30.0)

    served = [ms for kind, ms in outcomes if kind == "served"]
    shed = sum(1 for kind, _ in outcomes if kind == "shed")
    errors = sum(1 for kind, _ in outcomes if kind == "error")
    p50 = float(np.percentile(served, 50)) if served else float("nan")
    p99 = float(np.percentile(served, 99)) if served else float("nan")
    out = {
        "network": network,
        "n_queries": len(traffic),
        "identical": bool(identical),
        "capacity_qps": capacity_qps,
        "overload_factor": overload_factor,
        "offered_qps": offered_qps,
        "n_offered": int(n_offered),
        "served": len(served),
        "shed": int(shed),
        "errors": int(errors),
        "shed_rate": shed / max(n_offered, 1),
        "served_qps": len(served) / max(wall, 1e-9),
        "p50_ms": p50,
        "p99_ms": p99,
        "mean_service_ms": 1e3 / max(capacity_qps, 1e-9),
    }
    report(row(
        "serve_overload", p99 * 1e3,
        f"capacity_qps={capacity_qps:.2f};offered_qps={offered_qps:.2f};"
        f"shed_rate={out['shed_rate']:.2f};p50_ms={p50:.1f};"
        f"p99_ms={p99:.1f};errors={errors};identical={identical}"))
    return out


def run_map(name, network, *, n_queries=16, n_patterns=2, budget=1024,
            chains=16, mesh=None, report=print):
    """Annealed MAP/MPE serving benchmark: cold + warm qps for
    ``mode="map"`` traffic (simulated-annealing β schedule on the IU-exp
    weight path, assignment-stability retirement — see
    ``docs/inference_modes.md``).  The MAP rows live in their own report
    section rather than ``runs`` because ESS/s is not a meaningful
    throughput for annealed (deliberately non-mixing) chains; the gate
    compares warm queries/s only.  ``assignments_agree`` reports whether
    the cold and warm passes decoded the same argmax per query —
    informational (the passes consume different key-stream positions, so
    a near-tie can legitimately flip)."""
    import dataclasses

    from repro.pgm import networks
    from repro.serve.cli import synthetic_traffic
    from repro.serve.engine import PosteriorEngine

    bn = getattr(networks, network)()
    traffic = [dataclasses.replace(q, mode="map") for q in synthetic_traffic(
        bn, network, n_queries, n_patterns, np.random.default_rng(0), budget)]
    engine = PosteriorEngine({network: bn}, chains_per_query=chains,
                             burn_in=32, mesh=mesh)
    cold_dt, _, cold_results = _pass(engine, traffic)
    warm_dt, _, results = _pass(engine, traffic)
    stable = sum(r.converged for r in results)
    agree = sum(a.map_assignment == b.map_assignment
                for a, b in zip(cold_results, results))
    energy = float(np.mean([r.map_energy for r in results]))
    s = engine.cache.stats
    report(row(
        f"serve_{name}_cold", cold_dt / n_queries * 1e6,
        f"qps={n_queries/cold_dt:.2f};mode=map"))
    report(row(
        f"serve_{name}_warm", warm_dt / n_queries * 1e6,
        f"qps={n_queries/warm_dt:.2f};speedup={cold_dt/warm_dt:.1f}x;"
        f"hit_rate={s.hit_rate:.2f};map_stable={stable}/{n_queries};"
        f"agree={agree}/{n_queries};mean_energy={energy:.2f}"))
    return {
        "name": name,
        "network": network,
        "n_queries": n_queries,
        "retirement": engine.retirement,
        "cold": {"wall_s": cold_dt, "queries_per_s": n_queries / cold_dt},
        "warm": {"wall_s": warm_dt, "queries_per_s": n_queries / warm_dt},
        "map_stable": int(stable),
        "assignments_agree": int(agree),
        "mean_map_energy": energy,
        "cache_hit_rate": s.hit_rate,
    }


def run_filtering(name, network, *, n_streams=4, n_slices=6, budget=1024,
                  chains=8, burn_in=128, drift=0.25, mesh=None,
                  report=print):
    """Temporal filtering benchmark: per-slice latency for streaming-
    sensor traffic served *warm* (``stream_id`` set — each slice
    warm-starts from its stream's retained chains and skips burn-in) vs
    *cold* (identical traffic with ``stream_id`` stripped — every slice
    pays burn-in from scratch).  Both passes run through one engine, so
    everything after the cold pass's first slice is plan-cache-hot and
    the cold/warm latency ratio isolates the warm-start mechanism.

    Reported per the acceptance bar of ``docs/inference_modes.md``: the
    warm pass's per-slice plan-cache hit rate (must be 100% after slice
    0 — the gate fails otherwise), warm-started query counts per slice,
    and the cold/warm per-slice latency ratio
    ``benchmarks/check_serve_regression.py`` holds above
    ``--min-filtering-speedup``."""
    import dataclasses

    from repro.pgm import networks
    from repro.serve.cli import synthetic_stream_traffic
    from repro.serve.engine import PosteriorEngine

    bn = getattr(networks, network)()
    traffic = synthetic_stream_traffic(
        bn, network, n_streams, n_slices, np.random.default_rng(0), budget,
        drift=drift)
    slices = [traffic[i * n_streams:(i + 1) * n_streams]
              for i in range(n_slices)]
    engine = PosteriorEngine({network: bn}, chains_per_query=chains,
                             burn_in=burn_in, mesh=mesh)

    def _slice_pass(strip):
        times, hit_rates, warm = [], [], 0
        for sl in slices:
            qs = ([dataclasses.replace(q, stream_id=None) for q in sl]
                  if strip else sl)
            h0, m0 = engine.cache.stats.hits, engine.cache.stats.misses
            t0 = time.perf_counter()
            results = engine.answer_batch(qs)
            times.append(time.perf_counter() - t0)
            dh = engine.cache.stats.hits - h0
            dm = engine.cache.stats.misses - m0
            hit_rates.append(dh / max(dh + dm, 1))
            warm += sum(r.warm_start for r in results)
        return times, hit_rates, warm

    cold_times, _, _ = _slice_pass(strip=True)       # also warms the plans
    warm_times, warm_hits, warm_started = _slice_pass(strip=False)

    cold_ms = float(np.mean(cold_times[1:])) * 1e3
    warm_ms = float(np.mean(warm_times[1:])) * 1e3
    speedup = cold_ms / max(warm_ms, 1e-9)
    hit_after_0 = float(min(warm_hits[1:]))
    expected_warm = n_streams * (n_slices - 1)
    report(row(
        f"serve_{name}", warm_ms * 1e3,
        f"warm_slice_ms={warm_ms:.1f};cold_slice_ms={cold_ms:.1f};"
        f"speedup={speedup:.2f}x;hit_rate_after_slice0={hit_after_0:.2f};"
        f"warm_started={warm_started}/{expected_warm}"))
    return {
        "name": name,
        "network": network,
        "n_streams": n_streams,
        "n_slices": n_slices,
        "burn_in": burn_in,
        "retirement": engine.retirement,
        "cold_slice_ms": cold_ms,
        "warm_slice_ms": warm_ms,
        "slices_per_s_warm": 1e3 / max(warm_ms, 1e-9),
        "speedup": speedup,
        "warm_hit_rate_after_slice0": hit_after_0,
        "warm_started": int(warm_started),
        "expected_warm": int(expected_warm),
    }


def run_telemetry_overhead(network="asia", *, n_queries=16, n_patterns=2,
                           budget=2048, chains=16, repeats=8, report=print):
    """Null-recorder vs live-recorder warm throughput on identical
    traffic — the number the CI overhead gate holds at ≤ 5%.

    Protocol: warm both engines off the clock (plan-cache fill + XLA
    compile), then run ``repeats`` *interleaved* timed warm
    ``answer_batch`` passes per recorder, GC disabled.  Both engines
    share one seed, so pass *k* does bitwise-identical sampling on both
    sides — the ESS cancels exactly and the honest comparison is a pure
    time ratio on identical work.  ``ratio`` (what the gate holds
    ≥ 1 − tolerance) is the max of two robust estimators of that time
    ratio — the timeit-style min-time ratio, a trimmed-sum ratio, and
    the median of adjacent-pair ratios — because individual warm passes
    jitter ±10% on shared CI runners while the estimators stay centred;
    interleaving makes slow machine
    drift hit both sides equally, and the comparison is *self-relative*
    (both sides measured in this process, this run) so the gate is
    immune to runner speed-class drift.  ``ess_per_s_*`` report each
    side's throughput at its fastest pass."""
    import gc

    from repro.pgm import networks
    from repro.serve.cli import synthetic_traffic
    from repro.serve.engine import PosteriorEngine
    from repro.serve.telemetry import Telemetry

    bn = getattr(networks, network)()
    traffic = synthetic_traffic(
        bn, network, n_queries, n_patterns, np.random.default_rng(0), budget)
    engines = {}
    for label, tel in (("null", None), ("enabled", Telemetry())):
        engines[label] = PosteriorEngine(
            {network: bn}, chains_per_query=chains, burn_in=32,
            telemetry=tel)
        _pass(engines[label], traffic)       # warm the plan cache
    dts: dict[str, list[float]] = {"null": [], "enabled": []}
    ess: dict[str, list[float]] = {"null": [], "enabled": []}
    gc.collect()
    gc.disable()       # GC pauses are the dominant asymmetric jitter
    try:
        for _ in range(repeats):
            for label, engine in engines.items():
                dt, _, results = _pass(engine, traffic)
                dts[label].append(dt)
                ess[label].append(_ess(results))
    finally:
        gc.enable()
    ess_per_s = {}
    for label in ("null", "enabled"):
        k = min(range(repeats), key=dts[label].__getitem__)
        ess_per_s[label] = ess[label][k] / dts[label][k]

    # Three robust estimators of the same (work-identical) time ratio;
    # all are central, so their max keeps full sensitivity to a real
    # overhead regression (a true 10% cost drags every estimator to
    # ~0.90) while cutting the false-failure rate from runner timing
    # bursts that hit only one side's passes.
    def _trimmed(xs: list[float]) -> float:
        return sum(sorted(xs)[:-1]) if len(xs) > 1 else xs[0]

    pair_ratios = sorted(n / e for n, e in zip(dts["null"], dts["enabled"]))
    ratio = max(
        min(dts["null"]) / max(min(dts["enabled"]), 1e-12),
        _trimmed(dts["null"]) / max(_trimmed(dts["enabled"]), 1e-12),
        pair_ratios[len(pair_ratios) // 2])
    report(row("serve_telemetry_overhead",
               1e6 / max(ess_per_s["enabled"], 1e-9),
               f"ESS/s_null={ess_per_s['null']:.1f};"
               f"ESS/s_enabled={ess_per_s['enabled']:.1f};"
               f"ratio={ratio:.3f}"))
    return {"network": network, "n_queries": n_queries, "repeats": repeats,
            "ess_per_s_null": ess_per_s["null"],
            "ess_per_s_enabled": ess_per_s["enabled"],
            "ratio": ratio}


def run_sampler_compare(network="asia", *, n_queries=8, n_patterns=2,
                        budget=512, chains=8, report=print):
    """``sampler="xla"`` vs ``sampler="pallas"`` engine backends on
    identical traffic: warm MSample/s for both plus the bitwise-identity
    bit.  The regression gate holds ``identical`` unconditionally; the
    speedup is only meaningful off-CPU (on CPU the fused kernel runs
    through the Pallas *interpreter*), so the report carries the
    ``platform`` for the gate to condition on.

    The traffic covers *both* inference modes: the marginal queries get
    MAP-mode twins appended, so the one matrix row also pins the
    annealed (β-scaled) weight path to xla/pallas bitwise identity."""
    import dataclasses

    import jax

    from repro.pgm import networks
    from repro.serve.cli import synthetic_traffic
    from repro.serve.engine import PosteriorEngine

    bn = getattr(networks, network)()
    traffic = synthetic_traffic(
        bn, network, n_queries, n_patterns, np.random.default_rng(0), budget)
    traffic = traffic + [dataclasses.replace(q, mode="map")
                         for q in traffic[:max(n_patterns, 2)]]
    n_queries = len(traffic)
    out = {"network": network, "platform": jax.default_backend(),
           "n_queries": n_queries}
    results = {}
    for sampler in ("xla", "pallas"):
        engine = PosteriorEngine({network: bn}, chains_per_query=chains,
                                 burn_in=32, sampler=sampler, seed=7)
        _pass(engine, traffic)                       # warm the plan cache
        dt, samples, res = _pass(engine, traffic)
        results[sampler] = res
        # ESS is a mixing metric — meaningless for the annealed MAP
        # twins, so the throughput row counts only the marginal queries
        out[sampler] = {"wall_s": dt, "queries_per_s": n_queries / dt,
                        "msample_per_s": samples / dt / 1e6,
                        "ess_per_s": _ess(
                            [r for r in res if r.map_assignment is None]
                        ) / dt}
        report(row(f"serve_sampler_{sampler}", dt / n_queries * 1e6,
                   f"MSample/s={out[sampler]['msample_per_s']:.3f};"
                   f"platform={out['platform']}"))
    identical = all(_identical(a, b)
                    for a, b in zip(results["xla"], results["pallas"]))
    out["identical"] = bool(identical)
    out["speedup"] = (out["pallas"]["msample_per_s"]
                      / max(out["xla"]["msample_per_s"], 1e-12))
    report(row("serve_sampler_identity", 0.0,
               f"identical={identical};"
               f"speedup_pallas={out['speedup']:.2f}x"))
    return out


def run_diagnostics_compare(network="asia", *, n_queries=16, n_patterns=2,
                            budget=2048, chains=16, rhat_target=1.05,
                            ess_target=100.0, report=print):
    """Legacy vs rank retirement over identical traffic: per-mode mean
    sweeps-to-retirement, converged counts and ESS/s — the artifact
    (``BENCH_diagnostics.json``) CI uploads so the latency/statistical-
    quality trade-off of the retirement rule is tracked per commit."""
    from repro.pgm import networks
    from repro.serve.cli import synthetic_traffic
    from repro.serve.engine import PosteriorEngine

    bn = getattr(networks, network)()
    traffic = synthetic_traffic(
        bn, network, n_queries, n_patterns, np.random.default_rng(0), budget)
    out = {"suite": "serve_diagnostics", "network": network,
           "n_queries": n_queries, "rhat_target": rhat_target,
           "ess_target": ess_target, "modes": {}}
    for mode in ("legacy", "rank"):
        engine = PosteriorEngine(
            {network: bn}, chains_per_query=chains, burn_in=32,
            retirement=mode, rhat_target=rhat_target, ess_target=ess_target)
        _pass(engine, traffic)                       # warm the plan cache
        dt, samples, results = _pass(engine, traffic)
        sweeps = [r.n_sweeps for r in results]
        ess = _ess(results)
        out["modes"][mode] = {
            "wall_s": dt,
            "queries_per_s": n_queries / dt,
            "mean_sweeps_to_retirement": float(np.mean(sweeps)),
            "max_sweeps_to_retirement": int(max(sweeps)),
            "converged": int(sum(r.converged for r in results)),
            "msample_per_s": samples / dt / 1e6,
            "ess_per_s": ess / dt,
            "mean_min_ess": ess / n_queries,
        }
        m = out["modes"][mode]
        report(row(
            f"serve_diag_{mode}", dt / n_queries * 1e6,
            f"sweeps={m['mean_sweeps_to_retirement']:.0f};"
            f"MSample/s={m['msample_per_s']:.3f};"
            f"ESS/s={m['ess_per_s']:.1f};"
            f"converged={m['converged']}/{n_queries}"))
    return out


def main(report=print, *, smoke=False, stream=False, mesh_shape=None,
         trace_out="", metrics_out=""):
    """Benchmark-harness entry point; returns the JSON-able report."""
    mesh = None
    n_devices = 1
    if mesh_shape is not None:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(mesh_shape)
        n_devices = int(mesh.devices.size)
        report(f"# serve mesh {dict(mesh.shape)} over {n_devices} devices")
    kw = dict(mesh=mesh, report=report)
    if smoke:
        runs = [run("asia_8n", "asia", n_queries=8, budget=512, chains=8,
                    **kw),
                run_mrf("mrf_12x12", h=12, w=12, n_queries=8, budget=256,
                        **kw),
                run_ising("ising_16", side=16, n_queries=8, budget=256,
                          **kw)]
    else:
        runs = [run("asia_8n", "asia", **kw),
                run("child_scale_20n", "child_scale", n_queries=16, **kw),
                run_mrf("mrf_24x24", h=24, w=24, n_queries=16, **kw),
                run_ising("ising_32", side=32, n_queries=12, **kw)]
    # the retirement mode the runs actually used (each run records its
    # engine's) — the regression gate refuses to diff reports across
    # different modes, so a half-converted report must fail loudly here
    # rather than mislabel itself
    modes = {r.pop("retirement") for r in runs}
    if len(modes) != 1:
        raise RuntimeError(f"runs used mixed retirement modes: {modes}")
    rep = {"suite": "serve", "n_devices": n_devices,
           "retirement": modes.pop(),
           "mesh_shape": None if mesh_shape is None else list(mesh_shape),
           "runs": runs}
    # MAP qps + temporal-filtering rows (docs/inference_modes.md): their
    # own sections — ESS/s is not meaningful for annealed chains, and
    # the filtering row is per-slice latency, not per-query throughput
    if smoke:
        rep["map"] = run_map("asia_map", "asia", n_queries=8, budget=512,
                             chains=8, **kw)
        rep["filtering"] = run_filtering(
            "asia_filtering", "asia", n_streams=3, n_slices=4, budget=512,
            **kw)
    else:
        rep["map"] = run_map("asia_map", "asia", **kw)
        rep["filtering"] = run_filtering("asia_filtering", "asia", **kw)
    for section in ("map", "filtering"):
        if rep[section].pop("retirement") != rep["retirement"]:
            raise RuntimeError(
                f"{section} run used a different retirement mode")
    if stream:
        stream_kw = dict(kw, trace_out=trace_out, metrics_out=metrics_out)
        if smoke:
            rep["stream"] = run_stream(
                "asia_8n", "asia", n_queries=32, n_patterns=2, budget=512,
                chains=8, **stream_kw)
        else:
            rep["stream"] = run_stream("asia_8n", "asia", **stream_kw)
        if rep["stream"].pop("retirement") != rep["retirement"]:
            raise RuntimeError("stream run used a different retirement mode")
    # telemetry overhead: null vs live recorder on identical traffic —
    # self-relative, so the CI gate needs no baseline entry for it
    rep["telemetry_overhead"] = run_telemetry_overhead(report=report)
    # fused-pallas vs xla sampler backends: identity is gated always,
    # speedup only where the kernel compiles (non-CPU) — smoke-sized in
    # every mode, it is a correctness/tracking row, not a throughput one
    rep["sampler_pallas"] = run_sampler_compare(report=report)
    return rep


def scaling(device_counts, *, smoke=True, report=print):
    """Device-scaling report: re-run this module in forced-host
    subprocesses (XLA device count is fixed at backend init, so each
    point needs a fresh interpreter) and collect queries/s + MSample/s
    per device count."""
    out = []
    from repro.launch.mesh import force_host_devices

    for n in device_counts:
        env = dict(os.environ)
        env["PYTHONPATH"] = (
            _REPO + os.pathsep + os.path.join(_REPO, "src") + os.pathsep
            + env.get("PYTHONPATH", ""))
        force_host_devices(n, env)
        cmd = [sys.executable, "-m", "benchmarks.bench_serve",
               "--mesh-shape", str(n), "--json", "-"]
        if smoke:
            cmd.append("--smoke")
        p = subprocess.run(cmd, env=env, cwd=_REPO, capture_output=True,
                           text=True, timeout=1800)
        if p.returncode != 0:
            raise RuntimeError(f"scaling point n={n} failed:\n{p.stderr}")
        rep = json.loads(
            [ln for ln in p.stdout.splitlines() if ln.startswith("{")][-1])
        warm = rep["runs"][0]["warm"]
        out.append({"devices": n,
                    "queries_per_s": warm["queries_per_s"],
                    "msample_per_s": warm["msample_per_s"]})
        report(row(f"serve_scaling_{n}dev", 1e6 / max(warm["queries_per_s"], 1e-9),
                   f"qps={warm['queries_per_s']:.2f};"
                   f"MSample/s={warm['msample_per_s']:.3f}"))
    return out


def _cli(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="single small network (fast CI datapoint)")
    ap.add_argument("--stream", action="store_true",
                    help="add the open-loop streaming benchmark (admission "
                         "queue vs one-query-at-a-time synchronous serving)")
    ap.add_argument("--json", default="",
                    help="write a machine-readable report here ('-' = stdout)")
    ap.add_argument("--diagnostics-json", default="",
                    help="run legacy-vs-rank retirement over identical "
                         "traffic and write the comparison here "
                         "(sweeps-to-retirement, ESS/s per mode)")
    ap.add_argument("--mesh-shape", default="",
                    help="serve mesh, e.g. 4 or 2x2")
    ap.add_argument("--scaling", default="",
                    help="comma-separated forced-host device counts, "
                         "e.g. 1,2,4,8 — runs one subprocess per count "
                         "and nothing else in this process")
    ap.add_argument("--overload", action="store_true",
                    help="add the HTTP front-end overload benchmark: "
                         "bitwise served-vs-answer_batch identity, then "
                         "p50/p99 + shed rate at 2x measured capacity")
    ap.add_argument("--million-spin", action="store_true",
                    help="add the million-spin torus capacity datapoint "
                         "(compile wall + spin-updates/s; weekly CI)")
    ap.add_argument("--million-spin-side", type=int, default=1024,
                    help="torus side for --million-spin (side**2 spins)")
    ap.add_argument("--force-host-devices", type=int, default=0)
    ap.add_argument("--trace-out", default="",
                    help="with --stream: write the queued engine's "
                         "Chrome/Perfetto trace here (CI artifact)")
    ap.add_argument("--metrics-json", default="",
                    help="with --stream: write the queued engine's "
                         "stats()/metrics snapshot here (CI artifact)")
    args = ap.parse_args(argv)

    if args.scaling:
        # each point is a child process that owns the device(s); the
        # parent never touches jax, or the children could not get the chip
        counts = [int(s) for s in args.scaling.split(",") if s]
        # scaling points are always smoke-sized: one datapoint per device
        # count, each paying its own interpreter + XLA compile
        _write_report({"suite": "serve_scaling",
                       "scaling": scaling(counts, smoke=True)}, args.json)
        return

    if args.force_host_devices:
        from repro.launch.mesh import force_host_devices
        force_host_devices(args.force_host_devices)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    mesh_shape = None
    if args.mesh_shape:
        from repro.launch.mesh import parse_mesh_shape
        mesh_shape = parse_mesh_shape(args.mesh_shape)

    rep = main(smoke=args.smoke, stream=args.stream, mesh_shape=mesh_shape,
               trace_out=args.trace_out, metrics_out=args.metrics_json)
    if args.diagnostics_json:
        diag_kw = (dict(n_queries=8, budget=512, chains=8)
                   if args.smoke else {})
        diag = run_diagnostics_compare(**diag_kw)
        with open(args.diagnostics_json, "w") as f:
            json.dump(diag, f, indent=2)
        print(f"# wrote {args.diagnostics_json}")
    if args.overload:
        rep["overload"] = run_overload()
    if args.million_spin:
        rep["million_spin"] = run_million_spin(side=args.million_spin_side)
    _write_report(rep, args.json)


def _write_report(rep: dict, dest: str) -> None:
    if dest == "-":
        print(json.dumps(rep))
    elif dest:
        with open(dest, "w") as f:
            json.dump(rep, f, indent=2)
        print(f"# wrote {dest}")


if __name__ == "__main__":
    _cli()
