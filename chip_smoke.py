#!/usr/bin/env python3
"""Smoke run of the served Gibbs path on the TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 4-chip serve-mesh phase only

One chip: asia queries (the paper's Bayesian-network benchmark) and
scribble-mask queries on the Penguin MRF at its published size (500x333
sites, 2 labels) go through ``PosteriorEngine`` and ``AdmissionQueue``,
the objects ``python -m repro.serve.cli`` serves them with, once with
``sampler="xla"`` and once with ``sampler="pallas"`` (the fused KY
kernel, compiled).  The run fails unless no query failed, the asia
marginals agree with exact enumeration, the Penguin marginals are
distributions that find the ground-truth segmentation, the two samplers
agree bit for bit, and the compiled Penguin round runner holds the
kernel (``tpu_custom_call``).  Chains per query are chosen from the
compiled runner's bytes, so the Penguin group fits the chip.

``--chips 4``: the same traffic (pallas sampler) on a ``("batch",)``
serve mesh over 4 chips, compared bit for bit with the one-chip engine;
prints where the sharded arrays live and which device the engines of a
``WorkerPool`` use.

Times printed here are those of a smoke run, not a benchmark.  The last
line of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.pgm import networks  # noqa: E402
from repro.serve import AdmissionQueue, MrfQuery, PosteriorEngine, Query  # noqa: E402

PENGUIN_SHAPE = (500, 333)      # configs/aia_paper.py: PENGUIN
CHAIN_CANDIDATES = (4, 2, 1)    # chains per query, largest first
HBM_SHARE = 0.75                # of bytes_limit, for one Penguin round program
ASIA_Z = 5.0                    # |marginal - exact| in standard errors
ENGINE_KW = dict(sweeps_per_round=16, burn_in=16, ess_target=200.0,
                 max_rounds=256)

ASIA_QUERIES = (
    ({"dysp": 1}, ("lung", "bronc")),
    ({"smoke": 1}, ("lung", "bronc", "dysp")),
    ({"xray": 1, "smoke": 0}, ("lung", "tub")),
    ({"asia": 1, "dysp": 1}, ("tub", "either")),
)


def say(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def build_traffic(shape, seed: int):
    """asia queries, then two Penguin queries sharing one scribble pattern
    (one compiled plan, one micro-batched group)."""
    from repro.serve.cli import scribble_mask

    mrf, truth = networks.penguin_task(*shape)
    registry = {"asia": networks.asia(), "penguin": mrf}
    traffic = [Query("asia", ev, qv, n_samples=1 << 16)
               for ev, qv in ASIA_QUERIES]
    rng = np.random.default_rng(seed)
    h, w = shape
    mask = scribble_mask(h, w, rng, n_strokes=6)
    free_r, free_c = np.nonzero(~mask)
    for _ in range(2):
        pick = rng.choice(len(free_r), size=16, replace=False)
        sites = tuple((int(free_r[p]), int(free_c[p])) for p in pick)
        traffic.append(MrfQuery("penguin", mask, truth, query_sites=sites,
                                n_samples=256))
    return registry, traffic, truth


def runner_bytes(engine, query, lanes: int):
    """(bytes, compiled) of the round program that serves ``query``'s
    group at ``lanes`` chain lanes, placed as the engine places its state:
    arguments + outputs + temporaries, per device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.serve.families import family_of

    model = engine.networks[query.network]
    state = None
    if engine.mesh is not None:
        state = NamedSharding(engine.mesh,
                              family_of(model).state_spec(engine.mesh))
    args = (jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((lanes,) + model.shape, jnp.int32,
                                 sharding=state),
            jax.ShapeDtypeStruct((lanes,), jnp.int32))
    compiled = engine.round_runner(query).lower(*args).compile()
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes), compiled


def serve(engine, traffic, timeout: float = 900.0):
    """Submit the traffic as one batch through an admission queue and
    wait for every answer; a failed query raises here."""
    # submit_many + close(drain) forms the groups answer_batch would,
    # so runs with different samplers or devices are comparable bitwise
    queue = AdmissionQueue(engine, max_wait_ms=60_000.0)
    t0 = time.monotonic()
    handles = queue.submit_many(traffic)
    queue.close(drain=True)
    results = [h.result(timeout=timeout) for h in handles]
    return results, time.monotonic() - t0


def same(a, b) -> bool:
    return (a.n_sweeps == b.n_sweeps and a.n_samples == b.n_samples
            and a.bits_per_sample == b.bits_per_sample
            and a.marginals.keys() == b.marginals.keys()
            and all(np.array_equal(a.marginals[k], b.marginals[k])
                    for k in a.marginals))


def check_answers(results, registry, truth) -> None:
    """asia: every marginal within ASIA_Z standard errors of exact
    enumeration, the error taken at the query's worst effective sample
    size (plus 0.01 for the fixed-point weights).  Penguin: each queried
    marginal is a distribution, and most take the ground-truth label."""
    asia = registry["asia"]
    worst = 0.0
    n_sites = n_right = 0
    for r in results:
        q = r.query
        if q.network == "asia":
            exact = asia.marginals_exact(q.evidence)
            ess = max(r.diagnostics.min_ess, 1.0)
            for name, m in r.marginals.items():
                p = exact[asia.index(name)]
                err = np.abs(m - p) - 0.01
                worst = max(worst, float(np.max(
                    err / np.sqrt(np.maximum(p * (1 - p), 1e-4) / ess))))
        else:
            for (row, col) in q.query_sites:
                m = r.marginals[f"s{row},{col}"]
                require(m.shape == (2,) and np.isfinite(m).all()
                        and abs(float(m.sum()) - 1.0) < 1e-9,
                        f"penguin marginal {m} is not a distribution")
                n_sites += 1
                n_right += int(np.argmax(m) == truth[row, col])
    say(f"  asia: worst |marginal - exact| = {worst:.2f} standard errors "
        f"(limit {ASIA_Z})")
    say(f"  penguin: {n_right}/{n_sites} query sites take their "
        f"ground-truth label")
    require(worst < ASIA_Z, f"asia marginals off by {worst} s.e.")
    require(n_right >= 0.75 * n_sites,
            f"penguin: {n_right}/{n_sites} sites on the ground truth")


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def choose_chains(registry, query, seed: int, limit) -> int:
    """Largest of CHAIN_CANDIDATES whose Penguin group (two queries, so
    2 * chains lanes) fits HBM_SHARE of the chip, by the compiled bytes
    of the pallas round program, the larger of the two samplers'."""
    probe = PosteriorEngine(registry, sampler="pallas", seed=seed,
                            **ENGINE_KW)
    for c in CHAIN_CANDIDATES:
        t0 = time.monotonic()
        nbytes, _ = runner_bytes(probe, query, 2 * c)
        say(f"penguin pallas group at {2 * c} chain lanes: {nbytes} bytes "
            f"(compile {time.monotonic() - t0:.1f}s; bytes_limit {limit})")
        if limit is None or nbytes <= HBM_SHARE * limit:
            return c
    raise SystemExit("chip_smoke: no chain count fits the Penguin group")


def one_chip(shape, seed: int) -> None:
    import jax

    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    registry, traffic, truth = build_traffic(shape, seed)
    penguin = traffic[-1]
    chains = choose_chains(registry, penguin, seed, limit)
    runs = {}
    for sampler in ("xla", "pallas"):   # xla first: the peaks then differ
        engine = PosteriorEngine(registry, sampler=sampler, seed=seed,
                                 chains_per_query=chains, **ENGINE_KW)
        t0 = time.monotonic()
        nbytes, compiled = runner_bytes(engine, penguin, 2 * chains)
        has_kernel = "tpu_custom_call" in compiled.as_text()
        say(f"[{sampler}] penguin round program: {nbytes} bytes at "
            f"{2 * chains} lanes, set-up {time.monotonic() - t0:.1f}s, "
            f"tpu_custom_call={has_kernel}")
        if sampler == "pallas":
            require(has_kernel, "fused kernel missing from the pallas runner")
        results, wall = serve(engine, traffic)
        samples = sum(r.n_node_samples for r in results)
        say(f"[{sampler}] smoke run, not a benchmark: {len(results)} queries "
            f"in {wall:.2f}s ({len(results) / wall:.2f} queries/s, "
            f"{samples / wall / 1e6:.2f} MSample/s), converged "
            f"{sum(r.converged for r in results)}/{len(results)}, "
            f"peak_bytes_in_use {peak_bytes([dev])[0]}")
        check_answers(results, registry, truth)
        runs[sampler] = results
    n_same = sum(same(a, b) for a, b in zip(runs["xla"], runs["pallas"]))
    say(f"xla vs pallas: {n_same}/{len(traffic)} answers bitwise identical")
    require(n_same == len(traffic), "samplers disagree")


def four_chips(shape, seed: int) -> None:
    import jax

    from repro.launch.mesh import make_serve_mesh
    from repro.serve.worker import WorkerPool

    devices = jax.devices()[:4]
    registry, traffic, truth = build_traffic(shape, seed)
    kw = dict(ENGINE_KW, sampler="pallas", seed=seed,
              chains_per_query=CHAIN_CANDIDATES[0])

    # which device each engine of a WorkerPool uses (recorded, not set)
    pool = WorkerPool(lambda name: PosteriorEngine(registry, **kw), 2)
    for (name, worker), q in zip(pool.workers.items(), traffic):
        before = {id(a) for a in jax.live_arrays()}
        worker.submit(q).result(timeout=600)
        used = sorted({str(d) for a in jax.live_arrays()
                       if id(a) not in before for d in a.devices()})
        say(f"WorkerPool engine {name}: its arrays live on {used}")
    pool.close()

    mesh = make_serve_mesh((4,), devices=devices)
    say(f"serve mesh {dict(mesh.shape)} over {[str(d) for d in devices]}")
    runs = {}
    for label, m in (("1 chip", None), ("4-chip mesh", mesh)):
        engine = PosteriorEngine(registry, mesh=m, **kw)
        results, wall = serve(engine, traffic)
        say(f"[{label}] smoke run, not a benchmark: {len(results)} queries "
            f"in {wall:.2f}s, peak_bytes_in_use {peak_bytes(devices)}")
        check_answers(results, registry, truth)
        runs[label] = results
        if m is not None:
            lanes = 2 * kw["chains_per_query"]
            nbytes, compiled = runner_bytes(engine, traffic[-1], lanes)
            say(f"  penguin round program: {nbytes} bytes per device; "
                f"input shardings {compiled.input_shardings[0]}")
            sharded = [a for a in jax.live_arrays() if len(a.devices()) > 1]
            for a in sharded[:8]:
                say(f"  live array {a.shape} {a.dtype}: {a.sharding}")
            say(f"  {len(sharded)} live arrays span several devices")
    n_same = sum(same(a, b) for a, b in zip(runs["1 chip"],
                                            runs["4-chip mesh"]))
    say(f"1 chip vs 4-chip mesh: {n_same}/{len(traffic)} answers bitwise "
        f"identical")
    require(n_same == len(traffic), "mesh engine disagrees with one chip")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: both samplers on one chip; 4: the serve-mesh "
                         "phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU, JAX found "
                         f"{devices[0].platform}")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {len(devices)}")
    say(f"device: {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {cache}")
    t0 = time.monotonic()
    if args.chips == 4:
        four_chips(PENGUIN_SHAPE, args.seed)
    else:
        one_chip(PENGUIN_SHAPE, args.seed)
    say(f"total {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
