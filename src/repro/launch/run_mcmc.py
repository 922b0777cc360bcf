"""MCMC driver — the paper's own workloads on the AIA-analogue pipeline.

  PYTHONPATH=src python -m repro.launch.run_mcmc --config aia-bn-asia
  PYTHONPATH=src python -m repro.launch.run_mcmc --config aia-bn-asia \
      --evidence smoke=1,dysp=1 --query lung,bronc   # posterior query
  PYTHONPATH=src python -m repro.launch.run_mcmc --config aia-mrf-penguin \
      --scale 0.2 --sweeps 30
  PYTHONPATH=src python -m repro.launch.run_mcmc --config aia-mrf-penguin \
      --mesh 2x2 --devices 4   # distributed halo-exchange Gibbs (C3)

Bayesian-network configs with ``--evidence`` route through the posterior
query engine (:mod:`repro.serve`): evidence nodes are clamped at compile
time, the sweep program comes from the plan cache, and sampling
early-stops on the rank-normalized R̂ + ESS retirement rule
(``docs/diagnostics.md``; the report prints both the legacy split-R̂
and the rank diagnostics).
"""
from __future__ import annotations

import argparse
import os

from repro.serve.telemetry import monotonic


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--sweeps", type=int, default=0)
    ap.add_argument("--chains", type=int, default=0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="scale MRF image size (CPU-friendly runs)")
    ap.add_argument("--mesh", default="",
                    help="e.g. 2x2 — run distributed halo-exchange Gibbs")
    ap.add_argument("--devices", type=int, default=0,
                    help="fake host devices for --mesh on CPU")
    ap.add_argument("--no-iu", action="store_true")
    ap.add_argument("--sampler", choices=("xla", "pallas"), default="xla",
                    help="sampling backend: two-stage XLA ops or the "
                         "fused Pallas sweep kernel (bitwise-identical; "
                         "interpreted off-TPU)")
    ap.add_argument("--evidence", default="",
                    help="BN only: observations, e.g. smoke=1,dysp=1 — "
                         "answers a posterior query via repro.serve")
    ap.add_argument("--query", default="",
                    help="BN only: comma-separated query variables "
                         "(default: all unobserved)")
    ap.add_argument("--mode", default="marginals",
                    choices=("marginals", "map"),
                    help="with --evidence: posterior marginals (default) "
                         "or annealed MAP/MPE search (reports the argmax "
                         "assignment + its energy; docs/inference_modes.md)")
    ap.add_argument("--trace-out", default="",
                    help="with --evidence: write a Chrome/Perfetto trace "
                         "of the query lifecycle here")
    ap.add_argument("--metrics-json", default="",
                    help="with --evidence: write the engine.stats() "
                         "snapshot here as JSON")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.aia_paper import MCMC_CONFIGS
    from repro.launch.compile_cache import enable_compile_cache
    from repro.pgm import networks
    from repro.pgm.compile import compile_bayesnet, run_gibbs
    from repro.pgm.gibbs import init_labels, mrf_gibbs
    from repro.pgm.mesh_gibbs import make_mesh_gibbs_step, shard_mrf

    enable_compile_cache()
    cfg = MCMC_CONFIGS[args.config]
    sweeps = args.sweeps or cfg.n_sweeps
    chains = args.chains or cfg.n_chains
    use_iu = not args.no_iu

    if cfg.kind == "bayesnet" and args.evidence:
        from repro.serve import PosteriorEngine, Query, Telemetry, \
            parse_evidence

        bn = getattr(networks, cfg.network)()
        evidence = parse_evidence(args.evidence)
        qvars = tuple(v.strip() for v in args.query.split(",") if v.strip())
        tel = (Telemetry() if (args.trace_out or args.metrics_json)
               else None)
        engine = PosteriorEngine(
            {cfg.network: bn}, chains_per_query=chains, k=cfg.k,
            use_iu=use_iu, sampler=args.sampler, burn_in=cfg.burn_in,
            telemetry=tel)
        budget = chains * max(sweeps - cfg.burn_in, 1)
        res = engine.answer(Query(cfg.network, evidence, qvars,
                                  n_samples=budget, mode=args.mode))
        n_q = (len(res.marginals) if res.map_assignment is None
               else len(res.map_assignment))
        print(f"{cfg.network}: evidence {evidence} -> {n_q} query vars "
              f"(mode={args.mode})")
        print(f"{res.n_node_samples} RV samples in {res.wall_s:.2f}s -> "
              f"{res.n_node_samples/res.wall_s/1e6:.2f} MSample/s (CPU), "
              f"{res.bits_per_sample:.2f} bits/sample")
        d = res.diagnostics
        print(f"split-Rhat={res.rhat:.3f} rank-Rhat={d.rank_rhat:.3f} "
              f"folded-Rhat={d.folded_rhat:.3f} "
              f"ESS bulk/tail={d.ess_bulk:.0f}/{d.ess_tail:.0f} "
              f"({d.min_ess/res.wall_s:.0f} ESS/s)")
        print(f"converged={res.converged} kept={res.n_samples} "
              f"sweeps={d.sweeps_used} plan_cache_hit={res.cache_hit}")
        if res.map_assignment is not None:
            print(f"  MAP assignment (energy {res.map_energy:.3f} nats):")
            for var, val in res.map_assignment.items():
                print(f"    {var} = {val}")
        for var, m in res.marginals.items():
            print(f"  P({var} | e) = {np.round(m, 3)}")
        if args.trace_out:
            engine.telemetry.write_trace(args.trace_out)
            print(f"trace written to {args.trace_out}")
        if args.metrics_json:
            import json
            with open(args.metrics_json, "w") as f:
                json.dump(engine.stats(), f, indent=2)
            print(f"metrics snapshot written to {args.metrics_json}")
        return

    if cfg.kind == "bayesnet":
        bn = getattr(networks, cfg.network)()
        prog = compile_bayesnet(bn, k=cfg.k)
        print(f"{cfg.network}: {bn.n_nodes} nodes, "
              f"{prog.n_colors} colors (DSatur)")
        t0 = monotonic()
        x, counts, stats = run_gibbs(
            jax.random.PRNGKey(0), prog, n_chains=chains, n_sweeps=sweeps,
            burn_in=cfg.burn_in, use_iu=use_iu, sampler=args.sampler)
        jax.block_until_ready(counts)
        dt = monotonic() - t0
        n_samples = chains * sweeps * bn.n_nodes
        print(f"{n_samples} RV samples in {dt:.2f}s -> "
              f"{n_samples/dt/1e6:.2f} MSample/s (CPU)")
        print(f"random bits/sample: {float(stats.bits_used)/n_samples:.2f}")
        marg = np.asarray(counts, np.float64)
        marg /= np.clip(marg.sum(-1, keepdims=True), 1, None)
        for v in range(min(bn.n_nodes, 10)):
            print(f"  P({bn.names[v]}) = {np.round(marg[v,:bn.card[v]], 3)}")
        return

    # ---- MRF ------------------------------------------------------------
    h = max(int(cfg.height * args.scale), 16)
    w = max(int(cfg.width * args.scale), 16)
    if cfg.pairwise == "potts":
        mrf, truth = networks.penguin_task(h, w, beta=cfg.beta)
    else:
        mrf, truth = networks.art_task(h, w, n_labels=cfg.n_labels,
                                       beta=cfg.beta, tau=cfg.tau)
    print(f"{cfg.name}: {h}x{w}, L={mrf.n_labels}")

    if args.mesh:
        from repro.launch.mesh import make_pgm_mesh

        rows, cols = (int(x) for x in args.mesh.split("x"))
        mesh = make_pgm_mesh(rows, cols)
        key = jax.random.PRNGKey(0)
        lab, u, pw, valid, _ = shard_mrf(mesh, mrf, n_chains=chains, key=key)
        step = make_mesh_gibbs_step(mesh, k=cfg.k, use_iu=use_iu,
                                    sampler=args.sampler)
        t0 = monotonic()
        bits = 0
        for i in range(sweeps):
            key, sub = jax.random.split(key)
            lab, bgrid = step(sub, lab, u, pw, valid)
            bits += int(np.asarray(bgrid, np.int64).sum())
        jax.block_until_ready(lab)
        dt = monotonic() - t0
        final = np.asarray(lab)[0][:h, :w]
    else:
        key = jax.random.PRNGKey(0)
        lab = init_labels(key, mrf, chains)
        t0 = monotonic()
        lab, stats = mrf_gibbs(
            jax.random.PRNGKey(1), lab, jnp.asarray(mrf.unary),
            jnp.asarray(mrf.pairwise), n_sweeps=sweeps, k=cfg.k,
            use_iu=use_iu, sampler=args.sampler)
        jax.block_until_ready(lab)
        dt = monotonic() - t0
        bits = int(stats.bits_used)
        final = np.asarray(lab)[0]

    n_samples = chains * sweeps * h * w
    acc = float((final == truth).mean())
    print(f"{n_samples} site samples in {dt:.2f}s -> "
          f"{n_samples/dt/1e6:.2f} MSample/s (CPU)")
    print(f"bits/sample: {bits/n_samples:.2f}  accuracy vs truth: {acc:.4f}")


if __name__ == "__main__":
    main()
