"""Posterior query service: evidence-conditioned marginals vs exact
enumeration, clamp invariance, thinning/accounting arithmetic,
plan-cache behaviour (incl. mesh fingerprints, on-disk persistence),
CLI smoke."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.pgm import (
    compile_bayesnet, compile_factor_graph, compile_mrf, init_fg_states,
    init_mrf_states, init_states, make_sweep, networks, run_gibbs)
from repro.pgm.graph import MRFGrid
from repro.serve import (
    AdmissionQueue, PlanCache, PosteriorEngine, Query, load_compiled,
    make_fg_round_runner, make_mrf_round_runner, make_round_runner,
    parse_evidence, persisted_plan_path, save_compiled, split_rhat)


def _registry():
    return {"sprinkler": networks.sprinkler(), "asia": networks.asia()}


class TestEvidenceConditioning:
    def test_clamped_node_never_changes(self):
        """Evidence nodes are excluded from every gather plan, so a sweep
        can never resample them — the clamp is structural, not masked."""
        bn = networks.asia()
        prog = compile_bayesnet(bn, observed=("smoke", "xray"))
        for plan in prog.plans:
            assert not (set(plan.nodes.tolist()) & set(prog.observed))
        sweep = make_sweep(prog)
        ev = np.array([[1, 0]] * 8, np.int32)
        x = init_states(jax.random.PRNGKey(0), prog, 8, ev)
        for i in range(20):
            x, _ = sweep(jax.random.PRNGKey(i), x)
        x = np.asarray(x)
        assert (x[:, bn.index("smoke")] == 1).all()
        assert (x[:, bn.index("xray")] == 0).all()

    def test_run_gibbs_posterior_matches_enumeration(self):
        bn = networks.sprinkler()
        prog = compile_bayesnet(bn, observed=("wetgrass",))
        _, counts, _ = run_gibbs(
            jax.random.PRNGKey(0), prog, n_chains=256, n_sweeps=600,
            burn_in=150, evidence=(1,))
        marg = np.asarray(counts, np.float64)
        marg /= marg.sum(-1, keepdims=True)
        exact = bn.marginals_exact({"wetgrass": 1})
        for v in prog.free_nodes:
            assert np.abs(marg[v, :2] - exact[v]).max() < 0.03, bn.names[v]

    def test_all_observed_rejected(self):
        bn = networks.sprinkler()
        with pytest.raises(ValueError):
            compile_bayesnet(bn, observed=tuple(range(bn.n_nodes)))

    def test_conditional_oracle_consistency(self):
        """P(v) == sum_e P(v|e) P(e) — the oracle obeys total probability."""
        bn = networks.sprinkler()
        prior = bn.marginals_exact()
        w = bn.marginals_exact()[3]  # P(wetgrass)
        mixed = sum(
            w[e] * bn.marginals_exact({"wetgrass": e})[2] for e in (0, 1))
        assert np.abs(mixed - prior[2]).max() < 1e-9


class TestEngine:
    def test_sprinkler_posterior_matches_enumeration(self):
        eng = PosteriorEngine(_registry(), chains_per_query=64, burn_in=64)
        res = eng.answer(Query("sprinkler", {"wetgrass": 1},
                               ("rain", "sprinkler"), n_samples=32768))
        exact = networks.sprinkler().marginals_exact({"wetgrass": 1})
        assert np.abs(res.marginal("rain") - exact[2]).max() < 0.03
        assert np.abs(res.marginal("sprinkler") - exact[1]).max() < 0.03
        assert res.converged and res.rhat < 1.05

    def test_asia_posterior_matches_enumeration(self):
        eng = PosteriorEngine(_registry(), chains_per_query=64,
                              burn_in=256, sweeps_per_round=64)
        res = eng.answer(Query("asia", {"smoke": 1, "dysp": 1},
                               ("bronc", "lung"), n_samples=300_000))
        exact = networks.asia().marginals_exact({"smoke": 1, "dysp": 1})
        bn = networks.asia()
        assert np.abs(res.marginal("bronc") - exact[bn.index("bronc")]).max() < 0.04
        assert np.abs(res.marginal("lung") - exact[bn.index("lung")]).max() < 0.04

    def test_batch_mixed_patterns_and_networks(self):
        """One batch spanning two networks and two evidence patterns comes
        back in request order with per-query evidence respected."""
        eng = PosteriorEngine(_registry(), chains_per_query=32, burn_in=32)
        qs = [
            Query("sprinkler", {"wetgrass": 1}, ("rain",), n_samples=16384),
            Query("asia", {"smoke": 0}, ("bronc",), n_samples=8192),
            Query("sprinkler", {"wetgrass": 0}, ("rain",), n_samples=16384),
        ]
        res = eng.answer_batch(qs)
        assert [r.query is q for r, q in zip(res, qs)] == [True] * 3
        spr = networks.sprinkler()
        e1 = spr.marginals_exact({"wetgrass": 1})[2]
        e0 = spr.marginals_exact({"wetgrass": 0})[2]
        assert np.abs(res[0].marginal("rain") - e1).max() < 0.04
        assert np.abs(res[2].marginal("rain") - e0).max() < 0.04
        # the two sprinkler queries share a pattern -> same compiled plan
        assert eng.cache.stats.misses == 2  # one per (network, pattern) pair

    def test_query_var_cannot_be_observed(self):
        eng = PosteriorEngine(_registry())
        with pytest.raises(ValueError):
            eng.answer(Query("sprinkler", {"rain": 1}, ("rain",)))

    def test_unknown_network_rejected(self):
        with pytest.raises(KeyError):
            PosteriorEngine({}).answer(Query("nope", {}, ()))

    def test_split_rhat_behaviour(self):
        rng = np.random.default_rng(0)
        mixed = rng.normal(0.5, 0.1, (8, 32))
        assert split_rhat(mixed) < 1.1
        stuck = np.concatenate(
            [np.full((4, 32), 0.1), np.full((4, 32), 0.9)])
        stuck += rng.normal(0, 1e-3, stuck.shape)
        assert split_rhat(stuck) > 2.0
        assert split_rhat(np.full((4, 8), 0.3)) == 1.0
        assert split_rhat(np.zeros((4, 2))) == float("inf")  # too few rounds


class TestThinning:
    def test_per_lane_offsets_match_scalar(self):
        """A uniform per-lane offset vector keeps every lane on the same
        thinning schedule as the scalar form (the vector form exists so
        backfilled slots can restart their phase mid-group)."""
        prog = compile_bayesnet(networks.sprinkler())
        runner = make_round_runner(
            prog, sweeps_per_round=16, thin=3, use_iu=True)
        x = init_states(jax.random.PRNGKey(0), prog, 4)
        _, c_scalar, _, _, _ = runner(jax.random.PRNGKey(1), x, jnp.int32(16))
        _, c_vec, _, _, _ = runner(
            jax.random.PRNGKey(1), x, jnp.full((4,), 16, jnp.int32))
        assert np.array_equal(np.asarray(c_scalar), np.asarray(c_vec))
        # mixed offsets: lanes 2,3 run a fresh phase (6 kept in [0,16))
        # while lanes 0,1 continue an old one (5 kept in [16,32))
        _, c_mix, _, _, _ = runner(
            jax.random.PRNGKey(1), x, jnp.asarray([16, 16, 0, 0], jnp.int32))
        kept = np.asarray(c_mix).sum(-1)[:, 0]
        assert kept.tolist() == [5, 5, 6, 6]

    def test_round_runner_uses_global_offset(self):
        """Draws are kept on *global* post-burn-in sweep indices that are
        multiples of ``thin`` — a round-relative phase (the old bug) kept
        ceil(spr/thin) draws every round regardless of alignment."""
        prog = compile_bayesnet(networks.sprinkler())
        runner = make_round_runner(
            prog, sweeps_per_round=16, thin=3, use_iu=True)
        x = init_states(jax.random.PRNGKey(0), prog, 4)
        x, counts, _, _, _ = runner(jax.random.PRNGKey(1), x, jnp.int32(0))
        # kept global sweeps in [0, 16): 0, 3, 6, 9, 12, 15
        assert int(np.asarray(counts).sum(-1)[0, 0]) == 6
        x, counts, _, _, _ = runner(jax.random.PRNGKey(2), x, jnp.int32(16))
        # kept global sweeps in [16, 32): 18, 21, 24, 27, 30 — the
        # round-relative restart kept 6 with the wrong spacing
        assert int(np.asarray(counts).sum(-1)[0, 0]) == 5

    def test_engine_kept_count_accounting(self):
        """Result.n_samples equals lanes x (global multiples of thin in
        the sampled sweep range), not lanes x rounds x ceil(spr/thin)."""
        eng = PosteriorEngine(
            _registry(), chains_per_query=8, burn_in=16, sweeps_per_round=16,
            thin=3, rhat_target=0.0, min_rounds=4, max_rounds=4)
        res = eng.answer(Query("sprinkler", {"wetgrass": 1}, ("rain",),
                               n_samples=10**6))
        # 4 rounds x 16 sweeps: multiples of 3 in [0, 64) -> 22 per lane
        assert res.n_samples == 8 * 22  # old accounting claimed 8 * 24
        assert abs(res.marginal("rain").sum() - 1.0) < 1e-9

    def test_thin_one_unchanged(self):
        eng = PosteriorEngine(
            _registry(), chains_per_query=8, burn_in=16, sweeps_per_round=16,
            rhat_target=0.0, min_rounds=4, max_rounds=4)
        res = eng.answer(Query("sprinkler", {"wetgrass": 1}, ("rain",),
                               n_samples=10**6))
        assert res.n_samples == 8 * 64


class TestPlanCache:
    def test_hit_miss_and_eviction(self):
        cache = PlanCache(capacity=2)
        a, hit = cache.get("a", lambda: "A")
        assert (a, hit) == ("A", False)
        a, hit = cache.get("a", lambda: "A2")
        assert (a, hit) == ("A", True)  # no rebuild on hit
        cache.get("b", lambda: "B")
        cache.get("c", lambda: "C")  # evicts "a" (LRU)
        _, hit = cache.get("a", lambda: "A3")
        assert not hit
        assert cache.stats.hits == 1 and cache.stats.evictions == 2

    def test_same_pattern_hits_different_pattern_misses(self):
        eng = PosteriorEngine(_registry(), chains_per_query=8,
                              burn_in=16, max_rounds=4)
        eng.answer(Query("sprinkler", {"wetgrass": 1}, ("rain",),
                         n_samples=256))
        assert eng.cache.stats.misses == 1
        # same pattern, different observed value -> hit, no recompile
        eng.answer(Query("sprinkler", {"wetgrass": 0}, ("rain",),
                         n_samples=256))
        assert (eng.cache.stats.hits, eng.cache.stats.misses) == (1, 1)
        # different pattern -> miss
        eng.answer(Query("sprinkler", {"cloudy": 1}, ("rain",),
                         n_samples=256))
        assert (eng.cache.stats.hits, eng.cache.stats.misses) == (1, 2)

    def test_mesh_and_single_device_plan_keys_never_collide(self):
        """A runner jitted with sharding constraints for one mesh layout
        must not be served to an engine on another: keys carry the mesh
        fingerprint (shape + axis names + device ids), None for
        single-device."""
        from repro.launch.mesh import make_serve_mesh, mesh_fingerprint

        cache = PlanCache()
        e1 = PosteriorEngine(_registry(), chains_per_query=8, burn_in=16,
                             max_rounds=4, cache=cache)
        e2 = PosteriorEngine(_registry(), chains_per_query=8, burn_in=16,
                             max_rounds=4, cache=cache,
                             mesh=make_serve_mesh((1,)))
        assert mesh_fingerprint(e2.mesh) == (
            (1,), ("batch",), (jax.devices()[0].id,))
        assert (e1._plan_key("sprinkler", (3,))
                != e2._plan_key("sprinkler", (3,)))
        q = Query("sprinkler", {"wetgrass": 1}, ("rain",), n_samples=256)
        e1.answer(q)
        e2.answer(q)  # same pattern, different mesh -> must MISS
        assert (cache.stats.hits, cache.stats.misses) == (0, 2)
        e2.answer(q)  # same mesh -> hit
        assert (cache.stats.hits, cache.stats.misses) == (1, 2)

    def test_reregister_invalidates_cached_plans(self):
        """Replacing a network must not keep serving its old CPTs."""
        eng = PosteriorEngine(_registry(), chains_per_query=8,
                              burn_in=16, max_rounds=4)
        eng.answer(Query("sprinkler", {"wetgrass": 1}, ("rain",),
                         n_samples=256))
        assert len(eng.cache) == 1
        eng.register("sprinkler", networks.sprinkler())  # fresh object
        assert len(eng.cache) == 0
        eng.register("asia", eng.networks["asia"])  # same object -> no-op
        # re-registering did not clear unrelated stats bookkeeping
        eng.answer(Query("sprinkler", {"wetgrass": 1}, ("rain",),
                         n_samples=256))
        assert eng.cache.stats.misses == 2


class TestPlanPersistence:
    def test_save_load_roundtrip(self, tmp_path):
        """Every tensor of a CompiledBN survives the .npz round-trip."""
        bn = networks.asia()
        prog = compile_bayesnet(bn, observed=("smoke",))
        path = persisted_plan_path(
            str(tmp_path), "asia", prog.observed, bn, k=prog.k,
            quantize_cpt_bits=16)
        save_compiled(path, prog)
        loaded = load_compiled(path, bn)
        assert loaded is not None
        assert np.array_equal(loaded.log_cpt, prog.log_cpt)
        assert (loaded.max_card, loaded.k) == (prog.max_card, prog.k)
        assert loaded.observed == prog.observed
        assert len(loaded.plans) == len(prog.plans)
        for a, b in zip(loaded.plans, prog.plans):
            for f in ("nodes", "card", "self_base_off", "self_pa",
                      "self_pa_stride", "ch_off", "ch_vstride", "ch_self",
                      "ch_self_stride", "ch_pa", "ch_pa_stride"):
                assert np.array_equal(getattr(a, f), getattr(b, f)), f

    def test_warm_start_skips_compiler_chain(self, tmp_path, monkeypatch):
        """Second engine over the same cache dir must never reach
        compile_bayesnet — the persisted plans stand in for the whole
        compiler chain."""
        kw = dict(chains_per_query=8, burn_in=16, max_rounds=4, seed=5)
        q = Query("sprinkler", {"wetgrass": 1}, ("rain",), n_samples=256)
        e1 = PosteriorEngine(_registry(), plan_cache_dir=str(tmp_path), **kw)
        r1 = e1.answer(q)
        assert any(f.endswith(".npz") for f in os.listdir(tmp_path))

        import repro.serve.families as families_mod

        def boom(*a, **k):
            raise AssertionError("compiler chain ran despite persisted plan")

        monkeypatch.setattr(families_mod, "compile_bayesnet", boom)
        e2 = PosteriorEngine(_registry(), plan_cache_dir=str(tmp_path), **kw)
        r2 = e2.answer(q)
        # same seed, same plan -> bit-identical marginals
        assert np.array_equal(r1.marginal("rain"), r2.marginal("rain"))

    def test_content_fingerprint_keys_the_file(self, tmp_path):
        """A renamed/retrained network must not collide with a stale
        persisted plan: the path folds in the CPT content hash."""
        spr, asia = networks.sprinkler(), networks.asia()
        p1 = persisted_plan_path(str(tmp_path), "net", (0,), spr,
                                 k=12, quantize_cpt_bits=16)
        p2 = persisted_plan_path(str(tmp_path), "net", (0,), asia,
                                 k=12, quantize_cpt_bits=16)
        assert p1 != p2

    def test_corrupt_file_degrades_to_recompile(self, tmp_path):
        path = os.path.join(str(tmp_path), "plan_bad.npz")
        with open(path, "wb") as f:
            f.write(b"not an npz")
        assert load_compiled(path, networks.sprinkler()) is None


class TestParseEvidence:
    def test_parse_and_errors(self):
        assert parse_evidence("smoke=1,dysp=0") == {"smoke": 1, "dysp": 0}
        assert parse_evidence("") == {}
        with pytest.raises(ValueError):
            parse_evidence("smoke")
        with pytest.raises(ValueError):
            parse_evidence("smoke=yes")


class TestServeCLI:
    @pytest.mark.slow
    def test_cli_smoke(self, tmp_path):
        from conftest import run_subprocess

        code = (
            "from repro.serve.cli import main\n"
            "main(['--network', 'sprinkler', '--queries', '6',\n"
            "      '--patterns', '2', '--chains', '8', '--budget', '512',\n"
            "      '--burn-in', '16', '--show', '1'])\n"
        )
        rc, out = run_subprocess(code)
        assert rc == 0, out
        assert "warm/cold speedup" in out and "queries/s" in out

    @pytest.mark.slow
    def test_cli_stream_smoke(self, tmp_path):
        """--stream replays open-loop through the admission queue and
        --plan-cache-dir persists compiled plans on the way."""
        from conftest import run_subprocess

        cache_dir = str(tmp_path / "plans")
        code = (
            "from repro.serve.cli import main\n"
            "main(['--network', 'sprinkler', '--queries', '8',\n"
            "      '--patterns', '2', '--chains', '8', '--budget', '256',\n"
            "      '--burn-in', '16', '--stream', '--rate', '200',\n"
            f"      '--max-wait-ms', '50', '--plan-cache-dir', {cache_dir!r}])\n"
        )
        rc, out = run_subprocess(code)
        assert rc == 0, out
        assert "stream:" in out and "p50" in out and "speedup" in out
        import os
        assert any(f.endswith(".npz") for f in os.listdir(cache_dir)), out


class TestPallasSampler:
    """``sampler="pallas"`` ≡ ``sampler="xla"`` bit for bit, at every
    layer the flag reaches: the three family round runners and the
    queued serving path (docs/kernels.md pins the contract)."""

    @staticmethod
    def _assert_rounds_identical(run_xla, run_pallas, key, x, offset):
        out_x = run_xla(key, x, offset)
        out_p = run_pallas(key, x, offset)
        for a, b in zip(jax.tree_util.tree_leaves(out_x),
                        jax.tree_util.tree_leaves(out_p)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bn_round_runner_bitwise(self):
        prog = compile_bayesnet(networks.asia())
        mk = lambda s: make_round_runner(
            prog, sweeps_per_round=4, thin=1, use_iu=True, sampler=s)
        x = init_states(jax.random.PRNGKey(0), prog, 4)
        self._assert_rounds_identical(
            mk("xla"), mk("pallas"), jax.random.PRNGKey(1), x, jnp.int32(0))

    def test_mrf_round_runner_bitwise(self):
        # an even width, and an odd one (a pad column in the packed
        # colour) with clamped sites
        rng = np.random.default_rng(0)
        for (h, w), observed in (((6, 6), ()), ((5, 7), (0, 8, 13, 34))):
            mrf_prog = compile_mrf(MRFGrid.potts(
                rng.normal(0, 1, (h, w, 3)).astype(np.float32), beta=0.6),
                observed=observed)
            mk = lambda s: make_mrf_round_runner(
                mrf_prog, sweeps_per_round=4, thin=1, use_iu=True,
                sampler=s)
            x = init_mrf_states(
                jax.random.PRNGKey(0), mrf_prog, 2,
                jnp.ones((2, len(observed)), jnp.int32) if observed else None)
            self._assert_rounds_identical(
                mk("xla"), mk("pallas"), jax.random.PRNGKey(2), x,
                jnp.int32(0))

    def test_ising_round_runner_bitwise(self):
        prog = compile_factor_graph(networks.ising_torus(4, beta=0.4))
        mk = lambda s: make_fg_round_runner(
            prog, sweeps_per_round=4, thin=1, use_iu=True, sampler=s)
        x = init_fg_states(jax.random.PRNGKey(0), prog, 4)
        self._assert_rounds_identical(
            mk("xla"), mk("pallas"), jax.random.PRNGKey(3), x, jnp.int32(0))

    def test_engine_marginals_bitwise(self):
        """End to end through answer_batch: identical marginals, counts,
        and diagnostics for the same seed."""
        kw = dict(chains_per_query=4, burn_in=8, sweeps_per_round=8,
                  max_rounds=4, seed=11)
        qs = [Query("sprinkler", {"wetgrass": 1}, ("rain",), n_samples=256),
              Query("asia", {"smoke": 1}, ("lung",), n_samples=256)]
        rx = PosteriorEngine(_registry(), sampler="xla", **kw).answer_batch(qs)
        rp = PosteriorEngine(
            _registry(), sampler="pallas", **kw).answer_batch(qs)
        for a, b in zip(rx, rp):
            assert a.n_samples == b.n_samples
            for var in a.marginals:
                np.testing.assert_array_equal(a.marginal(var),
                                              b.marginal(var))

    @pytest.mark.slow
    def test_queued_identical_to_answer_batch_under_pallas(self):
        """The queue reroutes scheduling, never sampling — so streamed
        dispatch under the pallas sampler still matches answer_batch."""
        kw = dict(chains_per_query=4, burn_in=8, sweeps_per_round=8,
                  max_rounds=4, sampler="pallas", seed=11)
        qs = [Query("sprinkler", {"wetgrass": 1}, ("rain",), n_samples=256),
              Query("sprinkler", {"wetgrass": 0}, ("rain",), n_samples=256)]
        ref = PosteriorEngine(_registry(), **kw).answer_batch(qs)
        queue = AdmissionQueue(PosteriorEngine(_registry(), **kw),
                               max_wait_ms=3_600_000.0)
        try:
            hs = [queue.submit(q) for q in qs]
            queue.flush()
            got = [h.result(timeout=300.0) for h in hs]
        finally:
            queue.close()
        for a, b in zip(ref, got):
            assert a.n_samples == b.n_samples
            for var in a.marginals:
                np.testing.assert_array_equal(a.marginal(var),
                                              b.marginal(var))
