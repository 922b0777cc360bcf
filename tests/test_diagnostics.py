"""Convergence-diagnostics subsystem: rank-normalized R̂ / ESS math,
incremental-vs-one-shot agreement, engine retirement wiring, the asia
OR-gate regression (legacy split-R̂ retires early and biased, rank+ESS
keeps sampling to accuracy), and the perf gate's retirement-mode
mismatch handling."""
import numpy as np
import pytest

from repro.pgm.diagnostics import (
    Diagnostics, RunningDiagnostics, compute_diagnostics, ess_bulk,
    ess_mean, ess_tail, folded_rank_rhat, normal_quantile, rank_normalize,
    rank_rhat, split_rhat)


class TestNormalQuantile:
    def test_known_values(self):
        for p, want in [(0.5, 0.0), (0.975, 1.959964), (0.025, -1.959964),
                        (0.841344746, 1.0), (0.001, -3.090232)]:
            assert abs(float(normal_quantile(np.float64(p))) - want) < 1e-5
        assert normal_quantile(np.float64(0.0)) == -np.inf
        assert normal_quantile(np.float64(1.0)) == np.inf

    def test_vectorized_and_symmetric(self):
        p = np.linspace(0.01, 0.99, 99)
        z = normal_quantile(p)
        assert z.shape == p.shape
        assert np.all(np.diff(z) > 0)                    # monotone
        assert np.abs(z + z[::-1]).max() < 1e-9          # antisymmetric


class TestRankNormalize:
    def test_shape_and_pooling(self):
        rng = np.random.default_rng(0)
        draws = rng.normal(size=(4, 10))
        z = rank_normalize(draws)
        assert z.shape == draws.shape
        # z-scores are centered and order-preserving on the pooled draws
        assert abs(z.mean()) < 1e-9
        flat, zf = draws.ravel(), z.ravel()
        order = np.argsort(flat)
        assert np.all(np.diff(zf[order]) > 0)

    def test_monotone_invariance(self):
        """Ranks see through monotone transforms — exp(x) has the same
        rank-R̂ as x (the whole point vs plain split-R̂)."""
        rng = np.random.default_rng(1)
        draws = rng.normal(size=(6, 32))
        assert rank_rhat(draws) == pytest.approx(rank_rhat(np.exp(draws)))


class TestRhat:
    def test_iid_near_one(self):
        rng = np.random.default_rng(0)
        iid = rng.normal(0.5, 0.1, (8, 64))
        assert rank_rhat(iid) < 1.01
        assert folded_rank_rhat(iid) < 1.02

    def test_stuck_chains_blow_up(self):
        """Chains frozen at different levels must inflate rank-R̂ far
        past any sane threshold — with or without measurement noise."""
        rng = np.random.default_rng(0)
        stuck = np.concatenate(
            [np.full((4, 32), 0.1), np.full((4, 32), 0.9)])
        assert rank_rhat(stuck) > 1.5            # ranks separate the modes
        stuck += rng.normal(0, 1e-6, stuck.shape)
        assert rank_rhat(stuck) > 1.5
        assert split_rhat(stuck) > 1.5           # legacy fires here too —
        # its blind spot is *uniform* freezing, which no R̂ can see and
        # only the ESS gate guards (TestAsiaOrGateRegression)

    def test_folded_catches_scale_mismatch(self):
        """Chains agreeing in location but not spread pass rank-R̂ and
        fail folded-R̂ — the tail-behaviour variant."""
        rng = np.random.default_rng(2)
        mix = np.concatenate([rng.normal(0, 0.01, (4, 64)),
                              rng.normal(0, 1.0, (4, 64))])
        assert rank_rhat(mix) < 1.05
        assert folded_rank_rhat(mix) > 1.2

    def test_degenerate_inputs(self):
        assert rank_rhat(np.full((4, 8), 0.3)) == 1.0
        assert rank_rhat(np.zeros((4, 2))) == float("inf")  # too few rounds
        assert rank_rhat(np.zeros((1, 64))) == float("inf")  # one chain


class TestEss:
    def test_ess_bounded_by_total_draws(self):
        rng = np.random.default_rng(0)
        for shape in [(4, 16), (8, 64), (2, 128)]:
            draws = rng.normal(size=shape)
            assert 0 < ess_bulk(draws) <= draws.size
            assert 0 < ess_tail(draws) <= draws.size

    def test_iid_ess_near_total(self):
        rng = np.random.default_rng(0)
        iid = rng.normal(size=(8, 128))
        assert ess_bulk(iid) > 0.5 * iid.size

    def test_autocorrelated_ess_small(self):
        rng = np.random.default_rng(0)
        rho = 0.95
        ar = np.zeros((4, 256))
        x = np.zeros(4)
        for t in range(256):
            x = rho * x + rng.normal(size=4) * np.sqrt(1 - rho * rho)
            ar[:, t] = x
        # theory: ESS/N ~ (1-rho)/(1+rho) ~ 0.026
        assert ess_bulk(ar) < 0.1 * ar.size

    def test_constant_is_full_count(self):
        assert ess_bulk(np.full((4, 16), 0.3)) == 64.0
        assert ess_mean(np.zeros((2, 2))) == 0.0  # too short to estimate

    def test_tail_no_worse_than_bulk_on_heavy_tails(self):
        """Tail-ESS exists because tails mix slower: an AR chain's tail
        indicator must not report more effective draws than the cap."""
        rng = np.random.default_rng(3)
        draws = rng.standard_t(df=2, size=(8, 128))
        assert 0 < ess_tail(draws) <= draws.size


class TestSweepScaling:
    def test_iid_rounds_rescale_to_sweeps(self):
        """Round means of spr iid draws carry spr draws of information:
        the second-moment rescale must recover most of the total sweep
        count (and never exceed it)."""
        rng = np.random.default_rng(0)
        spr, c, r = 16, 8, 32
        draws = (rng.random((c, r, spr)) < 0.3).astype(np.float64)
        means, sqs = draws.mean(-1), (draws ** 2).mean(-1)
        d = compute_diagnostics(means, sqs, sweeps_per_round=spr)
        total = c * r * spr
        assert d.ess_bulk <= total
        assert d.ess_bulk > 0.5 * total
        # without second moments, ESS stays in round units
        d_rounds = compute_diagnostics(means, sweeps_per_round=spr)
        assert d_rounds.ess_bulk <= c * r

    def test_fully_correlated_rounds_do_not_inflate(self):
        """If every sweep in a round is identical (full within-round
        correlation), the rescale must collapse to ~round units, not
        claim spr times more effective draws."""
        rng = np.random.default_rng(1)
        spr, c, r = 16, 8, 32
        per_round = rng.random((c, r))          # one value per round
        means = per_round
        sqs = per_round ** 2                    # x binary-like: E[x^2]=E[x]^2
        d = compute_diagnostics(means, sqs, sweeps_per_round=spr)
        d_rounds = compute_diagnostics(means, sweeps_per_round=spr)
        assert d.ess_bulk <= 2.0 * d_rounds.ess_bulk


class TestIncremental:
    def test_matches_one_shot_exactly(self):
        """RunningDiagnostics fed per round equals compute_diagnostics
        over the pooled history — bit-exact, at every round count."""
        rng = np.random.default_rng(0)
        spr, c, r = 8, 6, 24
        means = rng.random((c, r))
        sqs = means + 0.1 * rng.random((c, r))
        run = RunningDiagnostics(sweeps_per_round=spr)
        for t in range(r):
            run.update(means[:, t], sqs[:, t])
            if t + 1 >= 4:
                assert run.compute() == compute_diagnostics(
                    means[:, :t + 1], sqs[:, :t + 1], sweeps_per_round=spr)
        assert run.rounds == r

    def test_cache_invalidation_and_legacy(self):
        rng = np.random.default_rng(0)
        run = RunningDiagnostics(sweeps_per_round=4)
        assert run.legacy_rhat() == float("inf")
        for t in range(8):
            run.update(rng.random(4), rng.random(4))
        d1 = run.compute()
        assert run.compute() is d1               # cached between updates
        run.update(rng.random(4), rng.random(4))
        assert run.compute() is not d1           # new round invalidates
        assert run.legacy_rhat() == pytest.approx(run.compute().rhat)

    def test_mixed_moment_forms_rejected(self):
        """Both transitions raise: dropping sq_c after supplying it AND
        introducing it after sq-less rounds (either way the mean/sq
        histories would silently misalign and corrupt the rescale)."""
        run = RunningDiagnostics()
        run.update(np.zeros(4), np.zeros(4))
        with pytest.raises(ValueError):
            run.update(np.zeros(4))
        run2 = RunningDiagnostics()
        run2.update(np.zeros(4))
        with pytest.raises(ValueError):
            run2.update(np.zeros(4), np.zeros(4))

    def test_rank_gate_matches_full_compute(self):
        """rank_gate() (the cheap pre-ESS check) must agree with the
        worst_rank_rhat of the full payload at every round count."""
        rng = np.random.default_rng(5)
        run = RunningDiagnostics(sweeps_per_round=4)
        assert run.rank_gate() == float("inf")
        for t in range(10):
            run.update(rng.random(6), rng.random(6))
            if t + 1 >= 4:
                assert run.rank_gate() == pytest.approx(
                    run.compute().worst_rank_rhat)


class TestEngineRetirement:
    def _registry(self):
        from repro.pgm import networks
        return {"sprinkler": networks.sprinkler(),
                "asia": networks.asia()}

    def test_diagnostics_payload_attached(self):
        from repro.serve import PosteriorEngine, Query

        eng = PosteriorEngine(self._registry(), chains_per_query=16,
                              burn_in=16, max_rounds=8)
        res = eng.answer(Query("sprinkler", {"wetgrass": 1}, ("rain",),
                               n_samples=2048))
        d = res.diagnostics
        assert isinstance(d, Diagnostics)
        assert d.sweeps_used == res.n_sweeps
        assert d.rhat == res.rhat
        assert 0 < d.min_ess <= res.n_sweeps * 16  # <= lanes x sweeps
        assert d.worst_rank_rhat == max(d.rank_rhat, d.folded_rhat)

    def test_bad_retirement_mode_rejected(self):
        from repro.serve import PosteriorEngine

        with pytest.raises(ValueError):
            PosteriorEngine({}, retirement="bogus")

    def test_ess_target_controls_retirement(self):
        """Same query, stricter per-query ess_target -> strictly more
        sweeps (the engine honours the per-query override)."""
        from repro.serve import PosteriorEngine, Query

        kw = dict(chains_per_query=16, burn_in=16, seed=0)
        loose = PosteriorEngine(self._registry(), **kw).answer(
            Query("sprinkler", {"wetgrass": 1}, ("rain",),
                  n_samples=10 ** 6, ess_target=10))
        strict = PosteriorEngine(self._registry(), **kw).answer(
            Query("sprinkler", {"wetgrass": 1}, ("rain",),
                  n_samples=10 ** 6, ess_target=10 ** 9))
        assert loose.n_sweeps < strict.n_sweeps
        assert loose.converged and not strict.converged

    def test_legacy_mode_matches_old_rule(self):
        """retirement="legacy" must reproduce the split-R̂-only rule:
        converged iff worst legacy split-R̂ < target."""
        from repro.serve import PosteriorEngine, Query

        eng = PosteriorEngine(self._registry(), chains_per_query=32,
                              burn_in=32, retirement="legacy", seed=1)
        res = eng.answer(Query("sprinkler", {"wetgrass": 1}, ("rain",),
                               n_samples=16384))
        assert res.converged == (res.rhat < eng.rhat_target)
        assert res.diagnostics is not None   # payload attached anyway


class TestAsiaOrGateRegression:
    """The ROADMAP failure mode: asia's near-deterministic OR gate.

    Conditioned on dysp=1, `tub` (an input of `either = tub OR lung`)
    is a rare event whose flips are coupled to the gate.  Legacy
    split-R̂ looks only at agreement between round means, so it may
    retire before the chains hold enough independent draws; the
    rank+ESS rule keeps sampling until the min-ESS gate passes.  Same
    configuration as the worked example in docs/diagnostics.md.

    How early legacy stops, and how biased its answer is, varies from
    seed to seed, so the claims are made over several seeds: both rules
    see the same chains, legacy never outlasts rank and stops sooner in
    total, every rank answer passes its ESS gate and lands within
    tolerance of the exact answer, and legacy's mean error is larger.
    """

    def test_legacy_retires_early_and_biased_rank_keeps_sampling(self):
        from repro.pgm import networks
        from repro.serve import PosteriorEngine, Query

        q = Query("asia", {"dysp": 1}, ("tub",), n_samples=10 ** 6)
        exact = networks.asia().marginals_exact({"dysp": 1})
        idx = networks.asia().index("tub")
        runs = []
        for seed in range(4):
            kw = dict(chains_per_query=16, burn_in=16, sweeps_per_round=16,
                      max_rounds=48, seed=seed)
            legacy = PosteriorEngine({"asia": networks.asia()},
                                     retirement="legacy", **kw).answer(q)
            rank = PosteriorEngine({"asia": networks.asia()},
                                   retirement="rank", **kw).answer(q)
            runs.append((legacy, rank))

        def err(res):
            return float(abs(res.marginal("tub") - exact[idx]).max())

        assert all(lg.converged and rk.converged for lg, rk in runs)
        # legacy never stops later than rank, and stops sooner overall
        assert all(lg.n_sweeps <= rk.n_sweeps for lg, rk in runs)
        assert (sum(lg.n_sweeps for lg, _ in runs)
                < sum(rk.n_sweeps for _, rk in runs))
        # rank kept sampling until the ESS gate passed, and is accurate
        assert all(rk.diagnostics.min_ess >= 100 for _, rk in runs)
        assert all(err(rk) < 0.02 for _, rk in runs)
        # the earlier stops cost accuracy on average
        assert (np.mean([err(lg) for lg, _ in runs])
                > np.mean([err(rk) for _, rk in runs]))


class TestRegressionGateModes:
    """check_serve_regression: ESS/s in the diff table, retirement-mode
    mismatch = setup error (exit 2), never a silent pass."""

    def _report(self, mode="rank", ess=100.0):
        return {
            "retirement": mode,
            "runs": [{
                "name": "r1",
                "warm": {"queries_per_s": 10.0, "ess_per_s": ess},
            }],
        }

    def test_mode_mismatch_is_setup_error(self):
        from benchmarks.check_serve_regression import check

        failures, setup = check(
            self._report("rank"), self._report("legacy"),
            tolerance=0.3, min_stream_speedup=1.5)
        assert any(f.metric == "retirement" for f in setup)

    def test_matching_modes_pass(self):
        from benchmarks.check_serve_regression import check

        failures, setup = check(
            self._report(), self._report(),
            tolerance=0.3, min_stream_speedup=1.5)
        assert not failures and not setup

    def test_ess_regression_fails_gate(self):
        from benchmarks.check_serve_regression import check

        failures, setup = check(
            self._report(ess=10.0), self._report(ess=100.0),
            tolerance=0.3, min_stream_speedup=1.5)
        assert any(f.metric == "r1.warm.ess_per_s" for f in failures)
        assert not setup

    def test_missing_baseline_ess_is_setup_error(self):
        from benchmarks.check_serve_regression import check

        base = self._report()
        del base["runs"][0]["warm"]["ess_per_s"]
        failures, setup = check(
            self._report(), base, tolerance=0.3, min_stream_speedup=1.5)
        assert any(f.metric == "r1.warm.ess_per_s" for f in setup)

    def test_missing_baseline_stream_ess_is_setup_error(self):
        from benchmarks.check_serve_regression import check

        cur, base = self._report(), self._report()
        for rep in (cur, base):
            rep["stream"] = {"queries_per_s": 50.0, "speedup": 2.0,
                             "identical": True, "ess_per_s": 1000.0}
        del base["stream"]["ess_per_s"]
        failures, setup = check(cur, base, tolerance=0.3,
                                min_stream_speedup=1.5)
        assert any(f.metric == "stream.ess_per_s" for f in setup)
        assert not failures
