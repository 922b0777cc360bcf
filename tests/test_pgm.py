"""PGM substrate: coloring invariants, Gibbs convergence to exact
marginals, compiler-chain correctness, MRF energy descent."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.pgm import (
    BNSweepStats,
    checkerboard,
    color_bayesnet,
    compile_bayesnet,
    init_labels,
    mrf_gibbs,
    networks,
    run_gibbs,
    sum_sweep_stats,
    verify_coloring,
)


class TestColoring:
    def test_checkerboard_two_colors(self):
        c = checkerboard(10, 7)
        assert set(np.unique(c)) == {0, 1}
        assert (c[1:, :] != c[:-1, :]).all()
        assert (c[:, 1:] != c[:, :-1]).all()

    @settings(max_examples=20, deadline=None)
    @given(st.integers(3, 30), st.integers(0, 10_000))
    def test_dsatur_valid_on_random_nets(self, n, seed):
        bn = networks.random_bayesnet(n, seed=seed)
        groups = color_bayesnet(bn)
        assert verify_coloring(bn.moralized(), groups)

    def test_mrf_checkerboard_is_blockgibbs(self):
        """The paper's claim: lattice MRFs need exactly 2 colors."""
        assert checkerboard(8, 8).max() == 1


class TestBNGibbs:
    def test_asia_converges_to_exact(self):
        bn = networks.asia()
        prog = compile_bayesnet(bn)
        _, counts, stats = run_gibbs(
            jax.random.PRNGKey(0), prog, n_chains=256, n_sweeps=800,
            burn_in=200)
        marg = np.asarray(counts, np.float64)
        marg /= marg.sum(-1, keepdims=True)
        exact = bn.marginals_exact()
        for v in range(bn.n_nodes):
            e = exact[v] / exact[v].sum()
            assert np.abs(marg[v, :2] - e).max() < 0.03, (bn.names[v],)

    def test_sprinkler_converges(self):
        bn = networks.sprinkler()
        prog = compile_bayesnet(bn)
        _, counts, _ = run_gibbs(
            jax.random.PRNGKey(1), prog, n_chains=256, n_sweeps=800,
            burn_in=200)
        marg = np.asarray(counts, np.float64)
        marg /= marg.sum(-1, keepdims=True)
        exact = bn.marginals_exact()
        for v in range(bn.n_nodes):
            e = exact[v] / exact[v].sum()
            assert np.abs(marg[v, :2] - e).max() < 0.03

    def test_exact_exp_and_iu_agree(self):
        bn = networks.asia()
        prog = compile_bayesnet(bn)
        _, c1, _ = run_gibbs(jax.random.PRNGKey(2), prog, n_chains=128,
                             n_sweeps=500, burn_in=100, use_iu=True)
        _, c2, _ = run_gibbs(jax.random.PRNGKey(2), prog, n_chains=128,
                             n_sweeps=500, burn_in=100, use_iu=False)
        m1 = np.asarray(c1, np.float64); m1 /= m1.sum(-1, keepdims=True)
        m2 = np.asarray(c2, np.float64); m2 /= m2.sum(-1, keepdims=True)
        assert np.abs(m1 - m2).max() < 0.05  # IU quantization is negligible

    def test_forward_sampling_oracle(self):
        """Gibbs marginals on a random net match ancestral sampling."""
        bn = networks.random_bayesnet(12, seed=7, max_card=3)
        prog = compile_bayesnet(bn)
        _, counts, _ = run_gibbs(jax.random.PRNGKey(3), prog, n_chains=256,
                                 n_sweeps=600, burn_in=150)
        marg = np.asarray(counts, np.float64)
        marg /= marg.sum(-1, keepdims=True)
        fwd = bn.sample_forward(np.random.default_rng(0), 200_000)
        for v in range(bn.n_nodes):
            f = np.bincount(fwd[:, v], minlength=prog.max_card) / len(fwd)
            assert np.abs(marg[v] - f).max() < 0.04, v


class TestMRFGibbs:
    def test_energy_decreases_and_segmentation_accurate(self):
        mrf, truth = networks.penguin_task(h=48, w=32)
        labels = init_labels(jax.random.PRNGKey(0), mrf, 2)
        e0 = mrf.energy(np.asarray(labels[0]))
        out, stats = mrf_gibbs(
            jax.random.PRNGKey(1), labels, jnp.asarray(mrf.unary),
            jnp.asarray(mrf.pairwise), n_sweeps=30)
        e1 = mrf.energy(np.asarray(out[0]))
        assert e1 < e0
        acc = (np.asarray(out[0]) == truth).mean()
        assert acc > 0.9, acc

    def test_stereo_truncated_linear(self):
        mrf, truth = networks.art_task(h=32, w=40, n_labels=8)
        labels = init_labels(jax.random.PRNGKey(2), mrf, 1)
        out, _ = mrf_gibbs(
            jax.random.PRNGKey(3), labels, jnp.asarray(mrf.unary),
            jnp.asarray(mrf.pairwise), n_sweeps=30)
        err = np.abs(np.asarray(out[0]).astype(int) - truth).mean()
        assert err < 1.0, err  # mean disparity error below one level

    def test_bits_per_sample_tracked(self):
        mrf, _ = networks.penguin_task(h=16, w=16)
        labels = init_labels(jax.random.PRNGKey(4), mrf, 1)
        _, stats = mrf_gibbs(
            jax.random.PRNGKey(5), labels, jnp.asarray(mrf.unary),
            jnp.asarray(mrf.pairwise), n_sweeps=5)
        n_samples = 16 * 16 * 5
        bits = float(stats.bits_used) / n_samples
        assert 1.0 < bits < 8.0  # binary labels: H+2 <= 3ish


PACKED_CASES = [
    # (H, W), clamp mask shape, per-lane beta
    ((6, 8), None, False),
    ((6, 8), "grid", False),
    ((6, 8), "lanes", True),
    ((5, 7), None, False),
    ((5, 7), "grid", True),
    ((5, 7), "lanes", False),
]
PACKED_IDS = [f"{h}x{w}-{c}-{'beta' if b else 'gibbs'}"
              for (h, w), c, b in PACKED_CASES]


@functools.lru_cache(maxsize=None)
def _packed_halfstep(shape, clamp_kind, with_beta, parity):
    """One traced half-step with the sampler's input and output kept:
    (labels in, labels out, stats, weights handed to ky_sample, its
    KYResult, clamp as (B, H, W), beta or None, unary, pairwise)."""
    from repro.pgm import gibbs

    h, w = shape
    b, n_labels = 2, 3
    rng = np.random.default_rng(h * 100 + w)
    unary = jnp.asarray(rng.normal(0, 1, (h, w, n_labels)), jnp.float32)
    pairwise = jnp.asarray(rng.random((n_labels, n_labels)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, n_labels, (b, h, w)), jnp.int32)
    clamp = None
    if clamp_kind == "grid":
        clamp = jnp.asarray(rng.random((h, w)) < 0.3)
    elif clamp_kind == "lanes":
        clamp = jnp.asarray(rng.random((b, h, w)) < 0.3)
    beta = jnp.asarray([0.5, 2.0], jnp.float32) if with_beta else None
    seen, ky_sample = {}, gibbs.ky_sample

    def spy(key, wts, **kw):
        seen["res"] = res = ky_sample(key, wts, **kw)
        seen["wts"] = wts
        return res

    def run(key, labels):
        out, stats = gibbs.checkerboard_halfstep.__wrapped__(
            key, labels, unary, pairwise, jnp.int32(parity), clamp=clamp,
            beta=beta)
        return out, stats, seen["wts"], seen["res"]

    gibbs.ky_sample = spy
    try:
        out, stats, wts, res = jax.jit(run)(jax.random.PRNGKey(7), labels)
    finally:
        gibbs.ky_sample = ky_sample
    full_clamp = (np.zeros((b, h, w), bool) if clamp is None
                  else np.broadcast_to(np.asarray(clamp), (b, h, w)))
    return dict(labels=np.asarray(labels), out=np.asarray(out),
                stats=stats, wts=np.asarray(wts), res=res,
                clamp=full_clamp, beta=beta, unary=unary,
                pairwise=pairwise)


def _active_sites(h, w, parity):
    """(row, packed column, grid column) of each packed lane of a row."""
    for r in range(h):
        for j in range((w + w % 2) // 2):
            yield r, j, 2 * j + (r + parity) % 2


class TestPackedHalfstep:
    """``checkerboard_halfstep`` walks only the active colour, packed to
    (B, H, W_even / 2) lanes; odd widths carry a pad column."""

    @pytest.mark.parametrize("case", PACKED_CASES, ids=PACKED_IDS)
    def test_active_weights_equal_full_grid_probe(self, case):
        from repro.pgm.gibbs import (
            _weights_from_energies, neighbor_pair_energy, site_weights)

        (h, w), clamp_kind, with_beta = case
        for parity in (0, 1):
            got = _packed_halfstep((h, w), clamp_kind, with_beta, parity)
            lab = jnp.asarray(got["labels"])
            if got["beta"] is None:
                full = site_weights(lab, got["unary"], got["pairwise"])
            else:
                e = got["unary"][None] + neighbor_pair_energy(
                    lab, got["pairwise"])
                full = _weights_from_energies(
                    e * got["beta"][:, None, None, None])
            full = np.asarray(full)
            packed = got["wts"].reshape(2, h, -1, full.shape[-1])
            for r, j, c in _active_sites(h, w, parity):
                if c < w:
                    np.testing.assert_array_equal(packed[:, r, j],
                                                  full[:, r, c])

    @pytest.mark.parametrize("case", PACKED_CASES, ids=PACKED_IDS)
    def test_only_active_free_sites_change(self, case):
        (h, w), clamp_kind, with_beta = case
        for parity in (0, 1):
            got = _packed_halfstep((h, w), clamp_kind, with_beta, parity)
            out, lab = got["out"], got["labels"]
            assert out.shape == lab.shape and out.dtype == lab.dtype
            active = (np.add.outer(np.arange(h), np.arange(w)) % 2
                      == parity)[None]
            frozen = ~active | got["clamp"]
            np.testing.assert_array_equal(out[frozen], lab[frozen])
            # the free active sites take the sampler's draw
            sample = np.asarray(got["res"].sample).reshape(2, h, -1)
            for r, j, c in _active_sites(h, w, parity):
                if c < w:
                    free = ~got["clamp"][:, r, c]
                    np.testing.assert_array_equal(
                        out[free, r, c], sample[free, r, j])

    @pytest.mark.parametrize("case", PACKED_CASES, ids=PACKED_IDS)
    def test_stats_sum_active_free_sites(self, case):
        (h, w), clamp_kind, with_beta = case
        for parity in (0, 1):
            got = _packed_halfstep((h, w), clamp_kind, with_beta, parity)
            bits = np.asarray(got["res"].bits_used).reshape(2, h, -1)
            att = np.asarray(got["res"].attempts).reshape(2, h, -1)
            want_bits = want_att = 0
            for r, j, c in _active_sites(h, w, parity):
                if c < w:
                    free = ~got["clamp"][:, r, c]
                    want_bits += int(bits[free, r, j].sum())
                    want_att += int(att[free, r, j].sum())
            assert int(got["stats"].bits_used) == want_bits
            assert int(got["stats"].attempts) == want_att
            assert want_att > 0

    @pytest.mark.parametrize("case", PACKED_CASES, ids=PACKED_IDS)
    def test_sampler_walks_half_the_padded_grid(self, case):
        (h, w), clamp_kind, with_beta = case
        for parity in (0, 1):
            got = _packed_halfstep((h, w), clamp_kind, with_beta, parity)
            assert got["wts"].shape == (2 * h * (w + w % 2) // 2, 3)


class TestSweepStatsOverflow:
    def test_sum_sweep_stats_survives_int32_wrap_magnitudes(self):
        """Totals that wrapped the old int32 scan carry stay exact: the
        old path accumulated bits/attempts in an int32 carry across all
        sweeps, so 8 sweeps of 2**30 bits summed to 2**33 mod 2**32 = 0
        (and long real runs went negative)."""
        per_sweep = BNSweepStats(
            bits_used=np.full(8, 2**30, np.int32),
            attempts=np.full(8, 2**30, np.int32))
        with np.errstate(over="ignore"):
            wrapped = per_sweep.bits_used.sum(dtype=np.int32)
        assert wrapped == 0  # what the old carry produced
        tot = sum_sweep_stats(per_sweep)
        assert tot.bits_used.dtype == np.int64
        assert int(tot.bits_used) == 8 * 2**30
        assert int(tot.attempts) == 8 * 2**30

    def test_run_gibbs_stats_are_host_int64_totals(self):
        from repro.pgm.compile import _run_gibbs_device

        bn = networks.sprinkler()
        prog = compile_bayesnet(bn)
        _, _, stats = run_gibbs(jax.random.PRNGKey(0), prog, n_chains=8,
                                n_sweeps=10, burn_in=2)
        assert stats.bits_used.dtype == np.int64
        assert int(stats.bits_used) > 0 and int(stats.attempts) > 0
        # totals equal the per-sweep device stats, which stay int32-sized
        _, _, per_sweep = _run_gibbs_device(
            jax.random.PRNGKey(0), prog, n_chains=8, n_sweeps=10, burn_in=2)
        assert per_sweep.bits_used.shape == (10,)
        assert (int(np.asarray(per_sweep.bits_used, np.int64).sum())
                == int(stats.bits_used))


class TestCompilerChain:
    def test_gather_plan_matches_direct_conditional(self):
        """The compiled gather-plan conditional equals the brute-force
        Markov-blanket conditional on random nets."""
        bn = networks.random_bayesnet(8, seed=3, max_card=3)
        prog = compile_bayesnet(bn, quantize_cpt_bits=None)
        from repro.pgm.compile import _color_update

        rng = np.random.default_rng(0)
        x = np.array([[rng.integers(0, c) for c in bn.card]])
        log_cpt = jnp.asarray(prog.log_cpt)

        for plan in prog.plans:
            # conditional from the plan (force argmax by sampling many)
            for gi, v in enumerate(plan.nodes):
                v = int(v)
                # brute force P(v | rest)
                logw = np.zeros(bn.card[v])
                for l in range(bn.card[v]):
                    xx = x[0].copy()
                    xx[v] = l
                    logw[l] = bn.logp(xx)
                pw = np.exp(logw - logw.max())
                pw /= pw.sum()
                # plan-based: run many samples of this color from state x
                b = 4000
                xs = jnp.asarray(np.tile(x, (b, 1)), jnp.int32)
                x2, _ = _color_update(
                    jax.random.PRNGKey(v), xs, plan, log_cpt,
                    prog.max_card, prog.k, False)
                samples = np.asarray(x2[:, v])
                f = np.bincount(samples, minlength=bn.card[v]) / b
                assert np.abs(f - pw).max() < 0.06, (v, f, pw)

    def test_quantization_error_bounded(self):
        bn = networks.asia()
        prog16 = compile_bayesnet(bn, quantize_cpt_bits=16)
        prog_f = compile_bayesnet(bn, quantize_cpt_bits=None)
        d = np.abs(prog16.log_cpt - prog_f.log_cpt).max()
        assert d < 1e-2


class TestMetropolis:
    def test_mh_converges_like_gibbs(self):
        """MH-within-checkerboard reaches comparable segmentation quality
        (paper: AIA accelerates 'Gibbs, MH, etc.')."""
        import jax
        from repro.pgm.metropolis import mrf_metropolis

        mrf, truth = networks.penguin_task(h=40, w=30)
        labels = init_labels(jax.random.PRNGKey(0), mrf, 2)
        out, stats = mrf_metropolis(
            jax.random.PRNGKey(1), labels, jnp.asarray(mrf.unary),
            jnp.asarray(mrf.pairwise), n_sweeps=60)
        acc = (np.asarray(out[0]) == truth).mean()
        assert acc > 0.9, acc
        assert 0.05 < float(stats.accept_rate) <= 1.0

    def test_mh_detailed_balance_statistically(self):
        """On a tiny 2-site chain, MH and Gibbs agree with the exact
        Boltzmann marginal."""
        import jax
        from repro.pgm.graph import MRFGrid
        from repro.pgm.metropolis import mrf_metropolis

        unary = np.zeros((1, 2, 2), np.float32)
        unary[0, 0] = [0.0, 1.0]   # site 0 prefers label 0
        unary[0, 1] = [0.5, 0.0]   # site 1 prefers label 1
        mrf = MRFGrid.potts(unary, beta=0.7)
        # exact marginal of site 0 by enumeration
        zs = []
        for a in (0, 1):
            for bb in (0, 1):
                e = unary[0, 0, a] + unary[0, 1, bb] + 0.7 * (a != bb)
                zs.append((a, np.exp(-e)))
        z = sum(w for _, w in zs)
        p0 = sum(w for a, w in zs if a == 0) / z
        chains = 4000
        labels = init_labels(jax.random.PRNGKey(2), mrf, chains)
        out, _ = mrf_metropolis(
            jax.random.PRNGKey(3), labels, jnp.asarray(mrf.unary),
            jnp.asarray(mrf.pairwise), n_sweeps=40)
        f0 = float((np.asarray(out[:, 0, 0]) == 0).mean())
        assert abs(f0 - p0) < 0.04, (f0, p0)
