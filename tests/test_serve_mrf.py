"""Masked-MRF serving: clamp-mask correctness (single-device and mesh
Gibbs), masked marginals vs the exact conditional, served-vs-direct and
queued-vs-batched identity, mask-pattern plan caching, and the sharded
MRF serve path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.pgm import (
    clamp_labels, compile_mrf, init_labels, init_mrf_states, mask_of,
    mrf_gibbs, networks)
from repro.pgm.graph import MRFGrid
from repro.serve import (
    AdmissionQueue, MrfQuery, PosteriorEngine, plan_key)
from repro.serve.plan_cache import pattern_key


def _two_site() -> MRFGrid:
    """1x2 grid whose conditionals are enumerable by hand."""
    unary = np.zeros((1, 2, 2), np.float32)
    unary[0, 0] = [0.0, 1.0]   # site 0 prefers label 0
    unary[0, 1] = [0.5, 0.0]   # site 1 prefers label 1
    return MRFGrid.potts(unary, beta=0.7)


def _scribble(h, w, seed=0, frac=0.15):
    rng = np.random.default_rng(seed)
    mask = rng.random((h, w)) < frac
    values = rng.integers(0, 2, (h, w))
    return mask, values


class TestClampMask:
    def test_clamped_sites_never_flip(self):
        """The headline invariant: under a clamp mask, observed pixels
        keep their pinned labels through every sweep while free pixels
        do get resampled."""
        mrf, truth = networks.penguin_task(h=12, w=10)
        mask, _ = _scribble(12, 10, seed=1, frac=0.3)
        values = np.where(mask, truth, 0)
        lab0 = clamp_labels(
            init_labels(jax.random.PRNGKey(0), mrf, 4), mask, values)
        lab, _ = mrf_gibbs(
            jax.random.PRNGKey(1), lab0, jnp.asarray(mrf.unary),
            jnp.asarray(mrf.pairwise), n_sweeps=25, clamp=jnp.asarray(mask))
        out = np.asarray(lab)
        assert (out[:, mask] == values[mask]).all()
        free0, free = np.asarray(lab0)[:, ~mask], out[:, ~mask]
        assert (free0 != free).any()  # the sampler did visit free sites

    def test_clamp_excluded_from_bit_accounting(self):
        """Clamped sites draw no random bits: a heavier mask must spend
        strictly fewer bits over the same sweeps."""
        mrf, _ = networks.penguin_task(h=16, w=16)
        lab = init_labels(jax.random.PRNGKey(0), mrf, 2)
        mask, values = _scribble(16, 16, seed=2, frac=0.5)
        _, s_clamped = mrf_gibbs(
            jax.random.PRNGKey(1), clamp_labels(lab, mask, values),
            jnp.asarray(mrf.unary), jnp.asarray(mrf.pairwise),
            n_sweeps=5, clamp=jnp.asarray(mask))
        _, s_free = mrf_gibbs(
            jax.random.PRNGKey(1), lab, jnp.asarray(mrf.unary),
            jnp.asarray(mrf.pairwise), n_sweeps=5)
        assert int(s_clamped.bits_used) < int(s_free.bits_used)

    def test_clamped_neighbours_feel_the_evidence(self):
        """A clamped site must keep contributing pairwise energy: on a
        strong ferromagnetic Potts grid with uniform unaries, clamping
        one site drags its free neighbour to the same label."""
        h = w = 3
        mrf = MRFGrid.potts(np.zeros((h, w, 2), np.float32), beta=3.0)
        mask = np.zeros((h, w), bool)
        mask[1, 1] = True
        values = np.ones((h, w), np.int64)
        eng = PosteriorEngine({"g": mrf}, chains_per_query=32, burn_in=32,
                              max_rounds=8)
        res = eng.answer(MrfQuery("g", mask, values,
                                  query_sites=((1, 0),), n_samples=8192))
        assert res.marginal("s1,0")[1] > 0.8  # pulled toward the clamp

    def test_compile_mrf_validation(self):
        mrf = _two_site()
        with pytest.raises(ValueError):
            compile_mrf(mrf, observed=(0, 1))       # all sites clamped
        with pytest.raises(ValueError):
            compile_mrf(mrf, observed=(5,))         # outside the lattice
        prog = compile_mrf(mrf, observed=(1,))
        assert mask_of(prog).tolist() == [[False, True]]
        assert (prog.n_sites, prog.n_free) == (2, 1)
        with pytest.raises(ValueError):
            init_mrf_states(jax.random.PRNGKey(0), prog, 2)  # no values


class TestMaskedMarginals:
    def test_two_site_matches_exact_conditional(self):
        """Masked 2-site grid: the served marginal of the free site
        equals the hand-enumerated conditional P(x1 | x0 = v)."""
        mrf = _two_site()
        eng = PosteriorEngine({"tiny": mrf}, chains_per_query=64,
                              burn_in=32, max_rounds=16)
        for v0 in (0, 1):
            mask = np.array([[True, False]])
            values = np.array([[v0, 0]])
            res = eng.answer(MrfQuery("tiny", mask, values,
                                      query_sites=((0, 1),),
                                      n_samples=30_000))
            e = mrf.unary[0, 1] + mrf.pairwise[:, v0]
            p = np.exp(-e)
            p /= p.sum()
            assert np.abs(res.marginal("s0,1") - p).max() < 0.03, (v0, p)

    def test_served_matches_direct_clamped_gibbs(self):
        """Engine marginals agree with a long direct ``mrf_gibbs`` run
        under the same clamp mask — the two code paths sample the same
        conditional distribution."""
        mrf, truth = networks.penguin_task(h=6, w=6, beta=1.0)
        mask = np.zeros((6, 6), bool)
        mask[0, :] = True
        values = np.where(mask, truth, 0)
        site = (3, 3)

        eng = PosteriorEngine({"p": mrf}, chains_per_query=64, burn_in=64,
                              max_rounds=32)
        res = eng.answer(MrfQuery("p", mask, values, query_sites=(site,),
                                  n_samples=60_000))

        lab = clamp_labels(
            init_labels(jax.random.PRNGKey(0), mrf, 256), mask, values)
        counts = np.zeros(2)
        key = jax.random.PRNGKey(1)
        for i in range(80):
            key, sub = jax.random.split(key)
            lab, _ = mrf_gibbs(sub, lab, jnp.asarray(mrf.unary),
                               jnp.asarray(mrf.pairwise), n_sweeps=1,
                               clamp=jnp.asarray(mask))
            if i >= 20:
                s = np.asarray(lab)[:, site[0], site[1]]
                counts += np.bincount(s, minlength=2)
        direct = counts / counts.sum()
        assert np.abs(res.marginal(f"s{site[0]},{site[1]}") - direct).max() \
            < 0.05, (res.marginal(f"s{site[0]},{site[1]}"), direct)

    def test_unmasked_query_serves_prior(self):
        """No mask at all is legal: the engine samples the unconditioned
        grid (pattern = ())."""
        mrf = _two_site()
        eng = PosteriorEngine({"tiny": mrf}, chains_per_query=32,
                              burn_in=32, max_rounds=8)
        res = eng.answer(MrfQuery("tiny", n_samples=4096))
        assert set(res.marginals) == {"s0,0", "s0,1"}
        for m in res.marginals.values():
            assert abs(m.sum() - 1.0) < 1e-9


class TestMrfQueryNormalization:
    def test_bad_queries_fail_fast(self):
        mrf, _ = networks.penguin_task(h=4, w=4)
        eng = PosteriorEngine({"p": mrf})
        mask = np.zeros((4, 4), bool)
        mask[0, 0] = True
        with pytest.raises(ValueError):   # mask without values
            eng.normalize(MrfQuery("p", mask))
        with pytest.raises(ValueError):   # label outside [0, L)
            eng.normalize(MrfQuery("p", mask, np.full((4, 4), 7)))
        with pytest.raises(ValueError):   # wrong mask shape
            eng.normalize(MrfQuery("p", np.zeros((3, 3), bool)))
        with pytest.raises(ValueError):   # query site is observed
            eng.normalize(MrfQuery("p", mask, np.zeros((4, 4)),
                                   query_sites=((0, 0),)))
        with pytest.raises(KeyError):     # query site outside lattice
            eng.normalize(MrfQuery("p", query_sites=((9, 9),)))
        with pytest.raises(ValueError):   # conflicting sparse evidence
            eng.normalize(MrfQuery("p", mask_sites=((0, 0, 1), (0, 0, 0))))
        with pytest.raises(ValueError):   # col == w must not alias (1, 0)
            eng.normalize(MrfQuery("p", mask_sites=((0, 4, 1),)))
        with pytest.raises(ValueError):   # everything clamped
            eng.normalize(MrfQuery("p", np.ones((4, 4), bool),
                                   np.zeros((4, 4))))

    def test_sparse_and_dense_masks_share_a_pattern(self):
        """mask_sites triples and a dense mask describing the same
        pixels normalize to the same evidence pattern (and therefore
        the same plan-cache entry and queue bucket)."""
        mrf, _ = networks.penguin_task(h=4, w=4)
        eng = PosteriorEngine({"p": mrf})
        mask = np.zeros((4, 4), bool)
        mask[1, 2] = mask[3, 0] = True
        values = np.zeros((4, 4), np.int64)
        values[1, 2] = 1
        _, ev_d, _, pat_d = eng.normalize(MrfQuery("p", mask, values))
        _, ev_s, _, pat_s = eng.normalize(
            MrfQuery("p", mask_sites=((1, 2, 1), (3, 0, 0))))
        assert ev_d == ev_s and pat_d == pat_s


class TestMrfPlanCache:
    def test_same_mask_hits_different_mask_misses(self):
        mrf, _ = networks.penguin_task(h=6, w=6)
        eng = PosteriorEngine({"p": mrf}, chains_per_query=8, burn_in=16,
                              max_rounds=4)
        mask, values = _scribble(6, 6, seed=0, frac=0.2)
        q = MrfQuery("p", mask, values, query_sites=_free_sites(mask, 2),
                     n_samples=256)
        eng.answer(q)
        assert eng.cache.stats.misses == 1
        # same mask, different observed labels -> hit, no recompile
        eng.answer(MrfQuery("p", mask, 1 - values,
                            query_sites=_free_sites(mask, 2), n_samples=256))
        assert (eng.cache.stats.hits, eng.cache.stats.misses) == (1, 1)
        mask2, values2 = _scribble(6, 6, seed=9, frac=0.2)
        eng.answer(MrfQuery("p", mask2, values2,
                            query_sites=_free_sites(mask2, 2), n_samples=256))
        assert (eng.cache.stats.hits, eng.cache.stats.misses) == (1, 2)

    def test_long_patterns_fold_to_digest(self):
        """Kilo-pixel masks make bounded-size cache keys, and distinct
        masks never share one."""
        a = tuple(range(1000))
        b = tuple(range(1, 1001))
        ka, kb = pattern_key(a), pattern_key(b)
        assert ka != kb and len(ka) == 3 and ka[0] == "sha1"
        assert pattern_key((1, 2, 3)) == (1, 2, 3)  # short stays verbatim
        kw = dict(k=12, use_iu=True, quantize_cpt_bits=16,
                  sweeps_per_round=16, thin=1)
        assert plan_key("m", a, **kw) != plan_key("m", b, **kw)


def _free_sites(mask, n):
    rs, cs = np.nonzero(~mask)
    return tuple((int(rs[i]), int(cs[i])) for i in range(n))


class TestMrfQueueServing:
    def test_streamed_identical_to_answer_batch(self):
        """The acceptance bit: masked-MRF queries served through the
        admission queue (bucketed by mask pattern, packed into one
        GroupRun) are bit-identical to ``answer_batch`` over the same
        traffic with the same seed; on an even and an odd width."""
        for h, w in ((8, 8), (7, 9)):
            mrf, _ = networks.penguin_task(h=h, w=w)
            mask_a, values = _scribble(h, w, seed=0, frac=0.2)
            mask_b, _ = _scribble(h, w, seed=1, frac=0.2)
            traffic = [
                MrfQuery("p", mask_a, values, _free_sites(mask_a, 2),
                         n_samples=2048),
                MrfQuery("p", mask_b, values, _free_sites(mask_b, 1),
                         n_samples=1024),
                MrfQuery("p", mask_a, 1 - values, _free_sites(mask_a, 2),
                         n_samples=2048),
            ]
            kw = dict(chains_per_query=8, burn_in=16, max_rounds=8)
            ref = PosteriorEngine({"p": mrf}, **kw,
                                  seed=11).answer_batch(traffic)
            eng = PosteriorEngine({"p": mrf}, **kw, seed=11)
            queue = AdmissionQueue(eng, max_wait_ms=3_600_000.0,
                                   max_group_lanes=len(traffic) * 8)
            try:
                handles = [queue.submit(q) for q in traffic]
                queue.flush()
                streamed = [h.result(timeout=600) for h in handles]
            finally:
                queue.close()
            # two mask_a queries share one bucket/plan; mask_b its own
            assert eng.cache.stats.misses == 2
            for a, b in zip(ref, streamed):
                assert a.n_samples == b.n_samples and a.rhat == b.rhat
                assert set(a.marginals) == set(b.marginals)
                for k in a.marginals:
                    assert np.array_equal(a.marginals[k], b.marginals[k])

    def test_mixed_family_batch(self):
        """One batch spanning a BayesNet and an MRF comes back in
        request order with the right marginal namespaces."""
        from repro.serve import Query

        mrf, _ = networks.penguin_task(h=6, w=6)
        registry = {"sprinkler": networks.sprinkler(), "p": mrf}
        eng = PosteriorEngine(registry, chains_per_query=8, burn_in=16,
                              max_rounds=4)
        mask, values = _scribble(6, 6, seed=3, frac=0.2)
        res = eng.answer_batch([
            Query("sprinkler", {"wetgrass": 1}, ("rain",), n_samples=512),
            MrfQuery("p", mask, values, _free_sites(mask, 2), n_samples=512),
        ])
        assert set(res[0].marginals) == {"rain"}
        assert all(name.startswith("s") for name in res[1].marginals)
        assert eng.cache.stats.misses == 2


@pytest.mark.slow
class TestMeshClamp:
    def test_mesh_clamped_sites_frozen_and_conditioned(self):
        """Distributed clamped Gibbs: observed pixels never flip across
        halo-exchange sweeps (including tile-boundary pixels), and the
        clamp conditions neighbours exactly like the single-device
        sampler — checked on a non-tile-multiple grid so the clamp mask
        composes with the pad-validity mask."""
        from conftest import run_subprocess

        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_pgm_mesh
from repro.pgm.graph import MRFGrid
from repro.pgm.gibbs import clamp_labels, init_labels, mrf_gibbs
from repro.pgm.mesh_gibbs import (
    make_mesh_gibbs_step, shard_clamp, shard_mrf)
h, w, beta = 11, 9, 2.5   # pads to 12x10 on a 2x2 mesh
mrf = MRFGrid.potts(np.zeros((h, w, 2), np.float32), beta=beta)
rng = np.random.default_rng(0)
mask = rng.random((h, w)) < 0.2
mask[5, :] = True          # a stroke crossing the tile boundary
values = np.ones((h, w), np.int64)   # clamp everything observed to 1
mesh = make_pgm_mesh(2, 2)
key = jax.random.PRNGKey(0)
lab, u, pw, valid, _ = shard_mrf(mesh, mrf, n_chains=32, key=key)
lab, clamp_dev = shard_clamp(mesh, mask, values, lab)
step = make_mesh_gibbs_step(mesh, clamped=True)
burn, keep = 30, 90
freq = np.zeros((h, w))
for i in range(burn + keep):
    key, sub = jax.random.split(key)
    lab, _ = step(sub, lab, u, pw, valid, clamp_dev)
    out = np.asarray(lab)[:, :h, :w]
    assert (out[:, mask] == 1).all(), f"clamp broke at sweep {i}"
    if i >= burn:
        freq += (out == 1).mean(0)
freq /= keep
# ferromagnetic pull: free sites lean to the clamped label, strongly so
# next to the stroke
assert freq[~mask].mean() > 0.6, freq[~mask].mean()
assert freq[4, :].mean() > 0.8, freq[4, :].mean()
# single-device clamped reference agrees sitewise
lab1 = clamp_labels(init_labels(jax.random.PRNGKey(5), mrf, 32),
                    mask, values)
ref = np.zeros((h, w))
k2 = jax.random.PRNGKey(6)
for i in range(burn + keep):
    k2, sub = jax.random.split(k2)
    lab1, _ = mrf_gibbs(sub, lab1, jnp.asarray(mrf.unary),
                        jnp.asarray(mrf.pairwise), n_sweeps=1,
                        clamp=jnp.asarray(mask))
    if i >= burn:
        ref += (np.asarray(lab1) == 1).mean(0)
ref /= keep
assert np.abs(freq - ref)[~mask].max() < 0.15
print("OK", freq[~mask].mean(), ref[~mask].mean())
"""
        rc, out = run_subprocess(code, devices=4)
        assert rc == 0, out

    def test_sharded_mrf_serve_matches_single_device(self):
        """The mesh serve path for MRF queries: a forced-host 4-device
        batch mesh returns bit-identical marginals to the single-device
        engine (same seeds, lane axis sharded over "batch")."""
        from conftest import run_subprocess

        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.launch.mesh import make_serve_mesh
from repro.pgm import networks
from repro.serve import MrfQuery, PosteriorEngine
mrf, _ = networks.penguin_task(h=8, w=8)
rng = np.random.default_rng(0)
mask = rng.random((8, 8)) < 0.2
values = rng.integers(0, 2, (8, 8))
rs, cs = np.nonzero(~mask)
sites = tuple((int(rs[i]), int(cs[i])) for i in range(3))
qs = [MrfQuery("p", mask, values, sites, n_samples=4096),
      MrfQuery("p", mask, 1 - values, sites, n_samples=4096)]
kw = dict(chains_per_query=8, burn_in=32, max_rounds=8, seed=3)
mesh = make_serve_mesh((4,))
sharded = PosteriorEngine({"p": mrf}, mesh=mesh, **kw).answer_batch(qs)
single = PosteriorEngine({"p": mrf}, **kw).answer_batch(qs)
for rs_, r1 in zip(sharded, single):
    assert set(rs_.marginals) == set(r1.marginals)
    for var in rs_.marginals:
        np.testing.assert_allclose(rs_.marginal(var), r1.marginal(var),
                                   atol=1e-12)
print("OK")
"""
        rc, out = run_subprocess(code, devices=4)
        assert rc == 0, out
