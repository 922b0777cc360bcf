"""MAP/MPE + temporal-filtering contracts (``docs/inference_modes.md``).

Covers the two inference modes of the unified :class:`Request` API:

* ``mode="map"`` — the annealed (simulated-annealing β schedule on the
  IU-exp weight path) MAP search must recover the *exact* enumeration
  argmax on every small-net fixture, under both sampler backends, and
  report the matching energy.
* temporal filtering (``stream_id``) — the warm-start contract: same
  seed + same slice stream is deterministic, retained states are
  re-clamped to the new slice's evidence, warm slices skip burn-in, and
  the admission queue never packs two slices of one stream into the
  same dispatch group.
* the versioned JSON request-file schema (v1 auto-upgrade, v2 mode /
  stream_id fields, loud failures on unknown versions and modes).
"""
import doctest
import json
import os

import numpy as np
import pytest

from repro.pgm import networks
from repro.pgm.graph import IsingModel
from repro.serve import (
    AdmissionQueue, IsingQuery, MrfQuery, PosteriorEngine, Query)
from repro.serve.cli import load_requests


def _registry():
    return {"sprinkler": networks.sprinkler(), "asia": networks.asia()}


def _exact_map(bn, evidence):
    """Brute-force joint argmax over the free variables given evidence —
    the oracle the annealed search must match (small nets only)."""
    grids = np.indices(tuple(bn.card)).reshape(bn.n_nodes, -1).T
    ev = bn.normalize_evidence(evidence)
    for v, val in ev.items():
        grids = grids[grids[:, v] == val]
    best = grids[np.argmax(bn.logp(grids))]
    return {bn.names[v]: int(best[v])
            for v in range(bn.n_nodes) if v not in ev}


def _frustrated_triangle() -> IsingModel:
    """Three antiferromagnetic couplings on a 3-cycle — no assignment
    satisfies all edges.  Small fields break the 6-fold ground-state
    degeneracy so the MAP answer is unique."""
    return IsingModel(n=3, edges=[[0, 1], [1, 2], [0, 2]], j=-1.0,
                      h=[0.3, -0.2, 0.1])


class TestAnnealedMap:
    @pytest.mark.parametrize("sampler", ["xla", "pallas"])
    @pytest.mark.parametrize("network,evidence", [
        ("sprinkler", {"wetgrass": 1}),
        ("asia", {"smoke": 1, "dysp": 1}),
    ])
    def test_recovers_exact_argmax(self, network, evidence, sampler):
        """The acceptance bar: annealed MAP == enumeration argmax on
        every small-net fixture, under both sampler backends."""
        bn = _registry()[network]
        eng = PosteriorEngine({network: bn}, chains_per_query=8,
                              burn_in=16, sampler=sampler, seed=0)
        res = eng.answer(Query(network, evidence, mode="map",
                               n_samples=4096))
        assert res.map_assignment == _exact_map(bn, evidence)
        assert res.converged          # retired on assignment stability
        assert res.marginals == {}    # a MAP answer is a point, not a dist
        # reported energy is the joint -log P̃ of (assignment, evidence)
        full = np.zeros(bn.n_nodes, np.int64)
        for name, val in {**res.map_assignment,
                          **{k: v for k, v in evidence.items()}}.items():
            full[bn.names.index(name)] = val
        assert res.map_energy == pytest.approx(-float(bn.logp(full)),
                                               abs=1e-4)

    @pytest.mark.parametrize("sampler", ["xla", "pallas"])
    def test_frustrated_triangle_ground_state(self, sampler):
        """MAP on a frustrated Ising triangle (spin 0 clamped up) finds
        a ground state of the conditioned model.  Frustration makes the
        ground state degenerate, so the check is on energy: the reported
        energy is the enumerated minimum, and the reported assignment is
        one of the states that attain it."""
        model = _frustrated_triangle()
        fg = model.to_factor_graph()
        grids = np.indices((2, 2, 2)).reshape(3, -1).T
        grids = grids[grids[:, 0] == 1]          # clamp s0 = +1
        energies = fg.energy(grids)
        e_min = float(energies.min())
        ground = {(int(g[1]), int(g[2])) for g, e in zip(grids, energies)
                  if e == pytest.approx(e_min, abs=1e-6)}

        eng = PosteriorEngine({"tri": model}, chains_per_query=8,
                              burn_in=16, sampler=sampler, seed=0)
        res = eng.answer(IsingQuery("tri", clamp_sites=((0, +1),),
                                    query_vars=(1, 2), mode="map",
                                    n_samples=2048))
        assert res.map_energy == pytest.approx(e_min, abs=1e-4)
        got = (res.map_assignment["s1"], res.map_assignment["s2"])
        assert got in ground

    def test_marginal_raises_on_map_result(self):
        eng = PosteriorEngine(_registry(), chains_per_query=8, burn_in=16,
                              seed=0)
        res = eng.answer(Query("sprinkler", {"wetgrass": 1}, ("rain",),
                               mode="map", n_samples=1024))
        with pytest.raises(ValueError, match="mode='map'"):
            res.marginal("rain")

    def test_map_fields_none_on_marginal_result(self):
        eng = PosteriorEngine(_registry(), chains_per_query=8, burn_in=16,
                              max_rounds=4, seed=0)
        res = eng.answer(Query("sprinkler", {"wetgrass": 1}, ("rain",),
                               n_samples=256))
        assert res.map_assignment is None and res.map_energy is None
        assert res.marginal("rain").shape == (2,)

    def test_mode_validated_at_construction(self):
        with pytest.raises(ValueError, match="unknown inference mode"):
            Query("sprinkler", {"wetgrass": 1}, mode="argmax")

    def test_beta_schedule_monotone_and_saturating(self):
        eng = PosteriorEngine(_registry(), map_beta0=0.5,
                              map_beta_growth=1.3, map_beta_max=8.0)
        betas = [eng.map_beta(t) for t in range(60)]
        assert betas[0] == pytest.approx(0.5)
        assert all(b <= a for a, b in zip(betas[1:], betas))  # non-decreasing
        assert betas[-1] == 8.0                               # saturates
        with pytest.raises(ValueError):
            PosteriorEngine(_registry(), map_beta_growth=0.5)

    def test_mixed_mode_batch_groups_split(self):
        """One batch mixing modes on the same (network, pattern): the
        marginal query still gets marginals, the MAP query an
        assignment — modes never share a group's runner call."""
        eng = PosteriorEngine(_registry(), chains_per_query=8, burn_in=16,
                              max_rounds=8, seed=0)
        r_marg, r_map = eng.answer_batch([
            Query("sprinkler", {"wetgrass": 1}, ("rain",), n_samples=512),
            Query("sprinkler", {"wetgrass": 1}, ("rain",), n_samples=512,
                  mode="map"),
        ])
        assert r_marg.map_assignment is None and r_marg.marginals
        assert r_map.map_assignment is not None and r_map.marginals == {}
        # same evidence pattern -> the MAP group still reuses the plan
        assert r_map.cache_hit


class TestTemporalFiltering:
    CHAINS = 8

    @staticmethod
    def _slices(n_slices=3):
        """One sensor re-observing the same pattern with drifting values
        plus a second stream — slice-major, as the admission path sees."""
        vals = [1, 0, 1, 0]
        return [[Query("sprinkler", {"wetgrass": vals[t]}, ("rain",),
                       n_samples=512, stream_id="a"),
                 Query("sprinkler", {"cloudy": vals[t]}, ("rain",),
                       n_samples=512, stream_id="b")]
                for t in range(n_slices)]

    def _engine(self, **kw):
        kw.setdefault("chains_per_query", self.CHAINS)
        kw.setdefault("burn_in", 16)
        kw.setdefault("seed", 3)
        return PosteriorEngine(_registry(), **kw)

    def test_same_seed_stream_is_deterministic(self):
        """T1: two same-seed engines fed the same slice stream produce
        bit-identical results, slice by slice."""
        outs = []
        for _ in range(2):
            eng = self._engine(max_rounds=4)
            outs.append([eng.answer_batch(sl) for sl in self._slices()])
        for slice_a, slice_b in zip(*outs):
            for a, b in zip(slice_a, slice_b):
                assert a.n_samples == b.n_samples and a.rhat == b.rhat
                assert a.warm_start == b.warm_start
                for k in a.marginals:
                    np.testing.assert_array_equal(a.marginals[k],
                                                  b.marginals[k])

    def test_stream_id_does_not_perturb_slice_zero(self):
        """Opting into temporal filtering is a pure opt-in: with nothing
        retained yet, a slice-0 query with a stream_id is bit-identical
        to the same query served cold (stream_id stripped)."""
        import dataclasses

        sl = self._slices(1)[0]
        a = self._engine(max_rounds=4).answer_batch(sl)
        b = self._engine(max_rounds=4).answer_batch(
            [dataclasses.replace(q, stream_id=None) for q in sl])
        for ra, rb in zip(a, b):
            assert ra.n_samples == rb.n_samples and ra.rhat == rb.rhat
            for k in ra.marginals:
                np.testing.assert_array_equal(ra.marginals[k],
                                              rb.marginals[k])

    def test_retained_states_reclamped_per_slice(self):
        """T2: retirement retains each stream's final chain states, and
        the next slice's evidence is re-clamped onto them — the retained
        block always reflects the *current* slice's observed values."""
        bn = _registry()["sprinkler"]
        wet = bn.names.index("wetgrass")
        eng = self._engine(max_rounds=4)
        slices = self._slices()

        r0 = eng.answer_batch(slices[0])
        assert not any(r.warm_start for r in r0)      # nothing retained yet
        blk = eng._retained[("sprinkler", "a")]
        assert blk.shape == (self.CHAINS, bn.n_nodes)
        assert (blk[:, wet] == 1).all()               # slice-0 evidence

        r1 = eng.answer_batch(slices[1])
        assert all(r.warm_start for r in r1)
        assert all(r.cache_hit for r in r1)           # same pattern, same plan
        blk = eng._retained[("sprinkler", "a")]
        assert (blk[:, wet] == 0).all()               # re-clamped to slice 1

        eng.reset_streams()
        assert not eng._retained
        r2 = eng.answer_batch(slices[2])
        assert not any(r.warm_start for r in r2)      # retention dropped

    def test_warm_slices_skip_burn_in(self):
        """T2 accounting: with retirement pinned at min_rounds, a warm
        slice's sweep count is exactly the cold count minus burn-in."""
        burn = 64
        eng = self._engine(burn_in=burn, rhat_target=100.0, ess_target=0.0)
        slices = self._slices(2)
        r0 = eng.answer_batch(slices[0])
        r1 = eng.answer_batch(slices[1])
        for cold, warm in zip(r0, r1):
            assert not cold.warm_start and warm.warm_start
            assert warm.n_sweeps == cold.n_sweeps - burn

    def test_warm_start_needs_fewer_sweeps_under_drift(self):
        """T3: under slowly drifting evidence the warm-started stream
        reaches the retirement targets in fewer total sweeps than the
        same traffic served cold (stream_id stripped)."""
        import dataclasses

        slices = self._slices()
        kw = dict(burn_in=64, ess_target=64.0)
        warm_eng, cold_eng = self._engine(**kw), self._engine(**kw)
        warm = [r for sl in slices for r in warm_eng.answer_batch(sl)]
        cold = [r for sl in slices for r in cold_eng.answer_batch(
            [dataclasses.replace(q, stream_id=None) for q in sl])]
        assert sum(r.n_sweeps for r in warm) < sum(r.n_sweeps for r in cold)
        assert sum(r.warm_start for r in warm) == 4   # slices 1-2, 2 streams

    def test_queue_serializes_same_stream_slices(self):
        """Two slices of one stream submitted together must dispatch in
        separate groups, in order — otherwise slice t+1 could not
        warm-start from slice t's retained states."""
        eng = self._engine(max_rounds=4)
        queue = AdmissionQueue(eng, max_wait_ms=3_600_000.0,
                               max_group_lanes=64)
        try:
            s0, s1 = (Query("sprinkler", {"wetgrass": v}, ("rain",),
                            n_samples=512, stream_id="a") for v in (1, 0))
            h0, h1 = queue.submit(s0), queue.submit(s1)
            queue.flush()
            r0 = h0.result(timeout=300)
            r1 = h1.result(timeout=300)
        finally:
            queue.close()
        assert not r0.warm_start
        assert r1.warm_start       # only possible if s1 ran after s0 retired

    def test_reregister_drops_streams(self):
        """Replacing a model invalidates its retained chain states —
        they were sampled under the old parameters."""
        eng = self._engine(max_rounds=4)
        eng.answer_batch(self._slices()[0])
        assert ("sprinkler", "a") in eng._retained
        eng.register("sprinkler", networks.sprinkler())
        assert ("sprinkler", "a") not in eng._retained


class TestRequestFileSchema:
    @staticmethod
    def _load(tmp_path, payload):
        p = tmp_path / "reqs.json"
        p.write_text(json.dumps(payload))
        return load_requests(str(p))

    def test_v1_auto_upgrades_to_marginals(self, tmp_path):
        qs, _ = self._load(tmp_path, [
            {"network": "sprinkler", "evidence": {"wetgrass": 1}},
        ])
        assert qs[0].mode == "marginals" and qs[0].stream_id is None

    def test_v1_refuses_v2_fields(self, tmp_path):
        for field in ("mode", "stream_id"):
            with pytest.raises(ValueError,
                               match=f"'{field}' requires schema version 2"):
                self._load(tmp_path, [
                    {"network": "sprinkler", field: "map"},
                ])

    def test_unknown_version_rejected(self, tmp_path):
        with pytest.raises(ValueError,
                           match=r"unknown request schema version 3"):
            self._load(tmp_path, [{"v": 3, "network": "sprinkler"}])

    def test_v2_mode_and_stream_id(self, tmp_path):
        qs, _ = self._load(tmp_path, [
            {"v": 2, "network": "sprinkler", "evidence": {"wetgrass": 1},
             "mode": "map"},
            {"v": 2, "network": "sprinkler", "evidence": {"cloudy": 0},
             "stream_id": "sensor3"},
            {"v": 2, "network": "mrf", "mask_sites": [[0, 0, 1]],
             "mode": "map", "stream_id": "cam0"},
        ])
        assert qs[0].mode == "map" and qs[0].stream_id is None
        assert qs[1].mode == "marginals" and qs[1].stream_id == "sensor3"
        assert isinstance(qs[2], MrfQuery)
        assert qs[2].mode == "map" and qs[2].stream_id == "cam0"

    def test_v2_unknown_mode_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="unknown inference mode"):
            self._load(tmp_path, [
                {"v": 2, "network": "sprinkler", "mode": "argmax"},
            ])


def test_docs_doctests():
    """Every ``>>>`` example in docs/inference_modes.md runs and prints
    exactly what the page claims (the schedule values, the sprinkler MAP
    assignment + energy, the warm-start flags)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "inference_modes.md")
    failures, tests = doctest.testfile(
        path, module_relative=False,
        optionflags=doctest.NORMALIZE_WHITESPACE)
    assert tests > 0, "no doctest examples found in inference_modes.md"
    assert failures == 0


class TestFamilyDispatch:
    def test_family_of_dispatches_on_query_type(self):
        from repro.serve.families import family_of

        assert family_of(
            Query("x", {"a": 1})).__class__.__name__ == "BayesNetFamily"
        assert family_of(
            MrfQuery("x")).__class__.__name__ == "MrfFamily"
        assert family_of(
            IsingQuery("x")).__class__.__name__ == "IsingFamily"
