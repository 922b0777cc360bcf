"""The generator is a function of the seed, and the benchmark's copies
of the model data match the program's own."""
import json

import numpy as np
import pytest

from harness import plugins, spec, traffic


def family(config):
    return plugins.load("families", config["family"]).Family(config)


@pytest.fixture(scope="module")
def asia():
    cell = spec.load_cell("asia.steady")
    return cell, family(cell.config)


@pytest.fixture(scope="module")
def penguin():
    cell = spec.load_cell("penguin.scribble")
    cfg = dict(cell.config, height=30, width=20)
    return cell, family(cfg)


def test_open_loop_same_seed_same_schedule(asia):
    cell, fam = asia
    a = traffic.schedule(fam, cell.traffic, 2**31 + 5, 10.0)
    b = traffic.schedule(fam, cell.traffic, 2**31 + 5, 10.0)
    c = traffic.schedule(fam, cell.traffic, 2**31 + 6, 10.0)
    assert a == b
    assert a != c


def test_open_loop_seeds_share_the_work_not_the_requests(asia):
    """Every seed gets the same arrival times, pattern counts, evidence
    strata counts and query sizes; the seed draws which request comes
    when, its evidence values and its query variables."""
    cell, fam = asia

    def shape(s):
        reqs = s["requests"]
        strata = sorted((tuple(sorted(r["wire"]["evidence"])),
                         tuple(sorted(r["wire"]["evidence"].items())))
                        for r in reqs)
        return ([r["t"] for r in reqs], strata,
                sorted(len(r["wire"]["query_vars"]) for r in reqs))

    s1 = traffic.schedule(fam, cell.traffic, 1, 10.0)
    s2 = traffic.schedule(fam, cell.traffic, 99, 10.0)
    assert shape(s1) == shape(s2)
    wires = [[json.dumps(r["wire"], sort_keys=True) for r in s["requests"]]
             for s in (s1, s2)]
    assert wires[0] != wires[1]
    assert sorted(wires[0]) != sorted(wires[1])      # query vars differ
    times = [r["t"] for r in s1["requests"]]
    assert times == sorted(times) and 0 <= times[0] and times[-1] < 10.0


def test_every_stratum_equally_often(asia):
    cell, fam = asia
    s = traffic.schedule(fam, cell.traffic, 2**32 + 3, 30.0)
    per: dict = {}
    for r in s["requests"]:
        ev = r["wire"]["evidence"]
        per.setdefault(tuple(sorted(ev)), []).append(
            tuple(sorted(ev.items())))
    for pattern, seen in per.items():
        counts = [seen.count(v) for v in set(seen)]
        assert len(set(seen)) == 2 ** len(pattern)
        assert max(counts) - min(counts) <= 1


def test_every_variable_asked_equally_often(asia):
    """Per pattern, each free variable is asked equally often (to one),
    whatever the seed; which sets go together is the seed's."""
    cell, fam = asia

    def counts(seed):
        out: dict = {}
        for r in traffic.schedule(fam, cell.traffic, seed, 30.0)["requests"]:
            w = r["wire"]
            assert len(set(w["query_vars"])) == len(w["query_vars"])
            assert not set(w["query_vars"]) & set(w["evidence"])
            key = tuple(sorted(w["evidence"]))
            for v in w["query_vars"]:
                out.setdefault(key, {}).setdefault(v, 0)
                out[key][v] += 1
        return out

    a, b = counts(11), counts(2**33 + 1)
    for key in a:
        assert set(a[key]) == set(fam.variables(
            [tuple(sorted(p)) for p in fam.patterns].index(key)))
        assert max(a[key].values()) - min(a[key].values()) <= 1
        for v in a[key]:
            assert abs(a[key][v] - b[key][v]) <= 1


def test_onoff_arrivals_fall_in_on_stretches():
    mix = {"arrivals": {"kind": "onoff", "on_s": 1.0, "off_s": 3.0}}
    t = traffic.arrival_times(mix, 200, 10.0, traffic.WINDOW)
    assert len(t) == 200 and (np.diff(t) >= 0).all()
    assert ((t % 4.0) < 1.0).all() and t.max() < 10.0
    assert (traffic.arrival_times(mix, 200, 10.0, traffic.WINDOW) == t).all()


def test_sessions_drift_on_one_pattern(asia):
    cell, fam = asia
    mix = dict(cell.traffic, sessions={"count": 5}, drift=0.25)
    s = traffic.schedule(fam, mix, 7, 10.0)
    by: dict = {}
    for r in s["requests"]:
        by.setdefault(r["wire"]["stream_id"], []).append(r["wire"])
    assert len(by) == 5
    kept = flips = 0
    for wires in by.values():
        assert len({tuple(sorted(w["evidence"])) for w in wires}) == 1
        for a, b in zip(wires, wires[1:]):
            same = a["evidence"] == b["evidence"]
            kept += same
            flips += not same
    assert kept > flips > 0


def test_zipf_counts():
    assert traffic.zipf_counts(100, 8, 1.1) == sorted(
        traffic.zipf_counts(100, 8, 1.1), reverse=True)
    assert sum(traffic.zipf_counts(37, 8, 1.1)) == 37


def test_closed_loop_users_and_patterns(penguin):
    cell, fam = penguin
    mix = dict(cell.traffic, query_sites=[1, 16])
    s = traffic.schedule(fam, mix, 3, 5.0)
    assert s == traffic.schedule(fam, mix, 3, 5.0)
    # two users per pattern, every request on its user's strokes
    for u, user in enumerate(s["users"]):
        for wire in user:
            assert wire["mask_sites"] == fam.patterns[u // 2]
            assert 1 <= len(wire["query_sites"]) <= 16
            clamped = {(r, c) for r, c, _ in wire["mask_sites"]}
            assert not clamped & {tuple(q) for q in wire["query_sites"]}
    # stroke pixels carry the ground-truth label
    for r, c, lab in fam.patterns[0]:
        assert fam.truth[r, c] == lab


def test_fresh_strokes_grow_every_request(penguin):
    cell, fam = penguin
    mix = dict(cell.traffic, query_sites=[1, 4], fresh_strokes=1)
    user = traffic.schedule(fam, mix, 4, 5.0)["users"][0]
    masks = [{tuple(s) for s in w["mask_sites"]} for w in user]
    assert masks[0] == set(map(tuple, fam.patterns[0]))
    assert masks[1] > masks[0]
    for a, b in zip(masks, masks[1:]):
        assert a <= b


def test_blob_image_matches_program():
    from repro.pgm import networks

    cfg = spec.load_cell("penguin.scribble").config
    fam = family(cfg)
    unary, beta = fam.data
    mrf, want = networks.penguin_task(cfg["height"], cfg["width"],
                                      beta=cfg["beta"])
    assert np.array_equal(fam.truth, want)
    assert np.array_equal(unary, mrf.unary)
    assert np.array_equal(fam.program().pairwise, mrf.pairwise)


def test_asia_tables_match_program(asia):
    from repro.pgm import networks

    _, fam = asia
    ref = networks.asia()
    names, card, parents, cpts = fam.data
    assert names == ref.names and card == ref.card
    assert parents == [tuple(p) for p in ref.parents]
    for a, b in zip(cpts, ref.cpt):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-12)


def test_exact_reference_matches_enumeration(asia):
    from repro.pgm import networks

    _, fam = asia
    bn_exact = plugins.load("reference", "bn_exact")
    ref = networks.asia()
    got = bn_exact.marginals(fam.data, {"xray": 1, "smoke": 0})
    want = ref.marginals_exact({"xray": 1, "smoke": 0})
    for v, name in enumerate(ref.names):
        np.testing.assert_allclose(got[name], want[v], atol=1e-12)
