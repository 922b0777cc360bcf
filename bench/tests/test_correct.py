"""The numbers that decide ``correct``, on synthetic answers."""
import numpy as np
import pytest

from harness import correct


def v(p1):
    return np.array([1.0 - p1, p1])


CAL = {"groups": ["a", "b"], "band": [0.0, 0.5]}


def test_rare_gap_pools_each_group():
    pairs = [("a", v(0.010), v(0.01)), ("a", v(0.012), v(0.01)),
             ("b", v(0.70), v(0.6)),               # rare label is 0: 0.3/0.4
             ("c", v(0.9), v(0.01))]               # not a calibration group
    assert correct.rare_gap(pairs, CAL) == pytest.approx(0.25)
    pairs[2] = ("b", v(0.60), v(0.6))
    assert correct.rare_gap(pairs, CAL) == pytest.approx(0.1)
    assert correct.rare_gap(pairs, dict(CAL, groups=None)) == pytest.approx(
        89.0)


def test_rare_gap_band_and_nothing_to_read():
    pairs = [("site", v(0.0), v(0.0001)), ("site", v(0.02), v(0.02))]
    cal = {"groups": None, "band": [0.0005, 0.05]}
    assert correct.rare_gap(pairs, cal) == pytest.approx(0.0)
    assert correct.rare_gap(pairs[:1], cal) is None
    assert correct.rare_gap([("site", None, v(0.02))], cal) is None


def test_floored_weights_fail_where_one_answer_passes():
    """2**-8 rounding of a 1% outcome is far inside one answer's error,
    and far outside the pooled one."""
    rng = np.random.default_rng(0)
    exact = 0.0101
    sound = rng.binomial(400, exact, 1000) / 400
    floored = rng.binomial(400, 2 / 255 * (1 - exact), 1000) / 400
    for served, bad in ((sound, False), (floored, True)):
        pairs = [("a", v(s), v(exact)) for s in served]
        gap = np.mean([abs(s - exact) for s in served])
        assert gap < 0.01
        assert bool(correct.rare_gap(pairs, CAL) > 0.1) is bad


def _compare(items, limits):
    config = {"reference": "bn_exact",
              "calibration": {"groups": None, "band": [0.0, 0.5]}}
    names, card = ["x", "y"], [2, 2]
    data = (names, card, [(), (0,)],
            [np.array([0.7, 0.3]), np.array([[0.9, 0.1], [0.2, 0.8]])])
    return correct.compare(config, data, items, n_due=len(items),
                           n_failed=0, limits=limits, ref_settings={},
                           seed=0)


def test_compare_exact_answers_and_malformed():
    wire = {"evidence": {"y": 1}, "query_vars": ["x"]}
    # P(x=1 | y=1) = 0.3*0.8 / (0.7*0.1 + 0.3*0.8)
    p = 0.24 / 0.31
    ok = _compare([(wire, {"marginals": {"x": [1 - p, p]}})],
                  {"gap_mean": 0.01, "rare_gap": 0.01})
    assert ok["correct"] and ok["numbers"]["gap_mean"]["value"] < 1e-12
    bad = _compare([(wire, {"marginals": {"x": [0.5, 0.6]}})],
                   {"gap_mean": 0.01, "rare_gap": 0.01})
    assert not bad["correct"] and bad["numbers"]["malformed"]["value"] == 1
