"""Window accounting on synthetic load-generator records."""
import numpy as np
import pytest

from harness import window


def rec(t_sched, t_recv, *, updates=0.0, wall=1.0, ess=0.0, t_send=None):
    return {"t_sched": t_sched, "t_send": t_sched if t_send is None else t_send,
            "t_recv": t_recv, "status": 200 if t_recv is not None else None,
            "answer": None if t_recv is None else {
                "n_node_samples": updates, "wall_s": wall, "ess": ess,
                "marginals": {}}}


def test_updates_are_spread_over_service_time():
    # 10M updates served over [8, 12]: half of them inside [0, 10]
    r = [rec(7.0, 12.0, updates=10e6, wall=4.0)]
    assert window.updates_in_window(r, 0.0, 10.0) == pytest.approx(5e6)
    assert window.msample_per_s(r, 0.0, 10.0) == pytest.approx(0.5)
    # a window boundary inside a round does not step the rate
    r2 = [rec(0.0, 2.0, updates=4e6, wall=2.0), rec(2.0, 4.0, updates=4e6,
                                                      wall=2.0)]
    assert window.msample_per_s(r2, 1.0, 3.0) == pytest.approx(2.0)


def test_p95_over_all_due_requests_counts_unanswered_at_age():
    recs = [rec(float(i) * 0.1, float(i) * 0.1 + 0.05) for i in range(19)]
    recs.append(rec(1.9, None))                  # never answered
    recs.append(rec(5.0, 5.01))                  # due after the window
    lat = window.latencies_s(recs, 0.0, 2.0, t_stop=11.9)
    assert len(lat) == 20
    assert lat.max() == pytest.approx(10.0)     # counted at its age
    assert window.percentile_ms(lat, 95) == pytest.approx(
        np.percentile(lat, 95) * 1e3)
    assert window.percentile_ms(lat, 50) == pytest.approx(50.0)


def test_ess_counts_answers_received_in_window():
    recs = [rec(0.0, 0.5, ess=100.0), rec(0.5, 1.5, ess=30.0),
            rec(0.9, 2.5, ess=1000.0)]
    assert window.ess_per_s(recs, 0.0, 2.0) == pytest.approx(65.0)


def test_generator_lateness():
    recs = [rec(1.0, 2.0, t_send=1.002), rec(2.0, 3.0, t_send=2.0)]
    late = window.lateness_ms(recs)
    assert late["max"] == pytest.approx(2.0)
    assert late["p50"] == pytest.approx(1.0)
