"""Window accounting on the host's monotonic clock.

``records`` are the load generator's: ``t_sched`` (when the request
was due; a closed-loop caller's send time), ``t_send``, ``t_recv``
(None if no answer came), ``status`` and the kept ``answer``.  A
request is *due in the window* when ``t_sched`` lies in ``[t0, t1)``.
"""
from __future__ import annotations

import numpy as np


def due(records: list[dict], t0: float, t1: float) -> list[dict]:
    return [r for r in records if "t_sched" in r and t0 <= r["t_sched"] < t1]


def answered(r: dict) -> bool:
    return r.get("status") == 200 and r.get("answer") is not None


def latencies_s(records: list[dict], t0: float, t1: float,
                t_stop: float) -> np.ndarray:
    """Scheduled arrival to answer, for every request due in the window;
    one never answered counts at its age when waiting stopped
    (``t_stop``)."""
    return np.array([(r["t_recv"] if r["t_recv"] is not None else t_stop)
                     - r["t_sched"] for r in due(records, t0, t1)])


def percentile_ms(lat_s: np.ndarray, q: float) -> float | None:
    if not len(lat_s):
        return None
    return float(np.percentile(lat_s, q) * 1e3)


def updates_in_window(records: list[dict], t0: float, t1: float) -> float:
    """Site updates done inside ``[t0, t1]``.  An answer reports the
    updates of its own chain lanes (``n_node_samples``) and its service
    time (``wall_s``, admission to retirement); its updates are spread
    evenly over ``[t_recv - wall_s, t_recv]`` and the part inside the
    window counts, so the rate does not step with whole answers."""
    total = 0.0
    for r in records:
        if not answered(r):
            continue
        a = r["answer"]
        wall = max(float(a["wall_s"]), 1e-9)
        s1 = r["t_recv"]
        s0 = s1 - wall
        overlap = max(0.0, min(s1, t1) - max(s0, t0))
        total += float(a["n_node_samples"]) * overlap / wall
    return total


def msample_per_s(records: list[dict], t0: float, t1: float) -> float:
    return updates_in_window(records, t0, t1) / (t1 - t0) / 1e6


def ess_per_s(records: list[dict], t0: float, t1: float) -> float:
    """Worst-case ESS (min of bulk and tail over the query variables) of
    every answer received in the window, over the window."""
    return sum(float(r["answer"]["ess"]) for r in records
               if answered(r) and t0 <= r["t_recv"] < t1) / (t1 - t0)


def lateness_ms(records: list[dict]) -> dict:
    """How late the generator sent, against the schedule."""
    late = [r["t_send"] - r["t_sched"] for r in records
            if r.get("t_send") is not None]
    if not late:
        return {"p50": 0.0, "max": 0.0}
    return {"p50": float(np.median(late) * 1e3),
            "max": float(np.max(late) * 1e3)}
