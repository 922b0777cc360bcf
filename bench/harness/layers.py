"""Shared arithmetic of the per-layer readers (spans and trace)."""
from __future__ import annotations

import numpy as np

from harness import cost, trace

ROUND_PROGRAM = "round_fn"     # the engine's jitted round runner


def busy_intervals(dev: dict):
    """The device's busy periods: its program (module) executions.  The
    ``XLA Ops`` line is capped (about 6.25M events on a v5e, reached
    within seconds by the many small operations of a Bayesian-network
    round), the ``XLA Modules`` line is not."""
    return dev["modules"] or dev["ops"]


def traced_end(run, dev: dict) -> float:
    """End of the part of the window the device trace covers.  The
    profiler stops recording device events when its buffer is full
    (an asia round's many small operations fill it in about 8 s); the
    engine still runs rounds after the last recorded event then, and
    the window is cut there.  Otherwise it is the window's end."""
    last = max((s + d for _, s, d in dev["modules"] + dev["ops"]),
               default=run.t0)
    later = [a for a, _, _ in run.spans("round") if a > last + 0.5]
    return min(run.t1, last) if later else run.t1


def device_idle_pct(run) -> float | None:
    dev = run.device()
    if dev is None:
        return None
    intervals = busy_intervals(dev)
    if not intervals:
        return None
    end = traced_end(run, dev)
    busy = trace.busy_s(intervals, run.t0, end)
    return 100.0 * (1.0 - busy / (end - run.t0))


def matched_rounds(run):
    """Each round program the device ran in the traced part of the
    window, with the engine's ``round`` span that encloses it:
    ``(device_s, span_s, lanes, sweeps)``."""
    dev = run.device()
    if dev is None:
        return []
    end = traced_end(run, dev)
    spans = run.spans("round")
    out = []
    for _, s, d in trace.module_events(dev, ROUND_PROGRAM):
        if not (run.t0 <= s and s + d <= end):
            continue
        mid = s + d / 2
        for a, b, args in spans:
            if a <= mid <= b:
                out.append((d, b - a, args["lanes_busy"]
                            + args["lanes_vacant"], args["sweeps"]))
                break
    return out


def sweep_updates_and_bytes(run, matched):
    h, w, n_labels = (run.config["height"], run.config["width"],
                      run.config["n_labels"])
    updates = sum(lanes * h * w * sweeps for _, _, lanes, sweeps in matched)
    nbytes = sum(cost.round_bytes(lanes, h, w, n_labels, sweeps)
                 for _, _, lanes, sweeps in matched)
    return updates, nbytes


def median_ms(xs) -> float | None:
    return float(np.median(xs) * 1e3) if len(xs) else None
