"""Reduction of a ``jax.profiler`` trace to device metrics.

:func:`load` reads the ``.xplane.pb`` a traced run writes and returns
the device's operations and module executions as ``(name, start_s,
dur_s)`` on the host's monotonic clock: the benchmark writes a marker
(``MARKER``) into the trace at a monotonic time it records, and that
pair fixes the offset.  The rest are pure functions of those lists, so
they are tested on a small recorded trace.
"""
from __future__ import annotations

import glob
import os

MARKER = "bench.window_start"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str, marker_monotonic: float) -> dict:
    """``{"devices": [{"name", "ops", "modules"}], "lines": summary}``;
    times in seconds on the monotonic clock."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    marker_ns = None
    devices, lines = [], {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        dev = {"name": plane.name, "ops": [], "modules": []}
        for line in plane.lines:
            n = 0
            for ev in line.events:
                n += 1
                if ev.name == MARKER and marker_ns is None:
                    marker_ns = ev.start_ns
                if not is_device:
                    continue
                if line.name == OPS_LINE:
                    dev["ops"].append((ev.name, ev.start_ns, ev.duration_ns))
                elif line.name == MODULES_LINE:
                    dev["modules"].append(
                        (ev.name, ev.start_ns, ev.duration_ns))
            lines[f"{plane.name} | {line.name}"] = n
        if is_device:
            devices.append(dev)
    if marker_ns is None:
        raise ValueError(f"marker {MARKER!r} not found in the trace")
    shift = marker_monotonic - marker_ns * 1e-9
    for dev in devices:
        for key in ("ops", "modules"):
            dev[key] = [(name, s * 1e-9 + shift, d * 1e-9)
                        for name, s, d in dev[key]]
    return {"devices": devices, "lines": lines}


def merged(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """Union of ``(name, start, dur)`` intervals clipped to [t0, t1]."""
    spans = sorted((max(s, t0), min(s + d, t1)) for _, s, d in intervals
                   if s < t1 and s + d > t0)
    out: list[list[float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(intervals, t0: float, t1: float) -> float:
    return sum(b - a for a, b in merged(intervals, t0, t1))


def short(name: str) -> str:
    """An operation's name as the trace gives it, without the HLO
    signature that follows ``" = "``."""
    return name.split(" = ", 1)[0][:120]


def top_ops(intervals, t0: float, t1: float, n: int = 10):
    """The ``n`` operation names with the most device time in [t0, t1]."""
    tot: dict[str, float] = {}
    for name, s, d in intervals:
        if t0 <= s < t1:
            key = short(name)
            tot[key] = tot.get(key, 0.0) + d
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]


def idle_gaps(intervals, t0: float, t1: float, host_spans, n: int = 10):
    """The ``n`` longest stretches of [t0, t1] with no device operation,
    each named by the innermost host span (``(name, start, end)`` on the
    same clock) around its middle."""
    busy = merged(intervals, t0, t1)
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        around = [s for s in host_spans if s[1] <= mid <= s[2]]
        name = (min(around, key=lambda s: s[2] - s[1])[0] if around
                else "no engine span")
        out.append([name, b - a])
    return out


def module_events(device: dict, substring: str):
    return [ev for ev in device["modules"] if substring in ev[0]]
