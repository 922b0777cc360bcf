"""Trace reduction on a small trace laid out as the v5e's is: on the
``XLA Ops`` line a round program's scan (``%while``) holds its body's
operations, so operations nest; on ``XLA Modules`` one ``jit_round_fn``
event per round; and the engine's ``round`` spans around them."""
import pytest

from harness import cost, layers, plugins, trace

ROUNDS = [  # (span start, span end, device start, device dur, lanes)
    (0.00, 1.30, 0.05, 1.00, 8),
    (1.30, 2.00, 1.40, 0.50, 4),
    (2.00, 3.40, 2.20, 1.00, 8),
]


class SmallRun:
    t0, t1 = 0.0, 3.5
    device_kind = "TPU v5 lite"
    config = {"height": 500, "width": 333, "n_labels": 2}

    def __init__(self):
        ops, mods = [], []
        for _, _, s, d, _ in ROUNDS:
            mods.append(("jit_round_fn(1)", s, d))
            ops.append(("%while.57 = (s32[]...)", s, d))
            ops.append(("%while.63 = (s32[1332000]...)", s + 0.1, d / 2))
            ops.append(("%fusion.193 = f32[1332000]...", s + 0.1 + d / 2,
                        0.1))
        self.trace = {"devices": [{"name": "/device:TPU:0", "ops": ops,
                                   "modules": mods}], "lines": {}}

    def device(self):
        return self.trace["devices"][0]

    def spans(self, name):
        assert name == "round"
        return [(a, b, {"lanes_busy": lanes, "lanes_vacant": 0,
                        "sweeps": 16}) for a, b, _, _, lanes in ROUNDS]


def read(metric, run):
    return plugins.load("metrics", metric).read(run)


def test_union_and_idle_by_hand():
    ops = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 1.0)]
    assert trace.merged(ops, 0.0, 5.0) == [(0.0, 1.5), (3.0, 4.0)]
    assert trace.busy_s(ops, 0.0, 5.0) == pytest.approx(2.5)
    assert trace.busy_s(ops, 1.0, 3.5) == pytest.approx(1.0)
    gaps = trace.idle_gaps(ops, 0.0, 5.0, [("round", 1.0, 2.5)])
    assert gaps == [["round", 1.5], ["no engine span", 1.0]]
    assert trace.top_ops(ops, 0.0, 5.0)[0] == ["a", 1.0]


def test_nested_operations_count_once():
    run = SmallRun()
    busy = trace.busy_s(run.device()["ops"], run.t0, run.t1)
    assert busy == pytest.approx(2.5)           # the three scans
    assert layers.device_idle_pct(run) == pytest.approx(100 * 1.0 / 3.5)
    top = trace.top_ops(run.device()["ops"], run.t0, run.t1)
    assert top[0] == ["%while.57", pytest.approx(2.5)]
    gaps = trace.idle_gaps(run.device()["ops"], run.t0, run.t1,
                           [("round", a, b) for a, b, *_ in ROUNDS])
    # idle from 1.05 to 1.40: its middle lies in the first round span
    assert gaps[0] == ["round", pytest.approx(0.35)]


def test_round_programs_match_spans():
    run = SmallRun()
    matched = layers.matched_rounds(run)
    assert [(d, lanes) for d, _, lanes, _ in matched] == [
        (1.0, 8), (0.5, 4), (1.0, 8)]
    updates, nbytes = layers.sweep_updates_and_bytes(run, matched)
    assert updates == (8 + 4 + 8) * 500 * 333 * 16
    assert nbytes == sum(cost.round_bytes(lanes, 500, 333, 2, 16)
                         for lanes in (8, 4, 8))
    device_s = sum(d for d, *_ in matched)
    roofline = 100 * nbytes / cost.peaks(run.device_kind)[
        "hbm_bytes_per_s"] / device_s
    assert 0 < roofline <= 100
    assert read("sweep_roofline_pct.penguin", run) == pytest.approx(roofline)
    assert read("update_ns.penguin", run) == pytest.approx(
        2.5 / updates * 1e9)
    # host share of a round: span minus its device time, (0.3+0.2+0.4)/3
    assert read("round_host_ms.asia", run) == pytest.approx(300.0)


def test_window_cut_where_the_device_trace_stops():
    """Rounds ran after the last recorded device event: the profiler's
    buffer was full, so idle is read over the covered part only."""
    run = SmallRun()
    dev = run.device()
    assert layers.traced_end(run, dev) == run.t1
    dev["modules"] = dev["modules"][:1]
    dev["ops"] = dev["ops"][:3]
    assert layers.traced_end(run, dev) == pytest.approx(1.05)
    assert layers.device_idle_pct(run) == pytest.approx(100 * 0.05 / 1.05)
    # only the rounds inside the traced part count for the host share
    assert [d for d, *_ in layers.matched_rounds(run)] == [1.0]
    assert read("round_host_ms.asia", run) == pytest.approx(300.0)

