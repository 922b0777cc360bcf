"""The readers of the program's own spans and counters, on a small
synthetic run: engine events as ``Telemetry.events()`` gives them
(``ts``/``dur`` in microseconds from the recorder's birth) and a device
whose program executions leave two idle gaps, one the dispatcher spent
in ``await_work`` and one no span covers."""
import pytest

from harness import plugins

T0 = 100.0        # the recorder's birth on the monotonic clock
GROUP, DISPATCHER, Q1, Q2 = 1, 2, 3, 4


def X(name, tid, t0, t1, **args):
    """A complete event over [t0, t1] seconds after the window opens,
    which is 10 s after the recorder's birth."""
    return {"name": name, "ph": "X", "pid": 1, "tid": tid,
            "ts": (10.0 + t0) * 1e6, "dur": (t1 - t0) * 1e6, "args": args}


class SpanRun:
    """Window [110, 120] on the monotonic clock (events 10 s after the
    recorder's birth): two engine rounds with their children, a backfill
    gap the dispatcher spent waiting, and a gap with no span at all."""

    tel_t0 = T0
    t0, t1 = 110.0, 120.0

    def __init__(self):
        ev = []
        # group_start, then round 1 [0.5, 3.0]: device busy [0.8, 2.4]
        ev.append(X("await_work", DISPATCHER, 0.0, 0.2))
        ev.append(X("group_start", DISPATCHER, 0.2, 0.5))
        ev.append(X("round", GROUP, 0.5, 3.0, site_updates=1000, bits=1500))
        ev.append(X("dispatch", GROUP, 0.5, 0.8))
        ev.append(X("device_wait", GROUP, 0.8, 2.4))
        ev.append(X("readback", GROUP, 2.4, 2.5))
        ev.append(X("judge", GROUP, 2.5, 3.0))
        ev.append(X("retire", GROUP, 2.6, 2.9, qid=Q1, sites=2))
        ev.append(X("deliver", DISPATCHER, 3.0, 3.1))
        # waiting for work [3.1, 5.0]: device idle, covered
        ev.append(X("await_work", DISPATCHER, 3.1, 5.0))
        # nothing recorded in [5.0, 6.0]: device idle, not covered
        ev.append(X("round", GROUP, 6.0, 9.0, site_updates=3000, bits=3900))
        ev.append(X("dispatch", GROUP, 6.0, 6.2))
        ev.append(X("device_wait", GROUP, 6.2, 8.2))
        ev.append(X("judge", GROUP, 8.2, 9.0))
        ev.append(X("retire", GROUP, 8.3, 8.4, qid=Q2, sites=1))
        ev.append(X("retire", GROUP, 8.5, 8.8, qid=Q1, sites=2))
        ev.append(X("deliver", DISPATCHER, 9.0, 9.2))
        # the queries and the front end's requests around them
        ev.append(X("query", Q1, 0.0, 2.9, qid=Q1))
        ev.append(X("request", Q1, -0.1, 3.3, qid=Q1, transport="ws"))
        ev.append(X("query", Q2, 5.5, 8.4, qid=Q2))
        ev.append(X("request", Q2, 5.4, 8.6, qid=Q2, transport="ws"))
        # a round before the window (ends inside it) and a late one
        ev.append(X("round", GROUP, -2.0, 0.4, site_updates=10**6, bits=0))
        ev.append(X("judge", GROUP, -0.5, 0.4))
        ev.append({"name": "lanes_busy", "ph": "C", "pid": 1,
                   "ts": 11e6, "args": {"lanes_busy": 8}})
        self.events = ev
        busy = [(0.8, 1.6), (6.2, 2.0), (9.6, 0.3)]
        mods = [("jit_round_fn(1)", 110.0 + s, d) for s, d in busy]
        self.trace = {"devices": [{"name": "/device:TPU:0", "ops": [],
                                   "modules": mods}], "lines": {}}

    def device(self):
        return self.trace["devices"][0]

    def spans(self, name):
        out = []
        for ev in self.events:
            if ev.get("ph") == "X" and ev["name"] == name:
                a = self.tel_t0 + ev["ts"] * 1e-6
                b = a + ev["dur"] * 1e-6
                if self.t0 <= b <= self.t1:
                    out.append((a, b, ev.get("args", {})))
        return out


class BareRun(SpanRun):
    """The same run as a program without the spans records it: rounds
    without counts, no judge, retire, request or dispatcher spans."""

    def __init__(self):
        super().__init__()
        keep = ("round", "query", "wait", "plan", "service")
        self.events = [dict(e, args={}) if e["name"] == "round" else e
                       for e in self.events
                       if e.get("ph") != "X" or e["name"] in keep]


def read(name, run):
    return plugins.load("metrics", name).read(run)


@pytest.mark.parametrize("cell", ["penguin", "asia"])
def test_round_judge_self_time(cell):
    # judges starting in the window: 0.5 s less a 0.3 s retire, and
    # 0.8 s less two retires of 0.1 and 0.3 s
    got = read(f"round_judge_ms.{cell}", SpanRun())
    assert got == pytest.approx((0.2 + 0.4) / 2 * 1e3)


def test_retire_per_retired_query():
    assert read("retire_ms.penguin", SpanRun()) == pytest.approx(
        (0.3 + 0.1 + 0.3) / 3 * 1e3)


def test_frontend_self_time_by_qid():
    # request less its own query: 3.4 - 2.9 and 3.2 - 2.9
    assert read("frontend_self_ms.asia", SpanRun()) == pytest.approx(
        (0.5 + 0.3) / 2 * 1e3)


def test_bits_per_update_of_window_rounds():
    # the round that started before the window is left out
    assert read("bits_per_update.penguin", SpanRun()) == pytest.approx(
        (1500 + 3900) / (1000 + 3000))


@pytest.mark.parametrize("cell", ["penguin", "asia"])
def test_idle_left_unattributed(cell):
    run = SpanRun()
    # idle: 10 s less 1.6 + 2.0 + 0.3 busy = 6.1 s; covered by the
    # dispatcher's spans except [5.0, 6.0] and [9.2, 9.6] and
    # [9.9, 10.0]
    assert read(f"idle_unattributed_pct.{cell}", run) == pytest.approx(
        100 * (1.0 + 0.4 + 0.1) / 6.1)


@pytest.mark.parametrize("name", [
    "round_judge_ms.penguin", "round_judge_ms.asia", "retire_ms.penguin",
    "frontend_self_ms.asia", "bits_per_update.penguin",
    "idle_unattributed_pct.penguin", "idle_unattributed_pct.asia"])
def test_silent_on_a_program_without_the_spans(name):
    assert read(name, BareRun()) is None
