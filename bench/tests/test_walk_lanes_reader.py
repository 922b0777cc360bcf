"""The reader of the Knuth-Yao walks per site update, on a small
synthetic run: ``round`` spans as ``Telemetry.events()`` gives them
(``ts``/``dur`` in microseconds from the recorder's birth), one of them
starting before the window."""
import pytest

from harness import plugins

T0 = 100.0        # the recorder's birth on the monotonic clock
GROUP = 1


def X(name, t0, t1, **args):
    """A complete event over [t0, t1] seconds after the window opens,
    which is 10 s after the recorder's birth."""
    return {"name": name, "ph": "X", "pid": 1, "tid": GROUP,
            "ts": (10.0 + t0) * 1e6, "dur": (t1 - t0) * 1e6, "args": args}


class RoundRun:
    """Window [110, 120] on the monotonic clock: two rounds in it, one
    that started before it, and a counter event."""

    tel_t0 = T0
    t0, t1 = 110.0, 120.0

    def __init__(self, ky_lanes=True):
        rounds = [(0.5, 3.0, 1000, 1500, 1010), (6.0, 9.0, 3000, 3900, 3030),
                  (-2.0, 0.4, 10**6, 0, 0)]
        ev = []
        for t0, t1, updates, bits, lanes in rounds:
            args = {"site_updates": updates, "bits": bits}
            if ky_lanes:
                args["ky_lanes"] = lanes
            ev.append(X("round", t0, t1, **args))
            ev.append(X("device_wait", t0 + 0.1, t1 - 0.1))
        ev.append({"name": "lanes_busy", "ph": "C", "pid": 1,
                   "ts": 11e6, "args": {"lanes_busy": 8}})
        self.events = ev


def read(name, run):
    return plugins.load("metrics", name).read(run)


def test_walk_lanes_per_update_of_window_rounds():
    # the round that started before the window is left out
    assert read("walk_lanes_per_update.penguin", RoundRun()) == \
        pytest.approx((1010 + 3030) / (1000 + 3000))


def test_walk_lanes_silent_where_rounds_lack_the_arg():
    # a program that counts bits but not walks (the form before
    # ky_lanes): no reading
    run = RoundRun(ky_lanes=False)
    assert read("walk_lanes_per_update.penguin", run) is None
    assert read("bits_per_update.penguin", run) is not None


@pytest.mark.parametrize("events", [[], [X("query", 0.0, 1.0, qid=3)]])
def test_silent_on_a_program_without_rounds(events):
    run = RoundRun()
    run.events = events
    assert read("walk_lanes_per_update.penguin", run) is None
