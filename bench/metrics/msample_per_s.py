"""Site updates done in the window over the window, in millions per
second: each answer's updates spread over its service time, the part
inside the window counted (``window.updates_in_window``)."""
from harness import window


def read(run):
    return window.msample_per_s(run.records, run.t0, run.t1)
