"""Worst-case effective sample size of every answer received in the
window, over the window."""
from harness import window


def read(run):
    return window.ess_per_s(run.records, run.t0, run.t1)
