"""Median, over every request sent in the window, of the time from a
user's send to that user's decoded answer."""
from harness import window


def read(run):
    return window.percentile_ms(
        window.latencies_s(run.records, run.t0, run.t1, run.t_stop), 50)
