"""95th percentile, over every request due in the window, of the time
from its scheduled arrival to its decoded answer (never answered:
its age when waiting stopped)."""
from harness import window


def read(run):
    return window.percentile_ms(
        window.latencies_s(run.records, run.t0, run.t1, run.t_stop), 95)
