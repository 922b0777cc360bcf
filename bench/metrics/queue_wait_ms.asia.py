"""Median ``wait`` phase (submit to admission) of the queries the
engine retired in the window."""
from harness import layers


def read(run):
    return layers.median_ms([b - a for a, b, _ in run.spans("wait")])
