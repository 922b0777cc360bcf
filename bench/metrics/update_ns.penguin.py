"""Device time of the round programs in the window over the site
visits they made (lanes x sites x sweeps, vacant lanes included: the
device updates them too), in nanoseconds."""
from harness import layers


def read(run):
    matched = layers.matched_rounds(run)
    updates, _ = layers.sweep_updates_and_bytes(run, matched)
    if not updates:
        return None
    return sum(d for d, *_ in matched) / updates * 1e9
