"""The least time the sweep's bytes (``harness.cost``) take at the
chip's HBM peak, over the device time of the round programs.  Memory
bound by construction: no compute bound is counted (the vector unit's
integer peak is not published)."""
from harness import cost, layers


def read(run):
    matched = layers.matched_rounds(run)
    _, nbytes = layers.sweep_updates_and_bytes(run, matched)
    device_s = sum(d for d, *_ in matched)
    if not device_s:
        return None
    peak = cost.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / peak / device_s
