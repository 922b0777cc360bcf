"""Share of the traced window in which no operation ran on the device."""
from harness import layers


def read(run):
    return layers.device_idle_pct(run)
