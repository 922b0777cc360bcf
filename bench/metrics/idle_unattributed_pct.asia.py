"""Share of device-idle time no dispatcher span covers, as
``idle_unattributed_pct.penguin`` reads it."""
from harness import plugins


def read(run):
    return plugins.load("metrics", "idle_unattributed_pct.penguin").read(run)
