"""Run the metric readers: ``bench/metrics/<name>.py`` defines
``read(run) -> float | None`` for the metric ``<name>``.  A reader that
finds nothing to read returns None and the metric is left out."""
from __future__ import annotations

from harness import plugins


def read_all(metrics: list[dict], run) -> dict:
    out = {}
    for m in metrics:
        value = plugins.load("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
