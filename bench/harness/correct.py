"""The comparison that decides ``correct``.

Every answer due in the window is compared with the plain reference the
configuration names (``bench/reference/<reference>.py``) once the
window has closed and the program's state is freed.  Two numbers are
compared, each with its limit from the cell's file:

* ``gap_mean``: the mean, over every queried variable (site) of every
  answer, of the largest gap between the served marginal and the
  reference's, in probability.  One answer's Monte-Carlo error sets its
  floor; a state that never moves or an answer altered reads far above.
* ``rare_gap``: how far the served probability of rare outcomes is off,
  pooled over the window.  For every group the configuration's
  ``calibration`` lists (all groups where it lists none), over the
  compared variables whose reference gives its least likely label a
  probability inside ``band``: |1 - sum served / sum reference| of that
  label; the largest over the groups.  Pooling hundreds of answers
  averages the Monte-Carlo error away, so a bias that one answer hides
  shows: weights rounded to fewer bits floor small probabilities.

An answer that is malformed (a variable missing, a marginal that is not
a distribution over the variable's labels) or a request that never got
an answer makes the run not correct whatever the numbers read, and so
does a window in which nothing could be compared.
"""
from __future__ import annotations

import numpy as np

from harness import plugins


def rare_gap(pairs, calibration: dict) -> float | None:
    lo, hi = calibration["band"]
    groups = calibration.get("groups")
    sums: dict[str, list[float]] = {}
    for group, served, exact in pairs:
        if served is None or (groups is not None and group not in groups):
            continue
        label = int(np.argmin(exact))
        if lo <= exact[label] < hi:
            s = sums.setdefault(group, [0.0, 0.0])
            s[0] += served[label]
            s[1] += exact[label]
    gaps = [abs(1.0 - s / e) for s, e in sums.values() if e > 0]
    return float(max(gaps)) if gaps else None


def compare(config: dict, data, items: list, *, n_due: int, n_failed: int,
            limits: dict, ref_settings: dict, seed: int) -> dict:
    """``items``: (wire request, kept answer) of every answered request
    due in the window; ``data``: the family's model data.  Returns
    ``{"correct": bool, "numbers": {name: {"value", "limit"}}}``."""
    ref = plugins.load("reference", config["reference"])
    pairs = ref.pairs(config, data, items, ref_settings, seed)
    gaps = [float(np.max(np.abs(s - e))) for _, s, e in pairs
            if s is not None]
    bad = sum(1 for _, s, _ in pairs if s is None)
    values = {"gap_mean": float(np.mean(gaps)) if gaps else None,
              "rare_gap": rare_gap(pairs, config["calibration"])}
    numbers = {name: {"value": v, "limit": limits[name]}
               for name, v in values.items()}
    numbers.update({
        "malformed": {"value": bad, "limit": 0},
        "unanswered": {"value": n_failed, "limit": 0},
        "compared": {"value": len(gaps), "limit": None},
    })
    ok = (n_due > 0 and bad == 0 and n_failed == 0
          and all(v is not None and v <= limits[name]
                  for name, v in values.items()))
    return {"correct": bool(ok), "numbers": numbers}
