"""Plain references: straightforward implementations of the posterior
each configuration asks for, sharing no code with the program.

A reference file ``bench/reference/<name>.py`` is the one a
configuration's ``reference`` key names.  It defines ``pairs(config,
data, items, settings, seed)``: for every queried variable of every
item ``(wire request, kept answer)``, one ``(group, served, exact)``
triple, where ``served`` is the answer's marginal as a float64 vector
(None when the answer leaves it out or it is not a distribution),
``exact`` the reference's, and ``group`` the name the comparison pools
it under (``harness/correct.py``).  ``data`` is the model data the
configuration's family made from the file, never the program's.
"""
from __future__ import annotations

import numpy as np


def distribution(m, n_labels: int) -> np.ndarray | None:
    """``m`` as a float64 distribution over ``n_labels`` labels, or None
    when it is not one."""
    a = np.asarray(m, np.float64) if m is not None else None
    if (a is None or a.shape != (n_labels,) or not np.isfinite(a).all()
            or (a < 0).any() or abs(a.sum() - 1.0) > 1e-6):
        return None
    return a
