"""Exact posterior marginals of a small Bayesian network by enumeration."""
from __future__ import annotations

import numpy as np

from reference import distribution


def marginals(tables, evidence: dict[str, int]) -> dict[str, np.ndarray]:
    """``P(v | evidence)`` for every node, by summing the joint over all
    assignments consistent with the evidence.  ``tables`` is
    ``(names, card, parents, cpts)``, parents as index tuples."""
    names, card, parents, cpts = tables
    n = len(names)
    grid = np.indices(card).reshape(n, -1).T
    for name, val in evidence.items():
        grid = grid[grid[:, names.index(name)] == int(val)]
    logp = np.zeros(len(grid))
    for v in range(n):
        idx = tuple(grid[:, p] for p in parents[v]) + (grid[:, v],)
        logp += np.log(cpts[v][idx])
    p = np.exp(logp - logp.max())
    p /= p.sum()
    return {names[v]: np.bincount(grid[:, v], weights=p, minlength=card[v])
            for v in range(n)}


def pairs(config, data, items, settings, seed):
    """One triple per query variable, pooled under the variable's name."""
    names, card = data[0], data[1]
    out, memo = [], {}
    for wire, answer in items:
        key = tuple(sorted(wire["evidence"].items()))
        if key not in memo:
            memo[key] = marginals(data, wire["evidence"])
        exact = memo[key]
        for var in wire["query_vars"]:
            served = distribution((answer["marginals"] or {}).get(var),
                                  card[names.index(var)])
            out.append((var, served, exact[var]))
    return out
