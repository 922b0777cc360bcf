"""Bayesian networks written out in the configuration file.

The file lists the nodes with their parents and conditional probability
tables, and the evidence *patterns* (observed node names), each one
compiled program, warmed in set-up.  A request clamps the pattern's
nodes to values and asks for the marginals of some free nodes.

Mix keys read here: ``drift`` (a request that follows another of the
same stream redraws each observed value with this probability).
"""
from __future__ import annotations

import itertools

import numpy as np


class Family:
    def __init__(self, config: dict):
        self.config = config
        nodes = config["nodes"]
        names = [n["name"] for n in nodes]
        index = {n: i for i, n in enumerate(names)}
        card = [int(n["card"]) for n in nodes]
        parents = [tuple(index[p] for p in n["parents"]) for n in nodes]
        cpts = [np.asarray(n["cpt"], np.float64) for n in nodes]
        # (names, card, parents as index tuples, cpts): what the plain
        # reference reads; made here, never by the program
        self.data = (names, card, parents, cpts)
        self.names, self.card = names, card
        self.patterns = [list(p) for p in config["patterns"]]

    def program(self):
        """The configuration as the program's own model object."""
        from repro.pgm.graph import BayesNet

        names, card, parents, cpts = self.data
        return BayesNet(card, parents, cpts, names)

    def strata(self, p: int) -> int:
        """Distinct evidence values of pattern ``p``."""
        return int(np.prod([self.card[self.names.index(n)]
                            for n in self.patterns[p]]))

    def variables(self, p: int) -> list[str]:
        """The variables a request on pattern ``p`` may ask."""
        return [n for n in self.names if n not in self.patterns[p]]

    def request(self, mix: dict, p: int, rng: np.random.Generator,
                n_query: int, stratum: int | None = None,
                prev: dict | None = None,
                query: list[str] | None = None) -> dict:
        obs = self.patterns[p]
        cards = [self.card[self.names.index(n)] for n in obs]
        if prev is not None and mix.get("drift"):
            vals = [int(rng.integers(c)) if rng.random() < mix["drift"]
                    else prev["evidence"][n] for n, c in zip(obs, cards)]
        elif stratum is not None:
            vals = list(itertools.product(*map(range, cards)))[stratum]
        else:
            vals = [int(rng.integers(c)) for c in cards]
        if query is None:
            free = self.variables(p)
            query = rng.choice(free, size=min(n_query, len(free)),
                               replace=False)
        return {"v": 2, "network": self.config["name"],
                "n_samples": int(self.config["n_samples"]),
                "evidence": {n: int(v) for n, v in zip(obs, vals)},
                "query_vars": [str(v) for v in query]}
