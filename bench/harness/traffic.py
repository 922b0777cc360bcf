"""The one traffic generator: a mix file of parameters in, a schedule of
wire requests out, everything a function of the seed.

Evidence *patterns* are fixed by the configuration file (each pattern
is one compiled program, warmed in set-up); what a request carries
beyond its pattern comes from the configuration's family
(``bench/families/<family>.py``), which draws it from the seed.

Mix keys (``bench/traffic/<name>.json``):

* ``loop``: ``open`` (requests at scheduled times over one WebSocket,
  whatever has come back) or ``closed`` (callers that each send their
  next request when the last is answered).
* ``query_vars`` / ``query_sites``: ``[lo, hi]``, the variables or
  sites a request asks.

Open loop:

* ``rate_qps``: mean offered rate; ``rate_qps`` x seconds requests.
* ``arrivals``: ``{"kind": "poisson"}`` (the default: times uniform on
  the window, a Poisson process given its count) or ``{"kind":
  "onoff", "on_s": a, "off_s": b}`` (the same count, uniform on the
  ``on`` stretches of a cycle of ``a + b`` seconds, none in between).
* ``zipf_s``: pattern popularity; pattern counts are Zipf shares of the
  count, rank = the configuration's pattern order.
* ``sessions``: optional ``{"count": n}``: requests go to ``n`` streams
  (``stream_id``), each on one pattern; a stream's later requests
  follow its earlier one (the family reads e.g. ``drift``).

Every seed gets the same arrival times and, for each pattern, the same
number of requests, of each evidence stratum (the family's distinct
evidence values: each equally often), of each query size, and of
queries of each free variable (each equally often): these set the work.
The seed draws which request comes when, which stratum and which set of
variables each carries, and the rest the family draws.

Closed loop: ``users`` callers, consecutive callers on one pattern,
each with ``max_per_user`` requests it sends one after another; a
caller's later requests follow its earlier one.
"""
from __future__ import annotations

import numpy as np

WINDOW, WARMUP = 0, 1      # rng streams: the measured window, set-up


def zipf_counts(n: int, k: int, s: float) -> list[int]:
    """``n`` requests over ``k`` ranks by Zipf shares, largest remainder."""
    share = 1.0 / np.arange(1, k + 1) ** s
    exact = n * share / share.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def sizes(mix: dict) -> tuple[int, int]:
    lo, hi = mix.get("query_vars", mix.get("query_sites"))
    return int(lo), int(hi)


def arrival_times(mix: dict, n: int, seconds: float, stream: int):
    """``n`` arrival offsets in [0, seconds), the same for every seed."""
    rng = np.random.default_rng([stream, n, 7])
    arr = mix.get("arrivals", {"kind": "poisson"})
    if arr["kind"] == "poisson":
        return np.sort(rng.uniform(0.0, seconds, n))
    if arr["kind"] == "onoff":
        on, off = float(arr["on_s"]), float(arr["off_s"])
        cycles, rest = divmod(seconds, on + off)
        on_total = cycles * on + min(rest, on)
        u = np.sort(rng.uniform(0.0, on_total, n))
        return (u // on) * (on + off) + u % on
    raise ValueError(f"unknown arrivals {arr['kind']!r}")


def _open(fam, mix, seed, seconds, stream, rate_qps):
    rng = np.random.default_rng([seed, stream])
    rate = float(rate_qps if rate_qps is not None else mix["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    times = arrival_times(mix, n, seconds, stream)
    lo, hi = sizes(mix)
    sess = mix.get("sessions")
    n_pat = len(fam.patterns)
    if sess:
        # each stream on one pattern (Zipf over the streams); request i
        # goes to a stream dealt out in a seeded order
        s_pat = np.repeat(np.arange(n_pat),
                          zipf_counts(int(sess["count"]), n_pat,
                                      float(mix["zipf_s"])))
        which_s = rng.permutation(np.arange(n) % len(s_pat))
        n_query = rng.permutation(lo + np.arange(n) % (hi - lo + 1))
        last: dict[int, dict] = {}
        reqs = []
        for i in range(n):
            s = int(which_s[i])
            p = int(s_pat[s])
            wire = fam.request(mix, p, rng, int(n_query[i]),
                               prev=last.get(s))
            wire["stream_id"] = f"s{s}"
            last[s] = wire
            reqs.append({"t": float(times[i]), "wire": wire})
        return {"loop": "open", "requests": reqs}
    plan = []
    for p, count in enumerate(zipf_counts(n, n_pat, float(mix["zipf_s"]))):
        sizes_p = lo + np.arange(count) % (hi - lo + 1)
        strata = rng.permutation(np.arange(count) % fam.strata(p))
        queries = _balanced(fam.variables(p), sizes_p, rng)
        plan += [(p, int(j), q) for j, q in zip(strata, queries)]
    order = rng.permutation(n)
    reqs = []
    for i in range(n):
        p, stratum, query = plan[order[i]]
        reqs.append({"t": float(times[i]),
                     "wire": fam.request(mix, p, rng, len(query),
                                         stratum=stratum, query=query)})
    return {"loop": "open", "requests": reqs}


def _balanced(variables: list, sizes, rng) -> list[list]:
    """Query sets of the given sizes that ask every variable equally
    often (to one): a seeded order of the variables, dealt out in turn
    and shuffled anew each time round."""
    deck: list = []
    out = []
    for size in sizes:
        if len(deck) < size:
            held = set(deck)
            deck += ([v for v in rng.permutation(variables).tolist()
                      if v not in held] + [v for v in variables if v in held])
        out.append(deck[:size])
        deck = deck[size:]
    return out


def _closed(fam, mix, seed, stream):
    rng = np.random.default_rng([seed, stream])
    lo, hi = sizes(mix)
    users, n_pat = int(mix["users"]), len(fam.patterns)
    out = []
    for u in range(users):
        p = u * n_pat // users
        wires, prev = [], None
        for _ in range(int(mix["max_per_user"])):
            prev = fam.request(mix, p, rng, int(rng.integers(lo, hi + 1)),
                               prev=prev)
            wires.append(prev)
        out.append(wires)
    return {"loop": "closed", "users": out}


def schedule(fam, mix: dict, seed: int, seconds: float,
             stream: int = WINDOW, rate_qps: float | None = None) -> dict:
    """The load generator's input: ``{"loop": "open", "requests":
    [{"t": offset_s, "wire": {...}}, ...]}`` or ``{"loop": "closed",
    "users": [[wire, ...], ...]}``.  ``rate_qps`` overrides an open
    mix's rate (the knee sweep of ``bench/control.py``)."""
    if mix["loop"] == "open":
        return _open(fam, mix, seed, seconds, stream, rate_qps)
    return _closed(fam, mix, seed, stream)


def probes(fam, mix: dict) -> list[dict]:
    """One request per pattern, for the engine's warm-up."""
    rng = np.random.default_rng(0)
    return [fam.request(mix, p, rng, sizes(mix)[0])
            for p in range(len(fam.patterns))]


def wire_of(sched: dict, record: dict) -> dict:
    if sched["loop"] == "open":
        return sched["requests"][record["i"]]["wire"]
    return sched["users"][record["user"]][record["i"]]
