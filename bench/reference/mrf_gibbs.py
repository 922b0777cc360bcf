"""Posterior marginals of a Potts grid MRF by plain checkerboard Gibbs
sampling in float32, with exact ``exp`` and categorical draws.

The energy of label ``l`` at a site is ``unary[r, c, l]`` plus ``beta``
for each of its (up to four, free boundary) neighbours that carries
another label; clamped sites keep their label and still act on their
neighbours.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference import distribution


@partial(jax.jit, static_argnames=("chains", "burn", "sweeps"))
def _counts(key, unary, beta, clamp, values, *, chains, burn, sweeps):
    h, w, n_labels = unary.shape
    labels = jnp.arange(n_labels)
    key, k0 = jax.random.split(key)
    x = jax.random.randint(k0, (chains, h, w), 0, n_labels)
    x = jnp.where(clamp, values, x)
    rows, cols = jnp.arange(h)[:, None], jnp.arange(w)[None, :]
    color = (rows + cols) % 2

    def energy(x):
        e = jnp.broadcast_to(unary, (chains, h, w, n_labels))
        for shift, axis, valid in (
                (1, 1, rows > 0), (-1, 1, rows < h - 1),
                (1, 2, cols > 0), (-1, 2, cols < w - 1)):
            nb = jnp.roll(x, shift, axis=axis)
            differ = labels != nb[..., None]
            e = e + beta * (differ & valid[None, ..., None])
        return e

    def half(x, key, parity):
        new = jax.random.categorical(key, -energy(x), axis=-1)
        return jnp.where((color == parity) & ~clamp, new, x)

    def sweep(i, carry):
        x, key, counts = carry
        key, k1, k2 = jax.random.split(key, 3)
        x = half(half(x, k1, 0), k2, 1)
        onehot = (x[..., None] == labels).sum(axis=0, dtype=jnp.int32)
        return x, key, counts + jnp.where(i >= burn, onehot, 0)

    counts = jnp.zeros((h, w, n_labels), jnp.int32)
    _, _, counts = jax.lax.fori_loop(0, burn + sweeps, sweep,
                                     (x, key, counts))
    return counts


def marginals(unary, beta: float, clamp_sites, *, chains: int, burn: int,
              sweeps: int, seed: int) -> np.ndarray:
    """(H, W, L) float64 marginals given ``clamp_sites`` ([row, col,
    label] triples), from ``chains`` x ``sweeps`` kept draws."""
    unary = jnp.asarray(unary, jnp.float32)
    h, w, _ = unary.shape
    clamp = np.zeros((h, w), bool)
    values = np.zeros((h, w), np.int32)
    for r, c, lab in clamp_sites:
        clamp[r, c], values[r, c] = True, lab
    counts = _counts(jax.random.PRNGKey(seed), unary, jnp.float32(beta),
                     jnp.asarray(clamp), jnp.asarray(values),
                     chains=chains, burn=burn, sweeps=sweeps)
    return np.asarray(counts, np.float64) / (chains * sweeps)


def pairs(config, data, items, settings, seed):
    """One triple per query site, pooled under one group, ``site``.  The
    reference runs once for every distinct set of clamped sites."""
    unary, beta = data
    n_labels = unary.shape[-1]
    by_mask: dict[tuple, list] = {}
    for wire, answer in items:
        key = tuple(map(tuple, wire["mask_sites"]))
        by_mask.setdefault(key, []).append((wire, answer))
    out = []
    for i, (key, its) in enumerate(sorted(by_mask.items())):
        ref = marginals(unary, beta, key, seed=seed + i, **settings)
        for wire, answer in its:
            for r, c in wire["query_sites"]:
                served = distribution((answer["marginals"] or {}).get(
                    f"s{r},{c}"), n_labels)
                out.append(("site", served, ref[r, c]))
    return out
