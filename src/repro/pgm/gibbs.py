"""Vectorized MCMC kernels: checkerboard Gibbs for MRF grids.

Distribution generation follows the AIA pipeline end-to-end: per-site
energies (fixed function units) → max-subtracted ``exp`` through the IU
LUT (C2) → fixed-point integer weights → non-normalized Knuth-Yao sample
(C1).  No per-site normalization sum is ever computed.

The lattice analogue of a "core" here is a VPU lane: all sites of one
checkerboard color across all chains are updated in one vector op.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.fixedpoint import DEFAULT_K
from repro.core.interp import InterpTable, exp_table
from repro.core.ky import ky_sample
from repro.kernels.fused_sweep import fused_gibbs_sample
from repro.pgm.graph import MRFGrid


class SweepStats(NamedTuple):
    bits_used: jax.Array   # scalar int32: random bits consumed this sweep
    attempts: jax.Array    # scalar int32


def neighbor_pair_energy(labels: jax.Array, pairwise: jax.Array) -> jax.Array:
    """(B, H, W, L) energy of each candidate label vs the 4 neighbors.

    Edge sites see only their in-grid neighbors (free boundary).
    """
    pw = pairwise  # (L, L); pw[l, m] = energy of candidate l next to m
    e = jnp.zeros(labels.shape + (pairwise.shape[0],), jnp.float32)
    h, w = labels.shape[-2:]

    def nbr(shift, axis):
        rolled = jnp.roll(labels, shift, axis=axis)
        contrib = jnp.take(pw.T, rolled, axis=0)  # (B, H, W, L): pw[l, rolled]
        # mask out the wrapped edge
        idx = jnp.arange(labels.shape[axis])
        if shift == 1:
            valid = idx > 0
        else:
            valid = idx < labels.shape[axis] - 1
        shape = [1] * labels.ndim
        shape[axis] = labels.shape[axis]
        return contrib * valid.reshape(shape)[..., None]

    e = e + nbr(1, -2) + nbr(-1, -2) + nbr(1, -1) + nbr(-1, -1)
    return e


def _weights_from_energies(
    energies: jax.Array,
    *,
    k: int = DEFAULT_K,
    table: InterpTable | None = None,
    use_iu: bool = True,
) -> jax.Array:
    """(..., L) energies → int32 non-normalized KY weights."""
    z = energies - jnp.min(energies, axis=-1, keepdims=True)  # best label → 0
    if use_iu:
        table = table or _EXP
        y = table(-z)  # exp(-z) via the IU LUT (z >= 0, clamped at 16)
    else:
        y = jnp.exp(-z)
    return jnp.floor(y * (2.0 ** k - 1.0)).astype(jnp.int32)


def site_weights(
    labels: jax.Array,
    unary: jax.Array,
    pairwise: jax.Array,
    *,
    k: int = DEFAULT_K,
    table: InterpTable | None = None,
    use_iu: bool = True,
) -> jax.Array:
    """(B, H, W, L) int32 non-normalized KY weights for every site."""
    energies = unary[None] + neighbor_pair_energy(labels, pairwise)
    return _weights_from_energies(energies, k=k, table=table, use_iu=use_iu)


def _odd_active(h: int, parity: jax.Array) -> jax.Array:
    """(H, 1) bool: True in the rows whose sites of colour ``parity`` sit
    in the odd columns ((r + c) % 2 == parity)."""
    return (((jnp.arange(h) + parity) % 2) == 1)[:, None]


def _active_columns(h: int, w2: int, parity: jax.Array) -> jax.Array:
    """(H, W_even / 2) grid column of each packed site of colour
    ``parity``."""
    return 2 * jnp.arange(w2)[None, :] + _odd_active(h, parity)


def _split_colours(x: jax.Array, parity: jax.Array, *, w_axis: int = -1,
                  fill=0) -> tuple[jax.Array, jax.Array]:
    """(this colour, the other colour) of a grid-shaped ``x``, each with
    W_even / 2 columns: the W axis (``w_axis``, right after the H axis)
    is padded to even with ``fill``, and each pair of columns gives its
    ``parity`` site to the first array and the other site to the second.
    Only pads, strided slices and selects: no gather."""
    w_axis %= x.ndim
    pad = [(0, 0)] * x.ndim
    pad[w_axis] = (0, x.shape[w_axis] % 2)
    xp = jnp.pad(x, pad, constant_values=fill)
    even = jax.lax.slice_in_dim(xp, 0, None, 2, axis=w_axis)
    odd = jax.lax.slice_in_dim(xp, 1, None, 2, axis=w_axis)
    sel = _odd_active(x.shape[w_axis - 1], parity).reshape(
        (-1,) + (1,) * (x.ndim - w_axis))
    return jnp.where(sel, odd, even), jnp.where(sel, even, odd)


def _merge_colours(mine: jax.Array, other: jax.Array, parity: jax.Array,
                  w: int) -> jax.Array:
    """Inverse of :func:`_split_colours` on a (..., H, W_even / 2) pair:
    the (..., H, w) grid, pad column dropped."""
    sel = _odd_active(mine.shape[-2], parity)
    even = jnp.where(sel, other, mine)
    odd = jnp.where(sel, mine, other)
    # interleave by interior padding: even columns at 0, 2, ..., odd at
    # 1, 3, ..., each zero where the other sits
    keep = [(0, 0, 0)] * (mine.ndim - 1)
    zero = jnp.zeros((), mine.dtype)
    grid = (jax.lax.pad(even, zero, keep + [(0, 1, 1)])
            + jax.lax.pad(odd, zero, keep + [(1, 0, 1)]))
    return grid[..., :w]


def _colour_pair_energy(other: jax.Array, parity: jax.Array, w: int,
                       pairwise: jax.Array) -> jax.Array:
    """(B, H, W_even / 2, L) energy of each candidate label at the sites
    of colour ``parity`` against their 4 neighbours, read from the other
    colour's labels ``other`` (packed by :func:`_split_colours`).

    Every neighbour of a site is of the other colour: up and down sit in
    the same column pair, left and right in this pair or the one before
    (after) as the row's colour sits in the even (odd) columns.  Edge
    sites see only their in-grid neighbours (free boundary), and the
    pad column of an odd W is no neighbour.  The four terms are summed in
    :func:`neighbor_pair_energy`'s order, so the energies are bitwise
    those of the full grid at the same sites."""
    pw = pairwise  # (L, L); pw[l, m] = energy of candidate l next to m
    h, w2 = other.shape[-2:]
    odd = _odd_active(h, parity)
    row = jnp.arange(h)[:, None]
    col = _active_columns(h, w2, parity)

    def nbr(labels, valid):
        contrib = jnp.take(pw.T, labels, axis=0)  # pw[l, labels]
        return contrib * valid[..., None]

    e = jnp.zeros(other.shape + (pw.shape[0],), jnp.float32)
    up = nbr(jnp.roll(other, 1, axis=-2), row > 0)
    down = nbr(jnp.roll(other, -1, axis=-2), row < h - 1)
    left = nbr(jnp.where(odd, other, jnp.roll(other, 1, axis=-1)), col > 0)
    right = nbr(jnp.where(odd, jnp.roll(other, -1, axis=-1), other),
                col < w - 1)
    return e + up + down + left + right


@partial(jax.jit, static_argnames=("k", "use_iu", "sampler", "mesh"))
def checkerboard_halfstep(
    key: jax.Array,
    labels: jax.Array,          # (B, H, W) int32
    unary: jax.Array,           # (H, W, L)
    pairwise: jax.Array,        # (L, L)
    parity: jax.Array,          # scalar int32 0/1
    *,
    clamp: jax.Array | None = None,   # (H, W) or (B, H, W) bool, True = frozen
    k: int = DEFAULT_K,
    use_iu: bool = True,
    sampler: str = "xla",
    beta: jax.Array | None = None,    # traced inverse temperature, (B,) or scalar
    mesh=None,                        # serve mesh the chain axis is sharded on
) -> tuple[jax.Array, SweepStats]:
    """Resample all sites of one checkerboard color, all chains at once.

    Only that colour is walked: :func:`_split_colours` packs its sites
    into (B, H, W_even / 2) (W padded to even; the pad column never
    updates and is no neighbour), and the energies, weights, bit words,
    KY walk and bit sums all run on the packed half, which is written
    back into its own sites alone.  The sampler sees B·H·W_even / 2
    lanes, not B·H·W.

    ``clamp`` marks evidence (observed-pixel) sites: they are skipped by
    the update and by the bit accounting, but their *fixed* labels still
    sit in ``labels`` and therefore keep contributing pairwise energy to
    their neighbours — exactly CPT conditioning, lattice edition.

    ``beta`` scales the site energies (traced, never a static argument):
    weights become ``exp(-β·(e - min e))``, the simulated-annealing
    sharpening the MAP mode drives; per-lane (B,) values anneal each
    chain on its own schedule.  None / 1.0 is ordinary Gibbs.  The scale
    is applied before the sampler branch, so the XLA and Pallas paths
    stay bitwise-interchangeable at every β.

    ``sampler="pallas"`` routes the distribution-generation tail and the
    KY walk through the fused kernel (``kernels/fused_sweep.py``): the
    per-site energies become negated log-weights (negation is exact, so
    ``-(e - min e)`` and ``(-e) - max(-e)`` feed the exp LUT the same
    floats) and the result is bitwise-identical to the XLA path: both
    receive the same packed rows and the same key.
    """
    w = labels.shape[-1]
    l = unary.shape[-1]
    mine, other = _split_colours(labels, parity)
    # named scopes: the weight path (energies and, under xla, the IU-exp
    # table) and the KY walk (under pallas, the fused kernel with its
    # table) are found by name in a profile's op metadata
    with jax.named_scope("weights"):
        energies = (_split_colours(unary, parity, w_axis=1)[0][None]
                    + _colour_pair_energy(other, parity, w, pairwise))
        if beta is not None:
            bb = jnp.asarray(beta, energies.dtype)
            energies = energies * (
                bb[:, None, None, None] if bb.ndim == 1 else bb)
        if sampler == "pallas":
            logw = (-energies).reshape((-1, l))
        else:
            wts = _weights_from_energies(energies, k=k, use_iu=use_iu)
    with jax.named_scope("ky_walk"):
        if sampler == "pallas":
            res = fused_gibbs_sample(key, logw, l, k=k, use_iu=use_iu,
                                     table=_EXP, mesh=mesh)
        else:
            res = ky_sample(key, wts.reshape((-1, l)))
    # the sites that update: real columns (not the pad) that are not clamped
    mask = _active_columns(*mine.shape[-2:], parity) < w
    if clamp is not None:
        mask = mask & ~_split_colours(clamp, parity, fill=True)[0]
    new = jnp.where(mask, res.sample.reshape(mine.shape), mine)
    labels = _merge_colours(new, other, parity, w)
    zero = jnp.zeros((), jnp.int32)
    stats = SweepStats(
        bits_used=jnp.sum(jnp.where(mask, res.bits_used.reshape(mine.shape), zero)),
        attempts=jnp.sum(jnp.where(mask, res.attempts.reshape(mine.shape), zero)),
    )
    return labels, stats


@partial(jax.jit, static_argnames=("n_sweeps", "k", "use_iu", "sampler"))
def mrf_gibbs(
    key: jax.Array,
    labels0: jax.Array,
    unary: jax.Array,
    pairwise: jax.Array,
    *,
    n_sweeps: int,
    clamp: jax.Array | None = None,
    k: int = DEFAULT_K,
    use_iu: bool = True,
    sampler: str = "xla",
) -> tuple[jax.Array, SweepStats]:
    """n_sweeps full checkerboard sweeps (2 half-steps each).

    ``clamp`` ((H, W) or (B, H, W) bool) freezes evidence sites for the
    whole run — pin their labels in ``labels0`` first (see
    :func:`clamp_labels`); clamped sites never resample but stay visible
    to their neighbours' energies.
    """

    def sweep(carry, i):
        labels, key = carry
        key, k0, k1 = jax.random.split(key, 3)
        labels, s0 = checkerboard_halfstep(
            k0, labels, unary, pairwise, jnp.int32(0), clamp=clamp,
            k=k, use_iu=use_iu, sampler=sampler)
        labels, s1 = checkerboard_halfstep(
            k1, labels, unary, pairwise, jnp.int32(1), clamp=clamp,
            k=k, use_iu=use_iu, sampler=sampler)
        return (labels, key), SweepStats(
            bits_used=s0.bits_used + s1.bits_used,
            attempts=s0.attempts + s1.attempts,
        )

    (labels, _), stats = jax.lax.scan(
        sweep, (labels0, key), jnp.arange(n_sweeps))
    return labels, SweepStats(
        bits_used=jnp.sum(stats.bits_used), attempts=jnp.sum(stats.attempts))


def clamp_labels(labels: jax.Array, clamp: jax.Array,
                 values: jax.Array) -> jax.Array:
    """Pin clamped sites of a (B, H, W) label field to their observed
    values ((H, W) or (B, H, W)); the companion of ``mrf_gibbs(clamp=)``."""
    clamp = jnp.asarray(clamp, bool)
    values = jnp.asarray(values, labels.dtype)
    if clamp.ndim == 2:
        clamp = clamp[None]
    if values.ndim == 2:
        values = values[None]
    return jnp.where(clamp, values, labels)


def init_labels(key: jax.Array, mrf: MRFGrid, n_chains: int) -> jax.Array:
    h, w = mrf.shape
    return jax.random.randint(key, (n_chains, h, w), 0, mrf.n_labels, jnp.int32)


_EXP = exp_table()
