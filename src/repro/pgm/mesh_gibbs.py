"""Distributed checkerboard Gibbs: inter-core register sharing on TPU (C3).

The AIA mesh lets a core read its N/E/S/W neighbours' shared registers in
one cycle instead of bouncing through the global buffer.  The TPU-native
analogue (DESIGN.md §2): shard the MRF lattice into per-device tiles over
a 2D `("row", "col")` device mesh and exchange **one-site halos** with the
four neighbours via `jax.lax.ppermute` (nearest-neighbour ICI collective-
permute) before each checkerboard half-step.

The "global buffer" baseline the paper compares against is also provided:
`all_gather` the full label field every half-step.  Per half-step and
device, halo exchange moves `2·(ht+wt)·4B` over nearest-neighbour links,
the baseline moves `(H·W − ht·wt)·4B` through the all-gather — the
benchmark reports the measured HLO collective bytes for both (the 3×
memory-read reduction analogue of Fig. 3b).

Grids whose H or W is not a tile multiple are padded; pad sites are
pinned to label 0 by their unary term *and* masked out of their real
neighbours' pairwise sums via the validity mask that `pad_mrf` /
`shard_mrf` produce (see `pad_mrf` for why the mask is load-bearing).

Devices: this module is mesh-agnostic; tests exercise it in a subprocess
with `--xla_force_host_platform_device_count`.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.fixedpoint import DEFAULT_K
from repro.core.interp import exp_table
from repro.core.ky import ky_sample
from repro.kernels.fused_sweep import fused_gibbs_sample
from repro.pgm.graph import MRFGrid

_EXP = exp_table()


class MeshMRF(NamedTuple):
    unary: jax.Array      # (H, W, L) sharded P("row", "col", None)
    pairwise: jax.Array   # (L, L) replicated
    h: int
    w: int


def pad_mrf(
    mrf: MRFGrid, nr: int, nc: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int, int]:
    """Pad unary to tile multiples; returns (unary, pairwise, valid, H', W').

    Pad (dummy) sites are pinned to label 0 by a huge unary penalty on
    every other label, and ``valid`` — True exactly on the true H×W
    extent — masks them out of their neighbours' pairwise sums.  The
    pinning alone is NOT enough: pad sites sit adjacent to real boundary
    sites, so without the mask they inject label-0 pairwise energy into
    rows h-1 / cols w-1 and bias the marginals whenever H or W is not a
    tile multiple.
    """
    h, w = mrf.shape
    hp, wp = -h % nr, -w % nc
    unary = np.pad(mrf.unary, ((0, hp), (0, wp), (0, 0)))
    if hp or wp:
        unary[h:, :, 1:] = 1e6
        unary[:, w:, 1:] = 1e6
    valid = np.zeros((h + hp, w + wp), bool)
    valid[:h, :w] = True
    return unary, mrf.pairwise, valid, h + hp, w + wp


def _halo_exchange(tile: jax.Array, row_axis: str, col_axis: str,
                   nr: int, nc: int) -> jax.Array:
    """Collect N/S/E/W one-site halos of a (B, ht, wt) int32 tile.

    ``nr``/``nc`` are the static mesh axis sizes (the ppermute pairs need
    concrete indices).
    Returns the padded (B, ht+2, wt+2) labels; which halo entries are
    *meaningful* is the caller's precomputed validity mask's business
    (:func:`blocked_validity` covers both the global boundary and pad
    sites).
    """

    def shift(x, axis_name, n, d):
        # receive from neighbour at index (i - d); edge devices get zeros
        perm = [(i, i + d) for i in range(n) if 0 <= i + d < n]
        return jax.lax.ppermute(x, axis_name, perm)

    north = shift(tile[:, -1:, :], row_axis, nr, +1)   # north nbr's last row
    south = shift(tile[:, :1, :], row_axis, nr, -1)    # south nbr's first row
    west = shift(tile[:, :, -1:], col_axis, nc, +1)
    east = shift(tile[:, :, :1], col_axis, nc, -1)

    b, ht, wt = tile.shape
    padded = jnp.zeros((b, ht + 2, wt + 2), tile.dtype)
    padded = padded.at[:, 1:-1, 1:-1].set(tile)
    padded = padded.at[:, 0, 1:-1].set(north[:, 0])
    padded = padded.at[:, -1, 1:-1].set(south[:, 0])
    padded = padded.at[:, 1:-1, 0].set(west[:, :, 0])
    padded = padded.at[:, 1:-1, -1].set(east[:, :, 0])
    return padded


def _tile_energies(padded, valid, unary_tile, pairwise):
    """(B, ht, wt, L) candidate-label energies from padded labels."""
    pwt = pairwise.T  # pw[l, m] -> row per neighbour label m
    ht, wt = unary_tile.shape[:2]

    def contrib(sl_r, sl_c):
        nbr = padded[:, sl_r, sl_c]
        v = valid[sl_r, sl_c]
        return jnp.take(pwt, nbr, axis=0) * v[None, :, :, None]

    inner_r, inner_c = slice(1, ht + 1), slice(1, wt + 1)
    e = unary_tile[None]
    e = e + contrib(slice(0, ht), inner_c)        # north
    e = e + contrib(slice(2, ht + 2), inner_c)    # south
    e = e + contrib(inner_r, slice(0, wt))        # west
    e = e + contrib(inner_r, slice(2, wt + 2))    # east
    return e


def make_mesh_gibbs_step(
    mesh: Mesh,
    *,
    row_axis: str = "row",
    col_axis: str = "col",
    k: int = DEFAULT_K,
    use_iu: bool = True,
    sampler: str = "xla",
    comm: str = "halo",  # "halo" (C3) | "allgather" (global-buffer baseline)
    clamped: bool = False,
):
    """Build the jitted distributed full-sweep fn.

    Signature: ``(key, labels, unary, pairwise, valid) -> (labels, bits)``
    with ``valid`` the *blocked padded* validity mask from
    :func:`shard_mrf`: each device's (ht+2, wt+2) tile already combines
    the global-boundary halo ring with the true-H×W extent, precomputed
    host-side — it is static data, so it costs no per-sweep collective.
    ``bits`` is a per-device (nr, nc) int32 grid of random bits spent by
    *real* (non-pad) sites this sweep — sum it host-side in int64
    (``np.asarray(bits, np.int64).sum()``); the old cross-mesh int32
    ``psum`` silently wrapped on large grids / long accumulations.

    With ``clamped=True`` the signature grows a trailing ``clamp``
    operand — an (H', W') bool field (True = observed pixel, sharded
    like the lattice; see :func:`shard_clamp`).  Clamped sites are
    excluded from the checkerboard update and the bit accounting but,
    unlike pad sites, stay *inside* the validity mask: their fixed
    labels keep feeding pairwise energy to their neighbours, which is
    what makes this evidence conditioning rather than lattice surgery.
    """
    nr, nc = mesh.shape[row_axis], mesh.shape[col_axis]

    def body(key, labels, unary_tile, pairwise, pvalid, *rest):
        r = jax.lax.axis_index(row_axis)
        c = jax.lax.axis_index(col_axis)
        key = jax.random.fold_in(key, r * nc + c)
        b, ht, wt = labels.shape
        l = unary_tile.shape[-1]
        row0, col0 = r * ht, c * wt
        # Neighbour validity masks pad sites out of real boundary sites'
        # pairwise sums (see pad_mrf); its interior is the tile's own
        # update/stats mask.
        valid_tile = pvalid[1:-1, 1:-1]
        if clamped:
            valid_tile = valid_tile & ~rest[0]

        def gather(tile):
            """(B, ht, wt) tile -> halo-padded (B, ht+2, wt+2) labels."""
            if comm == "halo":
                return _halo_exchange(tile, row_axis, col_axis, nr, nc)
            full = jax.lax.all_gather(tile, row_axis, axis=1, tiled=True)
            full = jax.lax.all_gather(full, col_axis, axis=2, tiled=True)
            hg, wg = nr * ht, nc * wt
            padded = jnp.zeros((tile.shape[0], hg + 2, wg + 2), tile.dtype)
            padded = padded.at[:, 1:-1, 1:-1].set(full)
            return jax.lax.dynamic_slice(
                padded, (0, row0, col0), (tile.shape[0], ht + 2, wt + 2))

        def halfstep(labels, parity, subkey):
            padded = gather(labels)
            e = _tile_energies(padded, pvalid, unary_tile, pairwise)
            if sampler == "pallas":
                # negation is exact, so (-e) - max(-e) == -(e - min e):
                # the fused kernel sees the same floats as the XLA tail
                res = fused_gibbs_sample(
                    subkey, (-e).reshape((-1, l)), l, k=k, use_iu=use_iu,
                    table=_EXP)
            else:
                z = e - jnp.min(e, axis=-1, keepdims=True)
                y = _EXP(-z) if use_iu else jnp.exp(-z)
                wts = jnp.floor(y * (2.0 ** k - 1.0)).astype(jnp.int32)
                res = ky_sample(subkey, wts.reshape((-1, l)))
            new = res.sample.reshape((b, ht, wt))
            gi = row0 + jnp.arange(ht)[:, None]
            gj = col0 + jnp.arange(wt)[None, :]
            # pad sites neither update nor count toward bit accounting
            mask = (((gi + gj) % 2) == parity) & valid_tile
            return jnp.where(mask[None], new, labels), jnp.sum(
                jnp.where(mask[None], res.bits_used.reshape((b, ht, wt)), 0))

        k0, k1 = jax.random.split(key)
        labels, bits0 = halfstep(labels, 0, k0)
        labels, bits1 = halfstep(labels, 1, k1)
        # per-device int32 is tile-local and safe; the global total is the
        # caller's int64 sum of the (nr, nc) grid
        return labels, (bits0 + bits1).reshape(1, 1)

    in_specs = (P(), P(None, row_axis, col_axis),
                P(row_axis, col_axis, None), P(), P(row_axis, col_axis))
    if clamped:
        in_specs = in_specs + (P(row_axis, col_axis),)
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(None, row_axis, col_axis), P(row_axis, col_axis)),
    )
    return jax.jit(mapped)


def blocked_validity(valid: np.ndarray, nr: int, nc: int) -> np.ndarray:
    """Per-tile padded validity masks, blocked for P(row, col) sharding.

    From the (H', W') extent mask, build a (nr*(ht+2), nc*(wt+2)) array
    whose (r, c) block is tile (r, c)'s halo-padded mask: the tile's own
    sites plus its one-site neighbour ring, False outside the global
    lattice and on pad sites.  Static data — computing it here (host,
    once) keeps the per-sweep step free of a mask exchange collective.
    """
    hp, wp = valid.shape
    ht, wt = hp // nr, wp // nc
    g = np.zeros((hp + 2, wp + 2), bool)
    g[1:-1, 1:-1] = valid
    out = np.zeros((nr * (ht + 2), nc * (wt + 2)), bool)
    for r in range(nr):
        for c in range(nc):
            out[r * (ht + 2):(r + 1) * (ht + 2),
                c * (wt + 2):(c + 1) * (wt + 2)] = (
                g[r * ht:r * ht + ht + 2, c * wt:c * wt + wt + 2])
    return out


def shard_mrf(mesh: Mesh, mrf: MRFGrid, n_chains: int, key: jax.Array,
              row_axis: str = "row", col_axis: str = "col"):
    """Pad + device_put the MRF, its validity mask, and an initial label
    field onto the mesh; returns ``(labels, unary, pairwise, valid, (H', W'))``.

    ``valid`` is the blocked per-tile padded mask from
    :func:`blocked_validity` — pass it straight to the step function
    from :func:`make_mesh_gibbs_step`.
    """
    nr, nc = mesh.shape[row_axis], mesh.shape[col_axis]
    unary, pairwise, valid, hp, wp = pad_mrf(mrf, nr, nc)
    labels0 = jax.random.randint(key, (n_chains, hp, wp), 0, mrf.n_labels, jnp.int32)
    labels0 = jnp.where(jnp.asarray(valid)[None], labels0, 0)  # pin pad sites
    u = jax.device_put(jnp.asarray(unary),
                       NamedSharding(mesh, P(row_axis, col_axis, None)))
    lab = jax.device_put(labels0,
                         NamedSharding(mesh, P(None, row_axis, col_axis)))
    pw = jax.device_put(jnp.asarray(pairwise), NamedSharding(mesh, P()))
    v = jax.device_put(jnp.asarray(blocked_validity(valid, nr, nc)),
                       NamedSharding(mesh, P(row_axis, col_axis)))
    return lab, u, pw, v, (hp, wp)


def shard_clamp(mesh: Mesh, clamp: np.ndarray, values: np.ndarray,
                labels: jax.Array, row_axis: str = "row",
                col_axis: str = "col") -> tuple[jax.Array, jax.Array]:
    """Pad + place a pixel-evidence mask for the clamped mesh step.

    ``clamp``/``values`` are (H, W) over the *true* lattice; the label
    field ``labels`` is the padded (B, H', W') one from :func:`shard_mrf`.
    Returns ``(labels, clamp_dev)``: labels with every clamped site
    pinned to its observed value, and the (H', W') device mask to pass
    as the trailing operand of ``make_mesh_gibbs_step(clamped=True)``.
    Pad sites stay unclamped — the validity mask already freezes them.
    """
    b, hp, wp = labels.shape
    h, w = np.asarray(clamp).shape
    pc = np.zeros((hp, wp), bool)
    pc[:h, :w] = np.asarray(clamp, bool)
    pv = np.zeros((hp, wp), np.int32)
    pv[:h, :w] = np.where(np.asarray(clamp, bool),
                          np.asarray(values, np.int32), 0)
    labels = jnp.where(jnp.asarray(pc)[None], jnp.asarray(pv)[None], labels)
    labels = jax.device_put(
        labels, NamedSharding(mesh, P(None, row_axis, col_axis)))
    clamp_dev = jax.device_put(
        jnp.asarray(pc), NamedSharding(mesh, P(row_axis, col_axis)))
    return labels, clamp_dev
