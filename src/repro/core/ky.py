"""Non-normalized Knuth-Yao sampling with rejection (paper §II-B, C1).

The sampler draws exact samples from *integer, non-normalized* weight
vectors ``w`` (shape ``(..., n)``) by walking the Knuth-Yao discrete
distribution generating (DDG) tree with single random bits.  The pad mass
``r = 2**K - sum(w)`` (with ``K = ceil(log2(sum(w)))``) is treated as an
implicit rejection outcome: reaching it restarts the walk, exactly as in
the AIA sampler unit and FLDR [Saad et al. 2020].  Because
``2**(K-1) < sum(w) <= 2**K``, the rejection probability is < 1/2 and the
expected number of restarts is < 2.

TPU adaptation (see DESIGN.md §2): instead of one branchy scalar walk per
sample, a whole batch of lanes walks DDG *levels* in lock-step inside a
``lax.while_loop``.  Per level the bit-plane column of the weight matrix
is extracted with shift/mask (the vector-register analogue of the AIA
register file's column-wise read port), a cumulative sum over outcomes
locates the leaf, and rejected lanes restart in place while finished
lanes idle.  The walk is short — ≈ entropy + 2 levels — so lock-step
masking wastes little work.

The expected number of random bits consumed per sample is ≈ H(p) + 2
(the paper's headline efficiency metric); ``KYResult.bits_used`` exposes
the exact per-lane count.

Bit-stream contract (see ``docs/kernels.md``): this module uses a
**per-lane bit cursor** — lane ``i`` reads bit ``t_i`` of its own uint32
word row, and ``t_i`` advances only while lane ``i`` is still walking.
:func:`ky_walk` is the cursor-and-walk core, and :func:`ky_walk_tile`
the same body on (b, 1) columns, so the fused Pallas sweep kernel
(``kernels/fused_sweep.py``) runs the *identical* walk on the identical
pre-generated words — which is what makes the
engine's ``sampler="pallas"`` path bitwise-interchangeable with
``sampler="xla"``.  The standalone KY kernel/oracle pair
(``kernels/ky_sampler.py`` / ``kernels/ref.py::ky_ref``) instead shares a
**global** bit cursor (every lane reads bit ``it`` of its own stream at
loop iteration ``it``); the two disciplines consume different bit
positions and are *not* bit-comparable with each other.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import rng as rng_lib
from repro.core.fixedpoint import ceil_log2


class KYResult(NamedTuple):
    sample: jax.Array      # (...,) int32 outcome indices
    bits_used: jax.Array   # (...,) int32 random bits consumed
    attempts: jax.Array    # (...,) int32 DDG walks started (>=1)
    ok: jax.Array          # (...,) bool: terminated within budget


def max_levels(k: int, n: int) -> int:
    """Upper bound on DDG depth for n outcomes of k-bit weights."""
    return int(k + max(int(jnp.ceil(jnp.log2(max(n, 2)))), 1) + 1)


def ky_walk(flat: jax.Array, bit_words: jax.Array) -> KYResult:
    """Lock-step DDG walk over pre-generated per-lane bit streams.

    Args:
      flat: (b, n) non-negative int32 weight rows.
      bit_words: (b, W) uint32; lane ``i`` consumes bits of row ``i``
        under the per-lane cursor (bit ``t_i``, advanced only while the
        lane is active).  The walk budget is ``W * 32`` bits per lane.

    This is the sampling core behind :func:`ky_sample`.  Returns a
    :class:`KYResult` with (b,) fields.  :func:`ky_walk_tile` runs the
    same body with every per-lane value a (b, 1) column, the layout the
    fused Pallas sweep kernel (``kernels/fused_sweep.py``) needs inside
    its body; the two consume identical bit positions and return
    bitwise-identical results for identical inputs.
    """
    r = _walk(flat, bit_words, tile=False)
    return r._replace(ok=r.ok > 0)


def ky_walk_tile(flat: jax.Array, bit_words: jax.Array) -> KYResult:
    """:func:`ky_walk` on a (b, n) tile, every per-lane value a (b, 1) column.

    The form the Mosaic TPU compiler lowers: 2-D loop carries, an int32
    ``ok`` (1 = done), and the inclusive prefix sum over outcomes as an
    f32 matmul with an upper triangle of ones (exact: the summands are
    0/1 bits and n < 2**24).
    """
    return _walk(flat, bit_words, tile=True)


def _walk(flat: jax.Array, bit_words: jax.Array, *, tile: bool) -> KYResult:
    """The walk of :func:`ky_walk` / :func:`ky_walk_tile`.

    Per-lane values are (b, 1) columns when ``tile`` and (b,) vectors
    otherwise: XLA pads a (b, 1) int32 array to 128 lanes on the TPU, so
    the XLA path keeps its loop carries 1-D.  Every step is integer
    arithmetic or an exact prefix sum, so both layouts give the same bits.
    """
    flat = jnp.asarray(flat, jnp.int32)
    b, n = flat.shape
    budget = int(bit_words.shape[-1]) * 32
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, n), 1)

    def col(v):      # a per-lane value against the (b, n) tile
        return v if tile else v[:, None]

    def lane_sum(x):
        return jnp.sum(x, axis=-1, keepdims=tile)

    def first_index(mask):   # first True along the outcomes; n where none
        return jnp.min(jnp.where(mask, iota, n), axis=-1, keepdims=tile)

    def prefix_sum(x):
        if not tile:
            return jnp.cumsum(x, axis=-1)
        upper = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
                 <= jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
        return jnp.dot(x.astype(jnp.float32), upper.astype(jnp.float32),
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    total = lane_sum(flat)
    # Defensive: an all-zero row would hang the walk; force outcome 0.
    flat = jnp.where((col(total) == 0) & (iota == 0), 1, flat)
    total = jnp.maximum(total, 1)

    k_lvl = jnp.maximum(ceil_log2(total), 1)      # per-lane K (>=1)
    reject_w = (jnp.int32(1) << k_lvl) - total    # pad mass (may be 0)

    def cond(state):
        done, _, _, _, t, _ = state
        return ((jnp.min(done) == 0)
                & (jnp.max(jnp.where(done > 0, 0, t)) < budget - 1))

    def body(state):
        done, d, c, res, t, att = state
        active = done == 0
        bit = rng_lib.get_bit(bit_words, jnp.minimum(t, budget - 1))
        d2 = 2 * d + (1 - bit)
        # Bit-plane column at level c: MSB-first bit of each weight.
        shift = k_lvl - 1 - c
        safe = jnp.maximum(shift, 0)
        plane = jnp.where(col(shift) >= 0, (flat >> col(safe)) & 1, 0)
        rcol = jnp.where(shift >= 0, (reject_w >> safe) & 1, 0)
        cum = prefix_sum(plane)
        colsum = lane_sum(plane) + rcol
        hit = d2 < colsum
        # first index with cum == d2+1; if none (leaf is the rejection pad),
        # sel lands past the real outcomes.
        sel = first_index(cum >= col(d2) + 1)
        is_real = hit & (sel < n)
        is_rej = hit & ~is_real
        # level overflow can't occur with exact pad mass, but guard anyway
        overflow = (~hit) & (c + 1 >= k_lvl)
        restart = (is_rej | overflow) & active
        finish = is_real & active
        done2 = jnp.where(finish, 1, done)
        res2 = jnp.where(finish, sel, res)
        d3 = jnp.where(restart, 0, jnp.where(hit, d, d2 - colsum))
        c2 = jnp.where(restart, 0, jnp.where(hit, c, c + 1))
        t2 = t + active.astype(jnp.int32)
        att2 = att + restart.astype(jnp.int32)
        return done2, d3, c2, res2, t2, att2

    # Degenerate rows where one outcome carries the whole mass are
    # deterministic: p = total/2^K = 1.0 has no fractional DDG expansion
    # (hypothesis-found corner, e.g. w = [0, 2]); resolve them up front
    # with zero random bits, exactly like the hardware's bypass path.
    top = jnp.max(flat, axis=-1, keepdims=tile)
    argmax0 = first_index(flat == col(top))
    deterministic = (top == total).astype(jnp.int32)

    # Derive the carry init from the inputs so it inherits any varying
    # manual axes when called inside shard_map (JAX >= 0.7 VMA rules).
    lane = slice(0, 1) if tile else 0
    zeros = flat[:, lane] * 0 + (bit_words[:, lane] * 0).astype(jnp.int32)
    state = (zeros + deterministic, zeros, zeros,
             jnp.where(deterministic > 0, argmax0, 0), zeros, zeros + 1)
    done, _, _, res, t, att = jax.lax.while_loop(cond, body, state)
    # Fallback for (astronomically unlikely) budget exhaustion.
    res = jnp.where(done > 0, res, argmax0)
    return KYResult(sample=res, bits_used=t, attempts=att, ok=done)


def ky_sample(
    key: jax.Array,
    weights: jax.Array,
    *,
    max_attempts: int = 32,
    bit_words: jax.Array | None = None,
) -> KYResult:
    """Draw one exact sample per lane from non-normalized int32 weights.

    Args:
      key: PRNG key (ignored if ``bit_words`` given).
      weights: (..., n) non-negative int32; rows must not be all-zero.
      max_attempts: restart budget; non-terminating lanes (prob < 2**-32)
        fall back to argmax and are flagged ``ok=False``.
      bit_words: optional pre-generated (..., W) uint32 bit stream — used
        by tests for bit-exact comparison with the reference/LFSR path.

    Returns KYResult with ``sample`` shaped like ``weights[..., 0]``.
    The bit stream is read with the per-lane cursor of :func:`ky_walk`;
    ``kernels/fused_sweep.py`` draws from the same stream for the same
    ``key``, which is what the engine's ``sampler=`` flag relies on.
    """
    w = jnp.asarray(weights, jnp.int32)
    batch_shape = w.shape[:-1]
    n = w.shape[-1]
    flat = w.reshape((-1, n))
    b = flat.shape[0]

    k_static = 31  # static per-attempt level cap (int32 weights)
    if bit_words is None:
        bit_words = rng_lib.random_bit_words(key, (b,), k_static * max_attempts)
    else:
        bit_words = bit_words.reshape((b, -1))

    r = ky_walk(flat, bit_words)
    return KYResult(
        sample=r.sample.reshape(batch_shape),
        bits_used=r.bits_used.reshape(batch_shape),
        attempts=r.attempts.reshape(batch_shape),
        ok=r.ok.reshape(batch_shape),
    )


def ky_sample_ref(weights, bits) -> tuple[int, int]:
    """Pure-Python single-lane reference (mirrors the AIA SU microcode).

    ``weights``: list[int]; ``bits``: iterable of 0/1.  Returns
    (outcome, bits_consumed).  Used as the oracle in bit-exact tests.
    """
    import math

    w = list(int(x) for x in weights)
    total = sum(w)
    assert total > 0
    if max(w) == total:  # deterministic-row bypass (p = 1.0, no DDG walk)
        return w.index(max(w)), 0
    k = max(1, math.ceil(math.log2(total))) if total > 1 else 1
    if (1 << k) < total:
        k += 1
    rej = (1 << k) - total
    wall = w + [rej]
    it = iter(bits)
    used = 0
    d = 0
    c = 0
    while True:
        b = next(it)
        used += 1
        d = 2 * d + (1 - int(b))
        col = [(x >> (k - 1 - c)) & 1 if k - 1 - c >= 0 else 0 for x in wall]
        s = 0
        hit = -1
        for i, bit_i in enumerate(col):
            s += bit_i
            if s == d + 1 and hit < 0:
                hit = i
        if d < s:
            if hit < len(w):
                return hit, used
            d = 0
            c = 0  # rejection: restart
            continue
        d -= s
        c += 1
