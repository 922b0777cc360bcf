"""Random-bit streams for the Knuth-Yao sampler.

The AIA SoC feeds its sampler units from LFSRs — a free-running stream of
single bits.  On TPU the idiomatic equivalent is a counter-based PRNG
(threefry via ``jax.random``): we pre-generate a budget of uint32 words
per sampler lane and index single bits out of them with shift/mask, which
is exactly the bit-plane access pattern the VPU is good at.

A software LFSR (Fibonacci x^32+x^22+x^2+x+1) is also provided, both as a
reference for the hardware behaviour and for bit-exact reproduction tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bit_budget_words(max_bits: int) -> int:
    """uint32 words needed to hold ``max_bits`` bits per lane."""
    return (max_bits + 31) // 32


def random_bit_words(key: jax.Array, shape: tuple, max_bits: int) -> jax.Array:
    """(*, words) uint32 random words supplying ``max_bits`` bits per lane."""
    words = bit_budget_words(max_bits)
    return jax.random.bits(key, shape + (words,), dtype=jnp.uint32)


def get_bit(words: jax.Array, idx: jax.Array) -> jax.Array:
    """Extract bit ``idx`` (0-based) from the per-lane word stream.

    ``words``: (..., W) uint32;  ``idx``: int32, one cursor per lane,
    shaped (...,) or (..., 1).  Returns int32 in {0, 1} shaped like
    ``idx``.  The bit is taken from every word and the cursor's word kept
    by a one-hot select and a sum over the W axis, not a gather, so the
    same code lowers inside a Mosaic kernel (where a per-lane gather
    along the word axis does not) and in XLA.
    """
    keep = idx.ndim == words.ndim
    cur = idx if keep else idx[..., None]
    bits = ((words >> (cur & 31).astype(jnp.uint32)) & jnp.uint32(1)
            ).astype(jnp.int32)
    word = jax.lax.broadcasted_iota(jnp.int32, words.shape, words.ndim - 1)
    return jnp.sum(jnp.where(word == cur >> 5, bits, 0), axis=-1,
                   keepdims=keep)


# ----------------------------------------------------------------------------
# Reference LFSR (matches a 32-bit Fibonacci LFSR; taps 32,22,2,1)
# ----------------------------------------------------------------------------
_LFSR_TAPS = (31, 21, 1, 0)  # 0-based bit positions of taps


def lfsr_step(state: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One LFSR step. Returns (new_state, output_bit). state: uint32 != 0."""
    state = jnp.asarray(state, jnp.uint32)
    fb = jnp.zeros_like(state)
    for t in _LFSR_TAPS:
        fb = fb ^ ((state >> jnp.uint32(t)) & jnp.uint32(1))
    new = (state >> jnp.uint32(1)) | (fb << jnp.uint32(31))
    return new, (state & jnp.uint32(1)).astype(jnp.int32)


def lfsr_bits(seed: int, n: int) -> jax.Array:
    """n LFSR output bits from a scalar seed (reference implementation)."""

    def body(state, _):
        state, bit = lfsr_step(state)
        return state, bit

    seed = jnp.uint32(seed if seed != 0 else 0xDEADBEEF)
    _, bits = jax.lax.scan(body, seed, None, length=n)
    return bits
