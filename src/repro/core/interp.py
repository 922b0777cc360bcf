"""LUT-based interpolation unit (paper §II-B, C2).

The AIA IU evaluates ``exp``, ``log`` … in a single cycle by piecewise
linear interpolation on a small LUT held in registers:

    y = LUT[idx] + frac * (LUT[idx+1] - LUT[idx])

where ``idx`` is the top bits and ``frac`` the residual of a fixed-point
input.  The JAX module keeps the same structure — a 2**m-entry table that
lives in VMEM on TPU (see ``kernels/interp_lut.py``), a shift/mask index
split, and one fused multiply-add — so the cost model carries over:
one small gather + one FMA per element instead of a transcendental.

``InterpTable.build`` constructs a table for an arbitrary scalar function
over a range; pre-built tables for exp/log/sigmoid/softplus cover the
distribution-generation pipeline of Gibbs sampling (energies -> weights).

:func:`masked_exp_weights` is the shared distribution-generation tail of
every Gibbs family (label mask → max-subtract → LUT-exp → fixed-point
floor).  It is deliberately plain ``jnp`` so the fused Pallas sweep
kernel (``kernels/fused_sweep.py``) can run the *same function* inside
the kernel body — that, together with ``core/ky.py::ky_walk`` and a
shared bit stream, is what makes ``sampler="pallas"`` bitwise-identical
to the ``sampler="xla"`` path (contract spelled out in
``docs/kernels.md``).  The Pallas wrapper around the bare LUT lives in
``kernels/interp_lut.py``; both it and the fused kernel accept
``interpret=True`` to run on CPU (the CI escape hatch).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class InterpTable:
    """Piecewise-linear LUT over [lo, hi] with 2**m segments."""

    table: jax.Array      # (2**m + 1,) float32 nodes; lut_rows form in a kernel
    lo: float
    hi: float
    m: int                # log2 #segments

    @staticmethod
    def build(fn: Callable, lo: float, hi: float, m: int = 8) -> "InterpTable":
        xs = np.linspace(lo, hi, (1 << m) + 1, dtype=np.float64)
        # host array: building a table at import must not claim a device
        tab = np.asarray(fn(xs), dtype=np.float32)
        return InterpTable(table=tab, lo=float(lo), hi=float(hi), m=m)

    def rows(self) -> jax.Array:
        """The node table as :func:`lut_rows`, the form a kernel reads."""
        return lut_rows(self.table, 1 << self.m)

    def __call__(self, x: jax.Array) -> jax.Array:
        """Interpolate fn(x); inputs are clamped to [lo, hi]."""
        x = jnp.asarray(x, jnp.float32)
        n = 1 << self.m
        scale = n / (self.hi - self.lo)
        t = jnp.clip((x - self.lo) * scale, 0.0, float(n))
        idx = jnp.minimum(t.astype(jnp.int32), n - 1)  # "IU.address"
        frac = t - idx.astype(jnp.float32)             # "offset"
        if self.table.ndim == 1:   # XLA: a plain gather
            y0 = jnp.take(self.table, idx, mode="clip")
            y1 = jnp.take(self.table, idx + 1, mode="clip")
        else:                      # in a kernel: lut_rows form, lane gathers
            y0, y1 = lut_pair(self.table, idx)
        return y0 + frac * (y1 - y0)           # single FMA, as in the IU

    def max_abs_error(self, fn: Callable, probe: int = 65536) -> float:
        xs = np.linspace(self.lo, self.hi, probe).astype(np.float32)
        exact = np.asarray(fn(xs.astype(np.float64)))
        approx = np.asarray(jax.jit(self.__call__)(xs))
        return float(np.max(np.abs(exact - approx)))


LUT_LANES = 128   # one TPU vreg row: the width of an in-register gather


def lut_rows(table: jax.Array, n: int) -> jax.Array:
    """``(2, W)`` rows ``[table[0:W], table[1:W + 1]]``, W = n rounded up to 128.

    The two node reads of an interpolation, laid out so both come from
    128-aligned chunks (``table`` is the ``(n + 1,)`` node array).
    """
    width = -(-n // LUT_LANES) * LUT_LANES
    tab = jnp.pad(table, (0, width + 1 - table.shape[0]))
    return jnp.stack([tab[:width], tab[1:width + 1]])


def lut_pair(rows: jax.Array, idx: jax.Array):
    """``(table[idx], table[idx + 1])`` elementwise, from :func:`lut_rows`.

    ``idx`` is an int32 (rows, 128) tile, as in the fused sweep kernel.
    Each 128-entry chunk of the rows is broadcast over the tile, read
    with a lane gather (``take_along_axis`` on the last axis, Mosaic's
    in-vreg ``tpu.dynamic_gather``) and kept where ``idx`` falls in that
    chunk.  A table read is exact, so this returns the same floats as
    the ``jnp.take`` of the XLA path, bit for bit.
    """
    chunk = idx >> 7                        # idx // LUT_LANES
    lane = idx & (LUT_LANES - 1)
    y0 = y1 = jnp.zeros(idx.shape, rows.dtype)
    for c in range(rows.shape[-1] // LUT_LANES):
        in_c = chunk == c
        part = rows[:, c * LUT_LANES:(c + 1) * LUT_LANES]
        got0, got1 = (
            jnp.take_along_axis(jnp.broadcast_to(part[r:r + 1], idx.shape),
                                lane, axis=-1, mode="promise_in_bounds")
            for r in (0, 1))
        y0 = jnp.where(in_c, got0, y0)
        y1 = jnp.where(in_c, got1, y1)
    return y0, y1


# Pytree registration: the node values are traced data, the range/shape
# metadata is static — so an InterpTable can cross a jit boundary (e.g.
# as the `table` argument of kernels.fused_sweep.fused_gibbs_sample).
jax.tree_util.register_pytree_node(
    InterpTable,
    lambda t: ((t.table,), (t.lo, t.hi, t.m)),
    lambda aux, ch: InterpTable(table=ch[0], lo=aux[0], hi=aux[1], m=aux[2]),
)


# Pre-built tables used by the Gibbs distribution-generation stage.
# exp over negative energies: exp(x) for x in [-16, 0] covers weights down
# to ~1e-7 — below quantization resolution for k<=24.
def exp_table(m: int = 10) -> InterpTable:
    return InterpTable.build(np.exp, -16.0, 0.0, m)


def log_table(m: int = 10) -> InterpTable:
    """LUT over the mantissa range [1, 2) — see ``iu_log``."""
    return InterpTable.build(np.log, 1.0, 2.0, m)


def iu_log(x: jax.Array, table: InterpTable | None = None) -> jax.Array:
    """log(x) via mantissa/exponent split + PWL LUT (the HW-idiomatic form).

    ``x = mant * 2**e`` with ``mant in [1, 2)``; ``log x = LUT(mant) +
    e*ln2``.  Uniform relative accuracy over the full positive range,
    unlike a single uniform table near 0.
    """
    table = table or _LOG_DEFAULT
    x = jnp.asarray(x, jnp.float32)
    mant, e = jnp.frexp(jnp.clip(x, 1e-30, None))  # mant in [0.5, 1)
    return table(mant * 2.0) + (e - 1).astype(jnp.float32) * jnp.float32(np.log(2.0))


def sigmoid_table(m: int = 10) -> InterpTable:
    return InterpTable.build(lambda x: 1.0 / (1.0 + np.exp(-x)), -8.0, 8.0, m)


def softplus_table(m: int = 10) -> InterpTable:
    return InterpTable.build(lambda x: np.log1p(np.exp(x)), -8.0, 8.0, m)


def iu_exp_weights(energies: jax.Array, k: int, table: InterpTable | None = None) -> jax.Array:
    """Energies -> non-normalized KY weights through the IU (fused path).

    ``w = floor(iu_exp(e - max(e)) * (2**k - 1))`` — the AIA distribution
    generation pipeline: subtract max (no sum-normalization), LUT-exp,
    fixed-point floor.  Output feeds ``ky_sample`` directly.
    """
    table = table or _EXP_DEFAULT
    e = jnp.asarray(energies, jnp.float32)
    z = e - jnp.max(e, axis=-1, keepdims=True)
    y = table(z)
    return jnp.floor(y * (2.0 ** k - 1.0)).astype(jnp.int32)


# Labels at or beyond a lane's cardinality are masked to this log-weight
# before the max-subtract: 4x the compiler chain's per-entry CPT floor
# (pgm.compile._NEG = -60), far below any reachable real energy, and deep
# under the exp-LUT's lo clamp so the masked weight quantizes to 0 for
# every k <= 23 (exp(-16) * (2**23 - 1) < 1).
MASK_NEG = -240.0


def masked_exp_weights(
    logw: jax.Array,
    card: jax.Array,
    k: int,
    *,
    use_iu: bool = True,
    table: "InterpTable | None" = None,
    mask_value: float = MASK_NEG,
) -> jax.Array:
    """Shared Gibbs distribution-generation tail: log-weights -> KY weights.

    ``w = floor(exp(logw - max logw) * (2**k - 1))`` with labels
    ``>= card`` first masked to ``mask_value`` (they quantize to weight 0
    for ``k <= 23``), and ``exp`` evaluated through the IU LUT when
    ``use_iu``.  ``logw`` is (..., L); ``card`` broadcasts against the
    batch axes (per-node cardinalities for BN/sparse plans, a scalar L
    for dense grids; a ``(..., 1)`` column as the fused kernel passes
    it).  This exact function runs both in the XLA sampler
    path (via ``pgm.compile.ky_weights``) and *inside* the fused Pallas
    kernel, so the two are bitwise-comparable by construction.
    """
    card = jnp.asarray(card, jnp.int32)
    if card.ndim < logw.ndim:
        card = card[..., None]
    ls = jax.lax.broadcasted_iota(jnp.int32, logw.shape, logw.ndim - 1)
    logw = jnp.where(ls < card, logw, mask_value)
    z = logw - jnp.max(logw, axis=-1, keepdims=True)
    if use_iu:
        y = (table or _EXP_DEFAULT)(z)
    else:
        y = jnp.exp(z)
    return jnp.floor(y * (2.0 ** k - 1.0)).astype(jnp.int32)


_EXP_DEFAULT = exp_table()
_LOG_DEFAULT = log_table()
