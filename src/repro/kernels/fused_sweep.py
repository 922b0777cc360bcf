"""Fused Pallas sweep kernel: the Gibbs sampling hot path in one kernel.

The paper's 1277 MSample/s headline comes from fusing the per-site
update — distribution generation through the IU and non-normalized KY
sampling — into one unit that keeps the distribution resident in the AC
register file.  This kernel is the software analogue: for each color
(BN / sparse factor graph) or checkerboard phase (MRF) the XLA-side plan
gather produces a (lanes, L) log-weight tile, and everything downstream
runs inside a single ``pallas_call`` with the tile resident in VMEM
end-to-end:

    label mask → max-subtract → IU-exp LUT interpolation
    → fixed-point floor (k-bit int32 weights) → per-lane KY DDG walk

No intermediate (weights, klvl, rej) tensors ever round-trip through HBM
— the fusion the ``sampler="pallas"`` engine flag buys.

Bitwise contract (docs/kernels.md): the kernel body calls the *same*
functions the XLA path uses — ``core.interp.masked_exp_weights`` for the
distribution-generation tail and ``core.ky.ky_walk_tile``, the body of
``core.ky.ky_walk`` on (b, 1) columns, for the DDG walk —
on bit words pre-generated outside the kernel by the same
``core.rng.random_bit_words(key, (b,), 992)`` call that
``core.ky.ky_sample`` makes internally.  ``sampler="pallas"`` is
therefore bitwise-identical to ``sampler="xla"`` (same samples, same
bits_used, same attempts) by construction, for every family.  The bit
stream uses the per-lane cursor of ``core/ky.py``; the standalone
``kernels/ky_sampler.py`` / ``ref.py::ky_ref`` pair instead shares a
global bit cursor and is *not* bit-comparable with this kernel.

Two deliberate deviations from perfect equivalence, both unreachable in
practice (asserted or noted):

* masked / lane-padding labels must quantize to weight 0, which holds
  for ``k <= 23`` (``exp(-16) * (2**23 - 1) < 1``); the wrapper rejects
  larger ``k``.
* the while-loop early-exit is per block rather than per batch, which
  can only diverge if some lane exhausts its 992-bit budget
  (probability < 2**-496).

``interpret=True`` (the default on non-TPU backends) runs the kernel
through the Pallas interpreter — the CPU/CI escape hatch shared with
``kernels/interp_lut.py`` and ``kernels/ky_sampler.py``.  On a TPU the
kernel is always compiled; ``tests/test_tpu_compile.py`` compiles it
for a described v5e at the Penguin and Art widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import interp as interp_lib
from repro.core import rng as rng_lib
from repro.core.ky import KYResult, ky_walk, ky_walk_tile

# masked-label log-weight floor — see core.interp.MASK_NEG
MASK_NEG = interp_lib.MASK_NEG

# largest fixed-point width for which masked labels quantize to weight 0:
# floor(exp(lo) * (2**k - 1)) == 0 with the exp LUT's lo = -16
MAX_FUSED_K = 23


def _resolve_interpret(interpret: bool | None) -> bool:
    """Default to the interpreter off-TPU (the CPU test path), compiled on TPU.

    On a TPU the kernel is always compiled: a kernel Mosaic refuses
    raises there, it never falls back to the interpreter.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _fused_kernel(logw_ref, card_ref, words_ref, tab_ref,
                  s_ref, bits_ref, att_ref, ok_ref,
                  *, k: int, use_iu: bool, lo: float, hi: float, m: int,
                  mask_value: float):
    """One (block_b, n_pad) tile: weights never leave VMEM.

    The body is just the two shared helpers — ``masked_exp_weights``
    builds the int32 weight tile in registers/VMEM, ``ky_walk_tile``
    samples from it in place.  The LUT block is pinned (index map
    (0, 0)), the analogue of the IU's dedicated table registers; it
    arrives already in ``lut_rows`` form.
    """
    table = interp_lib.InterpTable(table=tab_ref[...], lo=lo, hi=hi, m=m)
    w = interp_lib.masked_exp_weights(
        logw_ref[...], card_ref[...], k,
        use_iu=use_iu, table=table, mask_value=mask_value)
    r = ky_walk_tile(w, words_ref[...])
    s_ref[...] = r.sample
    bits_ref[...] = r.bits_used
    att_ref[...] = r.attempts
    ok_ref[...] = r.ok


def _pad_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@functools.partial(
    jax.jit,
    static_argnames=("k", "use_iu", "mask_value", "max_attempts", "block_b",
                     "interpret", "mesh"))
def fused_gibbs_sample(
    key: jax.Array,
    logw: jax.Array,        # (b, n) float32 gathered log-weights
    card: jax.Array,        # (b,) int32 per-lane cardinality (or scalar)
    *,
    k: int,
    use_iu: bool = True,
    table: interp_lib.InterpTable | None = None,
    mask_value: float = MASK_NEG,
    max_attempts: int = 32,
    block_b: int = 256,
    interpret: bool | None = None,
    mesh: Mesh | None = None,
) -> KYResult:
    """Fused distribution-generation + KY sampling, one lane per row.

    Drop-in replacement for the two-stage XLA path

        ``ky_sample(key, masked_exp_weights(logw, card, k, ...))``

    with identical results bit for bit (same ``key`` ⇒ same sample,
    bits_used, attempts, ok) — the invariant the round-runner bitwise
    tests pin.  Returns a :class:`KYResult` with (b,) fields.

    ``interpret=None`` auto-selects the Pallas interpreter off-TPU.

    ``mesh``: the lanes are sharded over the mesh's first axis (the serve
    mesh's "batch") and each device runs the kernel on its own lanes —
    XLA cannot partition a Mosaic kernel itself.  Every lane's walk reads
    only its own bit words, so the result is the same bit for bit.
    """
    if k > MAX_FUSED_K:
        raise ValueError(
            f"fused sampler requires k <= {MAX_FUSED_K} so masked labels "
            f"quantize to weight 0 (got k={k})")
    logw = jnp.asarray(logw, jnp.float32)
    b, n = logw.shape
    card = jnp.broadcast_to(jnp.asarray(card, jnp.int32), (b,))
    table = table or interp_lib._EXP_DEFAULT

    # Bit words are generated OUTSIDE the kernel at the true lane count —
    # the exact stream ky_sample(key, ...) would draw.  Under JAX's
    # partitionable threefry each word depends only on its (lane, word)
    # position, so the padded count would give the same first b rows; the
    # true count just draws fewer words.  Padding lanes get zero words;
    # they are deterministic rows and never read a bit.
    words = rng_lib.random_bit_words(key, (b,), 31 * max_attempts)
    call = functools.partial(
        _fused_call, k=k, use_iu=use_iu, lo=table.lo, hi=table.hi,
        m=table.m, mask_value=float(mask_value), block_b=block_b,
        interpret=_resolve_interpret(interpret))
    args = (logw, card, words, table.rows())
    if mesh is None:
        out = call(*args)
    else:
        lanes = P(mesh.axis_names[0])
        out = jax.shard_map(
            call, mesh=mesh, in_specs=(lanes, lanes, lanes, P()),
            out_specs=(lanes,) * 4, check_vma=False)(*args)
    s, bits, att, ok = out
    return KYResult(sample=s, bits_used=bits, attempts=att, ok=ok > 0)


def _fused_call(logw, card, words, rows, *, k, use_iu, lo, hi, m,
                mask_value, block_b, interpret):
    """Pad ``b`` lanes to whole blocks, run the kernel, slice back."""
    b, n = logw.shape
    block_b = max(8, int(block_b))
    b_pad = _pad_up(b, block_b)
    n_pad = _pad_up(n, 128)             # VPU lane width
    logw_p = jnp.pad(logw, ((0, b_pad - b), (0, n_pad - n)),
                     constant_values=mask_value)
    if b_pad > b:
        # padding lanes: all mass on outcome 0 -> deterministic bypass,
        # zero bits consumed, no effect on the block's while_loop trips
        logw_p = logw_p.at[b:, 0].set(0.0)
    card_p = jnp.pad(card, (0, b_pad - b), constant_values=1)[:, None]
    words_p = jnp.pad(words, ((0, b_pad - b), (0, 0)))

    n_words = int(words.shape[-1])
    kernel = functools.partial(
        _fused_kernel, k=k, use_iu=use_iu, lo=lo, hi=hi, m=m,
        mask_value=mask_value)
    block = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b_pad // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, n_words), lambda i: (i, 0)),
            pl.BlockSpec(rows.shape, lambda i: (0, 0)),
        ],
        out_specs=[block, block, block, block],
        out_shape=[jax.ShapeDtypeStruct((b_pad, 1), jnp.int32)] * 4,
        interpret=interpret,
    )(logw_p, card_p, words_p, rows)
    return tuple(o[:b, 0] for o in out)


def fused_gibbs_sample_ref(
    key: jax.Array,
    logw: jax.Array,
    card: jax.Array,
    *,
    k: int,
    use_iu: bool = True,
    table: interp_lib.InterpTable | None = None,
    mask_value: float = MASK_NEG,
    max_attempts: int = 32,
) -> KYResult:
    """Pure-XLA twin of :func:`fused_gibbs_sample` (no ``pallas_call``).

    Runs the identical shared helpers on the unpadded arrays — the
    three-way anchor of the bitwise tests: kernel ≡ this ref ≡ the
    engine's two-stage ``sampler="xla"`` path.
    """
    logw = jnp.asarray(logw, jnp.float32)
    b = logw.shape[0]
    card = jnp.broadcast_to(jnp.asarray(card, jnp.int32), (b,))
    w = interp_lib.masked_exp_weights(
        logw, card, k, use_iu=use_iu, table=table, mask_value=mask_value)
    words = rng_lib.random_bit_words(key, (b,), 31 * max_attempts)
    return ky_walk(w, words)
