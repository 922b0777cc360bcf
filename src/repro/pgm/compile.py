"""The AIA compiler chain for Bayesian networks (paper §III, C4).

Pipeline (mirrors Fig. 5):

  BayesNet (PPL IR) → fixed-point CPT quantization → moralize + DSatur
  coloring → per-color *gather plans* (static index/stride tensors) →
  jitted sweep program.

A gather plan is the TPU analogue of AIA's per-core binaries: for every
node of a color it precomputes, at compile time, the flat-CPT offsets and
strides needed to evaluate the Gibbs conditional

    P(v=l | MB) ∝ CPT_v[pa(v), l] · Π_{c ∈ ch(v)} CPT_c[pa(c)|v=l, x_c]

so the runtime inner loop is pure vector gathers + adds over the log-CPT
bank, followed by the IU-exp → KY-sample pipeline.  All nodes of a color
update in parallel (vector lanes ≙ AIA cores), chains batch on top.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fixedpoint import DEFAULT_K
from repro.core.interp import exp_table, masked_exp_weights
from repro.core.ky import ky_sample
from repro.kernels.fused_sweep import fused_gibbs_sample
from repro.pgm.coloring import color_bayesnet
from repro.pgm.graph import BayesNet

_NEG = -60.0  # log-domain floor (exp() underflows the k<=24 grid anyway)


@dataclass(frozen=True, eq=False)
class ColorPlan:
    """Static gather plan for one color group (all arrays np.int32)."""

    nodes: np.ndarray            # (G,) node ids
    card: np.ndarray             # (G,)
    self_base_off: np.ndarray    # (G,) CPT offset of node's own table
    self_pa: np.ndarray          # (G, P) parent ids (pad: 0)
    self_pa_stride: np.ndarray   # (G, P) strides    (pad: 0)
    ch_off: np.ndarray           # (G, C) child CPT offsets (pad: sentinel)
    ch_vstride: np.ndarray       # (G, C) stride of v in child's CPT (pad: 0)
    ch_self: np.ndarray          # (G, C) child ids (pad: 0)
    ch_self_stride: np.ndarray   # (G, C) stride of child's own dim (pad: 0)
    ch_pa: np.ndarray            # (G, C, P) other-parent ids (pad: 0)
    ch_pa_stride: np.ndarray     # (G, C, P) strides (pad: 0)


@dataclass(frozen=True, eq=False)
class CompiledBN:
    """Output of the compiler chain; consumed by ``make_sweep``.

    ``observed`` lists evidence-clamped node ids (the *evidence pattern*):
    those nodes appear in no gather plan, so a sweep never resamples them —
    their values are read straight out of the state vector by their
    children's gathers, which is exactly CPT conditioning on the clamp.
    One compiled program therefore serves *any* evidence values over the
    same pattern, which is what makes plan caching by pattern sound.
    """

    bn: BayesNet
    log_cpt: np.ndarray          # flat log-CPT bank (+ sentinel 0.0 at end)
    plans: tuple[ColorPlan, ...]
    max_card: int
    k: int                       # fixed-point weight precision
    observed: tuple[int, ...] = ()

    @property
    def n_colors(self) -> int:
        return len(self.plans)

    @property
    def free_nodes(self) -> tuple[int, ...]:
        obs = set(self.observed)
        return tuple(v for v in range(self.bn.n_nodes) if v not in obs)


def compile_bayesnet(
    bn: BayesNet,
    *,
    k: int = DEFAULT_K,
    quantize_cpt_bits: int | None = 16,
    observed=(),
) -> CompiledBN:
    """Run the full compiler chain on a BayesNet.

    ``observed``: evidence pattern — node ids (or names) to clamp.  Values
    are supplied at run time (``run_gibbs(evidence=...)`` or per-lane via
    the serve engine), so the compiled program is reusable across queries
    sharing the pattern.
    """
    observed = tuple(sorted({bn.index(v) for v in observed}))
    if len(observed) == bn.n_nodes:
        raise ValueError("all nodes observed — nothing to infer")
    # ---- stage 1: fixed-point quantization of the log-CPT bank ----------
    banks, offsets = [], {}
    pos = 0
    for v in range(bn.n_nodes):
        t = np.log(np.clip(bn.cpt[v].astype(np.float64), 1e-26, None))
        banks.append(np.maximum(t, _NEG).ravel())
        offsets[v] = pos
        pos += banks[-1].size
    flat = np.concatenate(banks + [np.zeros(1)])  # sentinel 0.0 at index pos
    sentinel = pos
    if quantize_cpt_bits is not None:
        # Qm.f fixed point over [_NEG, 0]: simulate by grid rounding.
        scale = (2 ** (quantize_cpt_bits - 7))  # ~7 integer bits for [-60,0]
        flat = np.round(flat * scale) / scale
    flat = flat.astype(np.float32)

    # ---- stage 2: coloring (moralize + DSatur), evidence nodes skipped ---
    groups = color_bayesnet(bn, skip=frozenset(observed))

    # ---- stage 3: gather plans -------------------------------------------
    def strides(v: int) -> np.ndarray:
        shape = bn.cpt[v].shape
        return np.array(
            [int(np.prod(shape[i + 1:])) for i in range(len(shape))], np.int64
        )

    max_pa = max((len(p) for p in bn.parents), default=0)
    max_ch = max((len(bn.children(v)) for v in range(bn.n_nodes)), default=0)
    p_pad, c_pad = max(max_pa, 1), max(max_ch, 1)

    plans = []
    for grp in groups:
        g = len(grp)
        plan = dict(
            nodes=np.asarray(grp, np.int32),
            card=np.array([bn.card[v] for v in grp], np.int32),
            self_base_off=np.array([offsets[v] for v in grp], np.int32),
            self_pa=np.zeros((g, p_pad), np.int32),
            self_pa_stride=np.zeros((g, p_pad), np.int32),
            ch_off=np.full((g, c_pad), sentinel, np.int32),
            ch_vstride=np.zeros((g, c_pad), np.int32),
            ch_self=np.zeros((g, c_pad), np.int32),
            ch_self_stride=np.zeros((g, c_pad), np.int32),
            ch_pa=np.zeros((g, c_pad, p_pad), np.int32),
            ch_pa_stride=np.zeros((g, c_pad, p_pad), np.int32),
        )
        for gi, v in enumerate(grp):
            v = int(v)
            st_v = strides(v)
            for j, p in enumerate(bn.parents[v]):
                plan["self_pa"][gi, j] = p
                plan["self_pa_stride"][gi, j] = st_v[j]
            for ci, c in enumerate(bn.children(v)):
                st_c = strides(c)
                plan["ch_off"][gi, ci] = offsets[c]
                plan["ch_self"][gi, ci] = c
                plan["ch_self_stride"][gi, ci] = st_c[-1]  # == 1
                for j, p in enumerate(bn.parents[c]):
                    if p == v:
                        plan["ch_vstride"][gi, ci] = st_c[j]
                    else:
                        # pack into the next free other-parent slot
                        slot = next(
                            s for s in range(p_pad)
                            if plan["ch_pa_stride"][gi, ci, s] == 0
                            and (plan["ch_pa"][gi, ci, s] == 0)
                        )
                        plan["ch_pa"][gi, ci, slot] = p
                        plan["ch_pa_stride"][gi, ci, slot] = st_c[j]
        plans.append(ColorPlan(**plan))

    return CompiledBN(
        bn=bn,
        log_cpt=flat,
        plans=tuple(plans),
        max_card=int(max(bn.card)),
        k=k,
        observed=observed,
    )


class BNSweepStats(NamedTuple):
    """Random-bit accounting of a sweep program.

    Device code only ever holds *per-sweep* int32 values (a single sweep
    cannot overflow int32 for any realistic lane count); totals across
    sweeps are accumulated host-side in int64 via :func:`sum_sweep_stats`
    — int32 carries silently wrapped on long runs, yielding negative
    bits-per-sample in benchmarks.
    """

    bits_used: jax.Array
    attempts: jax.Array


def sum_sweep_stats(stats: "BNSweepStats") -> "BNSweepStats":
    """Overflow-safe host-side total of per-sweep stats arrays.

    Sums in np.int64, so totals beyond 2**31 (trivially reached by
    lanes × nodes × sweeps × ~5 bits on long runs) stay exact.
    """
    return BNSweepStats(
        bits_used=np.asarray(stats.bits_used, np.int64).sum(),
        attempts=np.asarray(stats.attempts, np.int64).sum(),
    )


def ky_weights(logw: jax.Array, card: jax.Array, k: int,
               use_iu: bool) -> jax.Array:
    """Shared sampler tail: masked log-weights → int32 KY weights.

    ``logw``: (..., G, L) unnormalized log-probabilities; ``card``: (G,)
    per-variable cardinalities (labels past them are floored to an
    impossible weight).  This is the IU-exp → fixed-point stage every
    compiled family (BN gather plans, dense grids lowered to sparse
    plans, arbitrary factor graphs) funnels through — max-subtract,
    LUT exp, ``floor(y * (2^k - 1))`` — so the KY front-end sees one
    weight format regardless of how the energies were gathered.

    Thin wrapper over :func:`repro.core.interp.masked_exp_weights` — the
    same function the fused Pallas kernel runs *inside* its kernel body,
    which is what keeps ``sampler="pallas"`` bitwise-comparable.
    """
    return masked_exp_weights(logw, card, k, use_iu=use_iu, table=_EXP,
                              mask_value=_NEG * 4)


def _color_update(
    key: jax.Array,
    x: jax.Array,               # (B, n) int32 current states
    plan: ColorPlan,
    log_cpt: jax.Array,
    max_card: int,
    k: int,
    use_iu: bool,
    sampler: str = "xla",
    beta: jax.Array | None = None,   # traced inverse temperature, (B,) or scalar
    mesh=None,                       # serve mesh the lane axis is sharded on
) -> tuple[jax.Array, BNSweepStats]:
    with jax.named_scope("weights"):
        logw, card = _color_logw(x, plan, log_cpt, max_card, beta)
    nodes = jnp.asarray(plan.nodes)

    # --- IU-exp → fixed point → KY sample ---------------------------------
    # sampler="pallas": mask → LUT-exp → floor → KY walk fused in one
    # Pallas kernel, weight tile resident in VMEM (kernels/fused_sweep.py);
    # bitwise-identical to the two-stage XLA path below by construction.
    if sampler == "pallas":
        lane_card = jnp.broadcast_to(
            card[None], logw.shape[:-1]).reshape(-1)
        with jax.named_scope("ky_walk"):
            res = fused_gibbs_sample(
                key, logw.reshape((-1, max_card)), lane_card,
                k=k, use_iu=use_iu, table=_EXP, mesh=mesh)
    else:
        with jax.named_scope("weights"):
            wts = ky_weights(logw, card, k, use_iu)
        with jax.named_scope("ky_walk"):
            res = ky_sample(key, wts.reshape((-1, max_card)))
    new = res.sample.reshape(logw.shape[:-1]).astype(jnp.int32)  # (B, G)
    x = x.at[:, nodes].set(new)
    return x, BNSweepStats(jnp.sum(res.bits_used), jnp.sum(res.attempts))


def _color_logw(x, plan, log_cpt, max_card, beta):
    """(B, G, L) log-weights of one color's nodes given states ``x`` (own
    CPT row plus the children's likelihood terms, β-scaled), and the
    nodes' cardinalities (G,)."""
    ls = jnp.arange(max_card, dtype=jnp.int32)            # (L,)
    card = jnp.asarray(plan.card)                          # (G,)

    # --- own CPT row: offset + Σ stride_j * x[pa_j] + l -------------------
    pa_states = x[:, jnp.asarray(plan.self_pa)]            # (B, G, P)
    base = jnp.asarray(plan.self_base_off)[None] + jnp.sum(
        jnp.asarray(plan.self_pa_stride)[None] * pa_states, axis=-1
    )                                                      # (B, G)
    logw = jnp.take(log_cpt, base[..., None] + ls, mode="clip")  # (B, G, L)

    # --- children likelihood terms ---------------------------------------
    ch_pa_states = x[:, jnp.asarray(plan.ch_pa)]           # (B, G, C, P)
    ch_base = (
        jnp.asarray(plan.ch_off)[None]
        + jnp.sum(jnp.asarray(plan.ch_pa_stride)[None] * ch_pa_states, axis=-1)
        + jnp.asarray(plan.ch_self_stride)[None] * x[:, jnp.asarray(plan.ch_self)]
    )                                                      # (B, G, C)
    ch_idx = ch_base[..., None] + jnp.asarray(plan.ch_vstride)[None, ..., None] * ls
    logw = logw + jnp.sum(jnp.take(log_cpt, ch_idx, mode="clip"), axis=-2)

    # --- annealing: scale log-weights by the inverse temperature ----------
    # Applied before the sampler branch, so the XLA and Pallas paths see
    # the same floats and stay bitwise-interchangeable at every β.  β > 1
    # sharpens the conditional toward its argmax (simulated annealing for
    # MAP/MPE); β = 1 (or None) is ordinary Gibbs.  Per-lane (B,) values
    # let one jitted sweep mix annealed and unannealed chains.  The valid-
    # label max is subtracted *before* scaling so the best label pins at
    # 0 whatever β is — an unbounded β can then never push every valid
    # label under the mask floor ``ky_weights`` applies.
    if beta is not None:
        b = jnp.asarray(beta, logw.dtype)
        b = b[:, None, None] if b.ndim == 1 else b
        valid = ls[None, None, :] < card[None, :, None]
        m = jnp.max(jnp.where(valid, logw, -jnp.inf), axis=-1, keepdims=True)
        logw = (logw - m) * b
    return logw, card


def make_sweep(prog: CompiledBN, *, use_iu: bool = True,
               sampler: str = "xla"):
    """Build the jitted one-sweep function: (key, x) -> (x', stats)."""
    log_cpt = jnp.asarray(prog.log_cpt)

    def sweep(key: jax.Array, x: jax.Array):
        bits = jnp.int32(0)
        att = jnp.int32(0)
        for i, plan in enumerate(prog.plans):
            key, sub = jax.random.split(key)
            x, st = _color_update(
                sub, x, plan, log_cpt, prog.max_card, prog.k, use_iu,
                sampler)
            bits, att = bits + st.bits_used, att + st.attempts
        return x, BNSweepStats(bits, att)

    return jax.jit(sweep)


def init_states(
    key: jax.Array,
    prog: CompiledBN,
    n_chains: int,
    evidence_values: jax.Array | None = None,
) -> jax.Array:
    """Random (B, n) initial states with evidence columns clamped.

    ``evidence_values`` aligns with ``prog.observed``: either (O,) shared
    across chains or (B, O) per-lane — the serve engine packs different
    queries' values into different lanes of one jitted sweep.
    """
    n = prog.bn.n_nodes
    card = jnp.asarray(prog.bn.card, jnp.int32)
    u = jax.random.uniform(key, (n_chains, n))
    x0 = (u * card[None]).astype(jnp.int32)
    if prog.observed:
        if evidence_values is None:
            raise ValueError(
                f"program clamps nodes {prog.observed} but no evidence given")
        ev = jnp.asarray(evidence_values, jnp.int32)
        if ev.ndim == 1:
            ev = jnp.broadcast_to(ev[None], (n_chains, len(prog.observed)))
        x0 = x0.at[:, jnp.asarray(prog.observed, jnp.int32)].set(ev)
    return x0


@partial(jax.jit, static_argnames=(
    "prog", "n_sweeps", "n_chains", "burn_in", "use_iu", "sampler"))
def _run_gibbs_device(
    key: jax.Array,
    prog: CompiledBN,
    *,
    n_chains: int,
    n_sweeps: int,
    burn_in: int,
    use_iu: bool = True,
    sampler: str = "xla",
    evidence=None,
):
    """Jitted Gibbs scan; stats are *per-sweep* (n_sweeps,) int32 arrays.

    The scan carry deliberately does not accumulate bits/attempts: an
    int32 running total wraps on long runs (see :class:`BNSweepStats`).
    Each sweep's contribution is emitted as a scan output instead and
    totalled host-side by :func:`run_gibbs`.
    """
    n = prog.bn.n_nodes
    key, init_key = jax.random.split(key)
    x0 = init_states(
        init_key, prog, n_chains,
        None if evidence is None else jnp.asarray(evidence, jnp.int32))
    log_cpt = jnp.asarray(prog.log_cpt)

    def body(carry, i):
        key, x, counts = carry
        key, sub = jax.random.split(key)
        bits, att = jnp.int32(0), jnp.int32(0)
        for plan in prog.plans:
            sub, s2 = jax.random.split(sub)
            x, st = _color_update(
                s2, x, plan, log_cpt, prog.max_card, prog.k, use_iu,
                sampler)
            bits, att = bits + st.bits_used, att + st.attempts
        onehot = (x[..., None] == jnp.arange(prog.max_card)[None, None]).astype(jnp.int32)
        counts = counts + jnp.where(i >= burn_in, jnp.sum(onehot, axis=0), 0)
        return (key, x, counts), BNSweepStats(bits, att)

    counts0 = jnp.zeros((n, prog.max_card), jnp.int32)
    (key, x, counts), per_sweep = jax.lax.scan(
        body, (key, x0, counts0), jnp.arange(n_sweeps))
    return x, counts, per_sweep


def run_gibbs(
    key: jax.Array,
    prog: CompiledBN,
    *,
    n_chains: int,
    n_sweeps: int,
    burn_in: int,
    use_iu: bool = True,
    sampler: str = "xla",
    evidence=None,
):
    """Run BN Gibbs; returns (final_states, marginal_counts, stats).

    marginal_counts: (n_nodes, max_card) int32 accumulated after burn-in.
    ``stats``: int64 host scalars (per-sweep device stats summed without
    int32 wraparound).  ``evidence``: values for ``prog.observed`` (same
    order); required iff the program was compiled with an evidence
    pattern.  Deliberately a *traced* argument of the underlying jit: one
    compiled program serves any values over its pattern — changing them
    must not retrace.  Because totals materialize on the host, wrap this
    function's *device* half (``_run_gibbs_device``) if you need to call
    it under an outer ``jax.jit``.
    """
    x, counts, per_sweep = _run_gibbs_device(
        key, prog, n_chains=n_chains, n_sweeps=n_sweeps, burn_in=burn_in,
        use_iu=use_iu, sampler=sampler, evidence=evidence)
    return x, counts, sum_sweep_stats(per_sweep)


_EXP = exp_table()
