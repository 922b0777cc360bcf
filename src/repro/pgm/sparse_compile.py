"""Sparse-graph compile layer: chromatic Gibbs on arbitrary factor graphs.

The unified back half of the compiler chain.  Where
:mod:`repro.pgm.compile` gathers CPT rows and
:mod:`repro.pgm.mrf_compile` freezes a checkerboard, this module takes
*any* pairwise :class:`~repro.pgm.graph.FactorGraph` (or
:class:`~repro.pgm.graph.IsingModel`) and lowers it to the same
IU-exp → fixed-point → non-normalized-KY sweep substrate:

1. **color** the interaction graph (:func:`repro.pgm.coloring.color_graph`
   — DSatur for small graphs, iterated MIS for huge ones) so each phase
   updates a conditionally-independent node set;
2. **pack** each color's neighbour lists into padded CSR-style gather
   plans, bucketed by ceil-power-of-two degree so one ``(G, D)`` gather
   serves all nodes of similar degree with bounded padding waste.
   Padded slots point at a **zero sentinel table**, so they contribute
   an exact ``+0.0`` to the energy — no runtime validity mask on the hot
   path;
3. **sweep**: per color, gather neighbour labels, accumulate pairwise
   energies table-by-table, add unaries, and feed the shared
   :func:`repro.pgm.compile.ky_weights` tail into one
   :func:`~repro.core.ky.ky_sample` call over every node of the color.

The dense checkerboard is the degenerate case — 2 colors, degree
bucket D=4, one shared table — and
:func:`repro.pgm.mrf_compile.sparse_plan` lowers a compiled grid onto
it with a per-site neighbour order chosen so the energies (and hence
the int32 KY weights) are **bitwise identical** to the dense
:func:`repro.pgm.gibbs.site_weights` path; tests regression-check that.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fixedpoint import DEFAULT_K
from repro.core.ky import ky_sample
from repro.kernels.fused_sweep import fused_gibbs_sample
from repro.pgm.coloring import color_graph
from repro.pgm.compile import BNSweepStats, ky_weights, sum_sweep_stats
from repro.pgm.graph import FactorGraph, IsingModel

# Neighbour accumulation is a short unrolled chain of adds below this
# degree cap (keeps the grid lowering's left-to-right float association
# explicit); above it one 4-D gather + sum wins.
_UNROLL_DEGREE = 8


@dataclass(frozen=True, eq=False)
class DegreeBucket:
    """All nodes of one color whose degree rounds up to the same D.

    ``nodes``: (G,) node ids.  ``nbr``: (G, D) neighbour ids (padded
    slots point at node 0 — harmless, their table is the sentinel).
    ``tab``: (G, D) directed-table ids into the compiled table bank;
    padded slots carry the all-zero sentinel id.  ``valid``: (G, D)
    bool, True where a real edge sits — not consumed by the sweep (the
    sentinel already zeroes the padding) but kept for introspection and
    the Metropolis path.
    """

    nodes: np.ndarray
    nbr: np.ndarray
    tab: np.ndarray
    valid: np.ndarray


@dataclass(frozen=True, eq=False)
class SparsePlan:
    """One color phase: degree buckets + the concatenated node order.

    ``nodes`` is exactly ``concat(b.nodes for b in buckets)`` — the
    order energies/samples come out of the bucket loop, used for the
    scatter back into the state vector.
    """

    buckets: tuple[DegreeBucket, ...]
    nodes: np.ndarray


@dataclass(frozen=True, eq=False)
class CompiledFactorGraph:
    """A compiled sparse sweep program (hashable by identity, like
    :class:`repro.pgm.compile.CompiledBN` — usable as a jit static arg).

    ``tables``: (T + 1, L, L) directed energy-table bank; the last entry
    is the all-zero padding sentinel.  ``plans``: one
    :class:`SparsePlan` per color.  ``observed``: sorted clamped node
    ids (the evidence *pattern* — values arrive at init time).
    """

    fg: FactorGraph
    unary: np.ndarray
    tables: np.ndarray
    plans: tuple[SparsePlan, ...]
    max_card: int
    k: int
    observed: tuple[int, ...] = ()

    @property
    def n_vars(self) -> int:
        return self.fg.n_vars

    @property
    def n_colors(self) -> int:
        return len(self.plans)

    @property
    def n_free(self) -> int:
        return self.n_vars - len(self.observed)

    @property
    def free_nodes(self) -> np.ndarray:
        mask = np.ones(self.n_vars, bool)
        if self.observed:
            mask[list(self.observed)] = False
        return np.flatnonzero(mask).astype(np.int32)


def _ceil_pow2(deg: np.ndarray) -> np.ndarray:
    """Elementwise smallest power of two >= max(deg, 1)."""
    caps = np.ones(len(deg), np.int64)
    m = np.maximum(np.asarray(deg, np.int64), 1)
    while (caps < m).any():
        caps = np.where(caps < m, caps * 2, caps)
    return caps


def _pack_plans(n: int, groups, dir_src, dir_dst, dir_tab,
                sentinel: int) -> tuple[SparsePlan, ...]:
    """Directed adjacency arrays → per-color degree-bucketed gather plans.

    The stable sort by source preserves the *given* per-source order of
    directed entries — the hook the grid lowering uses to pin its
    up/down/left/right accumulation order (and with it, bitwise energy
    equality against the dense path).
    """
    order = np.argsort(dir_src, kind="stable")
    s_dst = dir_dst[order]
    s_tab = dir_tab[order]
    counts = np.bincount(dir_src, minlength=n).astype(np.int64)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    plans = []
    for grp in groups:
        grp = np.asarray(grp, np.int64)
        deg = counts[grp]
        caps = _ceil_pow2(deg)
        buckets = []
        for cap in np.unique(caps):
            d = int(cap)
            sel = grp[caps == cap]
            degs = counts[sel]
            ar = np.arange(d)
            valid = ar[None, :] < degs[:, None]
            idx = np.where(valid, offsets[sel][:, None] + ar[None, :], 0)
            if len(s_dst):
                nbr = np.where(valid, s_dst[idx], 0)
                tab = np.where(valid, s_tab[idx], sentinel)
            else:
                nbr = np.zeros_like(idx)
                tab = np.full_like(idx, sentinel)
            buckets.append(DegreeBucket(
                nodes=sel.astype(np.int32), nbr=nbr.astype(np.int32),
                tab=tab.astype(np.int32), valid=valid))
        plans.append(SparsePlan(
            buckets=tuple(buckets),
            nodes=np.concatenate([b.nodes for b in buckets])))
    return tuple(plans)


def compile_factor_graph(
    model: FactorGraph | IsingModel,
    *,
    k: int = DEFAULT_K,
    observed=(),
    method: str = "auto",
    validate: bool = False,
    directed=None,
    groups=None,
) -> CompiledFactorGraph:
    """Lower a sparse model onto colored degree-bucketed gather plans.

    ``observed``: node ids to clamp (the evidence pattern; values are
    supplied at init time, so one program serves any values over its
    pattern).  ``method``/``validate`` pass through to
    :func:`~repro.pgm.coloring.color_graph`.

    ``directed``/``groups`` are lowering overrides for callers that
    already know the plan structure (the dense-grid path): ``directed``
    is ``(src, dst, tab_ids, table_bank)`` with per-source entry order
    preserved into the packed plans; ``groups`` is the per-color node
    partition.  Default lowering derives both from the graph: each
    undirected edge becomes two directed entries (the reverse direction
    sees the transposed table), the table bank is deduplicated, and
    entries are ordered by (src, dst).
    """
    fg = model.to_factor_graph() if isinstance(model, IsingModel) else model
    n = fg.n_vars
    L = fg.max_card
    observed = tuple(sorted({fg.index(v) for v in observed}))
    if len(observed) == n:
        raise ValueError("all variables clamped — nothing to infer")

    if directed is not None:
        dir_src, dir_dst, dir_tab, bank = directed
        dir_src = np.asarray(dir_src, np.int64)
        dir_dst = np.asarray(dir_dst, np.int64)
        dir_tab = np.asarray(dir_tab, np.int64)
        bank = np.asarray(bank, np.float32).reshape(-1, L, L)
    elif len(fg.edges):
        src = np.concatenate([fg.edges[:, 0], fg.edges[:, 1]]).astype(np.int64)
        dst = np.concatenate([fg.edges[:, 1], fg.edges[:, 0]]).astype(np.int64)
        both = np.concatenate([fg.pair, fg.pair.transpose(0, 2, 1)])
        bank, inv = np.unique(both.reshape(len(src), L * L), axis=0,
                              return_inverse=True)
        bank = bank.reshape(-1, L, L)
        order = np.lexsort((dst, src))
        dir_src, dir_dst = src[order], dst[order]
        dir_tab = inv.reshape(-1)[order].astype(np.int64)
    else:
        dir_src = dir_dst = dir_tab = np.zeros(0, np.int64)
        bank = np.zeros((0, L, L), np.float32)

    sentinel = len(bank)
    tables = np.concatenate(
        [bank, np.zeros((1, L, L), np.float32)]).astype(np.float32)

    if groups is None:
        groups = color_graph(n, fg.edges, skip=set(observed),
                             method=method, validate=validate)
    plans = _pack_plans(n, groups, dir_src, dir_dst, dir_tab, sentinel)
    return CompiledFactorGraph(
        fg=fg, unary=np.asarray(fg.unary, np.float32), tables=tables,
        plans=plans, max_card=L, k=k, observed=observed)


# ---------------------------------------------------------------------------
# sweep execution
# ---------------------------------------------------------------------------

def _plan_energies(x: jax.Array, plan: SparsePlan, unary: jax.Array,
                   tables_flat: jax.Array, max_card: int) -> jax.Array:
    """(B, N_color, L) candidate-label energies for one color phase.

    Pairwise contributions accumulate from an exact-zero init in the
    packed neighbour order, then unaries are added — the float
    association the dense grid path uses, which is what makes the
    degenerate 2-color lowering bitwise-equal to
    :func:`repro.pgm.gibbs.site_weights`.
    """
    L = max_card
    ls = jnp.arange(L, dtype=jnp.int32)
    parts = []
    for bk in plan.buckets:
        nbr = jnp.asarray(bk.nbr)                    # (G, D)
        tab = jnp.asarray(bk.tab)                    # (G, D)
        xn = x[:, nbr]                               # (B, G, D)
        g, d = bk.nbr.shape
        e = jnp.zeros((x.shape[0], g, L), jnp.float32)
        if d <= _UNROLL_DEGREE:
            for j in range(d):
                idx = (tab[:, j][None, :, None] * (L * L)
                       + ls[None, None, :] * L
                       + xn[:, :, j][:, :, None])    # (B, G, L)
                e = e + jnp.take(tables_flat, idx)
        else:
            idx = (tab[None, :, :, None] * (L * L)
                   + ls[None, None, None, :] * L
                   + xn[..., None])                  # (B, G, D, L)
            e = e + jnp.sum(jnp.take(tables_flat, idx), axis=-2)
        parts.append(e)
    e = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    nodes = jnp.asarray(plan.nodes)
    return unary[nodes][None] + e


def _sparse_color_update(
    key: jax.Array,
    x: jax.Array,               # (B, n) int32 current states
    plan: SparsePlan,
    unary: jax.Array,
    tables_flat: jax.Array,
    card: jax.Array,
    max_card: int,
    k: int,
    use_iu: bool,
    sampler: str = "xla",
    beta: jax.Array | None = None,   # traced inverse temperature, (B,) or scalar
    mesh=None,                       # serve mesh the lane axis is sharded on
) -> tuple[jax.Array, BNSweepStats]:
    """Resample every node of one color, all lanes at once.

    ``beta`` scales the candidate energies before the sampler branch
    (traced, per-lane (B,) or scalar) — the simulated-annealing hook of
    the MAP mode; None / 1.0 is ordinary Gibbs.  Both sampler branches
    see the scaled energies, so they stay bitwise-interchangeable at
    every β.

    ``sampler="pallas"`` hands the negated energies straight to the fused
    kernel (``kernels/fused_sweep.py``) — ``-energies`` is exactly the
    log-weight tensor ``ky_weights`` receives, so the fused path is
    bitwise-identical to the XLA path by construction.
    """
    nodes = jnp.asarray(plan.nodes)
    energies = _plan_energies(x, plan, unary, tables_flat, max_card)
    if beta is not None:
        bb = jnp.asarray(beta, energies.dtype)
        energies = energies * (bb[:, None, None] if bb.ndim == 1 else bb)
    if sampler == "pallas":
        lane_card = jnp.broadcast_to(
            card[nodes][None], energies.shape[:-1]).reshape(-1)
        res = fused_gibbs_sample(
            key, (-energies).reshape((-1, max_card)), lane_card,
            k=k, use_iu=use_iu, mesh=mesh)
    else:
        wts = ky_weights(-energies, card[nodes], k, use_iu)
        res = ky_sample(key, wts.reshape((-1, max_card)))
    new = res.sample.reshape(energies.shape[:-1]).astype(jnp.int32)
    x = x.at[:, nodes].set(new)
    return x, BNSweepStats(jnp.sum(res.bits_used), jnp.sum(res.attempts))


def site_weights_sparse(
    prog: CompiledFactorGraph, x: jax.Array, *, use_iu: bool = True
) -> jax.Array:
    """(B, n, L) int32 KY weights of every planned node given states ``x``.

    Debug/regression probe (clamped nodes report zero weights): the
    grid-lowering tests compare this bitwise against the dense
    :func:`repro.pgm.gibbs.site_weights`.
    """
    unary = jnp.asarray(prog.unary)
    tables_flat = jnp.asarray(prog.tables).reshape(-1)
    card = jnp.asarray(prog.fg.card, jnp.int32)
    out = jnp.zeros(x.shape[:1] + (prog.n_vars, prog.max_card), jnp.int32)
    for plan in prog.plans:
        energies = _plan_energies(x, plan, unary, tables_flat, prog.max_card)
        wts = ky_weights(-energies, card[jnp.asarray(plan.nodes)],
                         prog.k, use_iu)
        out = out.at[:, jnp.asarray(plan.nodes)].set(wts)
    return out


def make_fg_sweep(prog: CompiledFactorGraph, *, use_iu: bool = True,
                  sampler: str = "xla"):
    """Build the jitted one-sweep function: (key, x) -> (x', stats)."""
    unary = jnp.asarray(prog.unary)
    tables_flat = jnp.asarray(prog.tables).reshape(-1)
    card = jnp.asarray(prog.fg.card, jnp.int32)

    def sweep(key: jax.Array, x: jax.Array):
        bits = jnp.int32(0)
        att = jnp.int32(0)
        for plan in prog.plans:
            key, sub = jax.random.split(key)
            x, st = _sparse_color_update(
                sub, x, plan, unary, tables_flat, card, prog.max_card,
                prog.k, use_iu, sampler)
            bits, att = bits + st.bits_used, att + st.attempts
        return x, BNSweepStats(bits, att)

    return jax.jit(sweep)


def init_fg_states(
    key: jax.Array,
    prog: CompiledFactorGraph,
    n_lanes: int,
    evidence_values: jax.Array | None = None,
) -> jax.Array:
    """Random (B, n) initial states with evidence columns clamped.

    ``evidence_values`` aligns with ``prog.observed``: either (O,)
    shared across lanes or (B, O) per-lane — the serve engine packs
    different queries' clamp values into different lanes of one jitted
    sweep, exactly like BN evidence columns.
    """
    card = jnp.asarray(prog.fg.card, jnp.int32)
    u = jax.random.uniform(key, (n_lanes, prog.n_vars))
    x0 = (u * card[None]).astype(jnp.int32)
    if prog.observed:
        if evidence_values is None:
            raise ValueError(
                f"program clamps nodes {prog.observed} but no evidence given")
        ev = jnp.asarray(evidence_values, jnp.int32)
        if ev.ndim == 1:
            ev = jnp.broadcast_to(ev[None], (n_lanes, len(prog.observed)))
        x0 = x0.at[:, jnp.asarray(prog.observed, jnp.int32)].set(ev)
    return x0


@partial(jax.jit, static_argnames=(
    "prog", "n_sweeps", "n_chains", "burn_in", "use_iu", "sampler"))
def _run_fg_gibbs_device(
    key: jax.Array,
    prog: CompiledFactorGraph,
    *,
    n_chains: int,
    n_sweeps: int,
    burn_in: int,
    use_iu: bool = True,
    sampler: str = "xla",
    evidence=None,
    x0=None,
):
    """Jitted sparse-Gibbs scan; stats are per-sweep (n_sweeps,) int32."""
    key, init_key = jax.random.split(key)
    if x0 is None:
        x0 = init_fg_states(
            init_key, prog, n_chains,
            None if evidence is None else jnp.asarray(evidence, jnp.int32))
    unary = jnp.asarray(prog.unary)
    tables_flat = jnp.asarray(prog.tables).reshape(-1)
    card = jnp.asarray(prog.fg.card, jnp.int32)

    def body(carry, i):
        key, x, counts = carry
        key, sub = jax.random.split(key)
        bits, att = jnp.int32(0), jnp.int32(0)
        for plan in prog.plans:
            sub, s2 = jax.random.split(sub)
            x, st = _sparse_color_update(
                s2, x, plan, unary, tables_flat, card, prog.max_card,
                prog.k, use_iu, sampler)
            bits, att = bits + st.bits_used, att + st.attempts
        onehot = (x[..., None]
                  == jnp.arange(prog.max_card)[None, None]).astype(jnp.int32)
        counts = counts + jnp.where(i >= burn_in, jnp.sum(onehot, axis=0), 0)
        return (key, x, counts), BNSweepStats(bits, att)

    counts0 = jnp.zeros((prog.n_vars, prog.max_card), jnp.int32)
    (key, x, counts), per_sweep = jax.lax.scan(
        body, (key, x0, counts0), jnp.arange(n_sweeps))
    return x, counts, per_sweep


def run_fg_gibbs(
    key: jax.Array,
    prog: CompiledFactorGraph,
    *,
    n_chains: int,
    n_sweeps: int,
    burn_in: int,
    use_iu: bool = True,
    sampler: str = "xla",
    evidence=None,
    x0=None,
):
    """Run sparse chromatic Gibbs; returns (states, counts, stats).

    ``counts``: (n_vars, max_card) int32 accumulated after burn-in,
    summed over chains.  ``evidence``: values for ``prog.observed``
    (same order) — a *traced* argument, so one compiled program serves
    any values over its pattern without retracing.  ``x0`` optionally
    overrides the random init (e.g. the all-up start the ferromagnet
    tests use below the critical temperature).
    """
    x, counts, per_sweep = _run_fg_gibbs_device(
        key, prog, n_chains=n_chains, n_sweeps=n_sweeps, burn_in=burn_in,
        use_iu=use_iu, sampler=sampler, evidence=evidence,
        x0=None if x0 is None else jnp.asarray(x0, jnp.int32))
    return x, counts, sum_sweep_stats(per_sweep)
