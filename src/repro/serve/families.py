"""Model-family adapters: one serving engine, three PGM families.

The AIA fabric runs MRF grids and Bayesian networks on the same 16
Gibbs cores (paper Fig. 7); the serving analogue is one
:class:`repro.serve.engine.PosteriorEngine` whose family-specific
surface — how a query normalizes to an evidence pattern, how a pattern
compiles to a sweep program, how a round runner advances the packed
lane state — lives behind the small adapter objects here.  Everything
else (lane packing, per-query split-R̂ retirement, plan caching,
admission-queue bucketing, mesh sharding, backfill) is family-agnostic
because every adapter presents the same *flat variable space* to the
engine:

* a state tensor with a leading chain-lane axis,
* per-round ``counts (B, M, L)`` / ``xmean (B, M)`` over M flat
  variables (BN: nodes; MRF: ``H*W`` sites; Ising/factor graph: graph
  nodes),
* an evidence pattern that is a sorted tuple of flat variable ids
  (BN: observed nodes; MRF: clamped ``r * W + c`` pixel indices;
  Ising: clamped spin ids), with per-lane evidence *values* packed
  ``(B, O)`` in pattern order.

``family_of(model)`` dispatches on the registered model's type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.pgm.compile import (
    BNSweepStats, _color_update, compile_bayesnet, init_states)
from repro.pgm.gibbs import SweepStats, checkerboard_halfstep
from repro.pgm.graph import BayesNet, FactorGraph, IsingModel, MRFGrid
from repro.pgm.mrf_compile import CompiledMRF, compile_mrf, init_mrf_states
from repro.pgm.sparse_compile import (
    CompiledFactorGraph, _sparse_color_update, compile_factor_graph,
    init_fg_states)
from repro.serve.plan_cache import (
    graph_fingerprint, load_compiled, persisted_plan_path, save_compiled)
from repro.sharding.specs import (
    serve_cpt_spec, serve_fg_state_spec, serve_mrf_state_spec,
    serve_state_spec)


# -- round runners ---------------------------------------------------------
def make_round_runner(prog, *, sweeps_per_round: int, thin: int,
                      use_iu: bool, sampler: str = "xla", mesh=None):
    """Jitted ``(key, x, offset[, beta]) -> (x, counts, xmean, xsq,
    stats)`` per round (Bayesian-network family).

    ``beta`` (traced float32, scalar or per-lane ``(B,)``; default None
    = ordinary Gibbs) is the inverse temperature of the simulated-
    annealing MAP mode: every color update scales its log-weights by it
    before the IU-exp tail, so one compiled round program serves both
    inference modes — and any point of an annealing schedule — without
    retracing.  Per-lane values let annealed (MAP) and β=1 (marginal)
    slots share one micro-batched group.

    ``offset`` (traced int32, scalar or per-lane ``(B,)``) is the global
    post-burn-in sweep index of the round's first sweep: draws are kept
    where the *global* index is a multiple of ``thin``.  A round-relative
    ``i % thin`` would restart the phase every round, so for
    ``sweeps_per_round % thin != 0`` the kept-draw spacing (and every
    downstream sample count) drifted.  The per-lane form lets one round
    serve lanes at *different* points of their thinning schedule — slots
    backfilled mid-flight by ``GroupRun.admit`` restart their own phase
    at 0 while their group mates keep counting.

    ``counts``: (B, n, L) thinned one-hot draw counts this round.
    ``xmean``:  (B, n) mean state over the round — per-lane scalar
    statistics for the convergence diagnostics (for a binary node this
    is its running posterior-probability estimate).
    ``xsq``:    (B, n) mean of x² over the round — the extra per-round
    moment :mod:`repro.pgm.diagnostics` needs to rescale round-unit ESS
    to sweep units (both moments accumulate inside the same fused scan,
    so diagnostics cost zero extra dispatches).
    ``stats``:  per-sweep (sweeps_per_round,) int32 arrays — summed
    host-side in int64 by the engine (int32 carries wrapped on long
    runs; see :class:`repro.pgm.compile.BNSweepStats`).

    With ``mesh`` the lane (batch) axis of ``x``/``counts`` is held to a
    NamedSharding over the mesh's "batch" axis and the log-CPT bank is
    placed per ``serve_cpt_spec`` — one compile per (plan, mesh).
    """
    log_cpt = jnp.asarray(prog.log_cpt)
    state_sharding = None
    if mesh is not None:
        log_cpt = jax.device_put(
            log_cpt, NamedSharding(mesh, serve_cpt_spec(mesh, log_cpt.size)))
        state_sharding = NamedSharding(mesh, serve_state_spec(mesh))
    L = prog.max_card

    def round_fn(key: jax.Array, x: jax.Array, offset: jax.Array,
                 beta: jax.Array | None = None):
        if state_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, state_sharding)

        def body(carry, i):
            key, x, counts, xsum, xsqsum = carry
            key, sub = jax.random.split(key)
            bits, att = jnp.int32(0), jnp.int32(0)
            for plan in prog.plans:
                sub, s2 = jax.random.split(sub)
                x, st = _color_update(
                    s2, x, plan, log_cpt, L, prog.k, use_iu, sampler,
                    beta, mesh)
                bits, att = bits + st.bits_used, att + st.attempts
            with jax.named_scope("counts"):
                onehot = (x[..., None] == jnp.arange(L)).astype(jnp.int32)
                kept = ((offset + i) % thin) == 0
                if kept.ndim:  # per-lane offsets: broadcast over (node, label)
                    kept = kept[:, None, None]
                counts = counts + jnp.where(kept, onehot, 0)
                xf = x.astype(jnp.float32)
                xsum = xsum + xf
                xsqsum = xsqsum + xf * xf
            return (key, x, counts, xsum, xsqsum), BNSweepStats(bits, att)

        counts0 = jnp.zeros(x.shape + (L,), jnp.int32)
        xsum0 = jnp.zeros(x.shape, jnp.float32)
        with jax.named_scope("round"):
            (key, x, counts, xsum, xsqsum), per_sweep = jax.lax.scan(
                body, (key, x, counts0, xsum0, xsum0),
                jnp.arange(sweeps_per_round))
        if state_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, state_sharding)
        return (x, counts, xsum / sweeps_per_round,
                xsqsum / sweeps_per_round, per_sweep)

    return jax.jit(round_fn)


def make_mrf_round_runner(prog: CompiledMRF, *, sweeps_per_round: int,
                          thin: int, use_iu: bool, sampler: str = "xla",
                          mesh=None):
    """Jitted ``(key, x, offset[, beta]) -> (x, counts, xmean, xsq,
    stats)`` per round (MRF family) — same contract as
    :func:`make_round_runner` (including the traced annealing ``beta``),
    over the flat site space.

    ``x`` is the (B, H, W) label field; the clamp mask compiled into
    ``prog`` is baked as a constant (the mask IS the plan — one XLA
    program per mask pattern, exactly one per BN evidence pattern).
    ``counts`` come back flattened (B, H*W, L) and ``xmean`` (B, H*W)
    so the engine's slot bookkeeping is family-blind.  With ``mesh``
    the lane axis shards over "batch" (``serve_mrf_state_spec``); the
    unary/pairwise fields are replicated — they are the gather operands
    of every lane's checkerboard update.
    """
    from repro.pgm.mrf_compile import mask_of

    unary = jnp.asarray(prog.mrf.unary)
    pairwise = jnp.asarray(prog.mrf.pairwise)
    clamp = jnp.asarray(mask_of(prog)) if prog.observed else None
    state_sharding = None
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        unary, pairwise = jax.device_put(unary, rep), jax.device_put(pairwise, rep)
        if clamp is not None:
            clamp = jax.device_put(clamp, rep)
        state_sharding = NamedSharding(mesh, serve_mrf_state_spec(mesh))
    h, w = prog.shape
    L = prog.n_labels

    def round_fn(key: jax.Array, x: jax.Array, offset: jax.Array,
                 beta: jax.Array | None = None):
        if state_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, state_sharding)
        b = x.shape[0]

        def body(carry, i):
            key, x, counts, xsum, xsqsum = carry
            key, k0, k1 = jax.random.split(key, 3)
            x, s0 = checkerboard_halfstep(
                k0, x, unary, pairwise, jnp.int32(0), clamp=clamp,
                k=prog.k, use_iu=use_iu, sampler=sampler, beta=beta,
                mesh=mesh)
            x, s1 = checkerboard_halfstep(
                k1, x, unary, pairwise, jnp.int32(1), clamp=clamp,
                k=prog.k, use_iu=use_iu, sampler=sampler, beta=beta,
                mesh=mesh)
            with jax.named_scope("counts"):
                flat = x.reshape(b, h * w)
                onehot = (flat[..., None] == jnp.arange(L)).astype(jnp.int32)
                kept = ((offset + i) % thin) == 0
                if kept.ndim:  # per-lane offsets: broadcast over (site, label)
                    kept = kept[:, None, None]
                counts = counts + jnp.where(kept, onehot, 0)
                ff = flat.astype(jnp.float32)
                xsum = xsum + ff
                xsqsum = xsqsum + ff * ff
            return (key, x, counts, xsum, xsqsum), SweepStats(
                s0.bits_used + s1.bits_used, s0.attempts + s1.attempts)

        counts0 = jnp.zeros((b, h * w, L), jnp.int32)
        xsum0 = jnp.zeros((b, h * w), jnp.float32)
        with jax.named_scope("round"):
            (key, x, counts, xsum, xsqsum), per_sweep = jax.lax.scan(
                body, (key, x, counts0, xsum0, xsum0),
                jnp.arange(sweeps_per_round))
        if state_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, state_sharding)
        return (x, counts, xsum / sweeps_per_round,
                xsqsum / sweeps_per_round, per_sweep)

    return jax.jit(round_fn)


def make_fg_round_runner(prog: CompiledFactorGraph, *,
                         sweeps_per_round: int, thin: int, use_iu: bool,
                         sampler: str = "xla", mesh=None):
    """Jitted ``(key, x, offset[, beta]) -> (x, counts, xmean, xsq,
    stats)`` per round (sparse factor-graph / Ising family) — same
    contract as :func:`make_round_runner` (including the traced
    annealing ``beta``), over the graph's flat node space.

    ``x`` is the (B, n) node-state tensor; the compiled color plans and
    degree buckets are baked as constants (the plan IS the program —
    one XLA build per (graph, clamp pattern), like one per BN evidence
    pattern).  With ``mesh`` the lane axis shards over "batch" and —
    for million-site graphs — the site axis additionally shards over
    "model" (``serve_fg_state_spec``); the unary/table banks are
    replicated (they are the gather operands of every lane's sweep).
    """
    unary = jnp.asarray(prog.unary)
    tables_flat = jnp.asarray(prog.tables).reshape(-1)
    card = jnp.asarray(prog.fg.card, jnp.int32)
    state_sharding = None
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        unary = jax.device_put(unary, rep)
        tables_flat = jax.device_put(tables_flat, rep)
        card = jax.device_put(card, rep)
        state_sharding = NamedSharding(
            mesh, serve_fg_state_spec(mesh, prog.n_vars))
    L = prog.max_card

    def round_fn(key: jax.Array, x: jax.Array, offset: jax.Array,
                 beta: jax.Array | None = None):
        if state_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, state_sharding)

        def body(carry, i):
            key, x, counts, xsum, xsqsum = carry
            key, sub = jax.random.split(key)
            bits, att = jnp.int32(0), jnp.int32(0)
            for plan in prog.plans:
                sub, s2 = jax.random.split(sub)
                x, st = _sparse_color_update(
                    s2, x, plan, unary, tables_flat, card, L, prog.k,
                    use_iu, sampler, beta, mesh)
                bits, att = bits + st.bits_used, att + st.attempts
            with jax.named_scope("counts"):
                onehot = (x[..., None] == jnp.arange(L)).astype(jnp.int32)
                kept = ((offset + i) % thin) == 0
                if kept.ndim:  # per-lane offsets: broadcast over (node, label)
                    kept = kept[:, None, None]
                counts = counts + jnp.where(kept, onehot, 0)
                xf = x.astype(jnp.float32)
                xsum = xsum + xf
                xsqsum = xsqsum + xf * xf
            return (key, x, counts, xsum, xsqsum), BNSweepStats(bits, att)

        counts0 = jnp.zeros(x.shape + (L,), jnp.int32)
        xsum0 = jnp.zeros(x.shape, jnp.float32)
        with jax.named_scope("round"):
            (key, x, counts, xsum, xsqsum), per_sweep = jax.lax.scan(
                body, (key, x, counts0, xsum0, xsum0),
                jnp.arange(sweeps_per_round))
        if state_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, state_sharding)
        return (x, counts, xsum / sweeps_per_round,
                xsqsum / sweeps_per_round, per_sweep)

    return jax.jit(round_fn)


# -- family adapters -------------------------------------------------------
class BayesNetFamily:
    """Engine adapter for :class:`repro.pgm.graph.BayesNet` models."""

    kind = "bayesnet"

    def normalize(self, model: BayesNet, query):
        """``(evidence-by-flat-id, query-var ids, pattern)``; raises on
        bad evidence or query vars that are observed."""
        ev = model.normalize_evidence(query.evidence)
        qvars = tuple(model.index(v) for v in query.query_vars) or tuple(
            v for v in range(model.n_nodes) if v not in ev)
        clash = [model.names[v] for v in qvars if v in ev]
        if clash:
            raise ValueError(f"query vars {clash} are observed")
        return ev, qvars, tuple(sorted(ev))

    def compile(self, model, pattern, *, k, quantize_cpt_bits):
        return compile_bayesnet(
            model, k=k, quantize_cpt_bits=quantize_cpt_bits,
            observed=pattern)

    def make_runner(self, prog, *, sweeps_per_round, thin, use_iu,
                    sampler="xla", mesh=None):
        return make_round_runner(
            prog, sweeps_per_round=sweeps_per_round, thin=thin,
            use_iu=use_iu, sampler=sampler, mesh=mesh)

    def init_states(self, key, prog, n_lanes, evidence_values):
        return init_states(key, prog, n_lanes, evidence_values)

    def clamp_states(self, prog, x, evidence_values):
        """Re-pin the evidence columns of *existing* states — the
        temporal warm start: retained chains from the previous slice,
        this slice's observations."""
        if not prog.observed:
            return x
        ev = jnp.asarray(evidence_values, jnp.int32)
        if ev.ndim == 1:
            ev = jnp.broadcast_to(ev[None], (x.shape[0], len(prog.observed)))
        return x.at[:, jnp.asarray(prog.observed, jnp.int32)].set(ev)

    def assignment_energy(self, model, assignment) -> float:
        """-log P(x) (nats) of a full assignment over every node — the
        MAP objective the annealed mode minimizes."""
        e = 0.0
        for v in range(model.n_nodes):
            idx = tuple(int(assignment[p]) for p in model.parents[v])
            p = float(model.cpt[v][idx + (int(assignment[v]),)])
            e -= float(np.log(max(p, 1e-26)))
        return e

    def state_spec(self, mesh):
        return serve_state_spec(mesh)

    def n_vars(self, prog) -> int:
        return prog.bn.n_nodes

    def max_card(self, prog) -> int:
        return prog.max_card

    def var_card(self, prog, v: int) -> int:
        return prog.bn.card[v]

    def var_name(self, model, v: int) -> str:
        return model.names[v]

    def n_free(self, prog) -> int:
        return len(prog.free_nodes)

    def ky_lanes(self, prog) -> int:
        """Knuth-Yao walks one chain runs per sweep: each colour's nodes."""
        return sum(len(plan.nodes) for plan in prog.plans)

    def plan_salt(self, model):
        """BN plans are fully determined by (name, pattern, knobs)."""
        return None

    # -- plan persistence (compiler chain is worth skipping for BNs) ------
    def persisted_path(self, directory, name, pattern, model, *,
                       k, quantize_cpt_bits):
        return persisted_plan_path(
            directory, name, pattern, model, k=k,
            quantize_cpt_bits=quantize_cpt_bits)

    def load_persisted(self, path, model):
        return load_compiled(path, model)

    def save_persisted(self, path, prog):
        save_compiled(path, prog)


class MrfFamily:
    """Engine adapter for :class:`repro.pgm.graph.MRFGrid` models.

    Flat variable ids are ``r * W + c``; evidence is a pixel mask plus
    observed labels (:class:`repro.serve.query.MrfQuery`).
    """

    kind = "mrf"

    def normalize(self, model: MRFGrid, query):
        import numpy as np

        h, w = model.shape
        ev: dict[int, int] = {}
        if query.mask is not None:
            mask = np.asarray(query.mask, bool)
            if mask.shape != (h, w):
                raise ValueError(
                    f"mask shape {mask.shape} != grid shape {(h, w)}")
            if mask.any():
                if query.values is None:
                    raise ValueError("mask given without values")
                values = np.asarray(query.values)
                if values.shape != (h, w):
                    raise ValueError(
                        f"values shape {values.shape} != grid shape {(h, w)}")
                rs, cs = np.nonzero(mask)
                for r, c in zip(rs.tolist(), cs.tolist()):
                    ev[r * w + c] = int(values[r, c])
        for site in getattr(query, "mask_sites", ()) or ():
            r, c, val = (int(s) for s in site)
            # per-coordinate check: a flat r*w+c range test would let an
            # out-of-range column alias onto a different pixel's row
            if not (0 <= r < h and 0 <= c < w):
                raise ValueError(f"clamped site ({r}, {c}) outside the "
                                 f"{(h, w)} lattice")
            if ev.get(r * w + c, val) != val:
                raise ValueError(f"conflicting evidence at site ({r}, {c})")
            ev[r * w + c] = val
        for v, val in ev.items():
            if not 0 <= val < model.n_labels:
                raise ValueError(
                    f"observed label {val} at site {divmod(v, w)} outside "
                    f"[0, {model.n_labels})")
        if len(ev) == h * w:
            raise ValueError("all sites clamped — nothing to infer")
        if query.query_sites:
            qvars = []
            for r, c in query.query_sites:
                r, c = int(r), int(c)
                if not (0 <= r < h and 0 <= c < w):
                    raise KeyError(f"query site ({r}, {c}) outside the "
                                   f"{(h, w)} lattice")
                qvars.append(r * w + c)
            clash = [divmod(v, w) for v in qvars if v in ev]
            if clash:
                raise ValueError(f"query sites {clash} are observed")
            qvars = tuple(qvars)
        else:
            qvars = tuple(v for v in range(h * w) if v not in ev)
        return ev, qvars, tuple(sorted(ev))

    def compile(self, model, pattern, *, k, quantize_cpt_bits):
        # quantize_cpt_bits is a CPT-bank knob; grids carry energies, not
        # CPTs, so it does not apply here (it still keys the plan cache)
        return compile_mrf(model, k=k, observed=pattern)

    def make_runner(self, prog, *, sweeps_per_round, thin, use_iu,
                    sampler="xla", mesh=None):
        return make_mrf_round_runner(
            prog, sweeps_per_round=sweeps_per_round, thin=thin,
            use_iu=use_iu, sampler=sampler, mesh=mesh)

    def init_states(self, key, prog, n_lanes, evidence_values):
        return init_mrf_states(key, prog, n_lanes, evidence_values)

    def clamp_states(self, prog, x, evidence_values):
        """Re-pin the clamped pixels of existing (B, H, W) label fields
        (temporal warm start)."""
        if not prog.observed:
            return x
        b = x.shape[0]
        h, w = prog.shape
        ev = jnp.asarray(evidence_values, jnp.int32)
        if ev.ndim == 1:
            ev = jnp.broadcast_to(ev[None], (b, len(prog.observed)))
        flat = x.reshape(b, h * w)
        flat = flat.at[:, jnp.asarray(prog.observed, jnp.int32)].set(ev)
        return flat.reshape(b, h, w)

    def assignment_energy(self, model, assignment) -> float:
        """Grid energy (unary + each lattice edge once) of a full
        assignment over every site — the MAP objective."""
        h, w = model.shape
        x = np.array([[int(assignment[r * w + c]) for c in range(w)]
                      for r in range(h)])
        unary = np.asarray(model.unary)
        pw = np.asarray(model.pairwise)
        e = float(unary[np.arange(h)[:, None], np.arange(w)[None, :], x].sum())
        e += float(pw[x[:, :-1], x[:, 1:]].sum())   # horizontal edges
        e += float(pw[x[:-1, :], x[1:, :]].sum())   # vertical edges
        return e

    def state_spec(self, mesh):
        return serve_mrf_state_spec(mesh)

    def n_vars(self, prog) -> int:
        return prog.n_sites

    def max_card(self, prog) -> int:
        return prog.n_labels

    def var_card(self, prog, v: int) -> int:
        return prog.n_labels

    def var_name(self, model, v: int) -> str:
        r, c = divmod(v, model.shape[1])
        return f"s{r},{c}"

    def n_free(self, prog) -> int:
        return prog.n_free

    def ky_lanes(self, prog) -> int:
        """Knuth-Yao walks one chain runs per sweep: the two half-steps
        each walk one colour packed to H x W_even / 2 lanes, clamped
        sites and the pad column of an odd W included."""
        h, w = prog.shape
        return h * (w + w % 2)

    def plan_salt(self, model):
        """MRF plans are fully determined by (name, pattern, knobs)."""
        return None

    # -- plan persistence: compiling an MRF plan is O(1), nothing to skip
    def persisted_path(self, directory, name, pattern, model, *,
                       k, quantize_cpt_bits):
        return None

    def load_persisted(self, path, model):  # pragma: no cover - unused
        return None

    def save_persisted(self, path, prog):  # pragma: no cover - unused
        pass


class IsingFamily:
    """Engine adapter for sparse :class:`repro.pgm.graph.IsingModel` /
    :class:`repro.pgm.graph.FactorGraph` models.

    Flat variable ids are graph node ids; evidence is a clamp mask over
    spins (:class:`repro.serve.query.IsingQuery` ``clamp_sites`` pairs —
    ``±1`` spins or ``{0, 1}`` labels), or a plain :class:`Query`-style
    evidence mapping for general factor graphs.  Queries sharing a
    clamp *pattern* share one compiled sparse sweep program
    (:class:`repro.pgm.sparse_compile.CompiledFactorGraph`) whatever
    their clamped values.
    """

    kind = "ising"

    def normalize(self, model, query):
        clamp = getattr(query, "clamp_sites", None)
        if clamp is not None:
            raw = {}
            for site, spin in clamp:
                v, spin = int(site), int(spin)
                if raw.get(v, spin) != spin:
                    raise ValueError(
                        f"conflicting evidence for spin {v}")
                raw[v] = spin
            ev = model.normalize_evidence(raw)
        else:
            ev = model.normalize_evidence(query.evidence)
        qvars = tuple(model.index(v) for v in query.query_vars) or tuple(
            v for v in range(model.n_vars) if v not in ev)
        clash = [model.var_name(v) for v in qvars if v in ev]
        if clash:
            raise ValueError(f"query vars {clash} are observed")
        return ev, qvars, tuple(sorted(ev))

    def compile(self, model, pattern, *, k, quantize_cpt_bits):
        # quantize_cpt_bits is a CPT-bank knob; factor graphs carry
        # energies, not CPTs (it still keys the plan cache)
        return compile_factor_graph(model, k=k, observed=pattern)

    def make_runner(self, prog, *, sweeps_per_round, thin, use_iu,
                    sampler="xla", mesh=None):
        return make_fg_round_runner(
            prog, sweeps_per_round=sweeps_per_round, thin=thin,
            use_iu=use_iu, sampler=sampler, mesh=mesh)

    def init_states(self, key, prog, n_lanes, evidence_values):
        return init_fg_states(key, prog, n_lanes, evidence_values)

    def clamp_states(self, prog, x, evidence_values):
        """Re-pin the clamped spins of existing (B, n) states (temporal
        warm start)."""
        if not prog.observed:
            return x
        ev = jnp.asarray(evidence_values, jnp.int32)
        if ev.ndim == 1:
            ev = jnp.broadcast_to(ev[None], (x.shape[0], len(prog.observed)))
        return x.at[:, jnp.asarray(prog.observed, jnp.int32)].set(ev)

    def assignment_energy(self, model, assignment) -> float:
        """Factor-graph energy (unary + each edge's directed table once)
        of a full assignment over every node — the MAP objective; for an
        Ising model this is the Hamiltonian up to its constant."""
        fg = (model.to_factor_graph()
              if isinstance(model, IsingModel) else model)
        x = np.array([int(assignment[v]) for v in range(fg.n_vars)])
        e = float(np.asarray(fg.unary)[np.arange(fg.n_vars), x].sum())
        if len(fg.edges):
            a, b = fg.edges[:, 0], fg.edges[:, 1]
            e += float(np.asarray(fg.pair)[
                np.arange(len(fg.edges)), x[a], x[b]].sum())
        return e

    def state_spec(self, mesh):
        return serve_fg_state_spec(mesh)

    def n_vars(self, prog) -> int:
        return prog.n_vars

    def max_card(self, prog) -> int:
        return prog.max_card

    def var_card(self, prog, v: int) -> int:
        return int(prog.fg.card[v])

    def var_name(self, model, v: int) -> str:
        return model.var_name(v)

    def n_free(self, prog) -> int:
        return prog.n_free

    def ky_lanes(self, prog) -> int:
        """Knuth-Yao walks one chain runs per sweep: each colour's nodes."""
        return sum(len(plan.nodes) for plan in prog.plans)

    def plan_salt(self, model):
        """Sparse plans are shaped by the graph itself (coloring, degree
        buckets), so the cache key folds a content fingerprint — a
        re-registered graph under the same name must miss.  Cached on
        the model object: hashing a million-spin graph once is fine,
        once per query is not."""
        salt = getattr(model, "_plan_salt", None)
        if salt is None:
            salt = graph_fingerprint(model)
            model._plan_salt = salt
        return salt

    # -- plan persistence: packing plans is cheap numpy, nothing to skip
    def persisted_path(self, directory, name, pattern, model, *,
                       k, quantize_cpt_bits):
        return None

    def load_persisted(self, path, model):  # pragma: no cover - unused
        return None

    def save_persisted(self, path, prog):  # pragma: no cover - unused
        pass


BAYESNET_FAMILY = BayesNetFamily()
MRF_FAMILY = MrfFamily()
ISING_FAMILY = IsingFamily()


def family_of(model):
    """The adapter serving a registered model — or a request.

    Dispatches on the model's type, or, for a :class:`repro.serve.query.
    Request`, on the *evidence payload*: a scribble mask
    (:class:`MrfQuery`) routes to the MRF family, a spin clamp
    (:class:`IsingQuery`) to the sparse Ising family, and a node-
    evidence mapping (:class:`Query`) to the Bayesian-network family —
    the same convention the JSON request-file parser uses.

    Example::

        family_of(networks.asia()).kind          # 'bayesnet'
        family_of(networks.penguin_task(8, 8)[0]).kind   # 'mrf'
        family_of(networks.ising_torus(8)).kind          # 'ising'
        family_of(MrfQuery("penguin")).kind              # 'mrf'
    """
    if isinstance(model, BayesNet):
        return BAYESNET_FAMILY
    if isinstance(model, MRFGrid):
        return MRF_FAMILY
    if isinstance(model, (IsingModel, FactorGraph)):
        return ISING_FAMILY
    from repro.serve.query import IsingQuery, MrfQuery, Query
    if isinstance(model, MrfQuery):
        return MRF_FAMILY
    if isinstance(model, IsingQuery):
        return ISING_FAMILY
    if isinstance(model, Query):
        return BAYESNET_FAMILY
    raise TypeError(
        f"no serving family for {type(model).__name__!r} "
        f"(expected BayesNet, MRFGrid, IsingModel, FactorGraph, or a "
        f"Query/MrfQuery/IsingQuery request)")
