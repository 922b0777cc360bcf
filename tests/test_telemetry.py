"""Serving-stack telemetry: metrics registry exports (Prometheus text
exposition, JSON snapshot), Chrome/Perfetto trace shape + per-query
span tiling, null-recorder default, engine.stats(), and the
answer_batch-vs-queued metrics identity."""
import json
import re

import numpy as np
import pytest

from repro.pgm import networks
from repro.serve import (
    AdmissionQueue, PosteriorEngine, Query, Telemetry, lifecycle_breakdown)
from repro.serve.telemetry import (
    NULL, Histogram, MetricsRegistry, NullTelemetry, log_bins)

RESULT_TIMEOUT = 300.0


def _registry():
    return {"sprinkler": networks.sprinkler()}


def _engine(**kw):
    kw.setdefault("chains_per_query", 8)
    kw.setdefault("burn_in", 16)
    kw.setdefault("max_rounds", 4)
    kw.setdefault("seed", 0)
    return PosteriorEngine(_registry(), **kw)


def _traffic(n=4):
    rng = np.random.default_rng(0)
    out = []
    for _ in range(n):
        out.append(Query("sprinkler", {"wetgrass": int(rng.integers(2))},
                         ("rain",), n_samples=256))
    return out


# -- metrics primitives ----------------------------------------------------
class TestMetricsPrimitives:
    def test_log_bins_cover_range_and_are_increasing(self):
        bins = log_bins(1e-3, 1e2, per_decade=4)
        assert bins[0] == pytest.approx(1e-3)
        assert bins[-1] >= 1e2
        assert all(a < b for a, b in zip(bins, bins[1:]))

    def test_log_bins_reject_bad_range(self):
        with pytest.raises(ValueError):
            log_bins(1.0, 1.0)
        with pytest.raises(ValueError):
            log_bins(0.0, 1.0)

    def test_histogram_buckets_le_semantics(self):
        h = Histogram(bins=(1.0, 10.0))
        for v in (0.5, 1.0, 5.0, 100.0):
            h.observe(v)
        # le-semantics: 1.0 lands in the le=1.0 bucket, 100 in +Inf
        assert h.counts == [2, 1, 1]
        assert h.count == 4 and h.sum == pytest.approx(106.5)
        assert 0.0 < h.quantile(0.5) <= 10.0
        assert Histogram(bins=(1.0,)).quantile(0.5) == 0.0  # empty

    def test_registry_label_children_and_kind_clash(self):
        reg = MetricsRegistry()
        reg.counter("retired", reason="a").inc()
        reg.counter("retired", reason="b").inc(2)
        assert reg.counter("retired", reason="b").value == 2
        with pytest.raises(ValueError):
            reg.gauge("retired")
        snap = reg.snapshot()
        assert snap["retired{reason=a}"] == 1
        assert snap["retired{reason=b}"] == 2


PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"               # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""     # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" -?[0-9.e+\-inf]+$")                      # value


class TestPrometheusExposition:
    def test_parses_line_by_line(self):
        reg = MetricsRegistry()
        reg.counter("serve_queries_total", "queries").inc(3)
        reg.gauge("serve_depth").set(2.5)
        h = reg.histogram("serve_wait_seconds", bins=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        text = reg.prometheus()
        assert text.endswith("\n")
        kinds = {}
        for line in text.splitlines():
            assert line, "no blank lines in exposition"
            if line.startswith("# HELP"):
                continue
            if line.startswith("# TYPE"):
                _, _, name, kind = line.split()
                kinds[name] = kind
                continue
            assert PROM_LINE.match(line), line
        assert kinds == {"serve_queries_total": "counter",
                         "serve_depth": "gauge",
                         "serve_wait_seconds": "histogram"}

    def test_histogram_buckets_cumulative_with_inf(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", bins=(0.1, 1.0))
        for v in (0.05, 0.5, 2.0):
            h.observe(v)
        lines = reg.prometheus().splitlines()
        buckets = [ln for ln in lines if ln.startswith("lat_bucket")]
        counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
        assert counts == sorted(counts), "cumulative bucket counts"
        assert 'le="+Inf"' in buckets[-1] and counts[-1] == 3
        assert "lat_count 3" in lines
        assert any(ln.startswith("lat_sum") for ln in lines)


# -- tracer ----------------------------------------------------------------
class TestTracer:
    def test_chrome_trace_round_trips_json(self):
        tel = Telemetry()
        tid = tel.track("query-0")
        from repro.serve.telemetry import monotonic
        t0 = monotonic()
        tel.complete("query", tid, t0, t0 + 0.25, reason="rhat+ess")
        tel.complete("wait", tid, t0, t0 + 0.1)
        tel.instant("retired", tid, reason="rhat+ess")
        tel.sample("queue_depth", 3)
        doc = json.loads(json.dumps(tel.chrome_trace()))
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert {"X", "i", "C", "M"} <= {e["ph"] for e in evs}
        for e in evs:
            if e["ph"] in ("X", "i", "C"):
                assert e["ts"] >= 0.0
            if e["ph"] == "X":
                assert e["dur"] >= 0.0 and isinstance(e["tid"], int)
        q = next(e for e in evs if e["name"] == "query")
        w = next(e for e in evs if e["name"] == "wait")
        # nesting by time containment on the same track
        assert w["tid"] == q["tid"]
        assert q["ts"] <= w["ts"]
        assert w["ts"] + w["dur"] <= q["ts"] + q["dur"] + 1e-6

    def test_null_recorder_is_inert(self):
        tel = NullTelemetry()
        assert tel.enabled is False and NULL.enabled is False
        assert tel.track("x") == 0
        tel.complete("a", 0, 0.0, 1.0)
        tel.instant("b", 0)
        tel.count("c")
        tel.observe("d", 1.0)
        assert tel.events() == []
        assert tel.chrome_trace()["traceEvents"] == []
        assert tel.metrics_snapshot() == {} and tel.prometheus() == ""

    def test_write_trace_and_metrics(self, tmp_path):
        tel = Telemetry()
        tel.count("serve_q_total", 2)
        tel.write_trace(str(tmp_path / "t.json"))
        with open(tmp_path / "t.json") as f:
            assert "traceEvents" in json.load(f)

    def test_lifecycle_breakdown_attributes_phases(self):
        evs = [{"name": "query", "ph": "X", "ts": 0.0, "dur": 250_000.0},
               {"name": "wait", "ph": "X", "ts": 0.0, "dur": 150_000.0},
               {"name": "plan", "ph": "X", "ts": 150_000.0, "dur": 80_000.0},
               {"name": "service", "ph": "X", "ts": 230_000.0,
                "dur": 20_000.0},
               {"name": "retired", "ph": "i", "ts": 250_000.0}]
        bd = lifecycle_breakdown(evs)
        assert bd["n_queries"] == 1
        assert bd["e2e_p50_ms"] == pytest.approx(250.0)
        assert bd["wait"]["p50_ms"] == pytest.approx(150.0)
        phase_sum = sum(bd[p]["total_s"] for p in ("wait", "plan", "service"))
        assert phase_sum == pytest.approx(bd["e2e_total_s"])


# -- engine integration ----------------------------------------------------
class TestEngineTelemetry:
    def test_default_engine_records_nothing(self):
        engine = _engine()
        engine.answer_batch(_traffic(2))
        assert engine.telemetry is NULL
        assert engine.telemetry.events() == []

    def test_stats_before_any_traffic(self):
        engine = _engine()
        st = engine.stats()
        # hit_rate must be 0.0 (not raise) with zero lookups
        assert st["plan_cache"]["hit_rate"] == 0.0
        assert st["plan_cache"]["hits"] == 0
        assert st["queue"] is None
        assert "metrics" not in st

    def test_spans_tile_e2e_latency(self):
        engine = _engine(telemetry=Telemetry())
        engine.answer_batch(_traffic(4))
        evs = engine.telemetry.events()
        by_tid = {}
        for e in evs:
            if e.get("ph") == "X" and e["name"] in (
                    "query", "wait", "plan", "service"):
                by_tid.setdefault(e["tid"], {})[e["name"]] = e
        queries = [v for v in by_tid.values() if "query" in v]
        assert len(queries) == 4
        for spans in queries:
            assert {"wait", "plan", "service"} <= set(spans)
            total = sum(spans[p]["dur"]
                        for p in ("wait", "plan", "service"))
            e2e = spans["query"]["dur"]
            # acceptance bound is 5%; construction makes it ~exact
            assert total == pytest.approx(e2e, rel=0.05)
            # shared boundaries: spans nest inside the umbrella
            assert spans["wait"]["ts"] == pytest.approx(
                spans["query"]["ts"], abs=1.0)

    def test_retirement_reason_and_metrics(self):
        engine = _engine(telemetry=Telemetry())
        results = engine.answer_batch(_traffic(3))
        evs = engine.telemetry.events()
        retired = [e for e in evs if e["name"] == "retired"]
        assert len(retired) == 3
        valid = {"rhat+ess", "rhat", "max-sweeps", "cancel"}
        assert {e["args"]["reason"] for e in retired} <= valid
        snap = engine.telemetry.metrics_snapshot()
        n_retired = sum(v for k, v in snap.items()
                        if k.startswith("serve_retired_total"))
        assert n_retired == 3
        assert snap["serve_rounds_total"] > 0
        assert "serve_e2e_seconds" not in snap  # no queue attached
        # stats() merges cache + metrics
        st = engine.stats()
        assert st["metrics"] == snap
        assert st["plan_cache"]["misses"] >= 1  # one compile per pattern
        assert all(r.converged or r.n_sweeps > 0 for r in results)

    def test_queued_metrics_match_answer_batch(self):
        """Deterministic counters (groups, rounds, sweeps, retirements)
        are identical whether the same traffic is caller-batched or
        flushed through the admission queue — the queue reroutes
        scheduling, never sampling."""
        traffic = _traffic(4)
        eng_a = _engine(telemetry=Telemetry())
        eng_a.answer_batch(traffic)

        eng_b = _engine(telemetry=Telemetry())
        queue = AdmissionQueue(eng_b, max_wait_ms=3_600_000.0,
                               max_group_lanes=8 * len(traffic))
        try:
            handles = [queue.submit(q) for q in traffic]
            queue.flush()
            for h in handles:
                h.result(timeout=RESULT_TIMEOUT)
        finally:
            queue.close()

        keys = ("serve_groups_total", "serve_rounds_total",
                "serve_sweeps_total", "serve_plan_cache_misses_total")
        snap_a = eng_a.telemetry.metrics_snapshot()
        snap_b = eng_b.telemetry.metrics_snapshot()
        for k in keys:
            assert snap_a[k] == snap_b[k], k
        retired = lambda s: {k: v for k, v in s.items()  # noqa: E731
                             if k.startswith("serve_retired_total")}
        assert retired(snap_a) == retired(snap_b)
        # queue-only counters exist only on the queued side
        assert snap_b["serve_queries_submitted_total"] == len(traffic)
        assert snap_b["serve_queries_finished_total{status=completed}"] \
            == len(traffic)
        assert snap_b["serve_e2e_seconds"]["count"] == len(traffic)
        # and the queue's stats surface through engine.stats()
        st = eng_b.stats()
        assert st["queue"]["submitted"] == len(traffic)
        assert st["queue"]["completed"] == len(traffic)

    def test_queued_trace_has_lifecycle_events(self):
        engine = _engine(telemetry=Telemetry())
        queue = AdmissionQueue(engine, max_wait_ms=50.0)
        try:
            h = queue.submit(_traffic(1)[0])
            h.result(timeout=RESULT_TIMEOUT)
        finally:
            queue.close()
        names = {e["name"] for e in engine.telemetry.events()}
        assert {"submit", "query", "wait", "plan", "service", "round",
                "retired", "deliver"} <= names
        bd = lifecycle_breakdown(engine.telemetry.events())
        assert bd["n_queries"] == 1
        phase_sum = sum(bd[p]["total_s"] for p in ("wait", "plan", "service"))
        assert phase_sum == pytest.approx(bd["e2e_total_s"], rel=0.05)


# -- in-program spans and counters -------------------------------------------
ROUND_CHILDREN = ("dispatch", "device_wait", "readback", "judge")
DISPATCHER_SPANS = ("await_work", "group_start", "deliver", "admit")


def _asia_engine(**kw):
    kw.setdefault("chains_per_query", 16)
    kw.setdefault("burn_in", 16)
    kw.setdefault("max_rounds", 6)
    kw.setdefault("seed", 0)
    return PosteriorEngine({"asia": networks.asia()}, telemetry=Telemetry(),
                           **kw)


def _asia_traffic():
    """Two evidence patterns, so the dispatcher starts two groups."""
    return ([Query("asia", {"smoke": v}, ("lung", "bronc"), n_samples=512)
             for v in (0, 1, 1)]
            + [Query("asia", {"xray": 1}, ("tub",), n_samples=512)])


class _RecordingQueue(AdmissionQueue):
    """Keeps every group run it starts, for the counters' arithmetic."""

    def __init__(self, *a, **k):
        self.runs = []
        super().__init__(*a, **k)

    def _group_run(self, name, pattern, batch):
        run = super()._group_run(name, pattern, batch)
        self.runs.append(run)
        return run


def _serve_through_queue(engine, traffic, **queue_kw):
    """Submit ``traffic``, flush, wait for every answer, close; returns
    the queue, the answers and the monotonic times of the first submit
    and of the return from ``close``.  One group per evidence pattern."""
    queue_kw.setdefault("max_wait_ms", 3_600_000.0)
    queue = _RecordingQueue(engine, **queue_kw)
    from repro.serve.telemetry import monotonic
    t_first = monotonic()
    handles = [queue.submit(q) for q in traffic]
    queue.flush()
    results = [h.result(timeout=RESULT_TIMEOUT) for h in handles]
    queue.close()
    return queue, results, t_first, monotonic()


def _spans(tel, names):
    """``(name, t0, t1, tid, args)`` of the complete events named in
    ``names``, on the monotonic clock."""
    out = []
    for e in tel.events():
        if e.get("ph") == "X" and e["name"] in names:
            a = tel._t0 + e["ts"] * 1e-6
            out.append((e["name"], a, a + e["dur"] * 1e-6, e["tid"],
                        e.get("args", {})))
    return out


def _covered(intervals, lo, hi):
    """Length of the union of ``(a, b)`` intervals clipped to [lo, hi]."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


@pytest.fixture(scope="module")
def served_asia():
    engine = _asia_engine()
    queue, results, t_first, t_closed = _serve_through_queue(
        engine, _asia_traffic())
    return engine, queue, results, t_first, t_closed


class TestEngineSpans:
    EPS = 1e-9

    def test_round_children_nest_and_tile_the_round(self, served_asia):
        tel = served_asia[0].telemetry
        rounds = _spans(tel, ("round",))
        children = _spans(tel, ROUND_CHILDREN)
        assert rounds and children
        total = covered = 0.0
        for _, a, b, tid, _ in rounds:
            mine = [(c0, c1) for _, c0, c1, ctid, _ in children
                    if ctid == tid and a - self.EPS <= c0 < b]
            assert all(c1 <= b + self.EPS for _, c1 in mine)
            assert len(mine) >= 3          # dispatch, device_wait, judge
            total += b - a
            covered += _covered(mine, a, b)
        assert covered >= 0.95 * total, covered / total
        # each retire lies inside a judge of its own group
        judges = _spans(tel, ("judge",))
        for _, r0, r1, tid, _ in _spans(tel, ("retire",)):
            assert any(jt == tid and j0 <= r0 and r1 <= j1 + self.EPS
                       for _, j0, j1, jt, _ in judges)

    def test_query_and_retire_share_qid(self, served_asia):
        tel = served_asia[0].telemetry
        queries = _spans(tel, ("query",))
        retires = _spans(tel, ("retire",))
        assert len(queries) == len(_asia_traffic()) == len(retires)
        for _, _, _, tid, args in queries:
            assert args["qid"] == tid
        assert ({args["qid"] for *_, args in queries}
                == {args["qid"] for *_, args in retires})
        for _, _, _, tid, args in _spans(tel, ("wait", "plan", "service")):
            if "qid" in args:
                assert args["qid"] == tid
        sites = {args["qid"]: args["sites"] for *_, args in retires}
        assert sorted(sites.values()) == [1, 2, 2, 2]

    def test_dispatcher_spans_tile_its_thread(self, served_asia):
        engine, queue, _, t_first, t_closed = served_asia
        tel = engine.telemetry
        own = _spans(tel, DISPATCHER_SPANS)
        assert {s[3] for s in own} == {queue.tel_tid}
        assert {"await_work", "group_start", "deliver"} <= {s[0] for s in own}
        spans = sorted((a, b) for _, a, b, *_ in
                       own + _spans(tel, ("round",)))
        for (_, b0), (a1, _) in zip(spans, spans[1:]):
            assert a1 >= b0 - self.EPS, "dispatcher spans overlap"
        life = t_closed - t_first
        assert _covered(spans, t_first, t_closed) >= 0.95 * life

    def test_counters_match_the_groups(self, served_asia):
        engine, queue, results, *_ = served_asia
        snap = engine.telemetry.metrics_snapshot()
        assert len(queue.runs) == 2
        assert snap["serve_site_updates_total"] == sum(
            r.bt * r.n_free * r.sweeps_done for r in queue.runs)
        assert snap["serve_random_bits_total"] == sum(
            r.bits for r in queue.runs)
        rounds = _spans(engine.telemetry, ("round",))
        assert sum(a["site_updates"] for *_, a in rounds) \
            == snap["serve_site_updates_total"]
        assert all(r.bits_per_sample > 0 for r in results)

    def test_ky_lanes_counter_adds_up_over_rounds(self, served_asia):
        # Bayesian network: each sweep walks every colour's nodes once
        engine, queue, *_ = served_asia
        snap = engine.telemetry.metrics_snapshot()
        rounds = _spans(engine.telemetry, ("round",))
        assert all("ky_lanes" in a for *_, a in rounds)
        assert sum(a["ky_lanes"] for *_, a in rounds) \
            == snap["serve_ky_lanes_total"]
        assert snap["serve_ky_lanes_total"] == sum(
            r.bt * sum(len(p.nodes) for p in r.prog.plans) * r.sweeps_done
            for r in queue.runs)

    def test_mrf_round_walks_one_packed_colour_per_half_step(self):
        # a 5 x 7 grid walks 5 x 8 lanes a chain a sweep: two half-steps
        # of 5 x 4, the pad column of the odd width included
        from repro.serve import MrfQuery

        mrf, truth = networks.penguin_task(h=5, w=7)
        mask = np.zeros((5, 7), bool)
        mask[2, 1:4] = True
        engine = PosteriorEngine({"p": mrf}, telemetry=Telemetry(),
                                 chains_per_query=4, burn_in=4,
                                 max_rounds=2, seed=0)
        traffic = [MrfQuery("p", mask, np.where(mask, truth, 0),
                            query_sites=((0, 0), (4, 6)), n_samples=64)]
        queue, *_ = _serve_through_queue(engine, traffic)
        rounds = _spans(engine.telemetry, ("round",))
        assert rounds
        for *_, a in rounds:
            assert a["ky_lanes"] == queue.runs[0].bt * 5 * 8 * a["sweeps"]
            assert a["site_updates"] == \
                queue.runs[0].bt * (35 - 3) * a["sweeps"]
        snap = engine.telemetry.metrics_snapshot()
        assert snap["serve_ky_lanes_total"] == sum(
            a["ky_lanes"] for *_, a in rounds)

    def test_one_group_bits_per_update_is_bits_per_sample(self):
        # every query retires at the cap in the same round, so each
        # answer's bits_per_sample covers the whole group run
        engine = _asia_engine(ess_target=1e12, max_rounds=4)
        traffic = [Query("asia", {"smoke": 1}, ("lung",), n_samples=512)
                   for _ in range(3)]
        queue, results, *_ = _serve_through_queue(
            engine, traffic, max_group_lanes=16 * len(traffic))
        assert len(queue.runs) == 1
        snap = engine.telemetry.metrics_snapshot()
        ratio = (snap["serve_random_bits_total"]
                 / snap["serve_site_updates_total"])
        for r in results:
            assert r.bits_per_sample == pytest.approx(ratio, rel=1e-12)


class TestSpanApi:
    def test_null_span_is_shared_and_records_nothing(self):
        tel = NullTelemetry()
        s = tel.span("a", 0)
        assert s is tel.span("b", 3, qid=1) is NULL.span("c", 0)
        with s, s:
            pass
        assert tel.events() == [] and NULL.events() == []

    def test_span_records_complete_event(self):
        tel = Telemetry()
        tid = tel.track("t")
        with tel.span("work", tid, qid=7):
            pass
        (ev,) = [e for e in tel.events() if e["ph"] == "X"]
        assert (ev["name"], ev["tid"], ev["args"]) == ("work", tid, {"qid": 7})
        assert ev["dur"] >= 0.0

    def test_span_lands_in_the_profiler_trace(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        tel = Telemetry()
        with jax.profiler.trace(str(tmp_path)):
            with tel.span("telemetry_probe_span", tel.track("t"), qid=3):
                pass
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        hits = [plane.name for plane in ProfileData.from_file(path).planes
                for line in plane.lines for ev in line.events
                if ev.name == "telemetry_probe_span"]
        assert hits and all(p.startswith("/host:") for p in hits)


@pytest.mark.parametrize("sampler", ["xla", "pallas"])
@pytest.mark.parametrize("family", ["bayesnet", "mrf"])
def test_round_program_carries_named_scopes(family, sampler):
    """The round program's op metadata names the sweep's parts, so a
    profile can attribute device time to them."""
    import jax
    import jax.numpy as jnp

    from repro.serve import MrfQuery

    lanes = 4
    if family == "bayesnet":
        engine = PosteriorEngine({"asia": networks.asia()}, sampler=sampler)
        query = Query("asia", {"smoke": 1}, ("lung",))
        x = jax.ShapeDtypeStruct((lanes, 8), jnp.int32)
    else:
        mrf, truth = networks.penguin_task(h=6, w=6)
        engine = PosteriorEngine({"p": mrf}, sampler=sampler)
        mask = np.zeros((6, 6), bool)
        mask[0, :] = True
        query = MrfQuery("p", mask, np.where(mask, truth, 0),
                         query_sites=((3, 3),))
        x = jax.ShapeDtypeStruct((lanes, 6, 6), jnp.int32)
    text = engine.round_runner(query).lower(
        jax.random.PRNGKey(0), x,
        jax.ShapeDtypeStruct((lanes,), jnp.int32)).as_text(debug_info=True)
    for scope in ("round", "weights", "ky_walk", "counts"):
        # a scope opens a location of its own, or a path segment
        assert f'"{scope}/' in text or f"/{scope}/" in text, scope


def _served_front_end():
    from repro.serve.server import start_in_thread
    from repro.serve.worker import WorkerPool

    registry = {"asia": networks.asia()}
    pool = WorkerPool(
        lambda name: PosteriorEngine(
            registry, telemetry=Telemetry(), chains_per_query=4,
            burn_in=8, max_rounds=4, seed=0), 1,
        queue_kwargs={"max_wait_ms": 5.0})
    return pool, start_in_thread(pool, port=0)


@pytest.mark.parametrize("transport", ["http", "ws"])
def test_front_end_request_spans(transport):
    """One query over HTTP or the WebSocket: one ``request`` span on the
    query's own track, around its engine ``query`` span and the front
    end's ``resolve`` and ``encode``."""
    from repro.serve.client import ServeClient

    pool, fe = _served_front_end()
    req = {"v": 2, "network": "asia", "evidence": {"smoke": 1},
           "query_vars": ["lung"], "n_samples": 64}
    try:
        client = ServeClient("127.0.0.1", fe.port)
        if transport == "http":
            resp = client.query(req)
        else:
            (resp,) = client.stream([req])
        assert "marginals" in resp
    finally:
        fe.stop_thread()
        pool.close(drain=False, timeout=10.0)
    tel = pool.workers["w0"].engine.telemetry
    (request,) = _spans(tel, ("request",))
    _, r0, r1, qid, args = request
    assert args == {"qid": qid, "transport": transport} and qid
    (query,) = _spans(tel, ("query",))
    assert query[4]["qid"] == qid
    for name in ("query", "resolve", "encode"):
        (span,) = [s for s in _spans(tel, (name,)) if s[3] == qid]
        assert r0 <= span[1] and span[2] <= r1, name
