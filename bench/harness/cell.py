"""One run of one cell: set up the served path, drive it for the
window through the HTTP front end, read the metrics, check the answers.

The system under test is what users call: ``serve/server.py``'s front
end (``start_in_thread``) over a one-worker ``WorkerPool``, its
``AdmissionQueue``, ``PosteriorEngine`` with the sampler it picks when
none is given, the round runners and the KY sampler tail.  Load comes
from a child process (``loadgen.py``) that never touches JAX.

Set-up (``setup_s``) runs from the start of the process to the start of
the window: imports, the model, the engine's own warm-up of every
pattern at every group shape this cell's queue can form
(``AdmissionQueue.warm``), and, where the cell's settings ask for it, a
short burst of the cell's own traffic (another rng stream) so that
backfill and the front end have run once before the window.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import subprocess
import sys
import threading
import time

from harness import (correct, layers, plugins, readers, spec, traffic,
                     trace, window)
from harness.spec import BENCH

RUNS = BENCH / "_runs"
LOADGEN = BENCH / "harness" / "loadgen.py"
LEAD_S = 1.0          # window opens this long after the load generator is up
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(SystemExit):
    """The run needs an accelerator JAX does not have."""


class Run:
    """What a metric reader sees (``bench/metrics/<name>.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def due(self):
        return window.due(self.records, self.t0, self.t1)

    def spans(self, name: str):
        """Engine telemetry spans ``(t0, t1, args)`` named ``name`` that
        end inside the window, on the monotonic clock."""
        out = []
        for ev in self.events:
            if ev.get("ph") != "X" or ev.get("name") != name:
                continue
            s0 = self.tel_t0 + ev["ts"] * 1e-6
            s1 = s0 + ev["dur"] * 1e-6
            if self.t0 <= s1 <= self.t1:
                out.append((s0, s1, ev.get("args", {})))
        return out

    def device(self):
        if not self.trace or not self.trace["devices"]:
            return None
        return self.trace["devices"][0]


def _devices(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"bench: no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def _drive(port: int, sched: dict, seconds: float, grace: float,
           tag: str) -> tuple[float, float, subprocess.Popen, str]:
    """Start the load generator and wait until it is ready (Python up,
    its client imported); returns (t_start, t_end, proc, spec path).
    The window opens ``LEAD_S`` after it said so."""
    RUNS.mkdir(exist_ok=True)
    path = str(RUNS / f"{tag}-{os.getpid()}.json")
    with open(path, "w") as f:
        json.dump({"port": port, "seconds": seconds, "grace": grace,
                   "schedule": sched}, f)
    proc = subprocess.Popen([sys.executable, str(LOADGEN), path],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    if proc.stdout.readline().strip() != "ready":
        proc.kill()
        proc.wait()
        raise RuntimeError(f"load generator failed: {proc.stderr.read()}")
    t_start = time.monotonic() + LEAD_S
    proc.stdin.write(f"{t_start!r}\n")
    proc.stdin.flush()
    return t_start, t_start + seconds, proc, path


def _collect(proc, path, timeout: float) -> dict:
    try:
        out, err = proc.communicate(timeout=timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        os.unlink(path)
    if proc.returncode != 0:
        raise RuntimeError(f"load generator failed: {err[-2000:]}")
    return json.loads(out.strip().splitlines()[-1])


def _sleep_until(t: float) -> None:
    while (dt := t - time.monotonic()) > 0:
        time.sleep(min(dt, 0.05))


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace_on: bool,
             t_proc0: float, *, allow_cpu: bool = False,
             rate_qps: float | None = None, engine_kw: dict | None = None,
             keep: list | None = None, warm: bool = True,
             say=print) -> dict:
    """One run; returns the result line's object (see ``run.py``).
    ``rate_qps`` overrides an open mix's rate and ``engine_kw`` the
    engine's settings (``bench/control.py``: the knee sweep and the
    precision control); ``keep``, when given, receives the :class:`Run`
    the readers saw; ``warm=False`` skips the warm-up (readings of
    ``correct`` only: the window then compiles)."""
    devs = _devices(cell.chips, allow_cpu)
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from repro.serve.engine import PosteriorEngine
    from repro.serve.protocol import parse_wire_request
    from repro.serve.server import start_in_thread
    from repro.serve.telemetry import Telemetry
    from repro.serve.worker import WorkerPool

    config, st = cell.config, cell.settings
    fam = plugins.load("families", config["family"]).Family(config)
    model = fam.program()
    tel = Telemetry()
    kw = dict(config["engine"], **(engine_kw or {}))
    engines = []

    def factory(name):
        eng = PosteriorEngine({config["name"]: model}, telemetry=tel,
                              seed=seed % (2 ** 31 - 1), **kw)
        engines.append(eng)
        return eng

    pool = WorkerPool(factory, 1, queue_kwargs=config.get("queue"))
    front = start_in_thread(pool, port=0)
    queue = pool.workers["w0"].queue
    say(f"bench: {cell.name} on {devs[0].device_kind} x{len(devs)}, "
        f"sampler {engines[0].sampler}, seed {seed}, {seconds}s window")
    grace = float(st.get("grace_s", 60.0))
    try:
        t = time.monotonic()
        if warm:
            queue.warm([parse_wire_request(w)[0]
                        for w in traffic.probes(fam, cell.traffic)])
        say(f"bench: engine warm-up {time.monotonic() - t:.1f}s")
        warm_s = float(st.get("warmup_traffic_s", 0.0)) if warm else 0.0
        if warm_s:
            t = time.monotonic()
            sched = traffic.schedule(fam, cell.traffic, seed, warm_s,
                                     traffic.WARMUP, rate_qps=rate_qps)
            _, _, proc, path = _drive(front.port, sched, warm_s, grace,
                                      "warmup")
            _collect(proc, path, warm_s + grace + 120)
            say(f"bench: traffic warm-up {time.monotonic() - t:.1f}s")

        sched = traffic.schedule(fam, cell.traffic, seed, seconds,
                                 rate_qps=rate_qps)
        compiles = []
        armed = threading.Event()

        def on_event(event, duration, **_):
            if event == COMPILE_EVENT and armed.is_set():
                compiles.append(time.monotonic())

        jax.monitoring.register_event_duration_secs_listener(on_event)
        t0, t1, proc, path = _drive(front.port, sched, seconds, grace,
                                    "window")
        setup_s = t0 - t_proc0
        trace_dir = str(RUNS / f"trace-{os.getpid()}")
        if trace_on:
            jax.profiler.start_trace(trace_dir)
        _sleep_until(t0)
        armed.set()
        with jax.profiler.TraceAnnotation(trace.MARKER):
            mark = time.monotonic()
        _sleep_until(t1)
        armed.clear()
        if trace_on:
            jax.profiler.stop_trace()
        done = _collect(proc, path, seconds + grace + 120)
        records, t_stop = done["records"], done["t_done"]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        events = tel.events()
        exhausted = sum(1 for r in records if r.get("exhausted"))
        if exhausted:
            say(f"bench: WARNING {exhausted} callers ran out of requests")
        records = [r for r in records if "t_sched" in r]
    finally:
        front.stop_thread()
        pool.close(drain=False, timeout=120)
    run = Run(cell=cell, config=config, seed=seed, seconds=seconds, t0=t0,
              t1=t1, t_stop=t_stop, records=records, setup_s=setup_s,
              events=events, tel_t0=tel._t0, trace=None,
              compiles=len(compiles), sched=sched,
              device_kind=devs[0].device_kind)
    if trace_on:
        run.trace = trace.load(trace_dir, mark)
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(f"bench: trace lines {json.dumps(run.trace['lines'])}")
    del pool, engines, queue, front
    gc.collect()
    jax.clear_caches()

    due = run.due()
    answered = [r for r in due if window.answered(r)]
    items = [(traffic.wire_of(sched, r), r["answer"]) for r in answered]
    t = time.monotonic()
    verdict = correct.compare(
        config, fam.data, items, n_due=len(due),
        n_failed=len(due) - len(answered), limits=st.get("limits", {}),
        ref_settings=st.get("reference", {}), seed=seed % (2 ** 31 - 1))
    say(f"bench: reference comparison {time.monotonic() - t:.1f}s")
    late = window.lateness_ms(records)
    say(f"bench: {len(due)} due, {len(answered)} answered, window "
        f"compiles {run.compiles}, generator late p50 {late['p50']:.3f} ms "
        f"max {late['max']:.3f} ms")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": verdict["correct"], "attempted": len(due),
              "failed": len(due) - len(answered), "device": dev}
    chosen = cell.per_layer if trace_on else cell.end_to_end
    result["metrics"] = readers.read_all(chosen, run)
    if trace_on and run.device() is not None:
        d0 = run.device()
        busy = layers.busy_intervals(d0)
        end = layers.traced_end(run, d0)
        dev["busy_s"] = trace.busy_s(busy, t0, end)
        dev["window_s"] = end - t0
        host = [("round", a, b) for a, b, _ in run.spans("round")]
        result["breakdown"] = {
            "device_ops": trace.top_ops(d0["ops"] or busy, t0, end),
            "idle_gaps": trace.idle_gaps(busy, t0, end, host)}
    result["checks"] = verdict["numbers"]
    if keep is not None:
        keep.append(run)
    return result
