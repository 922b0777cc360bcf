"""Runs of the harness on the CPU: it refuses to measure without a TPU,
and, with the look for a chip skipped, a broken timed path comes out
not correct under the cells' own limits."""
import os
import shutil
import subprocess
import sys
import time

import pytest

from harness import cell as cell_mod
from harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "asia.steady",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_benchmark_directory_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("_runs", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _tiny(name):
    c = spec.load_cell(name)
    if c.config["family"] == "mrf":
        c.config = dict(c.config, height=24, width=20,
                        patterns=dict(c.config["patterns"], strokes=3))
        c.traffic = dict(c.traffic, query_sites=[8, 64])
        c.settings = dict(c.settings, reference=dict(
            chains=8, burn=50, sweeps=200), grace_s=60.0)
    else:
        c.config = dict(c.config, patterns=c.config["patterns"][:2],
                        engine=dict(c.config["engine"], chains_per_query=8),
                        queue=dict(max_wait_ms=10.0, max_group_lanes=16))
        c.traffic = dict(c.traffic, rate_qps=4.0)
        c.settings = dict(c.settings, warmup_traffic_s=0.0)
    return c


@pytest.mark.parametrize("name", ["asia.steady", "penguin.scribble"])
@pytest.mark.parametrize("variant", ["sound", "stuck", "altered"])
def test_broken_path_is_not_correct(name, variant):
    import control

    c = _tiny(name)
    with control.planted(variant):
        res = cell_mod.run_cell(c, 2**31 + 11, 4.0, False, time.monotonic(),
                                allow_cpu=True, say=lambda m: None)
    gap = res["checks"]["gap_mean"]
    assert res["attempted"] > 0 and gap["limit"] is not None
    assert res["correct"] is (variant == "sound"), gap


@pytest.mark.parametrize("name", ["asia.steady", "penguin.scribble"])
def test_precision_control_is_not_correct(name):
    """The control, the program's own path with 8-bit Knuth-Yao weights
    for the 14 it runs with, at asia's own configuration and load and on
    a 100x67 Penguin: the pooled rare-outcome number fails where one
    answer's error cannot."""
    c = spec.load_cell(name)
    if c.config["family"] == "mrf":
        c.config = dict(c.config, height=100, width=67)
        c.traffic = dict(c.traffic, query_sites=[512, 2048])
    c.settings = dict(c.settings, warmup_traffic_s=0.0)
    res = cell_mod.run_cell(c, 2**31 + 11, 20.0, False, time.monotonic(),
                            allow_cpu=True, engine_kw={"k": 8},
                            say=lambda m: None)
    checks = res["checks"]
    assert res["failed"] == 0 and checks["malformed"]["value"] == 0
    assert checks["gap_mean"]["value"] <= checks["gap_mean"]["limit"]
    assert checks["rare_gap"]["value"] > checks["rare_gap"]["limit"]
    assert res["correct"] is False
