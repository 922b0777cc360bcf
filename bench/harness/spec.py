"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell is one entry of ``workloads``.  Its configuration, traffic mix
and settings live in files of their own, so a later change adds a cell
or a metric by adding files only:

* ``bench/configs/<config>.json``   - model, engine, evidence patterns,
  and the names of its ``family`` and ``reference``
* ``bench/families/<family>.py``    - builds a family's model and requests
* ``bench/reference/<reference>.py`` - the plain reference
* ``bench/traffic/<traffic>.json``  - the mix, read by ``traffic.py``
* ``bench/workloads/<cell>.json``   - run settings and correctness limits
* ``bench/metrics/<metric>.py``     - one reader per metric
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    settings: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    run_seconds: int


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def metrics_of(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics a cell reports: those that
    list it under ``workloads``; a metric without that key is reported
    in every cell (per-layer: every cell that reports what it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (cell in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load(root / "BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    e2e, per = metrics_of(bench, name)
    return Cell(
        name=name, chips=int(wl["chips"]),
        config=_load(root / cfg["file"]),
        traffic=_load(root / "bench" / "traffic" / f"{wl['traffic']}.json"),
        settings=_load(root / "bench" / "workloads" / f"{name}.json"),
        end_to_end=e2e, per_layer=per,
        run_seconds=int(bench["run_seconds"]))
