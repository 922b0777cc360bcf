"""Bytes the checkerboard Gibbs sweep needs, from shapes alone, and the
chip's peaks.

The count is the least HBM traffic of the algorithm, whatever sampler
implements the update.  Per half-sweep (one colour) over ``lanes``
chains of an ``h`` x ``w`` grid with ``labels`` labels:

* the label state read whole (neighbours), int32: ``lanes*h*w*4``
* the updated colour written, int32: ``lanes*h*w/2*4``
* one 32-bit random word per updated site: ``lanes*h*w/2*4``
* the updated colour's unary energies, float32: ``h*w/2*labels*4``
* the pairwise table, float32: ``labels*labels*4``
* the clamp mask, one byte a site: ``h*w``

and per sweep, the round's accumulators read and written once:

* one-hot label counts, int32: ``2*lanes*h*w*labels*4``
* the first and second moments, float32: ``2*2*lanes*h*w*4``
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (known: {sorted(table)})")
    return table[device_kind]


def half_sweep_bytes(lanes: int, h: int, w: int, labels: int) -> int:
    sites = h * w
    return (lanes * sites * 4 + lanes * sites // 2 * 4
            + lanes * sites // 2 * 4 + sites // 2 * labels * 4
            + labels * labels * 4 + sites)


def sweep_bytes(lanes: int, h: int, w: int, labels: int) -> int:
    sites = h * w
    return (2 * half_sweep_bytes(lanes, h, w, labels)
            + 2 * lanes * sites * labels * 4 + 2 * 2 * lanes * sites * 4)


def round_bytes(lanes: int, h: int, w: int, labels: int, sweeps: int) -> int:
    return sweeps * sweep_bytes(lanes, h, w, labels)
