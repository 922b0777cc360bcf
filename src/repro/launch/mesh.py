"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state).  Single pod: (16, 16) = 256 chips as
("data", "model"); multi-pod: (2, 16, 16) = 512 chips with the leading
"pod" axis carrying only data parallelism (cross-pod traffic = one
gradient all-reduce per step — DESIGN.md §6).

``make_serve_mesh`` builds the 1D/2D mesh the posterior query service
(:mod:`repro.serve`) shards its chain-lane batches over.
"""
from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

SERVE_AXES = ("batch", "model")


def parse_mesh_shape(spec: str) -> tuple[int, ...]:
    """Parse a CLI mesh shape: ``"4"`` -> (4,), ``"2x2"`` -> (2, 2)."""
    try:
        shape = tuple(int(s) for s in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"bad mesh shape {spec!r}: expected N or RxC") from None
    if not 1 <= len(shape) <= 2 or any(s < 1 for s in shape):
        raise ValueError(f"bad mesh shape {spec!r}: expected N or RxC")
    return shape


def force_host_devices(n: int, env: dict | None = None) -> None:
    """Make the CPU backend present ``n`` fake devices.

    Appends ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``
    in ``env`` (default ``os.environ``), preserving any flags already
    set.  The device count is fixed at backend init, so this must run
    before the target process's first jax *use* — importing jax (or this
    module) is fine, creating an array is not.
    """
    env = os.environ if env is None else env
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}").strip()


def make_serve_mesh(shape: tuple[int, ...] | None = None, *,
                    devices=None) -> Mesh:
    """1D ``("batch",)`` or 2D ``("batch", "model")`` mesh for repro.serve.

    The leading "batch" axis carries the engine's chain-lane axis
    (n_queries * chains_per_query); an optional trailing "model" axis
    lets very large flat log-CPT banks shard instead of replicate (see
    ``repro.sharding.specs.serve_cpt_spec``).  Defaults to all visible
    devices on a 1D batch mesh.
    """
    devices = list(jax.devices() if devices is None else devices)
    if shape is None:
        shape = (len(devices),)
    if not 1 <= len(shape) <= 2:
        raise ValueError(f"serve mesh must be 1D or 2D, got {shape}")
    n = int(np.prod(shape))
    if len(devices) < n:
        raise RuntimeError(
            f"serve mesh {shape} needs {n} devices, have {len(devices)} — "
            f"on CPU run under XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n}")
    return Mesh(np.asarray(devices[:n]).reshape(shape),
                SERVE_AXES[:len(shape)])


def mesh_fingerprint(mesh: Mesh | None):
    """Hashable identity of a mesh for plan-cache keys: (shape, axes,
    device ids).

    ``None`` for the single-device (no-mesh) path, so single-device plans
    and sharded plans can never collide in one cache — a runner compiled
    with sharding constraints for one mesh layout is wrong for another.
    Device ids matter too: same-shape meshes over *different* devices
    must not share a runner (its closed-over CPT bank and constraints
    are pinned to the mesh it was built for).
    """
    if mesh is None:
        return None
    return (tuple(mesh.devices.shape), tuple(mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    return jax.make_mesh(
        shape, axes, devices=devices[:n],
        axis_types=(AxisType.Auto,) * len(axes),
    )


def make_pgm_mesh(rows: int = 4, cols: int = 4) -> Mesh:
    """The AIA-analogue 2D core mesh for distributed MRF Gibbs (C3)."""
    n = rows * cols
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"pgm mesh needs {n} devices, have {len(devices)}")
    return jax.make_mesh((rows, cols), ("row", "col"),
                         devices=devices[:n],
                         axis_types=(AxisType.Auto, AxisType.Auto))
