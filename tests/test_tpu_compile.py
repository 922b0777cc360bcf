"""The fused Gibbs kernel compiles for a TPU v5e chip.

Every other test runs the kernel in the Pallas interpreter, which accepts
ops the TPU compiler (Mosaic) refuses.  These tests compile
``fused_gibbs_sample`` with ``interpret=False`` for a v5e chip that is
described, not attached, at the shapes the MRF round runner hands it:
the Penguin grid (500x333 sites, 2 labels) and the Art grid (288x384
sites, 16 labels), each over 8 chain lanes, on one chip and with the
lanes sharded over a 4-chip serve mesh.  Nothing runs; the compile
raises what the chip's compiler would raise, including a kernel tile
that overflows VMEM, a program that overflows HBM, or a kernel left for
XLA to partition.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding)

from repro.configs.aia_paper import ART, PENGUIN
from repro.kernels.fused_sweep import fused_gibbs_sample

V5E_HBM_BYTES = 16 * 10 ** 9   # Google Cloud "TPU v5e": 16 GB HBM per chip
CHAIN_LANES = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent
    # cache but not read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _compile(cfg, lane_sharding, key_sharding, mesh=None):
    lanes = CHAIN_LANES * cfg.height * cfg.width
    args = (jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=key_sharding),
            jax.ShapeDtypeStruct((lanes, cfg.n_labels), jnp.float32,
                                 sharding=lane_sharding),
            jax.ShapeDtypeStruct((lanes,), jnp.int32,
                                 sharding=lane_sharding))

    def sample(key, logw, card):
        return fused_gibbs_sample(key, logw, card, k=cfg.k, interpret=False,
                                  mesh=mesh)

    compiled = jax.jit(sample).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES
    return used


@pytest.mark.parametrize("cfg", [PENGUIN, ART], ids=lambda c: c.name)
def test_fused_kernel_compiles_for_v5e(topo, cfg):
    one_chip = SingleDeviceSharding(topo.devices[0])
    _compile(cfg, one_chip, one_chip)


@pytest.mark.parametrize("cfg", [PENGUIN, ART], ids=lambda c: c.name)
def test_fused_kernel_compiles_on_4_chip_serve_mesh(topo, cfg):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("batch",))
    per_device = _compile(cfg, NamedSharding(mesh, P("batch")),
                          NamedSharding(mesh, P()), mesh=mesh)
    single = _compile(cfg, SingleDeviceSharding(topo.devices[0]),
                      SingleDeviceSharding(topo.devices[0]))
    assert per_device < single   # each chip holds its own lanes only
