"""Multi-device integration tests (subprocess with fake host devices —
smoke tests and benches keep seeing 1 device, per the task spec).

Covers: mesh Gibbs halo-exchange vs all-gather equivalence + collective
bytes, MRF pad-site masking on non-tile-multiple grids, the sharded
posterior query service, sharded train-step parity with single-device,
dry-run builders on a small mesh, checkpoint restore-with-reshard
(elastic restart).
"""

import pytest

from conftest import run_subprocess


@pytest.mark.slow
class TestMeshGibbs:
    def test_halo_vs_allgather_and_bytes(self):
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, re
from repro.launch.mesh import make_pgm_mesh
from repro.pgm.networks import penguin_task
from repro.pgm.mesh_gibbs import make_mesh_gibbs_step, shard_mrf
mesh = make_pgm_mesh(2, 2)
mrf, truth = penguin_task(h=32, w=24, beta=2.0)
key = jax.random.PRNGKey(0)
lab, u, pw, valid, _ = shard_mrf(mesh, mrf, n_chains=2, key=key)
step = make_mesh_gibbs_step(mesh, comm="halo")
for i in range(25):
    key, sub = jax.random.split(key)
    lab, bits = step(sub, lab, u, pw, valid)
acc = (np.asarray(lab)[0][:32,:24] == truth).mean()
assert acc > 0.9, acc

def cbytes(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    tot = {}
    for line in txt.splitlines():
        for p in ("all-gather", "collective-permute"):
            if f" {p}(" in line or f"{p}-start" in line:
                m = re.findall(r"(s32|u32|f32)\\[([\\d,]*)\\]", line.split("=",1)[1])
                if m:
                    dt, dims = m[0]
                    sz = 4
                    for d in dims.split(","):
                        if d: sz *= int(d)
                    tot[p] = tot.get(p, 0) + sz
    return tot
halo = cbytes(step, key, lab, u, pw, valid)
ag = cbytes(make_mesh_gibbs_step(mesh, comm="allgather"), key, lab, u, pw, valid)
assert halo.get("collective-permute", 0) > 0
assert ag.get("all-gather", 0) > 5 * halo.get("collective-permute", 1)
print("HALO_BYTES", json.dumps(halo) if (json := __import__("json")) else 0)
print("OK")
"""
        rc, out = run_subprocess(code, devices=4)
        assert rc == 0, out
        assert "OK" in out

    def test_mesh_matches_single_device_stats(self):
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_pgm_mesh
from repro.pgm.networks import penguin_task
from repro.pgm.gibbs import mrf_gibbs, init_labels
from repro.pgm.mesh_gibbs import make_mesh_gibbs_step, shard_mrf
mesh = make_pgm_mesh(2, 2)
mrf, truth = penguin_task(h=24, w=24)
key = jax.random.PRNGKey(0)
lab, u, pw, valid, _ = shard_mrf(mesh, mrf, n_chains=2, key=key)
step = make_mesh_gibbs_step(mesh)
for i in range(20):
    key, sub = jax.random.split(key)
    lab, _ = step(sub, lab, u, pw, valid)
acc_mesh = (np.asarray(lab)[0] == truth).mean()
lab1 = init_labels(jax.random.PRNGKey(5), mrf, 2)
lab1, _ = mrf_gibbs(jax.random.PRNGKey(6), lab1, jnp.asarray(mrf.unary),
                    jnp.asarray(mrf.pairwise), n_sweeps=20)
acc_sd = (np.asarray(lab1)[0] == truth).mean()
assert abs(acc_mesh - acc_sd) < 0.08, (acc_mesh, acc_sd)
print("OK", acc_mesh, acc_sd)
"""
        rc, out = run_subprocess(code, devices=4)
        assert rc == 0, out

    def test_pad_sites_do_not_bias_boundary_marginals(self):
        """Regression: on a grid that is NOT a tile multiple, pad sites
        are pinned to label 0 — without the validity mask they leak
        label-0 pairwise energy into real boundary sites.  A symmetric
        MRF (uniform unary + Potts) has exact marginal 0.5 everywhere;
        the old code pushed the boundary row/col to ~0.76 and the corner
        to ~0.85."""
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_pgm_mesh
from repro.pgm.graph import MRFGrid
from repro.pgm.gibbs import mrf_gibbs, init_labels
from repro.pgm.mesh_gibbs import make_mesh_gibbs_step, shard_mrf
h, w, beta = 17, 13, 0.6   # 17x13 on a 2x2 mesh -> pads to 18x14
mrf = MRFGrid.potts(np.zeros((h, w, 2), np.float32), beta=beta)
mesh = make_pgm_mesh(2, 2)
key = jax.random.PRNGKey(0)
lab, u, pw, valid, _ = shard_mrf(mesh, mrf, n_chains=64, key=key)
step = make_mesh_gibbs_step(mesh)
burn, keep = 40, 120
freq = np.zeros((h, w))
for i in range(burn + keep):
    key, sub = jax.random.split(key)
    lab, _ = step(sub, lab, u, pw, valid)
    if i >= burn:
        freq += (np.asarray(lab)[:, :h, :w] == 0).mean(0)
freq /= keep
# exact symmetric answer: 0.5 at every site incl. the padded boundary
assert abs(freq[-1, -1] - 0.5) < 0.06, freq[-1, -1]       # corner
assert abs(freq[-1, :].mean() - 0.5) < 0.05, freq[-1, :].mean()
assert abs(freq[:, -1].mean() - 0.5) < 0.05, freq[:, -1].mean()
# and the single-device reference agrees on the same boundary sites
lab1 = init_labels(jax.random.PRNGKey(5), mrf, 64)
ref = np.zeros((h, w))
k2 = jax.random.PRNGKey(6)
for i in range(burn + keep):
    k2, sub = jax.random.split(k2)
    lab1, _ = mrf_gibbs(sub, lab1, jnp.asarray(mrf.unary),
                        jnp.asarray(mrf.pairwise), n_sweeps=1)
    if i >= burn:
        ref += (np.asarray(lab1) == 0).mean(0)
ref /= keep
assert np.abs(freq - ref)[-1, :].max() < 0.06
assert np.abs(freq - ref)[:, -1].max() < 0.06
print("OK", freq[-1, -1], ref[-1, -1])
"""
        rc, out = run_subprocess(code, devices=4)
        assert rc == 0, out


@pytest.mark.slow
class TestShardedServe:
    def test_sharded_engine_matches_single_device_and_exact(self):
        """The tentpole acceptance check: on a forced-host 4-device mesh
        the engine's posterior answers equal the single-device engine's
        (same seeds -> identical lane streams) and match exact
        enumeration; the lane-padding path (chains not divisible by the
        mesh) stays within statistical tolerance of the oracle."""
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.launch.mesh import make_serve_mesh
from repro.pgm import networks
from repro.serve import PosteriorEngine, Query
mesh = make_serve_mesh((4,))
registry = {"sprinkler": networks.sprinkler(), "asia": networks.asia()}
kw = dict(chains_per_query=32, burn_in=64, seed=3)
qs = [Query("sprinkler", {"wetgrass": 1}, ("rain", "sprinkler"),
            n_samples=32768),
      Query("asia", {"smoke": 1}, ("lung", "bronc"), n_samples=32768)]
sharded = PosteriorEngine(registry, mesh=mesh, **kw).answer_batch(qs)
single = PosteriorEngine(registry, **kw).answer_batch(qs)
for rs, r1, q in zip(sharded, single, qs):
    bn = registry[q.network]
    exact = bn.marginals_exact(q.evidence)
    for var in rs.marginals:
        np.testing.assert_allclose(rs.marginal(var), r1.marginal(var),
                                   atol=1e-12)  # same seeds, same draws
        assert np.abs(rs.marginal(var) - exact[bn.index(var)]).max() < 0.04
# lane padding: 2 queries x 6 chains = 12 lanes -> padded to 12+4k
pe = PosteriorEngine(registry, mesh=mesh, chains_per_query=6, burn_in=64,
                     max_rounds=48, seed=7)
rp = pe.answer(Query("sprinkler", {"wetgrass": 1}, ("rain",),
                     n_samples=16384))
exact = registry["sprinkler"].marginals_exact({"wetgrass": 1})
assert np.abs(rp.marginal("rain") - exact[2]).max() < 0.05
print("OK")
"""
        rc, out = run_subprocess(code, devices=4)
        assert rc == 0, out

    def test_fused_sampler_on_mesh_matches_single_device(self):
        """sampler="pallas" on a 4-device serve mesh runs the fused
        kernel per device on its own lanes (XLA cannot partition a
        Mosaic kernel); the answers equal the single-device engine's, and
        the xla sampler's, bit for bit — BN and MRF families."""
        code = """
import numpy as np
from repro.launch.mesh import make_serve_mesh
from repro.pgm import networks
from repro.serve import MrfQuery, PosteriorEngine, Query
mrf, truth = networks.penguin_task(h=6, w=5)
registry = {"sprinkler": networks.sprinkler(), "penguin": mrf}
mask = np.zeros((6, 5), bool); mask[2, 1:4] = True
qs = [Query("sprinkler", {"wetgrass": 1}, ("rain",), n_samples=512),
      MrfQuery("penguin", mask, truth, query_sites=((0, 0), (5, 4)),
               n_samples=256)]
kw = dict(chains_per_query=4, burn_in=16, max_rounds=6, seed=1)
runs = [PosteriorEngine(registry, sampler="pallas",
                        mesh=make_serve_mesh((4,)), **kw).answer_batch(qs),
        PosteriorEngine(registry, sampler="pallas", **kw).answer_batch(qs),
        PosteriorEngine(registry, sampler="xla", **kw).answer_batch(qs)]
for rs in zip(*runs):
    for var in rs[0].marginals:
        for r in rs[1:]:
            assert np.array_equal(rs[0].marginal(var), r.marginal(var))
print("OK")
"""
        rc, out = run_subprocess(code, devices=4)
        assert rc == 0, out

    def test_mesh_shape_2d_and_cli(self):
        """2D ("batch", "model") serve mesh + the CLI flags end to end."""
        code = """
from repro.serve.cli import main
main(["--network", "sprinkler", "--queries", "4", "--patterns", "2",
      "--chains", "8", "--budget", "256", "--burn-in", "16", "--show", "0",
      "--force-host-devices", "4", "--mesh-shape", "2x2"])
"""
        rc, out = run_subprocess(code)
        assert rc == 0, out
        assert "warm/cold speedup" in out and "serve mesh" in out


@pytest.mark.slow
class TestShardedTraining:
    def test_sharded_step_matches_single_device(self):
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models.transformer import init_model
from repro.sharding.specs import param_specs, batch_specs, named
from repro.training.train_step import init_train_state, make_train_step
from repro.training.data import TokenDataset, DataConfig

cfg = get_config("granite-20b", smoke=True).replace(dtype="float32")
params = init_model(jax.random.PRNGKey(0), cfg)
ds = TokenDataset(DataConfig(cfg.vocab, 16, 8))
batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
step_fn, _ = make_train_step(cfg, q_block=8)

# single device
s1 = init_train_state(cfg, params)
s1, m1 = jax.jit(step_fn)(s1, batch)

# sharded 4x2
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     devices=jax.devices()[:8],
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
with jax.set_mesh(mesh):
    ps = param_specs(cfg, params, mesh)
    pp = jax.device_put(params, named(mesh, ps))
    s2 = init_train_state(cfg, pp)
    bs = batch_specs(cfg, mesh, batch)
    b2 = jax.device_put(batch, named(mesh, bs))
    s2, m2 = jax.jit(step_fn)(s2, b2)
d1 = float(m1["loss"]); d2 = float(m2["loss"])
assert abs(d1 - d2) < 1e-4, (d1, d2)
diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
    a.astype(jnp.float32) - b.astype(jnp.float32)))),
    s1.params, jax.device_get(s2.params))
md = max(jax.tree.leaves(diffs))
assert md < 5e-4, md
print("OK", d1, d2, md)
"""
        rc, out = run_subprocess(code, devices=8)
        assert rc == 0, out

    def test_restore_with_reshard(self):
        """Checkpoint saved on one mesh restores onto another (elastic)."""
        code = """
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models.transformer import init_model
from repro.sharding.specs import param_specs, named
from repro.training import save, restore

cfg = get_config("phi4-mini-3.8b", smoke=True)
params = init_model(jax.random.PRNGKey(0), cfg)
mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                       devices=jax.devices()[:8],
                       axis_types=(jax.sharding.AxisType.Auto,)*2)
mesh_b = jax.make_mesh((2, 2), ("data", "model"),
                       devices=jax.devices()[:4],
                       axis_types=(jax.sharding.AxisType.Auto,)*2)
pa = jax.device_put(params, named(mesh_a, param_specs(cfg, params, mesh_a)))
with tempfile.TemporaryDirectory() as d:
    save(d, 1, pa)
    sh_b = named(mesh_b, param_specs(cfg, params, mesh_b))
    pb, step = restore(d, params, shardings=sh_b)
    for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
print("OK")
"""
        rc, out = run_subprocess(code, devices=8)
        assert rc == 0, out


@pytest.mark.slow
class TestDryrunSmall:
    def test_builders_compile_on_small_mesh(self):
        """The cell builders lower+compile on a 2x2 mesh for one arch of
        each step kind (full 16x16/512-dev sweep runs via launch.dryrun)."""
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.configs import get_config
from repro.configs.base import ShapeCfg
from repro.launch.builders import build_cell
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
cfg = get_config("granite-20b", smoke=True).replace(microbatch=2)
for shape in (ShapeCfg("t", 64, 8, "train"), ShapeCfg("p", 64, 4, "prefill"),
              ShapeCfg("d", 64, 4, "decode")):
    fn, args, insh, outsh, donate = build_cell(cfg, mesh, shape)
    with jax.set_mesh(mesh):
        c = jax.jit(fn, in_shardings=insh, out_shardings=outsh
                    ).lower(*args).compile()
        assert c.memory_analysis().temp_size_in_bytes >= 0
    print("ok", shape.kind)
print("OK")
"""
        rc, out = run_subprocess(code, devices=4)
        assert rc == 0, out
