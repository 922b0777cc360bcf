"""§II-A inter-core register sharing: halo-exchange vs global-buffer
(all-gather) collective bytes on the 2D mesh — the Fig. 3(b) 3× memory-
read-reduction analogue.  The bytes are counted in the compiled HLO of a
child process pinned to the CPU with 16 fake devices: it never needs the
accelerator, so it runs whether or not this process holds one, and this
process never touches jax."""
from __future__ import annotations

import json
import subprocess
import sys
import os

from benchmarks.common import row

_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, re, json
from repro.launch.mesh import make_pgm_mesh
from repro.pgm.networks import penguin_task
from repro.pgm.mesh_gibbs import make_mesh_gibbs_step, shard_mrf
mesh = make_pgm_mesh(4, 4)
mrf, _ = penguin_task(h=100, w=68)
key = jax.random.PRNGKey(0)
lab, u, pw, valid, _ = shard_mrf(mesh, mrf, n_chains=4, key=key)
def cbytes(step):
    txt = jax.jit(step).lower(key, lab, u, pw, valid).compile().as_text()
    tot = 0
    for line in txt.splitlines():
        for p in ("all-gather(", "all-gather-start", "collective-permute(",
                  "collective-permute-start"):
            if p in line and "=" in line:
                m = re.findall(r"(s32|u32|f32|pred)\\[([\\d,]*)\\]",
                               line.split("=",1)[1])
                if m:
                    dt, dims = m[0]
                    sz = {"s32":4,"u32":4,"f32":4,"pred":1}[dt]
                    for d in dims.split(","):
                        if d: sz *= int(d)
                    tot += sz
                break
    return tot
halo = cbytes(make_mesh_gibbs_step(mesh, comm="halo"))
ag = cbytes(make_mesh_gibbs_step(mesh, comm="allgather"))
print(json.dumps({"halo": halo, "allgather": ag}))
"""


def main(report=print):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src")
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", _CODE], env=env,
                       capture_output=True, text=True, timeout=900)
    line = [l for l in p.stdout.splitlines() if l.startswith("{")][-1]
    d = json.loads(line)
    ratio = d["allgather"] / max(d["halo"], 1)
    report(row("halo_exchange_bytes", d["halo"],
               f"allgather_bytes={d['allgather']};reduction={ratio:.1f}x;"
               f"paper_claim=3x_mem_reads"))


if __name__ == "__main__":
    main()
