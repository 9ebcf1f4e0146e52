"""By-hand rehearsal, not a test: compile each cell's chunk-step and
decode-span programs for a described (not attached) TPU v5e at the
cell's geometry and print their memory analysis.

    JAX_PLATFORMS=cpu python3 bench/fit.py [cell ...]

The server is built over shapes only (weights and the KV pool are never
allocated).  "need" is arguments + temporaries - donated aliases; it
must stay under the chip's 15.75 GiB of usable HBM, with the other
programs the process holds (the weights are shared, the pool donated).
Nothing runs, so nothing here is a time.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import harness  # noqa: E402

GiB = 2 ** 30


def fit(cell_name: str, one_chip) -> None:
    from repro.analysis.jaxpr_check import serving_programs
    from repro.models import api
    from repro.runtime.server import ChunkedServer
    c = harness.load_cell(cell_name)
    conf = c["conf"]
    cfg = harness.program_config(conf)
    params = jax.eval_shape(
        lambda: api.init(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype))
    real = api.init_cache
    api.init_cache = lambda *a, **k: jax.eval_shape(lambda: real(*a, **k))
    try:
        srv = ChunkedServer(cfg, params, batch_slots=conf["batch_slots"],
                            max_len=conf["max_len"])
    finally:
        api.init_cache = real
    on = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip), t)
    for name, _, jitted, ops in serving_programs(srv):
        m = jitted.lower(*on(ops)).compile().memory_analysis()
        need = (m.argument_size_in_bytes + m.temp_size_in_bytes
                - m.alias_size_in_bytes)
        print(f"{cell_name} {name}: slots {srv.B}, max_len {srv.max_len}, "
              f"pool {srv.num_blocks} blocks; arguments "
              f"{m.argument_size_in_bytes / GiB:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GiB:.2f} GiB, aliased "
              f"{m.alias_size_in_bytes / GiB:.2f} GiB, need "
              f"{need / GiB:.2f} GiB", flush=True)


def main(argv) -> None:
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    cells = argv or [w["name"] for w in harness.load_benchmark()["workloads"]]
    for name in cells:
        fit(name, one_chip)


if __name__ == "__main__":
    main(sys.argv[1:])
