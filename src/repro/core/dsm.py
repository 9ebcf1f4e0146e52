"""Distributed-shared-memory analogs (paper §III-D-3, Figs. 8-9).

Hopper DSM lets blocks in a cluster read/write each other's shared
memory over the SM-to-SM network.  The TPU structure in the same
architectural role is the ICI torus: cores exchange VMEM-resident data
via remote DMA, programmed in JAX with `shard_map` + `lax.ppermute` /
`all_to_all`.  A Hopper "cluster" maps to a subgroup of a mesh axis.

Three artifacts, mirroring the paper's three DSM benchmarks:
  * ring latency probe  -> one ppermute hop (paper: 180-cycle SM-to-SM)
  * RBC ring-based copy -> every rank adds its buffer to rank (r+1)%CS,
    with ILP = number of independent buffers in flight
  * distributed histogram -> bins partitioned across the cluster
    (reduce_scatter routing) vs. private per-core histograms (psum)

These functions are mesh-generic; tests/benchmarks run them on a
forced 8-device CPU platform in a child process pinned to
JAX_PLATFORMS=cpu, so the child never reaches for an accelerator the
parent holds.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _ring_perm(axis_size: int):
    return [(i, (i + 1) % axis_size) for i in range(axis_size)]


def rbc_ring_copy(x: jax.Array, mesh: Mesh, axis: str, *, hops: int = 1,
                  ilp: int = 1) -> jax.Array:
    """Ring-Based Copy: each rank accumulates the buffer of rank-1 ... rank-hops.

    `ilp` splits the payload into independent in-flight chunks, the
    analog of the paper's instruction-level-parallelism knob in Fig. 8.
    x is sharded over `axis` on its leading dim; returns same sharding.
    """
    size = mesh.shape[axis]
    assert hops < size or size == 1

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _rbc(xs):
        chunks = jnp.split(xs, ilp, axis=-1) if ilp > 1 else [xs]
        acc = [c for c in chunks]
        perm = _ring_perm(size)
        for _ in range(hops):
            # all ilp permutes are independent -> overlap on the wire
            moved = [lax.ppermute(c, axis, perm) for c in chunks]
            acc = [a + m for a, m in zip(acc, moved)]
            chunks = moved
        return jnp.concatenate(acc, axis=-1) if ilp > 1 else acc[0]

    return _rbc(x)


def ring_latency_probe(mesh: Mesh, axis: str) -> jax.Array:
    """One-hop ppermute of a single word — the SM-to-SM latency probe."""
    size = mesh.shape[axis]
    x = jnp.arange(size, dtype=jnp.int32).reshape(size, 1)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _hop(xs):
        return lax.ppermute(xs, axis, _ring_perm(size))

    return _hop(x)


def histogram_private_psum(values: jax.Array, nbins: int, mesh: Mesh,
                           axis: str) -> jax.Array:
    """Baseline (cluster size 1): full private histogram per core + psum.

    Every core counts all `nbins` bins over its element shard, then the
    histograms are summed.  VMEM cost per core: O(nbins).
    """
    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P())
    def _hist(vals):
        local = jnp.zeros((nbins,), jnp.int32).at[vals].add(1)
        return lax.psum(local, axis)

    return _hist(values)


def histogram_dsm(values: jax.Array, nbins: int, mesh: Mesh, axis: str
                  ) -> jax.Array:
    """DSM-analog histogram: bins partitioned across the cluster.

    Each core counts its full local histogram, but only `nbins/CS` bins
    are *kept* per core — the reduce_scatter routes each bin's partial
    counts to its owner over ICI, exactly like DSM atomics route
    increments to the block that owns the bin.  VMEM cost per core for
    the resident result: O(nbins/CS), which is what lets Fig. 9's larger
    Nbins keep high occupancy.
    """
    size = mesh.shape[axis]
    assert nbins % size == 0, "bins must split evenly across the cluster"

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _hist(vals):
        local = jnp.zeros((nbins,), jnp.int32).at[vals].add(1)
        # reduce_scatter: each rank receives the summed shard it owns
        return lax.psum_scatter(local, axis, scatter_dimension=0,
                                tiled=True)

    return _hist(values)


def all_to_all_exchange(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Full-cluster exchange (DSM load from every peer): all_to_all."""
    size = mesh.shape[axis]

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _a2a(xs):
        # xs: [chunk, size, ...] -> exchange dim 1 across the axis
        return lax.all_to_all(xs.reshape(size, -1), axis, split_axis=0,
                              concat_axis=0).reshape(xs.shape)

    return _a2a(x)


def modeled_rbc_throughput(payload_bytes: int, cluster_size: int, ilp: int,
                           link_gbps: float = 50.0) -> float:
    """Modeled RBC GB/s per core on the v5e ICI ring (Fig. 8 analog).

    One hop moves the payload over one link; ILP pipelines chunks so the
    link stays busy; contention: all CS ranks share the ring's 2 links
    per hop direction -> per-core sustained bandwidth saturates at the
    link rate and *degrades* as rings lengthen (more hops in flight),
    mirroring the paper's 3.27 TB/s (CS=2) -> 2.65 TB/s (CS=4) drop.
    """
    startup_frac = 1.0 / (1.0 + ilp)          # un-overlapped first chunk
    contention = 2.0 / cluster_size if cluster_size > 2 else 1.0
    eff = (1.0 - startup_frac * 0.5) * min(1.0, contention + 0.5)
    return link_gbps * eff
