"""XLA collective wrappers over a jax.sharding.Mesh axis.

These are the ICI-native transport verbs of the framework: where brpc moves
bytes through sockets/RDMA (SURVEY.md section 2.9), a TPU pod moves tensors
through ICI collectives. Each wrapper builds a shard_map'd, jitted closure
(cached per mesh/axis/shape/dtype) so repeated transfers hit the XLA
executable cache. Shapes are static and control flow is trace-free, keeping
everything on the MXU/ICI fast path.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(axis_sizes: dict, devices=None) -> Mesh:
    """Build a Mesh from {axis_name: size}; sizes must multiply to the
    device count used."""
    import numpy as np

    names = tuple(axis_sizes.keys())
    sizes = tuple(axis_sizes.values())
    n = 1
    for s in sizes:
        n *= s
    devs = devices if devices is not None else jax.devices()[:n]
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]).reshape(sizes), names)


def _axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


@functools.lru_cache(maxsize=256)
def _allreduce_fn(mesh: Mesh, axis: str, shape: Tuple[int, ...], dtype, op: str):
    def local(x):
        # x local: (1, ...) — this participant's contribution; drop the
        # participant dim so the reduction has the contribution's shape.
        x = x[0]
        if op == "add":
            return lax.psum(x, axis)
        if op == "max":
            return lax.pmax(x, axis)
        if op == "min":
            return lax.pmin(x, axis)
        if op == "mean":
            return lax.pmean(x, axis)
        raise ValueError(f"unknown op {op}")

    spec_in = P(axis)
    spec_out = P()  # replicated result
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=spec_in,
                                 out_specs=spec_out))


def allreduce(mesh: Mesh, axis: str, x, op: str = "add"):
    """Every participant contributes its shard (dim 0 sharded over `axis`);
    all receive the reduction. The ParallelChannel+ResponseMerger fusion of
    SURVEY.md section 2.12."""
    x = jnp.asarray(x)
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))
    return _allreduce_fn(mesh, axis, x.shape, x.dtype.name, op)(x)


@functools.lru_cache(maxsize=256)
def _allgather_fn(mesh: Mesh, axis: str, shape, dtype):
    def local(x):
        return lax.all_gather(x, axis, axis=0, tiled=True)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(), check_vma=False))


def allgather(mesh: Mesh, axis: str, x):
    """Shards (dim 0) gathered to every participant."""
    x = jnp.asarray(x)
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))
    return _allgather_fn(mesh, axis, x.shape, x.dtype.name)(x)


@functools.lru_cache(maxsize=256)
def _reduce_scatter_fn(mesh: Mesh, axis: str, shape, dtype):
    def local(x):
        # x local: (1, L) — this participant's full-length contribution;
        # result: its L/N slice of the sum.
        out = lax.psum_scatter(x[0], axis, scatter_dimension=0, tiled=True)
        return out[None, :]

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(axis)))


def reduce_scatter(mesh: Mesh, axis: str, x):
    """x: (N, L) — row i is participant i's contribution; returns (N, L/N)
    where row i is the summed slice owned by participant i."""
    x = jnp.asarray(x)
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))
    return _reduce_scatter_fn(mesh, axis, x.shape, x.dtype.name)(x)


@functools.lru_cache(maxsize=256)
def _ppermute_fn(mesh: Mesh, axis: str, shape, dtype, shift: int):
    n = _axis_size(mesh, axis)
    perm = [(i, (i + shift) % n) for i in range(n)]

    def local(x):
        return lax.ppermute(x, axis, perm)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(axis)))


def ring_shift(mesh: Mesh, axis: str, x, shift: int = 1):
    """Neighbor exchange along the ring — the cascade/pipeline hop and the
    building block of ring attention (tensor/ring_attention.py)."""
    x = jnp.asarray(x)
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))
    return _ppermute_fn(mesh, axis, x.shape, x.dtype.name, shift)(x)


@functools.lru_cache(maxsize=256)
def _all_to_all_fn(mesh: Mesh, axis: str, shape, dtype):
    def local(x):
        # x local: (1, N, ...) — slot j is this participant's message to j.
        # result local: (1, N, ...) — slot j is the message FROM j.
        y = lax.all_to_all(x, axis, split_axis=1, concat_axis=0, tiled=False)
        # y: (N, 1, ...) -> (1, N, ...)
        return jnp.swapaxes(y, 0, 1)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(axis)))


def all_to_all(mesh: Mesh, axis: str, x):
    """x: (N, N, ...) — x[i, j] is i's message to j; returns y with
    y[i, j] = x[j, i]. The PartitionChannel/expert-dispatch verb (MoE)."""
    x = jnp.asarray(x)
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))
    return _all_to_all_fn(mesh, axis, x.shape, x.dtype.name)(x)


def ici_bandwidth_probe(mesh: Mesh, axis: str, nbytes: int = 1 << 24,
                        iters: int = 10) -> dict:
    """Measure achieved collective bandwidth on this mesh — the
    rdma_performance harness analog (example/rdma_performance/client.cpp)."""
    import time

    n = _axis_size(mesh, axis)
    elems = max(n, nbytes // 4 // n * n)
    x = jnp.ones((elems,), jnp.float32)
    fn = _allreduce_fn(mesh, axis, x.shape, x.dtype.name, "add")
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))
    fn(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    total_bytes = x.nbytes * iters
    # allreduce moves 2*(n-1)/n of the data per link (ring algorithm)
    algo_bytes = total_bytes * 2 * (n - 1) / n
    return {
        "axis_size": n,
        "payload_bytes": int(x.nbytes),
        "iters": iters,
        "seconds": dt,
        "allreduce_GBps": total_bytes / dt / 1e9,
        "algo_GBps": algo_bytes / dt / 1e9,
    }
