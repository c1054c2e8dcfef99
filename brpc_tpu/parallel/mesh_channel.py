"""MeshChannel — combo-channel semantics fused onto a mesh axis.

The honest TPU translation of the combo channels (SURVEY.md section 2.12):
where ParallelChannel issues N socket writes and merges N responses
(parallel_channel.h:94-218), a MeshChannel performs ONE XLA collective over
an ICI mesh axis — the fan-out, the "responses," and the merge are a single
fused device program. The RPC-shaped API is kept deliberately:

    mc = MeshChannel(mesh, "dp")
    out = mc.parallel_call(fn, x, merger="add")   # ParallelChannel
    y   = mc.ring_call(fn, x)                     # cascade/pipeline hop
    z   = mc.partition_call(fns, x)               # PartitionChannel

so code written against combo channels ports directly onto silicon.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from brpc_tpu.parallel import collectives


class MeshChannel:
    """One mesh axis treated as a set of N sub-channels.

    Two fan-out axes compose here (ISSUE 13): the DEVICE axis keeps its
    XLA-collective lowering (parallel_call/ring_call/partition_call
    below — one fused device program), while the HOST axis goes native:
    attach_host_cluster() binds a brpc_tpu.rpc.native_cluster
    NativeCluster, and host_parallel_call() fans an RPC across that
    cluster's backends through the C++ fan-out core (DoublyBufferedData
    LB select, sub-calls on fibers, native merge) — the cross-host hop
    of a host×device 2D mesh without touching Python per sub-call.
    """

    def __init__(self, mesh: Mesh, axis: str, host_cluster=None):
        if axis not in mesh.shape:
            raise ValueError(f"axis {axis!r} not in mesh {tuple(mesh.shape)}")
        self.mesh = mesh
        self.axis = axis
        self.n = mesh.shape[axis]
        self._cache = {}
        self.host_cluster = host_cluster

    # -- host axis (native fan-out) ---------------------------------------
    def attach_host_cluster(self, cluster):
        """Bind the host axis: a NativeCluster whose backends are the
        peer hosts of this mesh slice."""
        self.host_cluster = cluster
        return self

    def host_parallel_call(self, service_method: str, payload: bytes,
                           timeout_ms: int = 1000, fail_limit: int = 0):
        """ParallelChannel semantics over the HOST axis: the request
        fans to every host backend natively; returns (error_code,
        merged_bytes, error_text, failed_subcalls)."""
        if self.host_cluster is None:
            raise ValueError("no host cluster attached "
                             "(attach_host_cluster)")
        return self.host_cluster.parallel_call(service_method, payload,
                                               timeout_ms=timeout_ms,
                                               fail_limit=fail_limit)

    # -- ParallelChannel analog -------------------------------------------
    def parallel_call(self, fn: Callable, x, merger: Optional[str] = "add"):
        """Apply fn to each participant's shard (dim 0 sharded over the
        axis), then merge with the named reduction — fn is the sub-call,
        merger the ResponseMerger. merger=None returns per-shard results
        (still sharded)."""
        key = ("par", id(fn), jnp.shape(x), str(jnp.result_type(x)), merger)
        run = self._cache.get(key)
        if run is None:
            axis = self.axis

            def local(s):
                r = fn(s)
                if merger is None:
                    return r
                if merger == "add":
                    return lax.psum(r, axis)
                if merger == "mean":
                    return lax.pmean(r, axis)
                if merger == "max":
                    return lax.pmax(r, axis)
                if merger == "concat":
                    return lax.all_gather(r, axis, axis=0, tiled=True)
                raise ValueError(f"unknown merger {merger}")

            out_spec = P(axis) if merger is None else P()
            run = jax.jit(jax.shard_map(local, mesh=self.mesh,
                                        in_specs=P(axis),
                                        out_specs=out_spec,
                                        check_vma=False))
            self._cache[key] = run
        x = jax.device_put(jnp.asarray(x),
                           NamedSharding(self.mesh, P(self.axis)))
        return run(x)

    def allreduce(self, x, op: str = "add"):
        return collectives.allreduce(self.mesh, self.axis, x, op)

    def allgather(self, x):
        return collectives.allgather(self.mesh, self.axis, x)

    def reduce_scatter(self, x):
        return collectives.reduce_scatter(self.mesh, self.axis, x)

    # -- cascade / pipeline analog ----------------------------------------
    def ring_call(self, fn: Callable, x, shift: int = 1):
        """Apply fn to the local shard then pass the result to the next
        participant on the ring — the cascade_echo / pipeline-stage hop."""
        key = ("ring", id(fn), jnp.shape(x), str(jnp.result_type(x)), shift)
        run = self._cache.get(key)
        if run is None:
            axis, n = self.axis, self.n
            perm = [(i, (i + shift) % n) for i in range(n)]

            def local(s):
                return lax.ppermute(fn(s), axis, perm)

            run = jax.jit(jax.shard_map(local, mesh=self.mesh,
                                        in_specs=P(axis),
                                        out_specs=P(axis)))
            self._cache[key] = run
        x = jax.device_put(jnp.asarray(x),
                           NamedSharding(self.mesh, P(self.axis)))
        return run(x)

    # -- PartitionChannel analog ------------------------------------------
    def partition_call(self, fn: Callable, x, gather: bool = True):
        """Each participant computes fn on ITS partition of the data (the
        partitioned request of partition_channel.h); gather=True returns
        the concatenated full result to all."""
        return self.parallel_call(fn, x, merger="concat" if gather else None)

    def all_to_all(self, x):
        return collectives.all_to_all(self.mesh, self.axis, x)

    def bandwidth_probe(self, nbytes: int = 1 << 22, iters: int = 5) -> dict:
        return collectives.ici_bandwidth_probe(self.mesh, self.axis,
                                               nbytes, iters)


@functools.lru_cache(maxsize=8)
def default_mesh(axis: str = "dp", size: Optional[int] = None) -> Mesh:
    n = size or len(jax.devices())
    return collectives.make_mesh({axis: n})
