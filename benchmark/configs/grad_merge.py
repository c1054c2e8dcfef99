"""grad_merge: gradient buckets merged across the chips of one host by
`MeshChannel.allreduce` over a one-axis mesh of every chip the cell holds.

Set-up makes the gradient sets on the chips in one jitted call from the
seed (each bucket is (chips, words) fp32, row i on chip i) and runs one
round of each set, which compiles the merge. The window drives rounds of
the traffic's `merge_rounds` kind.

Correctness, checked once the window has closed: the last round of each
set, every output shard on every chip, against a plain fp32 sum of the
bucket's rows on one chip. `max_abs_err` is the largest absolute
difference, and an output not held on every chip counts as infinite.
"""
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import lib  # noqa: E402


def make_sets(mesh, axis, sets, buckets, words, seed):
    """All gradient sets, made on the chips in one jitted call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[axis]
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)

    def gen(key):
        keys = jax.random.split(key, sets * buckets)
        return [[jax.random.normal(keys[s * buckets + b], (n, words),
                                   jnp.float32)
                 for b in range(buckets)] for s in range(sets)]

    return jax.jit(gen, out_shardings=NamedSharding(mesh, P(axis)))(key)


def reference_error(grads, merged, devices) -> float:
    """Largest |merged - sum of rows| over every shard on every chip; the
    sum is a plain fp32 sum of the rows, in row order, on the first chip."""
    import jax
    import jax.numpy as jnp

    homes = {s.device for s in merged.addressable_shards}
    if homes != set(devices):
        return float("inf")
    rows = sorted(grads.addressable_shards, key=lambda s: s.index[0].start)
    ref = None
    for s in rows:
        r = jax.device_put(s.data[0], devices[0])
        ref = r if ref is None else ref + r
    worst = 0.0
    for s in merged.addressable_shards:
        d = jnp.max(jnp.abs(s.data - jax.device_put(ref, s.device)))
        worst = max(worst, float(d))
    return worst


def run(win):
    import jax

    from brpc_tpu.parallel import MeshChannel, make_mesh

    t = win.traffic
    sets, buckets = int(t["sets"]), int(t["buckets"])
    bucket_bytes = int(t["bucket_mib"]) << 20
    chips = len(win.devices)
    mesh = make_mesh({"x": chips}, devices=list(win.devices))
    mc = MeshChannel(mesh, "x")
    grads = make_sets(mesh, "x", sets, buckets, bucket_bytes // 4, win.seed)
    last = {}

    def send(req):
        with win.annotate("merge_round"):
            out = [mc.allreduce(g) for g in grads[req["set"]]]
            jax.block_until_ready(out)
        last[req["set"]] = out
        return True, ""

    for s in range(sets):  # compiles the merge; every set once
        send({"set": s})
    last.clear()
    t0 = win.begin()
    records = win.kind.drive(send, t, win.seed, 0, t0, win.seconds,
                             win.clock)
    win.end(records)
    win.data["merge"] = {"chips": chips, "bucket_bytes": bucket_bytes,
                         "device_kind": win.devices[0].device_kind}
    win.data["memory_peak_bytes"] = lib.memory_peak(win.devices)
    worst = 0.0
    for s, outs in sorted(last.items()):
        for g, m in zip(grads[s], outs):
            worst = max(worst, reference_error(g, m, list(win.devices)))
    win.check("max_abs_err", worst, win.config["limits"]["max_abs_err"])


# -- the control and the planted faults (benchmark/faults.py) ---------------

def faults_for(traffic: dict):
    return ("control", "unchanged", "half", "exchange", "altered")


def patched(name: str, traffic: dict):
    """The control or a planted fault, patched into the program (see
    benchmark/faults.py)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from brpc_tpu.parallel import MeshChannel

    allreduce = MeshChannel.allreduce
    programs = {}
    previous = [None]

    def program(mesh, axis):
        n = mesh.shape[axis]

        def local(x):
            x = x[0]
            if name == "control":
                return lax.psum(x.astype(jnp.bfloat16), axis).astype(x.dtype)
            if name == "half":
                keep = lax.axis_index(axis) < n // 2
                return lax.psum(jnp.where(keep, x, 0), axis) * 2
            if name == "exchange":
                return x
            return lax.psum(x, axis).at[0].add(0.5)  # altered

        return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                                     out_specs=P(), check_vma=False))

    def patched(self, x, op="add"):
        if name == "unchanged":
            out = previous[0]
            if out is None:
                out = allreduce(self, x, op)
            previous[0] = out
            return out
        key = (self.mesh, self.axis)
        if key not in programs:
            programs[key] = program(self.mesh, self.axis)
        x = jax.device_put(x, NamedSharding(self.mesh, P(self.axis)))
        return programs[key](x)

    return lib.swap(MeshChannel, "allreduce", patched)
