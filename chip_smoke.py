#!/usr/bin/env python3
"""chip_smoke — the quickest proof that the main path runs on the chip.

One process holds the chip and is the server. Phases, one line each:

  (a) device   jax must find a TPU; anything else exits non-zero
  (b) native   clean build of the native core from the checkout's sources
  (c) server   Server(use_native_runtime=True) with an echo service and the
               TensorStore; tpu_std echo calls through a Channel
  (d) inproc   seeded fp32 pushes of 1, 16 and 64 MB through a device
               channel: stored on the TPU, on-device checksum equal to the
               host's, pulled back byte-equal
  (e) xproc    the same pushes from a host-only client child
               (JAX_PLATFORMS=cpu), each with the lane it rode
  (f) flagship the flagship train step at its bench width for a few
               steps: finite, falling loss and the step time

With --chips 4 only the four-chip phases run:

  (g) merge    1 GiB of fp32 gradients per chip merged in 64 MB buckets
               through MeshChannel.allreduce over 4 distinct chips, against
               a plain jnp sum of the shards on one chip
  (h) sharded  __graft_entry__.dryrun_multichip(4): forward parity with
               the local model and a falling loss

The last line of stdout is {"ok": true, "device": {...}}. --rehearse lets
the CPU backend stand in for the chip (small model and gradient sizes):
it is for rehearsing the control flow, and its timings mean nothing.
"""
import argparse
import contextlib
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PUSH_MB = (1, 16, 64)  # BASELINE streaming_echo's 1-64 MB range
SEED = 21
STEPS = 6  # the first two calls compile
LR = 0.1   # SGD on one batch: falls at every depth tried, bf16 included


@contextlib.contextmanager
def phase(tag: str, name: str):
    """Times one phase; the phase appends its result fields to `info`."""
    info = []
    t0 = time.perf_counter()
    yield info
    print(f"[{tag}] {name}: " + " ".join(info) +
          f" ({time.perf_counter() - t0:.2f}s)", flush=True)


def host_checksum(a):
    """(sum, position-weighted sum) of the fp32 words as uint32, mod 2^32:
    exact, so host and device must agree bit for bit."""
    import numpy as np

    w = a.view(np.uint32).reshape(-1)
    odd = np.arange(w.size, dtype=np.uint32) * np.uint32(2) + np.uint32(1)
    return (int(np.sum(w, dtype=np.uint32)),
            int(np.sum(w * odd, dtype=np.uint32)))


def device_checksum(x):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        w = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
        odd = jnp.arange(w.size, dtype=jnp.uint32) * 2 + 1
        return (jnp.sum(w, dtype=jnp.uint32),
                jnp.sum(w * odd, dtype=jnp.uint32))

    return tuple(int(v) for v in f(x))


def seeded(mb: int):
    import numpy as np

    return np.random.default_rng(SEED + mb).standard_normal(
        (mb << 20) // 4, dtype=np.float32)


def check_on_device(store, name: str, device, want_checksum):
    import jax

    arr = store.get(name)[0]
    if not isinstance(arr, jax.Array) or arr.devices() != {device}:
        raise AssertionError(f"{name} is not held on {device}: "
                             f"{type(arr).__name__}")
    got = device_checksum(arr)
    if got != tuple(want_checksum):
        raise AssertionError(f"{name}: device checksum {got} != host "
                             f"checksum {tuple(want_checksum)}")


# The host-only client of phase (e). It shares no JAX state with the
# parent: the parent holds the chip, so the child runs on the CPU backend.
CHILD = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1])
import numpy as np
from chip_smoke import PUSH_MB, host_checksum, seeded
from brpc_tpu.rpc import device_transport as dt
from brpc_tpu.rpc.tensor_service import TensorClient, make_device_channel

client = TensorClient(make_device_channel(sys.argv[2]))
for mb in PUSH_MB:
    host = seeded(mb)
    lanes0 = dt.lane_counters()
    t0 = time.perf_counter()
    cntl, resp = client.push(f"xproc_{mb}MB", [host])
    push_s = time.perf_counter() - t0
    assert not cntl.failed() and resp.ok, cntl.error_text
    lanes1 = dt.lane_counters()
    lane = [k for k in lanes1 if lanes1[k] > lanes0[k]]
    cntl, pulled = client.pull(f"xproc_{mb}MB")
    assert not cntl.failed() and pulled is not None, cntl.error_text
    same = np.array_equal(np.asarray(pulled[0]).view(np.uint32),
                          host.view(np.uint32))
    print(json.dumps({"mb": mb, "lane": lane, "push_s": push_s,
                      "checksum": host_checksum(host),
                      "pulled_equal": bool(same)}), flush=True)
"""


def one_chip_phases(args, device):
    import jax
    import numpy as np

    from brpc_tpu import native

    # (b) a clean build: a library copied in from elsewhere is never used
    with phase("b", "native build") as info:
        res = subprocess.run(
            ["make", "-B", "-C", os.path.join(ROOT, "native"),
             "libbrpc_tpu_native.so"], capture_output=True, text=True)
        if res.returncode != 0:
            sys.stderr.write(res.stderr[-8000:])
            raise SystemExit("native build failed")
        native.load()
        size = os.path.getsize(os.path.join(ROOT, "native",
                                            "libbrpc_tpu_native.so"))
        info.append(f"libbrpc_tpu_native.so {size} bytes")

    from brpc_tpu import rpc
    from brpc_tpu.rpc.proto import echo_pb2
    from brpc_tpu.rpc.tensor_service import (TensorClient,
                                             TensorStoreService,
                                             make_device_channel)

    class EchoService(rpc.Service):
        @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        def Echo(self, cntl, request, response, done):
            with rpc.ClosureGuard(done):
                response.message = request.message
                cntl.response_attachment.append(cntl.request_attachment)

    store = TensorStoreService()
    srv = rpc.Server(rpc.ServerOptions(use_native_runtime=True))
    srv.add_service(EchoService())
    srv.add_service(store)
    if srv.start("127.0.0.1:0") != 0:
        raise SystemExit("server failed to start")
    target = str(srv.listen_endpoint)
    try:
        # (c) tpu_std echo through a normal channel
        with phase("c", "server echo") as info:
            ch = rpc.Channel(rpc.ChannelOptions(timeout_ms=5000))
            if ch.init(target) != 0:
                raise SystemExit(f"channel init to {target} failed")
            lat = []
            for i in range(8):
                cntl = rpc.Controller()
                cntl.request_attachment.append(b"x" * (i * 1000))
                resp = echo_pb2.EchoResponse()
                ch.call_method("EchoService.Echo", cntl,
                               echo_pb2.EchoRequest(message=f"smoke-{i}"),
                               resp)
                if cntl.failed():
                    raise AssertionError(f"echo {i}: {cntl.error_text}")
                if (resp.message != f"smoke-{i}" or
                        cntl.response_attachment.to_bytes()
                        != b"x" * (i * 1000)):
                    raise AssertionError(f"echo {i}: wrong reply")
                lat.append(cntl.latency_us)
            ch.close()
            info.append(f"{len(lat)} replies ok on {target}, "
                        f"latency_us={[round(v) for v in lat]}")

        # (d) in-process pushes
        with phase("d", "in-process pushes") as info:
            client = TensorClient(make_device_channel(target))
            for mb in PUSH_MB:
                host = seeded(mb)
                t0 = time.perf_counter()
                cntl, resp = client.push(f"inproc_{mb}MB", [host])
                push_s = time.perf_counter() - t0
                if cntl.failed() or not resp.ok:
                    raise AssertionError(cntl.error_text)
                check_on_device(store, f"inproc_{mb}MB", device,
                                host_checksum(host))
                cntl, pulled = client.pull(f"inproc_{mb}MB")
                if cntl.failed() or pulled is None:
                    raise AssertionError(f"pull {mb}MB: {cntl.error_text}")
                if not np.array_equal(np.asarray(pulled[0]).view(np.uint32),
                                      host.view(np.uint32)):
                    raise AssertionError(f"pull {mb}MB: bytes differ")
                info.append(f"{mb}MB push={push_s * 1e3:.1f}ms "
                            f"on device, checksum ok, pull equal;")

        # (e) cross-process pushes from a host-only child
        with phase("e", "cross-process pushes") as info:
            res = subprocess.run(
                [sys.executable, "-c", CHILD, ROOT, target],
                capture_output=True, text=True, timeout=600, cwd=ROOT,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            if res.returncode != 0:
                sys.stderr.write(res.stderr[-8000:])
                raise AssertionError("host-only client child failed")
            rows = [json.loads(line) for line in res.stdout.splitlines()
                    if line.startswith("{")]
            if [r["mb"] for r in rows] != list(PUSH_MB):
                raise AssertionError(f"child reported {rows}")
            for r in rows:
                check_on_device(store, f"xproc_{r['mb']}MB", device,
                                r["checksum"])
                if not r["pulled_equal"]:
                    raise AssertionError(f"{r['mb']}MB pulled back wrong")
                info.append(f"{r['mb']}MB lane={'+'.join(r['lane'])} "
                            f"push={r['push_s'] * 1e3:.1f}ms "
                            f"on device, checksum ok, pull equal;")
    finally:
        srv.stop()

    # (f) the flagship train step
    import dataclasses

    import jax.numpy as jnp

    from brpc_tpu.tensor import init_params, make_spmd_train_step
    from brpc_tpu.tensor.config import FLAGSHIP, FLAGSHIP_BATCH, MeshSpec

    cfg, (B, T) = FLAGSHIP, FLAGSHIP_BATCH
    if args.rehearse:
        cfg, (B, T) = dataclasses.replace(
            cfg, vocab=1024, d_model=256, n_heads=4, d_head=64,
            d_ff=512, n_layers=2), (2, 128)
    with phase("f", "flagship train step") as info:
        params = init_params(cfg, jax.random.PRNGKey(SEED))
        _, step = make_spmd_train_step(cfg, MeshSpec(), lr=LR)
        tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), (B, T),
                                    0, cfg.vocab, dtype=jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        losses, times = [], []
        for _ in range(STEPS):
            t0 = time.perf_counter()
            loss, params = step(params, tokens, labels)
            jax.block_until_ready((loss, params))
            times.append(time.perf_counter() - t0)
            losses.append(float(loss))
        if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
            raise AssertionError(f"loss not finite and falling: {losses}")
        steady = sorted(times[2:])  # the first two calls compile
        info.append(
            f"d{cfg.d_model} L{cfg.n_layers} experts{cfg.n_experts} "
            f"vocab{cfg.vocab} B{B}xT{T} {cfg.dtype}: "
            f"loss {' '.join(f'{v:.4f}' for v in losses)}; "
            f"first two calls {times[0]:.2f}s {times[1]:.2f}s; "
            f"steady step median {steady[len(steady) // 2] * 1e3:.2f}ms "
            f"over {len(steady)}")


def four_chip_phases(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from brpc_tpu.parallel import MeshChannel, make_mesh

    per_chip = 1 << 30
    bucket = 64 << 20
    if args.rehearse:
        per_chip, bucket = 16 << 20, 4 << 20
    with phase("g", "gradient merge") as info:
        mesh = make_mesh({"x": 4})
        devs = list(mesh.devices.flat)
        if len({d.id for d in devs}) != 4:
            raise AssertionError(f"mesh does not span 4 devices: {devs}")
        mc = MeshChannel(mesh, "x")
        n = bucket // 4  # fp32 elements per chip per bucket
        gen = jax.jit(lambda k: jax.random.normal(k, (4, n), jnp.float32),
                      out_shardings=NamedSharding(mesh, P("x")))
        grads = [gen(jax.random.PRNGKey(SEED * 1000 + i))
                 for i in range(per_chip // bucket)]
        jax.block_until_ready(grads)
        mc.allreduce(grads[0]).block_until_ready()  # compile
        t0 = time.perf_counter()
        merged = [mc.allreduce(g) for g in grads]
        jax.block_until_ready(merged)
        merge_s = time.perf_counter() - t0
        worst = 0.0
        for g, m in zip(grads, merged):
            homes = {s.device for s in m.addressable_shards}
            if homes != set(devs):
                raise AssertionError(f"merge result on {homes}")
            shards = sorted(g.addressable_shards, key=lambda s: s.index)
            ref = sum(jax.device_put(s.data[0], devs[0]) for s in shards)
            for s in m.addressable_shards:
                d = float(jnp.max(jnp.abs(
                    s.data - jax.device_put(ref, s.device))))
                worst = max(worst, d)
        if not worst <= 1e-4:
            raise AssertionError(f"merge differs from jnp sum by {worst}")
        info.append(f"{len(grads)} x {bucket >> 20}MB buckets "
                    f"({per_chip >> 20}MB fp32 per chip) over "
                    f"{sorted(d.id for d in devs)}: {merge_s:.4f}s, "
                    f"max |allreduce - jnp sum| = {worst:g} on every chip")

    with phase("h", "sharded flagship step") as info:
        import __graft_entry__

        __graft_entry__.dryrun_multichip(4)
        info.append("dryrun_multichip(4) ok")


def main():
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="let the CPU backend stand in for the chip")
    args = ap.parse_args()

    import jax

    # (a) the device, before anything else of the repo is touched
    with phase("a", "device") as info:
        devs = jax.devices()
        dev = devs[0]
        info.append(f"platform={dev.platform} kind={dev.device_kind} "
                    f"count={len(devs)}")
        if dev.platform != "tpu" and not args.rehearse:
            raise SystemExit(f"no TPU: jax found {devs}")
        if len(devs) < args.chips:
            raise SystemExit(f"need {args.chips} devices, have {len(devs)}")

    sys.path.insert(0, ROOT)
    from brpc_tpu import compile_cache

    print(f"[-] compile cache: {compile_cache.enable()}", flush=True)
    # the fabric arena holds a whole 64 MB push
    os.environ.setdefault("BRPC_TPU_FABRIC_ARENA", str(128 << 20))
    if args.chips == 4:
        four_chip_phases(args)
    else:
        one_chip_phases(args, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
