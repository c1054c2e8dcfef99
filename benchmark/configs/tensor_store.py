"""tensor_store: a TensorStore service on the native runtime, in the process
that holds the chip, fed by host-only client processes on the same host.

The server is `rpc.Server(ServerOptions(use_native_runtime=True))` with
`TensorStoreService`; each client is a child process on JAX's CPU backend
using `TensorClient` over `make_device_channel`. The window drives
`TensorClient.push` -> `TensorStore.Push`: the bytes land in HBM before
the ack.

Correctness, checked once the window has closed: every name written in
the window holds, on the chip, the bytes of its last acknowledged write
(exact device checksum against the host checksum of the seeded payload);
`wrong_names` counts those that do not, limit 0.

Run as `tensor_store.py --client <json>` it is one client process.
"""
import gc
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import lib  # noqa: E402

PAYLOAD_STREAM = 1  # seeded streams: (1, client, nbytes, payload)
WARM_MS = 120000  # set-up requests wait out the server's first compiles


# -- the server (the process that holds the chip) ---------------------------

def run(win):
    from brpc_tpu import native

    native.load()  # builds the native core on a checkout's first run
    from brpc_tpu import rpc
    from brpc_tpu.rpc.device_transport import default_block_pool
    from brpc_tpu.rpc.proto import tensor_service_pb2 as ts_pb2
    from brpc_tpu.rpc.service import rpc_method
    from brpc_tpu.rpc.tensor_service import TensorStoreService

    if win.trace:
        class Store(TensorStoreService):
            """The store with a profiler span around each handler."""

            @rpc_method(ts_pb2.TensorPushRequest, ts_pb2.TensorPushResponse)
            def Push(self, cntl, request, response, done):
                with win.annotate("push"):
                    super().Push(cntl, request, response, done)
    else:
        Store = TensorStoreService

    traffic = win.traffic
    store = Store()
    srv = rpc.Server(rpc.ServerOptions(use_native_runtime=True))
    srv.add_service(store)
    if srv.start("127.0.0.1:0") != 0:
        raise RuntimeError("tensor store server failed to start")
    target = str(srv.listen_endpoint)
    clients = []
    try:
        for i in range(int(traffic["clients"])):
            clients.append(_spawn_client(win, target, i))
        device = win.devices[0]
        if default_block_pool().device != device:
            raise RuntimeError(f"block pool on {default_block_pool().device}"
                               f", cell chip {device}")
        _warm_pool(traffic, device)
        checksum = lib.device_checksum_fn()
        for c in clients:
            _expect_line(c, "ready")
        counters0 = native.stats_counters()
        t0 = win.begin()
        for c in clients:
            c.stdin.write(json.dumps({"t0": t0, "seconds": win.seconds})
                          + "\n")
            c.stdin.flush()
        results = [json.loads(_expect_line(c, "{")) for c in clients]
        records = [r for res in results for r in res["records"]]
        win.end(records)
        win.data["counters"] = {"before": counters0,
                                "after": native.stats_counters()}
        win.data["memory_peak_bytes"] = lib.memory_peak(win.devices)
        _check_pushes(win, store, device, checksum, results)
    finally:
        for c in clients:
            if c.poll() is None:
                c.kill()
            c.wait()
        srv.stop()
        srv.join(10)


def _spawn_client(win, target: str, index: int):
    args = {"target": target, "seed": win.seed, "client": index,
            "traffic": win.traffic}
    # clients run on the CPU backend; their compiles stay out of the
    # chip's cache
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--client",
         json.dumps(args)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
        cwd=lib.ROOT)


def _warm_pool(traffic, device):
    """Compiles the block pool's fill and view programs for every size the
    traffic sends, in every size class the pool may place it in: a busy
    class spills a push into the next larger one, and a program compiled
    in the window would stall it. Mirrors put_via_pool's staging."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from brpc_tpu.rpc.device_transport import DeviceBlockPool, pool_programs

    fill, view = pool_programs()
    for nbytes in (int(k) << 10 for k in traffic["sizes_kib"]):
        for size_class in DeviceBlockPool.SIZE_CLASSES:
            if nbytes > size_class:
                continue
            block = jax.device_put(jnp.zeros((size_class,), jnp.uint8),
                                   device)
            staged = np.zeros(min(DeviceBlockPool._pad_quantum(nbytes),
                                  size_class), np.uint8)
            view(fill(block, staged), nbytes, np.dtype(np.float32),
                 (nbytes // 4,)).block_until_ready()


def _expect_line(proc, prefix: str) -> str:
    while True:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(f"client {proc.pid} exited with "
                               f"{proc.wait()} before sending {prefix!r}")
        if line.startswith(prefix):
            return line


def _candidates(writes):
    """Payload keys a name may hold after `writes` (records of one name):
    every write that no acknowledged write strictly follows. A failed
    write may or may not have landed, so a name with one accepts any."""
    if any(not w["ok"] for w in writes):
        return {(w["nbytes"], w["payload"]) for w in writes}
    last_sent = max(w["sent"] for w in writes)
    return {(w["nbytes"], w["payload"]) for w in writes
            if w["done"] >= last_sent}


def _check_pushes(win, store, device, checksum, results):
    import jax

    wrong = checked = 0
    for res in results:
        sums = {(int(k.split(":")[0]), int(k.split(":")[1])): tuple(v)
                for k, v in res["checksums"].items()}
        by_name = {}
        for r in res["records"]:
            by_name.setdefault(r["name"], []).append(r)
        for name, writes in sorted(by_name.items()):
            checked += 1
            held = store.get(name)
            arr = held[0] if held else None
            if (not isinstance(arr, jax.Array) or arr.devices() != {device}
                    or checksum(arr) not in {sums[k] for k in
                                             _candidates(writes)}):
                wrong += 1
    sys.stderr.write(f"names checked: {checked}\n")
    win.check("wrong_names", wrong, win.config["limits"]["wrong_names"])


# -- the control and the planted faults (benchmark/faults.py) ---------------

def faults_for(traffic: dict):
    """One chip has no exchange to leave out."""
    return ("control", "unchanged", "half", "altered")


def _alter_one(arr):
    """The landed array with its first element changed."""
    return arr.reshape(-1).at[0].add(1).reshape(arr.shape)


def patched(name: str, traffic: dict):
    """The control or a planted fault, patched into the program (see
    benchmark/faults.py)."""
    from brpc_tpu.rpc import tensor_service

    Store = tensor_service.TensorStoreService
    push = Store.Push
    if name == "altered":
        receive = tensor_service.receive_tensors

        def altered_receive(*a, **k):
            arrays, seq = receive(*a, **k)
            return [_alter_one(x) for x in arrays], seq

        return lib.swap(tensor_service, "receive_tensors", altered_receive)
    def patched_push(self, cntl, request, response, done):
        import jax.numpy as jnp

        before = self.get(request.name)
        push(self, cntl, request, response, done)
        with self._lock:
            held = self._store[request.name]
            if name == "control":
                self._store[request.name] = [
                    a.astype(jnp.bfloat16).astype(a.dtype) for a in held]
            elif name == "half":  # the second half of each tensor lost
                self._store[request.name] = [
                    a.reshape(-1).at[a.size // 2:].set(0).reshape(a.shape)
                    for a in held]
            elif before is None:  # unchanged
                self._store.pop(request.name, None)
            else:
                self._store[request.name] = before

    patched_push.__rpc_method__ = push.__rpc_method__
    return lib.swap(Store, "Push", patched_push)


# -- one client process (JAX on the CPU backend) ----------------------------

def client_main(args: dict):
    import time

    sys.path.insert(0, lib.ROOT)
    from brpc_tpu.rpc.channel import ChannelOptions
    from brpc_tpu.rpc.tensor_service import TensorClient, make_device_channel

    t = args["traffic"]
    seed, me = int(args["seed"]), int(args["client"])
    kind = lib.load_module(os.path.join(BENCH_DIR, "traffic", "kinds",
                                        t["kind"] + ".py"), "kind")
    timeout_ms = float(t["timeout_ms"])
    client = TensorClient(make_device_channel(
        args["target"], ChannelOptions(timeout_ms=timeout_ms, max_retry=0,
                                       connect_timeout_ms=5000)))
    sizes = [int(k) << 10 for k in t["sizes_kib"]]
    payloads, checksums = {}, {}
    for nbytes in sizes:
        for p in range(int(t["payloads"])):
            host = lib.seeded_fp32(seed, (PAYLOAD_STREAM, me, nbytes, p),
                                   nbytes // 4)
            payloads[(nbytes, p)] = host
            checksums[f"{nbytes}:{p}"] = lib.host_checksum(host)
    for nbytes in sizes:  # warm every shape the window sends
        cntl, resp = client.push(f"warm/c{me}/{nbytes}",
                                 [payloads[(nbytes, 0)]], WARM_MS)
        if cntl.failed() or not resp.ok:
            raise SystemExit(f"warm-up push: {cntl.error_text}")

    def send(req):
        cntl, resp = client.push(
            req["name"], [payloads[(req["nbytes"], req["payload"])]],
            timeout_ms)
        return (not cntl.failed() and resp.ok), cntl.error_text

    print("ready", flush=True)
    go = json.loads(sys.stdin.readline())
    # the generator's own collector pauses would read as the server's
    gc.disable()
    records = kind.drive(send, t, seed, me, go["t0"], go["seconds"],
                         time.monotonic)
    gc.enable()
    print(json.dumps({"records": records, "checksums": checksums}),
          flush=True)
    client.channel.close()


if __name__ == "__main__":
    if sys.argv[1:2] != ["--client"]:
        raise SystemExit("usage: tensor_store.py --client <json>")
    client_main(json.loads(sys.argv[2]))
