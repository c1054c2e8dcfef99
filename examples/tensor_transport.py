#!/usr/bin/env python
"""tensor_transport — the rdma_performance counterpart
(example/rdma_performance/): pushes/pulls device tensors through the
TensorStore service over a device-handshaked channel and reports achieved
throughput, then probes raw collective bandwidth on the mesh.

  python examples/tensor_transport.py [--mb 8] [--iters 10]
"""
import argparse
import sys
import time

sys.path.insert(0, ".")

import numpy as np  # noqa: E402

from brpc_tpu import rpc  # noqa: E402
from brpc_tpu.rpc.tensor_service import (  # noqa: E402
    TensorClient,
    TensorStoreService,
    make_device_channel,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    svc = TensorStoreService()
    srv = rpc.Server()
    srv.add_service(svc)
    assert srv.start("127.0.0.1:0") == 0
    ch = make_device_channel(str(srv.listen_endpoint))
    client = TensorClient(ch)

    import jax.numpy as jnp

    nbytes = args.mb << 20
    arr = jnp.zeros((nbytes // 4,), jnp.float32)
    # warm
    cntl, _ = client.push("warm", [arr])
    assert not cntl.failed(), cntl.error_text
    sock = cntl._current_sock
    print(f"endpoint state: {sock.app_state.state} "
          f"(2=ESTABLISHED, 3=FALLBACK_TCP), "
          f"same_process={sock.app_state.same_process}")

    t0 = time.perf_counter()
    for i in range(args.iters):
        cntl, _ = client.push(f"t{i}", [arr])
        assert not cntl.failed(), cntl.error_text
    dt = time.perf_counter() - t0
    total = nbytes * args.iters
    print(f"pushed {args.iters} x {args.mb}MB in {dt:.3f}s "
          f"-> {total / dt / 1e9:.2f} GB/s "
          f"(zero-copy in-process device lane)")

    cntl, pulled = client.pull("t0")
    assert pulled is not None
    np.testing.assert_allclose(np.asarray(pulled[0])[:8],
                               np.asarray(arr)[:8])
    print("pull verified")

    # cross-process lane: a client child pushes into THIS server, which
    # holds the device. The child is host-only (JAX_PLATFORMS=cpu): a
    # chip belongs to one process. Payloads ride the descriptor-ring
    # fabric into our arena and land in HBM (rdma_performance shape).
    import os
    import subprocess

    script = (
        "import sys, time; sys.path.insert(0, '.')\n"
        "import numpy as np\n"
        "from brpc_tpu.rpc import device_transport as dt\n"
        "from brpc_tpu.rpc.tensor_service import (TensorClient,\n"
        "                                         make_device_channel)\n"
        "port, nbytes, iters = map(int, sys.argv[1:4])\n"
        "client = TensorClient(make_device_channel(f'127.0.0.1:{port}'))\n"
        "arr = np.zeros(nbytes // 4, np.float32)\n"
        "cntl, _ = client.push('xwarm', [arr])\n"
        "assert not cntl.failed(), cntl.error_text\n"
        "lanes0 = dt.lane_counters()\n"
        "t0 = time.perf_counter()\n"
        "for i in range(iters):\n"
        "    cntl, _ = client.push(f'x{i}', [arr])\n"
        "    assert not cntl.failed(), cntl.error_text\n"
        "dt_s = time.perf_counter() - t0\n"
        "lanes1 = dt.lane_counters()\n"
        "lane = [k for k in lanes1 if lanes1[k] > lanes0[k]]\n"
        "print(f'{dt_s} {lane}', flush=True)\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", script, str(srv.listen_endpoint.port),
         str(nbytes), str(args.iters)],
        capture_output=True, text=True, timeout=120, cwd=".",
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr
    dtx, lane = res.stdout.split(maxsplit=1)
    dtx = float(dtx)
    stored = svc.get(f"x{args.iters - 1}")[0]
    print(f"cross-process pushed {args.iters} x {args.mb}MB in {dtx:.3f}s "
          f"-> {nbytes * args.iters / dtx / 1e9:.2f} GB/s "
          f"(lanes {lane.strip()}, stored on {stored.devices()})")

    import jax

    if len(jax.devices()) >= 2:
        from brpc_tpu import parallel

        n = len(jax.devices())
        mesh = parallel.make_mesh({"x": n})
        stats = parallel.ici_bandwidth_probe(mesh, "x", nbytes=1 << 22,
                                             iters=5)
        print(f"mesh allreduce over {n} devices: "
              f"{stats['allreduce_GBps']:.2f} GB/s")
    srv.stop()


if __name__ == "__main__":
    main()
