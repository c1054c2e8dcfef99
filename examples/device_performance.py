#!/usr/bin/env python
"""device_performance — the example/rdma_performance twin: a
bvar-instrumented client/server pair hammering the device-transport lane
with concurrent pushers, reporting qps / latency percentiles / achieved
bandwidth from LatencyRecorders the way rdma_performance's client does
(client.cpp:50-52,136-183: g_latency_recorder + bvar reads per second).

  python examples/device_performance.py [--threads 2] [--mb 2] [--iters 8]
"""
import argparse
import sys
import threading
import time

sys.path.insert(0, ".")

import numpy as np  # noqa: E402

from brpc_tpu import bvar, rpc  # noqa: E402
from brpc_tpu.rpc import device_transport as dt  # noqa: E402
from brpc_tpu.rpc.tensor_service import (  # noqa: E402
    TensorClient,
    TensorStoreService,
    make_device_channel,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--threads", type=int, default=2)
    ap.add_argument("--mb", type=int, default=2)
    ap.add_argument("--iters", type=int, default=8)
    args = ap.parse_args()

    srv = rpc.Server(rpc.ServerOptions(num_threads=4))
    srv.add_service(TensorStoreService())
    assert srv.start("127.0.0.1:0") == 0
    target = str(srv.listen_endpoint)

    recorder = bvar.LatencyRecorder("device_perf")
    bytes_moved = bvar.Adder("device_perf_bytes")
    errors = bvar.Adder("device_perf_errors")
    payload = np.random.default_rng(0).standard_normal(
        (args.mb * 1024 * 1024) // 4).astype(np.float32)

    def pusher(tid: int):
        ch = make_device_channel(target)
        client = TensorClient(ch)
        for i in range(args.iters):
            t0 = time.perf_counter()
            cntl, resp = client.push(f"t{tid}.{i}", [payload])
            if cntl.failed() or not resp.ok:
                errors.update(1)
                continue
            recorder.update((time.perf_counter() - t0) * 1e6)
            bytes_moved.update(payload.nbytes)
        ch.close()

    t0 = time.perf_counter()
    threads = [threading.Thread(target=pusher, args=(t,))
               for t in range(args.threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    total = bytes_moved.get_value()
    counters = dt.lane_counters()
    lane = max(counters, key=counters.get)
    print(f"lane={lane} pushes={recorder.count()} "
          f"errors={errors.get_value()}")
    print(f"avg={recorder.latency():.0f}us "
          f"p99={recorder.latency_percentile(0.99):.0f}us "
          f"max={recorder.max_latency():.0f}us")
    print(f"throughput={total / wall / 1e9:.2f} GB/s "
          f"({total / 1e6:.0f} MB in {wall:.2f}s)")
    srv.stop()

    # The NATIVE bulk data path (streamed attachments through the C++
    # runtime: socket write queue -> dispatcher -> zero-copy echo): the
    # large-payload throughput of the native port, reported alongside
    # the Python-lane number above.
    try:
        import ctypes

        from brpc_tpu import native

        if native.available():
            lib = native.load()
            lib.nat_rpc_client_bench_bulk.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                ctypes.c_double, ctypes.POINTER(ctypes.c_uint64)]
            lib.nat_rpc_client_bench_bulk.restype = ctypes.c_double
            port = native.rpc_server_start(native_echo=True)
            try:
                moved = ctypes.c_uint64(0)
                gbps = lib.nat_rpc_client_bench_bulk(
                    b"127.0.0.1", port, args.mb << 20, 1.5,
                    ctypes.byref(moved))
                print(f"native_bulk={gbps:.2f} GB/s "
                      f"({moved.value / 1e6:.0f} MB echoed, "
                      f"{args.mb}MB attachments)")
            finally:
                native.rpc_server_stop()
    except Exception as e:
        print(f"native bulk lane unavailable: {e}")
    return 0 if recorder.count() > 0 and errors.get_value() == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
