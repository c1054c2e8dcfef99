"""Benchmark harnesses — the multi_threaded_echo + rdma_performance analogs.

echo_bench(): in-process loopback echo QPS with several client threads,
instrumented with a bvar LatencyRecorder exactly like
example/multi_threaded_echo_c++/client.cpp; reported against the
reference's 500k+ QPS production claim (docs/en/overview.md:88,
BASELINE.md).

collective_bench(): achieved allreduce bandwidth on the available device
mesh — the rdma_performance role (example/rdma_performance/client.cpp)
with ICI collectives in place of verbs.
"""
from __future__ import annotations

import threading
import time

BASELINE_QPS = 500_000.0  # docs/en/overview.md:88

# Published bf16 peak FLOP/s of one chip, keyed by jax's device_kind
# (Google Cloud TPU documentation, the per-version "System architecture"
# pages). A kind missing here is a KeyError, never a default.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v6 lite": 918e12,   # v6e
}


def echo_bench(n_threads: int = 8, duration_s: float = 3.0,
               payload: int = 16) -> dict:
    from brpc_tpu import bvar, rpc
    from brpc_tpu.rpc.proto import echo_pb2

    class EchoService(rpc.Service):
        @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        def Echo(self, cntl, request, response, done):
            response.message = request.message
            done()

    srv = rpc.Server(rpc.ServerOptions(num_threads=8,
                                       has_builtin_services=False))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0

    recorder = bvar.LatencyRecorder()
    stop = threading.Event()
    counts = [0] * n_threads
    errors_seen = [0] * n_threads
    msg = "x" * payload

    def client_thread(idx: int):
        ch = rpc.Channel(rpc.ChannelOptions(timeout_ms=2000))
        ch.init(str(srv.listen_endpoint))
        req = echo_pb2.EchoRequest(message=msg)
        while not stop.is_set():
            t0 = time.monotonic()
            cntl, resp = ch.call("EchoService.Echo", req,
                                 echo_pb2.EchoResponse)
            if cntl.failed():
                errors_seen[idx] += 1
                continue
            recorder.update((time.monotonic() - t0) * 1e6)
            counts[idx] += 1

    threads = [threading.Thread(target=client_thread, args=(i,))
               for i in range(n_threads)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(5)
    elapsed = time.monotonic() - t_start
    srv.stop()

    total = sum(counts)
    qps = total / elapsed
    return {
        "metric": "echo_qps_loopback",
        "value": round(qps, 1),
        "unit": "qps",
        "vs_baseline": round(qps / BASELINE_QPS, 4),
        "extra": {
            "threads": n_threads,
            "requests": total,
            "errors": sum(errors_seen),
            "avg_latency_us": round(recorder.latency(), 1),
            "p99_latency_us": round(recorder.latency_percentile(0.99), 1),
        },
    }


def http_lane_bench(seconds: float = 1.5) -> dict:
    """The native HTTP/1.1 lane (VERDICT r3 #1): HTTP parses in the native
    cut loop of a use_native_runtime port; usercode is C++ for /echo
    (builtin-native-service discipline, server.cpp:468-563) and Python for
    /EchoService/Echo (py lane, RPC-over-HTTP with JSON body). Reference
    counterpart: policy/http_rpc_protocol.cpp + details/http_parser.cpp.

    Returns {http_qps, http_py_qps}: native-usercode and Python-usercode
    throughput through the same native parse path.
    """
    import json as _json

    from brpc_tpu import native, rpc
    from brpc_tpu.rpc.proto import echo_pb2

    class EchoService(rpc.Service):
        @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        def Echo(self, cntl, request, response, done):
            response.message = request.message
            done()

    class PyEchoService(rpc.Service):
        """Distinct name so the native EchoService.Echo handler can't
        shadow it — the Python-usercode gRPC lane."""

        @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        def Echo(self, cntl, request, response, done):
            response.message = request.message
            done()

    srv = rpc.Server(rpc.ServerOptions(num_threads=4,
                                       use_native_runtime=True,
                                       native_builtin_echo=True))
    srv.add_service(EchoService())
    srv.add_service(PyEchoService())
    assert srv.start("127.0.0.1:0") == 0
    try:
        port = srv.listen_endpoint.port
        nat = native.http_client_bench("127.0.0.1", port, nconn=4,
                                       pipeline=128, seconds=seconds,
                                       path="/echo", post_body=b"x" * 16)
        body = _json.dumps({"message": "x" * 16}).encode()
        py = native.http_client_bench("127.0.0.1", port, nconn=2,
                                      pipeline=32, seconds=seconds,
                                      path="/EchoService/Echo",
                                      post_body=body,
                                      content_type="application/json")
        # gRPC-over-h2 through the same native parse path: native
        # usercode (the registered EchoService.Echo native handler) and
        # Python usercode (PyEchoService on the py lane)
        grpc_nat = native.grpc_client_bench("127.0.0.1", port, nconn=4,
                                            window=128, seconds=seconds,
                                            path="/EchoService/Echo",
                                            payload=b"x" * 16)
        req = echo_pb2.EchoRequest(message="x" * 16)
        grpc_py = native.grpc_client_bench(
            "127.0.0.1", port, nconn=2, window=32, seconds=seconds,
            path="/PyEchoService/Echo", payload=req.SerializeToString())
        # CLIENT lanes (nat_client.cpp): same loopback server, but the
        # load generator is the REAL framework client — NatChannel + h2
        # session / pipelined HTTP FIFO (reference client half:
        # policy/http2_rpc_protocol.h:133, http_rpc_protocol.cpp:663)
        grpc_cli = native.grpc_channel_bench(
            "127.0.0.1", port, nconn=2, window=128, seconds=seconds,
            path="/EchoService/Echo", payload=req.SerializeToString())
        http_cli = native.http_channel_bench(
            "127.0.0.1", port, nconn=2, window=128, seconds=seconds,
            path="/echo", body=b"x" * 16)
    finally:
        srv.stop()
    return {"http_qps": round(nat["qps"], 1),
            "http_py_qps": round(py["qps"], 1),
            "grpc_qps": round(grpc_nat["qps"], 1),
            "grpc_py_qps": round(grpc_py["qps"], 1),
            "grpc_client_qps": round(grpc_cli["qps"], 1),
            "http_client_qps": round(http_cli["qps"], 1)}


def _worker_echo_factory():
    """Service factory for the py-worker bench lane (imported by worker
    subprocesses as brpc_tpu.bench:_worker_echo_factory)."""
    from brpc_tpu import rpc
    from brpc_tpu.rpc.proto import echo_pb2

    class EchoService(rpc.Service):
        @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        def Echo(self, cntl, request, response, done):
            response.message = request.message
            done()

    return [EchoService()]


def py_workers_lane_bench(seconds: float = 1.5, workers: int = 2) -> dict:
    """Python usercode across WORKER PROCESSES (the shm lane,
    nat_shm_lane.cpp): same workload as http_py_qps but dispatched to
    `workers` interpreters. On a 1-CPU host this matches the in-process
    number (CPU-bound, not GIL-bound); on multicore hosts it scales with
    the worker count — the reference's usercode-concurrency product."""
    import json as _json
    import time as _time

    from brpc_tpu import native, rpc

    srv = rpc.Server(rpc.ServerOptions(
        num_threads=2, use_native_runtime=True, py_workers=workers,
        py_worker_factory="brpc_tpu.bench:_worker_echo_factory"))
    for s in _worker_echo_factory():
        srv.add_service(s)
    assert srv.start("127.0.0.1:0") == 0
    try:
        port = srv.listen_endpoint.port
        body = _json.dumps({"message": "x" * 16}).encode()
        # readiness: a worker answering 200 proves the lane is up (boot
        # includes a fresh interpreter + .so load; a fixed sleep flaked)
        import urllib.request as _url

        deadline = _time.time() + 20
        while _time.time() < deadline:
            try:
                req = _url.Request(
                    f"http://127.0.0.1:{port}/EchoService/Echo",
                    data=body,
                    headers={"Content-Type": "application/json"})
                if _url.urlopen(req, timeout=3).status == 200:
                    break
            except Exception:
                _time.sleep(0.3)
        r = native.http_client_bench("127.0.0.1", port, nconn=2,
                                     pipeline=32, seconds=seconds,
                                     path="/EchoService/Echo",
                                     post_body=body,
                                     content_type="application/json")
    finally:
        srv.stop()
    return {"http_py_workers_qps": round(r["qps"], 1),
            "py_workers": workers}


def redis_lane_bench(seconds: float = 1.5) -> dict:
    """Native Redis lane (VERDICT r4 #6, policy/redis_protocol.cpp role):
    RESP parsed in the native cut loop. redis_qps = native in-memory
    store execute (fully native); redis_py_qps = Python RedisService
    handlers behind the native parse (kind-6 py lane)."""
    from brpc_tpu import native, rpc
    from brpc_tpu.rpc.redis import DictRedisService, RedisService

    srv = rpc.Server(rpc.ServerOptions(num_threads=4,
                                       use_native_runtime=True,
                                       redis_service=RedisService(),
                                       native_redis_store=True))
    assert srv.start("127.0.0.1:0") == 0
    try:
        port = srv.listen_endpoint.port
        nat = native.redis_client_bench("127.0.0.1", port, nconn=2,
                                        pipeline=64, seconds=seconds)
    finally:
        srv.stop()
    srv2 = rpc.Server(rpc.ServerOptions(num_threads=4,
                                        use_native_runtime=True,
                                        redis_service=DictRedisService()))
    assert srv2.start("127.0.0.1:0") == 0
    try:
        port2 = srv2.listen_endpoint.port
        py = native.redis_client_bench("127.0.0.1", port2, nconn=2,
                                       pipeline=64, seconds=seconds)
    finally:
        srv2.stop()
    return {"redis_qps": round(nat["qps"], 1),
            "redis_py_qps": round(py["qps"], 1)}


def stream_lane_bench(total_mb: int = 64, chunk_mb: int = 4) -> dict:
    """Streaming over the native port (VERDICT r3 #2): DATA frames are cut
    in the native loop (kind-5 lane) and land in the Python Stream via
    zero-copy wraps; the client writes zero-copy user blocks. Reference
    counterpart: stream.cpp:98-115,307 write path + 458-586 window.

    Returns {stream_GBps} for a one-direction 64MB push, window 64MB.
    """
    import threading

    from brpc_tpu import rpc
    from brpc_tpu.rpc import errors
    from brpc_tpu.rpc.proto import echo_pb2

    class CountingSink(rpc.StreamInputHandler):
        def __init__(self):
            self.nbytes = 0
            self.done = threading.Event()
            self.target = total_mb << 20

        def on_received_messages(self, stream, messages):
            for m in messages:
                self.nbytes += len(m)
            if self.nbytes >= self.target:
                self.done.set()

    sink = CountingSink()

    class StreamSinkService(rpc.Service):
        @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        def OpenStream(self, cntl, request, response, done):
            s = rpc.stream_accept(
                cntl, rpc.StreamOptions(handler=sink,
                                        max_buf_size=64 << 20))
            if s is None:
                cntl.set_failed(errors.EINVAL, "no stream")
            response.message = "ok"
            done()

    srv = rpc.Server(rpc.ServerOptions(num_threads=4,
                                       use_native_runtime=True))
    srv.add_service(StreamSinkService())
    assert srv.start("127.0.0.1:0") == 0
    try:
        ch = rpc.Channel()
        assert ch.init(str(srv.listen_endpoint)) == 0
        cntl = rpc.Controller()
        cntl.timeout_ms = 5000
        stream = rpc.stream_create(
            cntl, rpc.StreamOptions(max_buf_size=64 << 20))
        resp = echo_pb2.EchoResponse()
        ch.call_method("StreamSinkService.OpenStream", cntl,
                       echo_pb2.EchoRequest(message="open"), resp)
        assert not cntl.failed(), cntl.error_text
        assert stream.wait_connected(3)
        chunk = b"x" * (chunk_mb << 20)
        total = total_mb << 20
        t0 = time.perf_counter()
        sent = 0
        while sent < total:
            rc = stream.write(chunk, timeout_s=15)
            if rc != 0:
                break
            sent += len(chunk)
        sink.done.wait(30)
        dt = time.perf_counter() - t0
        stream.close()
    finally:
        srv.stop()
    return {"stream_GBps": round(total / dt / 1e9, 3) if dt > 0 else 0.0}


def native_echo_bench(nconn: int = 2, seconds: float = 3.0,
                      payload: int = 16, pipeline: int = 128) -> dict:
    """Native C++ data path: epoll echo server + pipelined clients, both
    speaking the tpu_std wire format (native/src/echo_runtime.cpp). The
    pipelined window plays the role of the reference's many concurrent
    client bthreads (docs/cn/benchmark.md 单机1 setup)."""
    from brpc_tpu import native

    port = native.echo_server_start()
    try:
        sync = native.echo_client_bench("127.0.0.1", port, nconn=1,
                                        seconds=1.0, payload=payload,
                                        pipeline=1)
        piped = native.echo_client_bench("127.0.0.1", port, nconn=nconn,
                                         seconds=seconds, payload=payload,
                                         pipeline=pipeline)
    finally:
        native.echo_server_stop()
    qps = piped["qps"]
    return {
        "metric": "echo_qps_native",
        "value": round(qps, 1),
        "unit": "qps",
        "vs_baseline": round(qps / BASELINE_QPS, 4),
        "extra": {
            "connections": nconn,
            "pipeline_depth": pipeline,
            "payload_bytes": payload,
            "requests": piped["requests"],
            "sync_single_conn_qps": round(sync["qps"], 1),
        },
    }


def framework_echo_bench(nconn: int = 4, fibers_per_conn: int = 64,
                         seconds: float = 3.0, payload: int = 16) -> dict:
    """THE headline: echo through the native FRAMEWORK path — Channel
    pending table -> Socket write queue -> epoll dispatcher -> reader
    fibers -> Server dispatch -> response completion, all on the fiber
    scheduler and native IOBuf (nat_rpc.cpp). The multi_threaded_echo
    shape: many synchronous callers, shared connections.

    Extra fields report the pure-Python stack and the raw epoll bypass
    (ceiling probe, echo_runtime.cpp) honestly alongside."""
    import ctypes
    import os as _os_env

    from brpc_tpu import native

    # In-process loopback: server and client runtimes share one process,
    # so their sockets would multiplex through the same dispatcher loops.
    # NAT_DISP_SPLIT=1 partitions the pool (accepted sockets on even
    # loops, dialed on odd) so the numbers stop including cross-runtime
    # interference — see pick_dispatcher in native/src/nat_server.cpp.
    # Dedicated-process lanes (scaling_bench) leave it off. Must be set
    # before the first native runtime use in this process.
    _os_env.environ.setdefault("NAT_DISP_SPLIT", "1")

    # tail latency rides the native stat cells (nat_stats.cpp log2
    # histograms): zero them so the per-lane percentiles reported at the
    # end describe THIS run only
    try:
        native.stats_reset()
    except Exception:
        pass

    # flight recorder: BRPC_TPU_BENCH_PROF=1 attaches the in-process
    # native profiler (nat_prof) to the loopback lanes — the standing
    # replacement for the hand-run PROFILE_r*.md rounds; the gate
    # (tools/check.sh --bench) stores the flat profile in the artifact
    # so a lane regression arrives with its own profile attached.
    import os as _os

    prof_attached = False
    mu_prof_attached = False
    if _os.environ.get("BRPC_TPU_BENCH_PROF") == "1":
        try:
            prof_attached = native.prof_start(99) == 0
        except Exception:
            prof_attached = False
        # contention flight recorder rides the same knob: every
        # contended NatMutex wait in the loopback window is sampled
        # (threshold 0 — the slow path only fires on contention, so the
        # uncontended hot path cost is unchanged)
        try:
            mu_prof_attached = native.mu_prof_start(0, 1, 42) == 0
        except Exception:
            mu_prof_attached = False

    def _async_lane(port_, conns, window=256):
        """One async-windowed measurement; (qps, requests)."""
        out = ctypes.c_uint64(0)
        q = native.load().nat_rpc_client_bench_async(
            b"127.0.0.1", port_, conns, int(window),
            max(1.0, seconds / 2), payload, ctypes.byref(out))
        return q, out.value

    port = native.rpc_server_start(native_echo=True)
    try:
        fw = native.rpc_client_bench("127.0.0.1", port, nconn=nconn,
                                     fibers_per_conn=fibers_per_conn,
                                     seconds=seconds, payload=payload)
    finally:
        native.rpc_server_stop()

    # the io_uring lane (RingListener: provided-buffer recvs +
    # fixed-buffer sends, poller-inline drains), when the kernel allows
    # it — measured with both client shapes (sync fibers and the async
    # window)
    ring_qps = 0.0
    ring_async_qps = 0.0
    ring_async_requests = 0
    ring_async_shape = f"{nconn}conn"
    try:
        if native.use_io_uring(True) == 1:
            port_r = native.rpc_server_start(native_echo=True)
            try:
                ring = native.rpc_client_bench(
                    "127.0.0.1", port_r, nconn=nconn,
                    fibers_per_conn=fibers_per_conn,
                    seconds=seconds, payload=payload)
                ring_qps = ring["qps"]
                # shape sweep: more connections shard across the
                # dispatcher pool on many-core hosts, deeper windows
                # amortize per-burst costs; keep the best
                for shape_conns, win in ((nconn, 256), (nconn * 2, 256),
                                         (nconn, 512)):
                    q, reqs = _async_lane(port_r, shape_conns, win)
                    if q > ring_async_qps:
                        ring_async_qps = q
                        ring_async_requests = reqs
                        ring_async_shape = f"{shape_conns}conn/w{win}"
            finally:
                native.rpc_server_stop()
    except Exception:
        pass
    finally:
        try:
            native.use_io_uring(False)
        except Exception:
            pass

    # ceiling probe: purpose-built epoll loop, no scheduler/IOBuf/Socket
    bypass_qps = 0.0
    try:
        port2 = native.echo_server_start()
        try:
            bypass = native.echo_client_bench("127.0.0.1", port2, nconn=2,
                                              seconds=1.5, payload=payload,
                                              pipeline=128)
            bypass_qps = bypass["qps"]
        finally:
            native.echo_server_stop()
    except Exception:
        pass

    # async windowed lane: done-callback completions instead of parked
    # fibers (the brpc async-call usage pattern). Two connection shapes:
    # the narrow one wins on few cores, the wide one on many (sockets
    # shard across the dispatcher pool) — report the better.
    async_qps = 0.0
    async_requests = 0
    async_shape = f"{nconn}conn"
    try:
        port3 = native.rpc_server_start(native_echo=True)
        try:
            for shape_conns in (nconn, nconn * 2):
                q, reqs = _async_lane(port3, shape_conns)
                if q > async_qps:
                    async_qps = q
                    async_requests = reqs
                    async_shape = f"{shape_conns}conn"
        finally:
            native.rpc_server_stop()
    except Exception:
        pass

    # The pure-Python framework figure, honestly reported — measured in a
    # CLEAN subprocess: in-process it runs after every native lane has
    # started scheduler workers, dispatcher loops and py-lane threads in
    # this process, and that contamination (not the Python stack) moved
    # the number round over round (VERDICT r3 weak #2 root cause).
    python_qps = 0.0
    try:
        import os
        import subprocess
        import sys

        script = (
            "import sys; sys.path.insert(0, '.')\n"
            "from brpc_tpu.bench import echo_bench\n"
            f"r = echo_bench(n_threads=4, duration_s=1.5, "
            f"payload={payload})\n"
            "print(r['value'], flush=True)\n")
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        res = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=120,
                             cwd=repo_root,
                             env=dict(os.environ, JAX_PLATFORMS="cpu"))
        if res.returncode == 0:
            python_qps = float(res.stdout.strip().splitlines()[-1])
    except Exception:
        pass

    # the native HTTP/1.1 lane (VERDICT r3 #1): native parse + native
    # usercode (/echo) and native parse + Python usercode (RPC-over-HTTP)
    http_lanes = {}
    try:
        http_lanes = http_lane_bench(seconds=max(1.0, seconds / 2))
    except Exception:
        pass

    # native Redis lane (VERDICT r4 #6)
    redis_lanes = {}
    try:
        redis_lanes = redis_lane_bench(seconds=max(1.0, seconds / 2))
    except Exception:
        pass

    # flight-recorder replay lane (ISSUE 12): the committed golden
    # capture re-fired through the native replay client in press mode
    replay_lanes = {}
    try:
        replay_lanes = replay_lane_bench()
    except Exception:
        pass

    # native fan-out lanes (ISSUE 13, ROADMAP item 1): 32-backend
    # parallel fan-out + the Python-ParallelChannel comparison, then the
    # 1000-backend swarm churned by rolling SIGTERM restarts and live
    # naming updates (the zero-failed-RPC acceptance drill)
    fanout_lanes = {}
    try:
        fanout_lanes = fanout_lane_bench(seconds=max(1.0, seconds / 2))
    except Exception:
        pass
    swarm_lanes = {}
    try:
        swarm_lanes = fanout_swarm_bench()
    except Exception:
        pass

    # fleet-observatory scrape overhead (ISSUE 16): 1Hz builtin.stats
    # scrape armed vs unarmed — the <=3% always-on-scraping contract
    fleet_lanes = {}
    try:
        fleet_lanes = fleet_scrape_bench(round_s=max(1.0, seconds / 2))
    except Exception:
        pass

    # elastic-capacity drill (ISSUE 20): the fleet autoscaler resizing
    # a dynpart swarm live under the replayed golden-capture ramp, with
    # a mid-resize SIGKILL — zero failed RPCs, p99 under the ceiling,
    # capacity tracking the offered load, or the lane reports 0
    autoscale_lanes = {}
    try:
        autoscale_lanes = autoscale_drill_bench()
    except Exception:
        pass

    # connection-scale drill (ISSUE 14, ROADMAP item 5): 20k mostly-idle
    # keep-alive connections from client subprocesses, per-connection
    # bytes/fd/wakeup cost from the nat_res accounting, accept-storm
    # recovery, zero failed RPCs on the live subset
    conn_lanes = {}
    try:
        conn_lanes = conn_scale_bench()
    except Exception:
        pass

    # py-usercode across worker processes (VERDICT r4 #2, shm lane)
    worker_lanes = {}
    try:
        worker_lanes = py_workers_lane_bench(seconds=max(1.0, seconds / 2))
    except Exception:
        pass

    # streaming over the native port (VERDICT r3 #2)
    stream_lanes = {}
    try:
        stream_lanes = stream_lane_bench()
    except Exception:
        pass

    # the profiler window covers exactly the loopback lanes above (the
    # device/model sections below are DMA + XLA, a different profile)
    nat_prof = {}
    if prof_attached:
        try:
            native.prof_stop()
            flat = native.prof_report(collapsed=False)
            nat_prof = {
                "samples": native.prof_samples(),
                "flat": flat.splitlines()[:48],
            }
            native.prof_reset()
        except Exception:
            nat_prof = {}
    # top lock-wait stacks of the loopback window (extra.contention):
    # a lane regression caused by a lock reintroduced into the
    # write/dispatch path arrives with the contended stack attached
    contention = {}
    if mu_prof_attached:
        try:
            native.mu_prof_stop()
            collapsed = native.mu_prof_report(collapsed=True)
            contention = {
                "samples": native.mu_prof_samples(),
                "ranks": sorted(native.mu_rank_stats(),
                                key=lambda r: -r["wait_us"])[:16],
                "collapsed": collapsed.splitlines()[:32],
            }
            native.mu_prof_reset()
        except Exception:
            contention = {}

    # device-transport bandwidth (the rdma_performance analog): tracked
    # round over round in the artifact. Runs AFTER the loopback lanes
    # (its DMA sections poison them); shm_push runs first inside it.
    device_lanes = {}
    try:
        device_lanes = device_lane_bench()
    except Exception:
        pass

    # model step + collective rows (VERDICT r3 #6) — TPU work, last
    model_rows = model_collective_bench()

    # per-lane tail latency from the native log2 histograms (us): every
    # loopback lane above ran in this process, so the combined cells hold
    # echo/http/redis/grpc server latency (parse-complete -> response-
    # write) and the client-lane round trips. Tracked round over round so
    # a tail regression is visible even when qps holds.
    native_latency_us = {}
    try:
        for idx, lane_name in enumerate(native.stats_lane_names()):
            if not any(native.stats_hist(idx)):
                continue
            native_latency_us[lane_name] = {
                "p50": round(native.stats_quantile(idx, 0.50) / 1e3, 1),
                "p99": round(native.stats_quantile(idx, 0.99) / 1e3, 1),
                "p999": round(native.stats_quantile(idx, 0.999) / 1e3, 1),
            }
    except Exception:
        pass

    lanes = {"epoll": (fw["qps"], fw["requests"]),
             "io_uring": (ring_qps,
                          ring["requests"] if ring_qps > 0 else 0),
             "io_uring_async": (ring_async_qps, ring_async_requests),
             "async_windowed": (async_qps, async_requests)}
    lane = max(lanes, key=lambda k: lanes[k][0])
    qps, requests = lanes[lane]
    # per-lane client shape, so the headline's config is reproducible
    # (sync lanes park fibers_per_conn fibers; async keeps a 256-deep
    # window per connection with no per-call fiber)
    lane_config = {"epoll": f"{fibers_per_conn} sync fibers/conn",
                   "io_uring": f"{fibers_per_conn} sync fibers/conn",
                   "io_uring_async":
                       f"{ring_async_shape}, done-callbacks",
                   "async_windowed":
                       f"{async_shape}, window=256/conn, done-callbacks"}
    import os

    return {
        "metric": "echo_qps_framework_native",
        "value": round(qps, 1),
        "unit": "qps",
        "vs_baseline": round(qps / BASELINE_QPS, 4),
        "extra": {
            # client + server + py lanes share these cores; on 1 core the
            # absolute numbers carry the whole pipeline on one CPU.
            # Affinity, not cpu_count: the scaling lane keys on
            # sched_getaffinity, and benchgate's cpus2_scaling_x
            # unmeasurable-skip must agree with it (taskset/cgroup
            # cpusets shrink affinity without shrinking cpu_count).
            "host_cpus": len(os.sched_getaffinity(0)),
            "connections": nconn,
            "payload_bytes": payload,
            "requests": requests,
            "lane": lane,
            "lane_client_shape": lane_config[lane],
            "epoll_qps": round(fw["qps"], 1),
            "io_uring_qps": round(ring_qps, 1),
            "io_uring_async_qps": round(ring_async_qps, 1),
            "async_windowed_qps": round(async_qps, 1),
            "python_framework_qps": round(python_qps, 1),
            "bypass_ceiling_qps": round(bypass_qps, 1),
            "native_latency_us": native_latency_us,
            **({"nat_prof": nat_prof} if nat_prof else {}),
            **({"contention": contention} if contention else {}),
            "device_lanes": device_lanes,
            **http_lanes,
            **redis_lanes,
            **replay_lanes,
            **fanout_lanes,
            **swarm_lanes,
            **fleet_lanes,
            **autoscale_lanes,
            **conn_lanes,
            **worker_lanes,
            **stream_lanes,
            **model_rows,
        },
    }


def replay_lane_bench(times: int = 3, concurrency: int = 8) -> dict:
    """replay_qps: the committed 1k-request golden capture
    (tests/data/golden_capture_1k.rio, regenerate with
    tools/make_golden_capture.py) re-fired through the native replay
    client in press mode against a fresh native echo server — the
    flight recorder turned standing bench lane (any production-shaped
    capture can stand in for the golden file the same way). Zero failed
    RPCs is part of the lane's contract: a run with failures reports
    0 qps so the bench gate trips on it."""
    import os

    from brpc_tpu import native

    golden = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests", "data", "golden_capture_1k.rio")
    if not os.path.exists(golden):
        return {}
    port = native.rpc_server_start(native_echo=True)
    try:
        res = native.replay_run("127.0.0.1", port, golden, times=times,
                                concurrency=concurrency, timeout_ms=5000)
    finally:
        native.rpc_server_stop()
    if res["failed"]:
        return {"replay_qps": 0.0, "replay_failed": res["failed"]}
    return {"replay_qps": round(res["qps"], 1),
            "replay_p99_us": round(res["p99_us"], 1)}


def fleet_scrape_bench(round_s: float = 1.5, rounds: int = 3,
                       nconn: int = 2, fibers_per_conn: int = 32,
                       payload: int = 16) -> dict:
    """fleet_scrape_overhead_pct (ISSUE 16): headline echo qps with a
    1Hz fleet observatory scraping the SAME server (builtin.stats over
    the wire, full snapshot JSON each tick) versus unarmed, as a
    percent. The acceptance bar is <= 3% — the snapshot must stay cheap
    enough that always-on fleet scraping is free. Alternating
    unarmed/armed rounds, MAX qps per arm (host-noise discipline: a
    depressed round in either arm cannot fake an overhead or mask one
    that is real)."""
    from brpc_tpu import native
    from brpc_tpu.fleet import FleetObservatory

    port = native.rpc_server_start(native_echo=True)
    unarmed = 0.0
    armed = 0.0
    scrapes = 0
    try:
        # discarded warmup: the first round after server start runs cold
        # (fiber pool, dispatcher, client sockets) and measures ~25%
        # low on this host — an outlier in either arm would fake or
        # mask an overhead
        native.rpc_client_bench("127.0.0.1", port, nconn=nconn,
                                fibers_per_conn=fibers_per_conn,
                                seconds=min(1.0, round_s), payload=payload)
        for _ in range(rounds):
            r = native.rpc_client_bench("127.0.0.1", port, nconn=nconn,
                                        fibers_per_conn=fibers_per_conn,
                                        seconds=round_s, payload=payload)
            unarmed = max(unarmed, r["qps"])
            obs = FleetObservatory(endpoints=[f"127.0.0.1:{port}"],
                                   interval_s=1.0, register_bvars=False)
            try:
                obs.scrape_once()  # the loop ticks at 1Hz; prime now so
                obs.start()        # even a sub-second round is scraped
                r = native.rpc_client_bench(
                    "127.0.0.1", port, nconn=nconn,
                    fibers_per_conn=fibers_per_conn,
                    seconds=round_s, payload=payload)
                armed = max(armed, r["qps"])
                scrapes += obs.scrape_counts()[0]
            finally:
                obs.close()
    finally:
        native.rpc_server_stop()
    if unarmed <= 0:
        return {}
    overhead = max(0.0, (1.0 - armed / unarmed) * 100.0)
    return {"fleet_scrape_overhead_pct": round(overhead, 2),
            "fleet_scrape_unarmed_qps": round(unarmed, 1),
            "fleet_scrape_armed_qps": round(armed, 1),
            "fleet_scrape_count": scrapes}


def fanout_lane_bench(seconds: float = 1.5, backends: int = 32) -> dict:
    """Native fan-out lanes (ISSUE 13, ROADMAP item 1): one native echo
    server listening on `backends` ports (each port a distinct LB
    backend), fanned to by the C++ cluster's ParallelChannel verb —
    every call issues `backends` concurrent sub-calls over the
    DoublyBufferedData LB and merges responses natively.

    fanout_qps / fanout_p99_us: native parallel fan-out verb rate and
    tail. fanout_py_qps: the SAME fan-out through the pure-Python
    ParallelChannel against the same server (the path every fan-out
    paid before the native cluster); fanout_native_vs_py_x is the
    speedup the acceptance bar holds at >= 5x. Zero failed sub-calls is
    part of the lane contract: failures report 0 qps so the gate trips.
    """
    from brpc_tpu import native, rpc
    from brpc_tpu.rpc.proto import echo_pb2

    out: dict = {}
    port = native.rpc_server_start(native_echo=True)
    try:
        ports = [port]
        for _ in range(backends - 1):
            ports.append(native.rpc_server_add_port())
        h = native.cluster_create("rr", connect_timeout_ms=1000,
                                  health_check_ms=100, breaker=True)
        try:
            native.cluster_update(h, [f"127.0.0.1:{p}" for p in ports])
            r = native.cluster_bench(h, mode=1, param=0, seconds=seconds,
                                     concurrency=4, timeout_ms=3000)
            out["fanout_backends"] = backends
            if r["failed"]:
                out["fanout_qps"] = 0.0
                out["fanout_failed"] = r["failed"]
            else:
                out["fanout_qps"] = round(r["qps"], 1)
                out["fanout_p99_us"] = round(r["p99_us"], 1)
        finally:
            native.cluster_close(h)

        # the honest comparison: the pure-Python ParallelChannel fanning
        # to the SAME backends on the same host (sub-calls through the
        # Python Channel/Socket stack, threading.Event merge)
        from brpc_tpu.rpc.combo_channels import ParallelChannel

        pch = ParallelChannel()
        chans = []
        for p in ports:
            ch = rpc.Channel(rpc.ChannelOptions(timeout_ms=3000))
            ch.init(f"127.0.0.1:{p}")
            chans.append(ch)
            pch.add_channel(ch)
        req = echo_pb2.EchoRequest(message="x" * 16)
        py_seconds = max(1.0, seconds / 2)
        stop_at = time.monotonic() + py_seconds
        py_calls = 0
        py_failed = 0
        while time.monotonic() < stop_at:
            cntl = rpc.Controller()
            cntl.timeout_ms = 3000
            resp = echo_pb2.EchoResponse()
            pch.call_method("EchoService.Echo", cntl, req, resp)
            py_calls += 1
            if cntl.failed():
                py_failed += 1
        for ch in chans:
            ch.close()
        py_qps = py_calls / py_seconds
        out["fanout_py_qps"] = round(py_qps, 1)
        out["fanout_py_failed"] = py_failed
        if py_qps > 0 and out.get("fanout_qps", 0) > 0:
            out["fanout_native_vs_py_x"] = round(
                out["fanout_qps"] / py_qps, 2)
    finally:
        native.rpc_server_stop()
    return out


def conn_scale_bench(target_conns: int = 20000, client_procs: int = 4,
                     idle_s: float = 2.0) -> dict:
    """The connection-scale drill (ISSUE 14, ROADMAP item 5's last
    half): hold `target_conns` mostly-idle keep-alive tpu_std
    connections from client SUBPROCESSES against one in-process native
    server and measure what a connection COSTS from the nat_res
    accounting — bytes (accounted live delta / connection), fds, and
    idle wakeups/s — plus the accept-storm recovery time (spawn ->
    every connection accepted and answered) with a live RPC subset
    flooding throughout (zero failed calls is part of the contract:
    any failure, an unfinished storm, or a post-teardown leak in the
    transient subsystems reports conn_scale_conns 0 so the bench gate
    trips).

    The target is clamped to RLIMIT_NOFILE minus headroom (the server
    process holds one fd per connection); conn_scale_target records the
    CLAMPED target the drill actually ran (conn_scale_requested keeps
    the pre-clamp ask, so a fd-limited host is distinguishable from a
    failing drill). BRPC_TPU_CONN_SCALE overrides the target
    (0 disables the lane)."""
    import ctypes
    import os
    import resource
    import subprocess
    import sys
    import threading

    from brpc_tpu import native

    env_target = os.environ.get("BRPC_TPU_CONN_SCALE")
    if env_target is not None:
        try:
            target_conns = int(env_target)
        except ValueError:
            pass
        if target_conns <= 0:
            return {}
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    try:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
        soft = hard
    except (ValueError, OSError):
        pass
    conns = max(100, min(target_conns, soft - 1000))
    per_proc = max(1, conns // client_procs)
    conns = per_proc * client_procs

    lib = native.load()
    port = native.rpc_server_start(native_echo=True)
    out = {"conn_scale_target": conns,
           "conn_scale_requested": target_conns}
    procs = []
    live_stop = threading.Event()
    live_ok = [0]
    live_fail = [0]

    def _live_loop():
        # the live subset: continuous echo RPCs through the accept storm
        # and the idle window — the "zero failed RPCs on the live
        # subset" half of the acceptance contract
        h = lib.nat_channel_open(b"127.0.0.1", port, 0, 0, 0, 0)
        if not h:
            live_fail[0] += 1
            return
        resp = ctypes.c_char_p()
        rlen = ctypes.c_size_t(0)
        err = ctypes.c_char_p()
        while not live_stop.is_set():
            rc = lib.nat_channel_call(h, b"EchoService", b"Echo",
                                      b"live", 4, 3000,
                                      ctypes.byref(resp),
                                      ctypes.byref(rlen),
                                      ctypes.byref(err))
            if rc == 0 and rlen.value == 4:
                live_ok[0] += 1
            else:
                live_fail[0] += 1
            if resp:
                lib.nat_buf_free(resp)
                resp = ctypes.c_char_p()
            if err:
                lib.nat_buf_free(err)
                err = ctypes.c_char_p()
        lib.nat_channel_close(h)

    client_src = (
        "import socket, struct, sys, time\n"
        "port, n = int(sys.argv[1]), int(sys.argv[2])\n"
        "from brpc_tpu.rpc.proto import rpc_meta_pb2\n"
        "meta = rpc_meta_pb2.RpcMeta()\n"
        "meta.request.service_name = 'EchoService'\n"
        "meta.request.method_name = 'Echo'\n"
        "meta.correlation_id = 7\n"
        "mb = meta.SerializeToString()\n"
        "frame = (b'TRPC' + struct.pack('>II', len(mb) + 1, len(mb))\n"
        "         + mb + b'k')\n"
        "socks, failed = [], 0\n"
        "for i in range(n):\n"
        "    try:\n"
        "        s = socket.create_connection(('127.0.0.1', port),\n"
        "                                     timeout=20)\n"
        "        s.sendall(frame)\n"
        "        socks.append(s)\n"
        "    except OSError:\n"
        "        failed += 1\n"
        "# one echo answered per connection proves each was accepted\n"
        "# AND served through the storm (not just SYN-queued)\n"
        "answered = 0\n"
        "for s in socks:\n"
        "    try:\n"
        "        s.settimeout(30)\n"
        "        buf = b''\n"
        "        while len(buf) < 12:\n"
        "            got = s.recv(4096)\n"
        "            if not got:\n"
        "                raise OSError('eof')\n"
        "            buf += got\n"
        "        body, _ = struct.unpack('>II', buf[4:12])\n"
        "        while len(buf) < 12 + body:\n"
        "            got = s.recv(65536)\n"
        "            if not got:\n"
        "                raise OSError('eof')\n"
        "            buf += got\n"
        "        answered += 1\n"
        "    except OSError:\n"
        "        failed += 1\n"
        "print('READY %d %d' % (answered, failed), flush=True)\n"
        "sys.stdin.readline()  # parent closes stdin -> teardown\n"
        "for s in socks:\n"
        "    try:\n"
        "        s.close()\n"
        "    except OSError:\n"
        "        pass\n"
        "print('CLOSED', flush=True)\n")

    try:
        time.sleep(0.3)
        fd0 = len(os.listdir("/proc/self/fd"))
        res0 = {r["subsystem"]: r for r in native.res_stats()}
        live_thread = threading.Thread(target=_live_loop, daemon=True)
        live_thread.start()
        t_storm = time.perf_counter()
        for _ in range(client_procs):
            procs.append(subprocess.Popen(
                [sys.executable, "-c", client_src, str(port),
                 str(per_proc)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                env=dict(os.environ, JAX_PLATFORMS="cpu")))
        answered = failed = 0
        for p in procs:
            line = p.stdout.readline().decode().split()
            if len(line) == 3 and line[0] == "READY":
                answered += int(line[1])
                failed += int(line[2])
            else:
                failed += per_proc
        accept_storm_s = time.perf_counter() - t_storm
        # the live subset proved the storm; stop it BEFORE the idle
        # window so the wakeup figure measures what HOLDING the
        # connections costs, not the flood
        live_stop.set()
        live_thread.join(timeout=10)
        time.sleep(0.5)  # settle: in-flight drains, pools quiesce
        wake0 = native.stats_counters().get("nat_dispatcher_wakeups", 0)
        time.sleep(idle_s)
        wake1 = native.stats_counters().get("nat_dispatcher_wakeups", 0)
        fd1 = len(os.listdir("/proc/self/fd"))
        res1 = {r["subsystem"]: r for r in native.res_stats()}
        held = int(lib.nat_rpc_server_connections())
        out.update({
            "conn_scale_answered": answered,
            "conn_scale_failed": failed,
            "conn_held": held,
            "conn_accept_storm_s": round(accept_storm_s, 2),
            # positive subsystem deltas only: in a full bench run the
            # PRECEDING lanes' pools may still be draining through the
            # drill, and a negative total would poison the ceiling
            # band's baseline (the attribution dict below keeps the
            # signed per-subsystem truth)
            "conn_per_conn_bytes": round(
                sum(max(0, res1[s]["live_bytes"] - res0[s]["live_bytes"])
                    for s in res1) / max(1, answered), 1),
            "conn_per_conn_fds": round((fd1 - fd0) / max(1, answered), 3),
            "conn_idle_wakeups_per_s": round(
                max(0, wake1 - wake0) / idle_s, 1),
            "conn_live_ok": live_ok[0],
            "conn_live_failed": live_fail[0],
            # where the bytes sit: per-subsystem live deltas over the
            # drill (the accounting's attribution, not a guess)
            "conn_mem_by_subsystem": {
                sub: int(res1[sub]["live_bytes"]
                         - res0[sub]["live_bytes"])
                for sub in res1
                if res1[sub]["live_bytes"] != res0[sub]["live_bytes"]},
        })
        # teardown + churn balance: close every client and wait for the
        # transient subsystems to return (socket slots recycle to the
        # freelist but their slabs stay live BY DESIGN — ResourcePool)
        for p in procs:
            try:
                p.stdin.close()
            except OSError:
                pass
        for p in procs:
            p.wait(timeout=60)
        deadline = time.time() + 30
        while time.time() < deadline and \
                int(lib.nat_rpc_server_connections()) > 4:
            time.sleep(0.1)
        time.sleep(0.5)
        res2 = {r["subsystem"]: r for r in native.res_stats()}
        leaks = {}
        for sub in ("srv.pyreq", "dump.spill"):
            d = res2[sub]["live_objects"] - res0[sub]["live_objects"]
            if d > max(8, answered * 0.01):
                leaks[sub] = int(d)
        out["conn_balance_leaked"] = leaks
        ok = (failed == 0 and answered == conns and live_fail[0] == 0
              and live_ok[0] > 0 and not leaks)
        out["conn_scale_conns"] = answered if ok else 0
    except Exception as e:  # a wedged drill must not kill the artifact
        out["conn_scale_error"] = repr(e)
        out["conn_scale_conns"] = 0
        live_stop.set()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        native.rpc_server_stop()
    return out


def _spawn_swarm_server(base: int, count: int, repo_root: str, env: dict):
    """One swarm backend process: a native echo server listening on
    `count` consecutive ports from `base`. Returns the Popen (READY
    already seen) or None when a port in the range was taken.

    BRPC_TPU_CHURN_FAULT (the PR-8 chaos hook): when set, the SERVER
    process arms that NAT_FAULT spec at library load — the chaos lane's
    swarm round runs the whole drill with destructive seeds in the
    backends while the client side stays clean."""
    import os
    import subprocess
    import sys

    churn_spec = env.get("BRPC_TPU_CHURN_FAULT") or \
        os.environ.get("BRPC_TPU_CHURN_FAULT")
    if churn_spec:
        env = dict(env)
        env["NAT_FAULT"] = churn_spec
    # BRPC_TPU_SWARM_LIMITER (ISSUE 16 drill hook): arm the native
    # admission limiter in the SERVER process ("constant:1", "auto", ...)
    # so a fleet drill can inject real ELIMIT overload on a member —
    # py-lane floods past the limit shed with 2004 on the wire while the
    # native echo path keeps serving
    limiter_spec = env.get("BRPC_TPU_SWARM_LIMITER") or \
        os.environ.get("BRPC_TPU_SWARM_LIMITER") or ""

    script = (
        "import os, signal, sys\n"
        "sys.path.insert(0, '.')\n"
        "from brpc_tpu import native\n"
        f"base, count = {base}, {count}\n"
        "try:\n"
        "    native.rpc_server_start('127.0.0.1', base, 2, True)\n"
        "    for p in range(base + 1, base + count):\n"
        "        native.rpc_server_add_port('127.0.0.1', p)\n"
        f"    if {limiter_spec!r}:\n"
        f"        native.rpc_server_limiter({limiter_spec!r})\n"
        "except Exception:\n"
        "    print('BINDFAIL', flush=True)\n"
        "    sys.exit(17)\n"
        "print('READY', flush=True)\n"
        "def _term(sig, frm):\n"
        "    native.server_quiesce(3000)\n"  # graceful: lame-duck + drain
        "    native.rpc_server_stop()\n"
        "    os._exit(0)\n"
        "signal.signal(signal.SIGTERM, _term)\n"
        "while True:\n"
        "    signal.pause()\n")
    proc = subprocess.Popen([sys.executable, "-c", script],
                            stdout=subprocess.PIPE, text=True,
                            cwd=repo_root, env=dict(env, JAX_PLATFORMS="cpu"))
    line = proc.stdout.readline().strip()
    if line != "READY":
        proc.kill()
        proc.wait(timeout=10)
        return None
    return proc


def fanout_swarm_bench(backends: int = 1000, servers: int = 3,
                       bench_seconds: float = 12.0,
                       concurrency: int = 4) -> dict:
    """The ROADMAP acceptance drill: a `backends`-port in-process swarm
    (`servers` subprocesses, each hosting backends/servers native echo
    ports) behind one native cluster, churned by ROLLING SIGTERM
    restarts (graceful quiesce + lame-duck, PR 8) and LIVE naming
    add/remove (the file naming service rewritten mid-run) while the
    selective-with-retry verb floods it from C threads. The contract is
    ZERO failed RPCs once failover/retry settles — a run with failures
    reports swarm_qps 0 so the bench gate trips — with the per-backend
    qps distribution recorded in the artifact.

    Also records fanout1000_qps: the parallel verb fanning one call to
    all `backends` backends (measured before the churn starts)."""
    import json as _json
    import os
    import signal as _signal
    import threading as _threading

    from brpc_tpu import native
    from brpc_tpu.rpc.native_cluster import NativeCluster

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    per = backends // servers
    out: dict = {}
    procs = []
    bases = []
    nf_path = None
    cluster = None
    try:
        base_candidates = [21000, 23000, 25000, 27000, 29000, 19000]
        ci = 0
        for _ in range(servers):
            proc = None
            while proc is None and ci < len(base_candidates):
                base = base_candidates[ci]
                ci += 1
                proc = _spawn_swarm_server(base, per, repo_root, env)
                if proc is not None:
                    procs.append(proc)
                    bases.append(base)
        if len(procs) < servers:
            raise RuntimeError("swarm port ranges unavailable")
        all_ports = [b + i for b in bases for i in range(per)]
        import tempfile

        nf = tempfile.NamedTemporaryFile("w", suffix=".swarm.ns",
                                         delete=False)
        nf_path = nf.name

        def write_naming(ports):
            with open(nf_path, "w") as f:
                for p in ports:
                    f.write(f"127.0.0.1:{p}\n")

        write_naming(all_ports)
        nf.close()
        cluster = NativeCluster(lb="rr", connect_timeout_ms=1000,
                                health_check_ms=200, breaker=True,
                                name="swarm")
        cluster.watch(f"file://{nf_path}")
        n = cluster.backend_count()
        out["swarm_backends"] = n

        # parallel fan-out to the WHOLE swarm (pre-churn): one verb =
        # `backends` concurrent sub-calls + native merge
        r1000 = cluster.bench(mode=1, param=0, seconds=1.0,
                              concurrency=2, timeout_ms=8000)
        out["fanout1000_qps"] = (0.0 if r1000["failed"]
                                 else round(r1000["qps"], 1))

        # churn window: selective flood from C threads while this thread
        # SIGTERMs each server in turn and flaps the naming file
        result: dict = {}

        def flood():
            result.update(cluster.bench(mode=0, param=12,
                                        seconds=bench_seconds,
                                        concurrency=concurrency,
                                        timeout_ms=5000))

        flood_t = _threading.Thread(target=flood)
        flood_t.start()
        time.sleep(0.5)
        # live naming remove (the tail 5% of backends)...
        drop = max(1, n // 20)
        write_naming(all_ports[:-drop])
        restarts = 0
        for i in range(len(procs)):
            procs[i].send_signal(_signal.SIGTERM)
            try:
                procs[i].wait(timeout=20)
            except Exception:
                procs[i].kill()
                procs[i].wait(timeout=10)
            fresh = _spawn_swarm_server(bases[i], per, repo_root, env)
            if fresh is None:
                break
            procs[i] = fresh
            restarts += 1
        # ...and live naming re-add
        write_naming(all_ports)
        flood_t.join(timeout=bench_seconds + 60)
        out["swarm_restarts"] = restarts
        out["swarm_calls"] = result.get("calls", 0)
        out["swarm_p99_us"] = round(result.get("p99_us", 0.0), 1)
        failed = result.get("failed", -1)
        out["swarm_failed"] = failed
        # the zero-failed contract IS the lane value
        out["swarm_qps"] = (round(result.get("qps", 0.0), 1)
                            if failed == 0 and restarts == len(procs)
                            else 0.0)
        # per-backend qps distribution (the artifact's evidence that the
        # LB spread the flood): selects quantiles across live backends
        selects = sorted(row["selects"] for row in cluster.stats())
        if selects:
            out["swarm_selects_per_backend"] = {
                "min": selects[0],
                "p50": selects[len(selects) // 2],
                "max": selects[-1],
            }
        out["swarm_stats_note"] = _json.dumps(
            {"servers": len(procs), "ports_per_server": per})
    finally:
        if cluster is not None:
            cluster.close()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
            try:
                proc.wait(timeout=10)
            except Exception:
                pass
        if nf_path is not None:
            try:
                os.unlink(nf_path)
            except OSError:
                pass
    return out


def autoscale_drill_bench(ramp_times: int = 4,
                          qps_from: float = 150.0,
                          qps_to: float = 1200.0,
                          settle_s: float = 5.0,
                          p99_ceiling_ms: float = 250.0,
                          tracking_floor: float = 0.4) -> dict:
    """The ISSUE-20 elastic-capacity drill: a dynpart cluster over a
    live subprocess swarm, resized by the fleet autoscaler while the
    committed golden capture replays through the native replay client
    in RAMP mode (the offered-load curve) and a paced dynpart probe
    exercises the resize path end to end. One member is SIGKILLed
    mid-resize (never announced — the controller must notice the corpse
    in the rollup and replace it; the dynpart capacity rule routes
    around its half-dead scheme meanwhile).

    The SLO contract IS the lane value: autoscale_qps reports the
    replay's achieved qps only when the probe saw ZERO failed RPCs
    across every grow/shrink/crash, probe p99 stayed under the ceiling,
    the controller actually scaled both ways (>= 1 grow AND >= 1
    shrink), and capacity tracked the offered load (pool size within
    one member of the controller's desired size on >= tracking_floor of
    post-warmup decisions). Any breach reports 0 qps so the bench gate
    trips."""
    import os
    import tempfile
    import threading as _threading

    from brpc_tpu import native
    from brpc_tpu.fleet.autoscaler import (Autoscaler, AutoscalerConfig,
                                           SwarmPool)
    from brpc_tpu.fleet.observatory import FleetObservatory
    from brpc_tpu.fleet.slo import SloObjective
    from brpc_tpu.rpc.native_cluster import NativeCluster

    golden = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests", "data", "golden_capture_1k.rio")
    if not os.path.exists(golden):
        return {}

    out: dict = {}
    nf = tempfile.NamedTemporaryFile("w", suffix=".autoscale.ns",
                                     delete=False)
    nf_path = nf.name
    nf.close()
    cluster = None
    obs = None
    pool = None
    stop = _threading.Event()
    try:
        cluster = NativeCluster(lb="_dynpart", connect_timeout_ms=1000,
                                health_check_ms=200, breaker=True,
                                name="autoscale")
        obs = FleetObservatory(
            naming_url=f"file://{nf_path}", interval_s=0.4,
            objectives=[SloObjective(name="autoscale-p99",
                                     kind="latency", lane="echo",
                                     ceiling_ms=p99_ceiling_ms,
                                     budget=0.05)],
            name="autoscale", register_bvars=False)

        def publish_cb():
            # push the fresh list NOW (the file watchers' 2s poll is an
            # eternity against a 0.5s control loop)
            for w in (cluster._watcher, obs._cluster._watcher):
                if w is not None:
                    try:
                        w.refresh()
                    except Exception:
                        pass

        pool = SwarmPool(nf_path, base_port=26100, publish_cb=publish_cb)
        if pool.grow(2) < 2:
            raise RuntimeError("autoscale swarm port range unavailable")
        cluster.watch(f"file://{nf_path}")
        publish_cb()
        obs.start()
        anchor_port = pool.ports()[0]  # never retired above min=2

        cfg = AutoscalerConfig(min_backends=2, max_backends=6,
                               target_qps_per_backend=400.0,
                               p99_ceiling_ms=p99_ceiling_ms,
                               grow_step=2, shrink_step=2,
                               cooldown_s=0.6)
        scaler = Autoscaler(cfg, pool, obs)
        ctrl = _threading.Thread(target=scaler.run, args=(0.5, stop),
                                 daemon=True)
        ctrl.start()

        # the zero-failed probe: paced dynpart verbs through every
        # resize, with the same bounded client retry the swarm churn
        # lane rides (its selective verb retries in-verb; the fan verbs
        # have no failover, so an unannounced corpse can be the SOLE
        # seat of a one-group scheme for the 2-3 calls its transport
        # cool-down takes — the retry re-picks, the rr cursor moves to
        # a live member). fail_limit=0 = the verb fails only when EVERY
        # seated sub fails; a call that exhausts its retries is a
        # failed RPC and zeroes the lane.
        probe_lat_us: list = []
        probe_failed = [0]
        probe_retries = [0]
        probe_schemes: dict = {}

        def probe():
            while not stop.is_set():
                t0 = time.monotonic()
                rc = -1
                for attempt in range(3):
                    rc, _body, _err, _nfail, scheme = \
                        cluster.dynpart_call(
                            "EchoService.Echo", b"autoscale-probe",
                            timeout_ms=4000, fail_limit=0)
                    if rc == 0:
                        break
                    probe_retries[0] += 1
                if rc != 0:
                    probe_failed[0] += 1
                else:
                    probe_lat_us.append(
                        (time.monotonic() - t0) * 1e6)
                    probe_schemes[scheme] = \
                        probe_schemes.get(scheme, 0) + 1
                time.sleep(0.02)

        probe_t = _threading.Thread(target=probe, daemon=True)
        probe_t.start()

        # chaos arm: SIGKILL the newest member the moment the first
        # grow lands (mid-resize by construction)
        killed = [0]

        def assassin():
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not stop.is_set():
                if scaler.grows >= 1 and pool.size() >= 3:
                    if pool.kill_one() is not None:
                        killed[0] += 1
                    return
                time.sleep(0.1)

        kill_t = _threading.Thread(target=assassin, daemon=True)
        kill_t.start()

        # offered load: the golden capture ramped qps_from -> qps_to
        # against the anchor member (PR-11 replay, ramp mode)
        replay = native.replay_run("127.0.0.1", anchor_port, golden,
                                   times=ramp_times, qps=qps_from,
                                   qps_to=qps_to, concurrency=4,
                                   timeout_ms=5000)
        # load gone: the settle window is where the shrinks happen
        deadline = time.monotonic() + settle_s
        while time.monotonic() < deadline and \
                not (scaler.shrinks >= 1 and pool.size() <= 3):
            time.sleep(0.25)
        kill_t.join(timeout=5)
        stop.set()
        ctrl.join(timeout=10)
        probe_t.join(timeout=10)

        # capacity-tracking score: post-warmup decisions where the pool
        # sat within one member of the controller's own desired size
        recs = [r for r in scaler.decisions if r["qps"] > 0]
        tracked = sum(1 for r in recs
                      if abs(r["size"] - r["desired"]) <= 1)
        tracking = (tracked / len(recs)) if recs else 0.0

        probe_lat_us.sort()
        p99_us = (probe_lat_us[int(len(probe_lat_us) * 0.99)]
                  if probe_lat_us else 0.0)
        counters = native.stats_counters()
        out.update({
            "autoscale_replay_qps": round(replay["qps"], 1),
            "autoscale_probe_calls": len(probe_lat_us),
            "autoscale_probe_retries": probe_retries[0],
            "autoscale_failed": probe_failed[0] + replay["failed"],
            "autoscale_grows": scaler.grows,
            "autoscale_shrinks": scaler.shrinks,
            "autoscale_blocked": scaler.blocked,
            "autoscale_kills": killed[0],
            "autoscale_peak_size": max(r["size"] for r in
                                       scaler.decisions),
            "autoscale_tracking": round(tracking, 3),
            "autoscale_schemes": {str(k): v for k, v
                                  in sorted(probe_schemes.items())},
            "autoscale_resizes": counters.get("nat_dynpart_resizes", 0),
            "autoscale_p99_us": round(p99_us, 1),
        })
        contract_ok = (probe_failed[0] == 0 and replay["failed"] == 0
                       and scaler.grows >= 1 and scaler.shrinks >= 1
                       and killed[0] == 1
                       and p99_us <= p99_ceiling_ms * 1000
                       and tracking >= tracking_floor
                       and len(probe_lat_us) > 50)
        out["autoscale_qps"] = (round(replay["qps"], 1)
                                if contract_ok else 0.0)
    except Exception as e:  # a wedged drill must not kill the artifact
        out["autoscale_error"] = repr(e)
        out["autoscale_qps"] = 0.0
    finally:
        stop.set()
        if obs is not None:
            obs.close()
        if cluster is not None:
            cluster.close()
        if pool is not None:
            pool.close()
        try:
            os.unlink(nf_path)
        except OSError:
            pass
    return out


def _host_parallel_probe(seconds: float = 1.5) -> float:
    """Effective parallel CPU capacity of this host: total pure-CPU work
    of one pinned burner process per cpu, over one burner alone. ~N on a
    dedicated N-core host; shared/overcommitted containers measure well
    below N (this 2-vCPU dev container: 1.3-2.2x run over run) — the
    denominator that says whether a flat scaling curve is the runtime's
    fault or the host's."""
    import multiprocessing as mp
    import os
    import time as _t

    def burn(cpu, q):
        try:
            os.sched_setaffinity(0, {cpu})
        except OSError:
            pass
        t0 = _t.perf_counter()
        n = 0
        x = 1.0
        while _t.perf_counter() - t0 < seconds:
            for _ in range(10000):
                x = x * 1.0000001
            n += 10000
        q.put(n)

    cpus = sorted(os.sched_getaffinity(0))
    q = mp.Queue()
    p = mp.Process(target=burn, args=(cpus[0], q))
    p.start()
    p.join()
    single = q.get()
    procs = [mp.Process(target=burn, args=(c, q)) for c in cpus]
    for p in procs:
        p.start()
    for p in procs:
        p.join()
    total = sum(q.get() for _ in procs)
    return round(total / max(1, single), 2)


def scaling_bench(max_cpus: int, seconds: float = 2.0,
                  payload: int = 16) -> dict:
    """Multicore scaling lane (``bench.py --cpus N``, ROADMAP item 1):
    native framework echo qps measured at {1, 2, ..., N} CPUs. At each
    point the SERVER process is pinned (sched_setaffinity) to the first
    n host cpus and runs n dispatcher loops (NAT_DISPATCHERS=n, no
    dispatcher split — a dedicated server shards over its whole pool),
    and n CLIENT processes are pinned one per cpu driving async-windowed
    load. Separate processes mean the single-core point is the honest
    everything-on-one-core number and the curve measures the server
    runtime's own scale-out, not in-process cross-runtime interference.

    Artifact schema notes (ride as ``extra.scaling``):
      "1".."N"          qps at that cpu count
      cpu_sets          the exact server/client pin sets per point
      disp_stats        per-point per-dispatcher rows from the SERVER
                        process after the load ({sockets, wakeups,
                        sqpoll} per loop via nat_disp_stat) — a
                        sublinear-scaling finding arrives with the
                        dispatcher-balance evidence attached
      host_parallel_x   pure-CPU capacity control: one pinned burner per
                        cpu vs one alone — the ceiling ANY workload can
                        scale to on this host (overcommitted containers
                        sit far below the cpu count)
    The bench gate derives ``cpus2_scaling_x`` = qps(2)/qps(1) and holds
    a scaling-efficiency band against the committed baseline: sublinear
    scaling beyond tolerance fails the gate like any regression.
    """
    import json as _json
    import os
    import subprocess
    import sys

    host_cpus = sorted(os.sched_getaffinity(0))
    n_avail = len(host_cpus)
    out: dict = {"cpu_sets": {}}
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    server_script = (
        "import json, os, sys\n"
        "os.sched_setaffinity(0, {server_cpus})\n"
        "sys.path.insert(0, '.')\n"
        "from brpc_tpu import native\n"
        "port = native.rpc_server_start(nworkers={server_n},"
        " native_echo=True)\n"
        "print(port, flush=True)\n"
        "sys.stdin.readline()\n"
        # per-dispatcher evidence for the scaling artifact: wakeup/
        # SQPOLL/socket counts per loop AFTER the load, so a sublinear
        # finding shows whether the loops were actually balanced
        "print('DISP ' + json.dumps(native.dispatcher_stats()),"
        " flush=True)\n"
        "native.rpc_server_stop()\n")
    client_script = (
        "import os, sys, ctypes\n"
        "os.sched_setaffinity(0, {client_cpus})\n"
        "sys.path.insert(0, '.')\n"
        "from brpc_tpu import native\n"
        "lib = native.load()\n"
        "got = ctypes.c_uint64(0)\n"
        "q = lib.nat_rpc_client_bench_async(b'127.0.0.1', {port},"
        " {conns}, 256, {seconds}, {payload}, ctypes.byref(got))\n"
        "print('QPS', q, flush=True)\n")

    try:
        out["host_parallel_x"] = _host_parallel_probe()
    except Exception:
        pass

    # clamp to the cpus that actually exist: points beyond n_avail would
    # silently re-measure the full-host configuration and read as a
    # flat curve (the cpu_sets field records the real pin sets)
    for n in range(1, min(max(1, max_cpus), n_avail) + 1):
        cpus = host_cpus[:n]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["NAT_DISPATCHERS"] = str(len(cpus))
        env.pop("NAT_DISP_SPLIT", None)  # dedicated processes: no split
        srv = subprocess.Popen(
            [sys.executable, "-c", server_script.format(
                server_cpus=set(cpus), server_n=len(cpus))],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=repo_root, env=env)
        try:
            port = int(srv.stdout.readline())
            cenv = dict(env)
            cenv["NAT_DISPATCHERS"] = "1"
            clients = []
            try:
                for cpu in cpus:
                    clients.append(subprocess.Popen(
                        [sys.executable, "-c", client_script.format(
                            client_cpus={cpu}, port=port, conns=2,
                            seconds=seconds, payload=payload)],
                        stdout=subprocess.PIPE, text=True, cwd=repo_root,
                        env=cenv))
                qps = 0.0
                for cli in clients:
                    cout, _ = cli.communicate(timeout=120 + seconds)
                    for line in cout.splitlines():
                        if line.startswith("QPS "):
                            qps += float(line.split()[1])
                out[str(n)] = round(qps, 1)
                out["cpu_sets"][str(n)] = {
                    "server": sorted(cpus),
                    "clients": [[c] for c in cpus]}
            finally:
                # a wedged client must not outlive its point: a stray
                # PINNED load generator would contaminate every later
                # bench lane in this process
                for cli in clients:
                    if cli.poll() is None:
                        cli.kill()
                    try:
                        cli.wait(timeout=10)
                    except Exception:
                        pass
        finally:
            try:
                srv.stdin.close()
                # the server answers the stdin EOF with one
                # "DISP [...]" line of per-dispatcher counters, the
                # balance evidence for this point; read it on a helper
                # thread so a wedged server cannot hang the gate past
                # the 15s bound below
                def _read_disp(stream=srv.stdout, point=str(n)):
                    for line in stream:
                        if line.startswith("DISP "):
                            out.setdefault("disp_stats", {})[point] = \
                                _json.loads(line[5:])
                            break

                reader = threading.Thread(target=_read_disp, daemon=True)
                reader.start()
                reader.join(timeout=15)
                srv.wait(timeout=15)
            except Exception:
                srv.kill()
    return out


def device_lane_bench() -> dict:
    """Device-transport bandwidth numbers — the rdma_performance analog
    (example/rdma_performance/client.cpp:50-52,136-183 measures verbs
    GB/s; here each lane of the device transport is measured on the real
    chip): host<->device DMA, the in-process zero-copy lane, shm-arena
    staging, a two-process shm push, and the native bulk data path."""
    import time

    import numpy as np

    out = {}

    # two-process shm push: full RPC + descriptor-ring fabric path
    # (ISSUE 15: payload written ONCE into the server's blob arena as
    # kind-8 records, consumed in place as zero-copy lease-backed
    # arrays — no payload bytes on the wire, no staging copy on either
    # side). Runs FIRST: the client must not own a fabric segment of its
    # own (the shm_desc lane below creates one in this process).
    # The receiving server is a child of this JAX process, so it is held
    # to the CPU backend (one process per chip): these rows are a host
    # rehearsal of the lane, not a device figure.
    try:
        import os
        import subprocess
        import sys

        from brpc_tpu.rpc import device_transport as dt
        from brpc_tpu.rpc.tensor_service import (TensorClient,
                                                 make_device_channel)

        # the receiving server rides the NATIVE runtime: descriptor RPCs
        # parse in the C++ loop, usercode (lease consume) on the py lane
        script = (
            "import os, sys; sys.path.insert(0, '.')\n"
            "os.environ.setdefault('BRPC_TPU_FABRIC_ARENA',"
            " str(128 << 20))\n"
            "from brpc_tpu import rpc\n"
            "from brpc_tpu.rpc.tensor_service import TensorStoreService\n"
            "srv = rpc.Server(rpc.ServerOptions(num_threads=2,\n"
            "                 use_native_runtime=True))\n"
            "srv.add_service(TensorStoreService())\n"
            "assert srv.start('127.0.0.1:0') == 0\n"
            "print(srv.listen_endpoint.port, flush=True)\n"
            "sys.stdin.readline()\n")
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        proc = subprocess.Popen([sys.executable, "-c", script],
                                stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE, text=True,
                                cwd=repo_root,
                                env=dict(os.environ, JAX_PLATFORMS="cpu"))
        try:
            port = int(proc.stdout.readline())
            ch = make_device_channel(f"127.0.0.1:{port}")
            client = TensorClient(ch)
            arr = np.random.randint(0, 255, 8 << 20,
                                    dtype=np.uint8)
            # ONE name throughout: the fabric's blob arena is a RING, so
            # the store must keep replacing (= releasing) its zero-copy
            # lease-backed entries — a store retaining every name would
            # head-block arena reclaim (leases release out of order, but
            # the head only advances past released spans)
            client.push("serial", [arr])  # handshake + warm
            rounds = 8
            t0 = time.perf_counter()
            for i in range(rounds):
                cntl, resp = client.push("serial", [arr])
                assert not cntl.failed(), cntl.error_text
            dt_s = time.perf_counter() - t0
            out["shm_push_serial_GBps"] = round(
                arr.nbytes * rounds / dt_s / 1e9, 3)
            # concurrent pushes — the rdma_performance measurement shape
            # (client.cpp:136-183 runs many streams at once): arena
            # write, descriptor RPC and lease consume of different
            # pushes overlap, which is what the send window exists for
            import threading as _threading

            K, per = 3, 6
            errs = []

            def _pusher(tid):
                for i in range(per):
                    c, _ = client.push("serial", [arr])
                    if c.failed():
                        errs.append(c.error_text)

            t0 = time.perf_counter()
            ts = [_threading.Thread(target=_pusher, args=(t,))
                  for t in range(K)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt_s = time.perf_counter() - t0
            assert not errs, errs
            out["shm_push_GBps"] = round(
                arr.nbytes * per * K / dt_s / 1e9, 3)
            out["shm_push_lane"] = (
                "ring" if dt.lane_counters()["ring"] > 0 else "shm")
            out["shm_push_receiver"] = "host rehearsal (cpu backend)"
            ch.close()
        finally:
            proc.stdin.close()
            proc.wait(timeout=10)
    except Exception:
        pass

    # zero-copy descriptor-ring lane (nat_shm_lane.cpp): two-process push
    # through the lock-free descriptor rings + blob arena — the native
    # transport the shm usercode lane and bulk-tensor staging ride
    # (nat_shm_push_tensor). The small/large record split separates
    # per-record overhead from raw staging bandwidth: the round-4 byte
    # rings paid a robust-mutex lock + double memcpy + futex wake per
    # record, which is exactly what the small-record number would expose.
    try:
        import subprocess
        import sys

        from brpc_tpu import native

        lib = native.load()
        lib.nat_shm_lane_enable(0)  # retire any earlier lane/segment
        if lib.nat_shm_lane_create(16 << 20) == 0:
            name = lib.nat_shm_lane_name().decode()
            import os as _os

            repo_root = _os.path.dirname(_os.path.dirname(
                _os.path.abspath(__file__)))
            child = subprocess.Popen(
                [sys.executable, "-c", (
                    "import sys; sys.path.insert(0, '.')\n"
                    "from brpc_tpu import native\n"
                    "lib = native.load()\n"
                    f"assert lib.nat_shm_worker_attach("
                    f"{name!r}.encode()) == 0\n"
                    "lib.nat_shm_worker_drain_bench(8000)\n")],
                cwd=repo_root,
                env=dict(_os.environ, JAX_PLATFORMS="cpu"))
            deadline = time.time() + 30
            while (lib.nat_shm_lane_workers() < 1
                   and time.time() < deadline):
                time.sleep(0.05)
            if lib.nat_shm_lane_workers() >= 1:
                small = native.shm_push_bench(16 << 10, 1.0)
                large = native.shm_push_bench(1 << 20, 1.5)
                out["shm_desc_small_GBps"] = round(small["GBps"], 3)
                out["shm_desc_GBps"] = round(large["GBps"], 3)
            lib.nat_shm_lane_enable(0)  # shutdown: child drain exits
            child.wait(timeout=20)
    except Exception:
        pass

    # per-hop breakdown of the fabric path (ISSUE 15 satellite): where a
    # regression in the zero-copy pipeline lives — arena write (the ONE
    # producer memcpy), ring latency (push -> take), consume (zero-copy
    # lease -> np view), device_put (put_via_pool from the arena view).
    # In-process: the hops are the same code the two-process lanes run.
    try:
        from brpc_tpu import native

        lib = native.load()
        lib.nat_shm_lane_enable(0)
        if lib.nat_shm_lane_create(32 << 20) == 0 and \
                lib.nat_shm_producer_attach(
                    lib.nat_shm_lane_name()) >= 0:
            src = np.random.randint(0, 255, 1 << 20, dtype=np.uint8)
            hops = {"arena_write_us": [], "ring_us": [],
                    "consume_us": [], "device_put_us": []}
            from brpc_tpu.rpc.device_transport import \
                default_block_pool

            pool = default_block_pool()
            for i in range(20):
                t0 = time.perf_counter()
                rc = native.fabric_push(src, i)
                t1 = time.perf_counter()
                if rc != 0:
                    continue
                lease = native.fabric_take(2000)
                t2 = time.perf_counter()
                if lease is None:
                    continue
                view = np.frombuffer(lease.view(), dtype=np.uint8)
                t3 = time.perf_counter()
                arr = pool.put_via_pool(view, np.uint8,
                                        (view.size,))
                t4 = time.perf_counter()
                del arr
                lease.release()
                hops["arena_write_us"].append((t1 - t0) * 1e6)
                hops["ring_us"].append((t2 - t1) * 1e6)
                hops["consume_us"].append((t3 - t2) * 1e6)
                hops["device_put_us"].append((t4 - t3) * 1e6)
            if hops["arena_write_us"]:
                import statistics

                out["hops"] = {
                    k: round(statistics.median(v), 1)
                    for k, v in hops.items() if v}
        lib.nat_shm_lane_enable(0)
    except Exception:
        pass

    # read-arena grow prefault (drive-by satellite): the growable
    # read-side allocator seam (install_read_arena) must not
    # reintroduce the first-touch fault cliff on grow (the r05
    # 0.085->1.0 GB/s class) — a GROWN arena's first block writes must
    # run within a small factor of warm writes (every arena prefaults
    # at creation). Contract: a cliff reports 0 so the gate trips.
    try:
        from brpc_tpu.rpc import device_transport as dt

        chain = dt.ReadArenaChain(size=4 << 20, capacity=1 << 20)
        try:
            pinned = []  # hold the blocks: a dropped block's finalizer
            while True:  # would free its span and un-exhaust the arena
                b = chain.arenas[0].make_block(1 << 20)
                if b is None:
                    break
                pinned.append(b)
            grows0 = chain.grows
            blk = chain.alloc_block()  # forces a prefaulted grow
            assert blk is not None and chain.grows == grows0 + 1
            src = np.random.randint(0, 255, 1 << 20, dtype=np.uint8)

            def _write_bw(rounds):
                t0 = time.perf_counter()
                for _ in range(rounds):
                    np.frombuffer(blk.data, dtype=np.uint8)[:] = src
                return (1 << 20) * rounds / (
                    time.perf_counter() - t0) / 1e9

            first = _write_bw(1)   # includes any residual fault cost
            warm = _write_bw(8)
            gbps = round(first, 3)
            if first < warm / 6:   # the r05 cliff was ~12x
                gbps = 0.0
            out["read_arena_grow_GBps"] = gbps
            out["read_arena_warm_GBps"] = round(warm, 3)
        finally:
            chain.close()
    except Exception:
        pass

    # host <-> device DMA (the raw registered-memory bandwidth analog)
    try:
        import jax

        nbytes = 64 << 20
        host = np.random.randint(0, 255, nbytes, dtype=np.uint8)
        dev = jax.device_put(host)
        dev.block_until_ready()  # warm
        iters = 3
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.device_put(host).block_until_ready()
        out["h2d_GBps"] = round(nbytes * iters / (time.perf_counter() - t0)
                                / 1e9, 3)
        t0 = time.perf_counter()
        for _ in range(iters):
            np.asarray(dev)
        out["d2h_GBps"] = round(
            nbytes * iters / (time.perf_counter() - t0) / 1e9, 3)
    except Exception:
        pass

    # in-process zero-copy lane: ticket round trips carrying a real array
    try:
        import jax

        from brpc_tpu.rpc import device_transport as dt

        arr = jax.device_put(np.zeros(16 << 20, dtype=np.uint8))
        arr.block_until_ready()
        rounds = 200
        t0 = time.perf_counter()
        for _ in range(rounds):
            ticket = dt.inproc_publish([arr])
            got = dt.inproc_claim(ticket)
        dt_s = time.perf_counter() - t0
        assert got is not None
        out["inproc_GBps"] = round(int(arr.nbytes) * rounds / dt_s / 1e9, 3)
    except Exception:
        pass

    # shm-arena staging: device bytes -> pinned shared memory -> back
    # (the sender/receiver halves of the same-host lane, one process)
    try:
        from brpc_tpu.rpc import device_transport as dt

        arena = dt.HostArena(size=96 << 20)
        try:
            n = 32 << 20
            src = np.random.randint(0, 255, n, dtype=np.uint8)
            off = arena.alloc(n)
            rounds = 5
            t0 = time.perf_counter()
            for _ in range(rounds):
                dst = np.frombuffer(arena.shm.buf, dtype=np.uint8,
                                    count=n, offset=off)
                dst[:] = src
                back = np.frombuffer(arena.shm.buf, dtype=np.uint8,
                                     count=n, offset=off).copy()
            dt_s = time.perf_counter() - t0
            assert back[-1] == src[-1]
            # two copies per round; report one-direction bandwidth
            out["shm_stage_GBps"] = round(2 * n * rounds / dt_s / 1e9, 3)
        finally:
            arena.close()
    except Exception:
        pass

    # native bulk data path: 1MB attachments echoed through the full
    # native stack (socket write queue -> dispatcher -> native handler)
    try:
        import ctypes

        from brpc_tpu import native

        lib = native.load()
        lib.nat_rpc_client_bench_bulk.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_double,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.nat_rpc_client_bench_bulk.restype = ctypes.c_double
        port = native.rpc_server_start(native_echo=True)
        try:
            got = ctypes.c_uint64(0)
            gbps = lib.nat_rpc_client_bench_bulk(
                b"127.0.0.1", port, 1 << 20, 1.5, ctypes.byref(got))
            out["native_bulk_GBps"] = round(gbps, 3)
        finally:
            native.rpc_server_stop()
    except Exception:
        pass

    return out


def model_collective_bench() -> dict:
    """Round-over-round model + collective rows (VERDICT r3 #6): the
    single-chip flagship train-step rate on the real device, and the
    8-virtual-device CPU-mesh collective bandwidth — the measurable proxy
    for BASELINE.md's ParallelChannel-allreduce north star (harness shape:
    example/rdma_performance/client.cpp:136-183, timed loop over a fixed
    transfer size).

    Returns {model_step_per_s, model_tokens_per_s, collective_GBps,
    a2a_GBps}."""
    import os
    import subprocess
    import sys

    import jax
    import jax.numpy as jnp

    from brpc_tpu.tensor import ModelConfig, init_params, make_spmd_train_step
    from brpc_tpu.tensor.config import (FLAGSHIP, FLAGSHIP_BATCH, MeshSpec,
                                        flagship_flops_per_step)

    out = {}
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a step rate or MFU from the CPU backend is not a device figure
        out["model_rows"] = f"not measured: no TPU ({dev.device_kind})"
    else:
        peak = PEAK_BF16_FLOPS[dev.device_kind]

        def timed_steps(cfg, B, T, iters):
            """Steady-state step rate. Host-initialized params and the
            step's device outputs can differ in layout, so the SECOND
            call may compile a second executable: warm up twice,
            feeding the returned params back."""
            params = init_params(cfg, jax.random.PRNGKey(0))
            mesh, step = make_spmd_train_step(cfg, MeshSpec())
            key = jax.random.PRNGKey(1)
            tokens = jax.random.randint(key, (B, T), 0, cfg.vocab,
                                        dtype=jnp.int32)
            labels = jnp.roll(tokens, -1, axis=1)
            p = params
            for _ in range(2):
                loss, p = step(p, tokens, labels)
                jax.block_until_ready((loss, p))
            t0 = time.perf_counter()
            for _ in range(iters):
                loss, p = step(p, tokens, labels)
            jax.block_until_ready((loss, p))
            return iters / (time.perf_counter() - t0)

        # continuity row: the round-3/4 toy config
        toy = ModelConfig(vocab=256, d_model=128, n_heads=4, d_head=32,
                          d_ff=256, n_layers=2, n_experts=2)
        sps = timed_steps(toy, B=4, T=256, iters=30)
        out["model_step_per_s"] = round(sps, 2)
        out["model_tokens_per_s"] = round(4 * 256 * sps, 1)

        # MFU row: the flagship at its bench width, big enough to be
        # compute-dominated on one chip
        big = FLAGSHIP
        B, T = FLAGSHIP_BATCH
        sps_big = timed_steps(big, B, T, iters=10)
        flops_step = flagship_flops_per_step(big, B, T)
        out["model_big_config"] = (
            f"d{big.d_model}xL{big.n_layers} moe{big.n_experts} "
            f"cf{big.expert_capacity_factor} B{B}xT{T} {big.dtype}")
        out["model_big_step_per_s"] = round(sps_big, 2)
        out["model_big_tokens_per_s"] = round(B * T * sps_big, 1)
        out["model_flops_per_step"] = flops_step
        out["mfu"] = round(flops_step * sps_big / peak, 4)
        out["mfu_peak_tflops"] = peak / 1e12
        out["mfu_device_kind"] = dev.device_kind
    try:
        # collectives need >1 device: virtual 8-device CPU mesh in a
        # subprocess (the dryrun_multichip environment)
        script = (
            "import sys; sys.path.insert(0, '.')\n"
            "from brpc_tpu import parallel\n"
            "mesh = parallel.make_mesh({'x': 8})\n"
            "s = parallel.ici_bandwidth_probe(mesh, 'x',\n"
            "                                 nbytes=1 << 24, iters=5)\n"
            "import json; print(json.dumps(s), flush=True)\n")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=8")
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        res = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=300,
                             cwd=repo_root, env=env)
        if res.returncode == 0:
            import json as _json

            stats = _json.loads(res.stdout.strip().splitlines()[-1])
            out["collective_GBps"] = stats.get("allreduce_GBps")
            for k in ("allgather_GBps", "all_to_all_GBps", "a2a_GBps",
                      "reduce_scatter_GBps"):
                if k in stats:
                    out[k] = stats[k]
    except Exception:
        pass
    return out


def collective_bench(nbytes: int = 1 << 24, iters: int = 20) -> dict:
    """Allreduce bandwidth on the real device(s) — rdma_performance role."""
    import jax

    from brpc_tpu import parallel

    n = len(jax.devices())
    mesh = parallel.make_mesh({"x": n})
    stats = parallel.ici_bandwidth_probe(mesh, "x", nbytes=nbytes,
                                         iters=iters)
    return {
        "metric": "allreduce_GBps",
        "value": round(stats["allreduce_GBps"], 3),
        "unit": "GB/s",
        "vs_baseline": 0.0,  # no published RDMA GB/s in the reference
        "extra": stats,
    }
