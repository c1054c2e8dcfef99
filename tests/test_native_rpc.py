"""Native RPC runtime tests — the framework data path in C++ (nat_rpc.cpp):
Socket/dispatcher/messenger on fibers + native IOBuf, the py lane
(usercode on pthreads), wire compat with the Python tpu_std stack, and the
framework-path bench."""
import threading

import pytest

from brpc_tpu import native, rpc
from brpc_tpu.rpc.proto import echo_pb2

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


class PyEcho(rpc.Service):
    SERVICE_NAME = "EchoService"

    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        if request.code:
            cntl.set_failed(request.code, "requested failure")
        response.message = request.message
        done()


@pytest.fixture
def native_py_server():
    """A Python Server mounted on the native runtime port."""
    srv = rpc.Server(rpc.ServerOptions(num_threads=2,
                                       use_native_runtime=True))
    srv.add_service(PyEcho())
    assert srv.start("127.0.0.1:0") == 0
    yield srv
    srv.stop()


def test_python_service_on_native_port(native_py_server):
    """Python Channel -> native port -> py lane -> Python service."""
    srv = native_py_server
    ch = rpc.Channel()
    assert ch.init(str(srv.listen_endpoint)) == 0
    cntl, resp = ch.call("EchoService.Echo",
                         echo_pb2.EchoRequest(message="via-native"),
                         echo_pb2.EchoResponse, timeout_ms=5000)
    assert not cntl.failed(), cntl.error_text
    assert resp.message == "via-native"
    ch.close()


def test_python_service_error_on_native_port(native_py_server):
    srv = native_py_server
    ch = rpc.Channel()
    assert ch.init(str(srv.listen_endpoint)) == 0
    cntl, _ = ch.call("EchoService.Echo",
                      echo_pb2.EchoRequest(message="x", code=1003),
                      echo_pb2.EchoResponse, timeout_ms=5000)
    assert cntl.failed()
    assert cntl.error_code == 1003
    ch.close()


def test_unknown_method_on_native_port(native_py_server):
    srv = native_py_server
    ch = rpc.Channel()
    assert ch.init(str(srv.listen_endpoint)) == 0
    cntl, _ = ch.call("NoSuchService.Nope", echo_pb2.EchoRequest(message="x"),
                      echo_pb2.EchoResponse, timeout_ms=5000)
    assert cntl.failed()
    ch.close()


def test_native_client_to_python_service(native_py_server):
    """Native channel (fiber/butex client) against the py lane."""
    srv = native_py_server
    h = native.channel_open("127.0.0.1", srv.listen_endpoint.port)
    try:
        req = echo_pb2.EchoRequest(message="native-client")
        rc, body, err = native.channel_call(
            h, "EchoService", "Echo", req.SerializeToString())
        assert rc == 0, err
        resp = echo_pb2.EchoResponse()
        resp.ParseFromString(body)
        assert resp.message == "native-client"
    finally:
        native.channel_close(h)


def test_native_echo_handler_and_bench():
    """Native handler served zero-copy on fibers; framework-path bench."""
    port = native.rpc_server_start(native_echo=True)
    try:
        h = native.channel_open("127.0.0.1", port)
        rc, body, err = native.channel_call(h, "EchoService", "Echo",
                                            b"raw-bytes")
        assert rc == 0 and body == b"raw-bytes"
        native.channel_close(h)
        stats = native.rpc_client_bench("127.0.0.1", port, nconn=2,
                                        fibers_per_conn=8, seconds=0.5,
                                        payload=16)
        assert stats["requests"] > 100, stats
        assert native.rpc_server_requests() > 100
    finally:
        native.rpc_server_stop()


def test_concurrent_python_clients_on_native_port(native_py_server):
    srv = native_py_server
    errs = []

    def worker(i):
        ch = rpc.Channel()
        if ch.init(str(srv.listen_endpoint)) != 0:
            errs.append("init")
            return
        for j in range(20):
            cntl, resp = ch.call("EchoService.Echo",
                                 echo_pb2.EchoRequest(message=f"m{i}-{j}"),
                                 echo_pb2.EchoResponse, timeout_ms=5000)
            if cntl.failed() or resp.message != f"m{i}-{j}":
                errs.append(f"{i}/{j}: {cntl.error_text}")
                return

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs


def test_io_uring_datapath():
    """The RingListener lane (fork ring_listener.h analog): provided-buffer
    multishot receives + fixed-buffer sends, completions drained by the
    scheduler idle loop. Gated on kernel support."""
    rc = native.use_io_uring(True)
    if rc != 1:
        pytest.skip("io_uring unavailable in this kernel/sandbox")
    try:
        port = native.rpc_server_start("127.0.0.1", 0, nworkers=2,
                                       native_echo=True)
        assert port > 0
        ch = rpc.Channel()
        assert ch.init(f"127.0.0.1:{port}") == 0
        recv0, send0 = native.ring_counters()
        for i in range(40):
            cntl, resp = ch.call("EchoService.Echo",
                                 echo_pb2.EchoRequest(message=f"ring{i}"),
                                 echo_pb2.EchoResponse, timeout_ms=5000)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == f"ring{i}"
        recv1, send1 = native.ring_counters()
        # every request arrived via a provided-buffer recv completion and
        # every response left via a fixed-buffer send completion
        assert recv1 > recv0
        assert send1 > send0
        ch.close()
    finally:
        native.rpc_server_stop()
        native.use_io_uring(False)


def test_native_port_http_console():
    """The native port answers HTTP console GETs natively (the
    multi-protocol-port discipline): /health /status /vars /version."""
    import urllib.request

    port = native.rpc_server_start("127.0.0.1", 0, nworkers=2,
                                   native_echo=True)
    try:
        base = f"http://127.0.0.1:{port}"
        body = urllib.request.urlopen(f"{base}/health", timeout=5).read()
        assert body == b"OK\n"
        body = urllib.request.urlopen(f"{base}/status", timeout=5).read()
        assert b"nat_server_requests" in body
        assert b"nat_scheduler_workers" in body
        body = urllib.request.urlopen(f"{base}/version", timeout=5).read()
        assert body.startswith(b"brpc_tpu_native/")
        try:
            urllib.request.urlopen(f"{base}/nope", timeout=5)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
        # tpu_std still works on the same port after HTTP traffic
        ch = rpc.Channel()
        assert ch.init(f"127.0.0.1:{port}") == 0
        cntl, resp = ch.call("EchoService.Echo",
                             echo_pb2.EchoRequest(message="mixed"),
                             echo_pb2.EchoResponse, timeout_ms=5000)
        assert not cntl.failed() and resp.message == "mixed"
        ch.close()
    finally:
        native.rpc_server_stop()


def test_rss_flat_under_sustained_load():
    """VERDICT round-1 item 4's acceptance: memory stays flat over a
    sustained loopback run (TaskMeta reap + IOBuf block recycling + no
    per-request leaks on the native path)."""
    import ctypes
    import os

    if os.environ.get("BRPC_TPU_SANITIZED"):
        # ASan's quarantine + redzones keep RSS climbing by design; leak
        # detection under instrumentation is LSan's job (the C smoke leg
        # of tools/check.sh --soak), not this gate's
        pytest.skip("RSS-flatness gate is meaningless under ASan")

    def current_rss_mb() -> float:
        # CURRENT rss, not ru_maxrss: the high-water mark passes vacuously
        # when an earlier test already peaked higher
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE") / 1e6

    port = native.rpc_server_start("127.0.0.1", 0, nworkers=2,
                                   native_echo=True)
    try:
        out = ctypes.c_uint64(0)
        lib = native.load()
        # warmup builds steady-state pools/caches
        lib.nat_rpc_client_bench(b"127.0.0.1", port, 2, 32, 1.0, 16,
                                 ctypes.byref(out))
        rss0 = current_rss_mb()
        for _ in range(3):
            lib.nat_rpc_client_bench(b"127.0.0.1", port, 2, 32, 1.0, 16,
                                     ctypes.byref(out))
        grown_mb = current_rss_mb() - rss0
        assert grown_mb < 64, f"RSS grew {grown_mb:.1f}MB under load"
        assert out.value > 10000  # the run actually hammered the path
    finally:
        native.rpc_server_stop()


def test_async_windowed_client():
    """Done-callback completions (PendingCall.cb — the native async-RPC
    surface): a windowed client keeps many requests in flight with no
    parked fiber per call, and every request completes before return."""
    import ctypes

    port = native.rpc_server_start("127.0.0.1", 0, nworkers=2,
                                   native_echo=True)
    try:
        out = ctypes.c_uint64(0)
        qps = native.load().nat_rpc_client_bench_async(
            b"127.0.0.1", port, 2, 128, 1.0, 16, ctypes.byref(out))
        assert qps > 1000, qps
        assert out.value > 1000
    finally:
        native.rpc_server_stop()


def test_native_acall():
    """nat_channel_acall — the exported done-closure call: completion runs
    on a framework thread with the response bytes."""
    import threading

    port = native.rpc_server_start("127.0.0.1", 0, nworkers=2,
                                   native_echo=True)
    ch = None
    try:
        ch = native.channel_open("127.0.0.1", port)
        results = []
        done_evt = threading.Event()

        def done(code, resp):
            results.append((code, resp))
            if len(results) == 8:
                done_evt.set()

        for i in range(8):
            rc = native.channel_acall(ch, "EchoService", "Echo",
                                      f"payload{i}".encode(), done)
            assert rc == 0
        import gc

        gc.collect()  # thunks must survive GC until done fires
        assert done_evt.wait(5)
        assert all(code == 0 for code, _ in results)
        assert sorted(r for _, r in results) == sorted(
            f"payload{i}".encode() for i in range(8))
    finally:
        if ch is not None:
            native.channel_close(ch)
        native.rpc_server_stop()


def _deadline_roundtrip(port, timeout_ms=300):
    """Sync + async native calls against a server that never answers:
    both must complete with ERPCTIMEDOUT in ~timeout_ms."""
    import time

    h = native.channel_open("127.0.0.1", port)
    t0 = time.monotonic()
    rc, _, text = native.channel_call(h, "EchoService", "Echo", b"x",
                                      timeout_ms=timeout_ms)
    dt = time.monotonic() - t0
    assert rc == 1008, (rc, text)  # ERPCTIMEDOUT
    assert timeout_ms / 1000.0 * 0.5 < dt < 5.0, dt

    got = {}
    evt = threading.Event()

    def done(code, resp):
        got["code"] = code
        evt.set()

    t0 = time.monotonic()
    assert native.channel_acall(h, "EchoService", "Echo", b"x", done,
                                timeout_ms=timeout_ms) == 0
    assert evt.wait(10), "acall deadline never fired"
    assert got["code"] == 1008
    assert time.monotonic() - t0 < 5.0
    native.channel_close(h)


def test_native_call_deadline_epoll():
    """A stalled server (py lane enabled, nobody draining) strands the
    request; the native TimerThread must fail the call in ~timeout_ms —
    the controller.cpp:605 deadline semantics, sync and async."""
    port = native.rpc_server_start(native_echo=False)
    assert port > 0
    try:
        _deadline_roundtrip(port)
    finally:
        native.rpc_server_stop()


def test_native_call_deadline_ring():
    """Same deadline contract on the io_uring lane."""
    if native.use_io_uring(True) != 1:
        pytest.skip("io_uring unavailable in this kernel/sandbox")
    try:
        port = native.rpc_server_start(native_echo=False)
        assert port > 0
        try:
            _deadline_roundtrip(port)
        finally:
            native.rpc_server_stop()
    finally:
        native.use_io_uring(False)


def test_native_deadline_does_not_break_completions():
    """A timeout armed but beaten by the response must be a no-op (the
    pending-bit CAS arbitration): hammer calls with generous deadlines."""
    port = native.rpc_server_start(native_echo=True)
    assert port > 0
    try:
        h = native.channel_open("127.0.0.1", port)
        for i in range(200):
            rc, body, text = native.channel_call(
                h, "EchoService", "Echo", b"p%d" % i, timeout_ms=2000)
            assert rc == 0, (rc, text)
            assert body == b"p%d" % i
        native.channel_close(h)
    finally:
        native.rpc_server_stop()


def test_native_kill_and_revive():
    """Native connection robustness (health_check.cpp:146-237 semantics):
    kill the server under a live channel; calls fail fast with a
    deadline; restart the server (clean stop->start, no graveyard); the
    channel re-dials on demand and calls succeed again."""
    import time

    port = native.rpc_server_start(native_echo=True)
    assert port > 0
    try:
        h = native.channel_open("127.0.0.1", port, connect_timeout_ms=2000,
                                health_check_ms=50)
        rc, body, _ = native.channel_call(h, "EchoService", "Echo", b"pre",
                                          timeout_ms=3000)
        assert rc == 0 and body == b"pre"

        native.rpc_server_stop()
        # the failed socket must fail calls (not hang); reconnect attempts
        # against a dead port must respect the connect timeout
        rc, _, _ = native.channel_call(h, "EchoService", "Echo", b"mid",
                                       timeout_ms=500)
        assert rc != 0

        # restart on the SAME port (stop->start cycle, server.h:426-441)
        port2 = native.rpc_server_start(port=port, native_echo=True)
        assert port2 == port
        deadline = time.monotonic() + 10
        rc = -1
        while time.monotonic() < deadline:
            rc, body, _ = native.channel_call(h, "EchoService", "Echo",
                                              b"post", timeout_ms=1000)
            if rc == 0:
                break
            time.sleep(0.05)
        assert rc == 0 and body == b"post"
        native.channel_close(h)
    finally:
        native.rpc_server_stop()


def test_native_retry_rides_over_restart():
    """max_retry + on-demand re-dial: kill the server, restart it, and a
    SINGLE call with retries succeeds without any manual loop (the
    IssueRPC retry state machine role, controller.cpp:554-640)."""
    port = native.rpc_server_start(native_echo=True)
    try:
        h = native.channel_open("127.0.0.1", port, connect_timeout_ms=2000)
        rc, body, _ = native.channel_call(h, "EchoService", "Echo", b"a",
                                          timeout_ms=3000)
        assert rc == 0
        native.rpc_server_stop()
        port2 = native.rpc_server_start(port=port, native_echo=True)
        assert port2 == port
        # the first attempt fails on the dead socket; retries re-dial
        rc, body, text = native.channel_call(h, "EchoService", "Echo",
                                             b"retry-me", timeout_ms=10000,
                                             max_retry=5)
        assert rc == 0, (rc, text)
        assert body == b"retry-me"
        native.channel_close(h)
    finally:
        # a server left running would fail every later native test in
        # this process (one native-runtime server per process)
        native.rpc_server_stop()


def test_native_backup_request():
    """backup_ms: a stalled first attempt is overtaken by a duplicate
    send with the SAME correlation id; the first response to arrive wins
    (controller.cpp:1256 semantics). The py-lane service sleeps only on
    its first invocation, so the backup returns fast."""
    import time

    from brpc_tpu import rpc
    from brpc_tpu.rpc.proto import echo_pb2

    calls = []

    class SlowFirst(rpc.Service):
        SERVICE_NAME = "EchoService"

        @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        def Echo(self, cntl, request, response, done):
            calls.append(time.monotonic())
            if len(calls) == 1:
                time.sleep(1.5)
            response.message = request.message
            done()

    srv = rpc.Server(rpc.ServerOptions(num_threads=4,
                                       use_native_runtime=True))
    srv.add_service(SlowFirst())
    assert srv.start("127.0.0.1:0") == 0
    try:
        h = native.channel_open("127.0.0.1", srv.listen_endpoint.port)
        req = echo_pb2.EchoRequest(message="backup").SerializeToString()
        t0 = time.monotonic()
        rc, body, text = native.channel_call(h, "EchoService", "Echo", req,
                                             timeout_ms=10000,
                                             backup_ms=150)
        dt = time.monotonic() - t0
        assert rc == 0, (rc, text)
        resp = echo_pb2.EchoResponse()
        resp.ParseFromString(body)
        assert resp.message == "backup"
        # the duplicate (2nd invocation, no sleep) answered well before
        # the stalled 1st attempt's 1.5s sleep finished
        assert dt < 1.2, dt
        assert len(calls) == 2
        native.channel_close(h)
    finally:
        srv.stop()


def test_native_port_survives_garbage():
    """Protocol robustness: random garbage, truncated frames, oversized
    headers, and magic-prefix teases must fail the CONNECTION (or wait
    for more bytes), never the server — and real clients keep working
    throughout (the protocol-error discipline of the cut loop)."""
    import os
    import socket as pysocket
    import struct

    port = native.rpc_server_start(native_echo=True)
    assert port > 0
    try:
        h = native.channel_open("127.0.0.1", port)
        payloads = [
            b"\x00" * 64,                       # zeros
            b"GARBAGE-NOT-A-PROTOCOL" * 10,     # printable junk
            b"TRPC" + b"\xff" * 16,             # oversized body/meta
            b"TRPC" + struct.pack(">II", 10, 200),  # meta > body
            b"TR",                              # magic tease, then EOF
            os.urandom(512),                    # random bytes
        ]
        for junk in payloads:
            c = pysocket.create_connection(("127.0.0.1", port), timeout=5)
            c.sendall(junk)
            c.settimeout(2)
            try:
                while c.recv(4096):
                    pass  # server may answer nothing; wait for close
            except (TimeoutError, pysocket.timeout, ConnectionError):
                pass
            c.close()
            # the port is still healthy for real traffic
            rc, body, text = native.channel_call(h, "EchoService", "Echo",
                                                 b"still-up",
                                                 timeout_ms=3000)
            assert rc == 0, (rc, text, junk[:8])
            assert body == b"still-up"
        native.channel_close(h)
    finally:
        native.rpc_server_stop()


class SockId(rpc.Service):
    """Answers each request with the id of the native connection it came
    on."""
    SERVICE_NAME = "EchoService"

    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = str(cntl._server_socket.sock_id)
        done()


def _recv_exact(conn, n):
    out = b""
    while len(out) < n:
        chunk = conn.recv(n - len(out))
        if not chunk:
            break
        out += chunk
    return out


@pytest.fixture
def two_conns():
    """Two plain TCP connections to a native-runtime server, each with the
    server's id for it: [(socket, sock_id), (socket, sock_id)]."""
    import socket as pysocket
    import struct

    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.rpc.proto import rpc_meta_pb2
    from brpc_tpu.rpc.tpu_std_protocol import pack_frame

    srv = rpc.Server(rpc.ServerOptions(num_threads=2,
                                       use_native_runtime=True))
    srv.add_service(SockId())
    assert srv.start("127.0.0.1:0") == 0
    conns = []
    try:
        for cid in (1, 2):
            conn = pysocket.create_connection(
                ("127.0.0.1", srv.listen_endpoint.port), timeout=10)
            meta = rpc_meta_pb2.RpcMeta(correlation_id=cid)
            meta.request.service_name = "EchoService"
            meta.request.method_name = "Echo"
            frame = pack_frame(meta, echo_pb2.EchoRequest().
                               SerializeToString(), IOBuf())
            conn.sendall(frame.copy_to_bytes(len(frame)))
            body, meta_size = struct.unpack(">II", _recv_exact(conn, 12)[4:])
            resp = _recv_exact(conn, body)[meta_size:]
            conns.append((conn, int(echo_pb2.EchoResponse.FromString(
                resp).message)))
        yield conns
    finally:
        for conn, _ in conns:
            conn.close()
        srv.stop()


def test_sock_write_batch_keeps_each_connections_frames_in_order(two_conns):
    (ca, a), (cb, b) = two_conns
    frames = [(a, b"a0" * 3), (b, b"b0"), (a, b"a1" * 70000),
              (b, b"b1" * 5), (a, b"a2")]
    assert native.sock_write_batch(frames) == len(frames)
    for conn, sid in two_conns:
        want = b"".join(data for s, data in frames if s == sid)
        assert _recv_exact(conn, len(want)) == want


def test_sock_write_batch_skips_a_connection_that_is_gone(two_conns):
    (ca, a), (cb, b) = two_conns
    assert native.sock_set_failed(a) == 0
    assert native.sock_write_batch([(a, b"lost"), (b, b"kept"),
                                    (a, b"lost too")]) == 1
    assert _recv_exact(cb, 4) == b"kept"
    assert _recv_exact(ca, 1) == b""  # closed, nothing written


def test_sock_write_batch_counts_calls_and_frames(two_conns):
    (_, a), (cb, b) = two_conns
    before = native.stats_counters()
    frames = [(b, b"x"), (a, b"y"), (b, b"z")]
    assert native.sock_write_batch(frames) == 3
    assert native.sock_write_batch([]) == 0  # no crossing, nothing counted
    after = native.stats_counters()
    assert after["nat_sock_write_batches"] - \
        before["nat_sock_write_batches"] == 1
    assert after["nat_sock_write_batch_frames"] - \
        before["nat_sock_write_batch_frames"] == 3
    assert _recv_exact(cb, 2) == b"xz"


def test_coalesced_writes_send_a_scopes_frames_in_one_crossing(two_conns):
    """Shim writes inside a scope (a nested one joins it) are held and
    leave in one sock_write_batch when the outer scope closes, even when
    it closes on an exception."""
    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.rpc.native_runtime import NativeSocketShim, \
        coalesced_writes

    (ca, a), (cb, b) = two_conns
    shims = {a: NativeSocketShim(a), b: NativeSocketShim(b)}
    frames = [(a, b"first"), (b, b"second"), (a, b"third")]
    before = native.stats_counters()
    with pytest.raises(RuntimeError):
        with coalesced_writes():
            for sid, data in frames[:2]:
                shims[sid].write(IOBuf(data))
            with coalesced_writes():
                shims[a].write(IOBuf(frames[2][1]))
            ca.settimeout(0.2)
            with pytest.raises(OSError):  # held: nothing sent yet
                ca.recv(1)
            ca.settimeout(10)
            raise RuntimeError("the scope still flushes")
    after = native.stats_counters()
    assert after["nat_sock_write_batches"] - \
        before["nat_sock_write_batches"] == 1
    assert after["nat_sock_write_batch_frames"] - \
        before["nat_sock_write_batch_frames"] == 3
    assert _recv_exact(ca, 10) == b"firstthird"
    assert _recv_exact(cb, 6) == b"second"
    # outside a scope a write leaves at once, as one native write
    shims[b].write(IOBuf(b"now"))
    assert _recv_exact(cb, 3) == b"now"
    assert native.stats_counters()["nat_sock_write_batches"] == \
        after["nat_sock_write_batches"]
