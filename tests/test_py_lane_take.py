"""The py lane's batch take (nat_take_request_batch_info): one crossing
hands Python every field of every request taken.

- take_requests returns, for every kind a socket can produce, the tuple the
  per-field accessors (nat_req_kind / _field / _sock_id / _cid / ...) give
  for the same handle;
- a take of several queued requests makes exactly one foreign call;
- nat_py_takes counts the takes that returned something.
"""
import ctypes
import socket
import struct
import time
import types
import weakref

import numpy as np
import pytest

from brpc_tpu import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


def legacy_tuple(h):
    """The take_request tuple of handle `h` built from the per-field
    accessors, one crossing per field (the contract take_requests keeps)."""
    lib = native.load()

    def field(which):
        n = ctypes.c_size_t(0)
        p = lib.nat_req_field(h, which, ctypes.byref(n))
        return ctypes.string_at(p, n.value) if p and n.value else b""

    kind = lib.nat_req_kind(h)
    enq = lib.nat_req_enqueue_ns(h)
    sock_id, cid = lib.nat_req_sock_id(h), lib.nat_req_cid(h)
    if kind in (3, 4):
        return (h, kind, field(4), field(2), b"", sock_id, cid, field(0),
                field(1), 0, enq)
    if kind == 5:
        return (h, kind, b"", field(2), b"", sock_id, cid,
                lib.nat_req_compress(h), b"", lib.nat_req_aux(h), enq)
    return (h, kind, field(4), field(2), field(3), sock_id, cid, b"", b"",
            0, enq)


def tpu_std_frame(cid, payload, attachment=b""):
    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.rpc.proto import rpc_meta_pb2
    from brpc_tpu.rpc.tpu_std_protocol import pack_frame

    meta = rpc_meta_pb2.RpcMeta()
    meta.request.service_name = "EchoService"
    meta.request.method_name = "Echo"
    meta.correlation_id = cid
    att = IOBuf()
    if attachment:
        att.append(attachment)
    return pack_frame(meta, payload, att).to_bytes()


def stream_header(stream_id, ftype, payload_len):
    return (b"TSTR" + struct.pack(">I", 9 + payload_len) +
            struct.pack(">Q", stream_id) + bytes([ftype]))


@pytest.fixture
def port():
    p = native.rpc_server_start()
    native.rpc_server_enable_raw_fallback(True)
    native.rpc_server_native_http(True)
    native.rpc_server_redis(1)
    yield p
    native.rpc_server_stop()


def connect(port):
    s = socket.create_connection(("127.0.0.1", port), timeout=5)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


def take_n(n, timeout_s=10.0):
    items = []
    deadline = time.time() + timeout_s
    while len(items) < n and time.time() < deadline:
        items += native.take_requests(16, 50)
    assert len(items) == n, [it[1] for it in items]
    return items


def wait_queued(n, timeout_s=10.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if native.stats_counters()["nat_py_queue_depth"] >= n:
            return
        time.sleep(0.01)
    raise AssertionError(f"{n} requests never queued")


def free_all(items):
    for it in items:
        if it[0] is not None:
            native.req_free(it[0])


def test_tpu_std_request_with_attachment_matches_accessors(port):
    s = connect(port)
    s.sendall(tpu_std_frame(7, b"the payload", b"the attachment"))
    items = take_n(1)
    try:
        item = items[0]
        assert item[1] == 0
        assert item == legacy_tuple(item[0])
        assert item[3] == b"the payload" and item[4] == b"the attachment"
        assert item[6] == 7 and item[10] > 0
    finally:
        free_all(items)
        s.close()


def test_raw_bytes_and_close_match_accessors(port):
    s = connect(port)
    s.sendall(b"XYZW unknown framing, raw chunk\r\n")
    items = take_n(1)
    assert items[0][1] == 1
    assert items[0] == legacy_tuple(items[0][0])
    assert items[0][3] == b"XYZW unknown framing, raw chunk\r\n"
    s.close()
    closed = take_n(1)
    try:
        assert closed[0][1] == 2 and closed[0][5] == items[0][5]
        assert closed[0] == legacy_tuple(closed[0][0])
        assert closed[0][10] == 0
    finally:
        free_all(items + closed)


def test_http_request_matches_accessors(port):
    s = connect(port)
    s.sendall(b"POST /echo/it?x=1 HTTP/1.1\r\nHost: h\r\nX-Tag: abc\r\n"
              b"Content-Length: 5\r\n\r\nhello")
    items = take_n(1)
    try:
        item = items[0]
        assert item[1] == 3
        assert item == legacy_tuple(item[0])
        assert item[3] == b"hello"
        assert item[7] == b"POST" and item[8].startswith(b"/echo/it")
        assert b"x-tag: abc" in item[2]
    finally:
        free_all(items)
        s.close()


def test_redis_command_matches_accessors(port):
    s = connect(port)
    s.sendall(b"*2\r\n$3\r\nGET\r\n$3\r\nkey\r\n")
    items = take_n(1)
    try:
        assert items[0][1] == 6
        assert items[0] == legacy_tuple(items[0][0])
        assert items[0][3]
    finally:
        free_all(items)
        s.close()


def test_small_stream_frame_matches_accessors(port):
    s = connect(port)
    body = bytes(range(256)) * 4
    s.sendall(stream_header(42, 3, len(body)) + body)
    items = take_n(1)
    try:
        item = items[0]
        assert item[1] == 5 and item[0] is not None
        assert item == legacy_tuple(item[0])
        assert item[3] == body and item[7] == 3 and item[9] == 42
    finally:
        free_all(items)
        s.close()


def test_big_stream_frame_is_a_zero_copy_view_that_owns_its_handle(
        port, monkeypatch):
    finalizers = []

    def recording_finalize(obj, fn, *args):
        finalizers.append(args)
        return weakref.finalize(obj, fn, *args)

    monkeypatch.setattr(native, "weakref",
                        types.SimpleNamespace(finalize=recording_finalize))
    s = connect(port)
    body = bytes(range(251)) * 400  # 100,400 B: at least 64 KiB
    # the header alone first, so the body lands in the fill buffer
    s.sendall(stream_header(9, 1, len(body)))
    time.sleep(0.1)
    s.sendall(body)
    items = take_n(1)
    item = items[0]
    assert item[1] == 5 and item[0] is None
    assert isinstance(item[3], memoryview) and item[3].readonly
    (h,) = finalizers[0]
    want = legacy_tuple(h)
    assert (h,) + item[1:3] + (bytes(item[3]),) + item[4:] == want
    # zero copy: the view is the request's own buffer
    n = ctypes.c_size_t(0)
    p = native.load().nat_req_field(h, 2, ctypes.byref(n))
    assert np.frombuffer(item[3], np.uint8).ctypes.data == p
    assert n.value == len(body)
    s.close()


class CountingLib:
    """Stands in for load()'s library and records every call made."""

    def __init__(self, lib):
        self._lib = lib
        self.calls = []

    def __getattr__(self, name):
        fn = getattr(self._lib, name)

        def call(*args):
            self.calls.append(name)
            return fn(*args)

        return call


def test_a_take_of_queued_requests_is_one_foreign_call(port, monkeypatch):
    n = 6
    s = connect(port)
    s.sendall(b"".join(tpu_std_frame(100 + i, b"p%d" % i, b"a%d" % i)
                       for i in range(n)))
    wait_queued(n)
    proxy = CountingLib(native.load())
    monkeypatch.setattr(native, "load", lambda: proxy)
    items = native.take_requests(16, 1000)
    monkeypatch.undo()
    try:
        assert proxy.calls == ["nat_take_request_batch_info"]
        assert [it[6] for it in items] == [100 + i for i in range(n)]
        assert [it[3] for it in items] == [b"p%d" % i for i in range(n)]
        assert [it[4] for it in items] == [b"a%d" % i for i in range(n)]
        assert all(it == legacy_tuple(it[0]) for it in items)
    finally:
        free_all(items)
        s.close()


def test_py_takes_counts_each_take_that_returns_requests(port):
    n = 8
    s = connect(port)
    before = native.stats_counters()
    s.sendall(b"".join(tpu_std_frame(i, b"x") for i in range(n)))
    wait_queued(n)
    items = native.take_requests(16, 1000)
    mid = native.stats_counters()
    assert native.take_requests(16, 20) == []  # an empty take counts none
    after = native.stats_counters()
    try:
        assert len(items) == n
        assert mid["nat_py_takes"] - before["nat_py_takes"] == 1
        assert after["nat_py_takes"] == mid["nat_py_takes"]
        dispatched = mid["nat_py_dispatches"] - before["nat_py_dispatches"]
        assert dispatched == n
        assert dispatched / (mid["nat_py_takes"] - before["nat_py_takes"]) > 1
    finally:
        free_all(items)
        s.close()


def test_take_request_returns_the_batch_tuple(port):
    s = connect(port)
    s.sendall(tpu_std_frame(5, b"one", b"att"))
    item = None
    deadline = time.time() + 10
    while item is None and time.time() < deadline:
        item = native.take_request(50)
    try:
        assert item is not None and item == legacy_tuple(item[0])
    finally:
        native.req_free(item[0])
        s.close()


def test_py_takes_rides_vars():
    import http.client

    from brpc_tpu import rpc
    from brpc_tpu.bvar import native_vars
    from brpc_tpu.rpc.proto import echo_pb2

    class EchoService(rpc.Service):
        @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        def Echo(self, cntl, request, response, done):
            response.message = request.message
            done()

    srv = rpc.Server(rpc.ServerOptions(num_threads=2,
                                       use_native_runtime=True))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    try:
        port = srv.listen_endpoint.port
        ch = rpc.Channel()
        assert ch.init(f"127.0.0.1:{port}") == 0
        for i in range(5):
            cntl = rpc.Controller()
            resp = echo_pb2.EchoResponse()
            ch.call_method("EchoService.Echo", cntl,
                           echo_pb2.EchoRequest(message=f"m{i}"), resp)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == f"m{i}"
        native_vars.settle_for_tests()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        conn.request("GET", "/vars")
        r = conn.getresponse()
        body = r.read().decode()
        conn.close()
        assert r.status == 200
        vals = {}
        for line in body.splitlines():
            name, sep, v = line.partition(" : ")
            if sep and name.strip() in ("nat_py_takes", "nat_py_dispatches"):
                vals[name.strip()] = float(v)
        assert vals["nat_py_takes"] >= 1
        assert vals["nat_py_dispatches"] >= vals["nat_py_takes"]
    finally:
        srv.stop()
