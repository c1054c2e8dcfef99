"""End-to-end trace stitching (the flight recorder's rpcz leg).

trace_id / span_id / parent_span_id now survive process boundaries on
every native lane: the tpu_std RpcMeta trace fields, HTTP x-bd-trace-*
headers, gRPC x-bd-* metadata, and the shm worker lane's descriptor
records — so /rpcz ``find_trace`` returns the full client -> native
server -> shm worker chain with correct parent edges. These tests pin
the per-lane client/server linkage and the two-process worker chain
(ISSUE 6 acceptance: one trace_id, >= 3 linked spans through the shm
worker lane).
"""
import json
import time

import pytest

from brpc_tpu import rpc

# rpcz collection is off by default; this module reads spans
pytestmark = pytest.mark.usefixtures("rpcz_on")

native = pytest.importorskip("brpc_tpu.native")
if not native.available():
    pytest.skip("native toolchain unavailable", allow_module_level=True)


@pytest.fixture()
def nat_server():
    """Bare native server: echo native handler + native HTTP/h2 lanes +
    span sampling on (every call). Function-scoped: the native runtime
    owns ONE server at a time, and the shm-worker test below needs the
    port back for its own full rpc.Server."""
    port = native.rpc_server_start(native_echo=True)
    native.rpc_server_native_http(True)
    native.stats_enable_spans(1)
    native.stats_drain_spans()  # drop spans from earlier tests
    yield port
    native.stats_enable_spans(0)
    native.rpc_server_stop()


def _spans_for(trace_id, tries=20):
    """Drain native spans until the trace shows up (the write side is
    asynchronous for client completions)."""
    got = []
    for _ in range(tries):
        got += [r for r in native.stats_drain_spans()
                if r["trace_id"] == trace_id]
        kinds = {r["lane"] for r in got}
        if "client" in kinds and len(kinds) >= 2:
            break
        time.sleep(0.05)
    return got


def _assert_linked(spans, server_lane, trace_id, parent_span):
    client = [r for r in spans if r["lane"] == "client"]
    server = [r for r in spans if r["lane"] == server_lane]
    assert client, spans
    assert server, spans
    assert client[0]["trace_id"] == trace_id
    assert client[0]["parent_span_id"] == parent_span
    assert server[0]["trace_id"] == trace_id
    assert server[0]["parent_span_id"] == client[0]["span_id"]
    return client[0], server[0]


def test_tpu_std_client_server_spans_linked(nat_server):
    t, s = 0x1011, 0x77
    ch = native.channel_open("127.0.0.1", nat_server)
    try:
        with native.trace_scope(t, s):
            rc, body, _ = native.channel_call(ch, "EchoService", "Echo",
                                              b"x", timeout_ms=5000)
        assert rc == 0
    finally:
        native.channel_close(ch)
    cl, sv = _assert_linked(_spans_for(t), "echo", t, s)
    assert cl["method"] == "EchoService.Echo"
    assert sv["method"] == "EchoService.Echo"


def test_http_client_server_spans_linked(nat_server):
    t, s = 0x1012, 0x78
    ch = native.channel_open_http("127.0.0.1", nat_server)
    try:
        with native.trace_scope(t, s):
            status, out = native.http_call(ch, "GET", "/echo",
                                           timeout_ms=5000)
        assert status == 200
    finally:
        native.channel_close(ch)
    cl, sv = _assert_linked(_spans_for(t), "http", t, s)
    assert cl["method"] == "GET /echo"
    assert sv["method"] == "/echo"


def test_grpc_client_server_spans_linked(nat_server):
    t, s = 0x1013, 0x79
    ch = native.channel_open_grpc("127.0.0.1", nat_server)
    try:
        with native.trace_scope(t, s):
            gst, out, msg = native.grpc_call(ch, "/EchoService/Echo",
                                             b"hi", timeout_ms=5000)
        assert gst == 0, msg
    finally:
        native.channel_close(ch)
    _assert_linked(_spans_for(t), "grpc", t, s)


def test_trace_scope_restores_enclosing_context(nat_server):
    """A nested scope must restore the ENCLOSING ambient context on
    exit, not clobber it to zero — calls after the inner scope keep
    propagating the outer trace."""
    t_outer, s_outer = 0x2020, 0x31
    ch = native.channel_open("127.0.0.1", nat_server)
    try:
        with native.trace_scope(t_outer, s_outer):
            with native.trace_scope(0x2021, 0x32):
                pass  # inner scope closes...
            rc, _, _ = native.channel_call(ch, "EchoService", "Echo",
                                           b"n", timeout_ms=5000)
            assert rc == 0
    finally:
        native.channel_close(ch)
    spans = _spans_for(t_outer)
    cl = [r for r in spans if r["lane"] == "client"]
    assert cl and cl[0]["trace_id"] == t_outer
    assert cl[0]["parent_span_id"] == s_outer


def test_untraced_calls_start_fresh_roots(nat_server):
    """No ambient context: the sampled spans still record, with a fresh
    trace id and a 0 parent (a root), never trace_id 0."""
    ch = native.channel_open("127.0.0.1", nat_server)
    try:
        rc, _, _ = native.channel_call(ch, "EchoService", "Echo", b"y",
                                       timeout_ms=5000)
        assert rc == 0
    finally:
        native.channel_close(ch)
    time.sleep(0.1)
    recs = native.stats_drain_spans()
    assert recs
    for r in recs:
        assert r["trace_id"] != 0


def test_rpcz_find_trace_returns_parent_edges(nat_server):
    """The Python /rpcz surface: drained native spans carry
    parent_span_id, and find_trace stitches them into one trace."""
    from brpc_tpu import rpcz

    rpcz.clear_for_tests()
    t, s = 0x1014, 0x80
    ch = native.channel_open("127.0.0.1", nat_server)
    try:
        with native.trace_scope(t, s):
            rc, _, _ = native.channel_call(ch, "EchoService", "Echo",
                                           b"z", timeout_ms=5000)
        assert rc == 0
    finally:
        native.channel_close(ch)
    time.sleep(0.1)
    spans = rpcz.find_trace(t)
    assert len(spans) >= 2
    client = [x for x in spans if x.kind == "client"][0]
    server = [x for x in spans if x.kind == "server"][0]
    assert client.parent_span_id == s
    assert server.parent_span_id == client.span_id
    # and the page renders the trace
    body = rpcz.describe_recent_spans({"trace_id": f"{t:x}"})
    assert f"trace={t:016x}" in body


def test_kind8_descriptor_carries_trace_context():
    """Bulk-tensor (kind-8) shm descriptors: the unused sock_id/cid
    fields carry the ambient (trace_id, parent span) across the process
    boundary."""
    lib = native.load()
    lib.nat_shm_lane_enable(0)
    assert lib.nat_shm_lane_create(1 << 20) == 0
    assert lib.nat_shm_worker_attach(lib.nat_shm_lane_name()) == 0
    try:
        with native.trace_scope(0xbead, 0x42):
            assert lib.nat_shm_push_tensor(b"tensor-bytes", 12, 7) == 0
        h = lib.nat_shm_take_request(2000)
        assert h
        assert lib.nat_req_kind(h) == 8
        assert lib.nat_req_sock_id(h) == 0xbead   # trace_id
        assert lib.nat_req_cid(h) == 0x42         # parent span id
        assert lib.nat_req_aux(h) == 7            # caller tag untouched
        native.req_free(h)
    finally:
        lib.nat_shm_lane_enable(0)


def test_shm_worker_chain_three_linked_spans():
    """ISSUE 6 acceptance: a two-process echo through the shm worker
    lane yields ONE trace_id with >= 3 linked spans in /rpcz find_trace
    — client -> native server -> shm worker, correct parent edges."""
    from brpc_tpu import rpcz
    from tests.shm_worker_factory import make

    srv = rpc.Server(rpc.ServerOptions(
        num_threads=2, use_native_runtime=True, py_workers=1,
        py_worker_factory="tests.shm_worker_factory:make"))
    for s in make():
        srv.add_service(s)
    assert srv.start("127.0.0.1:0") == 0
    port = srv.listen_endpoint.port
    try:
        rpcz.clear_for_tests()
        t, s_parent = 0xfeed01, 0x99
        ch = native.channel_open_http("127.0.0.1", port)
        body = json.dumps({"message": "hi"}).encode()
        try:
            with native.trace_scope(t, s_parent):
                status, out = native.http_call(
                    ch, "POST", "/EchoService/Echo", body,
                    headers="Content-Type: application/json\r\n",
                    timeout_ms=15000)
            assert status == 200, out
        finally:
            native.channel_close(ch)
        deadline = time.time() + 10
        spans = []
        while time.time() < deadline:
            spans = rpcz.find_trace(t)
            if len(spans) >= 3:
                break
            time.sleep(0.2)
        assert len(spans) >= 3, [x.describe() for x in spans]
        client = [x for x in spans if x.kind == "client"][0]
        server = [x for x in spans
                  if "native:http" in str(x.remote_side)][0]
        worker = [x for x in spans
                  if "native:worker" in str(x.remote_side)][0]
        assert client.parent_span_id == s_parent
        assert server.parent_span_id == client.span_id
        assert worker.parent_span_id == server.span_id
        # one trace end to end
        assert {x.trace_id for x in spans} == {t}
    finally:
        srv.stop()
