"""rpcz tracing tests — span creation on both sides, parent/child chaining
through nested calls (the tls_bls parenting of span.h:76,116), trace-id
propagation over the wire, /rpcz page (SURVEY.md section 5).
"""
import http.client
import time

import pytest

from brpc_tpu import rpc, rpcz
from brpc_tpu.rpc.proto import echo_pb2

# rpcz collection is off by default; this module reads spans
pytestmark = pytest.mark.usefixtures("rpcz_on")


class FrontService(rpc.Service):
    """Calls a backend inside its handler — the cascade shape that must
    chain spans."""

    backend_channel = None

    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Front(self, cntl, request, response, done):
        assert rpcz.current_parent() is not None  # server span active
        back_cntl, back_resp = self.backend_channel.call(
            "BackService.Back", echo_pb2.EchoRequest(message=request.message),
            echo_pb2.EchoResponse, timeout_ms=3000,
        )
        response.message = f"front({back_resp.message})"
        done()


class BackService(rpc.Service):
    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Back(self, cntl, request, response, done):
        response.message = f"back({request.message})"
        done()


@pytest.fixture(scope="module")
def cascade():
    back_srv = rpc.Server(rpc.ServerOptions(num_threads=2))
    back_srv.add_service(BackService())
    assert back_srv.start("127.0.0.1:0") == 0
    back_ch = rpc.Channel()
    assert back_ch.init(str(back_srv.listen_endpoint)) == 0
    front_svc = FrontService()
    front_svc.backend_channel = back_ch
    front_srv = rpc.Server(rpc.ServerOptions(num_threads=2))
    front_srv.add_service(front_svc)
    assert front_srv.start("127.0.0.1:0") == 0
    yield front_srv, back_srv
    front_srv.stop()
    back_srv.stop()


def test_spans_collected(cascade):
    front_srv, _ = cascade
    rpcz.clear_for_tests()
    ch = rpc.Channel()
    assert ch.init(str(front_srv.listen_endpoint)) == 0
    cntl, resp = ch.call("FrontService.Front",
                         echo_pb2.EchoRequest(message="t"),
                         echo_pb2.EchoResponse, timeout_ms=5000)
    assert not cntl.failed(), cntl.error_text
    assert resp.message == "front(back(t))"
    time.sleep(0.1)
    spans = rpcz.recent_spans()
    kinds = [(s.kind, s.full_method) for s in spans]
    assert ("server", "FrontService.Front") in kinds
    assert ("server", "BackService.Back") in kinds
    assert ("client", "FrontService.Front") in kinds
    assert ("client", "BackService.Back") in kinds


def test_trace_chains_across_hops(cascade):
    front_srv, _ = cascade
    rpcz.clear_for_tests()
    ch = rpc.Channel()
    assert ch.init(str(front_srv.listen_endpoint)) == 0
    cntl, _ = ch.call("FrontService.Front",
                      echo_pb2.EchoRequest(message="x"),
                      echo_pb2.EchoResponse, timeout_ms=5000)
    assert not cntl.failed()
    time.sleep(0.1)
    spans = rpcz.recent_spans()
    front_server = next(s for s in spans
                        if (s.kind, s.full_method) == ("server",
                                                       "FrontService.Front"))
    back_client = next(s for s in spans
                       if (s.kind, s.full_method) == ("client",
                                                      "BackService.Back"))
    back_server = next(s for s in spans
                       if (s.kind, s.full_method) == ("server",
                                                      "BackService.Back"))
    # One trace end to end; back_client is a child of the front server span
    assert back_client.trace_id == front_server.trace_id
    assert back_client.parent_span_id == front_server.span_id
    assert back_server.trace_id == front_server.trace_id
    assert back_server.parent_span_id == back_client.span_id
    assert front_server.latency_us > 0


def test_span_annotations():
    span = rpcz.Span("server", "X.Y")
    span.annotate("step one")
    span.annotate("step two")
    span.end(0)
    text = span.describe()
    assert "step one" in text and "step two" in text


def test_rpcz_page(cascade):
    front_srv, _ = cascade
    ch = rpc.Channel()
    assert ch.init(str(front_srv.listen_endpoint)) == 0
    ch.call("FrontService.Front", echo_pb2.EchoRequest(message="p"),
            echo_pb2.EchoResponse, timeout_ms=5000)
    time.sleep(0.1)
    conn = http.client.HTTPConnection("127.0.0.1",
                                      front_srv.listen_endpoint.port,
                                      timeout=5)
    conn.request("GET", "/rpcz")
    r = conn.getresponse()
    body = r.read().decode()
    assert r.status == 200
    assert "FrontService.Front" in body
    conn.close()


def test_rpcz_disable_flag(cascade):
    from brpc_tpu.butil import flags

    front_srv, _ = cascade
    rpcz.clear_for_tests()
    assert flags.set_flag("enable_rpcz", False)
    try:
        ch = rpc.Channel()
        assert ch.init(str(front_srv.listen_endpoint)) == 0
        ch.call("FrontService.Front", echo_pb2.EchoRequest(message="d"),
                echo_pb2.EchoResponse, timeout_ms=5000)
        time.sleep(0.1)
        assert rpcz.recent_spans() == []
    finally:
        flags.set_flag("enable_rpcz", True)
