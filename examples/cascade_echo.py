#!/usr/bin/env python
"""cascade_echo — a call that hops through a chain of servers, with rpcz
tracing the whole path (example/cascade_echo_c++ counterpart; the
pipeline-stage shape of SURVEY.md section 2.12).

  python examples/cascade_echo.py [--depth 3]
"""
import argparse
import sys

sys.path.insert(0, ".")

from brpc_tpu import rpc, rpcz  # noqa: E402
from brpc_tpu.butil import flags  # noqa: E402
from brpc_tpu.rpc.proto import echo_pb2  # noqa: E402


class CascadeService(rpc.Service):
    SERVICE_NAME = "EchoService"

    def __init__(self, name, next_channel=None):
        self.name = name
        self.next_channel = next_channel

    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        if self.next_channel is not None:
            _, next_resp = self.next_channel.call(
                "EchoService.Echo",
                echo_pb2.EchoRequest(message=request.message),
                echo_pb2.EchoResponse, timeout_ms=3000)
            response.message = f"{self.name}->{next_resp.message}"
        else:
            response.message = self.name
        done()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=3)
    args = ap.parse_args()
    flags.set_flag("enable_rpcz", True)  # collection is off by default

    servers = []
    next_ch = None
    for i in reversed(range(args.depth)):
        srv = rpc.Server()
        srv.add_service(CascadeService(f"hop{i}", next_ch))
        assert srv.start("127.0.0.1:0") == 0
        servers.append(srv)
        next_ch = rpc.Channel()
        assert next_ch.init(str(srv.listen_endpoint)) == 0

    rpcz.clear_for_tests()
    cntl, resp = rpc.Channel(), echo_pb2.EchoResponse()
    head = rpc.Channel()
    assert head.init(str(servers[-1].listen_endpoint)) == 0
    cntl, resp = head.call("EchoService.Echo",
                           echo_pb2.EchoRequest(message="go"),
                           echo_pb2.EchoResponse, timeout_ms=5000)
    print("cascade result:", resp.message)

    import time

    time.sleep(0.1)
    spans = rpcz.recent_spans()
    traces = {s.trace_id for s in spans}
    print(f"rpcz collected {len(spans)} spans in {len(traces)} trace(s):")
    for s in spans:
        print("  ", s.describe().splitlines()[0])
    for srv in servers:
        srv.stop()


if __name__ == "__main__":
    main()
