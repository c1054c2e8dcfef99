#!/usr/bin/env python
"""parallel_echo — ParallelChannel fan-out (example/parallel_echo_c++
counterpart) plus its fused-device twin: the same call shape executed as
ONE XLA collective through MeshChannel (SURVEY.md section 2.12).

  python examples/parallel_echo.py
"""
import sys

sys.path.insert(0, ".")

from brpc_tpu import rpc  # noqa: E402
from brpc_tpu.rpc.proto import echo_pb2  # noqa: E402


class NodeEcho(rpc.Service):
    SERVICE_NAME = "EchoService"

    def __init__(self, name):
        self.name = name

    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = f"{self.name}:{request.message};"
        done()


class ConcatMerger(rpc.ResponseMerger):
    def merge(self, main, sub):
        main.message += sub.message
        return 0


def main():
    servers = []
    pc = rpc.ParallelChannel()
    for i in range(3):
        srv = rpc.Server()
        srv.add_service(NodeEcho(f"node{i}"))
        assert srv.start("127.0.0.1:0") == 0
        servers.append(srv)
        ch = rpc.Channel()
        assert ch.init(str(srv.listen_endpoint)) == 0
        pc.add_channel(ch, response_merger=ConcatMerger())

    cntl, resp = pc.call("EchoService.Echo",
                         echo_pb2.EchoRequest(message="fanout"),
                         echo_pb2.EchoResponse, timeout_ms=3000)
    print("RPC fan-out merged:", resp.message, f"({cntl.latency_us:.0f}us)")

    # The fused twin: same semantics, one device program.
    import jax

    if len(jax.devices()) >= 2:
        import jax.numpy as jnp

        from brpc_tpu import parallel

        n = len(jax.devices())
        mesh = parallel.make_mesh({"dp": n})
        mc = parallel.MeshChannel(mesh, "dp")
        shards = jnp.arange(float(n)).reshape(n, 1)
        merged = mc.parallel_call(lambda s: s * 2.0, shards, merger="add")
        print(f"Mesh fan-out (ONE allreduce over {n} devices):",
              float(merged.ravel()[0]))
    for srv in servers:
        srv.stop()


if __name__ == "__main__":
    main()
