"""SocketMap + bthread fd helper tests (details/socket_map, bthread/fd.cpp
shapes)."""
import socket as pysocket

import pytest

from brpc_tpu import bthread, rpc
from brpc_tpu.butil.endpoint import EndPoint
from brpc_tpu.rpc.proto import echo_pb2
from brpc_tpu.rpc.socket_map import SocketMap, get_global_socket_map


class EchoService(rpc.Service):
    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        done()


@pytest.fixture(scope="module")
def server():
    srv = rpc.Server(rpc.ServerOptions(num_threads=2))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    yield srv
    srv.stop()


def test_channels_share_single_connection(server):
    """Two channels, same endpoint, 'single' type → ONE shared socket."""
    ch1, ch2 = rpc.Channel(), rpc.Channel()
    assert ch1.init(str(server.listen_endpoint)) == 0
    assert ch2.init(str(server.listen_endpoint)) == 0
    c1, _ = ch1.call("EchoService.Echo", echo_pb2.EchoRequest(message="a"),
                     echo_pb2.EchoResponse, timeout_ms=3000)
    c2, _ = ch2.call("EchoService.Echo", echo_pb2.EchoRequest(message="b"),
                     echo_pb2.EchoResponse, timeout_ms=3000)
    assert not c1.failed() and not c2.failed()
    assert ch1._single_sid == ch2._single_sid  # shared via SocketMap
    ch1.close()
    ch2.close()


def test_different_protocols_get_different_connections(server):
    """SocketMapKey includes the protocol (socket_map.h): a tpu_std channel
    and an http channel to the SAME endpoint must NOT share a socket."""
    ch1 = rpc.Channel()
    ch2 = rpc.Channel(rpc.ChannelOptions(protocol="http"))
    assert ch1.init(str(server.listen_endpoint)) == 0
    assert ch2.init(str(server.listen_endpoint)) == 0
    c1, _ = ch1.call("EchoService.Echo", echo_pb2.EchoRequest(message="a"),
                     echo_pb2.EchoResponse, timeout_ms=3000)
    assert not c1.failed()
    c2, r2 = ch2.call("EchoService.Echo", echo_pb2.EchoRequest(message="h"),
                      echo_pb2.EchoResponse, timeout_ms=3000)
    assert not c2.failed(), c2.error_text
    assert r2.message == "h"
    assert ch1._single_sid != ch2._single_sid
    ch1.close()
    ch2.close()


def test_ssl_and_device_transport_keyed_separately():
    """ssl / device-transport channels never share a plain connection."""
    from brpc_tpu.rpc.socket_map import make_key

    ep = EndPoint("127.0.0.1", 1)
    plain = make_key(ep, protocol="tpu_std")
    ssl = make_key(ep, protocol="tpu_std", ssl=True)
    dev = make_key(ep, protocol="tpu_std", app_connect_id="device")
    assert len({plain, ssl, dev}) == 3
    smap = SocketMap()
    assert smap.insert(ep, key=plain) != smap.insert(ep, key=dev)
    assert smap.count() == 2


def test_socket_map_refcounting():
    smap = SocketMap()
    ep = EndPoint("127.0.0.1", 1)  # never connected: just identity mgmt
    sid1 = smap.insert(ep)
    sid2 = smap.insert(ep)
    assert sid1 == sid2
    assert smap.count() == 1
    smap.remove(ep)  # ref 2 -> 1
    assert smap.count() == 1
    smap.remove(ep)  # ref 1 -> 0: recycled
    assert smap.count() == 0
    sid3 = smap.insert(ep)
    assert sid3 != sid1  # new socket identity after recycle


def test_fd_wait_and_connect(server):
    s = bthread.connect(("127.0.0.1", server.listen_endpoint.port),
                        timeout_s=2)
    # writable right after connect
    assert bthread.fd_wait(s.fileno(), "w", timeout_s=2)
    # not readable yet (no data): timeout path
    assert not bthread.fd_wait(s.fileno(), "r", timeout_s=0.05)
    s.close()


def test_inline_writer_hands_off_after_its_own_message():
    """A caller that becomes the socket's writer writes its own message
    and hands whatever other threads queued behind it to KeepWrite
    (socket.cpp StartWrite): draining their queue inline would hold the
    caller for as long as they keep writing (a stream writer sharing the
    connection held an RPC caller for a whole 2 s storm)."""
    import threading

    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.rpc.socket import Socket

    a, b = pysocket.socketpair()
    sock = Socket.address(Socket.create(fd=a))
    writers = []

    class Later(IOBuf):
        def cut_into_socket(self, fd, *args):
            writers.append(threading.get_ident())
            return super().cut_into_socket(fd, *args)

    later = Later()
    later.append(b"y" * 100)

    class Mine(IOBuf):
        queued = False

        def cut_into_socket(self, fd, *args):
            if not self.queued:  # another producer queues behind us
                self.queued = True
                assert sock.write(later) == 0
            return super().cut_into_socket(fd, *args)

    mine = Mine()
    mine.append(b"x" * 100)
    try:
        assert sock.write(mine) == 0
        b.settimeout(5)
        got = b""
        while len(got) < 200:
            got += b.recv(200 - len(got))
        assert got == b"x" * 100 + b"y" * 100  # wire order kept
        assert writers and writers[0] != threading.get_ident()
    finally:
        sock.set_failed()
        b.close()
