"""RTMP family tests — AMF0 codec, FLV muxer, chunk layer, and an
end-to-end publish->play relay over a real multi-protocol server port
(the rtmp_protocol.cpp + amf.cpp + rtmp.cpp coverage slots)."""
import json
import socket as pysocket
import struct
import threading
import time

import pytest

from brpc_tpu import rpc
from brpc_tpu.rpc import amf, flv
from brpc_tpu.rpc import rtmp_protocol as rtmp
from brpc_tpu.rpc.proto import echo_pb2


class EchoService(rpc.Service):
    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        done()


# ---------------------------------------------------------------------------
# AMF0
# ---------------------------------------------------------------------------

def test_amf0_roundtrip():
    values = ["connect", 1.0, {"app": "live", "flashVer": "v1",
                               "nested": {"a": 2.5, "b": True}},
              None, [1.0, "two", False], "x" * 70000]
    blob = amf.encode_many(*values)
    back = amf.decode_all(blob)
    assert back == values


def test_amf0_ecma_array_and_errors():
    # ECMA array decodes as a dict (count hint + end marker)
    blob = bytes([amf.AMF0_ECMA_ARRAY]) + struct.pack(">I", 1)
    blob += struct.pack(">H", 3) + b"key" + amf.encode(5.0)
    blob += struct.pack(">H", 0) + bytes([amf.AMF0_OBJECT_END])
    v, pos = amf.decode(blob)
    assert v == {"key": 5.0} and pos == len(blob)
    with pytest.raises(amf.AmfError):
        amf.decode(b"\x00\x01")  # truncated number
    with pytest.raises(amf.AmfError):
        amf.decode(b"\x42")  # unknown marker


# ---------------------------------------------------------------------------
# FLV
# ---------------------------------------------------------------------------

def test_flv_roundtrip(tmp_path):
    path = tmp_path / "t.flv"
    with open(path, "wb") as fp:
        w = flv.FlvWriter(fp)
        w.write_metadata(0, amf.encode_many("onMetaData", {"fps": 30.0}))
        w.write_video(10, b"\x17\x00cfg")
        w.write_audio(12, b"\xaf\x00cfg")
        w.write_video(40, b"\x27\x01frame" * 3)
    data = open(path, "rb").read()
    assert flv.probe(data) == {"version": 1, "has_audio": True,
                               "has_video": True}
    tags = list(flv.read_tags(data))
    assert [t[0] for t in tags] == [flv.FLV_TAG_SCRIPT, flv.FLV_TAG_VIDEO,
                                    flv.FLV_TAG_AUDIO, flv.FLV_TAG_VIDEO]
    assert tags[3][1] == 40 and tags[3][2] == b"\x27\x01frame" * 3


# ---------------------------------------------------------------------------
# live client (the public client-session API doubles as the test client)
# ---------------------------------------------------------------------------

def _rtmp_connect(port, app="live"):
    return rtmp.rtmp_client_connect("127.0.0.1", port, app)


@pytest.fixture(scope="module")
def rtmp_server():
    srv = rpc.Server(rpc.ServerOptions(num_threads=4,
                                       rtmp_service=rtmp.RtmpService()))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    yield srv
    srv.stop()


def test_rtmp_publish_play_relay(rtmp_server):
    port = rtmp_server.listen_endpoint.port

    # publisher
    pconn, pub = _rtmp_connect(port)
    pub.send_command("createStream", 2.0, None)
    pub.pump(want=1)
    assert any(c[0] == "_result" for c in pub.commands())
    pub.inbox.clear()
    pub.send_command("publish", 3.0, None, "cam1", "live", stream_id=1)
    pub.pump(want=1)
    codes = [c[3]["code"] for c in pub.commands() if c[0] == "onStatus"]
    assert "NetStream.Publish.Start" in codes

    # publish metadata + an AVC sequence header + a frame BEFORE the
    # player joins (late-joiner priming must replay them)
    meta = amf.encode_many("onMetaData", {"width": 64.0, "height": 48.0})
    pub.send_message(rtmp.MSG_DATA_AMF0, 0, meta, stream_id=1)
    avc_cfg = b"\x17\x00\x00\x00\x00cfg-bytes"
    pub.send_message(rtmp.MSG_VIDEO, 0, avc_cfg, stream_id=1)
    pub.send_message(rtmp.MSG_VIDEO, 33, b"\x27\x01frame-early",
                     stream_id=1)
    time.sleep(0.3)  # let the relay ingest before the player joins

    # player joins late
    vconn, ply = _rtmp_connect(port)
    ply.send_command("createStream", 2.0, None)
    ply.send_command("play", 4.0, None, "cam1", stream_id=1)
    # priming: cached metadata + AVC header arrive before live frames
    assert ply.pump_until(
        lambda s: any(t == rtmp.MSG_DATA_AMF0 for t, _, _ in s.inbox)
        and any(p == avc_cfg for t, _, p in s.inbox
                if t == rtmp.MSG_VIDEO)), ply.inbox
    ply.inbox.clear()

    # live frames flow publisher -> player, timestamps preserved
    frame = b"\x27\x01live-frame-payload" * 40  # multi-chunk (>128B)
    pub.send_message(rtmp.MSG_VIDEO, 1000, frame, stream_id=1)
    pub.send_message(rtmp.MSG_AUDIO, 1010, b"\xaf\x01audio", stream_id=1)
    assert ply.pump_until(
        lambda s: any(t == rtmp.MSG_VIDEO for t, _, _ in s.inbox)
        and any(t == rtmp.MSG_AUDIO for t, _, _ in s.inbox)), ply.inbox
    vids = [(ts, p) for t, ts, p in ply.inbox if t == rtmp.MSG_VIDEO]
    auds = [(ts, p) for t, ts, p in ply.inbox if t == rtmp.MSG_AUDIO]
    assert (1000, frame) in vids
    assert (1010, b"\xaf\x01audio") in auds

    # FLV interop: the relayed payloads mux straight into FLV tags
    blob = flv.file_header() + flv.encode_tag(flv.FLV_TAG_VIDEO, 1000,
                                              frame)
    tags = list(flv.read_tags(blob))
    assert tags == [(flv.FLV_TAG_VIDEO, 1000, frame)]

    pconn.close()
    vconn.close()


def test_rtmp_shares_the_port(rtmp_server):
    """The multi-protocol port keeps answering RPC + HTTP while RTMP
    sessions run (one-port-all-protocols with rtmp enabled)."""
    ep = str(rtmp_server.listen_endpoint)
    ch = rpc.Channel(rpc.ChannelOptions(timeout_ms=5000))
    assert ch.init(ep) == 0
    cntl, resp = ch.call("EchoService.Echo",
                         echo_pb2.EchoRequest(message="beside-rtmp"),
                         echo_pb2.EchoResponse)
    assert not cntl.failed() and resp.message == "beside-rtmp"
    ch.close()

    import http.client

    conn = http.client.HTTPConnection("127.0.0.1",
                                      rtmp_server.listen_endpoint.port,
                                      timeout=5)
    conn.request("POST", "/EchoService/Echo",
                 body=json.dumps({"message": "http-beside-rtmp"}),
                 headers={"Content-Type": "application/json"})
    assert json.loads(conn.getresponse().read())[
        "message"] == "http-beside-rtmp"
    conn.close()


def test_rtmp_bad_second_publisher(rtmp_server):
    port = rtmp_server.listen_endpoint.port
    c1, s1 = _rtmp_connect(port)
    s1.send_command("createStream", 2.0, None)
    s1.send_command("publish", 3.0, None, "solo", "live", stream_id=1)
    assert s1.pump_until(
        lambda s: any(c[0] == "onStatus"
                      and c[3]["code"] == "NetStream.Publish.Start"
                      for c in s.commands()))
    c2, s2 = _rtmp_connect(port)
    s2.send_command("createStream", 2.0, None)
    s2.send_command("publish", 3.0, None, "solo", "live", stream_id=1)
    assert s2.pump_until(
        lambda s: any(c[0] == "onStatus"
                      and c[3]["code"] == "NetStream.Publish.BadName"
                      for c in s.commands()))
    c1.close()
    c2.close()


def test_rtmp_not_claimed_without_service():
    """A server WITHOUT rtmp_service must not claim 0x03 bytes — the
    connection fails as an unknown protocol instead of hanging."""
    srv = rpc.Server(rpc.ServerOptions(num_threads=2))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    try:
        conn = pysocket.create_connection(
            ("127.0.0.1", srv.listen_endpoint.port), timeout=5)
        conn.sendall(bytes([3]) + b"\x00" * rtmp.HANDSHAKE_SIZE)
        conn.settimeout(3)
        try:
            data = conn.recv(64)
        except (TimeoutError, pysocket.timeout):
            data = b"none"
        assert data == b"", "connection should be closed, not answered"
        conn.close()
    finally:
        srv.stop()


def test_rtmp_on_native_port():
    """RTMP rides the native port's raw fallback lane like every other
    non-tpu_std protocol: the C++ runtime owns the socket, the Python
    protocol stack runs the session."""
    from brpc_tpu import native

    if not native.available():
        pytest.skip("native toolchain unavailable")
    srv = rpc.Server(rpc.ServerOptions(num_threads=4,
                                       rtmp_service=rtmp.RtmpService(),
                                       use_native_runtime=True))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    try:
        port = srv.listen_endpoint.port
        pconn, pub = _rtmp_connect(port)
        pub.send_command("createStream", 2.0, None)
        pub.send_command("publish", 3.0, None, "ncam", "live", stream_id=1)
        assert pub.pump_until(
            lambda s: any(c[0] == "onStatus" and
                          c[3]["code"] == "NetStream.Publish.Start"
                          for c in s.commands()))
        vconn, ply = _rtmp_connect(port)
        ply.send_command("createStream", 2.0, None)
        ply.send_command("play", 4.0, None, "ncam", stream_id=1)
        assert ply.pump_until(
            lambda s: any(c[0] == "onStatus" and
                          c[3]["code"] == "NetStream.Play.Start"
                          for c in s.commands()))
        ply.inbox.clear()
        # live semantics: the server announces Play.Start before it
        # subscribes the player, so a frame published in that instant
        # may be missed — keep publishing until one arrives
        frame = (rtmp.MSG_VIDEO, 500, b"\x27\x01native-frame")
        for _ in range(10):
            pub.send_message(frame[0], frame[1], frame[2], stream_id=1)
            if ply.pump_until(lambda s: frame in s.inbox, timeout=1.0):
                break
        assert frame in ply.inbox, ply.inbox
        pconn.close()
        vconn.close()
    finally:
        srv.stop()


def test_chunk_split_reparse_and_abort():
    """Regression: a chunk whose header and body arrive in different TCP
    reads must not double-advance the timestamp on reparse; ABORT must
    discard its csid's partial message (spec 5.4.2)."""
    class _Sink:
        def write(self, buf, id_wait=None):
            return 0

        def failed(self):
            return False

    got = []

    class _Collect(rtmp.RtmpSession):
        def _on_message(self, t, sid, ts, payload):
            if t in (rtmp.MSG_AUDIO, rtmp.MSG_VIDEO, rtmp.MSG_DATA_AMF0):
                got.append((t, ts, payload))
            else:  # control messages (ABORT!) keep their semantics
                super()._on_message(t, sid, ts, payload)

    sess = _Collect(_Sink(), rtmp.RtmpService())
    sess.state = sess.ST_ESTABLISHED

    m0 = (bytes([3]) + (1000).to_bytes(3, "big") + (4).to_bytes(3, "big")
          + bytes([9]) + (1).to_bytes(4, "little") + b"AAAA")
    m1 = (bytes([(1 << 6) | 3]) + (33).to_bytes(3, "big")
          + (4).to_bytes(3, "big") + bytes([9]) + b"BBBB")
    data = bytearray(m0 + m1[:9])  # m1's header arrives; body later
    used = sess.consume(data)
    assert used == len(m0)
    del data[:used]
    data += m1[9:]
    sess.consume(data)
    assert got == [(9, 1000, b"AAAA"), (9, 1033, b"BBBB")], got

    # partial 300-byte message (one 128B chunk lands), then ABORT(csid=3)
    part = (bytes([3]) + (10).to_bytes(3, "big") + (300).to_bytes(3, "big")
            + bytes([9]) + (1).to_bytes(4, "little") + b"x" * 128)
    assert sess.consume(bytearray(part)) == len(part)
    abort = (bytes([2]) + (0).to_bytes(3, "big") + (4).to_bytes(3, "big")
             + bytes([2]) + (0).to_bytes(4, "little")
             + (3).to_bytes(4, "big"))
    sess.consume(bytearray(abort))
    fresh = (bytes([3]) + (2000).to_bytes(3, "big") + (2).to_bytes(3, "big")
             + bytes([9]) + (1).to_bytes(4, "little") + b"ZZ")
    sess.consume(bytearray(fresh))
    assert got[-1] == (9, 2000, b"ZZ")


def test_mpegts_roundtrip():
    """TS muxer/demuxer (the ts.h role): PES packetization with PTS,
    multi-packet payloads, adaptation-field stuffing, PSI tables with
    valid MPEG CRC32 — and the RTMP->FLV->TS pipeline shape."""
    from brpc_tpu.rpc import mpegts

    mux = mpegts.TsMuxer(has_audio=True)
    video1 = b"\x00\x00\x00\x01\x65" + bytes(range(256)) * 3  # ~770B, 5 pkts
    video2 = b"\x00\x00\x00\x01\x41" + b"delta-frame"
    audio1 = b"\xff\xf1AAC-frame-bytes"
    mux.write_video(0, video1, keyframe=True)
    mux.write_audio(23, audio1)
    mux.write_video(33, video2)
    data = mux.packets()
    assert len(data) % mpegts.TS_PACKET == 0
    assert all(data[i] == mpegts.SYNC
               for i in range(0, len(data), mpegts.TS_PACKET))

    got = list(mpegts.demux(data))
    vids = [(pts, es) for pid, pts, es in got if pid == mpegts.PID_VIDEO]
    auds = [(pts, es) for pid, pts, es in got if pid == mpegts.PID_AUDIO]
    assert vids == [(0, video1), (33, video2)]
    assert auds == [(23, audio1)]

    # the PSI tables carry valid MPEG CRCs (a set-top demuxer rejects
    # tables whose CRC fails — CRC over table_id..body must equal the
    # trailing 4 bytes)
    pat = mpegts._pat_table()
    assert mpegts._crc32_mpeg(pat[:-4]) == int.from_bytes(pat[-4:], "big")
    pmt = mpegts._pmt_table(True)
    assert mpegts._crc32_mpeg(pmt[:-4]) == int.from_bytes(pmt[-4:], "big")

    # sync loss raises rather than desyncing silently
    with pytest.raises(ValueError):
        list(mpegts.demux(b"\x00" * mpegts.TS_PACKET))


def test_mpegts_error_contract():
    """PAT layout is the 4-byte program-entry form; oversized audio and
    truncated streams fail with ValueError, never struct/Index errors."""
    from brpc_tpu.rpc import mpegts

    pat = mpegts._pat_table()
    # program entry = program_number(2) + reserved|PMT PID(2)
    assert pat[8:12] == bytes([0, 1]) + bytes([0xF0, 0x00])
    mux = mpegts.TsMuxer()
    with pytest.raises(ValueError, match="audio"):
        mux.write_audio(0, b"a" * 70000)
    mux.write_video(0, b"v" * 70000)  # unbounded video PES is legal
    data = mux.packets()
    with pytest.raises(ValueError, match="truncated"):
        list(mpegts.demux(data[:-7]))


def test_amf0_fuzz_never_crashes():
    """Random bytes through the AMF0 decoder: AmfError or a value, never
    an uncontrolled exception (the command path feeds it wire bytes)."""
    import random

    rng = random.Random(11)
    for _ in range(500):
        blob = bytes(rng.randrange(256) for _ in range(rng.randrange(48)))
        try:
            amf.decode_all(blob)
        except amf.AmfError:
            pass


def test_mpegts_demux_fuzz():
    """Packet-aligned random bytes: ValueError or clean output, never
    Index/struct errors."""
    import random

    from brpc_tpu.rpc import mpegts

    rng = random.Random(13)
    for _ in range(100):
        npkts = rng.randrange(1, 5)
        blob = bytearray(rng.randrange(256)
                         for _ in range(npkts * mpegts.TS_PACKET))
        if rng.random() < 0.7:
            for i in range(npkts):  # valid sync most of the time
                blob[i * mpegts.TS_PACKET] = mpegts.SYNC
        try:
            list(mpegts.demux(bytes(blob)))
        except ValueError:
            pass


def test_mpegts_pcr_and_truncated_pes():
    from brpc_tpu.rpc import mpegts

    # a stream written WITHOUT keyframe flags still carries a PCR
    mux = mpegts.TsMuxer(has_audio=False)
    mux.write_video(0, b"frame-a")
    mux.write_video(33, b"frame-b")
    data = mux.packets()
    pcr_seen = False
    for off in range(0, len(data), mpegts.TS_PACKET):
        pkt = data[off:off + mpegts.TS_PACKET]
        if (pkt[3] >> 4) & 0x2 and pkt[4] > 0 and pkt[5] & 0x10:
            pcr_seen = True
    assert pcr_seen, "PMT advertises PCR but none was emitted"

    # PTS flag set with the PTS bytes missing -> ValueError, not IndexError
    with pytest.raises(ValueError, match="truncated"):
        mpegts._finish_pes(mpegts.PID_VIDEO,
                           b"\x00\x00\x01\xe0\x00\x00\x80\x80\x05")
