"""RTMP client + digest handshake + cross-server relay pull.

Counterpart of the reference's RtmpClient/RtmpClientStream surface
(rtmp.h:723,797) and the digest handshake of policy/rtmp_protocol.cpp:149.
The relay test is the VERDICT r3 #10 shape: publish into server A (its
own process), server B's CLIENT pulls from A, a player reads from B —
the chunk layer exercised by a second implementation end to end.
"""
import subprocess
import sys
import threading
import time

import pytest

from brpc_tpu import rpc
from brpc_tpu.rpc import rtmp_client as rc
from brpc_tpu.rpc import rtmp_protocol as rp


def _start_rtmp_server():
    svc = rp.RtmpService()
    srv = rpc.Server(rpc.ServerOptions(num_threads=4, rtmp_service=svc))
    assert srv.start("127.0.0.1:0") == 0
    return srv, svc


def test_digest_primitives_roundtrip():
    c1, dig = rc.make_digest_c1()
    assert rc.find_digest(c1, rc.FP_KEY) is not None
    assert rc.find_digest(c1, rc.FMS_KEY) is None  # wrong key rejects
    s1, s1_dig = rc.make_digest_s1(0)
    assert rc.find_digest(s1, rc.FMS_KEY) is not None
    s2 = rc.make_chained_reply(dig, rc.FMS_KEY_FULL)
    assert rc.verify_chained_reply(s2, dig, rc.FMS_KEY_FULL)
    assert not rc.verify_chained_reply(s2, s1_dig, rc.FMS_KEY_FULL)


def test_digest_handshake_against_own_server():
    srv, svc = _start_rtmp_server()
    try:
        port = srv.listen_endpoint.port
        client = rc.RtmpClient("127.0.0.1", port, use_digest=True)
        client.connect()
        assert client.digest_mode  # the server answered with FMS digests
        stream = client.create_stream()
        stream.publish("digests")
        assert "digests" in svc.stream_names()
        client.close()
    finally:
        srv.stop()


def test_simple_handshake_still_accepted():
    srv, svc = _start_rtmp_server()
    try:
        port = srv.listen_endpoint.port
        client = rc.RtmpClient("127.0.0.1", port, use_digest=False)
        client.connect()
        assert not client.digest_mode
        stream = client.create_stream()
        stream.publish("plain")
        assert "plain" in svc.stream_names()
        client.close()
    finally:
        srv.stop()


def test_client_publish_then_play_roundtrip():
    """Both halves of the client against our server: publish media on one
    connection, play it back on another."""
    srv, svc = _start_rtmp_server()
    try:
        port = srv.listen_endpoint.port
        pub = rc.RtmpClient("127.0.0.1", port).connect()
        pstream = pub.create_stream().publish("cam0")
        pstream.send_metadata({"width": 640.0, "height": 480.0})
        pstream.send_video(b"\x17\x00AVCSEQ", 0)  # AVC seq header shape

        got = []
        done = threading.Event()

        def on_media(msg_type, ts, payload):
            got.append((msg_type, ts, payload))
            if len(got) >= 4:
                done.set()

        player = rc.RtmpClient("127.0.0.1", port).connect()
        player.start_reader()
        player.create_stream().play("cam0", on_media)
        # late joiner gets cached metadata + AVC header, then live frames
        pstream.send_video(b"\x27frame1", 40)
        pstream.send_audio(b"\xafaudio1", 40)
        assert done.wait(10), f"only received {got}"
        types = [t for t, _, _ in got]
        assert rp.MSG_DATA_AMF0 in types  # metadata replayed
        assert any(p == b"\x27frame1" for _, _, p in got)
        assert any(p == b"\xafaudio1" for _, _, p in got)
        player.close()
        pub.close()
    finally:
        srv.stop()


def test_two_process_relay_pull():
    """VERDICT r3 #10: publish into A (separate process), B pulls from A
    via its RtmpClient, a player reads from B."""
    script = (
        "import sys; sys.path.insert(0, '.')\n"
        "from brpc_tpu import rpc\n"
        "from brpc_tpu.rpc import rtmp_protocol as rp\n"
        "svc = rp.RtmpService()\n"
        "srv = rpc.Server(rpc.ServerOptions(num_threads=4,"
        " rtmp_service=svc))\n"
        "assert srv.start('127.0.0.1:0') == 0\n"
        "print(srv.listen_endpoint.port, flush=True)\n"
        "sys.stdin.readline()\n")
    proc = subprocess.Popen([sys.executable, "-c", script],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True, cwd="/root/repo")
    srv_b = None
    clients = []
    try:
        port_a = int(proc.stdout.readline())
        # publisher pushes into A
        pub = rc.RtmpClient("127.0.0.1", port_a).connect()
        clients.append(pub)
        pstream = pub.create_stream().publish("live0")
        pstream.send_metadata({"relay": 1.0})
        pstream.send_video(b"\x17\x00SEQ", 0)

        # server B (this process) pulls live0 from A
        srv_b, svc_b = _start_rtmp_server()
        puller = rc.pull_into_service(svc_b, "live0", "127.0.0.1", port_a)
        clients.append(puller)

        # player reads from B
        got = []
        done = threading.Event()

        def on_media(msg_type, ts, payload):
            got.append((msg_type, ts, payload))
            if any(p == b"\x27relayed" for _, _, p in got):
                done.set()

        player = rc.RtmpClient("127.0.0.1",
                               srv_b.listen_endpoint.port).connect()
        clients.append(player)
        player.start_reader()
        player.create_stream().play("live0", on_media)

        # live media published into A must reach B's player; keep pushing
        # (the pull may still be settling when the first frame goes out)
        deadline = time.monotonic() + 15
        while not done.is_set() and time.monotonic() < deadline:
            pstream.send_video(b"\x27relayed", 80)
            done.wait(0.25)
        assert done.is_set(), f"relay delivered only {got}"
    finally:
        for c in clients:
            try:
                c.close()
            except Exception:
                pass
        if srv_b is not None:
            srv_b.stop()
        proc.stdin.close()
        proc.wait(timeout=10)
