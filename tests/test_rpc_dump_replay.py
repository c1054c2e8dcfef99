"""Traffic flight recorder (ISSUE 12): always-on native rpc_dump
capture + the native replay/press lane.

Covers the tentpole surfaces end to end — the sampled dump tap at the
native seams writing butil/recordio.py-compatible files (byte-identical
payloads under the Python reader), the native replay client re-firing
captures through the real client lanes (both interop directions: native
capture -> Python reader, Python rpc_dump files -> native replay, with
a through-the-wire byte-identity check), the /rpc_dump console page
with its one-window 503+Retry-After guard, and the nat_dump_* /
nat_replay_* counter surface. The two-process acceptance test (capture
<-> /rpcz correlation + replay against a restarted server) lives in
tests/test_replay_acceptance.py — it needs exclusive ownership of the
native server slot.
"""
import glob
import http.client
import os
import socket as pysock
import threading
import time

import pytest

from brpc_tpu import rpc
from brpc_tpu.butil import flags as flags_mod
from brpc_tpu.butil.recordio import RecordReader, RecordWriter

native = pytest.importorskip("brpc_tpu.native")
if not native.available():
    pytest.skip("native toolchain unavailable", allow_module_level=True)

from brpc_tpu.rpc.proto import echo_pb2  # noqa: E402

# rpcz collection is off by default; this module reads spans
pytestmark = pytest.mark.usefixtures("rpcz_on")


class EchoService(rpc.Service):
    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        done()


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=15)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read().decode()
    headers = {k.lower(): v for k, v in r.getheaders()}
    conn.close()
    return r.status, body, headers


@pytest.fixture(scope="module")
def server():
    """A native-runtime server carrying every tapped seam: tpu_std echo
    (native handler), native HTTP usercode (/echo), and the native
    redis store."""
    from brpc_tpu.rpc.redis import RedisService

    srv = rpc.Server(rpc.ServerOptions(num_threads=2,
                                       use_native_runtime=True,
                                       native_builtin_echo=True,
                                       redis_service=RedisService(),
                                       native_redis_store=True))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    yield srv, srv.listen_endpoint.port
    if native.dump_running():  # a failed test must not leak the window
        native.dump_stop()
    srv.stop()


def _read_all(capture_dir):
    out = []
    for path in sorted(glob.glob(os.path.join(capture_dir, "*.rio"))):
        with RecordReader(path) as reader:
            out.extend(reader)
    return out


def _wait_written(n, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if native.dump_status()["written"] >= n:
            return
        time.sleep(0.05)


# ---------------------------------------------------------------------------
# tentpole a: capture at the native seams, Python-readable files
# ---------------------------------------------------------------------------

def test_capture_all_seams_python_reader_byte_identity(server, tmp_path):
    """Native-written recordio is readable by the existing Python reader
    with BYTE-IDENTICAL payloads, and every tapped seam (tpu_std, native
    HTTP usercode, redis store) lands records carrying its lane + the
    wire trace context."""
    srv, port = server
    d = str(tmp_path / "cap")
    assert native.dump_start(d, every=1, seed=11) == 0
    assert native.dump_running()
    try:
        sent = []
        h = native.channel_open("127.0.0.1", port)
        for i in range(12):
            payload = (b"dump-%04d|" % i) * (1 + i % 4)
            with native.trace_scope(0xD0D0 + i, 0x7):
                code, body, text = native.channel_call(
                    h, "EchoService", "Echo", payload, timeout_ms=5000)
            assert code == 0, (code, text)
            assert body == payload
            sent.append(payload)
        native.channel_close(h)

        hh = native.channel_open_http("127.0.0.1", port)
        st, body = native.http_call(hh, "POST", "/echo", b"http-dump-body")
        assert st == 200 and body == b"http-dump-body"
        native.channel_close(hh)

        sk = pysock.create_connection(("127.0.0.1", port), timeout=5)
        sk.sendall(b"*3\r\n$3\r\nSET\r\n$2\r\ndk\r\n$2\r\ndv\r\n")
        got = b""
        deadline = time.time() + 3
        while b"+OK" not in got and time.time() < deadline:
            got += sk.recv(4096)
        sk.close()

        _wait_written(len(sent) + 2)
    finally:
        native.dump_stop()

    records = _read_all(d)
    echo = [(m, p) for m, p in records if m["lane"] == "echo"]
    assert [p for _, p in echo] == sent  # byte identity, capture order
    for i, (m, _) in enumerate(echo):
        assert m["service"] == "EchoService" and m["method"] == "Echo"
        assert m["trace_id"] == 0xD0D0 + i  # cross-references /rpcz
        assert m["ts"] > 0
    http_recs = [(m, p) for m, p in records
                 if m["lane"] == "http" and m["method"] == "/echo"]
    assert http_recs and http_recs[0][0]["verb"] == "POST"
    assert http_recs[0][1] == b"http-dump-body"
    redis_recs = [(m, p) for m, p in records if m["lane"] == "redis"]
    assert redis_recs and redis_recs[0][0]["method"] == "SET"
    assert redis_recs[0][1] == b"*3\r\n$3\r\nSET\r\n$2\r\ndk\r\n$2\r\ndv\r\n"

    st = native.dump_status()
    assert st["written"] >= len(sent) + 2
    assert st["drops"] == 0


def test_capture_decimation_is_sampled(server, tmp_path):
    """every=N keeps roughly 1-in-N (seeded, deterministic): the tap is
    cheap enough to leave always-on because most requests never record."""
    srv, port = server
    d = str(tmp_path / "dec")
    assert native.dump_start(d, every=8, seed=3) == 0
    try:
        h = native.channel_open("127.0.0.1", port)
        for i in range(160):
            code, _, _ = native.channel_call(h, "EchoService", "Echo",
                                             b"x", timeout_ms=5000)
            assert code == 0
        native.channel_close(h)
        time.sleep(0.3)
    finally:
        native.dump_stop()
    st = native.dump_status()
    # binomial(160, 1/8): ~20 expected; the band is generous, the point
    # is "decimated, not all and not none"
    assert 2 <= st["samples"] <= 80, st


def test_oversize_payloads_skipped_whole(server, tmp_path):
    """A payload past max_payload is skipped WHOLE and counted — a
    truncated request is not replayable, so truncation is never an
    option."""
    srv, port = server
    d = str(tmp_path / "big")
    assert native.dump_start(d, every=1, seed=5, max_payload=1024) == 0
    try:
        h = native.channel_open("127.0.0.1", port)
        code, _, _ = native.channel_call(h, "EchoService", "Echo",
                                         b"B" * 4096, timeout_ms=5000)
        assert code == 0
        code, _, _ = native.channel_call(h, "EchoService", "Echo",
                                         b"small", timeout_ms=5000)
        assert code == 0
        native.channel_close(h)
        _wait_written(1)
    finally:
        native.dump_stop()
    st = native.dump_status()
    assert st["oversize"] == 1
    payloads = [p for _, p in _read_all(d)]
    assert b"small" in payloads
    assert all(len(p) <= 1024 for p in payloads)


def test_dump_start_contract(server, tmp_path):
    srv, port = server
    d = str(tmp_path / "c")
    assert native.dump_start(d, every=1) == 0
    try:
        assert native.dump_start(d, every=1) == -1  # double start loses
    finally:
        native.dump_stop()
    assert native.dump_stop() == 0  # idempotent
    assert native.dump_start("/proc/no-such-dir/x", every=1) == -2


# ---------------------------------------------------------------------------
# tentpole b: native replay — both interop directions
# ---------------------------------------------------------------------------

def test_python_rpc_dump_files_replay_natively_byte_identical(
        server, tmp_path):
    """Python rpc_dump files are replayable through the native replay
    client — proven through the wire: the server-side tap re-captures
    the replayed traffic and the payloads match the originals byte for
    byte."""
    from brpc_tpu.rpc import rpc_dump

    srv, port = server
    py_dir = str(tmp_path / "pydump")
    flags_mod.set_flag("rpc_dump", "true")
    flags_mod.set_flag("rpc_dump_dir", py_dir)
    flags_mod.set_flag("rpc_dump_sample_every", "1")
    originals = [(b"py-dump-%03d!" % i) * (1 + i % 3) for i in range(9)]
    try:
        for p in originals:
            rpc_dump.maybe_dump_request("EchoService.Echo", p)
    finally:
        rpc_dump.reset_for_tests()
        flags_mod.set_flag("rpc_dump", "false")
    assert glob.glob(py_dir + "/*.rio")

    recap_dir = str(tmp_path / "recap")
    assert native.dump_start(recap_dir, every=1, seed=2) == 0
    try:
        res = native.replay_run("127.0.0.1", port, py_dir, times=1,
                                concurrency=1, timeout_ms=5000)
        _wait_written(len(originals))
    finally:
        native.dump_stop()
    assert res["loaded"] == len(originals)
    assert res["failed"] == 0 and res["ok"] == len(originals)
    recaptured = [p for m, p in _read_all(recap_dir)
                  if m["lane"] == "echo"]
    # concurrency=1 preserves order; identity must hold byte for byte
    assert recaptured == originals


def test_native_capture_replayed_by_python_tool(server, tmp_path):
    """The OTHER interop direction: native-written capture files replay
    through the existing Python tools/rpc_replay.py (its tpu_std
    Channel) with zero failures."""
    import subprocess
    import sys

    srv, port = server
    d = str(tmp_path / "nat4py")
    assert native.dump_start(d, every=1, seed=13) == 0
    try:
        h = native.channel_open("127.0.0.1", port)
        for i in range(6):
            code, _, _ = native.channel_call(
                h, "EchoService", "Echo",
                echo_pb2.EchoRequest(
                    message=f"tool-{i}").SerializeToString(),
                timeout_ms=5000)
            assert code == 0
        native.channel_close(h)
        _wait_written(6)
    finally:
        native.dump_stop()

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "tools/rpc_replay.py", "--dir", d,
         "--server", f"127.0.0.1:{port}", "--timeout-ms", "5000"],
        capture_output=True, text=True, cwd=repo_root, env=env,
        timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "failed=0" in proc.stdout, proc.stdout
    assert "ok=6" in proc.stdout, proc.stdout


def test_native_replay_tool_entrypoint(server, tmp_path):
    """tools/rpc_replay.py --native drives nat_replay_run and reports
    quantiles + failure-derived exit code."""
    import subprocess
    import sys

    srv, port = server
    d = str(tmp_path / "toolnat")
    assert native.dump_start(d, every=1, seed=17) == 0
    try:
        h = native.channel_open("127.0.0.1", port)
        for _ in range(5):
            code, _, _ = native.channel_call(h, "EchoService", "Echo",
                                             b"tool-native",
                                             timeout_ms=5000)
            assert code == 0
        native.channel_close(h)
        _wait_written(5)
    finally:
        native.dump_stop()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "tools/rpc_replay.py", "--dir", d,
         "--server", f"127.0.0.1:{port}", "--native", "--times", "2",
         "--concurrency", "2", "--timeout-ms", "5000"],
        capture_output=True, text=True, cwd=repo_root, env=env,
        timeout=120)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "ok=10 failed=0" in proc.stdout, proc.stdout
    assert "p99=" in proc.stdout


def test_replay_rate_throttle_and_ramp(server, tmp_path):
    """qps throttling paces the fire schedule; a ramp's average rate is
    the mean of its endpoints (the cumulative-count integral)."""
    srv, port = server
    d = str(tmp_path / "rate")
    assert native.dump_start(d, every=1, seed=23) == 0
    try:
        h = native.channel_open("127.0.0.1", port)
        for _ in range(30):
            code, _, _ = native.channel_call(h, "EchoService", "Echo",
                                             b"r", timeout_ms=5000)
            assert code == 0
        native.channel_close(h)
        _wait_written(30)
    finally:
        native.dump_stop()
    res = native.replay_run("127.0.0.1", port, d, times=1, qps=200.0,
                            concurrency=4)
    assert res["failed"] == 0
    # 30 records at 200 qps = ~0.15s of schedule
    assert 0.1 <= res["seconds"] <= 2.0, res
    ramp = native.replay_run("127.0.0.1", port, d, times=2, qps=100.0,
                             qps_to=300.0, concurrency=4)
    assert ramp["failed"] == 0
    # 60 fires at mean 200 qps = ~0.3s
    assert 0.2 <= ramp["seconds"] <= 3.0, ramp
    assert ramp["p99_us"] >= ramp["p50_us"] > 0


def test_replay_empty_capture_raises(server, tmp_path):
    srv, port = server
    with pytest.raises(ValueError):
        native.replay_run("127.0.0.1", port, str(tmp_path / "nothing"))


# ---------------------------------------------------------------------------
# tentpole c: /rpc_dump page + counters
# ---------------------------------------------------------------------------

def test_rpc_dump_page_status(server):
    srv, port = server
    status, body, _ = _get(port, "/rpc_dump")
    assert status == 200
    assert "traffic flight recorder" in body
    assert "native recorder:" in body
    assert "python lane: -rpc_dump=" in body


def test_rpc_dump_page_capture_window_and_503_guard(server, tmp_path):
    """ISSUE 12 satellite: /rpc_dump?seconds=N arms a bounded capture
    window behind the SAME one-window guard as /hotspots/* — the second
    concurrent request gets 503 with Retry-After derived from the
    RUNNING window's remaining time."""
    srv, port = server
    d = str(tmp_path / "page")
    results = {}

    def first():
        results["first"] = _get(port,
                                f"/rpc_dump?seconds=2.5&dir={d}&every=1")

    t = threading.Thread(target=first)
    t.start()
    deadline = time.time() + 5
    while not native.dump_running() and time.time() < deadline:
        time.sleep(0.02)
    assert native.dump_running(), "page window never armed the recorder"
    status, body, headers = _get(port, "/rpc_dump?seconds=0.1")
    t.join()
    assert results["first"][0] == 200
    assert "capture files" in results["first"][1]
    assert status == 503, (status, body)
    assert "busy" in body
    assert 2 <= int(headers["retry-after"]) <= 4
    assert not native.dump_running()
    # the page's own GET rode the native HTTP seam while armed: the
    # window captured its console traffic into the requested dir
    recs = _read_all(d)
    assert any(m["lane"] == "http" for m, _ in recs), recs


def test_dump_replay_counters_in_vars_and_metrics(server):
    """The nat_dump_* / nat_replay_* counters ride /vars and
    /brpc_metrics like every other native counter (the enum drift guard
    in test_native_stats.py covers the full set; here the live values
    prove the earlier tests' traffic landed in them)."""
    srv, port = server
    snap = native.stats_counters()
    for name in ("nat_dump_samples", "nat_dump_records_written",
                 "nat_dump_bytes_written", "nat_dump_drops",
                 "nat_dump_oversize", "nat_dump_rotations",
                 "nat_replay_calls", "nat_replay_errors"):
        assert name in snap, name
    assert snap["nat_dump_samples"] > 0
    assert snap["nat_dump_bytes_written"] > 0
    assert snap["nat_replay_calls"] > 0
    status, body, _ = _get(port, "/vars")
    assert status == 200
    assert "nat_dump_samples" in body
    assert "nat_replay_calls" in body
    status, body, _ = _get(port, "/brpc_metrics")
    assert status == 200
    assert "nat_dump_records_written" in body
    assert "nat_replay_errors" in body


def test_file_rotation_keeps_generations(server, tmp_path):
    """Files rotate past max_file_bytes and only `generations` newest
    stay on disk (the rpcz SpanDB rotation shape)."""
    srv, port = server
    d = str(tmp_path / "rot")
    # ~600B payloads against a 2KB rotation threshold: every few
    # records rolls a generation
    assert native.dump_start(d, every=1, seed=29, max_file_bytes=2048,
                             generations=2) == 0
    try:
        h = native.channel_open("127.0.0.1", port)
        for i in range(30):
            code, _, _ = native.channel_call(h, "EchoService", "Echo",
                                             b"R" * 600, timeout_ms=5000)
            assert code == 0
        native.channel_close(h)
        _wait_written(30)
    finally:
        native.dump_stop()
    st = native.dump_status()
    assert st["rotations"] >= 3, st
    files = sorted(glob.glob(d + "/*.rio"))
    assert 1 <= len(files) <= 2, files  # older generations unlinked
    # the surviving files still parse cleanly
    for m, p in _read_all(d):
        assert m["method"] == "Echo" and p == b"R" * 600
