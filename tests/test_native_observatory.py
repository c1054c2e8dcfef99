"""Native observatory (ISSUE 9): per-method stats, native sockets in
/connections, and the lock-contention profiler.

Covers the three tentpole surfaces end to end — the per-method
MethodStatus table recorded at the native-handler call sites (/status
rows + labeled /brpc_metrics), the per-NatSocket /connections section
with monotonically-increasing counters under a two-process client, and
/hotspots/contention attributing NatMutex wait time to the contended
site — plus the satellites: the /hotspots/native concurrent-request 503
(Retry-After) and Prometheus label-value escaping for method paths.
"""
import http.client
import socket as pysock
import subprocess
import sys
import threading
import time

import pytest

from brpc_tpu import rpc
from brpc_tpu.rpc.proto import echo_pb2

native = pytest.importorskip("brpc_tpu.native")
if not native.available():
    pytest.skip("native toolchain unavailable", allow_module_level=True)


class EchoService(rpc.Service):
    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        done()


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read().decode()
    headers = {k.lower(): v for k, v in r.getheaders()}
    conn.close()
    return r.status, body, headers


@pytest.fixture(scope="module")
def server():
    """A native-runtime server carrying echo (native handler), HTTP
    (native /echo usercode) and redis (native store) traffic."""
    from brpc_tpu.rpc.redis import RedisService

    srv = rpc.Server(rpc.ServerOptions(num_threads=2,
                                       use_native_runtime=True,
                                       native_builtin_echo=True,
                                       redis_service=RedisService(),
                                       native_redis_store=True))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    port = srv.listen_endpoint.port

    h = native.channel_open("127.0.0.1", port)
    for _ in range(30):
        code, body, text = native.channel_call(h, "EchoService", "Echo",
                                               b"y" * 16)
        assert code == 0, (code, text)
    native.channel_close(h)

    status, body, _ = _get(port, "/echo")
    assert status == 200 and body == "pong"

    sk = pysock.create_connection(("127.0.0.1", port), timeout=5)
    sk.sendall(b"*3\r\n$3\r\nSET\r\n$1\r\nk\r\n$1\r\nv\r\n"
               b"*2\r\n$3\r\nGET\r\n$1\r\nk\r\n")
    got = b""
    deadline = time.time() + 3
    while b"$1\r\nv\r\n" not in got and time.time() < deadline:
        got += sk.recv(4096)
    sk.close()

    # the first page must read the rows this traffic made, not a
    # 0.25s-TTL snapshot that a sampler left by an earlier module in
    # this process took before them
    from brpc_tpu.bvar import native_vars

    native_vars.settle_for_tests()
    yield srv, port
    srv.stop()


# ---------------------------------------------------------------------------
# tentpole a: per-method stats
# ---------------------------------------------------------------------------

def test_method_stats_table(server):
    rows = {(r["lane"], r["method"]): r for r in native.method_stats()}
    echo = rows[("echo", "EchoService.Echo")]
    assert echo["count"] >= 30
    assert echo["errors"] == 0
    assert echo["concurrency"] == 0       # nothing mid-flight now
    assert echo["max_concurrency"] >= 1   # high-water was held
    assert ("http", "/echo") in rows
    assert rows[("http", "/echo")]["count"] >= 1
    assert ("redis", "SET") in rows and ("redis", "GET") in rows
    # per-method latency histogram answers quantiles
    lanes = native.stats_lane_names()
    p50 = native.method_quantile(lanes.index("echo"), "EchoService.Echo",
                                 0.5)
    p99 = native.method_quantile(lanes.index("echo"), "EchoService.Echo",
                                 0.99)
    assert 0 < p50 <= p99


def test_method_quantile_unknown_claims_no_slot(server):
    """A read-only quantile query for a method that never ran must not
    burn one of the never-freed table slots (typos would otherwise
    permanently shrink the table)."""
    lanes = native.stats_lane_names()
    before = {(r["lane"], r["method"]) for r in native.method_stats()}
    assert native.method_quantile(lanes.index("echo"),
                                  "NoSuch.Method.Typo", 0.99) == 0.0
    after = {(r["lane"], r["method"]) for r in native.method_stats()}
    assert after == before
    assert ("echo", "NoSuch.Method.Typo") not in after


def test_method_table_overflow_rows_reserved():
    """Method names arrive off the wire (HTTP paths, redis command
    words): the per-lane "(other)" overflow rows are claimed at load so
    a client spraying unique names can degrade attribution but never
    disable it."""
    rows = {(r["lane"], r["method"]) for r in native.method_stats()}
    for lane in native.stats_lane_names():
        assert (lane, "(other)") in rows


def test_redis_unknown_command_claims_no_slot(server):
    """Raw wire bytes in an unknown redis command word must not claim a
    method-table slot (only store-family commands record rows)."""
    srv, port = server
    sk = pysock.create_connection(("127.0.0.1", port), timeout=5)
    sk.sendall(b"*1\r\n$9\r\nBOGUSCMD1\r\n")
    deadline = time.time() + 3
    got = b""
    while b"\r\n" not in got and time.time() < deadline:
        got += sk.recv(4096)
    sk.close()
    assert ("redis", "BOGUSCMD1") not in {
        (r["lane"], r["method"]) for r in native.method_stats()}


def test_status_page_has_method_rows(server):
    srv, port = server
    status, body, _ = _get(port, "/status")
    assert status == 200
    assert "method EchoService.Echo [echo]:" in body
    assert "method /echo [http]:" in body
    # the row shape: count/qps/errors/concurrency/max/latency
    for line in body.splitlines():
        if line.strip().startswith("method EchoService.Echo"):
            assert "count=" in line and "qps=" in line
            assert "max_concurrency=" in line and "p99=" in line
            break
    else:
        pytest.fail("echo method row missing from /status")


def test_prometheus_method_labels(server):
    """ISSUE 9 drift satellite: the per-method/per-socket/contention vars
    appear in the Prometheus exposition with label values — method paths
    contain '/' and survive verbatim."""
    srv, port = server
    native.mu_contend_selftest(4, 50, 20)  # ensure a contention row
    status, body, _ = _get(port, "/brpc_metrics")
    assert status == 200
    assert 'nat_method_count{lane="echo",method="EchoService.Echo"}' \
        in body
    assert 'nat_method_count{lane="http",method="/echo"}' in body
    assert 'nat_method_latency_p99_us{lane="echo"' in body
    assert "nat_connection_in_bytes{sock_id=" in body
    assert 'nat_lock_contention_waits{rank="4",name="mu.selftest"}' \
        in body
    # full-surface presence + escaping drift coverage lives in
    # tests/test_native_stats.py::test_observatory_vars_in_prometheus_exposition


def test_prometheus_label_value_escaping():
    """Label values with '"', '\\' and newlines are escaped per the
    Prometheus exposition format (method paths may carry quotes)."""
    from brpc_tpu.bvar.variable import PassiveStatus, dump_prometheus

    var = PassiveStatus(
        lambda: {(("method", '/echo"x\\y\nz'),): 7},
        "test_escape_metric")
    try:
        text = dump_prometheus()
        assert ('test_escape_metric{method="/echo\\"x\\\\y\\nz"} 7'
                in text), text
    finally:
        var.hide()


def test_windowed_rate_clamps_negative(monkeypatch):
    """nat_stats_reset mid-window would otherwise publish a large
    negative qps/byte rate for up to one window length."""
    from brpc_tpu.bvar import native_vars, window

    w = object.__new__(native_vars._ClampedPerSecond)
    monkeypatch.setattr(window.PerSecond, "get_value",
                        lambda self: -123.4)
    assert w.get_value() == 0.0


# ---------------------------------------------------------------------------
# tentpole b: native /connections
# ---------------------------------------------------------------------------

def test_connections_page_lists_native_sockets(server):
    srv, port = server
    status, body, _ = _get(port, "/connections")
    assert status == 200
    assert "native sockets:" in body
    assert "unwritten" in body
    # the console request itself rides a native http session
    assert "|http" in body.replace(" ", "")


def test_connections_two_process_monotonic_counters(server):
    """ISSUE 9 satellite: a native client in ANOTHER process shows up in
    /connections as a live socket whose in/out byte counters increase
    monotonically while it keeps calling."""
    srv, port = server
    repo_root = __file__.rsplit("/", 2)[0]
    script = (
        "import sys, time; sys.path.insert(0, '.')\n"
        "from brpc_tpu import native\n"
        f"h = native.channel_open('127.0.0.1', {port})\n"
        "print('up', flush=True)\n"
        "t0 = time.time()\n"
        "while time.time() - t0 < 8.0:\n"
        "    code, body, text = native.channel_call(h, 'EchoService',\n"
        "                                           'Echo', b'z' * 64)\n"
        "    assert code == 0, (code, text)\n"
        "    time.sleep(0.005)\n"
        "native.channel_close(h)\n")
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen([sys.executable, "-c", script],
                            stdout=subprocess.PIPE, text=True,
                            cwd=repo_root, env=env)
    try:
        assert proc.stdout.readline().strip() == "up"
        time.sleep(0.5)

        def snap_rows():
            return {r["sock_id"]: r for r in native.conn_snapshot()
                    if r["server_side"] and r["protocol"] == "tpu_std"}

        first = snap_rows()
        assert first, "no accepted tpu_std socket visible"
        time.sleep(1.5)
        second = snap_rows()
        grew = 0
        for sid, r1 in first.items():
            r2 = second.get(sid)
            if r2 is None:
                continue
            assert r2["in_bytes"] >= r1["in_bytes"]
            assert r2["out_bytes"] >= r1["out_bytes"]
            if r2["in_bytes"] > r1["in_bytes"] and \
                    r2["out_bytes"] > r1["out_bytes"]:
                grew += 1
                assert r2["in_msgs"] > r1["in_msgs"]
                assert r2["out_msgs"] > r1["out_msgs"]
                assert r2["remote"].startswith("127.0.0.1:")
        assert grew >= 1, (first, second)
        # the /connections page renders the same socket with its rates
        status, body, _ = _get(port, "/connections")
        assert status == 200
        sid = next(s for s, r1 in first.items()
                   if second.get(s, r1)["in_bytes"] > r1["in_bytes"])
        assert str(sid) in body
    finally:
        proc.kill()
        proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# tentpole c: contention profiler
# ---------------------------------------------------------------------------

def test_contention_profiler_attributes_wait_to_stack():
    """ISSUE 9 satellite: a contended-NatMutex stress run shows up in the
    sampled report with the wait attributed to the right lock site (the
    synthesized "lock:mu.selftest" leaf of the frame-pointer stack)."""
    native.mu_prof_reset()
    assert native.mu_prof_start(0, 1, 42) == 0
    assert native.mu_prof_running()
    # double-start must lose (the window is a shared resource)
    assert native.mu_prof_start(0, 1, 42) == -1
    waits = native.mu_contend_selftest(4, 200, 30)
    assert native.mu_prof_stop() == 0
    assert waits >= 1
    assert native.mu_prof_samples() >= 1
    collapsed = native.mu_prof_report(collapsed=True)
    assert "lock:mu.selftest" in collapsed
    # wait-us weighted: the selftest stack's weight is positive
    weight = 0
    for line in collapsed.splitlines():
        if "lock:mu.selftest" in line and not line.startswith("#"):
            weight += int(line.rsplit(" ", 1)[1])
    assert weight >= 1
    flat = native.mu_prof_report(collapsed=False)
    assert "lock:mu.selftest" in flat and "waits" in flat
    # always-on per-rank totals carry it too
    ranks = {r["name"]: r for r in native.mu_rank_stats()}
    assert ranks["mu.selftest"]["waits"] >= waits
    assert ranks["mu.selftest"]["wait_us"] >= 1
    native.mu_prof_reset()
    assert native.mu_prof_samples() == 0
    assert all(r["name"] != "mu.selftest" for r in native.mu_rank_stats())


def test_mu_prof_reset_samples_keeps_rank_totals():
    """The per-rank wait totals ride /brpc_metrics as counters: the
    samples-only reset (what debug pages use) must not zero them."""
    native.mu_prof_reset()
    assert native.mu_prof_start(0, 1, 42) == 0
    waits = native.mu_contend_selftest(4, 100, 20)
    assert native.mu_prof_stop() == 0
    assert waits >= 1 and native.mu_prof_samples() >= 1
    native.mu_prof_reset_samples()
    assert native.mu_prof_samples() == 0
    ranks = {r["name"]: r for r in native.mu_rank_stats()}
    assert ranks["mu.selftest"]["waits"] >= waits  # totals survived
    native.mu_prof_reset()  # the full hygiene reset still clears them
    assert all(r["name"] != "mu.selftest" for r in native.mu_rank_stats())


def test_hotspots_contention_merges_native_and_python(server):
    srv, port = server
    native.mu_prof_reset()
    waits = native.mu_contend_selftest(4, 60, 20)
    status, body, _ = _get(port, "/hotspots/contention?seconds=0.3")
    assert status == 200
    assert "# native lock contention (nat_mu_prof" in body
    assert "# python wait-frame profile" in body
    # per-rank totals line the page carries (the selftest ran just above)
    assert "mu.selftest" in body
    # the page request must not reset the monotonic per-rank counters
    ranks = {r["name"]: r for r in native.mu_rank_stats()}
    assert ranks["mu.selftest"]["waits"] >= waits


def test_contention_window_during_traffic(server):
    """The armed window samples real traffic's contended waits (or at
    minimum the deliberately-contended selftest) without disturbing the
    serving path."""
    srv, port = server
    native.mu_prof_reset()
    assert native.mu_prof_start(0, 1, 7) == 0
    h = native.channel_open("127.0.0.1", port)
    for _ in range(50):
        code, _, _ = native.channel_call(h, "EchoService", "Echo", b"q")
        assert code == 0
    native.mu_contend_selftest(4, 300, 30)
    native.channel_close(h)
    assert native.mu_prof_stop() == 0
    rep = native.mu_prof_report(collapsed=True)
    assert "lock:" in rep
    native.mu_prof_reset()


# ---------------------------------------------------------------------------
# satellite: /hotspots/native single-window 503
# ---------------------------------------------------------------------------

def test_hotspots_native_concurrent_request_gets_503(server):
    """Regression (ISSUE 9 satellite): the nat_prof window is a single
    shared resource — a second concurrent /hotspots/native request gets
    503 + Retry-After instead of colliding with (or blocking behind) the
    running window."""
    srv, port = server
    results = {}

    def first():
        results["first"] = _get(port, "/hotspots/native?seconds=2.5")

    t = threading.Thread(target=first)
    t.start()
    # wait until the first request's window is ACTUALLY running (its
    # handler starts the in-process profiler), so the second request
    # deterministically collides with it
    deadline = time.time() + 5
    while not native.prof_running() and time.time() < deadline:
        time.sleep(0.02)
    assert native.prof_running(), "first window never started"
    status, body, headers = _get(port, "/hotspots/native?seconds=0.1")
    t.join()
    assert results["first"][0] == 200
    assert status == 503, (status, body)
    assert "busy" in body
    # Retry-After reflects the RUNNING window's remaining time (~2.5s),
    # not the rejected request's own tiny seconds parameter
    assert 2 <= int(headers["retry-after"]) <= 4


def test_hotspots_contention_concurrent_request_gets_503(server):
    """The nat_mu_prof sample window is shared the same way: a second
    concurrent /hotspots/contention request must 503 instead of having
    its aggregate wiped by the first window's stop + reset_samples."""
    srv, port = server
    results = {}

    def first():
        results["first"] = _get(port, "/hotspots/contention?seconds=2.5")

    t = threading.Thread(target=first)
    t.start()
    deadline = time.time() + 5
    while not native.mu_prof_running() and time.time() < deadline:
        time.sleep(0.02)
    assert native.mu_prof_running(), "first window never started"
    status, body, headers = _get(port, "/hotspots/contention?seconds=0.1")
    t.join()
    assert results["first"][0] == 200
    assert status == 503, (status, body)
    assert "busy" in body
    assert 2 <= int(headers["retry-after"]) <= 4
