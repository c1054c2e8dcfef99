"""Native-runtime observability (nat_stats.cpp): per-thread stat cells,
log2 latency histograms and the bounded span ring, surfaced through the
Python bvar registry and console pages — /vars, /status, /brpc_metrics
(Prometheus) and /rpcz show native traffic beside the Python lanes.

Also the clean-exit regression for the BENCH_r05 rc-139 class: a process
that ran the full native stack must exit 0 (static destructors must not
race detached runtime threads).
"""
import http.client
import socket as pysock
import subprocess
import sys
import time

import pytest

from brpc_tpu import rpc
from brpc_tpu.rpc.proto import echo_pb2

# rpcz collection is off by default; this module reads spans
pytestmark = pytest.mark.usefixtures("rpcz_on")

native = pytest.importorskip("brpc_tpu.native")
if not native.available():
    pytest.skip("native toolchain unavailable", allow_module_level=True)


class EchoService(rpc.Service):
    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        done()


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read().decode()
    conn.close()
    return r.status, body


@pytest.fixture(scope="module")
def server():
    """A native-runtime server carrying echo (native handler), HTTP
    (native /echo usercode) and redis (native store) traffic."""
    from brpc_tpu import rpcz
    from brpc_tpu.rpc.redis import RedisService

    rpcz.clear_for_tests()
    srv = rpc.Server(rpc.ServerOptions(num_threads=2,
                                       use_native_runtime=True,
                                       native_builtin_echo=True,
                                       redis_service=RedisService(),
                                       native_redis_store=True))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    port = srv.listen_endpoint.port

    # echo lane: 40 native framework calls
    h = native.channel_open("127.0.0.1", port)
    for _ in range(40):
        code, body, text = native.channel_call(h, "EchoService", "Echo",
                                               b"x" * 16)
        assert code == 0, (code, text)
    native.channel_close(h)

    # http lane: native-usercode GETs
    for _ in range(5):
        status, body = _get(port, "/echo")
        assert status == 200 and body == "pong"

    # redis lane: native-store SET/GET
    sk = pysock.create_connection(("127.0.0.1", port), timeout=5)
    sk.sendall(b"*3\r\n$3\r\nSET\r\n$1\r\nk\r\n$1\r\nv\r\n"
               b"*2\r\n$3\r\nGET\r\n$1\r\nk\r\n")
    got = b""
    deadline = time.time() + 3
    while b"$1\r\nv\r\n" not in got and time.time() < deadline:
        got += sk.recv(4096)
    sk.close()
    assert b"+OK\r\n" in got and b"$1\r\nv\r\n" in got

    # the first exposition must read the counters this traffic moved, not
    # a 0.25s-TTL snapshot the bvar sampler took before it
    from brpc_tpu.bvar import native_vars

    native_vars.settle_for_tests()
    yield srv, port
    srv.stop()


def test_vars_lists_native_counters(server):
    srv, port = server
    status, body = _get(port, "/vars")
    assert status == 200
    vals = {}
    for line in body.splitlines():
        if line.startswith("nat_") and " : " in line:
            name, _, v = line.partition(" : ")
            try:
                vals[name.strip()] = float(v)
            except ValueError:
                pass
    assert vals["nat_tpu_std_msgs_in"] >= 40
    assert vals["nat_tpu_std_responses_out"] >= 40
    assert vals["nat_http_msgs_in"] >= 5
    assert vals["nat_redis_msgs_in"] >= 2
    assert vals["nat_client_calls"] >= 40
    assert vals["nat_connections_accepted"] >= 3
    # bytes moved: every request carries at least its frame
    assert vals["nat_socket_read_bytes"] > 40 * 12
    assert vals["nat_socket_write_bytes"] > 0
    # percentile vars are exposed and plausible for the echo lane
    assert 0 < vals["nat_echo_latency_p50_us"] <= \
        vals["nat_echo_latency_p99_us"] + 0.1


def test_brpc_metrics_prometheus_exposition(server):
    srv, port = server
    status, body = _get(port, "/brpc_metrics")
    assert status == 200
    metrics = {}
    for line in body.splitlines():
        if line.startswith("nat_") and " " in line:
            name, _, v = line.partition(" ")
            metrics[name] = float(v)
    assert metrics["nat_tpu_std_msgs_in"] >= 40
    assert metrics["nat_redis_responses_out"] >= 2
    assert "# TYPE nat_tpu_std_msgs_in gauge" in body


def test_every_counter_enum_in_prometheus_exposition(server):
    """Drift guard (ISSUE 6): EVERY NatStats counter enum must appear in
    the /brpc_metrics Prometheus exposition — a counter added to the C++
    enum without surfacing here is a silent observability hole (the PR-5
    sextet was the motivating case)."""
    srv, port = server
    status, body = _get(port, "/brpc_metrics")
    assert status == 200
    exposed = {line.partition(" ")[0] for line in body.splitlines()
               if line and not line.startswith("#")}
    missing = [n for n in native.stats_counter_names() if n not in exposed]
    assert not missing, f"counters absent from /brpc_metrics: {missing}"
    # the PR-5 robustness counters specifically (the ISSUE 6 satellite)
    for name in ("nat_faults_injected", "nat_elimit_rejects",
                 "nat_queue_deadline_drops", "nat_retry_budget_exhausted",
                 "nat_breaker_isolations", "nat_breaker_revivals"):
        assert name in exposed, name
    # the flight-recorder counters specifically (the ISSUE 12 satellite:
    # every nat_dump_* / nat_replay_* counter rides the exposition)
    for name in ("nat_dump_samples", "nat_dump_records_written",
                 "nat_dump_bytes_written", "nat_dump_drops",
                 "nat_dump_oversize", "nat_dump_rotations",
                 "nat_replay_calls", "nat_replay_errors"):
        assert name in exposed, name
    # the fan-out cluster counters specifically (the ISSUE 13 satellite:
    # every LB/fan-out/naming-feed counter rides the exposition)
    for name in ("nat_lb_selects", "nat_fanout_calls",
                 "nat_fanout_subcalls", "nat_fanout_subcall_errors",
                 "nat_fanout_fails", "nat_cluster_updates",
                 "nat_cluster_backends_added",
                 "nat_cluster_backends_removed"):
        assert name in exposed, name
    # the elastic-capacity counters specifically (the ISSUE 20 satellite:
    # dynpart resizes + the autoscaler's grow/shrink/blocked verdicts)
    for name in ("nat_dynpart_resizes", "nat_autoscale_grows",
                 "nat_autoscale_shrinks", "nat_autoscale_blocked"):
        assert name in exposed, name


def test_observatory_vars_in_prometheus_exposition(server):
    """Drift guard extension (ISSUE 9): the per-method, per-socket and
    lock-contention vars must appear in the /brpc_metrics exposition as
    labeled rows, with label values escaped (method paths contain '/';
    quotes/backslashes must be escaped per the exposition format)."""
    import re

    from brpc_tpu.bvar.variable import _prom_label_escape

    srv, port = server
    native.mu_contend_selftest(4, 50, 20)  # ensure a contention row
    # a live native cluster (ISSUE 13): its per-backend rows must ride
    # the same exposition under the nat_cluster_backend_* names
    from brpc_tpu.rpc.native_cluster import NativeCluster

    cluster = NativeCluster(lb="rr", name="driftcluster")
    try:
        cluster.update([f"127.0.0.1:{port}"])
        cluster.call("EchoService.Echo", b"drift", timeout_ms=2000)
        # settle the 0.25s-TTL snapshot caches: an exposition rendered
        # within the TTL of an earlier test's dump replays that test's
        # conn/cluster snapshot, which predates the rows asserted below
        from brpc_tpu.bvar import native_vars

        native_vars.settle_for_tests()
        status, body = _get(port, "/brpc_metrics")
        assert status == 200
        for vname in ("nat_method_count", "nat_method_errors",
                      "nat_method_qps", "nat_method_concurrency",
                      "nat_method_max_concurrency",
                      "nat_method_latency_p99_us",
                      "nat_connection_in_bytes",
                      "nat_connection_out_bytes",
                      "nat_connection_unwritten_bytes",
                      "nat_connection_mem_bytes",
                      "nat_mem_live_bytes",
                      "nat_mem_live_objects",
                      "nat_mem_cum_allocs",
                      "nat_mem_cum_frees",
                      "nat_mem_hwm_bytes",
                      "nat_lock_contention_waits",
                      "nat_lock_contention_wait_us",
                      "nat_cluster_backend_selects",
                      "nat_cluster_backend_errors",
                      "nat_cluster_backend_inflight",
                      "nat_cluster_backend_breaker_open",
                      "nat_cluster_backend_lame_duck",
                      "nat_cluster_backend_ema_latency_us"):
            labeled = [ln for ln in body.splitlines()
                       if ln.startswith(vname + "{")]
            assert labeled, f"{vname} has no labeled rows in /brpc_metrics"
        assert ('nat_cluster_backend_selects{cluster="driftcluster",'
                f'backend="127.0.0.1:{port}"}}') in body
    finally:
        cluster.close()
    # (concrete live-traffic row values are asserted in
    # tests/test_native_observatory.py::test_prometheus_method_labels)
    # no label value may contain an UNESCAPED quote: every labeled row
    # must re-parse with the exposition's escaping rules
    lab_re = re.compile(r'^\w+\{((?:\w+="(?:[^"\\]|\\.)*",?)+)\} ')
    for ln in body.splitlines():
        if "{" in ln and ln.startswith("nat_"):
            assert lab_re.match(ln), f"malformed labeled row: {ln!r}"
    # escaping helper round-trips the nasty cases
    assert _prom_label_escape('a"b\\c\nd') == 'a\\"b\\\\c\\nd'


def test_multicore_counters_and_dispatcher_rows(server):
    """ISSUE 7 observability satellite: the dispatcher/scheduler scale-out
    counters ride the same drift-guarded enum (so /brpc_metrics carries
    them via the test above), and /vars carries one row triple per
    dispatcher loop (sockets owned, wakeups, SQPOLL state)."""
    srv, port = server
    snap = native.stats_counters()
    # every new counter exists in the snapshot surface
    for name in ("nat_dispatcher_wakeups", "nat_wsq_steals",
                 "nat_worker_parks", "nat_sqpoll_rings"):
        assert name in snap, name
    # the traffic above came through epoll rounds; workers idled between
    # bursts at least once
    assert snap["nat_dispatcher_wakeups"] > 0
    assert snap["nat_worker_parks"] > 0
    # per-dispatcher rows: pool size matches the export, and the rows'
    # wakeup total covers the counter snapshot taken above (both sides
    # increment at the same site in Dispatcher::run, and the rows are
    # read after the snapshot)
    ndisp = native.dispatcher_count()
    rows = native.dispatcher_stats()
    assert ndisp >= 1 and len(rows) == ndisp
    assert sum(r["wakeups"] for r in rows) >= snap["nat_dispatcher_wakeups"]
    for r in rows:
        assert r["sqpoll"] in (-1, 0, 1)
    # and /vars renders them
    status, body = _get(port, "/vars")
    assert status == 200
    for i in range(ndisp):
        assert f"nat_dispatcher_{i}_sockets" in body
        assert f"nat_dispatcher_{i}_wakeups" in body
        assert f"nat_dispatcher_{i}_sqpoll" in body


def test_status_summarizes_overload_counters(server):
    """/status carries a one-line overload/faults summary the moment any
    of the PR-5 counters moves (snapshot injected: the formatting
    contract, not the traffic)."""
    from brpc_tpu.bvar.native_vars import native_status_lines

    snap = {"nat_socket_read_bytes": 1, "nat_faults_injected": 7,
            "nat_elimit_rejects": 3, "nat_breaker_isolations": 1}
    joined = "\n".join(native_status_lines(snap=snap))
    assert "overload/faults:" in joined
    assert "faults_injected=7" in joined
    assert "elimit_rejects=3" in joined
    assert "breaker_isolations=1" in joined
    # all six keys render (zeros included once the line triggers)
    assert "queue_deadline_drops=0" in joined
    assert "breaker_revivals=0" in joined
    # quiet counters -> no line
    quiet = "\n".join(native_status_lines(
        snap={"nat_socket_read_bytes": 1}))
    assert "overload/faults:" not in quiet


def test_rpcz_shows_native_spans_with_ordered_timeline(server):
    from brpc_tpu import rpcz

    srv, port = server
    status, body = _get(port, "/rpcz")
    assert status == 200
    assert "native:" in body, body[:400]
    native_spans = [s for s in rpcz.recent_spans(4096)
                    if s.remote_side and s.remote_side.startswith("native:")]
    assert native_spans
    lanes_seen = set()
    for s in native_spans:
        lanes_seen.add(s.remote_side.split("/")[0])
        # recv <= parse <= dispatch <= write, carried as start_time plus
        # three timeline annotations ending at end_time
        times = [s.start_time] + [ts for ts, _ in s.annotations]
        assert times == sorted(times), (s.full_method, times)
        assert abs(s.annotations[-1][0] - s.end_time) < 1e-9
        assert s.end_time >= s.start_time
    assert "native:echo" in lanes_seen
    echo_spans = [s for s in native_spans
                  if s.full_method == "EchoService.Echo"]
    assert echo_spans and echo_spans[0].request_size == 16


def test_histogram_percentiles_monotone(server):
    lanes = native.stats_lane_names()
    assert lanes == ["echo", "http", "redis", "grpc", "client", "worker"]
    nonempty = 0
    for idx, lane in enumerate(lanes):
        hist = native.stats_hist(idx)
        if not any(hist):
            continue
        nonempty += 1
        p50 = native.stats_quantile(idx, 0.50)
        p99 = native.stats_quantile(idx, 0.99)
        p999 = native.stats_quantile(idx, 0.999)
        assert 0 < p50 <= p99 <= p999, (lane, p50, p99, p999)
        # the histogram total matches what the quantile walk saw
        assert sum(hist) > 0
    # echo, redis and client lanes definitely carried traffic
    assert nonempty >= 3


def test_status_page_has_native_section(server):
    srv, port = server
    status, body = _get(port, "/status")
    assert status == 200
    assert "native runtime:" in body
    assert "tpu_std: in=" in body
    assert "echo_latency_us: p50=" in body


def test_native_stack_exits_clean():
    """BENCH_r05 rc-139 regression: spin up the full native stack (server,
    scheduler workers, dispatchers, client channel, py lane), do work,
    stop, and exit — the process must not SIGSEGV in static destructors
    racing detached runtime threads."""
    script = (
        "import sys; sys.path.insert(0, '.')\n"
        "from brpc_tpu import rpc, native\n"
        "from brpc_tpu.rpc.proto import echo_pb2\n"
        "class E(rpc.Service):\n"
        "    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)\n"
        "    def Echo(self, cntl, request, response, done):\n"
        "        response.message = request.message\n"
        "        done()\n"
        "srv = rpc.Server(rpc.ServerOptions(num_threads=2,\n"
        "                 use_native_runtime=True,\n"
        "                 native_builtin_echo=True))\n"
        "srv.add_service(E())\n"
        "assert srv.start('127.0.0.1:0') == 0\n"
        "port = srv.listen_endpoint.port\n"
        "h = native.channel_open('127.0.0.1', port)\n"
        "for _ in range(100):\n"
        "    code, body, text = native.channel_call(h, 'EchoService',\n"
        "                                           'Echo', b'z' * 16)\n"
        "    assert code == 0, (code, text)\n"
        "native.channel_close(h)\n"
        "srv.stop()\n"
        "print('clean', flush=True)\n")
    import os

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=180,
                         cwd=repo_root, env=env)
    assert res.returncode == 0, (res.returncode, res.stderr[-2000:])
    assert "clean" in res.stdout


def test_lockrank_names_track_header():
    """Drift guard: every kLockRank constant in nat_lockrank.h (and
    every raw-rank `// N: name` comment row) must resolve through
    nat_mu_rank_name — the hand-mirrored switch in nat_prof.cpp is the
    only thing turning /hotspots/contention ranks into names, and a
    rank added to the header without a row would silently report as
    "rank<N>"."""
    import os
    import re

    hdr = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native", "src", "nat_lockrank.h")
    with open(hdr) as f:
        text = f.read()
    # same shape the natcheck lockorder pass parses
    ranks = {int(v): k for k, v in
             re.findall(r"\b(kLockRank\w+)\s*=\s*(\d+)", text)}
    assert len(ranks) >= 30, "lockrank header parse came up short"
    missing = [f"{name}={rank}" for rank, name in sorted(ranks.items())
               if native.mu_rank_name(rank) is None]
    assert not missing, (
        "nat_lockrank.h ranks without a mu_rank_name row "
        f"(add them to the switch in nat_prof.cpp): {missing}")
