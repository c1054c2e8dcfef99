"""Native memory observatory tests (ISSUE 14) — the nat_res ledger,
allocation-site heap/growth profiler, /heap/native + /growth/native
console pages, the /status RSS reconciliation, the /connections memory
column, and the churn-balance contract (every accounted subsystem
returns to its pre-churn live balance after dial/call/close churn and a
shm-worker SIGKILL+recover round)."""
import ctypes
import http.client
import threading
import time

import pytest

from brpc_tpu import rpc

native = pytest.importorskip("brpc_tpu.native")
if not native.available():
    pytest.skip("native toolchain unavailable", allow_module_level=True)

from brpc_tpu.rpc.proto import echo_pb2  # noqa: E402


def _res_rows():
    return {r["subsystem"]: r for r in native.res_stats()}


def _native_echo_traffic(port, n=20, payload=b"m" * 600):
    lib = native.load()
    h = lib.nat_channel_open(b"127.0.0.1", port, 0, 0, 0, 0)
    assert h
    resp = ctypes.c_char_p()
    rlen = ctypes.c_size_t(0)
    err = ctypes.c_char_p()
    try:
        for _ in range(n):
            rc = lib.nat_channel_call(h, b"EchoService", b"Echo", payload,
                                      len(payload), 3000,
                                      ctypes.byref(resp),
                                      ctypes.byref(rlen), ctypes.byref(err))
            assert rc == 0 and rlen.value == len(payload)
            if resp:
                lib.nat_buf_free(resp)
                resp = ctypes.c_char_p()
            if err:
                lib.nat_buf_free(err)
                err = ctypes.c_char_p()
    finally:
        lib.nat_channel_close(h)


# ---------------------------------------------------------------------------
# ledger surface
# ---------------------------------------------------------------------------


def test_ledger_rows_and_names():
    rows = native.res_stats()
    names = native.res_names()
    assert len(rows) == len(names) >= 10
    assert [r["subsystem"] for r in rows] == names
    for want in ("iobuf.block", "sock.slab", "sock.wreq", "srv.pyreq",
                 "sched.stack", "shm.seg", "dump.spill", "prof.cells",
                 "cluster", "stats.cell"):
        assert want in names, names
    for r in rows:
        assert r["hwm_bytes"] >= r["live_bytes"], r
        assert r["cum_allocs"] >= r["cum_frees"] or \
            r["live_objects"] == 0, r


def test_selftest_balances_under_concurrency():
    # 4 churner threads + a concurrent snapshot/report reader; the C
    # side asserts exact live/cum balance on the selftest subsystem
    assert native.res_selftest(4, 300) == 0


def test_accounted_bytes_totals_live():
    rows = native.res_stats()
    total = sum(r["live_bytes"] for r in rows)
    acct = native.res_accounted_bytes()
    # same quantity read through two paths (cells vs the global pairs):
    # equal modulo racing allocations
    assert abs(acct - total) < max(1 << 20, total // 4), (acct, total)


def test_traffic_populates_allocator_subsystems():
    port = native.rpc_server_start(native_echo=True)
    try:
        _native_echo_traffic(port)
        rows = _res_rows()
        assert rows["iobuf.block"]["live_bytes"] > 0
        assert rows["sock.slab"]["live_bytes"] > 0
        assert rows["sched.stack"]["live_bytes"] > 0
        assert rows["cluster"]["cum_allocs"] > 0
    finally:
        native.rpc_server_stop()


# ---------------------------------------------------------------------------
# churn balance — the leak-trend detector in test form
# ---------------------------------------------------------------------------


def test_churn_balance_dial_call_close():
    """Dial/call/close N channels over SIX identical rounds and assert
    the ledger CONVERGES: releases are deferred to dispatcher wakeups
    (the ResourcePool way) and close-sweep fibers run lazily, so any
    two-point comparison races the backlog — but a real leak (say one
    channel per round) grows the live series EVERY round without bound,
    while pools and deferred releases plateau once warmed. The last
    round must not exceed the mid-series plateau."""
    port = native.rpc_server_start(native_echo=True)
    lib = native.load()
    watched = ("cluster", "srv.pyreq", "dump.spill", "iobuf.block",
               "sock.wreq", "sock.slab", "sched.stack")
    try:
        def churn_round():
            hs = []
            for _ in range(6):
                h = lib.nat_channel_open(b"127.0.0.1", port, 0, 0, 0, 0)
                hs.append(h)
            for h in hs:
                resp = ctypes.c_char_p()
                rlen = ctypes.c_size_t(0)
                err = ctypes.c_char_p()
                for _ in range(10):
                    rc = lib.nat_channel_call(
                        h, b"EchoService", b"Echo", b"m" * 600, 600,
                        3000, ctypes.byref(resp), ctypes.byref(rlen),
                        ctypes.byref(err))
                    assert rc == 0
                    if resp:
                        lib.nat_buf_free(resp)
                        resp = ctypes.c_char_p()
                    if err:
                        lib.nat_buf_free(err)
                        err = ctypes.c_char_p()
            for h in hs:
                lib.nat_channel_close(h)

        def drain(deadline_s=30.0):
            # deferred releases complete on dispatcher wakeups over the
            # seconds after the mutual EOFs; poll until the transient
            # subsystems stop moving (two settled polls)
            prev = None
            end = time.time() + deadline_s
            while time.time() < end:
                time.sleep(1.0)
                # unmap the cached fiber stacks: sched.stack then counts
                # the fibers alive now, not the process's concurrency peak
                # (which other tests in this process set)
                lib.nat_sched_trim_stacks()
                rows = _res_rows()
                cur = tuple(rows[s]["live_objects"]
                            for s in ("cluster", "srv.pyreq",
                                      "dump.spill"))
                if cur == prev:
                    return rows
                prev = cur
            lib.nat_sched_trim_stacks()
            return _res_rows()

        series = []
        for _ in range(6):
            churn_round()
            rows = drain()
            series.append({s: (rows[s]["live_objects"],
                               rows[s]["live_bytes"]) for s in watched})
        rows = drain()
        # transient subsystems fully drain: every channel/slab/request
        # the six rounds allocated was released (a leak of even one
        # object per round would leave >= 6 here)
        for sub in ("cluster", "srv.pyreq", "dump.spill"):
            assert rows[sub]["live_objects"] <= 4, (sub, rows[sub],
                                                    series)
        # pooled subsystems plateau: the last round must not exceed the
        # mid-series high-water (pools warm, then stop growing)
        for sub in ("iobuf.block", "sock.wreq", "sock.slab",
                    "sched.stack"):
            plateau = max(series[i][sub][1] for i in (2, 3, 4))
            assert series[-1][sub][1] <= plateau + 8 * 8248, \
                (sub, series)
    finally:
        native.rpc_server_stop()


@pytest.mark.slow
def test_churn_balance_fabric_leases():
    """Tensor-fabric lease churn (ISSUE 15): six rounds of push -> take
    -> (held, then out-of-order released) leases leave the shm.span
    ledger row exactly balanced, and while leases are held the row
    carries exactly the leased payload bytes — the zero-copy structural
    contract (payload bytes accounted once per transfer)."""
    lib = native.load()
    lib.nat_shm_lane_enable(0)
    assert lib.nat_shm_lane_create(1 << 20) == 0
    assert lib.nat_shm_producer_attach(lib.nat_shm_lane_name()) >= 0
    base = _res_rows()["shm.span"]
    payload = b"t" * (64 << 10)
    for _ in range(6):
        held = []
        for i in range(4):
            assert lib.nat_shm_fabric_push(payload, len(payload), i) == 0
            lease = native.fabric_take(2000)
            assert lease is not None
            held.append(lease)
        row = _res_rows()["shm.span"]
        assert row["live_bytes"] - base["live_bytes"] \
            == 4 * len(payload)
        assert row["live_objects"] - base["live_objects"] == 4
        for lease in reversed(held):  # out-of-order vs take order
            lease.release()
        row = _res_rows()["shm.span"]
        assert row["live_bytes"] == base["live_bytes"]
        assert row["live_objects"] == base["live_objects"]
    final = _res_rows()["shm.span"]
    assert final["cum_allocs"] - base["cum_allocs"] == 24
    assert final["cum_frees"] - base["cum_frees"] == 24


def test_churn_balance_shm_worker_sigkill_recover():
    """The shm half of the churn-balance contract: a worker SIGKILLed
    mid-request is recovered (fence probe, arena scrub, slot reap) and
    the transient subsystems return to balance — recovery must not leak
    PyRequests or span contexts, and no new segment may appear."""
    pytest.importorskip("grpc")
    import grpc

    from tests.shm_worker_factory import make_slow

    srv = rpc.Server(rpc.ServerOptions(
        num_threads=2, use_native_runtime=True, py_workers=1,
        py_worker_factory="tests.shm_worker_factory:make_slow"))
    for s in make_slow():
        srv.add_service(s)
    assert srv.start("127.0.0.1:0") == 0
    lib = native.load()
    lib.nat_shm_lane_set_timeout_ms(30000)
    try:
        port = srv.listen_endpoint.port
        chan = grpc.insecure_channel(f"127.0.0.1:{port}")
        call = chan.unary_unary(
            "/EchoService/Echo",
            request_serializer=echo_pb2.EchoRequest.SerializeToString,
            response_deserializer=echo_pb2.EchoResponse.FromString)
        call(echo_pb2.EchoRequest(message="warm"), timeout=20)
        time.sleep(0.2)
        before = _res_rows()
        fut = call.future(echo_pb2.EchoRequest(message="boom"),
                          timeout=25)
        time.sleep(0.15)  # worker consumed it, parked in usercode
        victim = srv._native_mount._shm_workers[0]
        victim.kill()
        victim.wait(timeout=5)
        try:
            fut.result(timeout=20)
        except grpc.RpcError as e:
            assert e.code() == grpc.StatusCode.UNAVAILABLE, e
        # server keeps serving (in-process fallback)
        deadline = time.time() + 15
        ok = 0
        while time.time() < deadline and ok < 3:
            try:
                r = call(echo_pb2.EchoRequest(message="after"),
                         timeout=5)
                ok += 1 if r.message.startswith("after@") else 0
            except Exception:
                time.sleep(0.2)
        assert ok >= 3
        time.sleep(0.5)
        after = _res_rows()
        # recovery leaked nothing transient; the mapped segments are
        # untouched (recovery scrubs arenas in place, never remaps)
        assert after["srv.pyreq"]["live_objects"] <= \
            before["srv.pyreq"]["live_objects"] + 2, (before, after)
        assert after["shm.seg"]["live_bytes"] == \
            before["shm.seg"]["live_bytes"], (before, after)
        chan.close()
    finally:
        lib.nat_shm_lane_set_timeout_ms(2000)
        srv.stop()


# ---------------------------------------------------------------------------
# connection-scale drill (the 20k lane's test-sized twin)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_conn_scale_drill_small():
    """The connection-scale drill at test size: every connection
    accepted AND answered through the accept storm, zero failed RPCs on
    the live subset, per-connection cost recorded from the accounting,
    and no transient-subsystem leak after teardown."""
    from tools.drills import conn_scale_bench

    out = conn_scale_bench(target_conns=240, client_procs=2, idle_s=1.0)
    assert out, "lane disabled?"
    assert out.get("conn_scale_error") is None, out
    assert out["conn_scale_conns"] == 240, out
    assert out["conn_scale_failed"] == 0
    assert out["conn_live_failed"] == 0 and out["conn_live_ok"] > 0
    assert out["conn_per_conn_bytes"] > 0
    assert out["conn_accept_storm_s"] > 0
    assert out["conn_per_conn_fds"] == pytest.approx(1.0, abs=0.3)
    assert out["conn_balance_leaked"] == {}
    assert "sock.slab" in out["conn_mem_by_subsystem"]


# ---------------------------------------------------------------------------
# /heap/native + /growth/native + /status + /connections
# ---------------------------------------------------------------------------


class EchoService(rpc.Service):
    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        done()


@pytest.fixture(scope="module")
def console():
    srv = rpc.Server(rpc.ServerOptions(num_threads=2))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    nport = native.rpc_server_start(native_echo=True)
    yield srv, nport
    native.rpc_server_stop()
    srv.stop()


def _get(srv, path):
    conn = http.client.HTTPConnection(
        "127.0.0.1", srv.listen_endpoint.port, timeout=15)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read().decode()
    headers = dict(r.getheaders())
    conn.close()
    return r.status, body, headers


def test_heap_native_page_end_to_end(console):
    srv, nport = console
    status, body, _ = _get(srv, "/heap/native")  # arms the tracker
    assert status == 200 and "# nat_res heap:" in body
    _native_echo_traffic(nport, n=40, payload=b"z" * 3000)
    status, body, _ = _get(srv, "/heap/native")
    assert status == 200
    # collapsed stacks with the synthesized subsystem leaf
    assert "res:" in body, body[:400]
    status, flat, _ = _get(srv, "/heap/native?flat=1")
    assert status == 200 and "flat live bytes by leaf" in flat


def test_growth_native_page_windows(console):
    srv, nport = console
    _get(srv, "/heap/native")  # ensure armed
    status, body, _ = _get(srv, "/growth/native")
    assert status == 200 and "# nat_res growth:" in body
    # a bounded window: re-baseline, churn while it watches, report
    done = threading.Event()

    def churn():
        _native_echo_traffic(nport, n=30, payload=b"g" * 2000)
        done.set()

    t = threading.Thread(target=churn)
    t.start()
    status, body, _ = _get(srv, "/growth/native?seconds=1.0")
    t.join()
    assert status == 200 and "# nat_res growth:" in body


def test_heap_growth_python_pages(console):
    import tracemalloc

    from brpc_tpu.builtin import profilers

    srv, _ = console
    try:
        status, body, _ = _get(srv, "/heap")
        assert status == 200 and "heap profile" in body
        status, body, _ = _get(srv, "/growth")
        assert status == 200 and "growth profile" in body
    finally:
        # /heap starts tracemalloc; left on, it slows every later Python
        # allocation in this worker (test_stream_throughput_sanity's echo
        # fell from 0.19 to 0.04 GB/s, under its floor)
        tracemalloc.stop()
        with profilers._baseline_lock:
            profilers._growth_baseline = None


def test_heap_native_one_window_503():
    """The shared one-window guard: while one /heap/native or
    /growth/native window runs, the second gets 503 + Retry-After
    derived from the RUNNING window's remaining time."""
    from brpc_tpu.builtin import hotspots

    started = threading.Event()
    release = threading.Event()

    def long_window(_s):
        started.set()
        release.wait(timeout=10)
        return "done\n"

    results = []
    t = threading.Thread(
        target=lambda: results.append(
            hotspots._res_prof_window.run(5.0, long_window)))
    t.start()
    assert started.wait(timeout=5)
    second = hotspots._res_prof_window.run(1.0, lambda s: "nope\n")
    release.set()
    t.join()
    assert second[0] == 503
    assert "busy" in second[2]
    assert int(second[3]["Retry-After"]) >= 1


def test_status_rss_reconciliation_line(console):
    srv, nport = console
    _native_echo_traffic(nport, n=5)
    status, body, _ = _get(srv, "/status")
    assert status == 200
    assert "nat_mem: accounted=" in body, body
    assert "rss_delta_since_native_load=" in body
    assert "nat_mem subsystems:" in body


def test_connections_memory_column(console):
    srv, nport = console
    lib = native.load()
    h = lib.nat_channel_open(b"127.0.0.1", nport, 0, 0, 0, 0)
    try:
        _native_echo_traffic(nport, n=3)
        rows = native.conn_snapshot()
        assert rows, "no native sockets visible"
        assert all("mem_bytes" in r for r in rows)
        status, body, _ = _get(srv, "/connections")
        assert status == 200
        assert "mem_bytes" in body
        assert "native socket buffered memory:" in body
    finally:
        lib.nat_channel_close(h)


def test_metrics_drift_every_nat_mem_row(console):
    """ISSUE 14 drift satellite: every subsystem enum must surface as a
    labeled row in every nat_mem_* Prometheus family — a subsystem
    added to nat_res.h without its ledger rows is drift, not a choice
    (mirrors the counter-enum drift tests)."""
    from brpc_tpu import bvar
    from brpc_tpu.bvar.native_vars import register_native_bvars

    assert register_native_bvars()
    dump = bvar.dump_prometheus()
    names = native.res_names()
    assert len(names) == len(set(names))  # label values must be unique
    for fam in ("nat_mem_live_bytes", "nat_mem_live_objects",
                "nat_mem_cum_allocs", "nat_mem_cum_frees",
                "nat_mem_hwm_bytes"):
        for sub in names:
            row = f'{fam}{{subsystem="{sub}"}}'
            assert row in dump, f"missing {row}"
    # the per-connection memory column rides /brpc_metrics too
    assert "nat_mem_live_bytes" in dump
