"""Profiler-suite + SpanDB + console-page tests — the
hotspots_service.h:38-68 surface (heap/growth/contention/tpu), the on-disk
rpcz SpanDB (span.h:206-224), and the /vlog /dir /ids pages.
"""
import http.client
import threading
import time

import pytest

from brpc_tpu import rpc, rpcz
from brpc_tpu.butil import flags as flags_mod
from brpc_tpu.rpc.proto import echo_pb2

# rpcz collection is off by default; this module reads spans
pytestmark = pytest.mark.usefixtures("rpcz_on")


class EchoService(rpc.Service):
    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        response.message = request.message
        done()


@pytest.fixture(scope="module")
def server():
    srv = rpc.Server(rpc.ServerOptions(num_threads=4))
    srv.add_service(EchoService())
    assert srv.start("127.0.0.1:0") == 0
    yield srv
    srv.stop()


@pytest.fixture(scope="module", autouse=True)
def _stop_tracemalloc_after():
    """tracemalloc roughly doubles allocation cost; don't tax the rest of
    the suite once these tests are done."""
    yield
    import tracemalloc

    from brpc_tpu.builtin import profilers

    if tracemalloc.is_tracing():
        tracemalloc.stop()
    with profilers._baseline_lock:
        profilers._growth_baseline = None


def _get(server, path, timeout=15):
    conn = http.client.HTTPConnection(
        "127.0.0.1", server.listen_endpoint.port, timeout=timeout)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read()
    conn.close()
    return r.status, r.getheader("content-type", ""), body


def test_heap_profile(server):
    # First call may just start tracemalloc; second sees our allocation.
    _get(server, "/hotspots/heap")
    keep = [bytearray(256 * 1024) for _ in range(8)]  # noqa: F841
    status, ctype, body = _get(server, "/hotspots/heap")
    text = body.decode()
    assert status == 200
    assert "heap profile" in text and "bytes live" in text
    assert any(line.rsplit(" ", 1)[-1].isdigit()
               for line in text.splitlines() if not line.startswith("#"))


def test_growth_profile(server):
    _get(server, "/hotspots/heap")  # ensures tracing + baseline
    hog = [dict(x=i) for i in range(20000)]  # noqa: F841
    status, _, body = _get(server, "/hotspots/growth")
    assert status == 200
    assert b"growth profile" in body


def test_contention_profile(server):
    # Python-level waits (Condition/Event — what butex, execution queues
    # and bthread ids block on) are what the sampler can see; raw C-level
    # Lock.acquire leaves no Python frame.
    evt = threading.Event()

    def waiter_in_test():
        evt.wait()

    blocked = threading.Thread(target=waiter_in_test, daemon=True)
    blocked.start()
    time.sleep(0.05)
    try:
        status, _, body = _get(server, "/hotspots/contention?seconds=0.3")
        assert status == 200
        text = body.decode()
        assert "contention profile" in text
        assert "waiter_in_test" in text  # the blocked thread was observed
    finally:
        evt.set()
        blocked.join(1)


def test_tpu_trace_endpoint(server):
    status, ctype, body = _get(server, "/hotspots/tpu?seconds=0.2",
                               timeout=60)
    assert status == 200
    # jax profiler produces a zip of xplane files; if the backend refuses
    # (no profiler support), the endpoint explains in text instead.
    assert ctype in ("application/zip", "text/plain")
    if ctype == "application/zip":
        assert body[:2] == b"PK"


def test_pprof_heap(server):
    status, _, body = _get(server, "/pprof/heap")
    assert status == 200 and b"heap profile" in body


def test_span_db_persists_and_rotates(tmp_path):
    flags_mod.set_flag("rpcz_database_dir", str(tmp_path))
    try:
        span = rpcz.Span("server", "T.M", log_id=7)
        span.annotate("stage one")
        span.end(0)
        trace_id = span.trace_id
        rpcz.clear_for_tests()  # drop the memory window
        found = rpcz.find_trace(trace_id)
        assert len(found) == 1
        s = found[0]
        assert s.full_method == "T.M" and s.log_id == 7
        assert s.annotations and s.annotations[0][1] == "stage one"
        # rotation keeps the db bounded across generations
        db = rpcz._get_span_db()
        db._max = 1000  # rotate every 500
        last = None
        for i in range(1200):
            sp = rpcz.Span("client", f"T.M{i}")
            sp.end(0)
            last = sp
        assert rpcz.find_trace(last.trace_id)  # recent span still findable
        db.drain()
        import os

        files = os.listdir(tmp_path)
        assert "rpcz.0.recordio" in files and "rpcz.1.recordio" in files
    finally:
        flags_mod.set_flag("rpcz_database_dir", "")
        rpcz.clear_for_tests()


def test_vlog_page(server):
    import logging

    logging.getLogger("brpc_tpu.test_vlog")  # materialize a logger
    status, _, body = _get(server, "/vlog")
    assert status == 200
    assert b"brpc_tpu.test_vlog" in body
    status, _, body = _get(server, "/vlog?setlevel=brpc_tpu.test_vlog=DEBUG")
    assert status == 200
    assert logging.getLogger("brpc_tpu.test_vlog").level == 10


def test_dir_page(server, tmp_path):
    (tmp_path / "hello.txt").write_bytes(b"console dir page")
    status, _, body = _get(server, f"/dir{tmp_path}")
    assert status == 200 and b"hello.txt" in body
    status, _, body = _get(server, f"/dir{tmp_path}/hello.txt")
    assert status == 200 and body == b"console dir page"
    status, _, _ = _get(server, "/dir/no/such/path/zz")
    assert status == 404


def test_ids_page(server):
    status, _, body = _get(server, "/ids")
    assert status == 200 and b"id_slots:" in body
    from brpc_tpu.bthread import id as bthread_id

    idv = bthread_id.create_ranged(None, None, 3)
    try:
        status, _, body = _get(server, f"/ids?id={idv}")
        assert status == 200
        assert b"range=3" in body and b"destroyed=False" in body
    finally:
        bthread_id.lock(idv)
        bthread_id.unlock_and_destroy(idv)


def test_span_db_merges_across_eviction_boundary(tmp_path):
    """A trace with spans BOTH still in memory and aged to disk returns
    complete (the eviction-boundary merge in find_trace)."""
    flags_mod.set_flag("rpcz_database_dir", str(tmp_path))
    try:
        s1 = rpcz.Span("server", "T.First", log_id=1)
        s1.end(0)
        trace_id = s1.trace_id
        db = rpcz._get_span_db()
        db.drain()
        rpcz.clear_for_tests()  # s1 now lives only on disk
        s2 = rpcz.Span("client", "T.Second", trace_id=trace_id)
        s2.end(0)  # s2 in memory (and queued to disk)
        found = rpcz.find_trace(trace_id)
        methods = sorted(s.full_method for s in found)
        assert methods == ["T.First", "T.Second"]
        # no duplicate for s2 even though it is in memory AND on disk
        assert len(found) == 2
    finally:
        flags_mod.set_flag("rpcz_database_dir", "")
        rpcz.clear_for_tests()


def test_per_second_series_matches_get_value_semantics():
    """PerSecond.series plots the same quantity get_value reports (the
    SUM rate for IntRecorder-backed windows, not the average)."""
    from brpc_tpu import bvar

    rec = bvar.IntRecorder()
    win = bvar.PerSecond(rec, 5)
    try:
        import time as _t

        win._sampler.take_sample()  # baseline
        for v in (10, 20, 30):  # sum=60, num=3, avg=20
            rec.update(v)
        _t.sleep(0.05)
        win._sampler.take_sample()
        series = win.series()
        assert series, "series empty"
        samples = win._sampler.samples_in(5)
        # integrate rate over each pair's own dt: immune to extra samples
        # the background 1Hz collector may inject mid-test
        total = sum(rate * (samples[i + 1][0] - samples[i][0])
                    for i, (_, rate) in enumerate(series))
        # must integrate back to the SUM delta (60), not the avg (20)
        assert total == pytest.approx(60.0, rel=0.05), \
            f"integrated {total}, sum semantics expect 60 (avg would be 20)"
    finally:
        win.destroy()
