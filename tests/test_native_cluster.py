"""Native fan-out cluster (ISSUE 13): DoublyBufferedData LB core, the
combo-channel verbs at C++ speed, and the failure-handling contracts.

Covers the satellite checklist: the consistent-hash bounded-remap
property (~K/N keys move on a single-backend removal), partition merge
with fail_limit under injected faults, naming observer add/remove racing
in-flight selects, per-sub-call trace parenting, the Python combo
channels' native=True fast paths, multi-port servers, and the
zero-failed-RPC churn acceptance drill (slow-marked)."""
import collections
import os
import signal
import threading
import time

import pytest

from brpc_tpu import rpc  # noqa: F401 (protocol registry init)
from brpc_tpu.rpc.proto import echo_pb2

native = pytest.importorskip("brpc_tpu.native")
if not native.available():
    pytest.skip("native toolchain unavailable", allow_module_level=True)

from brpc_tpu.rpc.native_cluster import NativeCluster  # noqa: E402


@pytest.fixture()
def swarm_server():
    """One native echo server on 8 ports (the multi-port swarm seam)."""
    port = native.rpc_server_start(native_echo=True)
    ports = [port]
    for _ in range(7):
        ports.append(native.rpc_server_add_port())
    yield ports
    native.rpc_server_stop()


def _mk_cluster(ports, lb="rr", **kw):
    c = NativeCluster(lb=lb, connect_timeout_ms=1000,
                      health_check_ms=100, **kw)
    c.update([f"127.0.0.1:{p}" for p in ports])
    return c


# ---------------------------------------------------------------------------
# verbs
# ---------------------------------------------------------------------------

def test_selective_call_round_robins(swarm_server):
    with _mk_cluster(swarm_server) as c:
        assert c.backend_count() == len(swarm_server)
        for i in range(24):
            rc, body, err = c.call("EchoService.Echo", b"sel-%d" % i,
                                   timeout_ms=2000)
            assert rc == 0, err
            assert body == b"sel-%d" % i
        rows = c.stats()
        # rr spread: every backend served some of the 24 calls
        assert len(rows) == len(swarm_server)
        assert all(r["selects"] >= 1 for r in rows)
        assert all(r["errors"] == 0 for r in rows)


def test_parallel_call_merges_all_backends(swarm_server):
    with _mk_cluster(swarm_server) as c:
        rc, body, err, failed = c.parallel_call("EchoService.Echo",
                                                b"fan!", timeout_ms=3000)
        assert rc == 0, err
        assert failed == 0
        # native merge = concatenation in backend order
        assert body == b"fan!" * len(swarm_server)


def test_parallel_merge_is_protobuf_mergefrom(swarm_server):
    """Concatenated serialized protobufs parse as MergeFrom — the
    native merge IS the default ResponseMerger for proto payloads."""
    with _mk_cluster(swarm_server) as c:
        payload = echo_pb2.EchoRequest(message="pb-merge")
        rc, body, err, failed = c.parallel_call(
            "EchoService.Echo", payload.SerializeToString(),
            timeout_ms=3000)
        assert rc == 0 and failed == 0
        merged = echo_pb2.EchoResponse()
        merged.MergeFromString(body)
        assert merged.message == "pb-merge"


def test_partition_call_groups_by_tag(swarm_server):
    ports = swarm_server[:4]
    with NativeCluster(lb="rr") as c:
        c.update([(f"127.0.0.1:{p}", 1, f"{i % 2}/2")
                  for i, p in enumerate(ports)])
        rc, body, err, failed = c.partition_call(
            "EchoService.Echo", b"P", timeout_ms=3000, partitions=2)
        assert rc == 0, err
        assert failed == 0
        assert body == b"PP"  # one response per partition, merged


def test_partition_missing_partition_counts_failed(swarm_server):
    with NativeCluster(lb="rr") as c:
        # only partition 0 of a declared 2-way scheme has members
        c.update([(f"127.0.0.1:{swarm_server[0]}", 1, "0/2")])
        rc, body, err, failed = c.partition_call(
            "EchoService.Echo", b"x", timeout_ms=2000, partitions=2,
            fail_limit=2)
        assert rc == 0 and failed == 1  # under the limit: succeeds
        rc, _, err, failed = c.partition_call(
            "EchoService.Echo", b"x", timeout_ms=2000, partitions=2,
            fail_limit=1)
        assert rc != 0 and failed == 1  # at the limit: fails loudly
        assert "sub calls failed" in err


def test_partition_call_absent_scheme_fails_fast(swarm_server):
    """A partitions count naming a scheme with NO members must answer
    promptly (review finding: an empty fan once had nothing to wake the
    completion butex — a caller-thread hang with no timeout)."""
    with NativeCluster(lb="rr") as c:
        c.update([(f"127.0.0.1:{p}", 1, f"{i % 2}/2")
                  for i, p in enumerate(swarm_server[:2])])
        t0 = time.time()
        rc, _, err, failed = c.partition_call(
            "EchoService.Echo", b"x", timeout_ms=2000, partitions=3)
        assert rc != 0 and "partition" in err
        assert time.time() - t0 < 1.0  # failed fast, no wedge


def test_wrr_large_weights_never_starve(swarm_server):
    """Summed weights past the schedule cap rescale instead of
    truncating (review finding: a truncated schedule starved any
    backend whose first slot lay past the cap)."""
    ports = swarm_server[:2]
    with NativeCluster(lb="wrr") as c:
        c.update([(f"127.0.0.1:{ports[0]}", 5000, ""),
                  (f"127.0.0.1:{ports[1]}", 1, "")])
        picks = collections.Counter(
            c.select_debug(i) for i in range(2000))
        assert picks[f"127.0.0.1:{ports[1]}"] >= 1  # the tail still rides


def test_two_tuple_node_keeps_empty_tag(swarm_server):
    """(endpoint, weight) 2-tuples must not inherit a bogus tag (review
    finding: naive list padding handed them tag='1')."""
    with NativeCluster(lb="rr") as c:
        c.update([(f"127.0.0.1:{swarm_server[0]}", 5)])
        row = c.stats()[0]
        assert row["weight"] == 5
        assert row["tag"] == ""


def test_parallel_fail_limit_with_dead_backends(swarm_server):
    """fail_limit semantics with deterministic failures: dead ports
    fail their sub-calls, live ones merge."""
    live = swarm_server[:2]
    dead = [1, 2]  # nothing listens on ports 1/2 (reserved range)
    with NativeCluster(lb="rr", connect_timeout_ms=300) as c:
        c.update([f"127.0.0.1:{p}" for p in live + dead])
        rc, body, err, failed = c.parallel_call(
            "EchoService.Echo", b"F", timeout_ms=3000, fail_limit=3)
        assert rc == 0 and failed == 2
        assert body == b"FF"  # the two live responses merged
        rc, _, err, failed = c.parallel_call(
            "EchoService.Echo", b"F", timeout_ms=3000, fail_limit=2)
        assert rc != 0 and failed == 2
        assert "2/4 sub calls failed" in err


def test_partition_fail_limit_under_injected_faults(swarm_server):
    """NAT_FAULT seeds (the PR-5 table) against the fan-out merge: with
    write errors injected, every partition_call outcome must satisfy
    the fail_limit contract — rc==0 iff failed < limit — and recovery
    after clearing the table is complete."""
    ports = swarm_server[:4]
    with NativeCluster(lb="rr") as c:
        c.update([(f"127.0.0.1:{p}", 1, f"{i}/4")
                  for i, p in enumerate(ports)])
        native.fault_configure("seed=42;write:err=EPIPE:p=0.25")
        try:
            saw_failure = False
            for _ in range(40):
                rc, body, err, failed = c.partition_call(
                    "EchoService.Echo", b"f", timeout_ms=2000,
                    partitions=4, fail_limit=2)
                if rc == 0:
                    assert failed < 2
                    assert body == b"f" * (4 - failed)
                else:
                    assert failed >= 2
                    saw_failure = True
            assert saw_failure  # the seed actually injected
        finally:
            native.fault_configure(os.environ.get("NAT_FAULT", ""))
        # recovery: with the table cleared the scheme is whole again
        deadline = time.time() + 10
        while time.time() < deadline:
            rc, body, _, failed = c.partition_call(
                "EchoService.Echo", b"r", timeout_ms=2000, partitions=4)
            if rc == 0 and failed == 0:
                break
            time.sleep(0.1)  # cool-downs from the fault burst lapse
        assert rc == 0 and failed == 0 and body == b"rrrr"


# ---------------------------------------------------------------------------
# LB policies
# ---------------------------------------------------------------------------

def test_wrr_respects_weights(swarm_server):
    ports = swarm_server[:2]
    with NativeCluster(lb="wrr") as c:
        c.update([(f"127.0.0.1:{ports[0]}", 1, ""),
                  (f"127.0.0.1:{ports[1]}", 3, "")])
        picks = collections.Counter(
            c.select_debug(i) for i in range(400))
        heavy = picks[f"127.0.0.1:{ports[1]}"]
        light = picks[f"127.0.0.1:{ports[0]}"]
        assert light > 0 and heavy > 0
        assert 2.0 <= heavy / light <= 4.5  # ~3:1 smooth-wrr split


def test_consistent_hash_routes_by_request_code(swarm_server):
    with _mk_cluster(swarm_server, lb="c_hash") as c:
        # the same request code always lands on the same backend
        for code in (7, 99, 12345):
            first = c.select_debug(code)
            assert first is not None
            assert all(c.select_debug(code) == first for _ in range(10))


def test_consistent_hash_bounded_remap(swarm_server):
    """The bounded-remap property: removing ONE backend from N moves
    only the keys whose ring arc it owned (~K/N), everything else stays
    put. A naive mod-N hash would move ~K*(N-1)/N."""
    eps = [f"127.0.0.1:{40000 + i}" for i in range(20)]  # never dialed
    K = 1500
    with NativeCluster(lb="c_hash") as c:
        c.update(eps)
        before = {code: c.select_debug(code) for code in range(K)}
        victim = eps[7]
        c.update([e for e in eps if e != victim])
        moved = 0
        for code in range(K):
            after = c.select_debug(code)
            assert after != victim
            if before[code] != victim and after != before[code]:
                moved += 1
        # expected K/N = 75; allow generous slack for arc adjacency
        assert moved <= 3 * K // len(eps), \
            f"{moved} of {K} keys moved on one removal"


def test_la_policy_prefers_fast_backends(swarm_server):
    with _mk_cluster(swarm_server[:3], lb="la") as c:
        for i in range(30):
            rc, _, err = c.call("EchoService.Echo", b"la", timeout_ms=2000)
            assert rc == 0, err
        rows = c.stats()
        assert sum(r["selects"] for r in rows) >= 30
        assert all(r["ema_latency_us"] > 0 for r in rows)


# ---------------------------------------------------------------------------
# naming feed + membership races
# ---------------------------------------------------------------------------

def test_naming_update_add_remove(swarm_server):
    with NativeCluster(lb="rr") as c:
        c.update([f"127.0.0.1:{swarm_server[0]}"])
        assert c.backend_count() == 1
        c.update([f"127.0.0.1:{p}" for p in swarm_server])
        assert c.backend_count() == len(swarm_server)
        c.update([f"127.0.0.1:{p}" for p in swarm_server[:2]])
        assert c.backend_count() == 2
        rc, body, err = c.call("EchoService.Echo", b"after-shrink",
                               timeout_ms=2000)
        assert rc == 0, err


def test_naming_watcher_drives_cluster(swarm_server, tmp_path):
    nf = tmp_path / "swarm.ns"
    nf.write_text("".join(f"127.0.0.1:{p}\n" for p in swarm_server[:3]))
    with NativeCluster(lb="rr") as c:
        c.watch(f"file://{nf}")
        assert c.backend_count() == 3  # first resolution is synchronous
        rc, _, err = c.call("EchoService.Echo", b"ns", timeout_ms=2000)
        assert rc == 0, err
        # live add: the file naming service re-resolves on its interval
        nf.write_text("".join(f"127.0.0.1:{p}\n" for p in swarm_server))
        deadline = time.time() + 10
        while time.time() < deadline and \
                c.backend_count() != len(swarm_server):
            time.sleep(0.2)
        assert c.backend_count() == len(swarm_server)


def test_membership_updates_race_inflight_selects(swarm_server):
    """The DoublyBufferedData contract under fire: naming add/remove
    churns the server list from one thread while selects + calls run
    hot from others — no failed call may escape, every pick lands on a
    then-live version."""
    all_eps = [f"127.0.0.1:{p}" for p in swarm_server]
    with NativeCluster(lb="rr") as c:
        c.update(all_eps)
        stop = threading.Event()
        failures = []

        def caller():
            i = 0
            while not stop.is_set():
                rc, _, err = c.call("EchoService.Echo", b"race",
                                    timeout_ms=3000, max_retry=4)
                if rc != 0:
                    failures.append((rc, err))
                i += 1

        def selector():
            while not stop.is_set():
                ep = c.select_debug(0)
                assert ep is None or ep in all_eps

        threads = [threading.Thread(target=caller) for _ in range(2)]
        threads += [threading.Thread(target=selector)]
        for t in threads:
            t.start()
        # 60 membership flaps while the flood runs
        for i in range(60):
            keep = 2 + (i % (len(all_eps) - 2))
            c.update(all_eps[:keep])
            time.sleep(0.005)
        c.update(all_eps)
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not failures, failures[:5]


# ---------------------------------------------------------------------------
# health: breaker / cool-down / multi-port lifecycle
# ---------------------------------------------------------------------------

def test_dead_backend_cools_down_and_recovers(swarm_server):
    """Transport failures cool a dead backend out of the candidate set
    (the churn fix); selection keeps succeeding on the live peers."""
    live = [f"127.0.0.1:{p}" for p in swarm_server[:2]]
    dead = "127.0.0.1:1"
    with NativeCluster(lb="rr", connect_timeout_ms=200) as c:
        c.update(live + [dead])
        for _ in range(30):
            rc, _, err = c.call("EchoService.Echo", b"x", timeout_ms=3000,
                                max_retry=4)
            assert rc == 0, err
        dead_row = [r for r in c.stats() if r["endpoint"] == dead][0]
        # the cool-down capped the dead peer's attempts far below the
        # 30-call flood's rr share of repeated failures
        assert dead_row["errors"] <= 10


def test_server_remove_port_refuses_new_connects(swarm_server):
    extra = native.rpc_server_add_port()
    with NativeCluster(lb="rr", connect_timeout_ms=300) as c:
        c.update([f"127.0.0.1:{extra}"])
        rc, _, err = c.call("EchoService.Echo", b"pre", timeout_ms=2000)
        assert rc == 0, err
    assert native.rpc_server_remove_port(extra) == 0
    assert native.rpc_server_remove_port(extra) == -1  # idempotent-ish
    with NativeCluster(lb="rr", connect_timeout_ms=300) as c2:
        c2.update([f"127.0.0.1:{extra}"])
        rc, _, _ = c2.call("EchoService.Echo", b"post", timeout_ms=800,
                           max_retry=0)
        assert rc != 0  # the listener is gone


# ---------------------------------------------------------------------------
# tracing: per-sub-call spans parent under one trace
# ---------------------------------------------------------------------------

def test_parallel_subcall_spans_share_one_trace(swarm_server):
    native.stats_enable_spans(1)
    native.stats_drain_spans()  # flush older spans
    trace_id = 0x1234567
    try:
        with _mk_cluster(swarm_server[:3]) as c:
            with native.trace_scope(trace_id, 0x42):
                rc, _, err, failed = c.parallel_call(
                    "EchoService.Echo", b"span", timeout_ms=3000)
        assert rc == 0 and failed == 0, err
        deadline = time.time() + 5
        spans = []
        while time.time() < deadline:
            spans += native.stats_drain_spans()
            verb = [s for s in spans if s["trace_id"] == trace_id
                    and s["method"].startswith("parallel*")]
            subs = [s for s in spans if s["trace_id"] == trace_id
                    and s["method"] == "EchoService.Echo"
                    and s["lane"] == "client"]
            if verb and len(subs) >= 3:
                break
            time.sleep(0.05)
        assert verb, "fan-out verb span missing"
        assert len(subs) >= 3, f"only {len(subs)} sub-call spans"
        # every sub-call span nests under the verb's span
        assert all(s["parent_span_id"] == verb[0]["span_id"]
                   for s in subs)
        assert verb[0]["parent_span_id"] == 0x42
    finally:
        native.stats_enable_spans(0)


# ---------------------------------------------------------------------------
# the Python combo channels' native fast paths
# ---------------------------------------------------------------------------

def test_parallel_channel_native_fast_path(swarm_server):
    from brpc_tpu.rpc.combo_channels import ParallelChannel

    pch = ParallelChannel(native=True)
    listurl = "list://" + ",".join(f"127.0.0.1:{p}"
                                   for p in swarm_server[:4])
    assert pch.init(listurl) == 0
    try:
        assert pch.channel_count == 4
        cntl = rpc.Controller()
        cntl.timeout_ms = 3000
        resp = echo_pb2.EchoResponse()
        pch.call_method("EchoService.Echo", cntl,
                        echo_pb2.EchoRequest(message="np"), resp)
        assert not cntl.failed(), cntl.error_text
        assert resp.message == "np"
        assert cntl.latency_us > 0
        # async shape: done fires exactly once off-thread
        done_ev = threading.Event()
        cntl2 = rpc.Controller()
        cntl2.timeout_ms = 3000
        resp2 = echo_pb2.EchoResponse()
        pch.call_method("EchoService.Echo", cntl2,
                        echo_pb2.EchoRequest(message="async"), resp2,
                        done=lambda c: done_ev.set())
        assert done_ev.wait(10)
        assert not cntl2.failed() and resp2.message == "async"
        with pytest.raises(ValueError):
            pch.add_channel(object())  # mixed modes refuse loudly
    finally:
        pch.stop()


def test_selective_channel_native_fast_path(swarm_server):
    from brpc_tpu.rpc.combo_channels import SelectiveChannel

    sch = SelectiveChannel(max_retry=3, native=True)
    listurl = "list://" + ",".join(f"127.0.0.1:{p}"
                                   for p in swarm_server[:3])
    assert sch.init(listurl, "rr") == 0
    try:
        for i in range(6):
            cntl = rpc.Controller()
            cntl.timeout_ms = 2000
            resp = echo_pb2.EchoResponse()
            sch.call_method("EchoService.Echo", cntl,
                            echo_pb2.EchoRequest(message=f"s{i}"), resp)
            assert not cntl.failed(), cntl.error_text
            assert resp.message == f"s{i}"
    finally:
        sch.stop()


def test_partition_channel_native_fast_path(swarm_server, tmp_path):
    from brpc_tpu.rpc.combo_channels import PartitionChannel

    nf = tmp_path / "parts.ns"
    nf.write_text(f"127.0.0.1:{swarm_server[0]} 0/2\n"
                  f"127.0.0.1:{swarm_server[1]} 1/2\n")
    prt = PartitionChannel(native=True)
    assert prt.init(2, f"file://{nf}") == 0
    try:
        cntl = rpc.Controller()
        cntl.timeout_ms = 3000
        resp = echo_pb2.EchoResponse()
        prt.call_method("EchoService.Echo", cntl,
                        echo_pb2.EchoRequest(message="2way"), resp)
        assert not cntl.failed(), cntl.error_text
        assert resp.message == "2way"
    finally:
        prt.stop()


def test_mesh_channel_host_axis(swarm_server):
    """MeshChannel: the device axis keeps its XLA lowering; the host
    axis fans through the native cluster."""
    jax = pytest.importorskip("jax")
    import numpy as np

    from brpc_tpu.parallel import collectives
    from brpc_tpu.parallel.mesh_channel import MeshChannel

    mesh = collectives.make_mesh({"dp": len(jax.devices())})
    mc = MeshChannel(mesh, "dp")
    # device axis: the fused-collective lowering
    out = mc.parallel_call(lambda x: x * 2, np.ones(8, np.float32),
                           merger="add")
    assert float(out[0]) == 2.0 * len(jax.devices())
    # host axis: native fan-out over cluster backends
    with _mk_cluster(swarm_server[:3]) as cluster:
        mc.attach_host_cluster(cluster)
        rc, body, err, failed = mc.host_parallel_call(
            "EchoService.Echo", b"mesh", timeout_ms=3000)
        assert rc == 0 and failed == 0, err
        assert body == b"mesh" * 3
    with pytest.raises(ValueError):
        MeshChannel(mesh, "dp").host_parallel_call("X.Y", b"")


# ---------------------------------------------------------------------------
# churn acceptance (slow): zero failed RPCs through rolling SIGTERM
# restarts + live naming add/remove
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_swarm_churn_zero_failed_rpcs():
    """The ROADMAP item-1 acceptance, scaled for CI: a multi-process
    multi-port swarm behind the native cluster survives rolling SIGTERM
    restarts (graceful quiesce + lame-duck) and live naming updates
    with ZERO failed RPCs and a recorded per-backend distribution."""
    from tools.drills import fanout_swarm_bench

    r = fanout_swarm_bench(backends=120, servers=3, bench_seconds=8.0,
                           concurrency=3)
    assert r["swarm_backends"] == 120
    assert r["swarm_restarts"] == 3
    assert r["swarm_failed"] == 0, r
    assert r["swarm_qps"] > 0
    assert r["swarm_calls"] > 1000
    spread = r["swarm_selects_per_backend"]
    assert spread["min"] >= 1  # every backend took load
