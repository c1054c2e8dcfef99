"""Descriptor-ring shm transport (nat_shm_lane.cpp): transport-level
tests for the zero-copy lane — ring wrap + payload integrity, arena
exhaustion backpressure, the record-size throughput sweep, and
worker-SIGKILL mid-record recovery through the robust lifetime fence.

(The end-to-end server tests — usercode across worker processes, crash
recovery under live HTTP/gRPC traffic, pipelined ordering — live in
tests/test_shm_workers.py.)
"""
import ctypes
import signal
import subprocess
import sys
import time

import pytest

native = pytest.importorskip("brpc_tpu.native")
if not native.available():
    pytest.skip("native toolchain unavailable", allow_module_level=True)

lib = native.load()


def _fresh_lane(arena_bytes):
    # an in-process device server earlier in this worker leaves the
    # tensor fabric's drain thread taking records: these tests take
    # them themselves
    from brpc_tpu.rpc import device_transport as dt

    dt._fabric_stop.set()
    if dt._fabric_thread is not None:
        dt._fabric_thread.join(timeout=5)
    # a previous test's lane in this process must be fully shut down
    # (shutdown + unlink) before a new segment can replace it
    lib.nat_shm_lane_enable(0)
    assert lib.nat_shm_lane_create(arena_bytes) == 0
    return lib.nat_shm_lane_name().decode()


def _spawn_drainer(name, idle_exit_ms=4000):
    """Worker subprocess that attaches and drains records natively."""
    child = subprocess.Popen(
        [sys.executable, "-c", (
            "import sys; sys.path.insert(0, '.')\n"
            "from brpc_tpu import native\n"
            "lib = native.load()\n"
            f"assert lib.nat_shm_worker_attach({name!r}.encode()) == 0\n"
            f"print(lib.nat_shm_worker_drain_bench({idle_exit_ms}),"
            " flush=True)\n")],
        stdout=subprocess.PIPE, text=True, cwd=".")
    deadline = time.time() + 30
    while lib.nat_shm_lane_workers() < 1 and time.time() < deadline:
        time.sleep(0.05)
    assert lib.nat_shm_lane_workers() >= 1, "worker attach timed out"
    return child


def _take_payload(h):
    n = ctypes.c_size_t(0)
    p = lib.nat_req_field(h, 2, ctypes.byref(n))
    return ctypes.string_at(p, n.value) if p and n.value else b""


def test_ring_wrap_integrity():
    """300KB records through a 1MB arena: spans wrap the arena edge many
    times over; every payload must come back byte-identical (the wrap
    filler and reclaim cursor do their jobs)."""
    _fresh_lane(1 << 20)
    assert lib.nat_shm_worker_attach(
        lib.nat_shm_lane_name()) == 0  # same-process worker
    payload = bytes(range(256)) * 1200  # 300KB
    for i in range(100):
        assert lib.nat_shm_push_tensor(payload, len(payload), i) == 0, i
        h = lib.nat_shm_take_request(2000)
        assert h, f"record {i} not delivered"
        assert lib.nat_req_kind(h) == 8
        assert lib.nat_req_aux(h) == i
        assert _take_payload(h) == payload, f"record {i} corrupted"
        lib.nat_req_free(h)


def test_request_record_reads_the_arena_views():
    """A descriptor-lane request's record (nat_req_info, what the py-lane
    batch take fills) follows nat_req_field's rules: its payload is the
    view into the blob arena, and req_tuple gives what the per-field
    accessors give."""
    _fresh_lane(1 << 20)
    assert lib.nat_shm_worker_attach(lib.nat_shm_lane_name()) == 0
    payload = bytes(range(256)) * 64
    assert lib.nat_shm_push_tensor(payload, len(payload), 77) == 0
    h = lib.nat_shm_take_request(2000)
    assert h
    try:
        rec = native.req_info(h)
        n = ctypes.c_size_t(0)
        p = lib.nat_req_field(h, 2, ctypes.byref(n))
        assert rec.handle == h and rec.kind == 8 and rec.aux == 77
        assert rec.payload == p and rec.payload_len == n.value
        assert native.req_tuple(rec) == (
            h, 8, b"", payload, b"", lib.nat_req_sock_id(h),
            lib.nat_req_cid(h), b"", b"", 0, lib.nat_req_enqueue_ns(h))
        assert _take_payload(h) == payload
    finally:
        lib.nat_req_free(h)


def test_arena_exhaustion_backpressure():
    """Pushing without draining must fail cleanly once the blob arena is
    full (the backpressure bound), and succeed again after a drain frees
    spans — no wedge, no crash, no lost records."""
    _fresh_lane(1 << 20)
    assert lib.nat_shm_worker_attach(lib.nat_shm_lane_name()) == 0
    payload = b"x" * (300 << 10)
    pushed = 0
    while lib.nat_shm_push_tensor(payload, len(payload), pushed) == 0:
        pushed += 1
        assert pushed < 64, "arena never reported exhaustion"
    assert pushed >= 2  # ~3 x 300KB spans fit a 1MB arena
    drained = 0
    while True:
        h = lib.nat_shm_take_request(200)
        if not h:
            break
        lib.nat_req_free(h)
        drained += 1
    assert drained == pushed
    # space reclaimed: the lane accepts records again
    assert lib.nat_shm_push_tensor(payload, len(payload), 0) == 0
    h = lib.nat_shm_take_request(2000)
    assert h
    lib.nat_req_free(h)


# Host-load bound: a throughput ratio between record sizes on a shared
# host. Alone, 64 KB and 1 MB records move 8-11 and 13-15 GB/s; beside the
# tier-1 run's other five workers the 1 MB rate fell to 7.4-9.2 GB/s
# while 64 KB read 13-14 GB/s, in 2 of ~30 full runs (PR 21), one of
# them with the best of three interleaved windows per size.
@pytest.mark.slow
def test_record_size_sweep_monotone_throughput():
    """Per-record overhead must not dominate: pushing bigger records
    through the two-process lane yields more bytes/s (with slack for CI
    noise). This is the regression guard on the descriptor lane's whole
    point — the old byte rings paid lock+copy+futex per record and fell
    off a cliff on small records."""
    name = _fresh_lane(8 << 20)
    child = _spawn_drainer(name)
    try:
        gbps = []
        for size in (4 << 10, 64 << 10, 1 << 20):
            r = native.shm_push_bench(size, 0.6)
            assert r["records"] > 0, f"no records moved at {size}B"
            gbps.append(r["GBps"])
        # monotone with 25% slack: strict monotonicity flakes on a noisy
        # 1-2 CPU CI host, a real per-record-overhead cliff does not
        assert gbps[1] >= gbps[0] * 0.75, gbps
        assert gbps[2] >= gbps[1] * 0.75, gbps
        assert gbps[2] > 0.05, gbps  # large records must move real bytes
    finally:
        lib.nat_shm_lane_enable(0)  # shutdown: the child drain loop exits
        child.wait(timeout=15)


def test_fabric_lease_out_of_order_release_and_wrap():
    """Tensor-fabric leases (ISSUE 15): the receiver holds record spans
    past the drain loop and releases OUT OF ORDER; a held lease pins the
    arena head (content stays intact while later records churn), and
    after release the ring wraps cleanly with byte-identical payloads.
    Leased payload bytes sit in the shm.span nat_res ledger row — the
    structural zero-copy witness (payload bytes accounted ONCE)."""
    _fresh_lane(1 << 20)
    name = lib.nat_shm_lane_name()
    assert lib.nat_shm_producer_attach(name) >= 0  # in-process producer

    def span_row():
        return {r["subsystem"]: r for r in native.res_stats()}["shm.span"]

    live0 = span_row()["live_bytes"]
    pat_a = bytes(range(256)) * 800   # 200KB
    pat_b = b"B" * (200 << 10)
    assert lib.nat_shm_fabric_push(pat_a, len(pat_a), 1) == 0
    assert lib.nat_shm_fabric_push(pat_b, len(pat_b), 2) == 0
    la = native.fabric_take(2000)
    lb = native.fabric_take(2000)
    assert la is not None and lb is not None
    assert la.tag == 1 and lb.tag == 2
    # both spans pinned: the ledger carries exactly the leased bytes
    assert span_row()["live_bytes"] - live0 == len(pat_a) + len(pat_b)
    # zero-copy: the lease view IS the arena span (no staging buffer)
    import numpy as np

    va = np.frombuffer(la.view(), dtype=np.uint8)
    assert va.ctypes.data == la._ptr
    lb.release()  # OUT OF ORDER: b released while a (earlier) is held
    # churn more records past the held lease: the arena head is pinned
    # at a, but tail space still serves pushes until exhaustion
    churned = 0
    for i in range(3, 10):
        if lib.nat_shm_fabric_push(pat_b, len(pat_b), i) != 0:
            break
        h = native.fabric_take(2000)
        assert h is not None
        h.release()
        churned += 1
    assert churned >= 1
    # the held span's content is untouched by the churn
    assert bytes(va[:1024]) == pat_a[:1024]
    assert va[-1] == pat_a[-1]
    la.release()
    # head unpinned: the ring now wraps the arena edge many times over
    for i in range(12):
        assert lib.nat_shm_fabric_push(pat_a, len(pat_a), 100 + i) == 0, i
        h = native.fabric_take(2000)
        assert h is not None and h.tag == 100 + i
        assert h.tobytes() == pat_a, f"wrap corrupted record {i}"
        h.release()
    assert span_row()["live_bytes"] == live0  # every lease retired


def test_producer_sigkill_lease_epoch_guard():
    """SIGKILL a PRODUCER process while the receiver holds one of its
    leases: the robust fence surfaces EOWNERDEAD, recovery waits the
    lease out (bounded), drops the untaken record (counted), and the
    slot serves a fresh producer. The stale lease's release after
    recovery is epoch-fenced — no scribble on the recycled arena."""
    _fresh_lane(1 << 20)
    name = lib.nat_shm_lane_name().decode()
    drops0 = native.stats_counters().get("nat_fabric_recover_drops", 0)
    child = subprocess.Popen(
        [sys.executable, "-c", (
            "import sys, time; sys.path.insert(0, '.')\n"
            "from brpc_tpu import native\n"
            "lib = native.load()\n"
            f"assert lib.nat_shm_producer_attach({name!r}.encode()) >= 0\n"
            "assert lib.nat_shm_fabric_push(b'x' * 100000, 100000, 1) == 0\n"
            "assert lib.nat_shm_fabric_push(b'y' * 100000, 100000, 2) == 0\n"
            "print('PUSHED', flush=True)\n"
            "time.sleep(60)\n")],
        stdout=subprocess.PIPE, text=True, cwd=".")
    assert child.stdout.readline().strip() == "PUSHED"
    lease = native.fabric_take(5000)
    assert lease is not None and lease.tag == 1
    child.send_signal(signal.SIGKILL)
    child.wait(timeout=10)
    # recovery: EOWNERDEAD on the probe; the held lease is waited out
    # (bounded 5s) and then epoch-fenced; record 2 is dropped + counted
    t0 = time.time()
    recovered = 0
    while recovered == 0 and time.time() - t0 < 20:
        recovered = lib.nat_shm_lane_recover_probe()
        if recovered == 0:
            time.sleep(0.1)
    assert recovered == 1, "dead producer's fence was not recovered"
    assert native.stats_counters()["nat_fabric_recover_drops"] \
        >= drops0 + 1
    lease.release()  # stale epoch: must be a harmless no-op
    # the freed slot serves a replacement producer end to end
    child2 = subprocess.Popen(
        [sys.executable, "-c", (
            "import sys; sys.path.insert(0, '.')\n"
            "from brpc_tpu import native\n"
            "lib = native.load()\n"
            f"assert lib.nat_shm_producer_attach({name!r}.encode()) >= 0\n"
            "assert lib.nat_shm_fabric_push(b'z' * 50000, 50000, 9) == 0\n"
            "print('OK', flush=True)\n")],
        stdout=subprocess.PIPE, text=True, cwd=".")
    assert child2.stdout.readline().strip() == "OK"
    child2.wait(timeout=10)
    fresh = native.fabric_take(5000)
    assert fresh is not None and fresh.tag == 9
    assert fresh.tobytes() == b"z" * 50000
    fresh.release()


def test_worker_sigkill_mid_record_recovery():
    """SIGKILL a worker that consumed a record but never released its
    span or answered: the robust lifetime fence must surface the death
    (EOWNERDEAD on the recovery probe), the slot must be scrubbed and
    reusable, and the lane must keep accepting + delivering records to a
    replacement worker."""
    name = _fresh_lane(1 << 20)
    child = subprocess.Popen(
        [sys.executable, "-c", (
            "import sys, time; sys.path.insert(0, '.')\n"
            "from brpc_tpu import native\n"
            "lib = native.load()\n"
            f"assert lib.nat_shm_worker_attach({name!r}.encode()) == 0\n"
            "h = lib.nat_shm_take_request(10000)\n"
            "assert h\n"
            "print('TOOK', flush=True)\n"
            "time.sleep(60)\n")],  # holds the span + fence until killed
        stdout=subprocess.PIPE, text=True, cwd=".")
    deadline = time.time() + 30
    while lib.nat_shm_lane_workers() < 1 and time.time() < deadline:
        time.sleep(0.05)
    assert lib.nat_shm_lane_workers() >= 1
    payload = b"y" * (200 << 10)
    assert lib.nat_shm_push_tensor(payload, len(payload), 7) == 0
    assert child.stdout.readline().strip() == "TOOK"
    # kill MID-RECORD: descriptor consumed, span held, nothing answered
    child.send_signal(signal.SIGKILL)
    child.wait(timeout=10)
    # fence probe sees EOWNERDEAD and recovers exactly one slot
    deadline = time.time() + 10
    recovered = 0
    while recovered == 0 and time.time() < deadline:
        recovered = lib.nat_shm_lane_recover_probe()
        if recovered == 0:
            time.sleep(0.1)
    assert recovered == 1, "dead worker's fence was not recovered"
    assert lib.nat_shm_lane_workers() == 0
    # the freed slot serves a replacement worker; the scrubbed arena
    # accepts and delivers fresh records end to end
    child2 = _spawn_drainer(name, idle_exit_ms=2000)
    try:
        pushed = 0
        for i in range(20):
            if lib.nat_shm_push_tensor(payload, len(payload), i) == 0:
                pushed += 1
            time.sleep(0.01)
        assert pushed >= 10, "lane wedged after recovery"
    finally:
        lib.nat_shm_lane_enable(0)
        drained = int(child2.stdout.readline().strip())
        child2.wait(timeout=15)
        assert drained >= pushed  # replacement worker saw every record
