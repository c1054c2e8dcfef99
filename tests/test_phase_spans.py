"""Phase spans on the profiler's clock (rpcz.phase), the py-lane queue-wait
counter, and rpcz collection switched at run time.

Off a trace, a phase is one shared no-op; inside one, the server's phases
of a ring-lane push nest under `rpc.handle` on the host plane, and the
benchmark's reduction (benchmark/host_spans.py) totals them and names
idle gaps by them."""
import gc
import glob
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from brpc_tpu import rpc, rpcz
from brpc_tpu.butil import flags
from brpc_tpu.rpc.proto import echo_pb2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import host_spans  # noqa: E402

native = pytest.importorskip("brpc_tpu.native")

SERVER_PHASES = {"rpc.handle", "rpc.respond", "tensor.claim", "tensor.stage",
                 "tensor.fill", "tensor.view", "tensor.land_wait"}
SENDER_PHASES = {"tensor.window_wait", "tensor.arena_write"}


def _events(trace_dir):
    pb = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(pb)
    lines = [[(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
               dict(ev.stats) if ev.name == "py.gc" else {})
              for ev in line.events]
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    return pd, lines


def test_phase_without_a_trace_builds_nothing(monkeypatch):
    built = []
    monkeypatch.setattr(jax.profiler.TraceAnnotation, "__init__",
                        lambda self, *a, **k: built.append(a))
    assert not jax.profiler.TraceAnnotation.is_enabled()
    first = rpcz.phase("tensor.fill")
    with first:
        with rpcz.phase("tensor.view") as inner:
            assert inner is None
    assert rpcz.phase("rpc.handle") is first  # one shared context
    gc.collect()  # the py.gc hook builds nothing either
    assert built == []


def test_phase_and_gc_spans_inside_a_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        assert rpcz.phase("a") is not rpcz.phase("b")
        with rpcz.phase("collective.allreduce"):
            gc.collect()
    _, lines = _events(str(tmp_path))
    evs = [ev for line in lines for ev in line]
    assert any(name == "collective.allreduce" for _, _, name, _ in evs)
    gcs = [ev for ev in evs if ev[2] == "py.gc"]
    assert gcs and all(ev[3].get("generation") == 2 for ev in gcs)


def test_programs_are_named():
    from brpc_tpu.parallel import collectives
    from brpc_tpu.rpc.device_transport import pool_programs

    fill, view = pool_programs()
    block = jax.numpy.zeros(16, jax.numpy.uint8)
    text = fill.lower(block, np.zeros(8, np.uint8)).as_text()
    assert "module @jit_pool_fill" in text
    text = view.lower(block, 8, np.dtype(np.float32), (2,)).as_text()
    assert "module @jit_pool_view" in text
    mesh = collectives.make_mesh({"x": 2})
    fn = collectives._allreduce_fn(mesh, "x", (2, 4), "float32", "max")
    x = jax.device_put(np.ones((2, 4), np.float32),
                       jax.sharding.NamedSharding(
                           mesh, jax.sharding.PartitionSpec("x")))
    assert "module @jit_allreduce_max" in fn.lower(x).as_text()


# One ring-lane push in a child process (the fabric segment and producer
# mapping are process-wide): the child is both sender and server, traced.
RING_PUSH_SCRIPT = r"""
import gc, sys
sys.path.insert(0, ".")
import jax
import numpy as np
from brpc_tpu import rpc
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.rpc import device_transport as dt
from brpc_tpu.rpc.proto import rpc_meta_pb2
from brpc_tpu.rpc.proto import tensor_service_pb2 as ts_pb2
from brpc_tpu.rpc.tensor_service import TensorStoreService
from brpc_tpu.rpc.tpu_std_protocol import RpcMessage, process_request


class Sock:
    remote_side = None
    app_state = None
    written = []

    def write(self, buf, id_wait=None):
        self.written.append(len(buf))
        return 0

    def set_failed(self, *a):
        raise AssertionError("socket failed")


store = TensorStoreService()
srv = rpc.Server()
srv.add_service(store)
name = dt._fabric_arm_receiver()
assert name, "no fabric segment"
ep = dt.DeviceEndpoint()
ep.state = dt.ESTABLISHED
ep.peer_info = {"process": "peer", "host": dt._boot_id, "fabric": name,
                "device_count": 1}
arr = np.arange(3000, dtype=np.float32)  # 12000 B: staged to 16 KiB


def push(key):
    meta = rpc_meta_pb2.RpcMeta()
    meta.request.service_name = "TensorStore"
    meta.request.method_name = "Push"
    meta.correlation_id = 1
    assert ep.prepare_send([arr], meta, IOBuf())
    assert meta.tensors[0].sharding_spec.startswith("ring:")
    msg = RpcMessage(meta, ts_pb2.TensorPushRequest(name=key)
                     .SerializeToString(), IOBuf())
    msg.socket, msg.arg = Sock(), srv
    with jax.profiler.TraceAnnotation("push"):
        process_request(msg)
    ep.on_ack(int(meta.tensors[0].sharding_spec.rsplit(":", 1)[1]))


push("warm")
with jax.profiler.trace(sys.argv[1]):
    with jax.profiler.TraceAnnotation("window"):
        push("w")
        gc.collect()
held = store.get("w")[0]
assert np.array_equal(np.asarray(held), arr)
assert Sock.written
print("OK", flush=True)
"""


@pytest.fixture(scope="module")
def ring_push_trace(tmp_path_factory):
    if not native.available():
        pytest.skip("native toolchain unavailable")
    out = str(tmp_path_factory.mktemp("ring_push_trace"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", RING_PUSH_SCRIPT, out],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-3000:]
    return out


def test_ring_push_server_phases_nest_under_handle(ring_push_trace):
    _, lines = _events(ring_push_trace)
    window = [ev for line in lines for ev in line if ev[2] == "window"][0]
    inside = [[ev for ev in line if ev[0] >= window[0] and ev[1] <= window[1]]
              for line in lines]
    handles = [(line, ev) for line in inside for ev in line
               if ev[2] == "rpc.handle"]
    assert len(handles) == 1
    line, (s, e, _, _) = handles[0]
    nested = {ev[2] for ev in line if s <= ev[0] and ev[1] <= e}
    assert SERVER_PHASES <= nested, nested
    seen = {ev[2] for line in inside for ev in line}
    assert SENDER_PHASES <= seen and "py.gc" in seen, seen


def test_host_span_reduction_totals_and_labels(ring_push_trace):
    pd, _ = _events(ring_push_trace)
    got = host_spans.reduce(pd)
    totals, counts = got["host_span_totals"], got["host_span_counts"]
    assert SERVER_PHASES | SENDER_PHASES <= set(counts)
    assert counts["rpc.handle"] == 1 and counts["tensor.fill"] == 1
    assert all(totals[k] > 0 for k in counts)
    inner = sum(totals[k] for k in ("tensor.claim", "tensor.stage",
                                    "tensor.fill", "tensor.view",
                                    "tensor.land_wait", "rpc.respond"))
    assert totals["rpc.handle"] >= inner
    # no device plane on the CPU: the window is one idle gap, named by the
    # benchmark's push span and the phase that held the host longest
    gaps = host_spans.idle_gaps(pd, [0])
    assert len(gaps) == 1
    bench, _, phase = gaps[0][0].partition(" > ")
    assert bench == "push" and phase in host_spans.SPANS, gaps


class HeldEcho(rpc.Service):
    """Holds the handler of the first request until released."""
    SERVICE_NAME = "EchoService"

    def __init__(self):
        self.started = threading.Event()
        self.release = threading.Event()

    @rpc.rpc_method(echo_pb2.EchoRequest, echo_pb2.EchoResponse)
    def Echo(self, cntl, request, response, done):
        if request.message == "first":
            self.started.set()
            self.release.wait(10)
        response.message = request.message
        done()


def test_py_queue_wait_counts_a_request_held_behind_the_only_worker():
    if not native.available():
        pytest.skip("native toolchain unavailable")
    hold_s = 0.3
    svc = HeldEcho()
    srv = rpc.Server(rpc.ServerOptions(num_threads=1,
                                       use_native_runtime=True))
    srv.add_service(svc)
    assert srv.start("127.0.0.1:0") == 0
    errors = []

    def call(message):
        ch = rpc.Channel()
        ch.init(str(srv.listen_endpoint))
        cntl, _ = ch.call("EchoService.Echo",
                          echo_pb2.EchoRequest(message=message),
                          echo_pb2.EchoResponse, timeout_ms=10000)
        errors.append(cntl.error_text if cntl.failed() else "")
        ch.close()

    try:
        before = native.stats_counters()
        calls = [threading.Thread(target=call, args=(m,))
                 for m in ("first", "second")]
        calls[0].start()
        assert svc.started.wait(10)  # the only worker is held
        calls[1].start()
        deadline = time.monotonic() + 10
        # nat_py_dispatches counts a request as it enters the queue
        while (native.stats_counters()["nat_py_dispatches"]
               - before["nat_py_dispatches"] < 2):
            assert time.monotonic() < deadline, "second request not queued"
            time.sleep(0.005)
        time.sleep(hold_s)  # the second waits at least this long
        svc.release.set()
        for t in calls:
            t.join(10)
            assert not t.is_alive()
        assert errors == ["", ""]
        after = native.stats_counters()
    finally:
        svc.release.set()
        srv.stop()
        srv.join(10)
    waited = after["nat_py_queue_wait_ns"] - before["nat_py_queue_wait_ns"]
    assert waited >= hold_s * 1e9, waited


def test_enable_rpcz_flag_switches_native_span_sampling():
    if not native.available():
        pytest.skip("native toolchain unavailable")
    port = native.rpc_server_start(native_echo=True)
    ch = native.channel_open("127.0.0.1", port)

    def server_spans():
        native.stats_drain_spans()
        for _ in range(3):
            rc, _, _ = native.channel_call(ch, "EchoService", "Echo", b"x",
                                           timeout_ms=5000)
            assert rc == 0
        got = []
        for _ in range(10):
            got += [r for r in native.stats_drain_spans()
                    if r["lane"] == "echo"]
            if got:
                break
            time.sleep(0.05)
        return got

    try:
        assert flags.set_flag("enable_rpcz", True)
        assert server_spans()
        assert flags.set_flag("enable_rpcz", False)
        assert not server_spans()
    finally:
        flags.set_flag("enable_rpcz", False)
        native.channel_close(ch)
        native.rpc_server_stop()
