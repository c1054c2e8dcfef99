"""Cross-process device-transport lane tests (the rdma_endpoint/block_pool
cross-machine semantics, exercised across a real process boundary):
HostArena span accounting, the IOBuf blockmem seam, and a two-process
push/pull where tensor payloads ride the shared arena — NOT the TCP wire —
with retention-until-ACK observed on both sides."""
import os
import subprocess
import sys

import numpy as np
import pytest

from brpc_tpu import rpc
from brpc_tpu.rpc import device_transport as dt
from brpc_tpu.rpc.tensor_service import TensorClient, make_device_channel

SERVER_SCRIPT = r"""
import sys
sys.path.insert(0, ".")

from brpc_tpu import rpc
from brpc_tpu.rpc.tensor_service import TensorStoreService

svc = TensorStoreService()
srv = rpc.Server(rpc.ServerOptions(num_threads=2))
srv.add_service(svc)
assert srv.start("127.0.0.1:0") == 0
print(srv.listen_endpoint.port, flush=True)
sys.stdin.readline()  # parent closes stdin to stop us
srv.stop()
"""


def test_host_arena_spans():
    arena = dt.HostArena(size=1 << 20)
    try:
        total = arena.free_bytes()
        a = arena.alloc(1000)
        b = arena.alloc(5000)
        assert a is not None and b is not None and a != b
        assert arena.free_bytes() < total
        arena.free(a, 1000)
        arena.free(b, 5000)
        assert arena.free_bytes() == total  # spans coalesce back
    finally:
        arena.close()


def test_iobuf_blockmem_seam():
    """The blockmem_allocate hook: IOBuf appends stage into arena memory."""
    from brpc_tpu.butil import iobuf as iobuf_mod

    arena = dt.HostArena(size=1 << 20)
    try:
        arena.install_as_iobuf_allocator(capacity=4096)
        free0 = arena.free_bytes()
        buf = iobuf_mod.IOBuf()
        buf.append(b"x" * 10000)
        assert bytes(buf.to_bytes()) == b"x" * 10000
        assert arena.free_bytes() < free0  # blocks came from the arena
    finally:
        iobuf_mod.set_block_allocator(None)
        arena.close()


@pytest.fixture
def remote_store():
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen([sys.executable, "-c", SERVER_SCRIPT],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True, cwd=repo_root)
    port = int(proc.stdout.readline())
    yield port
    proc.stdin.close()
    proc.wait(timeout=10)


RING_CLIENT_SCRIPT = r"""
import sys
sys.path.insert(0, ".")
import numpy as np
from brpc_tpu.rpc import device_transport as dt
from brpc_tpu.rpc.tensor_service import TensorClient, make_device_channel

ch = make_device_channel(f"127.0.0.1:{sys.argv[1]}")
client = TensorClient(ch)

ring0 = dt._dev_ring.get_value()
wire0 = dt._dev_wire.get_value()

arr = np.arange(4096, dtype=np.float32).reshape(64, 64)
cntl, resp = client.push("w", [arr])
assert not cntl.failed(), cntl.error_text
assert resp.ok

sock = cntl._current_sock
ep = sock.app_state
assert isinstance(ep, dt.DeviceEndpoint)
assert ep.state == dt.ESTABLISHED
assert not ep.same_process and ep.same_host
# the server advertised its fabric and the push used it — no wire
# payload, no send-arena staging
assert ep.peer_info.get("fabric"), "server did not advertise a fabric"
assert dt._dev_ring.get_value() == ring0 + 1
assert dt._dev_wire.get_value() == wire0
assert len(cntl.request_attachment) == 0  # no payload bytes on the wire
# push response piggybacked the ACK: retention drained, window open
assert ep.retained_count == 0
assert ep.inflight_bytes == 0

cntl2, pulled = client.pull("w")
assert not cntl2.failed(), cntl2.error_text
np.testing.assert_array_equal(pulled[0], arr)
assert len(cntl2.response_attachment) == 0

# multi-tensor pushes ride one record per tensor
arrs = [np.full((32, 32), i, dtype=np.int32) for i in range(3)]
cntl3, resp3 = client.push("multi", arrs)
assert not cntl3.failed(), cntl3.error_text
assert dt._dev_ring.get_value() == ring0 + 2
cntl4, pulled4 = client.pull("multi")
assert not cntl4.failed(), cntl4.error_text
for i in range(3):
    np.testing.assert_array_equal(pulled4[i], arrs[i])

# int64 above 2^31 and float64 come back unchanged: the server keeps
# the dtypes its device would narrow on the host
wide = [np.array([2**40 + 3, -(2**33)], dtype=np.int64),
        np.array([1 + 2**-40], dtype=np.float64)]
cntl5, _ = client.push("wide", wide)
assert not cntl5.failed(), cntl5.error_text
cntl6, pulled6 = client.pull("wide")
assert not cntl6.failed(), cntl6.error_text
for got, want in zip(pulled6, wide):
    assert got.dtype == want.dtype, got.dtype
    np.testing.assert_array_equal(got, want)

ch.close()
print("OK", flush=True)
"""


def test_two_process_ring_transfer(remote_store):
    """Push+pull to a DIFFERENT process rides the descriptor-ring fabric
    by default (ISSUE 15): the payload is written once into the
    receiver's blob arena as kind-8 records, zero payload bytes in the
    attachment, and the receiver consumes the spans in place."""
    _run_client(RING_CLIENT_SCRIPT, remote_store)


FABRIC_OFF_SERVER_SCRIPT = SERVER_SCRIPT.replace(
    "import sys", "import os, sys\nos.environ['BRPC_TPU_FABRIC'] = '0'", 1)


def test_two_process_shm_arena_fallback():
    """With the fabric disabled on the server (BRPC_TPU_FABRIC=0) the
    same-host lane falls back to the shared HostArena staging path —
    still zero payload bytes on the wire."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen([sys.executable, "-c",
                             FABRIC_OFF_SERVER_SCRIPT],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True, cwd=repo_root)
    try:
        port = int(proc.stdout.readline())
        ch = make_device_channel(f"127.0.0.1:{port}")
        client = TensorClient(ch)

        shm0 = dt._dev_shm.get_value()
        wire0 = dt._dev_wire.get_value()
        arr = np.arange(4096, dtype=np.float32).reshape(64, 64)
        cntl, resp = client.push("w", [arr])
        assert not cntl.failed(), cntl.error_text
        ep = cntl._current_sock.app_state
        assert not ep.peer_info.get("fabric")
        assert dt._dev_shm.get_value() == shm0 + 1
        assert dt._dev_wire.get_value() == wire0
        assert len(cntl.request_attachment) == 0
        cntl2, pulled = client.pull("w")
        np.testing.assert_array_equal(pulled[0], arr)
        # int64 above 2^31 comes back unchanged through the arena
        wide = np.array([2**40 + 3, -(2**33)], dtype=np.int64)
        cntl3, _ = client.push("wide", [wide])
        assert not cntl3.failed(), cntl3.error_text
        assert dt._dev_shm.get_value() == shm0 + 2
        cntl4, pulled4 = client.pull("wide")
        assert pulled4[0].dtype == np.int64
        np.testing.assert_array_equal(pulled4[0], wide)
        ch.close()
    finally:
        proc.stdin.close()
        proc.wait(timeout=10)


WINDOW_CLIENT_SCRIPT = r"""
import sys, time
sys.path.insert(0, ".")
import numpy as np
from brpc_tpu.rpc import device_transport as dt
from brpc_tpu.rpc.tensor_service import TensorClient, make_device_channel

ch = make_device_channel(f"127.0.0.1:{sys.argv[1]}")
client = TensorClient(ch)
arena = dt.default_send_arena()
free0 = arena.free_bytes()
for i in range(8):
    arr = np.full((256, 256), i, dtype=np.float32)
    cntl, resp = client.push(f"t{i}", [arr])
    assert not cntl.failed(), cntl.error_text
# every push was acked synchronously -> every span freed
deadline = time.monotonic() + 5
while arena.free_bytes() != free0 and time.monotonic() < deadline:
    time.sleep(0.05)
assert arena.free_bytes() == free0
cntl, pulled = client.pull("t7")
np.testing.assert_array_equal(pulled[0], np.full((256, 256), 7,
                                                 dtype=np.float32))
ch.close()
print("OK", flush=True)
"""


def _run_client(script, port):
    """Run a push/pull client in a FRESH process: a process maps one
    fabric segment, so a client attached here as a producer would change
    what later tests in this worker find (and vice versa)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", script, str(port)],
                         capture_output=True, text=True, timeout=120,
                         cwd=repo_root)
    assert res.returncode == 0 and res.stdout.strip() == "OK", \
        res.stdout + res.stderr


def test_two_process_window_retention(remote_store):
    """Several in-flight pushes exercise the sliding window + retention
    across the process boundary; all spans release after the ACKs."""
    _run_client(WINDOW_CLIENT_SCRIPT, remote_store)


def test_wire_fallback_still_works():
    """FALLBACK_TCP peers (no arena/host match) use attachment bytes."""
    ep = dt.DeviceEndpoint()
    ep.state = dt.FALLBACK_TCP
    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.rpc.proto import rpc_meta_pb2

    meta = rpc_meta_pb2.RpcMeta()
    att = IOBuf()
    arr = np.arange(16, dtype=np.int32)
    assert ep.prepare_send([arr], meta, att)
    assert len(att) == arr.nbytes
    out, seq = dt.receive_tensors(meta, att)
    np.testing.assert_array_equal(out[0], arr)
    ep.on_ack(seq)
    assert ep.retained_count == 0


def test_transfer_server_lane_plumbing():
    """The jax transfer-server lane (device-to-device; the CROSS-HOST
    path auto-selected when peers are on different machines): publish/
    pull plumbing exercised same-process — the CPU backend's bulk
    transport is same-process-only, so the cross-process form needs real
    device backends (it aborts on CPU, hence no subprocess here)."""
    from brpc_tpu.butil.iobuf import IOBuf
    from brpc_tpu.rpc.proto import rpc_meta_pb2

    server = dt._global_xfer_server()
    if server is None:
        import pytest as _pytest

        _pytest.skip("jax build lacks the transfer server")
    ep = dt.DeviceEndpoint()
    ep.state = dt.ESTABLISHED
    # a fake CROSS-HOST xfer-capable peer selects the lane automatically
    ep.peer_info = {"process": "other-proc", "host": "other-host",
                    "xfer": True, "device_count": 1}
    ep.resolve_xfer_addr("127.0.0.1")
    assert ep._my_xfer_addr.startswith("127.0.0.1:")

    xfer0 = dt.lane_counters()["xfer"]
    meta = rpc_meta_pb2.RpcMeta()
    att = IOBuf()
    arr = np.arange(2048, dtype=np.float32).reshape(32, 64) * 0.5
    assert ep.prepare_send([arr], meta, att)
    spec = meta.tensors[0].sharding_spec
    assert spec.startswith("xfer|")
    assert len(att) == 0  # no payload bytes on the RPC wire
    assert dt.lane_counters()["xfer"] == xfer0 + 1

    out, seq = dt.receive_tensors(meta, att)
    np.testing.assert_array_equal(np.asarray(out[0]), arr)
    ep.on_ack(seq)
    assert ep.retained_count == 0 and ep.inflight_bytes == 0


XFER_SERVER_SCRIPT = r"""
import os, sys
os.environ["BRPC_TPU_FAKE_XFER"] = "1"
sys.path.insert(0, ".")

from brpc_tpu import rpc
from brpc_tpu.rpc.tensor_service import TensorStoreService

svc = TensorStoreService()
srv = rpc.Server(rpc.ServerOptions(num_threads=2))
srv.add_service(svc)
assert srv.start("127.0.0.1:0") == 0
print(srv.listen_endpoint.port, flush=True)
sys.stdin.readline()
srv.stop()
"""


def test_two_process_xfer_transfer():
    """The FULL xfer-lane pull path across a real process boundary via
    the in-repo fake transfer fabric (fake_transfer.py): publish on the
    sender's transfer server, wildcard dial-back address resolution,
    zero payload bytes on the RPC wire, retention released when the
    peer's pull completes, and the xfer counter incrementing."""
    from brpc_tpu.butil import flags as _flags
    from brpc_tpu.rpc.fake_transfer import FakeTransferServer

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, BRPC_TPU_FAKE_XFER="1")
    proc = subprocess.Popen([sys.executable, "-c", XFER_SERVER_SCRIPT],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True, cwd=repo_root, env=env)
    saved_server = dt._xfer_server
    fake = FakeTransferServer()
    dt._xfer_server = fake
    _flags.set_flag("device_transport_prefer_xfer", True)
    try:
        port = int(proc.stdout.readline())
        ch = make_device_channel(f"127.0.0.1:{port}")
        client = TensorClient(ch)

        xfer0 = dt.lane_counters()["xfer"]
        arr = np.arange(3000, dtype=np.float32).reshape(60, 50) * 1.5
        cntl, resp = client.push("xw", [arr])
        assert not cntl.failed(), cntl.error_text
        assert resp.ok

        ep = cntl._current_sock.app_state
        assert isinstance(ep, dt.DeviceEndpoint)
        assert ep.state == dt.ESTABLISHED
        assert ep._my_xfer_addr.startswith("127.0.0.1:")  # wildcard resolved
        # the lane fired: counter moved, nothing rode the RPC wire
        assert dt.lane_counters()["xfer"] == xfer0 + 1
        assert len(cntl.request_attachment) == 0
        # the peer's pull released the publication (retention-until-pull)
        assert fake.published_count() == 0
        assert ep.retained_count == 0 and ep.inflight_bytes == 0

        # and the values survived the fabric: pull them back over RPC
        cntl2, pulled = client.pull("xw")
        assert not cntl2.failed(), cntl2.error_text
        np.testing.assert_array_equal(np.asarray(pulled[0]), arr)
        ch.close()
    finally:
        _flags.set_flag("device_transport_prefer_xfer", False)
        dt._xfer_server = saved_server
        proc.stdin.close()
        proc.wait(timeout=10)
