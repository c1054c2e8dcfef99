"""REAL jax.experimental.transfer smoke — the xfer lane WITHOUT the fake
fabric (BRPC_TPU_FAKE_XFER unset).

The module skips where a transfer server cannot start and serve a
loopback pull in this process; where it can, the proof runs with no code
change (the reference gates its RDMA unittest the same way:
brpc_rdma_unittest.cpp #if BRPC_WITH_RDMA).
"""
import os
import subprocess
import sys

import numpy as np
import pytest


def _real_transfer_probe() -> str:
    """Empty string when a real transfer server can start AND serve a
    loopback pull; else the reason to skip."""
    if os.environ.get("BRPC_TPU_FAKE_XFER"):
        return "BRPC_TPU_FAKE_XFER forces the fake fabric"
    try:
        import jax
        from jax.experimental import transfer  # noqa: F401
    except Exception as e:
        return f"jax.experimental.transfer unavailable: {e}"
    try:
        import jax

        srv = transfer.start_transfer_server(jax.devices()[0].client)
        addr = srv.address()
        if not addr:
            return "transfer server reports no address"
        # loopback self-connect: the cheapest proof the fabric works
        conn = srv.connect(addr)
        arr = jax.numpy.arange(16, dtype=jax.numpy.float32)
        srv.await_pull(1, [arr])
        out = conn.pull(1, [jax.ShapeDtypeStruct(arr.shape, arr.dtype)])
        got = np.asarray(out[0])
        if not np.array_equal(got, np.asarray(arr)):
            return "loopback pull returned wrong bytes"
        return ""
    except Exception as e:
        return f"transfer fabric unusable here: {type(e).__name__}: {e}"


_SKIP_REASON = _real_transfer_probe()

pytestmark = pytest.mark.skipif(
    bool(_SKIP_REASON), reason=_SKIP_REASON or "real transfer usable")


XFER_SERVER_SCRIPT = r"""
import sys
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


def test_two_process_real_xfer_push_pull():
    """The full xfer-lane pull path across a process boundary on the
    REAL transfer fabric: publish on the sender's transfer server, peer
    pulls device-to-device, zero payload bytes on the RPC wire."""
    from brpc_tpu.butil import flags as _flags
    from brpc_tpu.rpc import device_transport as dt
    from brpc_tpu.rpc.tensor_service import TensorClient, make_device_channel

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("BRPC_TPU_FAKE_XFER", None)
    proc = subprocess.Popen([sys.executable, "-c", XFER_SERVER_SCRIPT],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True, cwd=repo_root, env=env)
    _flags.set_flag("device_transport_prefer_xfer", True)
    try:
        port = int(proc.stdout.readline())
        ch = make_device_channel(f"127.0.0.1:{port}")
        client = TensorClient(ch)

        xfer0 = dt.lane_counters()["xfer"]
        arr = np.arange(4096, dtype=np.float32).reshape(64, 64) * 0.5
        cntl, resp = client.push("real-xw", [arr])
        assert not cntl.failed(), cntl.error_text
        assert resp.ok
        assert dt.lane_counters()["xfer"] == xfer0 + 1
        assert len(cntl.request_attachment) == 0  # bytes rode the fabric

        cntl2, pulled = client.pull("real-xw")
        assert not cntl2.failed(), cntl2.error_text
        np.testing.assert_array_equal(np.asarray(pulled[0]), arr)
        ch.close()
    finally:
        _flags.set_flag("device_transport_prefer_xfer", False)
        proc.stdin.close()
        proc.wait(timeout=10)
