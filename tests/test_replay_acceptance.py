"""ISSUE 12 acceptance: the two-process flight-recorder round trip.

A native server under MIXED tpu_std + HTTP load from a client in
ANOTHER process, with dump sampling and span sampling armed: the
capture files must carry trace_ids findable in /rpcz for the same
window (a regression arrives with its profile AND the exact requests
that caused it), and a native replay of the capture against a
RESTARTED server must complete with zero failed RPCs and recorded
p50/p99. Kept in its own module: the tests own the whole native server
slot (start/stop/restart), which a module-scope rpc.Server fixture
could not share.
"""
import glob
import os
import subprocess
import sys
import time

import pytest

native = pytest.importorskip("brpc_tpu.native")
if not native.available():
    pytest.skip("native toolchain unavailable", allow_module_level=True)

from brpc_tpu.butil.recordio import RecordReader  # noqa: E402

# rpcz collection is off by default; this module reads spans
pytestmark = pytest.mark.usefixtures("rpcz_on")

N_STD = 25
N_HTTP = 10
TRACE_BASE = 0xACE0_0000


def _client_script(port):
    return (
        "import sys; sys.path.insert(0, '.')\n"
        "from brpc_tpu import native\n"
        f"h = native.channel_open('127.0.0.1', {port})\n"
        f"hh = native.channel_open_http('127.0.0.1', {port})\n"
        "print('up', flush=True)\n"
        f"for i in range({N_STD}):\n"
        f"    with native.trace_scope({TRACE_BASE} + i, 0x5):\n"
        "        code, body, text = native.channel_call(\n"
        "            h, 'EchoService', 'Echo',\n"
        "            b'mixed-load-%04d' % i, timeout_ms=5000)\n"
        "    assert code == 0, (code, text)\n"
        f"for i in range({N_HTTP}):\n"
        f"    with native.trace_scope({TRACE_BASE} + 0x1000 + i, 0x6):\n"
        "        st, body = native.http_call(hh, 'POST', '/echo',\n"
        "                                    b'h%d' % i, timeout_ms=5000)\n"
        "    assert st == 200, st\n"
        "native.channel_close(h)\n"
        "native.channel_close(hh)\n"
        "print('done', flush=True)\n")


def test_two_process_capture_rpcz_correlation_and_replay(tmp_path):
    from brpc_tpu import rpcz

    capture_dir = str(tmp_path / "acc")
    port = native.rpc_server_start(native_echo=True)
    native.rpc_server_native_http(True)
    native.stats_enable_spans(1)
    native.stats_drain_spans()  # drop spans from earlier tests
    assert native.dump_start(capture_dir, every=1, seed=77) == 0
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen([sys.executable, "-c", _client_script(port)],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, cwd=repo_root, env=env)
    try:
        assert proc.stdout.readline().strip() == "up"
        assert proc.stdout.readline().strip() == "done", proc.stderr.read()
        proc.wait(timeout=30)
        assert proc.returncode == 0, proc.stderr.read()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if native.dump_status()["written"] >= N_STD + N_HTTP:
                break
            time.sleep(0.05)
    finally:
        proc.kill()
        native.dump_stop()
        native.stats_enable_spans(0)

    # ---- capture files carry the window's trace ids ----
    records = []
    for path in sorted(glob.glob(os.path.join(capture_dir, "*.rio"))):
        with RecordReader(path) as reader:
            records.extend(reader)
    std_traces = {m["trace_id"] for m, _ in records if m["lane"] == "echo"}
    http_traces = {m["trace_id"] for m, _ in records
                   if m["lane"] == "http"}
    assert std_traces == {TRACE_BASE + i for i in range(N_STD)}
    assert {TRACE_BASE + 0x1000 + i
            for i in range(N_HTTP)} <= http_traces
    std_payloads = sorted(p for m, p in records if m["lane"] == "echo")
    assert std_payloads == sorted(b"mixed-load-%04d" % i
                                  for i in range(N_STD))

    # ---- the same trace ids resolve in /rpcz (drained native spans):
    # a captured request cross-references its span from the window ----
    correlated = 0
    for tid in list(std_traces)[:10]:
        spans = rpcz.find_trace(tid)
        if any(s.full_method == "EchoService.Echo" for s in spans):
            correlated += 1
    assert correlated >= 8, (correlated, len(std_traces))

    # ---- replay against a RESTARTED server: zero failed RPCs,
    # recorded latency quantiles ----
    native.rpc_server_stop()
    port2 = native.rpc_server_start(native_echo=True)
    native.rpc_server_native_http(True)
    try:
        res = native.replay_run("127.0.0.1", port2, capture_dir, times=1,
                                concurrency=4, timeout_ms=5000)
    finally:
        native.rpc_server_stop()
    assert res["failed"] == 0
    assert res["ok"] == res["sent"] == len(records)
    assert 0 < res["p50_us"] <= res["p99_us"]
