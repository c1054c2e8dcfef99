"""Smoke-run every example as a subprocess — the examples double as
integration tests, as the reference's example/ suite does in its CI."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXAMPLES = [
    ("examples/echo.py", ["demo"]),
    ("examples/parallel_echo.py", []),
    ("examples/partition_echo.py", []),
    ("examples/streaming_echo.py", []),
    ("examples/backup_request.py", []),
    ("examples/cascade_echo.py", []),
    ("examples/auto_concurrency_limiter.py", []),
    ("examples/http_server.py", []),
    ("examples/tensor_transport.py", ["--mb", "1", "--iters", "3"]),
    ("examples/multi_threaded_echo.py", ["--threads", "2",
                                         "--seconds", "1"]),
    ("examples/asynchronous_echo.py", []),
    ("examples/selective_echo.py", []),
    ("examples/dynamic_partition_echo.py", []),
    ("examples/grpc_echo.py", []),
    ("examples/redis_kv.py", []),
    ("examples/memcache_kv.py", []),
    ("examples/thrift_echo.py", []),
    ("examples/nshead_extension.py", []),
    ("examples/session_data.py", []),
    ("examples/legacy_pbrpc_echo.py", []),
    ("examples/device_performance.py", ["--threads", "2", "--mb", "1",
                                        "--iters", "3"]),
    ("examples/io_uring_echo.py", ["--seconds", "1"]),
    ("examples/native_client.py", []),
    ("examples/native_protocol_clients.py", []),
    ("examples/usercode_workers.py", []),
    ("examples/rtmp_relay.py", []),
    ("examples/fanout_swarm.py", ["--backends", "6", "--seconds", "2"]),
]


@pytest.mark.parametrize("script,args", EXAMPLES,
                         ids=[e[0].split("/")[-1] for e in EXAMPLES])
def test_example_runs(script, args):
    # one retry: examples carry real RPC deadlines, and the full suite's
    # compile phases can starve a subprocess on the 1-core CI box long
    # enough to miss one — a second clean run is the signal that matters
    for attempt in (1, 2):
        proc = subprocess.run(
            [sys.executable, script, *args],
            capture_output=True, text=True, timeout=180, cwd=REPO,
        )
        if proc.returncode == 0:
            return
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
