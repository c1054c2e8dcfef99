"""brpc_tpu.native — ctypes bindings to the C++ core (native/).

The native components mirror the reference's native layers (SURVEY.md
section 2: C++ throughout): a ucontext M:N fiber scheduler with lock-free
work stealing and butex (bthread's role), a refcounted-block IOBuf, a
varint RpcMeta codec, and an epoll echo runtime wire-compatible with the
Python tpu_std protocol. Built on demand with `make` (g++); cached .so.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import weakref
from typing import Optional

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
# BRPC_TPU_NATIVE_SO points the whole Python surface at an alternate
# build of the same library — the sanitizer soak (tools/check.sh --soak)
# runs the full pytest native matrix against
# libbrpc_tpu_native_asan.so this way (with libasan LD_PRELOADed).
_SO_PATH = os.environ.get(
    "BRPC_TPU_NATIVE_SO",
    os.path.join(_NATIVE_DIR, "libbrpc_tpu_native.so"))

_lib: Optional[ctypes.CDLL] = None
_lib_lock = threading.Lock()
# /proc/self/statm resident bytes just before the .so first loaded: the
# zero point the /status nat_mem RSS reconciliation diffs against
# (brpc_tpu.bvar.native_vars.rss_reconciliation_line).
_rss_at_load: Optional[int] = None


def _read_rss() -> int:
    try:
        with open("/proc/self/statm", "r") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")
    except (OSError, ValueError, IndexError):
        return 0


def rss_at_load() -> int:
    """Resident bytes captured immediately before the native library
    loaded (0 when it never loaded)."""
    return _rss_at_load or 0


class NativeUnavailable(RuntimeError):
    pass


class NatSpanRec(ctypes.Structure):
    """Mirror of nat_stats.h NatSpanRec — one sampled native-handled call
    (timestamps are CLOCK_MONOTONIC ns; see stats_now_ns for mapping)."""

    _fields_ = [
        ("trace_id", ctypes.c_uint64),
        ("span_id", ctypes.c_uint64),
        ("parent_span_id", ctypes.c_uint64),
        ("sock_id", ctypes.c_uint64),
        ("recv_ns", ctypes.c_uint64),
        ("parse_ns", ctypes.c_uint64),
        ("dispatch_ns", ctypes.c_uint64),
        ("write_ns", ctypes.c_uint64),
        ("protocol", ctypes.c_int32),
        ("error_code", ctypes.c_int32),
        ("req_bytes", ctypes.c_uint32),
        ("resp_bytes", ctypes.c_uint32),
        ("method", ctypes.c_char * 48),
    ]


class NatMethodStatRow(ctypes.Structure):
    """Mirror of nat_stats.h NatMethodStatRow — one per-method stats row
    (count/errors/current+max concurrency; lane indexes the NL_* table)."""

    _fields_ = [
        ("count", ctypes.c_uint64),
        ("errors", ctypes.c_uint64),
        ("concurrency", ctypes.c_int64),
        ("max_concurrency", ctypes.c_int64),
        ("lane", ctypes.c_int32),
        ("method", ctypes.c_char * 52),
    ]


class NatConnRow(ctypes.Structure):
    """Mirror of nat_stats.h NatConnRow — one native /connections row."""

    _fields_ = [
        ("sock_id", ctypes.c_uint64),
        ("in_bytes", ctypes.c_uint64),
        ("out_bytes", ctypes.c_uint64),
        ("in_msgs", ctypes.c_uint64),
        ("out_msgs", ctypes.c_uint64),
        ("read_calls", ctypes.c_uint64),
        ("write_calls", ctypes.c_uint64),
        ("unwritten_bytes", ctypes.c_uint64),
        ("mem_bytes", ctypes.c_uint64),
        ("fd", ctypes.c_int32),
        ("disp_idx", ctypes.c_int32),
        ("server_side", ctypes.c_int32),
        ("protocol", ctypes.c_char * 12),
        ("remote", ctypes.c_char * 24),
    ]


class NatResRow(ctypes.Structure):
    """Mirror of nat_res.h NatResRow — one per-subsystem resource-ledger
    row (live bytes/objects, cumulative allocs/frees, high-water)."""

    _fields_ = [
        ("live_bytes", ctypes.c_uint64),
        ("live_objects", ctypes.c_uint64),
        ("cum_allocs", ctypes.c_uint64),
        ("cum_frees", ctypes.c_uint64),
        ("cum_alloc_bytes", ctypes.c_uint64),
        ("hwm_bytes", ctypes.c_uint64),
        ("name", ctypes.c_char * 16),
    ]


class NatLockRankRow(ctypes.Structure):
    """Mirror of nat_stats.h NatLockRankRow — always-on per-rank
    contended-wait totals of the NatMutex slow path."""

    _fields_ = [
        ("waits", ctypes.c_uint64),
        ("wait_us", ctypes.c_uint64),
        ("rank", ctypes.c_int32),
        ("name", ctypes.c_char * 20),
    ]


class NatDumpStatusRec(ctypes.Structure):
    """Mirror of nat_dump.h NatDumpStatusRec — flight-recorder status
    (counts are since the current nat_dump_start window)."""

    _fields_ = [
        ("samples", ctypes.c_uint64),
        ("written", ctypes.c_uint64),
        ("bytes", ctypes.c_uint64),
        ("drops", ctypes.c_uint64),
        ("oversize", ctypes.c_uint64),
        ("rotations", ctypes.c_uint64),
        ("max_file_bytes", ctypes.c_uint64),
        ("max_payload", ctypes.c_uint64),
        ("seed", ctypes.c_uint64),
        ("every", ctypes.c_uint32),
        ("running", ctypes.c_int32),
        ("generations", ctypes.c_int32),
        ("dir", ctypes.c_char * 192),
    ]


class NatReplayResult(ctypes.Structure):
    """Mirror of nat_dump.h NatReplayResult — one nat_replay_run's
    outcome (latency quantiles cover successful calls)."""

    _fields_ = [
        ("loaded", ctypes.c_uint64),
        ("sent", ctypes.c_uint64),
        ("ok", ctypes.c_uint64),
        ("failed", ctypes.c_uint64),
        ("skipped", ctypes.c_uint64),
        ("seconds", ctypes.c_double),
        ("qps", ctypes.c_double),
        ("p50_us", ctypes.c_double),
        ("p99_us", ctypes.c_double),
    ]


class NatClusterRow(ctypes.Structure):
    """Mirror of nat_stats.h NatClusterRow — one per-backend row of a
    native cluster's server list (selects/errors/breaker/lame-duck)."""

    _fields_ = [
        ("selects", ctypes.c_uint64),
        ("errors", ctypes.c_uint64),
        ("inflight", ctypes.c_int64),
        ("ema_latency_us", ctypes.c_uint64),
        ("weight", ctypes.c_int32),
        ("breaker_open", ctypes.c_int32),
        ("lame_duck", ctypes.c_int32),
        ("part_index", ctypes.c_int32),
        ("part_total", ctypes.c_int32),
        ("endpoint", ctypes.c_char * 24),
        ("tag", ctypes.c_char * 16),
    ]


def _build() -> None:
    """Bring the library up to date with `make`. Serialised across
    processes by a file lock: the test runner's workers all load the
    library while collecting, and each must see the same outcome.
    Raises NativeUnavailable carrying the compiler's error."""
    import fcntl

    with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                           capture_output=True, timeout=600)
        except subprocess.CalledProcessError as e:
            raise NativeUnavailable(
                "native build failed:\n" +
                (e.stderr or b"").decode(errors="replace")[-4000:]) from None
        except (subprocess.TimeoutExpired, FileNotFoundError) as e:
            raise NativeUnavailable(
                f"native build could not run: {e}") from None


def load() -> ctypes.CDLL:
    """Load (building if needed) the native library."""
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        # incremental make keeps the .so in sync with the sources (a
        # no-op when up to date); a failed build is an error, never a
        # reason to load whatever library is lying around. An explicit
        # BRPC_TPU_NATIVE_SO override is loaded AS IS — the soak driver
        # builds its instrumented library itself.
        if "BRPC_TPU_NATIVE_SO" in os.environ:
            if not os.path.exists(_SO_PATH):
                raise NativeUnavailable(
                    "BRPC_TPU_NATIVE_SO points at a missing library: " +
                    _SO_PATH)
        else:
            _build()
        global _rss_at_load
        if _rss_at_load is None:
            _rss_at_load = _read_rss()
        lib = ctypes.CDLL(_SO_PATH)
        lib.nat_sched_start.argtypes = [ctypes.c_int]
        lib.nat_sched_start.restype = ctypes.c_int
        lib.nat_sched_stop.restype = None
        lib.nat_sched_workers.restype = ctypes.c_int
        lib.nat_sched_switches.restype = ctypes.c_uint64
        lib.nat_sched_trim_stacks.restype = ctypes.c_int
        lib.nat_bench_spawn_join.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.nat_bench_spawn_join.restype = ctypes.c_uint64
        lib.nat_bench_ping_pong.argtypes = [ctypes.c_int]
        lib.nat_bench_ping_pong.restype = ctypes.c_double
        lib.nat_wsq_selftest.restype = ctypes.c_int
        lib.nat_iobuf_selftest.restype = ctypes.c_int
        lib.nat_meta_selftest.restype = ctypes.c_int
        lib.nat_echo_server_start.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.nat_echo_server_start.restype = ctypes.c_int
        lib.nat_echo_server_stop.restype = None
        lib.nat_echo_server_requests.restype = ctypes.c_uint64
        lib.nat_echo_client_bench.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_double,
            ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.nat_echo_client_bench.restype = ctypes.c_double
        # -- native RPC runtime (framework path) --
        lib.nat_rpc_server_start.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib.nat_rpc_server_start.restype = ctypes.c_int
        lib.nat_rpc_server_stop.restype = None
        lib.nat_rpc_server_requests.restype = ctypes.c_uint64
        lib.nat_rpc_server_connections.restype = ctypes.c_uint64
        lib.nat_take_request.argtypes = [ctypes.c_int]
        lib.nat_take_request.restype = ctypes.c_void_p
        lib.nat_req_field.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_size_t)]
        lib.nat_req_field.restype = ctypes.c_void_p
        lib.nat_req_cid.argtypes = [ctypes.c_void_p]
        lib.nat_req_cid.restype = ctypes.c_int64
        lib.nat_req_aux.argtypes = [ctypes.c_void_p]
        lib.nat_req_aux.restype = ctypes.c_uint64
        lib.nat_req_compress.argtypes = [ctypes.c_void_p]
        lib.nat_req_compress.restype = ctypes.c_int32
        lib.nat_req_sock_id.argtypes = [ctypes.c_void_p]
        lib.nat_req_sock_id.restype = ctypes.c_uint64
        lib.nat_req_free.argtypes = [ctypes.c_void_p]
        lib.nat_req_free.restype = None
        lib.nat_req_kind.argtypes = [ctypes.c_void_p]
        lib.nat_req_kind.restype = ctypes.c_int32
        lib.nat_rpc_server_enable_raw_fallback.argtypes = [ctypes.c_int]
        lib.nat_rpc_server_enable_raw_fallback.restype = ctypes.c_int
        lib.nat_rpc_set_dispatchers.argtypes = [ctypes.c_int]
        lib.nat_rpc_set_dispatchers.restype = ctypes.c_int
        lib.nat_sock_write.argtypes = [
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_size_t]
        lib.nat_sock_write.restype = ctypes.c_int
        lib.nat_sock_set_failed.argtypes = [ctypes.c_uint64]
        lib.nat_sock_set_failed.restype = ctypes.c_int
        lib.nat_respond.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
            ctypes.c_size_t]
        lib.nat_respond.restype = ctypes.c_int
        lib.nat_channel_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int]
        lib.nat_channel_open.restype = ctypes.c_void_p
        lib.nat_channel_close.argtypes = [ctypes.c_void_p]
        lib.nat_channel_close.restype = None
        lib.nat_channel_call.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_char_p)]
        lib.nat_channel_call.restype = ctypes.c_int
        lib.nat_channel_call_full.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_char_p)]
        lib.nat_channel_call_full.restype = ctypes.c_int
        lib.nat_buf_free.argtypes = [ctypes.c_char_p]
        lib.nat_buf_free.restype = None
        lib.nat_rpc_client_bench.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_int, ctypes.POINTER(ctypes.c_uint64)]
        lib.nat_rpc_client_bench.restype = ctypes.c_double
        lib.nat_channel_acall.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_void_p,
            ctypes.c_void_p]
        lib.nat_channel_acall.restype = ctypes.c_int
        lib.nat_rpc_client_bench_async.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_int, ctypes.POINTER(ctypes.c_uint64)]
        lib.nat_rpc_client_bench_async.restype = ctypes.c_double
        lib.nat_rpc_use_io_uring.argtypes = [ctypes.c_int]
        lib.nat_rpc_use_io_uring.restype = ctypes.c_int
        lib.nat_ring_counters.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
        lib.nat_ring_counters.restype = None
        lib.nat_disp_count.argtypes = []
        lib.nat_disp_count.restype = ctypes.c_int
        lib.nat_disp_stat.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int)]
        lib.nat_disp_stat.restype = ctypes.c_int
        # -- native HTTP/1.1 lane --
        lib.nat_rpc_server_native_http.argtypes = [ctypes.c_int]
        lib.nat_rpc_server_native_http.restype = ctypes.c_int
        lib.nat_http_respond.argtypes = [
            ctypes.c_uint64, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_size_t, ctypes.c_int]
        lib.nat_http_respond.restype = ctypes.c_int
        lib.nat_sock_graceful_close.argtypes = [ctypes.c_uint64]
        lib.nat_sock_graceful_close.restype = ctypes.c_int
        lib.nat_grpc_respond.argtypes = [
            ctypes.c_uint64, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_size_t, ctypes.c_int, ctypes.c_char_p]
        lib.nat_grpc_respond.restype = ctypes.c_int
        lib.nat_rpc_server_ssl.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.nat_rpc_server_ssl.restype = ctypes.c_int
        # -- overload protection (nat_overload.cpp) --
        lib.nat_rpc_server_limiter.argtypes = [ctypes.c_char_p]
        lib.nat_rpc_server_limiter.restype = ctypes.c_int
        lib.nat_rpc_server_queue_deadline_ms.argtypes = [ctypes.c_int]
        lib.nat_rpc_server_queue_deadline_ms.restype = ctypes.c_int
        lib.nat_rpc_server_inflight.restype = ctypes.c_int
        lib.nat_rpc_server_limit.restype = ctypes.c_int
        # -- graceful quiesce/drain lifecycle (nat_quiesce.cpp) --
        lib.nat_server_quiesce.argtypes = [ctypes.c_int]
        lib.nat_server_quiesce.restype = ctypes.c_int
        lib.nat_server_draining.restype = ctypes.c_int
        # -- deterministic fault injection (nat_fault.cpp) --
        lib.nat_fault_configure.argtypes = [ctypes.c_char_p]
        lib.nat_fault_configure.restype = ctypes.c_int
        lib.nat_fault_enabled.restype = ctypes.c_int
        lib.nat_fault_injected.restype = ctypes.c_uint64
        # -- refcount-contract runtime twin (nat_refguard.cpp) --
        lib.nat_refguard_enabled.restype = ctypes.c_int
        lib.nat_refguard_ops.restype = ctypes.c_uint64
        lib.nat_refguard_selftest.argtypes = [ctypes.c_int]
        lib.nat_refguard_selftest.restype = ctypes.c_int
        # -- client circuit breaker + retry budget (nat_channel.cpp) --
        lib.nat_channel_set_breaker.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int]
        lib.nat_channel_set_breaker.restype = ctypes.c_int
        lib.nat_channel_breaker_state.argtypes = [ctypes.c_void_p]
        lib.nat_channel_breaker_state.restype = ctypes.c_int
        lib.nat_channel_retry_budget.argtypes = [ctypes.c_void_p]
        lib.nat_channel_retry_budget.restype = ctypes.c_int
        lib.nat_take_request_batch.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int]
        lib.nat_take_request_batch.restype = ctypes.c_int
        lib.nat_http_client_bench.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_size_t, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.nat_http_client_bench.restype = ctypes.c_double
        lib.nat_grpc_client_bench.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint64)]
        lib.nat_grpc_client_bench.restype = ctypes.c_double
        # -- native client lanes (HTTP/h2 through the framework client) --
        lib.nat_channel_open_proto.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_char_p]
        lib.nat_channel_open_proto.restype = ctypes.c_void_p
        lib.nat_http_call.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_int, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t)]
        lib.nat_http_call.restype = ctypes.c_int
        lib.nat_grpc_call.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_size_t, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_char_p)]
        lib.nat_grpc_call.restype = ctypes.c_int
        lib.nat_grpc_channel_bench.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint64)]
        lib.nat_grpc_channel_bench.restype = ctypes.c_double
        lib.nat_http_channel_bench.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint64)]
        lib.nat_http_channel_bench.restype = ctypes.c_double
        # -- native Redis lane --
        lib.nat_rpc_server_redis.argtypes = [ctypes.c_int]
        lib.nat_rpc_server_redis.restype = ctypes.c_int
        lib.nat_redis_respond.argtypes = [
            ctypes.c_uint64, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_size_t]
        lib.nat_redis_respond.restype = ctypes.c_int
        lib.nat_redis_client_bench.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.POINTER(ctypes.c_uint64)]
        lib.nat_redis_client_bench.restype = ctypes.c_double
        # -- shm usercode worker lane --
        lib.nat_shm_lane_create.argtypes = [ctypes.c_size_t]
        lib.nat_shm_lane_create.restype = ctypes.c_int
        lib.nat_shm_lane_name.restype = ctypes.c_char_p
        lib.nat_shm_lane_enable.argtypes = [ctypes.c_int]
        lib.nat_shm_lane_enable.restype = ctypes.c_int
        lib.nat_shm_seg_validate.argtypes = [ctypes.c_void_p,
                                             ctypes.c_size_t]
        lib.nat_shm_seg_validate.restype = ctypes.c_int
        lib.nat_shm_worker_attach.argtypes = [ctypes.c_char_p]
        lib.nat_shm_worker_attach.restype = ctypes.c_int
        lib.nat_shm_take_request.argtypes = [ctypes.c_int]
        lib.nat_shm_take_request.restype = ctypes.c_void_p
        lib.nat_shm_respond.argtypes = [
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_size_t, ctypes.c_int32, ctypes.c_char_p, ctypes.c_int]
        lib.nat_shm_respond.restype = ctypes.c_int
        lib.nat_shm_lane_set_timeout_ms.argtypes = [ctypes.c_int]
        lib.nat_shm_lane_set_timeout_ms.restype = ctypes.c_int
        lib.nat_shm_lane_workers.restype = ctypes.c_int
        lib.nat_shm_lane_max_workers.restype = ctypes.c_int
        lib.nat_shm_lane_recover_probe.restype = ctypes.c_int
        lib.nat_shm_push_tensor.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64]
        lib.nat_shm_push_tensor.restype = ctypes.c_int
        # -- tensor fabric (producer slots + receiver leases, ISSUE 15) --
        lib.nat_shm_producer_attach.argtypes = [ctypes.c_char_p]
        lib.nat_shm_producer_attach.restype = ctypes.c_int
        lib.nat_shm_fabric_push.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64]
        lib.nat_shm_fabric_push.restype = ctypes.c_int
        lib.nat_shm_fabric_take.argtypes = [ctypes.c_int]
        lib.nat_shm_fabric_take.restype = ctypes.c_void_p
        lib.nat_shm_push_bench.argtypes = [
            ctypes.c_size_t, ctypes.c_double,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.nat_shm_push_bench.restype = ctypes.c_double
        lib.nat_shm_worker_drain_bench.argtypes = [ctypes.c_int]
        lib.nat_shm_worker_drain_bench.restype = ctypes.c_uint64
        # -- native observability (nat_stats.cpp: per-thread stat cells,
        #    log2 latency histograms, rpcz span ring) --
        lib.nat_stats_counter_count.restype = ctypes.c_int
        lib.nat_stats_counter_name.argtypes = [ctypes.c_int]
        lib.nat_stats_counter_name.restype = ctypes.c_char_p
        lib.nat_stats_counters.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
        lib.nat_stats_counters.restype = ctypes.c_int
        lib.nat_stats_counter_bump.argtypes = [ctypes.c_char_p,
                                               ctypes.c_uint64]
        lib.nat_stats_counter_bump.restype = ctypes.c_int
        lib.nat_stats_lane_count.restype = ctypes.c_int
        lib.nat_stats_lane_name.argtypes = [ctypes.c_int]
        lib.nat_stats_lane_name.restype = ctypes.c_char_p
        lib.nat_stats_hist_nbuckets.restype = ctypes.c_int
        lib.nat_stats_hist.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
        lib.nat_stats_hist.restype = ctypes.c_int
        lib.nat_stats_hist_quantile.argtypes = [ctypes.c_int,
                                                ctypes.c_double]
        lib.nat_stats_hist_quantile.restype = ctypes.c_double
        lib.nat_stats_enable_spans.argtypes = [ctypes.c_int]
        lib.nat_stats_enable_spans.restype = None
        lib.nat_stats_drain_spans.argtypes = [ctypes.POINTER(NatSpanRec),
                                              ctypes.c_int]
        lib.nat_stats_drain_spans.restype = ctypes.c_int
        lib.nat_stats_reset.restype = None
        lib.nat_stats_now_ns.restype = ctypes.c_uint64
        # -- native observatory: per-method stats, /connections rows,
        #    lock-contention profiler (ISSUE 9) --
        lib.nat_method_stats.argtypes = [ctypes.POINTER(NatMethodStatRow),
                                         ctypes.c_int]
        lib.nat_method_stats.restype = ctypes.c_int
        lib.nat_method_quantile.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                            ctypes.c_double]
        lib.nat_method_quantile.restype = ctypes.c_double
        # -- fleet observatory: raw mergeable buckets + the wire snapshot
        #    behind builtin.stats (ISSUE 16) --
        lib.nat_method_hist.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                        ctypes.POINTER(ctypes.c_uint64),
                                        ctypes.c_int]
        lib.nat_method_hist.restype = ctypes.c_int
        lib.nat_stats_snapshot.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t)]
        lib.nat_stats_snapshot.restype = ctypes.c_int
        lib.nat_conn_snapshot.argtypes = [ctypes.POINTER(NatConnRow),
                                          ctypes.c_int]
        lib.nat_conn_snapshot.restype = ctypes.c_int
        # -- native memory observatory (nat_res.cpp, ISSUE 14) --
        lib.nat_res_count.restype = ctypes.c_int
        lib.nat_res_name.argtypes = [ctypes.c_int]
        lib.nat_res_name.restype = ctypes.c_char_p  # static string
        lib.nat_res_stats.argtypes = [ctypes.POINTER(NatResRow),
                                      ctypes.c_int]
        lib.nat_res_stats.restype = ctypes.c_int
        lib.nat_res_accounted_bytes.restype = ctypes.c_uint64
        lib.nat_res_prof_start.argtypes = [ctypes.c_int, ctypes.c_uint64]
        lib.nat_res_prof_start.restype = ctypes.c_int
        lib.nat_res_prof_stop.restype = ctypes.c_int
        lib.nat_res_prof_running.restype = ctypes.c_int
        lib.nat_res_prof_samples.restype = ctypes.c_uint64
        lib.nat_res_prof_reset.restype = None
        lib.nat_res_heap_report.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t)]
        lib.nat_res_heap_report.restype = ctypes.c_int
        lib.nat_res_growth_baseline.restype = ctypes.c_int
        lib.nat_res_growth_report.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t)]
        lib.nat_res_growth_report.restype = ctypes.c_int
        lib.nat_res_selftest.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.nat_res_selftest.restype = ctypes.c_int
        lib.nat_mu_prof_start.argtypes = [ctypes.c_int, ctypes.c_int,
                                          ctypes.c_uint64]
        lib.nat_mu_prof_start.restype = ctypes.c_int
        lib.nat_mu_prof_stop.restype = ctypes.c_int
        lib.nat_mu_prof_running.restype = ctypes.c_int
        lib.nat_mu_prof_samples.restype = ctypes.c_uint64
        lib.nat_mu_prof_reset.restype = None
        lib.nat_mu_prof_reset_samples.restype = None
        lib.nat_mu_prof_report.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t)]
        lib.nat_mu_prof_report.restype = ctypes.c_int
        lib.nat_mu_rank_stats.argtypes = [ctypes.POINTER(NatLockRankRow),
                                          ctypes.c_int]
        lib.nat_mu_rank_stats.restype = ctypes.c_int
        lib.nat_mu_rank_name.argtypes = [ctypes.c_int]
        lib.nat_mu_rank_name.restype = ctypes.c_char_p  # static string
        lib.nat_mu_contend_selftest.argtypes = [ctypes.c_int, ctypes.c_int,
                                                ctypes.c_int]
        lib.nat_mu_contend_selftest.restype = ctypes.c_uint64
        # -- traffic flight recorder (nat_dump.cpp / nat_replay.cpp) --
        lib.nat_dump_start.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_uint64]
        lib.nat_dump_start.restype = ctypes.c_int
        lib.nat_dump_stop.restype = ctypes.c_int
        lib.nat_dump_running.restype = ctypes.c_int
        lib.nat_dump_status.argtypes = [ctypes.POINTER(NatDumpStatusRec)]
        lib.nat_dump_status.restype = ctypes.c_int
        lib.nat_replay_run.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_double, ctypes.c_double, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(NatReplayResult)]
        lib.nat_replay_run.restype = ctypes.c_int
        # -- native fan-out cluster (nat_cluster.cpp / nat_lb.cpp) --
        lib.nat_rpc_server_add_port.argtypes = [ctypes.c_char_p,
                                                ctypes.c_int]
        lib.nat_rpc_server_add_port.restype = ctypes.c_int
        lib.nat_rpc_server_remove_port.argtypes = [ctypes.c_int]
        lib.nat_rpc_server_remove_port.restype = ctypes.c_int
        lib.nat_cluster_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib.nat_cluster_create.restype = ctypes.c_void_p
        lib.nat_cluster_close.argtypes = [ctypes.c_void_p]
        lib.nat_cluster_close.restype = None
        lib.nat_cluster_update.argtypes = [ctypes.c_void_p,
                                           ctypes.c_char_p]
        lib.nat_cluster_update.restype = ctypes.c_int
        lib.nat_cluster_backend_count.argtypes = [ctypes.c_void_p]
        lib.nat_cluster_backend_count.restype = ctypes.c_int
        lib.nat_cluster_select_debug.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_size_t]
        lib.nat_cluster_select_debug.restype = ctypes.c_int
        lib.nat_cluster_call.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
            ctypes.c_uint64, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_char_p)]
        lib.nat_cluster_call.restype = ctypes.c_int
        lib.nat_cluster_parallel_call.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int)]
        lib.nat_cluster_parallel_call.restype = ctypes.c_int
        lib.nat_cluster_partition_call.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int)]
        lib.nat_cluster_partition_call.restype = ctypes.c_int
        lib.nat_cluster_dynpart_call.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int)]
        lib.nat_cluster_dynpart_call.restype = ctypes.c_int
        lib.nat_cluster_dynpart_debug.argtypes = [
            ctypes.c_void_p, ctypes.c_double,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
        lib.nat_cluster_dynpart_debug.restype = ctypes.c_int
        lib.nat_cluster_stats.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(NatClusterRow),
                                          ctypes.c_int]
        lib.nat_cluster_stats.restype = ctypes.c_int
        lib.nat_cluster_bench.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_int, ctypes.c_int, ctypes.c_double, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_double)]
        lib.nat_cluster_bench.restype = ctypes.c_double
        # -- trace context + in-process sampling profiler (nat_prof.cpp) --
        lib.nat_trace_set.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
        lib.nat_trace_set.restype = None
        lib.nat_prof_start.argtypes = [ctypes.c_int]
        lib.nat_prof_start.restype = ctypes.c_int
        lib.nat_prof_stop.restype = ctypes.c_int
        lib.nat_prof_running.restype = ctypes.c_int
        lib.nat_prof_samples.restype = ctypes.c_uint64
        lib.nat_prof_reset.restype = None
        lib.nat_prof_report.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t)]
        lib.nat_prof_report.restype = ctypes.c_int
        # -- parser fuzz seams (nat_fuzz_entry.cpp / nat_replay.cpp) --
        lib.nat_fuzz_rpc_meta.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.nat_fuzz_rpc_meta.restype = ctypes.c_int
        lib.nat_fuzz_http.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.nat_fuzz_http.restype = ctypes.c_int
        lib.nat_fuzz_h2.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.nat_fuzz_h2.restype = ctypes.c_int
        lib.nat_fuzz_redis.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.nat_fuzz_redis.restype = ctypes.c_int
        lib.nat_fuzz_hpack.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.nat_fuzz_hpack.restype = ctypes.c_int
        lib.nat_fuzz_recordio.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.nat_fuzz_recordio.restype = ctypes.c_int
        lib.nat_fuzz_shm_seg.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.nat_fuzz_shm_seg.restype = ctypes.c_int
        _lib = lib
        return lib


def available() -> bool:
    try:
        load()
        return True
    except NativeUnavailable:
        return False


# -- convenience wrappers --------------------------------------------------

def sched_start(nworkers: int = 4) -> int:
    return load().nat_sched_start(nworkers)


def sched_stop():
    load().nat_sched_stop()


def bench_spawn_join(nfibers: int, rounds: int) -> int:
    return load().nat_bench_spawn_join(nfibers, rounds)


def bench_ping_pong(rounds: int = 10000) -> float:
    """Returns ns per fiber ping-pong round trip."""
    return load().nat_bench_ping_pong(rounds)


def echo_server_start(ip: str = "127.0.0.1", port: int = 0) -> int:
    """Starts the native echo server; returns the bound port."""
    rc = load().nat_echo_server_start(ip.encode(), port)
    if rc <= 0:
        raise RuntimeError("native echo server failed to start")
    return rc


def echo_server_stop():
    load().nat_echo_server_stop()


def echo_server_requests() -> int:
    return load().nat_echo_server_requests()


def echo_client_bench(ip: str, port: int, nconn: int = 2,
                      seconds: float = 2.0, payload: int = 16,
                      pipeline: int = 32) -> dict:
    out_requests = ctypes.c_uint64(0)
    qps = load().nat_echo_client_bench(ip.encode(), port, nconn, seconds,
                                       payload, pipeline,
                                       ctypes.byref(out_requests))
    return {"qps": qps, "requests": out_requests.value}


# -- native RPC runtime (framework path: Socket/dispatcher/messenger on
#    fibers + IOBuf; see native/src/nat_rpc.cpp) -----------------------------

def rpc_server_start(ip: str = "127.0.0.1", port: int = 0,
                     nworkers: int = 0, native_echo: bool = False) -> int:
    """Start the native RPC server; returns the bound port."""
    rc = load().nat_rpc_server_start(ip.encode(), port, nworkers,
                                     1 if native_echo else 0)
    if rc <= 0:
        raise RuntimeError("native rpc server failed to start")
    return rc


def use_io_uring(enable: bool = True) -> int:
    """Toggle the RingListener datapath (the fork's -use_io_uring). Returns
    1 = ring live, 0 = kernel refused (epoll stays), -1 = runtime error."""
    return load().nat_rpc_use_io_uring(1 if enable else 0)


def ring_counters():
    """(recv_completions, send_completions) of the io_uring datapath."""
    recv = ctypes.c_uint64()
    send = ctypes.c_uint64()
    load().nat_ring_counters(ctypes.byref(recv), ctypes.byref(send))
    return recv.value, send.value


def dispatcher_count() -> int:
    """Number of epoll/io_uring dispatcher loops in the pool (the
    event_dispatcher_num analog; default min(cores, 4))."""
    return load().nat_disp_count()


def dispatcher_stats() -> list:
    """Per-dispatcher rows: [{'sockets': owned-now, 'wakeups': rounds
    that delivered events, 'sqpoll': -1 no ring / 0 / 1}, ...]."""
    lib = load()
    rows = []
    for i in range(lib.nat_disp_count()):
        sockets = ctypes.c_uint64()
        wakeups = ctypes.c_uint64()
        sqpoll = ctypes.c_int()
        if lib.nat_disp_stat(i, ctypes.byref(sockets), ctypes.byref(wakeups),
                             ctypes.byref(sqpoll)) != 0:
            break
        rows.append({"sockets": sockets.value, "wakeups": wakeups.value,
                     "sqpoll": sqpoll.value})
    return rows


def rpc_server_stop():
    load().nat_rpc_server_stop()


def rpc_server_requests() -> int:
    return load().nat_rpc_server_requests()


def take_request(timeout_ms: int = 100):
    """Python lane: pull one item handed off by the native runtime.
    Returns (handle, kind, meta_bytes, payload, attachment, sock_id, seq,
    f0, f1, aux) or None. kind 0 = parsed tpu_std request; 1 = raw
    protocol bytes (seq orders chunks per socket); 2 = connection closed;
    3 = native-parsed HTTP request (f0 = verb, f1 = uri, meta_bytes =
    lowercased "key: value\\n" header lines, payload = body, seq = the
    connection-ordered response token for http_respond); 4 =
    native-parsed gRPC request (f1 = :path, payload = gRPC-framed body,
    seq = h2 stream id); 5 = streaming frame (aux = dest stream id,
    payload = frame body, seq orders frames per socket). aux is 0 except
    for kind 5."""
    lib = load()
    h = lib.nat_take_request(timeout_ms)
    if not h:
        return None
    kind = lib.nat_req_kind(h)
    def field(which):
        n = ctypes.c_size_t(0)
        p = lib.nat_req_field(h, which, ctypes.byref(n))
        return ctypes.string_at(p, n.value) if p and n.value else b""
    if kind in (3, 4):  # native-parsed HTTP / gRPC-over-h2
        return (h, kind, field(4), field(2), b"",
                lib.nat_req_sock_id(h), lib.nat_req_cid(h),
                field(0), field(1), 0)
    if kind == 5:  # native-cut streaming frame: aux = dest stream id,
        # f0 = frame type (same contract as take_requests), cid = order
        return (h, kind, b"", field(2), b"", lib.nat_req_sock_id(h),
                lib.nat_req_cid(h), lib.nat_req_compress(h), b"",
                lib.nat_req_aux(h))
    return (h, kind, field(4), field(2), field(3),
            lib.nat_req_sock_id(h), lib.nat_req_cid(h), b"", b"", 0)


def take_requests(max_items: int = 16, timeout_ms: int = 100):
    """Batch take: one condvar round + one FFI crossing per burst. Returns
    a list of the same tuples take_request yields (possibly empty)."""
    lib = load()
    arr = (ctypes.c_void_p * max_items)()
    n = lib.nat_take_request_batch(arr, max_items, timeout_ms)
    out = []
    for i in range(n):
        h = arr[i]
        kind = lib.nat_req_kind(h)

        def field(which, h=h):
            ln = ctypes.c_size_t(0)
            p = lib.nat_req_field(h, which, ctypes.byref(ln))
            return ctypes.string_at(p, ln.value) if p and ln.value else b""

        if kind in (3, 4):
            out.append((h, kind, field(4), field(2), b"",
                        lib.nat_req_sock_id(h), lib.nat_req_cid(h),
                        field(0), field(1), 0))
        elif kind == 5:
            # frame type rides in the f0 slot (the zero-copy path below
            # hands the handle to a finalizer, so it can't be queried
            # at dispatch time)
            ftype = lib.nat_req_compress(h)
            ln = ctypes.c_size_t(0)
            p = lib.nat_req_field(h, 2, ctypes.byref(ln))
            if p and ln.value >= 65536:
                # big stream payload: wrap the native buffer read-only
                # with ZERO copy; the request handle is freed when the
                # last view of the buffer is garbage-collected, so a
                # handler retaining the message stays safe. The handle
                # slot in the tuple is None: ownership moved here.
                cbuf = (ctypes.c_char * ln.value).from_address(p)
                weakref.finalize(cbuf, lib.nat_req_free, h)
                payload = memoryview(cbuf).toreadonly()
                out.append((None, kind, b"", payload, b"",
                            lib.nat_req_sock_id(h), lib.nat_req_cid(h),
                            ftype, b"", lib.nat_req_aux(h)))
                continue
            out.append((h, kind, b"", field(2), b"",
                        lib.nat_req_sock_id(h), lib.nat_req_cid(h),
                        ftype, b"", lib.nat_req_aux(h)))
        else:
            out.append((h, kind, field(4), field(2), field(3),
                        lib.nat_req_sock_id(h), lib.nat_req_cid(h),
                        b"", b"", 0))
    return out


def rpc_server_enable_raw_fallback(enable: bool = True) -> int:
    """Multi-protocol native port: unknown framing goes to the Python
    protocol stack as ordered raw chunks instead of failing the socket."""
    return load().nat_rpc_server_enable_raw_fallback(1 if enable else 0)


def rpc_set_dispatchers(n: int) -> int:
    """-event_dispatcher_num analog; call before the runtime starts."""
    return load().nat_rpc_set_dispatchers(n)


def req_free(handle):
    load().nat_req_free(handle)


def sock_write(sock_id: int, data: bytes) -> int:
    return load().nat_sock_write(sock_id, data, len(data))


def sock_set_failed(sock_id: int) -> int:
    return load().nat_sock_set_failed(sock_id)


def sock_graceful_close(sock_id: int) -> int:
    """Fail the socket once queued writes drain (FIN after the last
    response byte) — Connection: close semantics."""
    return load().nat_sock_graceful_close(sock_id)


def rpc_server_ssl(certfile: str, keyfile: str) -> int:
    """TLS on the native port (Socket-level SSLState role): connections
    whose first record is a TLS handshake get a native SSL session; the
    same port keeps answering plaintext. 0 = ok, -2 = libssl missing."""
    return load().nat_rpc_server_ssl(certfile.encode(), keyfile.encode())


def rpc_server_native_http(enable: bool = True) -> int:
    """Native HTTP/1.1 lane: HTTP-shaped connections parse in the native
    cut loop and surface as kind-3 py-lane requests."""
    return load().nat_rpc_server_native_http(1 if enable else 0)


def grpc_respond(sock_id: int, stream_id: int, payload: bytes = b"",
                 grpc_status: int = 0, grpc_message: str = "") -> int:
    """Answer a kind-4 request: unary gRPC response framed natively
    (HEADERS + DATA + grpc-status trailers) onto the h2 session."""
    return load().nat_grpc_respond(sock_id, stream_id, payload,
                                   len(payload), grpc_status,
                                   grpc_message.encode() or None)


def http_respond(sock_id: int, seq: int, data: bytes,
                 close_after: bool = False) -> int:
    """Answer a kind-3 request: data is the complete serialized HTTP
    response; ordering across pipelined requests is enforced natively."""
    return load().nat_http_respond(sock_id, seq, data, len(data),
                                   1 if close_after else 0)


def fault_configure(spec: str = "") -> int:
    """Install (or clear, with "") the deterministic fault table — see
    native/src/nat_fault.h for the grammar. 0 = ok, -1 = parse error.
    Same seed + same per-site op sequence = same fault schedule. The
    NAT_FAULT env var arms the table at library load (workers inherit
    it); restore the env spec with fault_configure(os.environ.get(
    "NAT_FAULT", ""))."""
    return load().nat_fault_configure(spec.encode() or None)


def fault_enabled() -> bool:
    return bool(load().nat_fault_enabled())


def fault_injected() -> int:
    """Total faults injected in THIS process since load (also exported
    as the nat_faults_injected counter)."""
    return load().nat_fault_injected()


def refguard_enabled() -> bool:
    """True when the loaded .so was built with -DNAT_REFGUARD (the
    NAT_REF_* ownership ledger of native/src/nat_refown.h is live —
    `make -C native refguard` + the BRPC_TPU_NATIVE_SO override)."""
    return bool(load().nat_refguard_enabled())


def refguard_ops() -> int:
    """Total refguard ledger operations recorded (0 in normal builds)."""
    return load().nat_refguard_ops()


def refguard_selftest(scenario: int = 0) -> int:
    """Scenario 0: balanced acquire/transfer/borrow/release/dead round
    (returns 0 in every build). Scenario 1: deliberate double release —
    ABORTS the process under refguard, returns -1 otherwise."""
    return load().nat_refguard_selftest(scenario)


def rpc_server_limiter(spec: str = "") -> int:
    """Native server admission control: "" / "none" = off, "auto" =
    gradient limiter (concurrency_limiter.py's AutoLimiter ported to the
    C++ lane), "constant:N" / "N" = fixed limit. Rejections answer
    ELIMIT(2004) / HTTP 503 / gRPC RESOURCE_EXHAUSTED on the wire."""
    return load().nat_rpc_server_limiter(spec.encode() or None)


def rpc_server_queue_deadline_ms(ms: int) -> int:
    """Queue-deadline drop: py-lane requests older than `ms` when a
    worker would take them are rejected with ELIMIT before dispatch
    (bounded accepted-request tail latency). <= 0 disables."""
    return load().nat_rpc_server_queue_deadline_ms(ms)


def rpc_server_inflight() -> int:
    """Currently admitted in-flight work requests (observability)."""
    return load().nat_rpc_server_inflight()


def rpc_server_limit() -> int:
    """Effective concurrency limit (auto: the computed one); 0 = off."""
    return load().nat_rpc_server_limit()


def server_quiesce(timeout_ms: int = 5000) -> int:
    """Graceful quiesce of the running native server (the Server::Stop
    (timeout)/Join lifecycle): stop accepting, lame-duck every live
    connection per protocol (h2 GOAWAY, HTTP Connection: close, tpu_std
    SHUTDOWN meta bit, RESP close-after-reply), drain admitted work
    (incl. shm-worker in-flight) under the deadline while rejecting new
    arrivals with ELIMIT/503/RESOURCE_EXHAUSTED, then close sockets only
    once their write stacks are idle. Returns 0 (drained clean), 1
    (deadline expired — stragglers were 503'd), -1 (no running server).
    Call rpc_server_stop() afterwards."""
    return load().nat_server_quiesce(timeout_ms)


def server_draining() -> bool:
    """True from quiesce start until the server stops/restarts."""
    return bool(load().nat_server_draining())


def channel_set_breaker(handle, enable: bool = True) -> int:
    """Per-channel circuit breaker (two-EMA-window isolation mirroring
    rpc/circuit_breaker.py): errored completions trip it, the socket is
    failed, calls fail fast through the isolation window, and the
    health-check chain revives + resets it once the peer answers."""
    return load().nat_channel_set_breaker(handle, 1 if enable else 0)


def channel_breaker_state(handle) -> int:
    """0 = closed (healthy), 1 = broken (isolated/awaiting revival)."""
    return load().nat_channel_breaker_state(handle)


def channel_retry_budget(handle) -> int:
    """Remaining channel retry budget in deci-tokens (a retry costs 10;
    every success replenishes 1, capped)."""
    return load().nat_channel_retry_budget(handle)


def rpc_server_redis(mode: int = 1) -> int:
    """Native Redis lane: 1 = RESP parsed natively, commands to the
    Python RedisService (kind-6); 2 = + native in-memory store for the
    GET/SET family."""
    return load().nat_rpc_server_redis(mode)


def redis_respond(sock_id: int, seq: int, data: bytes) -> int:
    """Answer a kind-6 request: data is the complete RESP reply;
    ordering across pipelined commands is enforced natively."""
    return load().nat_redis_respond(sock_id, seq, data, len(data))


def redis_client_bench(ip: str, port: int, nconn: int = 2,
                       pipeline: int = 64, seconds: float = 2.0) -> dict:
    """Raw RESP pipelined GET load against the native redis lane."""
    out_requests = ctypes.c_uint64(0)
    qps = load().nat_redis_client_bench(ip.encode(), port, nconn, pipeline,
                                        seconds,
                                        ctypes.byref(out_requests))
    return {"qps": qps, "requests": out_requests.value}


def grpc_client_bench(ip: str, port: int, nconn: int = 4,
                      window: int = 64, seconds: float = 2.0,
                      path: str = "/EchoService/Echo",
                      payload: bytes = b"x" * 16) -> dict:
    """gRPC-over-h2 bench client (minimal native h2 client, `window`
    concurrent unary streams per connection)."""
    out_requests = ctypes.c_uint64(0)
    qps = load().nat_grpc_client_bench(ip.encode(), port, nconn, window,
                                       seconds, path.encode(), payload,
                                       len(payload),
                                       ctypes.byref(out_requests))
    return {"qps": qps, "requests": out_requests.value}


def http_client_bench(ip: str, port: int, nconn: int = 4,
                      pipeline: int = 32, seconds: float = 2.0,
                      path: str = "/echo", post_body: bytes = b"",
                      content_type: str = "application/octet-stream"
                      ) -> dict:
    """HTTP bench client (blocking sockets, pipelined keep-alive).
    Empty post_body = GET, else POST with that body."""
    if isinstance(post_body, int):  # tolerate the byte-count shorthand
        post_body = b"x" * post_body
    out_requests = ctypes.c_uint64(0)
    qps = load().nat_http_client_bench(ip.encode(), port, nconn, pipeline,
                                       seconds, path.encode(), post_body,
                                       len(post_body),
                                       content_type.encode(),
                                       ctypes.byref(out_requests))
    return {"qps": qps, "requests": out_requests.value}


def respond(handle, error_code: int = 0, error_text: str = "",
            payload: bytes = b"", attachment: bytes = b"") -> int:
    """Python lane: answer a request taken with take_request."""
    return load().nat_respond(handle, error_code,
                              error_text.encode() or None,
                              payload, len(payload),
                              attachment, len(attachment))


def channel_open(ip: str, port: int, batch_writes: bool = False,
                 connect_timeout_ms: int = 0, health_check_ms: int = 0):
    """Open a native client channel. connect_timeout_ms bounds the dial
    (0 = 10s guard); health_check_ms > 0 revives a failed connection in
    the background, and any call after failure re-dials on demand."""
    h = load().nat_channel_open(ip.encode(), port, 0,
                                1 if batch_writes else 0,
                                connect_timeout_ms, health_check_ms)
    if not h:
        raise RuntimeError("native channel connect failed")
    return h


def channel_close(handle):
    load().nat_channel_close(handle)


def channel_open_http(ip: str, port: int, authority: str = "",
                      connect_timeout_ms: int = 0,
                      health_check_ms: int = 0):
    """Open a native HTTP/1.1 client channel (the client half of the
    native HTTP lane: request framing, pipelined response correlation,
    chunked decode — all in C++)."""
    h = load().nat_channel_open_proto(
        ip.encode(), port, 0, 0, connect_timeout_ms, health_check_ms, 1,
        authority.encode() or None)
    if not h:
        raise RuntimeError("native http channel connect failed")
    return h


def channel_open_grpc(ip: str, port: int, authority: str = "",
                      connect_timeout_ms: int = 0,
                      health_check_ms: int = 0):
    """Open a native h2/gRPC client channel (preface + SETTINGS + HPACK
    + flow-controlled unary streams in C++)."""
    h = load().nat_channel_open_proto(
        ip.encode(), port, 0, 0, connect_timeout_ms, health_check_ms, 2,
        authority.encode() or None)
    if not h:
        raise RuntimeError("native grpc channel connect failed")
    return h


def http_call(handle, verb: str, path: str, body: bytes = b"",
              headers: str = "", timeout_ms: int = 0):
    """Synchronous HTTP call through the native client lane. Returns
    (status, body_bytes); raises on transport errors. `headers` is raw
    "Name: value\\r\\n" lines appended to the request head."""
    lib = load()
    status = ctypes.c_int(0)
    resp = ctypes.c_char_p()
    rlen = ctypes.c_size_t(0)
    rc = lib.nat_http_call(handle, verb.encode(), path.encode(),
                           headers.encode() or None, body, len(body),
                           timeout_ms, ctypes.byref(status),
                           ctypes.byref(resp), ctypes.byref(rlen))
    if rc != 0:
        raise ConnectionError(f"native http call failed: rc={rc}")
    # pointer truthiness only: .value would strlen an un-terminated
    # malloc'd buffer (out-of-bounds read)
    out = b""
    if resp:
        out = ctypes.string_at(resp, rlen.value)
        lib.nat_buf_free(resp)
    return status.value, out


def grpc_call(handle, path: str, payload: bytes = b"",
              timeout_ms: int = 0):
    """Synchronous gRPC unary call through the native h2 client lane.
    Returns (grpc_status, response_bytes, message); raises on transport
    errors."""
    lib = load()
    st = ctypes.c_int(-1)
    resp = ctypes.c_char_p()
    rlen = ctypes.c_size_t(0)
    err = ctypes.c_char_p()
    rc = lib.nat_grpc_call(handle, path.encode(), payload, len(payload),
                           timeout_ms, ctypes.byref(st), ctypes.byref(resp),
                           ctypes.byref(rlen), ctypes.byref(err))
    # err IS NUL-terminated (malloc'd c_str copy); resp is NOT — only
    # pointer truthiness + string_at(len) may touch it
    message = ""
    if err:
        message = ctypes.string_at(err).decode(errors="replace")
        lib.nat_buf_free(err)
    if rc != 0:
        raise ConnectionError(
            f"native grpc call failed: {message or f'rc={rc}'}")
    out = b""
    if resp:
        out = ctypes.string_at(resp, rlen.value)
        lib.nat_buf_free(resp)
    return st.value, out, message


def grpc_channel_bench(ip: str, port: int, nconn: int = 4,
                       window: int = 64, seconds: float = 2.0,
                       path: str = "/EchoService/Echo",
                       payload: bytes = b"x" * 16) -> dict:
    """gRPC through the REAL native client lane (NatChannel + h2 session),
    `window` async unary calls in flight per connection."""
    out_requests = ctypes.c_uint64(0)
    qps = load().nat_grpc_channel_bench(ip.encode(), port, nconn, window,
                                        seconds, path.encode(), payload,
                                        len(payload),
                                        ctypes.byref(out_requests))
    return {"qps": qps, "requests": out_requests.value}


def http_channel_bench(ip: str, port: int, nconn: int = 4,
                       window: int = 64, seconds: float = 2.0,
                       path: str = "/echo", body: bytes = b"x" * 16) -> dict:
    """HTTP through the REAL native client lane (NatChannel + pipelined
    FIFO correlation), `window` async calls in flight per connection."""
    out_requests = ctypes.c_uint64(0)
    qps = load().nat_http_channel_bench(ip.encode(), port, nconn, window,
                                        seconds, path.encode(), body,
                                        len(body),
                                        ctypes.byref(out_requests))
    return {"qps": qps, "requests": out_requests.value}


ACALL_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_int32,
                            ctypes.POINTER(ctypes.c_char), ctypes.c_size_t)

_acall_live = {}  # id -> CFUNCTYPE thunk, alive until its done fires
_acall_live_lock = threading.Lock()


def channel_acall(handle, service: str, method: str, payload: bytes,
                  done, timeout_ms: int = 0) -> int:
    """Asynchronous call: done(error_code, response_bytes) runs on a
    framework FIBER (256KB stack) when the response arrives — keep it
    lightweight and non-blocking, exactly like a brpc done closure with
    usercode_in_pthread off; heavy work belongs on your own thread (hand
    off via a queue). Returns 0 when done WILL fire exactly once
    (possibly already, with an error code) — including failures detected
    before queueing, which also surface through done. The wrapper owns
    the callback thunk's lifetime."""
    holder = []

    def trampoline(_arg, code, resp, n):
        try:
            done(code, ctypes.string_at(resp, n) if n else b"")
        finally:
            with _acall_live_lock:
                _acall_live.pop(holder[0], None)

    cb = ACALL_CB(trampoline)
    holder.append(id(cb))
    with _acall_live_lock:
        _acall_live[id(cb)] = cb  # native side holds no GC-visible ref
    rc = load().nat_channel_acall(handle, service.encode(), method.encode(),
                                  payload, len(payload), timeout_ms, cb,
                                  None)
    if rc != 0:  # never queued: done will not fire
        with _acall_live_lock:
            _acall_live.pop(id(cb), None)
    return rc


def channel_call(handle, service: str, method: str,
                 payload: bytes = b"", timeout_ms: int = 0,
                 max_retry: int = 0, backup_ms: int = 0):
    """Synchronous call through the native client. timeout_ms > 0 arms a
    native deadline covering ALL attempts (ERPCTIMEDOUT on expiry);
    max_retry re-attempts failed-socket calls with on-demand re-dial;
    backup_ms > 0 re-sends the request if no response arrived in time
    (same correlation id — first response wins). Returns
    (error_code, response_bytes, error_text)."""
    lib = load()
    resp = ctypes.c_char_p()
    rlen = ctypes.c_size_t(0)
    err = ctypes.c_char_p()
    rc = lib.nat_channel_call_full(handle, service.encode(),
                                   method.encode(),
                                   payload, len(payload), timeout_ms,
                                   max_retry, backup_ms,
                                   ctypes.byref(resp),
                                   ctypes.byref(rlen), ctypes.byref(err))
    body = b""
    if resp:
        body = ctypes.string_at(resp, rlen.value)
        lib.nat_buf_free(resp)
    text = ""
    if err:
        text = ctypes.string_at(err).decode(errors="replace")
        lib.nat_buf_free(err)
    return rc, body, text


def rpc_client_bench(ip: str, port: int, nconn: int = 2,
                     fibers_per_conn: int = 32, seconds: float = 2.0,
                     payload: int = 16) -> dict:
    """Framework-path echo benchmark: sync calls from fibers through the
    full native client+server stack."""
    out_requests = ctypes.c_uint64(0)
    qps = load().nat_rpc_client_bench(ip.encode(), port, nconn,
                                      fibers_per_conn, seconds, payload,
                                      ctypes.byref(out_requests))
    return {"qps": qps, "requests": out_requests.value}


# -- shm descriptor-ring lane (nat_shm_lane.cpp) ----------------------------

def shm_push_bench(record_bytes: int, seconds: float = 1.0) -> dict:
    """Parent-side descriptor-ring throughput probe: push fixed-size
    records into the blob arena against live worker drains. Returns
    {"GBps": float, "records": int}. Requires a created lane with at
    least one attached worker (see nat_shm_worker_attach /
    shm_worker_drain_bench)."""
    out = ctypes.c_uint64(0)
    gbps = load().nat_shm_push_bench(record_bytes, seconds,
                                     ctypes.byref(out))
    return {"GBps": gbps, "records": out.value}


def shm_worker_drain_bench(idle_exit_ms: int = 1000) -> int:
    """Worker-side native drain loop: pops descriptors and releases their
    arena spans in place until the lane shuts down or `idle_exit_ms`
    passes with no data. Returns the number of records drained."""
    return load().nat_shm_worker_drain_bench(idle_exit_ms)


# -- tensor fabric: producer slots + receiver leases (ISSUE 15) -------------

class FabricLease:
    """One kind-8 tensor record leased from the descriptor-ring fabric.

    ``view()`` is a ZERO-COPY memoryview straight into the producer's
    shared blob arena: the span stays pinned (and accounted in the
    ``shm.span`` nat_res ledger row) until ``release()``, which may run
    OUT OF ORDER relative to other leases — the arena's released-bit +
    lazy reclaim is built for exactly that. Views must not be read after
    release (the producer reclaims the bytes). Dropping the last
    reference releases the lease too."""

    __slots__ = ("_h", "tag", "trace_id", "parent_span_id", "nbytes",
                 "_ptr", "__weakref__")

    def __init__(self, h: int):
        lib = load()
        self._h = h
        self.tag = lib.nat_req_aux(h)
        self.trace_id = lib.nat_req_sock_id(h)
        self.parent_span_id = lib.nat_req_cid(h) & ((1 << 63) - 1)
        n = ctypes.c_size_t(0)
        self._ptr = lib.nat_req_field(h, 2, ctypes.byref(n))
        self.nbytes = n.value

    def view(self) -> memoryview:
        if self._h is None:
            raise ValueError("fabric lease already released")
        if self.nbytes == 0 or not self._ptr:
            return memoryview(b"")
        return memoryview(
            (ctypes.c_char * self.nbytes).from_address(self._ptr))

    def tobytes(self) -> bytes:
        return bytes(self.view()) if self.nbytes else b""

    def release(self):
        h, self._h = self._h, None
        if h:
            load().nat_req_free(h)

    def __del__(self):
        try:
            self.release()
        except Exception:
            pass


def fabric_producer_attach(name) -> int:
    """Claim a PRODUCER slot on a peer's fabric segment (by shm name).
    This process becomes the sole producer of that slot's ring; a crash
    here surfaces as EOWNERDEAD on the receiver's recovery probe.
    Returns the slot index (>= 0) or -1."""
    if isinstance(name, str):
        name = name.encode()
    return load().nat_shm_producer_attach(name)


def fabric_push(data, tag: int) -> int:
    """Stage `data` ONCE into the attached fabric's shared blob arena and
    publish one kind-8 descriptor (the producer write of the zero-copy
    path). numpy arrays push straight from their buffer (no bytes()
    staging copy). Returns 0, or -1 on backpressure (ring/arena full)."""
    lib = load()
    try:
        import numpy as np

        if isinstance(data, np.ndarray):
            a = np.ascontiguousarray(data)
            ptr = ctypes.cast(ctypes.c_void_p(a.ctypes.data),
                              ctypes.c_char_p)
            return lib.nat_shm_fabric_push(ptr, a.nbytes, tag)
    except ImportError:
        pass
    if isinstance(data, memoryview):
        data = bytes(data)
    elif not isinstance(data, (bytes, bytearray)):
        data = bytes(data)
    return lib.nat_shm_fabric_push(bytes(data) if isinstance(
        data, bytearray) else data, len(data), tag)


def fabric_take(timeout_ms: int = 200):
    """Receiver side: take one pushed tensor record from any producer
    slot as a FabricLease (zero-copy arena view, out-of-order release),
    or None on timeout/shutdown."""
    h = load().nat_shm_fabric_take(timeout_ms)
    return FabricLease(h) if h else None


# -- native observability (nat_stats.cpp) -----------------------------------

def stats_counter_names() -> list:
    """Names of the native monotonic counters, index-aligned with the
    snapshot stats_counters() returns."""
    lib = load()
    n = lib.nat_stats_counter_count()
    return [lib.nat_stats_counter_name(i).decode() for i in range(n)]


def stats_counters() -> dict:
    """Combined snapshot {name: value} of every native counter (per-thread
    cells summed; gauges computed in place)."""
    lib = load()
    n = lib.nat_stats_counter_count()
    arr = (ctypes.c_uint64 * n)()
    got = lib.nat_stats_counters(arr, n)
    return {lib.nat_stats_counter_name(i).decode(): arr[i]
            for i in range(got)}


def stats_counter_bump(name: str, delta: int = 1) -> int:
    """Bump a native counter by NAME from Python-side controllers (the
    fleet autoscaler charges nat_autoscale_* here so its decisions land
    in the same /vars + /brpc_metrics surface as native events).
    Returns the counter id, or -1 for an unknown name."""
    return load().nat_stats_counter_bump(name.encode(), delta)


def stats_lane_names() -> list:
    """Latency-histogram lane names (echo/http/redis/grpc/client)."""
    lib = load()
    return [lib.nat_stats_lane_name(i).decode()
            for i in range(lib.nat_stats_lane_count())]


def stats_hist(lane: int) -> list:
    """Combined log2-bucket latency histogram of one lane (counts; bucket
    b covers [2^(b-1), 2^b) ns)."""
    lib = load()
    nb = lib.nat_stats_hist_nbuckets()
    arr = (ctypes.c_uint64 * nb)()
    got = lib.nat_stats_hist(lane, arr, nb)
    return list(arr[:got])


def stats_quantile(lane: int, q: float) -> float:
    """Latency quantile (ns) over a lane's combined histogram,
    interpolated inside the winning log2 bucket; 0.0 when empty."""
    return load().nat_stats_hist_quantile(lane, q)


def stats_enable_spans(every: int = 1):
    """0 = spans off; N = record one of every N native-handled calls into
    the bounded span ring (the bvar::Collector budget analog)."""
    load().nat_stats_enable_spans(every)


def stats_now_ns() -> int:
    """The span clock (CLOCK_MONOTONIC ns) — subtract from time.time() to
    map drained span timestamps onto wall time."""
    return load().nat_stats_now_ns()


def stats_drain_spans(max_spans: int = 4096) -> list:
    """Drain up to max_spans native span records as dicts (consuming
    them); timestamps are monotonic ns (see stats_now_ns)."""
    lib = load()
    arr = (NatSpanRec * max_spans)()
    n = lib.nat_stats_drain_spans(arr, max_spans)
    lanes = stats_lane_names()
    out = []
    for i in range(n):
        r = arr[i]
        lane_i = r.protocol
        out.append({
            "trace_id": r.trace_id,
            "span_id": r.span_id,
            "parent_span_id": r.parent_span_id,
            "sock_id": r.sock_id,
            "recv_ns": r.recv_ns,
            "parse_ns": r.parse_ns,
            "dispatch_ns": r.dispatch_ns,
            "write_ns": r.write_ns,
            "lane": lanes[lane_i] if 0 <= lane_i < len(lanes)
                    else str(lane_i),
            "error_code": r.error_code,
            "req_bytes": r.req_bytes,
            "resp_bytes": r.resp_bytes,
            "method": r.method.decode(errors="replace"),
        })
    return out


def stats_reset():
    """Zero every stat cell and forget undrained spans (test/bench
    hygiene only)."""
    load().nat_stats_reset()


# -- native observatory (ISSUE 9) -------------------------------------------

def method_stats() -> list:
    """Per-method stats rows of the native MethodStatus table: one dict
    per (lane, method) recorded at the native-handler call sites and the
    shm worker emit path — {'lane', 'method', 'count', 'errors',
    'concurrency', 'max_concurrency'}."""
    lib = load()
    lanes = stats_lane_names()
    arr = (NatMethodStatRow * 128)()
    n = lib.nat_method_stats(arr, 128)
    out = []
    for i in range(n):
        r = arr[i]
        out.append({
            "lane": lanes[r.lane] if 0 <= r.lane < len(lanes)
                    else str(r.lane),
            "method": r.method.decode(errors="replace"),
            "count": r.count,
            "errors": r.errors,
            # an in-flight end racing a stats_reset can briefly read -1
            "concurrency": max(0, r.concurrency),
            "max_concurrency": max(0, r.max_concurrency),
        })
    return out


def method_quantile(lane: int, method: str, q: float) -> float:
    """Latency quantile (ns) of one method's own log2 histogram."""
    return load().nat_method_quantile(lane, method.encode(), q)


def method_hist(lane: int, method: str) -> list:
    """Raw log2 buckets of one method's latency histogram (the mergeable
    form: fleet quantiles are computed from bucket-wise sums across
    processes, never from averaged percentiles). Empty list when the
    method has no slot."""
    lib = load()
    nb = lib.nat_stats_hist_nbuckets()
    arr = (ctypes.c_uint64 * nb)()
    n = lib.nat_method_hist(lane, method.encode(), arr, nb)
    if n < 0:
        return []
    return list(arr[:n])


def stats_snapshot() -> bytes:
    """The builtin.stats snapshot JSON, built in-process (the same bytes
    the wire endpoint serves): counters, per-lane and per-method raw
    log2 buckets, overload/quiesce state, open client channels, and the
    nat_res subsystem ledger."""
    lib = load()
    out = ctypes.c_char_p()
    n = ctypes.c_size_t(0)
    rc = lib.nat_stats_snapshot(ctypes.byref(out), ctypes.byref(n))
    if rc != 0 or not out:
        return b""
    try:
        return ctypes.string_at(out, n.value)
    finally:
        lib.nat_buf_free(out)


def conn_snapshot() -> list:
    """Native /connections rows: one dict per live native socket with
    byte/message/syscall counters, unwritten (queued-not-yet-accepted)
    bytes, sniffed protocol, peer address and owning dispatcher."""
    lib = load()
    # n == cap means the table may be truncated (the C export clamps to
    # the caller's buffer): regrow so a thousand-backend fan-out shows
    # every socket instead of a silently partial table
    cap = 1024
    while True:
        arr = (NatConnRow * cap)()
        n = lib.nat_conn_snapshot(arr, cap)
        if n < cap:
            break
        cap *= 2
    out = []
    for i in range(n):
        r = arr[i]
        out.append({
            "sock_id": r.sock_id,
            "in_bytes": r.in_bytes,
            "out_bytes": r.out_bytes,
            "in_msgs": r.in_msgs,
            "out_msgs": r.out_msgs,
            "read_calls": r.read_calls,
            "write_calls": r.write_calls,
            "unwritten_bytes": r.unwritten_bytes,
            "mem_bytes": r.mem_bytes,
            "fd": r.fd,
            "disp_idx": r.disp_idx,
            "server_side": bool(r.server_side),
            "protocol": r.protocol.decode(errors="replace"),
            "remote": r.remote.decode(errors="replace"),
        })
    return out


def res_stats() -> list:
    """The native memory observatory's per-subsystem ledger: one dict
    per allocator subsystem (iobuf blocks, socket slabs, WriteReq pools,
    fiber stacks, shm segments, ...) with live bytes/objects, cumulative
    allocs/frees and the high-water mark."""
    lib = load()
    n = lib.nat_res_count()
    arr = (NatResRow * n)()
    got = lib.nat_res_stats(arr, n)
    out = []
    for i in range(got):
        r = arr[i]
        out.append({
            "subsystem": r.name.decode(errors="replace"),
            "live_bytes": r.live_bytes,
            "live_objects": r.live_objects,
            "cum_allocs": r.cum_allocs,
            "cum_frees": r.cum_frees,
            "cum_alloc_bytes": r.cum_alloc_bytes,
            "hwm_bytes": r.hwm_bytes,
        })
    return out


def res_names() -> list:
    """Subsystem names in enum order (the nat_mem_* label values)."""
    lib = load()
    return [lib.nat_res_name(i).decode()
            for i in range(lib.nat_res_count())]


def res_accounted_bytes() -> int:
    """Total live bytes across every accounted native subsystem — the
    /status RSS reconciliation's accounted side."""
    return load().nat_res_accounted_bytes()


def res_prof_start(every: int = 1, seed: int = 42) -> int:
    """Arm allocation-site stack sampling (1-in-`every`, seeded
    deterministic). 0 = ok, -1 = already running (an embedder owns it —
    report without stealing, the nat_prof discipline)."""
    return load().nat_res_prof_start(every, seed)


def res_prof_stop() -> int:
    return load().nat_res_prof_stop()


def res_prof_running() -> bool:
    return bool(load().nat_res_prof_running())


def res_prof_samples() -> int:
    return load().nat_res_prof_samples()


def res_prof_reset():
    """Forget sampled sites/baseline (the always-on ledger is separate
    and untouched)."""
    load().nat_res_prof_reset()


def res_heap_report(collapsed: bool = True) -> str:
    """/heap/native body: live bytes by allocation site — collapsed
    stacks (default, leaf = "res:<subsystem>") or a flat table."""
    lib = load()
    out = ctypes.c_char_p()
    n = ctypes.c_size_t(0)
    rc = lib.nat_res_heap_report(1 if collapsed else 0, ctypes.byref(out),
                                 ctypes.byref(n))
    if rc != 0 or not out:
        return ""
    try:
        return ctypes.string_at(out, n.value).decode(errors="replace")
    finally:
        lib.nat_buf_free(out)


def res_growth_baseline() -> int:
    """Re-take the /growth/native zero point."""
    return load().nat_res_growth_baseline()


def res_growth_report() -> str:
    """/growth/native body: collapsed stacks weighted by live-bytes
    growth since the baseline."""
    lib = load()
    out = ctypes.c_char_p()
    n = ctypes.c_size_t(0)
    rc = lib.nat_res_growth_report(ctypes.byref(out), ctypes.byref(n))
    if rc != 0 or not out:
        return ""
    try:
        return ctypes.string_at(out, n.value).decode(errors="replace")
    finally:
        lib.nat_buf_free(out)


def res_selftest(nthreads: int = 4, iters: int = 200) -> int:
    """Deterministic alloc/free churn with concurrent snapshot/report
    readers; 0 = the ledger balanced exactly."""
    return load().nat_res_selftest(nthreads, iters)


def mu_prof_start(threshold_us: int = 0, every: int = 1,
                  seed: int = 42) -> int:
    """Arm contended-NatMutex stack sampling: waits >= threshold_us are
    rate-decimated to one in `every` (seeded, deterministic) and sampled
    with a frame-pointer stack naming the contended lock site. 0 = ok,
    -1 = already running (a bench/embedder owns the window)."""
    return load().nat_mu_prof_start(threshold_us, every, seed)


def mu_prof_stop() -> int:
    """Stop sampling; accumulated contention samples stay reportable."""
    return load().nat_mu_prof_stop()


def mu_prof_running() -> bool:
    return bool(load().nat_mu_prof_running())


def mu_prof_samples() -> int:
    return load().nat_mu_prof_samples()


def mu_prof_reset():
    """Forget sampled stacks AND the always-on per-rank wait totals."""
    load().nat_mu_prof_reset()


def mu_prof_reset_samples():
    """Forget sampled stacks only; the per-rank wait totals stay
    monotonic (they are exported as Prometheus counters)."""
    load().nat_mu_prof_reset_samples()


def mu_prof_report(collapsed: bool = True) -> str:
    """Contention profile: collapsed stacks weighted by wait-us
    (default; leaf frame = "lock:<rank name>") or a flat wait-us table
    per contended lock site."""
    lib = load()
    out = ctypes.c_char_p()
    n = ctypes.c_size_t(0)
    rc = lib.nat_mu_prof_report(1 if collapsed else 0, ctypes.byref(out),
                                ctypes.byref(n))
    if rc != 0 or not out:
        return ""
    try:
        return ctypes.string_at(out, n.value).decode(errors="replace")
    finally:
        lib.nat_buf_free(out)


def mu_rank_stats() -> list:
    """Always-on per-rank contended-wait totals (independent of
    sampling): [{'rank', 'name', 'waits', 'wait_us'}, ...]."""
    lib = load()
    arr = (NatLockRankRow * 128)()
    n = lib.nat_mu_rank_stats(arr, 128)
    return [{"rank": arr[i].rank,
             "name": arr[i].name.decode(errors="replace"),
             "waits": arr[i].waits,
             "wait_us": arr[i].wait_us} for i in range(n)]


def mu_rank_name(rank: int):
    """Human name of a NatMutex lock rank, or None when unnamed (the
    drift test asserts every nat_lockrank.h constant resolves)."""
    nm = load().nat_mu_rank_name(rank)
    return nm.decode() if nm is not None else None


def mu_contend_selftest(nthreads: int = 4, iters: int = 100,
                        hold_us: int = 20) -> int:
    """Deterministic contention generator (tests): N threads fight over
    one declared-rank NatMutex; returns that rank's contended-wait
    count so far."""
    return load().nat_mu_contend_selftest(nthreads, iters, hold_us)


# Python-side shadow of the C-side thread-local trace context (the
# Python wrappers are the only setters from this interpreter), so
# trace_scope can RESTORE the enclosing context on exit instead of
# clobbering it to (0,0) — nested scopes / scopes inside an already
# traced request keep propagating after they close.
_trace_tls = threading.local()


def trace_set(trace_id: int = 0, span_id: int = 0):
    """Arm (or clear, with 0,0) this thread's ambient trace context:
    native client calls issued on this thread propagate (trace_id,
    span_id) on the wire — tpu_std meta trace fields, HTTP x-bd-trace-*
    headers, gRPC metadata, kind-8 shm descriptors — so the receiving
    side's spans chain under span_id in /rpcz find_trace."""
    load().nat_trace_set(trace_id, span_id)
    _trace_tls.ctx = (trace_id, span_id)


class trace_scope:
    """with native.trace_scope(trace_id, span_id): ... — arm the ambient
    trace context for the calls inside, restoring the PREVIOUS context
    (not bare zero) on exit."""

    def __init__(self, trace_id: int, span_id: int):
        self._ctx = (trace_id, span_id)
        self._prev = (0, 0)

    def __enter__(self):
        self._prev = getattr(_trace_tls, "ctx", (0, 0))
        trace_set(*self._ctx)
        return self

    def __exit__(self, *exc):
        trace_set(*self._prev)


# -- traffic flight recorder (nat_dump.cpp / nat_replay.cpp) ----------------

def dump_start(directory: str, every: int = 1, seed: int = 42,
               max_file_bytes: int = 64 << 20, generations: int = 4,
               max_payload: int = 1 << 20) -> int:
    """Arm the native traffic flight recorder: sample 1-in-`every`
    requests at the native protocol seams (tpu_std, native HTTP,
    gRPC/h2, redis store, kind-8 shm descriptors) into recordio files
    under `directory` — the format butil/recordio.py reads — rotated
    past max_file_bytes keeping `generations` files. Payloads past
    max_payload are skipped whole (a truncated request is not
    replayable). 0 = ok, -1 = already running, -2 = dir/file error."""
    return load().nat_dump_start(directory.encode(), every, seed,
                                 max_file_bytes, generations, max_payload)


def dump_stop() -> int:
    """Disarm the recorder: drain the capture rings, flush + close the
    current file. Safe when not running."""
    return load().nat_dump_stop()


def dump_running() -> bool:
    return bool(load().nat_dump_running())


def dump_status() -> dict:
    """Flight-recorder status snapshot (counts since the current start;
    config reflects the armed window, or the last one when stopped)."""
    st = NatDumpStatusRec()
    load().nat_dump_status(ctypes.byref(st))
    return {
        "running": bool(st.running),
        "dir": st.dir.decode(errors="replace"),
        "every": st.every,
        "seed": st.seed,
        "samples": st.samples,
        "written": st.written,
        "bytes": st.bytes,
        "drops": st.drops,
        "oversize": st.oversize,
        "rotations": st.rotations,
        "max_file_bytes": st.max_file_bytes,
        "max_payload": st.max_payload,
        "generations": st.generations,
    }


def replay_run(ip: str, port: int, files, times: int = 1,
               qps: float = 0.0, qps_to: float = 0.0,
               concurrency: int = 4, timeout_ms: int = 2000) -> dict:
    """Replay captured recordio traffic against ip:port through the
    native client lanes (tpu_std / HTTP / gRPC). `files` is a path, a
    directory, or a list of either. qps > 0 throttles the fire schedule
    (qps_to > 0 ramps linearly to it across the run); qps <= 0 is press
    mode: no throttle, `concurrency` callers back to back. Raises on
    empty captures / connect failures."""
    if qps_to > 0 and qps <= 0:
        # fire_time ignores the ramp without a starting rate: running
        # UNTHROTTLED when the caller asked for a 500-qps ceiling is
        # the opposite of what they meant — refuse loudly
        raise ValueError("qps_to requires a starting qps > 0 "
                         "(use qps=<low>, qps_to=<high> for a ramp)")
    if isinstance(files, (list, tuple)):
        spec = ";".join(str(f) for f in files)
    else:
        spec = str(files)
    res = NatReplayResult()
    rc = load().nat_replay_run(ip.encode(), port, spec.encode(), times,
                               qps, qps_to, concurrency, timeout_ms,
                               ctypes.byref(res))
    if rc == -1:
        raise ValueError(f"no replayable records under {spec!r}")
    if rc != 0:
        raise ConnectionError(f"native replay failed: rc={rc}")
    return {
        "loaded": res.loaded,
        "sent": res.sent,
        "ok": res.ok,
        "failed": res.failed,
        "skipped": res.skipped,
        "seconds": res.seconds,
        "qps": res.qps,
        "p50_us": res.p50_us,
        "p99_us": res.p99_us,
    }


# -- native fan-out cluster (nat_cluster.cpp / nat_lb.cpp) ------------------

def rpc_server_add_port(ip: str = "127.0.0.1", port: int = 0) -> int:
    """Listen on another port with the RUNNING native server (the
    swarm-backend seam: one process, N ports, each port a distinct LB
    backend). Returns the bound port; raises if no server is running."""
    rc = load().nat_rpc_server_add_port(ip.encode(), port)
    if rc <= 0:
        raise RuntimeError("nat_rpc_server_add_port failed")
    return rc


def rpc_server_remove_port(port: int) -> int:
    """Unregister a port added with rpc_server_add_port (accepted
    connections keep serving; new connects are refused)."""
    return load().nat_rpc_server_remove_port(port)


def cluster_create(lb: str = "rr", connect_timeout_ms: int = 500,
                   health_check_ms: int = 100, breaker: bool = True):
    """Open a native cluster: DoublyBufferedData server list, native LB
    (rr/wrr/random/wr/la/c_hash), per-backend lazily-dialed channels
    with circuit breakers + lame-duck failover. Feed it with
    cluster_update; call through cluster_call / cluster_parallel_call /
    cluster_partition_call. The higher-level wrapper (NativeCluster in
    brpc_tpu.rpc.native_cluster) adds the naming-observer thread."""
    h = load().nat_cluster_create(lb.encode(), connect_timeout_ms,
                                  health_check_ms, 1 if breaker else 0)
    if not h:
        raise RuntimeError(f"nat_cluster_create failed (lb={lb!r})")
    return h


def cluster_close(handle):
    load().nat_cluster_close(handle)


def cluster_node_entry(node):
    """(endpoint[, weight[, tag]]) tuple or bare endpoint ->
    (endpoint, weight, tag) with per-missing-field defaults (naive list
    padding would hand a 2-tuple the weight default as its TAG)."""
    if isinstance(node, (tuple, list)):
        ep = node[0]
        weight = node[1] if len(node) > 1 else 1
        tag = node[2] if len(node) > 2 else ""
        return str(ep), int(weight), str(tag)
    return str(node), 1, ""


def cluster_update(handle, servers) -> int:
    """Full-list naming feed. `servers` is a spec string of
    "ip:port[ weight[ tag]]" entries (';'/','/newline separated) or an
    iterable of such entries / (endpoint, weight, tag) tuples. Returns
    the backend count."""
    if not isinstance(servers, (str, bytes)):
        parts = []
        for s in servers:
            ep, weight, tag = cluster_node_entry(s)
            parts.append(f"{ep} {weight} {tag}".strip())
        servers = ";".join(parts)
    if isinstance(servers, str):
        servers = servers.encode()
    rc = load().nat_cluster_update(handle, servers)
    if rc < 0:
        raise ValueError("malformed server spec (or closed cluster)")
    return rc


def cluster_backend_count(handle) -> int:
    return load().nat_cluster_backend_count(handle)


def cluster_select_debug(handle, request_code: int = 0):
    """Which endpoint would the LB pick for request_code right now?
    Lookup-only (no dial, no counters); None when nothing is usable."""
    buf = ctypes.create_string_buffer(32)
    rc = load().nat_cluster_select_debug(handle, request_code, buf, 32)
    return buf.value.decode() if rc == 0 else None


def cluster_call(handle, service: str, method: str, payload: bytes = b"",
                 timeout_ms: int = 0, max_retry: int = 2,
                 request_code: int = 0):
    """SelectiveChannel verb: LB-pick one backend, fail over to another
    on failure (timeout covers all attempts). Returns
    (error_code, response_bytes, error_text)."""
    lib = load()
    resp = ctypes.c_char_p()
    rlen = ctypes.c_size_t(0)
    err = ctypes.c_char_p()
    rc = lib.nat_cluster_call(handle, service.encode(), method.encode(),
                              payload, len(payload), timeout_ms, max_retry,
                              request_code, ctypes.byref(resp),
                              ctypes.byref(rlen), ctypes.byref(err))
    body = b""
    if resp:
        body = ctypes.string_at(resp, rlen.value)
        lib.nat_buf_free(resp)
    text = ""
    if err:
        text = ctypes.string_at(err).decode(errors="replace")
        lib.nat_buf_free(err)
    return rc, body, text


def _cluster_fan(fn, handle, service, method, payload, timeout_ms, args):
    lib = load()
    resp = ctypes.c_char_p()
    rlen = ctypes.c_size_t(0)
    err = ctypes.c_char_p()
    failed = ctypes.c_int(0)
    rc = fn(handle, service.encode(), method.encode(), payload,
            len(payload), timeout_ms, *args, ctypes.byref(resp),
            ctypes.byref(rlen), ctypes.byref(err), ctypes.byref(failed))
    body = b""
    if resp:
        body = ctypes.string_at(resp, rlen.value)
        lib.nat_buf_free(resp)
    text = ""
    if err:
        text = ctypes.string_at(err).decode(errors="replace")
        lib.nat_buf_free(err)
    return rc, body, text, failed.value


def cluster_parallel_call(handle, service: str, method: str,
                          payload: bytes = b"", timeout_ms: int = 0,
                          fail_limit: int = 0):
    """ParallelChannel verb: fan the request to EVERY backend, merge the
    successful responses natively (concatenation in backend order ==
    protobuf MergeFrom). Returns (error_code, merged_bytes, error_text,
    failed_subcalls); fails once failed sub-calls reach fail_limit
    (<= 0 = all must fail)."""
    return _cluster_fan(load().nat_cluster_parallel_call, handle, service,
                        method, payload, timeout_ms, (fail_limit,))


def cluster_partition_call(handle, service: str, method: str,
                           payload: bytes = b"", timeout_ms: int = 0,
                           partitions: int = 0, fail_limit: int = 0):
    """PartitionChannel verb: one sub-call per "i/n" partition group
    (partitions = n; 0 infers the largest scheme present), merged in
    partition order. Returns (error_code, merged_bytes, error_text,
    failed_subcalls)."""
    return _cluster_fan(load().nat_cluster_partition_call, handle, service,
                        method, payload, timeout_ms,
                        (partitions, fail_limit))


def cluster_dynpart_call(handle, service: str, method: str,
                         payload: bytes = b"", timeout_ms: int = 0,
                         fail_limit: int = 0):
    """DynamicPartitionChannel verb: the partition count is picked PER
    CALL from the live "i/n" schemes, weighted by usable capacity
    (_dynpart LB), then fanned one sub-call per group. A resize is never
    caller-visible — in-flight fans complete against their pinned server
    list version. Returns (error_code, merged_bytes, error_text,
    failed_subcalls, chosen_scheme)."""
    lib = load()
    resp = ctypes.c_char_p()
    rlen = ctypes.c_size_t(0)
    err = ctypes.c_char_p()
    failed = ctypes.c_int(0)
    scheme = ctypes.c_int(0)
    rc = lib.nat_cluster_dynpart_call(
        handle, service.encode(), method.encode(), payload, len(payload),
        timeout_ms, fail_limit, ctypes.byref(resp), ctypes.byref(rlen),
        ctypes.byref(err), ctypes.byref(failed), ctypes.byref(scheme))
    body = b""
    if resp:
        body = ctypes.string_at(resp, rlen.value)
        lib.nat_buf_free(resp)
    text = ""
    if err:
        text = ctypes.string_at(err).decode(errors="replace")
        lib.nat_buf_free(err)
    return rc, body, text, failed.value, scheme.value


def cluster_dynpart_debug(handle, x01: float = 0.0,
                          max_schemes: int = 64) -> dict:
    """Equivalence probe for the dynpart pick: the live scheme table
    (ascending part_total with usable capacities) plus the scheme the
    weighted walk chooses for the caller-supplied point x01 in [0,1) —
    so a Python DynPartLB walk can be replayed against identical inputs.
    Returns {'schemes': [(part_total, capacity), ...], 'chosen': int}."""
    totals = (ctypes.c_int * max_schemes)()
    caps = (ctypes.c_int * max_schemes)()
    chosen = ctypes.c_int(0)
    n = load().nat_cluster_dynpart_debug(handle, x01, totals, caps,
                                         max_schemes,
                                         ctypes.byref(chosen))
    n = min(n, max_schemes)
    return {"schemes": [(totals[i], caps[i]) for i in range(n)],
            "chosen": chosen.value}


def cluster_stats(handle, max_rows: int = 4096) -> list:
    """Per-backend rows: [{'endpoint', 'tag', 'weight', 'selects',
    'errors', 'inflight', 'ema_latency_us', 'breaker_open', 'lame_duck',
    'part_index', 'part_total'}, ...]."""
    arr = (NatClusterRow * max_rows)()
    n = load().nat_cluster_stats(handle, arr, max_rows)
    out = []
    for i in range(n):
        r = arr[i]
        out.append({
            "endpoint": r.endpoint.decode(errors="replace"),
            "tag": r.tag.decode(errors="replace"),
            "weight": r.weight,
            "selects": r.selects,
            "errors": r.errors,
            "inflight": r.inflight,
            "ema_latency_us": r.ema_latency_us,
            "breaker_open": bool(r.breaker_open),
            "lame_duck": bool(r.lame_duck),
            "part_index": r.part_index,
            "part_total": r.part_total,
        })
    return out


def cluster_bench(handle, mode: int = 0, service: str = "EchoService",
                  method: str = "Echo", payload: bytes = b"x" * 16,
                  timeout_ms: int = 2000, param: int = 2,
                  seconds: float = 2.0, concurrency: int = 4) -> dict:
    """Drive the cluster from C threads: mode 0 = selective (param =
    max_retry), 1 = parallel (param = fail_limit), 2 = dynpart (param =
    fail_limit; the autoscale drill's flood). ctypes releases the
    GIL for the whole run, so churn orchestration (SIGTERMs, naming
    updates) can ride a Python thread beside it. Returns {'qps',
    'calls', 'failed', 'p99_us'}."""
    calls = ctypes.c_uint64(0)
    failed = ctypes.c_uint64(0)
    p99 = ctypes.c_double(0.0)
    qps = load().nat_cluster_bench(
        handle, mode, service.encode(), method.encode(), payload,
        len(payload), timeout_ms, param, seconds, concurrency,
        ctypes.byref(calls), ctypes.byref(failed), ctypes.byref(p99))
    return {"qps": qps, "calls": calls.value, "failed": failed.value,
            "p99_us": p99.value}


# -- in-process sampling profiler (nat_prof.cpp) ----------------------------

def prof_start(hz: int = 99) -> int:
    """Start SIGPROF/CPU-time stack sampling at `hz` (frame-pointer
    unwind into lock-free per-thread rings). 0 = ok, -1 = already
    running, -2 = handler/timer install failed."""
    return load().nat_prof_start(hz)


def prof_stop() -> int:
    """Stop sampling; accumulated samples stay reportable."""
    return load().nat_prof_stop()


def prof_running() -> bool:
    return bool(load().nat_prof_running())


def prof_samples() -> int:
    """Samples captured since start/reset."""
    return load().nat_prof_samples()


def prof_reset():
    """Forget everything sampled so far."""
    load().nat_prof_reset()


def prof_report(collapsed: bool = False) -> str:
    """Render the accumulated profile: flat self-sample symbol table
    (default, the PROFILE_r*.md shape) or collapsed stacks
    (flamegraph.pl / speedscope compatible)."""
    lib = load()
    out = ctypes.c_char_p()
    n = ctypes.c_size_t(0)
    rc = lib.nat_prof_report(1 if collapsed else 0, ctypes.byref(out),
                             ctypes.byref(n))
    if rc != 0 or not out:
        return ""
    try:
        return ctypes.string_at(out, n.value).decode(errors="replace")
    finally:
        lib.nat_buf_free(out)
