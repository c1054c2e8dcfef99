"""rpcz — per-call tracing spans.

Counterpart of brpc's rpcz (SURVEY.md section 5; span.h:47-224,
builtin/rpcz_service): a Span per server/client call; nested client calls
parent under the enclosing server span via thread-local state (the
tls_bls.rpcz_parent_span trick, span.h:76,116); trace/span ids propagate in
the RpcMeta; spans are sampled into a bounded collector (the
bvar::Collector role with its global sample budget, collector.h:40) and
browsable at /rpcz. Annotate() adds free-text timeline entries. As in
brpc, collection is off until the enable_rpcz flag is set.

phase(name) is the other kind of span: a named phase inside a call (the
fabric claim, the landing wait, an all-reduce launch) recorded on the JAX
profiler's host plane, on the clock of the device's own events. It costs
one check while no profiler trace runs, and records only while one does
(/hotspots/tpu, or any jax.profiler trace).
"""
from __future__ import annotations

import contextlib
import gc
import random
import sys
import threading
import time
from collections import deque
from typing import Deque, List, Optional

from brpc_tpu.butil import flags

flags.define_bool("enable_rpcz", False, "collect rpcz spans")
flags.define_int("rpcz_max_spans", 4096,
                 "bounded span store (collector budget analog)")
flags.define_int("rpcz_sample_every", 1,
                 "keep 1 of every N spans (sampling rate limit)")
flags.define_string("rpcz_database_dir", "",
                    "persist sampled spans on disk (the SpanDB of "
                    "span.h:206-224); empty = in-memory only")
flags.define_int("rpcz_database_max_spans", 200000,
                 "rotate the on-disk SpanDB past this many spans")

_tls = threading.local()


class Span:
    __slots__ = (
        "trace_id", "span_id", "parent_span_id", "kind", "full_method",
        "remote_side", "start_time", "end_time", "error_code",
        "request_size", "response_size", "annotations", "log_id",
    )

    def __init__(self, kind: str, full_method: str, trace_id: int = 0,
                 parent_span_id: int = 0, log_id: int = 0):
        self.kind = kind  # "server" | "client"
        self.full_method = full_method
        self.trace_id = trace_id or random.getrandbits(63)
        self.span_id = random.getrandbits(63)
        self.parent_span_id = parent_span_id
        self.remote_side = None
        self.start_time = time.time()
        self.end_time = 0.0
        self.error_code = 0
        self.request_size = 0
        self.response_size = 0
        self.annotations: List = []
        self.log_id = log_id

    def annotate(self, text: str):
        """Free-text timeline entry (Annotate, span.h:80-84)."""
        self.annotations.append((time.time(), text))

    def end(self, error_code: int = 0):
        self.end_time = time.time()
        self.error_code = error_code
        _submit(self)

    @property
    def latency_us(self) -> float:
        if not self.end_time:
            return 0.0
        return (self.end_time - self.start_time) * 1e6

    def describe(self) -> str:
        lines = [
            f"trace={self.trace_id:016x} span={self.span_id:016x} "
            f"parent={self.parent_span_id:016x} [{self.kind}] "
            f"{self.full_method} remote={self.remote_side} "
            f"latency={self.latency_us:.0f}us error={self.error_code} "
            f"req={self.request_size}B resp={self.response_size}B"
        ]
        for ts, text in self.annotations:
            offset_us = (ts - self.start_time) * 1e6
            lines.append(f"    +{offset_us:.0f}us {text}")
        return "\n".join(lines)


# -- phase spans on the profiler's clock -------------------------------------

_TraceAnnotation = None  # jax.profiler.TraceAnnotation once it is loaded
_NO_SPAN = contextlib.nullcontext()


def _annotation_class():
    """jax.profiler.TraceAnnotation, or None while the process has not
    loaded jax.profiler: it then has no trace to record into, and a span
    must not pay for importing JAX."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        prof = sys.modules.get("jax.profiler")
        if prof is not None:  # (no attribute yet while it is importing)
            _TraceAnnotation = getattr(prof, "TraceAnnotation", None)
    return _TraceAnnotation


def phase(name: str):
    """`with phase("tensor.fill"): ...` records a span named `name` in the
    running profiler trace; with no trace running it returns one shared
    no-op context and builds nothing."""
    ta = _TraceAnnotation or _annotation_class()
    if ta is None or not ta.is_enabled():
        return _NO_SPAN
    return ta(name)


_gc_span = [None]


def _gc_phase(stage: str, info: dict):
    """gc.callbacks hook: each collector pass is a `py.gc` span, with its
    generation, while a trace runs."""
    if stage == "start":
        ta = _TraceAnnotation or _annotation_class()
        if ta is not None and ta.is_enabled():
            span = ta("py.gc", generation=info.get("generation", -1))
            span.__enter__()
            _gc_span[0] = span
    else:
        span, _gc_span[0] = _gc_span[0], None
        if span is not None:
            span.__exit__(None, None, None)


gc.callbacks.append(_gc_phase)


# -- thread-local parenting (tls_bls analog) --------------------------------

def current_parent() -> Optional[Span]:
    return getattr(_tls, "parent_span", None)


def set_parent(span: Optional[Span]):
    _tls.parent_span = span


class parent_scope:
    """with parent_scope(server_span): handler()  — nested client calls
    chain under it."""

    def __init__(self, span: Optional[Span]):
        self._span = span
        self._prev = None

    def __enter__(self):
        self._prev = current_parent()
        set_parent(self._span)
        return self._span

    def __exit__(self, *exc):
        set_parent(self._prev)


# -- on-disk SpanDB (span.h:206-224) ----------------------------------------

class SpanDB:
    """Persists sampled spans to recordio files so traces survive the
    in-memory window; rotated in two generations like the reference's
    SpanDB keeps a bounded disk footprint."""

    def __init__(self, directory: str, max_spans: int):
        import os

        os.makedirs(directory, exist_ok=True)
        self._dir = directory
        self._max = max(1000, max_spans)
        self._lock = threading.Lock()
        self._count = 0
        self._writer = None
        # Spans are handed off to a background writer (the reference feeds
        # SpanDB from the Collector's thread) so RPC completion never
        # touches the disk while holding the CallId lock.
        self._queue: Deque[Span] = deque()
        self._queue_cond = threading.Condition()
        self._closed = False
        self._open()
        self._thread = threading.Thread(target=self._drain_loop,
                                        name="rpcz-spandb", daemon=True)
        self._thread.start()

    def _path(self, gen: int) -> str:
        import os

        return os.path.join(self._dir, f"rpcz.{gen}.recordio")

    def _open(self):
        from brpc_tpu.butil.recordio import RecordWriter

        self._writer = RecordWriter(self._path(0))

    def append(self, span: "Span"):
        """Non-blocking enqueue; the background thread persists it."""
        with self._queue_cond:
            if self._closed:
                return
            if len(self._queue) > 65536:  # backpressure: drop, don't stall
                return
            self._queue.append(span)
            self._queue_cond.notify()

    def _drain_loop(self):
        while True:
            with self._queue_cond:
                while not self._queue and not self._closed:
                    self._queue_cond.wait()
                if self._closed and not self._queue:
                    return
                batch = list(self._queue)
                self._queue.clear()
            try:
                self._write_batch(batch)
            except Exception:
                pass  # disk trouble must never kill the writer thread

    def _write_batch(self, batch):
        import json

        with self._lock:
            for span in batch:
                payload = json.dumps({
                    "trace_id": span.trace_id, "span_id": span.span_id,
                    "parent_span_id": span.parent_span_id,
                    "kind": span.kind,
                    "full_method": span.full_method,
                    "remote_side": span.remote_side,
                    "start_time": span.start_time,
                    "end_time": span.end_time,
                    "error_code": span.error_code,
                    "request_size": span.request_size,
                    "response_size": span.response_size,
                    "log_id": span.log_id,
                    "annotations": span.annotations,
                }).encode()
                self._writer.write({"trace_id": f"{span.trace_id:016x}"},
                                   payload)
                self._count += 1
                if self._count >= self._max // 2:
                    self._rotate()
            self._writer.flush()

    def drain(self, timeout_s: float = 5.0):
        """Wait for queued spans to reach disk (readers want fresh data)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._queue_cond:
                if not self._queue:
                    return
            time.sleep(0.005)

    def close(self):
        with self._queue_cond:
            self._closed = True
            self._queue_cond.notify()
        self._thread.join(5)
        with self._lock:
            self._writer.close()

    def _rotate(self):
        import os

        self._writer.close()
        try:
            os.replace(self._path(0), self._path(1))
        except OSError:
            pass
        self._count = 0
        self._open()

    def find_trace(self, trace_id: int) -> List["Span"]:
        """Read back every span of a trace from both generations."""
        import json
        import os

        from brpc_tpu.butil.recordio import RecordReader

        needle = f"{trace_id:016x}"
        out: List[Span] = []
        self.drain(1.0)
        with self._lock:
            self._writer.flush()
        for gen in (1, 0):
            path = self._path(gen)
            if not os.path.exists(path):
                continue
            reader = RecordReader(path)
            while True:
                rec = reader.read()
                if rec is None:
                    break
                meta, payload = rec
                if meta.get("trace_id") != needle:
                    continue
                d = json.loads(payload.decode())
                span = Span(d["kind"], d["full_method"],
                            trace_id=d["trace_id"],
                            parent_span_id=d["parent_span_id"],
                            log_id=d["log_id"])
                span.span_id = d["span_id"]
                span.remote_side = d["remote_side"]
                span.start_time = d["start_time"]
                span.end_time = d["end_time"]
                span.error_code = d["error_code"]
                span.request_size = d["request_size"]
                span.response_size = d["response_size"]
                span.annotations = [tuple(a) for a in d["annotations"]]
                out.append(span)
        return out


_span_db: Optional[SpanDB] = None
_span_db_lock = threading.Lock()


def _get_span_db() -> Optional[SpanDB]:
    directory = flags.get_flag("rpcz_database_dir")
    global _span_db
    if not directory and _span_db is None:
        return None  # common case: feature off — skip the lock entirely
    if not directory:
        with _span_db_lock:
            if _span_db is not None:
                try:
                    _span_db.close()
                except Exception:
                    pass
                _span_db = None
        return None
    with _span_db_lock:
        if _span_db is None or _span_db._dir != directory:
            if _span_db is not None:
                try:
                    _span_db.close()  # release the old writer's fd
                except Exception:
                    pass
            _span_db = SpanDB(directory,
                              flags.get_flag("rpcz_database_max_spans"))
    return _span_db


# -- collector --------------------------------------------------------------

_spans: Deque[Span] = deque(maxlen=4096)
_spans_lock = threading.Lock()
_counter = [0]


def _submit(span: Span):
    if not flags.get_flag("enable_rpcz"):
        return
    every = max(1, flags.get_flag("rpcz_sample_every"))
    with _spans_lock:
        _counter[0] += 1
        if _counter[0] % every:
            return
        if _spans.maxlen != flags.get_flag("rpcz_max_spans"):
            resized: Deque[Span] = deque(
                _spans, maxlen=max(16, flags.get_flag("rpcz_max_spans")))
            globals()["_spans"] = resized
        _spans.append(span)
    try:
        db = _get_span_db()
        if db is not None:
            db.append(span)
    except Exception:
        pass  # disk trouble must never fail the RPC path


def sync_native_spans(_value=None):
    """Native span sampling follows enable_rpcz and rpcz_sample_every:
    called when either flag is set and when a native server starts. A
    native core that is not loaded yet has nothing to switch."""
    native = sys.modules.get("brpc_tpu.native")
    if native is None or native._lib is None:
        return
    every = 0
    if flags.get_flag("enable_rpcz"):
        every = max(1, flags.get_flag("rpcz_sample_every"))
    native.stats_enable_spans(every)


flags.on_set("enable_rpcz", sync_native_spans)
flags.on_set("rpcz_sample_every", sync_native_spans)


def drain_native_spans() -> int:
    """Pull sampled span records out of the native runtime's bounded ring
    (nat_stats.cpp) and file them with the Python spans, so /rpcz shows
    native-handled calls beside the Python lanes. Native sampling already
    applied the rpcz_sample_every stride, so records go straight into the
    store. Returns the number drained."""
    try:
        from brpc_tpu import native

        if not native.available():
            return 0
        recs = native.stats_drain_spans(4096)
        if not recs:
            return 0
        # map CLOCK_MONOTONIC span timestamps onto wall time
        offset = time.time() - native.stats_now_ns() / 1e9
    except Exception:
        return 0
    for r in recs:
        kind = "client" if r["lane"] == "client" else "server"
        span = Span(kind, r["method"] or f"native.{r['lane']}",
                    trace_id=r["trace_id"],
                    parent_span_id=r.get("parent_span_id", 0))
        span.span_id = r["span_id"]
        span.remote_side = f"native:{r['lane']}/sock={r['sock_id']}"
        span.start_time = offset + r["recv_ns"] / 1e9
        span.end_time = offset + r["write_ns"] / 1e9
        span.error_code = r["error_code"]
        span.request_size = r["req_bytes"]
        span.response_size = r["resp_bytes"]
        span.annotations = [
            (offset + r["parse_ns"] / 1e9, "native parse done"),
            (offset + r["dispatch_ns"] / 1e9, "native usercode done"),
            (offset + r["write_ns"] / 1e9, "native response queued"),
        ]
        with _spans_lock:
            _spans.append(span)
        # persist like _submit does (sampling already happened native-side):
        # the deque ages out in seconds under load, and find_trace recovers
        # older spans from the disk store — native spans must be there too
        try:
            db = _get_span_db()
            if db is not None:
                db.append(span)
        except Exception:
            pass  # disk trouble must never fail the drain
    return len(recs)


def recent_spans(limit: int = 100) -> List[Span]:
    drain_native_spans()
    with _spans_lock:
        return list(_spans)[-limit:]


def find_trace(trace_id: int) -> List[Span]:
    drain_native_spans()
    with _spans_lock:
        found = [s for s in _spans if s.trace_id == trace_id]
    # Merge with the on-disk SpanDB: parts of the trace may have aged out
    # of the bounded memory window while others are still in it.
    try:
        db = _get_span_db()
    except Exception:
        db = None
    if db is not None:
        try:
            seen = {s.span_id for s in found}
            found.extend(s for s in db.find_trace(trace_id)
                         if s.span_id not in seen)
        except Exception:
            pass
    return found


def clear_for_tests():
    # flush the native ring too: stale native records must not resurface
    # in a later test's /rpcz listing
    try:
        from brpc_tpu import native

        if native.available():
            native.stats_drain_spans(4096)
    except Exception:
        pass
    with _spans_lock:
        _spans.clear()
        _counter[0] = 0


def describe_recent_spans(query: Optional[dict] = None) -> str:
    """/rpcz page body (builtin/rpcz_service.cpp role)."""
    query = query or {}
    if "trace_id" in query:
        try:
            spans = find_trace(int(query["trace_id"], 16))
        except ValueError:
            return "bad trace_id\n"
    else:
        limit = int(query.get("limit", "50") or 50)
        spans = recent_spans(limit)
    if not spans:
        return "no spans collected (enable_rpcz flag / traffic?)\n"
    return "\n".join(s.describe() for s in reversed(spans)) + "\n"


# -- wiring helpers ----------------------------------------------------------

def start_server_span(full_method: str, meta, remote_side) -> Optional[Span]:
    if not flags.get_flag("enable_rpcz"):
        return None
    span = Span("server", full_method,
                trace_id=meta.request.trace_id,
                parent_span_id=meta.request.span_id,
                log_id=meta.request.log_id)
    span.remote_side = str(remote_side) if remote_side else None
    return span


def start_client_span(full_method: str, cntl) -> Optional[Span]:
    if not flags.get_flag("enable_rpcz"):
        return None
    parent = current_parent()
    span = Span("client", full_method,
                trace_id=parent.trace_id if parent else 0,
                parent_span_id=parent.span_id if parent else 0,
                log_id=cntl.log_id)
    cntl.trace_id = span.trace_id
    cntl.span_id = span.span_id
    return span
