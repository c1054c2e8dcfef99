"""Native-runtime mount for the Python Server.

The native RPC runtime (native/src/nat_rpc.cpp) owns the port: accept,
epoll, fiber readers, tpu_std framing, and the Socket write queue all run
in C++ on native IOBuf blocks. Requests whose method has no NATIVE handler
are handed to this adapter's pthread pool — the usercode_backup_pool
discipline (details/usercode_backup_pool.h:29-72): Python user code runs on
Python threads, never on fiber stacks — and the full Python server path
(`process_request`: auth, interceptor, MethodStatus, rpcz spans,
compression) executes unchanged, writing its response back through the
native socket via a shim.

A thread that answers many requests at once (a batching consumer) opens
`coalesced_writes()` around its answers: the shim's writes then gather on
the thread and leave in one native crossing when the scope closes.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

from brpc_tpu import native
from brpc_tpu.butil.endpoint import EndPoint
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.rpc.proto import rpc_meta_pb2


class _WriteScope(threading.local):
    frames = None  # (sock_id, bytes) pairs while a scope is open


_scope = _WriteScope()


def _flush_writes():
    frames = _scope.frames
    if frames:
        native.sock_write_batch(frames)
        frames.clear()


@contextlib.contextmanager
def coalesced_writes():
    """Holds this thread's NativeSocketShim writes and sends them in one
    `sock_write_batch` when the scope closes, exception or not: each
    connection still gets its frames in order. A nested scope joins the
    outer one."""
    if _scope.frames is not None:
        yield
        return
    _scope.frames = []
    try:
        yield
    finally:
        try:
            _flush_writes()
        finally:
            _scope.frames = None


class NativeSocketShim:
    """Quacks like rpc.Socket for the server-side response path: write()
    re-enters the native runtime's write queue for this connection. The
    raw fallback lane also runs full protocol sessions over it, so it
    carries the read portal / matched-protocol state the InputMessenger
    expects (protocols attach their own per-connection attributes freely,
    as they do on the real Socket)."""

    def __init__(self, sock_id: int):
        from brpc_tpu.butil.iobuf import IOPortal

        self.sock_id = sock_id
        self.remote_side: Optional[EndPoint] = None
        self.app_state = None
        self._failed = False
        self.read_portal = IOPortal()
        self.matched_protocol = None

    def write(self, buf: IOBuf, id_wait=None) -> int:
        data = buf.copy_to_bytes(len(buf))
        frames = _scope.frames
        if frames is not None:
            frames.append((self.sock_id, data))
            return 0
        return native.sock_write(self.sock_id, data)

    def set_failed(self, error_code=0, error_text: str = ""):
        self._failed = True
        _flush_writes()  # answers held in a scope leave before the fail
        native.sock_set_failed(self.sock_id)

    def failed(self) -> bool:
        return self._failed

    def fd(self):
        return None


class _NativeHttpShim(NativeSocketShim):
    """Response path for a native-parsed HTTP request (kind 3): the
    serialized response rides nat_http_respond, which preserves pipelined
    request order via the native session's reorder window. Connection:
    close is honored natively (the parse records close-requesting seqs),
    so the ECLOSE set_failed from http_protocol._respond is a no-op here
    — a hard set_failed would race earlier pipelined responses."""

    def __init__(self, sock_id: int, seq: int):
        super().__init__(sock_id)
        self.seq = seq
        # rpcz: when the dispatch armed a server span, the RESPONSE write
        # is the completion point (handlers may respond long after the
        # handler function returned) — end it here with the real status
        self.span = None

    def _end_span(self, data: bytes):
        span, self.span = self.span, None
        if span is None:
            return
        try:
            status = int(data[9:12]) if data[:5] == b"HTTP/" else 0
        except ValueError:
            status = 0
        try:
            span.end(status if status >= 400 else 0)
        except Exception:
            pass

    def write(self, buf, id_wait=None) -> int:
        data = buf.copy_to_bytes(len(buf))
        self._end_span(data)
        return native.http_respond(self.sock_id, self.seq, data)

    def set_failed(self, error_code=0, error_text: str = ""):
        from brpc_tpu.rpc import errors

        self._failed = True
        if error_code == errors.ECLOSE:
            return  # native close_seqs closes after this response flushes
        # a request failed without a response write is exactly what the
        # trace exists to debug: submit the armed span with the error
        span, self.span = self.span, None
        if span is not None:
            try:
                span.end(error_code or 500)
            except Exception:
                pass
        native.sock_set_failed(self.sock_id)


class _StreamSession:
    """Per-connection dispatcher for natively-cut streaming frames
    (kind 5): frames are reassembled by per-socket sequence (py-lane
    pthreads race) and fed straight into the Python Stream objects —
    the ordered-delivery role stream.py gets from process_inline on the
    Python port, without re-parsing framing in Python."""

    FRAME_DATA = 0
    FRAME_FEEDBACK = 1
    FRAME_CLOSE = 2

    def __init__(self, sock_id: int):
        self.sock_id = sock_id
        self.lock = threading.Lock()
        self.pending = {}
        self.next_seq = 1
        self.busy = False

    def feed(self, seq: int, ftype: int, dest_id: int, payload: bytes):
        with self.lock:
            self.pending[seq] = (ftype, dest_id, payload)
            if self.busy:
                return
            self.busy = True
        while True:
            with self.lock:
                item = self.pending.pop(self.next_seq, None)
                if item is None:
                    self.busy = False
                    return
                self.next_seq += 1
            try:
                self._dispatch(*item)
            except Exception:
                import logging

                logging.getLogger(__name__).exception(
                    "native stream frame dispatch raised")

    def _dispatch(self, ftype: int, dest_id: int, payload: bytes):
        import struct

        from brpc_tpu.butil.iobuf import IOBuf
        from brpc_tpu.rpc.stream import Stream

        stream = Stream.find(dest_id)
        if stream is None:
            return  # already closed; drop silently (reference behavior)
        if ftype == self.FRAME_DATA:
            if len(payload) >= 65536:
                buf = IOBuf()  # zero-copy wrap: bytes are immutable
                buf.append_user_data(payload)
            else:
                buf = IOBuf(payload)
            stream._on_data(buf)
        elif ftype == self.FRAME_FEEDBACK:
            (consumed,) = struct.unpack(">Q", payload)
            stream._on_feedback(consumed)
        elif ftype == self.FRAME_CLOSE:
            stream.close(notify_remote=False)


class _RawSession:
    """Per-connection protocol session for the raw fallback lane (the
    native port's multi-protocol capability, input_messenger.h:33-154):
    the native runtime shovels ordered byte chunks; the Python
    InputMessenger cuts and dispatches them exactly as it would from a
    real socket. Chunks may arrive on any py-lane pthread — they are
    reassembled by sequence number and processed by a single drainer at a
    time (busy flag), preserving per-connection ordering."""

    def __init__(self, messenger, sock_id: int):
        self.messenger = messenger
        self.sock = NativeSocketShim(sock_id)
        self.lock = threading.Lock()
        self.chunks = {}
        self.next_seq = 1
        self.busy = False

    def feed(self, seq: int, data: bytes):
        with self.lock:
            self.chunks[seq] = data
            if self.busy:
                return  # the active drainer will pick it up
            self.busy = True
        while True:
            with self.lock:
                got = False
                while self.next_seq in self.chunks:
                    self.sock.read_portal.append(
                        self.chunks.pop(self.next_seq))
                    self.next_seq += 1
                    got = True
                if not got:
                    self.busy = False
                    return
            try:
                self.messenger._cut_and_process(self.sock, read_eof=False)
            except Exception:
                import logging

                logging.getLogger(__name__).exception(
                    "raw-lane protocol session raised")
                self.sock.set_failed()


class NativeRuntimeMount:
    """Runs a Python Server's services on a native port."""

    def __init__(self, server, num_threads: int = 0):
        self.server = server
        self.port = 0
        self._threads = []
        self._stopping = False
        self._num_threads = num_threads or max(2, server.options.num_threads)
        self._messenger = None
        self._raw_sessions = {}
        self._stream_sessions = {}
        self._raw_lock = threading.Lock()

    def start(self, ip: str = "127.0.0.1", port: int = 0,
              native_echo: bool = False) -> int:
        from brpc_tpu.rpc.input_messenger import InputMessenger
        from brpc_tpu.rpc.protocol import list_server_protocols

        self.port = native.rpc_server_start(ip, port,
                                            nworkers=0,
                                            native_echo=native_echo)
        # one pane of glass: the C++ stat cells become bvars (/vars,
        # /status, /brpc_metrics) and native spans drain into /rpcz
        try:
            from brpc_tpu.bvar.native_vars import register_native_bvars

            register_native_bvars()
        except Exception:
            pass
        from brpc_tpu import rpcz
        from brpc_tpu.butil import flags as _flags

        if _flags.get_flag("enable_rpcz"):
            rpcz.sync_native_spans()
        # full protocol registry for the raw fallback lane: the native
        # port keeps the Python port's one-port-all-protocols capability
        protocols = list_server_protocols()
        if self.server.options.enabled_protocols:
            protocols = [p for p in protocols
                         if p.name in self.server.options.enabled_protocols]
        self._messenger = InputMessenger(protocols, arg=self.server)
        native.rpc_server_enable_raw_fallback(True)
        # native HTTP/1.1 + h2/gRPC parse lanes (kind-3/4 requests): parse
        # native, execute Python — only when those protocols are mounted
        if any(p.name in ("http", "h2:grpc") for p in protocols):
            try:
                native.rpc_server_native_http(True)
            except AttributeError:
                pass  # older .so without the lane
        # native Redis lane (kind-6): RESP parsed in C++, commands run in
        # the Python RedisService — or, with native_redis_store, the
        # GET/SET family executes against a C++ in-memory store and only
        # unknown commands reach Python
        if self.server.redis_service is not None:
            try:
                native.rpc_server_redis(
                    2 if getattr(self.server.options,
                                 "native_redis_store", False) else 1)
            except AttributeError:
                pass
        # TLS on the native port (ServerSSLOptions role)
        if self.server.options.ssl_certfile:
            rc = native.rpc_server_ssl(self.server.options.ssl_certfile,
                                       self.server.options.ssl_keyfile)
            if rc != 0:
                native.rpc_server_stop()
                raise RuntimeError(
                    f"native TLS unavailable (rc={rc}): libssl missing or "
                    f"bad cert/key")
        for i in range(self._num_threads):
            t = threading.Thread(target=self._worker,
                                 name=f"native_py_lane_{i}", daemon=True)
            t.start()
            self._threads.append(t)
        # usercode worker processes (shm lane): kind-3/4 dispatch fans
        # out across N interpreters; the in-process lane keeps serving
        # every other kind (and is the overflow path when rings fill)
        opts = self.server.options
        if getattr(opts, "py_workers", 0) > 0 and \
                getattr(opts, "py_worker_factory", ""):
            self._start_shm_workers(opts.py_workers, opts.py_worker_factory)
        return self.port

    def _start_shm_workers(self, n: int, factory: str):
        import os
        import subprocess
        import sys

        lib = native.load()
        # the descriptor-ring lane pre-carves per-worker rings/arenas:
        # slots are bounded (asked of the library, not hand-mirrored) and
        # extra workers would fail attach and exit silently — clamp loudly
        max_workers = lib.nat_shm_lane_max_workers()
        if n > max_workers:
            import logging

            logging.getLogger("brpc_tpu.native").warning(
                "py_workers=%d exceeds the shm lane's %d worker slots; "
                "clamping", n, max_workers)
            n = max_workers
        if lib.nat_shm_lane_create(0) != 0:
            raise RuntimeError("shm lane creation failed")
        name = lib.nat_shm_lane_name().decode()
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        # workers are host-only: the server process may hold the chip,
        # and a chip belongs to one process
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = repo_root + os.pathsep + \
            env.get("PYTHONPATH", "")
        self._shm_workers = [
            subprocess.Popen([sys.executable, "-m",
                              "brpc_tpu.rpc.shm_worker", name, factory],
                             env=env, cwd=repo_root)
            for _ in range(n)
        ]
        # readiness barrier BEFORE the lane routes any request: a fresh
        # interpreter + .so load takes seconds on a loaded host, and
        # early requests would otherwise sit in the ring against the
        # reap deadline. A worker that dies at boot only lowers the
        # attach target (the rest still count).
        import time as _time

        deadline = _time.time() + 30
        while _time.time() < deadline:
            alive = sum(1 for p in self._shm_workers if p.poll() is None)
            if lib.nat_shm_lane_workers() >= max(alive, 1) or alive == 0:
                break
            _time.sleep(0.1)
        lib.nat_shm_lane_enable(1)

    def stop(self, quiesce_timeout_ms: int = 0):
        # Graceful quiesce FIRST, while the py lane and the shm workers
        # are still serving: stop accepting, lame-duck every connection,
        # drain admitted work (incl. shm-worker in-flight) under the
        # deadline, reject new arrivals on the wire. Only then tear the
        # serving machinery down.
        if quiesce_timeout_ms > 0:
            try:
                native.load().nat_server_quiesce(quiesce_timeout_ms)
            except Exception:
                pass  # older .so without the export: abrupt stop
        self._stopping = True
        workers = getattr(self, "_shm_workers", None)
        if workers:
            try:
                native.load().nat_shm_lane_enable(0)
            except Exception:
                pass
            for p in workers:
                p.terminate()
            for p in workers:
                try:
                    p.wait(timeout=3)
                except Exception:
                    p.kill()
            self._shm_workers = []
        native.rpc_server_stop()
        for t in self._threads:
            t.join(timeout=2.0)
        with self._raw_lock:
            self._raw_sessions.clear()

    # -- the py lane --------------------------------------------------------
    def _worker(self):
        while not self._stopping:
            items = native.take_requests(16, 100)
            if not items:
                continue
            # nat_py_queue_wait_ns: each request's wait from its enqueue
            # stamp to the start of its dispatch (behind the earlier items
            # of its batch too), charged once per batch
            waited = 0
            try:
                for item in items:
                    if item[10]:
                        waited += time.monotonic_ns() - item[10]
                    self._dispatch_one(item)
            finally:
                if waited > 0:
                    native.stats_counter_bump("nat_py_queue_wait_ns", waited)

    def _dispatch_one(self, item):
        from brpc_tpu.rpc.tpu_std_protocol import RpcMessage, process_request

        (handle, kind, meta_bytes, payload, attachment, sock_id, seq,
         f0, f1, aux, _enqueue_ns) = item
        if kind == 5:  # native-cut streaming frame
            ftype = f0  # frame type rides in the tuple (zero-copy big
            # payloads hand their handle to a GC finalizer: handle=None)
            if handle is not None:
                native.req_free(handle)
            with self._raw_lock:
                sess = self._stream_sessions.get(sock_id)
                if sess is None:
                    sess = _StreamSession(sock_id)
                    self._stream_sessions[sock_id] = sess
            sess.feed(seq, ftype, aux, payload)
            return
        if kind == 3:  # native-parsed HTTP request
            native.req_free(handle)
            self._handle_http(f0, f1, meta_bytes, payload, sock_id, seq)
            return
        if kind == 4:  # native-parsed gRPC-over-h2 request
            native.req_free(handle)
            self._handle_grpc(f1, meta_bytes, payload, sock_id, seq)
            return
        if kind == 6:  # native-parsed RESP command
            native.req_free(handle)
            self._handle_redis(payload, sock_id, seq)
            return
        if kind == 1:  # raw protocol bytes
            native.req_free(handle)
            with self._raw_lock:
                sess = self._raw_sessions.get(sock_id)
                if sess is None:
                    sess = _RawSession(self._messenger, sock_id)
                    self._raw_sessions[sock_id] = sess
            sess.feed(seq, payload)
            return
        if kind == 2:  # connection closed: drop the sessions
            native.req_free(handle)
            with self._raw_lock:
                self._raw_sessions.pop(sock_id, None)
                self._stream_sessions.pop(sock_id, None)
            return
        if True:
            try:
                meta = rpc_meta_pb2.RpcMeta()
                meta.ParseFromString(meta_bytes)
                att = IOBuf()
                if attachment:
                    att.append(attachment)
                msg = RpcMessage(meta, payload, att)
                msg.socket = NativeSocketShim(sock_id)
                msg.arg = self.server
                process_request(msg)
            except Exception as e:  # answer rather than drop
                try:
                    native.respond(handle, 2001, f"py-lane dispatch: {e}")
                    handle = None
                except Exception:
                    pass
            finally:
                if handle is not None:
                    native.req_free(handle)

    def _handle_grpc(self, path: bytes, flat_headers: bytes, data: bytes,
                     sock_id: int, sid: int):
        """kind-4 dispatch: the native h2 session decoded HEADERS (HPACK)
        and buffered the gRPC-framed body; run the same method dispatch as
        the Python h2 stack (_dispatch_server_request semantics) and
        answer through the native response framer."""
        import time as _time

        from brpc_tpu.rpc import errors
        from brpc_tpu.rpc.controller import Controller
        from brpc_tpu.rpc.h2_protocol import (
            GRPC_INTERNAL,
            GRPC_NOT_FOUND,
            GRPC_OK,
            GRPC_RESOURCE_EXHAUSTED,
            GRPC_UNIMPLEMENTED,
            _parse_grpc_timeout,
            error_to_grpc_status,
            grpc_unwrap,
        )

        def respond(payload=b"", status=GRPC_OK, message=""):
            native.grpc_respond(sock_id, sid, payload, status, message)

        try:
            server = self.server
            pstr = path.decode("latin-1")
            parts = [p for p in pstr.split("/") if p]
            if len(parts) != 2:
                return respond(b"", GRPC_UNIMPLEMENTED, f"bad path {pstr}")
            entry = server.find_method(parts[0], parts[1])
            if entry is None:
                missing = server.find_service(parts[0]) is None
                return respond(
                    b"", GRPC_NOT_FOUND if missing else GRPC_UNIMPLEMENTED,
                    f"unknown method {pstr}")
            service_obj, minfo, method_status = entry
            headers = {}
            for line in flat_headers.decode("latin-1").split("\n"):
                if line:
                    k, _, v = line.partition(": ")
                    headers[k] = v
            # rpcz: chain this dispatch under the caller's span when the
            # request carried x-bd-trace-* gRPC metadata (the native
            # client lane stamps it; values hex)
            span = None
            try:
                tid = headers.get("x-bd-trace-id")
                if tid:
                    from brpc_tpu import rpcz as _rpcz

                    span = _rpcz.Span(
                        "server", f"grpc {pstr}", trace_id=int(tid, 16),
                        parent_span_id=int(
                            headers.get("x-bd-span-id") or "0", 16))
            except Exception:
                span = None
            cntl = Controller()
            cntl.server = server
            cntl.service_name, cntl.method_name = parts[0], parts[1]
            cntl.server_start_time = _time.monotonic()
            timeout = headers.get("grpc-timeout")
            if timeout:
                cntl.timeout_ms = _parse_grpc_timeout(timeout)
            if not method_status.on_requested():
                return respond(b"", GRPC_RESOURCE_EXHAUSTED,
                               "reached max_concurrency")
            request = minfo.request_class()
            body = grpc_unwrap(data)
            try:
                if body:
                    request.ParseFromString(body)
            except Exception as e:
                method_status.on_response(errors.EREQUEST,
                                          cntl.server_start_time)
                return respond(b"", GRPC_INTERNAL,
                               f"fail to parse request: {e}")
            response = minfo.response_class()
            responded = [False]

            def done():
                if responded[0]:
                    return
                responded[0] = True
                method_status.on_response(cntl.error_code_value,
                                          cntl.server_start_time)
                if cntl.failed():
                    respond(b"",
                            error_to_grpc_status(cntl.error_code_value),
                            cntl.error_text_value)
                else:
                    respond(response.SerializeToString(), GRPC_OK)
                # the span ends when the CALL completes (done may fire
                # from another thread long after the handler returned —
                # the async-done contract tpu_std_protocol documents),
                # so latency/error reflect the real completion
                if span is not None:
                    try:
                        span.end(cntl.error_code_value)
                    except Exception:
                        pass

            try:
                if span is not None:
                    from brpc_tpu import rpcz as _rpcz

                    with _rpcz.parent_scope(span):
                        minfo.handler(service_obj, cntl, request, response,
                                      done)
                else:
                    minfo.handler(service_obj, cntl, request, response,
                                  done)
            except Exception as e:
                if not responded[0]:
                    cntl.set_failed(errors.EINVAL, f"method raised: {e}")
                    done()
        except Exception as e:
            respond(b"", GRPC_INTERNAL, f"py-lane grpc dispatch: {e}")

    def _handle_redis(self, packed: bytes, sock_id: int, seq: int):
        """kind-6 dispatch: argv was RESP-parsed natively (count +
        (len,bytes)* packing); run the Python RedisService handler and
        answer through the native reorder window (nat_redis_respond)."""
        import struct as _struct

        from brpc_tpu.rpc.redis import RedisReply

        try:
            (count,) = _struct.unpack_from(">I", packed, 0)
            pos = 4
            args = []
            for _ in range(count):
                (n,) = _struct.unpack_from(">I", packed, pos)
                pos += 4
                args.append(packed[pos:pos + n])
                pos += n
            service = getattr(self.server, "redis_service", None)
            if service is None:
                reply = RedisReply.error("ERR no redis service")
            else:
                reply = service.dispatch(args)
        except Exception as e:
            reply = RedisReply.error(f"ERR dispatch raised: {e}")
        try:
            encoded = reply.encode()
        except Exception as e:
            # e.g. a handler returned a plain str: the seq MUST still be
            # answered or the ordered window wedges the connection
            encoded = RedisReply.error(f"ERR bad reply object: {e}").encode()
        try:
            native.redis_respond(sock_id, seq, encoded)
        except Exception:
            pass  # socket already gone; the session dies with it

    def _handle_http(self, verb: bytes, uri: bytes, flat_headers: bytes,
                     body: bytes, sock_id: int, seq: int):
        """kind-3 dispatch: rebuild the HttpRequest from natively-parsed
        fields and run the unchanged Python HTTP server path (routing,
        RESTful map, builtin console, RPC-over-HTTP). Ordering across
        pipelined requests is native-side, so workers may process
        same-connection requests concurrently."""
        from brpc_tpu.butil.iobuf import IOBuf as _IOBuf
        from brpc_tpu.rpc.http_message import HttpRequest
        from brpc_tpu.rpc.http_protocol import (
            HttpInputMessage,
            process_request as http_process_request,
        )

        span = None
        shim = None
        try:
            req = HttpRequest(verb.decode("latin-1"), uri.decode("latin-1"))
            hd = req.headers._headers
            for line in flat_headers.decode("latin-1").split("\n"):
                if line:
                    k, _, v = line.partition(": ")
                    hd[k] = v  # keys pre-lowercased natively
            if body:
                req.body = _IOBuf(body)
            msg = HttpInputMessage(req)
            shim = _NativeHttpShim(sock_id, seq)
            msg.socket = shim
            msg.arg = self.server
            # rpcz: chain under the caller's span when the request carried
            # x-bd-trace-* headers (hex; stamped by the native client lane)
            try:
                tid = hd.get("x-bd-trace-id")
                if tid:
                    from brpc_tpu import rpcz as _rpcz

                    span = _rpcz.Span(
                        "server",
                        f"{verb.decode('latin-1')} {uri.decode('latin-1')}",
                        trace_id=int(tid, 16),
                        parent_span_id=int(hd.get("x-bd-span-id") or "0",
                                           16))
            except Exception:
                span = None
            if span is not None:
                from brpc_tpu import rpcz as _rpcz

                # armed on the shim: the span ends at the RESPONSE write
                # (handlers may respond asynchronously long after this
                # function returns — ending at handler-return would
                # record phantom latency/status for them)
                shim.span = span
                with _rpcz.parent_scope(span):
                    http_process_request(msg)
            else:
                http_process_request(msg)
        except Exception as e:
            # the dispatch itself blew up before a response reached the
            # shim: the span must still submit — a failing request is
            # exactly what the trace exists to debug
            if shim is not None and shim.span is not None:
                shim.span = None
                try:
                    span.end(500)
                except Exception:
                    pass
            body = f"{e}\n".encode()
            resp = (f"HTTP/1.1 500 Internal Server Error\r\n"
                    f"Content-Length: {len(body)}\r\n\r\n").encode() + body
            try:
                native.http_respond(sock_id, seq, resp)
            except Exception:
                pass
