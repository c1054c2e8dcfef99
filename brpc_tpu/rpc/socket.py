"""Socket — THE connection abstraction of the RPC layer.

Counterpart of brpc::Socket (/root/reference/src/brpc/socket.{h,cpp}):

* versioned 64-bit SocketId addressing into a ResourcePool, so a stale id
  can never touch a recycled connection (socket_inl.h:28-185);
* a write path shaped like the wait-free design of socket.h:293-333 — any
  thread appends to the write queue; exactly one becomes the writer, tries
  one inline write on its own thread, and hands leftovers to a KeepWrite
  scheduler task that waits for EPOLLOUT;
* SetFailed + health-check revival (socket.h:438-441,
  details/health_check.cpp:70-237): in-flight correlation ids registered on
  the socket are errored with EFAILEDSOCKET, and a timer probes the remote
  side until the socket revives;
* an app-level connect hook (`app_connect`, the AppConnect seam of
  socket.h:108-130) — the pluggable-transport seam where the device/ICI
  endpoint attaches, exactly where brpc's RDMA endpoint attaches.
"""
from __future__ import annotations

import socket as pysocket
import threading
import time
from collections import deque
from typing import Callable, Optional

from brpc_tpu import bvar
from brpc_tpu.bthread import start_background, timer_add
from brpc_tpu.bthread import id as bthread_id
from brpc_tpu.butil.endpoint import EndPoint
from brpc_tpu.butil.iobuf import IOBuf, IOPortal
from brpc_tpu.butil.pools import ResourcePool
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.event_dispatcher import get_global_dispatcher

_in_bytes = bvar.Adder("socket_in_bytes")
_out_bytes = bvar.Adder("socket_out_bytes")
_conn_count = bvar.Adder("socket_connection_count")


class SocketUser:
    """Owner hook — health checking override (socket.h:74-88)."""

    def before_recycle(self, sock: "Socket"):
        pass

    def check_health(self, sock: "Socket") -> bool:
        """Return True if the remote is healthy again (default: TCP probe)."""
        try:
            probe = pysocket.create_connection(
                (sock.remote_side.ip, sock.remote_side.port), timeout=1.0
            )
            probe.close()
            return True
        except OSError:
            return False

    def on_revived(self, sock: "Socket"):
        pass


class _WriteRequest:
    __slots__ = ("buf", "id_wait")

    def __init__(self, buf: IOBuf, id_wait: Optional[int]):
        self.buf = buf
        self.id_wait = id_wait


class Socket:
    _pool: ResourcePool = None
    _pool_lock = threading.Lock()
    # attribute names a freshly-reset Socket owns; anything beyond these
    # is protocol-attached dynamic state (h2 connections, pipelined-
    # correlation queues, parked esp/nova cids, mongo contexts, ...) and
    # must be cleared on revive()/recycling — stale protocol state on a
    # fresh TCP connection corrupts the stream. Captured automatically
    # from the first reset, so protocols can never forget to register.
    _core_attrs: "frozenset[str]" = None

    def __init__(self):
        self._reset()

    def _reset(self):
        self._clear_protocol_state()  # recycled objects keep attributes
        self._fd: Optional[pysocket.socket] = None
        self._sid: int = 0
        self.remote_side: Optional[EndPoint] = None
        self.local_side: Optional[EndPoint] = None
        self._failed = False
        self.error_code = 0
        self.error_text = ""
        self._write_q: deque = deque()
        self._write_lock = threading.Lock()
        self._connect_lock = threading.Lock()
        self._writing = False
        self._epollout = threading.Event()
        self._reading = False
        self._reading_lock = threading.Lock()
        # Lame duck (graceful server churn): the peer signaled it is
        # draining — in-flight RPCs keep completing here, but selection
        # (LB _usable, the single-connection reuse paths) must send NEW
        # calls elsewhere, and the eventual close is a PLANNED removal
        # (no circuit-breaker sample). Cleared by revive/_reset.
        self.lame_duck = False
        self.on_edge_triggered_events: Optional[Callable[["Socket"], None]] = None
        self.user: Optional[SocketUser] = None
        self.health_check_interval_s: float = -1
        self._hc_running = False
        self.read_portal = IOPortal()
        self.matched_protocol = None  # remembered by InputMessenger
        self._inflight_ids = set()  # correlation ids to fail on SetFailed
        self._inflight_lock = threading.Lock()
        self.connection_type = "single"
        self._conn_ready = False  # fd usable for RPC (post-handshake)
        self.app_connect = None  # AppConnect seam (device transport attaches)
        self.on_connected = None  # protocol-pin hook, runs pre-registration
        self.app_state = None  # transport-private state (e.g. DeviceEndpoint)
        self.ssl_context = None  # client TLS context (ChannelSSLOptions)
        self.conn_data = None  # owner context (e.g. pooled-socket home)
        self.create_time = time.monotonic()
        if Socket._core_attrs is None:
            Socket._core_attrs = frozenset(self.__dict__.keys())

    # -- pool & id ---------------------------------------------------------
    @classmethod
    def _get_pool(cls) -> ResourcePool:
        if cls._pool is None:
            with cls._pool_lock:
                if cls._pool is None:
                    cls._pool = ResourcePool(Socket)
        return cls._pool

    @classmethod
    def create(cls, fd: Optional[pysocket.socket] = None,
               remote_side: Optional[EndPoint] = None,
               on_edge_triggered_events=None,
               user: Optional[SocketUser] = None,
               health_check_interval_s: float = -1,
               app_connect=None, ssl_context=None) -> int:
        """Returns a SocketId; Socket.address(sid) resolves it (or None once
        recycled)."""
        sid, sock = cls._get_pool().get_resource()
        sock._reset()
        sock._sid = sid
        sock._fd = fd
        sock.remote_side = remote_side
        sock.on_edge_triggered_events = on_edge_triggered_events
        sock.user = user
        sock.health_check_interval_s = health_check_interval_s
        sock.app_connect = app_connect
        sock.ssl_context = ssl_context
        _conn_count.update(1)
        if fd is not None:
            fd.setblocking(False)
            sock._conn_ready = True
            sock._register_with_dispatcher()
        return sid

    @classmethod
    def address(cls, sid: int) -> Optional["Socket"]:
        """Version-validated id lookup (socket_inl.h:28-185 Address): None
        once the socket is recycled. A SetFailed socket is still
        addressable — failure is a separate state callers check with
        .failed(), exactly as in the reference (health check and error
        reporting need to reach failed-but-live sockets)."""
        return cls._get_pool().address(sid)

    @property
    def socket_id(self) -> int:
        return self._sid

    def fd(self) -> Optional[pysocket.socket]:
        return self._fd

    def failed(self) -> bool:
        return self._failed

    def mark_lame_duck(self):
        """The peer signaled graceful drain (tpu_std SHUTDOWN bit, h2
        GOAWAY, HTTP Connection: close): finish in-flight work on this
        connection, route new work elsewhere. Idempotent; NOT a failure
        — in-flight correlation ids stay registered and complete."""
        self.lame_duck = True

    def usable_for_new_calls(self) -> bool:
        """Healthy AND not draining: the selection predicate the LB and
        the single-connection reuse paths share."""
        return not self._failed and not self.lame_duck

    # -- connect -----------------------------------------------------------
    def connect(self, timeout_s: float = 1.0) -> int:
        """Client-side TCP connect (blocking in the caller's task, as a
        bthread-mode connect would); then the AppConnect hook upgrades the
        transport (RDMA handshake analog)."""
        try:
            fd = pysocket.create_connection(
                (self.remote_side.ip, self.remote_side.port), timeout=timeout_s
            )
        except OSError as e:
            return e.errno or errors.EFAILEDSOCKET
        fd.setsockopt(pysocket.IPPROTO_TCP, pysocket.TCP_NODELAY, 1)
        if self.ssl_context is not None:
            try:
                fd.settimeout(timeout_s)
                fd = self.ssl_context.wrap_socket(
                    fd, server_hostname=self.remote_side.ip)
            except OSError as e:
                try:
                    fd.close()
                except OSError:
                    pass
                return errors.ESSL if not e.errno else e.errno
        fd.setblocking(False)
        self._fd = fd
        try:
            host, port = fd.getsockname()[:2]
            self.local_side = EndPoint(host, port)
        except OSError:
            pass
        # AppConnect runs BEFORE dispatcher registration so the handshake
        # owns the connection's first bytes (the RDMA TCP-handshake order,
        # rdma_endpoint.h:94-115).
        if self.app_connect is not None:
            rc = self.app_connect(self)
            if rc != 0:
                self.set_failed(rc, "app connect failed")
                return rc
        # Protocol-pinning hook, ALSO pre-registration: a speaks-first
        # peer (h2 servers send SETTINGS immediately) must find the
        # client-side protocol state attached before the dispatcher can
        # deliver its first bytes. A hook failure is a failed connect.
        if self.on_connected is not None:
            try:
                self.on_connected(self)
            except Exception as e:
                self.set_failed(errors.EFAILEDSOCKET,
                                f"on_connected hook failed: {e}")
                return errors.EFAILEDSOCKET
        self._register_with_dispatcher()
        self._conn_ready = True
        return 0

    def ensure_connected(self, timeout_s: float = 1.0) -> int:
        """Lazy connect for sockets created unconnected (NS-created LB
        nodes); thread-safe connect-once: the connect lock is held across
        the whole dial so racing callers wait instead of double-dialing.
        The lock-free fast path keys on _conn_ready, which connect()
        publishes only AFTER the app-level handshake — a racing caller must
        not write RPC bytes into a handshake in progress."""
        if self._conn_ready:
            return 0
        with self._connect_lock:
            if self._conn_ready:
                return 0
            if self._failed:
                return self.error_code or errors.EFAILEDSOCKET
            return self.connect(timeout_s)

    def _register_with_dispatcher(self):
        fdno = self._fd.fileno()
        get_global_dispatcher(fdno).add_consumer(fdno, self.start_input_event)

    # -- read entry --------------------------------------------------------
    def start_input_event(self):
        """Dispatcher callback (Socket::StartInputEvent, socket.cpp:2312):
        start one reader task unless one is already draining this socket.
        The fd's read events are suspended while the reader runs (edge
        trigger + re-arm, as the reference's EPOLLET delivers)."""
        with self._reading_lock:
            if self._reading or self._failed:
                return
            self._reading = True
        handler = self.on_edge_triggered_events
        if handler is None:
            with self._reading_lock:
                self._reading = False
            return
        fd = self._fd
        fdno = fd.fileno() if fd is not None else -1
        if fdno >= 0:
            get_global_dispatcher(fdno).suspend_read(fdno)
        start_background(self._run_input_handler, handler, fdno)

    def _run_input_handler(self, handler, fdno: int):
        try:
            handler(self)
        finally:
            with self._reading_lock:
                self._reading = False
            if fdno >= 0 and not self._failed:
                get_global_dispatcher(fdno).resume_read(fdno)

    # -- write path --------------------------------------------------------
    def write_backlog_bytes(self) -> int:
        """Bytes queued but not yet written — the write-overflow signal
        media relays use to shed slow consumers (socket.h backlog role)."""
        with self._write_lock:
            return sum(len(r.buf) for r in self._write_q)

    def write(self, buf: IOBuf, id_wait: Optional[int] = None,
              on_queued: Optional[Callable[[], None]] = None) -> int:
        """Queue a whole message; never interleaves with other writers
        (socket.h:293-333 semantics). `on_queued` runs under the queue lock
        at append time, so per-connection ordered state (pipelined
        correlation entries, as PipelinedInfo is pushed inside
        Socket::Write in the reference) matches the wire order exactly."""
        if id_wait is not None:
            with self._inflight_lock:
                self._inflight_ids.add(id_wait)
        req = _WriteRequest(buf, id_wait)
        with self._write_lock:
            # Re-check failure under the lock: a concurrent set_failed has
            # either drained the queue already (we must not append after
            # it) or will drain our request after we append.
            if self._failed:
                # Only notify if set_failed's in-flight sweep did not
                # already error this cid (double-error would look like two
                # failed attempts to the retry machinery).
                with self._inflight_lock:
                    was_present = id_wait in self._inflight_ids
                    self._inflight_ids.discard(id_wait)
                if was_present:
                    self._notify_failure(id_wait)
                return errors.EFAILEDSOCKET
            self._write_q.append(req)
            if on_queued is not None:
                on_queued()
            if self._writing:
                return 0  # current writer will flush us
            self._writing = True
        # We are the writer: one inline attempt on this thread, then hand
        # off to a KeepWrite task (socket.cpp:1287-1305,1585). The inline
        # attempt ends with our own message: draining what other threads
        # keep queuing behind it would hold this caller for as long as
        # they write (a stream writer on the shared connection held an
        # RPC caller for its whole 2 s storm).
        if not self._flush_some(until=req):
            start_background(self._keep_write)
        return 0

    def _flush_some(self, until: Optional[_WriteRequest] = None) -> bool:
        """Write until drained (True), or would-block or `until` written
        with more queued (False: the writer role passes to KeepWrite)."""
        while True:
            with self._write_lock:
                if not self._write_q:
                    self._writing = False
                    return True
                req = self._write_q[0]
            fd = self._fd
            if fd is None:
                # Concurrently failed; set_failed drains the queue. Step
                # down as writer so a revived socket can elect a new one.
                with self._write_lock:
                    self._writing = False
                return True
            try:
                n = req.buf.cut_into_socket(fd)
            except (BlockingIOError, InterruptedError):
                return False
            except OSError as e:
                self.set_failed(e.errno or errors.EFAILEDSOCKET,
                                f"write failed: {e}")
                return True
            if n > 0:
                _out_bytes.update(n)
            if req.buf.empty():
                with self._write_lock:
                    if self._write_q and self._write_q[0] is req:
                        self._write_q.popleft()
                    if req is until and self._write_q:
                        return False
            elif n == 0:
                return False

    def _keep_write(self):
        fdno = self._fd.fileno() if self._fd else -1
        while not self._failed:
            self._epollout.clear()
            if self._flush_some():
                return
            if self._failed or self._fd is None:
                return
            get_global_dispatcher(fdno).add_epollout(fdno, self._epollout.set)
            self._epollout.wait(timeout=1.0)

    # -- failure & revival -------------------------------------------------
    def set_failed(self, error_code: int = errors.EFAILEDSOCKET,
                   error_text: str = "") -> bool:
        with self._write_lock:
            if self._failed:
                return False
            self._failed = True
            self._conn_ready = False
        self.error_code = error_code
        self.error_text = error_text
        fd = self._fd
        if fd is not None:
            closed = False
            try:
                fdno = fd.fileno()
                if fdno >= 0:
                    # unregister AND close on the loop thread, ordered:
                    # a caller-side close would let the fd number be
                    # reused under the selector / the stale queued
                    # remove (the accept-vs-teardown race class)
                    get_global_dispatcher(fdno).remove_and_close(fdno, fd)
                    closed = True
            except OSError:
                pass
            if not closed:
                try:
                    fd.close()
                except OSError:
                    pass
            self._fd = None
        self._epollout.set()  # unblock KeepWrite
        # Fail queued writes and in-flight RPCs (socket.cpp SetFailed path).
        with self._write_lock:
            pending = list(self._write_q)
            self._write_q.clear()
        for req in pending:
            self._notify_failure(req.id_wait)
        with self._inflight_lock:
            inflight, self._inflight_ids = list(self._inflight_ids), set()
        for cid in inflight:
            bthread_id.error(cid, error_code, error_text or "socket failed")
        if self.health_check_interval_s > 0:
            self._start_health_check()
        return True

    def _notify_failure(self, id_wait: Optional[int]):
        if id_wait is not None:
            bthread_id.error(id_wait, self.error_code or errors.EFAILEDSOCKET,
                             self.error_text or "socket failed")

    def remove_inflight(self, cid: int):
        with self._inflight_lock:
            self._inflight_ids.discard(cid)

    def _start_health_check(self):
        if self.remote_side is None:
            return
        with self._write_lock:
            if self._hc_running:
                return
            self._hc_running = True
        timer_add(self.health_check_interval_s, self._health_check_once)

    def _health_check_once(self):
        user = self.user or _default_user
        try:
            healthy = user.check_health(self)
        except Exception:
            healthy = False
        if healthy:
            rc = self.revive()
            if rc == 0:
                with self._write_lock:
                    self._hc_running = False
                    failed_again = self._failed
                if failed_again:
                    # a set_failed that ran inside the revive window saw
                    # _hc_running still True and skipped scheduling — its
                    # failure is ours to cover, or the socket stays dead
                    # with no checker (seen as a rare no-revival hang in
                    # the churn test)
                    self._start_health_check()
                else:
                    user.on_revived(self)
                return
            # probe said healthy but the reconnect failed (transient):
            # keep the checker alive instead of abandoning the socket
        timer_add(self.health_check_interval_s, self._health_check_once)

    def revive(self) -> int:
        """Reconnect and clear the failed state (Socket::Revive role).

        Holds the connect lock across reset+dial: _reset_keep_identity
        clears _failed, and from that instant an ensure_connected caller
        would otherwise dial CONCURRENTLY — two fds, with _fd ending on
        one while the dispatcher delivers responses for the other (seen
        as a revived-but-deaf socket in the churn test)."""
        with self._connect_lock:
            if self._conn_ready and not self._failed:
                return 0  # a racing dial already revived it
            self._reset_keep_identity()
            rc = self.connect()
            if rc != 0:
                self._failed = True
                return rc
            return 0

    def _reset_keep_identity(self):
        self._failed = False
        self.lame_duck = False  # a revived connection serves new calls
        self.error_code = 0
        self.error_text = ""
        self.read_portal = IOPortal()
        self.matched_protocol = None
        self._epollout = threading.Event()
        self._writing = False
        self._conn_ready = False
        self._clear_protocol_state()

    def _clear_protocol_state(self):
        core = Socket._core_attrs
        if core is None:
            return
        for name in [n for n in self.__dict__ if n not in core]:
            del self.__dict__[name]

    def recycle(self):
        """Return to the pool — all outstanding SocketIds become stale."""
        if self.user:
            self.user.before_recycle(self)
        if not self._failed:
            self.set_failed(errors.ECLOSE, "recycled")
        self.health_check_interval_s = -1
        _conn_count.update(-1)
        Socket._get_pool().return_resource(self._sid)

    def __repr__(self):
        state = "failed" if self._failed else "ok"
        return f"Socket(id={self._sid:#x}, remote={self.remote_side}, {state})"


_default_user = SocketUser()
