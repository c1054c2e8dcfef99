"""tpu_std — the canonical framed protocol (baidu_std's role).

Counterpart of policy/baidu_rpc_protocol.cpp
(/root/reference/src/brpc/policy/baidu_rpc_protocol.cpp:95-137): a 12-byte
header `"TRPC" + body_size + meta_size`, then an RpcMeta protobuf, the
payload, and an attachment whose size rides in the meta. The attachment is
the tensor lane: device payloads are described by meta.tensors so the
receiver can rebuild jax.Arrays (host path materializes bytes; the device
transport hands buffers to XLA directly).

Server path ProcessRpcRequest (:314) and response path SendRpcResponse
(:139) are process_request / the done closure here; client response path
(:565) is process_response.
"""
from __future__ import annotations

import struct
import time

import numpy as np

from brpc_tpu import rpcz
from brpc_tpu.bthread import id as bthread_id
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.rpc import compress as compress_mod
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.protocol import (
    InputMessageBase,
    ParseResult,
    Protocol,
    ProtocolType,
    register_protocol,
)
from brpc_tpu.rpc.proto import rpc_meta_pb2

MAGIC = b"TRPC"
HEADER_LEN = 12
MAX_BODY = 512 * 1024 * 1024


class RpcMessage(InputMessageBase):
    __slots__ = ("meta", "payload", "attachment", "is_request")

    def __init__(self, meta, payload: bytes, attachment: IOBuf):
        super().__init__()
        self.meta = meta
        self.payload = payload
        self.attachment = attachment
        self.is_request = meta.HasField("request")


def pack_frame(meta, payload: bytes, attachment: IOBuf) -> IOBuf:
    meta.attachment_size = len(attachment)
    meta_bytes = meta.SerializeToString()
    body_size = len(meta_bytes) + len(payload) + len(attachment)
    out = IOBuf()
    out.append(MAGIC + struct.pack(">II", body_size, len(meta_bytes)))
    out.append(meta_bytes)
    if payload:
        out.append(payload)
    if len(attachment):
        out.append(attachment)  # zero-copy ref share
    return out


def _meta_shutdown_bit(meta_bytes: bytes) -> bool:
    """Lame-duck SHUTDOWN bit: top-level RpcMeta varint field 8 — our
    native servers' graceful-drain signal. The field is not in
    rpc_meta.proto (proto3 drops it silently), so scan the raw bytes
    with a minimal tag walk."""
    i, n = 0, len(meta_bytes)

    def varint(i):
        v = s = 0
        while i < n:
            b = meta_bytes[i]
            i += 1
            v |= (b & 0x7F) << s
            if not b & 0x80:
                return v, i
            s += 7
        return None, i

    while i < n:
        tag, i = varint(i)
        if tag is None:
            return False
        field, wire = tag >> 3, tag & 7
        if field == 8 and wire == 0:
            v, i = varint(i)
            return bool(v)
        if wire == 0:
            v, i = varint(i)
            if v is None:
                return False
        elif wire == 2:
            ln, i = varint(i)
            if ln is None or i + ln > n:
                return False
            i += ln
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            return False
    return False


def parse(portal: IOBuf, sock, read_eof: bool, arg) -> ParseResult:
    """ParseRpcMessage analog (baidu_rpc_protocol.cpp:95-137)."""
    if len(portal) < HEADER_LEN:
        head = portal.copy_to_bytes(min(4, len(portal)))
        if MAGIC.startswith(head):
            return ParseResult.not_enough()
        return ParseResult.try_others()
    header = portal.copy_to_bytes(HEADER_LEN)
    if header[:4] != MAGIC:
        return ParseResult.try_others()
    body_size, meta_size = struct.unpack(">II", header[4:12])
    if body_size > MAX_BODY or meta_size > body_size:
        return ParseResult.error_()
    if len(portal) < HEADER_LEN + body_size:
        return ParseResult.not_enough()
    portal.pop_front(HEADER_LEN)
    meta_bytes = portal.cutn_bytes(meta_size)
    meta = rpc_meta_pb2.RpcMeta()
    try:
        meta.ParseFromString(meta_bytes)
    except Exception:
        return ParseResult.error_()
    # Lame-duck signal (graceful server churn): mark the socket draining
    # — LB selection skips it, in-flight RPCs keep completing, and the
    # eventual close is a planned removal. A correlation_id-0 control
    # frame carries no call; rejection frames proceed normally (their
    # cid completes with ELIMIT, which the retry path re-balances).
    if meta.HasField("response") and _meta_shutdown_bit(meta_bytes):
        try:
            sock.mark_lame_duck()
        except AttributeError:
            pass  # shims without the flag (native raw lane)
    att_size = meta.attachment_size
    payload_size = body_size - meta_size - att_size
    if payload_size < 0:
        return ParseResult.error_()
    payload = portal.cutn_bytes(payload_size)
    attachment = portal.cut(att_size)
    return ParseResult.ok(RpcMessage(meta, payload, attachment))


# -- tensor attachment helpers (TPU-native lane) ---------------------------

def attach_arrays(cntl_attachment: IOBuf, meta, arrays):
    """Describe + append device arrays to an attachment."""
    for arr in arrays:
        t = meta.tensors.add()
        t.dtype = str(arr.dtype)
        t.shape.extend(int(d) for d in arr.shape)
        t.nbytes = int(arr.nbytes)
        cntl_attachment.append_device_array(arr)


def extract_arrays(attachment: IOBuf, meta):
    """Rebuild numpy arrays (host path) from a tensor-bearing attachment.
    The device transport overrides this with direct HBM handoff."""
    out = []
    for t in meta.tensors:
        raw = attachment.cutn_bytes(t.nbytes)
        try:
            import ml_dtypes  # bundled with jax: bfloat16 etc.

            dtype = np.dtype(t.dtype) if t.dtype in np.sctypeDict else np.dtype(
                getattr(ml_dtypes, t.dtype)
            )
        except (TypeError, AttributeError, ImportError):
            dtype = np.dtype(t.dtype)
        out.append(np.frombuffer(raw, dtype=dtype).reshape(tuple(t.shape)))
    return out


# -- client side -----------------------------------------------------------

def serialize_request(request, cntl: Controller):
    if request is None:
        return b""
    if isinstance(request, (bytes, bytearray)):
        return bytes(request)
    return request.SerializeToString()


def pack_request(payload: bytes, cntl: Controller, correlation_id: int) -> IOBuf:
    meta = rpc_meta_pb2.RpcMeta()
    service, _, method = cntl._method_full_name.rpartition(".")
    meta.request.service_name = service
    meta.request.method_name = method
    meta.request.log_id = cntl.log_id
    meta.request.trace_id = cntl.trace_id
    meta.request.span_id = cntl.span_id
    if cntl._deadline is not None:
        remain_ms = max(0, int((cntl._deadline - time.monotonic()) * 1000))
        meta.request.timeout_ms = remain_ms
    auth = (cntl._channel.options.auth
            if cntl._channel is not None else None)
    if auth is not None:
        cred = auth.generate_credential(cntl)
        if cred is None:
            raise ValueError("authenticator refused to generate credential")
        meta.request.auth_data = cred
    meta.correlation_id = correlation_id
    meta.compress_type = cntl.compress_type
    if cntl._request_stream is not None:
        meta.stream_id = cntl._request_stream.stream_id
    if cntl._outbound_tensors:
        # Tensor lane: the socket's DeviceEndpoint (or a per-call fallback)
        # fills meta.tensors + attachment (device_transport.py).
        from brpc_tpu.rpc.device_transport import DeviceEndpoint

        ep = (cntl._current_sock.app_state
              if cntl._current_sock is not None else None)
        if not isinstance(ep, DeviceEndpoint):
            ep = DeviceEndpoint()
        ep.prepare_send(cntl._outbound_tensors, meta,
                        cntl.request_attachment)
    payload = compress_mod.compress(payload, cntl.compress_type)
    return pack_frame(meta, payload, cntl.request_attachment)


def process_response(msg: RpcMessage):
    """Client completion (baidu_rpc_protocol.cpp:565): lock the attempt's
    CallId version and hand the controller the response."""
    cid = msg.meta.correlation_id
    try:
        cntl = bthread_id.lock(cid)
    except (KeyError, TimeoutError):
        return  # late/duplicate response for an already-ended RPC
    if not isinstance(cntl, Controller):
        try:
            bthread_id.unlock(cid)
        except Exception:
            pass
        return
    payload = compress_mod.decompress(msg.payload, msg.meta.compress_type)
    cntl._on_response(msg.meta, payload, msg.attachment, msg.socket)


# -- server side -----------------------------------------------------------

def send_rpc_response(sock, correlation_id: int, cntl: Controller,
                      response, attachment: IOBuf):
    """SendRpcResponse analog (baidu_rpc_protocol.cpp:139); the
    `rpc.respond` phase span."""
    with rpcz.phase("rpc.respond"):
        # Handlers may have pre-filled tensors into the response meta.
        meta = cntl._response_meta or rpc_meta_pb2.RpcMeta()
        meta.correlation_id = correlation_id
        meta.response.error_code = cntl.error_code_value
        if cntl.error_code_value:
            meta.response.error_text = cntl.error_text_value
        if cntl._accepted_stream is not None:
            meta.stream_id = cntl._accepted_stream.stream_id
        payload = b""
        if response is not None and not cntl.failed():
            payload = (bytes(response)
                       if isinstance(response, (bytes, bytearray))
                       else response.SerializeToString())
            payload = compress_mod.compress(payload, cntl.compress_type)
        meta.compress_type = cntl.compress_type
        frame = pack_frame(meta, payload, attachment)
        sock.write(frame)
    if cntl.close_connection_flag:
        sock.set_failed(errors.ECLOSE, "close_connection requested")


def process_request(msg: RpcMessage):
    """Server path (ProcessRpcRequest, baidu_rpc_protocol.cpp:314), from
    the request's parse to the response handed to the socket: the
    `rpc.handle` phase span."""
    with rpcz.phase("rpc.handle"):
        return _process_request(msg)


def _process_request(msg: RpcMessage):
    server = msg.arg
    meta = msg.meta
    cid = meta.correlation_id
    sock = msg.socket
    cntl = Controller()
    cntl.server = server
    cntl.remote_side = sock.remote_side
    cntl.service_name = meta.request.service_name
    cntl.method_name = meta.request.method_name
    cntl.log_id = meta.request.log_id
    cntl.trace_id = meta.request.trace_id
    cntl.compress_type = meta.compress_type
    cntl.request_attachment = msg.attachment
    cntl._remote_stream_id = meta.stream_id
    cntl._server_socket = sock
    cntl._rpc_meta = meta
    cntl._response_meta = rpc_meta_pb2.RpcMeta()
    cntl.server_start_time = time.monotonic()
    if meta.request.timeout_ms > 0:
        cntl.timeout_ms = meta.request.timeout_ms

    if server is None:
        cntl.set_failed(errors.EINVAL, "no server bound to connection")
        return send_rpc_response(sock, cid, cntl, None, IOBuf())

    if server.auth is not None:
        ok, ctx = False, None
        try:
            ok, ctx = server.auth.verify_credential(
                meta.request.auth_data, sock.remote_side)
        except Exception:
            ok = False
        if not ok:
            cntl.set_failed(errors.EAUTH, "authentication failed")
            return send_rpc_response(sock, cid, cntl, None, IOBuf())
        cntl.auth_context = ctx

    if server.interceptor is not None:
        try:
            ok, code, text = server.interceptor(cntl)
        except Exception as e:
            ok, code, text = False, errors.EINVAL, f"interceptor raised: {e}"
        if not ok:
            cntl.set_failed(code or errors.EPERM, text or "rejected")
            return send_rpc_response(sock, cid, cntl, None, IOBuf())

    entry = server.find_method(cntl.service_name, cntl.method_name)
    if entry is None:
        missing_service = server.find_service(cntl.service_name) is None
        cntl.set_failed(
            errors.ENOSERVICE if missing_service else errors.ENOMETHOD,
            f"unknown {cntl.service_name}.{cntl.method_name}",
        )
        return send_rpc_response(sock, cid, cntl, None, IOBuf())
    service_obj, method_info, method_status = entry

    if not method_status.on_requested():
        cntl.set_failed(errors.ELIMIT, "reached max_concurrency")
        return send_rpc_response(sock, cid, cntl, None, IOBuf())

    request = method_info.request_class()
    try:
        payload = compress_mod.decompress(msg.payload, meta.compress_type)
        if payload:
            request.ParseFromString(payload)
    except Exception as e:
        method_status.on_response(errors.EREQUEST, cntl.server_start_time)
        cntl.set_failed(errors.EREQUEST, f"fail to parse request: {e}")
        return send_rpc_response(sock, cid, cntl, None, IOBuf())

    response = method_info.response_class()
    responded = [False]

    span = rpcz.start_server_span(
        f"{cntl.service_name}.{cntl.method_name}", meta, sock.remote_side)
    cntl.span = span
    if span is not None:
        span.request_size = len(msg.payload)

    if server.session_pool is not None:
        cntl.session_local_data = server.session_pool.borrow()

    def done():
        if responded[0]:
            return
        responded[0] = True
        method_status.on_response(cntl.error_code_value,
                                  cntl.server_start_time)
        if span is not None:
            span.end(cntl.error_code_value)
        if server.session_pool is not None:
            server.session_pool.return_(cntl.session_local_data)
            cntl.session_local_data = None
        send_rpc_response(sock, cid, cntl, response,
                          cntl.response_attachment)

    # The handler owns `done` (may call it asynchronously later); we only
    # respond for it if it raises before responding. Nested client calls
    # made by the handler parent under this span (tls_bls parenting).
    try:
        with rpcz.parent_scope(span):
            method_info.handler(service_obj, cntl, request, response, done)
    except Exception as e:
        if not responded[0]:
            cntl.set_failed(errors.EINVAL, f"method raised: {e}")
            done()


register_protocol(Protocol(
    name="tpu_std",
    type=ProtocolType.TPU_STD,
    parse=parse,
    serialize_request=serialize_request,
    pack_request=pack_request,
    process_request=process_request,
    process_response=process_response,
))
