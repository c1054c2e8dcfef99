"""Usercode worker process for the shm lane (nat_shm_lane.cpp).

The parent's native runtime parses HTTP/gRPC requests and fans kind-3/4
dispatch across N of these processes over the zero-copy descriptor-ring
transport — fixed 64-byte descriptors on lock-free per-worker rings,
payload bytes written once into a shared blob arena and handed to this
process as views (the request record's fields point straight into the
arena; the copy below into Python bytes is the only one on the worker
side). Python usercode scales past one interpreter's GIL the way the
reference runs usercode on all N workers (server.h:59-285 num_threads,
details/usercode_backup_pool.h:29-72).

This process holds its slot's ROBUST lifetime fence from attach until
death; a SIGKILL here surfaces as EOWNERDEAD on the parent's recovery
probe, which drains what this worker already answered, reaps what it
consumed, and frees the slot for a replacement.

Invocation (by brpc_tpu.rpc.server, not by hand):

    python -m brpc_tpu.rpc.shm_worker <shm_name> <module:factory>

`factory()` returns the list of Service objects to serve — the worker
rebuilds them (services must be constructible in a fresh process; the
same constraint every prefork server imposes on app state).
"""
from __future__ import annotations

import importlib
import sys

from brpc_tpu import bvar

# -- kind-8 tensor sink (ISSUE 15) ------------------------------------------
#
# Bulk tensor records (nat_shm_push_tensor / the device-lane fabric) used
# to hit a dead end here: no usercode hook, span silently released. A
# worker-side consumer registers a sink — called with a FabricLease whose
# view() reads the record's arena span IN PLACE; the sink OWNS the lease
# and may hold it past further takes, releasing out of order (e.g. after
# a jax.device_put completes). Unregistered records are counted, never
# silently dropped.

_tensor_sink = None
_sink_drops = bvar.Adder("shm_tensor_sink_unregistered_drops")


def set_tensor_sink(fn):
    """Register fn(lease) as this worker's bulk-tensor consumer (call it
    from the service factory — the factory runs in the worker process).
    The sink owns the lease: it must release() it, possibly out of
    order. Pass None to unregister."""
    global _tensor_sink
    _tensor_sink = fn


def tensor_sink_drops() -> int:
    """Records dropped because no sink was registered (observability —
    also exported as the shm_tensor_sink_unregistered_drops bvar)."""
    return _sink_drops.get_value()


def dispatch_tensor_record(native_mod, h) -> bool:
    """Route one kind-8 handle to the registered sink as a lease.
    Returns True when a sink consumed it (and now owns the span)."""
    lease = native_mod.FabricLease(h)
    sink = _tensor_sink
    if sink is None:
        _sink_drops.update(1)
        lease.release()
        return False
    try:
        sink(lease)
        return True
    except Exception:
        lease.release()  # idempotent: a sink that released already is fine
        return False


def main(shm_name: str, factory_spec: str) -> int:
    from brpc_tpu import native, rpc

    lib = native.load()  # signatures declared centrally in native.load()
    if lib.nat_shm_worker_attach(shm_name.encode()) != 0:
        print(f"shm_worker: cannot attach {shm_name}", file=sys.stderr)
        return 1

    # Responses ride the shm response ring; the parent's drainer feeds
    # them through the ordered per-session emitters. The module-level
    # rebind is worker-local: this process never owns sockets.
    def http_respond(sock_id, seq, data, close_after=False):
        return lib.nat_shm_respond(3, sock_id, seq, data, len(data), 0,
                                   None, 1 if close_after else 0)

    def grpc_respond(sock_id, stream_id, payload=b"", grpc_status=0,
                     grpc_message=""):
        return lib.nat_shm_respond(4, sock_id, stream_id, payload,
                                   len(payload), grpc_status,
                                   grpc_message.encode() or None, 0)

    native.http_respond = http_respond
    native.grpc_respond = grpc_respond
    native.sock_write = lambda *a, **k: -1       # no sockets here
    native.sock_write_batch = lambda *a, **k: 0
    native.sock_set_failed = lambda *a, **k: -1

    mod_name, _, fn_name = factory_spec.partition(":")
    factory = getattr(importlib.import_module(mod_name), fn_name)
    services = factory()

    from brpc_tpu.builtin import register_builtin_services
    from brpc_tpu.rpc.native_runtime import NativeRuntimeMount

    server = rpc.Server(rpc.ServerOptions())
    for svc in services:
        server.add_service(svc)
    register_builtin_services(server)
    mount = NativeRuntimeMount(server, num_threads=1)

    import os

    while True:
        h = lib.nat_shm_take_request(500)
        if not h:
            # attach armed PR_SET_PDEATHSIG, but belt-and-braces: a
            # reparented worker (parent hard-killed before prctl) must
            # not poll a leaked segment forever
            if os.getppid() == 1:
                return 0
            continue
        rec = native.req_info(h)  # every field in one crossing
        if rec.kind == 8:
            # bulk tensor record: deliver to the registered tensor sink
            # as an out-of-order-releasable lease (unregistered sinks
            # count the drop instead of losing it silently)
            dispatch_tensor_record(native, h)
            continue
        (_, kind, headers, payload, _, sock_id, seq, verb_or_blank, path,
         _, _) = native.req_tuple(rec)
        lib.nat_req_free(h)  # req_tuple copied out: the arena span frees
        try:
            if kind == 3:
                mount._handle_http(verb_or_blank, path, headers, payload,
                                   sock_id, seq)
            elif kind == 4:
                mount._handle_grpc(path, headers, payload, sock_id, seq)
        except Exception as e:  # answer rather than drop
            try:
                if kind == 3:
                    body = f"{e}\n".encode()
                    resp = (f"HTTP/1.1 500 Internal Server Error\r\n"
                            f"Content-Length: {len(body)}\r\n\r\n"
                            ).encode() + body
                    http_respond(sock_id, seq, resp)
                else:
                    grpc_respond(sock_id, seq, b"", 13, f"{e}")
            except Exception:
                pass


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]) or 0)
