"""Device transport — the ICI endpoint playing brpc's RDMA role.

Counterpart of the RDMA subsystem (SURVEY.md section 2.9,
/root/reference/src/brpc/rdma/):

* DeviceBlockPool ⇔ block_pool.{h,cpp}: pre-registered arenas carved into
  size-class blocks (8KB/64KB/2MB there; byte-capacity HBM buffers here),
  plugged in where IOBuf gets its memory, so payloads are transfer-ready
  without a registration step on the hot path.
* DeviceEndpoint ⇔ RdmaEndpoint (rdma_endpoint.h:55-226): lives inside a
  Socket via the app_connect seam (socket.h:108-130); the TCP connection
  performs the credential handshake (the GID/QPN exchange analog —
  platform, device ids, process identity) through the state machine
  UNINIT→HANDSHAKING→ESTABLISHED, falling back to plain TCP when either
  side has no device (FALLBACK_TCP, rdma_endpoint.h:94-115); sends retain
  source buffers until the peer's ACK (the _sbuf retention discipline,
  rdma_endpoint.h:214), with a sliding window limiting in-flight bytes and
  window updates piggybacked on ACK frames (rdma_endpoint.h:132-138).
* device_helper ⇔ rdma_helper.{h,cpp}: device discovery/identity.

Transfer semantics by locality:
  same process  — zero-copy: the receiving side gets the SAME jax.Array
                  (the loopback-ICI stand-in; on a pod this is an ICI DMA);
  cross process — tensor bytes ride the TCP wire (the FALLBACK_TCP path),
                  re-materialized with jax.device_put on arrival.
"""
from __future__ import annotations

import contextlib
import functools
import struct
import threading
import uuid
from typing import Dict, List, Optional, Tuple

from brpc_tpu import bvar, rpcz
from brpc_tpu.butil.iobuf import IOBuf

# -- allocator tuning (the tcmalloc role) -----------------------------------
# brpc ships with tcmalloc precisely because glibc malloc mmap()s every
# multi-MB buffer and returns it on free, so each transfer repays the full
# page-fault + munmap cost (docs/cn/memory_management.md rationale). The
# transfer lanes here allocate an N-MB landing buffer per receive; raising
# the mmap threshold keeps those on the reusable heap — measured 2x on the
# same-host copy-out path.


def _tune_allocator():
    try:
        import ctypes

        libc = ctypes.CDLL("libc.so.6", use_errno=True)
        M_MMAP_THRESHOLD = -3
        libc.mallopt(M_MMAP_THRESHOLD, 256 << 20)
    except (OSError, AttributeError):
        pass  # non-glibc platform: allocator stays stock


_tune_allocator()

# -- device_helper (rdma_helper analog) ------------------------------------

_process_uuid = uuid.uuid4().hex


def _host_boot_id() -> str:
    """Same-host identity: two processes share a zero-copy arena only when
    they share a kernel (the GID-subnet check analog)."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            return f.read().strip()
    except OSError:
        import socket as pysocket

        return pysocket.gethostname()


_boot_id = _host_boot_id()


def local_device_info(arm_fabric: bool = False) -> dict:
    """Discovery: platform + device ids (GID/LID discovery analog). The
    send arena's name rides along like the GID/QPN credentials so the peer
    can map our registered memory. With arm_fabric=True (the SERVER half
    of the handshake) the descriptor-ring tensor fabric is armed and its
    segment name advertised, so same-host peers can attach as producers
    and push payloads with zero bytes on the wire (the ring lane)."""
    arena = default_send_arena()
    info = {
        "process": _process_uuid,
        "host": _boot_id,
        "arena": arena.name if arena is not None else "",
        # descriptor-ring fabric inbox (ISSUE 15): advertised only when
        # the receiver drain is actually running — a peer that sees a
        # name will push kind-8 payloads with nothing on the wire
        "fabric": _fabric_arm_receiver() if arm_fabric else "",
        # advertised ONLY when the server actually started: a peer that
        # sees True may publish xfer-lane payloads with nothing on the
        # wire, so import success alone is not proof enough
        "xfer": _global_xfer_server() is not None,
    }
    import jax

    # a process that cannot reach its backend fails here, loudly: it
    # must not pass for a device-less peer
    devs = jax.devices()
    info["platform"] = devs[0].platform
    info["device_count"] = len(devs)
    return info


# -- DeviceBlockPool (block_pool analog) ------------------------------------

_pool_acquired = bvar.Adder("device_block_pool_acquired")
_pool_released = bvar.Adder("device_block_pool_released")
# acquisitions by block word width: which widths the traffic engages
_pool_acquired_by_word = {
    word: bvar.Adder(f"device_block_pool_acquired_u{8 * width}")
    for width, word in ((1, "uint8"), (2, "uint16"), (4, "uint32"))}


def _block_word(np_dtype) -> str:
    """The unsigned word a pooled block holds for a payload of `np_dtype`:
    as wide as one element (up to 4 bytes), so the view is a same-width
    bitcast. Wider dtypes keep byte blocks."""
    import numpy as np

    return {2: "uint16", 4: "uint32"}.get(np.dtype(np_dtype).itemsize,
                                          "uint8")


class DeviceBlockPool:
    """Pre-allocated HBM blocks by size class and word width — the role
    of the reference's registered-memory pool (block_pool.h:29-94: arenas
    carved into 8KB/64KB/2MB blocks that ALL transfer traffic flows
    through).

    The jax-idiomatic rendition: incoming transfer bytes are written into
    a pooled buffer with a DONATING jitted update, so the pooled HBM is
    genuinely the memory the bytes land in (no per-transfer allocation),
    then sliced and bitcast into the typed array handed to the
    application. A block holds unsigned words as wide as the payload's
    elements (uint8, uint16 or uint32; `_block_word`), so that bitcast
    reinterprets whole elements and never repacks bytes; size classes are
    in bytes whatever the word. Byte blocks are allocated up front, a
    wider word's blocks the first time that width lands.
    acquire()/release() remain available for raw leases."""

    SIZE_CLASSES = (8 << 10, 64 << 10, 2 << 20)  # block_pool's classes

    def __init__(self, blocks_per_class: int = 8, device=None):
        import jax

        self._device = device or jax.devices()[0]
        self._blocks_per_class = blocks_per_class
        self._free: Dict[Tuple[int, str], List] = {}
        self._lock = threading.Lock()
        with self._lock:
            self._allocate_word("uint8")

    @property
    def device(self):
        """The device whose HBM the pool's blocks live in."""
        return self._device

    def _new_block(self, size_class: int, word: str):
        import jax
        import jax.numpy as jnp
        import numpy as np

        return jax.device_put(
            jnp.zeros((size_class // np.dtype(word).itemsize,), word),
            self._device)

    def _allocate_word(self, word: str):
        # caller holds self._lock
        if (self.SIZE_CLASSES[0], word) in self._free:
            return
        for size in self.SIZE_CLASSES:
            self._free[(size, word)] = [
                self._new_block(size, word)
                for _ in range(self._blocks_per_class)]

    def acquire(self, nbytes: int, word: str = "uint8"):
        """Returns (size_class, buffer) — a block of `word` elements
        holding at least `nbytes` — or None if exhausted/oversized."""
        with self._lock:
            self._allocate_word(word)
            for size in self.SIZE_CLASSES:
                free = self._free[(size, word)]
                if nbytes <= size and free:
                    _pool_acquired.update(1)
                    _pool_acquired_by_word[word].update(1)
                    return size, free.pop()
        return None

    def release(self, size_class: int, buf):
        key = (size_class, str(buf.dtype))
        with self._lock:
            if key in self._free:
                self._free[key].append(buf)
                _pool_released.update(1)

    def stats(self) -> Dict[Tuple[int, str], int]:
        """Free blocks per (size class in bytes, word dtype name)."""
        with self._lock:
            return {k: len(v) for k, v in self._free.items()}

    @staticmethod
    def _pad_quantum(nbytes: int) -> int:
        # quantize the host-side staging length to powers of two so the
        # jit cache stays bounded (~10 entries per class) instead of one
        # compiled fill per distinct payload size
        q = 4096
        while q < nbytes:
            q <<= 1
        return q

    def landing(self) -> "Landing":
        """`with pool.landing() as land: arr = land.put(...)`."""
        return Landing(self)

    def put_via_pool(self, host_u8, np_dtype, shape, device=None):
        """Host->device put of raw bytes THROUGH pooled memory: one
        `Landing.put` in a scope of its own. Returns a ready jax.Array of
        `np_dtype`/`shape`; a dtype the device would narrow (see
        `device_holds`) is refused."""
        with self.landing() as land:
            return land.put(host_u8, np_dtype, shape, device)


class Landing:
    """One message landing in HBM. Each `put` dispatches one tensor's
    bytes and returns its array at once; leaving the scope waits for
    every array landed in it, once, and only then hands the pooled
    blocks back to the freelist, where the next fill may donate them.
    Host memory the landings read from (a ring lease, an arena span)
    may be reused once the scope has closed, on success or failure."""

    def __init__(self, pool: DeviceBlockPool):
        self._pool = pool
        self._arrays: List = []
        self._blocks: List[Tuple[int, object]] = []  # to hand back

    def __enter__(self) -> "Landing":
        return self

    def __exit__(self, *exc):
        import jax

        try:
            if self._arrays:
                with rpcz.phase("tensor.land_wait"):
                    jax.block_until_ready(self._arrays)
        finally:
            for size_class, block in self._blocks:
                self._pool.release(size_class, block)

    def track(self, arrays) -> List:
        """Arrays landed by other means, for the scope's wait."""
        arrays = list(arrays)
        self._arrays.extend(arrays)
        return arrays

    def put(self, host_u8, np_dtype, shape, device=None):
        """The bytes land in a pooled block of `np_dtype`'s word width
        (donated update — same HBM each time), then a device-side
        slice+bitcast produces the typed array. A plain device_put when
        the pool is exhausted, the payload is oversized, or a different
        target device is asked for."""
        import jax
        import numpy as np

        pool = self._pool
        if not device_holds(np_dtype):
            raise ValueError(f"put_via_pool: {np.dtype(np_dtype)} would be "
                             f"narrowed on the device (x64 is off)")
        nbytes = int(host_u8.size)
        target = device or pool.device
        word = _block_word(np_dtype)
        got = pool.acquire(nbytes, word) if target == pool.device else None
        if got is None:
            with rpcz.phase("tensor.device_put"):
                arr = jax.device_put(host_u8.view(np_dtype).reshape(shape),
                                     target)
            self._arrays.append(arr)
            return arr
        size_class, buf = got
        try:
            padded = min(pool._pad_quantum(nbytes), size_class)
            if padded != nbytes:
                # np.empty + tail zero: one nbytes memcpy plus a small
                # tail clear, not a full padded zero-fill + copy
                with rpcz.phase("tensor.stage"):
                    staged = np.empty(padded, dtype=np.uint8)
                    staged[:nbytes] = host_u8
                    staged[nbytes:] = 0
            else:
                staged = host_u8
            fill, view = pool_programs()
            with rpcz.phase("tensor.fill"):
                # a reinterpretation, no copy: nbytes is a whole number
                # of elements and the padded length a multiple of 4096
                filled = fill(buf, staged.view(word))
        except BaseException:
            # the fill failed mid-donation: buf may be dead — refill
            # the class with a fresh buffer instead of a poisoned one
            pool.release(size_class, pool._new_block(size_class, word))
            raise
        # `filled` aliases the donated memory; it IS the pool buffer
        # from here on, home once the scope's wait has seen the view
        self._blocks.append((size_class, filled))
        with rpcz.phase("tensor.view"):
            arr = view(filled, nbytes, np.dtype(np_dtype), tuple(shape))
        self._arrays.append(arr)
        return arr


@functools.lru_cache(maxsize=None)
def pool_programs():
    """The pool's two device programs, jitted once per process (jit keys
    them by shape): `fill(block, staged)` writes staged words over the
    head of a DONATED block, and `view(filled, nbytes, dtype, shape)`
    slices the head's `nbytes` and bitcasts them into the typed array —
    elementwise when the block's word is as wide as `dtype`, else by
    grouping bytes (a byte block under a wider dtype). Their traces name
    them `jit_pool_fill` and `jit_pool_view`."""
    import jax

    def pool_fill(block, staged):
        return jax.lax.dynamic_update_slice(block, staged, (0,))

    def pool_view(filled, nbytes, dtype, shape):
        width = filled.dtype.itemsize
        head = filled[:nbytes // width]
        if dtype.itemsize == width:
            head = head.view(dtype)
        else:
            head = jax.lax.bitcast_convert_type(
                head.reshape(-1, dtype.itemsize // width), dtype)
        return head.reshape(shape)

    return (jax.jit(pool_fill, donate_argnums=(0,)),
            jax.jit(pool_view, static_argnums=(1, 2, 3)))


_default_pool: Optional[DeviceBlockPool] = None
_default_pool_lock = threading.Lock()


def default_block_pool() -> DeviceBlockPool:
    global _default_pool
    if _default_pool is None:
        with _default_pool_lock:
            if _default_pool is None:
                _default_pool = DeviceBlockPool()
    return _default_pool


# -- HostArena (the cross-process half of block_pool) ------------------------
#
# The reference registers big arenas with ibv_reg_mr so the NIC can DMA
# them (block_pool.h:29-94). The TPU-host translation is a PINNED-HOST
# shared-memory arena: the sender stages tensor bytes into it once
# (device->host DMA), the wire carries only an (arena, offset) descriptor,
# and a same-host peer maps the arena and hands the bytes straight to
# jax.device_put — no payload bytes on the TCP stream, no pickling.

class HostArena:
    """Shared pinned-host arena carved by a first-fit span allocator.

    Pages are PRE-FAULTED at creation/attach (one touch per 4KB page):
    on sandboxed/TPU hosts the first write to a fresh shm mapping costs
    orders of magnitude more than the copy itself (BENCH_r05 measured
    the staging lane at 0.27 GB/s while warm copies ran >1.5 GB/s —
    first-touch fault cost, not memory bandwidth). Registration-time
    prefault is exactly what ibv_reg_mr does for the reference's RDMA
    arenas: pay the pinning once, outside the transfer path."""

    def __init__(self, size: int = 64 << 20, name: Optional[str] = None,
                 create: bool = True, prefault: bool = True):
        from multiprocessing import shared_memory

        if create:
            self.shm = shared_memory.SharedMemory(create=True, size=size)
            if prefault:
                self._prefault(write=True)
        else:
            self.shm = shared_memory.SharedMemory(name=name)
            # A non-owner must NOT let Python's resource tracker unlink
            # the segment when THIS process exits (3.12 has no track=False;
            # the tracker would otherwise destroy the owner's live arena).
            from multiprocessing import resource_tracker

            resource_tracker.unregister(self.shm._name, "shared_memory")
            if prefault:
                # attach side reads: fault the mapping in before the
                # receive path timing matters
                self._prefault(write=False)
        self.name = self.shm.name
        self.size = self.shm.size
        self._free = [(0, self.size)]  # sorted (offset, size) spans
        self._lock = threading.Lock()
        self.owner = create

    def _prefault(self, write: bool):
        import numpy as np

        view = np.frombuffer(self.shm.buf, dtype=np.uint8)
        if write:
            view[::4096] = 0  # one store per page
        else:
            int(view[::4096].sum())  # one load per page

    # -- span allocator ----------------------------------------------------
    def alloc(self, nbytes: int) -> Optional[int]:
        nbytes = max(64, (nbytes + 63) & ~63)  # 64B-aligned spans
        with self._lock:
            for i, (off, sz) in enumerate(self._free):
                if sz >= nbytes:
                    if sz == nbytes:
                        self._free.pop(i)
                    else:
                        self._free[i] = (off + nbytes, sz - nbytes)
                    return off
        return None

    def free(self, offset: int, nbytes: int):
        nbytes = max(64, (nbytes + 63) & ~63)
        with self._lock:
            self._free.append((offset, nbytes))
            self._free.sort()
            merged = []
            for off, sz in self._free:
                if merged and merged[-1][0] + merged[-1][1] == off:
                    merged[-1] = (merged[-1][0], merged[-1][1] + sz)
                else:
                    merged.append((off, sz))
            self._free = [(o, s) for o, s in merged]

    def free_bytes(self) -> int:
        with self._lock:
            return sum(s for _, s in self._free)

    def view(self, offset: int, nbytes: int) -> memoryview:
        return memoryview(self.shm.buf)[offset:offset + nbytes]

    # -- blockmem_allocate adapter ------------------------------------------
    def make_block(self, capacity: int = 256 << 10):
        """A writable IOBuf Block carved from this arena (the
        blockmem_allocate hook, iobuf.cpp:163-168); freed back when the
        block is collected. Returns None when exhausted."""
        import weakref

        from brpc_tpu.butil.iobuf import Block

        off = self.alloc(capacity)
        if off is None:
            return None
        b = Block.__new__(Block)
        b.data = self.view(off, capacity)
        b.size = 0
        b.capacity = capacity
        b.kind = Block.USER
        b.deleter = None
        b.meta = off
        b.device_array = None
        weakref.finalize(b, self.free, off, capacity)
        return b

    def install_as_iobuf_allocator(self, capacity: int = 256 << 10):
        """Point IOBuf's block factory at this arena, so every appended
        payload is staged in transfer-registered memory (the 'all IOBuf
        memory is RDMA-registered' configuration of docs/cn/rdma.md)."""
        from brpc_tpu.butil import iobuf as iobuf_mod

        iobuf_mod.set_block_allocator(lambda: self.make_block(capacity))

    def close(self):
        try:
            if self.owner:
                self.shm.unlink()
        except OSError:
            pass
        try:
            self.shm.close()
        except BufferError:
            # Live memoryviews (IOBuf blocks / transfer views carved from
            # the arena) still export the mapping. DETACH instead of
            # retrying: null the SharedMemory's buf/mmap so its __del__
            # cannot re-raise (the round-2 unraisable-BufferError leak
            # seam); the orphaned mmap object unmaps itself once the last
            # exported view dies — no leak, no warning.
            self.shm._buf = None
            self.shm._mmap = None
        except OSError:
            pass


_send_arena: Optional[HostArena] = None
_send_arena_lock = threading.Lock()
_send_arena_enabled = True


def default_send_arena() -> Optional[HostArena]:
    """Process-wide outbound arena (created lazily; advertised in the
    handshake)."""
    global _send_arena
    if not _send_arena_enabled:
        return None
    if _send_arena is None:
        with _send_arena_lock:
            if _send_arena is None:
                try:
                    _send_arena = HostArena()
                except OSError:
                    return None
    return _send_arena


_attached_arenas: Dict[str, HostArena] = {}
_attached_lock = threading.Lock()


def _cleanup_arenas():
    global _send_arena
    if _send_arena is not None:
        _send_arena.close()
        _send_arena = None
    with _attached_lock:
        for arena in _attached_arenas.values():
            arena.close()
        _attached_arenas.clear()


import atexit  # noqa: E402

atexit.register(_cleanup_arenas)


def attach_arena(name: str) -> Optional[HostArena]:
    """Map a peer's arena by name (their ibv_reg_mr region, our mmap)."""
    with _attached_lock:
        arena = _attached_arenas.get(name)
        if arena is None:
            try:
                arena = HostArena(name=name, create=False)
            except (OSError, FileNotFoundError):
                return None
            _attached_arenas[name] = arena
    return arena


# -- in-process tensor exchange (the loopback "ICI") ------------------------

_inproc_registry: Dict[int, List] = {}
_inproc_lock = threading.Lock()
_inproc_next = [1]

_dev_zero_copy = bvar.Adder("device_transport_zero_copy_transfers")
_dev_shm = bvar.Adder("device_transport_shm_transfers")
_dev_wire = bvar.Adder("device_transport_wire_transfers")
_dev_xfer = bvar.Adder("device_transport_xfer_transfers")
_dev_ring = bvar.Adder("device_transport_ring_transfers")


# -- descriptor-ring tensor fabric (the ring lane, ISSUE 15) ----------------
#
# The same-host cross-process lane re-plumbed onto the PR-3 descriptor
# ring (nat_shm_lane.cpp): the RECEIVER owns a shm segment whose slots
# peers claim as PRODUCERS; a send writes its payload ONCE into the
# shared blob arena (nat_shm_fabric_push, kind-8 descriptor) and the
# receiver's drain thread takes it as a LEASE consumed in place —
# producer-write -> arena -> jax.device_put/put_via_pool with no
# intermediate memcpy, and no payload bytes on the TCP wire. Leases
# release OUT OF ORDER (the arena's released-bit discipline), and a
# producer SIGKILL surfaces as EOWNERDEAD on the receiver's recovery
# probe (the robust lifetime fence the worker lane already proves).

_fabric_lock = threading.Lock()
_fabric_name: Optional[str] = None
_fabric_thread: Optional[threading.Thread] = None
_fabric_stop = threading.Event()
_fabric_cv = threading.Condition()
_fabric_records: Dict[int, object] = {}   # tag -> (FabricLease, deadline)
_fabric_sink = None                       # optional delivery override
_producer_target: Optional[str] = None    # segment we attached to
_FABRIC_RECORD_TTL_S = 30.0


def fabric_set_sink(fn):
    """Override the tag-registry delivery: every kind-8 record taken by
    the receiver drain goes to fn(lease) instead (the lease is OWNED by
    the sink — it must release, possibly out of order). Pass None to
    restore the registry."""
    global _fabric_sink
    _fabric_sink = fn


def _fabric_arm_receiver() -> str:
    """Create (or adopt) this process's fabric segment and start the
    receiver drain thread. Returns the segment name, or '' when the
    fabric is disabled (BRPC_TPU_FABRIC=0) or its segment cannot be
    created. A native core that does not build raises NativeUnavailable."""
    global _fabric_name, _fabric_thread
    import os

    if os.environ.get("BRPC_TPU_FABRIC", "1") == "0":
        return ""
    from brpc_tpu import native

    lib = native.load()
    with _fabric_lock:
        if _fabric_thread is not None and _fabric_thread.is_alive():
            name = lib.nat_shm_lane_name() or b""
            return name.decode() or (_fabric_name or "")
        size = int(os.environ.get("BRPC_TPU_FABRIC_ARENA",
                                  str(32 << 20)))
        if lib.nat_shm_lane_create(size) != 0:
            return ""
        _fabric_name = lib.nat_shm_lane_name().decode()
        _fabric_stop.clear()
        t = threading.Thread(target=_fabric_drain_loop, daemon=True,
                             name="tensor-fabric-drain")
        _fabric_thread = t
        t.start()
        return _fabric_name


def _fabric_drain_loop():
    from brpc_tpu import native

    import time

    while not _fabric_stop.is_set():
        try:
            lease = native.fabric_take(200)
        except Exception:
            # the drain is a thread boundary: report why it stopped,
            # or pushes fail later with "record never arrived"
            import logging

            logging.getLogger(__name__).exception(
                "tensor fabric drain stopped")
            return
        now = time.monotonic()
        with _fabric_cv:
            # purge abandoned records (a sender whose RPC failed after
            # the push): their leases must not pin the arena forever.
            # Runs on EVERY wakeup incl. empty timeouts — a pinned-full
            # arena stops new records from arriving, so an
            # arrival-gated purge could never free it.
            stale = [t for t, (_, dl) in _fabric_records.items()
                     if dl <= now]
            for t in stale:
                _fabric_records.pop(t)[0].release()
        if lease is None:
            continue
        sink = _fabric_sink
        if sink is not None:
            try:
                sink(lease)
            except Exception:
                lease.release()
            continue
        with _fabric_cv:
            _fabric_records[lease.tag] = (lease,
                                          now + _FABRIC_RECORD_TTL_S)
            _fabric_cv.notify_all()


def _fabric_claim(tag: int, timeout_s: float = 10.0):
    """Receiver side: wait for the drain thread to deliver tag's lease."""
    import time

    deadline = time.monotonic() + timeout_s
    with rpcz.phase("tensor.claim"), _fabric_cv:
        while True:
            entry = _fabric_records.pop(tag, None)
            if entry is not None:
                return entry[0]
            remain = deadline - time.monotonic()
            if remain <= 0:
                return None
            _fabric_cv.wait(remain)


def _fabric_attach_producer(name: str) -> bool:
    """Attach this process as a PRODUCER on the peer segment `name`.
    The native mapping is process-wide, so only one target segment per
    process: a process that owns its own segment (it is a receiver /
    shm-worker parent) or already attached elsewhere falls back to the
    shm-arena lane for other peers."""
    global _producer_target
    from brpc_tpu import native

    lib = native.load()
    with _fabric_lock:
        if _producer_target is not None:
            return _producer_target == name
        own = (lib.nat_shm_lane_name() or b"").decode()
        if own and own != name:
            return False  # this process's mapping belongs to its own seg
        if lib.nat_shm_producer_attach(name.encode()) < 0:
            return False
        _producer_target = name
        return True

from brpc_tpu.butil import flags as _flags  # noqa: E402

_flags.define_bool(
    "device_transport_prefer_xfer", False,
    "use the jax transfer-server lane even for same-host peers (it is "
    "always used for cross-host device peers when both sides support "
    "it). CAUTION: the CPU backend's bulk transport is same-process-"
    "only — forcing this across processes needs a real device backend")


def lane_counters() -> dict:
    """Public per-lane transfer counts (also exposed as bvars under
    device_transport_*): {'inproc': N, 'ring': N, 'shm': N, 'wire': N,
    'xfer': N}."""
    return {"inproc": _dev_zero_copy.get_value(),
            "ring": _dev_ring.get_value(),
            "shm": _dev_shm.get_value(),
            "wire": _dev_wire.get_value(),
            "xfer": _dev_xfer.get_value()}


# -- jax transfer-server lane (the DEVICE-to-DEVICE cross-host path: the
# true ICI/DCN translation of the RDMA QP — rdma_endpoint.h:55-57's role
# when peers live on different machines) ------------------------------------

_xfer_server = None
_xfer_server_lock = threading.Lock()
_xfer_conns: Dict[str, object] = {}
_xfer_conns_lock = threading.Lock()


def _global_xfer_server():
    """Lazy singleton jax.experimental.transfer server; None when the
    backend/jax build lacks it. Start failures are NOT latched: an early
    failure (e.g. before jax is fully configured) retries on the next
    handshake rather than silently disabling the lane forever. Started
    eagerly by device handshakes because the advertisement must be
    truthful — a peer that sees True may put zero payload on the wire."""
    global _xfer_server
    if _xfer_server is not None:
        return _xfer_server
    with _xfer_server_lock:
        if _xfer_server is None:
            import os

            if os.environ.get("BRPC_TPU_FAKE_XFER"):
                # test transport seam: a cross-process TCP fake of the
                # transfer fabric (the CPU backend's real bulk transport
                # is same-process-only)
                from brpc_tpu.rpc.fake_transfer import FakeTransferServer

                _xfer_server = FakeTransferServer()
                return _xfer_server
            try:
                import jax
                from jax.experimental import transfer

                _xfer_server = transfer.start_transfer_server(
                    jax.devices()[0].client)
            except Exception:
                return None  # retry on a later call
    return _xfer_server


def _xfer_connect(addr: str):
    with _xfer_conns_lock:
        conn = _xfer_conns.get(addr)
    if conn is not None:
        return conn
    server = _global_xfer_server()
    if server is None:
        raise ValueError("no local transfer server to connect from")
    conn = server.connect(addr)  # dial OUTSIDE the lock: a hung peer
    with _xfer_conns_lock:       # must not block other peers' receives
        return _xfer_conns.setdefault(addr, conn)


def _xfer_evict(addr: str):
    """Drop a cached connection (e.g. after a failed pull) so the next
    receive redials — a restarted sender on the same address recovers."""
    with _xfer_conns_lock:
        _xfer_conns.pop(addr, None)


def inproc_publish(arrays: List) -> int:
    """Register device arrays for same-process zero-copy pickup; returns a
    ticket riding the wire in their place. No staging memory is needed —
    the arrays themselves are the transfer (strictly better than the
    reference's registered-block copy for this lane); the DeviceBlockPool
    serves the lanes that DO materialize bytes (shm/wire receives route
    through put_via_pool)."""
    with _inproc_lock:
        ticket = _inproc_next[0]
        _inproc_next[0] += 1
        _inproc_registry[ticket] = arrays
    return ticket


def inproc_claim(ticket: int) -> Optional[List]:
    with _inproc_lock:
        return _inproc_registry.pop(ticket, None)


# -- DeviceEndpoint (RdmaEndpoint analog) -----------------------------------

# endpoint states (rdma_endpoint.h:94-115)
UNINIT = 0
HANDSHAKING = 1
ESTABLISHED = 2
FALLBACK_TCP = 3

_HANDSHAKE_MAGIC = b"TDEV"
DEFAULT_WINDOW_BYTES = 64 << 20  # in-flight tensor bytes per endpoint


class DeviceEndpoint:
    """Attached to a Socket through app_connect; upgrades the connection
    for tensor transfer."""

    def __init__(self, window_bytes: int = DEFAULT_WINDOW_BYTES):
        self.state = UNINIT
        self.peer_info: dict = {}
        self.window_bytes = window_bytes
        self._inflight = 0
        self._window_cond = threading.Condition()
        # sends retained until ACKed (the _sbuf retention, rdma_endpoint.h:214)
        self._retained: Dict[int, Tuple[List, int]] = {}
        self._next_seq = 1
        self._lock = threading.Lock()
        # transfer-server lane: our address as reachable by THIS peer
        # (wildcard host resolved against the handshake connection), and
        # a per-endpoint uuid base so pull ids never collide
        self._my_xfer_addr = ""
        self._xfer_uuid_base = int(uuid.uuid4().int & ((1 << 62) - 1)
                                   ) & ~0xFFFFF

    def resolve_xfer_addr(self, local_ip: str):
        """Called with the handshake connection's local IP: publishes the
        transfer server's address with any wildcard host substituted, so
        the peer can dial back over the same network path."""
        server = _global_xfer_server()
        if server is None or not local_ip:
            return
        addr = server.address()
        host, _, port = addr.rpartition(":")
        if host in ("[::]", "0.0.0.0", ""):
            host = local_ip
        self._my_xfer_addr = f"{host}:{port}"

    # ---- handshake over the TCP connection (GID/QPN exchange analog) ----
    def app_connect(self, sock) -> int:
        """Blocking handshake on the freshly-connected socket. On any
        failure the connection falls back to plain TCP rather than dying
        (the FALLBACK_TCP story of rdma.md)."""
        self.state = HANDSHAKING
        # Attach to the socket up-front so even FALLBACK_TCP outcomes leave
        # the endpoint reachable via sock.app_state (window/ACK bookkeeping
        # applies to the wire path too).
        sock.app_state = self
        try:
            import json

            info = json.dumps(local_device_info()).encode()
            frame = _HANDSHAKE_MAGIC + struct.pack(">I", len(info)) + info
            fd = sock.fd()
            fd.setblocking(True)
            fd.sendall(frame)
            header = _recv_exact(fd, 8)
            if header is None or header[:4] != _HANDSHAKE_MAGIC:
                self.state = FALLBACK_TCP
                fd.setblocking(False)
                return 0
            (length,) = struct.unpack(">I", header[4:8])
            peer = _recv_exact(fd, length)
            fd.setblocking(False)
            if peer is None:
                self.state = FALLBACK_TCP
                return 0
            self.peer_info = json.loads(peer)
            mine = local_device_info()
            if (self.peer_info.get("device_count", 0) > 0
                    and mine["device_count"] > 0):
                self.state = ESTABLISHED
                if self.peer_info.get("xfer"):
                    try:
                        self.resolve_xfer_addr(fd.getsockname()[0])
                    except OSError:
                        pass
            else:
                self.state = FALLBACK_TCP
            return 0
        except OSError:
            self.state = FALLBACK_TCP
            return 0

    @property
    def same_process(self) -> bool:
        return self.peer_info.get("process") == _process_uuid

    @property
    def same_host(self) -> bool:
        return self.peer_info.get("host") == _boot_id

    # ---- send path ------------------------------------------------------
    def prepare_send(self, arrays: List, meta, attachment: IOBuf,
                     timeout_s: float = 10.0) -> bool:
        """Fill meta.tensors + attachment for `arrays` according to the
        endpoint state; blocks while the send window is full.

        Lane selection (rdma_endpoint.h:94-115 state machine applied to
        locality): same process -> pass the jax.Array itself; same host ->
        stage bytes ONCE into the shared HostArena and ship an (arena,
        offset) descriptor (no payload on the wire); otherwise ->
        FALLBACK_TCP wire bytes."""
        total = sum(int(a.nbytes) for a in arrays)
        with rpcz.phase("tensor.window_wait"), self._window_cond:
            import time

            deadline = time.monotonic() + timeout_s
            while self._inflight + total > self.window_bytes:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    return False
                self._window_cond.wait(remain)
            self._inflight += total
        with self._lock:
            seq = self._next_seq
            self._next_seq += 1
        meta.compress_type = 0
        for a in arrays:
            t = meta.tensors.add()
            t.dtype = str(a.dtype)
            t.shape.extend(int(d) for d in a.shape)
            t.nbytes = int(a.nbytes)

        try:
            release = self._fill_lane(arrays, meta, attachment, seq, total)
        except Exception:
            with self._window_cond:
                self._inflight -= total
                self._window_cond.notify_all()
            raise
        with self._lock:
            self._retained[seq] = (release, total)
        return True

    def _fill_lane(self, arrays, meta, attachment, seq, total):
        release = None
        if self.state == ESTABLISHED and self.same_process:
            # zero-copy: ship a ticket instead of bytes
            ticket = inproc_publish(arrays)
            meta.tensors[0].sharding_spec = f"inproc:{ticket}:{seq}"
            _dev_zero_copy.update(1)
            release = (lambda t=ticket: inproc_claim(t))
        elif (self.state == ESTABLISHED and self._my_xfer_addr
              and self.peer_info.get("xfer")
              and (not self.same_host
                   or _flags.get_flag("device_transport_prefer_xfer"))):
            # device-to-device over the transfer fabric: publish on OUR
            # transfer server; the peer pulls straight into its devices.
            # No payload bytes on the RPC wire; jax releases the source
            # buffers once the peer's pull completes.
            import jax
            import numpy as np

            server = _global_xfer_server()
            uid = self._xfer_uuid_base + seq
            jarrays = [a if isinstance(a, jax.Array)
                       else jax.device_put(np.ascontiguousarray(a))
                       for a in arrays]
            # device_put canonicalizes dtypes (float64->float32 without
            # x64): the meta must describe what was PUBLISHED
            for t, ja in zip(meta.tensors, jarrays):
                t.dtype = str(ja.dtype)
                t.nbytes = int(ja.nbytes)
            server.await_pull(uid, jarrays)
            meta.tensors[0].sharding_spec = (
                f"xfer|{self._my_xfer_addr}|{uid}|{seq}")
            _dev_xfer.update(1)
            release = (lambda: None)
        elif (self.state == ESTABLISHED and self.same_host
              and self.peer_info.get("fabric")
              and self._ring_lane_send(arrays, meta, seq)):
            # descriptor-ring fabric: payload written ONCE into the
            # receiver's blob arena (kind-8 records), consumed in place
            # on the far side — zero payload bytes on the wire, zero
            # intermediate memcpy. The receiver owns the spans (leases),
            # so there is nothing to free on ACK; the window retention
            # still bounds in-flight bytes.
            _dev_ring.update(1)
            release = (lambda: None)
        elif self.state == ESTABLISHED and self.same_host:
            arena = default_send_arena()
            offset = arena.alloc(total) if arena is not None else None
            if offset is not None:
                import numpy as np

                pos = offset
                with rpcz.phase("tensor.arena_write"):
                    for a in arrays:
                        n = int(a.nbytes)
                        dst = np.frombuffer(arena.shm.buf, dtype=np.uint8,
                                            count=n, offset=pos)
                        # one device->host DMA straight into registered
                        # memory
                        host = np.ascontiguousarray(np.asarray(a))
                        dst[:] = host.reshape(-1).view(np.uint8)
                        pos += n
                meta.tensors[0].sharding_spec = (
                    f"shm:{arena.name}:{offset}:{seq}")
                _dev_shm.update(1)
                release = (lambda o=offset, n=total: arena.free(o, n))
        if release is None and not (self.state == ESTABLISHED
                                    and self.same_process):
            import numpy as np

            meta.tensors[0].sharding_spec = f"wire::{seq}"
            for a in arrays:
                attachment.append(np.asarray(a).tobytes())
            _dev_wire.update(1)
        return release

    def _ring_lane_send(self, arrays, meta, seq) -> bool:
        """Push every tensor's bytes as one kind-8 fabric record each
        (tags base..base+n-1) onto the peer's descriptor ring; the spec
        `ring:<base>:<n>:<seq>` rides the RPC in place of any payload.
        False -> the caller falls through to the shm-arena/wire lanes."""
        name = self.peer_info.get("fabric") or ""
        if not name or not _fabric_attach_producer(name):
            return False
        if len(arrays) > 256:
            # the per-seq tag stride is 256 (base = uuid + (seq << 8)):
            # more tensors would collide with the next seq's tags and
            # the receiver could claim the wrong record — fall back
            return False
        import time

        import numpy as np

        from brpc_tpu import native

        base = (self._xfer_uuid_base + (seq << 8)) & ((1 << 62) - 1)
        pushed = 0
        for i, a in enumerate(arrays):
            host = np.ascontiguousarray(np.asarray(a))
            flat = host.reshape(-1).view(np.uint8)
            # Bounded backoff only: the blob arena is a RING — a receiver
            # retaining leases indefinitely head-blocks reclaim, and the
            # right response is falling back to the shm-arena lane, not
            # stalling the send path (size the fabric to the consumer's
            # retention with BRPC_TPU_FABRIC_ARENA).
            with rpcz.phase("tensor.arena_write"):
                deadline = time.monotonic() + 0.25
                while native.fabric_push(flat, base + i) != 0:
                    if time.monotonic() >= deadline:
                        # stranded records (tags base..base+pushed-1) are
                        # purged by the receiver's registry TTL
                        return False
                    time.sleep(0.0005)
            pushed += 1
        meta.tensors[0].sharding_spec = (
            f"ring:{base}:{len(arrays)}:{seq}")
        return True

    def on_ack(self, seq: int):
        """Peer confirmed receipt: run the lane's release action (free the
        arena span / drop the unclaimed ticket) + open the window
        (piggybacked-ACK path, rdma_endpoint.h:132-138)."""
        with self._lock:
            entry = self._retained.pop(seq, None)
        if entry is not None:
            release, total = entry
            try:
                if release is not None:
                    release()
            finally:
                with self._window_cond:
                    self._inflight = max(0, self._inflight - total)
                    self._window_cond.notify_all()

    @property
    def inflight_bytes(self) -> int:
        return self._inflight

    @property
    def retained_count(self) -> int:
        return len(self._retained)


def _recv_exact(fd, n: int) -> Optional[bytes]:
    out = b""
    while len(out) < n:
        chunk = fd.recv(n - len(out))
        if not chunk:
            return None
        out += chunk
    return out


def device_holds(dtype) -> bool:
    """True when a jax.Array can hold `dtype` exactly. With x64 off, JAX
    narrows 64-bit types to 32 bits, which would change pushed values."""
    import jax

    return jax.dtypes.canonicalize_dtype(dtype) == dtype


def _landing(device, dtype):
    """Where a received tensor lands: `device`, unless the device would
    narrow its dtype — such a tensor stays a host array, byte-exact."""
    return device if device is not None and device_holds(dtype) else None


def _np_dtype(name: str):
    import numpy as np

    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _bind_lease(arr, lease):
    """Tie a fabric lease's lifetime to the zero-copy array carved from
    it: the span releases (out of order, whenever) when the array dies.
    The finalizer itself holds the lease reference, so the arena bytes
    stay valid for exactly as long as the array is reachable."""
    import weakref

    weakref.finalize(arr, lease.release)


# -- read-side arena seam (the all-IOBuf-memory-registered config) ----------
#
# The reference points IOBuf's blockmem_allocate at its registered pool
# so EVERY buffer a socket drains into is transfer-ready (SURVEY 2.9).
# install_read_arena is that configuration for the Python stack: socket
# reads land in prefaulted HostArena blocks, growing by whole prefaulted
# arenas on exhaustion — the grow path must never reintroduce the
# first-touch fault cliff (BENCH_r05's 0.085 GB/s staging artifact), so
# every grown arena prefaults at creation exactly like the first.

_read_chain = None
_read_chain_lock = threading.Lock()


class ReadArenaChain:
    """A growable chain of prefaulted HostArenas serving IOBuf blocks."""

    MAX_ARENAS = 8

    def __init__(self, size: int = 32 << 20, capacity: int = 256 << 10):
        self.size = size
        self.capacity = capacity
        self._lock = threading.Lock()
        self.arenas = [HostArena(size=size)]
        self.grows = 0

    def alloc_block(self):
        with self._lock:
            arenas = list(self.arenas)
        for arena in reversed(arenas):
            b = arena.make_block(self.capacity)
            if b is not None:
                return b
        with self._lock:
            if len(self.arenas) >= self.MAX_ARENAS:
                return None  # plain host blocks take over
            try:
                arena = HostArena(size=self.size)  # prefaulted at create
            except OSError:
                return None
            self.arenas.append(arena)
            self.grows += 1
        return arena.make_block(self.capacity)

    def close(self):
        for arena in self.arenas:
            arena.close()


def install_read_arena(size: int = 32 << 20,
                       capacity: int = 256 << 10) -> ReadArenaChain:
    """Install a prefaulted, growable arena chain as the IOBuf block
    factory (HostArena.install_as_iobuf_allocator generalized with a
    grow path). Returns the chain; uninstall_read_arena undoes it."""
    global _read_chain
    from brpc_tpu.butil import iobuf as iobuf_mod

    with _read_chain_lock:
        if _read_chain is None:
            _read_chain = ReadArenaChain(size=size, capacity=capacity)
        iobuf_mod.set_block_allocator(_read_chain.alloc_block)
    return _read_chain


def uninstall_read_arena():
    global _read_chain
    from brpc_tpu.butil import iobuf as iobuf_mod

    with _read_chain_lock:
        iobuf_mod.set_block_allocator(None)
        chain, _read_chain = _read_chain, None
    if chain is not None:
        chain.close()


def receive_tensors(meta, attachment: IOBuf, device=None) -> Tuple[List, Optional[int]]:
    """Reconstruct arrays from a tensor-bearing message. Returns
    (arrays, ack_seq). Zero-copy when the sender published in-process;
    mapped straight out of the sender's shared arena when same-host (the
    recv-zero-copy-into-registered-blocks path, rdma_endpoint.h:214-219).
    With `device`, each tensor lands there unless the device would narrow
    its dtype (`device_holds`): that one stays a host array, byte-exact.
    A message lands in one `Landing`, whose one wait finishes the
    host-to-device copies before ring leases go back to the producer and
    before the caller ACKs: the sender reuses its span after ACK
    (retention-until-ACK, rdma_endpoint.h:214)."""
    import numpy as np

    if not meta.tensors:
        return [], None
    spec = meta.tensors[0].sharding_spec or ""
    if spec.startswith("xfer|"):
        # pull device-to-device from the sender's transfer server; the
        # sender frees its buffers once our pull completes
        import jax

        _, addr, uid_s, seq_s = spec.split("|")
        conn = _xfer_connect(addr)
        dev = device if device is not None else jax.devices()[0]
        sharding = jax.sharding.SingleDeviceSharding(dev)
        specs = [jax.ShapeDtypeStruct(tuple(t.shape), _np_dtype(t.dtype),
                                      sharding=sharding)
                 for t in meta.tensors]
        try:
            with default_block_pool().landing() as land, \
                    rpcz.phase("tensor.device_put"):
                arrays = land.track(conn.pull(int(uid_s), specs))
        except Exception:
            _xfer_evict(addr)  # redial next time (sender restarts)
            raise
        return arrays, int(seq_s)
    parts = spec.split(":")
    seq = int(parts[-1]) if len(parts) >= 3 and parts[-1].isdigit() else None
    if parts[0] == "inproc" and parts[1].isdigit():
        arrays = inproc_claim(int(parts[1]))
        if arrays is None:
            # Ticket gone (already claimed / sender restarted). No payload
            # rode the wire for this lane — falling through would misread
            # an empty attachment, so fail loudly.
            raise ValueError(f"device transport: in-process ticket "
                             f"{parts[1]} is no longer claimable")
        if device is not None:
            import jax

            # arrays already on the device pass through as the SAME
            # object; host arrays the sender published land in HBM
            with default_block_pool().landing() as land, \
                    rpcz.phase("tensor.device_put"):
                arrays = land.track(
                    a if (isinstance(a, jax.Array)
                          and a.devices() == {device})
                    or _landing(device, a.dtype) is None
                    else jax.device_put(a, device) for a in arrays)
        return arrays, seq
    # the byte lanes: find each tensor's bytes, then land them
    leases = []  # ring leases this call still owns
    try:
        if parts[0] == "ring" and len(parts) == 4:
            # descriptor-ring fabric: the payload arrived as kind-8
            # records in OUR blob arena (the sender wrote it there once);
            # the leases are consumed IN PLACE
            base, count = int(parts[1]), int(parts[2])
            if count != len(meta.tensors):
                raise ValueError("device transport: ring record count "
                                 f"{count} != {len(meta.tensors)} tensors")
            for tag in range(base, base + count):
                leases.append(_fabric_claim(tag))
                if leases[-1] is None:
                    raise ValueError(
                        f"device transport: ring record {tag} never "
                        f"arrived (fabric receiver not draining?)")
            found = [lease.view() for lease in leases]
        elif parts[0] == "shm" and len(parts) == 4:
            arena = attach_arena(parts[1])
            if arena is None:
                raise ValueError(
                    f"device transport: cannot attach shared arena "
                    f"{parts[1]!r} (sender chose the same-host lane but "
                    f"the shm namespace is not shared)")
            found, pos = [], int(parts[2])
            for t in meta.tensors:
                found.append(arena.shm.buf[pos:pos + t.nbytes])
                pos += t.nbytes
        else:
            # wire path: materialize from attachment bytes
            found = [attachment.cutn_bytes(t.nbytes) for t in meta.tensors]
        arrays = []
        with (default_block_pool().landing() if device is not None
              else contextlib.nullcontext()) as land:
            for i, (t, raw) in enumerate(zip(meta.tensors, found)):
                dtype = _np_dtype(t.dtype)
                dev = _landing(device, dtype)
                if dev is not None:
                    # host->device DMA straight from the found bytes
                    # THROUGH the device block pool (block_pool.h role:
                    # transfer bytes land in pooled, pre-allocated HBM)
                    arrays.append(land.put(np.frombuffer(raw, np.uint8),
                                           dtype, tuple(t.shape), dev))
                    continue
                arr = np.frombuffer(raw, dtype=dtype)
                if leases:
                    # zero-copy: the array IS the arena span; the lease
                    # releases when the last view of it is collected.
                    # Bind the finalizer to the BASE frombuffer array:
                    # numpy collapses .base chains to it, so any derived
                    # view (slices of the reshaped array) keeps it — and
                    # therefore the lease — alive; binding to the
                    # reshape wrapper would let a slice outlive the span.
                    _bind_lease(arr, leases[i])
                    leases[i] = None
                elif parts[0] == "shm":
                    # own the bytes before ACK lets the sender reuse them
                    arr = arr.copy()
                arrays.append(arr.reshape(tuple(t.shape)))
    finally:
        for lease in leases:
            if lease is not None:
                lease.release()
    return arrays, seq
