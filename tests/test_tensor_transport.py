"""Device/tensor transport tests — the RDMA-subsystem test role
(brpc_rdma_unittest.cpp shape, SURVEY.md section 4): handshake state
machine, pool accounting, push/pull roundtrips with numerical equality,
zero-copy same-process path, retention-until-ACK.
"""
import numpy as np
import pytest

from brpc_tpu import bvar, rpc
from brpc_tpu.rpc import device_transport as dt
from brpc_tpu.rpc.proto import rpc_meta_pb2
from brpc_tpu.rpc.tensor_service import (
    TensorClient,
    TensorStoreService,
    make_device_channel,
)


@pytest.fixture(scope="module")
def store_server():
    svc = TensorStoreService()
    srv = rpc.Server(rpc.ServerOptions(num_threads=4))
    srv.add_service(svc)
    assert srv.start("127.0.0.1:0") == 0
    yield srv, svc
    srv.stop()


def test_local_device_info():
    info = dt.local_device_info()
    assert info["device_count"] >= 1
    assert info["platform"] in ("cpu", "tpu")


def test_block_pool_acquire_release():
    pool = dt.DeviceBlockPool(blocks_per_class=2)
    stats0 = pool.stats()
    assert all(v == 2 for v in stats0.values())
    got = pool.acquire(10_000)  # → 64KB class
    assert got is not None
    size, buf = got
    assert size == 64 << 10
    assert buf.dtype == np.uint8
    assert pool.stats()[(size, "uint8")] == 1
    pool.release(size, buf)
    assert pool.stats()[(size, "uint8")] == 2
    assert pool.acquire(10 << 20) is None  # above the largest class


def test_endpoint_prepare_and_receive_wire():
    ep = dt.DeviceEndpoint()
    ep.state = dt.FALLBACK_TCP
    from brpc_tpu.butil.iobuf import IOBuf

    meta = rpc_meta_pb2.RpcMeta()
    att = IOBuf()
    arrays = [np.arange(12, dtype=np.float32).reshape(3, 4),
              np.ones((2, 2), dtype=np.int32)]
    assert ep.prepare_send(arrays, meta, att)
    assert len(meta.tensors) == 2
    assert ep.inflight_bytes == sum(a.nbytes for a in arrays)
    assert ep.retained_count == 1
    out, seq = dt.receive_tensors(meta, att)
    np.testing.assert_array_equal(out[0], arrays[0])
    np.testing.assert_array_equal(out[1], arrays[1])
    ep.on_ack(seq)
    assert ep.inflight_bytes == 0
    assert ep.retained_count == 0


def test_endpoint_window_blocks():
    ep = dt.DeviceEndpoint(window_bytes=100)
    ep.state = dt.FALLBACK_TCP
    from brpc_tpu.butil.iobuf import IOBuf

    meta = rpc_meta_pb2.RpcMeta()
    big = np.zeros(200, dtype=np.uint8)
    assert not ep.prepare_send([big], meta, IOBuf(), timeout_s=0.05)


def test_push_pull_roundtrip(store_server):
    srv, svc = store_server
    ch = make_device_channel(str(srv.listen_endpoint))
    assert ch is not None
    client = TensorClient(ch)
    arrays = [np.random.default_rng(0).normal(size=(8, 16)).astype(np.float32)]
    cntl, resp = client.push("w0", arrays)
    assert not cntl.failed(), cntl.error_text
    assert resp.ok
    stored = svc.get("w0")
    assert stored is not None
    # a host array pushed to the service lands on the server's device
    import jax

    assert isinstance(stored[0], jax.Array)
    assert stored[0].devices() == {dt.default_block_pool().device}
    np.testing.assert_array_equal(np.asarray(stored[0]), arrays[0])
    cntl2, pulled = client.pull("w0")
    assert not cntl2.failed(), cntl2.error_text
    np.testing.assert_allclose(np.asarray(pulled[0]), arrays[0])


WIDE = [np.array([2**40 + 3, -(2**33), 2**31 + 1], dtype=np.int64),
        np.array([1 + 2**-40, 1e300], dtype=np.float64),
        np.arange(6, dtype=np.float32)]


@pytest.mark.parametrize("lane", ["inproc", "wire"])
def test_wide_dtypes_held_exactly(store_server, lane):
    """int64 above 2^31 and float64 survive a push unchanged: the device
    would narrow them (x64 off), so they stay host arrays while the
    float32 beside them lands on the device."""
    import jax

    device = dt.default_block_pool().device
    if lane == "inproc":
        srv, svc = store_server
        client = TensorClient(make_device_channel(str(srv.listen_endpoint)))
        cntl, resp = client.push("wide", [a.copy() for a in WIDE])
        assert not cntl.failed(), cntl.error_text
        stored = svc.get("wide")
        cntl2, pulled = client.pull("wide")
        assert not cntl2.failed(), cntl2.error_text
    else:
        ep = dt.DeviceEndpoint()
        ep.state = dt.FALLBACK_TCP
        from brpc_tpu.butil.iobuf import IOBuf

        meta, att = rpc_meta_pb2.RpcMeta(), IOBuf()
        assert ep.prepare_send(WIDE, meta, att)
        stored, _ = dt.receive_tensors(meta, att, device=device)
        pulled = stored
    for got, want in zip(stored[:2], WIDE[:2]):
        assert isinstance(got, np.ndarray) and got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    assert isinstance(stored[2], jax.Array)
    assert stored[2].devices() == {device}
    for got, want in zip(pulled, WIDE):
        assert np.asarray(got).dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), want)


def test_pull_missing(store_server):
    srv, _ = store_server
    ch = make_device_channel(str(srv.listen_endpoint))
    client = TensorClient(ch)
    cntl, arrays = client.pull("no-such-tensor")
    assert not cntl.failed()
    assert arrays is None


def test_handshake_establishes(store_server):
    """The device handshake upgrades the connection: client endpoint must
    be ESTABLISHED (both sides have jax devices) and see the peer."""
    srv, _ = store_server
    ch = make_device_channel(str(srv.listen_endpoint))
    client = TensorClient(ch)
    cntl, _ = client.push("hs", [np.ones(4, np.float32)])
    assert not cntl.failed(), cntl.error_text
    sock = cntl._current_sock
    ep = sock.app_state
    assert isinstance(ep, dt.DeviceEndpoint)
    assert ep.state == dt.ESTABLISHED
    assert ep.peer_info["device_count"] >= 1


def test_same_process_zero_copy(store_server):
    """In-process transfer passes the SAME array object through (the
    loopback-ICI path)."""
    srv, svc = store_server
    ch = make_device_channel(str(srv.listen_endpoint))
    client = TensorClient(ch)
    import jax.numpy as jnp

    arr = jnp.arange(32, dtype=jnp.float32)
    cntl, resp = client.push("zc", [arr])
    assert not cntl.failed(), cntl.error_text
    stored = svc.get("zc")
    assert stored[0] is arr  # identity: no copy was made


def test_device_jax_array_roundtrip(store_server):
    srv, svc = store_server
    ch = make_device_channel(str(srv.listen_endpoint))
    client = TensorClient(ch)
    import jax.numpy as jnp

    arr = jnp.linspace(0, 1, 64, dtype=jnp.float32).reshape(8, 8)
    cntl, _ = client.push("jx", [arr])
    assert not cntl.failed(), cntl.error_text
    cntl2, pulled = client.pull("jx")
    assert not cntl2.failed()
    np.testing.assert_allclose(np.asarray(pulled[0]), np.asarray(arr))


# payload dtype -> the word its pooled block holds
_WORDS = {"float32": "uint32", "int32": "uint32", "bfloat16": "uint16",
          "float16": "uint16", "int8": "uint8", "uint8": "uint8",
          "bool": "uint8"}


@pytest.mark.parametrize("payload", ["exact", "padded", "spilled"])
@pytest.mark.parametrize("dtype", list(_WORDS))
def test_block_pool_put_via_pool_roundtrip(dtype, payload):
    """Transfer bytes must land in a pooled block of the dtype's word
    width (donating fill) and come back out as the right typed array,
    bit for bit; every block goes home and the width's counter moves.
    `exact` fills the 8 KiB class, `padded` stages 6000 bytes up to it,
    `spilled` finds the 8 KiB class busy and lands in the 64 KiB one."""
    import jax

    word = _WORDS[dtype]
    np_dtype = dt._np_dtype(dtype)
    pool = dt.DeviceBlockPool(blocks_per_class=2)
    nbytes = 6000 if payload == "padded" else 8 << 10
    rng = np.random.default_rng(nbytes)
    raw = rng.integers(0, 256, nbytes, dtype=np.uint8)
    if np_dtype == np.bool_:
        raw &= 1
    src = raw.view(np_dtype).reshape(2, -1)
    held = []
    if payload == "spilled":
        held = [pool.acquire(nbytes, word) for _ in range(2)]
        assert all(size == 8 << 10 for size, _ in held)
    counter = bvar.find_exposed(f"device_block_pool_acquired_u"
                                f"{8 * np.dtype(word).itemsize}")
    count0, total0 = counter.get_value(), dt._pool_acquired.get_value()
    arr = pool.put_via_pool(raw.copy(), np_dtype, src.shape,
                            jax.devices()[0])
    assert arr.dtype == np_dtype and arr.shape == src.shape
    assert np.asarray(arr).tobytes() == src.tobytes()
    assert counter.get_value() == count0 + 1
    assert dt._pool_acquired.get_value() == total0 + 1
    for size, buf in held:
        pool.release(size, buf)
    # every block is back home after the put, in every width allocated
    assert pool.stats() == {(size, w): 2
                            for size in dt.DeviceBlockPool.SIZE_CLASSES
                            for w in {"uint8", word}}


def test_block_pool_put_via_pool_refuses_narrowed():
    """A dtype the device would narrow (float64 without x64) is refused,
    never converted, and takes no block."""
    import jax

    pool = dt.DeviceBlockPool(blocks_per_class=2)
    before = pool.stats()
    src64 = np.linspace(0, 1, 10)
    with pytest.raises(ValueError, match="narrowed"):
        pool.put_via_pool(src64.view(np.uint8), np.float64, (10,),
                          jax.devices()[0])
    assert pool.stats() == before


def test_block_pool_put_via_pool_oversized_falls_back():
    """Above the largest class a put is a plain device_put."""
    import jax

    pool = dt.DeviceBlockPool(blocks_per_class=2)
    before = pool.stats()
    big = np.zeros(4 << 20, dtype=np.uint8)
    arr_big = pool.put_via_pool(big, np.uint8, (4 << 20,),
                                jax.devices()[0])
    assert arr_big.shape == (4 << 20,)
    assert pool.stats() == before


# -- receive_tensors: every lane lands through one pool scope ---------------

LANES = ["wire", "shm", "ring", "inproc"]


class _FakeLease:
    """A fabric lease over plain host bytes that counts its releases."""

    def __init__(self, tag, raw):
        self.tag, self._raw, self.releases = tag, bytearray(raw), 0

    def view(self):
        return memoryview(self._raw)

    def release(self):
        self.releases += 1


@pytest.fixture(scope="module")
def send_arena():
    """A named arena the tests write as a same-host sender would. The
    receiver finds it already attached: a second mapping of a segment
    this process created would end its registration twice."""
    arena = dt.HostArena(size=1 << 20)
    dt._attached_arenas[arena.name] = arena
    yield arena
    dt._attached_arenas.pop(arena.name, None)
    arena.close()


def _message(lane, arrays, arena, deliver=None):
    """(meta, attachment, leases) of a message carrying `arrays` on
    `lane`; on the ring lane only the records in `deliver` arrive."""
    import time

    from brpc_tpu.butil.iobuf import IOBuf

    meta, att, leases = rpc_meta_pb2.RpcMeta(), IOBuf(), []
    for a in arrays:
        t = meta.tensors.add()
        t.dtype, t.nbytes = str(a.dtype), int(a.nbytes)
        t.shape.extend(a.shape)
    if lane == "wire":
        spec = "wire::1"
        for a in arrays:
            att.append(a.tobytes())
    elif lane == "shm":
        spec, pos = f"shm:{arena.name}:0:1", 0
        buf = np.frombuffer(arena.shm.buf, dtype=np.uint8)
        for a in arrays:
            buf[pos:pos + a.nbytes] = np.frombuffer(a.tobytes(), np.uint8)
            pos += a.nbytes
    elif lane == "ring":
        base = (1 << 61) + int(np.random.default_rng().integers(1 << 40))
        spec = f"ring:{base}:{len(arrays)}:1"
        leases = [_FakeLease(base + i, a.tobytes())
                  for i, a in enumerate(arrays)]
        with dt._fabric_cv:
            for i, lease in enumerate(leases):
                if deliver is None or i in deliver:
                    dt._fabric_records[lease.tag] = (
                        lease, time.monotonic() + 600)
            dt._fabric_cv.notify_all()
    else:
        spec = f"inproc:{dt.inproc_publish(list(arrays))}:1"
    meta.tensors[0].sharding_spec = spec
    return meta, att, leases


def _payload(dtype, nbytes, seed):
    raw = np.random.default_rng(seed).integers(0, 256, nbytes,
                                               dtype=np.uint8)
    np_dtype = dt._np_dtype(dtype)
    if np_dtype == np.bool_:
        raw &= 1
    return raw.view(np_dtype).reshape(2, -1)


def _warm_word(pool, dtype):
    """Allocate the dtype's block word up front, so stats() taken before
    a receive already lists every class that receive may use."""
    pool.release(*pool.acquire(1, _WORDS[dtype]))


@pytest.mark.parametrize("dtype", list(_WORDS))
@pytest.mark.parametrize("lane", LANES)
def test_receive_tensors_lands_every_lane(lane, dtype, send_arena):
    """Each lane's bytes land on the device exactly; every pool block is
    back home afterwards and each ring lease is released exactly once."""
    pool = dt.default_block_pool()
    _warm_word(pool, dtype)
    src = _payload(dtype, 6000, seed=len(dtype))
    before = pool.stats()
    meta, att, leases = _message(lane, [src], send_arena)
    arrays, seq = dt.receive_tensors(meta, att, device=pool.device)
    assert seq == 1
    (arr,) = arrays
    assert arr.devices() == {pool.device}
    assert arr.dtype == src.dtype and arr.shape == src.shape
    assert np.asarray(arr).tobytes() == src.tobytes()
    assert pool.stats() == before
    assert [lease.releases for lease in leases] == [1] * len(leases)


@pytest.mark.parametrize("lane", LANES)
def test_receive_tensors_waits_once(lane, send_arena, monkeypatch):
    """A two-tensor message makes exactly one block_until_ready."""
    import jax

    waits = []
    real = jax.block_until_ready

    def counting(x):
        waits.append(x)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    src = [_payload("float32", 6000, seed=1), _payload("int8", 8192, 2)]
    meta, att, _ = _message(lane, src, send_arena)
    arrays, _ = dt.receive_tensors(meta, att,
                                   device=dt.default_block_pool().device)
    assert len(waits) == 1
    assert len(waits[0]) == 2
    for got, want in zip(arrays, src):
        assert np.asarray(got).tobytes() == want.tobytes()


def test_message_beyond_pool_blocks_lands_exactly():
    """Ten tensors of one size class into a pool of eight blocks there:
    the extra tensors land by a plain device_put, bytes unchanged, and
    every block goes home."""
    pool = dt.default_block_pool()
    _warm_word(pool, "float32")
    src = [_payload("float32", 100 << 10, seed=i) for i in range(10)]
    before = pool.stats()
    meta, att, _ = _message("wire", src, None)
    arrays, _ = dt.receive_tensors(meta, att, device=pool.device)
    for got, want in zip(arrays, src):
        assert np.asarray(got).tobytes() == want.tobytes()
    assert pool.stats() == before


def test_ring_missing_record_releases_claimed_leases(monkeypatch):
    """A ring message whose second record never arrives raises, and the
    lease already claimed goes back exactly once."""
    claim = dt._fabric_claim
    monkeypatch.setattr(dt, "_fabric_claim",
                        lambda tag, timeout_s=10.0: claim(tag, 0.05))
    src = [_payload("float32", 6000, seed=i) for i in range(2)]
    meta, att, leases = _message("ring", src, None, deliver={0})
    with pytest.raises(ValueError, match="never arrived"):
        dt.receive_tensors(meta, att,
                           device=dt.default_block_pool().device)
    assert [lease.releases for lease in leases] == [1, 0]
