"""TableService against its plain reference (rpc/table_reference.py), on
a 4,096-record table on the CPU, through a native-runtime Server and an
ordinary Channel: the bytes of every read and the final table, batches
with repeated (key, field) updates, padded bucket slots, the asynchronous
`done`, and a control that serves reads from a stale snapshot."""
import threading
import time

import numpy as np
import pytest

from brpc_tpu import bvar, native, rpc
from brpc_tpu.rpc import errors
from brpc_tpu.rpc import table_service as ts
from brpc_tpu.rpc.proto import table_service_pb2 as tb_pb2
from brpc_tpu.rpc.table_reference import TableReference

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")

RECORDS, WORDS, FIELDS = 4096, 250, 10
FIELD_BYTES = WORDS * 4 // FIELDS


class Probe(ts.TableService):
    """The service, recording each batch and the thread that runs it.
    `gate`, when set, holds the next batch until it is set or `gate_s`
    passes; `snapshot`, when set, answers reads from that host copy (the
    control); `fail` makes the batch program raise; an op on a key in
    `close_keys` asks to close its connection."""

    def __init__(self, table):
        super().__init__(table)
        self.batches = []
        self.threads = []
        self.gate = None
        self.gate_s = 10.0
        self.snapshot = None
        self.fail = False
        self.close_keys = set()

    def _apply(self, ops):
        self.batches.append([
            (op.cntl.log_id, op.key, op.field,
             None if op.words is None else op.words.tobytes(), op.response)
            for op in ops])
        self.threads.append(threading.get_ident())
        for op in ops:
            if op.key in self.close_keys:
                op.cntl.close_connection()
        super()._apply(ops)

    def _run(self, bucket, at, values, keys):
        gate, self.gate = self.gate, None
        if gate is not None:
            gate.wait(self.gate_s)
        if self.fail:
            raise RuntimeError("planted batch fault")
        rows = super()._run(bucket, at, values, keys)
        return rows if self.snapshot is None else self.snapshot[keys]


def channel(address, connection_type="single"):
    ch = rpc.Channel(rpc.ChannelOptions(timeout_ms=10000, max_retry=0,
                                        connection_type=connection_type))
    assert ch.init(address) == 0
    return ch


@pytest.fixture(scope="module")
def served():
    import jax.numpy as jnp

    rng = np.random.default_rng(2**31 + 7)
    table = jnp.asarray(rng.integers(0, 2**32, (RECORDS, WORDS),
                                     dtype=np.uint32))
    svc = Probe(table)
    srv = rpc.Server(rpc.ServerOptions(use_native_runtime=True))
    srv.add_service(svc)
    assert srv.start("127.0.0.1:0") == 0
    ch = channel(str(srv.listen_endpoint))
    yield svc, ch, str(srv.listen_endpoint)
    ch.close()
    srv.stop()
    srv.join(10)
    assert svc.close()


@pytest.fixture
def table(served):
    """The service and channel, a reference holding the table as it
    stands, and the batch log cleared."""
    svc, ch, _ = served
    host = np.asarray(svc.table)
    ref = TableReference({k: host[k].tobytes() for k in range(RECORDS)})
    svc.batches.clear()
    svc.threads.clear()
    return svc, ch, ref


def zipf_keys(rng, n, theta=0.99):
    """Keys over the table, Zipfian with constant theta, hot keys spread
    by a seeded permutation."""
    p = 1.0 / np.arange(1, RECORDS + 1) ** theta
    return rng.permutation(RECORDS)[rng.choice(RECORDS, n, p=p / p.sum())]


def call(ch, op, log_id=0):
    """One op through the channel: ("read", key) or ("update", key,
    field, value). Returns (controller, response)."""
    cntl = rpc.Controller()
    cntl.log_id = log_id
    if op[0] == "read":
        req, resp = tb_pb2.TableReadRequest(key=op[1]), \
            tb_pb2.TableReadResponse()
        ch.call_method("Table.Read", cntl, req, resp)
    else:
        req = tb_pb2.TableUpdateRequest(key=op[1], field=op[2], value=op[3])
        resp = tb_pb2.TableUpdateResponse()
        ch.call_method("Table.Update", cntl, req, resp)
    return cntl, resp


def replay(svc, ref):
    """Runs the reference batch by batch as the service formed them;
    returns {log_id: record the reference reads} and checks that the
    service answered each read with the same bytes."""
    want = {}
    for batch in svc.batches:
        ops = [("read", key) if field < 0 else
               ("update", key, field, value)
               for _, key, field, value, _ in batch]
        for (log_id, _, field, _, resp), got in zip(batch,
                                                    ref.apply_batch(ops)):
            if field < 0:
                assert resp.record == got
                want[log_id] = got
    return want


def wait_for(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.01)
    return True


def held_batch(svc, chans, plan):
    """Holds a first batch (a read of key 1) at the gate, queues `plan`
    behind it, op i on chans[i % len(chans)], and opens the gate: the
    plan runs as one batch. Returns [(controller, response)] in plan
    order, and the first batch's."""
    svc.gate = held = threading.Event()
    first = [None]
    head = threading.Thread(
        target=lambda: first.__setitem__(0, call(chans[0], ("read", 1))))
    head.start()
    assert wait_for(lambda: svc.batches)
    results = [None] * len(plan)
    left = [len(plan)]
    done = threading.Event()
    lock = threading.Lock()

    def on_done(i, cntl, resp):
        results[i] = (cntl, resp)
        with lock:
            left[0] -= 1
            if not left[0]:
                done.set()

    for i, op in enumerate(plan):
        if op[0] == "read":
            method = "Table.Read"
            req = tb_pb2.TableReadRequest(key=op[1])
            resp = tb_pb2.TableReadResponse()
        else:
            method = "Table.Update"
            req = tb_pb2.TableUpdateRequest(key=op[1], field=op[2],
                                            value=op[3])
            resp = tb_pb2.TableUpdateResponse()
        cntl = rpc.Controller()
        cntl.log_id = 200 + i
        chans[i % len(chans)].call_method(
            method, cntl, req, resp,
            done=lambda c, i=i, r=resp: on_done(i, c, r))
    assert wait_for(lambda: len(svc._queue._tasks) == len(plan))
    held.set()
    assert done.wait(30)
    head.join(30)
    assert [len(b) for b in svc.batches] == [1, len(plan)]
    return results, first[0]


@pytest.fixture
def second_channel(served):
    """A channel that opens a connection of its own for every call
    (channels to one server share their "single" connection)."""
    ch2 = channel(served[2], connection_type="short")
    yield ch2
    ch2.close()


def assert_table_is(svc, ref):
    host = np.asarray(svc.table)
    for k in range(RECORDS):
        assert host[k].tobytes() == ref.read(k), k


def counters():
    return {n: bvar.find_exposed(n).get_value() for n in ts.COUNTERS}


@pytest.mark.parametrize("seed", [2**31 + 11, 2**40 + 3])
def test_random_ops_match_the_reference(table, seed):
    svc, ch, ref = table
    rng = np.random.default_rng(seed)
    n_threads, per_thread = 8, 48
    keys = zipf_keys(rng, n_threads * per_thread)
    plan = []
    for i, key in enumerate(keys):
        if rng.random() < 0.5:
            plan.append(("read", int(key)))
        else:
            plan.append(("update", int(key), int(rng.integers(FIELDS)),
                         rng.bytes(FIELD_BYTES)))
    got, failed = {}, []
    svc.gate = threading.Event()  # the first batch waits, the rest queue
    svc.gate_s = 0.3
    before = counters()

    def worker(t):
        for i in range(t, len(plan), n_threads):
            cntl, resp = call(ch, plan[i], log_id=i + 1)
            if cntl.failed():
                failed.append(cntl.error_text)
            elif plan[i][0] == "read":
                got[i + 1] = resp.record
            else:
                assert resp.ok

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads)
    assert not failed, failed[:3]
    want = replay(svc, ref)
    assert got == want
    assert_table_is(svc, ref)
    after = counters()
    n_batches = after["table_batches"] - before["table_batches"]
    assert n_batches == len(svc.batches) < len(plan)
    assert max(len(b) for b in svc.batches) > 1
    assert after["table_reads"] - before["table_reads"] == \
        sum(op[0] == "read" for op in plan)
    assert after["table_updates"] - before["table_updates"] == \
        sum(op[0] == "update" for op in plan)
    assert after["table_queue_wait_ns"] > before["table_queue_wait_ns"]


def test_repeated_updates_in_a_batch_keep_the_last(table):
    svc, ch, ref = table
    rng = np.random.default_rng(5)
    hot, field = 17, 3
    plan = [("update", hot, field, rng.bytes(FIELD_BYTES)),
            ("update", hot, field + 1, rng.bytes(FIELD_BYTES)),
            ("update", hot, field, rng.bytes(FIELD_BYTES)),
            ("read", hot),
            ("update", hot, field, rng.bytes(FIELD_BYTES))]
    before = counters()
    results, _ = held_batch(svc, [ch], plan)
    assert not any(c.failed() for c, _ in results)
    after = counters()
    assert after["table_updates_superseded"] - \
        before["table_updates_superseded"] == 2
    want = replay(svc, ref)
    assert results[3][1].record == want[203]
    # the field holds the write to reach the queue last of the three
    last = [value for _, key, f, value, _ in svc.batches[1]
            if (key, f) == (hot, field)][-1]
    at = field * FIELD_BYTES
    assert results[3][1].record[at:at + FIELD_BYTES] == last
    assert_table_is(svc, ref)


def test_padded_slots_write_nothing(table):
    svc, _, _ = table
    before = np.asarray(svc.table).copy()
    bucket = 8
    at = np.zeros((bucket, 2), np.int32)
    at[:, 0] = RECORDS + np.arange(bucket)  # every slot padded
    at[0] = (RECORDS - 1, 9 * 25)  # one real update, the last field
    values = np.full((bucket, 25), 0xA5A5A5A5, np.uint32)
    rows = svc._run(bucket, at, values, np.zeros(bucket, np.int32))
    after = np.asarray(svc.table)
    changed = np.argwhere(after != before)
    assert set(map(tuple, changed)) == {(RECORDS - 1, w)
                                        for w in range(225, 250)}
    assert (rows == after[0]).all()


def test_done_is_called_after_the_handler_returns(table):
    svc, _, _ = table
    held = threading.Event()
    svc.gate = held
    answered = threading.Event()
    cntl = rpc.Controller()
    resp = tb_pb2.TableReadResponse()
    svc.Read(cntl, tb_pb2.TableReadRequest(key=9), resp, answered.set)
    assert not answered.is_set()  # queued; the batch is held
    held.set()
    assert answered.wait(10)
    assert not cntl.failed()
    assert resp.record == np.asarray(svc.table)[9].tobytes()


def test_reads_from_a_snapshot_disagree_with_the_reference(table):
    """The control: a service that answers reads from a copy taken before
    the ops runs every update yet reads stale records, and the
    comparison with the reference catches it."""
    svc, ch, ref = table
    svc.snapshot = np.asarray(svc.table).copy()
    try:
        rng = np.random.default_rng(23)
        stale = 0
        for key in zipf_keys(rng, 20):
            key = int(key)
            value = rng.bytes(FIELD_BYTES)
            cntl, _ = call(ch, ("update", key, 0, value))
            assert not cntl.failed()
            cntl, resp = call(ch, ("read", key))
            assert not cntl.failed()
            ref.update(key, 0, value)
            stale += resp.record != ref.read(key)
        assert stale == 20
        assert_table_is(svc, ref)  # the updates themselves landed
    finally:
        svc.snapshot = None


@pytest.mark.parametrize("op,why", [
    (("read", RECORDS), "not below"),
    (("update", 3, FIELDS, b"x" * FIELD_BYTES), "fields below"),
    (("update", 3, 0, b"x" * (FIELD_BYTES - 1)), "values 100 B"),
])
def test_bad_requests_fail_at_once(table, op, why):
    svc, ch, ref = table
    cntl, _ = call(ch, op)
    assert cntl.failed() and cntl.error_code_value == errors.EREQUEST
    assert why in cntl.error_text
    assert not svc.batches
    assert_table_is(svc, ref)


def test_buckets_cover_the_cap_and_are_all_compiled(table):
    svc, _, _ = table
    assert ts.BUCKETS[0] == 1 and ts.BUCKETS[-1] == ts.BATCH_CAP
    assert [ts.bucket_of(n) for n in (1, 2, 3, 5, 200, 256)] == \
        [1, 2, 4, 8, 256, 256]
    with pytest.raises(ValueError):
        ts.bucket_of(ts.BATCH_CAP + 1)
    assert sorted(svc._programs) == list(ts.BUCKETS)


def crossings(monkeypatch):
    """Records every native write crossing as (thread, "write" or
    "batch", frames)."""
    seen = []
    write, write_batch = native.sock_write, native.sock_write_batch

    def counted_write(sock_id, data):
        seen.append((threading.get_ident(), "write", 1))
        return write(sock_id, data)

    def counted_batch(items):
        seen.append((threading.get_ident(), "batch", len(items)))
        return write_batch(items)

    monkeypatch.setattr(native, "sock_write", counted_write)
    monkeypatch.setattr(native, "sock_write_batch", counted_batch)
    return seen


def test_a_batchs_answers_leave_in_one_crossing(table, second_channel,
                                                  monkeypatch):
    svc, ch, ref = table
    seen = crossings(monkeypatch)
    rng = np.random.default_rng(29)
    plan = [("read", int(k)) if i % 3 else
            ("update", int(k), i % FIELDS, rng.bytes(FIELD_BYTES))
            for i, k in enumerate(zipf_keys(rng, 12))]
    results, first = held_batch(svc, [ch, second_channel], plan)
    assert not first[0].failed()
    assert not any(c.failed() for c, _ in results)
    want = replay(svc, ref)
    for i, op in enumerate(plan):
        if op[0] == "read":
            assert results[i][1].record == want[200 + i]
        else:
            assert results[i][1].ok
    consumer = set(svc.threads)
    assert [(kind, n) for t, kind, n in seen if t in consumer] == \
        [("batch", 1), ("batch", len(plan))]
    assert_table_is(svc, ref)


def test_a_close_connection_answer_still_reaches_the_client(
        table, second_channel):
    svc, ch, ref = table
    svc.close_keys = {77}
    try:
        # the op that closes its connection is answered first in the
        # batch; the op behind it, on the other connection, is answered too
        results, _ = held_batch(svc, [second_channel, ch],
                                [("read", 77), ("read", 78)])
    finally:
        svc.close_keys = set()
    for (cntl, resp), key in zip(results, (77, 78)):
        assert not cntl.failed(), cntl.error_text
        assert resp.record == ref.read(key)
    cntl, resp = call(ch, ("read", 79))
    assert not cntl.failed() and resp.record == ref.read(79)


def test_a_batch_whose_program_raises_answers_every_op(table, monkeypatch):
    svc, ch, ref = table
    seen = crossings(monkeypatch)
    plan = [("read", 5), ("update", 6, 2, b"u" * FIELD_BYTES), ("read", 7)]
    svc.fail = True
    try:
        results, first = held_batch(svc, [ch], plan)
    finally:
        svc.fail = False
    for cntl, _ in results + [first]:
        assert cntl.failed() and cntl.error_code_value == errors.EDEVICE
        assert "planted batch fault" in cntl.error_text
    consumer = set(svc.threads)
    assert [(kind, n) for t, kind, n in seen if t in consumer] == \
        [("batch", 1), ("batch", len(plan))]
    assert_table_is(svc, ref)  # the failed batch changed nothing


def test_reference_batch_applies_updates_before_reads():
    old = bytes(range(10)) * 2
    ref = TableReference({0: old, 1: old}, field_count=4)
    out = ref.apply_batch([("read", 0), ("update", 0, 1, b"ABCDE"),
                           ("update", 0, 1, b"VWXYZ"), ("read", 1)])
    new = old[:5] + b"VWXYZ" + old[10:]
    assert out == [new, None, None, old]
    with pytest.raises(ValueError):
        ref.update(0, 4, b"ABCDE")
