"""TableService — fixed-width records resident in device memory, served by
point reads and one-field updates.

The table is one `uint32[records, words]` array on a device, each record
FIELD_COUNT fields of `words // FIELD_COUNT` words. It is handed over
whole at construction: a deployment loads a partition in bulk (an ingest of
sorted files), not through an insert per record over RPC.

`Read(key)` answers the whole record; `Update(key, field, value)` writes
one field. A handler parses and checks its request, queues it on the
table's ExecutionQueue and returns without calling `done`. The queue's
consumer takes whatever is queued, up to BATCH_CAP ops, and runs ONE
compiled program per batch: it scatters the batch's updates into the
table in place (the table is donated, so no second copy of it is ever
held) and then gathers the rows the batch reads. One device-to-host copy
brings those rows back, and only then is each op's `done` called.

- Ops in one batch are concurrent: none has been acknowledged. Applying
  every update before every read is therefore a valid linearisation, as
  is any order among updates to one (key, field); the host keeps the last
  to arrive and drops the others (XLA leaves the order of a scatter's
  repeated indices unspecified), counting them in
  `table_updates_superseded`.
- Batch shapes are fixed: a batch is padded to the smallest power of two
  that holds its reads and its kept updates (BUCKETS). A padded update
  slot points past the last record and is dropped; a padded read slot
  reads record 0, which is discarded. Every bucket is compiled when the
  service is built, so none compiles while it serves.

The consumer answers a batch's ops inside one `coalesced_writes()` scope,
so on the native runtime their frames leave in one native write and the
consumer gives up the interpreter lock once per batch, not once per op.

Spans (`rpcz.phase`): `table.batch` (the consumer's time for one batch),
`table.dispatch` (dispatch of the program), `table.wait` (the wait for the
program and the copy of the rows to the host), `table.answer` (the
batch's answers, serialized and written). Counters (bvar): see
`COUNTERS`.
"""
from __future__ import annotations

import functools
import logging
import time
from typing import List, Optional

import numpy as np

from brpc_tpu import bvar, rpcz
from brpc_tpu.bthread.execution_queue import ExecutionQueue
from brpc_tpu.rpc import errors
from brpc_tpu.rpc.native_runtime import coalesced_writes
from brpc_tpu.rpc.proto import table_service_pb2 as tb_pb2
from brpc_tpu.rpc.service import Service, rpc_method

FIELD_COUNT = 10  # fields of a record, as YCSB's usertable has
# ops one batch takes at most, the ExecutionQueue's own default
BATCH_CAP = 256
BUCKETS = tuple(1 << i for i in range(BATCH_CAP.bit_length()))

_batches = bvar.Adder("table_batches")
_reads = bvar.Adder("table_reads")
_updates = bvar.Adder("table_updates")
_superseded = bvar.Adder("table_updates_superseded")
# enqueue to the start of the op's batch, summed over ops
_queue_wait_ns = bvar.Adder("table_queue_wait_ns")
COUNTERS = ("table_batches", "table_reads", "table_updates",
            "table_updates_superseded", "table_queue_wait_ns")

_log = logging.getLogger(__name__)


def bucket_of(n: int) -> int:
    """The smallest bucket that holds n ops (n <= BATCH_CAP)."""
    for b in BUCKETS:
        if b >= n:
            return b
    raise ValueError(f"{n} ops exceed the batch cap {BATCH_CAP}")


@functools.lru_cache(maxsize=None)
def batch_program():
    """The jitted batch program, its table argument donated:

        (table u32[R, W], at i32[B, 2], values u32[B, F], keys i32[B])
            -> (table u32[R, W], rows u32[B, W])

    Row i of `at` is (record, first word of the field) of update i, whose
    F words are `values[i]`; a record index past the table drops the
    update. `rows` are the records `keys` name, after every update."""
    import jax
    from jax import lax

    def run(table, at, values, keys):
        dnums = lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0, 1))
        table = lax.scatter(table, at, values, dnums,
                            indices_are_sorted=False, unique_indices=True,
                            mode=lax.GatherScatterMode.FILL_OR_DROP)
        return table, table[keys]

    return jax.jit(run, donate_argnums=0)


def batch_arg_shapes(table_shape, field_words: int, bucket: int,
                     sharding=None):
    """ShapeDtypeStructs of the batch program's arguments for one bucket."""
    import jax

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (spec(tuple(table_shape), np.uint32),
            spec((bucket, 2), np.int32),
            spec((bucket, field_words), np.uint32),
            spec((bucket,), np.int32))


class _Op:
    __slots__ = ("key", "field", "words", "cntl", "response", "done",
                 "enqueued_ns")

    def __init__(self, key, field, words, cntl, response, done):
        self.key, self.field, self.words = key, field, words
        self.cntl, self.response, self.done = cntl, response, done
        self.enqueued_ns = time.monotonic_ns()


class TableService(Service):
    """Point reads and one-field updates of a device-resident table."""

    SERVICE_NAME = "Table"

    def __init__(self, table):
        if table.ndim != 2 or table.dtype != np.uint32 or \
                table.shape[1] % FIELD_COUNT:
            raise ValueError(f"table must be uint32[records, words] with "
                             f"words a multiple of {FIELD_COUNT}, got "
                             f"{table.dtype}{list(table.shape)}")
        self.records = table.shape[0]
        self.field_words = table.shape[1] // FIELD_COUNT
        self._table = table
        program = batch_program()
        self._programs = {
            b: program.lower(*batch_arg_shapes(
                table.shape, self.field_words, b, table.sharding)).compile()
            for b in BUCKETS}
        self._queue = ExecutionQueue(self._consume, batch_size=BATCH_CAP)

    @property
    def table(self):
        """The table as the last batch left it (read between batches)."""
        return self._table

    def close(self, timeout: Optional[float] = 10.0) -> bool:
        """Stops taking ops; those queued are still answered."""
        self._queue.stop()
        return self._queue.join(timeout)

    # -- handlers -----------------------------------------------------------
    @rpc_method(tb_pb2.TableReadRequest, tb_pb2.TableReadResponse)
    def Read(self, cntl, request, response, done):
        if request.key >= self.records:
            cntl.set_failed(errors.EREQUEST,
                            f"key {request.key} not below {self.records}")
            done()
            return
        self._submit(_Op(request.key, -1, None, cntl, response, done))

    @rpc_method(tb_pb2.TableUpdateRequest, tb_pb2.TableUpdateResponse)
    def Update(self, cntl, request, response, done):
        nbytes = self.field_words * 4
        if (request.key >= self.records
                or request.field >= FIELD_COUNT
                or len(request.value) != nbytes):
            cntl.set_failed(
                errors.EREQUEST,
                f"update of key {request.key} field {request.field} with "
                f"{len(request.value)} B: keys are below {self.records}, "
                f"fields below {FIELD_COUNT}, values {nbytes} B")
            done()
            return
        words = np.frombuffer(request.value, dtype="<u4")
        self._submit(_Op(request.key, request.field, words, cntl, response,
                         done))

    def _submit(self, op: _Op):
        if not self._queue.execute(op):
            op.cntl.set_failed(errors.ECLOSE, "table service stopped")
            op.done()

    # -- the consumer -------------------------------------------------------
    def _consume(self, tasks) -> int:
        ops = list(tasks)
        if not ops:
            return 0
        start = time.monotonic_ns()
        _queue_wait_ns.update(sum(start - op.enqueued_ns for op in ops))
        with rpcz.phase("table.batch"):
            try:
                self._apply(ops)
            except Exception as e:  # every op of the batch is answered
                _log.exception("table batch failed")
                with coalesced_writes():
                    for op in ops:
                        op.cntl.set_failed(errors.EDEVICE,
                                           f"table batch failed: {e}")
                        op.done()
        return 0

    def _apply(self, ops: List[_Op]):
        reads = [op for op in ops if op.field < 0]
        kept = {}
        for op in ops:  # in arrival order: the last to arrive is kept
            if op.field >= 0:
                kept[(op.key, op.field)] = op
        n_updates = len(ops) - len(reads)
        b = bucket_of(max(len(reads), len(kept), 1))
        fw = self.field_words
        at = np.zeros((b, 2), np.int32)
        at[:, 0] = self.records + np.arange(b)  # past the table: dropped
        values = np.zeros((b, fw), np.uint32)
        for i, op in enumerate(kept.values()):
            at[i] = (op.key, op.field * fw)
            values[i] = op.words
        keys = np.zeros(b, np.int32)
        keys[:len(reads)] = [op.key for op in reads]
        rows = self._run(b, at, values, keys)
        _batches.update(1)
        _reads.update(len(reads))
        _updates.update(n_updates)
        _superseded.update(n_updates - len(kept))
        with rpcz.phase("table.answer"), coalesced_writes():
            for i, op in enumerate(reads):
                op.response.record = rows[i].tobytes()
            for op in ops:
                if op.field >= 0:
                    op.response.ok = True
                op.done()

    def _run(self, bucket: int, at, values, keys) -> np.ndarray:
        """Runs one batch's program; returns the rows read, on the host."""
        with rpcz.phase("table.dispatch"):
            self._table, rows = self._programs[bucket](self._table, at,
                                                       values, keys)
        with rpcz.phase("table.wait"):
            rows.block_until_ready()
            return np.asarray(rows)
