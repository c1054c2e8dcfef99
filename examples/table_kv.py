#!/usr/bin/env python
"""table_kv — a key-value table held in device memory behind TableService:
point reads and one-field updates through an ordinary Channel, batched by
the service into one device program per batch.

  python examples/table_kv.py [--records 1024]

On a machine without an accelerator JAX keeps the table on the CPU.
"""
import argparse
import sys
import threading

sys.path.insert(0, ".")

import numpy as np  # noqa: E402

from brpc_tpu import rpc  # noqa: E402
from brpc_tpu.rpc.proto import table_service_pb2 as tb_pb2  # noqa: E402
from brpc_tpu.rpc.table_service import (  # noqa: E402
    FIELD_COUNT as FIELDS,
    TableService,
)

FIELD_BYTES = 100  # YCSB's record: ten 100-byte fields


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=1024)
    args = ap.parse_args()
    import jax.numpy as jnp

    # the bulk load: every record's bytes at once, as one device array
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.integers(0, 2**32, (args.records,
                                                FIELDS * FIELD_BYTES // 4),
                                     dtype=np.uint32))
    svc = TableService(table)
    srv = rpc.Server(rpc.ServerOptions(use_native_runtime=True))
    srv.add_service(svc)
    if srv.start("127.0.0.1:0") != 0:
        return 1
    ch = rpc.Channel(rpc.ChannelOptions(timeout_ms=10000, max_retry=0))
    if ch.init(str(srv.listen_endpoint)) != 0:
        return 1

    key, field, value = 7, 4, b"v" * FIELD_BYTES
    cntl, resp = ch.call("Table.Update", tb_pb2.TableUpdateRequest(
        key=key, field=field, value=value), tb_pb2.TableUpdateResponse)
    ok = not cntl.failed() and resp.ok
    cntl, resp = ch.call("Table.Read", tb_pb2.TableReadRequest(key=key),
                         tb_pb2.TableReadResponse)
    got = resp.record[field * FIELD_BYTES:(field + 1) * FIELD_BYTES]
    ok = ok and not cntl.failed() and got == value
    print(f"read key {key} after updating field {field}: "
          f"{len(resp.record)} B, field {'matches' if ok else 'DIFFERS'}")

    # concurrent callers: the service answers them in shared batches
    errors = []

    def reader(t):
        for k in range(t, args.records, len(threads)):
            c, r = ch.call("Table.Read", tb_pb2.TableReadRequest(key=k),
                           tb_pb2.TableReadResponse)
            if c.failed() or len(r.record) != FIELDS * FIELD_BYTES:
                errors.append(c.error_text or "short record")

    threads = [threading.Thread(target=reader, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    from brpc_tpu import bvar

    batches = bvar.find_exposed("table_batches").get_value()
    reads = bvar.find_exposed("table_reads").get_value()
    print(f"{reads} reads in {batches} batches, {len(errors)} failed")
    ch.close()
    srv.stop()
    srv.join(10)
    svc.close()
    return 0 if ok and not errors else 1


if __name__ == "__main__":
    sys.exit(main())
