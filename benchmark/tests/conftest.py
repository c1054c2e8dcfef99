"""What the benchmark's older test files list per cell and per metric,
for the cells and metrics added after them, so that those tests cover
the additions too:

  SMALL  (test_faults.py) the size at which a cell runs on the CPU
  NEW    (test_host_span_readers.py) the per-layer entries, in order, that
         follow the accepted ones at the end of BENCHMARK.json's list

Each table here is merged into the module's own before its tests run.
"""
import pytest

SMALL = {
    # 4,096 records; 2 clients of 8 in flight keep a 1 s run light
    "kv.ycsb_b": {"records": 4096, "clients": 2, "in_flight": 8},
}
NEW = {
    "ops_per_batch.kv": ("program_counter", "table service batcher",
                         "tensor_GBps", ["kv.ycsb_b"]),
    "queue_wait_us_per_op.kv": ("program_counter", "table service batcher",
                                "tensor_GBps", ["kv.ycsb_b"]),
    "batch_us_per_batch.kv": ("program_span", "table service batcher",
                              "tensor_GBps", ["kv.ycsb_b"]),
    "table_wait_us_per_batch.kv": ("program_span", "device table program",
                                   "tensor_GBps", ["kv.ycsb_b"]),
    "table_hbm_roofline.kv": ("device_trace", "device table program",
                              "tensor_GBps", ["kv.ycsb_b"]),
}


@pytest.fixture(autouse=True)
def _tables_cover_the_additions(request):
    for name, added in (("SMALL", SMALL), ("NEW", NEW)):
        table = getattr(request.module, name, None)
        if isinstance(table, dict):
            for key, value in added.items():
                table.setdefault(key, value)
