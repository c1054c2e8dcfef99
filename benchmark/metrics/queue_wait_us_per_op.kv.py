"""Time an op waits in the table service's queue, per op in the window:
the `table_queue_wait_ns` delta (enqueue to the start of the op's batch)
over the `table_reads` + `table_updates` deltas, in µs."""
import lib


def read(data):
    d = lib.counter_deltas(data, "table_counters")
    if not d or "table_queue_wait_ns" not in d:
        return None
    ops = d["table_reads"] + d["table_updates"]
    return d["table_queue_wait_ns"] / ops / 1e3 if ops > 0 else None
