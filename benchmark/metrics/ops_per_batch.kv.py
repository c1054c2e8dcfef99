"""Ops the table service ran per batch in the window: the
`table_reads` + `table_updates` deltas over the `table_batches` delta,
read in the chip's process before and after the window. A program
without the counters reads None."""
import lib


def read(data):
    d = lib.counter_deltas(data, "table_counters")
    if not d or not d.get("table_batches"):
        return None
    return (d["table_reads"] + d["table_updates"]) / d["table_batches"]
