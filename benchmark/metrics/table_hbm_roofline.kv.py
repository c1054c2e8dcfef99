"""Share of the HBM roofline of the table program: the least time of the
window's batches at the HBM peak (`table_roofline.least_s` of the
`table_reads` and `table_updates` deltas) over the trace's device busy
time. The table program is the only device work in this cell's window,
so busy time is its time: a traced run on TPU v5 lite showed only its
operations (the scatter's while loop with its fusions and
dynamic-update-slice, the row gather, the copies of its arguments)."""
import lib
import table_roofline


def read(data):
    tr = data.get("trace")
    d = lib.counter_deltas(data, "table_counters")
    if not tr or tr["busy_s"] <= 0 or not d or "table_reads" not in d \
            or not d["table_reads"] + d["table_updates"]:
        return None  # no op in the window: no share to read
    least = table_roofline.least_s(data["device_kind"], d["table_reads"],
                                   d["table_updates"])
    return least / tr["busy_s"] * 100.0
