"""Share of the all-reduce roofline: the least time the chips could take
for the buckets merged in the traced window, over the summed device time
of the all-reduce operations (per chip). The least time of one bucket is
the larger of its ICI bytes per chip at the ICI peak and its HBM bytes at
the HBM peak (`roofline.allreduce_least_s`)."""
import roofline

OP = "all-reduce"


def read(data):
    tr = data.get("trace")
    merge = data.get("merge")
    if not tr or not merge:
        return None
    op_s = sum(v for k, v in tr["kind_totals"].items() if OP in k)
    calls = sum(v for k, v in tr["kind_counts"].items() if OP in k)
    if op_s <= 0 or calls <= 0:
        return None
    least, _ = roofline.allreduce_least_s(
        merge["device_kind"], merge["chips"], merge["bucket_bytes"])
    return calls * least / op_s * 100.0
