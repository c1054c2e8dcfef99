"""Host time of one table batch: the program's `table.batch` spans (the
consumer's time for a batch: dedupe, staging, dispatch, wait, copy and
the answers) in the traced window, over their count, in µs."""
import host_spans

SPANS = ("table.batch",)


def read(data):
    return host_spans.per_span_us(data, SPANS)
