"""Host time one table batch waits for its device program and the copy
of its rows to the host: the program's `table.wait` spans in the traced
window, over their count, in µs."""
import host_spans

SPANS = ("table.wait",)


def read(data):
    return host_spans.per_span_us(data, SPANS)
