"""Host time one table batch waits for its device program and the copy
of its rows to the host: the program's `table.wait` spans in the traced
window, over their count, in µs."""
import table_spans


def read(data):
    return table_spans.per_span_us(data, "table.wait")
