"""Host time to launch one all-reduce: the program's
`collective.allreduce` spans (array conversion, placement, program
lookup and dispatch) in the traced window, over their count, in µs."""
import host_spans

SPANS = ("collective.allreduce",)


def read(data):
    return host_spans.per_span_us(data, SPANS)
