"""Host time to launch one all-reduce: the program's
`collective.allreduce` spans (array conversion, placement, program
lookup and dispatch) in the traced window, over their count, in µs."""
import host_spans

SPAN = "collective.allreduce"


def read(data):
    hs = host_spans.of(data)
    if not hs or not hs["host_span_counts"].get(SPAN):
        return None
    return (hs["host_span_totals"][SPAN] / hs["host_span_counts"][SPAN]
            * 1e6)
