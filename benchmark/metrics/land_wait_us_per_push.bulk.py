"""Host time spent waiting for a push's landing to complete, per
acknowledged push: the program's `tensor.land_wait` spans
(`block_until_ready` on the landed arrays) in the traced window, over the
pushes completed."""
import host_spans

SPANS = ("tensor.land_wait",)


def read(data):
    return host_spans.per_push_us(data, SPANS)
