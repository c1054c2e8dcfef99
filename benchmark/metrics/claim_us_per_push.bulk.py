"""Time the server waits for the fabric's drain thread to hand over a
push's record, per acknowledged push: the program's `tensor.claim` spans
in the traced window, over the pushes completed."""
import host_spans

SPANS = ("tensor.claim",)


def read(data):
    return host_spans.per_push_us(data, SPANS)
