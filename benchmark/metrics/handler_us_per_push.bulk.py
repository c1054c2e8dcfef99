"""Python-lane handler time per acknowledged push: the program's
`rpc.handle` spans (request parse to response handed to the socket) in
the traced window, over the pushes completed."""
import host_spans

SPANS = ("rpc.handle",)


def read(data):
    return host_spans.per_push_us(data, SPANS)
