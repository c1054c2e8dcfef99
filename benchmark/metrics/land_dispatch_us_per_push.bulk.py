"""Host time spent dispatching a push's landing in HBM, per acknowledged
push: the program's `tensor.stage` (padding copy), `tensor.fill` and
`tensor.view` (the block pool's two programs) and `tensor.device_put`
(plain landings) spans in the traced window, over the pushes completed."""
import host_spans

SPANS = ("tensor.stage", "tensor.fill", "tensor.view", "tensor.device_put")


def read(data):
    return host_spans.per_push_us(data, SPANS)
