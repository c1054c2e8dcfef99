"""Time a request waits for the Python lane, per request handed to it, in
the window: the server's `nat_py_queue_wait_ns` delta (enqueue to the
start of its dispatch, behind earlier requests of its batch too) over
its `nat_py_dispatches` delta, in µs. A program without the counter
reads None."""
KEY = "nat_py_queue_wait_ns"


def read(data):
    c = data.get("counters")
    if not c or KEY not in c["before"] or KEY not in c["after"]:
        return None
    n = c["after"]["nat_py_dispatches"] - c["before"]["nat_py_dispatches"]
    if n <= 0:
        return None
    return (c["after"][KEY] - c["before"][KEY]) / n / 1e3
