"""Device busy time per acknowledged push: the union of the chip's
operation intervals in the traced window, over the pushes completed."""


def read(data):
    tr = data.get("trace")
    ok = sum(1 for r in data["records"] if r["ok"])
    if not tr or not ok:
        return None
    return tr["busy_s"] / ok * 1e6
