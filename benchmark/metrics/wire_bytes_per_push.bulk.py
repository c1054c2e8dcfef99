"""Bytes the server's sockets read per acknowledged push in the window
(`nat_socket_read_bytes` delta): only the meta should ride the socket
when the payload takes the descriptor-ring fabric."""


def read(data):
    c = data.get("counters")
    ok = sum(1 for r in data["records"] if r["ok"])
    if not c or not ok:
        return None
    key = "nat_socket_read_bytes"
    return (c["after"][key] - c["before"][key]) / ok
