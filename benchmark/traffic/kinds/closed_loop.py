"""Closed loop: each client keeps `in_flight` requests outstanding and
issues the next only when one completes, until the window closes.

Parameters (traffic file):
  clients      client processes
  in_flight    requests outstanding per client
  sizes_kib    tensor sizes (fp32, 1-D), taken in turn
  names        names a client writes or reads, round-robin
  payloads     distinct seeded payloads per size (pushes); coprime to
               `names`, so successive writes of one name always differ

A request is due when it is issued, so its latency is its service time.
"""
import math
import threading
import time


def requests(params: dict, client: int):
    """The endless request sequence of one client: dicts with `k`, `name`,
    `nbytes` and `payload` (index into that size's payloads)."""
    sizes = [int(k) << 10 for k in params["sizes_kib"]]
    names, payloads = int(params["names"]), int(params.get("payloads", 1))
    if math.gcd(names, payloads) != 1:
        raise ValueError("payloads must be coprime to names")
    k = 0
    while True:
        yield {"k": k, "name": f"c{client}/n{k % names}",
               "nbytes": sizes[k % len(sizes)],
               "payload": k % payloads}
        k += 1


def drive(send, params: dict, seed: int, client: int, t0: float,
          seconds: float, clock):
    """Runs the loop from t0 until t0 + seconds; requests in flight at
    the close complete and count. Returns one record per request."""
    seq = requests(params, client)
    lock = threading.Lock()
    records = []
    end = t0 + seconds

    def slot():
        while True:
            with lock:
                now = clock()
                if now >= end:
                    return
                req = next(seq)
            sent = clock()
            ok, err = send(req)
            done = clock()
            with lock:
                records.append(dict(req, due=sent, sent=sent, done=done,
                                    ok=ok, error=err))

    time.sleep(max(0.0, t0 - clock()))
    threads = [threading.Thread(target=slot, name=f"slot{i}")
               for i in range(int(params["in_flight"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records
