"""YCSB's closed loop: each client keeps `in_flight` ops outstanding, as
YCSB's client threads do when no `-target` rate is set, and issues the
next only when one completes, until the window closes.

Parameters (traffic file):
  clients            client processes
  in_flight          ops outstanding per client
  records            keys drawn from: 0 .. records - 1
  read_proportion    share of reads; the rest are updates
  update_proportion  (1 - read_proportion; stated for the record)
  zipfian_constant   YCSB's constant; only its default, 0.99, is defined
  field_count        fields of a record; an update writes one, uniform
  field_length       bytes of a field
  timeout_ms         an op's deadline

Keys come from YCSB's ScrambledZipfianGenerator: a Zipfian draw over
10^10 items with theta 0.99 and YCSB's precomputed zeta
26.46902820178302, FNV-64 hashed, mod `records`. Every draw is seeded
from the run's seed and the client's index.

An op is due when it is issued, so its latency is its service time.
"""
import threading
import time

import numpy as np

ZIPF_ITEMS = 10_000_000_000
ZIPF_THETA = 0.99
ZIPF_ZETAN = 26.46902820178302  # zeta(10^10, 0.99), as YCSB hardcodes it
FNV_OFFSET_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
OPS_STREAM = 3  # seeded streams: (3, client)
CHUNK = 4096


def zipfian_items(u):
    """ZipfianGenerator.nextLong(10^10) for uniform draws u in [0, 1)."""
    theta = ZIPF_THETA
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / ZIPF_ITEMS) ** (1.0 - theta)) / (
        1.0 - zeta2 / ZIPF_ZETAN)
    uz = u * ZIPF_ZETAN
    ret = (ZIPF_ITEMS * np.power(eta * u - eta + 1.0, alpha)).astype(
        np.int64)
    ret = np.where(uz < 1.0 + 0.5 ** theta, 1, ret)
    return np.where(uz < 1.0, 0, ret)


def fnvhash64(values):
    """YCSB's Utils.fnvhash64: FNV-1 over the 8 bytes, low byte first,
    then the absolute value as a signed 64-bit integer."""
    v = np.asarray(values, dtype=np.int64).view(np.uint64).copy()
    h = np.full(v.shape, FNV_OFFSET_64, dtype=np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def scrambled_zipfian(u, records: int):
    """ScrambledZipfianGenerator(0, records - 1).nextValue() for uniform
    draws u."""
    return fnvhash64(zipfian_items(u)) % records


def check(params: dict):
    if float(params["zipfian_constant"]) != ZIPF_THETA:
        raise ValueError("YCSB defines the scrambled Zipfian's zeta only "
                         f"for its default constant {ZIPF_THETA}")
    if abs(float(params["read_proportion"])
           + float(params["update_proportion"]) - 1.0) > 1e-9:
        raise ValueError("read and update proportions must sum to 1")


def requests(params: dict, seed: int, client: int):
    """The endless op sequence of one client: dicts with `k`, `op`
    ("read" or "update"), `key` and `field` (the field an update writes,
    -1 for a read)."""
    check(params)
    rng = np.random.default_rng([seed % (1 << 64), OPS_STREAM, client])
    records = int(params["records"])
    reads = float(params["read_proportion"])
    fields = int(params["field_count"])
    k = 0
    while True:
        keys = scrambled_zipfian(rng.random(CHUNK), records)
        is_read = rng.random(CHUNK) < reads
        field = rng.integers(0, fields, CHUNK)
        for i in range(CHUNK):
            if is_read[i]:
                yield {"k": k, "op": "read", "key": int(keys[i]),
                       "field": -1}
            else:
                yield {"k": k, "op": "update", "key": int(keys[i]),
                       "field": int(field[i])}
            k += 1


def drive(send, params: dict, seed: int, client: int, t0: float,
          seconds: float, clock):
    """Runs the loop from t0 until t0 + seconds; ops in flight at the
    close complete and count. `send(op)` returns (ok, error text) and may
    add what it observed to `op`. Returns one record per op."""
    seq = requests(params, seed, client)
    lock = threading.Lock()
    records = []
    end = t0 + seconds

    def slot():
        while True:
            with lock:
                if clock() >= end:
                    return
                op = next(seq)
            sent = clock()
            ok, err = send(op)
            done = clock()
            with lock:
                records.append(dict(op, due=sent, sent=sent, done=done,
                                    ok=ok, error=err))

    time.sleep(max(0.0, t0 - clock()))
    threads = [threading.Thread(target=slot, name=f"slot{i}")
               for i in range(int(params["in_flight"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records
