"""Rounds of gradient merges: each round merges every bucket of one
gradient set and waits for its last bucket; the sets alternate, so no
round's result can be reused for the next.

Parameters (traffic file):
  buckets     buckets per gradient set
  bucket_mib  fp32 MiB per chip in one bucket
  sets        gradient sets made from the seed, used in turn
"""
import time


def drive(send, params: dict, seed: int, client: int, t0: float,
          seconds: float, clock):
    """Runs rounds from t0 until t0 + seconds; the round in progress at
    the close completes and counts. `send({"set": i})` merges set i and
    returns (ok, error). Returns one record per round."""
    sets = int(params["sets"])
    nbytes = int(params["buckets"]) * (int(params["bucket_mib"]) << 20)
    records = []
    time.sleep(max(0.0, t0 - clock()))
    r = 0
    while clock() < t0 + seconds:
        req = {"set": r % sets, "nbytes": nbytes}
        sent = clock()
        ok, err = send(req)
        done = clock()
        records.append(dict(req, due=sent, sent=sent, done=done, ok=ok,
                            error=err))
        r += 1
    return records
