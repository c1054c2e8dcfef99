#!/usr/bin/env python
"""Regenerate the committed golden capture the fleet drill replays.

tests/data/golden_capture_1k.rio is a deterministic 1000-request
tpu_std capture (seeded payload sizes, recordio format —
butil/recordio.py) that tests/test_fleet_observatory.py re-fires
through the native replay client against a live server group.
Committing the capture (not just this generator) keeps the drill's
traffic byte-stable: a change in what it sees is a runtime change,
never a workload drift.

Usage: python tools/make_golden_capture.py [out_path]
"""
from __future__ import annotations

import os
import random
import sys

sys.path.insert(0, ".")

N_RECORDS = 1000
SEED = 20260804


def main():
    from brpc_tpu.butil.recordio import RecordWriter

    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        "tests", "data", "golden_capture_1k.rio")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    if os.path.exists(out):
        os.unlink(out)  # RecordWriter appends; the capture must be exact
    rng = random.Random(SEED)
    with RecordWriter(out) as w:
        for i in range(N_RECORDS):
            # production-shaped size mix: mostly small, a long tail
            size = rng.choice((16, 16, 32, 64, 128, 256, 1024))
            payload = bytes((i + j * 7) % 256 for j in range(size))
            w.write({"service": "EchoService", "method": "Echo",
                     "log_id": i, "ts": 0.0, "lane": "echo"}, payload)
    print(f"wrote {N_RECORDS} records to {out} "
          f"({os.path.getsize(out)} bytes)")


if __name__ == "__main__":
    main()
