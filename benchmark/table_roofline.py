"""The least HBM bytes and time of the table service's batches.

A batch of `reads` point reads and `updates` one-field updates must at
least read each row it returns and write it into the gathered result
(2 x 1,000 B a read) and write each updated field (100 B an update). The
least time is those bytes at the chip's HBM peak (`peaks.json`). Nothing
here reads the program.
"""
import roofline

RECORD_BYTES, FIELD_BYTES = 1000, 100


def least_bytes(reads: int, updates: int) -> int:
    return reads * 2 * RECORD_BYTES + updates * FIELD_BYTES


def least_s(device_kind: str, reads: int, updates: int) -> float:
    return least_bytes(reads, updates) / \
        roofline.peaks(device_kind)["hbm_bytes_per_s"]
