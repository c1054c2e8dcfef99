"""Peaks by device kind and the bytes a collective must move.

An unknown device kind is an error, not a default.
"""
import os

import lib

PEAKS_FILE = os.path.join(lib.BENCH_DIR, "peaks.json")


def peaks(device_kind: str) -> dict:
    table = lib.load_json(PEAKS_FILE)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def allreduce_ici_bytes(n: int, nbytes: int) -> float:
    """Bytes each chip must send to all-reduce `nbytes` over n chips: the
    bandwidth-optimal schedule sends 2(n-1)/n of the data."""
    return 2.0 * (n - 1) / n * nbytes


def allreduce_hbm_bytes(n: int, nbytes: int) -> float:
    """HBM bytes each chip must touch at the least: read its contribution
    and write the result."""
    return 2.0 * nbytes


def allreduce_least_s(device_kind: str, n: int, nbytes: int):
    """(least seconds, bound) of one all-reduce: the larger of its ICI and
    HBM times at peak, and which of the two it is."""
    p = peaks(device_kind)
    ici = allreduce_ici_bytes(n, nbytes) / (p["ici_bits_per_s"] / 8)
    hbm = allreduce_hbm_bytes(n, nbytes) / p["hbm_bytes_per_s"]
    return (ici, "ici") if ici >= hbm else (hbm, "hbm")
