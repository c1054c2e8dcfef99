"""Helpers shared by the benchmark's configurations, generators and readers.

Nothing here imports the program under test: seeded payloads, exact
checksums, percentiles and file loading by name.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# Clearing bit 30 of an fp32 word caps its exponent below 128, so seeded
# random words are always finite: no NaN payload for a path to canonicalize.
FINITE_MASK = 0xBFFFFFFF


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Imports a file by path; names with dots (metric files) cannot be
    imported the usual way."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def seeded_words(seed: int, stream: tuple, nwords: int):
    """`nwords` finite fp32 values as uint32 words, from the seed and a
    tuple of non-negative ints naming the stream."""
    import numpy as np

    rng = np.random.default_rng([seed % (1 << 64), *stream])
    words = np.frombuffer(rng.bytes(nwords * 4), dtype=np.uint32).copy()
    words &= np.uint32(FINITE_MASK)
    return words


def seeded_fp32(seed: int, stream: tuple, nwords: int):
    return seeded_words(seed, stream, nwords).view("float32")


def host_checksum(a) -> tuple:
    """(sum, position-weighted sum) of the 32-bit words, mod 2^32: exact,
    so host and device must agree bit for bit."""
    import numpy as np

    w = np.ascontiguousarray(a).view(np.uint32).reshape(-1)
    odd = np.arange(w.size, dtype=np.uint32) * np.uint32(2) + np.uint32(1)
    return (int(np.sum(w, dtype=np.uint32)),
            int(np.sum(w * odd, dtype=np.uint32)))


def device_checksum_fn():
    """The same checksum as one jitted program on the array's device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        w = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
        odd = jnp.arange(w.size, dtype=jnp.uint32) * 2 + 1
        return jnp.stack([jnp.sum(w, dtype=jnp.uint32),
                          jnp.sum(w * odd, dtype=jnp.uint32)])

    return lambda x: tuple(int(v) for v in f(x))


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of the devices (0 where the
    backend keeps no count)."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices))


def quantile(values, q: float) -> float:
    """Nearest-rank quantile (q in [0, 1]) of a non-empty sequence; an
    infinite value stands for a request that failed."""
    vals = sorted(values)
    if not vals:
        raise ValueError("quantile of no values")
    k = max(0, min(len(vals) - 1, math.ceil(q * len(vals)) - 1))
    return vals[k]


def window_GBps(data: dict) -> float:
    """Bytes of every acknowledged request per second, over the time from
    the window's start to the completion of the last request issued in it."""
    done = sum(r["nbytes"] for r in data["records"] if r["ok"])
    return done / (data["t_end"] - data["t0"]) / 1e9


def idle_share(data: dict):
    """Share (%) of the traced window in which no operation ran on a chip,
    averaged over the cell's chips; None without a trace."""
    tr = data.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0


def counter_deltas(data: dict, key: str):
    """{counter: after - before} of the counters a configuration read
    before and after the window into `data[key]`, or None for a run (or
    a program) without them."""
    c = data.get(key)
    if not c or not c["before"] or not c["after"]:
        return None
    return {k: c["after"][k] - c["before"][k] for k in c["after"]
            if k in c["before"]}


@contextlib.contextmanager
def swap(obj, attr: str, value):
    """Sets obj.attr to value for the duration of the block."""
    old = getattr(obj, attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        setattr(obj, attr, old)
