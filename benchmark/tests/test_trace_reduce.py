"""The reduction from a profiler trace to busy, idle, operation totals and
labelled idle gaps, checked on traces recorded on the chip and committed
under tests/data, against a sweep over the same events written apart;
and the roofline arithmetic against numbers worked by hand."""
import glob
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import roofline  # noqa: E402
import trace_reduce  # noqa: E402

TRACES = sorted(glob.glob(os.path.join(BENCH, "tests", "data",
                                       "*.xplane.pb")))


def tpu_ids(pd):
    return sorted(int(p.name.rsplit(":", 1)[1]) for p in pd.planes
                  if p.name.startswith("/device:TPU:"))


def sweep_busy_ns(events, lo, hi):
    """Busy time by a sweep over start/end points with a depth counter."""
    points = []
    for s, e, *_ in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort(key=lambda p: (p[0], -p[1]))
    busy, depth, since = 0, 0, None
    for t, d in points:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


def test_union_and_clip():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [
        [0, 4], [5, 7]]
    assert trace_reduce._clip([(0, 2), (3, 9)], 1, 5) == [(1, 2), (3, 5)]


@pytest.mark.parametrize("path", TRACES,
                         ids=[os.path.basename(p) for p in TRACES])
def test_recorded_trace(path):
    pd = trace_reduce.load(path)
    ids = tpu_ids(pd)
    assert ids, "no TPU plane in the recorded trace"
    got = trace_reduce.reduce(pd, ids)
    lo, hi = trace_reduce.window_of(pd)
    ops = trace_reduce.device_ops(pd, ids)
    busy = sum(sweep_busy_ns(ops[i], lo, hi) for i in ids) / len(ids)
    assert got["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert got["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < got["busy_s"] <= got["window_s"]
    # op totals: clipped durations per name, per chip, add up to at least
    # the busy time (overlapping ops count once in busy)
    assert sum(got["op_totals"].values()) >= got["busy_s"] * (1 - 1e-9)
    names = {name for i in ids for s, e, name, _ in ops[i]
             if e > lo and s < hi}
    assert set(got["op_totals"]) == names
    assert got["top_ops"] == sorted(got["top_ops"], key=lambda kv: -kv[1])
    # idle gaps on the first chip: the window less its busy time
    first = sweep_busy_ns(ops[ids[0]], lo, hi)
    gaps = got["idle_gaps"]
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert sum(g for _, g in gaps) <= (hi - lo - first) / 1e9 + 1e-9
    assert all(name in trace_reduce.HOST_SPANS + ("none",)
               for name, _ in gaps)


def test_allreduce_roofline_arithmetic():
    # 4 chips, 64 MiB: each chip sends 2*3/4 of it over ICI at 200 GB/s
    # (1,600 Gbit/s) and touches 2 x 64 MiB of HBM at 819 GB/s
    nbytes = 64 << 20
    assert roofline.allreduce_ici_bytes(4, nbytes) == 1.5 * nbytes
    assert roofline.allreduce_hbm_bytes(4, nbytes) == 2 * nbytes
    least, bound = roofline.allreduce_least_s("TPU v5 lite", 4, nbytes)
    assert bound == "ici"
    assert least == pytest.approx(1.5 * nbytes / 200e9)
    # on two chips each sends 2(2-1)/2 = all of its bytes once
    least2, bound2 = roofline.allreduce_least_s("TPU v5 lite", 2, nbytes)
    assert (bound2, least2) == ("ici", pytest.approx(nbytes / 200e9))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
