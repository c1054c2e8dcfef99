"""The reduction from a profiler trace to busy, idle, operation totals and
labelled idle gaps, checked on traces recorded on the chip and committed
under tests/data, against a sweep over the same events written apart;
the phase spans' totals, counts and labelled gaps against the readings
of the reducers they replaced, and on a trace built by hand; and the
roofline arithmetic against numbers worked by hand."""
import glob
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import host_spans  # noqa: E402
import lib  # noqa: E402
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


# What `host_spans.reduce` and `host_spans.idle_gaps(pd, [0])` read on
# each recorded trace while host_spans and table_spans kept a fixed list
# of spans each; `table.*` totals and counts are table_spans.reduce's.
BEFORE = {
    "tensor_store.push.xplane.pb": {
        "host_span_totals": {}, "host_span_counts": {},
        "idle_gaps": [
            ["push", 0.320137285], ["push", 0.015669688],
            ["push", 0.01412782], ["push", 0.011451393],
            ["push", 0.009421813], ["push", 0.007440312],
            ["push", 0.006488024], ["push", 0.006264322],
            ["push", 0.005959426], ["push", 0.005769566]]},
    "store.push2m.xplane.pb": {
        "host_span_totals": {
            "rpc.handle": 0.954105227, "tensor.claim": 0.002213796,
            "tensor.fill": 0.147341141, "tensor.view": 0.151181352,
            "tensor.land_wait": 0.471241535, "rpc.respond": 0.067212763,
            "py.gc": 0.0017239},
        "host_span_counts": {
            "rpc.handle": 541, "tensor.claim": 541, "tensor.fill": 541,
            "tensor.view": 541, "tensor.land_wait": 541,
            "rpc.respond": 541, "py.gc": 3},
        "idle_gaps": [
            ["push > tensor.land_wait", 0.310584584],
            ["push > py.gc", 0.009287076],
            ["push > tensor.land_wait", 0.008715832],
            ["push > tensor.land_wait", 0.008061335],
            ["push > tensor.land_wait", 0.006686897],
            ["push > tensor.land_wait", 0.006508541],
            ["push > tensor.land_wait", 0.006391319],
            ["push > tensor.land_wait", 0.006163626],
            ["push > tensor.land_wait", 0.006010184],
            ["push > tensor.land_wait", 0.005605185]]},
    "kv.ycsb_b.xplane.pb": {
        "host_span_totals": {
            "rpc.handle": 0.131889607, "py.gc": 0.043699392,
            "rpc.respond": 0.060181302,
            "table.batch": 0.48508714, "table.wait": 0.0855893},
        "host_span_counts": {
            "rpc.handle": 1537, "py.gc": 54, "rpc.respond": 1537,
            "table.batch": 49, "table.wait": 49},
        "idle_gaps": [
            ["none > rpc.handle", 0.32641566], ["none > py.gc", 0.046222183],
            ["none > py.gc", 0.034417469],
            ["none > rpc.handle", 0.025387213],
            ["none > rpc.respond", 0.020879068],
            ["none > rpc.respond", 0.019911343],
            ["none > rpc.handle", 0.01980628],
            ["none > rpc.handle", 0.019417721],
            ["none > rpc.handle", 0.019394921],
            ["none > rpc.handle", 0.018630182]]},
}
# The program_span readings printed by the traced runs (TPU v5 lite,
# windows of 1 s and 0.5 s) that recorded the traces, with the requests
# they acknowledged.
PRINTED = {
    "store.push2m.xplane.pb": (541, {
        "handler_us_per_push.bulk": 1763.5956136783734,
        "claim_us_per_push.bulk": 4.092044362292052,
        "land_dispatch_us_per_push.bulk": 551.797584103512,
        "land_wait_us_per_push.bulk": 871.0564417744918}),
    "kv.ycsb_b.xplane.pb": (1537, {
        "batch_us_per_batch.kv": 9899.73755102041,
        "table_wait_us_per_batch.kv": 1746.7204081632651}),
}


def recorded(name):
    return trace_reduce.load(os.path.join(BENCH, "tests", "data", name))


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_span_reduction_reads_as_before(name):
    pd = recorded(name)
    got = host_spans.reduce(pd)
    want = BEFORE[name]
    # spans declared since are totalled besides
    for key in ("host_span_totals", "host_span_counts"):
        assert {k: got[key].get(k) for k in want[key]} == want[key]
    assert host_spans.idle_gaps(pd, [0]) == want["idle_gaps"]


@pytest.mark.parametrize("name", sorted(PRINTED))
def test_span_readers_read_what_the_run_printed(name):
    ok, printed = PRINTED[name]
    data = {"records": [{"ok": True}] * ok,
            "host_spans": host_spans.reduce(recorded(name))}
    for metric, value in printed.items():
        reader = lib.load_module(os.path.join(BENCH, "metrics",
                                              metric + ".py"), "m")
        assert reader.read(data) == value, metric


def built_trace(lines, lo=1000, hi=2000):
    """A trace of one host plane: the window [lo, hi) and one line of
    (name, start_ns, duration_ns) events per entry of `lines`."""
    def line(k, evs):
        return SimpleNamespace(name=f"t{k}", events=[
            SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs])

    host = SimpleNamespace(name="/host:CPU", lines=[
        line(k, evs) for k, evs in
        enumerate([[("window", lo, hi - lo)]] + lines)])
    return SimpleNamespace(planes=[host, SimpleNamespace(
        name="/device:TPU:0", lines=[line(0, [("table.batch", 0, 5000)])])])


def test_host_spans_total_the_declared_names_in_the_window(monkeypatch):
    pd = built_trace([
        [("table.batch", 900, 300), ("table.wait", 1100, 50),
         ("table.batch", 1500, 100), ("table.batch", 2000, 10)],
        [("rpc.handle", 1900, 500), ("not.declared", 1200, 10),
         ("py.gc", 100, 10)]])
    got = host_spans.reduce(pd)
    assert got["host_span_counts"] == {"table.batch": 2, "table.wait": 1,
                                       "rpc.handle": 1}
    assert got["host_span_totals"] == pytest.approx(
        {"table.batch": 300e-9, "table.wait": 50e-9, "rpc.handle": 100e-9})
    # a name is totalled once a reader declares it
    monkeypatch.setattr(host_spans, "declared_spans",
                        lambda: {"not.declared"})
    assert host_spans.reduce(pd) == {
        "host_span_totals": {"not.declared": pytest.approx(10e-9),
                             "rpc.handle": pytest.approx(100e-9)},
        "host_span_counts": {"not.declared": 1, "rpc.handle": 1}}


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
