"""The readers of the program's phase spans and of the py-lane queue-wait
counter, on hand-built window data and on data that lacks their input
(a program without the spans or the counter, or an untraced run)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import host_spans  # noqa: E402
import lib  # noqa: E402

SPEC = lib.benchmark_spec()
SPAN_READERS = [m["name"] for m in SPEC["per_layer"]
                if m["source"] == "program_span"]


def reader(name):
    return lib.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                           "m_" + name)


def records(ok, failed=0):
    return [{"ok": True}] * ok + [{"ok": False}] * failed


def spans(**seconds):
    return {"host_span_totals": dict(seconds),
            "host_span_counts": {k: 4 for k in seconds}}


@pytest.mark.parametrize("name,totals,want", [
    ("handler_us_per_push.bulk", {"rpc.handle": 0.5, "tensor.claim": 0.1},
     500.0),
    ("claim_us_per_push.bulk", {"rpc.handle": 0.5, "tensor.claim": 0.1},
     100.0),
    ("land_dispatch_us_per_push.bulk",
     {"tensor.stage": 0.01, "tensor.fill": 0.02, "tensor.view": 0.03,
      "tensor.land_wait": 0.5}, 60.0),
    ("land_dispatch_us_per_push.bulk", {"tensor.device_put": 0.2}, 200.0),
    ("land_wait_us_per_push.bulk", {"tensor.land_wait": 0.25}, 250.0),
])
def test_push_span_readers(name, totals, want):
    # 1000 acknowledged pushes; a failed one is not counted
    data = {"records": records(1000, 3), "host_spans": spans(**totals)}
    assert reader(name).read(data) == pytest.approx(want)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_read_none_without_their_spans(name):
    r = reader(name)
    own = {s: 1.0 for s in r.SPANS}
    others = {s: 1.0 for s in host_spans.span_names() - set(r.SPANS)}
    # an untraced run, a trace of a program without the spans, no pushes
    assert r.read({"records": records(5), "trace": None}) is None
    assert r.read({"records": records(5),
                   "host_spans": spans(**others)}) is None
    assert r.read({"records": records(5), "host_spans": spans(**own)}) > 0
    if name.endswith(".bulk"):  # per push: none acknowledged
        assert r.read({"records": [],
                       "host_spans": spans(**own, **others)}) is None


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_declare_their_spans(name):
    names = reader(name).SPANS
    assert isinstance(names, tuple) and names
    assert all(isinstance(n, str) and n for n in names)


def test_allreduce_launch_reader():
    data = {"records": records(10), "host_spans": {
        "host_span_totals": {"collective.allreduce": 0.008},
        "host_span_counts": {"collective.allreduce": 160}}}
    assert reader("launch_us_per_allreduce.merge").read(data) == \
        pytest.approx(50.0)


def test_queue_wait_reader():
    r = reader("py_queue_wait_us_per_push.bulk")
    before = {"nat_py_dispatches": 10, "nat_py_queue_wait_ns": 1000}
    after = {"nat_py_dispatches": 410, "nat_py_queue_wait_ns": 20001000}
    data = {"records": records(400),
            "counters": {"before": before, "after": after}}
    assert r.read(data) == pytest.approx(50.0)
    # a program without the counter, a run without counters, no requests
    old = {k: v for k, v in after.items() if k != "nat_py_queue_wait_ns"}
    assert r.read({"records": [], "counters": {"before": before,
                                                 "after": old}}) is None
    assert r.read({"records": records(4)}) is None
    assert r.read({"records": [], "counters": {"before": before,
                                                 "after": before}}) is None
