"""The table service's phase spans in a profiler trace of one window, and
its counters over the window, for the readers of the `kv.ycsb_b` cell.

TableService records `table.batch` (the consumer's time for one batch),
`table.dispatch` (dispatch of the batch program) and `table.wait` (the
wait for the program and the copy of its rows to the host) as
`rpcz.phase` events on the trace's host plane. Clipped to the benchmark's
`window` span (`trace_reduce.window_of`):

  totals  seconds per span name, summed over threads
  counts  events per span name that overlap the window

A program without the spans, or an untraced run, reads as no spans.
Readers call `per_span_us(data, name)`, which reduces the trace that
run.py leaves in TRACE_DIR once per run and keeps the result in `data`,
and `counter_deltas(data)` for the counters (`table_batches`,
`table_reads`, `table_updates`, `table_updates_superseded`,
`table_queue_wait_ns`) that the configuration reads in the chip's process
before and after the window.
Run as a script it prints the totals and counts of a trace:

    python3 benchmark/table_spans.py <dir-or-xplane.pb>
"""
from __future__ import annotations

import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import lib  # noqa: E402
import trace_reduce  # noqa: E402

TRACE_DIR = os.path.join(lib.ROOT, ".bench_trace")
SPANS = ("table.batch", "table.dispatch", "table.wait")


def reduce(pd) -> dict:
    lo, hi = trace_reduce.window_of(pd)
    totals, counts = {}, {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name not in SPANS:
                    continue
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e > lo and s < hi:
                    totals[ev.name] = totals.get(ev.name, 0) + \
                        min(e, hi) - max(s, lo)
                    counts[ev.name] = counts.get(ev.name, 0) + 1
    return {"totals": {k: v / 1e9 for k, v in totals.items()},
            "counts": counts}


def of(data: dict):
    """The reduction of this run's trace, or None for a run without one."""
    if "table_spans" not in data:
        data["table_spans"] = (reduce(trace_reduce.load(TRACE_DIR))
                               if data.get("trace") else None)
    return data["table_spans"]


def per_span_us(data: dict, name: str) -> float | None:
    """Mean µs of the named span in the window; None when there is no
    trace or no such span."""
    spans = of(data)
    if not spans or not spans["counts"].get(name):
        return None
    return spans["totals"][name] / spans["counts"][name] * 1e6


def counter_deltas(data: dict):
    """{counter: after - before} of the service's counters in the window,
    or None for a run (or a program) without them."""
    c = data.get("table_counters")
    if not c or not c["before"] or not c["after"]:
        return None
    return {k: c["after"][k] - c["before"][k] for k in c["after"]
            if k in c["before"]}


if __name__ == "__main__":
    print(json.dumps(reduce(trace_reduce.load(sys.argv[1])), indent=1))
