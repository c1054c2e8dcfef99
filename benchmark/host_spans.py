"""The program's phase spans in a profiler trace of one window, for the
`program_span` readers, and the idle gaps named down to the phase that
held the host.

The program records its phases (`rpcz.phase`) as events on the trace's
host plane, on the clock of the device's `XLA Ops` events. From the
benchmark's `window` span:

  host_span_totals  seconds per span name, clipped to the window and
                    summed over threads
  host_span_counts  events per span name that overlap the window
  idle_gaps         trace_reduce's ten longest idle gaps on the first
                    chip, each labelled `<benchmark span> > <phase>`: the
                    benchmark span (push, merge_round) that overlaps it
                    most, then the phase (of this file's SPANS) whose
                    own time (its time less its children's, on its
                    thread) overlaps it most. A gap that no phase covers
                    keeps trace_reduce's label.

The names totalled are those of `span_names()`: every span that a
`program_span` reader of BENCHMARK.json declares in the `SPANS` of its
file (`metrics/<name>.py`), and this file's `SPANS`, the phases that
name the gaps. A reader of a new span declares it there and is read
with no edit here. Nothing here imports the program, and a program
that records none of the names reads as no spans.

run.py reduces a traced run's trace once, into `data["host_spans"]`,
which the readers take through `per_push_us` and `per_span_us`. Run as a
script it prints the totals, counts and labelled gaps of a trace:

    python3 benchmark/host_spans.py <dir-or-xplane.pb> [device ids]
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

# the phases that name the idle gaps; totalled too
SPANS = (
    "rpc.handle", "rpc.respond",
    "tensor.claim", "tensor.stage", "tensor.fill", "tensor.view",
    "tensor.land_wait", "tensor.device_put",
    "tensor.window_wait", "tensor.arena_write",
    "collective.allreduce", "py.gc",
)


def declared_spans() -> set:
    """The span names that the `program_span` readers of BENCHMARK.json
    declare in their `SPANS`."""
    names = set()
    for m in lib.benchmark_spec()["per_layer"]:
        if m["source"] == "program_span":
            names.update(lib.load_module(
                os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"),
                "metric_" + m["name"]).SPANS)
    return names


def span_names() -> set:
    return declared_spans() | set(SPANS)


def _lines(pd, names):
    """[[(start_ns, end_ns, name), ...] per host line] of the named
    spans."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in line.events if ev.name in names]
            if evs:
                out.append(evs)
    return out


def reduce(pd) -> dict:
    """Totals and counts in the window of the spans of `span_names()`."""
    lo, hi = trace_reduce.window_of(pd)
    totals, counts = {}, {}
    for evs in _lines(pd, span_names()):
        for s, e, name in evs:
            if e > lo and s < hi:
                totals[name] = totals.get(name, 0) + min(e, hi) - max(s, lo)
                counts[name] = counts.get(name, 0) + 1
    return {"host_span_totals": {k: v / 1e9 for k, v in totals.items()},
            "host_span_counts": counts}


def _found(data: dict, names):
    """(seconds, events) of each named span the run's reduction holds."""
    hs = data.get("host_spans")
    if not hs:
        return []
    return [(hs["host_span_totals"][n], hs["host_span_counts"][n])
            for n in names if hs["host_span_counts"].get(n)]


def per_push_us(data: dict, names) -> float | None:
    """Summed seconds of the named spans in the window per acknowledged
    request, in µs; None when no trace, no request or none of the spans."""
    ok = sum(1 for r in data["records"] if r["ok"])
    found = _found(data, names)
    if not found or not ok:
        return None
    return sum(t for t, _ in found) / ok * 1e6


def per_span_us(data: dict, names) -> float | None:
    """Mean µs of one event of the named spans in the window; None when
    there is no trace or none of the spans."""
    found = _found(data, names)
    if not found:
        return None
    return sum(t for t, _ in found) / sum(n for _, n in found) * 1e6


def self_segments(events):
    """(start, end, name) pieces of one thread's properly nested events in
    which each event is the innermost one open."""
    points = sorted({x for s, e, _ in events for x in (s, e)})
    order = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    out, stack, k = [], [], 0
    for a, b in zip(points, points[1:]):
        # a closed event is on top of the stack: its children closed first
        while stack and stack[-1][1] <= a:
            stack.pop()
        while k < len(order) and order[k][0] <= a:
            if order[k][1] > a:
                stack.append(order[k])
            k += 1
        if stack:
            out.append((a, b, stack[-1][2]))
    return out


def _phase_label(gap, segments):
    over = {}
    for s, e, name in segments:
        d = min(e, gap[1]) - max(s, gap[0])
        if d > 0:
            over[name] = over.get(name, 0) + d
    return max(over, key=over.get) if over else None


def idle_gaps(pd, device_ids):
    """trace_reduce's idle gaps, each label extended by ` > <phase>`."""
    lo, hi = trace_reduce.window_of(pd)
    first = trace_reduce.device_ops(pd, device_ids)[device_ids[0]]
    union = trace_reduce._union(trace_reduce._clip(
        [ev[:2] for ev in first if ev[1] > lo and ev[0] < hi], lo, hi))
    edges = [lo] + [x for iv in union for x in iv] + [hi]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    gaps.sort(key=lambda g: g[0] - g[1])
    bench = trace_reduce.host_spans(pd, lo, hi)
    segments = [seg for evs in _lines(pd, SPANS)
                for seg in self_segments(evs)]
    out = []
    for g in gaps[:trace_reduce.TOP]:
        label = trace_reduce._label(g, bench)
        phase = _phase_label(g, segments)
        if phase is not None:
            label = f"{label} > {phase}"
        out.append([label, (g[1] - g[0]) / 1e9])
    return out


if __name__ == "__main__":
    pd = trace_reduce.load(sys.argv[1])
    ids = [int(a) for a in sys.argv[2:]] or [0]
    print(json.dumps({**reduce(pd), "idle_gaps": idle_gaps(pd, ids)},
                     indent=1))
