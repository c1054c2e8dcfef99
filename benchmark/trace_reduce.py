"""Reduces a JAX profiler trace of one window to the numbers the per-layer
readers take.

The window is the benchmark's own `window` span on a host plane. On each
device plane (`/device:TPU:<id>`) the device's operations are the events
of its `XLA Ops` line. From those, clipped to the window:

  busy_s     union of the operation intervals, averaged over the chips
  window_s   length of the window
  op_totals  seconds per operation (`op_label`), summed over chips / chips
  op_counts  events per operation, summed over chips / chips
  kind_totals, kind_counts  the same per opcode (`all-reduce`, `fusion`)
  top_ops    the ten operations with the largest op_totals
  idle_gaps  the ten longest gaps on the first chip in which no operation
             ran, each named by the benchmark span (push,
             merge_round) that overlapped it most, else `none`

Run as a script it prints every plane and line of a trace with a few
event names, for looking at a trace by hand:

    python3 benchmark/trace_reduce.py <dir-or-xplane.pb>
"""
from __future__ import annotations

import glob
import os
import re
import sys

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
HOST_SPANS = ("push", "merge_round")
TOP = 10


def xplane_file(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(xplane_file(path))


def _union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(pd):
    """(start_ns, end_ns) of the benchmark's window span."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    raise ValueError("trace has no window span")


def host_spans(pd, lo, hi):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e > lo and s < hi:
                        spans.append((max(s, lo), min(e, hi), ev.name))
    return spans


HLO = re.compile(r"^(%\S+) = (\S+?)(?:\{\S*\})? ([a-z][a-z0-9-]*)\(")


def op_label(name: str):
    """(label, kind) of an XLA op event. The TPU names an event by its HLO
    instruction (`%copy.3 = u8[65536]{...} copy(...)`): the kind is the
    opcode and the label `opcode shape instruction`. Other names stand
    as they are."""
    m = HLO.match(name)
    if not m:
        return name, name
    instr, shape, opcode = m.groups()
    return f"{opcode} {shape} {instr}", opcode


def device_ops(pd, device_ids):
    """{device id: [(start_ns, end_ns, name, kind), ...]} from each chip's
    XLA Ops line."""
    want = {f"/device:TPU:{i}": i for i in device_ids}
    found = {}
    for plane in pd.planes:
        if plane.name not in want:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                found[want[plane.name]] = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     *op_label(ev.name))
                    for ev in line.events]
    # a chip that ran no operation in the trace has no such line
    return {i: found.get(i, []) for i in device_ids}


def _label(gap, spans):
    best, name = 0, "none"
    for s, e, n in spans:
        over = min(e, gap[1]) - max(s, gap[0])
        if over > best:
            best, name = over, n
    return name


def reduce(pd, device_ids):
    lo, hi = window_of(pd)
    ops = device_ops(pd, device_ids)
    n = len(device_ids)
    busy_ns, totals, counts, kinds, kind_counts = 0, {}, {}, {}, {}
    first_union = None
    for i in device_ids:
        events = [ev for ev in ops[i] if ev[1] > lo and ev[0] < hi]
        for s, e, name, kind in events:
            d = min(e, hi) - max(s, lo)
            totals[name] = totals.get(name, 0) + d
            counts[name] = counts.get(name, 0) + 1
            kinds[kind] = kinds.get(kind, 0) + d
            kind_counts[kind] = kind_counts.get(kind, 0) + 1
        union = _union(_clip([ev[:2] for ev in events], lo, hi))
        busy_ns += sum(e - s for s, e in union)
        if first_union is None:
            first_union = union
    spans = host_spans(pd, lo, hi)
    edges = [lo] + [x for iv in first_union for x in iv] + [hi]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    gaps.sort(key=lambda g: g[0] - g[1])
    op_totals = {k: v / n / 1e9 for k, v in totals.items()}
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "op_totals": op_totals,
        "op_counts": {k: v / n for k, v in counts.items()},
        "kind_totals": {k: v / n / 1e9 for k, v in kinds.items()},
        "kind_counts": {k: v / n for k, v in kind_counts.items()},
        "top_ops": sorted(([k, v] for k, v in op_totals.items()),
                          key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": [[_label(g, spans), (g[1] - g[0]) / 1e9]
                      for g in gaps[:TOP]],
    }


def describe(pd, per_line: int = 6):
    for plane in pd.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({ev.name for ev in evs})
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{len(names)} names; first: "
                  + ", ".join(f"{ev.name}@{ev.start_ns:.0f}+"
                              f"{ev.duration_ns:.0f}"
                              for ev in evs[:per_line]))
            if len(names) <= 40:
                print(f"    names: {names}")


if __name__ == "__main__":
    describe(load(sys.argv[1]))
