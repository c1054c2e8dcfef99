"""Hotspots — on-demand CPU profiling behind the console.

Counterpart of /hotspots/cpu + /pprof (builtin/hotspots_service.h:38-68,
builtin/pprof_service.h:26-48): GET /hotspots/cpu?seconds=N runs a
statistical sampler over sys._current_frames() (all threads, the
whole-process view gperftools gives the reference) and returns collapsed
stacks ("frame;frame;frame count" lines — flamegraph.pl / speedscope
ingestible). The TPU-side profiler hook (XProf) plugs in the same handler
table (SURVEY.md section 5).
"""
from __future__ import annotations

import sys
import threading
import time
from collections import Counter
from typing import Dict


def sample_cpu(seconds: float = 1.0, hz: int = 99) -> str:
    """Collapsed-stack sample of every live thread."""
    seconds = max(0.1, min(10.0, seconds))
    interval = 1.0 / max(1, hz)
    stacks: Counter = Counter()
    deadline = time.monotonic() + seconds
    own = threading.get_ident()
    nsamples = 0
    while time.monotonic() < deadline:
        frames: Dict[int, object] = sys._current_frames()
        for tid, frame in frames.items():
            if tid == own:
                continue
            parts = []
            f = frame
            depth = 0
            while f is not None and depth < 64:
                code = f.f_code
                parts.append(f"{code.co_name} ({code.co_filename.rsplit('/', 1)[-1]}:{f.f_lineno})")
                f = f.f_back
                depth += 1
            if parts:
                stacks[";".join(reversed(parts))] += 1
        nsamples += 1
        time.sleep(interval)
    lines = [f"# cpu profile: {nsamples} samples at {hz}Hz over {seconds}s",
             "# format: collapsed stacks (flamegraph.pl compatible)"]
    for stack, count in stacks.most_common():
        lines.append(f"{stack} {count}")
    return "\n".join(lines) + "\n"


def thread_dump() -> str:
    """Instantaneous stacks of all threads (/threads page role)."""
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in frames.items():
        out.append(f"--- thread {names.get(tid, '?')} ({tid}) ---")
        f = frame
        depth = 0
        while f is not None and depth < 64:
            code = f.f_code
            out.append(f"  {code.co_filename}:{f.f_lineno} {code.co_name}")
            f = f.f_back
            depth += 1
    return "\n".join(out) + "\n"


class _ProfWindow:
    """One profiler window at a time: the sample window (nat_prof's
    SIGPROF aggregate, nat_mu_prof's contention aggregate) is a single
    shared resource — a concurrent request's stop/reset would wipe
    another window's samples mid-flight, so the SECOND request gets
    503 + Retry-After instead of a corrupted/blocking collision.
    Retry-After derives from the RUNNING window's remaining time (its
    monotonic deadline), not the rejected request's own seconds."""

    def __init__(self, clamp_max_s: float, busy_text: str):
        self._lock = threading.Lock()
        self._deadline = 0.0
        self._clamp_max_s = clamp_max_s
        self._busy_text = busy_text

    def run(self, seconds: float, sample_fn):
        if not self._lock.acquire(blocking=False):
            remaining = self._deadline - time.monotonic()
            retry_s = max(1, int(remaining) + 1)
            return (503, "text/plain", self._busy_text,
                    {"Retry-After": str(retry_s)})
        try:
            # mirror the sampler's own window clamp: the advertised
            # Retry-After must reflect the window that actually runs,
            # not a caller-supplied ?seconds=3600
            seconds = max(0.1, min(self._clamp_max_s, seconds))
            self._deadline = time.monotonic() + seconds
            return 200, "text/plain", sample_fn(seconds)
        finally:
            self._lock.release()


_native_prof_window = _ProfWindow(
    30.0, "nat_prof busy: another /hotspots/native window is running\n")
_contention_prof_window = _ProfWindow(
    10.0, "nat_mu_prof busy: another /hotspots/contention window is "
          "running\n")
# /heap/native and /growth/native share ONE window: both drain the same
# allocation-event rings and the growth baseline is shared state — a
# concurrent pair would race baseline/report (the /hotspots/* 503 +
# Retry-After discipline)
_res_prof_window = _ProfWindow(
    30.0, "nat_res busy: another /heap/native or /growth/native window "
          "is running\n")


def _res_ensure_armed():
    """Arm the native allocation-site tracker on first use (the
    tracemalloc ensure-on-first-profile discipline). Returns (native
    module or None, fresh: True when tracking JUST started)."""
    try:
        from brpc_tpu import native

        if not native.available():
            return None, False
    except Exception:
        return None, False
    if native.res_prof_running():
        return native, False
    # rc == -1: an embedder owns the profiler — report without stealing
    return native, native.res_prof_start(1, 42) == 0


def heap_native(seconds: float = 0.0, flat: bool = False) -> str:
    """/heap/native body: live bytes by native allocation site from the
    nat_res ledger's sampled profiler (the tcmalloc /heap role for the
    runtime's OWN allocators, which tracemalloc cannot see). ?seconds=N
    lets the armed tracker observe N seconds of churn before reporting.
    Caller must hold _res_prof_window."""
    native, fresh = _res_ensure_armed()
    if native is None:
        return "native runtime unavailable\n"
    if seconds > 0:
        time.sleep(min(seconds, 30.0))
    report = native.res_heap_report(collapsed=not flat)
    if fresh:
        report = ("# note: allocation-site tracking just started; pool "
                  "memory allocated earlier is in the nat_mem_* ledger "
                  "but not attributed to a site — rerun for steady "
                  "state\n") + report
    return report


def growth_native(seconds: float = 0.0) -> str:
    """/growth/native body: live-bytes-by-site growth since the
    baseline (taken at arming). ?seconds=N re-takes the baseline NOW
    and reports the growth of exactly that window — the leak-trend
    question ("what grew while I watched") answered directly. Caller
    must hold _res_prof_window."""
    native, fresh = _res_ensure_armed()
    if native is None:
        return "native runtime unavailable\n"
    if seconds > 0:
        native.res_growth_baseline()
        time.sleep(min(seconds, 30.0))
    report = native.res_growth_report()
    if fresh:
        report = ("# note: tracking just started; baseline taken now — "
                  "rerun (or pass ?seconds=N) to see growth\n") + report
    return report


def sample_native(seconds: float = 1.0, hz: int = 99,
                  collapsed: bool = True) -> str:
    """Native-runtime CPU profile via nat_prof (the in-process SIGPROF
    sampler, native/src/nat_prof.cpp): samples every thread actually
    burning CPU — fiber workers, dispatcher loops, py-lane pthreads —
    with frame-pointer unwind through the C++ core, where the Python
    sampler above only sees interpreter frames. Caller must hold
    _native_prof_window (hotspots_handler serializes windows there)."""
    try:
        from brpc_tpu import native

        if not native.available():
            return "native runtime unavailable\n"
    except Exception as e:
        return f"native runtime unavailable: {e}\n"
    seconds = max(0.1, min(30.0, seconds))
    rc = native.prof_start(hz)
    owns = rc == 0
    if rc == -2:
        return "nat_prof: could not install SIGPROF handler/timer\n"
    # rc == -1: a bench/embedder already runs the profiler — report
    # the window without stealing ownership of start/stop/reset
    time.sleep(seconds)
    if owns:
        native.prof_stop()
    report = native.prof_report(collapsed=collapsed)
    if owns:
        native.prof_reset()
    return report or "nat_prof: no samples (no native CPU burned?)\n"


def sample_contention(seconds: float = 1.0, hz: int = 99) -> str:
    """/hotspots/contention: the native NatMutex wait profile (nat_mu_prof
    — collapsed stacks weighted by wait-us, leaf = "lock:<rank name>")
    merged with the Python wait-frame sampler. The native sampler is
    armed for exactly the window the Python sampler spends sleeping, so
    both halves describe the same interval."""
    from brpc_tpu.builtin import profilers

    seconds = max(0.1, min(10.0, seconds))
    native_mod = None
    owns = False
    try:
        from brpc_tpu import native as native_mod  # type: ignore

        if native_mod.available():
            # sample every contended wait in the window (threshold 0);
            # a bench/embedder already holding the window (rc == -1)
            # keeps ownership — we still report it
            owns = native_mod.mu_prof_start(0, 1, 42) == 0
        else:
            native_mod = None
    except Exception:
        native_mod = None
    try:
        py_report = profilers.contention_profile(seconds, hz)
    except BaseException:
        # disarm the native sampler we armed: leaving g_mu_on set would
        # make every later window see rc == -1 and silently lose its
        # native contention report until restart
        if native_mod is not None and owns:
            try:
                native_mod.mu_prof_stop()
                native_mod.mu_prof_reset_samples()
            except Exception:
                pass
        raise
    parts = []
    if native_mod is not None:
        try:
            if owns:
                native_mod.mu_prof_stop()
            ranks = native_mod.mu_rank_stats()
            parts.append("# native lock contention (nat_mu_prof: "
                         "contended NatMutex waits, wait-us weighted)")
            parts.append(native_mod.mu_prof_report(collapsed=True).rstrip())
            if ranks:
                parts.append("# per-rank wait totals since start/reset:")
                for r in sorted(ranks, key=lambda r: -r["wait_us"]):
                    parts.append(
                        f"#   rank {r['rank']:>3d} {r['name']:<14s} "
                        f"waits={r['waits']} wait_us={r['wait_us']}")
            if owns:
                # samples only: the per-rank totals ride /brpc_metrics
                # as counters and must survive debug-page requests
                native_mod.mu_prof_reset_samples()
        except Exception as e:
            parts.append(f"# native contention profiler failed: {e}")
    parts.append("# python wait-frame profile")
    parts.append(py_report.rstrip())
    return "\n".join(parts) + "\n"


def hotspots_handler(server, req):
    """/hotspots/{cpu,native,heap,growth,contention,tpu} — the full
    profiler surface of hotspots_service.h:38-68 (+ the XProf TPU
    translation and the nat_prof native sampler)."""
    from brpc_tpu.builtin import profilers

    parts = [p for p in req.path.split("/") if p]
    kind = parts[1] if len(parts) > 1 else "cpu"
    seconds = float(req.query.get("seconds", "1") or 1)
    if kind == "cpu":
        return 200, "text/plain", sample_cpu(seconds)
    if kind == "native":
        collapsed = req.query.get("flat", "") in ("", "0")
        # 503 + Retry-After on collision (regression: ISSUE 9 satellite)
        return _native_prof_window.run(
            seconds, lambda s: sample_native(s, collapsed=collapsed))
    if kind == "heap":
        return 200, "text/plain", profilers.heap_profile()
    if kind == "growth":
        return 200, "text/plain", profilers.growth_profile()
    if kind == "contention":
        return _contention_prof_window.run(seconds, sample_contention)
    if kind == "tpu":
        ctype, body = profilers.tpu_trace(seconds)
        return 200, ctype, body
    return 404, "text/plain", f"unknown hotspots kind {kind}\n"


def pprof_handler(server, req):
    """/pprof/{profile,heap,growth,symbol} — pprof_service.h:26-48 slots."""
    from brpc_tpu.builtin import profilers

    parts = [p for p in req.path.split("/") if p]
    kind = parts[1] if len(parts) > 1 else "profile"
    if kind == "profile":
        seconds = float(req.query.get("seconds", "1") or 1)
        return 200, "text/plain", sample_cpu(seconds)
    if kind == "heap":
        return 200, "text/plain", profilers.heap_profile()
    if kind == "growth":
        return 200, "text/plain", profilers.growth_profile()
    if kind == "contention":
        seconds = float(req.query.get("seconds", "1") or 1)
        return 200, "text/plain", profilers.contention_profile(seconds)
    if kind == "symbol":
        return 200, "text/plain", "python frames are pre-symbolized\n"
    return 404, "text/plain", f"unknown pprof endpoint {kind}\n"


def threads_handler(server, req):
    return 200, "text/plain", thread_dump()
