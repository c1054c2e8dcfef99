"""Heap / growth / contention / TPU profilers behind /hotspots.

Counterpart of the reference's profiler suite
(/root/reference/src/brpc/builtin/hotspots_service.h:38-68: CPU, HEAP,
GROWTH, CONTENTION via gperftools/tcmalloc hooks) translated to this
runtime:

- heap      -> tracemalloc snapshot, allocations by stack (collapsed)
- growth    -> tracemalloc diff against the first snapshot taken since
               profiling started (tcmalloc's cumulative-growth view)
- contention-> statistical sampler keeping only stacks blocked in lock /
               condition waits (the reference hooks its own mutexes;
               sampling the wait frames gives the same "who waits where"
               answer without patching every lock)
- tpu       -> jax.profiler trace (XProf) zipped for TensorBoard — the
               SURVEY §5 TPU translation of the pprof endpoints
"""
from __future__ import annotations

import io
import os
import sys
import threading
import time
import tracemalloc
import zipfile
from collections import Counter

_growth_baseline = None
_baseline_lock = threading.Lock()


def _ensure_tracemalloc(frames: int = 16) -> bool:
    """Start tracemalloc on first profile request. Returns False if it
    JUST started (no data yet)."""
    global _growth_baseline
    if tracemalloc.is_tracing():
        # Tracing was begun externally (PYTHONTRACEMALLOC / user code):
        # adopt the current state as the growth baseline.
        with _baseline_lock:
            if _growth_baseline is None:
                _growth_baseline = tracemalloc.take_snapshot()
        return True
    tracemalloc.start(frames)
    with _baseline_lock:
        _growth_baseline = tracemalloc.take_snapshot()
    return False


def _collapse(stat) -> str:
    parts = []
    for frame in reversed(stat.traceback):
        parts.append(f"{os.path.basename(frame.filename)}:{frame.lineno}")
    return ";".join(parts) if parts else "<unknown>"


def heap_profile(top: int = 64) -> str:
    """Live allocations by stack, collapsed format, byte counts."""
    fresh = not _ensure_tracemalloc()
    snap = tracemalloc.take_snapshot()
    stats = snap.statistics("traceback")
    total = sum(s.size for s in stats)
    lines = [
        f"# heap profile: {len(stats)} allocation sites, "
        f"{total} bytes live (tracemalloc)",
        "# format: collapsed stacks, value = live bytes",
    ]
    if fresh:
        lines.append("# note: tracing just started; rerun for steady state")
    for s in stats[:top]:
        lines.append(f"{_collapse(s)} {s.size}")
    return "\n".join(lines) + "\n"


def growth_profile(top: int = 64) -> str:
    """Allocation growth since profiling began (tcmalloc HEAP_GROWTH)."""
    fresh = not _ensure_tracemalloc()
    snap = tracemalloc.take_snapshot()
    with _baseline_lock:
        baseline = _growth_baseline
    lines = ["# growth profile: bytes allocated since profiling start",
             "# format: collapsed stacks, value = grown bytes"]
    if fresh or baseline is None:
        lines.append("# note: baseline just taken; rerun to see growth")
        return "\n".join(lines) + "\n"
    diffs = snap.compare_to(baseline, "traceback")
    grown = [d for d in diffs if d.size_diff > 0]
    grown.sort(key=lambda d: d.size_diff, reverse=True)
    lines.insert(1, f"# {len(grown)} growing sites, "
                    f"{sum(d.size_diff for d in grown)} bytes total")
    for d in grown[:top]:
        lines.append(f"{_collapse(d)} {d.size_diff}")
    return "\n".join(lines) + "\n"


_WAIT_LEAVES = ("wait", "acquire", "_wait_for_tstate_lock", "wait_for",
                "futex_wait", "join")
_WAIT_FILES = ("threading.py", "butex.py", "parking_lot.py",
               "execution_queue.py", "id.py")


def contention_profile(seconds: float = 1.0, hz: int = 99) -> str:
    """Stacks observed blocked in lock/condition waits
    (contention_profiler.md's question answered by sampling)."""
    seconds = max(0.1, min(10.0, seconds))
    interval = 1.0 / max(1, hz)
    stacks: Counter = Counter()
    own = threading.get_ident()
    deadline = time.monotonic() + seconds
    nsamples = 0
    while time.monotonic() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == own or frame is None:
                continue
            leaf = frame.f_code
            fname = os.path.basename(leaf.co_filename)
            if not (leaf.co_name.startswith(_WAIT_LEAVES)
                    or leaf.co_name in _WAIT_LEAVES) or \
                    fname not in _WAIT_FILES:
                continue
            parts = []
            f = frame
            depth = 0
            while f is not None and depth < 64:
                code = f.f_code
                parts.append(
                    f"{code.co_name} "
                    f"({os.path.basename(code.co_filename)}:{f.f_lineno})")
                f = f.f_back
                depth += 1
            stacks[";".join(reversed(parts))] += 1
        nsamples += 1
        time.sleep(interval)
    lines = [
        f"# contention profile: {nsamples} samples at {hz}Hz over "
        f"{seconds}s; stacks blocked in lock/cond waits",
        "# format: collapsed stacks, value = samples observed waiting",
    ]
    for stack, count in stacks.most_common():
        lines.append(f"{stack} {count}")
    if len(lines) == 2:
        lines.append("# no contention observed")
    return "\n".join(lines) + "\n"


def tpu_trace(seconds: float = 1.0):
    """XProf/libtpu trace via jax.profiler; returns (content_type, body).
    Loading the zip into TensorBoard's profile viewer gives the device
    timeline — the TPU-idiomatic /hotspots backend (SURVEY §5)."""
    seconds = max(0.1, min(30.0, seconds))
    import tempfile

    if (os.cpu_count() or 1) < 2 and not os.environ.get(
            "BRPC_TPU_FORCE_TPU_TRACE"):
        # Trace collection is not bounded by `seconds`: profiler start/stop
        # does several seconds of native work that monopolises the only
        # core, starving every other handler on the server (observed as
        # cascading 60s timeouts on 1-cpu CI). Explain instead of hanging;
        # BRPC_TPU_FORCE_TPU_TRACE=1 overrides when the stall is acceptable.
        return ("text/plain",
                "profiler trace skipped: single-cpu host (trace collection "
                "would starve the server; set BRPC_TPU_FORCE_TPU_TRACE=1 "
                "to force)\n")
    try:
        import jax
    except Exception as e:  # pragma: no cover - jax is baked in
        return "text/plain", f"jax unavailable: {e}\n"
    with tempfile.TemporaryDirectory(prefix="xprof_") as tmp:
        try:
            with jax.profiler.trace(tmp):
                # idle-wait: RPC traffic and device work during the window
                # get captured by the profiler's own hooks
                time.sleep(seconds)
        except Exception as e:
            return "text/plain", f"profiler trace failed: {e}\n"
        buf = io.BytesIO()
        nfiles = 0
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
            for root, _dirs, files in os.walk(tmp):
                for name in files:
                    full = os.path.join(root, name)
                    zf.write(full, os.path.relpath(full, tmp))
                    nfiles += 1
        if nfiles == 0:
            return "text/plain", "profiler produced no trace files\n"
        return "application/zip", buf.getvalue()
