#!/usr/bin/env python3
"""Runs one benchmark cell once and prints its result as one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from BENCHMARK.json: the cell's configuration
(`benchmark/configs/<config>.{json,py}`), its traffic
(`benchmark/traffic/<cell>.json`, whose `kind` names a generator in
`benchmark/traffic/kinds/`) and one reader per metric
(`benchmark/metrics/<metric>.py`). With --trace 0 the line carries the
cell's end-to-end metrics; with --trace 1 its per-layer metrics, read from
a profiler trace of the window and from the program's counters.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, ROOT)

import lib  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# JAX's persistent compile cache, at a fixed path inside the checkout: the
# path is part of the cache's key, so only a checkout's first run compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
# no eviction: it reads a side file per entry that a concurrent writer may
# not have made yet, and then the write fails
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
# a run that has not ended by then is stopped, its clients with it
RUN_LIMIT_S = 330


class CellError(RuntimeError):
    """A cell that cannot run as specified (missing chip, file, metric)."""


class Window:
    """What a configuration is handed: the cell's specification and the
    hooks that open and close the measured window. What it records goes
    into `data`, which the metric readers read."""

    def __init__(self, cell, config, traffic, kind, seed, seconds, trace,
                 devices):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.kind = kind
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.checks = []  # (name, value, limit)
        self.data = {}
        self._annotation = None
        self._compiles = 0
        self._counting = False

    clock = staticmethod(time.monotonic)

    def annotate(self, name: str):
        """A span in the profiler's trace (a no-op when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def begin(self, lead_s: float = 0.3) -> float:
        """Closes set-up and returns the window's start, `lead_s` from now
        (clients are told it; the trace is running by then)."""
        self.data["setup_s"] = time.monotonic() - T_START
        if self.trace:
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation("window")
        t0 = time.monotonic() + lead_s
        self.data["t0"] = t0
        self._counting = True
        if self._annotation is not None:
            time.sleep(max(0.0, t0 - time.monotonic()))
            self._annotation.__enter__()
        return t0

    def end(self, records):
        """Closes the window over its request records (dicts with due,
        sent, done, ok, nbytes)."""
        self._counting = False
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            import jax

            jax.profiler.stop_trace()
        t0 = self.data["t0"]
        self.data["records"] = records
        self.data["t_end"] = max([t0 + self.seconds] +
                                 [r["done"] for r in records])
        self.data["compiles_in_window"] = self._compiles

    def on_compile(self, event: str, *args, **kwargs):
        if self._counting and "backend_compile" in event:
            self._compiles += 1

    def check(self, name: str, value: float, limit: float):
        """One number compared with the plain reference, beside its limit:
        the run is correct only if every value is at most its limit."""
        self.checks.append((name, float(value), float(limit)))


def cell_spec(spec: dict, name: str):
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise CellError(f"no workload named {name!r} in BENCHMARK.json")


def metrics_of(spec: dict, cell: str, kind: str):
    """The metrics a cell reports: `end_to_end` or `per_layer` entries that
    list it, or that list no cells."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def check_devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise CellError(f"no TPU: JAX found {devs[0].platform} devices "
                        f"{devs}")
    if len(devs) < chips:
        raise CellError(f"the cell needs {chips} chips, JAX found "
                        f"{len(devs)}: {devs}")
    return devs[:chips]


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, traffic_override=None):
    """Runs one cell; returns the result dict and the window's data. Tests
    pass require_tpu=False and smaller sizes through the override."""
    spec = lib.benchmark_spec()
    cell = cell_spec(spec, name)
    config = lib.load_json(os.path.join(
        BENCH_DIR, "configs", cell["config"] + ".json"))
    traffic = lib.load_json(os.path.join(
        BENCH_DIR, "traffic", cell["name"] + ".json"))
    traffic.update(traffic_override or {})
    kind = lib.load_module(os.path.join(
        BENCH_DIR, "traffic", "kinds", traffic["kind"] + ".py"),
        "kind_" + traffic["kind"])
    for key, setting in config.get("env", {}).items():
        os.environ[key] = str(setting["value"])

    import jax

    devices = check_devices(int(cell["chips"]), require_tpu)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from brpc_tpu import compile_cache

    compile_cache.enable()
    win = Window(cell, config, traffic, kind, seed, seconds, trace, devices)
    jax.monitoring.register_event_duration_secs_listener(win.on_compile)
    mod = lib.load_module(os.path.join(
        BENCH_DIR, "configs", cell["config"] + ".py"),
        "config_" + cell["config"])
    mod.run(win)

    if trace:
        import host_spans
        import trace_reduce

        win.data["trace"] = win.data["host_spans"] = None
        if devices[0].platform == "tpu":
            pd = trace_reduce.load(TRACE_DIR)
            win.data["trace"] = trace_reduce.reduce(
                pd, [d.id for d in devices])
            win.data["host_spans"] = host_spans.reduce(pd)
    table = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(spec, name, table):
        reader = lib.load_module(os.path.join(
            BENCH_DIR, "metrics", m["name"] + ".py"), "metric_" + m["name"])
        value = reader.read(win.data)
        if value is None:
            if table == "end_to_end":
                raise CellError(f"{m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": win.data.get("memory_peak_bytes", 0)}
    result = {"correct": all(v <= lim for _, v, lim in win.checks)
              and bool(win.checks),
              "attempted": len(win.data["records"]),
              "failed": sum(1 for r in win.data["records"] if not r["ok"]),
              "metrics": metrics, "device": device}
    tr = win.data.get("trace")
    if tr:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["top_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["compiles_in_window"] = win.data["compiles_in_window"]
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in win.checks}
    return result, win.data


def report(result: dict, records=()):
    errors = {}
    for r in records:
        if not r["ok"]:
            errors[r["error"]] = errors.get(r["error"], 0) + 1
    for e, n in sorted(errors.items(), key=lambda kv: -kv[1])[:5]:
        sys.stderr.write(f"failed x{n}: {e}\n")
    sys.stderr.write("compiles in the window: "
                     f"{result['compiles_in_window']}\n")
    for n, c in result["checks"].items():
        sys.stderr.write(f"check {n}: {c['value']!r} (limit {c['limit']!r})"
                         "\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def overrun(*_):
        raise CellError(f"the run did not end within {RUN_LIMIT_S} s")

    signal.signal(signal.SIGALRM, overrun)
    signal.alarm(RUN_LIMIT_S)
    try:
        result, data = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace))
    except CellError as e:
        sys.stderr.write(f"benchmark: {e}\n")
        return 2
    report(result, data["records"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
