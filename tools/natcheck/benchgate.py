"""benchgate — the standing perf regression gate (tools/check.sh --bench).

The flight recorder's third leg (ROADMAP item 5): every gated run
executes ``bench.py`` with the native profiler attached
(BRPC_TPU_BENCH_PROF=1), writes a schema'd artifact — headline lane
values, git sha, the nat_prof flat profile of the loopback lanes, and
the rpcz/native-histogram latency percentiles — then diffs the headline
lanes against the LAST COMMITTED ``BENCH_r*.json`` baseline with
per-lane tolerance bands. A regression beyond a lane's band hard-fails
the gate, and the failure report carries the current run's profile so
the regression arrives with its own flame data attached (the un-blinding
the multicore/fan-out refactors of ROADMAP items 1-2 need).

Tolerance bands: the default band is 15% (the hard-fail contract).
Lanes with measured round-over-round noise on the 1-CPU dev host carry
wider bands (Python-usercode lanes bounce with GIL scheduling; the
worker lane doubled between r04 and r05 from boot-timing alone) — a
wider band is a documented noise floor, not a licence to regress.

Baseline discipline: a COMMITTED ``BENCH_r*.json`` baseline records,
per lane, the MINIMUM over several clean rounds on the recording host —
the credible floor, not one sample. Shared-container scheduling moves
single-run lane values by tens of percent in both directions (r06
measured ±50% between identical back-to-back runs); banding against the
floor keeps the gate quiet on that noise while a real regression (a
code change that halves a lane) still lands far below floor - band.
``make_baseline(artifacts)`` composes the floor from N gated runs.

``compare(baseline, current)`` is a pure function over two artifact
dicts so the golden tests (tests/test_bench_gate.py) can exercise the
clean / one-lane-regressed / missing-lane / schema-drift verdicts
without running a single benchmark.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
from typing import Dict, List, Optional

from tools.natcheck import Finding, REPO_ROOT

SCHEMA = "brpc_tpu-bench-artifact/2"
# /2 only ADDS the extra.contention block (top lock-wait stacks of the
# loopback window) — artifacts of the previous generation stay fully
# comparable, so committed /1 baselines (BENCH_r07) keep gating until a
# fresh round is recorded.
SCHEMA_COMPAT = {"brpc_tpu-bench-artifact/1", SCHEMA}

# artifact written by every gated run (gitignored; the committed
# baseline is the newest BENCH_r*.json carrying the schema field)
DEFAULT_OUT = os.path.join(REPO_ROOT, "BENCH_latest.json")

# headline lane -> relative tolerance band (fraction of the baseline
# value the current run may fall short by before the gate fails)
DEFAULT_TOL = 0.15
HEADLINE_LANES: Dict[str, float] = {
    # native data-path lanes: stable round over round — the 15% contract
    "value": DEFAULT_TOL,               # the headline echo qps
    "epoll_qps": DEFAULT_TOL,
    "async_windowed_qps": DEFAULT_TOL,
    "http_qps": DEFAULT_TOL,
    "grpc_qps": DEFAULT_TOL,
    "redis_qps": DEFAULT_TOL,
    "grpc_client_qps": DEFAULT_TOL,
    "http_client_qps": DEFAULT_TOL,
    # io_uring availability depends on the kernel; when present it is
    # stable, and a 0 baseline (ring refused) skips the row entirely
    "io_uring_qps": DEFAULT_TOL,
    "io_uring_async_qps": DEFAULT_TOL,
    # flight-recorder replay of the committed golden capture (press
    # mode): native data path, but the short window includes capture
    # parse + thread ramp — banded wider until committed rounds prove
    # it as stable as the long-window lanes
    "replay_qps": 0.30,
    # Python-usercode lanes: GIL scheduling noise on the 1-CPU host
    "http_py_qps": 0.30,
    "grpc_py_qps": 0.30,
    "redis_py_qps": 0.30,
    # worker processes add boot/attach timing on top (r04->r05: 2x swing)
    "http_py_workers_qps": 0.50,
    # bulk/transport lanes: dominated by host memcpy bandwidth
    "stream_GBps": 0.30,
    "native_bulk_GBps": 0.30,
    "shm_desc_GBps": 0.30,
    "shm_desc_small_GBps": 0.50,
    # tensor-fabric RPC push lane (ISSUE 15): the full device-channel
    # path (kind-8 arena write -> descriptor RPC -> lease consume); a
    # Python RPC stack drives it, so the band is the py-lane class.
    # read_arena_grow_GBps reports 0 when the grow path reintroduces
    # the first-touch fault cliff, tripping the band like a collapse.
    "shm_push_GBps": 0.50,
    "read_arena_grow_GBps": 0.50,
    # multicore scaling efficiency (bench.py --cpus N): qps(2cpus) /
    # qps(1cpu) from the pinned two-process lane. On the shared dev
    # container the HOST's own parallel capacity swings 1.3-2.2x run
    # over run (extra.scaling.host_parallel_x records it), so the band
    # is wide; the absolute sublinear check below is the hard floor.
    "cpus2_scaling_x": 0.35,
    # native fan-out lanes (ISSUE 13): the parallel verb to 32 / 1000
    # backends and the swarm churn drill's selective flood. Each lane
    # reports 0 qps when ANY RPC failed (the zero-failed contract), so
    # a failing drill trips the band like a throughput collapse. The
    # Python-comparison lane bounces with GIL scheduling (wide band).
    "fanout_qps": 0.30,
    "fanout1000_qps": 0.50,
    "swarm_qps": 0.30,
    "fanout_py_qps": 0.50,
    # connection-scale drill (ISSUE 14): connections held idle with the
    # live subset at zero failures — the lane reports 0 when ANY RPC
    # failed, the storm left connections unanswered, or a transient
    # subsystem leaked after teardown, so a failing drill trips the
    # band like a throughput collapse
    "conn_scale_conns": DEFAULT_TOL,
    # elastic-capacity drill (ISSUE 20): the autoscaler resizing a
    # dynpart swarm under the replayed golden-capture ramp with a
    # mid-resize SIGKILL. The lane reports the replay qps only when the
    # WHOLE contract held (zero failed RPCs through grows/shrinks/the
    # crash, p99 under the ceiling, capacity tracking offered load), so
    # any contract breach trips the band as a collapse to 0. Ramp-mode
    # replay qps itself carries the replay-lane noise class.
    "autoscale_qps": 0.50,
}

# Latency CEILING lanes: these regress UPWARD — the gate fails when the
# current value exceeds baseline * (1 + band). Composed from the same
# artifacts; extract_lanes carries them beside the throughput lanes.
CEILING_LANES: Dict[str, float] = {
    "fanout_p99_us": 0.50,
    "swarm_p99_us": 0.50,
    # autoscale drill probe p99 (ISSUE 20): paced dynpart probes riding
    # through live resizes — latency regressing upward here means a
    # resize became caller-visible
    "autoscale_p99_us": 0.50,
    # memory-observatory ceilings (ISSUE 14): per-connection accounted
    # bytes (a regression here is a memory-cost regression even when
    # qps holds) and the accept-storm recovery time. Both noisy on the
    # shared container — wide bands; make_baseline takes the MAX.
    "conn_per_conn_bytes": 0.50,
    "conn_accept_storm_s": 1.00,
}

# ABSOLUTE ceiling lanes: gated against a fixed bar, not a baseline —
# the fleet-observatory contract (ISSUE 16) is that a 1Hz builtin.stats
# scrape costs <= 3% of headline qps on ANY host, so no committed
# baseline can relax it. Carried in artifacts/baselines like the
# relative ceilings (make_baseline takes the MAX over clean rounds).
ABS_CEILING_LANES: Dict[str, float] = {
    "fleet_scrape_overhead_pct": 3.0,
}

# Hard sublinear-scaling floor: when the host probe shows real parallel
# headroom (host_parallel_x >= the MIN_HOST bar) and the runtime still
# scales below MIN_X, that is a failing finding regardless of baseline —
# a shared-state bottleneck reintroduced into the write/dispatch path,
# exactly what ROADMAP item 1 forbids. On an overcommitted host (probe
# below the bar) the check is moot: nothing can scale there.
SCALING_ABS_MIN_X = 1.15
SCALING_MIN_HOST_X = 1.6


def extract_lanes(bench: dict) -> Dict[str, float]:
    """Headline lane values out of one bench.py result dict (transport
    lanes live nested under extra.device_lanes; the scaling ratio is
    derived from the extra.scaling curve)."""
    lanes: Dict[str, float] = {}
    extra = bench.get("extra", {}) or {}
    device = extra.get("device_lanes", {}) or {}
    for key in (list(HEADLINE_LANES) + list(CEILING_LANES)
                + list(ABS_CEILING_LANES)):
        if key == "value":
            v = bench.get("value")
        elif key == "cpus2_scaling_x":
            scaling = extra.get("scaling", {}) or {}
            q1, q2 = scaling.get("1"), scaling.get("2")
            v = round(float(q2) / float(q1), 3) \
                if isinstance(q1, (int, float)) and \
                isinstance(q2, (int, float)) and q1 > 0 else None
        else:
            v = extra.get(key, device.get(key))
        if isinstance(v, (int, float)):
            lanes[key] = float(v)
    return lanes


def make_artifact(bench: dict, round_n: int, rc: int = 0,
                  git_sha: str = "") -> dict:
    """Wrap one bench.py result into the schema'd artifact of record."""
    extra = bench.get("extra", {}) or {}
    return {
        "schema": SCHEMA,
        "n": round_n,
        "rc": rc,
        "git_sha": git_sha or _git_sha(),
        "generated_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime()),
        "lanes": extract_lanes(bench),
        "scaling": extra.get("scaling", {}),
        "rpcz_percentiles": extra.get("native_latency_us", {}),
        "nat_prof": extra.get("nat_prof", {}),
        "contention": extra.get("contention", {}),
        "bench": bench,
    }


def make_baseline(artifacts: List[dict], round_n: int) -> dict:
    """Compose a committable baseline from N clean gated runs: the
    newest run's record (bench/profile/percentiles) with each headline
    lane replaced by its MINIMUM over the runs (the host's credible
    floor — see the module docstring)."""
    clean = [a for a in artifacts if a.get("rc", 0) == 0]
    if not clean:
        raise ValueError("no clean (rc=0) artifacts to compose")
    base = dict(clean[-1])
    floor: Dict[str, float] = {}
    for art in clean:
        for lane, v in (art.get("lanes") or {}).items():
            if lane.endswith("_scaling_x"):
                # scaling ratios record the best ACHIEVED ratio (a
                # crushed shared-host round would otherwise bake an
                # unachievably-low scaling bar into the baseline)
                if lane not in floor or float(v) > floor[lane]:
                    floor[lane] = float(v)
            elif lane in CEILING_LANES or lane in ABS_CEILING_LANES:
                # latency ceilings take the MAXIMUM over clean rounds —
                # the credible worst case plays the floor's role for a
                # lane that regresses upward
                if lane not in floor or float(v) > floor[lane]:
                    floor[lane] = float(v)
            elif lane not in floor or float(v) < floor[lane]:
                floor[lane] = float(v)
    base["lanes"] = floor
    base["n"] = round_n
    base["baseline_runs"] = len(clean)
    return base


def _git_sha() -> str:
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=REPO_ROOT,
                             capture_output=True, text=True, timeout=30)
        return out.stdout.strip() if out.returncode == 0 else ""
    except OSError:
        return ""


def find_baseline(repo_root: str = REPO_ROOT) -> Optional[str]:
    """Newest committed BENCH_r*.json that speaks the artifact schema."""
    best_n, best = -1, None
    for name in os.listdir(repo_root):
        m = re.match(r"BENCH_r(\d+)\.json$", name)
        if not m:
            continue
        path = os.path.join(repo_root, name)
        try:
            with open(path, "r", encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if doc.get("schema") not in SCHEMA_COMPAT:
            continue  # pre-gate rounds (r01..r05) have no lane schema
        if int(m.group(1)) > best_n:
            best_n, best = int(m.group(1)), path
    return best


def _host_cpus(artifact: dict) -> int:
    """CPUs the recording host actually had (bench.py records
    extra.host_cpus); 0 when the artifact predates the field."""
    v = ((artifact.get("bench") or {}).get("extra") or {}).get("host_cpus")
    return v if isinstance(v, int) else 0


def _profile_excerpt(current: dict, lines: int = 12) -> str:
    flat = (current.get("nat_prof") or {}).get("flat") or []
    if not flat:
        return " (no profile attached: run with BRPC_TPU_BENCH_PROF=1)"
    return "; profile of the regressing run:\n      " + "\n      ".join(
        flat[:lines])


def _contention_excerpt(current: dict, lines: int = 6) -> str:
    """Top lock-wait stacks of the regressing run (extra.contention) —
    a lane that slowed down because a lock crept back into the
    write/dispatch path names itself here."""
    collapsed = (current.get("contention") or {}).get("collapsed") or []
    if not collapsed:
        return ""
    return "; top lock-wait stacks:\n      " + "\n      ".join(
        collapsed[:lines])


def compare(baseline: dict, current: dict) -> List[Finding]:
    """Diff two artifacts' headline lanes. Pure function (golden-tested:
    clean / one-lane-regressed / missing-lane / schema-drift)."""
    findings: List[Finding] = []
    where = "tools/check.sh --bench"
    # either side may speak any compatible generation — the bump (/2)
    # only ADDS the contention block, so committed /1 rounds (BENCH_r07)
    # keep gating and re-diffing old artifacts keeps working
    for doc, label in ((baseline, "baseline"), (current, "current")):
        if doc.get("schema") not in SCHEMA_COMPAT:
            findings.append(Finding(
                "bench", "schema-drift", where,
                f"{label} artifact schema is "
                f"{doc.get('schema')!r}, expected {SCHEMA!r} — regenerate "
                f"it with the gate (artifacts of a different schema are "
                f"not comparable)"))
    if findings:
        return findings
    if current.get("rc", 0) != 0:
        findings.append(Finding(
            "bench", "bench-failed", where,
            f"bench.py exited rc={current.get('rc')} — the artifact of "
            f"record is untrustworthy (the BENCH_r05 rc-139 class)"))
        return findings
    base_lanes = baseline.get("lanes", {})
    cur_lanes = current.get("lanes", {})
    for lane, tol in HEADLINE_LANES.items():
        if lane not in base_lanes:
            continue  # lane did not exist at baseline time: nothing to hold
        base_v = float(base_lanes[lane])
        if base_v <= 0:
            continue  # unmeasurable at baseline (e.g. io_uring refused)
        if lane not in cur_lanes:
            if lane == "cpus2_scaling_x" and _host_cpus(current) < 2:
                # a 1-cpu host cannot measure a 2-cpu scaling ratio:
                # unmeasurable on this container, not silently dropped
                # (the io_uring-refused 0-baseline case's twin on the
                # current side)
                continue
            findings.append(Finding(
                "bench", "missing-lane", where,
                f"lane {lane!r} present in the baseline "
                f"({base_v:.1f}) but missing from the current run — a "
                f"silently-dropped lane is a regression, not a skip"
                + _contention_excerpt(current) + _profile_excerpt(current)))
            continue
        cur_v = float(cur_lanes[lane])
        floor = base_v * (1.0 - tol)
        if cur_v < floor:
            drop = 100.0 * (1.0 - cur_v / base_v)
            findings.append(Finding(
                "bench", "regression", where,
                f"lane {lane!r} regressed {drop:.1f}%: {base_v:.1f} -> "
                f"{cur_v:.1f} (tolerance band {tol * 100:.0f}%)"
                + _contention_excerpt(current) + _profile_excerpt(current)))
    # latency ceiling lanes regress UPWARD: current above the committed
    # worst case + band is a tail regression even when qps held
    for lane, tol in CEILING_LANES.items():
        if lane not in base_lanes:
            continue
        base_v = float(base_lanes[lane])
        if base_v <= 0 or lane not in cur_lanes:
            continue  # unmeasured either side (a failing drill already
            # trips through its 0-qps twin lane)
        cur_v = float(cur_lanes[lane])
        ceiling = base_v * (1.0 + tol)
        if cur_v > ceiling:
            rise = 100.0 * (cur_v / base_v - 1.0)
            findings.append(Finding(
                "bench", "regression", where,
                f"latency lane {lane!r} regressed {rise:.1f}% upward: "
                f"{base_v:.1f} -> {cur_v:.1f} us (ceiling band "
                f"{tol * 100:.0f}%)"
                + _contention_excerpt(current) + _profile_excerpt(current)))
    # absolute ceiling lanes: a fixed bar, independent of any baseline
    # (the fleet 1Hz-scrape <=3% contract); missing lane = unmeasured =
    # skip (the bench may run with the fleet lane disabled)
    for lane, bar in ABS_CEILING_LANES.items():
        cur_v = cur_lanes.get(lane)
        if isinstance(cur_v, (int, float)) and float(cur_v) > bar:
            findings.append(Finding(
                "bench", "abs-ceiling", where,
                f"lane {lane!r} measured {float(cur_v):.2f}, above the "
                f"absolute bar {bar:.2f} — the always-on fleet scrape "
                f"contract (ISSUE 16) does not bend with baselines"
                + _contention_excerpt(current) + _profile_excerpt(current)))
    # absolute sublinear-scaling floor (independent of any baseline):
    # the host probe proved parallel headroom, the runtime didn't use it
    scaling_x = cur_lanes.get("cpus2_scaling_x")
    host_x = (current.get("scaling") or {}).get("host_parallel_x")
    if isinstance(scaling_x, (int, float)) and \
            isinstance(host_x, (int, float)) and \
            host_x >= SCALING_MIN_HOST_X and scaling_x < SCALING_ABS_MIN_X:
        disp = (current.get("scaling") or {}).get("disp_stats", {})
        disp_note = ""
        if disp:
            # dispatcher-balance evidence: the per-loop wakeup split at
            # each measured point says whether the loops shared the load
            disp_note = "; per-dispatcher rows: " + "; ".join(
                f"{pt}cpus={rows}" for pt, rows in sorted(disp.items()))
        findings.append(Finding(
            "bench", "sublinear-scaling", where,
            f"2-cpu scaling is {scaling_x:.2f}x while the host's own "
            f"parallel capacity probe measured {host_x:.2f}x — the "
            f"runtime left real cores idle (shared-state bottleneck in "
            f"the write/dispatch path?)" + disp_note
            + _contention_excerpt(current) + _profile_excerpt(current)))
    return findings


def run_bench(timeout_s: int = 2400) -> dict:
    """Execute bench.py with the profiler attached; returns the current
    artifact (rc recorded; the last stdout line is the result JSON)."""
    env = dict(os.environ)
    env["BRPC_TPU_BENCH_PROF"] = "1"
    # scaling curve up to 2 cpus (or however many the host has): the
    # cpus2_scaling_x lane + sublinear check need the {1,2} points
    ncpus = min(2, len(os.sched_getaffinity(0)))
    try:
        proc = subprocess.run(
            [sys.executable, "bench.py", "--cpus", str(ncpus)],
            cwd=REPO_ROOT, capture_output=True, text=True, env=env,
            timeout=timeout_s)
    except subprocess.TimeoutExpired:
        # a wedged bench is the failure class the gate exists to catch:
        # report it through the bench-failed contract, not a traceback
        # (rc mirrors subprocess's killed-by-SIGKILL convention)
        return make_artifact({}, round_n=0, rc=-9)
    bench: dict = {}
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                bench = json.loads(line)
                break
            except ValueError:
                continue
    return make_artifact(bench, round_n=0, rc=proc.returncode)


def run(out_path: str = "") -> List[Finding]:
    """The gate: bench -> artifact -> diff vs the committed baseline."""
    out_path = out_path or os.environ.get("BENCH_GATE_OUT", DEFAULT_OUT)
    baseline_path = find_baseline()
    current = run_bench()
    try:
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(current, f, indent=1, sort_keys=False)
            f.write("\n")
        print(f"benchgate: artifact written to "
              f"{os.path.relpath(out_path, REPO_ROOT)}")
    except OSError as e:
        print(f"benchgate: could not write artifact: {e}", file=sys.stderr)
    if baseline_path is None:
        # first gated round: nothing schema-comparable committed yet —
        # a failed bench still fails, a clean one records the artifact
        if current.get("rc", 0) != 0:
            return [Finding(
                "bench", "bench-failed", "tools/check.sh --bench",
                f"bench.py exited rc={current.get('rc')} (and no "
                f"schema'd BENCH_r*.json baseline exists yet)")]
        print("benchgate: no schema'd BENCH_r*.json baseline committed "
              "yet — artifact recorded, nothing to diff")
        return []
    with open(baseline_path, "r", encoding="utf-8") as f:
        baseline = json.load(f)
    print(f"benchgate: baseline "
          f"{os.path.relpath(baseline_path, REPO_ROOT)} "
          f"(round {baseline.get('n')}, sha "
          f"{str(baseline.get('git_sha'))[:12]})")
    return compare(baseline, current)
