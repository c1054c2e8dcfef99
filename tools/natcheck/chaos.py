"""Chaos soak — fixed-seed fault-injection run (``tools/check.sh --chaos``).

Three legs, each a Finding on failure:

1. C smoke (uninstrumented ``nat_smoke``) under ``CHAOS_SPEC`` in the
   ``NAT_FAULT`` environment — the whole smoke (echo sync/async, http,
   redis, shm descriptor rings, its own internal natfault round) runs
   with the ambient fault table armed.
2. The pytest native matrix under the same spec, plus the dedicated
   fault/overload suites (which install their own destructive specs at
   runtime via ``nat_fault_configure`` and restore the env spec after).
3. The ``churn`` round: the rolling-restart drill of
   tests/test_graceful_shutdown.py (3 server processes restarted
   round-robin under a client flood) with DESTRUCTIVE seeds armed in the
   SERVER processes via ``CHURN_SPEC`` — random EPIPE on socket writes
   plus a worker SIGKILL on every worker's 5th shm take. The assertion
   is the graceful-degradation contract itself: zero failed requests
   once retries settle, every SIGTERM'd server exits 0.

``CHAOS_SPEC`` deliberately uses only **semantics-preserving** faults:
short reads/writes (every parser must stay incremental), EINTR on both
directions (the drain/requeue retry arms), connect delays (timeout-clamp
paths) and dropped doorbells (the waiter-gated wake protocol must degrade
to its bounded-timeout polls). Destructive faults — ECONNRESET/EPIPE,
dropped writes, worker SIGKILL — change observable outcomes by design,
so they live in tests that assert the *recovery*, not the absence of the
fault: tests/test_native_fault.py, tests/test_native_overload.py and the
fault-table SIGKILL test in tests/test_shm_worker_crash.py.

Determinism: the fault schedule is a pure function of (seed, site, rule
index, per-site op index) — re-running the lane with the same seed over
the same op sequence replays the same faults. The op *ordering* across
sockets still depends on thread interleaving; the seed pins the
schedule, not the scheduler.

``BRPC_TPU_SANITIZED=1`` is set for the pytest leg so the matrix's
perf/RSS gates loosen — a perturbed run is not a perf regression.

The combined log is written to ``native/CHAOS.md`` — commit it clean.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import List, Tuple

from tools.natcheck import Finding, REPO_ROOT

NATIVE_DIR = os.path.join(REPO_ROOT, "native")
CHAOS_MD = os.path.join(NATIVE_DIR, "CHAOS.md")

# The documented fixed-seed chaos spec (see module docstring for why
# only semantics-preserving faults ride the ambient environment).
CHAOS_SPEC = ("seed=42;"
              "read:short:p=0.05;read:err=EINTR:p=0.02;"
              "write:short:p=0.05;write:err=EINTR:p=0.02;"
              "connect:delay_ms=20:p=0.2;"
              "doorbell:drop:p=0.05")

# The churn round's DESTRUCTIVE spec, armed only in the rolling-restart
# drill's server processes (the test asserts recovery, not absence).
CHURN_SPEC = "seed=42;write:err=EPIPE:p=0.002;worker:kill@5"

# The native-lane matrix (the soak set) + the fault/overload suites.
PYTEST_MATRIX = [
    "tests/test_native.py", "tests/test_native_rpc.py",
    "tests/test_native_client.py", "tests/test_native_http.py",
    "tests/test_native_h2.py", "tests/test_native_redis.py",
    "tests/test_native_streaming.py", "tests/test_native_stats.py",
    "tests/test_shm_workers.py", "tests/test_shm_desc_ring.py",
    "tests/test_shm_worker_crash.py",
    "tests/test_native_fault.py", "tests/test_native_overload.py",
    "tests/test_native_cluster.py",
]


def _smoke_leg() -> Tuple[List[Finding], str]:
    findings: List[Finding] = []
    try:
        subprocess.run(["make", "-C", NATIVE_DIR, "nat_smoke"], check=True,
                       capture_output=True, timeout=900)
    except subprocess.CalledProcessError as e:
        findings.append(Finding(
            "chaos", "smoke-build", "native/Makefile",
            "build failed: " +
            (e.stderr or b"").decode(errors="replace")[-800:]))
        return findings, "chaos smoke: BUILD FAILED"
    env = dict(os.environ)
    env["NAT_FAULT"] = CHAOS_SPEC
    try:
        proc = subprocess.run(
            [os.path.join(NATIVE_DIR, "nat_smoke")], capture_output=True,
            timeout=600, env=env)
    except subprocess.TimeoutExpired:
        # a hang under injected faults IS the defect class this hunts
        findings.append(Finding(
            "chaos", "smoke-hang", "native/nat_smoke",
            "chaos smoke timed out under NAT_FAULT (hang/deadlock?)"))
        return findings, "chaos smoke: TIMED OUT"
    out = proc.stdout.decode(errors="replace") + \
        proc.stderr.decode(errors="replace")
    if proc.returncode != 0:
        findings.append(Finding(
            "chaos", "smoke", "native/nat_smoke",
            f"chaos smoke exited rc={proc.returncode}: "
            f"{out.strip()[-400:]}"))
    return findings, out


def _pytest_leg() -> Tuple[List[Finding], str]:
    findings: List[Finding] = []
    env = dict(os.environ)
    env["NAT_FAULT"] = CHAOS_SPEC
    env["BRPC_TPU_SANITIZED"] = "1"  # loosen perf/RSS gates: perturbed
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", *PYTEST_MATRIX, "-q", "-m",
             "not slow", "-p", "no:cacheprovider"],
            capture_output=True, timeout=1800, env=env, cwd=REPO_ROOT)
    except subprocess.TimeoutExpired:
        return [Finding("chaos", "pytest-hang", "tests/",
                        "chaos python matrix timed out")], \
            "chaos pytest: TIMED OUT"
    out = proc.stdout.decode(errors="replace") + \
        proc.stderr.decode(errors="replace")
    if proc.returncode != 0:
        tail = out.strip().splitlines()[-1] if out.strip() else "?"
        findings.append(Finding(
            "chaos", "pytest", "tests/",
            f"chaos python matrix rc={proc.returncode}: {tail}"))
    return findings, out


def _churn_leg() -> Tuple[List[Finding], str]:
    """Seeded rolling-restart drill: servers run under CHURN_SPEC, the
    client flood must settle with zero failures (the two-process churn
    acceptance test of the graceful-drain lifecycle)."""
    findings: List[Finding] = []
    env = dict(os.environ)
    env.pop("NAT_FAULT", None)  # the CLIENT side stays clean; servers
    env["BRPC_TPU_CHURN_FAULT"] = CHURN_SPEC  # arm via the test hook
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest",
             "tests/test_graceful_shutdown.py", "-q",
             "-k", "churn or rolling_restart or sigterm",
             "-p", "no:cacheprovider"],
            capture_output=True, timeout=900, env=env, cwd=REPO_ROOT)
    except subprocess.TimeoutExpired:
        return [Finding("chaos", "churn-hang", "tests/",
                        "churn round timed out (drain wedged?)")], \
            "chaos churn: TIMED OUT"
    out = proc.stdout.decode(errors="replace") + \
        proc.stderr.decode(errors="replace")
    if proc.returncode != 0:
        tail = out.strip().splitlines()[-1] if out.strip() else "?"
        findings.append(Finding(
            "chaos", "churn", "tests/test_graceful_shutdown.py",
            f"churn round rc={proc.returncode}: {tail}"))
    return findings, out


def _swarm_leg() -> Tuple[List[Finding], str]:
    """Swarm round (ISSUE 13): the multi-port fan-out churn drill
    (tests/test_native_cluster.py's slow acceptance) with DESTRUCTIVE
    seeds armed in every swarm SERVER process — random EPIPE on socket
    writes plus the worker-kill seed — while the cluster client stays
    clean. The assertion is the fan-out contract itself: zero failed
    RPCs through rolling SIGTERM restarts + live naming updates."""
    findings: List[Finding] = []
    env = dict(os.environ)
    env.pop("NAT_FAULT", None)  # the CLIENT side stays clean; servers
    env["BRPC_TPU_CHURN_FAULT"] = CHURN_SPEC  # armed via the drill hook
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest",
             "tests/test_native_cluster.py", "-q",
             "-k", "swarm_churn or membership_updates",
             "-p", "no:cacheprovider"],
            capture_output=True, timeout=900, env=env, cwd=REPO_ROOT)
    except subprocess.TimeoutExpired:
        return [Finding("chaos", "swarm-hang", "tests/",
                        "swarm round timed out (fan-out wedged?)")], \
            "chaos swarm: TIMED OUT"
    out = proc.stdout.decode(errors="replace") + \
        proc.stderr.decode(errors="replace")
    if proc.returncode != 0:
        tail = out.strip().splitlines()[-1] if out.strip() else "?"
        findings.append(Finding(
            "chaos", "swarm", "tests/test_native_cluster.py",
            f"swarm round rc={proc.returncode}: {tail}"))
    return findings, out


def _resize_leg() -> Tuple[List[Finding], str]:
    """Resize round (ISSUE 20): the dynpart resize-under-fault matrix
    (tests/test_dynpart_native.py) — swarm members added/retired live so
    the partition scheme set resizes mid-flood, with DESTRUCTIVE seeds
    armed in every member process (EPIPE write storms) and a SIGKILL
    landing mid-resize. The assertion is the elastic-capacity contract:
    a resize is never caller-visible and zero calls fail once the
    bounded retry settles."""
    findings: List[Finding] = []
    env = dict(os.environ)
    env.pop("NAT_FAULT", None)  # the CLIENT side stays clean; servers
    env["BRPC_TPU_CHURN_FAULT"] = CHURN_SPEC  # armed via the pool hook
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest",
             "tests/test_dynpart_native.py", "-q",
             "-k", "resize_under_fault",
             "-p", "no:cacheprovider"],
            capture_output=True, timeout=900, env=env, cwd=REPO_ROOT)
    except subprocess.TimeoutExpired:
        return [Finding("chaos", "resize-hang", "tests/",
                        "resize round timed out (publish wedged?)")], \
            "chaos resize: TIMED OUT"
    out = proc.stdout.decode(errors="replace") + \
        proc.stderr.decode(errors="replace")
    if proc.returncode != 0:
        tail = out.strip().splitlines()[-1] if out.strip() else "?"
        findings.append(Finding(
            "chaos", "resize", "tests/test_dynpart_native.py",
            f"resize round rc={proc.returncode}: {tail}"))
    return findings, out


def run(write_log: bool = True) -> List[Finding]:
    findings: List[Finding] = []
    sections = []
    t0 = time.time()
    got, out = _smoke_leg()
    findings.extend(got)
    sections.append(("C smoke under NAT_FAULT", out))
    got, out = _pytest_leg()
    findings.extend(got)
    sections.append(("pytest native matrix under NAT_FAULT", out))
    got, out = _churn_leg()
    findings.extend(got)
    sections.append(("churn round (rolling restart under %s)" %
                     CHURN_SPEC, out))
    got, out = _swarm_leg()
    findings.extend(got)
    sections.append(("swarm round (fan-out churn under %s)" %
                     CHURN_SPEC, out))
    got, out = _resize_leg()
    findings.extend(got)
    sections.append(("resize round (dynpart resize-under-fault under %s)" %
                     CHURN_SPEC, out))

    if write_log:
        with open(CHAOS_MD, "w", encoding="utf-8") as f:
            f.write("# native chaos soak log\n\n")
            f.write("Produced by `tools/check.sh --chaos` "
                    "(tools/natcheck/chaos.py). The C smoke and the\n"
                    "pytest native matrix run with the fixed-seed fault "
                    "spec below armed via the\n`NAT_FAULT` environment; "
                    "the dedicated fault/overload suites additionally\n"
                    "install destructive specs at runtime and assert the "
                    "recovery paths.\nThe churn round runs the "
                    "rolling-restart drill (SIGTERM drain + failover)\n"
                    "with the destructive churn spec armed in the server "
                    "processes.\n\n")
            f.write("Spec: `%s`\n" % CHAOS_SPEC)
            f.write("Churn spec (server processes): `%s`\n\n" % CHURN_SPEC)
            f.write("Result: %s (%d finding(s), %.0fs)\n\n" %
                    ("CLEAN" if not findings else "FAILING",
                     len(findings), time.time() - t0))
            for f2 in findings:
                f.write("- FINDING: %s\n" % f2)
            for title, body in sections:
                tail = "\n".join(body.strip().splitlines()[-25:])
                f.write("\n## %s\n\n```\n%s\n```\n" % (title, tail))
    return findings
