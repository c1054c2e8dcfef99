"""natcheck fleet round (tools/check.sh --fleet): the fleet observatory
driven against a LIVE 3-server group.

Three native echo server subprocesses behind a file naming feed, real
traffic through real channels, then the whole ISSUE-16 chain end to
end: wire-native builtin.stats scrape of every member -> histogram
merge -> fleet quantiles -> SLO engine. The merge contract is checked
EXACTLY: the merged method buckets must equal the bucket-wise sum of
every member's buckets (log2 histograms admit an exact merge — that is
the reason raw buckets ride the wire instead of percentiles), and the
fleet quantile must come from those merged buckets.
"""
from __future__ import annotations

import os
import signal
import tempfile
import time
from typing import List

from tools.natcheck import Finding, REPO_ROOT

WHERE = "tools/check.sh --fleet"
SERVERS = 3
CALLS_PER_BACKEND = 200


def _finding(rule: str, msg: str) -> Finding:
    return Finding("fleet", rule, WHERE, msg)


def run() -> List[Finding]:
    findings: List[Finding] = []
    import sys

    sys.path.insert(0, REPO_ROOT)
    from brpc_tpu import native  # noqa: F401 — fail early when .so missing
    from tools.drills import _spawn_swarm_server
    from brpc_tpu.fleet import FleetObservatory, SloObjective, hist

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = []
    ports = []
    nf_path = None
    obs = None
    try:
        base_candidates = [22100, 24100, 26100, 28100, 20100, 18100]
        ci = 0
        while len(procs) < SERVERS and ci < len(base_candidates):
            base = base_candidates[ci]
            ci += 1
            proc = _spawn_swarm_server(base, 1, REPO_ROOT, env)
            if proc is not None:
                procs.append(proc)
                ports.append(base)
        if len(procs) < SERVERS:
            return [_finding("no-ports",
                             "could not bind a 3-server group (all "
                             "candidate port ranges taken)")]

        nf = tempfile.NamedTemporaryFile("w", suffix=".fleet.ns",
                                         delete=False)
        nf_path = nf.name
        for p in ports:
            nf.write(f"127.0.0.1:{p}\n")
        nf.close()

        # real traffic through real channels, per member
        from brpc_tpu import native as nat

        for p in ports:
            ch = nat.channel_open("127.0.0.1", p)
            if not ch:
                findings.append(_finding(
                    "dial", f"could not dial live member 127.0.0.1:{p}"))
                continue
            try:
                failed = 0
                for _ in range(CALLS_PER_BACKEND):
                    rc, _resp, _err = nat.channel_call(
                        ch, "EchoService", "Echo", b"fleet-round",
                        timeout_ms=5000)
                    failed += rc != 0
                if failed:
                    findings.append(_finding(
                        "traffic",
                        f"{failed}/{CALLS_PER_BACKEND} echo calls "
                        f"failed against 127.0.0.1:{p}"))
            finally:
                nat.channel_close(ch)
        if findings:
            return findings

        obs = FleetObservatory(
            naming_url=f"file://{nf_path}",
            interval_s=0.5,
            objectives=[SloObjective(name="fleet-round-p99",
                                     kind="latency", lane="echo",
                                     method="EchoService.Echo",
                                     ceiling_ms=1000.0, budget=0.001,
                                     fast_window_s=5, slow_window_s=10)],
            register_bvars=False)
        deadline = time.time() + 10
        merged = obs.scrape_once()
        while (len(merged.get("backends", {})) < SERVERS
               and time.time() < deadline):
            time.sleep(0.3)
            merged = obs.scrape_once()

        backends = merged.get("backends", {})
        up = [ep for ep, b in backends.items() if b.get("up")]
        if len(up) != SERVERS:
            findings.append(_finding(
                "membership",
                f"expected {SERVERS} live members, scraped "
                f"{len(up)} up of {len(backends)} known"))

        row = merged.get("methods", {}).get("echo/EchoService.Echo")
        if row is None:
            findings.append(_finding(
                "merge", "merged rollup has no echo/EchoService.Echo "
                         "row after real traffic"))
            return findings
        want = SERVERS * CALLS_PER_BACKEND
        if row["count"] < want:
            findings.append(_finding(
                "merge",
                f"merged count {row['count']} < {want} sent calls — "
                f"a member's stream was dropped from the merge"))

        # the EXACT-merge contract: merged buckets == bucket-wise sum of
        # every member's raw buckets off the wire
        summed = [0] * hist.NBUCKETS
        for snap in obs.snapshots().values():
            if not (snap.ok and snap.data):
                continue
            for m in snap.data.get("methods", []):
                if (m["lane"], m["method"]) == ("echo",
                                                "EchoService.Echo"):
                    summed = hist.merge(summed,
                                        hist.dense(m.get("buckets", [])))
        if summed != row["buckets"]:
            findings.append(_finding(
                "merge-exact",
                "merged histogram != bucket-wise sum of member "
                "histograms — the exact-merge contract is broken"))
        p99 = hist.quantile(row["buckets"], 0.99)
        if not 0.0 < p99 < 60e9:
            findings.append(_finding(
                "quantile",
                f"fleet p99 {p99}ns from merged buckets is not sane"))

        # the SLO engine saw the streams and stands quiet (1s ceiling on
        # a loopback echo cannot burn)
        st = obs.slo.status().get("fleet-round-p99")
        if st is None or st["stream_total"] <= 0:
            findings.append(_finding(
                "slo", "SLO engine did not ingest the merged stream"))
        elif st["alert"]:
            findings.append(_finding(
                "slo", "SLO alert firing on an unburned objective "
                       "(1s ceiling on loopback echo)"))
    finally:
        if obs is not None:
            obs.close()
        for proc in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in procs:
            try:
                proc.wait(timeout=15)
            except Exception:
                proc.kill()
                proc.wait(timeout=10)
        if nf_path is not None:
            try:
                os.unlink(nf_path)
            except OSError:
                pass
    return findings
