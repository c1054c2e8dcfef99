"""Fleet observatory (ISSUE 16): wire-native stats scrape, mergeable
log2 histograms, and the SLO burn-rate engine.

Covers the tentpole end to end — the builtin.stats snapshot served by
the native server over its own wire, the Python histogram twin pinned
against the native quantile walker, the fleet collector's exact merge
with per-backend drill-down, the /fleet console page, the fleet_*
Prometheus drift contract, the multi-window burn-rate engine — and the
acceptance drill: a 3-process swarm under a replayed flood with
injected ELIMIT overload and one rolling restart, where the merged p99
must sit within one log2 bucket of per-server truth, the burn-rate
alert must fire during the flood and clear after it, and the
restarting member's state must be visible in the rollup.
"""
import http.client
import json
import os
import random
import signal
import tempfile
import threading
import time

import pytest

from brpc_tpu.fleet import hist
from brpc_tpu.fleet.slo import SloEngine, SloObjective

# rpcz collection is off by default; this module reads spans
pytestmark = pytest.mark.usefixtures("rpcz_on")

native = pytest.importorskip("brpc_tpu.native")
if not native.available():
    pytest.skip("native toolchain unavailable", allow_module_level=True)

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "golden_capture_1k.rio")


# ---------------------------------------------------------------------------
# histogram math: the merge property the whole design rests on
# ---------------------------------------------------------------------------

def _bucketize(samples):
    h = [0] * hist.NBUCKETS
    for ns in samples:
        h[hist.bucket_of(ns)] += 1
    return h


def test_bucket_bounds_roundtrip():
    for b in range(1, hist.NBUCKETS):
        lo = int(hist.bucket_lo(b))
        hi = int(hist.bucket_hi(b)) - 1
        assert hist.bucket_of(lo) == b
        assert hist.bucket_of(hi) == b
    assert hist.bucket_of(0) == 0
    assert hist.bucket_of(1) == 0 or hist.bucket_of(1) == 1
    # over-range clamps into the last bucket instead of dropping
    assert hist.bucket_of(1 << 60) == hist.NBUCKETS - 1


def test_merge_is_exact_bucketwise_sum():
    rng = random.Random(16)
    streams = [[rng.randrange(100, 10_000_000) for _ in range(500)]
               for _ in range(4)]
    hists = [_bucketize(s) for s in streams]
    merged = hist.merge(*hists)
    for b in range(hist.NBUCKETS):
        assert merged[b] == sum(h[b] for h in hists)
    assert hist.total(merged) == sum(len(s) for s in streams)


def test_histogram_merge_quantile_property():
    """THE merge contract: for many random per-server streams, the
    quantile computed from the MERGED buckets equals the quantile of
    the concatenated raw stream to within one log2 bucket — while the
    average of per-server percentiles (the thing this design forbids)
    can be arbitrarily wrong."""
    rng = random.Random(1606)
    for trial in range(20):
        nservers = rng.randrange(2, 8)
        streams = []
        for _ in range(nservers):
            # heterogeneous shapes: some members fast, some slow, some
            # bimodal — exactly where averaged percentiles lie
            base = rng.choice([1_000, 50_000, 2_000_000])
            n = rng.randrange(50, 800)
            s = [max(1, int(rng.lognormvariate(0, 1.0) * base))
                 for _ in range(n)]
            if rng.random() < 0.3:
                s += [base * 64] * rng.randrange(1, 20)
            streams.append(s)
        merged = hist.merge(*[_bucketize(s) for s in streams])
        concat = sorted(x for s in streams for x in s)
        for q in (0.5, 0.9, 0.99):
            est = hist.quantile(merged, q)
            true = concat[min(len(concat) - 1,
                              int(q * len(concat)))]
            # within one log2 bucket of the true sample quantile
            assert abs(hist.bucket_of(int(est))
                       - hist.bucket_of(true)) <= 1, (
                trial, q, est, true)


def test_fraction_above_agrees_with_quantile():
    rng = random.Random(7)
    samples = [max(1, int(rng.lognormvariate(0, 1.5) * 40_000))
               for _ in range(3000)]
    buckets = _bucketize(samples)
    for q in (0.5, 0.9, 0.99):
        ceiling = hist.quantile(buckets, q)
        bad, tot = hist.fraction_above(buckets, ceiling)
        assert tot == len(samples)
        # the interpolations are the same line: bad/tot ~ 1-q
        assert abs(bad / tot - (1.0 - q)) < 0.02, (q, bad / tot)


def test_dense_expands_sparse_wire_form():
    assert hist.dense([[0, 3], [7, 2], [43, 1]])[0] == 3
    assert hist.dense([[7, 2]])[7] == 2
    assert sum(hist.dense([[0, 3], [7, 2], [43, 1]])) == 6
    # out-of-range buckets on the wire are dropped, not a crash
    assert sum(hist.dense([[99, 5], [-1, 5]])) == 0


# ---------------------------------------------------------------------------
# SLO burn-rate engine
# ---------------------------------------------------------------------------

def _merged_with(count, errors, buckets=None):
    return {"methods": {"echo/EchoService.Echo": {
        "lane": "echo", "method": "EchoService.Echo",
        "count": count, "errors": errors,
        "buckets": buckets or [0] * hist.NBUCKETS}}}


def test_slo_error_burn_fires_and_clears():
    obj = SloObjective(name="err", kind="errors", lane="echo",
                       budget=0.01, fast_window_s=10, slow_window_s=60)
    eng = SloEngine([obj])
    t0 = 1000.0
    eng.ingest(_merged_with(1000, 0), now=t0)
    # a hard outage: every new sample is an error -> burn 100x budget
    eng.ingest(_merged_with(1100, 100), now=t0 + 5)
    st = eng.status()["err"]
    assert st["fast_burn"] >= obj.fast_burn
    assert st["slow_burn"] >= obj.slow_burn
    assert st["alert"] and st["fired_total"] == 1
    # recovery: the stream moves on clean; once the windows slide past
    # the bad minute the burn decays and the alert clears
    t = t0 + 5
    while t < t0 + 120:
        t += 5
        eng.ingest(_merged_with(1100 + int(t - t0) * 10, 100), now=t)
    st = eng.status()["err"]
    assert not st["alert"]
    assert st["cleared_total"] == 1


def test_slo_multiwindow_suppresses_blips():
    """A short blip trips the fast window but cannot spend the slow
    window's budget — no page (the whole point of multi-window)."""
    obj = SloObjective(name="blip", kind="errors", budget=0.001,
                       fast_window_s=10, slow_window_s=1000,
                       fast_burn=14.4, slow_burn=6.0)
    eng = SloEngine([obj])
    t0 = 5000.0
    eng.ingest(_merged_with(100_000, 0), now=t0)
    for i in range(1, 200):  # long clean history
        eng.ingest(_merged_with(100_000 + i * 1000, 0), now=t0 + i)
    # a blip: 500 bad of the fast window's ~10k new samples (5% >>
    # budget there) but only 0.25% of the slow window's 200k
    eng.ingest(_merged_with(300_000, 500), now=t0 + 200)
    st = eng.status()["blip"]
    assert st["fast_burn"] >= obj.fast_burn
    assert st["slow_burn"] < obj.slow_burn
    assert not st["alert"]


def test_slo_latency_kind_counts_from_merged_buckets():
    obj = SloObjective(name="lat", kind="latency", ceiling_ms=1.0,
                       budget=0.05, fast_window_s=10, slow_window_s=20)
    eng = SloEngine([obj])
    fast = _bucketize([100_000] * 900)        # 0.1ms: under ceiling
    slow = _bucketize([100_000_000] * 100)    # 100ms: over ceiling
    t0 = 100.0
    eng.ingest(_merged_with(900, 0, fast), now=t0)
    eng.ingest(_merged_with(1000, 0, hist.merge(fast, slow)),
               now=t0 + 5)
    st = eng.status()["lat"]
    # all 100 new samples are over the 1ms ceiling: burn = 1.0/0.05
    assert st["alert"]
    assert abs(st["fast_burn"] - 20.0) < 0.5


def test_slo_restart_clamps_negative_deltas():
    """A member restart shrinks cumulative merged counts; the burn must
    read 'no new samples', never a negative rate or a phantom page."""
    obj = SloObjective(name="rst", kind="errors", budget=0.01,
                       fast_window_s=10, slow_window_s=20)
    eng = SloEngine([obj])
    eng.ingest(_merged_with(5000, 50), now=10.0)
    eng.ingest(_merged_with(100, 1), now=15.0)  # restart: counts drop
    st = eng.status()["rst"]
    assert st["fast_burn"] == 0.0
    assert not st["alert"]


def test_slo_objective_validation():
    with pytest.raises(ValueError):
        SloObjective(name="x", kind="availability")
    with pytest.raises(ValueError):
        SloObjective(name="x", budget=0.0)
    with pytest.raises(ValueError):
        SloEngine([SloObjective(name="dup"), SloObjective(name="dup")])


# ---------------------------------------------------------------------------
# single-process integration: wire snapshot, python/native pinning,
# scrape+merge, /fleet page, metrics drift
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_port():
    port = native.rpc_server_start(native_echo=True)
    ch = native.channel_open("127.0.0.1", port)
    assert ch
    try:
        for _ in range(300):
            rc, _resp, _err = native.channel_call(
                ch, "EchoService", "Echo", b"fleet", timeout_ms=5000)
            assert rc == 0
    finally:
        native.channel_close(ch)
    yield port
    native.rpc_server_stop()


def test_builtin_stats_snapshot_on_the_wire(served_port):
    """The wire-native endpoint: one tpu_std call returns the versioned
    snapshot with RAW buckets, server state and the mem ledger."""
    ch = native.channel_open("127.0.0.1", served_port)
    try:
        rc, body, _err = native.channel_call(ch, "builtin", "stats",
                                             b"", timeout_ms=5000)
    finally:
        native.channel_close(ch)
    assert rc == 0
    snap = json.loads(body)
    assert snap["v"] == 1
    assert snap["counters"]["nat_stats_snapshots"] >= 1
    rows = {f"{m['lane']}/{m['method']}": m for m in snap["methods"]}
    echo = rows["echo/EchoService.Echo"]
    assert echo["count"] >= 300
    assert sum(c for _b, c in echo["buckets"]) == echo["count"]
    assert "inflight" in snap["server"] and "draining" in snap["server"]
    assert isinstance(snap["mem"], dict) and snap["mem"]
    assert isinstance(snap["channels"], list)


def test_python_quantile_pins_native_walker(served_port):
    """hist.quantile is a line-for-line port of nat_hist_quantile; the
    two must agree exactly on the same live buckets."""
    lane = native.stats_lane_names().index("echo")
    buckets = native.method_hist(lane, "EchoService.Echo")
    assert buckets and sum(buckets) >= 300
    for q in (0.5, 0.9, 0.99, 0.999):
        py = hist.quantile(buckets, q)
        nat = native.method_quantile(lane, "EchoService.Echo", q)
        assert py == pytest.approx(nat, rel=1e-9), q


def test_scrape_merge_and_drilldown(served_port):
    from brpc_tpu.fleet import FleetObservatory

    ep = f"127.0.0.1:{served_port}"
    with FleetObservatory(endpoints=[ep], register_bvars=False) as obs:
        merged = obs.scrape_once()
        assert merged["backends"][ep]["up"]
        row = merged["methods"]["echo/EchoService.Echo"]
        assert row["count"] >= 300
        assert row["per_backend"][ep]["count"] == row["count"]
        # merged == the one member's raw buckets (exact)
        lane = native.stats_lane_names().index("echo")
        assert row["buckets"] == native.method_hist(lane,
                                                    "EchoService.Echo")
        assert obs.method_quantile("EchoService.Echo", 0.99) > 0
        s, e = obs.scrape_counts()
        assert (s, e) == (1, 0)


def test_scrape_marks_dead_backend_down(served_port):
    from brpc_tpu.fleet import FleetObservatory

    live = f"127.0.0.1:{served_port}"
    dead = "127.0.0.1:1"
    with FleetObservatory(endpoints=[live, dead],
                          register_bvars=False) as obs:
        merged = obs.scrape_once()
        assert merged["backends"][live]["up"]
        assert not merged["backends"][dead]["up"]
        s, e = obs.scrape_counts()
        assert s == 1 and e == 1


def test_fleet_console_page(served_port):
    """/fleet on the Python console: rollup + drill-down + JSON dump."""
    from brpc_tpu import rpc
    from brpc_tpu.fleet import FleetObservatory, SloObjective as Obj

    srv = rpc.Server(rpc.ServerOptions(num_threads=1))
    assert srv.start("127.0.0.1:0") == 0
    ep = f"127.0.0.1:{served_port}"
    try:
        with FleetObservatory(endpoints=[ep], register_bvars=False,
                              objectives=[Obj(name="page-p99")]) as obs:
            obs.scrape_once()

            def get(path):
                conn = http.client.HTTPConnection(
                    "127.0.0.1", srv.listen_endpoint.port, timeout=10)
                conn.request("GET", path)
                r = conn.getresponse()
                body = r.read().decode()
                conn.close()
                return r.status, body

            status, body = get("/fleet")
            assert status == 200
            assert ep in body
            assert "echo/EchoService.Echo" in body
            assert "page-p99" in body
            status, body = get(f"/fleet?backend={ep}")
            assert status == 200 and "snapshot v1" in body
            status, body = get("/fleet?json=1")
            doc = json.loads(body)
            assert ep in doc[obs.name]["backends"]
    finally:
        srv.stop()


def test_fleet_metrics_drift(served_port):
    """Every fleet_*/SLO variable the module exposes shows up in the
    Prometheus dump, and no unlisted fleet_* row exists — additions
    must land in FLEET_VAR_NAMES or this fails (the drift contract)."""
    from brpc_tpu import fleet
    from brpc_tpu.bvar.variable import dump_prometheus

    ep = f"127.0.0.1:{served_port}"
    with fleet.FleetObservatory(
            endpoints=[ep],
            objectives=[fleet.SloObjective(name="drift-p99")]) as obs:
        obs.scrape_once()
        prom = dump_prometheus()
        rows = [ln for ln in prom.splitlines()
                if ln.startswith("fleet_") and not ln.startswith("# ")]
        present = {ln.split("{")[0].split(" ")[0] for ln in rows}
        missing = set(fleet.FLEET_VAR_NAMES) - present
        assert not missing, f"registered but not exported: {missing}"
        unlisted = present - set(fleet.FLEET_VAR_NAMES)
        assert not unlisted, (
            f"fleet_* rows not declared in FLEET_VAR_NAMES: {unlisted}")
        # the labeled dimensions carry real labels
        assert any(f'backend="{ep}"' in ln for ln in rows)
        assert any('slo="drift-p99"' in ln for ln in rows)


def test_find_trace_fans_out_over_consoles(served_port):
    """find_trace queries every member's /rpcz; a member whose console
    holds spans for the id contributes to the stitched chain."""
    from brpc_tpu import rpc
    from brpc_tpu.fleet import FleetObservatory

    srv = rpc.Server(rpc.ServerOptions(num_threads=1))
    assert srv.start("127.0.0.1:0") == 0
    console = f"127.0.0.1:{srv.listen_endpoint.port}"
    ep = f"127.0.0.1:{served_port}"
    try:
        with FleetObservatory(endpoints=[ep], register_bvars=False,
                              console_map={ep: console}) as obs:
            assert obs.console_of(ep) == console
            # unknown id: clean empty answer from the whole fleet
            parts = obs.find_trace(0xdeadbeef)
            assert parts == [] or all("trace=" in p["body"]
                                      for p in parts)
            assert "no spans" in obs.stitched_trace(0x1)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# the acceptance drill: 3-process swarm, replayed flood, injected
# ELIMIT overload, one rolling restart
# ---------------------------------------------------------------------------

def _flood_member(port, results, idx):
    try:
        results[idx] = native.replay_run("127.0.0.1", port, GOLDEN,
                                         times=2, concurrency=8,
                                         timeout_ms=5000)
    except Exception as exc:  # pragma: no cover - drill diagnostics
        results[idx] = {"error": str(exc)}


def _elimit_probe(port, n=6):
    """Flood the py-lane (no consumer, constant:1 limiter): the first
    call parks on the single admission slot, the rest shed with real
    ELIMIT on the wire."""
    def one():
        ch = native.channel_open("127.0.0.1", port)
        if ch:
            try:
                native.channel_call(ch, "PyLane", "Blocked", b"x",
                                    timeout_ms=400)
            finally:
                native.channel_close(ch)
    ts = [threading.Thread(target=one) for _ in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)


@pytest.mark.skipif(not os.path.exists(GOLDEN),
                    reason="golden capture missing")
def test_three_process_flood_drill():
    from brpc_tpu.bench import _spawn_swarm_server
    from brpc_tpu.fleet import FleetObservatory, SloObjective

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["BRPC_TPU_SWARM_LIMITER"] = "constant:1"  # the ELIMIT injector
    procs, ports = [], []
    nf_path = None
    obs = None
    try:
        for base in (23300, 25300, 27300, 29300, 21300, 19300):
            proc = _spawn_swarm_server(base, 1, repo_root, env)
            if proc is not None:
                procs.append(proc)
                ports.append(base)
            if len(procs) == 3:
                break
        if len(procs) < 3:
            pytest.skip("no free port ranges for the 3-server group")
        eps = [f"127.0.0.1:{p}" for p in ports]

        nf = tempfile.NamedTemporaryFile("w", suffix=".fleet.ns",
                                         delete=False)
        nf_path = nf.name
        for ep in eps:
            nf.write(ep + "\n")
        nf.close()

        # sub-microsecond ceiling: during the flood every sample is
        # "bad", so the burn is budget^-1 = 100x — fires; after the
        # flood the windows drain and it clears. Short windows keep the
        # drill under test time; the engine logic is window-agnostic.
        obs = FleetObservatory(
            naming_url=f"file://{nf_path}", interval_s=10.0,
            objectives=[SloObjective(name="drill-p99", kind="latency",
                                     lane="echo",
                                     method="EchoService.Echo",
                                     ceiling_ms=0.0001, budget=0.01,
                                     fast_window_s=2.0,
                                     slow_window_s=4.0)],
            register_bvars=False)
        deadline = time.time() + 15
        merged = obs.scrape_once()
        while (sum(1 for b in merged["backends"].values() if b["up"])
               < 3 and time.time() < deadline):
            time.sleep(0.3)
            merged = obs.scrape_once()
        assert sum(1 for b in merged["backends"].values()
                   if b["up"]) == 3, merged["backends"]

        # -- replayed flood over the whole group, scraping at ~5Hz ----
        results = [None] * 3
        threads = [threading.Thread(target=_flood_member,
                                    args=(p, results, i))
                   for i, p in enumerate(ports)]
        for t in threads:
            t.start()
        fired = False
        while any(t.is_alive() for t in threads):
            merged = obs.scrape_once()
            fired = fired or obs.slo.status()["drill-p99"]["alert"]
            time.sleep(0.2)
        for t in threads:
            t.join(timeout=30)
        for r in results:
            assert r and not r.get("error") and r.get("failed") == 0, \
                results

        # keep scraping past the flood so the alert latches even if the
        # loop above raced the last window
        for _ in range(4):
            merged = obs.scrape_once()
            fired = fired or obs.slo.status()["drill-p99"]["alert"]
            time.sleep(0.2)
        assert fired, obs.slo.status()
        assert obs.slo.alerts_fired_total() >= 1

        # -- merged p99 within one log2 bucket of per-server truth ----
        row = merged["methods"]["echo/EchoService.Echo"]
        assert row["count"] >= 3 * 2000  # 1k capture x2 x3 members
        member_hists = []
        for snap in obs.snapshots().values():
            assert snap.ok
            for m in snap.data["methods"]:
                if m["method"] == "EchoService.Echo":
                    member_hists.append(hist.dense(m["buckets"]))
        assert len(member_hists) == 3
        truth = hist.merge(*member_hists)
        assert row["buckets"] == truth  # the merge is EXACT
        merged_p99_b = hist.bucket_of(int(hist.quantile(row["buckets"],
                                                        0.99)))
        per_server_b = [hist.bucket_of(int(hist.quantile(h, 0.99)))
                        for h in member_hists]
        assert (min(per_server_b) - 1 <= merged_p99_b
                <= max(per_server_b) + 1), (merged_p99_b, per_server_b)

        # -- injected ELIMIT overload is visible in the rollup --------
        _elimit_probe(ports[0])
        merged = obs.scrape_once()
        ep0 = eps[0]
        assert merged["backends"][ep0]["elimit_rejects"] > 0, \
            merged["backends"][ep0]
        assert merged["counters"]["nat_elimit_rejects"] > 0

        # -- one rolling restart: the member's departure shows in the
        #    rollup (down/draining/lame-duck/breaker), then it rejoins -
        victim = procs[2]
        victim.send_signal(signal.SIGTERM)
        saw_departure = False
        deadline = time.time() + 25
        while time.time() < deadline:
            merged = obs.scrape_once()
            b = merged["backends"].get(eps[2], {})
            if (not b.get("up", True)) or b.get("draining") \
                    or b.get("lame_duck") or b.get("breaker_open"):
                saw_departure = True
            if saw_departure and victim.poll() is not None:
                break
            time.sleep(0.2)
        assert saw_departure, merged["backends"].get(eps[2])
        victim.wait(timeout=20)
        fresh = _spawn_swarm_server(ports[2], 1, repo_root, env)
        assert fresh is not None, "restarted member failed to bind"
        procs[2] = fresh
        deadline = time.time() + 15
        while time.time() < deadline:
            merged = obs.scrape_once()
            if merged["backends"].get(eps[2], {}).get("up"):
                break
            time.sleep(0.3)
        assert merged["backends"][eps[2]]["up"], merged["backends"]

        # -- quiet period: the windows drain, the alert clears --------
        deadline = time.time() + 12
        cleared = False
        while time.time() < deadline:
            obs.scrape_once()
            st = obs.slo.status()["drill-p99"]
            if not st["alert"] and st["cleared_total"] >= 1:
                cleared = True
                break
            time.sleep(0.4)
        assert cleared, obs.slo.status()
    finally:
        if obs is not None:
            obs.close()
        for proc in procs:
            if proc is not None and proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in procs:
            if proc is not None:
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
