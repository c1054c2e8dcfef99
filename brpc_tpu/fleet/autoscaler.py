"""Self-healing autoscaling swarm under an SLO contract (ISSUE 20).

Closes the loop the fleet stack left open: the observatory (PR 16)
already scrapes every member wire-natively, merges histograms, and burns
SLO budgets — this module CONSUMES those rollups and resizes the swarm
live. Growth spawns fresh backend processes (native echo servers, extra
listeners via nat_rpc_server_add_port); retirement goes through the
PR-8 graceful quiesce, never a close under traffic. Every decision is
charged to the native counter surface (nat_autoscale_grows / shrinks /
blocked via nat_stats_counter_bump), so /vars and /brpc_metrics show
the controller's behavior next to the data plane's.

The two halves are deliberately separable:

``Autoscaler``  — the pure decision engine. Reads any observatory-shaped
                  source (``merged()`` + an SLO ``status()``), computes
                  windowed qps/p99 from CUMULATIVE merged rollups by
                  deltaing against the previous step, and drives any
                  pool-shaped executor (``size()``/``grow()``/
                  ``shrink()``). Unit tests feed it a scripted fake
                  observatory and a counting pool — no sockets.

``SwarmPool``   — the real executor: one subprocess per member, naming
                  published to a file:// feed consumed by BOTH the data
                  plane (the dynpart cluster) and the observatory. The
                  published "i/n" tags split live members into TWO
                  overlapping partition schemes, so one SIGKILLed member
                  zeroes only its own scheme's capacity and the dynpart
                  pick routes around it — the half-dead-scheme rule in
                  nat_lb_dynpart_capacity is what keeps the flood at
                  zero failed calls while the autoscaler replaces the
                  corpse.
"""
from __future__ import annotations

import math
import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

from brpc_tpu.fleet import hist as _hist


def _bump(name: str, delta: int = 1):
    """Charge a decision to the native counter surface; quietly a no-op
    when the native library is absent (pure-Python unit tests)."""
    try:
        from brpc_tpu import native

        if native.available():
            native.stats_counter_bump(name, delta)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# the decision engine
# ---------------------------------------------------------------------------

@dataclass
class AutoscalerConfig:
    """The SLO contract, as knobs.

    Capacity tracks offered load inside [band_low, band_high] utilization
    of ``target_qps_per_backend``: above band_high the swarm grows,
    below band_low it shrinks, in between it holds — that IS the
    "capacity within a band of offered load" acceptance clause. A p99
    over ``p99_ceiling_ms`` forces grow pressure regardless of
    utilization (latency is the contract, qps only the estimator).
    Shrinks are vetoed while any SLO objective burns or any member
    drains — a controller that removes capacity during an incident is
    an outage amplifier.
    """

    min_backends: int = 1
    max_backends: int = 16
    target_qps_per_backend: float = 4000.0
    band_low: float = 0.40
    band_high: float = 0.85
    p99_ceiling_ms: float = 50.0
    grow_step: int = 2
    shrink_step: int = 1
    cooldown_s: float = 2.0
    lane: str = "echo"
    method: Optional[str] = None  # None = whole lane

    def desired_for(self, qps: float) -> int:
        """Backend count that puts utilization mid-band for ``qps``."""
        mid = (self.band_low + self.band_high) / 2.0
        want = math.ceil(qps / max(1e-9, self.target_qps_per_backend * mid))
        return max(self.min_backends, min(self.max_backends, int(want)))


class Autoscaler:
    """Rollup in, resize out. One ``step()`` per observatory interval.

    ``source`` is observatory-shaped: ``merged()`` returning the PR-16
    rollup dict, and ``slo.status()`` (any object with an ``alert``
    field per objective row). ``pool`` is executor-shaped: ``size()``,
    ``grow(k) -> int`` (members actually added), ``shrink(k) -> int``.
    """

    def __init__(self, config: AutoscalerConfig, pool, source,
                 clock: Callable[[], float] = time.monotonic):
        self.config = config
        self.pool = pool
        self.source = source
        self._clock = clock
        self._lock = threading.Lock()
        self._last_rollup: Optional[dict] = None  # (for qps/p99 deltas)
        self._last_count = 0
        self._last_buckets: List[int] = [0] * _hist.NBUCKETS
        self._last_ts: Optional[float] = None
        self._last_action_ts: Optional[float] = None
        self.decisions: List[dict] = []
        self.grows = 0
        self.shrinks = 0
        self.blocked = 0

    # -- rollup readers ----------------------------------------------------
    def _stream(self, merged: dict):
        """(cumulative_count, cumulative_buckets) of the configured
        lane/method stream from one merged rollup."""
        prefix = f"{self.config.lane}/"
        rows = [r for key, r in merged.get("methods", {}).items()
                if key.startswith(prefix) and
                (self.config.method is None or
                 key == prefix + self.config.method)]
        count = sum(r.get("count", 0) for r in rows)
        buckets = _hist.merge(*[r.get("buckets", []) for r in rows]) \
            if rows else [0] * _hist.NBUCKETS
        return count, buckets

    def _window(self, merged: dict, now: float):
        """Windowed (qps, p99_ms) since the previous step: merged rollups
        are cumulative, so the delta histogram IS the window's latency
        distribution. Deltas clamp at zero — a member restart shrinks
        the cumulative sums and must read as an empty window, not a
        negative one."""
        count, buckets = self._stream(merged)
        qps, p99_ms = 0.0, 0.0
        if self._last_ts is not None and now > self._last_ts:
            d_count = max(0, count - self._last_count)
            d_buckets = [max(0, b - a) for a, b
                         in zip(self._last_buckets, buckets)]
            qps = d_count / (now - self._last_ts)
            if sum(d_buckets) > 0:
                p99_ms = _hist.quantile(d_buckets, 0.99) / 1e6
        self._last_count, self._last_buckets = count, buckets
        self._last_ts = now
        return qps, p99_ms

    @staticmethod
    def _member_state(merged: dict):
        """(healthy, draining, broken) member counts from the rollup's
        per-backend rows (both the member's own snapshot and the
        collector's breaker view)."""
        healthy = draining = broken = 0
        for row in merged.get("backends", {}).values():
            if row.get("draining"):
                draining += 1
            elif row.get("breaker_open") or not row.get("up", False):
                broken += 1
            else:
                healthy += 1
        return healthy, draining, broken

    def _slo_burning(self) -> bool:
        slo = getattr(self.source, "slo", None)
        if slo is None:
            return False
        try:
            return any(row.get("alert") for row in slo.status().values())
        except Exception:
            return False

    # -- the control step --------------------------------------------------
    def step(self, now: Optional[float] = None) -> dict:
        """One observe-decide-act round. Returns the decision record
        (also appended to ``decisions``): action grow/shrink/hold/
        blocked, the observed qps/p99/member state, and why."""
        now = self._clock() if now is None else now
        merged = self.source.merged()
        with self._lock:
            qps, p99_ms = self._window(merged, now)
            healthy, draining, broken = self._member_state(merged)
            size = self.pool.size()
            cfg = self.config
            desired = cfg.desired_for(qps)
            # latency breach forces grow pressure even when the qps
            # estimator says capacity is fine (the ceiling is the SLO)
            if p99_ms > cfg.p99_ceiling_ms > 0 and desired <= size:
                desired = min(cfg.max_backends, size + 1)
            # a broken member contributes no capacity: replace it by
            # aiming the pool at desired + broken live processes
            desired = min(cfg.max_backends, desired + broken)

            rec = {"ts": now, "qps": round(qps, 1),
                   "p99_ms": round(p99_ms, 3), "size": size,
                   "healthy": healthy, "draining": draining,
                   "broken": broken, "desired": desired,
                   "action": "hold", "why": "in-band", "delta": 0}

            in_cooldown = (self._last_action_ts is not None and
                           now - self._last_action_ts < cfg.cooldown_s)
            if desired > size:
                if in_cooldown:
                    rec.update(action="blocked", why="cooldown")
                elif size >= cfg.max_backends:
                    rec.update(action="blocked", why="at-max")
                else:
                    k = min(cfg.grow_step, cfg.max_backends - size,
                            desired - size)
                    added = self.pool.grow(k)
                    rec.update(action="grow", delta=added,
                               why=("p99-ceiling"
                                    if p99_ms > cfg.p99_ceiling_ms > 0
                                    else "over-band"))
                    if added > 0:
                        self._last_action_ts = now
            elif desired < size:
                if in_cooldown:
                    rec.update(action="blocked", why="cooldown")
                elif size <= cfg.min_backends:
                    rec.update(action="blocked", why="at-min")
                elif self._slo_burning():
                    rec.update(action="blocked", why="slo-burning")
                elif draining > 0:
                    rec.update(action="blocked", why="member-draining")
                elif p99_ms > cfg.p99_ceiling_ms > 0:
                    rec.update(action="blocked", why="p99-ceiling")
                else:
                    k = min(cfg.shrink_step, size - cfg.min_backends,
                            size - desired)
                    removed = self.pool.shrink(k)
                    rec.update(action="shrink", delta=removed,
                               why="under-band")
                    if removed > 0:
                        self._last_action_ts = now

            if rec["action"] == "grow":
                self.grows += 1
                _bump("nat_autoscale_grows")
            elif rec["action"] == "shrink":
                self.shrinks += 1
                _bump("nat_autoscale_shrinks")
            elif rec["action"] == "blocked":
                self.blocked += 1
                _bump("nat_autoscale_blocked")
            self.decisions.append(rec)
            return rec

    # -- background loop ---------------------------------------------------
    def run(self, interval_s: float, stop: threading.Event):
        """Step until ``stop`` is set (the drill's controller thread)."""
        while not stop.wait(interval_s):
            try:
                self.step()
            except Exception:
                # a wedged scrape must not kill the controller; the next
                # interval retries against a fresh rollup
                pass


# ---------------------------------------------------------------------------
# the real executor: a subprocess swarm behind a file:// naming feed
# ---------------------------------------------------------------------------

def swarm_tags(ports: List[int]) -> List[str]:
    """Partition tags for the live port list, laid out so ONE member
    crash can never fail a dynpart verb:

    - the first two members form the ANCHOR scheme "0/1" — a single
      redundant group, so it stays usable through any one crash;
    - every further member joins the ELASTIC scheme "i/(n-2)" — one
      member per group, so a crash there loses one sub-response
      (partial merge, fail_limit=0 still succeeds) until the cool-down
      zeroes the scheme's capacity (nat_lb_dynpart_capacity's
      no-usable-member rule) and the pick routes to the anchor.

    Growth/shrink appends/pops elastic members, so every resize changes
    the elastic scheme's total — a real dynpart layout change
    (nat_dynpart_resizes) per scale event. n == 3 degenerates to one
    "0/1" scheme of three (the elastic total would collide with the
    anchor's and the groups merge — still fully redundant)."""
    n = len(ports)
    if n == 0:
        return []
    if n <= 2:
        return ["0/1"] * n
    return ["0/1", "0/1"] + [f"{i}/{n - 2}" for i in range(n - 2)]


@dataclass
class _Member:
    port: int
    proc: subprocess.Popen


class SwarmPool:
    """One native echo backend process per member, membership published
    to ``naming_path`` (the file:// feed both the dynpart cluster and
    the observatory watch). ``extra_ports`` listeners per member ride
    nat_rpc_server_add_port inside the member process. Spawned members
    honor BRPC_TPU_CHURN_FAULT (the PR-8 chaos hook: the spec lands in
    NAT_FAULT at library load), so the chaos lane runs the whole
    autoscale drill with destructive seeds armed in the backends."""

    def __init__(self, naming_path: str, base_port: int = 26100,
                 extra_ports: int = 0,
                 publish_cb: Optional[Callable[[], None]] = None,
                 env: Optional[dict] = None):
        self.naming_path = naming_path
        self._base = base_port
        self._extra = max(0, extra_ports)
        self._publish_cb = publish_cb
        self._env = dict(env if env is not None else os.environ)
        self._env.setdefault("JAX_PLATFORMS", "cpu")
        self._members: List[_Member] = []
        self._next_port = base_port
        self._lock = threading.Lock()
        self.spawn_failures = 0

    # -- membership --------------------------------------------------------
    def size(self) -> int:
        with self._lock:
            return len(self._members)

    def ports(self) -> List[int]:
        with self._lock:
            return [m.port for m in self._members]

    def publish(self):
        """Rewrite the naming feed from the live member list, with the
        two-scheme "i/n" tag split. The write is atomic (tmp + rename)
        so a naming refresh never reads a half-written list."""
        with self._lock:
            ports = [m.port for m in self._members]
        tags = swarm_tags(ports)
        tmp = self.naming_path + ".tmp"
        with open(tmp, "w") as f:
            for p, t in zip(ports, tags):
                f.write(f"127.0.0.1:{p} {t}\n")
        os.replace(tmp, self.naming_path)
        if self._publish_cb is not None:
            self._publish_cb()

    # -- spawn/retire ------------------------------------------------------
    def _spawn(self) -> Optional[_Member]:
        ports_per = 1 + self._extra
        for _ in range(32):  # walk past ports taken by other suites
            with self._lock:
                base = self._next_port
                self._next_port += ports_per
            churn = self._env.get("BRPC_TPU_CHURN_FAULT") or \
                os.environ.get("BRPC_TPU_CHURN_FAULT")
            # members are host-only: a chip belongs to one process
            env = dict(self._env, JAX_PLATFORMS="cpu")
            if churn:
                env["NAT_FAULT"] = churn
            script = (
                "import os, signal, sys\n"
                "sys.path.insert(0, '.')\n"
                "from brpc_tpu import native\n"
                f"base, count = {base}, {ports_per}\n"
                "try:\n"
                "    native.rpc_server_start('127.0.0.1', base, 2, True)\n"
                "    for p in range(base + 1, base + count):\n"
                "        native.rpc_server_add_port('127.0.0.1', p)\n"
                "except Exception:\n"
                "    print('BINDFAIL', flush=True)\n"
                "    sys.exit(17)\n"
                "print('READY', flush=True)\n"
                "def _term(sig, frm):\n"
                "    native.server_quiesce(3000)\n"
                "    native.rpc_server_stop()\n"
                "    os._exit(0)\n"
                "signal.signal(signal.SIGTERM, _term)\n"
                "while True:\n"
                "    signal.pause()\n")
            repo_root = os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))
            proc = subprocess.Popen([sys.executable, "-c", script],
                                    stdout=subprocess.PIPE, text=True,
                                    cwd=repo_root, env=env)
            line = proc.stdout.readline().strip()
            if line == "READY":
                return _Member(base, proc)
            proc.kill()
            proc.wait(timeout=10)
            self.spawn_failures += 1
        return None

    def grow(self, k: int) -> int:
        """Spawn ``k`` members, publish once all are READY. Returns the
        count actually added (port exhaustion degrades, not raises)."""
        added = 0
        for _ in range(max(0, k)):
            m = self._spawn()
            if m is None:
                break
            with self._lock:
                self._members.append(m)
            added += 1
        if added:
            self.publish()
        return added

    def shrink(self, k: int, quiesce_timeout_s: float = 10.0) -> int:
        """Retire ``k`` members gracefully: UNPUBLISH first (the naming
        refresh stops new picks landing on them), then SIGTERM — the
        member runs nat_server_quiesce (lame-duck + drain, PR 8) before
        exiting, so in-flight calls complete. Returns the count
        retired."""
        victims: List[_Member] = []
        with self._lock:
            for _ in range(max(0, min(k, len(self._members)))):
                victims.append(self._members.pop())
        if not victims:
            return 0
        self.publish()
        for m in victims:
            if m.proc.poll() is None:
                m.proc.send_signal(signal.SIGTERM)
        for m in victims:
            try:
                m.proc.wait(timeout=quiesce_timeout_s)
            except Exception:
                m.proc.kill()
                m.proc.wait(timeout=10)
        return len(victims)

    def kill_one(self, publish: bool = False) -> Optional[int]:
        """SIGKILL the NEWEST member WITHOUT unpublishing it (the chaos
        arm of the drill: a crash is never announced, and killing the
        freshest member lands the crash mid-resize when a grow just
        seated it). The autoscaler sees the corpse as a broken member in
        the next rollup and replaces it; the dynpart capacity rule
        routes around its half-dead scheme in the meantime. Returns the
        killed port."""
        with self._lock:
            if not self._members:
                return None
            m = self._members.pop()
        m.proc.kill()
        try:
            m.proc.wait(timeout=10)
        except Exception:
            pass
        if publish:
            self.publish()
        return m.port

    def close(self):
        with self._lock:
            victims, self._members = self._members, []
        for m in victims:
            if m.proc.poll() is None:
                m.proc.kill()
            try:
                m.proc.wait(timeout=10)
            except Exception:
                pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
