"""Multi-process drills of the native runtime: the fan-out swarm under
churn, the swarm's member servers, and the connection-scale storm.

Each drill spawns real server or client subprocesses on localhost and
returns a dict of what it observed; the tests that call them hold the
contract (zero failed RPCs, every backend served, no leak after
teardown):

* fanout_swarm_bench: tests/test_native_cluster.py (slow).
* _spawn_swarm_server: tests/test_fleet_observatory.py and
  tools/natcheck/fleet.py.
* conn_scale_bench: tests/test_native_memory.py (slow).
"""
from __future__ import annotations

import time


def conn_scale_bench(target_conns: int = 20000, client_procs: int = 4,
                     idle_s: float = 2.0) -> dict:
    """The connection-scale drill: hold `target_conns` mostly-idle
    keep-alive tpu_std
    connections from client SUBPROCESSES against one in-process native
    server and measure what a connection COSTS from the nat_res
    accounting — bytes (accounted live delta / connection), fds, and
    idle wakeups/s — plus the accept-storm recovery time (spawn ->
    every connection accepted and answered) with a live RPC subset
    flooding throughout (zero failed calls is part of the contract:
    any failure, an unfinished storm, or a post-teardown leak in the
    transient subsystems reports conn_scale_conns 0).

    The target is clamped to RLIMIT_NOFILE minus headroom (the server
    process holds one fd per connection); conn_scale_target records the
    CLAMPED target the drill actually ran (conn_scale_requested keeps
    the pre-clamp ask, so a fd-limited host is distinguishable from a
    failing drill). BRPC_TPU_CONN_SCALE overrides the target
    (0 disables the drill)."""
    import ctypes
    import os
    import resource
    import subprocess
    import sys
    import threading

    from brpc_tpu import native

    env_target = os.environ.get("BRPC_TPU_CONN_SCALE")
    if env_target is not None:
        try:
            target_conns = int(env_target)
        except ValueError:
            pass
        if target_conns <= 0:
            return {}
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    try:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
        soft = hard
    except (ValueError, OSError):
        pass
    conns = max(100, min(target_conns, soft - 1000))
    per_proc = max(1, conns // client_procs)
    conns = per_proc * client_procs

    lib = native.load()
    port = native.rpc_server_start(native_echo=True)
    out = {"conn_scale_target": conns,
           "conn_scale_requested": target_conns}
    procs = []
    live_stop = threading.Event()
    live_ok = [0]
    live_fail = [0]

    def _live_loop():
        # the live subset: continuous echo RPCs through the accept storm
        # and the idle window — the "zero failed RPCs on the live
        # subset" half of the acceptance contract
        h = lib.nat_channel_open(b"127.0.0.1", port, 0, 0, 0, 0)
        if not h:
            live_fail[0] += 1
            return
        resp = ctypes.c_char_p()
        rlen = ctypes.c_size_t(0)
        err = ctypes.c_char_p()
        while not live_stop.is_set():
            rc = lib.nat_channel_call(h, b"EchoService", b"Echo",
                                      b"live", 4, 3000,
                                      ctypes.byref(resp),
                                      ctypes.byref(rlen),
                                      ctypes.byref(err))
            if rc == 0 and rlen.value == 4:
                live_ok[0] += 1
            else:
                live_fail[0] += 1
            if resp:
                lib.nat_buf_free(resp)
                resp = ctypes.c_char_p()
            if err:
                lib.nat_buf_free(err)
                err = ctypes.c_char_p()
        lib.nat_channel_close(h)

    client_src = (
        "import socket, struct, sys, time\n"
        "port, n = int(sys.argv[1]), int(sys.argv[2])\n"
        "from brpc_tpu.rpc.proto import rpc_meta_pb2\n"
        "meta = rpc_meta_pb2.RpcMeta()\n"
        "meta.request.service_name = 'EchoService'\n"
        "meta.request.method_name = 'Echo'\n"
        "meta.correlation_id = 7\n"
        "mb = meta.SerializeToString()\n"
        "frame = (b'TRPC' + struct.pack('>II', len(mb) + 1, len(mb))\n"
        "         + mb + b'k')\n"
        "socks, failed = [], 0\n"
        "for i in range(n):\n"
        "    try:\n"
        "        s = socket.create_connection(('127.0.0.1', port),\n"
        "                                     timeout=20)\n"
        "        s.sendall(frame)\n"
        "        socks.append(s)\n"
        "    except OSError:\n"
        "        failed += 1\n"
        "# one echo answered per connection proves each was accepted\n"
        "# AND served through the storm (not just SYN-queued)\n"
        "answered = 0\n"
        "for s in socks:\n"
        "    try:\n"
        "        s.settimeout(30)\n"
        "        buf = b''\n"
        "        while len(buf) < 12:\n"
        "            got = s.recv(4096)\n"
        "            if not got:\n"
        "                raise OSError('eof')\n"
        "            buf += got\n"
        "        body, _ = struct.unpack('>II', buf[4:12])\n"
        "        while len(buf) < 12 + body:\n"
        "            got = s.recv(65536)\n"
        "            if not got:\n"
        "                raise OSError('eof')\n"
        "            buf += got\n"
        "        answered += 1\n"
        "    except OSError:\n"
        "        failed += 1\n"
        "print('READY %d %d' % (answered, failed), flush=True)\n"
        "sys.stdin.readline()  # parent closes stdin -> teardown\n"
        "for s in socks:\n"
        "    try:\n"
        "        s.close()\n"
        "    except OSError:\n"
        "        pass\n"
        "print('CLOSED', flush=True)\n")

    try:
        time.sleep(0.3)
        fd0 = len(os.listdir("/proc/self/fd"))
        res0 = {r["subsystem"]: r for r in native.res_stats()}
        live_thread = threading.Thread(target=_live_loop, daemon=True)
        live_thread.start()
        t_storm = time.perf_counter()
        for _ in range(client_procs):
            procs.append(subprocess.Popen(
                [sys.executable, "-c", client_src, str(port),
                 str(per_proc)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                env=dict(os.environ, JAX_PLATFORMS="cpu")))
        answered = failed = 0
        for p in procs:
            line = p.stdout.readline().decode().split()
            if len(line) == 3 and line[0] == "READY":
                answered += int(line[1])
                failed += int(line[2])
            else:
                failed += per_proc
        accept_storm_s = time.perf_counter() - t_storm
        # the live subset proved the storm; stop it BEFORE the idle
        # window so the wakeup figure measures what HOLDING the
        # connections costs, not the flood
        live_stop.set()
        live_thread.join(timeout=10)
        time.sleep(0.5)  # settle: in-flight drains, pools quiesce
        wake0 = native.stats_counters().get("nat_dispatcher_wakeups", 0)
        time.sleep(idle_s)
        wake1 = native.stats_counters().get("nat_dispatcher_wakeups", 0)
        fd1 = len(os.listdir("/proc/self/fd"))
        res1 = {r["subsystem"]: r for r in native.res_stats()}
        held = int(lib.nat_rpc_server_connections())
        out.update({
            "conn_scale_answered": answered,
            "conn_scale_failed": failed,
            "conn_held": held,
            "conn_accept_storm_s": round(accept_storm_s, 2),
            # positive subsystem deltas only: pools of work that ran
            # before the drill may still be draining through it (the
            # attribution dict below keeps the signed per-subsystem
            # truth)
            "conn_per_conn_bytes": round(
                sum(max(0, res1[s]["live_bytes"] - res0[s]["live_bytes"])
                    for s in res1) / max(1, answered), 1),
            "conn_per_conn_fds": round((fd1 - fd0) / max(1, answered), 3),
            "conn_idle_wakeups_per_s": round(
                max(0, wake1 - wake0) / idle_s, 1),
            "conn_live_ok": live_ok[0],
            "conn_live_failed": live_fail[0],
            # where the bytes sit: per-subsystem live deltas over the
            # drill (the accounting's attribution, not a guess)
            "conn_mem_by_subsystem": {
                sub: int(res1[sub]["live_bytes"]
                         - res0[sub]["live_bytes"])
                for sub in res1
                if res1[sub]["live_bytes"] != res0[sub]["live_bytes"]},
        })
        # teardown + churn balance: close every client and wait for the
        # transient subsystems to return (socket slots recycle to the
        # freelist but their slabs stay live BY DESIGN — ResourcePool)
        for p in procs:
            try:
                p.stdin.close()
            except OSError:
                pass
        for p in procs:
            p.wait(timeout=60)
        deadline = time.time() + 30
        while time.time() < deadline and \
                int(lib.nat_rpc_server_connections()) > 4:
            time.sleep(0.1)
        time.sleep(0.5)
        res2 = {r["subsystem"]: r for r in native.res_stats()}
        leaks = {}
        for sub in ("srv.pyreq", "dump.spill"):
            d = res2[sub]["live_objects"] - res0[sub]["live_objects"]
            if d > max(8, answered * 0.01):
                leaks[sub] = int(d)
        out["conn_balance_leaked"] = leaks
        ok = (failed == 0 and answered == conns and live_fail[0] == 0
              and live_ok[0] > 0 and not leaks)
        out["conn_scale_conns"] = answered if ok else 0
    except Exception as e:  # a wedged drill reports, never raises
        out["conn_scale_error"] = repr(e)
        out["conn_scale_conns"] = 0
        live_stop.set()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        native.rpc_server_stop()
    return out


def _spawn_swarm_server(base: int, count: int, repo_root: str, env: dict):
    """One swarm backend process: a native echo server listening on
    `count` consecutive ports from `base`. Returns the Popen (READY
    already seen) or None when a port in the range was taken.

    BRPC_TPU_CHURN_FAULT (the chaos hook): when set, the SERVER
    process arms that NAT_FAULT spec at library load — the chaos lane's
    swarm round runs the whole drill with destructive seeds in the
    backends while the client side stays clean."""
    import os
    import subprocess
    import sys

    churn_spec = env.get("BRPC_TPU_CHURN_FAULT") or \
        os.environ.get("BRPC_TPU_CHURN_FAULT")
    if churn_spec:
        env = dict(env)
        env["NAT_FAULT"] = churn_spec
    # BRPC_TPU_SWARM_LIMITER (the fleet drill's hook): arm the native
    # admission limiter in the SERVER process ("constant:1", "auto", ...)
    # so a fleet drill can inject real ELIMIT overload on a member —
    # py-lane floods past the limit shed with 2004 on the wire while the
    # native echo path keeps serving
    limiter_spec = env.get("BRPC_TPU_SWARM_LIMITER") or \
        os.environ.get("BRPC_TPU_SWARM_LIMITER") or ""

    script = (
        "import os, signal, sys\n"
        "sys.path.insert(0, '.')\n"
        "from brpc_tpu import native\n"
        f"base, count = {base}, {count}\n"
        "try:\n"
        "    native.rpc_server_start('127.0.0.1', base, 2, True)\n"
        "    for p in range(base + 1, base + count):\n"
        "        native.rpc_server_add_port('127.0.0.1', p)\n"
        f"    if {limiter_spec!r}:\n"
        f"        native.rpc_server_limiter({limiter_spec!r})\n"
        "except Exception:\n"
        "    print('BINDFAIL', flush=True)\n"
        "    sys.exit(17)\n"
        "print('READY', flush=True)\n"
        "def _term(sig, frm):\n"
        "    native.server_quiesce(3000)\n"  # graceful: lame-duck + drain
        "    native.rpc_server_stop()\n"
        "    os._exit(0)\n"
        "signal.signal(signal.SIGTERM, _term)\n"
        "while True:\n"
        "    signal.pause()\n")
    proc = subprocess.Popen([sys.executable, "-c", script],
                            stdout=subprocess.PIPE, text=True,
                            cwd=repo_root, env=dict(env, JAX_PLATFORMS="cpu"))
    line = proc.stdout.readline().strip()
    if line != "READY":
        proc.kill()
        proc.wait(timeout=10)
        return None
    return proc


def fanout_swarm_bench(backends: int = 1000, servers: int = 3,
                       bench_seconds: float = 12.0,
                       concurrency: int = 4) -> dict:
    """The fan-out acceptance drill: a `backends`-port swarm
    (`servers` subprocesses, each hosting backends/servers native echo
    ports) behind one native cluster, churned by ROLLING SIGTERM
    restarts (graceful quiesce + lame-duck) and LIVE naming
    add/remove (the file naming service rewritten mid-run) while the
    selective-with-retry verb floods it from C threads. The contract is
    ZERO failed RPCs once failover/retry settles — a run with failures
    reports swarm_qps 0 — with the per-backend qps distribution
    recorded in the result.

    Also records fanout1000_qps: the parallel verb fanning one call to
    all `backends` backends (measured before the churn starts)."""
    import json as _json
    import os
    import signal as _signal
    import threading as _threading

    from brpc_tpu import native
    from brpc_tpu.rpc.native_cluster import NativeCluster

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    per = backends // servers
    out: dict = {}
    procs = []
    bases = []
    nf_path = None
    cluster = None
    try:
        base_candidates = [21000, 23000, 25000, 27000, 29000, 19000]
        ci = 0
        for _ in range(servers):
            proc = None
            while proc is None and ci < len(base_candidates):
                base = base_candidates[ci]
                ci += 1
                proc = _spawn_swarm_server(base, per, repo_root, env)
                if proc is not None:
                    procs.append(proc)
                    bases.append(base)
        if len(procs) < servers:
            raise RuntimeError("swarm port ranges unavailable")
        all_ports = [b + i for b in bases for i in range(per)]
        import tempfile

        nf = tempfile.NamedTemporaryFile("w", suffix=".swarm.ns",
                                         delete=False)
        nf_path = nf.name

        def write_naming(ports):
            with open(nf_path, "w") as f:
                for p in ports:
                    f.write(f"127.0.0.1:{p}\n")

        write_naming(all_ports)
        nf.close()
        cluster = NativeCluster(lb="rr", connect_timeout_ms=1000,
                                health_check_ms=200, breaker=True,
                                name="swarm")
        cluster.watch(f"file://{nf_path}")
        n = cluster.backend_count()
        out["swarm_backends"] = n

        # parallel fan-out to the WHOLE swarm (pre-churn): one verb =
        # `backends` concurrent sub-calls + native merge
        r1000 = cluster.bench(mode=1, param=0, seconds=1.0,
                              concurrency=2, timeout_ms=8000)
        out["fanout1000_qps"] = (0.0 if r1000["failed"]
                                 else round(r1000["qps"], 1))

        # churn window: selective flood from C threads while this thread
        # SIGTERMs each server in turn and flaps the naming file
        result: dict = {}

        def flood():
            result.update(cluster.bench(mode=0, param=12,
                                        seconds=bench_seconds,
                                        concurrency=concurrency,
                                        timeout_ms=5000))

        flood_t = _threading.Thread(target=flood)
        flood_t.start()
        time.sleep(0.5)
        # live naming remove (the tail 5% of backends)...
        drop = max(1, n // 20)
        write_naming(all_ports[:-drop])
        restarts = 0
        for i in range(len(procs)):
            procs[i].send_signal(_signal.SIGTERM)
            try:
                procs[i].wait(timeout=20)
            except Exception:
                procs[i].kill()
                procs[i].wait(timeout=10)
            fresh = _spawn_swarm_server(bases[i], per, repo_root, env)
            if fresh is None:
                break
            procs[i] = fresh
            restarts += 1
        # ...and live naming re-add
        write_naming(all_ports)
        flood_t.join(timeout=bench_seconds + 60)
        out["swarm_restarts"] = restarts
        out["swarm_calls"] = result.get("calls", 0)
        out["swarm_p99_us"] = round(result.get("p99_us", 0.0), 1)
        failed = result.get("failed", -1)
        out["swarm_failed"] = failed
        # the zero-failed contract IS the result
        out["swarm_qps"] = (round(result.get("qps", 0.0), 1)
                            if failed == 0 and restarts == len(procs)
                            else 0.0)
        # per-backend qps distribution (the evidence that the
        # LB spread the flood): selects quantiles across live backends
        selects = sorted(row["selects"] for row in cluster.stats())
        if selects:
            out["swarm_selects_per_backend"] = {
                "min": selects[0],
                "p50": selects[len(selects) // 2],
                "max": selects[-1],
            }
        out["swarm_stats_note"] = _json.dumps(
            {"servers": len(procs), "ports_per_server": per})
    finally:
        if cluster is not None:
            cluster.close()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
            try:
                proc.wait(timeout=10)
            except Exception:
                pass
        if nf_path is not None:
            try:
                os.unlink(nf_path)
            except OSError:
                pass
    return out
