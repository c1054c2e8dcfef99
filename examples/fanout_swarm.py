"""fanout_swarm — the native fan-out demo (ISSUE 13, ROADMAP item 1).

Spins N in-process backends (one native echo server listening on N
ports — the multi-port swarm seam), puts a native PartitionChannel and
a native cluster in front of them with a LIVE file naming service, then
demonstrates the three things the native fan-out core exists for:

  1. parallel fan-out + native merge across every backend (the
     ParallelChannel verb: one call, N concurrent sub-calls on fibers,
     responses merged in C++);
  2. live naming updates: the server-list file is rewritten while
     selective traffic flows — the DoublyBufferedData swap + reader
     quiesce re-balances with zero dropped calls;
  3. a rolling-restart loop: listeners are removed and re-added port by
     port while a selective flood runs — the per-backend breakers,
     transport cool-downs and failover retry keep every RPC whole.

Run:  python examples/fanout_swarm.py [--backends 16] [--seconds 6]
"""
import argparse
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from brpc_tpu import native  # noqa: E402
from brpc_tpu.rpc.combo_channels import PartitionChannel  # noqa: E402
from brpc_tpu.rpc.native_cluster import NativeCluster  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backends", type=int, default=16)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args()

    # --- the swarm: one native echo server, N listening ports ---------
    port = native.rpc_server_start(native_echo=True)
    ports = [port] + [native.rpc_server_add_port()
                      for _ in range(args.backends - 1)]
    print(f"swarm: {len(ports)} backends "
          f"(ports {ports[0]}..{ports[-1]})")

    # --- live naming: a server-list file the watcher re-reads ---------
    nf = tempfile.NamedTemporaryFile("w", suffix=".swarm.ns",
                                     delete=False)

    def write_naming(plist, partitioned=False):
        with open(nf.name, "w") as f:
            for i, p in enumerate(plist):
                tag = f" {i % 4}/4" if partitioned else ""
                f.write(f"127.0.0.1:{p}{tag}\n")

    write_naming(ports)
    nf.close()

    try:
        # --- 1. parallel fan-out + native merge -----------------------
        with NativeCluster(lb="rr", name="swarm-demo") as cluster:
            cluster.watch(f"file://{nf.name}")
            rc, body, err, failed = cluster.parallel_call(
                "EchoService.Echo", b"ping", timeout_ms=3000)
            assert rc == 0, err
            print(f"parallel fan-out: {cluster.backend_count()} "
                  f"backends answered in one call "
                  f"(merged {len(body)} bytes, {failed} failed)")

            # --- 2. live naming updates under selective traffic -------
            stop = threading.Event()
            stats = {"calls": 0, "failed": 0}

            def flood():
                while not stop.is_set():
                    rc, _, _ = cluster.call("EchoService.Echo", b"x",
                                            timeout_ms=3000, max_retry=8)
                    stats["calls"] += 1
                    if rc != 0:
                        stats["failed"] += 1

            t = threading.Thread(target=flood)
            t.start()
            deadline = time.time() + args.seconds

            # shrink + regrow the naming file while traffic flows
            write_naming(ports[: len(ports) // 2])
            time.sleep(min(2.5, args.seconds / 2))
            write_naming(ports)

            # --- 3. rolling restarts: remove + re-add listeners -------
            restarted = 0
            while time.time() < deadline and restarted < len(ports) - 1:
                victim = ports[1 + restarted % (len(ports) - 1)]
                native.rpc_server_remove_port(victim)
                time.sleep(0.05)
                native.rpc_server_add_port(port=victim)
                restarted += 1
            stop.set()
            t.join()
            print(f"churn window: {stats['calls']} selective calls, "
                  f"{stats['failed']} failed, {restarted} listener "
                  f"restarts, live naming shrink+regrow")

            spread = sorted(r["selects"] for r in cluster.stats())
            print(f"per-backend selects: min={spread[0]} "
                  f"p50={spread[len(spread) // 2]} max={spread[-1]}")

        # --- the combo-channel face: a native PartitionChannel --------
        write_naming(ports, partitioned=True)
        prt = PartitionChannel(native=True)
        assert prt.init(4, f"file://{nf.name}") == 0
        try:
            from brpc_tpu import rpc
            from brpc_tpu.rpc.proto import echo_pb2

            cntl = rpc.Controller()
            cntl.timeout_ms = 3000
            resp = echo_pb2.EchoResponse()
            prt.call_method("EchoService.Echo", cntl,
                            echo_pb2.EchoRequest(message="sharded"),
                            resp)
            assert not cntl.failed(), cntl.error_text
            print(f"native PartitionChannel (4-way '{'i/4'}' tags): "
                  f"merged response message={resp.message!r}")
        finally:
            prt.stop()
    finally:
        os.unlink(nf.name)
        native.rpc_server_stop()
    print("ok")


if __name__ == "__main__":
    main()
