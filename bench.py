"""Benchmark driver — prints ONE JSON line with the headline metric.

Headline (BASELINE.md): echo RPC throughput through the native core (the
multi_threaded_echo analog), reported as QPS vs the reference's 500k QPS
production claim (docs/en/overview.md:88).

JSON schema (one line on stdout):
  metric / value / unit / vs_baseline  — the headline figure
  extra.*_qps                          — per-lane throughput
  extra.native_latency_us              — per-lane tail latency from the
      native log2 histograms (nat_stats.cpp), keyed by lane
      (echo/http/redis/grpc/client), each {p50, p99, p999} in
      microseconds measured parse-complete -> response-write (server
      lanes) or call-begin -> completion (client lane)
  extra.device_lanes                   — device-transport GB/s rows
      (incl. shm_push_* over the descriptor-ring fabric,
      and the read_arena_grow_GBps prefault-on-grow regression row)
  extra.scaling                        — with --cpus N: the per-core
      scaling curve {"1": qps, ..., "N": qps, "cpu_sets": ...} from
      taskset-pinned two-process echo runs; server and client runtimes
      are pinned to DISJOINT cpu sets from 2 cpus up (schema note: the
      in-process lanes above keep sharing cores — the curve is the
      interference-free measurement). The bench gate derives
      cpus2_scaling_x = qps(2)/qps(1) and bands it like any lane.
The process must exit 0: the artifact of record is untrustworthy if the
bench dies at teardown (BENCH_r05 rc 139).
"""
import argparse
import json


def bench_echo():
    """Echo QPS over loopback through the native C++ data path (the
    multi_threaded_echo analog). The native core must build: a failed
    build raises with the compiler's error."""
    from brpc_tpu import native
    from brpc_tpu.bench import framework_echo_bench

    native.load()
    return framework_echo_bench()


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cpus", type=int, default=0, metavar="N",
                    help="record a per-core scaling curve at {1..N} cpus "
                         "(taskset-pinned two-process echo lane) into "
                         "extra.scaling")
    ap.add_argument("--conn-scale", type=int, default=None, metavar="N",
                    help="override the connection-scale drill's target "
                         "connection count (default 20000, clamped to "
                         "RLIMIT_NOFILE; 0 disables the lane)")
    args = ap.parse_args()
    if args.conn_scale is not None:
        import os

        os.environ["BRPC_TPU_CONN_SCALE"] = str(args.conn_scale)
    from brpc_tpu import compile_cache

    compile_cache.enable()
    result = bench_echo()
    # device-side figure riding the extras (the rdma_performance north
    # star): achieved allreduce bandwidth — only meaningful on a REAL
    # multi-device mesh (one device moves zero inter-chip bytes)
    import jax

    if len(jax.devices()) > 1:
        from brpc_tpu.bench import collective_bench

        coll = collective_bench(nbytes=1 << 24, iters=10)
        result.setdefault("extra", {})["allreduce_GBps"] = coll["value"]
    # multicore scaling curve (--cpus N): qps at {1..N} cpus, pinned
    # server/client processes — sublinear scaling is a bench-gate finding
    if args.cpus > 0:
        from brpc_tpu.bench import scaling_bench

        result.setdefault("extra", {})["scaling"] = scaling_bench(args.cpus)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
