// nat_abi — ABI manifest generator for the natcheck contract checker.
//
// Compiles against nat_api.h + nat_stats.h and prints, as JSON on stdout:
//   - sizeof/offsetof/field types of every struct shared with ctypes;
//   - the return/argument types of every exported extern "C" symbol.
// Types are stringified at compile time from the REAL declarations
// (decltype over the function pointers), so the manifest cannot drift from
// the header — and the header cannot drift from the definitions because
// every defining TU includes it. The Python half of the checker
// (tools/natcheck/abi.py) diffs this manifest against the ctypes layer and
// against `nm -D` of the built .so.
//
// Canonical type names (shared contract with tools/natcheck/abi.py):
//   i8 u8 i16 u16 i32 u32 i64 u64 f32 f64 char void fnptr
//   ptr:<T>  arr:<N>:<T>  struct:<Name>
#include <cstdio>
#include <cstddef>
#include <string>
#include <type_traits>
#include <vector>

#include "nat_api.h"
#include "nat_dump.h"
#include "nat_res.h"
#include "nat_stats.h"

namespace {

// Undefined primary template: an exported signature using a type not
// listed below is a COMPILE error here — extend the map (and the Python
// mirror in tools/natcheck/abi.py) instead of shipping an unchecked type.
template <typename T>
struct Ty;

#define NAT_TY(T, NAME) \
  template <>           \
  struct Ty<T> {        \
    static std::string get() { return NAME; } \
  }

NAT_TY(void, "void");
NAT_TY(char, "char");
NAT_TY(bool, "u8");
NAT_TY(signed char, "i8");
NAT_TY(unsigned char, "u8");
NAT_TY(short, "i16");
NAT_TY(unsigned short, "u16");
NAT_TY(int, "i32");
NAT_TY(unsigned int, "u32");
NAT_TY(long, "i64");
NAT_TY(unsigned long, "u64");
NAT_TY(long long, "i64");
NAT_TY(unsigned long long, "u64");
NAT_TY(float, "f32");
NAT_TY(double, "f64");
NAT_TY(brpc_tpu::NatSpanRec, "struct:NatSpanRec");
NAT_TY(brpc_tpu::NatMethodStatRow, "struct:NatMethodStatRow");
NAT_TY(brpc_tpu::NatConnRow, "struct:NatConnRow");
NAT_TY(brpc_tpu::NatLockRankRow, "struct:NatLockRankRow");
NAT_TY(brpc_tpu::NatDumpStatusRec, "struct:NatDumpStatusRec");
NAT_TY(brpc_tpu::NatReplayResult, "struct:NatReplayResult");
NAT_TY(brpc_tpu::NatClusterRow, "struct:NatClusterRow");
NAT_TY(brpc_tpu::NatResRow, "struct:NatResRow");
#undef NAT_TY

template <typename T>
struct Ty<T*> {
  static std::string get() {
    return "ptr:" + Ty<typename std::remove_cv<T>::type>::get();
  }
};

template <typename T, size_t N>
struct Ty<T[N]> {
  static std::string get() {
    return "arr:" + std::to_string(N) + ":" +
           Ty<typename std::remove_cv<T>::type>::get();
  }
};

// Function pointers collapse to "fnptr": the ctypes side passes CFUNCTYPE
// thunks (or void*), and pointer width is all the FFI boundary sees.
template <typename R, typename... A>
struct Ty<R (*)(A...)> {
  static std::string get() { return "fnptr"; }
};

template <typename T>
struct Sig;

template <typename R, typename... A>
struct Sig<R (*)(A...)> {
  static std::string get() {
    std::string s = "{\"ret\":\"" + Ty<R>::get() + "\",\"args\":[";
    const std::vector<std::string> args = {Ty<A>::get()...};
    for (size_t i = 0; i < args.size(); i++) {
      if (i) s += ",";
      s += "\"" + args[i] + "\"";
    }
    s += "]}";
    return s;
  }
};

struct FieldRow {
  const char* name;
  size_t offset;
  size_t size;
  std::string type;
};

void print_struct(const char* name, size_t size,
                  const std::vector<FieldRow>& fields, bool last) {
  printf("    \"%s\": {\"size\": %zu, \"fields\": [\n", name, size);
  for (size_t i = 0; i < fields.size(); i++) {
    printf("      [\"%s\", %zu, %zu, \"%s\"]%s\n", fields[i].name,
           fields[i].offset, fields[i].size, fields[i].type.c_str(),
           i + 1 < fields.size() ? "," : "");
  }
  printf("    ]}%s\n", last ? "" : ",");
}

}  // namespace

int main() {
  printf("{\n  \"abi_version\": 1,\n  \"pointer_size\": %zu,\n",
         sizeof(void*));

  // ---- shared structs ----------------------------------------------------
  // Field lists reference the real members (offsetof + decltype): a
  // removed/renamed field breaks this build, a reorder changes offsets, an
  // added field changes sizeof — all surface as manifest/ctypes diffs.
  printf("  \"structs\": {\n");
  using brpc_tpu::NatClusterRow;
  using brpc_tpu::NatConnRow;
  using brpc_tpu::NatDumpStatusRec;
  using brpc_tpu::NatLockRankRow;
  using brpc_tpu::NatMethodStatRow;
  using brpc_tpu::NatReplayResult;
  using brpc_tpu::NatResRow;
  using brpc_tpu::NatSpanRec;
#define NAT_FIELD(S, F) \
  FieldRow { #F, offsetof(S, F), sizeof(S::F), Ty<decltype(S::F)>::get() }
  print_struct("NatSpanRec", sizeof(NatSpanRec),
               {
                   NAT_FIELD(NatSpanRec, trace_id),
                   NAT_FIELD(NatSpanRec, span_id),
                   NAT_FIELD(NatSpanRec, parent_span_id),
                   NAT_FIELD(NatSpanRec, sock_id),
                   NAT_FIELD(NatSpanRec, recv_ns),
                   NAT_FIELD(NatSpanRec, parse_ns),
                   NAT_FIELD(NatSpanRec, dispatch_ns),
                   NAT_FIELD(NatSpanRec, write_ns),
                   NAT_FIELD(NatSpanRec, protocol),
                   NAT_FIELD(NatSpanRec, error_code),
                   NAT_FIELD(NatSpanRec, req_bytes),
                   NAT_FIELD(NatSpanRec, resp_bytes),
                   NAT_FIELD(NatSpanRec, method),
               },
               false);
  print_struct("NatMethodStatRow", sizeof(NatMethodStatRow),
               {
                   NAT_FIELD(NatMethodStatRow, count),
                   NAT_FIELD(NatMethodStatRow, errors),
                   NAT_FIELD(NatMethodStatRow, concurrency),
                   NAT_FIELD(NatMethodStatRow, max_concurrency),
                   NAT_FIELD(NatMethodStatRow, lane),
                   NAT_FIELD(NatMethodStatRow, method),
               },
               false);
  print_struct("NatConnRow", sizeof(NatConnRow),
               {
                   NAT_FIELD(NatConnRow, sock_id),
                   NAT_FIELD(NatConnRow, in_bytes),
                   NAT_FIELD(NatConnRow, out_bytes),
                   NAT_FIELD(NatConnRow, in_msgs),
                   NAT_FIELD(NatConnRow, out_msgs),
                   NAT_FIELD(NatConnRow, read_calls),
                   NAT_FIELD(NatConnRow, write_calls),
                   NAT_FIELD(NatConnRow, unwritten_bytes),
                   NAT_FIELD(NatConnRow, mem_bytes),
                   NAT_FIELD(NatConnRow, fd),
                   NAT_FIELD(NatConnRow, disp_idx),
                   NAT_FIELD(NatConnRow, server_side),
                   NAT_FIELD(NatConnRow, protocol),
                   NAT_FIELD(NatConnRow, remote),
               },
               false);
  print_struct("NatResRow", sizeof(NatResRow),
               {
                   NAT_FIELD(NatResRow, live_bytes),
                   NAT_FIELD(NatResRow, live_objects),
                   NAT_FIELD(NatResRow, cum_allocs),
                   NAT_FIELD(NatResRow, cum_frees),
                   NAT_FIELD(NatResRow, cum_alloc_bytes),
                   NAT_FIELD(NatResRow, hwm_bytes),
                   NAT_FIELD(NatResRow, name),
               },
               false);
  print_struct("NatLockRankRow", sizeof(NatLockRankRow),
               {
                   NAT_FIELD(NatLockRankRow, waits),
                   NAT_FIELD(NatLockRankRow, wait_us),
                   NAT_FIELD(NatLockRankRow, rank),
                   NAT_FIELD(NatLockRankRow, name),
               },
               false);
  print_struct("NatDumpStatusRec", sizeof(NatDumpStatusRec),
               {
                   NAT_FIELD(NatDumpStatusRec, samples),
                   NAT_FIELD(NatDumpStatusRec, written),
                   NAT_FIELD(NatDumpStatusRec, bytes),
                   NAT_FIELD(NatDumpStatusRec, drops),
                   NAT_FIELD(NatDumpStatusRec, oversize),
                   NAT_FIELD(NatDumpStatusRec, rotations),
                   NAT_FIELD(NatDumpStatusRec, max_file_bytes),
                   NAT_FIELD(NatDumpStatusRec, max_payload),
                   NAT_FIELD(NatDumpStatusRec, seed),
                   NAT_FIELD(NatDumpStatusRec, every),
                   NAT_FIELD(NatDumpStatusRec, running),
                   NAT_FIELD(NatDumpStatusRec, generations),
                   NAT_FIELD(NatDumpStatusRec, dir),
               },
               false);
  print_struct("NatReplayResult", sizeof(NatReplayResult),
               {
                   NAT_FIELD(NatReplayResult, loaded),
                   NAT_FIELD(NatReplayResult, sent),
                   NAT_FIELD(NatReplayResult, ok),
                   NAT_FIELD(NatReplayResult, failed),
                   NAT_FIELD(NatReplayResult, skipped),
                   NAT_FIELD(NatReplayResult, seconds),
                   NAT_FIELD(NatReplayResult, qps),
                   NAT_FIELD(NatReplayResult, p50_us),
                   NAT_FIELD(NatReplayResult, p99_us),
               },
               false);
  print_struct("NatClusterRow", sizeof(NatClusterRow),
               {
                   NAT_FIELD(NatClusterRow, selects),
                   NAT_FIELD(NatClusterRow, errors),
                   NAT_FIELD(NatClusterRow, inflight),
                   NAT_FIELD(NatClusterRow, ema_latency_us),
                   NAT_FIELD(NatClusterRow, weight),
                   NAT_FIELD(NatClusterRow, breaker_open),
                   NAT_FIELD(NatClusterRow, lame_duck),
                   NAT_FIELD(NatClusterRow, part_index),
                   NAT_FIELD(NatClusterRow, part_total),
                   NAT_FIELD(NatClusterRow, endpoint),
                   NAT_FIELD(NatClusterRow, tag),
               },
               true);
#undef NAT_FIELD
  printf("  },\n");

  // ---- exported symbols --------------------------------------------------
  printf("  \"symbols\": {\n");
  struct SymRow {
    const char* name;
    std::string sig;
  };
  const std::vector<SymRow> syms = {
#define NAT_SYM(fn) SymRow{#fn, Sig<decltype(&fn)>::get()}
      NAT_SYM(nat_sched_start),
      NAT_SYM(nat_sched_stop),
      NAT_SYM(nat_sched_workers),
      NAT_SYM(nat_sched_switches),
      NAT_SYM(nat_sched_trim_stacks),
      NAT_SYM(nat_bench_spawn_join),
      NAT_SYM(nat_bench_ping_pong),
      NAT_SYM(nat_wsq_selftest),
      NAT_SYM(nat_iobuf_selftest),
      NAT_SYM(nat_meta_selftest),
      NAT_SYM(nat_echo_server_start),
      NAT_SYM(nat_echo_server_stop),
      NAT_SYM(nat_echo_server_requests),
      NAT_SYM(nat_echo_client_bench),
      NAT_SYM(nat_io_counters),
      NAT_SYM(nat_rpc_set_dispatchers),
      NAT_SYM(nat_rpc_server_start),
      NAT_SYM(nat_rpc_server_stop),
      NAT_SYM(nat_rpc_server_enable_raw_fallback),
      NAT_SYM(nat_rpc_server_native_http),
      NAT_SYM(nat_rpc_server_redis),
      NAT_SYM(nat_rpc_server_requests),
      NAT_SYM(nat_rpc_server_connections),
      NAT_SYM(nat_rpc_use_io_uring),
      NAT_SYM(nat_ring_counters),
      NAT_SYM(nat_disp_count),
      NAT_SYM(nat_disp_stat),
      NAT_SYM(nat_take_request),
      NAT_SYM(nat_take_request_batch),
      NAT_SYM(nat_req_kind),
      NAT_SYM(nat_req_field),
      NAT_SYM(nat_req_cid),
      NAT_SYM(nat_req_aux),
      NAT_SYM(nat_req_compress),
      NAT_SYM(nat_req_sock_id),
      NAT_SYM(nat_req_free),
      NAT_SYM(nat_respond),
      NAT_SYM(nat_sock_write),
      NAT_SYM(nat_sock_set_failed),
      NAT_SYM(nat_http_respond),
      NAT_SYM(nat_sock_graceful_close),
      NAT_SYM(nat_grpc_respond),
      NAT_SYM(nat_redis_respond),
      NAT_SYM(nat_rpc_server_ssl),
      NAT_SYM(nat_rpc_server_limiter),
      NAT_SYM(nat_rpc_server_queue_deadline_ms),
      NAT_SYM(nat_rpc_server_inflight),
      NAT_SYM(nat_rpc_server_limit),
      NAT_SYM(nat_server_quiesce),
      NAT_SYM(nat_server_draining),
      NAT_SYM(nat_fault_configure),
      NAT_SYM(nat_fault_enabled),
      NAT_SYM(nat_fault_injected),
      NAT_SYM(nat_channel_open),
      NAT_SYM(nat_channel_open_proto),
      NAT_SYM(nat_channel_close),
      NAT_SYM(nat_channel_call),
      NAT_SYM(nat_channel_call_full),
      NAT_SYM(nat_channel_acall),
      NAT_SYM(nat_channel_set_breaker),
      NAT_SYM(nat_channel_breaker_state),
      NAT_SYM(nat_channel_retry_budget),
      NAT_SYM(nat_buf_free),
      NAT_SYM(nat_http_call),
      NAT_SYM(nat_http_acall),
      NAT_SYM(nat_grpc_call),
      NAT_SYM(nat_grpc_acall),
      NAT_SYM(nat_rpc_client_bench),
      NAT_SYM(nat_rpc_client_bench_async),
      NAT_SYM(nat_rpc_client_bench_bulk),
      NAT_SYM(nat_http_client_bench),
      NAT_SYM(nat_grpc_client_bench),
      NAT_SYM(nat_redis_client_bench),
      NAT_SYM(nat_grpc_channel_bench),
      NAT_SYM(nat_http_channel_bench),
      NAT_SYM(nat_shm_lane_create),
      NAT_SYM(nat_shm_lane_max_workers),
      NAT_SYM(nat_shm_lane_workers),
      NAT_SYM(nat_shm_lane_name),
      NAT_SYM(nat_shm_lane_enable),
      NAT_SYM(nat_shm_lane_set_timeout_ms),
      NAT_SYM(nat_shm_lane_recover_probe),
      NAT_SYM(nat_shm_seg_validate),
      NAT_SYM(nat_shm_worker_attach),
      NAT_SYM(nat_shm_take_request),
      NAT_SYM(nat_shm_respond),
      NAT_SYM(nat_shm_push_tensor),
      NAT_SYM(nat_shm_producer_attach),
      NAT_SYM(nat_shm_fabric_push),
      NAT_SYM(nat_shm_fabric_take),
      NAT_SYM(nat_shm_push_bench),
      NAT_SYM(nat_shm_worker_drain_bench),
      NAT_SYM(nat_stats_counter_count),
      NAT_SYM(nat_stats_now_ns),
      NAT_SYM(nat_stats_counter_name),
      NAT_SYM(nat_stats_counters),
      NAT_SYM(nat_stats_counter_bump),
      NAT_SYM(nat_stats_lane_count),
      NAT_SYM(nat_stats_lane_name),
      NAT_SYM(nat_stats_hist_nbuckets),
      NAT_SYM(nat_stats_hist),
      NAT_SYM(nat_stats_hist_quantile),
      NAT_SYM(nat_stats_enable_spans),
      NAT_SYM(nat_stats_drain_spans),
      NAT_SYM(nat_stats_reset),
      NAT_SYM(nat_trace_set),
      NAT_SYM(nat_method_stats),
      NAT_SYM(nat_method_quantile),
      NAT_SYM(nat_method_hist),
      NAT_SYM(nat_stats_snapshot),
      NAT_SYM(nat_conn_snapshot),
      NAT_SYM(nat_mu_prof_start),
      NAT_SYM(nat_mu_prof_stop),
      NAT_SYM(nat_mu_prof_running),
      NAT_SYM(nat_mu_prof_samples),
      NAT_SYM(nat_mu_prof_reset),
      NAT_SYM(nat_mu_prof_reset_samples),
      NAT_SYM(nat_mu_prof_report),
      NAT_SYM(nat_mu_rank_stats),
      NAT_SYM(nat_mu_rank_name),
      NAT_SYM(nat_mu_contend_selftest),
      NAT_SYM(nat_refguard_enabled),
      NAT_SYM(nat_refguard_ops),
      NAT_SYM(nat_refguard_selftest),
      NAT_SYM(nat_dump_start),
      NAT_SYM(nat_dump_stop),
      NAT_SYM(nat_dump_running),
      NAT_SYM(nat_dump_status),
      NAT_SYM(nat_replay_run),
      NAT_SYM(nat_rpc_server_add_port),
      NAT_SYM(nat_rpc_server_remove_port),
      NAT_SYM(nat_cluster_create),
      NAT_SYM(nat_cluster_close),
      NAT_SYM(nat_cluster_update),
      NAT_SYM(nat_cluster_backend_count),
      NAT_SYM(nat_cluster_select_debug),
      NAT_SYM(nat_cluster_call),
      NAT_SYM(nat_cluster_parallel_call),
      NAT_SYM(nat_cluster_partition_call),
      NAT_SYM(nat_cluster_dynpart_call),
      NAT_SYM(nat_cluster_dynpart_debug),
      NAT_SYM(nat_cluster_stats),
      NAT_SYM(nat_cluster_bench),
      NAT_SYM(nat_res_count),
      NAT_SYM(nat_res_name),
      NAT_SYM(nat_res_stats),
      NAT_SYM(nat_res_accounted_bytes),
      NAT_SYM(nat_res_prof_start),
      NAT_SYM(nat_res_prof_stop),
      NAT_SYM(nat_res_prof_running),
      NAT_SYM(nat_res_prof_samples),
      NAT_SYM(nat_res_prof_reset),
      NAT_SYM(nat_res_heap_report),
      NAT_SYM(nat_res_growth_baseline),
      NAT_SYM(nat_res_growth_report),
      NAT_SYM(nat_res_selftest),
      NAT_SYM(nat_prof_start),
      NAT_SYM(nat_prof_stop),
      NAT_SYM(nat_prof_running),
      NAT_SYM(nat_prof_samples),
      NAT_SYM(nat_prof_reset),
      NAT_SYM(nat_prof_report),
      NAT_SYM(nat_fuzz_rpc_meta),
      NAT_SYM(nat_fuzz_http),
      NAT_SYM(nat_fuzz_h2),
      NAT_SYM(nat_fuzz_redis),
      NAT_SYM(nat_fuzz_hpack),
      NAT_SYM(nat_fuzz_recordio),
      NAT_SYM(nat_fuzz_shm_seg),
#undef NAT_SYM
  };
  for (size_t i = 0; i < syms.size(); i++) {
    printf("    \"%s\": %s%s\n", syms[i].name, syms[i].sig.c_str(),
           i + 1 < syms.size() ? "," : "");
  }
  printf("  }\n}\n");
  return 0;
}
