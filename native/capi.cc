// C API for Python ctypes bindings (torchft_tpu/coordination.py).
// Role-equivalent of the reference's pyo3 extension module src/lib.rs:
// server lifecycles + blocking client RPCs. ctypes releases the GIL around
// every call, matching the reference's py.allow_threads behavior.
//
// Conventions: returns int status (see TFT_* codes); out-strings are
// malloc'd and must be freed with tft_free.

#include <cstdlib>
#include <cstring>
#include <string>

#include "aggregator.h"
#include "healthwatch.h"
#include "history.h"
#include "kvstore.h"
#include "lighthouse.h"
#include "manager_server.h"
#include "net.h"
#include "quorum.h"
#include "reduce.h"
#include "wire.h"

using namespace tft;

extern "C" {

enum {
  TFT_OK = 0,
  TFT_TIMEOUT = 1,
  TFT_ERROR = 2,
  TFT_NOT_FOUND = 3,
  TFT_INVALID = 4,
  TFT_UNAVAILABLE = 5,
};

static char* dup_str(const std::string& s) {
  char* out = static_cast<char*>(malloc(s.size() + 1));
  memcpy(out, s.data(), s.size());
  out[s.size()] = '\0';
  return out;
}

static int status_of(const RpcError& e) {
  if (e.code == "timeout") return TFT_TIMEOUT;
  if (e.code == "not_found") return TFT_NOT_FOUND;
  if (e.code == "invalid") return TFT_INVALID;
  if (e.code == "unavailable") return TFT_UNAVAILABLE;
  return TFT_ERROR;
}

#define TFT_TRY(...)                                    \
  try {                                                 \
    __VA_ARGS__;                                        \
  } catch (const RpcError& e) {                         \
    if (err) *err = dup_str(e.what());                  \
    return status_of(e);                                \
  } catch (const std::exception& e) {                   \
    if (err) *err = dup_str(e.what());                  \
    std::string msg = e.what();                         \
    return msg.find("timed out") != std::string::npos   \
               ? TFT_TIMEOUT                            \
               : TFT_ERROR;                             \
  }

void tft_free(char* p) { free(p); }

// ---- host ring frames (process_group.py) ----
// A whole buffer over a Python socket's fd, outside the interpreter: 0
// done, 1 the peer made no progress for idle_ms, 2 closed, else -errno.
int tft_fd_send_all(int fd, const void* data, size_t len, int64_t idle_ms,
                    int more) {
  return fd_send_all(fd, data, len, idle_ms, more != 0);
}
int tft_fd_recv_all(int fd, void* data, size_t len, int64_t idle_ms) {
  return fd_recv_all(fd, data, len, idle_ms);
}

// ---- host ring reductions (process_group.py) ----
// dst += src over n bfloat16 elements, bit for bit ml_dtypes' add.
void tft_bf16_add(void* dst, const void* src, size_t n) {
  bf16_add(static_cast<uint16_t*>(dst), static_cast<const uint16_t*>(src), n);
}

// ---------------------------------------------------------------- lighthouse
int tft_lighthouse_new(const char* bind, int64_t min_replicas,
                       int64_t join_timeout_ms, int64_t quorum_tick_ms,
                       int64_t heartbeat_timeout_ms, void** out, char** err) {
  TFT_TRY({
    LighthouseOpts opts;
    opts.min_replicas = min_replicas;
    opts.join_timeout_ms = join_timeout_ms;
    opts.quorum_tick_ms = quorum_tick_ms;
    opts.heartbeat_timeout_ms = heartbeat_timeout_ms;
    *out = new Lighthouse(bind, opts);
    return TFT_OK;
  })
}

// JSON-opts constructor (supersedes the scalar one above, which is kept for
// ABI compat): {"bind": ..., "min_replicas": N, "join_timeout_ms": N,
// "quorum_tick_ms": N, "heartbeat_timeout_ms": N, "health": {...}} — the
// "health" object is HealthOpts (healthwatch.h), absent -> defaults
// (observe mode).
int tft_lighthouse_new_v2(const char* opts_json, void** out, char** err) {
  TFT_TRY({
    Json j = Json::parse(opts_json);
    LighthouseOpts opts;
    std::string bind = j.get_or("bind", Json("0.0.0.0:0")).as_string();
    opts.min_replicas = j.get_or("min_replicas", Json(int64_t{1})).as_int();
    opts.join_timeout_ms =
        j.get_or("join_timeout_ms", Json(int64_t{60000})).as_int();
    opts.quorum_tick_ms =
        j.get_or("quorum_tick_ms", Json(int64_t{100})).as_int();
    opts.heartbeat_timeout_ms =
        j.get_or("heartbeat_timeout_ms", Json(int64_t{5000})).as_int();
    opts.history_path = j.get_or("history_path", Json("")).as_string();
    opts.policy_ring = j.get_or("policy_ring", Json(int64_t{0})).as_int();
    opts.metrics_per_replica_limit =
        j.get_or("metrics_per_replica_limit", Json(int64_t{64})).as_int();
    HealthOpts health =
        HealthOpts::from_json(j.get_or("health", Json::object()));
    *out = new Lighthouse(bind, opts, health);
    return TFT_OK;
  })
}

// ---- policy plane: in-process control surface on the lighthouse handle.
// These are C-API calls for the co-located policy engine, NOT wire RPCs —
// the wire protocol stays at its five methods; frames ride existing
// heartbeat/agg_tick replies.
int tft_lighthouse_set_policy(void* h, const char* frame_json, char** err) {
  TFT_TRY({
    static_cast<Lighthouse*>(h)->set_policy(Json::parse(frame_json));
    return TFT_OK;
  })
}

char* tft_lighthouse_policy(void* h) {
  return dup_str(static_cast<Lighthouse*>(h)->policy_json());
}

char* tft_lighthouse_drain_events(void* h) {
  return dup_str(static_cast<Lighthouse*>(h)->drain_events());
}

int tft_lighthouse_retune_health(void* h, const char* partial_json, char** out,
                                 char** err) {
  TFT_TRY({
    *out = dup_str(
        static_cast<Lighthouse*>(h)->retune_health(Json::parse(partial_json)));
    return TFT_OK;
  })
}

char* tft_lighthouse_address(void* h) {
  return dup_str(static_cast<Lighthouse*>(h)->address());
}
int tft_lighthouse_port(void* h) { return static_cast<Lighthouse*>(h)->port(); }
void tft_lighthouse_shutdown(void* h) {
  static_cast<Lighthouse*>(h)->shutdown();
}
void tft_lighthouse_free(void* h) { delete static_cast<Lighthouse*>(h); }

// ---------------------------------------------------------------- aggregator
// Pod-level lighthouse aggregator (aggregator.h). opts_json: {"bind": ...,
// "root_addr": ..., "agg_id": ...?, "tick_ms": N, "heartbeat_timeout_ms": N,
// "connect_timeout_ms": N}.
int tft_aggregator_new(const char* opts_json, void** out, char** err) {
  TFT_TRY({
    Json j = Json::parse(opts_json);
    AggregatorOpts opts;
    std::string bind = j.get_or("bind", Json("0.0.0.0:0")).as_string();
    opts.root_addr = j.get("root_addr").as_string();
    opts.agg_id = j.get_or("agg_id", Json("")).as_string();
    opts.tick_ms = j.get_or("tick_ms", Json(int64_t{100})).as_int();
    opts.heartbeat_timeout_ms =
        j.get_or("heartbeat_timeout_ms", Json(int64_t{5000})).as_int();
    opts.connect_timeout_ms =
        j.get_or("connect_timeout_ms", Json(int64_t{10000})).as_int();
    *out = new Aggregator(bind, opts);
    return TFT_OK;
  })
}

char* tft_aggregator_address(void* h) {
  return dup_str(static_cast<Aggregator*>(h)->address());
}
int tft_aggregator_port(void* h) { return static_cast<Aggregator*>(h)->port(); }
char* tft_aggregator_status(void* h) {
  return dup_str(static_cast<Aggregator*>(h)->status_json().dump());
}
void tft_aggregator_shutdown(void* h) {
  static_cast<Aggregator*>(h)->shutdown();
}
void tft_aggregator_free(void* h) { delete static_cast<Aggregator*>(h); }

// ------------------------------------------------------------------- manager
int tft_manager_new(const char* opts_json, void** out, char** err) {
  TFT_TRY({
    Json j = Json::parse(opts_json);
    ManagerOpts opts;
    opts.replica_id = j.get("replica_id").as_string();
    opts.lighthouse_addr = j.get("lighthouse_addr").as_string();
    opts.hostname = j.get_or("hostname", Json("")).as_string();
    opts.bind = j.get_or("bind", Json("0.0.0.0:0")).as_string();
    opts.store_addr = j.get_or("store_addr", Json("")).as_string();
    opts.world_size = j.get_or("world_size", Json(int64_t{1})).as_int();
    opts.heartbeat_interval_ms =
        j.get_or("heartbeat_interval_ms", Json(int64_t{100})).as_int();
    opts.connect_timeout_ms =
        j.get_or("connect_timeout_ms", Json(int64_t{10000})).as_int();
    opts.quorum_retries = j.get_or("quorum_retries", Json(int64_t{0})).as_int();
    opts.aggregator_addr = j.get_or("aggregator_addr", Json("")).as_string();
    *out = new ManagerServer(opts);
    return TFT_OK;
  })
}

char* tft_manager_control_status(void* h) {
  return dup_str(static_cast<ManagerServer*>(h)->control_status_json());
}

int tft_manager_publish_telemetry(void* h, const char* telemetry_json,
                                  char** err) {
  TFT_TRY({
    static_cast<ManagerServer*>(h)->publish_telemetry(telemetry_json);
    return TFT_OK;
  })
}

char* tft_manager_health(void* h) {
  return dup_str(static_cast<ManagerServer*>(h)->health_json());
}

char* tft_manager_policy(void* h) {
  return dup_str(static_cast<ManagerServer*>(h)->policy_json());
}

char* tft_manager_clock_skew(void* h) {
  return dup_str(static_cast<ManagerServer*>(h)->clock_skew_json());
}

char* tft_manager_address(void* h) {
  return dup_str(static_cast<ManagerServer*>(h)->address());
}
int tft_manager_port(void* h) { return static_cast<ManagerServer*>(h)->port(); }
void tft_manager_shutdown(void* h) {
  static_cast<ManagerServer*>(h)->shutdown();
}
void tft_manager_free(void* h) { delete static_cast<ManagerServer*>(h); }

// ------------------------------------------------------------------- clients
// Client handles own a persistent RpcClient: its cached keep-alive
// connection is reused across calls (reconnecting if stale), and concurrent
// calls from other threads transparently fall back to one-shot connections.
struct ClientHandle {
  RpcClient client;
  ClientHandle(const char* addr, int64_t connect_timeout_ms)
      : client(addr, Millis(connect_timeout_ms)) {}
};

int tft_client_new(const char* addr, int64_t connect_timeout_ms, void** out,
                   char** err) {
  TFT_TRY({
    *out = new ClientHandle(addr, connect_timeout_ms);
    return TFT_OK;
  })
}
void tft_client_free(void* h) { delete static_cast<ClientHandle*>(h); }

// Generic call: params/result as JSON strings. Used by Python for every RPC.
int tft_client_call(void* h, const char* method, const char* params_json,
                    int64_t timeout_ms, char** result, char** err) {
  TFT_TRY({
    auto* c = static_cast<ClientHandle*>(h);
    Json params = Json::parse(params_json);
    Json r = c->client.call(method, params, Millis(timeout_ms));
    if (result) *result = dup_str(r.dump());
    return TFT_OK;
  })
}

// ------------------------------------------------------------------- kvstore
int tft_kvstore_new(const char* bind, void** out, char** err) {
  TFT_TRY({
    *out = new KvStoreServer(bind);
    return TFT_OK;
  })
}
int tft_kvstore_port(void* h) { return static_cast<KvStoreServer*>(h)->port(); }
void tft_kvstore_shutdown(void* h) {
  static_cast<KvStoreServer*>(h)->shutdown();
}
void tft_kvstore_free(void* h) { delete static_cast<KvStoreServer*>(h); }

// ------------------------------------------------------- pure quorum logic
// Exposed for unit tests (reference pattern: src/lighthouse.rs:627-1071 and
// src/manager.rs:881-1108 test these as pure functions).

// state_json: {"participants": [{"member": {...}, "joined_ms_ago": N}],
//              "heartbeats": {"rid": age_ms}, "prev_quorum": {...}|null,
//              "quorum_id": N}
int tft_quorum_compute(const char* state_json, const char* opts_json,
                       char** result, char** err) {
  TFT_TRY({
    Json js = Json::parse(state_json);
    Json jo = Json::parse(opts_json);
    LighthouseOpts opts;
    opts.min_replicas = jo.get_or("min_replicas", Json(int64_t{1})).as_int();
    opts.join_timeout_ms =
        jo.get_or("join_timeout_ms", Json(int64_t{60000})).as_int();
    opts.heartbeat_timeout_ms =
        jo.get_or("heartbeat_timeout_ms", Json(int64_t{5000})).as_int();

    TimePoint now = Clock::now();
    LighthouseState state;
    state.quorum_id = js.get_or("quorum_id", Json(int64_t{0})).as_int();
    // Bind to a named value: get_or returns a temporary, and a range-for over
    // a reference into it would dangle.
    Json participants = js.get_or("participants", Json::array());
    for (const auto& p : participants.as_array()) {
      MemberDetails d;
      d.member = QuorumMember::from_json(p.get("member"));
      d.joined = now - Millis(p.get_or("joined_ms_ago", Json(int64_t{0})).as_int());
      state.participants[d.member.replica_id] = d;
    }
    if (js.contains("heartbeats")) {
      for (const auto& [rid, age] : js.get("heartbeats").as_object())
        state.heartbeats[rid] = now - Millis(age.as_int());
    }
    if (js.contains("prev_quorum") && !js.get("prev_quorum").is_null())
      state.prev_quorum = QuorumSnapshot::from_json(js.get("prev_quorum"));
    if (js.contains("excluded")) {
      for (const auto& rid : js.get("excluded").as_array())
        state.excluded.insert(rid.as_string());
    }

    auto [met, reason] = quorum_compute(now, state, opts);
    Json out = Json::object();
    out["reason"] = reason;
    if (met) {
      Json parts = Json::array();
      for (const auto& m : *met) parts.push_back(m.to_json());
      out["participants"] = parts;
    } else {
      out["participants"] = Json();
    }
    if (result) *result = dup_str(out.dump());
    return TFT_OK;
  })
}

// ------------------------------------------------------- pure health logic
// Parity hooks for tests: torchft_tpu/healthwatch.py carries the canonical
// Python scoring/policy spec, and tests drive the SAME synthetic inputs
// through these to pin the native ledger to it.

// windows_json: {"rid": [samples...]} -> {"rid": score}
int tft_health_scores(const char* windows_json, const char* opts_json,
                      char** result, char** err) {
  TFT_TRY({
    Json jw = Json::parse(windows_json);
    HealthOpts opts = HealthOpts::from_json(Json::parse(opts_json));
    std::map<std::string, std::vector<double>> windows;
    for (const auto& [rid, arr] : jw.as_object()) {
      std::vector<double> w;
      for (const auto& v : arr.as_array()) w.push_back(v.as_double());
      windows[rid] = w;
    }
    auto scores = straggler_scores(windows, opts);
    Json out = Json::object();
    for (const auto& [rid, s] : scores) out[rid] = s;
    if (result) *result = dup_str(out.dump());
    return TFT_OK;
  })
}

// Deterministic ledger replay on a synthetic clock. opts_json: HealthOpts
// fields plus "heartbeat_timeout_ms" and "min_replicas". script_json: array
// of {"t_ms": N, "replica_id": ..., "telemetry": {...}?} beats and
// {"t_ms": N, "tick": true} ticks, applied in order.
int tft_health_replay(const char* script_json, const char* opts_json,
                      char** result, char** err) {
  TFT_TRY({
    Json js = Json::parse(script_json);
    Json jo = Json::parse(opts_json);
    HealthOpts opts = HealthOpts::from_json(jo);
    int64_t hb_timeout =
        jo.get_or("heartbeat_timeout_ms", Json(int64_t{5000})).as_int();
    int64_t min_replicas =
        jo.get_or("min_replicas", Json(int64_t{1})).as_int();
    HealthLedger ledger(opts, hb_timeout, min_replicas);

    TimePoint base = Clock::now();
    int64_t last_t = 0;
    Json events = Json::array();
    for (const auto& entry : js.as_array()) {
      int64_t t_ms = entry.get_or("t_ms", Json(int64_t{0})).as_int();
      last_t = t_ms;
      TimePoint now = base + Millis(t_ms);
      std::vector<Json> evs;
      if (entry.get_or("tick", Json(false)).as_bool()) {
        evs = ledger.tick(
            now, entry.get_or("prune_after_ms", Json(10 * hb_timeout)).as_int());
      } else {
        std::string rid = entry.get("replica_id").as_string();
        const Json* telemetry = nullptr;
        Json t;
        if (entry.contains("telemetry") && !entry.get("telemetry").is_null()) {
          t = entry.get("telemetry");
          telemetry = &t;
        }
        evs = ledger.on_heartbeat(rid, telemetry, now);
      }
      for (auto& e : evs) {
        e["t_ms"] = t_ms;
        events.push_back(e);
      }
    }
    Json out = Json::object();
    out["events"] = events;
    out["ledger"] = ledger.to_json(base + Millis(last_t));
    Json ex = Json::array();
    for (const auto& rid : ledger.exclusions()) ex.push_back(rid);
    out["excluded"] = ex;
    if (result) *result = dup_str(out.dump());
    return TFT_OK;
  })
}

// ------------------------------------------------------ recorded history
// Read path for the lighthouse's history JSONL (history.h). Takes the file
// CONTENT (not a path) so tests and remote tooling can feed bytes from
// anywhere; returns {"events": [...], "summary": {...}} where summary is
// the pure history_fold — mirrored by torchft_tpu.tracing.history_fold,
// parity pinned by test (same convention as tft_health_replay).
int tft_history_replay(const char* jsonl, char** result, char** err) {
  TFT_TRY({
    Json events = Json::array();
    std::string text(jsonl);
    size_t pos = 0;
    while (pos <= text.size()) {
      size_t nl = text.find('\n', pos);
      size_t end = nl == std::string::npos ? text.size() : nl;
      std::string line = text.substr(pos, end - pos);
      pos = end + 1;
      // skip blank lines (trailing newline, hand-edited files)
      if (line.find_first_not_of(" \t\r") == std::string::npos) {
        if (nl == std::string::npos) break;
        continue;
      }
      events.push_back(Json::parse(line));
      if (nl == std::string::npos) break;
    }
    Json out = Json::object();
    out["events"] = events;
    out["summary"] = history_fold(events);
    if (result) *result = dup_str(out.dump());
    return TFT_OK;
  })
}

int tft_compute_quorum_results(const char* replica_id, int64_t group_rank,
                               const char* quorum_json, int init_sync,
                               char** result, char** err) {
  TFT_TRY({
    QuorumSnapshot q = QuorumSnapshot::from_json(Json::parse(quorum_json));
    ManagerQuorumResult r =
        compute_quorum_results(replica_id, group_rank, q, init_sync != 0);
    if (result) *result = dup_str(r.to_json().dump());
    return TFT_OK;
  })
}

}  // extern "C"
