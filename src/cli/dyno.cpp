// dynolog_tpu: `dyno` CLI — operator front-end to the daemon's RPC port.
// Behavioral parity: reference cli/src (Rust; rebuilt in C++ since Rust is
// not in this environment — SURVEY §2.6): global --hostname/--port
// (main.rs:33-41), verbs `status` (status.rs:16-24) and `gputrace` with
// job_id/pids/duration_ms/iterations/log_file/profile_start_time/
// profile_start_iteration_roundup/process_limit (main.rs:43-75), building a
// key=value on-demand config (gputrace.rs:28-42) and printing per-pid trace
// paths (:63-78). Extensions: `tpurace` alias for gputrace, `version`, and
// `metrics`/`query` verbs reading the in-daemon metric history.
#include <algorithm>
#include <chrono>
#include <cstdio>
#include <ctime>
#include <fstream>
#include <iostream>
#include <memory>
#include <optional>
#include <set>
#include <sstream>
#include <string>
#include <thread>
#include <vector>

#include "src/common/Flags.h"
#include "src/common/Strings.h"
#include "src/common/Json.h"
#include "src/common/Time.h"
#include "src/common/Version.h"
#include "src/core/SpanJournal.h"
#include "src/rpc/JsonRpcServer.h"
#include "src/tracing/CaptureUtils.h"

DYN_DEFINE_string(hostname, "localhost", "Daemon host to connect to");
DYN_DEFINE_int32(port, 1778, "Daemon RPC port");
DYN_DEFINE_int32(
    rpc_timeout_ms,
    0,
    "Per-IO deadline for daemon RPCs (connect/send/recv). 0 = the client "
    "default (10s) — the CLI can no longer hang forever on a blackholed "
    "daemon; negative keeps fully blocking IO");

// gputrace/tpurace options (defaults match the reference CLI, main.rs:49-74).
DYN_DEFINE_int64(job_id, 0, "Job id of the application to trace");
DYN_DEFINE_string(pids, "0", "Comma separated pids to trace (0 = all)");
DYN_DEFINE_int64(duration_ms, 500, "Trace duration in ms");
DYN_DEFINE_int64(
    iterations,
    -1,
    "Training iterations to trace; takes precedence over duration");
DYN_DEFINE_string(log_file, "", "Output path for the trace");
DYN_DEFINE_int64(
    profile_start_time,
    0,
    "Unix timestamp (ms) for synchronized collection across hosts");
DYN_DEFINE_int64(
    profile_start_iteration_roundup,
    1,
    "Start an iteration-based trace at a multiple of this value");
DYN_DEFINE_int32(process_limit, 3, "Max number of processes to profile");
DYN_DEFINE_int32(
    python_tracer_level,
    -1,
    "gputrace/tpurace: jax python tracer level for this capture "
    "(0 disables python-stack tracing and its multi-hundred-ms stop "
    "cost; -1 = profiler default)");
DYN_DEFINE_int32(
    host_tracer_level,
    -1,
    "gputrace/tpurace/pushtrace: host (C++) tracer level for this "
    "capture (-1 = profiler default)");
DYN_DEFINE_int32(
    device_tracer_level,
    -1,
    "gputrace/tpurace/pushtrace: device tracer level for this capture "
    "(-1 = profiler default)");
DYN_DEFINE_bool(
    trace_json,
    true,
    "gputrace/tpurace: also produce trace.json.gz + summary.json in the "
    "background after the capture (--notrace_json = xplane.pb only)");

// cputrace options
DYN_DEFINE_int64(top, 20, "cputrace/perfsample: max threads in the breakdown");
DYN_DEFINE_string(
    event,
    "cycles",
    "perfsample: event to sample (builtin name, rNNNN raw, or "
    "pmu/term=.../ string)");
DYN_DEFINE_int64(
    sample_period,
    0,
    "perfsample: events per sample (0 = default 1M; clamped >= 1000)");

// pushtrace options (capture via the app's jax.profiler server — no shim)
DYN_DEFINE_int32(
    profiler_port,
    9012,
    "pushtrace: the app's jax.profiler.start_server port");
DYN_DEFINE_string(
    profiler_host,
    "localhost",
    "pushtrace: host the profiler server listens on");

// autotrigger options (`dyno autotrigger add|list|remove`)
DYN_DEFINE_string(
    metric,
    "",
    "autotrigger add: store series to watch (see `dyno metrics`)");
DYN_DEFINE_string(
    above,
    "",
    "autotrigger add: fire when the metric exceeds this value");
DYN_DEFINE_string(
    below,
    "",
    "autotrigger add: fire when the metric drops under this value");
DYN_DEFINE_int32(
    for_ticks,
    1,
    "autotrigger add: consecutive samples past the threshold before firing");
DYN_DEFINE_int64(
    cooldown_s,
    300,
    "autotrigger add: minimum seconds between fired traces");
DYN_DEFINE_int64(
    max_fires,
    0,
    "autotrigger add: stop after this many fired traces (0 = unlimited)");
DYN_DEFINE_int64(trigger_id, -1, "autotrigger remove: rule id to delete");
DYN_DEFINE_int64(
    keep_last,
    0,
    "autotrigger add: keep only the newest N fired captures of this rule "
    "on disk, pruning older trace dirs/manifests (0 = keep all)");
DYN_DEFINE_string(
    peers,
    "",
    "autotrigger add: comma-separated peer daemons (host[:port]); when "
    "the rule trips, the fired config is relayed to every peer with one "
    "shared future start time so all ranks capture the same window");
DYN_DEFINE_int64(
    sync_delay_ms,
    2000,
    "autotrigger add: future-start offset for peer-synchronized fires");
DYN_DEFINE_string(
    capture,
    "shim",
    "autotrigger add: how a fired rule captures — \"shim\" hands a config "
    "to the in-app shim/libkineto, \"push\" drives the app's jax.profiler "
    "server (--profiler_host/--profiler_port; no shim needed)");
DYN_DEFINE_bool(
    with_baseline,
    false,
    "autotrigger add: also capture a healthy-state trace right now "
    "(<log_file>_baseline) so a later fired trace can be diffed against "
    "it with `python -m dynolog_tpu.trace FIRED --diff BASELINE`");
DYN_DEFINE_bool(
    diagnose,
    false,
    "autotrigger add: when a fired capture completes, run the trace-diff "
    "diagnosis engine against --baseline automatically and record the "
    "ranked report (retrieve with `dyno diagnose`)");
DYN_DEFINE_string(
    baseline,
    "",
    "diagnose / autotrigger add --diagnose: the baseline to diff "
    "against — a saved baseline JSON (python -m dynolog_tpu.diagnose "
    "--save-baseline) or a healthy-state capture (trace dir / manifest). "
    "With --with_baseline --diagnose and no --baseline, the baseline "
    "capture armed now is used");

// query options
DYN_DEFINE_string(metrics, "", "Comma separated metric names (empty = all)");
DYN_DEFINE_int64(start_ts, 0, "Query start (unix ms; 0 = beginning)");
DYN_DEFINE_bool(
    stats,
    false,
    "query: include per-series stats (min/max/avg/p50/p95/p99/diff/rate)");
DYN_DEFINE_int64(
    watch_interval_ms,
    1000,
    "watch: poll cadence in ms (clamped >= 200)");
DYN_DEFINE_int64(end_ts, 0, "Query end (unix ms; 0 = now)");
DYN_DEFINE_string(
    trace_id,
    "",
    "selftrace: only spans of this trace id (16-hex, as printed by "
    "gputrace/tpurace or shown in span args); empty dumps the whole ring");
DYN_DEFINE_string(
    path,
    "",
    "fetch: absolute path of the capture artifact on the daemon's host "
    "(must sit under the daemon's --trace_output_root); streamed back "
    "over the RPC connection as chunk frames");

// fleet options (`dyno fleet` against a --relay daemon)
DYN_DEFINE_bool(
    fleet_hosts,
    false,
    "fleet: print the full per-host state table (liveness, watermark, "
    "duplicates, flaps) instead of just the summary + stragglers");
DYN_DEFINE_string(
    skew_metric,
    "",
    "fleet: also report per-pod min/max/spread of this metric across the "
    "pod's hosts (step-time skew spotting; e.g. "
    "--skew_metric=job42.step_time_ms_p95)");
DYN_DEFINE_int32(
    depth,
    0,
    "fleet: levels of relay-tree drill-down to print — 0 shows the "
    "merged global view plus the tree summary, >=1 adds the per-child "
    "relay breakdown (hosts, records, applied watermarks per subtree)");
DYN_DEFINE_string(
    pod,
    "",
    "fleet: drill into one pod — its tree-wide aggregate (per-metric "
    "count/sum/min/max), this relay's local member hosts, and each "
    "child relay's contribution");
DYN_DEFINE_bool(
    versions,
    false,
    "fleet: print the per-version host cohort (announced build, or "
    "v<proto> for pre-version senders) — canary visibility during a "
    "rolling upgrade ('3 hosts on 0.7.0, 97 on v0')");

namespace {

using namespace dynotpu;

// Persistent daemon connection, created lazily and reused across every
// RPC this invocation makes — watch/top loops and the async-capture
// polls used to reconnect per call, which at cluster fan-out is exactly
// the connection churn the daemon's event-loop transport exists to
// avoid. Only a RETRIABLE failure (stale keep-alive connection the
// daemon reaped; the verb provably never ran — see
// JsonRpcClient::CallResult) is retried, exactly once, on a fresh
// connection: blind retries could fire a non-idempotent verb
// (gputrace, addTraceTrigger) twice.
std::unique_ptr<JsonRpcClient> gClient;

// One trace-id per CLI invocation, a fresh span-id per request: the
// `trace_ctx` wire field every RPC carries, so the daemon's verb span —
// and through the on-demand config, the Python shim's capture/convert
// spans — all share this invocation's identity. `dyno selftrace
// --trace_id=<id>` then reconstructs the whole request across both
// languages. Old daemons ignore the extra field.
uint64_t cliTraceId() {
  static uint64_t traceId = mintId();
  return traceId;
}

void attachTraceCtx(json::Value& request) {
  if (!request.contains("trace_ctx")) {
    request["trace_ctx"] = TraceContext{cliTraceId(), mintId()}.header();
  }
}

bool roundTrip(
    const std::string& body,
    std::string* responseOut,
    std::string* errorOut = nullptr) {
  for (int attempt = 0; attempt < 2; ++attempt) {
    if (gClient && gClient->stale()) {
      gClient.reset(); // peer hung up between round trips: reconnect
    }
    if (!gClient) {
      try {
        gClient = std::make_unique<JsonRpcClient>(
            FLAGS_hostname, FLAGS_port, FLAGS_rpc_timeout_ms);
      } catch (const std::exception& e) {
        if (errorOut) {
          *errorOut = e.what();
        }
        return false; // connect refused/timed out: retrying now is noise
      }
    }
    auto result = gClient->callWithStatus(body, responseOut);
    if (result == JsonRpcClient::CallResult::kOk) {
      return true;
    }
    gClient.reset();
    if (errorOut) {
      *errorOut = "no response from daemon (bad request?)";
    }
    if (result != JsonRpcClient::CallResult::kRetriable) {
      return false;
    }
  }
  return false;
}

int rpc(json::Value request, json::Value* responseOut = nullptr) {
  attachTraceCtx(request);
  std::string responseStr, error;
  if (!roundTrip(request.dump(), &responseStr, &error)) {
    std::cerr << "error: " << error << "\n";
    return 1;
  }
  std::cout << "response = " << responseStr << std::endl;
  if (responseOut) {
    std::string err;
    *responseOut = json::Value::parse(responseStr, &err);
  }
  return 0;
}

// Quiet round trip: returns the parsed response (null on any failure).
json::Value rpcCall(json::Value request) {
  attachTraceCtx(request);
  std::string responseStr;
  if (!roundTrip(request.dump(), &responseStr)) {
    return json::Value();
  }
  std::string err;
  auto parsed = json::Value::parse(responseStr, &err);
  return err.empty() ? parsed : json::Value();
}

int runStatus() {
  auto req = json::Value::object();
  req["fn"] = "getStatus";
  return rpc(req);
}

int runVersion() {
  std::cout << "dyno CLI version " << kVersion << std::endl;
  auto req = json::Value::object();
  req["fn"] = "getVersion";
  int rc = rpc(req);
  if (rc != 0) {
    return rc;
  }
  // Versioned wire hello: announce this CLI's proto/build, print what
  // the connection settled on (min of the two). An old daemon answers
  // the getVersion above but knows no `hello` — the negotiation then
  // reads v0, which is exactly the protocol level the pair speaks.
  auto hello = json::Value::object();
  hello["fn"] = "hello";
  hello["proto"] = kWireProtoVersion;
  hello["build"] = std::string("dyno-") + kVersion;
  auto resp = rpcCall(hello);
  if (resp.isObject() && resp.at("status").asString("") == "ok") {
    std::printf(
        "negotiated wire proto %lld (daemon build %s, daemon proto %lld)\n",
        static_cast<long long>(resp.at("proto").asInt(0)),
        resp.at("build").asString("?").c_str(),
        static_cast<long long>(resp.at("server_proto").asInt(0)));
  } else {
    std::printf("negotiated wire proto 0 (daemon predates the hello verb)\n");
  }
  return 0;
}

// Builds the on-demand profiling config handed to the client's profiler —
// the same key=value text format libkineto consumes (gputrace.rs:28-40), so
// both the JAX shim and PyTorch apps understand it. One definition for
// every path that emits a config (gputrace and the baseline capture).
std::string buildTraceConfig(
    const std::string& logFile,
    int64_t startTimeMs,
    int64_t iterations,
    bool includeCaptureKnobs = true) {
  std::ostringstream cfg;
  cfg << "PROFILE_START_TIME=" << startTimeMs << "\n";
  cfg << "ACTIVITIES_LOG_FILE=" << logFile << "\n";
  if (iterations > 0) {
    cfg << "PROFILE_START_ITERATION_ROUNDUP="
        << FLAGS_profile_start_iteration_roundup << "\n";
    cfg << "ACTIVITIES_ITERATIONS=" << iterations;
  } else {
    cfg << "ACTIVITIES_DURATION_MSECS=" << FLAGS_duration_ms;
  }
  if (!includeCaptureKnobs) {
    return cfg.str();
  }
  // Per-capture profiler knobs (understood by the JAX shim; unknown keys
  // are ignored by libkineto-style consumers, so mixed fleets are safe).
  if (FLAGS_python_tracer_level >= 0) {
    cfg << "\nPROFILE_PYTHON_TRACER_LEVEL=" << FLAGS_python_tracer_level;
  }
  if (FLAGS_host_tracer_level >= 0) {
    cfg << "\nPROFILE_HOST_TRACER_LEVEL=" << FLAGS_host_tracer_level;
  }
  if (FLAGS_device_tracer_level >= 0) {
    cfg << "\nPROFILE_DEVICE_TRACER_LEVEL=" << FLAGS_device_tracer_level;
  }
  if (!FLAGS_trace_json) {
    cfg << "\nTRACE_JSON=0";
  }
  return cfg.str();
}

int runTrace() {
  if (FLAGS_log_file.empty()) {
    std::cerr << "error: --log_file is required\n";
    return 1;
  }
  std::string config = buildTraceConfig(
      FLAGS_log_file, FLAGS_profile_start_time, FLAGS_iterations);
  std::cout << "Trace config:\n" << config << std::endl;

  auto req = json::Value::object();
  req["fn"] = "setKinetOnDemandRequest";
  req["config"] = config;
  req["job_id"] = FLAGS_job_id;
  req["process_limit"] = FLAGS_process_limit;
  auto& pids = req["pids"];
  pids = json::Value::array();
  for (const auto& tok : splitCsv(FLAGS_pids)) {
    try {
      pids.append(std::stoll(tok));
    } catch (const std::exception&) {
      std::cerr << "error: bad pid in --pids: '" << tok << "'\n";
      return 1;
    }
  }

  json::Value response;
  int rc = rpc(req, &response);
  if (rc != 0) {
    return rc;
  }
  if (response.at("status").asString("") == "refused") {
    // Typed resource-pressure refusal: the daemon is protecting its
    // host (full disk, fd exhaustion) and will admit again once the
    // `health` verb's resources section reports ok. Exit 3 so scripts
    // can distinguish "retry later" from a real failure.
    std::cerr << "gputrace refused: " << response.at("error").asString("")
              << "\n";
    return 3;
  }
  const auto& matched = response.at("processesMatched");
  if (matched.size() == 0) {
    std::cout << "No processes were matched, please check --job_id or --pids"
              << std::endl;
    return 0;
  }
  std::cout << "Matched " << matched.size() << " processes" << std::endl;
  std::cout << "Trace output files will be written to:" << std::endl;
  for (const auto& pid : matched.items()) {
    std::cout << "    "
              << tracing::withTracePathSuffix(
                     FLAGS_log_file, "_" + std::to_string(pid.asInt()))
              << std::endl;
  }
  {
    char buf[20];
    std::snprintf(
        buf, sizeof(buf), "%016llx",
        static_cast<unsigned long long>(cliTraceId()));
    std::cout << "Control-plane trace id: " << buf
              << " (inspect with: dyno selftrace --trace_id=" << buf << ")"
              << std::endl;
  }
  return 0;
}

// The daemon's own span journal (C++ verb/tick/sink spans merged with
// the spans Python clients flushed back over IPC), printed as one valid
// Chrome-trace JSON document — load it in chrome://tracing or Perfetto.
int runSelfTrace() {
  auto req = json::Value::object();
  req["fn"] = "selftrace";
  if (!FLAGS_trace_id.empty()) {
    req["trace_id"] = FLAGS_trace_id;
  }
  auto response = rpcCall(req);
  if (!response.isObject()) {
    std::cerr << "selftrace: daemon unreachable\n";
    return 2;
  }
  if (response.at("status").asString("") != "ok") {
    std::cerr << "selftrace: " << response.dump() << "\n";
    return 1;
  }
  auto doc = json::Value::object();
  doc["displayTimeUnit"] = "ms";
  doc["otherData"] = json::Value::object();
  doc["otherData"]["clock"] = response.at("clock").asString("unix_us");
  doc["otherData"]["spans_recorded"] = response.at("spans_recorded").asInt();
  doc["otherData"]["ring_capacity"] = response.at("ring_capacity").asInt();
  if (response.at("ipc_wakeups").isObject()) { // absent from older daemons
    doc["otherData"]["ipc_wakeups"] = response.at("ipc_wakeups");
  }
  if (response.at("tpu_rows").isInt()) { // absent from older daemons
    doc["otherData"]["tpu_rows"] = response.at("tpu_rows");
  }
  doc["traceEvents"] = response.at("traceEvents");
  const std::string out = doc.dump();
  if (!FLAGS_log_file.empty()) {
    std::ofstream file(FLAGS_log_file);
    if (!file) {
      std::cerr << "selftrace: cannot write " << FLAGS_log_file << "\n";
      return 1;
    }
    file << out << "\n";
    std::cout << "wrote " << response.at("traceEvents").size()
              << " span(s) to " << FLAGS_log_file << std::endl;
  } else {
    std::cout << out << std::endl;
  }
  return 0;
}

// Pull one capture artifact off the daemon's host over the RPC
// connection: `dyno fetch --path=/abs/remote/artifact [--log_file=dest]`.
// The daemon answers with a JSON header frame, then length-prefixed
// CHUNK frames read straight off the file, then a zero-length END frame
// (ServiceHandler::fetchTrace + JsonRpcServer::streamRequest). The
// deadline is PER FRAME (SO_RCVTIMEO re-arms on every recv), so a slow
// but progressing multi-MB stream is never cut off by the 10s default —
// only a genuine mid-stream stall is. The local write is atomic
// (tmp + rename): a truncated stream can never masquerade as a fetched
// artifact. Exit 0 fetched, 1 refused/truncated, 2 unreachable.
int runFetch() {
  if (FLAGS_path.empty()) {
    std::cerr << "error: --path is required (the artifact's absolute path "
                 "on the daemon's host)\n";
    return 1;
  }
  auto req = json::Value::object();
  req["fn"] = "fetchTrace";
  req["path"] = FLAGS_path;
  attachTraceCtx(req);
  // A dedicated connection, not roundTrip(): the reply spans many frames
  // and a blind reconnect mid-stream could silently restart the fetch.
  std::unique_ptr<JsonRpcClient> client;
  try {
    client = std::make_unique<JsonRpcClient>(
        FLAGS_hostname, FLAGS_port, FLAGS_rpc_timeout_ms);
  } catch (const std::exception& e) {
    std::cerr << "error: " << e.what() << "\n";
    return 2;
  }
  std::string header;
  if (!client->send(req.dump()) || !client->recv(header)) {
    std::cerr << "error: no response from daemon\n";
    return 2;
  }
  std::string err;
  auto response = json::Value::parse(header, &err);
  if (!err.empty() || !response.isObject()) {
    std::cerr << "error: unparseable response: " << header << "\n";
    return 1;
  }
  if (response.at("status").asString("") != "ok") {
    std::cerr << "fetch: " << response.dump() << "\n";
    return 1;
  }
  if (response.at("stream").asString("") != "chunks") {
    std::cerr << "fetch: daemon did not stream (old daemon?): "
              << response.dump() << "\n";
    return 1;
  }
  std::string dest = FLAGS_log_file;
  if (dest.empty()) {
    // Default: the artifact's own name in the working directory.
    auto slash = FLAGS_path.rfind('/');
    dest = slash == std::string::npos ? FLAGS_path
                                      : FLAGS_path.substr(slash + 1);
  }
  const std::string tmp = dest + ".tmp";
  uint64_t total = 0;
  {
    std::ofstream out(tmp, std::ios::binary | std::ios::trunc);
    if (!out) {
      std::cerr << "fetch: cannot write " << tmp << "\n";
      return 1;
    }
    while (true) {
      std::string chunk;
      if (!client->recv(chunk)) {
        // No END frame ⇒ the stream is TRUNCATED (daemon died, read
        // failure mid-stream, per-frame deadline tripped): discard the
        // partial tmp — a short artifact must never land at dest.
        out.close();
        ::remove(tmp.c_str());
        std::cerr << "fetch: stream truncated after " << total
                  << " bytes (no END frame)\n";
        return 1;
      }
      if (chunk.empty()) {
        break; // END frame
      }
      out.write(chunk.data(), static_cast<std::streamsize>(chunk.size()));
      total += chunk.size();
      if (!out) {
        out.close();
        ::remove(tmp.c_str());
        std::cerr << "fetch: local write failed at " << total << " bytes\n";
        return 1;
      }
    }
    out.close();
    if (!out) {
      ::remove(tmp.c_str());
      std::cerr << "fetch: local write failed on close\n";
      return 1;
    }
  }
  // durability-ok: CLI download — atomic publish so a reader never
  // sees a short file; the authoritative copy stays on the daemon.
  if (std::rename(tmp.c_str(), dest.c_str()) != 0) {
    ::remove(tmp.c_str());
    std::cerr << "fetch: cannot rename into " << dest << "\n";
    return 1;
  }
  std::cout << "fetched " << total << " bytes to " << dest << std::endl;
  return 0;
}

// Automated trace-diff diagnosis (src/tracing/Diagnoser.h): with
// --log_file + --baseline, ask the daemon to run the engine on that
// capture now; otherwise list the registry of reports (auto-trigger
// fired diagnoses included), --trace_id narrowing to one request's.
// Exit codes are scriptable like `dyno health`: 0 = clean (or list
// printed), 1 = diagnosis failed, 2 = daemon unreachable,
// 3 = regression diagnosed.
int runDiagnose() {
  auto req = json::Value::object();
  req["fn"] = "diagnose";
  if (!FLAGS_log_file.empty()) {
    if (FLAGS_baseline.empty()) {
      std::cerr << "error: --baseline is required with --log_file\n";
      return 1;
    }
    req["target"] = FLAGS_log_file;
    req["baseline"] = FLAGS_baseline;
    // The daemon runs the engine synchronously under its own
    // --diagnose_timeout_ms (60s default); the client default 10s recv
    // deadline would misreport a >10s diagnosis as "daemon unreachable"
    // (exit 2). Pad past the server bound unless the operator set an
    // explicit deadline (the async-capture verbs do the same).
    if (FLAGS_rpc_timeout_ms == 0) {
      FLAGS_rpc_timeout_ms = 90'000;
      gClient.reset(); // rebuilt lazily with the padded deadline
    }
    auto response = rpcCall(req);
    if (!response.isObject()) {
      std::cerr << "diagnose: daemon unreachable\n";
      return 2;
    }
    if (response.at("status").asString("") != "ok") {
      std::cerr << "diagnose: " << response.dump() << "\n";
      return 1;
    }
    const std::string verdict = response.at("verdict").asString("?");
    std::cout << "diagnosis: " << verdict << " — "
              << response.at("headline").asString("") << std::endl;
    const auto& findings = response.at("report").at("findings");
    for (size_t i = 0; i < findings.size(); ++i) {
      const auto& f = findings.at(i);
      std::cout << "  " << (i + 1) << ". ("
                << f.at("kind").asString("?") << ") "
                << f.at("message").asString("") << std::endl;
    }
    std::cout << "report: " << response.at("report_path").asString("")
              << "  (trace id " << response.at("trace_id").asString("")
              << ")" << std::endl;
    return verdict == "regressed" ? 3 : 0;
  }
  if (!FLAGS_trace_id.empty()) {
    req["trace_id"] = FLAGS_trace_id;
  }
  auto response = rpcCall(req);
  if (!response.isObject()) {
    std::cerr << "diagnose: daemon unreachable\n";
    return 2;
  }
  if (response.at("status").asString("") != "ok") {
    std::cerr << "diagnose: " << response.dump() << "\n";
    return 1;
  }
  const auto& reports = response.at("reports");
  if (reports.size() == 0) {
    std::cout << "no diagnosis reports (runs_total="
              << response.at("runs_total").asInt(0) << ")" << std::endl;
    return 0;
  }
  std::printf("%-3s %-4s %-8s %-9s %4s %-16s %s\n", "id", "rule",
              "status", "verdict", "find", "trace_id", "headline/error");
  for (size_t i = 0; i < reports.size(); ++i) {
    const auto& r = reports.at(i);
    std::string line = r.at("headline").asString("");
    if (line.empty()) {
      line = r.at("error").asString("-");
    }
    std::printf(
        "%-3lld %-4lld %-8s %-9s %4lld %-16.16s %s\n",
        static_cast<long long>(r.at("id").asInt()),
        static_cast<long long>(r.at("rule_id").asInt()),
        r.at("status").asString("?").c_str(),
        r.at("verdict").asString("-").c_str(),
        static_cast<long long>(r.at("findings").asInt()),
        r.at("trace_id").asString("").c_str(), line.c_str());
    const std::string path = r.at("report_path").asString("");
    if (!path.empty() && r.at("status").asString("") == "ok") {
      std::printf("      -> %s\n", path.c_str());
    }
  }
  return 0;
}

// Shared start+poll protocol for the async capture verbs (cputrace,
// perfsample): the daemon captures asynchronously so its dispatch thread
// stays responsive; we start, then poll <fn>Result.
int runAsyncCapture(json::Value req, const std::string& fn) {
  req["fn"] = fn;
  req["duration_ms"] = FLAGS_duration_ms;
  req["top"] = FLAGS_top;
  auto started = rpcCall(req);
  if (started.isObject() && started.at("status").asString() == "refused") {
    std::cerr << fn << " refused: " << started.at("error").asString("")
              << "\n";
    return 3; // typed resource-pressure refusal: retry after recovery
  }
  if (!started.isObject() || started.at("status").asString() != "started") {
    std::cout << "response = " << started.dump() << std::endl;
    return started.isObject() &&
            started.at("status").asString() == "busy"
        ? 1
        : 2;
  }
  auto poll = json::Value::object();
  poll["fn"] = fn + "Result";
  // Pad past the daemon's own worst case (pushtrace pads its Profile RPC
  // deadline by 15s): the CLI must not give up seconds before a capture
  // the daemon still considers live.
  const auto deadline = std::chrono::steady_clock::now() +
      std::chrono::milliseconds(FLAGS_duration_ms + 20'000);
  while (std::chrono::steady_clock::now() < deadline) {
    std::this_thread::sleep_for(std::chrono::milliseconds(200));
    auto report = rpcCall(poll);
    if (!report.isObject()) {
      std::cerr << "daemon unreachable while polling" << std::endl;
      return 2;
    }
    if (report.at("status").asString() != "pending") {
      std::cout << "response = " << report.dump() << std::endl;
      return report.at("status").asString() == "ok" ? 0 : 1;
    }
  }
  std::cerr << "timed out waiting for " << fn << " report" << std::endl;
  return 2;
}

int runCpuTrace() {
  return runAsyncCapture(json::Value::object(), "cputrace");
}

int runPushTrace() {
  if (FLAGS_log_file.empty()) {
    std::cerr << "error: --log_file is required\n";
    return 1;
  }
  auto req = json::Value::object();
  req["profiler_port"] = FLAGS_profiler_port;
  req["profiler_host"] = FLAGS_profiler_host;
  req["log_file"] = FLAGS_log_file;
  // Per-capture tracer levels (-1 = keep the daemon's defaults), same
  // knobs gputrace passes through the shim config.
  if (FLAGS_host_tracer_level >= 0) {
    req["host_tracer_level"] = FLAGS_host_tracer_level;
  }
  if (FLAGS_device_tracer_level >= 0) {
    req["device_tracer_level"] = FLAGS_device_tracer_level;
  }
  if (FLAGS_python_tracer_level >= 0) {
    req["python_tracer_level"] = FLAGS_python_tracer_level;
  }
  return runAsyncCapture(std::move(req), "pushtrace");
}

int runPerfSample() {
  auto req = json::Value::object();
  req["event"] = FLAGS_event;
  req["sample_period"] = FLAGS_sample_period;
  return runAsyncCapture(std::move(req), "perfsample");
}

int runQuery(bool listOnly) {
  auto req = json::Value::object();
  if (listOnly) {
    req["fn"] = "listMetrics";
    return rpc(req);
  }
  req["fn"] = "queryMetrics";
  req["stats"] = FLAGS_stats;
  req["start_ts"] = FLAGS_start_ts;
  req["end_ts"] = FLAGS_end_ts > 0 ? FLAGS_end_ts : nowUnixMillis();
  auto& names = req["metrics"];
  names = json::Value::array();
  for (const auto& tok : splitCsv(FLAGS_metrics)) {
    names.append(tok);
  }
  return rpc(req);
}

// Live follow: print the latest value of each metric every interval (the
// `watch dyno query` loop as a built-in; Ctrl-C exits).
int runWatch() {
  auto names = splitCsv(FLAGS_metrics);
  if (names.empty()) {
    std::cerr << "watch: --metrics required" << std::endl;
    return 1;
  }
  const int64_t intervalMs = std::max<int64_t>(FLAGS_watch_interval_ms, 200);
  // Window wide enough to hold the newest sample of slow-cadence metrics
  // (the default kernel interval is 60s) so a line always carries every
  // metric's latest value, without ever shipping the full history.
  const int64_t windowMs = std::max<int64_t>(3 * intervalMs, 130'000);
  int64_t lastPrinted = 0;
  int emptyPolls = 0;
  int unreachablePolls = 0;
  while (true) {
    auto req = json::Value::object();
    req["fn"] = "queryMetrics";
    req["start_ts"] = nowUnixMillis() - windowMs;
    req["end_ts"] = nowUnixMillis();
    auto& arr = req["metrics"];
    arr = json::Value::array();
    for (const auto& n : names) {
      arr.append(n);
    }
    auto response = rpcCall(req);
    if (!response.isObject()) {
      // A restarting daemon shouldn't kill a live-follow session; give up
      // only after a sustained outage (like a `watch dyno query` loop).
      if (++unreachablePolls == 1) {
        std::cerr << "daemon unreachable; retrying" << std::endl;
      }
      if (unreachablePolls >= 10) {
        std::cerr << "daemon unreachable for " << unreachablePolls
                  << " polls; giving up" << std::endl;
        return 2;
      }
      std::this_thread::sleep_for(std::chrono::milliseconds(intervalMs));
      continue;
    }
    unreachablePolls = 0;
    if (!response.at("metrics").isObject()) {
      // e.g. {"status":"failed","error":"metric store not enabled"}
      std::cerr << "watch failed: " << response.dump() << std::endl;
      return 1;
    }
    std::ostringstream line;
    int64_t newest = 0;
    int matched = 0;
    for (const auto& n : names) {
      const auto& series = response.at("metrics").at(n);
      if (!series.isObject()) {
        continue;
      }
      const auto& values = series.at("values");
      const auto& stamps = series.at("timestamps");
      if (values.size() == 0) {
        continue;
      }
      matched++;
      line << " " << n << "=" << values.at(values.size() - 1).asDouble();
      newest = std::max(newest, stamps.at(stamps.size() - 1).asInt());
    }
    if (matched == 0) {
      // Not necessarily fatal (collectors may still be warming up), but
      // silence forever would hide a typo'd metric name. Consecutive
      // count, reset on data: warns once per sustained dry spell.
      if (++emptyPolls == 10) {
        std::cerr << "watch: no data for any of --metrics yet "
                  << "(check `dyno metrics` for known series)" << std::endl;
      }
    } else if (newest > lastPrinted) {
      emptyPolls = 0;
      time_t secs = static_cast<time_t>(newest / 1000);
      char stamp[16];
      std::strftime(stamp, sizeof(stamp), "%H:%M:%S", ::localtime(&secs));
      std::cout << stamp << line.str() << std::endl;
      lastPrinted = newest;
    }
    std::this_thread::sleep_for(std::chrono::milliseconds(intervalMs));
  }
}

// Latest value of one store series within the trailing window, if any.
std::optional<double> latestOf(const json::Value& series) {
  if (!series.isObject()) {
    return std::nullopt;
  }
  const auto& values = series.at("values");
  if (values.size() == 0) {
    return std::nullopt;
  }
  return values.at(values.size() - 1).asDouble();
}

// tpu-info-style device table rendered from the daemon's metric history:
// one row per device, latest value per column. Answers "how busy are my
// chips" in one command without an in-app tool.
int runTpuTable() {
  auto listReq = json::Value::object();
  listReq["fn"] = "listMetrics";
  auto listed = rpcCall(listReq);
  if (!listed.isObject() || !listed.at("metrics").isArray()) {
    std::cerr << "tpu: daemon unreachable or metric store disabled\n";
    return 2;
  }
  std::set<int> devices;
  std::vector<std::string> tpuSeries;
  const auto& names = listed.at("metrics");
  for (size_t i = 0; i < names.size(); ++i) {
    const std::string name = names.at(i).asString("");
    if (name.rfind("tpu", 0) != 0) {
      continue;
    }
    size_t dot = name.find('.');
    if (dot == std::string::npos || dot <= 3) {
      continue;
    }
    try {
      devices.insert(std::stoi(name.substr(3, dot - 3)));
      tpuSeries.push_back(name);
    } catch (const std::exception&) {
    }
  }
  if (devices.empty()) {
    std::cerr << "tpu: no device metrics in the store "
                 "(is --enable_tpu_monitor on?)\n";
    return 1;
  }

  auto req = json::Value::object();
  req["fn"] = "queryMetrics";
  req["start_ts"] = nowUnixMillis() - 130'000;
  req["end_ts"] = nowUnixMillis();
  auto& arr = req["metrics"];
  arr = json::Value::array();
  for (const auto& n : tpuSeries) {
    arr.append(n);
  }
  auto response = rpcCall(req);
  if (!response.isObject() || !response.at("metrics").isObject()) {
    std::cerr << "tpu: query failed\n";
    return 2;
  }
  const auto& series = response.at("metrics");
  auto latest = [&](int device, const char* metric) {
    return latestOf(
        series.at("tpu" + std::to_string(device) + "." + metric));
  };
  auto cell = [](std::optional<double> v, const char* fmt) {
    char buf[32];
    if (!v) {
      return std::string("   -");
    }
    std::snprintf(buf, sizeof(buf), fmt, *v);
    return std::string(buf);
  };

  std::printf("%-4s %7s %7s %6s %16s %6s %5s %6s %6s\n", "dev", "duty%",
              "tc%", "mxu%", "hbm used/total", "hbm%", "thr", "link",
              "queue");
  for (int device : devices) {
    auto used = latest(device, "hbm_used_bytes");
    auto total = latest(device, "hbm_total_bytes");
    std::string hbm = "       -";
    std::string hbmPct = "   -";
    if (used && total && *total > 0) {
      char buf[40];
      std::snprintf(buf, sizeof(buf), "%6.2f/%5.1f GiB", *used / (1 << 30),
                    *total / double(1 << 30));
      hbm = buf;
      std::snprintf(buf, sizeof(buf), "%5.1f", *used / *total * 100.0);
      hbmPct = buf;
    }
    std::printf(
        "%-4d %7s %7s %6s %16s %6s %5s %6s %6s\n", device,
        cell(latest(device, "tpu_duty_cycle_pct"), "%7.1f").c_str(),
        cell(latest(device, "tensorcore_duty_cycle_pct"), "%7.1f").c_str(),
        cell(latest(device, "mxu_util_pct"), "%6.1f").c_str(), hbm.c_str(),
        hbmPct.c_str(),
        cell(latest(device, "tpu_throttle_score"), "%5.0f").c_str(),
        cell(latest(device, "ici_link_health"), "%6.0f").c_str(),
        cell(latest(device, "hlo_queue_size"), "%6.0f").c_str());
  }
  return 0;
}

// Daemon self-health table: one row per supervised component (collector
// loops, IPC monitor, remote sinks) from the `health` verb. Exit status is
// scriptable: 0 = everything up, 1 = degradation somewhere, 2 = daemon
// unreachable — so fleet health checks are one `dyno health` per host.
int runHealth() {
  auto req = json::Value::object();
  req["fn"] = "health";
  auto response = rpcCall(req);
  if (!response.isObject()) {
    std::cerr << "health: daemon unreachable\n";
    return 2;
  }
  const std::string status = response.at("status").asString("?");
  std::printf(
      "daemon: %s (uptime %.0fs)\n", status.c_str(),
      response.at("uptime_s").asDouble());
  const auto& components = response.at("components");
  if (!components.isObject() || components.fields().empty()) {
    std::printf("no supervised components reported\n");
    return status == "ok" ? 0 : 1;
  }
  std::printf(
      "%-16s %-10s %8s %6s %6s %10s  %s\n", "component", "state", "restarts",
      "cfail", "drops", "tick-ago-s", "last error");
  for (const auto& [name, comp] : components.fields()) {
    std::string tickAgo = "-";
    if (comp.contains("seconds_since_tick")) {
      char buf[32];
      std::snprintf(
          buf, sizeof(buf), "%.1f", comp.at("seconds_since_tick").asDouble());
      tickAgo = buf;
    }
    std::string lastError = comp.at("last_error").asString("");
    std::printf(
        "%-16s %-10s %8lld %6lld %6lld %10s  %s\n", name.c_str(),
        comp.at("state").asString("?").c_str(),
        static_cast<long long>(comp.at("restarts").asInt()),
        static_cast<long long>(comp.at("consecutive_failures").asInt()),
        static_cast<long long>(comp.at("drops").asInt()), tickAgo.c_str(),
        lastError.empty() ? "-" : lastError.c_str());
  }
  // Durability section (PR 9): per-endpoint sink spill queues and the
  // control-state snapshot — "is telemetry durable right now" in the
  // same scriptable call.
  const auto& durability = response.at("durability");
  if (durability.isObject()) {
    const auto& sinks = durability.at("sinks");
    if (sinks.isObject() && !sinks.fields().empty()) {
      std::printf(
          "%-28s %10s %10s %8s %8s %8s\n", "spill queue", "pending",
          "acked", "evicted", "corrupt", "apperr");
      for (const auto& [name, wal] : sinks.fields()) {
        std::printf(
            "%-28s %10lld %10lld %8lld %8lld %8lld\n", name.c_str(),
            static_cast<long long>(wal.at("pending_records").asInt()),
            static_cast<long long>(wal.at("acked_seq").asInt()),
            static_cast<long long>(wal.at("evicted_records").asInt()),
            static_cast<long long>(wal.at("corrupt_records").asInt()),
            static_cast<long long>(wal.at("append_errors").asInt()));
      }
    }
    const auto& snap = durability.at("snapshot");
    if (snap.isObject()) {
      std::printf(
          "state snapshot: %s writes=%lld errors=%lld recovered=%s%s%s\n",
          snap.at("path").asString("-").c_str(),
          static_cast<long long>(snap.at("writes").asInt()),
          static_cast<long long>(snap.at("write_errors").asInt()),
          snap.at("recovered").asBool() ? "yes" : "no",
          snap.contains("recover_error") ? " recover_error=" : "",
          snap.at("recover_error").asString("").c_str());
    }
  }
  // Resource-governance section (PR 13): pressure level, per-class
  // usage/eviction accounting, fd/RSS self-checks, admission refusals —
  // "is the daemon protecting its host right now" in the same call.
  const auto& resources = response.at("resources");
  if (resources.isObject()) {
    const auto& disk = resources.at("disk");
    const auto& fds = resources.at("fds");
    std::printf(
        "resources: pressure=%s disk=%lld/%lldB fds=%lld/%lld rss=%lldMB "
        "refusals=%lld write_failures=%lld%s%s\n",
        resources.at("pressure").asString("?").c_str(),
        static_cast<long long>(disk.at("usage_bytes").asInt()),
        static_cast<long long>(disk.at("budget_bytes").asInt()),
        static_cast<long long>(fds.at("open").asInt()),
        static_cast<long long>(fds.at("max").asInt()),
        static_cast<long long>(resources.at("rss_mb").asInt()),
        static_cast<long long>(resources.at("refusals").asInt()),
        static_cast<long long>(resources.at("write_failures").asInt()),
        resources.contains("last_error") ? " last_error=" : "",
        resources.at("last_error").asString("").c_str());
    const auto& classes = resources.at("classes");
    if (classes.isObject() && !classes.fields().empty()) {
      std::printf(
          "%-20s %4s %6s %12s %6s %10s\n", "artifact class", "prio",
          "evict", "bytes", "files", "reclaimed");
      for (const auto& [name, cls] : classes.fields()) {
        std::printf(
            "%-20s %4lld %6s %12lld %6lld %10lld\n", name.c_str(),
            static_cast<long long>(cls.at("priority").asInt()),
            cls.at("never_evict").asBool() ? "never" : "yes",
            static_cast<long long>(cls.at("usage_bytes").asInt()),
            static_cast<long long>(cls.at("files").asInt()),
            static_cast<long long>(cls.at("reclaimed_bytes").asInt()));
      }
    }
  }
  const auto& failpoints = response.at("failpoints");
  for (size_t i = 0; i < failpoints.size(); ++i) {
    const auto& fp = failpoints.at(i);
    std::printf(
        "failpoint %s spec=%s hits=%lld\n",
        fp.at("name").asString("?").c_str(),
        fp.at("spec").asString("-").c_str(),
        static_cast<long long>(fp.at("hits").asInt()));
  }
  return status == "ok" ? 0 : 1;
}

// Fleet pane of glass: one `fleet` RPC against the aggregation relay
// (a daemon started with --relay) instead of a connection per host.
// Exit 0 = no tracked host is stale or lost, 1 = degraded fleet,
// 2 = unreachable or not a relay.
int runFleet() {
  auto req = json::Value::object();
  req["fn"] = "fleet";
  req["top_k"] = FLAGS_top;
  req["detail"] = FLAGS_fleet_hosts;
  if (!FLAGS_metrics.empty()) {
    auto& metrics = req["metrics"];
    metrics = json::Value::array();
    for (const auto& m : splitCsv(FLAGS_metrics)) {
      metrics.append(m);
    }
  }
  if (!FLAGS_skew_metric.empty()) {
    req["skew_metric"] = FLAGS_skew_metric;
  }
  if (FLAGS_depth > 0) {
    req["depth"] = FLAGS_depth;
  }
  if (!FLAGS_pod.empty()) {
    req["pod"] = FLAGS_pod;
  }
  auto response = rpcCall(req);
  if (!response.isObject()) {
    std::cerr << "fleet: daemon unreachable\n";
    return 2;
  }
  if (response.at("status").asString("") != "ok") {
    std::cerr << "fleet: " << response.at("error").asString("failed")
              << "\n";
    return 2;
  }
  const auto& counts = response.at("counts");
  const long long lost = counts.at("lost").asInt();
  const long long stale = counts.at("stale").asInt();
  std::printf(
      "fleet: %lld host(s) — %lld live, %lld stale, %lld lost  "
      "(acks: %s)\n",
      static_cast<long long>(counts.at("hosts").asInt()),
      static_cast<long long>(counts.at("live").asInt()), stale, lost,
      response.at("durable_acks").asBool() ? "durable" : "immediate");
  const auto& ingest = response.at("ingest");
  std::printf(
      "ingest: %lld record(s), %lld duplicate(s) suppressed, "
      "%lld seq gap(s), %lld rollup(s) shed, %lld stale-epoch, "
      "%lld connection(s)\n",
      static_cast<long long>(ingest.at("records").asInt()),
      static_cast<long long>(ingest.at("duplicates_suppressed").asInt()),
      static_cast<long long>(ingest.at("seq_gaps").asInt()),
      static_cast<long long>(ingest.at("shed_rollups").asInt()),
      static_cast<long long>(ingest.at("stale_epoch").asInt()),
      static_cast<long long>(ingest.at("connections").asInt()));
  const long long degraded =
      response.at("health_degraded_components").asInt();
  if (degraded > 0) {
    std::printf("health: %lld degraded component(s) across the fleet\n",
                degraded);
  }
  // Per-version cohort (--versions, or automatically once the fleet is
  // mixed): the canary answer during a rolling upgrade.
  const auto& versionsDoc = response.at("versions");
  if (FLAGS_versions ||
      (versionsDoc.isObject() && versionsDoc.size() > 1)) {
    if (!versionsDoc.isObject() || versionsDoc.size() == 0) {
      std::printf("versions: (relay predates version tracking)\n");
    } else {
      std::string lineOut = "versions:";
      bool first = true;
      for (const auto& [label, count] : versionsDoc.fields()) {
        lineOut += (first ? " " : ", ") +
            std::to_string(static_cast<long long>(count.asInt(0))) +
            " host(s) on " + label;
        first = false;
      }
      const long long skipped =
          response.at("ingest").at("fields_skipped").asInt(0);
      if (skipped > 0) {
        lineOut += "  (" + std::to_string(skipped) +
            " newer-version field(s) skipped)";
      }
      std::printf("%s\n", lineOut.c_str());
    }
  }
  // Tree shape + tree-wide leaf totals (the depth-2 coherence numbers):
  // only worth a line once the relay actually has children.
  const auto& tree = response.at("tree");
  if (tree.isObject() && tree.at("children_count").asInt() > 0) {
    const auto& global = response.at("global").at("ingest");
    std::printf(
        "tree: %lld relay(s), depth %lld, %lld direct child(ren); "
        "global %lld leaf record(s), %lld applied, %lld gap(s)\n",
        static_cast<long long>(tree.at("relays").asInt()),
        static_cast<long long>(tree.at("depth").asInt()),
        static_cast<long long>(tree.at("children_count").asInt()),
        static_cast<long long>(global.at("records").asInt()),
        static_cast<long long>(global.at("applied_sum").asInt()),
        static_cast<long long>(global.at("seq_gaps").asInt()));
  }
  if (tree.isObject() && tree.at("children").isObject()) {
    std::printf(
        "%-28s %-7s %6s %6s %6s %10s %10s %6s %12s\n", "child relay",
        "state", "depth", "relays", "hosts", "records", "applied",
        "gaps", "export-ago-s");
    for (const auto& [name, c] : tree.at("children").fields()) {
      std::printf(
          "%-28s %-7s %6lld %6lld %6lld %10lld %10lld %6lld %12.1f\n",
          name.c_str(), c.at("state").asString("?").c_str(),
          static_cast<long long>(c.at("depth").asInt()),
          static_cast<long long>(c.at("relays").asInt()),
          static_cast<long long>(c.at("hosts").asInt()),
          static_cast<long long>(c.at("records_sum").asInt()),
          static_cast<long long>(c.at("applied_sum").asInt()),
          static_cast<long long>(c.at("seq_gaps").asInt()),
          c.at("seconds_since_export").asDouble());
    }
  }
  const auto& stragglers = response.at("stragglers");
  if (stragglers.size() > 0) {
    std::printf("%-28s %-7s %14s\n", "straggler", "state", "ingest-ago-s");
    for (const auto& s : stragglers.items()) {
      std::printf(
          "%-28s %-7s %14.1f\n", s.at("host").asString("?").c_str(),
          s.at("state").asString("?").c_str(),
          s.at("seconds_since_ingest").asDouble());
    }
  }
  const auto& pods = response.at("pods");
  // Print the pod section for any real pod structure (a single-pod job
  // with --skew_metric included); only the degenerate all-unlabeled
  // ("-") single bucket is noise.
  bool showPods = pods.isObject() && pods.fields().size() > 1;
  if (pods.isObject()) {
    for (const auto& [name, pod] : pods.fields()) {
      showPods = showPods || name != "-" || pod.at("skew").isObject();
    }
  }
  if (showPods) {
    for (const auto& [name, pod] : pods.fields()) {
      std::printf(
          "pod %-16s %lld host(s), %lld live",
          name.c_str(), static_cast<long long>(pod.at("hosts").asInt()),
          static_cast<long long>(pod.at("live").asInt()));
      const auto& skew = pod.at("skew");
      if (skew.isObject()) {
        std::printf(
            "  %s: min %.3f max %.3f spread %.3f",
            skew.at("metric").asString("?").c_str(),
            skew.at("min").asDouble(), skew.at("max").asDouble(),
            skew.at("spread").asDouble());
      }
      std::printf("\n");
    }
  }
  const auto& podDetail = response.at("pod_detail");
  if (podDetail.isObject()) {
    std::printf("pod %s drill-down:\n",
                podDetail.at("pod").asString("?").c_str());
    const auto& agg = podDetail.at("rollup");
    if (agg.isObject()) {
      std::printf(
          "  aggregate: %lld host(s), %lld live, %lld record(s), "
          "applied %lld, %lld gap(s), %lld dup(s)\n",
          static_cast<long long>(agg.at("hosts").asInt()),
          static_cast<long long>(agg.at("live").asInt()),
          static_cast<long long>(agg.at("records_sum").asInt()),
          static_cast<long long>(agg.at("applied_sum").asInt()),
          static_cast<long long>(agg.at("seq_gaps").asInt()),
          static_cast<long long>(agg.at("duplicates").asInt()));
      for (const auto& [metric, m] : agg.at("metrics").fields()) {
        const long long n = m.at("count").asInt();
        std::printf(
            "  %-32s n=%lld mean=%.3f min=%.3f max=%.3f\n",
            metric.c_str(), n,
            n > 0 ? m.at("sum").asDouble() / n : 0.0,
            m.at("min").asDouble(), m.at("max").asDouble());
      }
    }
    for (const auto& [host, h] : podDetail.at("hosts").fields()) {
      std::printf(
          "  member %-24s %-7s applied=%lld records=%lld\n", host.c_str(),
          h.at("state").asString("?").c_str(),
          static_cast<long long>(h.at("applied_seq").asInt()),
          static_cast<long long>(h.at("records").asInt()));
    }
    for (const auto& [child, agg2] : podDetail.at("children").fields()) {
      std::printf(
          "  via child %-21s %lld host(s), %lld record(s)\n",
          child.c_str(),
          static_cast<long long>(agg2.at("hosts").asInt()),
          static_cast<long long>(agg2.at("records_sum").asInt()));
    }
  }
  const auto& table = response.at("metrics");
  if (table.isObject()) {
    for (const auto& [host, values] : table.fields()) {
      std::printf("%-28s", host.c_str());
      for (const auto& [metric, value] : values.fields()) {
        std::printf("  %s=%.3f", metric.c_str(), value.asDouble());
      }
      std::printf("\n");
    }
  }
  const auto& detail = response.at("hosts_detail");
  if (detail.isObject()) {
    std::printf(
        "%-28s %-7s %10s %10s %6s %6s %6s %12s\n", "host", "state",
        "applied", "records", "dups", "gaps", "flaps", "ingest-ago-s");
    for (const auto& [host, h] : detail.fields()) {
      std::printf(
          "%-28s %-7s %10lld %10lld %6lld %6lld %6lld %12.1f\n",
          host.c_str(), h.at("state").asString("?").c_str(),
          static_cast<long long>(h.at("applied_seq").asInt()),
          static_cast<long long>(h.at("records").asInt()),
          static_cast<long long>(h.at("duplicates").asInt()),
          static_cast<long long>(h.at("seq_gaps").asInt()),
          static_cast<long long>(h.at("flaps").asInt()),
          h.at("seconds_since_ingest").asDouble());
    }
  }
  return (lost > 0 || stale > 0) ? 1 : 0;
}

int runJobs(bool quiet = false); // defined below; top embeds it

// Live dashboard: host line + TPU device table, redrawn in place every
// --watch_interval_ms (a `watch` + `tpu` combination; --once for scripts).
int runTop(bool once) {
  const int64_t intervalMs = std::max<int64_t>(FLAGS_watch_interval_ms, 500);
  int misses = 0;
  while (true) {
    auto req = json::Value::object();
    req["fn"] = "queryMetrics";
    req["start_ts"] = nowUnixMillis() - 130'000;
    req["end_ts"] = nowUnixMillis();
    auto& arr = req["metrics"];
    arr = json::Value::array();
    for (const char* name :
         {"cpu_util", "loadavg_1m", "mem_available_kb", "mem_total_kb",
          "context_switches_per_sec"}) {
      arr.append(name);
    }
    auto response = rpcCall(req);
    if (!response.isObject() || !response.at("metrics").isObject()) {
      if (++misses >= 5) {
        std::cerr << "top: daemon unreachable\n";
        return 2;
      }
      std::this_thread::sleep_for(std::chrono::milliseconds(intervalMs));
      continue;
    }
    misses = 0;
    const auto& m = response.at("metrics");
    if (!once) {
      std::printf("\033[H\033[2J"); // cursor home + clear
    }
    time_t now = time(nullptr);
    char stamp[32];
    std::strftime(stamp, sizeof(stamp), "%H:%M:%S", ::localtime(&now));
    std::printf("dynolog_tpu top — %s  (every %lldms, Ctrl-C exits)\n",
                stamp, static_cast<long long>(intervalMs));
    auto cell = [&](const char* name, const char* fmt) {
      auto v = latestOf(m.at(name));
      char buf[32];
      if (!v) {
        return std::string("-");
      }
      std::snprintf(buf, sizeof(buf), fmt, *v);
      return std::string(buf);
    };
    auto avail = latestOf(m.at("mem_available_kb"));
    auto total = latestOf(m.at("mem_total_kb"));
    std::string mem = "-";
    if (avail && total && *total > 0) {
      char buf[48];
      std::snprintf(buf, sizeof(buf), "%.1f/%.1f GiB free",
                    *avail / (1 << 20), *total / double(1 << 20));
      mem = buf;
    }
    std::printf("host: cpu %s%%  load1 %s  mem %s  ctxsw/s %s\n\n",
                cell("cpu_util", "%.1f").c_str(),
                cell("loadavg_1m", "%.2f").c_str(), mem.c_str(),
                cell("context_switches_per_sec", "%.0f").c_str());
    runTpuTable(); // prints its own message when no TPU metrics exist
    std::printf("\n");
    runJobs(/*quiet=*/true); // job telemetry, when any app reports it
    if (once) {
      return 0;
    }
    std::fflush(stdout);
    std::this_thread::sleep_for(std::chrono::milliseconds(intervalMs));
  }
}

// Job telemetry table: one row per job<id>.* prefix in the store (the
// shim's "pstat" reports) — training throughput and step-time SLOs at a
// glance, the application-level companion of `dyno tpu`.
int runJobs(bool quiet) {
  auto listReq = json::Value::object();
  listReq["fn"] = "listMetrics";
  auto listed = rpcCall(listReq);
  if (!listed.isObject() || !listed.at("metrics").isArray()) {
    if (!quiet) {
      std::cerr << "jobs: daemon unreachable or metric store disabled\n";
    }
    return 2;
  }
  std::set<std::string> jobs;
  std::vector<std::string> jobSeries;
  const auto& names = listed.at("metrics");
  for (size_t i = 0; i < names.size(); ++i) {
    const std::string name = names.at(i).asString("");
    if (name.rfind("job", 0) != 0) {
      continue;
    }
    size_t dot = name.find('.');
    if (dot == std::string::npos || dot <= 3) {
      continue;
    }
    // Digits-only between "job" and "." — a hypothetical "jobqueue.depth"
    // series must not render a bogus row (same validation as `dyno tpu`).
    const std::string id = name.substr(3, dot - 3);
    if (id.find_first_not_of("0123456789") != std::string::npos) {
      continue;
    }
    jobs.insert(name.substr(0, dot));
    jobSeries.push_back(name);
  }
  if (jobs.empty()) {
    if (!quiet) {
      std::cerr << "jobs: no job telemetry in the store (apps report it "
                   "by calling TraceClient.step())\n";
    }
    return 1;
  }
  auto req = json::Value::object();
  req["fn"] = "queryMetrics";
  req["start_ts"] = nowUnixMillis() - 130'000;
  req["end_ts"] = nowUnixMillis();
  auto& arr = req["metrics"];
  arr = json::Value::array();
  for (const auto& n : jobSeries) {
    arr.append(n);
  }
  auto response = rpcCall(req);
  if (!response.isObject() || !response.at("metrics").isObject()) {
    if (!quiet) {
      std::cerr << "jobs: query failed\n";
    }
    return 2;
  }
  const auto& series = response.at("metrics");
  auto cell = [&](const std::string& job, const char* metric,
                  const char* fmt) {
    auto v = latestOf(series.at(job + "." + metric));
    char buf[32];
    if (!v) {
      return std::string("-");
    }
    std::snprintf(buf, sizeof(buf), fmt, *v);
    return std::string(buf);
  };
  std::printf("%-10s %10s %9s %9s %9s\n", "job", "steps/s", "p50 ms",
              "p95 ms", "max ms");
  for (const auto& job : jobs) {
    std::printf(
        "%-10s %10s %9s %9s %9s\n", job.c_str(),
        cell(job, "steps_per_sec", "%10.1f").c_str(),
        cell(job, "step_time_p50_ms", "%9.2f").c_str(),
        cell(job, "step_time_p95_ms", "%9.2f").c_str(),
        cell(job, "step_time_max_ms", "%9.2f").c_str());
  }
  return 0;
}

// Anomaly-triggered capture rules living in the daemon: `add` installs a
// threshold watch on a metric-store series, the daemon fires a gputrace-
// style config at the job when it trips (addTraceTrigger RPC).
int runAutoTrigger(const std::vector<std::string>& positional) {
  // A daemon-side {"status":"failed",...} must fail the CLI too, so ops
  // scripts installing rules can't mistake a refusal for success.
  auto rpcChecked = [](const json::Value& req, json::Value* out = nullptr) {
    json::Value response;
    int rc = rpc(req, &response);
    if (rc == 0 && response.isObject() &&
        response.at("status").asString("ok") != "ok") {
      rc = 1;
    }
    if (out) {
      *out = std::move(response);
    }
    return rc;
  };
  const std::string sub = positional.size() > 1 ? positional[1] : "list";
  if (sub == "list") {
    auto req = json::Value::object();
    req["fn"] = "listTraceTriggers";
    auto response = rpcCall(req);
    if (!response.isObject()) {
      std::cerr << "autotrigger: daemon unreachable\n";
      return 2;
    }
    if (response.at("status").asString("ok") != "ok") {
      std::cerr << "autotrigger: " << response.at("error").asString()
                << "\n";
      return 1;
    }
    const auto& triggers = response.at("triggers");
    if (triggers.size() == 0) {
      std::cout << "no auto-trigger rules installed" << std::endl;
      return 0;
    }
    std::printf("%-3s %-32s %-5s %10s %4s %6s %7s %5s %4s %9s %s\n", "id",
                "metric", "op", "threshold", "for", "cd(s)", "capture",
                "fires", "att", "last val", "last result");
    for (size_t i = 0; i < triggers.size(); ++i) {
      const auto& t = triggers.at(i);
      std::string last = t.at("last_result").asString("");
      if (last.empty()) {
        last = "-";
      }
      // A fired shim rule's trace path lives in last_trace_path; surface
      // it so operators can find the capture without a raw RPC (push-mode
      // results already embed their dir).
      std::string path = t.at("last_trace_path").asString("");
      if (!path.empty() && last.find(path) == std::string::npos) {
        last += " -> " + path;
      }
      std::printf(
          "%-3lld %-32.32s %-5s %10.4g %4lld %6lld %7s %5lld %4lld %9.4g "
          "%s\n",
          static_cast<long long>(t.at("id").asInt()),
          t.at("metric").asString().c_str(),
          t.at("op").asString().c_str(), t.at("threshold").asDouble(),
          static_cast<long long>(t.at("for_ticks").asInt()),
          static_cast<long long>(t.at("cooldown_s").asInt()),
          t.at("capture").asString().c_str(),
          static_cast<long long>(t.at("fire_count").asInt()),
          static_cast<long long>(t.at("attempt_count").asInt()),
          t.at("last_value").asDouble(), last.c_str());
    }
    return 0;
  }
  if (sub == "remove") {
    if (FLAGS_trigger_id < 0 && FLAGS_metric.empty()) {
      std::cerr << "error: autotrigger remove needs --trigger_id or "
                   "--metric (removes every rule watching that series)\n";
      return 1;
    }
    auto req = json::Value::object();
    req["fn"] = "removeTraceTrigger";
    if (!FLAGS_metric.empty()) {
      req["metric"] = FLAGS_metric;
    } else {
      req["trigger_id"] = FLAGS_trigger_id;
    }
    return rpcChecked(req);
  }
  if (sub != "add") {
    std::cerr << "error: unknown autotrigger subcommand '" << sub
              << "' (add | list | remove)\n";
    return 1;
  }
  if (FLAGS_metric.empty()) {
    std::cerr << "error: --metric is required (see `dyno metrics`)\n";
    return 1;
  }
  if (FLAGS_log_file.empty()) {
    std::cerr << "error: --log_file is required\n";
    return 1;
  }
  if (FLAGS_above.empty() == FLAGS_below.empty()) {
    std::cerr << "error: exactly one of --above / --below is required\n";
    return 1;
  }
  const bool below = !FLAGS_below.empty();
  const std::string& rawThreshold = below ? FLAGS_below : FLAGS_above;
  double threshold;
  try {
    // Whole-token parse: "30e" or "30,5" must be rejected, not truncated.
    size_t consumed = 0;
    threshold = std::stod(rawThreshold, &consumed);
    if (consumed != rawThreshold.size()) {
      throw std::invalid_argument(rawThreshold);
    }
  } catch (const std::exception&) {
    std::cerr << "error: threshold is not a number: '" << rawThreshold
              << "'\n";
    return 1;
  }
  if (FLAGS_capture != "shim" && FLAGS_capture != "push") {
    std::cerr << "error: --capture must be 'shim' or 'push'\n";
    return 1;
  }
  if (FLAGS_with_baseline && FLAGS_capture == "push") {
    std::cerr << "error: --with_baseline works with --capture=shim; for a "
                 "push-mode baseline run `dyno pushtrace` directly\n";
    return 1;
  }
  // Closed-loop diagnosis: the rule needs a baseline to diff against.
  // With --with_baseline and no explicit --baseline, the healthy-state
  // capture armed below IS the baseline (the engine resolves its
  // per-pid manifest when the fired diagnosis runs).
  std::string diagnoseBaseline = FLAGS_baseline;
  if (FLAGS_diagnose && diagnoseBaseline.empty()) {
    if (!FLAGS_with_baseline) {
      std::cerr << "error: --diagnose needs --baseline (a saved baseline "
                   "or healthy capture) or --with_baseline\n";
      return 1;
    }
    diagnoseBaseline =
        tracing::withTracePathSuffix(FLAGS_log_file, "_baseline");
  }
  auto req = json::Value::object();
  req["fn"] = "addTraceTrigger";
  req["metric"] = FLAGS_metric;
  req["op"] = below ? "below" : "above";
  req["threshold"] = threshold;
  req["for_ticks"] = FLAGS_for_ticks;
  req["cooldown_s"] = FLAGS_cooldown_s;
  req["max_fires"] = FLAGS_max_fires;
  req["job_id"] = FLAGS_job_id;
  req["duration_ms"] = FLAGS_duration_ms;
  req["log_file"] = FLAGS_log_file;
  req["process_limit"] = FLAGS_process_limit;
  req["capture"] = FLAGS_capture;
  req["profiler_host"] = FLAGS_profiler_host;
  req["profiler_port"] = FLAGS_profiler_port;
  req["peers"] = FLAGS_peers;
  req["sync_delay_ms"] = FLAGS_sync_delay_ms;
  req["keep_last"] = FLAGS_keep_last;
  req["diagnose"] = FLAGS_diagnose;
  if (FLAGS_diagnose) {
    req["baseline"] = diagnoseBaseline;
  }
  json::Value response;
  int rc = rpcChecked(req, &response);
  if (rc == 0) {
    std::cout << "trigger " << response.at("trigger_id").asInt()
              << " installed: trace job " << FLAGS_job_id << " when "
              << FLAGS_metric << (below ? " < " : " > ") << threshold
              << " for " << FLAGS_for_ticks << " sample(s)" << std::endl;
  }
  if (rc == 0 && FLAGS_with_baseline) {
    // Healthy-state reference captured at arm time: a fired anomaly trace
    // has something to `dynolog_tpu.trace FIRED --diff` against.
    std::string baselinePath =
        tracing::withTracePathSuffix(FLAGS_log_file, "_baseline");
    auto base = json::Value::object();
    base["fn"] = "setKinetOnDemandRequest";
    // Knobs excluded: the rule's FIRED captures use profiler defaults
    // (the daemon builds those configs), so the baseline must be captured
    // identically or `trace FIRED --diff BASELINE` compares apples to
    // oranges.
    base["config"] = buildTraceConfig(
        baselinePath, /*startTimeMs=*/0, /*iterations=*/-1,
        /*includeCaptureKnobs=*/false);
    base["job_id"] = FLAGS_job_id;
    base["process_limit"] = FLAGS_process_limit;
    base["pids"] = json::Value::array();
    auto baseResp = rpcCall(base);
    if (!baseResp.isObject()) {
      std::cout << "warning: baseline not captured (daemon unreachable "
                   "for the baseline request)" << std::endl;
    } else if (baseResp.at("activityProfilersTriggered").size() > 0) {
      // Triggered, not merely matched: a busy profiler (undelivered prior
      // config) matches but captures nothing.
      std::cout << "baseline capture started -> " << baselinePath
                << " (diff a fired trace with: python -m dynolog_tpu.trace "
                   "FIRED --diff "
                << baselinePath << ")" << std::endl;
    } else {
      bool busy = baseResp.at("activityProfilersBusy").asInt(0) > 0;
      size_t matched = baseResp.at("processesMatched").size();
      std::string why, fix;
      if (busy) {
        why = "profiler busy with an undelivered config";
        fix = "re-run this command once the app is idle";
      } else if (matched > 0) {
        why = "matched " + std::to_string(matched) +
            " process(es) but triggered none";
        fix = "check --process_limit";
      } else {
        why = "no registered processes for job " +
            std::to_string(FLAGS_job_id);
        fix = "re-run this command once the app is up";
      }
      std::cout << "warning: baseline not captured (" << why << "); " << fix
                << std::endl;
    }
  }
  return rc;
}

void usage() {
  std::cerr
      << "usage: dyno [--hostname H] [--port P] <verb> [options]\n"
      << "verbs:\n"
      << "  status      check daemon status\n"
      << "  health      supervision state per component (collectors, "
         "sinks); exit 0=up 1=degraded 2=unreachable\n"
      << "  selftrace   the daemon's own span journal (RPC verbs, "
         "collector ticks, sink pushes, shim capture/convert) as "
         "Chrome-trace JSON (--trace_id filters one request; "
         "--log_file writes a file)\n"
      << "  version     print CLI + daemon version\n"
      << "  gputrace    trigger an on-demand trace (reference verb name)\n"
      << "  tpurace     alias of gputrace\n"
      << "  cputrace    host scheduling trace: per-thread CPU breakdown\n"
      << "              (--duration_ms, --top)\n"
      << "  perfsample  PMU sampling profile: per-thread event weights\n"
      << "              (--event, --sample_period, --duration_ms, --top)\n"
      << "  metrics     list metrics held by the daemon's history store\n"
      << "  query       fetch metric history (--metrics, --start_ts, "
         "--end_ts, --stats)\n"
      << "  watch       live-follow metrics (--metrics, "
         "--watch_interval_ms)\n"
      << "  tpu         device table: duty/tensorcore/MXU %, HBM, "
         "throttle, link health\n"
      << "  jobs        job telemetry table: steps/s, step-time "
         "p50/p95/max per reporting job\n"
      << "  tpustatus   TPU runtime status via its gRPC metric service "
         "(host, core ids)\n"
      << "  top         live host + TPU dashboard (`top once` prints one "
         "frame)\n"
      << "  pushtrace   capture via the app's jax.profiler server "
         "(--profiler_port; no shim needed)\n"
      << "  fetch       pull a capture artifact off the daemon's host "
         "over the RPC connection\n"
      << "              (--path=/abs/remote/artifact [--log_file=dest]; "
         "needs the daemon's --trace_output_root)\n"
      << "  autotrigger add|list|remove — fire a trace automatically when "
         "a metric crosses a threshold\n"
      << "              (--metric, --above|--below, --for_ticks, "
         "--cooldown_s, --max_fires, --job_id, --log_file,\n"
      << "              --capture=shim|push [--profiler_port] for shim-free "
         "capture via the app's jax.profiler server,\n"
      << "              --with_baseline to also capture a healthy-state "
         "reference for trace --diff,\n"
      << "              --diagnose [--baseline=] to auto-run the "
         "trace-diff diagnosis on every fired capture)\n"
      << "  diagnose    trace-diff regression diagnosis: list reports "
         "(--trace_id filters), or run one now\n"
      << "              (--log_file=CAPTURE --baseline=BASELINE); exit "
         "0=clean 1=failed 2=unreachable 3=regressed\n"
      << "  fleet       fleet view from an aggregation relay (a daemon "
         "run with --relay): liveness counts,\n"
      << "              dedup/ingest counters, stragglers "
         "(--top), per-pod skew (--skew_metric), per-host\n"
      << "              rollups (--metrics), full table (--fleet_hosts); "
         "exit 0=all live 1=degraded 2=unreachable;\n"
      << "              relay trees (--relay_upstream daemons): global "
         "view is tree-wide, --depth=N prints the\n"
      << "              per-child-relay breakdown, --pod=NAME drills "
         "into one pod's members + aggregates,\n"
      << "              --versions prints the per-version host cohort "
         "(rolling-upgrade canary visibility)\n"
      << "run `dyno --help` for flags\n";
}

} // namespace

int main(int argc, char** argv) {
  auto positional = dynotpu::FlagRegistry::instance().parse(argc, argv);
  if (positional.empty()) {
    usage();
    return 1;
  }
  const std::string& verb = positional[0];
  if (verb == "status") {
    return runStatus();
  }
  if (verb == "health") {
    return runHealth();
  }
  if (verb == "selftrace") {
    return runSelfTrace();
  }
  if (verb == "version") {
    return runVersion();
  }
  if (verb == "gputrace" || verb == "tpurace") {
    return runTrace();
  }
  if (verb == "cputrace") {
    return runCpuTrace();
  }
  if (verb == "perfsample") {
    return runPerfSample();
  }
  if (verb == "pushtrace") {
    return runPushTrace();
  }
  if (verb == "fetch") {
    return runFetch();
  }
  if (verb == "metrics") {
    return runQuery(/*listOnly=*/true);
  }
  if (verb == "query") {
    return runQuery(/*listOnly=*/false);
  }
  if (verb == "watch") {
    return runWatch();
  }
  if (verb == "tpu") {
    return runTpuTable();
  }
  if (verb == "jobs") {
    return runJobs();
  }
  if (verb == "top") {
    bool once = false;
    for (size_t i = 1; i < positional.size(); ++i) {
      once = once || positional[i] == "once";
    }
    return runTop(once);
  }
  if (verb == "autotrigger") {
    return runAutoTrigger(positional);
  }
  if (verb == "diagnose") {
    return runDiagnose();
  }
  if (verb == "fleet") {
    return runFleet();
  }
  if (verb == "tpustatus") {
    auto req = json::Value::object();
    req["fn"] = "getTpuRuntimeStatus";
    return rpc(req);
  }
  std::cerr << "unknown verb: " << verb << "\n";
  usage();
  return 1;
}
