#include "src/rpc/ServiceHandler.h"

#include <sys/stat.h>

#include <algorithm>
#include <chrono>
#include <limits>
#include <map>
#include <mutex>

#include "src/common/Defs.h"
#include "src/common/Failpoints.h"
#include "src/common/Flags.h"
#include "src/common/GrpcClient.h"
#include "src/core/Health.h"
#include "src/common/Ports.h"
#include "src/common/ProtoWire.h"
#include "src/common/Version.h"
#include "src/core/Histograms.h"
#include "src/core/ResourceGovernor.h"
#include "src/core/SinkWal.h"
#include "src/core/SpanJournal.h"
#include "src/core/StateSnapshot.h"
#include "src/metrics/MetricStore.h"
#include "src/relay/FleetRelay.h"
#include "src/tracing/AutoTrigger.h"
#include "src/tracing/CaptureUtils.h"
#include "src/tracing/CpuTraceCapturer.h"
#include "src/tracing/Diagnoser.h"
#include "src/tracing/IPCMonitor.h"
#include "src/tracing/PushTraceCapturer.h"
#include "src/tpumon/TpuMonitor.h"

DYN_DEFINE_string(
    trace_output_root,
    "",
    "When set, every RPC-supplied trace output path (pushtrace log_file, "
    "auto-trigger rule log_file — paths the DAEMON writes or prunes) must "
    "be an absolute path under this directory; requests pointing elsewhere "
    "are refused. Bounds what a network caller can make the daemon write. "
    "Empty = unrestricted (reference behavior).");

DYN_DEFINE_bool(
    enable_failpoints,
    false,
    "Allow the `failpoint` RPC verb to arm/disarm named failpoints at "
    "runtime (fault drills, integration tests). Off by default: a "
    "network caller must not be able to inject faults into a production "
    "daemon. $DYNO_FAILPOINTS arming at startup works regardless.");

namespace dynotpu {

namespace {

// Lexical containment check for caller-supplied output paths against
// --trace_output_root. Deliberately lexical (absolute, no '.'/'..'
// segments, prefix match): it bounds what a NETWORK caller can name;
// symlinks inside the root are the operator's own filesystem layout.
bool pathAllowedByRoot(const std::string& path, std::string* error) {
  const std::string& root = ::FLAGS_trace_output_root;
  if (root.empty()) {
    return true;
  }
  auto fail = [&](const std::string& why) {
    *error = "log_file " + why + " (--trace_output_root=" + root + ")";
    return false;
  };
  if (path.empty() || path[0] != '/') {
    return fail("must be an absolute path under the trace output root");
  }
  std::string segment;
  for (size_t i = 1; i <= path.size(); ++i) {
    if (i == path.size() || path[i] == '/') {
      if (segment == "." || segment == "..") {
        return fail("must not contain '.' or '..' segments");
      }
      segment.clear();
    } else {
      segment += path[i];
    }
  }
  std::string normRoot = root;
  while (normRoot.size() > 1 && normRoot.back() == '/') {
    normRoot.pop_back();
  }
  if (normRoot == "/") {
    return true; // root "/" = any absolute, traversal-free path
  }
  if (path.compare(0, normRoot.size(), normRoot) != 0 ||
      (path.size() > normRoot.size() && path[normRoot.size()] != '/')) {
    return fail("is outside the trace output root");
  }
  return true;
}

// Strictly parses an optional trace-id filter field (1-16 hex chars,
// as gputrace prints): true with *out = 0 when absent, true with the
// parsed id when valid, false on anything else — a typo'd filter must
// error loudly, never silently match everything. One definition for
// every verb that filters by trace-id (selftrace, diagnose).
bool parseTraceIdFilter(const std::string& filter, uint64_t* out) {
  *out = 0;
  if (filter.empty()) {
    return true;
  }
  bool valid = filter.size() <= 16;
  for (char c : filter) {
    valid = valid &&
        ((c >= '0' && c <= '9') || (c >= 'a' && c <= 'f') ||
         (c >= 'A' && c <= 'F'));
  }
  return valid && (*out = std::strtoull(filter.c_str(), nullptr, 16)) != 0;
}

constexpr char kBadTraceIdFilter[] =
    "trace_id must be 1-16 hex chars (as printed by gputrace)";

// Negotiated-wire-version accounting for the health verb's "wire"
// section: every `hello` verb records the proto the connection settled
// on (min(theirs, ours)) and the peer's build string, so a mixed-version
// control plane is visible from one health call during a rolling
// upgrade. Bounded: hostile build strings cannot grow the map past
// kMaxPeerBuilds (overflow lands in "other").
class WireNegotiations {
 public:
  static WireNegotiations& instance() {
    static WireNegotiations* registry = new WireNegotiations();
    return *registry;
  }

  void note(int64_t proto, const std::string& build) {
    std::lock_guard<std::mutex> lock(mutex_);
    protoCounts_[proto]++;
    std::string key = build.empty() ? "v0" : build.substr(0, 64);
    if (builds_.size() >= kMaxPeerBuilds && builds_.find(key) == builds_.end()) {
      key = "other";
    }
    builds_[key]++;
  }

  json::Value snapshot() const {
    std::lock_guard<std::mutex> lock(mutex_);
    auto out = json::Value::object();
    out["proto"] = kWireProtoVersion;
    out["build"] = kVersion;
    auto negotiated = json::Value::object();
    for (const auto& [proto, count] : protoCounts_) {
      negotiated[std::to_string(proto)] = count;
    }
    out["negotiated"] = std::move(negotiated);
    auto builds = json::Value::object();
    for (const auto& [build, count] : builds_) {
      builds[build] = count;
    }
    out["peer_builds"] = std::move(builds);
    return out;
  }

 private:
  static constexpr size_t kMaxPeerBuilds = 32;
  mutable std::mutex mutex_;
  std::map<int64_t, int64_t> protoCounts_; // guarded_by(mutex_)
  std::map<std::string, int64_t> builds_; // guarded_by(mutex_)
};

// Armed/previously-hit failpoints as the JSON array both the health and
// failpoint verbs serve — one writer, so a new Stat field can't reach
// one verb and not the other.
json::Value listFailpointsJson() {
  auto armed = json::Value::array();
  for (const auto& stat : failpoints::Registry::instance().list()) {
    auto entry = json::Value::object();
    entry["name"] = stat.name;
    entry["spec"] = stat.spec;
    entry["hits"] = stat.hits;
    entry["remaining"] = stat.remaining;
    armed.append(std::move(entry));
  }
  return armed;
}

} // namespace

std::string ServiceHandler::processRequest(
    const std::string& requestStr,
    std::string* streamFileOut) {
  // Fault drill for the RPC plane: a throw here exercises the worker
  // pool's containment (the caller loses its connection, the daemon
  // loses nothing).
  failpoints::maybeFail("rpc.verb");
  std::string err;
  auto request = json::Value::parse(requestStr, &err);
  if (!err.empty() || !request.isObject()) {
    DLOG_ERROR << "Bad RPC request: " << err << " in: " << requestStr;
    return "";
  }
  if (!request.contains("fn")) {
    DLOG_ERROR << "RPC request missing 'fn': " << requestStr;
    return "";
  }
  const std::string fn = request.at("fn").asString();
  // Request identity: the optional `trace_ctx` wire field ("%016x/%016x",
  // minted by dyno/unitrace). Absent or malformed ⇒ the daemon mints one
  // (SpanScope does), so pre-tracing clients stay wire-compatible. The
  // verb span parents every downstream span of this request — including
  // the Python shim's, via the TRACE_CONTEXT config key injected below.
  auto wireCtx = TraceContext::parse(request.at("trace_ctx").asString(""));
  SpanScope verbSpan(
      "rpc." + fn,
      wireCtx ? wireCtx->traceId : 0,
      wireCtx ? wireCtx->spanId : 0);
  // Observed on every exit path (throwing verb bodies included). The
  // label is re-pointed at "unknown" for an unmatched fn: a hostile fn
  // string must not mint scrape series.
  ScopedLatency verbLatency(&HistogramRegistry::observeRpcVerb, fn);
  auto response = json::Value::object();

  // Graceful degradation under resource pressure: NEW capture/diagnose
  // admissions are refused while the governor reports HARD pressure —
  // admitting work the daemon cannot finish (full disk, fd exhaustion)
  // would turn one failing resource into partial artifacts and wedged
  // sessions. The refusal is TYPED (status "refused" +
  // error_kind "resource_pressure") so callers and scripts can
  // distinguish "retry after recovery" from a real failure; read-only
  // verbs (health, metrics, fleet, selftrace) always answer — pressure
  // must be diagnosable through the daemon, not around it.
  auto refusedUnderPressure = [&response](const char* what) {
    std::string reason;
    if (ResourceGovernor::instance().admit(what, &reason)) {
      return false;
    }
    response["status"] = "refused";
    response["error_kind"] = "resource_pressure";
    response["error"] = reason;
    return true;
  };

  if (fn == "getStatus") {
    response["status"] = getStatus();
    // Build identity on the cheapest verb every prober already calls —
    // fleet tooling correlates behavior against version without a
    // second RPC.
    response["version"] = kVersion;
    response["proto"] = kWireProtoVersion;
  } else if (fn == "getVersion") {
    response["version"] = kVersion;
    response["proto"] = kWireProtoVersion;
  } else if (fn == "hello") {
    // Versioned wire hello: the peer announces {"proto": N, "build":
    // "..."} and both sides settle on min(theirs, ours). A client that
    // never sends one is proto 0 — today's wire, fully served. The
    // negotiation is RECORDED (health's "wire" section), never
    // enforced: version skew degrades to the common subset, it does not
    // refuse service.
    const int64_t theirs =
        std::max<int64_t>(request.at("proto").asInt(0), 0);
    const int64_t negotiated = std::min<int64_t>(theirs, kWireProtoVersion);
    WireNegotiations::instance().note(
        negotiated, request.at("build").asString(""));
    response["status"] = "ok";
    response["proto"] = negotiated;
    response["server_proto"] = kWireProtoVersion;
    response["build"] = kVersion;
    // Durable-schema advertisement: what this build writes (the
    // downgrade-planning answer — see docs/COMPATIBILITY.md).
    auto schemas = json::Value::object();
    schemas["wal_record"] = kWalRecordVersion;
    schemas["state_snapshot"] = kSnapshotVersion;
    response["schemas"] = std::move(schemas);
  } else if (fn == "setKinetOnDemandRequest" || fn == "setOnDemandTraceConfig") {
    // Primary verb name kept for dyno-CLI/libkineto wire compatibility.
    if (refusedUnderPressure("capture config")) {
      // handled
    } else if (!request.contains("config") || !request.contains("pids")) {
      response["status"] = "failed";
    } else {
      std::set<int32_t> pids;
      for (const auto& p : request.at("pids").items()) {
        pids.insert(static_cast<int32_t>(p.asInt()));
      }
      int64_t jobId = request.at("job_id").asInt(0);
      int32_t limit =
          static_cast<int32_t>(request.at("process_limit").asInt(1000));
      int32_t configType = static_cast<int32_t>(request.at("config_type")
              .asInt(static_cast<int32_t>(TraceConfigType::ACTIVITIES)));
      // The installed config carries this request's identity into the
      // Python shim (TRACE_CONTEXT=..., parented under this verb span)
      // unless the caller built one in — a unitrace-authored context
      // wins over the daemon's injection.
      auto result = setOnDemandTraceConfig(
          jobId,
          pids,
          withTraceContext(
              request.at("config").asString(), verbSpan.childContext()),
          configType,
          limit);
      response = result.toJson();
    }
  } else if (fn == "queryMetrics") {
    if (!metricStore_) {
      response["status"] = "failed";
      response["error"] = "metric store not enabled";
    } else {
      int64_t startTs = request.at("start_ts").asInt(0);
      int64_t endTs = request.at("end_ts").asInt(INT64_MAX);
      std::vector<std::string> names;
      for (const auto& n : request.at("metrics").items()) {
        names.push_back(n.asString());
      }
      response = metricStore_->query(
          names, startTs, endTs, request.at("stats").asBool(false));
    }
  } else if (fn == "cputrace") {
    // Async: a capture must never wedge the single dispatch thread. Clients
    // poll cputraceResult for the report.
    if (!refusedUnderPressure("cputrace capture")) {
      int64_t durationMs = request.at("duration_ms").asInt(500);
      int64_t top = request.at("top").asInt(20);
      response = cpuTraceSession_.start(
          [durationMs, top](const std::atomic<bool>& cancel) {
            return captureCpuTrace(durationMs, top, &cancel);
          });
      if (response.at("status").asString() == "started") {
        response["duration_ms"] = tracing::clampCaptureDurationMs(durationMs);
      }
    }
  } else if (fn == "cputraceResult") {
    response = cpuTraceSession_.result();
  } else if (fn == "perfsample") {
    std::string event = request.at("event").asString();
    if (event.empty()) {
      event = "cycles";
    }
    int64_t durationMs = request.at("duration_ms").asInt(500);
    int64_t top = request.at("top").asInt(20);
    // Negative periods would wrap in the uint64 cast; 0 = capturer default.
    uint64_t period = static_cast<uint64_t>(
        std::max<int64_t>(request.at("sample_period").asInt(0), 0));
    if (!refusedUnderPressure("perfsample capture")) {
      response = perfSampleSession_.start(
          [event, durationMs, period, top](const std::atomic<bool>& cancel) {
            return capturePerfSamples(event, durationMs, period, top,
                                      &cancel);
          });
      if (response.at("status").asString() == "started") {
        response["duration_ms"] = tracing::clampCaptureDurationMs(durationMs);
      }
    }
  } else if (fn == "perfsampleResult") {
    response = perfSampleSession_.result();
  } else if (fn == "pushtrace") {
    // Push-mode capture through the app's jax.profiler server (no shim);
    // async like the other captures so Profile()'s blocking window never
    // wedges the dispatch thread.
    int64_t durationMs = request.at("duration_ms").asInt(2000);
    int profilerPort =
        static_cast<int>(request.at("profiler_port").asInt(9012));
    std::string profilerHost =
        request.at("profiler_host").asString("localhost");
    std::string logFile = request.at("log_file").asString();
    // Optional per-capture tracer levels (absent = jax profile defaults).
    // Range-validated at the
    // RPC boundary: the CLI filters negatives, but the JSON RPC is the
    // public surface and a stray -1 would serialize as a 2^64-1 varint
    // in ProfileOptions.
    tracing::PushProfileOptions opts;
    bool levelsValid = true;
    for (auto& [key, slot] :
         {std::pair<const char*, int*>{
              "host_tracer_level", &opts.hostTracerLevel},
          {"device_tracer_level", &opts.deviceTracerLevel},
          {"python_tracer_level", &opts.pythonTracerLevel}}) {
      const auto& field = request.at(key);
      if (field.isNull()) {
        continue; // absent = daemon default
      }
      // Fail closed on type AND range: a string "7" (a shell wrapper
      // that forgot to cast) must not silently capture at the default.
      int64_t v = field.asInt(-1);
      if (!field.isInt() || v < 0 || v > 9) {
        levelsValid = false;
      } else {
        *slot = static_cast<int>(v);
      }
    }
    std::string pathError;
    if (refusedUnderPressure("pushtrace capture")) {
      // typed refusal already in `response`
    } else if (!levelsValid) {
      response["status"] = "failed";
      response["error"] = "tracer levels must be in [0, 9]";
    } else if (logFile.empty()) {
      response["status"] = "failed";
      response["error"] = "log_file required";
    } else if (!pathAllowedByRoot(logFile, &pathError)) {
      response["status"] = "failed";
      response["error"] = pathError;
    } else {
      response = pushTraceSession_.start(
          AsyncReportSession::CaptureFnWithProgress(
              [profilerHost, profilerPort, durationMs, logFile, opts](
                  const std::atomic<bool>& cancel,
                  const AsyncReportSession::ProgressFn& progress) {
                // The streaming write publishes bytes_streamed progress:
                // `pushtraceResult` polls show a live capture moving.
                return tracing::capturePushTrace(
                    profilerHost, profilerPort, durationMs, logFile,
                    &cancel, opts, progress);
              }));
      if (response.at("status").asString() == "started") {
        response["duration_ms"] = tracing::clampPushDurationMs(durationMs);
      }
    }
  } else if (fn == "pushtraceResult") {
    response = pushTraceSession_.result();
  } else if (fn == "listMetrics") {
    if (!metricStore_) {
      response["status"] = "failed";
      response["error"] = "metric store not enabled";
    } else {
      response = metricStore_->listMetrics();
    }
  } else if (fn == "health") {
    response = health();
  } else if (fn == "fleet") {
    response = fleet(request);
  } else if (fn == "selftrace") {
    response = selftrace(request);
  } else if (fn == "fetchTrace") {
    response = fetchTrace(request, streamFileOut);
  } else if (fn == "diagnose") {
    response = diagnose(request);
  } else if (fn == "failpoint") {
    response = failpoint(request);
  } else if (fn == "getTpuRuntimeStatus") {
    response = getTpuRuntimeStatus();
  } else if (fn == "addTraceTrigger") {
    response = addTraceTrigger(request);
  } else if (fn == "removeTraceTrigger") {
    // By id, or by metric (all rules watching it) — the cluster fan-out
    // removes by metric because rule ids differ per daemon.
    const std::string metric = request.at("metric").asString("");
    if (!autoTrigger_) {
      response["status"] = "failed";
      response["error"] = "auto-trigger disabled (needs the metric store)";
    } else if (!metric.empty()) {
      // Idempotent: "remove everything watching M" has succeeded when
      // nothing watches M (pod-wide disarm re-runs must not report
      // failure on hosts whose rule already fired out or never armed).
      response["status"] = "ok";
      response["removed"] =
          static_cast<int64_t>(autoTrigger_->removeRulesByMetric(metric));
    } else if (autoTrigger_->removeRule(request.at("trigger_id").asInt(-1))) {
      response["status"] = "ok";
      response["removed"] = static_cast<int64_t>(1);
    } else {
      response["status"] = "failed";
      response["error"] = "no such trigger";
    }
  } else if (fn == "listTraceTriggers") {
    if (!autoTrigger_) {
      response["status"] = "failed";
      response["error"] = "auto-trigger disabled (needs the metric store)";
    } else {
      response = autoTrigger_->listRules();
      response["status"] = "ok";
    }
  } else {
    DLOG_ERROR << "Unknown RPC fn: " << fn;
    verbLatency.setLabel("unknown");
    return "";
  }
  return response.dump();
}

json::Value ServiceHandler::selftrace(const json::Value& request) {
  // Chrome-trace "X" (complete) events straight from the journal ring:
  // C++ spans (verb bodies, collector ticks, sink pushes, IPC hand-offs)
  // and Python spans (flushed over the "span" datagram) side by side,
  // each stamped with its own pid/tid so chrome://tracing lanes them per
  // process. args carries the ids so one gputrace request is grep-able
  // by its trace-id across both languages.
  auto response = json::Value::object();
  auto& journal = SpanJournal::instance();
  auto spans = journal.snapshot();
  // Optional trace-id filter: `dyno selftrace --trace_id=...` narrows
  // the dump to one request's spans. Strictly parsed: a typo'd filter
  // must fail loudly, not silently dump the whole ring as if it were
  // the request's trace.
  uint64_t wantTrace = 0;
  if (!parseTraceIdFilter(request.at("trace_id").asString(""), &wantTrace)) {
    response["status"] = "failed";
    response["error"] = kBadTraceIdFilter;
    return response;
  }
  char hexbuf[20];
  auto hex = [&hexbuf](uint64_t v) {
    std::snprintf(
        hexbuf, sizeof(hexbuf), "%016llx",
        static_cast<unsigned long long>(v));
    return std::string(hexbuf);
  };
  auto events = json::Value::array();
  for (const auto& span : spans) {
    if (wantTrace != 0 && span.traceId != wantTrace) {
      continue;
    }
    auto event = json::Value::object();
    event["name"] = std::string(span.name);
    event["ph"] = "X";
    event["ts"] = span.startUs;
    event["dur"] = span.durUs;
    event["pid"] = static_cast<int64_t>(span.pid);
    event["tid"] = static_cast<int64_t>(span.tid);
    auto args = json::Value::object();
    args["trace_id"] = hex(span.traceId);
    args["span_id"] = hex(span.spanId);
    args["parent_id"] = hex(span.parentId);
    event["args"] = std::move(args);
    events.append(std::move(event));
  }
  response["status"] = "ok";
  response["clock"] = "unix_us";
  response["spans_recorded"] = static_cast<int64_t>(journal.recorded());
  response["ring_capacity"] = static_cast<int64_t>(journal.capacity());
  // The IPC thread's wake-ups by cause, beside the ipc.config_handoff
  // spans they serve: `timeout` alone on an idle daemon.
  const auto wakes = tracing::IPCMonitor::wakeCounts();
  auto wakeups = json::Value::object();
  wakeups["message"] = static_cast<int64_t>(wakes.message);
  wakeups["posted"] = static_cast<int64_t>(wakes.posted);
  wakeups["timeout"] = static_cast<int64_t>(wakes.timeout);
  response["ipc_wakeups"] = std::move(wakeups);
  // Device rows of the TPU monitor's last tick: one a chip the backend
  // reported (4 on a four-chip host), 0 before the first tick.
  response["tpu_rows"] = tpumon::TpuMonitor::lastTickRows();
  response["traceEvents"] = std::move(events);
  return response;
}

json::Value ServiceHandler::diagnose(const json::Value& request) {
  auto response = json::Value::object();
  if (!diagnoser_) {
    response["status"] = "failed";
    response["error"] = "diagnosis disabled (no diagnoser wired in)";
    return response;
  }
  // Optional trace-id filter, shared with selftrace (one parser, so
  // the two verbs can never drift): a typo'd filter must error, not
  // silently list everything.
  uint64_t wantTrace = 0;
  if (!parseTraceIdFilter(request.at("trace_id").asString(""), &wantTrace)) {
    response["status"] = "failed";
    response["error"] = kBadTraceIdFilter;
    return response;
  }
  const std::string target = request.at("target").asString("");
  if (target.empty()) {
    // List mode: the registry of completed/in-flight reports. The
    // verb's own diagnose.* span makes even read-only diagnosis
    // activity visible in selftrace.
    SpanScope listSpan("diagnose.list", 0, 0);
    response = diagnoser_->list(
        wantTrace, request.at("include_report").asBool(false));
    response["status"] = "ok";
    return response;
  }
  // Run mode: the engine reads `target`/`baseline` and WRITES
  // <target>.diagnosis.json — bound both like every other RPC-supplied
  // path the daemon acts on. New engine runs are refused under hard
  // resource pressure (the report write would fail anyway; the typed
  // refusal tells the caller to retry after recovery).
  std::string pressureReason;
  if (!ResourceGovernor::instance().admit("diagnose run", &pressureReason)) {
    response["status"] = "refused";
    response["error_kind"] = "resource_pressure";
    response["error"] = pressureReason;
    return response;
  }
  const std::string baseline = request.at("baseline").asString("");
  if (baseline.empty()) {
    response["status"] = "failed";
    response["error"] = "baseline required with target";
    return response;
  }
  std::string pathError;
  if (!pathAllowedByRoot(target, &pathError) ||
      !pathAllowedByRoot(baseline, &pathError)) {
    response["status"] = "failed";
    response["error"] = pathError;
    return response;
  }
  // Parent the run under this request's wire context so `dyno diagnose
  // --log_file=...` joins the CLI invocation's trace-id.
  auto wireCtx = TraceContext::parse(request.at("trace_ctx").asString(""));
  auto report = diagnoser_->runNow(
      target,
      baseline,
      wireCtx ? *wireCtx : TraceContext::mint());
  response = report.toJson(/*includeBody=*/true);
  response["status"] = report.status;
  return response;
}

json::Value ServiceHandler::fetchTrace(
    const json::Value& request,
    std::string* streamFileOut) {
  auto response = json::Value::object();
  const std::string path = request.at("path").asString("");
  std::string pathError;
  struct stat st{};
  if (streamFileOut == nullptr) {
    response["status"] = "failed";
    response["error"] = "fetchTrace needs a chunk-streaming transport";
  } else if (path.empty()) {
    response["status"] = "failed";
    response["error"] = "path required";
  } else if (::FLAGS_trace_output_root.empty()) {
    // Reads are gated harder than writes: pushtrace writing anywhere is
    // the reference's historical behavior, but a network verb READING
    // arbitrary daemon-readable files is an exfiltration primitive —
    // the operator must scope it explicitly.
    response["status"] = "failed";
    response["error"] =
        "fetchTrace requires --trace_output_root (refusing to serve "
        "arbitrary files)";
  } else if (!pathAllowedByRoot(path, &pathError)) {
    response["status"] = "failed";
    response["error"] = pathError;
  } else if (::stat(path.c_str(), &st) != 0 || !S_ISREG(st.st_mode)) {
    response["status"] = "failed";
    response["error"] = "no such artifact file: " + path;
  } else {
    response["status"] = "ok";
    response["stream"] = "chunks";
    response["path"] = path;
    // Informative (the stream may race a concurrent writer); the
    // zero-length END frame is the authoritative terminator.
    response["bytes"] = static_cast<int64_t>(st.st_size);
    *streamFileOut = path;
  }
  return response;
}

json::Value ServiceHandler::addTraceTrigger(const json::Value& request) {
  auto response = json::Value::object();
  if (!autoTrigger_) {
    response["status"] = "failed";
    response["error"] = "auto-trigger disabled (needs the metric store)";
    return response;
  }
  tracing::TriggerRule rule;
  std::string error;
  if (!tracing::ruleFromJson(request, &rule, &error)) {
    response["status"] = "failed";
    response["error"] = error;
    return response;
  }
  // The daemon writes (push mode) and PRUNES (keep_last retention, every
  // mode) paths derived from the rule's log_file — bound them.
  if (!pathAllowedByRoot(rule.logFile, &error)) {
    response["status"] = "failed";
    response["error"] = error;
    return response;
  }
  int64_t id = autoTrigger_->addRule(std::move(rule), &error);
  if (id < 0) {
    response["status"] = "failed";
    response["error"] = error;
  } else {
    response["status"] = "ok";
    response["trigger_id"] = id;
  }
  return response;
}

json::Value ServiceHandler::fleet(const json::Value& request) {
  auto response = json::Value::object();
  if (!fleetRelay_) {
    response["status"] = "failed";
    response["error"] =
        "this daemon is not a fleet relay (start it with --relay)";
    return response;
  }
  const int64_t topK = std::max<int64_t>(request.at("top_k").asInt(10), 0);
  std::vector<std::string> metrics;
  for (const auto& m : request.at("metrics").items()) {
    if (!m.asString().empty()) {
      metrics.push_back(m.asString());
    }
  }
  response = fleetRelay_->query(
      topK,
      request.at("detail").asBool(false),
      metrics,
      request.at("skew_metric").asString(""),
      // Tree drill-down: depth >= 1 adds the per-child-relay breakdown
      // (tree.children); pod names one pod for a member/aggregate
      // drill (pod_detail). Both default off — the global merged view
      // is always present.
      std::max<int64_t>(request.at("depth").asInt(0), 0),
      request.at("pod").asString(""));
  response["status"] = "ok";
  return response;
}

json::Value ServiceHandler::health() {
  // Always answers (no enable flag): supervision state is operational
  // telemetry, and a daemon built before the health registry existed
  // simply reports no components.
  json::Value response;
  if (health_) {
    response = health_->snapshot();
  } else {
    response = json::Value::object();
    response["status"] = "ok";
    response["components"] = json::Value::object();
    response["degraded"] = json::Value::array();
  }
  response["version"] = kVersion;
  // Wire-version surface: this build's proto plus every negotiation the
  // hello verb recorded — "which versions are talking to this daemon"
  // is one health call during a rolling upgrade.
  response["wire"] = WireNegotiations::instance().snapshot();
  // Durability surface: per-endpoint sink spill queues (pending backlog,
  // acked watermark, eviction drops — the only loss the durable sink
  // path ever takes) plus the control-state snapshot's write/recovery
  // status. Always present, so "is telemetry durable right now" is one
  // health call away; sinks is empty without --sink_spill_dir and
  // snapshot is absent without --state_file (the documented schema —
  // a writes=0/recovered=no row on a daemon that never enabled
  // snapshots would read as a durability failure).
  auto durability = json::Value::object();
  durability["sinks"] = WalRegistry::instance().snapshot();
  if (snapshotter_ && snapshotter_->enabled()) {
    durability["snapshot"] = snapshotter_->status();
  }
  response["durability"] = std::move(durability);
  // Resource-governance surface: pressure level, per-class usage and
  // eviction accounting, fd/RSS self-checks, admission refusals — the
  // "is the daemon protecting its host right now" section
  // (docs/RELIABILITY.md resource-pressure matrix). Always present:
  // unconfigured, it reports pressure ok with empty classes.
  response["resources"] = ResourceGovernor::instance().snapshot();
  if (::FLAGS_enable_failpoints) {
    response["failpoints"] = listFailpointsJson();
  }
  return response;
}

json::Value ServiceHandler::failpoint(const json::Value& request) {
  auto response = json::Value::object();
  if (!::FLAGS_enable_failpoints) {
    response["status"] = "failed";
    response["error"] =
        "failpoints disabled (start the daemon with --enable_failpoints)";
    return response;
  }
  const std::string action = request.at("action").asString("list");
  std::string error;
  if (action == "arm") {
    const std::string name = request.at("name").asString();
    const std::string spec = request.at("spec").asString();
    if (failpoints::Registry::instance().arm(name, spec, &error)) {
      response["status"] = "ok";
    } else {
      response["status"] = "failed";
      response["error"] = error;
    }
  } else if (action == "disarm") {
    const std::string name = request.at("name").asString();
    if (name == "*") {
      failpoints::Registry::instance().disarmAll();
      response["status"] = "ok";
    } else if (failpoints::Registry::instance().disarm(name)) {
      response["status"] = "ok";
    } else {
      response["status"] = "failed";
      response["error"] = "no such failpoint armed: " + name;
    }
  } else if (action == "list") {
    response["status"] = "ok";
    response["failpoints"] = listFailpointsJson();
  } else {
    response["status"] = "failed";
    response["error"] = "action must be arm | disarm | list";
  }
  return response;
}

json::Value ServiceHandler::getTpuRuntimeStatus() {
  // One-shot query of the TPU runtime's own status RPC
  // (tpu.monitoring.runtime.RuntimeMetricService/GetTpuRuntimeStatus,
  // vendored schema src/tpumon/proto/tpu_metric_service.proto): host name
  // + which cores the runtime reports state for. Soft-fails when no
  // runtime serves the port.
  auto response = json::Value::object();
  // Strict parsing (src/common/Ports.h): a typo'd override must make the
  // one-shot query fail with a clear error, not probe a garbage-derived
  // port. First list entry wins for this single-runtime status verb.
  // Port policy matches GrpcRuntimeBackend::init: a VALID
  // DYNO_TPU_GRPC_PORT override wins outright (junk in the
  // runtime-owned list must not break an explicitly-configured query);
  // otherwise the consulted var, set-but-malformed, fails the query —
  // probing a default or garbage-derived port a typo'd list never named
  // is exactly the wrong-runtime failure strict parsing exists to
  // prevent. The default port applies only when neither var is set.
  int port = 8431;
  const char* badVar = nullptr;
  if (const char* env = std::getenv("DYNO_TPU_GRPC_PORT"); env && env[0]) {
    auto ports = parseStrictPortList(env);
    if (ports.empty()) {
      badVar = "DYNO_TPU_GRPC_PORT";
    } else {
      port = ports.front();
    }
  } else if (const char* listEnv = std::getenv("TPU_RUNTIME_METRICS_PORTS");
             listEnv && listEnv[0]) {
    auto ports = parseStrictPortList(listEnv);
    if (ports.empty()) {
      badVar = "TPU_RUNTIME_METRICS_PORTS";
    } else {
      port = ports.front();
    }
  }
  if (badVar) {
    response["status"] = "failed";
    response["error"] = std::string(badVar) +
        " is set but not a valid port list; refusing to probe a port it "
        "never named";
    return response;
  }
  GrpcClient client("localhost", port);
  std::string req; // GetTpuRuntimeStatusRequest{} — include_hlo_info=false
  std::string error;
  auto resp = client.call(
      "/tpu.monitoring.runtime.RuntimeMetricService/GetTpuRuntimeStatus",
      req,
      &error);
  if (!resp) {
    response["status"] = "failed";
    response["error"] = "no TPU runtime metric service on localhost:" +
        std::to_string(port) + " (" + error + ")";
    return response;
  }
  response["status"] = "ok";
  response["port"] = static_cast<int64_t>(port);
  auto& cores = response["cores"];
  cores = json::Value::array();
  protowire::walk(*resp, [&](const protowire::Field& f) {
    if (f.number == 1 && f.wireType == 2) {
      response["host_name"] = std::string(f.bytes);
    } else if (f.number == 2 && f.wireType == 2) { // core_states entry
      if (auto key = protowire::find(f.bytes, 1); key && key->wireType == 0) {
        cores.append(key->asInt64());
      }
    }
  });
  return response;
}

} // namespace dynotpu
