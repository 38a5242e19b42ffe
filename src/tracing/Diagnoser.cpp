#include "src/tracing/Diagnoser.h"

#include <fcntl.h>
#include <poll.h>
#include <signal.h>
#include <sys/stat.h>
#include <sys/wait.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <cstring>

#include "src/common/Defs.h"
#include "src/common/Flags.h"
#include "src/common/Time.h"
#include "src/core/Histograms.h"
#include "src/metrics/MetricStore.h"

DYN_DEFINE_string(
    diagnose_python,
    "python3",
    "Interpreter the diagnosis engine (`python -m dynolog_tpu.diagnose`) "
    "runs under when a fired capture or the `diagnose` RPC verb asks for "
    "a trace-diff report. Empty disables diagnosis entirely.");

DYN_DEFINE_string(
    diagnose_pythonpath,
    "",
    "Prepended to the engine child's PYTHONPATH so dynolog_tpu resolves "
    "from a source checkout (empty = rely on the installed package).");

DYN_DEFINE_int64(
    diagnose_timeout_ms,
    60000,
    "Wall-clock bound on one diagnosis engine run; an engine past it is "
    "killed and the report recorded as failed (the daemon never inherits "
    "a wedged child).");

extern char** environ;

namespace dynotpu {
namespace tracing {

Diagnoser::Options Diagnoser::Options::fromFlags(
    const std::string& obsEndpoint) {
  Options options;
  options.pythonExe = ::FLAGS_diagnose_python;
  options.pythonPath = ::FLAGS_diagnose_pythonpath;
  options.obsEndpoint = obsEndpoint;
  options.timeoutMs = ::FLAGS_diagnose_timeout_ms;
  return options;
}

json::Value Diagnoser::Report::toJson(bool includeBody) const {
  auto obj = json::Value::object();
  obj["id"] = id;
  obj["rule_id"] = ruleId;
  obj["target"] = target;
  obj["baseline"] = baseline;
  obj["report_path"] = reportPath;
  obj["status"] = status;
  obj["verdict"] = verdict;
  obj["headline"] = headline;
  obj["findings"] = findings;
  obj["created_ms"] = createdMs;
  if (!error.empty()) {
    obj["error"] = error;
  }
  char buf[20];
  std::snprintf(
      buf, sizeof(buf), "%016llx",
      static_cast<unsigned long long>(traceId));
  obj["trace_id"] = std::string(buf);
  if (includeBody && body.isObject()) {
    obj["report"] = body;
  }
  return obj;
}

Diagnoser::Diagnoser(Options options, std::shared_ptr<MetricStore> store)
    : options_(std::move(options)), store_(std::move(store)) {}

Diagnoser::~Diagnoser() {
  stop();
}

void Diagnoser::stop() {
  stopRequested_.store(true);
  std::thread worker;
  {
    std::lock_guard<std::mutex> lock(mutex_);
    worker = std::move(worker_);
  }
  if (worker.joinable()) {
    worker.join();
  }
}

size_t Diagnoser::reportCount() const {
  std::lock_guard<std::mutex> lock(mutex_);
  return reports_.size();
}

int64_t Diagnoser::record(Report report) {
  std::lock_guard<std::mutex> lock(mutex_);
  report.id = nextId_++;
  int64_t id = report.id;
  reports_.push_back(std::move(report));
  if (reports_.size() > kMaxReports) {
    reports_.erase(reports_.begin());
  }
  return id;
}

void Diagnoser::updateReport(int64_t id, const Report& report) {
  std::lock_guard<std::mutex> lock(mutex_);
  for (auto& existing : reports_) {
    if (existing.id == id) {
      int64_t keepId = existing.id;
      existing = report;
      existing.id = keepId;
      return;
    }
  }
}

void Diagnoser::bumpCountersOnce(bool ok) {
  HistogramRegistry::instance().bumpDiagnosis(ok);
  int64_t runs, failures;
  {
    std::lock_guard<std::mutex> lock(mutex_);
    runsTotal_++;
    if (!ok) {
      failuresTotal_++;
    }
    runs = runsTotal_;
    failures = failuresTotal_;
  }
  if (store_) {
    // Cumulative series in the metric store: diagnosis activity is
    // graphable/alertable (`dyno watch diagnoser.runs`) like trigger
    // fires are. Named diagnoser.* (not diagnosis.*): the store gauge
    // renders as dynolog_diagnoser_* on the scrape, which must not
    // collide with the registry's dynolog_diagnosis_* COUNTER families
    // — one exposition declaring the same family as both gauge and
    // counter is invalid openmetrics-text.
    store_->addSamples(
        {{"diagnoser.runs", static_cast<double>(runs)},
         {"diagnoser.failures", static_cast<double>(failures)}},
        nowUnixMillis());
  }
}

namespace {

// Bounded child stdout (the engine's --json report line): a runaway
// engine must not balloon daemon memory.
constexpr size_t kMaxChildOutput = 1 << 20;

// "<base>.json" -> "<base>.diagnosis.json"; non-.json targets get the
// suffix appended (mirrors the Python engine's --out conventions).
std::string diagnosisPathFor(const std::string& target) {
  if (target.size() > 5 && target.rfind(".json") == target.size() - 5) {
    return target.substr(0, target.size() - 5) + ".diagnosis.json";
  }
  return target + ".diagnosis.json";
}

// Runs the engine child with a deadline; returns exit status (-1 =
// spawn/timeout failure with *error set) and the child's stdout. A
// raised abort flag (daemon shutdown) kills the child within ~200ms —
// SIGTERM must never wait out a 60s engine deadline.
int runChild(
    const std::vector<std::string>& argv,
    const std::vector<std::pair<std::string, std::string>>& envOverrides,
    int64_t timeoutMs,
    const std::atomic<bool>* abort,
    std::string* output,
    std::string* error) {
  int pipefd[2];
  if (::pipe(pipefd) != 0) {
    *error = std::string("pipe: ") + std::strerror(errno);
    return -1;
  }
  // Pre-build argv/envp outside the fork (no allocation between fork and
  // exec). Env: the parent's, with the overrides replacing any existing
  // entry of the same key.
  std::vector<std::string> envStrings;
  for (char** e = environ; e && *e; ++e) {
    std::string entry = *e;
    bool overridden = false;
    for (const auto& [key, _] : envOverrides) {
      if (entry.compare(0, key.size() + 1, key + "=") == 0) {
        overridden = true;
        break;
      }
    }
    if (!overridden) {
      envStrings.push_back(std::move(entry));
    }
  }
  for (const auto& [key, value] : envOverrides) {
    envStrings.push_back(key + "=" + value);
  }
  std::vector<char*> argvPtrs, envPtrs;
  for (const auto& a : argv) {
    argvPtrs.push_back(const_cast<char*>(a.c_str()));
  }
  argvPtrs.push_back(nullptr);
  for (const auto& e : envStrings) {
    envPtrs.push_back(const_cast<char*>(e.c_str()));
  }
  envPtrs.push_back(nullptr);

  pid_t pid = ::fork();
  if (pid < 0) {
    *error = std::string("fork: ") + std::strerror(errno);
    ::close(pipefd[0]);
    ::close(pipefd[1]);
    return -1;
  }
  if (pid == 0) {
    // Child: stdout -> pipe, stderr silenced (the engine's diagnostics
    // go to its --out report; a chatty stderr must not interleave with
    // daemon logs), own session so a timeout kill reaps the whole tree.
    ::setsid();
    ::dup2(pipefd[1], STDOUT_FILENO);
    int devnull = ::open("/dev/null", O_WRONLY);
    if (devnull >= 0) {
      ::dup2(devnull, STDERR_FILENO);
    }
    ::close(pipefd[0]);
    ::close(pipefd[1]);
    ::execve(argvPtrs[0], argvPtrs.data(), envPtrs.data());
    // execve failed; try PATH resolution for a bare interpreter name.
    ::execvpe(argvPtrs[0], argvPtrs.data(), envPtrs.data());
    ::_exit(127);
  }
  ::close(pipefd[1]);
  int flags = ::fcntl(pipefd[0], F_GETFL, 0);
  ::fcntl(pipefd[0], F_SETFL, flags | O_NONBLOCK);
  int64_t deadline = nowUnixMillis() + timeoutMs;
  bool timedOut = false;
  char buf[4096];
  while (true) {
    int64_t left = deadline - nowUnixMillis();
    if (left <= 0 || (abort && abort->load())) {
      timedOut = true;
      break;
    }
    struct pollfd pfd {pipefd[0], POLLIN, 0};
    int rc = ::poll(&pfd, 1, static_cast<int>(std::min<int64_t>(left, 200)));
    if (rc > 0) {
      ssize_t n = ::read(pipefd[0], buf, sizeof(buf));
      if (n > 0) {
        if (output->size() < kMaxChildOutput) {
          output->append(buf, static_cast<size_t>(n));
        }
        continue;
      }
      if (n == 0) {
        break; // EOF: child closed stdout (exiting)
      }
      if (errno != EAGAIN && errno != EINTR) {
        break;
      }
    }
    // Also reap promptly if the child exited without closing stdout
    // (it can't: dup2'd — but a crashed interpreter can).
    int status;
    pid_t done = ::waitpid(pid, &status, WNOHANG);
    if (done == pid) {
      // Drain whatever is left.
      ssize_t n;
      while ((n = ::read(pipefd[0], buf, sizeof(buf))) > 0) {
        if (output->size() < kMaxChildOutput) {
          output->append(buf, static_cast<size_t>(n));
        }
      }
      ::close(pipefd[0]);
      return WIFEXITED(status) ? WEXITSTATUS(status) : -1;
    }
  }
  ::close(pipefd[0]);
  if (timedOut) {
    // Kill the whole engine session; a wedged child must not outlive
    // its deadline.
    ::kill(-pid, SIGKILL);
    ::kill(pid, SIGKILL);
  }
  int status = 0;
  ::waitpid(pid, &status, 0);
  if (timedOut) {
    *error = (abort && abort->load())
        ? "diagnosis engine aborted (daemon shutting down)"
        : "diagnosis engine timed out after " +
            std::to_string(timeoutMs) + "ms";
    return -1;
  }
  return WIFEXITED(status) ? WEXITSTATUS(status) : -1;
}

} // namespace

Diagnoser::Report Diagnoser::runEngine(
    const std::string& target,
    const std::string& baseline,
    const TraceContext& ctx,
    int64_t ruleId) {
  Report report;
  report.ruleId = ruleId;
  report.target = target;
  report.baseline = baseline;
  report.traceId = ctx.traceId;
  report.createdMs = nowUnixMillis();
  report.reportPath = diagnosisPathFor(target);
  if (options_.pythonExe.empty()) {
    report.status = "failed";
    report.error = "diagnosis disabled (--diagnose_python is empty)";
    return report;
  }
  // The engine run is itself a diagnose.* span under the request's
  // trace-id, and the child inherits the span's context so its own
  // diagnose.engine span parents here — `dyno selftrace` shows
  // breach -> capture -> diff -> report as one tree.
  SpanScope runSpan("diagnose.run", ctx.traceId, ctx.spanId);
  ScopedLatency latency(&HistogramRegistry::observeDiagnosisRun, "run");
  std::vector<std::string> argv = {
      options_.pythonExe, "-m",     "dynolog_tpu.diagnose",
      target,             "--baseline", baseline,
      "--json",           "--out",      report.reportPath,
  };
  std::vector<std::pair<std::string, std::string>> env = {
      {"DYNO_TRACE_CTX", runSpan.childContext().header()},
  };
  if (!options_.obsEndpoint.empty()) {
    env.emplace_back("DYNO_OBS_ENDPOINT", options_.obsEndpoint);
  }
  if (!options_.pythonPath.empty()) {
    const char* existing = ::getenv("PYTHONPATH");
    env.emplace_back(
        "PYTHONPATH",
        existing && existing[0]
            ? options_.pythonPath + ":" + existing
            : options_.pythonPath);
  }
  std::string output, error;
  int rc = runChild(
      argv, env, options_.timeoutMs, &stopRequested_, &output, &error);
  if (rc != 0) {
    report.status = "failed";
    report.error = !error.empty()
        ? error
        : "diagnosis engine exited " + std::to_string(rc);
    DLOG_ERROR << "diagnose: engine failed on " << target << ": "
               << report.error;
    return report;
  }
  std::string parseErr;
  auto body = json::Value::parse(output, &parseErr);
  if (!parseErr.empty() || !body.isObject()) {
    report.status = "failed";
    report.error = "engine emitted unparseable report: " + parseErr;
    return report;
  }
  report.status = "ok";
  report.verdict = body.at("verdict").asString("");
  report.headline = body.at("headline").asString("");
  report.findings = body.at("finding_count").asInt(0);
  report.body = std::move(body);
  DLOG_INFO << "diagnose: " << report.verdict << " — " << report.headline
            << " -> " << report.reportPath;
  return report;
}

Diagnoser::Report Diagnoser::runNow(
    const std::string& target,
    const std::string& baseline,
    const TraceContext& ctx,
    int64_t ruleId) {
  auto report = runEngine(target, baseline, ctx, ruleId);
  bool ok = report.status == "ok";
  report.id = record(report);
  bumpCountersOnce(ok);
  return report;
}

int64_t Diagnoser::diagnoseCapture(
    int64_t ruleId,
    const std::string& manifestPath,
    const std::string& baseline,
    const TraceContext& ctx,
    int64_t waitDeadlineMs) {
  // Cheap enqueue span so even a skipped fire is visible in selftrace
  // under the request's trace-id.
  SpanScope enqueueSpan("diagnose.enqueue", ctx.traceId, ctx.spanId);
  Report pending;
  pending.ruleId = ruleId;
  pending.target = manifestPath;
  pending.baseline = baseline;
  pending.traceId = ctx.traceId;
  pending.createdMs = nowUnixMillis();
  pending.reportPath = diagnosisPathFor(manifestPath);
  std::thread previous;
  bool skipped = false;
  int64_t skippedReportId = 0;
  {
    std::lock_guard<std::mutex> lock(mutex_);
    if (workerBusy_) {
      // Single-flight: a fire during a running diagnosis is recorded as
      // skipped (the NEXT fire diagnoses fresh data anyway; queuing
      // stale captures would diagnose history). Distinct status, and
      // counted as a failure below — a breach storm losing diagnoses
      // must move dynolog_diagnosis_failures_total, not hide from it.
      pending.status = "skipped";
      pending.error = "diagnosis worker busy; capture skipped";
      pending.id = nextId_++;
      skippedReportId = pending.id;
      reports_.push_back(pending);
      if (reports_.size() > kMaxReports) {
        reports_.erase(reports_.begin());
      }
      skipped = true;
    } else {
      // !workerBusy_: the previous worker has recorded its result; join
      // can only wait out thread exit.
      previous = std::move(worker_);
      workerBusy_ = true;
    }
  }
  if (skipped) {
    bumpCountersOnce(/*ok=*/false); // takes mutex_ itself
    return skippedReportId;
  }
  if (previous.joinable()) {
    // blocking-ok: reaps an already-finished engine worker (workerBusy_
    // was false, so its body has recorded its result and returned).
    previous.join();
  }
  pending.status = "waiting";
  int64_t id = record(pending);
  TraceContext childCtx = enqueueSpan.childContext();
  {
    std::lock_guard<std::mutex> lock(mutex_);
    // unsupervised-thread: one bounded engine run per fire (manifest
    // wait + child deadline), joined via workerBusy_ handshake before
    // the next fire and at stop().
    worker_ = std::thread([this, id, ruleId, manifestPath, baseline,
                           childCtx, waitDeadlineMs] {
      Report result;
      {
        // The wait for the shim to finish writing the capture is its
        // own span: config hand-off to manifest is exactly the capture
        // latency the benchmark reads as capture_ms_p50.
        SpanScope waitSpan(
            "diagnose.capture_wait", childCtx.traceId, childCtx.spanId);
        int64_t deadline = nowUnixMillis() + waitDeadlineMs;
        bool found = false;
        while (nowUnixMillis() < deadline && !stopRequested_.load()) {
          struct stat st;
          if (::stat(manifestPath.c_str(), &st) == 0) {
            found = true;
            break;
          }
          ::usleep(200 * 1000);
        }
        if (!found) {
          result.ruleId = ruleId;
          result.target = manifestPath;
          result.baseline = baseline;
          result.traceId = childCtx.traceId;
          result.createdMs = nowUnixMillis();
          result.status = "failed";
          result.error = stopRequested_.load()
              ? "daemon shutting down before the capture completed"
              : "capture manifest never appeared (shim down? capture "
                "failed?)";
          updateReport(id, result);
          bumpCountersOnce(false);
          {
            std::lock_guard<std::mutex> lock(mutex_);
            workerBusy_ = false;
          }
          return;
        }
      }
      result = runEngine(
          manifestPath, baseline, TraceContext{childCtx.traceId,
          childCtx.spanId}, ruleId);
      updateReport(id, result);
      bumpCountersOnce(result.status == "ok");
      std::lock_guard<std::mutex> lock(mutex_);
      workerBusy_ = false;
    });
  }
  return id;
}

json::Value Diagnoser::list(uint64_t traceIdFilter, bool includeBody) const {
  auto response = json::Value::object();
  auto& arr = response["reports"];
  arr = json::Value::array();
  std::lock_guard<std::mutex> lock(mutex_);
  for (auto it = reports_.rbegin(); it != reports_.rend(); ++it) {
    if (traceIdFilter != 0 && it->traceId != traceIdFilter) {
      continue;
    }
    arr.append(it->toJson(includeBody));
  }
  response["runs_total"] = runsTotal_;
  response["failures_total"] = failuresTotal_;
  return response;
}

} // namespace tracing
} // namespace dynotpu
