#include "src/tracing/IPCMonitor.h"

#include <poll.h>

#include <algorithm>
#include <chrono>
#include <cmath>
#include <cstring>
#include <map>
#include <utility>
#include <vector>

#include "src/common/Defs.h"
#include "src/common/Time.h"
#include "src/core/Histograms.h"
#include "src/core/SpanJournal.h"
#include "src/metrics/MetricStore.h"

namespace dynotpu {
namespace tracing {

// Longest the monitor thread blocks with nothing to do. Nothing on the
// hand-off path waits for it (a message or a posted job ends the poll at
// once); it only bounds how late the thread notices stop_, the end of
// its supervised slice and the kick-subscriber TTL sweep. The reference
// sleeps 10 ms between polls instead (IPCMonitor.cpp:22): a deliberate
// departure, docs/PARITY.md.
constexpr int64_t kMaxWaitMs = 250;
// Kick-subscription hygiene: entries refresh on each "sub" and die after
// the TTL (shims re-subscribe about every 30s); the global address cap
// bounds what hostile local datagrams can make the daemon remember.
constexpr int64_t kKickSubTtlMs = 5 * 60 * 1000;
constexpr size_t kMaxKickSubs = 256;

IPCMonitor::IPCMonitor(
    std::shared_ptr<TraceConfigManager> configManager,
    const std::string& endpointName,
    std::shared_ptr<MetricStore> metricStore)
    : configManager_(std::move(configManager)),
      fabric_(ipc::FabricManager::factory(endpointName)),
      metricStore_(std::move(metricStore)) {
  if (!fabric_) {
    DLOG_ERROR << "IPCMonitor: endpoint '" << endpointName
               << "' unavailable; on-demand tracing disabled";
  }
}

std::atomic<uint64_t> IPCMonitor::wakeMessage_{0};
std::atomic<uint64_t> IPCMonitor::wakePosted_{0};
std::atomic<uint64_t> IPCMonitor::wakeTimeout_{0};

IPCMonitor::WakeCounts IPCMonitor::wakeCounts() {
  return {wakeMessage_.load(), wakePosted_.load(), wakeTimeout_.load()};
}

void IPCMonitor::loop() {
  serve(/*deadlineMs=*/-1);
}

void IPCMonitor::runSlice(int64_t maxMs) {
  serve(nowUnixMillis() + maxMs);
}

void IPCMonitor::serve(int64_t deadlineMs) {
  while (fabric_ && !stop_.load()) {
    const int64_t leftMs =
        deadlineMs < 0 ? kMaxWaitMs : deadlineMs - nowUnixMillis();
    if (leftMs <= 0) {
      return;
    }
    bool handled = pollOnce();
    sendPendingKicks();
    // Everything queued is drained before the thread blocks again: a
    // handled message goes straight to the next one.
    if (!handled) {
      waitForWork(static_cast<int>(std::min(leftMs, kMaxWaitMs)));
    }
  }
}

void IPCMonitor::waitForWork(int timeoutMs) {
  // Level-triggered on both descriptors: a datagram or a post that lands
  // between the pass above and this poll ends it at once.
  pollfd fds[2] = {
      {fabric_->fd(), POLLIN, 0},
      {configManager_->postedFd(), POLLIN, 0},
  };
  int ready = ::poll(fds, 2, timeoutMs);
  if (stop_.load()) {
    return; // stop() woke us: no cause of the thread's own
  }
  if (ready <= 0) {
    wakeTimeout_++; // EINTR counts as one: the pass runs either way
  } else if (fds[0].revents) {
    wakeMessage_++;
  } else {
    wakePosted_++;
  }
}

void IPCMonitor::sendPendingKicks() {
  if (!fabric_) {
    return;
  }
  int64_t now = nowUnixMillis();
  for (int64_t jobId : configManager_->drainPostedJobs()) {
    auto it = kickSubs_.find(jobId);
    if (it == kickSubs_.end()) {
      continue; // nobody opted in for this job; they'll poll
    }
    for (auto addrIt = it->second.begin(); addrIt != it->second.end();) {
      if (now - addrIt->second > kKickSubTtlMs) {
        addrIt = it->second.erase(addrIt);
        kickSubCount_--;
        continue;
      }
      auto kick = ipc::Message::createFromPod(jobId, kMsgTypeKick);
      // ONE send attempt, no backoff: this runs on the daemon's single
      // IPC thread, and a wedged subscriber (full receive buffer) must
      // not stall config/registration service for every other client —
      // a dropped kick costs the subscriber one poll interval, nothing
      // else. A failed send also drops the subscription: a gone client
      // should not be retried until the TTL.
      if (!fabric_->sync_send(*kick, addrIt->first, /*numRetries=*/1)) {
        addrIt = it->second.erase(addrIt);
        kickSubCount_--;
        continue;
      }
      ++addrIt;
    }
    if (it->second.empty()) {
      kickSubs_.erase(it);
    }
  }
  // Global TTL sweep, independent of config activity: entries for jobs
  // that never post (client restarts leave a fresh address each time)
  // must not pin the subscriber cap forever.
  if (now - lastKickSweepMs_ > kKickSubTtlMs / 4) {
    lastKickSweepMs_ = now;
    for (auto jobIt = kickSubs_.begin(); jobIt != kickSubs_.end();) {
      for (auto addrIt = jobIt->second.begin();
           addrIt != jobIt->second.end();) {
        if (now - addrIt->second > kKickSubTtlMs) {
          addrIt = jobIt->second.erase(addrIt);
          kickSubCount_--;
        } else {
          ++addrIt;
        }
      }
      jobIt = jobIt->second.empty() ? kickSubs_.erase(jobIt)
                                    : std::next(jobIt);
    }
  }
}

void IPCMonitor::handleSubscribe(std::unique_ptr<ipc::Message> msg) {
  if (msg->metadata.size < sizeof(ClientSubscribe)) {
    DLOG_ERROR << "IPCMonitor: short 'sub' message";
    return;
  }
  ClientSubscribe sub;
  std::memcpy(&sub, msg->buf.get(), sizeof(sub));
  if (sub.reserved != 0) {
    DLOG_ERROR << "IPCMonitor: rejecting 'sub' with nonzero reserved from "
               << msg->src;
    return;
  }
  // Same hygiene gate as telemetry: only registered jobs, bounded total.
  if (configManager_->processCount(sub.jobId) == 0) {
    DLOG_ERROR << "IPCMonitor: dropping 'sub' for unregistered job "
               << sub.jobId << " from " << msg->src;
    return;
  }
  auto& addrs = kickSubs_[sub.jobId];
  auto it = addrs.find(msg->src);
  if (it != addrs.end()) {
    it->second = nowUnixMillis(); // refresh
    return;
  }
  if (kickSubCount_ >= kMaxKickSubs) {
    DLOG_ERROR << "IPCMonitor: kick-subscriber cap (" << kMaxKickSubs
               << ") reached; dropping 'sub' from " << msg->src;
    if (addrs.empty()) {
      kickSubs_.erase(sub.jobId);
    }
    return;
  }
  addrs[msg->src] = nowUnixMillis();
  kickSubCount_++;
}

// hot-path: the monitor thread's pass body — the dispatch itself never
// blocks (recv is non-blocking; the thread blocks in waitForWork only,
// with nothing queued). Replies inside the handlers are the known,
// bounded exception: sync_send's retry backoff can stall the pass
// against a peer with a full socket buffer. The interprocedural
// reach pass sees those chains now; each reply site carries its audited
// // blocking-ok waiver (docs/STATIC_ANALYSIS.md).
bool IPCMonitor::pollOnce() {
  if (!fabric_ || !fabric_->recv()) {
    return false;
  }
  auto msg = fabric_->retrieve_msg();
  if (!msg) {
    return false;
  }
  processMsg(std::move(msg));
  return true;
}

void IPCMonitor::processMsg(std::unique_ptr<ipc::Message> msg) {
  // "ctxt" must be checked with its full 4 bytes; "req" is a 3-byte prefix
  // match (same dispatch as reference IPCMonitor.cpp:44-56).
  if (std::memcmp(msg->metadata.type, kMsgTypeContext, 4) == 0) {
    handleContext(std::move(msg));
  } else if (std::memcmp(msg->metadata.type, kMsgTypePerfStats, 5) == 0) {
    handlePerfStats(std::move(msg));
  } else if (std::memcmp(msg->metadata.type, kMsgTypeSubscribe, 4) == 0) {
    handleSubscribe(std::move(msg));
  } else if (std::memcmp(msg->metadata.type, kMsgTypeSpan, 5) == 0) {
    handleSpan(std::move(msg));
  } else if (std::memcmp(msg->metadata.type, kMsgTypeRequest, 3) == 0) {
    handleRequest(std::move(msg));
  } else {
    // The tag comes from an untrusted peer and may lack a NUL terminator.
    std::string tag(
        msg->metadata.type,
        strnlen(msg->metadata.type, ipc::kTypeSize));
    DLOG_ERROR << "IPCMonitor: unknown message type " << tag;
  }
}

void IPCMonitor::handleRequest(std::unique_ptr<ipc::Message> msg) {
  if (msg->metadata.size < sizeof(ClientRequest)) {
    DLOG_ERROR << "IPCMonitor: short 'req' message";
    return;
  }
  auto* req = reinterpret_cast<const ClientRequest*>(msg->buf.get());
  if (req->nPids <= 0 ||
      msg->metadata.size <
          sizeof(ClientRequest) + sizeof(int32_t) * req->nPids) {
    DLOG_ERROR << "IPCMonitor: bad pid count in 'req': " << req->nPids;
    return;
  }
  const auto* pids =
      reinterpret_cast<const int32_t*>(msg->buf.get() + sizeof(ClientRequest));
  std::vector<int32_t> pidList(pids, pids + req->nPids);

  auto unixUs = [] {
    return std::chrono::duration_cast<std::chrono::microseconds>(
               std::chrono::system_clock::now().time_since_epoch())
        .count();
  };
  const int64_t handoffStartUs = unixUs();
  std::string config = configManager_->obtainOnDemandConfig(
      req->jobId, pidList, req->configType);

  auto reply = ipc::Message::createFromString(config, kMsgTypeRequest);
  // blocking-ok: config replies are one per capture request (not per
  // tick); sync_send's retry backoff is bounded (kMaxRetries) and only
  // engages against a peer with a full socket buffer.
  if (!fabric_->sync_send(*reply, msg->src)) {
    DLOG_ERROR << "IPCMonitor: failed to return config to " << msg->src;
  }
  if (!config.empty()) {
    // A config actually left the daemon: record the hand-off under the
    // request's own trace-id (the TRACE_CONTEXT key the RPC verb — or
    // unitrace — embedded), so `selftrace` shows the IPC leg between the
    // rpc.* span and the shim's capture spans. Configs without a context
    // (auto-trigger fires, pre-tracing CLIs) land under trace-id 0.
    auto ctx = traceContextFromConfig(config);
    SpanJournal::instance().record(
        "ipc.config_handoff",
        ctx ? ctx->traceId : 0,
        mintId(),
        ctx ? ctx->spanId : 0,
        handoffStartUs,
        unixUs() - handoffStartUs);
  }
}

void IPCMonitor::handleSpan(std::unique_ptr<ipc::Message> msg) {
  if (msg->metadata.size < sizeof(ClientSpan)) {
    DLOG_ERROR << "IPCMonitor: short 'span' message";
    return;
  }
  ClientSpan wire;
  std::memcpy(&wire, msg->buf.get(), sizeof(wire));
  // Hostile-datagram discipline, same as 'pstat': every field is
  // untrusted. Negative durations/timestamps or a nonzero reserved are
  // rejected rather than journaled.
  if (wire.reserved != 0 || wire.durUs < 0 || wire.startUs < 0) {
    DLOG_ERROR << "IPCMonitor: rejecting 'span' with invalid fields from "
               << msg->src;
    return;
  }
  Span span;
  span.traceId = wire.traceId;
  span.spanId = wire.spanId;
  span.parentId = wire.parentId;
  span.startUs = wire.startUs;
  span.durUs = wire.durUs;
  span.pid = wire.pid;
  span.tid = wire.pid; // Python reports per-process; lane by pid
  std::memcpy(span.name, wire.name, std::min(sizeof(span.name), sizeof(wire.name)));
  span.name[sizeof(span.name) - 1] = '\0';
  SpanJournal::instance().record(span);
  // The conversion leg's timing doubles as the scrape histogram the
  // daemon cannot measure itself (the convert runs in the client's
  // export process).
  if (std::strncmp(span.name, "trace.convert", sizeof(span.name)) == 0) {
    HistogramRegistry::instance().observeTraceConvert(
        static_cast<double>(wire.durUs) / 1e6);
  }
}

void IPCMonitor::handlePerfStats(std::unique_ptr<ipc::Message> msg) {
  if (!metricStore_) {
    return; // telemetry leg disabled; drop silently (fire-and-forget wire)
  }
  if (msg->metadata.size < sizeof(ClientPerfStats)) {
    DLOG_ERROR << "IPCMonitor: short 'pstat' message";
    return;
  }
  ClientPerfStats stats;
  std::memcpy(&stats, msg->buf.get(), sizeof(stats));
  // Hostile-datagram discipline (same posture as the other handlers): every
  // field is untrusted. Reject non-finite or nonsense values rather than
  // poisoning the store.
  auto bad = [](double v) { return !std::isfinite(v) || v < 0; };
  if (stats.reserved != 0 || stats.windowS <= 0 ||
      !std::isfinite(stats.windowS) || bad(stats.steps) ||
      bad(stats.stepTimeP50Ms) || bad(stats.stepTimeP95Ms) ||
      bad(stats.stepTimeMaxMs)) {
    // reserved is documented "must be 0 on the wire" (IPCMonitor.h); the
    // check keeps it honestly reusable as a future version/flags field.
    DLOG_ERROR << "IPCMonitor: rejecting 'pstat' with invalid fields from "
               << msg->src;
    return;
  }
  // Only jobs with registered trace clients may publish telemetry. The
  // fabric trusts local processes (any of them can register, here as in
  // the reference's ipcfabric), so this is a hygiene gate, not
  // authentication; what bounds hostile series-minting is the cap below —
  // the store never expires series, so the daemon refuses to track
  // telemetry for more than kMaxTelemetryJobs distinct jobs per lifetime.
  if (configManager_->processCount(stats.jobId) == 0) {
    DLOG_ERROR << "IPCMonitor: dropping 'pstat' for unregistered job "
               << stats.jobId << " from " << msg->src;
    return;
  }
  constexpr size_t kMaxTelemetryJobs = 64;
  if (telemetryJobs_.insert(stats.jobId).second &&
      telemetryJobs_.size() > kMaxTelemetryJobs) {
    telemetryJobs_.erase(stats.jobId);
    DLOG_ERROR << "IPCMonitor: telemetry job cap (" << kMaxTelemetryJobs
               << ") reached; dropping 'pstat' for new job " << stats.jobId;
    return;
  }
  // Individually-finite fields can still divide to +inf (steps huge,
  // window denormal); the store must only ever see finite samples.
  double stepsPerSec = stats.steps / stats.windowS;
  if (!std::isfinite(stepsPerSec)) {
    DLOG_ERROR << "IPCMonitor: rejecting 'pstat' with non-finite rate from "
               << msg->src;
    return;
  }
  // Interned ids, cached per job: after a job's first report, a pstat
  // datagram costs four id pushes into the store's sharded hot path —
  // no per-datagram "job<id>." string concatenation or map nodes.
  auto idsIt = telemetryIds_.find(stats.jobId);
  if (idsIt == telemetryIds_.end()) {
    const std::string prefix = "job" + std::to_string(stats.jobId) + ".";
    idsIt = telemetryIds_
                .emplace(
                    stats.jobId,
                    std::array<uint32_t, 4>{
                        metricStore_->intern(prefix + "steps_per_sec"),
                        metricStore_->intern(prefix + "step_time_p50_ms"),
                        metricStore_->intern(prefix + "step_time_p95_ms"),
                        metricStore_->intern(prefix + "step_time_max_ms")})
                .first;
  }
  const auto& ids = idsIt->second;
  std::vector<std::pair<uint32_t, double>> samples;
  samples.reserve(4);
  samples.emplace_back(ids[0], stepsPerSec);
  if (stats.steps > 0 && stats.stepTimeP50Ms > 0) {
    // A report can carry a step count with no percentiles: a job whose
    // step period exceeds the shim's report window has an exact rate
    // (count/elapsed) but no inter-step duration that fits inside one
    // window. Zero percentiles mean "not measured", never "0 ms".
    samples.emplace_back(ids[1], stats.stepTimeP50Ms);
    samples.emplace_back(ids[2], stats.stepTimeP95Ms);
    samples.emplace_back(ids[3], stats.stepTimeMaxMs);
  }
  metricStore_->addSamples(samples, nowUnixMillis());
}

void IPCMonitor::handleContext(std::unique_ptr<ipc::Message> msg) {
  if (msg->metadata.size < sizeof(ClientContext)) {
    DLOG_ERROR << "IPCMonitor: short 'ctxt' message";
    return;
  }
  auto* ctxt = reinterpret_cast<const ClientContext*>(msg->buf.get());
  int32_t count = -1;
  count = configManager_->registerContext(ctxt->jobId, ctxt->pid, ctxt->device);

  auto reply = ipc::Message::createFromPod(count, kMsgTypeContext);
  // blocking-ok: context acks happen once per client registration;
  // sync_send's retry backoff is bounded (kMaxRetries).
  if (!fabric_->sync_send(*reply, msg->src)) {
    DLOG_ERROR << "IPCMonitor: failed to ack context from " << msg->src;
  }
}

} // namespace tracing
} // namespace dynotpu
