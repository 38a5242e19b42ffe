// dynolog_tpu: daemon-side IPC monitor for profiler-client handshakes.
// Behavioral parity: reference dynolog/src/tracing/IPCMonitor.{h,cpp} —
// one thread over FabricManager (IPCMonitor.cpp:33-41; the reference
// sleeps 10ms between polls, this one blocks in poll(2) until a message
// or a posted config arrives, docs/PARITY.md), dispatch on the
// 4-byte message type: "ctxt" registers a client process (replying with the
// per-device instance count, :90-113), "req" hands out the pending on-demand
// config (replying with the config string, :58-88). Wire structs match
// ipcfabric/Utils.h so both the dynolog_tpu Python shim and stock libkineto
// clients are served.
#pragma once

#include <array>
#include <atomic>
#include <cstdint>
#include <map>
#include <memory>
#include <set>
#include <string>

#include "src/ipc/FabricManager.h"
#include "src/tracing/TraceConfigManager.h"

namespace dynotpu {

class MetricStore; // fwd (src/metrics/MetricStore.h)

namespace tracing {

// Wire structs (layout-compatible with reference ipcfabric/Utils.h:15-34).
struct ClientContext {
  int32_t device; // accelerator ordinal the client runs on ("gpu" in ref)
  int32_t pid;
  int64_t jobId;
};
static_assert(sizeof(ClientContext) == 16, "wire layout");

struct ClientRequest {
  int32_t configType;
  int32_t nPids;
  int64_t jobId;
  // followed by int32_t pids[nPids] (leaf process first)
};
static_assert(sizeof(ClientRequest) == 16, "wire layout");

// Fire-and-forget step-telemetry report from the app shim ("pstat", no
// reference analog — libkineto never reports app progress back to the
// daemon). The daemon folds it into the metric store as job<jobId>.*
// series, giving the always-on history (and the auto-trigger rules) an
// application-level signal: step rate and step-time percentiles.
struct ClientPerfStats {
  int32_t pid;
  int32_t reserved; // alignment; must be 0 on the wire
  int64_t jobId;
  double windowS; // wall seconds this report covers
  double steps; // steps completed in the window
  double stepTimeP50Ms; // percentiles over the window's steps (0 if none)
  double stepTimeP95Ms;
  double stepTimeMaxMs;
};
static_assert(sizeof(ClientPerfStats) == 56, "wire layout");

// Kick-subscription handshake (no reference analog; libkineto never
// learns about configs except by polling). A shim that sends "sub"
// after registering gets a "kick" datagram (payload: int64 jobId) the
// moment a config is installed for its job, collapsing pickup latency
// from ~poll_interval/2 to the monitor thread's wake-up. Purely an
// optimization: delivery is still the poll, a lost kick costs nothing,
// and clients that never subscribe (stock libkineto) are never sent
// unsolicited messages.
struct ClientSubscribe {
  int32_t pid;
  int32_t reserved; // must be 0 on the wire (future version/flags)
  int64_t jobId;
};
static_assert(sizeof(ClientSubscribe) == 16, "wire layout");

// Fire-and-forget completed-span report from a Python client ("span", no
// reference analog — part of the control-plane self-tracing layer,
// src/core/SpanJournal.h). The shim/converter flush their half of a
// request's spans here so `selftrace` can merge both languages into one
// Chrome trace; a span named trace.convert additionally feeds the
// dynolog_trace_convert_seconds scrape histogram. The journal ring is
// fixed-size, so hostile flooding only churns the daemon's own flight
// recorder, never its memory.
struct ClientSpan {
  uint64_t traceId;
  uint64_t spanId;
  uint64_t parentId;
  int64_t startUs; // unix micros
  int64_t durUs;
  int32_t pid;
  int32_t reserved; // must be 0 on the wire (future version/flags)
  char name[48]; // NUL-padded ASCII (truncated client-side)
};
static_assert(sizeof(ClientSpan) == 96, "wire layout");

constexpr char kDaemonEndpointName[] = "dynolog"; // ref Utils.h:36
constexpr char kMsgTypeRequest[] = "req";
constexpr char kMsgTypeContext[] = "ctxt";
constexpr char kMsgTypePerfStats[] = "pstat";
constexpr char kMsgTypeSubscribe[] = "sub";
constexpr char kMsgTypeKick[] = "kick";
constexpr char kMsgTypeSpan[] = "span";

class IPCMonitor {
 public:
  explicit IPCMonitor(
      std::shared_ptr<TraceConfigManager> configManager,
      const std::string& endpointName = kDaemonEndpointName,
      std::shared_ptr<MetricStore> metricStore = nullptr);

  // Runs until stop(): handles every queued message, kicks every posted
  // job, then blocks until the next of either.
  void loop();

  // Supervised slice: like loop(), but returns after ~maxMs so the
  // owning Supervisor gets a heartbeat per slice and can contain an
  // exception (a hostile datagram, a fabric error) by rebuilding the
  // monitor instead of losing the thread.
  void runSlice(int64_t maxMs);

  // Also ends a blocked wait, through the manager's wake descriptor.
  void stop() {
    stop_.store(true);
    configManager_->wakeDrainer();
  }

  // Why the monitor thread left its blocking wait, counted over the
  // process's life (all incarnations: the supervisor rebuilds the
  // monitor, the counts go on): a datagram on the socket, a posted
  // config, or the wait's own timeout. An idle daemon shows timeouts
  // only, a handful a second; the `selftrace` verb reports all three.
  struct WakeCounts {
    uint64_t message;
    uint64_t posted;
    uint64_t timeout;
  };
  static WakeCounts wakeCounts();

  // Processes at most one pending message; returns whether one was handled
  // (deterministic entry point for tests).
  bool pollOnce();

  // Drains freshly-posted configs and kicks their subscribers
  // (deterministic entry point for tests; loop() calls it every pass).
  void sendPendingKicks();

  bool active() const {
    return fabric_ != nullptr;
  }

 private:
  // deadlineMs: unix ms to return at, negative for never.
  void serve(int64_t deadlineMs);
  // Blocks until the socket or the manager's postedFd() is readable, or
  // timeoutMs pass; counts the cause.
  void waitForWork(int timeoutMs);
  void processMsg(std::unique_ptr<ipc::Message> msg);
  void handleRequest(std::unique_ptr<ipc::Message> msg);
  void handleContext(std::unique_ptr<ipc::Message> msg);
  void handlePerfStats(std::unique_ptr<ipc::Message> msg);
  void handleSubscribe(std::unique_ptr<ipc::Message> msg);
  void handleSpan(std::unique_ptr<ipc::Message> msg);

  std::shared_ptr<TraceConfigManager> configManager_;
  std::unique_ptr<ipc::FabricManager> fabric_;
  std::shared_ptr<MetricStore> metricStore_;
  // Kick subscriptions: jobId → (client endpoint address → last "sub"
  // unix ms). Only touched on the monitor thread. Entries refresh on
  // every "sub" (shims re-subscribe periodically), expire after
  // kKickSubTtlMs, and the total address count is capped — hostile
  // datagrams must not grow this unboundedly.
  std::map<int64_t, std::map<std::string, int64_t>> kickSubs_;
  size_t kickSubCount_ = 0;
  int64_t lastKickSweepMs_ = 0;
  // Jobs that have published step telemetry: store series never expire, so
  // the set is capped — see handlePerfStats. Only touched on the monitor
  // thread (pollOnce/loop), no lock needed.
  std::set<int64_t> telemetryJobs_;
  // jobId → interned ids of its four job<id>.* series (rate, p50, p95,
  // max), resolved once per job so the per-datagram path allocates no
  // prefixed names. Monitor thread only, bounded by kMaxTelemetryJobs.
  std::map<int64_t, std::array<uint32_t, 4>> telemetryIds_;
  std::atomic<bool> stop_{false};
  static std::atomic<uint64_t> wakeMessage_;
  static std::atomic<uint64_t> wakePosted_;
  static std::atomic<uint64_t> wakeTimeout_;
};

} // namespace tracing
} // namespace dynotpu
