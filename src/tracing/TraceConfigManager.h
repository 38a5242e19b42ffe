// dynolog_tpu: registry of profiler-client processes + on-demand trace
// config hand-off. Transport-independent: used by both the RPC layer (CLI
// pushes configs in) and the IPC monitor (JAX-app shims pull configs out).
//
// Behavioral parity: reference dynolog/src/LibkinetoConfigManager.{h,cpp} —
// jobId → {pid-ancestry-set → process} registry (LibkinetoConfigManager.h:70-76),
// keep-alive GC expiring clients idle >60s (LibkinetoConfigManager.cpp:24,98-127),
// base config file refresh (:25,90-96), busy detection + process_limit
// (:193-289). Clients here are JAX processes holding the dynolog_tpu Python
// shim instead of libkineto, but the semantics are identical so PyTorch
// libkineto clients keep working over the same IPC wire format.
#pragma once

#include <chrono>
#include <condition_variable>
#include <map>
#include <memory>
#include <mutex>
#include <set>
#include <string>
#include <thread>
#include <vector>

#include "src/common/Json.h"
#include "src/common/Time.h"

namespace dynotpu {

// Bitmask of which profiler a config targets (wire-compatible with the
// reference's LibkinetoConfigType).
enum class TraceConfigType : int32_t {
  EVENTS = 0x1,
  ACTIVITIES = 0x2,
};

struct TraceTriggerResult {
  std::vector<int32_t> processesMatched;
  std::vector<int32_t> eventProfilersTriggered;
  std::vector<int32_t> activityProfilersTriggered;
  int32_t eventProfilersBusy = 0;
  int32_t activityProfilersBusy = 0;

  json::Value toJson() const;
};

class TraceConfigManager {
 public:
  explicit TraceConfigManager(
      std::chrono::seconds keepAlive = std::chrono::seconds(60),
      std::string baseConfigPath = kDefaultBaseConfigPath);
  virtual ~TraceConfigManager();

  TraceConfigManager(const TraceConfigManager&) = delete;
  TraceConfigManager& operator=(const TraceConfigManager&) = delete;

  static std::shared_ptr<TraceConfigManager> getInstance();

  // Client side (via IPC): explicit registration of a client process running
  // on `device`. Returns the number of registered instances on that device
  // for the job.
  int32_t registerContext(int64_t jobId, int32_t pid, int32_t device);

  // Client side (via IPC): periodic poll. `pids` is the client's pid
  // ancestry, leaf first. Registers the process if new, refreshes its
  // keep-alive, and returns+clears any pending config for `configType`
  // (newline-joined if both profilers have one).
  std::string obtainOnDemandConfig(
      int64_t jobId,
      const std::vector<int32_t>& pids,
      int32_t configType);

  // Operator side (via RPC): install `config` for every registered process
  // of `jobId` matching `pids` (empty or {0} = all). At most `limit`
  // processes are triggered per profiler type; a process whose previous
  // config was not yet consumed counts as busy.
  TraceTriggerResult setOnDemandConfig(
      int64_t jobId,
      const std::set<int32_t>& pids,
      const std::string& config,
      int32_t configType,
      int32_t limit);

  int processCount(int64_t jobId) const;

  // Jobs that had a config installed since the last drain (at least one
  // process matched). The IPC monitor drains this whenever postedFd()
  // wakes it and sends "kick" datagrams to subscribed shims, collapsing
  // config pickup latency from ~poll_interval/2 to the thread's wake-up.
  // Kicks are an optimization only — polling remains the delivery
  // mechanism.
  std::vector<int64_t> drainPostedJobs();

  // Wake descriptor of the drainer (an eventfd): readable from a post to
  // postedJobs_ until the next drainPostedJobs(). The IPC monitor's
  // thread blocks in poll(2) on it beside the fabric's socket, so a
  // posted job is kicked without waiting out a timer.
  int postedFd() const {
    return postedFd_;
  }

  // Makes postedFd() readable with nothing posted: IPCMonitor::stop()
  // uses it to cut a blocked poll short.
  void wakeDrainer();

  // Unix ms of the last setOnDemandConfig that triggered at least one
  // profiler for `jobId` (0 = never). Lets the auto-trigger engine
  // suppress redundant local fires while a capture — operator-initiated
  // or relayed from a peer daemon — is already pending or in flight.
  int64_t lastTriggeredUnixMs(int64_t jobId) const;

  // Base (always-on) config visible to clients; refreshed from
  // baseConfigPath by the manager thread.
  std::string baseConfig() const;

  // Crash/restart coherence (src/core/StateSnapshot.h): the in-flight
  // capture picture — per job: registered process count, pids with a
  // pending (installed, not yet consumed) config, and the last config
  // push time. A restarted daemon cannot re-own these hand-offs (the
  // shim finishes its capture locally and writes the manifest
  // regardless), but it records what straddled the crash so the health
  // verb's durability section and the logs can account for every
  // capture instead of silently forgetting it.
  json::Value snapshotSessions() const;

  // Deterministic GC entry point for tests.
  void runGcForTesting() {
    std::lock_guard<std::mutex> lock(mutex_);
    runGcLocked();
  }

  static constexpr const char* kDefaultBaseConfigPath =
      "/etc/dynolog_tpu/trace.conf";

 protected:
  // Hook points for subclasses (reference keeps equivalent virtual on*
  // methods, LibkinetoConfigManager.h:61-67).
  virtual void onRegisterProcess(const std::set<int32_t>& pids) {}
  virtual void onSetOnDemandConfig(const std::set<int32_t>& pids) {}
  virtual void onProcessCleanup(const std::set<int32_t>& pids) {}

 private:
  struct ClientProcess {
    int32_t pid = 0; // leaf pid
    std::string eventConfig;
    std::string activityConfig;
    TimePoint lastRequest;
  };

  void managerLoop();
  void runGcLocked();
  void refreshBaseConfig();

  const std::chrono::seconds keepAlive_;
  const std::string baseConfigPath_;

  mutable std::mutex mutex_;
  std::condition_variable cv_;
  bool stop_ = false; // guarded_by(mutex_)

  // Jobs with a freshly-installed config, pending kick fan-out.
  std::vector<int64_t> postedJobs_; // guarded_by(mutex_)
  // eventfd behind postedFd(); written on every post, cleared by the
  // drain. Opened in the constructor, closed in the destructor.
  const int postedFd_;

  // jobId → pid-ancestry-set → process state
  std::map<int64_t, std::map<std::set<int32_t>, ClientProcess>>
      jobs_; // guarded_by(mutex_)
  // jobId → device → registered pids (size = instance count per device)
  std::map<int64_t, std::map<int32_t, std::set<int32_t>>>
      instancesPerDevice_; // guarded_by(mutex_)
  // jobId → last registerContext time; lets GC reap jobs whose clients
  // registered but died before ever polling (so they never enter jobs_).
  std::map<int64_t, TimePoint> lastRegister_; // guarded_by(mutex_)
  // jobId → unix ms of the last config push that triggered a profiler.
  std::map<int64_t, int64_t> lastTriggered_; // guarded_by(mutex_)
  std::string baseConfig_; // guarded_by(mutex_)

  // Written once in the constructor, joined in the destructor; no other
  // thread ever touches it.
  std::thread managerThread_; // unguarded(ctor/dtor lifecycle only)
};

} // namespace dynotpu
