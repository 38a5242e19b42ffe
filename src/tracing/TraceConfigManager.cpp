#include "src/tracing/TraceConfigManager.h"

#include <sys/eventfd.h>
#include <unistd.h>

#include <fstream>

#include "src/common/Defs.h"

namespace dynotpu {

json::Value TraceTriggerResult::toJson() const {
  auto obj = json::Value::object();
  auto toArray = [](const std::vector<int32_t>& v) {
    auto arr = json::Value::array();
    for (auto pid : v) {
      arr.append(pid);
    }
    return arr;
  };
  obj["processesMatched"] = toArray(processesMatched);
  obj["eventProfilersTriggered"] = toArray(eventProfilersTriggered);
  obj["activityProfilersTriggered"] = toArray(activityProfilersTriggered);
  obj["eventProfilersBusy"] = eventProfilersBusy;
  obj["activityProfilersBusy"] = activityProfilersBusy;
  return obj;
}

TraceConfigManager::TraceConfigManager(
    std::chrono::seconds keepAlive,
    std::string baseConfigPath)
    : keepAlive_(keepAlive),
      baseConfigPath_(std::move(baseConfigPath)),
      postedFd_(::eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC)) {
  if (postedFd_ < 0) {
    // poll(2) skips a negative descriptor: kicks then leave at the IPC
    // thread's poll timeout instead of at once, and nothing else changes.
    DLOG_ERROR << "TraceConfigManager: eventfd failed (" << errno
               << "); config kicks will wait for the IPC poll timeout";
  }
  // unsupervised-thread: lifecycle bound to this singleton's ctor/dtor;
  // managerLoop only expires registry entries under its own lock.
  managerThread_ = std::thread([this] { managerLoop(); });
}

TraceConfigManager::~TraceConfigManager() {
  {
    std::lock_guard<std::mutex> lock(mutex_);
    stop_ = true;
  }
  cv_.notify_all();
  managerThread_.join();
  if (postedFd_ >= 0) {
    ::close(postedFd_);
  }
}

void TraceConfigManager::wakeDrainer() {
  if (postedFd_ >= 0) {
    // An eventfd write fails only at counter overflow (EAGAIN): the
    // descriptor is readable already, which is all a wake asks for.
    (void)::eventfd_write(postedFd_, 1);
  }
}

std::shared_ptr<TraceConfigManager> TraceConfigManager::getInstance() {
  static auto instance = std::make_shared<TraceConfigManager>();
  return instance;
}

void TraceConfigManager::managerLoop() {
  while (true) {
    refreshBaseConfig();
    std::unique_lock<std::mutex> lock(mutex_);
    // Predicate wait: without it, a stop() racing ahead of this wait_for
    // would be missed and shutdown would block a full keep-alive period.
    auto interval = std::max<std::chrono::seconds>(keepAlive_, std::chrono::seconds(1));
    cv_.wait_for(lock, interval, [this] { return stop_; });
    if (stop_) {
      break;
    }
    runGcLocked();
  }
}

void TraceConfigManager::refreshBaseConfig() {
  std::ifstream file(baseConfigPath_);
  if (!file) {
    return;
  }
  std::string cfg(
      (std::istreambuf_iterator<char>(file)), std::istreambuf_iterator<char>());
  std::lock_guard<std::mutex> lock(mutex_);
  if (!cfg.empty() && cfg != baseConfig_) {
    baseConfig_ = cfg;
  }
}

void TraceConfigManager::runGcLocked() {
  auto now = Clock::now();
  for (auto jobIt = jobs_.begin(); jobIt != jobs_.end();) {
    auto& procs = jobIt->second;
    for (auto procIt = procs.begin(); procIt != procs.end();) {
      if (now - procIt->second.lastRequest > keepAlive_) {
        DLOG_INFO << "Stopped tracking process " << procIt->second.pid
                  << " of job " << jobIt->first;
        onProcessCleanup(procIt->first);
        procIt = procs.erase(procIt);
      } else {
        ++procIt;
      }
    }
    if (procs.empty()) {
      DLOG_INFO << "Stopped tracking job " << jobIt->first;
      instancesPerDevice_.erase(jobIt->first);
      lastRegister_.erase(jobIt->first);
      lastTriggered_.erase(jobIt->first);
      jobIt = jobs_.erase(jobIt);
    } else {
      ++jobIt;
    }
  }
  // Reap device-instance registrations whose clients registered but never
  // polled (crashed before the first obtainOnDemandConfig): they have no
  // jobs_ entry, so the loop above can't see them.
  for (auto it = instancesPerDevice_.begin();
       it != instancesPerDevice_.end();) {
    if (jobs_.count(it->first) == 0) {
      auto lastIt = lastRegister_.find(it->first);
      if (lastIt == lastRegister_.end() ||
          now - lastIt->second > keepAlive_) {
        DLOG_INFO << "Reaping stale registrations for job " << it->first;
        lastRegister_.erase(it->first);
        it = instancesPerDevice_.erase(it);
        continue;
      }
    }
    ++it;
  }
}

int32_t TraceConfigManager::registerContext(
    int64_t jobId,
    int32_t pid,
    int32_t device) {
  std::lock_guard<std::mutex> lock(mutex_);
  auto& instances = instancesPerDevice_[jobId][device];
  instances.insert(pid);
  lastRegister_[jobId] = Clock::now();
  DLOG_INFO << "Registered client pid " << pid << " (job " << jobId
            << ", device " << device << ")";
  return static_cast<int32_t>(instances.size());
}

std::string TraceConfigManager::obtainOnDemandConfig(
    int64_t jobId,
    const std::vector<int32_t>& pids,
    int32_t configType) {
  std::set<int32_t> pidSet(pids.begin(), pids.end());
  std::lock_guard<std::mutex> lock(mutex_);

  auto [it, isNew] = jobs_[jobId].emplace(pidSet, ClientProcess{});
  ClientProcess& process = it->second;
  if (isNew) {
    // pids is the ancestry list, leaf (requesting) process first.
    process.pid = pids.empty() ? 0 : pids[0];
    DLOG_INFO << "Tracking new client pid " << process.pid << " for job "
              << jobId;
    onRegisterProcess(pidSet);
  }

  std::string ret;
  if ((configType & static_cast<int32_t>(TraceConfigType::EVENTS)) &&
      !process.eventConfig.empty()) {
    ret += process.eventConfig + "\n";
    process.eventConfig.clear();
  }
  if ((configType & static_cast<int32_t>(TraceConfigType::ACTIVITIES)) &&
      !process.activityConfig.empty()) {
    ret += process.activityConfig + "\n";
    process.activityConfig.clear();
  }
  process.lastRequest = Clock::now();
  return ret;
}

TraceTriggerResult TraceConfigManager::setOnDemandConfig(
    int64_t jobId,
    const std::set<int32_t>& pids,
    const std::string& config,
    int32_t configType,
    int32_t limit) {
  TraceTriggerResult res;
  size_t nPids = pids.size();
  // Empty target set, or the single pid 0, means "all processes of the job"
  // (reference keeps the same two spellings for CLI back-compat,
  // LibkinetoConfigManager.cpp:244-249).
  bool matchAll = nPids == 0 || (nPids == 1 && *pids.begin() == 0);

  std::lock_guard<std::mutex> lock(mutex_);
  for (auto& [ancestry, process] : jobs_[jobId]) {
    bool matched = matchAll;
    if (!matched) {
      for (int32_t pid : ancestry) {
        if (pids.count(pid)) {
          matched = true;
          break;
        }
      }
    }
    if (!matched) {
      continue;
    }
    res.processesMatched.push_back(process.pid);

    if ((configType & static_cast<int32_t>(TraceConfigType::EVENTS)) &&
        static_cast<int32_t>(res.eventProfilersTriggered.size()) < limit) {
      if (process.eventConfig.empty()) {
        process.eventConfig = config;
        res.eventProfilersTriggered.push_back(process.pid);
      } else {
        res.eventProfilersBusy++;
      }
    }
    if ((configType & static_cast<int32_t>(TraceConfigType::ACTIVITIES)) &&
        static_cast<int32_t>(res.activityProfilersTriggered.size()) < limit) {
      if (process.activityConfig.empty()) {
        process.activityConfig = config;
        res.activityProfilersTriggered.push_back(process.pid);
      } else {
        res.activityProfilersBusy++;
      }
    }
  }
  if (!res.activityProfilersTriggered.empty() ||
      !res.eventProfilersTriggered.empty()) {
    lastTriggered_[jobId] = nowUnixMillis();
    // Queue the kick: subscribed shims get told a config is waiting
    // instead of discovering it at their next poll tick. Hard cap so
    // the queue stays bounded even with NO drainer attached (IPC
    // monitor disabled or its endpoint bind failed — the daemon keeps
    // serving RPC either way, and auto-triggers can fire for days);
    // a live drainer wakes on every post and never lets it near the cap.
    if (postedJobs_.size() < 1024) {
      postedJobs_.push_back(jobId);
      wakeDrainer();
    }
  }
  if (!res.activityProfilersTriggered.empty()) {
    onSetOnDemandConfig(pids);
  }
  DLOG_INFO << "On-demand trace request for job " << jobId << ": matched "
            << res.processesMatched.size() << " process(es), triggered "
            << res.activityProfilersTriggered.size() << ", busy "
            << res.activityProfilersBusy;
  return res;
}

std::vector<int64_t> TraceConfigManager::drainPostedJobs() {
  std::lock_guard<std::mutex> lock(mutex_);
  if (postedFd_ >= 0) {
    // Under the lock, so a post after this read is in the next drain
    // AND leaves the descriptor readable for it.
    eventfd_t count;
    (void)::eventfd_read(postedFd_, &count);
  }
  std::vector<int64_t> out;
  out.swap(postedJobs_);
  return out;
}

int64_t TraceConfigManager::lastTriggeredUnixMs(int64_t jobId) const {
  std::lock_guard<std::mutex> lock(mutex_);
  auto it = lastTriggered_.find(jobId);
  return it == lastTriggered_.end() ? 0 : it->second;
}

int TraceConfigManager::processCount(int64_t jobId) const {
  std::lock_guard<std::mutex> lock(mutex_);
  auto it = jobs_.find(jobId);
  return it == jobs_.end() ? 0 : static_cast<int>(it->second.size());
}

std::string TraceConfigManager::baseConfig() const {
  std::lock_guard<std::mutex> lock(mutex_);
  return baseConfig_;
}

json::Value TraceConfigManager::snapshotSessions() const {
  std::lock_guard<std::mutex> lock(mutex_);
  auto out = json::Value::array();
  for (const auto& [jobId, procs] : jobs_) {
    auto entry = json::Value::object();
    entry["job_id"] = jobId;
    entry["processes"] = static_cast<int64_t>(procs.size());
    auto& pending = entry["pending_pids"];
    pending = json::Value::array();
    for (const auto& [pids, proc] : procs) {
      if (!proc.eventConfig.empty() || !proc.activityConfig.empty()) {
        pending.append(static_cast<int64_t>(proc.pid));
      }
    }
    auto lastIt = lastTriggered_.find(jobId);
    entry["last_triggered_unix_ms"] =
        lastIt == lastTriggered_.end() ? int64_t(0) : lastIt->second;
    out.append(std::move(entry));
  }
  return out;
}

} // namespace dynotpu
