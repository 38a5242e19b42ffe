// dynolog_tpu: unified resource governance — the self-protection layer
// that makes "always-on and never harms the host" hold under disk, fd,
// and memory pressure (the failure episodes ARGUS-class production
// monitors actually survive; PAPERS.md).
//
// Problem being solved: after the durability work the daemon owns a lot
// of persistent state — WAL spill segments, state snapshots, trace
// artifacts, diagnosis reports, upstream-relay WALs — each with its own
// ad-hoc bound but no SHARED budget and no disk-pressure awareness. A
// full disk used to surface as scattered strerror lines (or silent
// growth) while the daemon kept admitting new capture work it could not
// finish. The governor makes resource exhaustion a first-class, drilled,
// loudly-degraded failure mode:
//
//   - every on-disk artifact CLASS registers with a priority and a
//     reclaim callback; the governor tracks per-class usage plus
//     statvfs free space on each registered root;
//   - a global --resource_disk_budget_bytes and a free-space floor
//     (--resource_disk_min_free_pct) are enforced with PRIORITIZED
//     eviction: ring profiles and old trace artifacts are reclaimed
//     before anything durable; never-evict classes (state snapshots,
//     the ack-pending WAL frontier) are tracked and budgeted but NEVER
//     reclaimed — the PR 9/10 durability invariants hold under pressure;
//   - fd and RSS watermarks (--resource_max_fds / --resource_rss_soft_mb)
//     are self-checked each governor tick and shed the same way;
//   - pressure state (ok / soft / hard) is published through the
//     "resources" health component, a `resources` section in the
//     `health` verb, and dynolog_resource_* OpenMetrics gauges;
//   - under HARD pressure new capture/diagnose admissions are refused
//     with a typed RPC error (admit()); durable telemetry is DEFERRED
//     (the sink path parks intervals, never drops); and everything
//     recovers automatically when the resource returns — the next clean
//     tick drops the pressure state, no restart required.
//
// Process-wide singleton like WalRegistry/HistogramRegistry: the
// persistence paths that must escalate into it (SinkWal, AutoTrigger
// pruning, capturers) are constructed far from Main's wiring. Main
// configures it from flags; with the default disk config (budget 0,
// floor 0) it observes and publishes but never evicts, so the legacy
// unbounded disk behavior is strictly opt-in to leave. Two guards stay
// armed by default on purpose: maxFds=0 self-derives the watermark
// from the process's own RLIMIT_NOFILE (hard only at 95% — genuine fd
// exhaustion, which no operator wants "off"), and a persistence-path
// write failure (noteWriteFailure) always escalates.
//
// The pure-Python mirror (dynolog_tpu/supervise.py ResourceGovernor,
// same class/priority/pressure semantics and snapshot keys) backs the
// pre-build pressure smoke (scripts/pressure_smoke.py) and the tier-1
// pressure tests (tests/test_pressure.py).
#pragma once

#include <cstdint>
#include <functional>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <vector>

#include "src/common/Json.h"
#include "src/core/Health.h"

namespace dynotpu {

class ResourceGovernor {
 public:
  // ok -> soft -> hard; ordered so thresholds compare numerically.
  enum class Pressure { kOk = 0, kSoft = 1, kHard = 2 };

  struct Options {
    int64_t diskBudgetBytes = 0; // 0 = no budget (observe only)
    double diskMinFreePct = 0.0; // statvfs floor per root; 0 = off
    // Soft threshold as a share of the budget (hard = at/over budget).
    double softFraction = 0.85;
    // 0 = self-derive from RLIMIT_NOFILE (configure()); soft at 80%,
    // hard at 95%. Set explicitly to budget below the rlimit.
    int64_t maxFds = 0;
    int64_t rssSoftMb = 0; // 0 = off; soft at 1x, hard at 1.5x
  };

  // usage() -> {bytes, files} for the class right now. reclaim(target)
  // frees ~target bytes of the class's lowest-value artifacts (oldest
  // first is the house policy) and returns the bytes actually freed.
  using UsageFn = std::function<std::pair<int64_t, int64_t>()>;
  using ReclaimFn = std::function<int64_t(int64_t targetBytes)>;

  static ResourceGovernor& instance();

  // Main wires these once at startup (before any tick). configure() is
  // also how tests shrink the budget mid-run.
  void configure(const Options& opts);
  void setHealth(std::shared_ptr<ComponentHealth> health);

  // Registers one artifact class. Lower priority = reclaimed first.
  // neverEvict classes are tracked + budgeted but never reclaimed (the
  // durability invariant: snapshots and the ack-pending WAL frontier
  // survive pressure). root (may be empty) adds a statvfs watch point.
  // Re-registering a name replaces its callbacks (collector restarts).
  void registerClass(
      const std::string& name,
      int priority,
      bool neverEvict,
      const std::string& root,
      UsageFn usage,
      ReclaimFn reclaim = nullptr);

  // One governor tick: refresh per-class usage and per-root free space,
  // self-check fds/RSS, run prioritized eviction while over budget or
  // under the floor, publish the resulting pressure to health. Cheap
  // enough for a 1s supervised cadence. Returns the pressure after any
  // reclaim this tick achieved.
  Pressure tick();

  Pressure pressure() const;

  // Admission check for new capture/diagnose work: true = admitted.
  // Under HARD pressure returns false with *error set to the operator-
  // facing reason (the typed RPC refusal rides it). Refusals counted.
  bool admit(const char* what, std::string* error = nullptr);

  // A persistence-path write failed with `err` (ENOSPC and friends):
  // escalate to HARD immediately — pressure must be loud within one
  // tick of the first refused write, not one statvfs cadence later.
  // Recovery is automatic: a later tick with clean signals drops it.
  void noteWriteFailure(const std::string& site, int err);

  // A bounded-retention prune could not remove its victims (permissions,
  // EIO): the artifact class may now grow without bound, which is a
  // governor problem, not a log line (AutoTrigger escalates here).
  void noteReclaimFailure(const std::string& site, const std::string& what);

  // The `health` verb's "resources" section:
  //   {"pressure", "disk": {budget_bytes, usage_bytes, min_free_pct,
  //    roots: {path: free_pct}}, "fds": {open, max}, "rss_mb",
  //    "classes": {name: {priority, never_evict, usage_bytes, files,
  //    reclaims, reclaimed_bytes}}, "refusals", "write_failures",
  //    "reclaim_failures", "last_error"}
  json::Value snapshot() const;

  // dynolog_resource_* gauge/counter block for the /metrics exposition.
  std::string renderOpenMetrics() const;

  // Tests: drop classes, counters, thresholds, health binding.
  void resetForTesting();

  static const char* pressureName(Pressure p);

 private:
  struct ClassState {
    int priority = 0;
    bool neverEvict = false;
    std::string root;
    UsageFn usage;
    ReclaimFn reclaim;
    int64_t usageBytes = 0;
    int64_t files = 0;
    int64_t reclaims = 0;
    int64_t reclaimedBytes = 0;
  };

  void publishLocked();

  mutable std::mutex mutex_;
  Options opts_; // guarded_by(mutex_)
  std::shared_ptr<ComponentHealth> health_; // guarded_by(mutex_)
  std::map<std::string, ClassState> classes_; // guarded_by(mutex_)
  Pressure pressure_ = Pressure::kOk; // guarded_by(mutex_)
  std::map<std::string, double> rootFreePct_; // guarded_by(mutex_)
  int64_t openFds_ = -1; // guarded_by(mutex_)
  int64_t maxFdsEffective_ = 0; // guarded_by(mutex_)
  int64_t rssMb_ = -1; // guarded_by(mutex_)
  int64_t totalUsage_ = 0; // guarded_by(mutex_)
  int64_t refusals_ = 0; // guarded_by(mutex_)
  int64_t writeFailures_ = 0; // guarded_by(mutex_)
  int64_t reclaimFailures_ = 0; // guarded_by(mutex_)
  int64_t ticks_ = 0; // guarded_by(mutex_)
  bool writeFailurePending_ = false; // guarded_by(mutex_)
  std::string lastError_; // guarded_by(mutex_)
};

// Shared helpers for the default artifact-class callbacks (Main's class
// registrations and the unit tests use the same ones, so "usage" means
// the same bytes everywhere).

// Recursive {bytes, files} of every regular file under `root` (0,0 when
// absent). Symlinks are not followed.
std::pair<int64_t, int64_t> dirUsage(const std::string& root);

// Reclaims ~targetBytes under `root`, oldest mtime first, skipping
// files younger than graceSeconds (a family mid-write must not be
// deleted under its writer) and anything matching a ".tmp" suffix's
// in-flight discipline is fair game like any other file. Returns the
// bytes freed. Empty subdirectories left behind are removed best-effort.
int64_t reclaimOldestFiles(
    const std::string& root, int64_t targetBytes, int64_t graceSeconds);

} // namespace dynotpu
