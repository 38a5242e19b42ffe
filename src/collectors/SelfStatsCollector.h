// dynolog_tpu: the daemon's own resource footprint as store series —
// "monitor the monitor". The <1% overhead budget (BASELINE.md) is a
// production property; these series make it observable in production
// instead of only in benchmark runs: dyno watch --metrics=daemon_cpu_pct, or a
// Prometheus alert on daemon_rss_kb. No reference analog (the reference
// daemon never reports its own cost).
#pragma once

#include <cstdint>
#include <string>

#include "src/core/Logger.h"

namespace dynotpu {

class SelfStatsCollector {
 public:
  // `rootDir` prefixes the /proc lookup so tests can use fixture trees
  // (the KernelCollector TESTROOT idiom); pid 0 = self.
  explicit SelfStatsCollector(std::string rootDir = "", int pid = 0);

  void step();

  // daemon_cpu_pct (CPU over the wall interval since the previous step;
  // skipped on the first sample), daemon_rss_kb, daemon_threads,
  // daemon_open_fds.
  void log(Logger& logger);

 private:
  const std::string procDir_;
  bool first_ = true;
  bool valid_ = false;

  double cpuSeconds_ = 0; // utime+stime, cumulative
  double prevCpuSeconds_ = 0;
  int64_t wallMs_ = 0;
  int64_t prevWallMs_ = 0;
  int64_t rssKb_ = 0;
  int64_t threads_ = 0;
  int64_t openFds_ = 0;
};

} // namespace dynotpu
