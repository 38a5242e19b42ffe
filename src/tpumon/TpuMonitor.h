// dynolog_tpu: TPU device monitor — the DCGM leg rebuilt for TPU.
// Behavioral parity: reference dynolog/src/gpumon/DcgmGroupInfo.{h,cpp} —
// factory/update/log lifecycle (factory returning nullptr on failure,
// DcgmGroupInfo.cpp:97-133), watched-field selection from a CSV flag
// (DcgmGroupInfo.h:21-22), per-device metric maps rebuilt each tick with
// blank-value detection feeding an error metric (:295-335), one logger
// finalize per device (:348-368), and SLURM job attribution read from
// /proc/<pid>/environ of processes using the device (gpumon/Utils.cpp:26-68;
// pid discovery here scans /proc/*/fd for TPU device nodes instead of
// popen("nvidia-smi pmon")).
#pragma once

#include <atomic>
#include <memory>
#include <string>
#include <vector>

#include "src/core/Logger.h"
#include "src/tpumon/TpuMetricBackend.h"

namespace dynotpu {
namespace tpumon {

// pids with an open fd on a TPU device node (/dev/accel*, /dev/vfio/*).
// `rootDir` prefixes /proc and /dev for tests.
std::vector<int32_t> getPidsOnTpu(const std::string& rootDir = "");

// Selected environment of a pid (SLURM_JOB_ID etc.) for attribution.
std::map<std::string, std::string> readProcessEnv(
    int32_t pid,
    const std::string& rootDir = "");

class TpuMonitor {
 public:
  // nullptr when no backend is usable (daemon skips the TPU loop, like the
  // reference when DCGM init fails, Main.cpp:130-143).
  static std::unique_ptr<TpuMonitor> factory();
  static std::unique_ptr<TpuMonitor> factoryWithBackend(
      std::unique_ptr<TpuMetricBackend> backend,
      std::vector<int32_t> fields);

  // Pulls one sample set from the backend.
  void update();

  // Emits the latest samples: one finalize() per device, entity-tagged.
  void log(Logger& logger);

  const std::vector<TpuDeviceSample>& latestSamples() const {
    return samples_;
  }

  // Lifetime count of invalid/blank samples seen by update() — logged on
  // the tick-level summary row so a rotting backend is visible even when
  // it stops yielding device rows entirely.
  int64_t sampleErrors() const {
    return errorCount_;
  }

  std::string backendName() const {
    return backend_->name();
  }

  // Valid device rows of the process's last update(), whichever monitor
  // made it (the supervisor may rebuild the monitor; the count goes on):
  // 0 before the first tick. The `selftrace` verb reports it as
  // `tpu_rows`, so a chip the metric source stops reporting is a counter
  // that falls, not only a series that ends.
  static int64_t lastTickRows();

 private:
  TpuMonitor(
      std::unique_ptr<TpuMetricBackend> backend,
      std::vector<int32_t> fields)
      : backend_(std::move(backend)), fields_(std::move(fields)) {}

  std::unique_ptr<TpuMetricBackend> backend_;
  std::vector<int32_t> fields_;
  std::vector<TpuDeviceSample> samples_;
  int64_t errorCount_ = 0;
  static std::atomic<int64_t> lastTickRows_;
};

} // namespace tpumon
} // namespace dynotpu
