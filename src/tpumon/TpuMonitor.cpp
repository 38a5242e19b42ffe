#include "src/tpumon/TpuMonitor.h"

#include <dirent.h>
#include <unistd.h>

#include <cstring>
#include <fstream>

#include "src/common/Defs.h"
#include "src/common/Flags.h"

// Watched TPU fields, CSV of TpuFieldId values (DCGM's --dcgm_fields analog,
// DcgmGroupInfo.h:21-22). Default: duty cycle, HBM, ICI.
DYN_DEFINE_string(
    tpu_fields,
    "1,2,3,4,5,6,7,12,13,14,15,16,17,18,19,20,21,22,23,24,25,26,27,28,29,30",
    "Comma separated TPU field ids to watch (13-20 are the measured ICI "
    "collective metrics, 21-30 the libtpu SDK monitoring metrics; each only "
    "appears when a backend supplies it)");

DYN_DEFINE_string(
    tpu_metric_backend,
    "auto",
    "TPU metric backend: auto | grpc | libtpu | file | fake (grpc = the "
    "TPU runtime's RuntimeMetricService on localhost:8431, tpu-info's "
    "data source)");

DYN_DEFINE_string(
    tpu_metrics_file,
    "/tmp/dynolog_tpu_metrics.json",
    "Snapshot path for the 'file' TPU metric backend");

DYN_DEFINE_int32(
    tpu_fake_devices,
    4,
    "Device count simulated by the 'fake' TPU metric backend");

DYN_DEFINE_bool(
    tpu_job_attribution,
    true,
    "Attach SLURM/user attribution from /proc/<pid>/environ of TPU processes");

namespace dynotpu {
namespace tpumon {

std::vector<int32_t> getPidsOnTpu(const std::string& rootDir) {
  std::vector<int32_t> pids;
  std::string procPath = rootDir + "/proc";
  DIR* proc = opendir(procPath.c_str());
  if (!proc) {
    return pids;
  }
  while (dirent* entry = readdir(proc)) {
    char* end = nullptr;
    long pid = std::strtol(entry->d_name, &end, 10);
    if (!end || *end != '\0' || pid <= 0) {
      continue;
    }
    std::string fdDir = procPath + "/" + entry->d_name + "/fd";
    DIR* fds = opendir(fdDir.c_str());
    if (!fds) {
      continue; // permission or gone
    }
    bool usesTpu = false;
    while (dirent* fd = readdir(fds)) {
      if (fd->d_name[0] == '.') {
        continue;
      }
      char target[256];
      std::string link = fdDir + "/" + fd->d_name;
      ssize_t n = readlink(link.c_str(), target, sizeof(target) - 1);
      if (n <= 0) {
        continue;
      }
      target[n] = '\0';
      if (std::strstr(target, "/dev/accel") ||
          std::strstr(target, "/dev/vfio")) {
        usesTpu = true;
        break;
      }
    }
    closedir(fds);
    if (usesTpu) {
      pids.push_back(static_cast<int32_t>(pid));
    }
  }
  closedir(proc);
  return pids;
}

std::map<std::string, std::string> readProcessEnv(
    int32_t pid,
    const std::string& rootDir) {
  // Attribution keys the reference exports as logger columns
  // (DcgmGroupInfo.cpp:56-60).
  static const char* kKeys[] = {
      "SLURM_JOB_ID", "SLURM_JOB_USER", "SLURM_JOB_PARTITION", "USER",
      "JOB_ID"};
  std::map<std::string, std::string> out;
  std::ifstream f(
      rootDir + "/proc/" + std::to_string(pid) + "/environ",
      std::ios::binary);
  if (!f) {
    return out;
  }
  std::string data(
      (std::istreambuf_iterator<char>(f)), std::istreambuf_iterator<char>());
  size_t pos = 0;
  while (pos < data.size()) {
    size_t end = data.find('\0', pos);
    if (end == std::string::npos) {
      end = data.size();
    }
    std::string entry = data.substr(pos, end - pos);
    size_t eq = entry.find('=');
    if (eq != std::string::npos) {
      std::string key = entry.substr(0, eq);
      for (const char* want : kKeys) {
        if (key == want) {
          out[key] = entry.substr(eq + 1);
        }
      }
    }
    pos = end + 1;
  }
  return out;
}

std::unique_ptr<TpuMonitor> TpuMonitor::factory() {
  auto fields = parseFieldIds(FLAGS_tpu_fields);
  const std::string& mode = FLAGS_tpu_metric_backend;

  auto tryBackend = [&](std::unique_ptr<TpuMetricBackend> backend)
      -> std::unique_ptr<TpuMonitor> {
    if (backend && backend->init()) {
      DLOG_INFO << "TpuMonitor using backend: " << backend->name();
      return factoryWithBackend(std::move(backend), fields);
    }
    return nullptr;
  };

  if (mode == "fake") {
    return tryBackend(makeFakeBackend(FLAGS_tpu_fake_devices));
  }
  if (mode == "file") {
    return tryBackend(makeFileBackend(FLAGS_tpu_metrics_file));
  }
  if (mode == "libtpu") {
    return tryBackend(makeLibtpuBackend());
  }
  if (mode == "grpc") {
    return tryBackend(makeGrpcRuntimeBackend(/*deferBind=*/true));
  }
  // auto: the runtime's own gRPC metric service first (only alive when a
  // real runtime holds the chips — the strongest signal and the freshest
  // data), then the libtpu SDK library, then the file exporter. The
  // libtpu SDK can bind successfully yet see zero local devices (chip held
  // by a remote runtime, or TPU-less host with the wheel installed);
  // requireDevices makes init() fail in that case so the exporter-fed file
  // backend still carries the metrics — explicit --tpu_metric_backend=libtpu
  // skips the probe and trusts the binding.
  if (auto m = tryBackend(makeGrpcRuntimeBackend())) {
    return m;
  }
  if (auto m = tryBackend(makeLibtpuBackend(/*requireDevices=*/true))) {
    return m;
  }
  if (auto m = tryBackend(makeFileBackend(FLAGS_tpu_metrics_file))) {
    return m;
  }
  DLOG_WARNING << "No TPU metric backend available";
  return nullptr;
}

std::unique_ptr<TpuMonitor> TpuMonitor::factoryWithBackend(
    std::unique_ptr<TpuMetricBackend> backend,
    std::vector<int32_t> fields) {
  return std::unique_ptr<TpuMonitor>(
      new TpuMonitor(std::move(backend), std::move(fields)));
}

std::atomic<int64_t> TpuMonitor::lastTickRows_{0};

int64_t TpuMonitor::lastTickRows() {
  return lastTickRows_.load();
}

void TpuMonitor::update() {
  samples_ = backend_->sample();
  int64_t rows = 0;
  for (const auto& s : samples_) {
    if (s.valid) {
      rows++;
    } else {
      errorCount_++;
    }
  }
  lastTickRows_.store(rows);
}

void TpuMonitor::log(Logger& logger) {
  // Job attribution is host-wide (one scan per tick, not per device).
  std::map<std::string, std::string> attribution;
  std::string tpuPids;
  if (FLAGS_tpu_job_attribution) {
    for (int32_t pid : getPidsOnTpu()) {
      if (!tpuPids.empty()) {
        tpuPids += ",";
      }
      tpuPids += std::to_string(pid);
      if (attribution.empty()) {
        attribution = readProcessEnv(pid);
      }
    }
  }

  const auto& fieldNames = tpuFieldIdToName();
  for (const auto& s : samples_) {
    logger.logInt("device", s.device);
    logger.logStr("entity", "tpu" + std::to_string(s.device));
    if (!s.chipType.empty()) {
      logger.logStr("chip_type", s.chipType);
    }
    for (int32_t field : fields_) {
      auto it = s.values.find(field);
      if (it != s.values.end()) {
        logger.logFloat(fieldNames.at(field), it->second);
      }
    }
    // Blank/invalid samples surface as an error counter rather than fake
    // zeros (reference sets dcgm_error the same way, DcgmGroupInfo.cpp:320-332).
    if (!s.valid) {
      logger.logInt("tpu_error", 1);
    }
    if (!tpuPids.empty()) {
      logger.logStr("tpu_pids", tpuPids);
    }
    for (const auto& [key, value] : attribution) {
      logger.logStr(key, value);
    }
    logger.setTimestamp();
    logger.finalize();
  }
}

} // namespace tpumon
} // namespace dynotpu
