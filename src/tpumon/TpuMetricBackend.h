// dynolog_tpu: pluggable sources of TPU device telemetry.
// This subsystem replaces the reference's gpumon/DCGM leg (SURVEY §2.2).
// Where DcgmGroupInfo polls libdcgm field groups, a TpuMetricBackend yields
// one sample map per TPU device per tick. Three backends:
//   - FakeTpuBackend: deterministic synthetic metrics; the unit-test backend
//     the reference never had for gpumon (SURVEY §4 note).
//   - FileTpuBackend: reads a JSON snapshot the job itself writes
//     (dynolog_tpu.exporter.write_snapshot, called in-process: a chip
//     belongs to one process); covers metrics that only surface in-process.
//   - LibtpuBackend: dlopen'd libtpu monitoring API with graceful
//     degradation when the library or symbols are absent — the
//     DcgmApiStub.cpp:121-186 soft-fail pattern.
#pragma once

#include <cstdint>
#include <map>
#include <memory>
#include <string>
#include <vector>

namespace dynotpu {
namespace tpumon {

// TPU metric field ids (DCGM field-id analog, DcgmGroupInfo.cpp:36-53).
// ICI counters take the role of nvlink_tx/rx; TensorCore duty cycle maps to
// tensorcore_active; HBM bandwidth to hbm_mem_bw_util.
enum TpuFieldId : int32_t {
  kTensorCoreDutyCyclePct = 1,
  kHbmBwUtilPct = 2,
  kHbmUsedBytes = 3,
  kHbmTotalBytes = 4,
  kIciTxBytes = 5,
  kIciRxBytes = 6,
  kDutyCyclePct = 7,
  kMemoryBwUtilPct = 8,
  kHostToDeviceBytes = 9,
  kDeviceToHostBytes = 10,
  kUncorrectableEccErrors = 11,
  kMxuUtilPct = 12,
  // Collective telemetry published by dynolog_tpu.collectives (BASELINE
  // config 5): measured ICI bus bandwidth + latency per collective.
  kIciAllGatherGbps = 13,
  kIciReduceScatterGbps = 14,
  kIciAllReduceGbps = 15,
  kIciLatencyUs = 16,
  kIciAllGatherUs = 17,
  kIciReduceScatterUs = 18,
  kIciAllReduceUs = 19,
  kCollectiveMeshDevices = 20,
  // Fields surfaced by the vendor libtpu SDK monitoring surface
  // (libtpu.sdk.tpumonitoring metric names; docs/LIBTPU_SDK_ABI.md).
  kIciLinkHealth = 21, // 0 healthy … 10 link unusable
  kTpuThrottleScore = 22, // 0 not throttled … 10 = 100% throttled
  kHloQueueSize = 23, // enqueued-not-dequeued HLOs per core
  kBufferTransferLatencyUs = 24, // DCN buffer transfer, mean
  kCollectiveE2eLatencyUs = 25, // collective end-to-end, mean
  kHloExecutionTimingUs = 26, // HLO enqueue→dequeue, mean
  kTcpMinRttUs = 27,
  kTcpDeliveryRateMbps = 28,
  kH2dTransferLatencyUs = 29,
  kD2hTransferLatencyUs = 30,
};

// field id → metric name as logged (docs/METRICS.md catalog).
const std::map<int32_t, std::string>& tpuFieldIdToName();

// Parses a comma-separated field id list ("1,2,5,6"); unknown ids dropped.
std::vector<int32_t> parseFieldIds(const std::string& csv);

struct TpuDeviceSample {
  int32_t device = 0; // local device ordinal
  std::string chipType; // e.g. "tpu_v5p"
  std::map<int32_t, double> values; // field id → value
  bool valid = true; // false => backend returned blank values this tick
};

class TpuMetricBackend {
 public:
  virtual ~TpuMetricBackend() = default;

  // One-time setup; false = backend unusable on this host.
  virtual bool init() = 0;

  // One sample per local TPU device.
  virtual std::vector<TpuDeviceSample> sample() = 0;

  virtual std::string name() const = 0;
};

std::unique_ptr<TpuMetricBackend> makeFakeBackend(int numDevices);
std::unique_ptr<TpuMetricBackend> makeFileBackend(const std::string& path);
// requireDevices: init() additionally probes one sample and fails when the
// bound library reports zero devices — used by the auto factory so a
// device-less binding doesn't shadow the file-exporter fallback.
std::unique_ptr<TpuMetricBackend> makeLibtpuBackend(bool requireDevices = false);
// Reads the TPU runtime's own gRPC metric service on localhost (the
// tpu-info data source); init() fails when nothing serves the port.
// deferBind=true (explicit --tpu_metric_backend=grpc): init() succeeds
// even when every configured runtime is down, and the per-tick re-probe
// binds them when they come up — the daemon often starts before the TPU
// runtimes at host boot. false (the auto chain): all-down fails init so
// the chain can fall through to the libtpu/file backends.
std::unique_ptr<TpuMetricBackend> makeGrpcRuntimeBackend(
    bool deferBind = false);

} // namespace tpumon
} // namespace dynotpu
