// dynolog_tpu: push-mode trace capture via the app's profiler server.
// The pull path (ipcfabric + shim polling, SURVEY §3.5 semantics) needs
// the app to import the shim; this is the alternative the SURVEY build
// plan names ("profiler-server push as an alternative backend", §7): any
// JAX/TF app that called jax.profiler.start_server(port) exposes
// tensorflow.ProfilerService, and the daemon drives a capture by calling
// Profile{duration_ms, emit_xspace} on it — no shim, no app polling. The
// schema is vendored in src/tpumon/proto/profiler_service.proto; the call
// rides the in-tree GrpcClient.
#pragma once

#include <atomic>
#include <cstdint>
#include <functional>
#include <string>

#include "src/common/Json.h"

namespace dynotpu {
namespace tracing {

// tensorflow.ProfileOptions tracer levels for a push capture. Defaults
// match jax's own profile defaults (host "info", device on, python off —
// python tracing costs seconds of server-side stop time). `dyno
// pushtrace --host_tracer_level/...` sets them through the pushtrace RPC.
struct PushProfileOptions {
  int hostTracerLevel = 2;
  int deviceTracerLevel = 1;
  int pythonTracerLevel = 0;
};

// Blocking capture: Profile() holds the stream open for durationMs and
// then streams back the serialized XSpace, which lands in the
// TensorBoard layout
// (<log_file minus .json>_push/plugins/profile/<ts>/machine.xplane.pb)
// plus a manifest at <log_file minus .json>_push.json. The XSpace is
// written INCREMENTALLY: ProfileResponse DATA slices flow through a
// protowire::StreamExtractor into the xplane's tmp file as they arrive
// (the disk write overlaps the transfer and the daemon never holds the
// multi-MB XSpace in memory), and the file is renamed into place only
// after the RPC finishes with an OK status. The returned report carries
// {status, trace_dir, manifest, xspace_bytes} or {status: "failed",
// error}. A raised `cancel` token aborts the capture within ~100ms —
// before the Profile RPC, mid-connect, or between response frames
// (GrpcClient's cancel-aware poll loop). `progress`, when set, receives
// {phase, bytes_streamed} updates the RPC result() poll surfaces while
// the capture is pending.
json::Value capturePushTrace(
    const std::string& profilerHost,
    int profilerPort,
    int64_t durationMs,
    const std::string& logFile,
    const std::atomic<bool>* cancel = nullptr,
    const PushProfileOptions& opts = {},
    const std::function<void(json::Value)>& progress = nullptr);

} // namespace tracing
} // namespace dynotpu
