#include "src/tracing/PushTraceCapturer.h"

#include <sys/stat.h>
#include <unistd.h>

#include <atomic>
#include <cerrno>
#include <cstdio>
#include <cstring>
#include <ctime>
#include <fstream>

#include "src/common/Defs.h"
#include "src/common/Failpoints.h"
#include "src/common/GrpcClient.h"
#include "src/common/ProtoWire.h"
#include "src/core/ResourceGovernor.h"
#include "src/tracing/CaptureUtils.h"
#include "src/common/Time.h"

namespace dynotpu {
namespace tracing {

namespace {
namespace pw = protowire;

bool makeDirs(const std::string& path) {
  std::string partial;
  for (size_t i = 0; i < path.size(); ++i) {
    if (path[i] == '/' && i > 0) {
      partial = path.substr(0, i);
      if (::mkdir(partial.c_str(), 0755) < 0 && errno != EEXIST) {
        return false;
      }
    }
  }
  return ::mkdir(path.c_str(), 0755) == 0 || errno == EEXIST;
}

} // namespace

json::Value capturePushTrace(
    const std::string& profilerHost,
    int profilerPort,
    int64_t durationMs,
    const std::string& logFile,
    const std::atomic<bool>* cancel,
    const PushProfileOptions& profileOpts,
    const std::function<void(json::Value)>& progress) {
  durationMs = clampPushDurationMs(durationMs);
  auto report = json::Value::object();
  if (cancel && cancel->load()) {
    report["status"] = "failed";
    report["error"] = "cancelled before the Profile RPC was issued";
    return report;
  }

  // Process-wide single flight: the profiler service rejects concurrent
  // sessions, and both the pushtrace RPC and push-mode auto-triggers call
  // through here — serializing at the capture layer keeps the invariant
  // in one place. The loser fails fast with a clear reason (auto-trigger
  // rules treat that as retryable).
  static std::atomic<bool> inFlight{false};
  bool expected = false;
  if (!inFlight.compare_exchange_strong(expected, true)) {
    report["status"] = "failed";
    report["error"] = "another push capture is already in progress";
    return report;
  }
  struct Release {
    std::atomic<bool>& flag;
    ~Release() {
      flag.store(false);
    }
  } release{inFlight};

  // tensorflow.ProfileRequest (vendored schema): duration_ms=1, opts=4,
  // repository_root=5, session_id=6, host_name=7, emit_xspace=9. With
  // emit_xspace the server returns the XSpace in the response instead of
  // writing it server-side. ProfileOptions must be explicit: a defaulted
  // opts message means tracer levels 0 and the server records nothing.
  std::string opts; // tensorflow.ProfileOptions
  pw::putUint64(opts, 5, 1); // version
  pw::putUint64(
      opts, 2, static_cast<uint64_t>(profileOpts.hostTracerLevel));
  pw::putUint64(
      opts, 3, static_cast<uint64_t>(profileOpts.deviceTracerLevel));
  pw::putUint64(
      opts, 4, static_cast<uint64_t>(profileOpts.pythonTracerLevel));
  pw::putUint64(opts, 9, static_cast<uint64_t>(durationMs));
  std::string req;
  pw::putUint64(req, 1, static_cast<uint64_t>(durationMs));
  pw::putMessage(req, 4, opts);
  pw::putString(req, 6, "dynolog_push");
  pw::putString(req, 7, profilerHost);
  pw::putBool(req, 9, true);

  // TensorBoard repository layout, like the shim's jax.profiler output —
  // prepared BEFORE the Profile RPC so the response can stream straight
  // to disk as DATA frames arrive.
  std::string base = logFile;
  if (base.size() > 5 && base.rfind(".json") == base.size() - 5) {
    base = base.substr(0, base.size() - 5);
  }
  char stamp[32];
  time_t now = ::time(nullptr);
  std::strftime(stamp, sizeof(stamp), "%Y_%m_%d_%H_%M_%S", ::localtime(&now));
  std::string traceDir =
      base + "_push/plugins/profile/" + stamp;
  if (!makeDirs(traceDir)) {
    report["status"] = "failed";
    report["error"] = "cannot create " + traceDir + ": " +
        std::strerror(errno);
    return report;
  }
  std::string xplanePath = traceDir + "/machine.xplane.pb";
  std::string tmpPath = xplanePath + ".tmp";
  // Debris discipline for every failure exit below: the tmp is unlinked
  // (a torn xplane must never look like an artifact) and the dir tree —
  // created BEFORE the RPC so the response can stream to disk — is
  // removed bottom-up. rmdir only removes empty dirs, so parents shared
  // with an earlier successful capture survive untouched.
  auto cleanupTmp = [&] {
    ::unlink(tmpPath.c_str());
    ::rmdir(traceDir.c_str());
    ::rmdir((base + "_push/plugins/profile").c_str());
    ::rmdir((base + "_push/plugins").c_str());
    ::rmdir((base + "_push").c_str());
  };
  // trace.artifact.write failpoint: the errno-level full-disk drill for
  // the streaming artifact sink. Fired AFTER the tmp exists so the
  // failure path proves the abort contract: tmp unlinked, dir tree
  // removed, nothing ever renamed — a partial artifact can never be
  // published, drilled or real.
  std::ofstream xplaneOut(tmpPath, std::ios::binary | std::ios::trunc);
  if (failpoints::maybeFail("trace.artifact.write") || !xplaneOut) {
    const int writeErrno = errno;
    report["status"] = "failed";
    report["error"] = "cannot create " + tmpPath + ": " +
        std::strerror(writeErrno);
    ResourceGovernor::instance().noteWriteFailure(
        "trace.artifact.write", writeErrno);
    cleanupTmp();
    return report;
  }

  // Streaming extraction: ProfileResponse is {small fields + one
  // multi-MB xspace (field 8)}. The extractor forwards xspace payload
  // slices into the tmp file as each DATA frame arrives — the disk
  // write overlaps the transfer, the daemon never materializes the
  // XSpace, and the poll surface sees live bytes_streamed progress.
  int64_t lastProgressMb = -1;
  pw::StreamExtractor extractor(8, [&](std::string_view slice) {
    xplaneOut.write(
        slice.data(), static_cast<std::streamsize>(slice.size()));
    if (progress) {
      int64_t mb =
          static_cast<int64_t>(extractor.streamedBytes() >> 20);
      if (mb != lastProgressMb) {
        lastProgressMb = mb;
        auto p = json::Value::object();
        p["phase"] = "streaming_xspace";
        p["bytes_streamed"] =
            static_cast<int64_t>(extractor.streamedBytes());
        progress(std::move(p));
      }
    }
    return static_cast<bool>(xplaneOut);
  });

  GrpcClient client(profilerHost, profilerPort);
  std::string error;
  // Profile() blocks server-side for the whole window; pad the deadline.
  // The cancel token propagates into the client's poll loop, so daemon
  // shutdown aborts the in-flight window within ~100ms instead of
  // waiting out durationMs + 15s.
  int64_t rpcStartMs = nowUnixMillis();
  GrpcCallStats rpcStats;
  auto resp = client.call(
      "/tensorflow.ProfilerService/Profile",
      req,
      &error,
      static_cast<int>(durationMs) + 15'000,
      cancel,
      &rpcStats,
      [&](std::string_view msgSlice) { return extractor.feed(msgSlice); });
  int64_t rpcMs = nowUnixMillis() - rpcStartMs;
  if (!resp) {
    cleanupTmp();
    report["status"] = "failed";
    report["error"] = "profiler server " + profilerHost + ":" +
        std::to_string(profilerPort) + ": " + error +
        " (is jax.profiler.start_server(port) running in the app?)";
    return report;
  }

  // tensorflow.ProfileResponse: tool_data=6, empty_trace=7, xspace=8.
  // The xspace went to disk through the extractor; the remaining small
  // fields are a normal message walk.
  bool emptyTrace = false;
  pw::walk(extractor.others(), [&](const pw::Field& f) {
    if (f.number == 7 && f.wireType == 0) {
      emptyTrace = f.varint != 0;
    }
  });
  if (!extractor.complete() || extractor.streamedBytes() == 0) {
    cleanupTmp();
    report["status"] = "failed";
    report["error"] = emptyTrace
        ? "profiler returned an empty trace (no device activity in window?)"
        : "profiler response carried no XSpace";
    return report;
  }

  // Finalize: everything already hit the page cache during the stream;
  // what remains is flush + the atomic rename.
  int64_t writeStartMs = nowUnixMillis();
  xplaneOut.close();
  if (!xplaneOut ||
      // durability-ok: trace artifact — atomic publish (no torn reader
      // view) is the goal; a crash losing an in-flight capture is
      // acceptable and the capture is re-runnable.
      ::rename(tmpPath.c_str(), xplanePath.c_str()) != 0) {
    ResourceGovernor::instance().noteWriteFailure(
        "trace.artifact.write", errno);
    cleanupTmp();
    report["status"] = "failed";
    report["error"] = "write failed: " + xplanePath;
    return report;
  }
  int64_t writeMs = nowUnixMillis() - writeStartMs;
  uint64_t xspaceBytes = extractor.streamedBytes();

  auto manifest = json::Value::object();
  manifest["mode"] = "push";
  manifest["trace_dir"] = base + "_push";
  manifest["profiler"] = profilerHost + ":" + std::to_string(profilerPort);
  manifest["duration_ms"] = durationMs;
  manifest["host_tracer_level"] = profileOpts.hostTracerLevel;
  manifest["device_tracer_level"] = profileOpts.deviceTracerLevel;
  manifest["python_tracer_level"] = profileOpts.pythonTracerLevel;
  manifest["xspace_bytes"] = static_cast<int64_t>(xspaceBytes);
  // The xplane was written through the streaming chunk pipeline: DATA
  // slices went to disk as they arrived, so the transfer and the write
  // overlap and write_ms below is only the flush+rename tail.
  manifest["streamed_write"] = true;
  // Latency decomposition, mirroring the shim manifest's timing marks:
  // rpc = capture window + the server's own session/serialize/transfer
  // cost (outside this codebase), write = our local finalize tail.
  // first_data splits the server side from the transfer: request → first
  // DATA byte covers the window + the server's session + device-trace
  // collection + serialize, while stream − first_data is the localhost
  // copy of the serialized XSpace to the daemon — overlapped with the
  // disk write by the streaming sink.
  manifest["rpc_ms"] = rpcMs;
  manifest["server_overhead_ms"] = rpcMs - durationMs;
  manifest["rpc_first_data_ms"] = rpcStats.firstDataMs;
  manifest["rpc_stream_ms"] = rpcStats.streamMs;
  manifest["write_ms"] = writeMs;
  manifest["ended_ms"] = nowUnixMillis();
  manifest["status"] = "ok";
  // Atomic (tmp + rename): the manifest's existence IS the completion
  // signal pollers key on (same contract as the shim's manifest,
  // shim.py _finish_trace) — a reader must never see a half-written
  // JSON.
  std::string manifestPath = base + "_push.json";
  {
    std::string tmpPath = manifestPath + ".tmp";
    std::ofstream f(tmpPath);
    f << manifest.dump();
    f.close();
    // durability-ok: capture manifest — same artifact posture as the
    // xplane above (atomicity wanted, crash-durability not).
    if (!f || ::rename(tmpPath.c_str(), manifestPath.c_str()) != 0) {
      ::unlink(tmpPath.c_str()); // don't leak the partial tmp
      report["status"] = "failed";
      report["error"] = "manifest write failed: " + manifestPath;
      return report;
    }
  }

  report["status"] = "ok";
  report["trace_dir"] = base + "_push";
  report["manifest"] = manifestPath;
  report["xspace_bytes"] = static_cast<int64_t>(xspaceBytes);
  report["streamed_write"] = true;
  report["rpc_ms"] = rpcMs;
  report["server_overhead_ms"] = rpcMs - durationMs;
  report["rpc_first_data_ms"] = rpcStats.firstDataMs;
  report["rpc_stream_ms"] = rpcStats.streamMs;
  report["write_ms"] = writeMs;
  return report;
}

} // namespace tracing
} // namespace dynotpu
