// dynolog_tpu daemon entrypoint ("dynologd").
// Behavioral parity: reference dynolog/src/Main.cpp — flag-driven wiring
// (:33-58), per-collector threads each running a collect→log→sleep loop
// (:81-150), RPC server on port 1778 (:163-164), optional IPC monitor thread
// (:169-174). Differences: the GPU (DCGM) leg is replaced by the TPU monitor,
// the metric_frame store is wired in as a queryable history (the reference
// never connected it), shutdown is signal-driven rather than kill-only, and
// every collector loop runs under the fault-containment Supervisor
// (src/daemon/Supervisor.h): a throwing collector or sink degrades that one
// component — recorded in the health registry, observable via `dyno health`
// and the OpenMetrics dynolog_component_up gauges — instead of taking the
// daemon down.
#include <csignal>
#include <sys/stat.h>

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <memory>
#include <mutex>
#include <sstream>
#include <thread>
#include <vector>

#include "src/collectors/KernelCollector.h"
#include "src/collectors/PerfMonitor.h"
#include "src/collectors/SelfStatsCollector.h"
#include "src/common/Defs.h"
#include "src/common/Failpoints.h"
#include "src/common/Flags.h"
#include "src/common/Version.h"
#include "src/core/Health.h"
#include "src/core/Logger.h"
#include "src/core/OpenMetricsServer.h"
#include "src/core/RemoteLoggers.h"
#include "src/core/ResourceGovernor.h"
#include "src/core/StateSnapshot.h"
#include "src/daemon/Supervisor.h"
#include "src/metrics/MetricStore.h"
#include "src/perf/EventParser.h"
#include "src/relay/FleetRelay.h"
#include "src/relay/FleetWatcher.h"
#include "src/rpc/JsonRpcServer.h"
#include "src/rpc/ServiceHandler.h"
#include "src/tracing/CaptureUtils.h"
#include "src/tracing/AutoTrigger.h"
#include "src/tracing/Diagnoser.h"
#include "src/tracing/IPCMonitor.h"
#include "src/tracing/TraceConfigManager.h"
#include "src/tpumon/TpuMonitor.h"

DYN_DEFINE_int32(port, 1778, "Port for listening to RPC requests");
DYN_DEFINE_string(
    rpc_bind,
    "",
    "Interface address for the RPC and OpenMetrics listeners: empty binds "
    "all interfaces (the reference daemon's behavior); set 127.0.0.1 or "
    "::1 to keep the action-taking RPC surface (captures, trigger rules, "
    "trace-file writes) reachable from this host only");
DYN_DEFINE_int32(
    kernel_monitor_reporting_interval_s,
    60,
    "Seconds between kernel (procfs) metric reports");
DYN_DEFINE_int32(
    tpu_monitor_reporting_interval_s,
    10,
    "Seconds between TPU device metric reports (DCGM leg analog)");
DYN_DEFINE_int32(
    perf_monitor_reporting_interval_s,
    60,
    "Seconds between CPU PMU metric reports");
DYN_DEFINE_bool(
    enable_ipc_monitor,
    false,
    "Enable IPC monitor for on-system tracing requests");
DYN_DEFINE_bool(enable_perf_monitor, false, "Enable heartbeat perf monitoring");
DYN_DEFINE_bool(enable_tpu_monitor, false, "Enable TPU device monitoring");
DYN_DEFINE_bool(use_JSON, true, "Emit metrics as JSON lines on stdout");
DYN_DEFINE_string(
    json_log_file,
    "",
    "Also append JSON metric lines to this file");
DYN_DEFINE_bool(
    enable_metric_store,
    true,
    "Keep an in-daemon metric history, queryable via the queryMetrics RPC");
DYN_DEFINE_int32(
    metric_store_capacity,
    14400,
    "Rows of history in the in-daemon store's shared timestamp ring. Every "
    "logger finalize (each kernel tick AND each TPU device row) consumes "
    "one row, so retention = capacity / rows-per-interval");
DYN_DEFINE_string(
    ipc_endpoint_name,
    "dynolog",
    "UNIX socket name for the profiler-client IPC fabric");
DYN_DEFINE_bool(
    use_tcp_relay,
    false,
    "Forward JSON metric lines over TCP to a relay (FBRelay analog)");
DYN_DEFINE_string(relay_host, "localhost", "TCP relay host");
DYN_DEFINE_int32(relay_port, 1777, "TCP relay port");
DYN_DEFINE_string(
    http_logger_url,
    "",
    "POST each metric interval as JSON to this http:// endpoint "
    "(ODS/Scuba-leg analog); empty disables");
DYN_DEFINE_int32(
    auto_trigger_eval_interval_ms,
    2000,
    "Cadence at which trace auto-trigger rules (addTraceTrigger RPC / "
    "`dyno autotrigger`) are evaluated against the metric store. Requires "
    "--enable_metric_store");
DYN_DEFINE_string(
    auto_trigger_rules,
    "",
    "JSON file with an array of auto-trigger rules installed at startup "
    "({metric, op, threshold, for_ticks, cooldown_s, max_fires, job_id, "
    "duration_ms, log_file, process_limit, capture: shim|push, "
    "profiler_host, profiler_port} — the addTraceTrigger RPC schema), so "
    "a supervised daemon restarts with its SLO watches armed");
DYN_DEFINE_int32(
    prometheus_port,
    -1,
    "Serve the metric history's current values in Prometheus/OpenMetrics "
    "text format on this port (GET /metrics; 0 auto-assigns, -1 disables). "
    "Requires --enable_metric_store");
DYN_DEFINE_int32(
    listen_backlog,
    128,
    "listen(2) backlog for the RPC and OpenMetrics listeners. The old "
    "hardcoded 16 was trivially exceeded at cluster fan-out (unitrace "
    "polling N hosts), where excess SYNs see kernel-dependent stalls");
DYN_DEFINE_int32(
    rpc_max_connections,
    128,
    "Concurrent connection cap per listener; above it the oldest idle "
    "connection is evicted to admit the new caller, so fd exhaustion "
    "(or a slowloris herd) can never lock operators out");
DYN_DEFINE_int32(
    rpc_request_timeout_ms,
    5000,
    "Per-connection deadline for a started-but-incomplete request and "
    "for an unread response (the slowloris bound). Unlike the old serial "
    "transport's 5s SO_RCVTIMEO, expiry costs only that connection — "
    "other callers are served concurrently by the event loop");
DYN_DEFINE_int32(
    rpc_idle_timeout_ms,
    60000,
    "How long a persistent (keep-alive) connection may sit idle between "
    "requests before the daemon reaps it");
DYN_DEFINE_int32(
    rpc_worker_threads,
    2,
    "Worker threads executing RPC verb bodies and OpenMetrics exposition "
    "rendering (per listener; clamped >= 1). The epoll thread itself "
    "never runs a verb, so accept/IO stay responsive under heavy "
    "queries and gputrace triggers");
DYN_DEFINE_bool(
    relay,
    false,
    "Run the fleet aggregation relay: terminate the acked TCP relay sink "
    "connections of a fleet of daemons on --relay_listen_port, dedupe "
    "replayed WAL records into an effectively-once sharded fleet view "
    "(per-host liveness, rollups, stragglers), and serve it via the "
    "`fleet` RPC verb / `dyno fleet`. With --state_file the fleet view "
    "rides the control-state snapshot and acks are bounded by persisted "
    "watermarks, so a relay SIGKILL never loses acknowledged records "
    "(docs/RELIABILITY.md). Collectors still run; disable them with "
    "their own flags for a dedicated relay");
DYN_DEFINE_string(
    relay_upstream,
    "",
    "Fleet relay (--relay): HOST:PORT of a PARENT fleet relay. Makes "
    "this relay a tree NODE instead of a terminus: its whole fleet view "
    "is re-exported upstream as merge-able rollup records over the same "
    "durable acked WAL transport it terminates (RelayLogger + SinkWal, "
    "stamped with this relay's own host/boot_epoch/wal_seq identity), so "
    "relays compose into per-pod -> per-region -> global trees and a "
    "mid-tree SIGKILL loses nothing and double-counts nothing "
    "(docs/ARCHITECTURE.md fleet tree; docs/RELIABILITY.md). Empty = "
    "terminus. Give the relay --sink_spill_dir or the upstream leg "
    "degrades to drop-on-outage like any sink");
DYN_DEFINE_int32(
    relay_export_interval_ms,
    2000,
    "Fleet relay: cadence of the --relay_upstream rollup re-export. Keep "
    "well under the parent's --fleet_stale_after_ms — the export stream "
    "is this relay's liveness heartbeat in the parent's view");
DYN_DEFINE_string(
    fleet_advertise_host,
    "",
    "Address other fleet nodes should dial to reach THIS daemon's RPC "
    "port, stamped as rpc_host/rpc_port into every durable sink payload "
    "(with the actual bound port) so a fleet watcher can trigger "
    "captures on it. Empty stamps only rpc_port; the watcher then dials "
    "the --fleet_host_id as a hostname");
DYN_DEFINE_string(
    state_file,
    "",
    "Versioned durable-control-state snapshot file (crash/restart "
    "coherence): auto-trigger rules with their cooldown/fire runtime, "
    "component health / breaker states, and in-flight capture sessions "
    "are periodically persisted here (tmp+fsync+rename) and recovered at "
    "boot. A torn or corrupt snapshot fails closed to defaults, loudly. "
    "Empty disables (legacy amnesiac restarts)");
DYN_DEFINE_int32(
    state_snapshot_interval_s,
    30,
    "Seconds between durable control-state snapshots to --state_file "
    "(plus one final snapshot on clean shutdown); bounds how much "
    "control-state history a SIGKILL can cost");
DYN_DEFINE_int64(
    resource_disk_budget_bytes,
    0,
    "Global disk budget across every governed artifact class (WAL spill, "
    "state snapshots, trace artifacts under --trace_output_root). Over it "
    "the resource governor reclaims lowest-priority classes first (ring "
    "profiles and old trace artifacts before anything durable; snapshots "
    "and the ack-pending WAL frontier are never evicted) and reports "
    "soft/hard pressure through health, the `health` verb's resources "
    "section, and dynolog_resource_* gauges. 0 = no budget (the governor "
    "still observes and publishes)");
DYN_DEFINE_double(
    resource_disk_min_free_pct,
    0.0,
    "Free-space floor (statvfs, percent) on every governed artifact "
    "root: below it pressure goes hard — new capture/diagnose admissions "
    "are refused with a typed RPC error and eviction runs — recovering "
    "automatically when space returns. 0 disables the floor");
DYN_DEFINE_int32(
    resource_check_interval_ms,
    1000,
    "Cadence of the resource governor's supervised self-check tick "
    "(disk usage + statvfs refresh, prioritized eviction, fd/RSS "
    "watermarks, pressure publication)");
DYN_DEFINE_int64(
    resource_max_fds,
    0,
    "File-descriptor watermark for the governor's self-check: soft "
    "pressure at 80%, hard (admission refusal) at 95%. 0 = derive from "
    "the process's own RLIMIT_NOFILE soft limit; set explicitly to "
    "budget below it");
DYN_DEFINE_int64(
    resource_rss_soft_mb,
    0,
    "Resident-set-size soft watermark (MB) for the governor's "
    "self-check: soft pressure at the watermark, hard at 1.5x — the "
    "monitoring daemon must never be the process that tips the host "
    "into OOM. 0 disables");

DYN_DECLARE_string(perf_metrics);
DYN_DECLARE_string(trace_output_root);
DYN_DECLARE_string(sink_spill_dir);

namespace dynotpu {

namespace {

std::atomic<bool> gStop{false};
std::mutex gStopMutex;
std::condition_variable gStopCv;

// The RPC port this daemon actually bound (--port=0 auto-assigns), set in
// main() before any collector loop starts; the durable-payload stamper
// advertises it fleet-wide so a fleet watcher can dial back for captures.
std::atomic<int> gAdvertisedRpcPort{0};

void handleSignal(int) {
  // Async-signal-safe: only the atomic store. Waiters use timed waits, so
  // no notify is needed from the handler (condition_variable::notify is not
  // on the async-signal-safe list and its wakeup could be lost anyway).
  gStop.store(true);
}

} // namespace

// One logger per collector thread, fanned out to the enabled sinks
// (reference rebuilds its CompositeLogger every tick, Main.cpp:60-75; here
// each collector loop builds one once per collector incarnation, so the
// relay sink can hold a persistent connection). Remote sinks share the
// registry's per-sink health components ("relay_sink"/"http_sink") across
// loops: the breaker state and drop counts aggregate there, and a
// contained exception from ANY sink is recorded under "logger_sinks".
static std::shared_ptr<Logger> makeLogger(
    std::shared_ptr<MetricStore> store,
    std::shared_ptr<HealthRegistry> health) {
  std::vector<std::shared_ptr<Logger>> sinks;
  if (FLAGS_use_JSON || !FLAGS_json_log_file.empty()) {
    sinks.push_back(
        std::make_shared<JsonLogger>(FLAGS_json_log_file, FLAGS_use_JSON));
  }
  if (FLAGS_use_tcp_relay) {
    auto relaySink = std::make_shared<RelayLogger>(
        FLAGS_relay_host, FLAGS_relay_port,
        health->component("relay_sink"));
    // Fleet health rollup: the durable payload carries this host's
    // degraded-component count, so the aggregation relay can answer
    // "which hosts are sick" without a second channel or polling. The
    // rpc_host/rpc_port advertisement rides the same stamp: the fleet
    // watcher dials these back to trigger a capture on this daemon.
    relaySink->setPayloadStamper([health](json::Value& batch) {
      batch["health_degraded"] =
          static_cast<int64_t>(health->snapshot().at("degraded").size());
      if (int port = gAdvertisedRpcPort.load(); port > 0) {
        batch["rpc_port"] = static_cast<int64_t>(port);
      }
      if (!FLAGS_fleet_advertise_host.empty()) {
        batch["rpc_host"] = FLAGS_fleet_advertise_host;
      }
    });
    sinks.push_back(std::move(relaySink));
  }
  if (!FLAGS_http_logger_url.empty()) {
    sinks.push_back(std::make_shared<HttpLogger>(
        FLAGS_http_logger_url, health->component("http_sink")));
  }
  if (store) {
    sinks.push_back(std::make_shared<MetricStoreLogger>(store));
  }
  auto sinkErrors = health->component("logger_sinks");
  return std::make_shared<CompositeLogger>(
      std::move(sinks),
      [sinkErrors](const std::string& error) { sinkErrors->addDrop(error); });
}

// Supervised collector loops: the Supervisor owns restart/backoff/breaker
// policy; each factory builds one incarnation of the collector state and
// returns its tick. The collector.*.step failpoints let tests and fault
// drills inject the throw/delay scenarios the supervision exists for.

static void superviseKernelMonitor(
    Supervisor& supervisor,
    std::shared_ptr<HealthRegistry> health,
    std::shared_ptr<MetricStore> store) {
  DLOG_INFO << "Running kernel monitor loop, interval = "
            << FLAGS_kernel_monitor_reporting_interval_s << "s";
  supervisor.run(
      "kernel_monitor",
      [] { return int64_t(FLAGS_kernel_monitor_reporting_interval_s) * 1000; },
      [&health, &store]() -> Supervisor::Ticker {
        auto collector = std::make_shared<KernelCollector>();
        // The daemon's own footprint rides the kernel tick (same logger
        // row): the <1% overhead budget stays observable in production,
        // not just in benchmark runs.
        auto selfStats = std::make_shared<SelfStatsCollector>();
        auto logger = makeLogger(store, health);
        return [collector, selfStats, logger] {
          failpoints::maybeFail("collector.kernel.step");
          collector->step();
          collector->log(*logger);
          selfStats->step();
          selfStats->log(*logger);
          logger->finalize();
        };
      });
}

static void supervisePerfMonitor(
    Supervisor& supervisor,
    std::shared_ptr<HealthRegistry> health,
    std::shared_ptr<MetricStore> store) {
  supervisor.run(
      "perf_monitor",
      [] { return int64_t(FLAGS_perf_monitor_reporting_interval_s) * 1000; },
      [&health, &store]() -> Supervisor::Ticker {
        // Slash-aware split: commas inside pmu/term=v,term=v/ bodies stay
        // put.
        auto perfmon = std::shared_ptr<PerfMonitor>(
            PerfMonitor::factory(perf::splitEventList(FLAGS_perf_metrics)));
        if (!perfmon) {
          DLOG_ERROR << "Perf monitor unavailable; perf monitoring disabled";
          health->component("perf_monitor")
              ->disable("perf monitor unavailable (no PMU access?)");
          return nullptr;
        }
        DLOG_INFO << "Running perf monitor loop, interval = "
                  << FLAGS_perf_monitor_reporting_interval_s << "s";
        auto logger = makeLogger(store, health);
        return [perfmon, logger] {
          failpoints::maybeFail("collector.perf.step");
          perfmon->step();
          perfmon->log(*logger);
          logger->finalize();
        };
      });
}

static void superviseTpuMonitor(
    Supervisor& supervisor,
    std::shared_ptr<HealthRegistry> health,
    std::shared_ptr<MetricStore> store) {
  supervisor.run(
      "tpu_monitor",
      [] { return int64_t(FLAGS_tpu_monitor_reporting_interval_s) * 1000; },
      [&health, &store]() -> Supervisor::Ticker {
        auto tpumon =
            std::shared_ptr<tpumon::TpuMonitor>(tpumon::TpuMonitor::factory());
        if (!tpumon) {
          DLOG_ERROR << "TPU monitor unavailable; tpu monitoring disabled";
          health->component("tpu_monitor")
              ->disable("no usable TPU metric backend");
          return nullptr;
        }
        DLOG_INFO << "Running TPU monitor loop, interval = "
                  << FLAGS_tpu_monitor_reporting_interval_s << "s";
        auto logger = makeLogger(store, health);
        return [tpumon, logger] {
          failpoints::maybeFail("collector.tpu.step");
          tpumon->update();
          tpumon->log(*logger); // per-device rows, each finalized inside
          // Tick-level summary row + flush — the finalize this loop
          // historically never issued: a zero-device tick now still
          // reaches every sink (relay/HTTP/store), so a dead libtpu read
          // shows up as a flushed row with the error counter instead of
          // silence.
          logger->logInt(
              "tpu_devices",
              static_cast<int64_t>(tpumon->latestSamples().size()));
          logger->logInt("tpu_sample_errors_total", tpumon->sampleErrors());
          logger->setTimestamp();
          logger->finalize();
        };
      });
}

} // namespace dynotpu

int main(int argc, char** argv) {
  using namespace dynotpu;
  FlagRegistry::instance().parse(argc, argv);
  DLOG_INFO << "Starting dynologd " << kVersion;

  std::signal(SIGINT, handleSignal);
  std::signal(SIGTERM, handleSignal);
  // Network peers disconnecting mid-write must surface as EPIPE on the
  // socket, never as a process-killing signal.
  std::signal(SIGPIPE, SIG_IGN);

  auto health = std::make_shared<HealthRegistry>();
  Supervisor supervisor(
      health, Supervisor::fromFlags(), [] { return gStop.load(); });

  // Resource governance (docs/RELIABILITY.md resource-pressure matrix):
  // every on-disk artifact class registers with a priority and a reclaim
  // policy; the supervised governor tick below enforces the global
  // budget + free-space floor with prioritized eviction, self-checks
  // fd/RSS watermarks, and publishes ok/soft/hard pressure. Never-evict
  // classes (WAL spill, state snapshots) keep the PR 9/10 durability
  // invariants under pressure: the ack-pending frontier is never the
  // thing reclaimed.
  {
    auto& governor = ResourceGovernor::instance();
    ResourceGovernor::Options governorOpts;
    governorOpts.diskBudgetBytes = FLAGS_resource_disk_budget_bytes;
    governorOpts.diskMinFreePct = FLAGS_resource_disk_min_free_pct;
    governorOpts.maxFds = FLAGS_resource_max_fds;
    governorOpts.rssSoftMb = FLAGS_resource_rss_soft_mb;
    governor.configure(governorOpts);
    governor.setHealth(health->component("resources"));
    if (!::FLAGS_sink_spill_dir.empty()) {
      const std::string root = ::FLAGS_sink_spill_dir;
      governor.registerClass(
          "wal_spill", /*priority=*/100, /*neverEvict=*/true, root,
          [root] { return dirUsage(root); });
    }
    if (!FLAGS_state_file.empty()) {
      const std::string path = FLAGS_state_file;
      size_t slash = path.rfind('/');
      const std::string root =
          slash == std::string::npos ? std::string(".") : path.substr(0, slash);
      governor.registerClass(
          "state_snapshot", /*priority=*/90, /*neverEvict=*/true, root,
          [path]() -> std::pair<int64_t, int64_t> {
            struct stat st{};
            if (::stat(path.c_str(), &st) != 0) {
              return {0, 0};
            }
            return {static_cast<int64_t>(st.st_size), 1};
          });
    }
    if (!::FLAGS_trace_output_root.empty()) {
      // The reclaimable class: capture artifacts, push dirs, diagnosis
      // reports — everything the capture plane writes under the scoped
      // root. Oldest families go first; the 120s grace keeps a family
      // mid-write (shim still serializing) out of the reclaimer's reach.
      const std::string root = ::FLAGS_trace_output_root;
      governor.registerClass(
          "trace_artifacts", /*priority=*/10, /*neverEvict=*/false, root,
          [root] { return dirUsage(root); },
          [root](int64_t target) {
            return reclaimOldestFiles(root, target, /*graceSeconds=*/120);
          });
    }
  }

  std::shared_ptr<MetricStore> store;
  if (FLAGS_enable_metric_store) {
    store = std::make_shared<MetricStore>(
        int64_t(FLAGS_kernel_monitor_reporting_interval_s) * 1000,
        static_cast<size_t>(FLAGS_metric_store_capacity));
  }

  auto configManager = TraceConfigManager::getInstance();
  // Trace-diff diagnosis engine runner: the `diagnose` RPC verb and
  // diagnose=true auto-trigger rules hand fired captures here; its
  // engine child flushes diagnose.* spans back over this daemon's IPC
  // endpoint so selftrace joins the whole closed loop under one id.
  auto diagnoser = std::make_shared<tracing::Diagnoser>(
      tracing::Diagnoser::Options::fromFlags(FLAGS_ipc_endpoint_name),
      store);
  std::shared_ptr<tracing::AutoTriggerEngine> autoTrigger;
  if (store) {
    autoTrigger = std::make_shared<tracing::AutoTriggerEngine>(
        store, configManager, FLAGS_auto_trigger_eval_interval_ms);
    autoTrigger->setDiagnoser(diagnoser);
  } else if (!FLAGS_auto_trigger_rules.empty()) {
    DLOG_ERROR << "--auto_trigger_rules needs --enable_metric_store; ignored";
  }

  // Fleet aggregation relay (--relay): bound here, synchronously, so the
  // picked port (--relay_listen_port=0) is announced before any sender
  // could race it; the ingest loop itself runs supervised below.
  std::shared_ptr<relay::FleetRelay> fleetRelay;
  if (FLAGS_relay) {
    fleetRelay = std::make_shared<relay::FleetRelay>(
        relay::FleetRelay::Options::fromFlags());
    try {
      fleetRelay->ensureListening();
    } catch (const std::exception& e) {
      DLOG_ERROR << "fleet relay: " << e.what() << " (exiting)";
      return 1;
    }
    std::cout << "DYNOLOG_RELAY_PORT=" << fleetRelay->port() << std::endl;
  }

  // Crash/restart coherence (--state_file): recover the previous
  // incarnation's durable control state BEFORE anything starts ticking,
  // then snapshot periodically. Recovery fails closed: any load error
  // (missing file is fine on first boot; torn/corrupt/cross-version is
  // not) boots with defaults and says so loudly — here and in the
  // health verb's durability.snapshot section.
  StateSnapshotter::Options snapOpts;
  snapOpts.path = FLAGS_state_file;
  snapOpts.intervalS = FLAGS_state_snapshot_interval_s;
  auto snapshotter = std::make_shared<StateSnapshotter>(snapOpts);
  bool stateRecovered = false;
  int restoredRules = 0;
  if (snapshotter->enabled()) {
    struct stat st{};
    if (::stat(FLAGS_state_file.c_str(), &st) != 0) {
      DLOG_INFO << "state snapshot: no " << FLAGS_state_file
                << " yet (first boot); starting from defaults";
      snapshotter->noteRecovery(false, "");
    } else {
      std::string error;
      auto sections = StateSnapshotter::load(FLAGS_state_file, &error);
      if (!error.empty()) {
        DLOG_ERROR << "STATE SNAPSHOT RECOVERY FAILED (booting with "
                   << "defaults): " << error;
        snapshotter->noteRecovery(false, error);
      } else {
        int rules = autoTrigger
            ? autoTrigger->restoreFromSnapshot(sections.at("autotrigger"))
            : 0;
        restoredRules = rules;
        int comps = health->restore(sections.at("health"));
        // Fleet view (relay mode): watermarks + epochs + rollups rewind
        // to the snapshot's consistent point; re-delivered records
        // re-apply exactly once relative to it. Absent section (pre-
        // relay snapshot, or relay newly enabled) restores nothing.
        int fleetHosts = fleetRelay
            ? fleetRelay->restoreFromSnapshot(sections.at("fleet"))
            : 0;
        if (fleetHosts > 0) {
          DLOG_INFO << "state snapshot: fleet view restored for "
                    << fleetHosts << " host(s)";
        }
        const auto& sessions = sections.at("sessions");
        for (const auto& s : sessions.items()) {
          // Sessions that straddled the crash: the shim side finishes
          // locally and its manifest is adopted by the restored rules'
          // fired-family scan; this log line is the daemon-side record.
          DLOG_INFO << "state snapshot: job " << s.at("job_id").asInt()
                    << " had " << s.at("pending_pids").size()
                    << " pending config(s) and "
                    << s.at("processes").asInt()
                    << " registered process(es) at the time of the "
                    << "previous shutdown/crash";
        }
        DLOG_INFO << "state snapshot: recovered " << rules << " rule(s), "
                  << comps << " health component(s), "
                  << sessions.size() << " session record(s) from "
                  << FLAGS_state_file;
        snapshotter->noteRecovery(true, "");
        stateRecovered = true;
        // Forward tolerance: sections this binary has no restorer for
        // (written by a newer version) ride along into every snapshot
        // this incarnation writes, so an upgrade-then-downgrade round
        // trip loses nothing (docs/COMPATIBILITY.md).
        snapshotter->adoptForeignSections(sections);
      }
    }
    snapshotter->addProvider("autotrigger", [autoTrigger]() {
      return autoTrigger ? autoTrigger->snapshotState()
                         : json::Value::array();
    });
    snapshotter->addProvider("health", [health]() {
      return health->snapshot().at("components");
    });
    snapshotter->addProvider("sessions", [configManager]() {
      return configManager->snapshotSessions();
    });
    if (fleetRelay) {
      // Durable-ack discipline: each snapshot collect STAGES the fleet
      // watermarks; the post-write commit promotes them to the ack
      // ceiling. An ACK the relay sends thus never exceeds what a
      // persisted snapshot holds — a relay SIGKILL can rewind the fleet
      // view only to a point senders were never acked past.
      snapshotter->addProvider("fleet", [fleetRelay]() {
        return fleetRelay->snapshotState();
      });
      snapshotter->addOnCommit([fleetRelay]() {
        fleetRelay->commitDurable();
      });
      fleetRelay->setDurableAcks(true);
    }
    snapshotter->start();
  }
  if (autoTrigger && !FLAGS_auto_trigger_rules.empty()) {
    if (stateRecovered && restoredRules > 0) {
      // The snapshot's rule set (which includes the file's rules as of
      // the last snapshot, plus every runtime add/remove since) is
      // authoritative: re-loading the file here would duplicate rules
      // on every restart and resurrect deliberately-removed ones. A
      // snapshot that restored ZERO rules (e.g. written by a previous
      // incarnation that ran without --enable_metric_store) carries no
      // such authority, so the file still loads.
      DLOG_INFO << "--auto_trigger_rules skipped: rules restored from "
                << FLAGS_state_file;
    } else {
      tracing::loadRulesFile(*autoTrigger, FLAGS_auto_trigger_rules);
    }
  }
  if (autoTrigger) {
    autoTrigger->start();
  }
  auto handler = std::make_shared<ServiceHandler>(
      configManager, store, autoTrigger, health, diagnoser, snapshotter,
      fleetRelay);

  EventLoopServer::Tuning rpcTuning;
  rpcTuning.backlog = FLAGS_listen_backlog;
  rpcTuning.maxConnections =
      static_cast<size_t>(std::max(FLAGS_rpc_max_connections, 1));
  rpcTuning.requestTimeoutMs = FLAGS_rpc_request_timeout_ms;
  rpcTuning.idleTimeoutMs = FLAGS_rpc_idle_timeout_ms;
  rpcTuning.workerThreads = FLAGS_rpc_worker_threads;

  JsonRpcServer server(
      FLAGS_port,
      [handler](const std::string& request) {
        // Streaming-capable dispatch: a verb may name an artifact file
        // (fetchTrace) that the transport then streams to the caller as
        // CHUNK/END frames after the response body.
        RpcReply reply;
        std::string streamFile;
        reply.body = handler->processRequest(request, &streamFile);
        reply.streamFile = std::move(streamFile);
        return reply;
      },
      FLAGS_rpc_bind,
      rpcTuning);
  // With --port=0 announce the picked port so tests/scripts can find it.
  std::cout << "DYNOLOG_PORT=" << server.getPort() << std::endl;
  gAdvertisedRpcPort.store(server.getPort());
  server.run();

  std::unique_ptr<OpenMetricsServer> promServer;
  if (FLAGS_prometheus_port >= 0) {
    if (store) {
      promServer = std::make_unique<OpenMetricsServer>(
          FLAGS_prometheus_port, store, FLAGS_rpc_bind, rpcTuning, health);
      std::cout << "DYNOLOG_PROMETHEUS_PORT=" << promServer->getPort()
                << std::endl;
      promServer->run();
    } else {
      DLOG_ERROR << "--prometheus_port needs --enable_metric_store; disabled";
    }
  }

  std::vector<std::thread> threads;
  // Current IPC monitor incarnation: rebuilt by the supervisor after a
  // contained failure (so corrupted monitor/fabric state never leaks
  // into the next slice), and stoppable from the shutdown path below.
  std::mutex ipcMonitorMutex;
  std::shared_ptr<tracing::IPCMonitor> ipcMonitor; // guarded by the mutex
  if (FLAGS_enable_ipc_monitor) {
    threads.emplace_back([&supervisor, &health, &ipcMonitorMutex,
                          &ipcMonitor, &configManager, &store] {
      supervisor.run(
          "ipc_monitor",
          [] { return int64_t(0); }, // slices back to back; no idle gap
          [&]() -> Supervisor::Ticker {
            {
              // Release the previous incarnation FIRST: the abstract
              // socket must be unbound before the rebuild can bind it.
              std::lock_guard<std::mutex> lock(ipcMonitorMutex);
              ipcMonitor.reset();
            }
            auto monitor = std::make_shared<tracing::IPCMonitor>(
                configManager, FLAGS_ipc_endpoint_name, store);
            if (!monitor->active()) {
              health->component("ipc_monitor")
                  ->disable("IPC endpoint unavailable");
              return nullptr;
            }
            {
              std::lock_guard<std::mutex> lock(ipcMonitorMutex);
              ipcMonitor = monitor;
            }
            return [monitor] {
              failpoints::maybeFail("collector.ipc.poll");
              // ~1s slices: one health heartbeat per slice, exceptions
              // contained per slice; inside, the thread blocks in
              // poll(2) and wakes on the message or the posted config.
              monitor->runSlice(1000);
            };
          });
    });
  }
  if (fleetRelay) {
    // Supervised ingest loop: a throwing slice (bad bind after a port
    // steal, allocation failure) degrades the "fleet_relay" component
    // and retries with backoff — the SAME FleetRelay object re-ticks, so
    // a contained failure never wipes the fleet view.
    threads.emplace_back([&supervisor, fleetRelay] {
      supervisor.run(
          "fleet_relay",
          [] { return int64_t(0); }, // slices back to back; no idle gap
          [fleetRelay]() -> Supervisor::Ticker {
            return [fleetRelay] {
              failpoints::maybeFail("relay.ingest.slice");
              fleetRelay->runSlice(1000);
            };
          });
    });
  }
  if (fleetRelay && !FLAGS_relay_upstream.empty()) {
    // Hierarchical tier: re-export this relay's fleet view to the
    // parent relay as merge-able rollup records over the SAME durable
    // acked transport the senders use — a relay is just a sender with a
    // bigger payload. The RelayLogger reuses the whole durable stack
    // (SinkWal spill, anti-entropy hello, ack-gated trim), so a parent
    // outage parks rollups on disk and a mid-tree crash re-exports from
    // recovered state with the identity the parent dedupes on.
    const std::string upstream = FLAGS_relay_upstream;
    std::string upstreamHost = upstream;
    int upstreamPort = FLAGS_relay_port;
    if (size_t colon = upstream.rfind(':'); colon != std::string::npos) {
      upstreamHost = upstream.substr(0, colon);
      try {
        upstreamPort = std::stoi(upstream.substr(colon + 1));
      } catch (const std::exception&) {
        DLOG_ERROR << "--relay_upstream: bad port in '" << upstream
                   << "'; upstream export disabled";
        upstreamHost.clear();
      }
    }
    if (!upstreamHost.empty()) {
      threads.emplace_back([&supervisor, &health, fleetRelay,
                            upstreamHost, upstreamPort] {
        supervisor.run(
            "relay_upstream",
            [] {
              return int64_t(std::max(FLAGS_relay_export_interval_ms, 100));
            },
            [&health, fleetRelay, upstreamHost,
             upstreamPort]() -> Supervisor::Ticker {
              auto logger = std::make_shared<RelayLogger>(
                  upstreamHost, upstreamPort,
                  health->component("relay_upstream"));
              logger->setPayloadStamper([](json::Value& batch) {
                if (int port = gAdvertisedRpcPort.load(); port > 0) {
                  batch["rpc_port"] = static_cast<int64_t>(port);
                }
                if (!FLAGS_fleet_advertise_host.empty()) {
                  batch["rpc_host"] = FLAGS_fleet_advertise_host;
                }
              });
              return [fleetRelay, logger] {
                // exportRollup fires relay.upstream.export: error mode
                // skips the round (counted), throw is contained here by
                // the supervisor.
                auto doc = fleetRelay->exportRollup();
                if (!doc.isObject()) {
                  return;
                }
                logger->logDocument(doc);
                logger->setTimestamp();
                logger->finalize();
              };
            });
      });
    }
  }
  std::shared_ptr<relay::FleetWatcher> fleetWatcher;
  if (fleetRelay) {
    auto watchOpts = relay::FleetWatcher::Options::fromFlags();
    if (watchOpts.enabled()) {
      // Fleet-driven automated diagnosis: fleet telemetry picks which
      // host to profile and what healthy peer to compare it against,
      // then hands the pair to the diagnosis engine — no human in the
      // loop (docs/DIAGNOSIS.md, docs/ARCHITECTURE.md fleet tree).
      const int64_t durationMs = watchOpts.durationMs;
      const int64_t jobId = watchOpts.jobId;
      const int64_t waitMs = watchOpts.captureWaitMs;
      auto trigger = [durationMs, jobId](
                         const std::string& fleetHost,
                         const std::string& rpcHost,
                         int64_t rpcPort,
                         const std::string& tracePath,
                         const TraceContext& ctx) -> std::string {
        if (rpcPort <= 0) {
          DLOG_WARNING << "fleet watcher: " << fleetHost
                       << " advertised no rpc_port; cannot capture";
          return "";
        }
        std::ostringstream cfg;
        cfg << "PROFILE_START_TIME=0\n"
            << "ACTIVITIES_LOG_FILE=" << tracePath << "\n"
            << "ACTIVITIES_DURATION_MSECS=" << durationMs;
        auto req = json::Value::object();
        req["fn"] = "setKinetOnDemandRequest";
        req["config"] = withTraceContext(cfg.str(), ctx);
        req["job_id"] = jobId;
        req["process_limit"] = 1;
        req["pids"] = json::Value::array();
        req["trace_ctx"] = ctx.header();
        JsonRpcClient client(
            rpcHost.empty() ? fleetHost : rpcHost,
            static_cast<int>(rpcPort));
        std::string responseText;
        if (!client.call(req.dump(), &responseText)) {
          return "";
        }
        auto response = json::Value::parse(responseText);
        const auto& triggered =
            response.at("activityProfilersTriggered");
        if (!triggered.isArray() || triggered.size() == 0) {
          return "";
        }
        return tracing::withTracePathSuffix(
            tracePath,
            "_" + std::to_string(triggered.items()[0].asInt()));
      };
      auto diagnoseHook = [diagnoser, waitMs](
                              const std::string& target,
                              const std::string& baseline,
                              const TraceContext& ctx) {
        // The Diagnoser's single-flight worker waits (bounded) for the
        // outlier manifest, then runs the engine with the peer capture
        // as baseline; the report lands in the registry under ctx's
        // trace-id (`dyno diagnose --trace_id=`).
        diagnoser->diagnoseCapture(0, target, baseline, ctx, waitMs);
      };
      fleetWatcher = std::make_shared<relay::FleetWatcher>(
          fleetRelay, watchOpts, std::move(trigger),
          std::move(diagnoseHook));
      threads.emplace_back([&supervisor, fleetWatcher, watchOpts] {
        supervisor.run(
            "fleet_watch",
            [watchOpts] { return watchOpts.evalIntervalMs; },
            [fleetWatcher]() -> Supervisor::Ticker {
              return [fleetWatcher] {
                fleetWatcher->tick();
              };
            });
      });
    }
  }
  // Resource-governor self-check loop: supervised like every collector
  // (a throwing usage probe degrades "resource_governor", not the
  // daemon). The PRESSURE state lives in the separate "resources"
  // component the governor publishes to — the loop's own heartbeat must
  // not mask a parked pressure state with its tickOk.
  threads.emplace_back([&supervisor] {
    supervisor.run(
        "resource_governor",
        [] {
          return int64_t(std::max(FLAGS_resource_check_interval_ms, 100));
        },
        []() -> Supervisor::Ticker {
          return [] {
            failpoints::maybeFail("resource.governor.tick");
            ResourceGovernor::instance().tick();
          };
        });
  });
  if (FLAGS_enable_tpu_monitor) {
    threads.emplace_back([&supervisor, &health, &store] {
      superviseTpuMonitor(supervisor, health, store);
    });
  }
  if (FLAGS_enable_perf_monitor) {
    threads.emplace_back([&supervisor, &health, &store] {
      supervisePerfMonitor(supervisor, health, store);
    });
  }
  threads.emplace_back([&supervisor, &health, &store] {
    superviseKernelMonitor(supervisor, health, store);
  });

  {
    std::unique_lock<std::mutex> lock(gStopMutex);
    while (!gStop.load()) {
      gStopCv.wait_for(lock, std::chrono::milliseconds(200), [] {
        return gStop.load();
      });
    }
  }
  DLOG_INFO << "Shutting down dynologd";
  // Wake every supervised loop out of tick sleeps, backoffs and parks so
  // the joins below complete within the grace period.
  supervisor.requestStop();
  if (fleetRelay) {
    fleetRelay->stop(); // cut an in-flight ingest slice short
  }
  // Final state snapshot BEFORE the stateful subsystems tear down, so a
  // clean shutdown hands the next incarnation its freshest state.
  snapshotter->stop();
  if (autoTrigger) {
    autoTrigger->stop();
  }
  // After the trigger engine (no new fires): join any in-flight
  // diagnosis worker so no engine child outlives main().
  diagnoser->stop();
  {
    std::lock_guard<std::mutex> lock(ipcMonitorMutex);
    if (ipcMonitor) {
      ipcMonitor->stop(); // wakes the thread out of its poll(2)
    }
  }
  server.stop();
  // After the dispatcher quiesces: cancel + join any in-flight
  // cputrace/perfsample/pushtrace worker so no capture thread outlives
  // main() into static teardown (drain loops honor the cancel token
  // within ~50ms; the push RPC has its own bounded deadline).
  handler->stopCaptures();
  if (promServer) {
    promServer->stop();
  }
  for (auto& t : threads) {
    t.join();
  }
  return 0;
}
