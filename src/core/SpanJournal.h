// dynolog_tpu: control-plane self-tracing — trace context + span journal.
//
// Beyond-reference capability: the reference daemon observes other
// programs but cannot observe itself; a gputrace request crosses
// CLI → RPC verb → IPCMonitor → client shim → capture → convert → sink
// with no shared identity, so the latency each stage adds is invisible.
// ARGUS-style production diagnosis (PAPERS.md) hinges on exactly this
// cross-component request tracing. This header gives the daemon:
//
//  - TraceContext: a 64-bit trace-id + span-id pair. Minted by `dyno`
//    and unitrace, carried as the optional `trace_ctx` field of the
//    framed JSON wire ("%016x/%016x" hex — absent field ⇒ the daemon
//    mints one, so old clients stay wire-compatible), propagated into
//    the on-demand config string as TRACE_CONTEXT=... and picked up by
//    the Python shim, so ONE id names the whole request across both
//    languages.
//  - SpanJournal: a fixed-size lock-free ring of completed spans,
//    written from event-loop workers (RPC verbs), collector ticks (the
//    Supervisor), sink pushes (RemoteLoggers) and the IPC monitor
//    (config hand-offs + spans flushed by Python clients over the
//    "span" datagram). Writers claim a slot with one fetch_add and
//    publish it with a per-slot seqlock — a reader (the `selftrace`
//    verb) never blocks a writer and simply skips slots caught
//    mid-write.
//  - SpanScope: RAII helper that times a section and records it.
//
// The Python mirror lives in dynolog_tpu/obs.py (same context format,
// same span fields); `dyno selftrace` merges both halves into one
// Chrome-trace JSON of the daemon itself. See docs/OBSERVABILITY.md.
#pragma once

#include <atomic>
#include <cstdint>
#include <cstring>
#include <optional>
#include <string>
#include <vector>

namespace dynotpu {

// One request's identity on the wire: trace-id names the whole request,
// span-id names the sender's span (the parent of whatever the receiver
// does with it).
struct TraceContext {
  uint64_t traceId = 0;
  uint64_t spanId = 0;

  bool valid() const {
    return traceId != 0;
  }

  // "%016x/%016x" — the `trace_ctx` JSON field and the TRACE_CONTEXT
  // config value share this one spelling (obs.py parses/emits the same).
  std::string header() const;

  // Fresh nonzero trace-id + span-id.
  static TraceContext mint();
  // Parse a header; nullopt on anything malformed (never throws — the
  // field arrives from the network).
  static std::optional<TraceContext> parse(const std::string& text);
};

// Random nonzero 64-bit id (thread-local generator, no locks).
uint64_t mintId();

// One completed span. POD-sized fields only: the journal ring copies
// these in and out under a seqlock, so no member may allocate.
struct Span {
  static constexpr size_t kNameBytes = 48;
  uint64_t traceId = 0;
  uint64_t spanId = 0;
  uint64_t parentId = 0;
  int64_t startUs = 0; // unix micros
  int64_t durUs = 0;
  int32_t pid = 0;
  int32_t tid = 0;
  char name[kNameBytes] = {}; // NUL-terminated (truncated if longer)
};

// Fixed-size lock-free ring of completed spans. Writers are wait-free
// (one fetch_add + a seqlock publish); readers snapshot without ever
// stalling a writer. Oldest entries are overwritten — self-tracing is a
// flight recorder, not an archive. Thread-safe for any number of
// concurrent writers and readers.
class SpanJournal {
 public:
  // capacity 0 disables recording entirely (--selftrace_capacity=0).
  explicit SpanJournal(size_t capacity = kDefaultCapacity);

  // Process-wide journal; capacity from --selftrace_capacity at first
  // use. Producers (verb handlers, Supervisor, sinks) all write here.
  static SpanJournal& instance();

  void record(const Span& span);
  // Convenience: stamps pid/tid and truncates the name.
  void record(
      const std::string& name,
      uint64_t traceId,
      uint64_t spanId,
      uint64_t parentId,
      int64_t startUs,
      int64_t durUs);

  // Consistent copies of every published slot, oldest first. Slots
  // caught mid-write (seqlock moved) are skipped, never torn.
  std::vector<Span> snapshot() const;

  // Spans recorded over this journal's lifetime (monotonic; the ring
  // holds min(recorded, capacity) of them).
  uint64_t recorded() const {
    return next_.load(std::memory_order_relaxed);
  }

  size_t capacity() const {
    return slots_.size();
  }

  static constexpr size_t kDefaultCapacity = 4096;

 private:
  struct Slot {
    // Even = published generation; odd = write in progress. 0 = empty.
    std::atomic<uint64_t> seq{0};
    Span span; // published via seq (seqlock); no lock to annotate
  };

  std::vector<Slot> slots_;
  std::atomic<uint64_t> next_{0};
};

// Times a section and records it on destruction. Mints its own span-id
// (exposed so callees can be parented under it — e.g. the RPC verb span
// becomes the parent the TRACE_CONTEXT config key carries to the shim).
class SpanScope {
 public:
  SpanScope(
      std::string name,
      uint64_t traceId,
      uint64_t parentId,
      SpanJournal* journal = nullptr);
  ~SpanScope();

  SpanScope(const SpanScope&) = delete;
  SpanScope& operator=(const SpanScope&) = delete;

  uint64_t spanId() const {
    return spanId_;
  }
  uint64_t traceId() const {
    return traceId_;
  }
  // Trace context naming THIS span as the parent of downstream work.
  TraceContext childContext() const {
    return TraceContext{traceId_, spanId_};
  }

 private:
  std::string name_;
  uint64_t traceId_;
  uint64_t parentId_;
  uint64_t spanId_;
  int64_t startUs_;
  SpanJournal* journal_;
};

// The on-demand config key carrying the context into the Python shim
// (TraceConfig.parse in dynolog_tpu/client/shim.py reads it).
constexpr char kTraceContextConfigKey[] = "TRACE_CONTEXT";

// Appends TRACE_CONTEXT=<header> to a key=value config string unless the
// caller already set one (a unitrace-built config wins over the daemon's
// injection).
std::string withTraceContext(std::string config, const TraceContext& ctx);

// The TRACE_CONTEXT value inside a key=value config string, if any.
std::optional<TraceContext> traceContextFromConfig(const std::string& config);

} // namespace dynotpu
