"""Control-plane self-tracing — the Python half.

Pure-Python mirror of the daemon's self-observation layer
(src/core/SpanJournal.{h,cpp} + src/core/Histograms.{h,cpp}):

- ``TraceContext``: the 64-bit trace-id/span-id pair. One id names a
  whole control-plane request across both languages: minted by `dyno` /
  unitrace, carried as the optional ``trace_ctx`` field of the framed
  JSON wire, injected into the on-demand config as ``TRACE_CONTEXT=...``
  by the daemon's RPC verb, parsed back out here by the shim. The header
  spelling ("%016x/%016x") is pinned by both sides' tests.
- ``SpanJournal`` / ``span()``: a bounded ring of completed spans plus a
  context-manager that times a section and records it. The shim, the
  trace converter and the cluster RPC client all record here; the shim
  (and the converter's export child, via ``maybe_flush_env``) flush the
  ring back to the daemon over the fire-and-forget ``"span"`` IPC
  datagram, so ``dyno selftrace`` shows one merged Chrome trace of the
  daemon AND its clients.
- ``HistogramFamily``: the fixed-bucket latency histogram with the same
  bounds and `_bucket`/`_sum`/`_count` OpenMetrics rendering as the C++
  registry — the schema pin scripts/obs_smoke.py and tests validate
  without a C++ toolchain (same posture as supervise.py for the health
  schema).

Kept dependency-free (stdlib only; the IPC client is imported lazily at
flush time) and injectable (``now``), so tests drive time synthetically.
See docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import random
import threading
import time
from dataclasses import dataclass, field

# The on-demand config key carrying the context daemon -> shim
# (src/core/SpanJournal.h kTraceContextConfigKey).
CONFIG_KEY = "TRACE_CONTEXT"
# Env vars handing a context + flush target to subprocesses (the shim's
# trace-convert export child).
ENV_TRACE_CTX = "DYNO_TRACE_CTX"
ENV_FLUSH_ENDPOINT = "DYNO_OBS_ENDPOINT"

# Mirror of src/core/Histograms.cpp LatencyHistogram::bounds() — change
# both or dashboards break. 500µs..10s, ~1-2.5-5 per decade.
DEFAULT_BOUNDS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)

# Wire limit for span names (src/tracing/IPCMonitor.h ClientSpan.name,
# NUL terminator included).
NAME_BYTES = 48


def mint_id() -> int:
    """Fresh nonzero 64-bit id (the C++ side uses the same range)."""
    while True:
        v = random.getrandbits(64)
        if v:
            return v


@dataclass(frozen=True)
class TraceContext:
    """One request's identity: trace_id names the request, span_id the
    sender's span (the parent of whatever the receiver does with it)."""

    trace_id: int
    span_id: int

    def header(self) -> str:
        return f"{self.trace_id:016x}/{self.span_id:016x}"

    def child(self) -> "TraceContext":
        """Same trace, fresh span-id — what a caller hands downstream."""
        return TraceContext(self.trace_id, mint_id())

    @classmethod
    def mint(cls) -> "TraceContext":
        return cls(mint_id(), mint_id())

    @classmethod
    def parse(cls, text: str) -> "TraceContext | None":
        """Exactly '<16 hex>/<16 hex>' (the C++ parser is byte-identical);
        anything else — wrong length, stray chars, zero trace-id — is
        None, never an exception (the field arrives from the network)."""
        if not isinstance(text, str) or len(text) != 33 or text[16] != "/":
            return None
        try:
            trace_id = int(text[:16], 16)
            span_id = int(text[17:], 16)
        except ValueError:
            return None
        if trace_id == 0:
            return None
        return cls(trace_id, span_id)


@dataclass
class Span:
    """One completed span (field-compatible with the C++ journal's)."""

    name: str
    trace_id: int
    span_id: int
    parent_id: int
    start_us: int
    dur_us: int
    pid: int = field(default_factory=os.getpid)

    @property
    def end_us(self) -> int:
        return self.start_us + self.dur_us

    def chrome_event(self) -> dict:
        return {
            "name": self.name,
            "ph": "X",
            "ts": self.start_us,
            "dur": self.dur_us,
            "pid": self.pid,
            "tid": self.pid,
            "args": {
                "trace_id": f"{self.trace_id:016x}",
                "span_id": f"{self.span_id:016x}",
                "parent_id": f"{self.parent_id:016x}",
            },
        }


class SpanJournal:
    """Bounded ring of completed spans. Thread-safe; oldest entries are
    overwritten (a flight recorder, like the C++ ring). ``drain()`` hands
    the contents to a flusher exactly once."""

    def __init__(self, capacity: int = 2048):
        self._lock = threading.Lock()
        self._capacity = max(int(capacity), 0)
        self._spans: list[Span] = []
        self.recorded = 0

    def record(self, span: Span) -> None:
        if self._capacity == 0:
            return
        with self._lock:
            self.recorded += 1
            self._spans.append(span)
            if len(self._spans) > self._capacity:
                del self._spans[: len(self._spans) - self._capacity]

    def snapshot(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def drain(self) -> list[Span]:
        with self._lock:
            spans, self._spans = self._spans, []
            return spans

    def chrome_trace(self) -> dict:
        """A valid Chrome-trace JSON document of the ring's contents
        (chrome://tracing / Perfetto load it directly)."""
        events = [s.chrome_event() for s in self.snapshot()]
        events.sort(key=lambda e: e["ts"])
        return {"displayTimeUnit": "ms", "traceEvents": events}


#: Process-wide journal — the shim, converter and cluster client record
#: here; flush_spans()/maybe_flush_env() empty it toward the daemon.
JOURNAL = SpanJournal()

_current: contextvars.ContextVar[TraceContext | None] = contextvars.ContextVar(
    "dynolog_tpu_trace_ctx", default=None)


def current() -> TraceContext | None:
    """The ambient trace context, if any (set_current/span manage it)."""
    return _current.get()


def set_current(ctx: TraceContext | None) -> None:
    _current.set(ctx)


def from_env(environ=None) -> TraceContext | None:
    """Context handed to this process via $DYNO_TRACE_CTX (the export
    child's inheritance path)."""
    return TraceContext.parse((environ or os.environ).get(ENV_TRACE_CTX, ""))


@contextlib.contextmanager
def span(
    name: str,
    ctx: TraceContext | None = None,
    journal: SpanJournal | None = None,
    now=time.time,
    start_us: int | None = None,
):
    """Times a section and records it on exit (exceptions included — a
    failing capture's span is exactly the interesting one). The section
    runs with the ambient context set to THIS span (same trace, this
    span-id as parent), so nested spans parent correctly. Yields the
    recorded-on-exit Span (ids valid inside the block; timing filled at
    exit). `start_us` opens the span in the past: for a phase that began
    before its context was known (the shim's config fetch) or on another
    thread (the capture's finish)."""
    parent = ctx if ctx is not None else current()
    rec = Span(
        name=name[: NAME_BYTES - 1],
        trace_id=parent.trace_id if parent else mint_id(),
        span_id=mint_id(),
        parent_id=parent.span_id if parent else 0,
        start_us=int(now() * 1e6) if start_us is None else start_us,
        dur_us=0,
    )
    token = _current.set(TraceContext(rec.trace_id, rec.span_id))
    try:
        yield rec
    finally:
        _current.reset(token)
        rec.dur_us = max(int(now() * 1e6) - rec.start_us, 0)
        (journal if journal is not None else JOURNAL).record(rec)


class Histogram:
    """One fixed-bucket latency histogram (C++ LatencyHistogram mirror)."""

    def __init__(self, bounds=DEFAULT_BOUNDS):
        self.bounds = tuple(bounds)
        self.buckets = [0] * (len(self.bounds) + 1)  # per-bucket, not cum.
        self.count = 0
        self.sum = 0.0
        self._lock = threading.Lock()

    def observe(self, seconds: float) -> None:
        if not seconds >= 0:  # NaN/negative clock skew
            seconds = 0.0
        idx = 0
        while idx < len(self.bounds) and seconds > self.bounds[idx]:
            idx += 1
        with self._lock:
            self.buckets[idx] += 1
            self.count += 1
            self.sum += seconds


def _fmt(v: float) -> str:
    """%g-style canonical le/sum formatting, matching the C++ renderer."""
    return f"{v:g}"


class HistogramFamily:
    """A named histogram family rendering the conformant OpenMetrics
    block: `# HELP`, `# TYPE ... histogram`, then per-series cumulative
    `_bucket{...,le="..."}`, `_sum`, `_count`. label_key=None renders a
    single unlabeled series; a labeled family always renders the
    {<label>="all"} aggregate first (C++ registry behavior)."""

    def __init__(self, name: str, help_text: str, label_key: str | None = None):
        self.name = name
        self.help = help_text
        self.label_key = label_key
        self.aggregate = Histogram()
        self.children: dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def observe(self, seconds: float, label: str | None = None) -> None:
        self.aggregate.observe(seconds)
        if self.label_key is None or label is None:
            return
        with self._lock:
            hist = self.children.get(label)
            if hist is None:
                hist = self.children[label] = Histogram()
        hist.observe(seconds)

    def _series(self, labels: str, hist: Histogram) -> str:
        out = []
        cumulative = 0
        for bound, n in zip(hist.bounds, hist.buckets):
            cumulative += n
            out.append(
                f'{self.name}_bucket{{{labels}le="{_fmt(bound)}"}} '
                f"{cumulative}")
        # +Inf/_count from the cumulative bucket sum, mirroring the C++
        # renderer (there the separate count atomic can race a scrape
        # into a non-monotonic histogram).
        cumulative += hist.buckets[-1]
        out.append(f'{self.name}_bucket{{{labels}le="+Inf"}} {cumulative}')
        block = "{" + labels[:-1] + "}" if labels else ""
        out.append(f"{self.name}_sum{block} {_fmt(hist.sum)}")
        out.append(f"{self.name}_count{block} {cumulative}")
        return "\n".join(out) + "\n"

    def render(self) -> str:
        out = f"# HELP {self.name} {self.help}\n"
        out += f"# TYPE {self.name} histogram\n"
        if self.label_key is None:
            return out + self._series("", self.aggregate)
        out += self._series(f'{self.label_key}="all",', self.aggregate)
        with self._lock:
            children = sorted(self.children.items())
        for label, hist in children:
            out += self._series(f'{self.label_key}="{label}",', hist)
        return out


def render_exposition(families: list[HistogramFamily]) -> str:
    """Families rendered as one OpenMetrics exposition, terminated with
    `# EOF` like the daemon's /metrics (src/core/OpenMetricsServer.cpp)."""
    return "".join(f.render() for f in families) + "# EOF\n"


def flush_spans(
    endpoint: str, journal: SpanJournal | None = None
) -> int:
    """Drains the journal and sends each span to the daemon's IPC
    endpoint as a fire-and-forget "span" datagram (the daemon merges
    them into its own ring for `selftrace`). Best-effort: a dead daemon
    costs nothing but the drained spans. In order of start, so a parent
    goes before what it holds: the export child's trace.convert, which the
    daemon's histogram feeds on, is not queued behind its planes' spans.
    Returns the count sent."""
    journal = journal if journal is not None else JOURNAL
    spans = sorted(journal.drain(), key=lambda s: s.start_us)
    if not spans:
        return 0
    from dynolog_tpu.client import ipc  # lazy: obs stays stdlib-only

    sent = 0
    try:
        with ipc.IpcClient() as client:
            for s in spans:
                if client.send_span(s, dest=endpoint):
                    sent += 1
    except OSError:
        pass  # no socket dir / bind failure: self-tracing is best-effort
    return sent


def maybe_flush_env(journal: SpanJournal | None = None) -> int:
    """flush_spans() toward $DYNO_OBS_ENDPOINT when set (the export
    child's exit path); no-op otherwise."""
    endpoint = os.environ.get(ENV_FLUSH_ENDPOINT)
    if not endpoint:
        return 0
    return flush_spans(endpoint, journal)
