"""XLA trace summarizer: what's inside a captured .xplane.pb.

The daemon + shim capture traces (`dyno gputrace` → jax.profiler); this
module answers the operator's next question — *what did the device spend
its time on* — without TensorBoard: from the one decode of each plane that
`dynolog_tpu.xspace` makes (the module that knows the XSpace wire format;
pure stdlib) it prints per-plane op aggregates, and the same decode feeds
the Chrome trace the shim's export child writes beside the summary.

CLI::

    python -m dynolog_tpu.trace <trace_dir | manifest.json | file.xplane.pb>
        [--top 15] [--plane SUBSTR] [--json]

`trace_dir` is what the manifest's `trace_dir` field points at (the shim's
output); the newest session under plugins/profile/ is summarized.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
import threading
import time
from dataclasses import dataclass, field, replace

from dynolog_tpu import obs
from dynolog_tpu.xspace import (
    CONTENT_FIELDS,
    _decode_plane,
    _Plane,
    _plane_outline,
    _plane_spans,
    iter_plane_bufs,
    verify_schema_pins,
)

# (op-name fragment, kind of collective), first match wins, `_` read as `-`:
# XLA names an op it inserts itself after its opcode (`all-reduce.3`) and one
# the program wrote after the JAX primitive (`psum.1`, `ragged_all_to_all.85`).
# dynolog_tpu.diagnose classes ops by the same test.
COLLECTIVE_KINDS = (
    ("all-reduce", "all-reduce"), ("psum-scatter", "reduce-scatter"),
    ("psum", "all-reduce"), ("all-gather", "all-gather"),
    ("reduce-scatter", "reduce-scatter"), ("all-to-all", "all-to-all"),
    ("collective-permute", "collective-permute"),
    ("ppermute", "collective-permute"), ("collective", "collective"),
    ("send", "send"), ("recv", "recv"),
)


def collective_kind(op_name: str) -> str | None:
    """The kind of collective an op is by its name, None for any other op."""
    low = op_name.lower().replace("_", "-")
    return next((kind for tok, kind in COLLECTIVE_KINDS if tok in low), None)


def is_collective(op_name: str) -> bool:
    return collective_kind(op_name) is not None


@dataclass
class OpAggregate:
    name: str
    # total_ps is inclusive, what the bytes say: a `while` event spans its
    # trips. self_ps is the same less the events that lie directly inside
    # on the same line (`held` of them): where nothing nests the two are
    # equal, and self times add up to the time the device was busy.
    total_ps: int = 0
    count: int = 0
    self_ps: int = 0
    held: int = 0
    flops: float = 0.0
    bytes_accessed: float = 0.0
    # Result shapes seen for this op ("bf16[128,512]"), parsed from the
    # HLO expression in the event metadata. Capped (SHAPES_PER_OP): the
    # diagnosis diff only needs "did the fusion's shape change", not an
    # exhaustive shape census.
    shapes: set = field(default_factory=set)


# Max distinct result shapes tracked per aggregated op.
SHAPES_PER_OP = 4


def _op_shape(name: str) -> str:
    """Result-shape token of an HLO expression metadata name:
    '%fusion.116 = bf16[128,512]{1,0} fusion(...)' -> 'bf16[128,512]'.
    Empty for non-HLO names (host ops, already-plain names)."""
    if not name.startswith("%"):
        return ""
    _, sep, rhs = name.partition(" = ")
    if not sep:
        return ""
    token = rhs.split(" ", 1)[0]
    # Drop the layout annotation ({1,0}) — a layout-only change is below
    # the diff's resolution, and keeping it would alias one shape into
    # many strings.
    return token.split("{", 1)[0]


@dataclass
class PlaneSummary:
    name: str
    lines: int = 0
    events: int = 0
    bytes: int = 0  # the plane's payload, as plane_index counts it
    # bytes by CONTENT_FIELDS name, "other" (id, name) besides: they add
    # up to `bytes`
    content: dict = field(default_factory=lambda: dict.fromkeys(
        (*CONTENT_FIELDS.values(), "other"), 0))
    event_metadata: int = 0  # entries of the event-metadata map
    duration_ps: int = 0  # max event end across lines
    ops: dict = field(default_factory=dict)  # name -> OpAggregate
    # scope (op_scope of the op's `tf_op`) -> [self_ps, events], over the
    # lines the op table reads: the self times add up as the ops' do
    scopes: dict = field(default_factory=dict)
    line_names: list = field(default_factory=list)
    step_durations_ps: list = field(default_factory=list)  # "Steps" line


def _op_key(name: str, group: bool) -> str:
    """Display/aggregation key for an event name. Device-plane XLA op
    metadata carries the full HLO expression ('%fusion.116 = bf16[...]'):
    keep the op token; with group=True also fold the .N instance suffix so
    all fusions aggregate ('fusion.116' -> 'fusion')."""
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
    if group:
        base = name.rsplit(".", 1)
        if len(base) == 2 and base[1].isdigit():
            name = base[0]
    return name


NO_SCOPE = "(none)"
# Components of an op's path that the framework writes, not the program:
# what a transform or a control-flow primitive leaves in the name stack.
# `name(inner)` of a transform reads as `inner`; `jit(f)` names a function,
# not a scope, and goes whole.
SCOPE_WRAPPERS = frozenset((
    "jvp", "transpose", "vmap", "pmap", "remat", "checkpoint", "custom_jvp",
    "custom_vjp"))
SCOPE_FUNCTIONS = frozenset(("jit", "pjit"))
SCOPE_STRUCTURE = frozenset((
    "checkpoint", "rematted_computation", "remat", "while", "body", "cond",
    "scan", "closed_call", "core_call", "shard_map", "pallas_call",
    "custom_jvp_call", "custom_vjp_call", "custom_lin"))


def op_scope(tf_op: str) -> str:
    """The scope of an op by the path in its metadata: the outermost
    component the program wrote itself (`jax.named_scope`), NO_SCOPE where
    there is none. The trailing component is the primitive
    ("dot_general:") and is set aside; so are the framework's own
    (SCOPE_STRUCTURE, `jit(...)`, `branch_1_fun`), and a transform's
    wrapper is read through to what it wraps (`transpose(jvp(attn))` ->
    `attn`, `jvp()` -> nothing). An op XLA made itself carries its own name
    alone ("ragged-dot-none:", or no path at all): NO_SCOPE. Of an op XLA
    merged from several ("a/b/mul;a/c/add:") the first path is read."""
    parts = tf_op.split(";", 1)[0].rstrip(":").split("/")[:-1]
    for part in parts:
        while part.endswith(")") and "(" in part:
            head, _, inner = part.partition("(")
            if head in SCOPE_FUNCTIONS:
                part = ""
            elif head in SCOPE_WRAPPERS:
                part = inner[:-1]
            else:
                break
        if part and part not in SCOPE_STRUCTURE and not (
                part.startswith("branch_") and part.endswith("_fun")):
            return part
    return NO_SCOPE


def _plane_summary(
    plane: _Plane, group: bool = True, by_category: bool = False
) -> PlaneSummary:
    out = PlaneSummary(
        name=plane.name, bytes=plane.bytes,
        event_metadata=plane.event_metadata, lines=len(plane.lines),
        line_names=[lname for _, lname, _, _ in plane.lines])
    out.content.update(plane.content)
    # Device planes carry several views of the same window (Steps, XLA
    # Modules, XLA Ops, Async XLA Ops); the op table reads the synchronous
    # "XLA Ops" line when present so step-number and module events don't
    # pollute it and async copies don't double count compute time.
    has_xla_ops = "XLA Ops" in out.line_names
    # metadata id -> (its row, flops, bytes, its scope's [self_ps, events]):
    # an op's path is read once an op, not once an event
    by_id: dict[int, tuple] = {}
    for _, lname, _, events in plane.lines:
        out.events += len(events)
        count_ops = not has_xla_ops or lname == "XLA Ops"
        steps = lname == "Steps"
        # whether some event starts before the one written before it
        # ends: lies inside it, overlaps it, or the line is not in order
        overlap, last_end_ps = False, 0
        for meta_id, offset_ps, duration_ps, own in events:
            end_ps = offset_ps + duration_ps
            if end_ps > out.duration_ps:
                out.duration_ps = end_ps
            if steps and duration_ps > 0:
                out.step_durations_ps.append(duration_ps)
            if not count_ops:
                continue
            row = by_id.get(meta_id)
            if row is None:
                name = plane.names.get(meta_id, f"op#{meta_id}")
                costs = plane.costs.get(meta_id, {})
                key = (costs.get("hlo_category", "uncategorized")
                       if by_category else _op_key(name, group))
                agg = out.ops.setdefault(key, OpAggregate(key))
                # an id's shape is the same at every event: offered once
                shape = _op_shape(name)
                if shape and len(agg.shapes) < SHAPES_PER_OP:
                    agg.shapes.add(shape)
                row = by_id[meta_id] = (
                    agg, costs.get("flops", 0.0),
                    costs.get("bytes_accessed", 0.0),
                    out.scopes.setdefault(
                        op_scope(costs.get("tf_op", "")), [0, 0]))
            agg, flops, nbytes, scope = row
            if own and (own.get("flops") or own.get("bytes_accessed")):
                flops = own.get("flops", 0.0)
                nbytes = own.get("bytes_accessed", 0.0)
            agg.total_ps += duration_ps
            agg.count += 1
            agg.flops += flops
            agg.bytes_accessed += nbytes
            agg.self_ps += duration_ps
            scope[0] += duration_ps
            scope[1] += 1
            if offset_ps < last_end_ps:
                overlap = True
            last_end_ps = end_ps
        if overlap and count_ops:
            _take_held_time(events, by_id)
    return out


def _take_held_time(events: list, by_id: dict) -> None:
    """Takes from each row's `self_ps`, and from its scope's, the time of
    the events that lie directly inside its events on this line, and counts
    them (`held`): the
    second pass of a line on which events overlap. A line not in start
    order (an enclosing event before what it holds) is sorted first. An
    event lies inside the innermost event still open at its start, or in
    none: one that overlaps it without lying inside is left whole."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    open_events: list[tuple] = []  # (end, its by_id row), innermost last
    for meta_id, offset_ps, duration_ps, _ in events:
        while open_events and open_events[-1][0] <= offset_ps:
            open_events.pop()
        end_ps = offset_ps + duration_ps
        if open_events and end_ps <= open_events[-1][0]:
            holder, _, _, scope = open_events[-1][1]
            holder.self_ps -= duration_ps
            holder.held += 1
            scope[0] -= duration_ps
        open_events.append((end_ps, by_id[meta_id]))


def summarize_xplane_bytes(
    data: bytes, group: bool = True, by_category: bool = False
) -> list[PlaneSummary]:
    return [_plane_summary(_decode_plane(data, a, b), group, by_category)
            for a, b in _plane_spans(data)]


# What one entry of a plane's event-metadata map weighs, in bytes of
# `lines` (`_plane_weight`): what converting an entry costs over what a
# byte of a device plane's events costs. Fitted again by PR 42, whose loops
# made both cheaper (least squares over the twelve device planes of the six
# capture cells' kept artifacts, each converted seven times on the chip
# machine at nice 19: an entry 21.2 -> 15.2 us, an event 5.8 -> 4.5 us of
# 47.5-55.7 bytes, so 182 -> 170 bytes an entry; PR 40 had put it at 200).
METADATA_ENTRY_WEIGHT = 170

# The least a forked worker's share has to weigh, in `_plane_weight`'s unit
# (10-12 thousand to a millisecond of a device plane's `convert.plane` on
# the chip machine since PR 42, so about 60 ms, the same 60 ms as the
# 500 000 PR 40 set at 8-9 thousand a millisecond): what a fork, its pipes
# and the fragment's way back cost. From the spans of PR 39's and PR 40's
# traced runs (PERF.md section 5): a worker's first plane starts 38-75 ms
# after the caller could have started its own, and the last fragment takes
# 13-41 ms from its plane's end through the pipe to the span's close.
# Offline, with PR 42's loops, on the kept four-chip artifacts (chip
# machine, nice 19, median of seven): one worker against none converts
# `olmo2-13b-v5e4`'s in 246.1 against 268.5 ms (its share of 1.16 M, 102 ms
# of work, buys 22) and the sparse job's in 386.8 against 464.5 (1.91 M,
# 181 ms, buys 78), so a fork's whole cost is 80-103 ms and a share of
# 0.70-0.95 M would lose up to 20 ms by it: no artifact of today's cells
# holds one. The threshold lies between what the one-chip artifacts hold
# beside their device plane (`/host:CPU`, 74-100 thousand, 14-22 ms: no
# fork) and what four device planes leave the second process (1.16 and
# 1.91 M: a fork). It is also how far behind the split counts a worker as
# starting, so it has to stay under the heaviest plane of a four-chip
# artifact (0.78 M at 13b-v5e4), or the caller is dealt three device
# planes of four.
FORK_WORTH_WEIGHT = 700_000

# zlib's level for the streamed trace.json.gz: the artifact is a scratch
# view, and level 1 costs a fraction of the default level-9 `gzip.open` CPU
# for ~15-25% larger output.
GZIP_LEVEL = 1

# Added to a forked worker's niceness (`os.nice` takes an increment), so
# that parallel conversion can never compete with a training loop at normal
# priority. The caller is not re-niced: the shim's export subprocess is at
# nice 19 already.
WORKER_NICE = 10


def _plane_weight(
    line_bytes: int, lines: int, entries: int
) -> tuple[int, int]:
    """(weight, lines) of a plane from one walk of its top level
    (`xspace._plane_outline`: no line and no metadata entry is opened). The
    weight says what converting the plane will cost, and that is events
    and op metadata, not size: the bytes under `lines` plus
    METADATA_ENTRY_WEIGHT for every entry of the event-metadata map.
    `/host:metadata`, the largest plane of every artifact (1.1-27.4 MB of
    HLO in its stats), holds one entry and no line, weighs 170 and converts
    in 0.1-0.3 ms.

    How it maps to milliseconds, from artifacts kept from the six capture
    cells and converted on the chip machine at nice 19 (PR 42, chip call
    1; PR 40's reading of the code before in brackets): an event costs
    about 4.5 us [6] through the whole conversion wherever it lies and a
    metadata entry about 15 us [23] (`_read_entry`: its names, and the
    four stats of its dozen that the cost model wants, read where they
    lie; then its row of the summary and its name `json.dumps`-ed); a
    device plane's event is 48-56 bytes of its line, so its
    `convert.plane` lasts a millisecond for every 10-12 thousand of weight
    [8-9] (olmo2-1b's `/device:TPU:0`: 27.9 k events in 1.55 MB of lines,
    7600 entries, weight 2.84 M, 234 ms [348]; the hybrid job's 1.61 M,
    152 ms [219]; deepseek-v2-lite's 1.00 M, 83 ms; olmo2-7b-2l's 252 k,
    21 ms [31]); a host thread's event is 16 bytes, so `/host:CPU`
    (2.6-5.3 k events, 183-272 entries, weight 74-138 k) takes 14-28 ms,
    twice what its weight says. The rule needs no better: the shares it
    tells apart lie a factor of seven under the threshold (one chip:
    74-100 k beside the device plane) and of 1.7-2.7 over it (four chips:
    a worker's two device planes, 1.16 and 1.91 M)."""
    return line_bytes + METADATA_ENTRY_WEIGHT * entries, lines


def _plane_events(pid: int, plane_buf: bytes) -> list[dict]:
    """Chrome trace events for ONE plane (the process_name metadata event,
    then per line a thread_name event plus the complete events), as dicts:
    the tests' reference for `_plane_json`, on no product path.

    Mapping: plane -> process (pid), line -> thread (tid), event ->
    complete event ("ph":"X") at ts = line.timestamp_ns + offset_ps,
    named by its XEventMetadata display_name (fallback: name).
    """
    plane = _decode_plane(plane_buf, 0, len(plane_buf))
    events: list[dict] = [_process_name(pid, plane)]
    for lid, lname, ts_ns, line_events in plane.lines:
        events.append(_thread_name(pid, lid, lname))
        base_us = ts_ns / 1e3
        for meta_id, offset_ps, duration_ps, _ in line_events:
            events.append({
                "ph": "X", "pid": pid, "tid": lid,
                "name": plane.shown.get(meta_id, f"op#{meta_id}"),
                "ts": base_us + offset_ps / 1e6,
                "dur": duration_ps / 1e6,
            })
    return events


def _process_name(pid: int, plane: _Plane) -> dict:
    return {"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": plane.name}}


def _thread_name(pid: int, lid: int, lname: str) -> dict:
    return {"ph": "M", "pid": pid, "tid": lid, "name": "thread_name",
            "args": {"name": lname}}


def _plane_json(pid: int, plane: _Plane) -> bytes:
    """`_plane_events` of a decoded plane as a UTF-8 JSON fragment: the
    events, `", "`-joined, WITHOUT the surrounding array brackets, each byte
    for byte what `json.dumps` prints (a float by `float.__repr__`: `!r`),
    so that the planes' fragments joined by `", "` are `json.dump` of the
    whole list. A name is `json.dumps`-ed once a metadata id, not an event."""
    parts = [json.dumps(_process_name(pid, plane))]
    quoted: dict[int, str] = {}
    for lid, lname, ts_ns, line_events in plane.lines:
        parts.append(json.dumps(_thread_name(pid, lid, lname)))
        head = f'{{"ph": "X", "pid": {pid}, "tid": {lid}, "name": '
        base_us = ts_ns / 1e3
        for meta_id, offset_ps, duration_ps, _ in line_events:
            name = quoted.get(meta_id)
            if name is None:
                name = quoted[meta_id] = json.dumps(
                    plane.shown.get(meta_id, f"op#{meta_id}"))
            parts.append(
                f'{head}{name}, "ts": {base_us + offset_ps / 1e6!r}, '
                f'"dur": {duration_ps / 1e6!r}}}')
    return ", ".join(parts).encode()


def xplane_to_chrome_trace(data: bytes) -> dict:
    """Convert one serialized XSpace to Chrome trace-event JSON (the
    trace.json.gz artifact jax.profiler's own export writes next to the
    xplane.pb — loadable in chrome://tracing and, minus the metadata
    field, ui.perfetto.dev).

    This is the single-shot in-memory form (everything in one dict): the
    tests' reference, on no product path. The product's writer is the
    streamed `write_chrome_trace_gz`, which produces the same events plane
    by plane without materializing the whole list.
    """
    events: list[dict] = []
    for pid, plane_buf in enumerate(iter_plane_bufs(data), start=1):
        events.extend(_plane_events(pid, plane_buf))
    return {"displayTimeUnit": "ns", "traceEvents": events}


@dataclass
class ConvertBudget:
    """The one setting of the background converter: how many processes may
    convert planes at one time.

    Post-processing must stay bounded and off the capture path: unbudgeted
    converters pile up across back-to-back captures and take CPU from every
    later one and from the job.

    max_workers: the most processes that convert planes at one time, THE
    CALLER ONE OF THEM (the work is pure-Python and GIL-bound, so threads
    cannot parallelize it): 1 never forks (a host whose job must never see
    a fork beside it sets that); the default is the caller and at most one
    forked worker. An upper bound, not a request: how many of them an
    artifact gets is read from the artifact (`_shares`: a worker is forked
    only for a share of planes worth a fork, and at one chip none is), and
    only from a (near-)single-threaded process like the shim's export
    subprocess (fork safety; see _iter_fragments), serial elsewhere.

    Set by DYNO_TRACE_CONVERT_WORKERS (`from_env`, and therefore the shim's
    export subprocess, whose environment carries a capture's
    TRACE_CONVERT_WORKERS config key under that name). That subprocess is
    started as its capture's window opens and reads it then
    (`export_child`): once to decide whether the pool's modules are worth
    importing before the artifact exists (it cannot know yet whether the
    artifact will be worth a fork), and again when it converts.
    """

    max_workers: int = 0  # 0 = auto: min(2, cpu count), the caller counted

    def resolved_workers(self, n_planes: int) -> int:
        """Processes the budget allows over `n_planes` planes, the caller
        among them: 1 is the caller alone."""
        workers = self.max_workers
        if workers <= 0:
            workers = min(2, os.cpu_count() or 1)
        return max(1, min(workers, n_planes))

    @classmethod
    def from_env(cls) -> "ConvertBudget":
        try:
            return cls(int(os.environ.get("DYNO_TRACE_CONVERT_WORKERS", 0)))
        except ValueError:
            return cls()  # a malformed setting must not sink the conversion


def _nice_worker(ctx: "obs.TraceContext | None" = None) -> None:
    """Pool-worker initializer: deprioritize before any plane work, and
    take the conversion's span context as this process's ambient one, so
    that a plane's spans parent to trace.convert wherever the plane runs."""
    obs.set_current(ctx)
    try:
        os.nice(WORKER_NICE)
    except OSError:
        pass


def _plane_fragment(job: tuple[int, bytes]) -> bytes:
    """One plane's events as a UTF-8 JSON fragment (`_plane_json`)."""
    return _convert_plane(job)[0]


def _convert_plane(
    job: tuple[int, bytes], start: int = 0, end: int | None = None,
    top: list | None = None,
) -> tuple:
    """The converter's unit of work: one plane decoded ONCE, its fragment
    and its PlaneSummary both made from that decode (the summary None where
    aggregating it raised: the fragment still goes). `job` is the plane's
    Chrome-trace pid and the buffer that holds it: the plane's own bytes
    where a worker was sent them (top-level so ProcessPoolExecutor can
    pickle it by reference), the whole file with the plane at [start:end)
    where the process that read it converts (`top`: `_decode_plane`'s).

    Third of the result: the call's spans, convert.plane round all of it
    and convert.decode round `_decode_plane` alone, under the ambient
    context and with the pid of the process that ran it; and, ONLY for a
    plane some of whose metadata entries or events the generic path had to
    read (`_Plane.generic` above zero), convert.generic laid over that
    plane's convert.decode (its start, its length, its child): the
    journal's mark of a decode that left the loops written for the wire
    layout. They travel with the result because a pool worker has no
    journal anyone flushes (`_iter_fragments` records them in the
    caller's)."""
    pid, buf = job
    spans = obs.SpanJournal()
    with obs.span("convert.plane", journal=spans):
        with obs.span("convert.decode", journal=spans) as decode:
            plane = _decode_plane(
                buf, start, len(buf) if end is None else end, top)
        if plane.generic:
            spans.record(replace(
                decode, name="convert.generic", span_id=obs.mint_id(),
                parent_id=decode.span_id))
        try:
            summary = _plane_summary(plane)
        except Exception:  # noqa: BLE001 - a summarizer bug must not cost
            summary = None  # the trace.json.gz; the caller finds the None
        fragment = _plane_json(pid, plane)
    return fragment, summary, spans.snapshot()


def _fork_safe() -> bool:
    """Whether forking a worker pool is safe here. Only from a
    (near-)single-threaded process: the shim's export subprocess
    qualifies, an in-process caller inside a multithreaded app does not.
    Two tells, both needed: live Python threads, and jax itself — XLA's
    native thread pools are invisible to threading.active_count, so a
    loaded jax means multithreaded regardless of the count. (spawn would
    dodge the fork hazard but re-executes the parent __main__, which
    breaks the `python -c` export child.)"""
    return threading.active_count() == 1 and "jax" not in sys.modules


def _shares(
    weights: list[tuple[int, int]], processes: int
) -> tuple[list[int], list[int], int]:
    """Who converts which plane, from every plane's `_plane_weight`: (the
    caller's planes, the workers' planes, how many workers to fork), each
    list in the order its planes are to be converted. Longest share first
    over the planes that hold lines: heaviest first, each to the process
    that carries least so far, a worker counted as starting one fork
    (FORK_WORTH_WEIGHT) behind, so the caller (which ties go to) converts
    the heaviest plane itself, and at once. A plane with no line in it
    stays with the caller, where its bytes are, last. As many of
    `processes` as leave EVERY forked worker a share of FORK_WORTH_WEIGHT
    or more: with today's artifacts the caller alone wherever one device
    plane is the conversion (one chip), one worker at four chips. No
    worker: every plane the caller's, in file order."""
    lined = sorted((i for i, (_, lines) in enumerate(weights) if lines),
                   key=lambda i: -weights[i][0])
    lineless = [i for i, (_, lines) in enumerate(weights) if not lines]
    for n in range(min(processes, len(lined)), 1, -1):
        loads = [sum(weights[i][0] for i in lineless)] + [
            FORK_WORTH_WEIGHT] * (n - 1)
        mine, theirs = [], []
        for i in lined:
            k = loads.index(min(loads))
            loads[k] += weights[i][0]
            (theirs if k else mine).append(i)
        # every worker's share, less the fork it started behind by
        if min(loads[1:]) - FORK_WORTH_WEIGHT >= FORK_WORTH_WEIGHT:
            return mine + lineless, theirs, n - 1
    return list(range(len(weights))), [], 0


def _iter_fragments(data: bytes, budget: ConvertBudget):
    """Per-plane (JSON fragment, PlaneSummary) pairs (`_convert_plane`) of
    the serialized XSpace `data`, in plane order, under the budget.

    The caller is a converter: it holds the bytes, so it converts by offsets
    into them, nothing copied, pickled or piped. Where the budget allows a
    second process and forking is safe, every plane is weighed before any
    is converted (`_plane_weight`) and the planes are split (`_shares`):
    the caller keeps the heaviest plane and every plane with no line in
    it; nice'd workers are forked only for shares that outweigh a fork
    (FORK_WORTH_WEIGHT), BEFORE the caller starts on its own share, and are
    sent their planes' bytes heaviest first. The pairs go out in file
    order once the caller's share is done, a worker's as they fall due.
    Where no share is worth a fork (every one-chip artifact today), under
    `max_workers=1` and from a multithreaded caller: no pool, file order.

    Pool failure — at setup (sandboxes without working fork) OR mid-run (a
    worker OOM-killed: BrokenProcessPool, a RuntimeError) — leaves the
    caller converting what the pool has not handed back: a dead pool must
    degrade to slow conversion, never to a missing artifact. Each plane's
    spans are recorded here, in this process's journal, once a plane,
    whichever process converted it."""
    spans = _plane_spans(data)
    processes = budget.resolved_workers(len(spans))
    tops, theirs, workers = [None] * len(spans), [], 0
    if processes > 1 and _fork_safe():
        outlines = [_plane_outline(data, a, b) for a, b in spans]
        tops = [top for top, *_ in outlines]
        ours, theirs, workers = _shares(
            [_plane_weight(*counts) for _, *counts in outlines], processes)

    def keep_spans(converted: tuple) -> tuple:
        fragment, summary, plane_spans = converted
        for span in plane_spans:
            obs.JOURNAL.record(span)
        return fragment, summary

    def convert(i: int) -> tuple:
        return keep_spans(_convert_plane((i + 1, data), *spans[i], tops[i]))

    mine: dict[int, tuple] = {}  # the caller's share, converted, by plane
    sent: dict = {}  # a worker's plane -> its Future
    done = 0

    def due(i: int) -> tuple:
        if i in mine:
            return mine.pop(i)
        if i in sent:
            try:
                return keep_spans(sent.pop(i).result())
            except (OSError, RuntimeError):
                pass  # the pool died under this plane
        return convert(i)

    if workers:
        try:
            from concurrent.futures import ProcessPoolExecutor

            with ProcessPoolExecutor(
                max_workers=workers,
                initializer=_nice_worker,
                initargs=(obs.current(),),
            ) as pool:
                # the first submit forks; the workers pull from one queue
                for i in theirs:
                    a, b = spans[i]
                    sent[i] = pool.submit(_convert_plane, (i + 1, data[a:b]))
                for i in ours:
                    mine[i] = convert(i)
                for i in range(len(spans)):
                    yield due(i)
                    done = i + 1
            return
        except (OSError, RuntimeError):
            pass  # no pool to be had; planes [done:] convert below
    for i in range(done, len(spans)):
        yield due(i)


def stream_write(path: str, chunks) -> int:
    """Atomic chunked file write: tmp + rename, tmp unlinked on ANY
    failure (no orphaned .tmp next to the artifact), bytes written
    returned. The chunk iterable may be lazily produced (a profiler
    stream draining, memoryview slices of a collected XSpace): each chunk
    hits the page cache as it arrives, so the write overlaps the
    producer instead of buffering the whole payload first."""
    tmp_path = path + ".tmp"
    written = 0
    try:
        with open(tmp_path, "wb") as f:
            for chunk in chunks:
                f.write(chunk)
                written += len(chunk)
        os.replace(tmp_path, path)
    finally:
        try:
            os.unlink(tmp_path)  # no-op after a successful rename
        except OSError:
            pass
    return written


def _derived_path(xplane_path: str, ext: str) -> str:
    """<dir>/<host>.xplane.pb -> <dir>/<host><ext> for companion files."""
    suffix = ".xplane.pb"
    base = (
        xplane_path[: -len(suffix)]
        if xplane_path.endswith(suffix)
        else xplane_path
    )
    return base + ext


def _read_xplane(xplane_path: str, data: bytes | None) -> bytes:
    if data is not None:
        return data
    with open(xplane_path, "rb") as f:
        return f.read()


def write_chrome_trace_gz(
    xplane_path: str,
    data: bytes | None = None,
    budget: ConvertBudget | None = None,
    summaries: list | None = None,
) -> str:
    """Write <base>.trace.json.gz next to an .xplane.pb (the companion
    artifact jax's own stop_trace export produces); returns its path. Where
    `summaries` is given, each plane's PlaneSummary (None where aggregating
    it raised) is appended to it as the plane's fragment goes: the same
    decode made both (`_convert_plane`).

    Streamed and budgeted: planes convert to JSON fragments where the
    bytes are, beside a nice'd worker only where the artifact has a share
    of planes worth a fork (`_iter_fragments`, per `budget`), and each
    fragment goes through a chunked `zlib.compressobj` at GZIP_LEVEL in
    file order — the event list is never materialized (the fragments are,
    beside a worker, until their turn), and the CPU cost is a fraction of
    a monolithic level-9 `gzip.open` + `json.dump`
    (`write_chrome_trace_gz_single`, the tests' reference).
    Write-then-rename, tmp unlinked on failure: a reader (TensorBoard, an
    operator's scp) must never see a torn gzip, and a converter crash
    must not orphan a .tmp next to the trace dir."""
    import zlib

    if budget is None:
        budget = ConvertBudget.from_env()
    data = _read_xplane(xplane_path, data)
    out_path = _derived_path(xplane_path, ".trace.json.gz")

    def gz_chunks():
        comp = zlib.compressobj(
            GZIP_LEVEL, zlib.DEFLATED, 16 + zlib.MAX_WBITS)
        yield comp.compress(b'{"displayTimeUnit": "ns", "traceEvents": [')
        first = True
        for fragment, summary in _iter_fragments(data, budget):
            if summaries is not None:
                summaries.append(summary)
            if not fragment:
                continue
            if not first:
                yield comp.compress(b", ")
            yield comp.compress(fragment)
            first = False
        yield comp.compress(b"]}")
        yield comp.flush()

    # stream_write owns the tmp/rename/unlink-on-failure discipline.
    stream_write(out_path, gz_chunks())
    return out_path


def write_chrome_trace_gz_single(
    xplane_path: str, data: bytes | None = None
) -> str:
    """The single-shot converter: one in-memory dict, one monolithic
    default-level `gzip.open` + `json.dump`. The reference that
    tests/test_trace_convert.py compares the streamed converter against,
    on no product path."""
    import gzip

    trace = xplane_to_chrome_trace(_read_xplane(xplane_path, data))
    out_path = _derived_path(xplane_path, ".trace.json.gz")
    tmp_path = out_path + ".tmp"
    try:
        with gzip.open(tmp_path, "wt") as f:
            json.dump(trace, f)
        os.replace(tmp_path, out_path)
    finally:
        try:
            os.unlink(tmp_path)
        except OSError:
            pass
    return out_path


def write_summary_json(
    xplane_path: str,
    data: bytes | None = None,
    planes: list | None = None,
) -> str:
    """Write <base>.summary.json next to an .xplane.pb: the summarize()
    output (planes, step stats, top-op table with roofline columns), so
    every capture self-describes without the operator running anything —
    produced by the shim's background export alongside trace.json.gz, from
    the `planes` that conversion kept where it kept them all."""
    if planes is None or None in planes:
        planes = summarize_xplane_bytes(_read_xplane(xplane_path, data))
    out_path = _derived_path(xplane_path, ".summary.json")
    # stream_write owns the tmp/rename/unlink-on-failure discipline.
    stream_write(out_path, [
        json.dumps(_summarize_planes(planes), indent=1).encode()])
    return out_path


def write_derived_artifacts(
    xplane_path: str, budget: ConvertBudget | None = None
) -> list[str]:
    """Background-export entry point: read the xplane ONCE, decode each of
    its planes ONCE (the Chrome trace's writer hands on the PlaneSummary
    each plane's decode also gave) and write each companion artifact in its
    own failure domain — a summarizer bug must not cost the trace.json.gz,
    and a converter that breaks off leaves the summary a pass of its own.
    Returns written paths.

    Self-tracing: the whole conversion runs under a trace.convert span —
    parented to the capture's TRACE_CONTEXT when the shim handed one down
    via $DYNO_TRACE_CTX — with one convert.plane span a plane under it and
    that plane's convert.decode inside (`_convert_plane`; the pid is this
    process's, or a forked worker's for the planes one was sent: only an
    artifact with a share of planes worth a fork gets one,
    `_iter_fragments`). What is not plane work (the read, the planes
    weighed, a worker's fork and pipes where there is one, gzip, both
    writes) is trace.convert's self time. When $DYNO_OBS_ENDPOINT names a
    daemon, the spans are flushed
    back to it on the way out, after both files are renamed (the daemon
    folds trace.convert's duration into the
    dynolog_trace_convert_seconds scrape histogram, and all of them into
    the `selftrace` journal)."""
    from dynolog_tpu import failpoints

    # Fault drill: trace.convert=throw kills this export exactly the way
    # a SIGKILL'd/crashed export child does (the xplane is already on
    # disk; derived .tmp debris is reclaimed by the shim's startup sweep).
    failpoints.fire("trace.convert")
    try:
        with obs.span("trace.convert", ctx=obs.from_env() or obs.current()):
            with open(xplane_path, "rb") as f:
                data = f.read()
            written = []
            planes: list | None = []
            try:
                written.append(
                    write_chrome_trace_gz(xplane_path, data, budget, planes))
            except Exception:  # noqa: BLE001 - derived artifacts are
                planes = None  # best-effort; the xplane.pb is on disk
            try:
                written.append(write_summary_json(xplane_path, data, planes))
            except Exception:  # noqa: BLE001 - as above
                pass
    finally:
        obs.maybe_flush_env()
    return written


def export_child() -> int:
    """The export child's life, from its interpreter being up at nice 19
    (`shim._EXPORT_CHILD_CODE`): started by the shim as a capture's window
    OPENS, it makes every import the conversion will make (the lazy ones
    too: the pool's, where its ConvertBudget allows a second process, since
    whether the artifact is worth one shows only once it exists; the span
    flush's, where there is a daemon to flush to), says so (`ready
    <unix seconds>` on standard output), and blocks reading one line of
    standard input. A JSON string is the
    artifact's path, handed over as its write completes:
    `write_derived_artifacts`. An empty line or end of file is a capture
    that owes nothing (it failed, or the shim is gone): exit 0, no file
    touched, no span opened or flushed."""
    import zlib  # noqa: F401 - write_chrome_trace_gz's

    from dynolog_tpu import failpoints  # noqa: F401

    if ConvertBudget.from_env().resolved_workers(2) > 1:
        try:  # what _iter_fragments' pool imports as it starts
            import concurrent.futures.process  # noqa: F401
            import multiprocessing.popen_fork  # noqa: F401
            import multiprocessing.synchronize  # noqa: F401
        except ImportError:
            pass  # no working pool here: _iter_fragments goes serial
    if os.environ.get(obs.ENV_FLUSH_ENDPOINT):
        from dynolog_tpu.client import ipc  # noqa: F401 - obs.flush_spans'
    try:
        os.write(1, f"ready {time.time():.6f}\n".encode())
    except OSError:
        pass  # nobody listens; the path still arrives
    line = sys.stdin.readline().strip()
    if not line:
        return 0
    write_derived_artifacts(json.loads(line))
    return 0


def find_xplane_files(target: str) -> list[str]:
    """Resolve a trace dir / shim manifest / direct file to xplane paths."""
    if target.endswith(".xplane.pb"):
        return [target]
    if target.endswith(".json"):
        with open(target) as f:
            target = json.load(f)["trace_dir"]
    hits = sorted(
        glob.glob(os.path.join(target, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not hits:
        return []
    # Newest profiler session only (a dir can accumulate several).
    newest_session = os.path.dirname(hits[-1])
    return [p for p in hits if os.path.dirname(p) == newest_session]


def summarize(
    target: str, group: bool = True, by_category: bool = False
) -> dict:
    planes: list[PlaneSummary] = []
    for path in find_xplane_files(target):
        with open(path, "rb") as f:
            planes.extend(
                summarize_xplane_bytes(
                    f.read(), group=group, by_category=by_category))
    return _summarize_planes(planes)


def compact_profile(data: bytes, top: int = 40, group: bool = False) -> dict:
    """Promote one serialized XSpace to a compact op-level profile — the
    continuous-capture ring's storage unit (shim.CaptureRing) and the
    diagnosis engine's comparable: the summarize() output with the op
    table capped at `top` rows plus size metadata, produced plane by plane
    in the calling process (no pool: ring promotion on a training host
    forks nothing beside the job)."""
    # group=False by default: per-op-INSTANCE rows (fusion.116, not fusion)
    # are the diagnosable unit — "which fusion regressed" is the whole
    # question the diff engine answers.
    profile = _summarize_planes(summarize_xplane_bytes(data, group=group))
    profile["top_ops"] = profile["top_ops"][:top]
    profile["xspace_bytes"] = len(data)
    return profile


def _summarize_planes(planes: list[PlaneSummary]) -> dict:
    out = {"planes": [], "top_ops": []}
    # Step-time distribution from device "Steps" lines — the trace-side
    # view of the operator's primary metric.
    step_ps = sorted(
        d for p in planes for d in p.step_durations_ps)
    if step_ps:
        def _pctl(p):
            # nearest-rank: ceil(p*n)-th order statistic (p50 of 2 = lower)
            k = math.ceil(p * len(step_ps))
            return step_ps[min(max(k - 1, 0), len(step_ps) - 1)]
        out["steps"] = {
            "count": len(step_ps),
            "mean_ms": round(sum(step_ps) / len(step_ps) / 1e9, 3),
            "p50_ms": round(_pctl(0.50) / 1e9, 3),
            "p95_ms": round(_pctl(0.95) / 1e9, 3),
            "max_ms": round(step_ps[-1] / 1e9, 3),
        }
    merged: dict[str, OpAggregate] = {}
    device_planes = [p for p in planes if "device" in p.name.lower()
                     or "tpu" in p.name.lower() or "gpu" in p.name.lower()]
    for p in planes:
        # Shares are over SELF time: it adds up to the time the plane's ops
        # kept the device busy, where inclusive time counts the inside of
        # a loop once for the body's ops and again for the `while`.
        op_ps = sum(a.self_ps for a in p.ops.values())
        kinds: dict[str, list] = {}  # kind of collective -> [ps, count]
        for name, agg in p.ops.items():
            kind = collective_kind(name)
            if kind is not None:
                row = kinds.setdefault(kind, [0, 0])
                row[0] += agg.self_ps
                row[1] += agg.count
        collective_ps = sum(ps for ps, _ in kinds.values())
        out["planes"].append(
            {
                "name": p.name,
                "lines": p.lines,
                "events": p.events,
                "duration_ms": round(p.duration_ps / 1e9, 3),
                # time of the plane's collective ops over all its op time
                "collective_pct": round(
                    100.0 * collective_ps / op_ps, 2) if op_ps else 0.0,
                # the same time by kind: gradient reduction (all-reduce)
                # or expert exchange (all-to-all) is the first question
                # of a slow sparse job
                "collectives": {
                    kind: {"total_ms": round(ps / 1e9, 3), "count": count}
                    for kind, (ps, count) in sorted(
                        kinds.items(), key=lambda kv: -kv[1][0])},
                # the ops that held others on the device's op line
                # (`while`, `conditional`, `call`): inclusive time, events,
                # and the events directly inside them, from which the trip
                # count reads (inside / count / the body's ops). A host
                # line's events nest too, as call stacks: not listed
                "loops": {
                    name: {"total_ms": round(agg.total_ps / 1e9, 3),
                           "count": agg.count, "inside": agg.held}
                    for name, agg in sorted(
                        p.ops.items(), key=lambda kv: -kv[1].total_ps)
                    if agg.held and "XLA Ops" in p.line_names},
                # which mechanism the time went to: self time, its share and
                # the events by the scope the program gave the op
                # (`jax.named_scope`, read from the op's `tf_op` by
                # `op_scope`); they add up to the plane's busy time as
                # `top_ops`' `self_ms` does. NO_SCOPE: ops with no path (XLA's
                # own copies, a kernel it substituted) or none of the
                # program's in it. A device's op line alone
                "scopes": {
                    name: {"self_ms": round(ps / 1e9, 3),
                           "pct": round(100.0 * ps / op_ps, 1) if op_ps
                           else 0.0,
                           "count": count}
                    for name, (ps, count) in sorted(
                        p.scopes.items(), key=lambda kv: -kv[1][0])
                    if "XLA Ops" in p.line_names},
                # what the plane's bytes are made of: `<field>_bytes` add
                # up to `bytes`; entries of the event-metadata map
                "bytes": p.bytes,
                **{f"{name}_bytes": n for name, n in p.content.items()},
                "event_metadata": p.event_metadata,
            }
        )
        # Op table from device planes when present (the question operators
        # ask), host planes otherwise.
        if p in (device_planes or planes):
            for name, agg in p.ops.items():
                m = merged.setdefault(name, OpAggregate(name))
                m.total_ps += agg.total_ps
                m.self_ps += agg.self_ps
                m.count += agg.count
                m.flops += agg.flops
                m.bytes_accessed += agg.bytes_accessed
                for shape in agg.shapes:
                    if len(m.shapes) < SHAPES_PER_OP:
                        m.shapes.add(shape)
    # Rows rank by self time and `pct` is a share of it; `total_ms` and
    # `count` stay what the bytes say, inclusive.
    self_total_ps = sum(a.self_ps for a in merged.values()) or 1
    for agg in sorted(merged.values(), key=lambda a: (-a.self_ps, -a.total_ps)):
        row = {
            "op": agg.name,
            "total_ms": round(agg.total_ps / 1e9, 3),
            "count": agg.count,
            "self_ms": round(agg.self_ps / 1e9, 3),
            "pct": round(agg.self_ps / self_total_ps * 100.0, 1),
        }
        # Roofline view when the profiler recorded cost models: achieved
        # compute/memory rates over the op's own device time (self time:
        # a `while`'s cost model counts one trip, its time all of them),
        # plus arithmetic intensity (FLOP per HBM byte). Rates are
        # suppressed for sub-microsecond marker events (async
        # copy-start/-done completions), whose durations don't represent
        # the transfer. Marker heuristic: zero-FLOP ops whose events
        # average < 1µs are async completion markers, not transfers.
        marker = (
            agg.flops == 0 and agg.count > 0
            and agg.total_ps / agg.count < 1e6
        )
        if agg.self_ps > 0 and agg.flops > 0:
            row["gflops_per_s"] = round(agg.flops / (agg.self_ps / 1e3), 1)
        if agg.self_ps > 0 and agg.bytes_accessed > 0 and not marker:
            row["gib_per_s"] = round(
                agg.bytes_accessed / (agg.self_ps / 1e12) / (1 << 30), 1)
        if agg.flops > 0 and agg.bytes_accessed > 0:
            row["flop_per_byte"] = round(agg.flops / agg.bytes_accessed, 2)
        if agg.shapes:
            # Sorted for deterministic JSON — the diagnosis diff compares
            # these lists across captures (fusion-shape changes).
            row["shapes"] = sorted(agg.shapes)
        out["top_ops"].append(row)
    return out


def diff_summaries(base: dict, cur: dict) -> dict:
    """Op-level regression report between two summaries (same flags).

    Windows differ in length between captures, so the comparable unit is
    per-occurrence mean time (total_ms / count) plus each op's share of
    device time; rows are ranked by estimated total impact — the per-call
    delta times the current call count (an op only present on one side
    contributes its whole total there).
    """
    out: dict = {"ops": []}
    bs, cs = base.get("steps"), cur.get("steps")
    if bs and cs:
        out["steps"] = {
            "base_p50_ms": bs["p50_ms"],
            "p50_ms": cs["p50_ms"],
            "delta_p50_ms": round(cs["p50_ms"] - bs["p50_ms"], 3),
            "base_p95_ms": bs["p95_ms"],
            "p95_ms": cs["p95_ms"],
            "delta_p95_ms": round(cs["p95_ms"] - bs["p95_ms"], 3),
        }
    base_ops = {o["op"]: o for o in base.get("top_ops", [])}
    cur_ops = {o["op"]: o for o in cur.get("top_ops", [])}
    for name in base_ops.keys() | cur_ops.keys():
        b, c = base_ops.get(name), cur_ops.get(name)

        def per_call(o):
            # self time where the summary has it (PR 36 on): a slower body
            # op is then its own finding, not the `while`'s around it too
            if not o or not o["count"]:
                return None
            return o.get("self_ms", o["total_ms"]) / o["count"]

        bpc, cpc = per_call(b), per_call(c)
        row = {
            "op": name,
            "base_ms_per_call": round(bpc, 4) if bpc is not None else None,
            "ms_per_call": round(cpc, 4) if cpc is not None else None,
            "base_pct": b["pct"] if b else None,
            "pct": c["pct"] if c else None,
            "base_count": b["count"] if b else 0,
            "count": c["count"] if c else 0,
        }
        if bpc is not None and cpc is not None:
            row["delta_ms_per_call"] = round(cpc - bpc, 4)
            impact = (cpc - bpc) * row["count"]
        elif c is not None:  # new op: its whole current total is the impact
            impact = c.get("self_ms", c["total_ms"])
        else:  # op vanished: its baseline total came off the profile
            impact = -b.get("self_ms", b["total_ms"])
        if row["base_pct"] is not None and row["pct"] is not None:
            row["delta_pp"] = round(row["pct"] - row["base_pct"], 1)
        row["impact_ms"] = round(impact, 3)
        out["ops"].append(row)
    out["ops"].sort(key=lambda r: -abs(r["impact_ms"]))
    # The same by scope, over the device planes: which mechanism grew. A
    # summary from before scopes were read (a stored baseline) has none.
    base_scopes, cur_scopes = _scope_totals(base), _scope_totals(cur)
    rows = []
    for name in base_scopes.keys() & cur_scopes.keys():
        (b_ms, b_n), (c_ms, c_n) = base_scopes[name], cur_scopes[name]
        if not b_n or not c_n:
            continue
        bpc, cpc = b_ms / b_n, c_ms / c_n
        rows.append({
            "scope": name,
            "base_ms_per_call": round(bpc, 4),
            "ms_per_call": round(cpc, 4),
            "delta_ms_per_call": round(cpc - bpc, 4),
            "count": c_n,
            "impact_ms": round((cpc - bpc) * c_n, 3),
        })
    if rows:
        out["scopes"] = sorted(rows, key=lambda r: -abs(r["impact_ms"]))
    return out


def _scope_totals(summary: dict) -> dict:
    """scope -> [self_ms, events] over the planes of a summary."""
    totals: dict = {}
    for plane in summary.get("planes", []):
        for name, row in plane.get("scopes", {}).items():
            total = totals.setdefault(name, [0.0, 0])
            total[0] += row["self_ms"]
            total[1] += row["count"]
    return totals


def _print_diff(diff: dict, baseline: str, top: int) -> None:
    print(f"regression report vs baseline {baseline}")
    if "steps" in diff:
        s = diff["steps"]
        print(
            f"steps vs baseline: p50 {s['base_p50_ms']:.3f} -> "
            f"{s['p50_ms']:.3f} ms ({s['delta_p50_ms']:+.3f}), "
            f"p95 {s['base_p95_ms']:.3f} -> {s['p95_ms']:.3f} "
            f"({s['delta_p95_ms']:+.3f})")
    print(f"\n{'op':<36} {'ms/call':>17} {'Δms/call':>9} "
          f"{'% device':>15} {'Δpp':>6} {'impact ms':>10}")

    def cell(v, fmt, width):
        return (format(v, fmt) if v is not None else "-").rjust(width)

    for row in diff["ops"][:top]:
        print(
            f"{row['op']:<36.36} "
            f"{cell(row['base_ms_per_call'], '.4f', 8)}->"
            f"{cell(row['ms_per_call'], '.4f', 0):<7} "
            f"{cell(row.get('delta_ms_per_call'), '+.4f', 9)} "
            f"{cell(row['base_pct'], '.1f', 6)}->"
            f"{cell(row['pct'], '.1f', 0):<5} "
            f"{cell(row.get('delta_pp'), '+.1f', 6)} "
            f"{row['impact_ms']:>+10.3f}")


def _print_content(planes: list[dict]) -> None:
    """The second table of a summary: each plane's bytes by what holds
    them. The events are in `lines`; the rest is metadata and stats, the
    same for a window of any length."""
    names = (*CONTENT_FIELDS.values(), "other")
    print(f"\n{'plane':<40} {'bytes':>10} " + " ".join(
        f"{name.replace('_metadata', ' meta'):>10}" for name in names)
        + f" {'meta rows':>9}")
    for p in planes:
        print(f"{p['name']:<40.40} {p['bytes']:>10} " + " ".join(
            f"{p[name + '_bytes']:>10}" for name in names)
            + f" {p['event_metadata']:>9}")
    total = sum(p["bytes"] for p in planes)
    in_lines = sum(p["lines_bytes"] for p in planes)
    if total:
        print(f"not in lines (metadata and stats): "
              f"{100.0 * (total - in_lines) / total:.1f} % of {total} bytes")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "target", nargs="?", default="",
        help="trace dir, shim manifest, or .xplane.pb")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--plane", default="", help="only planes containing this")
    ap.add_argument("--json", action="store_true")
    ap.add_argument(
        "--per-op", action="store_true",
        help="keep op instance names (fusion.116) instead of grouping by "
             "base op (fusion)")
    ap.add_argument(
        "--by-category", action="store_true",
        help="aggregate by hlo_category (XProf op-profile view: loop "
             "fusion, convolution, copy, ...) instead of op name")
    ap.add_argument(
        "--verify-schema", action="store_true",
        help="cross-check the parser's pinned xplane field numbers "
             "against the descriptor embedded in the installed wheel, "
             "then exit (0 = verified or no descriptor, 1 = mismatch)")
    ap.add_argument(
        "--diff", default="",
        help="baseline trace (dir/manifest/.xplane.pb): print an op-level "
             "regression report of TARGET vs the baseline instead of a "
             "summary — which ops got slower per call, which grew their "
             "share of device time")
    args = ap.parse_args(argv)

    if args.verify_schema:
        ok, mismatches = verify_schema_pins()
        if ok is None:
            print("no xplane descriptor found in installed wheels; "
                  "pinned schema stands unverified")
            return 0
        if ok:
            print("xplane schema pins match the wheel's descriptor")
            return 0
        for m in mismatches:
            print(f"SCHEMA MISMATCH: {m}", file=sys.stderr)
        return 1
    if not args.target:
        ap.error("target required")

    summary = summarize(
        args.target, group=not args.per_op, by_category=args.by_category)
    if args.diff:
        if args.plane:
            print("note: --plane has no effect with --diff (op tables are "
                  "already device-plane scoped)", file=sys.stderr)
        baseline = summarize(
            args.diff, group=not args.per_op, by_category=args.by_category)
        if not baseline["planes"] or not summary["planes"]:
            print("no .xplane.pb found", file=sys.stderr)
            return 1
        diff = diff_summaries(baseline, summary)
        if args.json:
            print(json.dumps(diff))
        else:
            _print_diff(diff, args.diff, args.top)
        return 0
    if args.plane:
        summary["planes"] = [
            p for p in summary["planes"] if args.plane in p["name"]
        ]
    summary["top_ops"] = summary["top_ops"][: args.top]
    if args.json:
        print(json.dumps(summary))
        return 0
    if not summary["planes"]:
        print("no .xplane.pb found", file=sys.stderr)
        return 1
    if not any(p["events"] for p in summary["planes"]):
        # A trace with planes but zero parsed events smells like schema
        # drift — check the pins against the wheel and say so.
        ok, mismatches = verify_schema_pins()
        if ok is False:
            for m in mismatches:
                print(f"warning: SCHEMA MISMATCH: {m}", file=sys.stderr)
    print(f"{'plane':<40} {'lines':>6} {'events':>8} {'span ms':>9} "
          f"{'coll %':>7}")
    for p in summary["planes"]:
        print(f"{p['name']:<40.40} {p['lines']:>6} {p['events']:>8} "
              f"{p['duration_ms']:>9.3f} {p['collective_pct']:>7.2f}")
        for kind, row in p["collectives"].items():
            print(f"    {kind:<36} {row['count']:>8} events "
                  f"{row['total_ms']:>9.3f} ms")
        for name, row in p["loops"].items():
            print(f"    {name:<36} {row['count']:>8} events "
                  f"{row['total_ms']:>9.3f} ms, holding {row['inside']}")
        for name, row in p["scopes"].items():
            print(f"    scope {name:<30} {row['count']:>8} events "
                  f"{row['self_ms']:>9.3f} ms self {row['pct']:>5.1f} %")
    _print_content(summary["planes"])
    if "steps" in summary:
        s = summary["steps"]
        print(f"\nsteps: {s['count']}  mean {s['mean_ms']:.3f} ms  "
              f"p50 {s['p50_ms']:.3f}  p95 {s['p95_ms']:.3f}  "
              f"max {s['max_ms']:.3f}")
    has_roofline = any(
        "gflops_per_s" in op or "gib_per_s" in op
        for op in summary["top_ops"])
    # `%` is the share of self time; `total ms` is inclusive
    hdr = (f"\n{'op':<40} {'total ms':>9} {'count':>7} {'self ms':>9} "
           f"{'%':>6}")
    if has_roofline:
        hdr += f" {'GFLOP/s':>9} {'GiB/s':>8} {'FLOP/B':>7}"
    print(hdr)
    for op in summary["top_ops"]:
        line = (f"{op['op']:<40.40} {op['total_ms']:>9.3f} {op['count']:>7} "
                f"{op['self_ms']:>9.3f} {op['pct']:>6.1f}")
        if has_roofline:
            line += (f" {op.get('gflops_per_s', 0):>9.1f}"
                     f" {op.get('gib_per_s', 0):>8.1f}"
                     f" {op.get('flop_per_byte', 0):>7.2f}")
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
