"""The benchmark's plain reducer from an .xplane.pb to numbers.

`jax.profiler.ProfileData`, a dictionary and a sum: nothing of
dynolog_tpu.trace is imported, because that summarizer is the product and
check C3 holds it against this one. What is read:

- per-op total device time and event count on a device plane's "XLA Ops"
  line (the synchronous ops; "Async XLA Ops" would double count), and the
  same by kind of op over several planes (check C5 holds the summary the
  export child writes against it);
- busy time as the union of those events' intervals, the span from the
  first op's start to the last op's end, and the idle share 1 - busy/span;
- the gaps between ops, longest first;
- executions of a program: events on the "XLA Modules" line whose name
  starts with the program's module name. ONE EVENT ON "XLA Modules" IS ONE
  EXECUTION: the runtime writes one module event per launch of a compiled
  program on the core;
- host spans (jax.profiler.TraceAnnotation) by name, from the host plane.

ProfileData gives `start_ns` as a float64 of nanoseconds. On this jaxlib it
counts from the session's opening, whose unix time the artifact's `Task
Environment` plane carries (`spans.trace_origin_ns` lays it on unix time);
a jaxlib that counts from the epoch holds a start only to 256 ns. Durations
are exact. Sums and counts do not depend on starts; the union and the gaps
do, to that resolution.
"""

from __future__ import annotations

import glob
import os
import warnings
from dataclasses import dataclass, field

XLA_OPS = "XLA Ops"
XLA_MODULES = "XLA Modules"


def device_plane_name(index: int) -> str:
    return f"/device:TPU:{index}"


def find_xplane(trace_dir: str) -> str | None:
    """The newest *.xplane.pb under <trace_dir>/plugins/profile/*/."""
    files = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                               "*.xplane.pb")),
        key=os.path.getmtime)
    return files[-1] if files else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def load_bytes(data: bytes):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(data)


def _events(line) -> list:
    with warnings.catch_warnings():
        # the binding's event_stats type lacks __module__; not ours to fix
        warnings.simplefilter("ignore", DeprecationWarning)
        return list(line.events)


def find_plane(profile, name: str):
    for plane in profile.planes:
        if plane.name == name:
            return plane
    return None


def find_line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def op_key(name: str) -> str:
    """'%fusion.116 = bf16[128,512]{1,0} fusion(...)' -> 'fusion.116'."""
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
    return name


def group_key(key: str) -> str:
    """'fusion.116' -> 'fusion': instances of one kind of op together."""
    base, _, suffix = key.rpartition(".")
    return base if base and suffix.isdigit() else key


@dataclass
class PlaneReduction:
    plane: str
    events: int = 0
    ops: dict = field(default_factory=dict)  # op key -> [total_ns, count]
    busy_ns: float = 0.0
    span_ns: float = 0.0
    first_ns: float = 0.0
    gaps: list = field(default_factory=list)  # (gap_ns, start_ns, end_ns)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_ns / self.span_ns)

    def groups(self) -> dict:
        """op group -> total_ns, instances of a kind summed."""
        out: dict = {}
        for key, (total, _count) in self.ops.items():
            out[group_key(key)] = out.get(group_key(key), 0.0) + total
        return out

    def top_groups(self, n: int = 10) -> list:
        ranked = sorted(self.groups().items(), key=lambda kv: -kv[1])
        return [[name, total / 1e9] for name, total in ranked[:n]]


def reduce_plane(plane, keep_gaps: int = 5) -> PlaneReduction | None:
    """None for no plane, no "XLA Ops" line on it, or no event on that."""
    line = find_line(plane, XLA_OPS) if plane is not None else None
    events = _events(line) if line is not None else []
    if not events:
        return None
    out = PlaneReduction(plane=plane.name, events=len(events))
    intervals = []
    for ev in events:
        entry = out.ops.setdefault(op_key(ev.name), [0.0, 0])
        entry[0] += ev.duration_ns
        entry[1] += 1
        intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    intervals.sort()
    out.first_ns = intervals[0][0]
    gaps = []
    cur_start, cur_end = intervals[0]
    for start, end in intervals[1:]:
        if start > cur_end:
            out.busy_ns += cur_end - cur_start
            gaps.append((start - cur_end, cur_end, start))
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    out.busy_ns += cur_end - cur_start
    out.span_ns = cur_end - out.first_ns
    out.gaps = sorted(gaps, reverse=True)[:keep_gaps]
    return out


def reduce_groups(profile, planes: list) -> dict:
    """op group -> [total_ns, count] over the "XLA Ops" lines of `planes`,
    instances of a kind together: the op table an operator ranks from.
    Where `planes` is empty (a trace without a device plane: the CPU
    rehearsal), over every line of every plane, as the summary's own
    docstring has it: "device planes when present, host planes otherwise"."""
    lines = [find_line(p, XLA_OPS) for p in planes] or [
        line for p in profile.planes for line in p.lines]
    out: dict = {}
    for line in lines:
        for ev in _events(line):
            entry = out.setdefault(group_key(op_key(ev.name)), [0.0, 0])
            entry[0] += ev.duration_ns
            entry[1] += 1
    return out


def count_executions(plane, module_prefix: str) -> int:
    """Launches of the program whose module name starts with
    `module_prefix` ("jit_step"), one per event of the "XLA Modules" line."""
    line = find_line(plane, XLA_MODULES)
    if line is None:
        return 0
    return sum(1 for ev in _events(line) if ev.name.startswith(module_prefix))


def host_spans(profile, prefix: str) -> list:
    """(name, start_ns, end_ns) of host events whose name starts with
    `prefix`, over every line of every /host: plane."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in _events(line):
                if ev.name.startswith(prefix):
                    out.append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def label_gaps(gaps, spans, prefix: str) -> list:
    """[[label, seconds], ...]: each device gap named after the host span
    that covers its middle ("other" when none does)."""
    out = []
    for gap_ns, start, end in gaps:
        mid = (start + end) / 2.0
        label = "other"
        for name, s0, s1 in spans:
            if s0 <= mid <= s1:
                label = name[len(prefix):]
                break
        out.append([label, gap_ns / 1e9])
    return out
