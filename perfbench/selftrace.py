"""What the readers of the daemon's own journal share: the reply of `dyno
selftrace`, as the operator gets it, laid on the window and on the job's
longest passes.

The harness asks once, in traced runs, after the window and after every
check (`Run.read_journals`): `run["selftrace"]` holds the spans (`name`,
`ts` and `dur` in unix microseconds, `pid`, `tid`, `args`) and the two
counters that ride in the same reply, `ipc_wakeups` and `tpu_rows`. The
daemon's journal is a ring of `ring_capacity` spans that keeps the newest,
and a span is journaled when it ends. The readers need the spans of the
window alone, so a ring that wrapped harms nothing unless the oldest span
it still holds began after the window opened: `run["selftrace_oldest_ms"]`
against `run["window_start"]` is the one condition under which every
reader here returns None. A daemon that did not answer leaves
`run["selftrace"]["error"]` and no spans: None as well.
"""

from __future__ import annotations

import json

import stats

TPU_TICK = "collector.tpu_monitor.tick"
KERNEL_TICK = "collector.kernel_monitor.tick"
HANDOFF = "ipc.config_handoff"
CAPTURE_VERB = "rpc.setKinetOnDemandRequest"  # what `dyno gputrace` sends
CONVERT = "trace.convert"  # the export child's, handed over as it exits
DAEMON_PREFIXES = ("collector.", "rpc.", "ipc.")
# The IPC thread's "tick" is a one-second slice of blocking in poll(2), back
# to back: it lies over every pass and says nothing about any.
IPC_SLICE = "collector.ipc_monitor.tick"


def parse(stdout: str) -> dict:
    """`dyno selftrace`'s document (Chrome trace: `traceEvents`, and the
    counters under `otherData`) as the record keeps it. Raises ValueError
    or KeyError on anything else."""
    doc = json.loads(stdout)
    other = doc["otherData"]
    spans = [{"name": e["name"], "ts": e["ts"], "dur": e["dur"],
              "pid": e.get("pid"), "tid": e.get("tid"),
              "args": e.get("args", {})} for e in doc["traceEvents"]]
    return {"spans": spans,
            "spans_recorded": other.get("spans_recorded"),
            "ring_capacity": other.get("ring_capacity"),
            "ipc_wakeups": other.get("ipc_wakeups"),
            "tpu_rows": other.get("tpu_rows")}


def oldest_ms(journal: dict) -> float | None:
    """Where the oldest span the ring still holds began, unix ms."""
    starts = [s["ts"] for s in journal.get("spans", ())]
    return min(starts) / 1e3 if starts else None


def journal(run: dict) -> dict | None:
    """The run's selftrace where it reaches back to the window's opening."""
    found = run.get("selftrace")
    oldest = run.get("selftrace_oldest_ms")
    if not found or "spans" not in found or oldest is None:
        return None
    if oldest > run["window_start"] * 1e3:
        return None
    return found


def window_ms(run: dict, name: str) -> list | None:
    """Durations (ms) of the spans `name` that began inside the window;
    None where the journal does not reach back to its opening."""
    found = journal(run)
    if found is None:
        return None
    lo, hi = run["window_start"] * 1e6, run["window_end"] * 1e6
    return [s["dur"] / 1e3 for s in found["spans"]
            if s["name"] == name and lo <= s["ts"] < hi]


def window_median_ms(run: dict, name: str) -> float | None:
    durations = window_ms(run, name)
    return stats.median(durations) if durations else None


def convert_starts_us(run: dict) -> dict | None:
    """The request's trace id -> where its export child's `trace.convert`
    span began, unix microseconds; None where the journal does not reach
    back to the window's opening."""
    found = journal(run)
    if found is None:
        return None
    return {s["args"].get("trace_id"): s["ts"] for s in found["spans"]
            if s["name"] == CONVERT}


def pass_bounds_us(run: dict, longest: list) -> tuple:
    """(start, end) on the wall clock of one `longest_passes` entry
    `[ms, ended s into the window, parts]`."""
    ms, ended_s = longest[0], longest[1]
    end = (run["window_start"] + ended_s) * 1e6
    return end - ms * 1e3, end


def over_pass(run: dict, longest: list) -> list | None:
    """The journal's spans that overlap the pass on the wall clock, each
    with where it began (ms after the pass did), in order of start; None
    where the journal does not reach back to the window's opening."""
    found = journal(run)
    if found is None:
        return None
    lo, hi = pass_bounds_us(run, longest)
    return [(s, (s["ts"] - lo) / 1e3)
            for s in sorted(found["spans"], key=lambda s: s["ts"])
            if s["ts"] < hi and s["ts"] + s["dur"] > lo]


def spans_over(run: dict, longest: list) -> list | None:
    """Every daemon span (tick, verb, hand-off) that overlaps the pass, as
    `[name, began ms after the pass did, length ms]`."""
    found = over_pass(run, longest)
    if found is None:
        return None
    return [[s["name"], round(began, 2), round(s["dur"] / 1e3, 2)]
            for s, began in found
            if s["name"].startswith(DAEMON_PREFIXES) and s["name"] != IPC_SLICE]


def longest_tick_over(run: dict, longest: list, name: str = TPU_TICK) -> float | None:
    """The length (ms) of the longest span `name` that overlaps the pass;
    0.0 where none does."""
    found = over_pass(run, longest)
    if found is None:
        return None
    return max((s["dur"] / 1e3 for s, _ in found if s["name"] == name),
               default=0.0)
