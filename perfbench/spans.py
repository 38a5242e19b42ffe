"""What the span and step-mark readers share: the capture manifests' `spans`,
`steps` and `job_cost_ms`, and the overlay of a capture's device trace on
unix time.

The shim records every phase of a capture as an `obs` span (unix
microseconds) and lists them in the manifest, with the job's own step marks
and what the capture cost them. A program that lacks them (the parent of
the PR that added them) writes manifests without `spans`: every reader here
then returns None.

Their reader files are named `xspan.<metric>` because `gen_benchmark.py`
emitted the `per_layer` table in file order until PR 31 and an accepted
entry keeps its place; accepted names stay, a new reader is named for what
it reads.

The device trace's clock is not assumed. `ProfileData` hands event starts
either as nanoseconds since the epoch or as nanoseconds since the session
opened, whose unix time the artifact's `Task Environment` plane carries as
the stat `profile_start_time`; `trace_origin_ns` tells the two apart by
magnitude. The mapping is then held against the two `dynolog.clock_sync`
marks the shim's profiler backend puts into every capture, each carrying
`time.time_ns()` as the stat `unix_ns`: `xspan.trace_clock_skew_us`.
"""

from __future__ import annotations

import stats
import xplane

CLOCK_MARK = "dynolog.clock_sync"
ENVIRONMENT_PLANE = "Task Environment"
NO_MARK_US = 1e6  # a second: unusable by the metric's own criterion
PHASES = ("shim.profiler_start", "shim.window", "shim.collect", "shim.feed")


def spanned(run: dict) -> list:
    """The window's ok captures whose manifest lists its spans."""
    return [c for c in run["captures"]
            if c["ok"] and "spans" in c["manifest"]]


def span_of(manifest: dict, name: str) -> tuple | None:
    """(start_us, end_us) of the manifest's span `name`, or None."""
    for row in manifest["spans"]:
        if row["name"] == name:
            return row["start_us"], row["start_us"] + row["dur_us"]
    return None


def span_ms(manifest: dict, name: str) -> float:
    """The span's length in milliseconds; 0 where the manifest has none."""
    found = span_of(manifest, name)
    return (found[1] - found[0]) / 1e3 if found else 0.0


def median_of(run: dict, value) -> float | None:
    """Median over the spanned captures of value(capture); None where the
    program wrote no spans."""
    captures = spanned(run)
    return stats.median([value(c) for c in captures]) if captures else None


def feed_end_us(manifest: dict) -> int:
    """Where the poll thread's part of a capture ends: the end of
    shim.feed, or of shim.capture where the backend recorded no feed."""
    return (span_of(manifest, "shim.feed")
            or span_of(manifest, "shim.capture"))[1]


def finish_ms(capture: dict) -> float:
    """From the end of the feed to the manifest's rename (its mtime)."""
    return capture["done_t"] * 1e3 - feed_end_us(capture["manifest"]) / 1e3


def unaccounted_ms(capture: dict) -> float:
    """capture_ms less everything a span or a mark covers."""
    manifest = capture["manifest"]
    fetch_end = span_of(manifest, "shim.config_fetch")[1]
    return (capture["capture_ms"]
            - (fetch_end / 1e3 - capture["spawn_t"] * 1e3)
            - sum(span_ms(manifest, name) for name in PHASES)
            - finish_ms(capture))


def excess_ms(steps: list, baseline_us: float, lo_us: float,
              hi_us: float) -> float:
    """Sum over the (end_us, dur_us) steps that overlap [lo, hi] of what
    each took beyond the baseline, never negative a step."""
    return sum(max(dur - baseline_us, 0.0) for end, dur in steps
               if end > lo_us and end - dur < hi_us) / 1e3


def outside_cost_ms(run: dict, capture: dict) -> float:
    """`job_cost_ms.total` recomputed from the benchmark's own passes: the
    record's `step_ms` add up to the window, so pass i ended at
    window_start + the sum of the first i; baseline their median."""
    manifest = capture["manifest"]
    end_us, steps = run["window_start"] * 1e6, []
    for ms in run["step_ms"]:
        end_us += ms * 1e3
        steps.append((end_us, ms * 1e3))
    return excess_ms(
        steps, stats.median(run["step_ms"]) * 1e3,
        span_of(manifest, "shim.config_fetch")[0], feed_end_us(manifest))


# ------------------------------------------------ the device trace on unix time

def trace_origin_ns(profile, sample_start_ns: float) -> float | None:
    """What to add to an event's start_ns to get unix nanoseconds: 0 where
    the starts already are (past 1e17, the year 1973), else the session's
    opening from the Task Environment plane; None where that is absent."""
    if sample_start_ns > 1e17:
        return 0.0
    plane = xplane.find_plane(profile, ENVIRONMENT_PLANE)
    stats_ = dict(plane.stats) if plane is not None else {}
    return stats_.get("profile_start_time")


def clock_marks(profile) -> list:
    """(start_ns, carried unix_ns) of every dynolog.clock_sync host event."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in xplane._events(line):
                if ev.name == CLOCK_MARK:
                    carried = dict(ev.stats).get("unix_ns")
                    if carried is not None:
                        out.append((ev.start_ns, carried))
    return out


_OVERLAYS: dict = {}  # trace path -> overlay: two readers, one parse


def overlay(run: dict) -> dict | None:
    """The capture that `run["trace"]["path"]` belongs to, laid on unix
    time: the skew of the mapping at its clock marks, its longest "XLA Ops"
    gap, the shim spans and the job's steps over that gap. None where
    there is no trace, the program wrote no spans, or the artifact holds
    no device events. The record is left as the harness made it."""
    trace = run.get("trace")
    capture = trace and next(
        (c for c in spanned(run)
         if trace["path"].startswith(c["manifest"]["trace_dir"] + "/")), None)
    if not capture:
        return None
    if trace["path"] not in _OVERLAYS:
        _OVERLAYS[trace["path"]] = _overlay(run, trace, capture)
    return _OVERLAYS[trace["path"]]


def _overlay(run: dict, trace: dict, capture: dict) -> dict | None:
    manifest = capture["manifest"]
    profile = xplane.load(trace["path"])
    planes = (xplane.reduce_plane(xplane.find_plane(
        profile, xplane.device_plane_name(i)))
        for i in range(run["device"]["count"]))
    device = next((p for p in planes if p is not None), None)
    if device is None:
        return None
    out = {"capture": capture["k"], "skew_us": NO_MARK_US, "marks": 0,
           "gap_ms": None, "job_excess_ms": 0.0}
    origin = trace_origin_ns(profile, device.first_ns)
    if origin is None:
        return out
    out["origin_ns"] = origin
    marks = clock_marks(profile)
    out["marks"] = len(marks)
    if marks:
        out["skew_us"] = max(
            abs(int(origin) + round(start) - carried)
            for start, carried in marks) / 1e3
    if not device.gaps:
        return out
    gap_ns, start_ns, end_ns = device.gaps[0]
    lo_us, hi_us = (origin + start_ns) / 1e3, (origin + end_ns) / 1e3
    baseline_us = manifest["job_cost_ms"]["baseline_ms"] * 1e3
    out.update(
        gap_ms=gap_ns / 1e6, gap_unix_us=[lo_us, hi_us],
        # each shim span's milliseconds under the gap
        spans_over={row["name"]: round(
            (min(hi_us, row["start_us"] + row["dur_us"])
             - max(lo_us, row["start_us"])) / 1e3, 3)
            for row in manifest["spans"]
            if row["start_us"] < hi_us
            and row["start_us"] + row["dur_us"] > lo_us},
        steps_over=[[end, dur] for end, dur in manifest["steps"]
                    if end > lo_us and end - dur < hi_us],
        job_excess_ms=excess_ms(manifest["steps"], baseline_us, lo_us, hi_us))
    return out
