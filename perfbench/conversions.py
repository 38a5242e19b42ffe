"""What the readers of the export child's spans share: one conversion laid
out as the program records it (docs/OBSERVABILITY.md, "One capture, one
timeline").

On the child's side, in the daemon's journal (`run["selftrace"]`, handed
over as the child exits): `trace.convert`, under it one `convert.plane` a
plane of the artifact with that plane's `convert.decode` inside; a span's
`pid` is the process that ran it, a pool worker's where there was one. They
are matched to a capture by `args.trace_id` against the manifest's
`trace_ctx`, as `convert_lag_ms` does. On the shim's side, in the manifest's
`spans`: `export.boot` (before the child's `Popen` to its `ready`) and
`export.idle` (its `ready` to the hand-over), where the hand-over was warm.

A conversion counts where the journal holds it whole: its `trace.convert`
and as many `convert.plane` and `convert.decode` as the manifest's `planes`
has rows. One whose spans were dropped on the wire is left out, not read as
0; a program that records none of this (the parent of the PR that added it)
leaves every reader here None.
"""

from __future__ import annotations

import selftrace
import spans
import stats

PLANE = "convert.plane"
DECODE = "convert.decode"
BOOT = "export.boot"
IDLE = "export.idle"


def conversions(run: dict) -> list | None:
    """`{"convert": span, "planes": [span], "decodes": [span]}` for each of
    the window's ok captures whose conversion the journal holds whole; None
    where the journal does not reach back to the window's opening."""
    found = selftrace.journal(run)
    if found is None:
        return None
    by_trace: dict = {}
    for s in found["spans"]:
        if s["name"] in (selftrace.CONVERT, PLANE, DECODE):
            by_trace.setdefault(s["args"].get("trace_id"), {}).setdefault(
                s["name"], []).append(s)
    whole = []
    for c in run["captures"]:
        if not c["ok"]:
            continue
        manifest = c["manifest"]
        held = by_trace.get(manifest.get("trace_ctx", "").split("/")[0], {})
        rows = len(manifest.get("planes", ()))
        if rows and len(held.get(selftrace.CONVERT, ())) == 1 and (
                len(held.get(PLANE, ())) == len(held.get(DECODE, ())) == rows):
            whole.append({"convert": held[selftrace.CONVERT][0],
                          "planes": held[PLANE], "decodes": held[DECODE]})
    return whole


def median_of(run: dict, value) -> float | None:
    """Median over the whole conversions of value(conversion); None where
    the journal holds none."""
    found = conversions(run)
    return stats.median([value(c) for c in found]) if found else None


def union_us(found: list) -> int:
    """Microseconds that at least one of the spans covers."""
    covered, reached = 0, 0
    for start, end in sorted((s["ts"], s["ts"] + s["dur"]) for s in found):
        if end > reached:
            covered += end - max(start, reached)
            reached = end
    return covered


def life_median_ms(run: dict, name: str) -> float | None:
    """Median length of the manifests' span `name` (`export.boot`,
    `export.idle`) over the window's ok captures that list it."""
    found = [spans.span_of(c["manifest"], name) for c in spans.spanned(run)]
    lengths = [(end - start) / 1e3 for start, end in filter(None, found)]
    return stats.median(lengths) if lengths else None
