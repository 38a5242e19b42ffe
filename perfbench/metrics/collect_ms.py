"""Median of the manifests' `collect_ms`: the runtime's drain of the trace at
profiler stop, the part of a capture that grows with the events in it."""

import stats

NAME = "collect_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    values = [c["manifest"]["timing"]["collect_ms"] for c in run["captures"]
              if c["ok"] and "collect_ms" in c["manifest"]["timing"]]
    return stats.median(values) if values else None
