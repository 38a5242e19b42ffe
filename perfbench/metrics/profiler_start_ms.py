"""Median of the manifests' `profiler_start_ms`: opening the profiler session."""

import stats

NAME = "profiler_start_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    values = [c["manifest"]["timing"]["profiler_start_ms"] for c in run["captures"]
              if c["ok"] and "profiler_start_ms" in c["manifest"]["timing"]]
    return stats.median(values) if values else None
