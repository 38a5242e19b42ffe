"""Median of the manifests' `write_ms`: streaming the xspace to disk."""

import stats

NAME = "write_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    values = [c["manifest"]["timing"]["write_ms"] for c in run["captures"]
              if c["ok"] and "write_ms" in c["manifest"]["timing"]]
    return stats.median(values) if values else None
