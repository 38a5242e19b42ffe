"""Median of the manifests' `xspace_bytes`: the size of one window's trace."""

import stats

NAME = "xspace_bytes"
UNIT = "bytes"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    values = [c["manifest"]["timing"]["xspace_bytes"] for c in run["captures"]
              if c["ok"] and "xspace_bytes" in c["manifest"]["timing"]]
    return stats.median(values) if values else None
