"""Median, over the window's captures, of the shim's `received_ms` mark minus
the operator's spawn of `dyno gputrace` (same host clock): CLI + RPC + the
daemon's IPC hand-off + the shim's kick or poll."""

import stats

NAME = "pickup_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "IPC hand-off"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    values = [c["manifest"]["timing"]["received_ms"] - c["spawn_t"] * 1e3
              for c in run["captures"] if c["ok"]]
    return stats.median(values) if values else None
