"""Median length of the daemon's `ipc.config_handoff` spans that began inside
the window: from the shim's request in the IPC thread's hand to the config
sent back, the part of `pickup_ms` that is the daemon's IPC thread."""

import selftrace

NAME = "ipc_handoff_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "IPC hand-off"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return selftrace.window_median_ms(run, selftrace.HANDOFF)
