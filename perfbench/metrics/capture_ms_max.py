"""The slowest capture of the window, from the operator's samples."""

NAME = "capture_ms_max"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return max(run["capture_ms"]) if run["capture_ms"] else None
