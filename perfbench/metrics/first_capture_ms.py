"""The process's first capture as the operator saw it: `capture_ms` of the
warm capture the harness makes before the window. The profiler's first
session in a process pays seconds of one-time work inside `stop()`; it is
part of `setup_s` and nowhere else on the ledger."""

NAME = "first_capture_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "shim capture"
MOVES = "setup_s"
CELLS = ('capture',)


def read(run: dict):
    warm = run.get("warm_capture") or [{}]
    return warm[0].get("capture_ms") if warm[0].get("ok") else None
