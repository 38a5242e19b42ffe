"""dynologd's resident set at the window's end: `VmRSS` of
/proc/<pid>/status, read where the daemon's CPU seconds are."""

NAME = "daemon_rss_mb"
UNIT = "MB"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "collectors and TPU backend"
MOVES = "step_ms_p50"
CELLS = ('steady', 'capture')


def read(run: dict):
    kb = run.get("daemon_rss_kb")
    return kb / 1024.0 if kb else None
