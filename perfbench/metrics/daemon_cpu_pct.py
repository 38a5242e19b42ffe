"""dynologd's CPU over the window, percent of one core: utime + stime of
/proc/<pid>/stat at the window's two ends over its length."""

NAME = "daemon_cpu_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "collectors and TPU backend"
MOVES = "step_ms_p50"
CELLS = ('steady',)


def read(run: dict):
    if "daemon_cpu_s" not in run:
        return None
    return 100.0 * run["daemon_cpu_s"] / run["window_s"]
