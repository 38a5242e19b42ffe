"""The job's 95th-percentile step under capture traffic (nearest rank): a few
stalled steps a capture make it too wide to decide a PR, so it is a
per-layer metric here and end to end only in steady cells."""

import stats

NAME = "step_ms_p95.capture"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "shim capture"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    try:
        return stats.tail(run["step_ms"], 0.95, run["window_s"] * 1e3)
    except stats.TooFewSamples:
        return None
