"""Of the longest "XLA Ops" gap in the breakdown's capture, laid on unix time:
the excess over the median step of the job's own steps that overlap it. A gap
longer than that excess is a hole in the trace, not idleness of the device."""

import spans

NAME = "xspan.idle_gap_job_excess_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    over = spans.overlay(run)
    return over["job_excess_ms"] if over else None
