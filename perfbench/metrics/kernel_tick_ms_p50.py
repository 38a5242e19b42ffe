"""Median length of the daemon's `collector.kernel_monitor.tick` spans that
began inside the window: /proc read, the daemon's own footprint, the row
logged and flushed."""

import selftrace

NAME = "kernel_tick_ms_p50"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "collectors and TPU backend"
MOVES = "step_ms_p50"
CELLS = ('steady', 'capture')


def read(run: dict):
    return selftrace.window_median_ms(run, selftrace.KERNEL_TICK)
