"""Median length of the daemon's `collector.tpu_monitor.tick` spans that began
inside the window: one read of the TPU backend (the gRPC metric service of the
job's own runtime), the rows logged and flushed. The supervisor lays the span
round the tick alone; the sleep to the next one is outside it."""

import selftrace

NAME = "tpu_tick_ms_p50"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "collectors and TPU backend"
MOVES = "step_ms_p50"
CELLS = ('steady', 'capture')


def read(run: dict):
    return selftrace.window_median_ms(run, selftrace.TPU_TICK)
