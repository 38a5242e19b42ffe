"""The longest `collector.tpu_monitor.tick` span that began inside the window:
a read of the runtime's metric service that hung shows here and not in the
median."""

import selftrace

NAME = "tpu_tick_ms_max"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "collectors and TPU backend"
MOVES = "step_ms_p50"
CELLS = ('steady', 'capture')


def read(run: dict):
    durations = selftrace.window_ms(run, selftrace.TPU_TICK)
    return max(durations) if durations else None
