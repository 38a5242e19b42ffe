"""Under the window's longest pass of the job (`longest_passes[0]`), the
length of the longest `collector.tpu_monitor.tick` span that overlaps it on
the wall clock; 0 where none does. Most runs hold no stall and read 0 or one
ordinary tick. The reading that matters comes from a run whose longest pass
exceeds a second: a tick of about the same length lies under it, or no
daemon span does (the harness prints every daemon span over the three
longest passes beside it)."""

import selftrace

NAME = "longest_pass_tick_overlap_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "collectors and TPU backend"
MOVES = "step_ms_p50"
CELLS = ('steady', 'capture')


def read(run: dict):
    passes = run.get("longest_passes")
    if not passes:
        return None
    return selftrace.longest_tick_over(run, passes[0])
