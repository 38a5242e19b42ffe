"""Median over a run's captures of the manifest's `profiler_start_cpu_us`
over the span `shim.profiler_start`: how much of the profiler session's
opening the calling thread spent on a CPU. Near 100, the call computes
there; far below, it waits or the work is on other threads (the shim's
account of a library call it cannot see into, docs/OBSERVABILITY.md)."""

import spans
import stats

NAME = "xspan.xstart_cpu_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    shares = [
        c["manifest"]["timing"]["profiler_start_cpu_us"] / 10.0 / wall_ms
        for c in spans.spanned(run)
        if "profiler_start_cpu_us" in c["manifest"]["timing"]
        and (wall_ms := spans.span_ms(c["manifest"], "shim.profiler_start"))]
    return stats.median(shares) if shares else None
