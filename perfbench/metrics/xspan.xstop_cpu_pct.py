"""Median over a run's captures of the manifest's `collect_cpu_us` over the
span `shim.collect`: how much of the drain (`ProfilerSession.stop()`) the
calling thread spent on a CPU. Near 100, the drain decodes and serialises
there and is priced by what the artifact holds; far below, it waits or the
work is on other threads (`xspan.xstop_others_cpu_ms` says which)."""

import spans
import stats

NAME = "xspan.xstop_cpu_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    shares = [
        c["manifest"]["timing"]["collect_cpu_us"] / 10.0 / wall_ms
        for c in spans.spanned(run)
        if "collect_cpu_us" in c["manifest"]["timing"]
        and (wall_ms := spans.span_ms(c["manifest"], "shim.collect"))]
    return stats.median(shares) if shares else None
