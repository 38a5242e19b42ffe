"""Median over a run's captures of the manifest's `collect_proc_cpu_us` less
`collect_cpu_us`: CPU time that threads other than the one inside
`ProfilerSession.stop()` spent while it ran. It holds the job's own threads
(its dispatch goes on under the drain); beyond those it is the drain's work
done elsewhere, as per-device drains side by side would be."""

import stats

NAME = "xspan.xstop_others_cpu_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    others = [(t["collect_proc_cpu_us"] - t["collect_cpu_us"]) / 1e3
              for c in run["captures"] if c["ok"]
              for t in [c["manifest"]["timing"]]
              if "collect_proc_cpu_us" in t and "collect_cpu_us" in t]
    return stats.median(others) if others else None
