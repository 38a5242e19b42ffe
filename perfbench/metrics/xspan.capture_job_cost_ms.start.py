"""Median of the manifests' `job_cost_ms.start`: the excess of the job's steps
that overlap `shim.profiler_start`."""

import spans

NAME = "xspan.capture_job_cost_ms.start"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "shim capture"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return spans.median_of(
        run, lambda c: c["manifest"]["job_cost_ms"]["start"])
