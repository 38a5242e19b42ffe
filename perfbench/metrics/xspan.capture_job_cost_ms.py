"""Median of the manifests' `job_cost_ms.total`: over the job's own step marks
that overlap a capture, what each took beyond the median step."""

import spans

NAME = "xspan.capture_job_cost_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "shim capture"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return spans.median_of(
        run, lambda c: c["manifest"]["job_cost_ms"]["total"])
