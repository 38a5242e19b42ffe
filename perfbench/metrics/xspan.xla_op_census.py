"""Distinct op names on "XLA Ops" over the device planes of the capture the
breakdown reads, by the benchmark's reducer: what the drain's metadata, the
summary's walk and the Chrome trace scale with (`collect_ms`,
`derived_ms_p50`); 2414 at the dense four chips (PERF.md section 4)."""

import device_ops

NAME = "xspan.xla_op_census"
UNIT = "ops"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    ops = device_ops.device_ops(run)
    return float(len(ops)) if ops else None
