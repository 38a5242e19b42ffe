"""Of the device planes of the capture the breakdown reads: the time of the
`while` events on "XLA Ops" (an XLA loop is one event that spans its trips,
with the body's ops inside it on the same line) over all op time, by the
benchmark's reducer: how much of the job's step is loops on the device (the
chunked recurrence of `dynolog_tpu/models/linear_attention.py`, forward and
backward). The reducer adds durations, so the body's ops are in the
denominator beside the `while` that holds them: the share is of inclusive
time, as `top_op_share` is. 0.0 for a job whose step is a straight line of
ops, because its planes were summed, not by default."""

import device_ops

NAME = "xspan.xla_while_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return device_ops.share_pct(run, "while")
