"""Of the device planes of the capture the breakdown reads: the time of the
grouped expert products (`jax.lax.ragged_dot` in `dynolog_tpu/models/moe.py`;
XLA names the TPU's kernels `ragged-dot-none.<n>` and their tile metadata
`ragged-dot-metadata.<n>`) over all op time, by the benchmark's reducer: how
much of a sparse job's step is the experts' own arithmetic. 0.0 for a job with
no expert layer, because its planes were summed, not by default."""

import device_ops

NAME = "xspan.moe_expert_op_pct"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return device_ops.share_pct(run, "ragged-dot")
