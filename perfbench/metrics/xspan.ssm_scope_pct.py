"""Of the device planes of the capture the breakdown reads: the self time of
the ops under the job's `ssm.` scopes (`jax.named_scope` in
`dynolog_tpu/models/mamba2.py`: `ssm.project`, `ssm.conv`, `ssm.chunk`, the
products inside a chunk, `ssm.state`, the product that hands the state from
chunk to chunk, and `ssm.out`; read from each op's `tf_op` through the
wheel's protobuf binding, `scope_ops.py`) over all op time: how much of a
step is the state-space blocks. 0.0 for a job without them, because its
planes were summed, not by default."""

import scope_ops

NAME = "xspan.ssm_scope_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return scope_ops.scope_share_pct(run, "ssm.")
