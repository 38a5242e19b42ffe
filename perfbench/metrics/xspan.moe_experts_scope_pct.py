"""Of the device planes of the capture the breakdown reads: the self time of
the ops under the job's `moe.experts` scope (what lies between the grouped
products of `dynolog_tpu/models/moe.py`: the masks and the SiLU or ReLU^2
over the expert buffer, forward, rematerialised and backward, and the trips
of the loops that walk it where the job has them; the products themselves
are XLA's `ragged-dot` kernels, outside every scope, and
`xspan.moe_expert_op_pct` reads those; read from each op's `tf_op` through
the wheel's protobuf binding, `scope_ops.py`) over all op time. 0.0 for a
job without an expert layer, because its planes were summed, not by
default."""

import scope_ops

NAME = "xspan.moe_experts_scope_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return scope_ops.scope_share_pct(run, "moe.experts")
