"""Of the device planes of the capture the breakdown reads: the self time of
the ops under the job's `moe.shared` scope (the shared experts' SwiGLU in
`dynolog_tpu/models/moe.py`, which every token visits beside the experts it
is routed to; read from each op's `tf_op` through the wheel's protobuf
binding, `scope_ops.py`) over all op time. 0.0 for a job without shared
experts, because its planes were summed, not by default."""

import scope_ops

NAME = "xspan.moe_shared_scope_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return scope_ops.scope_share_pct(run, "moe.shared")
