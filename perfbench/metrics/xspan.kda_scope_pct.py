"""Of the device planes of the capture the breakdown reads: the self time of
the ops under the job's `kda.` scopes (`jax.named_scope` in
`dynolog_tpu/models/linear_attention.py`: `kda.project`, `kda.conv`,
`kda.chunk_prepare`, a chunk's two products under the decay and the solve,
`kda.scan`, the loop over the chunks, and `kda.out`; forward, computed again
and backward; read from each op's `tf_op` through the wheel's protobuf
binding, `scope_ops.py`) over all op time: how much of a step is the Kimi
Delta Attention layers. None where the planes hold no op or no wheel reads
them."""

import scope_ops

NAME = "xspan.kda_scope_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return scope_ops.scope_share_pct(run, "kda.")
