"""Of the device planes of the capture the breakdown reads: the self time of
the ops under the three windowed flash kernels' scopes (a kernel's own name
is its scope: `flash_attention_window_fwd`, `flash_attention_window_bwd_dq`,
`flash_attention_window_bwd_dkv`, `dynolog_tpu/ops/flash_attention.py` under
a `window`; read from each op's `tf_op` through the wheel's protobuf binding,
`scope_ops.py`) over all op time: how much of a step is the windowed layers'
attention. 0.0 for a job without a windowed layer, because its planes were
summed, not by default."""

import scope_ops

NAME = "xspan.attn_window_scope_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "observed job"
MOVES = "step_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return scope_ops.scope_share_pct(run, "flash_attention_window_")
